"""End-to-end training driver: ~110M-parameter tiny-lm for a few hundred
steps on the synthetic (learnable) stream, with checkpoint/restart.

    PYTHONPATH=src python examples/train_tiny_lm.py --steps 300
    PYTHONPATH=src python examples/train_tiny_lm.py --steps 400   # resumes
"""

import argparse

from repro.configs import get_config
from repro.data import DataConfig
from repro.training import Trainer, TrainerConfig
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/tiny_lm_ckpt")
    ap.add_argument("--state-bits", type=int, default=32, choices=[8, 32])
    args = ap.parse_args()

    cfg = get_config("tiny-lm")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    tcfg = TrainerConfig(total_steps=args.steps, log_every=10,
                         ckpt_every=50, ckpt_dir=args.ckpt,
                         peak_lr=args.lr, warmup=30,
                         state_bits=args.state_bits)
    trainer = Trainer(cfg, tcfg, dcfg)
    state = trainer.run()
    first = trainer.metrics_log[0]["loss"] if trainer.metrics_log else None
    last = trainer.metrics_log[-1]["loss"] if trainer.metrics_log else None
    print(f"\ndone at step {int(state.step)}: loss {first:.3f} -> {last:.3f}"
          f"  (stragglers flagged: {len(trainer.straggler_steps)})")


if __name__ == "__main__":
    main()
