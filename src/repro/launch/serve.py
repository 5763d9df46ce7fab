"""Serving launcher: --arch <id> through the paged-KV engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny-lm --reduced
"""

import argparse

import numpy as np
import jax

from repro.configs import get_config, reduce_config
from repro.models import transformer as tf
from repro.serving import ServingEngine
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=args.slots, max_seq=128,
                        temperature=args.temperature, eos_id=-1)
    rng = np.random.default_rng(0)
    handles = [eng.submit(list(rng.integers(1, cfg.vocab, 5)),
                          max_new=args.max_new)
               for _ in range(args.requests)]
    out = eng.run_to_completion()
    for h in handles:
        print(f"seq {h.sid}: {out[h.sid]}")


if __name__ == "__main__":
    main()
