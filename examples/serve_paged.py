"""Serve a small model with batched requests through the paged-KV engine
(continuous batching, Tiara paged-attention decode path).

    PYTHONPATH=src python examples/serve_paged.py --requests 8
    PYTHONPATH=src python examples/serve_paged.py --resolver tiara --homes 4

With ``--resolver tiara`` every decode step resolves its block tables by
posting PagedKVFetch operators from per-sequence sessions through the
ServingLoop (the disaggregated path); with ``--homes > 1`` the regions
shard over a device mesh and the INDIGO-style re-homing sweep migrates
hot regions toward their accessors (see the audit printed at the end).
"""

import argparse
import time

import numpy as np
import jax

from repro.configs import get_config, reduce_config
from repro.models import transformer as tf
from repro.serving import ServingEngine
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--resolver", choices=("host", "tiara"),
                    default="host",
                    help="block-table resolution: local (host) or "
                         "posted through the endpoint (tiara)")
    ap.add_argument("--homes", type=int, default=1,
                    help="device-mesh rows homing the tiara regions")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full 110M tiny-lm (slower on CPU)")
    args = ap.parse_args()

    cfg = get_config("tiny-lm")
    if not args.full_size:
        cfg = reduce_config(cfg)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params, max_slots=args.slots, max_seq=128,
        temperature=args.temperature, eos_id=-1,
        resolver=args.resolver, n_homes=args.homes,
        placement="auto" if args.homes > 1 else "single",
        rehome_every=2)

    rng = np.random.default_rng(0)
    handles = []
    for i in range(args.requests):
        prompt = list(rng.integers(1, cfg.vocab, 4 + i % 9))
        handles.append(engine.submit(prompt, max_new=args.max_new))
    print(f"submitted {len(handles)} requests into {args.slots} slots "
          f"({engine.allocator.n_pages} KV pages of {cfg.page_size} tokens, "
          f"resolver={args.resolver})")

    t0 = time.time()
    steps = 0
    while not engine.finished():
        engine.step()
        steps += 1
        if steps % 8 == 0:
            act = sum(1 for s in engine.active if s)
            print(f"  step {steps}: active={act} waiting="
                  f"{len(engine.waiting)} page-util="
                  f"{engine.allocator.utilization():.0%}")
    dt = time.time() - t0
    out = engine.results()
    n_tok = sum(len(v) for v in out.values())
    print(f"\ngenerated {n_tok} tokens in {steps} engine steps "
          f"({dt:.1f}s, {n_tok / dt:.1f} tok/s on CPU)")
    for h in handles[:4]:
        print(f"  seq {h.sid} [{'ok' if h.ok else h.status}]: "
              f"{out[h.sid]}")
    aud = engine.resolver_audit()
    if aud:
        print(f"resolver audit: {aud['waves']:.0f} waves, "
              f"{aud['rehomes']:.0f} rehomes "
              f"({aud['rehomed_words']:.0f} words moved), "
              f"cross-device reply words {aud['cross_device_words']:.0f}, "
              f"home skew {aud['home_skew']:.2f}")
    assert engine.allocator.free_pages == engine.allocator.n_pages, \
        "page leak!"


if __name__ == "__main__":
    main()
