"""Benchmark harness — one module per paper table/figure.

Prints the required ``name,us_per_call,derived`` CSV followed by
human-readable comparison tables (derived vs. the paper's claimed value
with the ratio).  The Tiara side is the cycle-level MP simulator replaying
verified-operator traces; baselines are the paper's analytical models.

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig10] [--json out]
"""

from __future__ import annotations

import argparse
import contextlib
import inspect
import json
import os
import signal
import sys
import threading
import time
import traceback
import warnings

from benchmarks import (bench_async_overlap, bench_e2e_paged,
                        bench_fault_overhead, bench_graph, bench_lock,
                        bench_mixed_batch, bench_moe, bench_offload,
                        bench_paged_attention, bench_ptw, bench_serving,
                        bench_sharded, bench_static_analysis,
                        bench_table1, bench_vm_throughput, bench_wcet)
from benchmarks._workbench import fmt_table
from repro.launch.compile_cache import enable_compile_cache

# Per-module wall-clock budget: one hung bench (an XLA compile gone
# quadratic, a deadlocked wait) must report as a module failure instead
# of eating the CI job's whole 45-minute budget.  0 disables the alarm.
MODULE_TIMEOUT_S = int(os.environ.get("BENCH_MODULE_TIMEOUT_S", "900"))


class ModuleTimeout(Exception):
    pass


@contextlib.contextmanager
def _deadline(seconds: int, key: str):
    """SIGALRM-based wall-clock cap around one module (main thread,
    POSIX only — a no-op where SIGALRM is unavailable).  ``signal()``
    raises ``ValueError`` off the main thread (e.g. the harness driven
    from a worker thread of an embedding process), so warn and run
    uncapped instead of crashing every module."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return
    if threading.current_thread() is not threading.main_thread():
        warnings.warn(
            f"benchmark module {key!r}: SIGALRM timeout unavailable off "
            f"the main thread; running without a wall-clock cap",
            RuntimeWarning, stacklevel=2)
        yield
        return

    def _fire(signum, frame):
        raise ModuleTimeout(
            f"benchmark module {key!r} exceeded {seconds}s "
            f"(BENCH_MODULE_TIMEOUT_S)")

    prev = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)

MODULES = [
    ("table1", "Table 1: RTT cost of indirection", bench_table1),
    ("fig2_3", "Figures 2-3: offload crossover", bench_offload),
    ("fig6_7", "Figures 6-7: graph traversal", bench_graph),
    ("fig8", "Figure 8: page-table walk", bench_ptw),
    ("fig9", "Figure 9: distributed lock", bench_lock),
    ("fig10", "Figure 10: disaggregated PagedAttention",
     bench_paged_attention),
    ("sec4.5", "Section 4.5: MoE expert gather", bench_moe),
    ("vm_tput", "Engine throughput: interp vs batched vs compiled",
     bench_vm_throughput),
    ("mixed", "Multi-tenant mixed-op batching vs per-op launches",
     bench_mixed_batch),
    ("sharded", "Sharded pool over a device mesh vs single device",
     bench_sharded),
    ("async_overlap", "Async MEMCPY overlap: split-phase vs serialized",
     bench_async_overlap),
    ("fault_overhead", "Runtime protection cost on the fault-free path",
     bench_fault_overhead),
    ("serving", "Overload-safe serving loop: goodput and tails at 2x",
     bench_serving),
    ("static_analysis", "Static conflict proofs: sweep-skip + soundness",
     bench_static_analysis),
    ("e2e_paged", "End-to-end disaggregated paged decode vs host resolve",
     bench_e2e_paged),
    ("wcet", "Line-rate certification: soundness corpus + admission "
     "fail-fast", bench_wcet),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module key")
    ap.add_argument("--json", default=None, help="dump rows as JSON")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke configuration: small batches, few "
                         "reps, for modules that support it")
    args = ap.parse_args()
    enable_compile_cache()

    all_rows = []
    tables = []
    crashed = []
    for key, title, mod in MODULES:
        if args.only and args.only not in key:
            continue
        kwargs = {}
        if args.quick and "quick" in inspect.signature(mod.rows).parameters:
            kwargs["quick"] = True
        t0 = time.time()
        # a crashed (or hung — see _deadline) module must not silently
        # vanish from the report: run the remaining modules, but exit
        # nonzero so the scheduled bench-smoke job cannot pass on it
        try:
            with _deadline(MODULE_TIMEOUT_S, key):
                rows = mod.rows(**kwargs)
        except Exception:
            traceback.print_exc()
            print(f"::error::benchmark module {key!r} crashed",
                  file=sys.stderr)
            crashed.append(key)
            continue
        dt = time.time() - t0
        all_rows.extend(rows)
        tables.append(fmt_table(rows, f"{title}  [{dt:.1f}s]"))

    print("name,us_per_call,derived")
    for r in all_rows:
        print(r.csv())
    print()
    for t in tables:
        print(t)
        print()

    claims = [r for r in all_rows if r.paper is not None]
    ok = sum(1 for r in claims if r.ratio() is not None
             and 0.7 <= r.ratio() <= 1.3)
    print(f"== claim check: {ok}/{len(claims)} paper-anchored rows within "
          f"+/-30% of the claimed value ==")
    worst = sorted((r for r in claims if r.ratio() is not None),
                   key=lambda r: abs(1 - r.ratio()), reverse=True)[:5]
    for r in worst:
        print(f"   largest deviation: {r.name}: derived {r.derived:.3g} "
              f"vs paper {r.paper:.3g} (x{r.ratio():.2f}) {r.note}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump([r.__dict__ for r in all_rows], f, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)

    if crashed:
        print(f"== {len(crashed)} benchmark module(s) crashed: "
              f"{', '.join(crashed)} ==", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
