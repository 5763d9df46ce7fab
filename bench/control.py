"""Readings that set a cell's limits: the program's over many seeds, and
the control's beside them.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 20

For each seed, in this one process: build the cell, run its traffic for
``--seconds`` at the cell's own load, free the system's device state,
then compare what it produced with the plain reference (the program's
reading) and take the control's reading on the same requests: for a
served model, the float8 pass in the program's place; for the operator
mix, the reference with one stated guarantee broken.  One JSON line per
seed.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(spec: dict, seed: int, seconds: float) -> dict:
    cfg = spec["config"]
    devs = run.devices(spec["cell"]["chips"])
    driver = run.load_module(spec["bench_dir"] / "drivers" / f"{cfg['driver']}.py")
    cell = driver.build(cfg, spec["traffic"], seed, len(devs))
    cell.window(seconds)
    cell.release()
    gc.collect()
    program, attempted, failed = cell.check()
    if cfg["driver"] == "decode":
        control, _, _ = cell.check(fp8=True)
    else:
        control, _, _ = cell.check(control=True)
    out = {"seed": seed, "attempted": attempted, "failed": failed,
           "program": {k: v for k, (v, _) in program.items()},
           "control": {k: v for k, (v, _) in control.items()},
           "limits": {k: lim for k, (_, lim) in program.items()}}
    del cell
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    spec = run.cell_spec(bench, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        run.devices(spec["cell"]["chips"])
    except run.NoDevice as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
