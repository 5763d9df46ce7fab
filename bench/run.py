"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell; ``bench/configs/<config>.json`` holds
the configuration and the driver that runs it
(``bench/drivers/<driver>.py``); ``bench/traffic/<traffic>.json`` holds
the mix; each per-layer metric is read by ``bench/layers/<metric>.py``.

A run builds the system, warms up the shapes of its own traffic, measures
for ``--seconds`` (with ``--trace 1``, a traced window of the mix's
``trace_seconds`` instead), reads the device's peak memory, frees the
system's device state and then compares what the timed path produced
with the plain reference.  The last line of standard output is one JSON
object; the last lines of standard error are the numbers compared, each
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str, bench_dir: Path = BENCH) -> dict:
    """The cell named ``workload`` with its configuration, its traffic
    mix, its end-to-end metrics and its per-layer metrics, each found by
    name under ``bench_dir``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(bench_dir.parent / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if applies(m) and m["moves"] in e2e_names]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layers, "bench_dir": bench_dir}


def devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips; JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


class CompileCount:
    """Programs JAX compiles, or loads from its persistent cache, from
    the moment this is made: none may fall inside the measured window."""

    def __init__(self) -> None:
        import jax

        self.n = 0

        def on_event(name: str, **_) -> None:
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.n += 1

        def on_duration(name: str, secs: float, **_) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def reduce_layers(spec: dict, trace, run: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell, by its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(spec["bench_dir"] / "layers"
                             / f"{m['name']}.py")
        value = reader.reduce(trace, run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(spec: dict, seed: int, seconds: float, traced: bool, *,
             find_devices: Callable[[int], list] = devices,
             cache: bool = True) -> dict:
    """One run of the cell ``spec`` (see :func:`cell_spec`); returns the
    result line as a dict.  Tests pass CPU devices to ``find_devices``
    and run without the persistent compile cache."""
    cfg = spec["config"]
    devs = find_devices(spec["cell"]["chips"])
    for path in (str(ROOT / "src"), str(BENCH)):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    if cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = load_module(spec["bench_dir"] / "drivers"
                         / f"{cfg['driver']}.py")
    peaks = load_json(spec["bench_dir"] / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if traced and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    compiles = CompileCount()
    cell = driver.build(cfg, spec["traffic"], seed, len(devs))
    before = compiles.n

    if traced:
        import timeline as tr

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        seconds = min(seconds, spec["traffic"]["trace_seconds"])
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                info = cell.window(seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        info = cell.window(seconds)
    window_compiles = compiles.n - before
    setup_s = info["t0"] - T_START
    memory_peak = peak_bytes(devs)
    cell.release()
    gc.collect()
    checks, attempted, failed = cell.check()

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result: dict = {"correct": all(v <= lim for v, lim in checks.values()),
                    "attempted": attempted, "failed": failed,
                    "window_compiles": window_compiles}
    if traced:
        trace = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run = dict(info, peaks=peaks[kind], config=cfg,
                   traffic=spec["traffic"])
        result["metrics"] = reduce_layers(spec, trace, run)
        device.update(busy_s=tr.busy_s(trace), window_s=trace.window_s)
        result["device"] = device
        result["breakdown"] = tr.breakdown(trace)
    else:
        values = dict(driver.end_to_end(info), setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values.pop(m["name"]),
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
        # what the driver measured besides, for reading; not a metric
        result["also"] = values
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    bench = load_json(ROOT / "BENCHMARK.json")
    try:
        result = run_cell(cell_spec(bench, args.workload), args.seed,
                          args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(f"programs compiled or loaded in the window: "
          f"{result['window_compiles']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
