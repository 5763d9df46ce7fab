"""The VM wave as a share of its roofline: the useful bytes of a wave's
posts (``bench/work/fabric.py``) over the wave's device time, over the
chip's peak HBM bandwidth, in percent.  The posts do next to no
arithmetic, so bandwidth is the bound."""

import timeline as tr


def reduce(trace, run):
    wave_bytes = run.get("wave_bytes") or []
    times = []
    for chip in trace.chips:
        runs = tr.module_runs(trace, chip, tr.FABRIC_WAVE_PROGRAM)
        if runs:
            times.append(tr.total(tr.union(runs, *trace.window))
                         / len(runs) * 1e-9)
    if not wave_bytes or not times:
        return None
    per_wave_s = sum(times) / len(times)
    mean_bytes = sum(wave_bytes) / len(wave_bytes)
    return 100.0 * mean_bytes / per_wave_s / run["peaks"]["hbm_bytes_per_s"]
