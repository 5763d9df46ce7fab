"""Device time of the resolver's VM waves per decode step: every block
table of the step resolved through the fabric."""

import timeline as tr


def reduce(trace, run):
    steps = sum(1 for _, d in run["steps"] if d)
    if not trace.chips or not steps:
        return None
    runs = tr.module_runs(trace, trace.chips[0], tr.FABRIC_WAVE_PROGRAM)
    if not runs:
        return None
    return tr.total(tr.union(runs, *trace.window)) / steps * 1e-6
