"""Device time of one run of the decode program (the model step over
every slot)."""

import timeline as tr


def reduce(trace, run):
    if not trace.chips:
        return None
    runs = tr.most_runs(trace, trace.chips[0], tr.MODEL_PROGRAM)
    if not runs:
        return None
    return tr.total(tr.union(runs, *trace.window)) / len(runs) * 1e-6
