"""Device time of one VM wave: the union of the intervals in which the
wave program runs, per run, averaged over the chips."""

import timeline as tr

WAVE_PROGRAM = tr.FABRIC_WAVE_PROGRAM


def reduce(trace, run):
    per_chip = []
    for chip in trace.chips:
        runs = tr.module_runs(trace, chip, WAVE_PROGRAM)
        if runs:
            per_chip.append(tr.total(tr.union(runs, *trace.window))
                            / len(runs) * 1e-6)
    return sum(per_chip) / len(per_chip) if per_chip else None
