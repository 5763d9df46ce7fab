"""The host's cost per wave as the device sees it: the device's idle
time in the traced window over the waves that ran in it, averaged over
the chips.  With two waves in flight, a device that idles at all waits
for the host to retire one wave and launch the next."""

import timeline as tr


def reduce(trace, run):
    lo, hi = trace.window
    per_chip = []
    for chip in trace.chips:
        runs = tr.module_runs(trace, chip, tr.FABRIC_WAVE_PROGRAM)
        if runs:
            idle = (hi - lo) - tr.total(tr.busy(trace, chip))
            per_chip.append(idle / len(runs) * 1e-6)
    return sum(per_chip) / len(per_chip) if per_chip else None
