"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips."""

import timeline as tr


def reduce(trace, run):
    if not trace.chips:
        return None
    return 100.0 * sum(tr.idle_share(trace, c) for c in trace.chips) \
        / len(trace.chips)
