"""Device time of the collectives (all-gather, all-reduce, all-to-all,
reduce-scatter, collective-permute) per wave of the sharded engine,
averaged over the chips."""

import timeline as tr

COLLECTIVES = r"\b(all-gather|all-reduce|all-to-all|reduce-scatter|" \
    r"collective-permute)(-start|-done)?$"


def reduce(trace, run):
    per_chip = []
    for chip in trace.chips:
        runs = tr.module_runs(trace, chip, tr.FABRIC_WAVE_PROGRAM)
        if not runs:
            continue
        per_chip.append(tr.op_time(trace, chip, COLLECTIVES) / len(runs)
                        * 1e-6)
    if len(trace.chips) < 2 or not per_chip:
        return None
    return sum(per_chip) / len(per_chip)
