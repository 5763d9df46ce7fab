"""The decode program as a share of its roofline, in percent: the bytes
a step must read (``bench/work/decode.py``: every weight but the unused
embedding rows, and each active sequence's keys and values up to its
length) over the program's device time, over peak HBM bandwidth.  At
32 tokens a step the arithmetic is far below the chip's peak, so
bandwidth is the bound."""

import timeline as tr


def reduce(trace, run):
    if not trace.chips or not run["decode_bytes"]:
        return None
    runs = tr.most_runs(trace, trace.chips[0], tr.MODEL_PROGRAM)
    if not runs:
        return None
    step_s = tr.total(tr.union(runs, *trace.window)) / len(runs) * 1e-9
    mean_bytes = sum(run["decode_bytes"]) / len(run["decode_bytes"])
    return 100.0 * mean_bytes / step_s / run["peaks"]["hbm_bytes_per_s"]
