"""The paged-attention kernel as a share of its roofline, in percent:
the keys and values it must read (each sequence up to its length) over
the kernel's device time, over peak HBM bandwidth."""

import timeline as tr


def reduce(trace, run):
    if not trace.chips or not run["kv_bytes"]:
        return None
    chip = trace.chips[0]
    decodes = tr.most_runs(trace, chip, tr.MODEL_PROGRAM)
    kernel_ns = tr.op_time(trace, chip, tr.PAGED_ATTENTION_OP)
    if not decodes or not kernel_ns:
        return None
    per_step_s = kernel_ns / len(decodes) * 1e-9
    mean_bytes = sum(run["kv_bytes"]) / len(run["kv_bytes"])
    return 100.0 * mean_bytes / per_step_s / run["peaks"]["hbm_bytes_per_s"]
