"""Model operations of the engine steps in the traced window (every
decode and prefill, ``bench/work/decode.py``) over the window times the
chip's peak bf16 rate, in percent."""


def reduce(trace, run):
    if not trace.chips or not run["flops"]:
        return None
    return 100.0 * run["flops"] / (trace.window_s
                                   * run["peaks"]["bf16_flops"]
                                   * len(trace.chips))
