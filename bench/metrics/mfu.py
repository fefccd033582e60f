"""mfu: the whole step's model FLOPs (forward and backward at the regular
layout, ``flops.py``) times the window's iterations, over the window and
the chips' peak (%).  The FLOPs the CPU trainer does count too."""


def read(rec):
    peaks = rec["peaks"]
    if peaks is None or rec["window_s"] <= 0:
        return None
    rate = rec["flops_per_iter"] * rec["iters"] / rec["window_s"]
    return 100.0 * rate / (rec["chips"] * peaks["flops_per_s"])
