"""h2d_mb_per_iter: feature bytes shipped host to device (MB, padding
included) over the window, per iteration."""


def read(rec):
    return rec["traffic"]["shipped_bytes"] / rec["iters"] / 1e6
