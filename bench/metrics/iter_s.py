"""iter_s: wall-clock seconds of the window's one ``train`` call over the
iterations it completed, each the whole batch across every trainer."""


def read(rec):
    return rec["window_s"] / rec["iters"]
