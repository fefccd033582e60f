"""gather_ms: the host row gathers (ms), the CPU trainer's positional
gather included: the span ``hyscale.load.gather`` in the traced window,
summed over threads, per iteration."""
from bench import spans


def read(rec):
    return spans.ms_per_iter(rec, __file__, "hyscale.load.gather")
