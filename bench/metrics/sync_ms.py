"""sync_ms: the gradient sync (ms): ``Synchronizer.all_reduce``'s
weighted average after the last trainer reported, the span
``hyscale.sync`` in the traced window, per iteration."""
from bench import spans


def read(rec):
    return spans.ms_per_iter(rec, __file__, "hyscale.sync")
