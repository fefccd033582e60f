"""schedule_ms: the combine's host schedule (ms): its tables (``unique``,
``searchsorted``, ``argsort``) and the miss rows' bucket padding, the
span ``hyscale.transfer.schedule`` in the traced window, per iteration."""
from bench import spans


def read(rec):
    return spans.ms_per_iter(rec, __file__, "hyscale.transfer.schedule")
