"""dispatch_ms: the call into the combine's device program (ms), the
host-to-device copy of its tables and any compile included: the span
``hyscale.transfer.dispatch`` in the traced window, per iteration."""
from bench import spans


def read(rec):
    return spans.ms_per_iter(rec, __file__, "hyscale.transfer.dispatch")
