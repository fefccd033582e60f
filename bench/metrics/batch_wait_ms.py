"""batch_wait_ms: the train loop's wait for the pipeline's next batch
(ms): the span ``hyscale.wait_batch`` in the traced window, per
iteration."""
from bench import spans


def read(rec):
    return spans.ms_per_iter(rec, __file__, "hyscale.wait_batch")
