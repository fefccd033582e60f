"""idle_wait_batch: share of the traced window in which the device was
idle while the train loop waited for the pipeline's next batch (%): idle
under the span ``hyscale.wait_batch`` (``spans.idle_by_span``), averaged
over the chips used."""
from bench import spans


def read(rec):
    s = spans.of_run(rec, __file__)
    if (s is None or s["idle_by_span"] is None
            or "hyscale.wait_batch" not in s["spans"]):
        return None
    return 100.0 * s["idle_by_span"]["wait_batch"] / s["window_s"]
