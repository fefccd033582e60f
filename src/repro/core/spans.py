"""Program spans on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` that also
times its interval with ``time.perf_counter``: after the block,
``.seconds`` holds the time the trace shows for the same interval.  Every
stage time of ``StageTimes`` is taken through it, so the DRM, the
benchmark's per-layer metrics and a profiler trace read one clock.

The annotation records only while a profiler session runs
(``jax.profiler.start_trace``); outside one a span costs a few
microseconds, and an iteration of the hybrid trainer opens about 25.

Names are ``hyscale.<layer>[.<part>]``.  Layer spans carry the batch's
``iteration=`` so that one batch's spans on the train loop, the pipeline
stages and the trainer threads can be joined; a part span lies inside
its layer's span on the same thread.  The train loop's iterations are
profiler steps (``step``).
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "step"]


class span(TraceAnnotation):
    """``with span("hyscale.load", iteration=i) as s: ...``; then
    ``s.seconds``."""

    __slots__ = ("_t0", "seconds")

    def __init__(self, name: str, **ids) -> None:
        # the annotation takes its start time when it is made and its end
        # time when it is left: read the clock right after each
        super().__init__(name, **ids)
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self.seconds = time.perf_counter() - self._t0


def step(name: str, step_num: int) -> span:
    """A ``span`` that marks one profiler step: the event
    ``jax.profiler.StepTraceAnnotation(name, step_num=step_num)`` makes
    (a root event, ``_r=1``)."""
    return span(name, _r=1, step_num=step_num)
