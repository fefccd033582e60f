"""Two-stage feature prefetching / pipelined runtime (paper Section IV-B).

Generic bounded-queue pipeline: each stage runs in its own host thread and
communicates through ``queue.Queue(maxsize=depth)``.  ``depth`` is the
prefetch window — with the paper's default (2) the Feature Loader works on
mini-batch i+2 while the Data Transfer stage ships mini-batch i+1 and the
accelerator executes mini-batch i (paper Fig. 7).

The stages overlap because they use different resources (host RAM channel,
PCIe channel, device compute) and mini-batches are independent.  Disabling
TFP (``depth=0``) degenerates to sequential stage execution — that is the
ablation baseline of Fig. 11.

Every item carries a ``timings`` dict; each stage call is timed once, as
the span ``hyscale.<stage>`` (``core.spans``, carrying the item's
``iteration=``), and its seconds are the stage's service time, which the
Runtime feeds to the DRM engine.  A pipelined stage's wait on its input
queue is the span ``hyscale.<stage>.starved``: the pipeline-level stall
signal, seen on a profiler trace — a stage whose upstream is the
bottleneck shows long starved spans, a stage that IS the bottleneck shows
none.

Failure model & degraded modes
------------------------------

A stage that *raises* already fails fast: the error latches, the feeder
stops consuming payloads, every worker drains to its sentinel, and the
current ``run()`` re-raises — no deadlock, no stale state on the next
run.  A stage that *wedges* (a gather stuck on a dead NFS mount, an
injected 30 s delay) used to hang the consumer forever; with
``watchdog_seconds > 0`` the consumer polls its output queue and checks
per-stage heartbeats: a stage busy on one item (or the feeder's batch
generator stuck) past the deadline raises ``PipelineStallError`` naming
the wedged stage, how long it has been stuck, every queue depth and
per-stage completion counts — a diagnosis instead of a hang.  The
watchdog never fires while items keep arriving, and 0 (the default)
keeps the legacy blocking behaviour.  Deterministic fault hook:
``pipeline.<stage>`` fires before each stage invocation (injected
delays wedge the stage and back queues up into a queue-full storm;
injected errors exercise the stage-failure protocol).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

from .spans import span

__all__ = ["PipelineItem", "Stage", "PrefetchPipeline", "PipelineStallError"]

_SENTINEL = object()


@dataclasses.dataclass
class PipelineItem:
    seq: int
    payload: Any
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Stage:
    name: str
    fn: Callable[[PipelineItem], PipelineItem]   # mutates/returns the item


class PipelineStallError(RuntimeError):
    """A pipeline stage (or the feeder) made no progress past the
    watchdog deadline.  Carries the wedged stage's name plus a queue /
    completion snapshot for diagnosis."""

    def __init__(self, stage: str, stalled_seconds: float,
                 watchdog_seconds: float, queue_depths: Dict[str, int],
                 completed: Dict[str, int]):
        self.stage = stage
        self.stalled_seconds = stalled_seconds
        self.watchdog_seconds = watchdog_seconds
        self.queue_depths = dict(queue_depths)
        self.completed = dict(completed)
        super().__init__(
            f"pipeline stage {stage!r} wedged: no progress for "
            f"{stalled_seconds:.1f}s (watchdog {watchdog_seconds:.1f}s); "
            f"queue depths {queue_depths}; items completed per stage "
            f"{completed}")


class PrefetchPipeline:
    """Chains stages over bounded queues; ``depth=0`` means fully sequential.

    Concurrency note (why there is no ``guarded_by`` declaration here,
    unlike the other threaded modules — the annotation is opt-in and this
    class deliberately has nothing to declare): every ``run()`` threads
    its own per-run locals (queues, heartbeat dicts, error holder, stop
    event) through the workers it spawns, and all cross-thread handoffs
    ride the bounded ``queue.Queue``s, whose put/get pairs establish the
    happens-before edges.  The heartbeat dicts are single-writer (their
    own stage thread); the watchdog only ever *reads* them, and a torn
    read costs one poll tick, not correctness.  ``self._error`` is
    observability-only, written after the run's threads are joined."""

    def __init__(self, stages: List[Stage], depth: int = 2,
                 watchdog_seconds: float = 0.0,
                 fault_injector=None):
        self.stages = stages
        self.depth = int(depth)
        self.watchdog_seconds = float(watchdog_seconds)
        self.fault_injector = fault_injector
        # last completed run's failure (observability only): every run()
        # threads its OWN error holder + stop event through its workers,
        # so threads left over from an abandoned earlier run can never
        # contaminate a later run's state
        self._error: Optional[BaseException] = None

    # ------------------------------------------------------------ sequential

    def _run_sequential(self, items: Iterable[PipelineItem]
                        ) -> Iterator[PipelineItem]:
        for item in items:
            for st in self.stages:
                if self.fault_injector is not None:
                    self.fault_injector.fire(f"pipeline.{st.name}")
                item = self._call(st, item)
            yield item

    @staticmethod
    def _call(st: Stage, item: PipelineItem) -> PipelineItem:
        """One stage call, timed once: span ``hyscale.<stage>``, whose
        seconds become ``timings[<stage>]``."""
        with span("hyscale." + st.name, iteration=item.seq) as s:
            item = st.fn(item)
        item.timings[st.name] = s.seconds
        return item

    # ------------------------------------------------------------- pipelined

    def _worker(self, st: Stage, q_in: "queue.Queue", q_out: "queue.Queue",
                state: Dict[str, Optional[BaseException]],
                stop: threading.Event, hb: Dict[str, Any]):
        failed = False
        starved = f"hyscale.{st.name}.starved"
        while True:
            with span(starved):
                item = q_in.get()
            if item is _SENTINEL:
                q_out.put(_SENTINEL)
                return
            if failed or stop.is_set():
                continue            # drain so the feeder never blocks
            try:
                # heartbeat: the watchdog reads (busy, since) to tell a
                # wedged stage from an idle one
                hb["since"] = time.perf_counter()
                hb["busy"] = True
                if self.fault_injector is not None:
                    self.fault_injector.fire(f"pipeline.{st.name}")
                item = self._call(st, item)
                hb["busy"] = False
                hb["done"] += 1
            except BaseException as e:  # propagate to consumer
                hb["busy"] = False
                state["error"] = e
                stop.set()          # feeder: stop pulling new payloads
                failed = True       # keep draining until the sentinel
                continue
            q_out.put(item)

    def _check_stall(self, beats: List[Dict[str, Any]],
                     qs: List["queue.Queue"],
                     stop: threading.Event) -> None:
        """Raise ``PipelineStallError`` if any busy stage (or the feeder's
        generator pull) exceeded the watchdog deadline."""
        now = time.perf_counter()
        for hb in beats:
            if hb["busy"] and now - hb["since"] > self.watchdog_seconds:
                stop.set()
                depths = {}
                for i, q in enumerate(qs):
                    label = (self.stages[i].name if i < len(self.stages)
                             else "output") + "_in"
                    depths[label] = q.qsize()
                completed = {hb2["name"]: hb2["done"] for hb2 in beats}
                raise PipelineStallError(
                    hb["name"], now - hb["since"], self.watchdog_seconds,
                    depths, completed)

    def run(self, items: Iterable[PipelineItem]) -> Iterator[PipelineItem]:
        # a pipeline object is reusable: a clean run must not re-raise a
        # stale exception, so failure state is PER RUN (closed over below)
        self._error = None
        if self.depth <= 0:
            yield from self._run_sequential(items)
            return
        state: Dict[str, Optional[BaseException]] = {"error": None}
        stop = threading.Event()
        qs: List["queue.Queue"] = [queue.Queue(maxsize=self.depth)
                                   for _ in range(len(self.stages) + 1)]
        beats: List[Dict[str, Any]] = [
            {"name": st.name, "busy": False, "since": 0.0, "done": 0}
            for st in self.stages]
        feed_hb: Dict[str, Any] = {"name": "feed", "busy": False,
                                   "since": 0.0, "done": 0}
        threads = [threading.Thread(target=self._worker,
                                    args=(st, qs[i], qs[i + 1], state, stop,
                                          beats[i]),
                                    daemon=True)
                   for i, st in enumerate(self.stages)]
        for t in threads:
            t.start()

        def feed():
            try:
                it = iter(items)
                while True:
                    if stop.is_set():
                        break       # a stage died: don't consume payloads
                    # the generator pull is heartbeat-tracked too: a
                    # wedged batch source (not just a wedged stage) must
                    # also be diagnosable
                    feed_hb["since"] = time.perf_counter()
                    feed_hb["busy"] = True
                    try:
                        item = next(it)
                    except StopIteration:
                        feed_hb["busy"] = False
                        break
                    feed_hb["busy"] = False
                    feed_hb["done"] += 1
                    qs[0].put(item)
            finally:
                feed_hb["busy"] = False
                qs[0].put(_SENTINEL)

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        wd = self.watchdog_seconds
        poll = min(0.2, wd / 5.0) if wd > 0 else None
        while True:
            if poll is None:
                item = qs[-1].get()
            else:
                try:
                    item = qs[-1].get(timeout=poll)
                except queue.Empty:
                    # nothing arrived this tick: is someone wedged?  (the
                    # stalled stage's thread stays stuck inside st.fn —
                    # nothing can unstick it — so raise a diagnosis
                    # instead of inheriting its hang)
                    self._check_stall(beats + [feed_hb], qs, stop)
                    continue
            if item is _SENTINEL:
                break
            yield item
        feeder.join()
        for t in threads:
            t.join()
        if state["error"] is not None:
            self._error = state["error"]
            raise state["error"]
