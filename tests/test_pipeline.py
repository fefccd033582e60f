"""Two-stage prefetch pipeline: pipelined == sequential, order preserved,
stage times recorded, errors propagate."""
import time

import pytest

from repro.core import PipelineItem, PrefetchPipeline, Stage


def _items(n):
    return (PipelineItem(seq=i, payload=i) for i in range(n))


def _stages():
    return [Stage("sample", lambda it: _apply(it, lambda x: x * 2)),
            Stage("load", lambda it: _apply(it, lambda x: x + 1)),
            Stage("transfer", lambda it: _apply(it, lambda x: x * 10))]


def _apply(item, fn):
    item.payload = fn(item.payload)
    return item


@pytest.mark.parametrize("depth", [0, 1, 2, 4])
def test_pipeline_results_match_sequential(depth):
    pipe = PrefetchPipeline(_stages(), depth=depth)
    out = [(it.seq, it.payload) for it in pipe.run(_items(20))]
    assert out == [(i, (i * 2 + 1) * 10) for i in range(20)]


def test_stage_timings_recorded():
    pipe = PrefetchPipeline(_stages(), depth=2)
    names = {"sample", "load", "transfer"}
    for it in pipe.run(_items(3)):
        # one service time per stage; a stage's wait on its input queue is
        # a span on the trace (hyscale.<stage>.starved), not a timing
        assert set(it.timings) == names
        assert all(t >= 0 for t in it.timings.values())


def test_sequential_mode_records_no_waits():
    pipe = PrefetchPipeline(_stages(), depth=0)
    for it in pipe.run(_items(3)):
        assert set(it.timings) == {"sample", "load", "transfer"}


def test_pipeline_overlaps_stages():
    """With depth>=1 total wall time < sum of all stage times (overlap).

    Stages sleep, releasing the GIL, so even this 1-core container
    overlaps them — exactly the paper's claim that Feature Loading and
    Data Transfer use different resources concurrently.
    """
    def slow(name, dt):
        def fn(item):
            time.sleep(dt)
            return item
        return Stage(name, fn)

    stages = [slow("a", 0.02), slow("b", 0.02), slow("c", 0.02)]
    n = 10
    t0 = time.perf_counter()
    list(PrefetchPipeline(stages, depth=2).run(_items(n)))
    t_pipe = time.perf_counter() - t0
    t0 = time.perf_counter()
    list(PrefetchPipeline(stages, depth=0).run(_items(n)))
    t_seq = time.perf_counter() - t0
    assert t_pipe < 0.75 * t_seq, (t_pipe, t_seq)


def test_error_propagates():
    def boom(item):
        if item.seq == 3:
            raise ValueError("boom")
        return item

    pipe = PrefetchPipeline([Stage("s", boom)], depth=2)
    with pytest.raises(ValueError, match="boom"):
        list(pipe.run(_items(10)))


@pytest.mark.parametrize("depth", [0, 2])
def test_error_state_cleared_between_runs(depth):
    """A reused pipeline must not re-raise a stale exception on a clean
    run (regression: _error survived across run() calls)."""
    arm = {"on": True}

    def maybe_boom(item):
        if arm["on"] and item.seq == 1:
            raise ValueError("boom")
        return item

    pipe = PrefetchPipeline([Stage("s", maybe_boom)], depth=depth)
    with pytest.raises(ValueError, match="boom"):
        list(pipe.run(_items(5)))
    arm["on"] = False
    out = [it.seq for it in pipe.run(_items(5))]
    assert out == list(range(5))


def test_feeder_stops_consuming_payloads_after_failure():
    """After a stage fails, the feeder must stop draining the payload
    generator (regression: payload side effects — e.g. the trainer's
    epoch cursor — kept advancing for batches that were silently
    dropped)."""
    produced = []

    def gen(n):
        for i in range(n):
            produced.append(i)
            yield PipelineItem(seq=i, payload=i)

    def boom(item):
        if item.seq == 1:
            raise ValueError("boom")
        time.sleep(0.002)
        return item

    pipe = PrefetchPipeline([Stage("s", boom)], depth=2)
    with pytest.raises(ValueError, match="boom"):
        list(pipe.run(gen(200)))
    # a few in-flight payloads may slip through (queue depth + one in
    # hand), but nothing close to the full generator
    assert len(produced) < 50, f"feeder drained {len(produced)} payloads"
