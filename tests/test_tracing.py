"""The trainer's stage spans on the profiler clock (``core.spans``).

A tiny hybrid trainer (CPU trainer + one accelerator trainer, device
cache) runs a few iterations under ``jax.profiler``, with the pipeline
two or one batches ahead or sequential; the ``.xplane.pb`` it writes is read back with
``ProfileData``.  Each host thread has a line of its own on the host
plane, so "its thread" is "its line"."""
import collections
import glob
import os
import sys
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import HybridConfig, HybridGNNTrainer
from repro.core.spans import span, step
from repro.graph import GNNConfig, make_dataset

ITERS = 5
LOOP = ("hyscale.step", "hyscale.wait_batch", "hyscale.train",
        "hyscale.update", "hyscale.drm")
STAGES = ("hyscale.sample", "hyscale.load", "hyscale.transfer")
TRAINERS = ("hyscale.train.cpu", "hyscale.train.accel")
# part span -> the span it lies in, on the same thread, in every iteration
PARTS = {"hyscale.sync": "hyscale.train",
         "hyscale.load.lookup": "hyscale.load",
         "hyscale.load.gather": "hyscale.load",
         "hyscale.transfer.schedule": "hyscale.transfer",
         "hyscale.transfer.dispatch": "hyscale.transfer",
         "hyscale.transfer.ship": "hyscale.transfer",
         "hyscale.transfer.wait": "hyscale.transfer"}


def _events(trace_dir):
    """``hyscale.*`` host events: (line index, name, start, end, stats)."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("hyscale."):
                    out.append((li, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module", params=[2, 1, 0],
                ids=["tfp2", "tfp1", "sequential"])
def traced(request, tmp_path_factory):
    depth = request.param
    ds = make_dataset("ogbn-products", scale=0.002, seed=0)
    gnn = GNNConfig(model="sage", layer_dims=(100, 32, 47), fanouts=(4, 3),
                    num_classes=47)
    tr = HybridGNNTrainer(ds, gnn, HybridConfig(
        total_batch=128, n_accel=1, hybrid=True, use_drm=False,
        tfp_depth=depth, cache_fraction=0.3, dedup=True, seed=0))
    tr.train(3)                              # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    hist = list(tr.train(ITERS)[-ITERS:])
    jax.profiler.stop_trace()
    tr.close()
    return depth, hist, _events(trace_dir)


def _of(events, name, iteration=None):
    return [e for e in events if e[1] == name
            and (iteration is None or e[4].get("iteration") == iteration)]


def _inside(child, parent):
    return (child[0] == parent[0] and parent[2] <= child[2]
            and child[3] <= parent[3])


def _loop_line(events):
    (line,) = {e[0] for e in _of(events, "hyscale.step")}
    return line


def test_every_span_once_per_iteration_on_its_thread(traced):
    depth, hist, events = traced
    assert [m.iteration for m in hist] == list(range(ITERS))
    loop = _loop_line(events)
    steps = _of(events, "hyscale.step")
    assert sorted(e[4]["step_num"] for e in steps) == list(range(ITERS))
    for i in range(ITERS):
        for name in LOOP[1:] + STAGES + TRAINERS:
            got = _of(events, name, i)
            assert len(got) == 1, (name, i, got)
        for name in LOOP[1:]:
            assert _of(events, name, i)[0][0] == loop, name
        for name in TRAINERS:
            assert _of(events, name, i)[0][0] != loop, name
    stage_lines = {name: {e[0] for e in _of(events, name)}
                   for name in STAGES}
    for name, lines in stage_lines.items():
        assert len(lines) == 1, (name, lines)
        if depth:       # each stage on a thread of its own
            assert lines != {loop}, name
            assert _of(events, name + ".starved"), name
        else:           # sequential: the loop runs the stages
            assert lines == {loop}, name
    if depth:
        assert len(set().union(*stage_lines.values())) == len(STAGES)


def test_child_spans_lie_inside_their_parent(traced):
    depth, _, events = traced
    # the schedule span holds the miss rows' bucket padding, which a batch
    # may not need
    every_batch = {part: layer for part, layer in PARTS.items()
                   if part != "hyscale.transfer.schedule"}
    for i in range(ITERS):
        (st,) = [e for e in _of(events, "hyscale.step")
                 if e[4]["step_num"] == i]
        loop = [_of(events, name, i)[0] for name in LOOP[1:]]
        assert all(_inside(e, st) for e in loop)
        assert [e[2] for e in loop] == sorted(e[2] for e in loop)
        if not depth:
            wait = _of(events, "hyscale.wait_batch", i)[0]
            assert all(_inside(_of(events, name, i)[0], wait)
                       for name in STAGES)
        for part, layer in every_batch.items():
            parent = _of(events, layer, i)[0]
            inside = [e for e in _of(events, part) if _inside(e, parent)]
            assert inside, (part, i)
    for part, layer in PARTS.items():
        parents = _of(events, layer)
        for e in _of(events, part):
            assert any(_inside(e, p) for p in parents), (part, e)


def test_stage_times_are_their_spans(traced):
    """Each time the program keeps is its span's duration: within 1% in
    the median iteration.  The two clocks are read one Python call apart,
    where another thread may take the GIL for up to a switch interval, so
    a single iteration may differ by that much."""
    _, hist, events = traced

    def secs(name, i, key="iteration"):
        (e,) = [e for e in _of(events, name) if e[4][key] == i]
        return (e[3] - e[2]) * 1e-9

    fields = {"hyscale.load": lambda m: m.times.t_load,
              "hyscale.transfer": lambda m: m.times.t_tran,
              "hyscale.train.cpu": lambda m: m.times.t_tc,
              "hyscale.train.accel": lambda m: m.times.t_ta,
              "hyscale.update": lambda m: m.t_sync,
              "hyscale.step": lambda m: m.iter_time}
    for name, got in fields.items():
        key = "step_num" if name == "hyscale.step" else "iteration"
        pairs = [(got(m), secs(name, m.iteration, key)) for m in hist]
        errors = sorted(abs(g / t - 1) for g, t in pairs)
        assert errors[len(errors) // 2] < 0.01, (name, pairs)
        slack = sys.getswitchinterval() + 1e-3
        assert all(abs(g - t) < 0.01 * t + slack for g, t in pairs), \
            (name, pairs)


def test_iter_time_is_the_wall_time(traced):
    """The iteration's wall time holds its training step and its update,
    one after the other (the modelled max of stage times need not)."""
    _, hist, _ = traced
    for m in hist:
        assert m.iter_time >= max(m.times.t_tc, m.times.t_ta) + m.t_sync
        assert m.mteps == pytest.approx(m.edges / m.iter_time / 1e6)


def test_wait_batch_and_loop_spans_cover_the_steps(traced):
    _, _, events = traced
    covered = collections.defaultdict(float)
    for name in LOOP[1:]:
        for e in _of(events, name):
            covered[e[4]["iteration"]] += e[3] - e[2]
    for e in _of(events, "hyscale.step"):
        assert covered[e[4]["step_num"]] <= e[3] - e[2]
        assert covered[e[4]["step_num"]] >= 0.9 * (e[3] - e[2])


def test_span_times_its_block():
    with span("hyscale.test", iteration=1) as s:
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 1.0


def test_span_times_a_block_that_raises():
    s = span("hyscale.test")
    with pytest.raises(ValueError):
        with s:
            raise ValueError("boom")
    assert s.seconds >= 0


def test_step_is_a_span():
    with step("hyscale.step", step_num=3) as s:
        pass
    assert isinstance(s, span) and s.seconds >= 0
