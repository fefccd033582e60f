"""The program's spans in a trace (``bench/spans.py``) and the readers of
the metrics that read them."""
import os
import shutil

import pytest

from bench import harness, spans, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("batch_wait_ms", "sync_ms", "gather_ms", "schedule_ms",
           "dispatch_ms", "idle_wait_batch")


def _op(start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "kind": "op", "name": "fusion.1:fusion",
            "start_ns": start, "dur_ns": dur}


def _host(name, start, dur):
    return {"plane": "/host:CPU", "kind": "host", "name": name,
            "start_ns": start, "dur_ns": dur}


def _iteration(t, wait=400, train=300, sync=50, update=200, drm=100):
    """One train loop iteration starting at ``t`` on the loop thread:
    step > wait_batch, train (> sync at its end), update, drm."""
    end = t + wait + train + update + drm
    return [_host("hyscale.step", t, end - t),
            _host("hyscale.wait_batch", t, wait),
            _host("hyscale.train", t + wait, train),
            _host("hyscale.sync", t + wait + train - sync, sync),
            _host("hyscale.update", t + wait + train, update),
            _host("hyscale.drm", t + wait + train + update, drm)]


def _events():
    """A window of two iterations (0..1000, 1000..2000) inside a window
    span of 0..2100; the device runs the training step (200 ns into
    train) and the update, and once during the wait, while the transfer
    stage dispatches the combine on another thread."""
    ev = [_host(trace.WINDOW_SPAN, 0, 2100)]
    for t in (0, 1000):
        ev += _iteration(t)
        ev += [_op(t + 400, 200), _op(t + 700, 150)]
    ev += [_host("hyscale.transfer", 100, 250),
           _host("hyscale.transfer.dispatch", 150, 100),
           _op(200, 50),
           _host("PjitFunction(_assemble_tiled_device)", 150, 100)]
    return ev


def test_spans_sum_over_threads_inside_the_window():
    ev = _events() + [_host("hyscale.load.gather", 50, 100),
                      _host("hyscale.load.gather", 60, 100),   # 2nd thread
                      _host("hyscale.load.gather", 2050, 100),  # half out
                      _host("not.ours", 0, 500)]
    s = spans.reduce(ev)
    assert s["window_s"] == pytest.approx(2100e-9)
    assert s["iterations"] == 2
    assert s["spans"]["hyscale.wait_batch"] == pytest.approx(800e-9)
    assert s["spans"]["hyscale.sync"] == pytest.approx(100e-9)
    assert s["spans"]["hyscale.load.gather"] == pytest.approx(250e-9)
    assert s["spans"]["hyscale.transfer.dispatch"] == pytest.approx(100e-9)
    assert "not.ours" not in s["spans"]


def test_idle_is_put_down_to_the_innermost_loop_span():
    s = spans.reduce(_events())
    idle = s["idle_by_span"]
    # wait 0..400 holds one 50-ns op (under the other thread's dispatch):
    # the gap under the dispatch is still the loop's wait for a batch
    assert idle["wait_batch"] == pytest.approx(2 * 400e-9 - 50e-9)
    # train 400..700: the step runs 400..600, sync 650..700 is idle
    assert idle["train"] == pytest.approx(2 * 50e-9)
    assert idle["sync"] == pytest.approx(2 * 50e-9)
    assert idle["update"] == pytest.approx(2 * 50e-9)
    assert idle["drm"] == pytest.approx(2 * 100e-9)
    assert idle["none"] == pytest.approx(100e-9)   # 2000..2100
    busy = 50 + 2 * (200 + 150)
    assert sum(idle.values()) == pytest.approx((2100 - busy) * 1e-9)


def test_idle_by_span_averages_over_devices():
    ev = [_host(trace.WINDOW_SPAN, 0, 100),
          _host("hyscale.wait_batch", 0, 100),
          _op(0, 100), _op(0, 50, plane="/device:TPU:1")]
    assert spans.reduce(ev)["idle_by_span"]["wait_batch"] == \
        pytest.approx(25e-9)


def test_a_trace_without_program_spans():
    ev = [_host(trace.WINDOW_SPAN, 0, 100), _op(10, 20),
          _host("PjitFunction(_grad)", 0, 50)]
    s = spans.reduce(ev)
    assert s["spans"] == {} and s["iterations"] == 0
    assert s["idle_by_span"]["none"] == pytest.approx(80e-9)
    assert spans.reduce([_op(0, 10)]) is None
    assert spans.reduce([_host(trace.WINDOW_SPAN, 0, 100)]
                        )["idle_by_span"] is None


def _traced_events():
    return _events() + [_host("hyscale.load.gather", 0, 300),
                        _host("hyscale.transfer.schedule", 0, 40)]


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """The metric readers in a bench directory of their own, whose
    ``out/trace`` holds one (stand-in) trace file; ``trace.load`` hands
    back the events the test sets."""
    b = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), b / "metrics")
    (b / "out" / "trace").mkdir(parents=True)
    (b / "out" / "trace" / "t.xplane.pb").write_bytes(b"")
    events = {"value": _traced_events()}
    monkeypatch.setattr(trace, "load", lambda d: events["value"])
    return str(b), events


def _read(bench_dir, name, rec):
    mod = harness._module(os.path.join(bench_dir, "metrics", name + ".py"),
                          "test_metric_" + name)
    return mod.read(rec)


# per iteration of the two in _traced_events (ms), or % of its window
EXPECTED = {"batch_wait_ms": 1e3 * 800e-9 / 2,
            "sync_ms": 1e3 * 100e-9 / 2,
            "gather_ms": 1e3 * 300e-9 / 2,
            "schedule_ms": 1e3 * 40e-9 / 2,
            "dispatch_ms": 1e3 * 100e-9 / 2,
            "idle_wait_batch": 100 * (800 - 50) / 2100}


@pytest.mark.parametrize("name", READERS)
def test_reader_with_a_trace(bench_copy, name):
    bench_dir, _ = bench_copy
    rec = {"trace": {"window_s": 2100e-9}, "iters": 2}
    assert _read(bench_dir, name, rec) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_trace(bench_copy, name):
    bench_dir, _ = bench_copy
    assert _read(bench_dir, name, {"trace": None, "iters": 2}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_program_without_spans(bench_copy, name):
    """The program before it had spans: a trace, but nothing to read."""
    bench_dir, events = bench_copy
    events["value"] = [e for e in _traced_events()
                       if not e["name"].startswith(spans.PREFIX)]
    rec = {"trace": {"window_s": 2100e-9}, "iters": 2}
    assert _read(bench_dir, name, rec) is None
