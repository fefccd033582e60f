"""The trace reduction: busy union, idle share, per-module time, gaps."""
import gzip
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _op(start, dur, name="fusion.1:fusion", plane="/device:TPU:0"):
    return {"plane": plane, "kind": "op", "name": name, "start_ns": start,
            "dur_ns": dur}


def _module(start, dur, name, plane="/device:TPU:0"):
    return {"plane": plane, "kind": "module", "name": name,
            "start_ns": start, "dur_ns": dur}


def _host(name, start, dur):
    return {"plane": "/host:CPU", "kind": "host", "name": name,
            "start_ns": start, "dur_ns": dur}


def test_op_names_from_hlo_text():
    assert trace.op_name("%copy.2 = f32[9,100]{1,0:T(8,128)} copy(f32[9,100]"
                         "{0,1:T(8,128)} %cache.1), sharding={replicated}") \
        == "copy.2:copy"
    assert trace.op_name("%c.1 = f32[8,128]{1,0} custom-call(s32[1] %a)") \
        == "c.1:custom-call"


def test_union_merges_overlaps():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (30, 31)]) == 26
    assert trace.union_ns([]) == 0


def test_idle_gaps_inside_window():
    assert trace.idle_gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]


def test_reduce_clips_to_window_and_names_gaps():
    events = [_host(trace.WINDOW_SPAN, 100, 1000),
              _host("PjitFunction(_grad)", 400, 300),
              _op(50, 100),                      # half inside the window
              _module(190, 120, "jit__assemble_tiled_device(7)"),
              _op(200, 100, name="custom-call.1:custom-call"),
              _op(250, 100),                     # overlaps the previous op
              _op(900, 100),
              _op(1200, 50)]                     # after the window
    s = trace.reduce(events)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx((50 + 150 + 100) * 1e-9)
    assert trace.module_seconds(s, ["_assemble_tiled_device"]) == \
        pytest.approx(120e-9)
    assert s["ops"]["jit__assemble_tiled_device/custom-call.1:custom-call"] \
        == pytest.approx(100e-9)
    assert s["ops"]["jit__assemble_tiled_device/fusion.1:fusion"] == \
        pytest.approx(100e-9)
    assert s["ops"]["?/fusion.1:fusion"] == pytest.approx(150e-9)
    # longest gap 350..900 is mostly under the dispatch span
    assert s["idle_gaps"][0] == ["host: PjitFunction(_grad)",
                                 pytest.approx(550e-9)]


def test_reduce_averages_busy_over_devices():
    events = [_host(trace.WINDOW_SPAN, 0, 100), _op(0, 100),
              _op(0, 50, plane="/device:TPU:1")]
    assert trace.reduce(events)["busy_s"] == pytest.approx(75e-9)


def test_reduce_without_device_ops_gives_nothing():
    assert trace.reduce([_host(trace.WINDOW_SPAN, 0, 100)]) is None
    assert trace.reduce([_op(0, 10)]) is None


def _sweep_busy(events, w0, w1):
    """Busy time by a sweep over start/end points (independent of
    ``union_ns``)."""
    points = []
    for e in events:
        s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if t > s:
            points += [(s, 1), (t, -1)]
    busy, active, last = 0.0, 0, None
    for x, d in sorted(points, key=lambda p: (p[0], -p[1])):
        if active > 0:
            busy += x - last
        active += d
        last = x
    return busy


def test_recorded_excerpt_of_a_chip_trace():
    """One second of a traced sage-products.hbm-cache window on a TPU v5e:
    2,074 op events, 377 module runs and 3,045 host spans."""
    path = os.path.join(DATA, "trace_excerpt_hbm_cache.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    s = trace.reduce(events)
    win = [e for e in events if e["name"] == trace.WINDOW_SPAN][0]
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    ops = [e for e in events if e["kind"] == "op"]
    assert s["window_s"] == pytest.approx(1.0)
    assert s["busy_s"] == pytest.approx(_sweep_busy(ops, w0, w1) * 1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    combine = sum(min(e["start_ns"] + e["dur_ns"], w1)
                  - max(e["start_ns"], w0) for e in events
                  if e["kind"] == "module"
                  and e["name"].startswith("jit__assemble_tiled_device(")
                  and e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0)
    assert combine > 0
    assert trace.module_seconds(s, ["_assemble_tiled_device"]) == \
        pytest.approx(combine * 1e-9)
    assert sum(s["ops"].values()) == pytest.approx(
        sum(min(e["start_ns"] + e["dur_ns"], w1) - max(e["start_ns"], w0)
            for e in ops if e["start_ns"] < w1
            and e["start_ns"] + e["dur_ns"] > w0) * 1e-9)
    gaps = [g for _, g in s["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(name.startswith("host: ") for name, _ in s["idle_gaps"])


@pytest.mark.parametrize("thread_name", ["python3", "bench-main"])
def test_load_reads_the_lines_of_the_process_threads(tmp_path, thread_name):
    """The profiler names a host line by its thread's name, which follows
    the name the process was started under: the window span is found
    whatever that name is."""
    script = textwrap.dedent(f"""
        with open("/proc/self/comm", "w") as f:
            f.write({thread_name!r})
        import jax, jax.numpy as jnp
        from bench import trace
        jax.profiler.start_trace({str(tmp_path)!r})
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            jnp.ones(8).block_until_ready()
        jax.profiler.stop_trace()
        events = trace.load({str(tmp_path)!r})
        print(trace.main_thread_name(),
              sum(e["name"] == trace.WINDOW_SPAN for e in events))
        """)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == [thread_name, "1"]
