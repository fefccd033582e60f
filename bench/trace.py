"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three kinds of events, as plain dicts (the form of the recorded excerpt
in ``tests/data``):

* device operations: every event on the "XLA Ops" line of a
  ``/device:TPU:<n>`` plane;
* device modules: every event on its "XLA Modules" line, one per run of
  a compiled program (``jit_<name>(<id>)``);
* host spans: every event on the host plane's lines of the Python
  threads (the benchmark's own ``TraceAnnotation`` spans and JAX's
  dispatch spans such as ``PjitFunction(<name>)``).  The profiler names a
  thread's line by the thread's name, which Python threads inherit from
  the process: ``python3`` where the program was started as ``python3``,
  ``python`` where it was started as ``python``.

``reduce`` turns them into the numbers the metric readers use: the traced
window (the benchmark's ``bench.window`` span), the seconds in which any
operation ran on each device inside it (the union of their intervals,
averaged over devices), device seconds per module and per operation, and
the longest idle gaps, each named by the host span that covers most of it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


def main_thread_name() -> str:
    """The name this process's main thread has (and its Python threads
    with it), as the profiler names their lines."""
    with open("/proc/self/comm") as f:
        return f.read().strip()


def load(trace_dir: str, host_line: Optional[str] = None) -> List[dict]:
    """Device-op and host-span events of the newest trace under
    ``trace_dir``; the host spans are those of the lines named
    ``host_line``, by default this process's main thread's name (the
    trace is read by the process that recorded it)."""
    from jax.profiler import ProfileData
    if host_line is None:
        host_line = main_thread_name()
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out: List[dict] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            if host and line.name != host_line:
                continue
            for ev in line.events:
                kind = ("host" if host else
                        "op" if line.name == "XLA Ops" else "module")
                out.append({"plane": plane.name, "kind": kind,
                            "name": op_name(ev.name) if kind == "op"
                            else ev.name,
                            "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def op_name(hlo: str) -> str:
    """``name:opcode`` of an op event named by its HLO text
    (``%fusion.3 = f32[8,128]{1,0} fusion(...), ...``); other names as
    they are."""
    m = _HLO.match(hlo)
    return f"{m.group(1)}:{m.group(2)}" if m else hlo


_HLO = re.compile(r"%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in _merged(intervals))


def _merged(intervals: Iterable[Tuple[float, float]]
            ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, w0: float, w1: float
          ) -> Optional[Tuple[float, float]]:
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def idle_gaps(busy: List[Tuple[float, float]], w0: float, w1: float
              ) -> List[Tuple[float, float]]:
    """Intervals of ``[w0, w1]`` in which nothing of ``busy`` ran."""
    gaps, t = [], w0
    for s, e in _merged(busy):
        if s > t:
            gaps.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        gaps.append((t, w1))
    return [g for g in gaps if g[1] > g[0]]


def _gap_name(gap: Tuple[float, float], host: List[dict]) -> str:
    """The host span that covers most of ``gap`` (the innermost of equal
    cover); the benchmark's window span only where nothing else does."""
    best, best_key = "host: nothing traced", None
    for h in host:
        c = _clip(h["start_ns"], h["start_ns"] + h["dur_ns"], *gap)
        if c is None:
            continue
        key = (h["name"] != WINDOW_SPAN, c[1] - c[0], -h["dur_ns"])
        if best_key is None or key > best_key:
            best, best_key = "host: " + h["name"], key
    return best


def reduce(events: List[dict], top: int = 10) -> Optional[dict]:
    """Window, busy seconds, per-module and per-op device seconds and the
    longest idle gaps; None when the trace holds no window span or no
    device operation inside it."""
    host = [e for e in events if e["kind"] == "host"]
    win = [e for e in host if e["name"] == WINDOW_SPAN]
    if not win:
        return None
    w = max(win, key=lambda e: e["dur_ns"])
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    by_dev: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    modules: Dict[str, float] = collections.defaultdict(float)
    ops: Dict[str, float] = collections.defaultdict(float)
    runs: Dict[str, List[Tuple[float, float, str]]] = \
        collections.defaultdict(list)
    for e in events:
        if e["kind"] == "module":
            name = _MODULE_ID.sub("", e["name"])
            runs[e["plane"]].append((e["start_ns"],
                                     e["start_ns"] + e["dur_ns"], name))
            c = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], w0, w1)
            if c is not None:
                modules[name] += (c[1] - c[0]) * 1e-9
    starts = {p: [r[0] for r in sorted(rs)] for p, rs in runs.items()}
    runs = {p: sorted(rs) for p, rs in runs.items()}
    for e in events:
        if e["kind"] != "op":
            continue
        c = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], w0, w1)
        if c is None:
            continue
        by_dev[e["plane"]].append(c)
        i = bisect.bisect_right(starts.get(e["plane"], []), e["start_ns"]) - 1
        rs = runs.get(e["plane"], [])
        module = rs[i][2] if i >= 0 and e["start_ns"] < rs[i][1] else "?"
        ops[f"{module}/{e['name']}"] += (c[1] - c[0]) * 1e-9
    if not by_dev:
        return None
    busy_ns = sum(union_ns(iv) for iv in by_dev.values()) / len(by_dev)
    inner_host = [h for h in host if _clip(h["start_ns"],
                                           h["start_ns"] + h["dur_ns"],
                                           w0, w1)]
    gaps = sorted((g for iv in by_dev.values()
                   for g in idle_gaps(iv, w0, w1)),
                  key=lambda g: g[1] - g[0], reverse=True)
    named = [[_gap_name(g, inner_host), (g[1] - g[0]) * 1e-9]
             for g in gaps[:top]]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "devices": len(by_dev),
        "modules": dict(modules),
        "ops": dict(ops),
        "top_ops": [[k, v] for k, v in sorted(ops.items(),
                                              key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }


def module_seconds(summary: dict, patterns: Iterable[str]) -> float:
    """Device seconds of the modules whose name holds any of
    ``patterns``."""
    pats = tuple(patterns)
    return sum(s for m, s in summary["modules"].items()
               if any(p in m for p in pats))
