"""The program's own spans in a traced run.

The trainer marks every layer boundary with a span named
``hyscale.<layer>[.<part>]`` (``repro.core.spans``).  ``reduce`` turns the
events of a trace (``trace.load``) into:

* ``spans``: the seconds of each ``hyscale.*`` span name inside the
  window (the benchmark's ``bench.window`` span), summed over threads;
* ``idle_by_span``: the device's idle seconds in the window put down to
  what the train loop was doing: under each of its spans (``LOOP_SPANS``;
  where they nest, the innermost wins), or under none of them (``none``),
  averaged over devices.

The metric readers find the run's trace where the runner leaves it,
``<bench>/out/trace``, and read nothing (None) where the run was not
traced or the program emits no such span.  To print both for a traced
run, per iteration of the window:

    python3 bench/spans.py [<trace dir>]
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PREFIX = "hyscale."
# the train loop's spans: they tile its iterations (sync lies in train)
LOOP_SPANS = ("wait_batch", "train", "sync", "update", "drm")
STEP = PREFIX + "step"

Interval = Tuple[float, float]


def _loop_pieces(host: List[dict], w0: float, w1: float
                 ) -> List[Tuple[float, float, str]]:
    """``[w0, w1]`` cut into pieces, each labelled by the innermost train
    loop span over it (the shortest of those that cover it), or
    ``none``."""
    loop = []
    for h in host:
        label = h["name"][len(PREFIX):]
        if h["name"].startswith(PREFIX) and label in LOOP_SPANS:
            c = trace._clip(h["start_ns"], h["start_ns"] + h["dur_ns"],
                            w0, w1)
            if c is not None:
                loop.append((c[0], c[1], label))
    loop.sort()
    points = sorted({w0, w1, *(p for s, e, _ in loop for p in (s, e))})
    pieces: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(loop) and loop[k][0] <= a:
            active.append(loop[k])
            k += 1
        active = [x for x in active if x[1] > a]
        inner = min(active, key=lambda x: x[1] - x[0], default=None)
        pieces.append((a, b, inner[2] if inner else "none"))
    return pieces


def idle_by_span(busy: Dict[str, List[Interval]], host: List[dict],
                 w0: float, w1: float) -> Dict[str, float]:
    """Idle seconds of ``[w0, w1]`` under each loop span and under none,
    averaged over the devices of ``busy`` (each a list of op
    intervals)."""
    pieces = _loop_pieces(host, w0, w1)
    out = dict.fromkeys(LOOP_SPANS + ("none",), 0.0)
    for iv in busy.values():
        k = 0
        for g0, g1 in trace.idle_gaps(iv, w0, w1):
            while pieces[k][1] <= g0:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < g1:
                a, b, label = pieces[j]
                out[label] += (min(b, g1) - max(a, g0)) * 1e-9 / len(busy)
                j += 1
    return out


def reduce(events: List[dict]) -> Optional[dict]:
    """Window seconds, the iterations in it (its ``hyscale.step`` spans),
    ``spans`` and ``idle_by_span``; None without a window span."""
    host = [e for e in events if e["kind"] == "host"]
    win = [e for e in host if e["name"] == trace.WINDOW_SPAN]
    if not win:
        return None
    w = max(win, key=lambda e: e["dur_ns"])
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    spans: Dict[str, float] = {}
    inner = []
    for h in host:
        c = trace._clip(h["start_ns"], h["start_ns"] + h["dur_ns"], w0, w1)
        if c is None or not h["name"].startswith(PREFIX):
            continue
        inner.append(h)
        spans[h["name"]] = spans.get(h["name"], 0.0) + (c[1] - c[0]) * 1e-9
    busy: Dict[str, List[Interval]] = {}
    for e in events:
        if e["kind"] == "op":
            c = trace._clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                            w0, w1)
            if c is not None:
                busy.setdefault(e["plane"], []).append(c)
    return {"window_s": (w1 - w0) * 1e-9,
            "iterations": sum(h["name"] == STEP and h["start_ns"] >= w0
                              for h in inner),
            "spans": spans,
            "idle_by_span": idle_by_span(busy, inner, w0, w1)
            if busy else None}


def of_run(rec: dict, reader_file: str) -> Optional[dict]:
    """``reduce`` of the traced run's trace, which the runner leaves under
    ``out/trace`` of the bench directory that holds the reader; None where
    the run was not traced (or its trace held no device operation) or the
    program emits no span.  Kept in the record, which every reader of
    the run is handed, so the trace is read once."""
    if rec.get("trace") is None:
        return None
    if "program_spans" not in rec:
        bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(
            reader_file)))
        trace_dir = os.path.join(bench_dir, "out", "trace")
        s = (reduce(trace.load(trace_dir))
             if glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True) else None)
        rec["program_spans"] = s if s is not None and s["spans"] else None
    return rec["program_spans"]


def ms_per_iter(rec: dict, reader_file: str, name: str) -> Optional[float]:
    """Milliseconds of span ``name`` per iteration of the window."""
    s = of_run(rec, reader_file)
    if s is None or name not in s["spans"]:
        return None
    return 1e3 * s["spans"][name] / rec["iters"]


def main(argv: List[str]) -> int:
    trace_dir = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "out", "trace")
    s = reduce(trace.load(trace_dir))
    if s is None or not s["iterations"]:
        print(f"no window span with hyscale.step spans under {trace_dir}",
              file=sys.stderr)
        return 1
    n = s["iterations"]
    idle = s["idle_by_span"] or {}
    loop = sum(s["spans"].get(PREFIX + k, 0.0) for k in LOOP_SPANS
               if k != "sync")
    print(json.dumps({
        "window_s": s["window_s"], "iterations": n,
        "ms_per_iter": {k: 1e3 * v / n for k, v in sorted(s["spans"].items())},
        "loop_cover": loop / s["window_s"],
        "idle_by_span_s": idle,
        "idle_share_of_window": {k: v / s["window_s"]
                                 for k, v in idle.items()},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
