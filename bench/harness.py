"""Registry and result line of the benchmark.

Everything that belongs to one configuration, traffic mix, runner or
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``: the configuration's sizes, its source, what
  was cut, the precision it states, the limits of its comparison, and
  ``kind``, the runner that drives it;
* ``traffic/<traffic>.json``: the trainer settings of the mix and its
  warm-up;
* ``runners/<kind>.py``: ``run(ctx) -> record``, one run of a cell;
* ``metrics/<metric>.py``: ``read(record) -> float | None``, one metric
  from the run's record (None: nothing to read, and the metric is left
  out of the line).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              spec_path: Optional[str] = None) -> Cell:
    spec = _load_json(spec_path or os.path.join(os.path.dirname(bench_dir),
                                                "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(bench_dir, "configs",
                                       w["config"] + ".json")),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def runner(cell: Cell) -> ModuleType:
    kind = cell.config["kind"]
    return _module(os.path.join(cell.bench_dir, "runners", kind + ".py"),
                   f"bench_runner_{kind}")


def read_metrics(cell: Cell, record: Dict[str, Any], traced: bool
                 ) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics (untraced run) or its per-layer
    metrics (traced run), each from its reader."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        mod = _module(os.path.join(cell.bench_dir, "metrics",
                                   m["name"] + ".py"),
                      "bench_metric_" + m["name"].replace(".", "_")
                      .replace("-", "_"))
        value = mod.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(record: Dict[str, Any], metrics: Dict[str, Any],
                checks: List[Any]) -> str:
    out: Dict[str, Any] = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": record["device"],
    }
    if record.get("breakdown") is not None:
        out["breakdown"] = record["breakdown"]
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    return json.dumps(out)


def print_checks(checks: List[Any]) -> None:
    for name, value, limit in checks:
        ok = "ok" if value <= limit else "FAILED"
        print(f"check {name} = {value!r} (limit {limit!r}) {ok}",
              file=sys.stderr, flush=True)
