"""A tiny copy of the benchmark for the tests: the real harness, runners
and metric readers over small configurations that the CPU can run."""
from __future__ import annotations

import argparse
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONFIGS = {
    "tiny-sage": {"model": "sage", "layer_dims": [16, 32, 5],
                  "num_classes": 5},
    "tiny-gcn": {"model": "gcn", "layer_dims": [24, 32, 7],
                 "num_classes": 7},
}


def make(root: str, extra_cells=(), traffic_name: str = "hbm-cache"
         ) -> str:
    """A bench directory under ``root`` holding the real configuration,
    traffic, runner and metric files plus the tiny configurations, and a
    BENCHMARK.json beside it naming one cell per tiny configuration."""
    bench = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "metrics", "runners"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    for name in os.listdir(os.path.join(bench, "traffic")):
        path = os.path.join(bench, "traffic", name)
        trf = json.load(open(path))
        trf.update(warmup_max_iters=6)
        with open(path, "w") as f:
            json.dump(trf, f)
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "sage-products.json")))
    for name, over in TINY_CONFIGS.items():
        cfg = dict(base, name=name, num_nodes=3000, num_edges=60000,
                   batch=256, **over)
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    spec["workloads"] = [
        {"name": f"{c}.{traffic_name}", "config": c,
         "traffic": traffic_name, "chips": 1, "why": "tiny"}
        for c in TINY_CONFIGS] + list(extra_cells)
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return bench


def args(cell: str, seed: int = 2**31 + 7, seconds: float = 0.5,
         trace: int = 0) -> argparse.Namespace:
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)


def run(root: str, bench: str, cell: str, **kw) -> dict:
    from bench import run as bench_run
    return bench_run.run_cell(args(cell, **kw), require_tpu=False,
                              bench_dir=bench,
                              spec_path=os.path.join(root, "BENCHMARK.json"))
