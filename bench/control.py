"""Readings that the limits of a GNN training cell are set from.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] \
        --control-seeds <n> [<n> ...] --out <file.json>

For every seed it builds the cell's data and trainer as a run does,
drives the trainer through the checked steps and reads the compared
numbers of the sound program (the lower readings).  For each control
seed it also reads, on the same data:

* ``control_bf16_transfer``: the program with its own lower-precision
  path switched on (``feature_dtype="bfloat16"``);
* ``control_bf16_reference``: the reference in the program's place,
  computed in bfloat16 (weights, features, optimizer state), the step
  below the float32 the configuration states;
* faults planted in the reference put in the program's place:
  ``fault_half_batch`` (each trainer's first half of targets, the mean
  over them), ``fault_one_trainer`` (the exchange between the CPU and the
  accelerator trainer left out: one trainer's mean gradient),
  ``fault_altered_row`` (one layer-0 row altered where it is produced),
  ``fault_state_unchanged`` (the step returns its state unchanged).

The benchmark's own runs never run this; it writes one JSON file of
readings, and the limits in ``configs/<config>.json`` are set from them.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def half_block(block: dict, fanouts: Sequence[int]) -> dict:
    """The sampled block of the first half of ``block``'s targets, in the
    same regular layout."""
    targets = np.asarray(block["targets"])
    h = max(1, targets.shape[0] // 2)
    keep = np.arange(h)
    size = targets.shape[0]
    hops = []
    for src, f in zip(block["hop_src"], fanouts):
        entries = (keep[:, None] * int(f) + np.arange(int(f))).reshape(-1)
        hops.append(np.asarray(src)[entries])
        keep = np.concatenate([keep, size + entries])
        size += np.asarray(src).shape[0]
    return dict(block, targets=targets[:h], hop_src=hops, share=h)


def readings(cell, seed: int, control: bool) -> Dict[str, Dict[str, float]]:
    import jax.numpy as jnp
    from bench.runners import gnn_hybrid as g
    cfg, trf = cell.config, cell.traffic
    data = g.make_data(cfg, seed)

    def program(overrides=None) -> dict:
        tr, params0 = g.build_trainer(cfg, trf, data, seed, overrides)
        try:
            prog = g.checked_steps(tr, params0)
        finally:
            tr.close()
        del tr
        gc.collect()
        return prog

    prog = program()
    ref = g.run_reference(cfg, prog, data)
    out = {"program": g.compared(cfg, prog, data, ref)}
    if not control:
        return out

    prog_a = program({"feature_dtype": "bfloat16"})
    out["control_bf16_transfer"] = g.compared(
        cfg, prog_a, data, g.run_reference(cfg, prog_a, data))

    def in_place(result: dict, steps=None) -> dict:
        return dict(result, params0=prog["params0"],
                    steps=steps if steps is not None else prog["steps"])

    bf16 = g.run_reference(cfg, prog, data, dtype=jnp.bfloat16,
                           precision="default")
    bf16_steps = [{n: dict(b, x0=_rows(data, b).astype(jnp.bfloat16))
                   for n, b in blocks.items()} for blocks in prog["steps"]]
    out["control_bf16_reference"] = g.compared(
        cfg, in_place(bf16, bf16_steps), data, ref)

    fan = cfg["fanouts"]
    half_steps = [{n: half_block(b, fan) for n, b in blocks.items()}
                  for blocks in prog["steps"]]
    half = g.run_reference(cfg, dict(prog, steps=half_steps), data)
    out["fault_half_batch"] = g.compared(cfg, in_place(half), data, ref)

    one_steps = [{n: b for n, b in blocks.items() if n != "cpu"} or blocks
                 for blocks in prog["steps"]]
    one = g.run_reference(cfg, dict(prog, steps=one_steps), data)
    out["fault_one_trainer"] = g.compared(cfg, in_place(one), data, ref)

    def alter(x0):
        x0 = np.array(x0)
        x0[0] += 1.0
        return x0

    altered = g.run_reference(cfg, prog, data, x0_of=alter)
    alt_steps = [{n: dict(b, x0=alter(b["x0"])) for n, b in blocks.items()}
                 for blocks in prog["steps"]]
    out["fault_altered_row"] = g.compared(
        cfg, in_place(altered, alt_steps), data, ref)

    still = {"losses": ref["losses"][:1] * len(ref["losses"]),
             "first_grad": {k: np.zeros_like(v)
                            for k, v in ref["first_grad"].items()},
             "params": dict(prog["params0"])}
    out["fault_state_unchanged"] = g.compared(cfg, in_place(still), data,
                                              ref)
    return out


def _rows(data: dict, block: dict) -> np.ndarray:
    from bench import reference
    ids, _ = reference.block_arrays(block["targets"], block["hop_src"],
                                    data["graph"].degrees())
    return data["x"][ids]


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(BENCH_DIR))
    from bench import run as bench_run
    bench_run.set_environment()
    from bench import harness
    import jax
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        print("control.py: JAX found no TPU; readings are taken on the chip",
              file=sys.stderr)
        return 3
    cell = harness.load_cell(args.workload)
    results = {}
    for seed in list(dict.fromkeys(args.seeds + args.control_seeds)):
        t0 = time.perf_counter()
        results[str(seed)] = readings(cell, seed,
                                      seed in args.control_seeds)
        print(f"seed {seed} ({time.perf_counter() - t0:.1f} s): "
              f"{json.dumps(results[str(seed)])}", flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "readings": results}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
