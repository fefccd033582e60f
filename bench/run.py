"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, runner and metrics are found by
the names ``BENCHMARK.json`` gives them (``harness.py``).  With
``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  The numbers compared for ``correct`` are
printed beside their limits as the last lines of standard error and
under ``checks`` in the result line.

Without a TPU, or with fewer TPUs than the cell asks for, it exits with
code 3 and prints no result: it never falls back to the CPU.  JAX's
persistent compilation cache is kept in ``bench/.jax_cache`` inside the
checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
HANG_SECONDS = 1150     # a wedged trainer thread dumps every stack and exits


@dataclasses.dataclass
class Context:
    cell: Any
    args: argparse.Namespace
    t_start: float
    out_dir: str
    require_tpu: bool


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def set_environment() -> None:
    """Before JAX is imported: the host CPU backend beside the TPU (the CPU
    trainer runs there), the compile cache inside the checkout, and the
    TPU runtime's logs off."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH_DIR,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def run_cell(args: argparse.Namespace, require_tpu: bool = True,
             bench_dir: str = BENCH_DIR, spec_path: Optional[str] = None
             ) -> Dict[str, Any]:
    """Run the cell; returns its record with ``metrics`` and ``line`` (the
    result line) added.  ``require_tpu=False`` drives the same run on
    whatever devices JAX has (the tests, on the CPU)."""
    from bench import harness
    cell = harness.load_cell(args.workload, bench_dir, spec_path)
    import jax
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"setup: compile cache {enable_compile_cache()}", flush=True)
    out_dir = os.path.join(bench_dir, "out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell=cell, args=args, t_start=T_START, out_dir=out_dir,
                  require_tpu=require_tpu)
    record = harness.runner(cell).run(ctx)
    record["metrics"] = harness.read_metrics(cell, record, bool(args.trace))
    record["line"] = harness.result_line(record, record["metrics"],
                                         record["checks"])
    return record


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    set_environment()
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if len(tpus) < cell.chips:
        print(f"bench/run.py: {args.workload} needs {cell.chips} TPU(s), "
              f"JAX found {len(tpus)} (default backend "
              f"{jax.default_backend()}); nothing was run", file=sys.stderr)
        return 3
    faulthandler.dump_traceback_later(HANG_SECONDS, exit=True)
    record = run_cell(args)
    faulthandler.cancel_dump_traceback_later()
    harness.print_checks(record["checks"])
    print(record["line"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
