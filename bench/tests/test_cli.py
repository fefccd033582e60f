"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import BENCH

ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "sage-products.hbm-cache", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_platforms="cpu"):
    env = dict(os.environ, JAX_PLATFORMS=env_platforms)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was run" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
