"""Launch-layer coverage: run the dry-run machinery end-to-end on a SMALL
forced-device mesh in a subprocess (the 512-device production sweep lives
in launch/dryrun.py; tests must not pollute this process's jax device
count, so we fork)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch.dryrun import run_cell
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
out = []
for arch, shape, policy in [("smollm-135m", "train_4k", "tp2d"),
                            ("smollm-135m", "decode_32k", "serve2d"),
                            ("rwkv6-1.6b", "prefill_32k", "tp2d")]:
    r = run_cell(arch, shape, mesh, verbose=False, policy=policy)
    out.append({k: r[k] for k in ("arch", "shape", "status")}
               | {"frac": r.get("roofline", {}).get("roofline_fraction"),
                  "coll": r.get("collectives", {}).get("total")})
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_cells_compile_on_small_mesh():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    results = json.loads(line[len("RESULT:"):])
    assert len(results) == 3
    for r in results:
        assert r["status"] == "ok", r
        assert r["frac"] is not None
    # the partitioned programs actually contain collectives
    assert any((r["coll"] or 0) > 0 for r in results)
