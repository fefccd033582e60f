"""The control and the planted faults come out not correct under the
configuration's limits, and the sound program comes out correct (tiny
size, CPU).  On the chip the same readings are taken at the cells' own
sizes by ``python bench/control.py``."""
import jax
import pytest

from bench import compare, control, harness
from bench.tests import tiny


@pytest.fixture(autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("cell", ["tiny-sage.hbm-cache", "tiny-gcn.hbm-cache"])
def test_control_and_faults_fail_the_limits(tmp_path, cell):
    root = str(tmp_path)
    b = tiny.make(root)
    c = harness.load_cell(cell, b, str(tmp_path / "BENCHMARK.json"))
    got = control.readings(c, 2**32 + 5, control=True)
    limits = c.config["limits"]
    assert compare.verdict(got["program"], limits)[0], got["program"]
    for name, values in got.items():
        if name != "program":
            assert not compare.verdict(values, limits)[0], (name, values)
