"""Whole runs of the harness at a tiny size on the CPU: the chip check is
skipped, everything else is the run the chip makes."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture(autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def bench(tmp_path):
    return str(tmp_path), tiny.make(str(tmp_path))


@pytest.mark.parametrize("cell", ["tiny-sage.hbm-cache", "tiny-gcn.hbm-cache"])
def test_reference_matches_trainer(bench, cell):
    root, b = bench
    rec = tiny.run(root, b, cell)
    checks = {name: value for name, value, _ in rec["checks"]}
    assert rec["correct"], rec["checks"]
    assert checks["x0_gap"] == 0 and checks["bad_edges"] == 0
    assert checks["loss_gap"] < 1e-5 and checks["grad_gap"] < 1e-5
    line = json.loads(rec["line"])
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"iter_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_traced_run_reports_per_layer_metrics(bench):
    root, b = bench
    rec = tiny.run(root, b, "tiny-sage.hbm-cache", trace=1)
    got = set(json.loads(rec["line"])["metrics"])
    # no TPU plane in a CPU trace: the device readers find nothing and
    # their metrics are left out, never reported as 0
    assert {"cpu_share", "transfer_ms", "cache_hit_rate",
            "compiles_in_window"} <= got
    assert not got & {"device_idle", "combine_roofline", "mfu", "iter_s"}


def test_checked_steps_are_one_pipelined_train_call(bench, monkeypatch):
    from bench.runners import gnn_hybrid
    from repro.core.hybrid import HybridGNNTrainer
    calls = []
    orig = HybridGNNTrainer.train

    def counted(self, n):
        calls.append(n)
        return orig(self, n)

    monkeypatch.setattr(HybridGNNTrainer, "train", counted)
    root, b = bench
    rec = tiny.run(root, b, "tiny-sage.hbm-cache")
    assert rec["correct"], rec["checks"]
    assert calls[0] == gnn_hybrid.CHECKED_STEPS


@pytest.mark.parametrize("attr", ["_dev_topology", "cache"])
def test_run_refuses_another_regime(bench, monkeypatch, attr):
    """A trainer that falls back to another sampler or cache tier than its
    traffic states (as a changed size limit in the program would make it)
    stops the run before the checked steps."""
    from repro.core.hybrid import HybridGNNTrainer
    orig = HybridGNNTrainer.__init__

    def fallen_back(self, *a, **kw):
        orig(self, *a, **kw)
        setattr(self, attr, None)

    monkeypatch.setattr(HybridGNNTrainer, "__init__", fallen_back)
    root, b = bench
    with pytest.raises(RuntimeError, match="not the regime"):
        tiny.run(root, b, "tiny-sage.hbm-cache")


def _state_unchanged(monkeypatch):
    from repro.core.hybrid import HybridGNNTrainer
    monkeypatch.setattr(HybridGNNTrainer, "_apply_update",
                        lambda self, grads: 0.0)


def _half_batch(monkeypatch):
    from repro.core import hybrid
    from repro.graph.models import forward

    def half_loss(params, cfg, batch, x0):
        logits = forward(params, cfg, batch, x0)
        h = logits.shape[0] // 2
        logp = jax.nn.log_softmax(logits[:h].astype(jnp.float32), axis=-1)
        lab = batch.labels[:h, None].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, lab, axis=-1).mean()
        return nll, (logits.argmax(-1) == batch.labels).mean()

    monkeypatch.setattr(hybrid, "loss_fn", half_loss)


def _one_trainer(monkeypatch):
    from repro.core.protocol import Synchronizer
    orig = Synchronizer.all_reduce

    def first_only(self):
        with self._cond:
            while self._done != self.n_trainers:
                self._cond.wait()
            grads = self._slots[0][0]
        orig(self)
        return jax.device_put(grads, self.device)

    monkeypatch.setattr(Synchronizer, "all_reduce", first_only)


def _altered_row(monkeypatch):
    from repro.core import hybrid
    orig = hybrid.assemble_features

    def altered(*a, **kw):
        return orig(*a, **kw).at[0].add(1.0)

    monkeypatch.setattr(hybrid, "assemble_features", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _one_trainer, _altered_row])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    root, b = bench
    fault(monkeypatch)
    rec = tiny.run(root, b, "tiny-sage.hbm-cache")
    assert not rec["correct"], rec["checks"]
    assert not json.loads(rec["line"])["correct"]


def test_new_cell_found_from_new_files_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files (and entries) are found by name; no harness file changes."""
    root = str(tmp_path)
    b = tiny.make(root, traffic_name="no-cache")
    cfg = json.load(open(os.path.join(b, "configs", "tiny-sage.json")))
    cfg.update(name="tiny-wide", layer_dims=[40, 32, 3], num_classes=3)
    json.dump(cfg, open(os.path.join(b, "configs", "tiny-wide.json"), "w"))
    trf = json.load(open(os.path.join(b, "traffic", "no-cache.json")))
    trf.update(name="half-cache")
    trf["hybrid"]["cache_fraction"] = 0.5
    json.dump(trf, open(os.path.join(b, "traffic", "half-cache.json"), "w"))
    with open(os.path.join(b, "metrics", "positions_per_iter.py"), "w") as f:
        f.write("def read(rec):\n"
                "    return rec['traffic']['positions'] / rec['iters']\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["workloads"].append({"name": "tiny-wide.half-cache",
                              "config": "tiny-wide", "traffic": "half-cache",
                              "chips": 1, "why": "new files alone"})
    spec["per_layer"].append({"name": "positions_per_iter", "unit": "rows",
                              "better": "lower", "source": "program_counter",
                              "layer": "load", "moves": "iter_s"})
    json.dump(spec, open(spec_path, "w"))
    rec = tiny.run(root, b, "tiny-wide.half-cache", trace=1)
    assert rec["correct"], rec["checks"]
    metrics = json.loads(rec["line"])["metrics"]
    assert metrics["positions_per_iter"]["value"] > 0
    assert 0 < metrics["cache_hit_rate"]["value"] < 100
