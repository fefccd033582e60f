"""End-to-end integration tests for the hybrid training system:
protocol + TFP + DRM + synchronizer driving real GNN training, plus
fault tolerance (trainer failure mid-run) and checkpointing."""
import jax
import numpy as np
import pytest

from repro.core import HybridConfig, HybridGNNTrainer
from repro.graph import GNNConfig, make_dataset


def _dataset():
    return make_dataset("ogbn-products", scale=0.003, seed=0)


def _gcfg(**kw):
    base = dict(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                num_classes=47)
    base.update(kw)
    return GNNConfig(**base)


def test_full_system_trains(tmp_path):
    ds = _dataset()
    # learnable task: label = sign of the first input feature, so a few
    # SGD steps must reduce the loss (random labels would only test
    # memorization, too slow for a unit test)
    feats = ds.take_features(np.arange(ds.num_nodes))
    ds.labels = (feats[:, 0] > 0).astype(np.int32)
    hcfg = HybridConfig(total_batch=256, n_accel=2, hybrid=True,
                        use_drm=True, tfp_depth=2, lr=5e-3,
                        share_quantum=32, seed=0)
    tr = HybridGNNTrainer(ds, _gcfg(num_classes=2), hcfg)
    hist = tr.train(10)
    assert len(hist) == 10
    losses = [m.loss for m in hist]
    assert all(np.isfinite(losses))
    assert min(losses[5:]) < losses[0]
    assert tr.mean_mteps() > 0
    # the assignment always conserves the total batch
    for m in hist:
        cpu_b, accel_b = m.assignment
        assert cpu_b + accel_b * hcfg.n_accel == hcfg.total_batch


def test_ablation_modes_all_run():
    ds = _dataset()
    modes = dict(
        baseline=HybridConfig(total_batch=128, n_accel=2, hybrid=False,
                              use_drm=False, tfp_depth=0, seed=1),
        hybrid=HybridConfig(total_batch=128, n_accel=2, hybrid=True,
                            use_drm=False, tfp_depth=0, seed=1),
        drm=HybridConfig(total_batch=128, n_accel=2, hybrid=True,
                         use_drm=True, tfp_depth=0, seed=1),
        tfp=HybridConfig(total_batch=128, n_accel=2, hybrid=True,
                         use_drm=True, tfp_depth=2, seed=1),
    )
    for name, hcfg in modes.items():
        tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
        hist = tr.train(4)
        assert len(hist) == 4, name
        assert all(np.isfinite(m.loss) for m in hist), name


def test_trainer_failure_is_survived():
    """Kill accel0 at iteration 2: the system drops it, rebalances, and
    keeps training (straggler/fault mitigation via the DRM machinery)."""
    ds = _dataset()
    hcfg = HybridConfig(total_batch=128, n_accel=2, hybrid=True,
                        use_drm=True, tfp_depth=0, share_quantum=16, seed=2)
    tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
    tr.inject_failure("accel0", at_iteration=2)
    hist = tr.train(8)
    assert len(hist) == 8
    # iterations after the failure still make progress with finite loss
    assert all(np.isfinite(m.loss) for m in hist[3:])
    assert "accel0" in tr._failed
    # total work is still conserved across surviving trainers
    cpu_b, accel_b = hist[-1].assignment
    assert cpu_b + accel_b * tr.runtime.assignment.n_accel \
        == hcfg.total_batch


def test_checkpoint_callback_fires(tmp_path):
    ds = _dataset()
    hcfg = HybridConfig(total_batch=128, n_accel=1, tfp_depth=0,
                        ckpt_every=2, seed=3)
    tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
    saved = []
    tr.set_checkpoint_callback(lambda step, p, o: saved.append(step))
    tr.train(5)
    assert saved == [1, 3]


def test_gradient_compression_modes():
    ds = _dataset()
    for method in ("bf16", "int8"):
        hcfg = HybridConfig(total_batch=64, n_accel=1, tfp_depth=0,
                            compression=method, seed=4)
        tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
        hist = tr.train(3)
        assert all(np.isfinite(m.loss) for m in hist), method


def test_straggler_mitigation_shifts_share():
    """A persistently SLOW (not dead) trainer: the DRM engine must shift
    mini-batch share away from it — the paper's balance_work acting as
    continuous straggler mitigation.  Driven through the same Runtime
    path the trainer uses (deterministic synthetic stage times: the
    'accelerator' is 5x slower per row)."""
    from repro.core import StageTimes
    ds = _dataset()
    hcfg = HybridConfig(total_batch=256, n_accel=1, hybrid=True,
                        use_drm=True, tfp_depth=0, share_quantum=16,
                        drm_damping=0.5, seed=5)
    tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
    a0 = tr.runtime.assignment.accel_batch
    for _ in range(12):
        a = tr.runtime.assignment
        times = StageTimes(t_sa=0.0, t_sc=0.01, t_load=0.01, t_tran=0.001,
                           t_tc=a.cpu_batch * 1.0,
                           t_ta=a.accel_batch * 5.0)
        tr.runtime.end_iteration(times)
    assert tr.runtime.assignment.accel_batch < a0, \
        "DRM failed to shift work away from the straggler"
    assert tr.runtime.assignment.total_batch == 256


def test_inflight_batch_survives_share_requantize():
    """With TFP prefetch in flight the DRM can re-quantize a share to 0
    after a batch was sampled; the batch still belongs to the trainers it
    was sampled for (regression: the stage consumers used to intersect
    with the *current* assignment, which could come up empty and crash
    the synchronizer)."""
    ds = _dataset()
    hcfg = HybridConfig(total_batch=256, n_accel=2, hybrid=True,
                        use_drm=False, tfp_depth=0, seed=0,
                        cache_fraction=0.2)
    tr = HybridGNNTrainer(ds, _gcfg(), hcfg)
    item = tr._make_payload(0)
    assert set(item.payload["minibatch"]) == set()  # built lazily by stages
    tr._stage_sample(item)
    tr._stage_load(item)
    tr._stage_transfer(item)
    sampled_for = set(item.payload["minibatch"])
    assert "accel0" in sampled_for
    # the DRM flips everything onto the CPU trainer mid-pipeline
    tr.runtime.assignment.accel_batch = 0
    tr.runtime.assignment.cpu_batch = hcfg.total_batch
    grads, ttimes, metrics = tr._run_trainers(item)
    assert np.isfinite(metrics["loss"])
    assert grads is not None
    tr.loader.close()


def test_trainer_step_error_is_raised_not_hung():
    """A trainer whose gradient step raises (a device or compile error)
    hands the synchronizer nothing: train() raises that error instead of
    waiting forever for the missing gradients."""
    import threading
    ds = _dataset()
    tr = HybridGNNTrainer(ds, _gcfg(), HybridConfig(
        total_batch=256, n_accel=1, hybrid=True, use_drm=False,
        tfp_depth=0, share_quantum=32, seed=0))

    def broken(*args):
        raise RuntimeError("device step failed")

    tr._grad_jit = broken
    raised = []

    def run():
        try:
            tr.train(1)
        except RuntimeError as e:
            raised.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    assert [str(e) for e in raised] == ["device step failed"]
    tr.close()
