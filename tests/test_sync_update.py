"""The sync + update layer as compiled programs: the trainer's jitted AdamW
step against the eager optimizer, the synchronizer's jitted weighted mean
against the eager formula, that neither builds a new program when the
mini-batch shares change, and the benchmark's use of the trainer (its
state replaced after construction, ``_apply_update`` wrapped, checkpoints
handed live arrays)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridConfig, HybridGNNTrainer, Synchronizer
from repro.core import protocol
from repro.graph import GNNConfig, init_params, make_dataset
from repro.optim import adamw
from repro.optim.optimizers import apply_updates

SAGE = GNNConfig(model="sage", layer_dims=(100, 16, 47), fanouts=(3, 2),
                 num_classes=47)
GAT = GNNConfig(model="gat", layer_dims=(100, 8, 8, 47), fanouts=(3, 3, 2),
                num_classes=47, heads=2)
LR = 1e-3
# the compiled step fuses the same float32 formula, so an element may round
# differently by an ulp or two (a fused multiply-add); where a moment
# cancels to near 0 that ulp of its inputs is a large share of it, so the
# gap is bounded by the leaf's scale, not each element's
RTOL = 1e-6


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.abs(want).max()) if want.size else 0.0
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=RTOL * scale,
                               err_msg=what)


@pytest.fixture(scope="module")
def dataset():
    return make_dataset("ogbn-products", scale=0.002, seed=0)


def _trainer(dataset, gnn, **kw):
    fields = dict(total_batch=32, n_accel=1, hybrid=True, use_drm=False,
                  tfp_depth=0, share_quantum=8, seed=3, lr=LR)
    fields.update(kw)
    return HybridGNNTrainer(dataset, gnn, HybridConfig(**fields))


@pytest.mark.parametrize("gnn", [SAGE, GAT], ids=["sage", "gat"])
def test_compiled_update_matches_eager_adamw(dataset, gnn):
    """Three steps of ``_apply_update`` against the eager
    ``adamw().update`` + ``apply_updates`` from the same start; each step
    consumes the params and state it was handed and leaves the returned
    ones, live, in ``tr.params`` / ``tr.opt_state``."""
    tr = _trainer(dataset, gnn)
    try:
        opt = adamw(LR)
        params = jax.tree.map(jnp.array, tr.params)      # copies
        state = jax.tree.map(jnp.array, tr.opt_state)
        for i in range(3):
            grads = jax.tree.map(
                lambda p, k=jax.random.PRNGKey(i): jax.random.normal(
                    k, p.shape, p.dtype), params)
            before = jax.tree.leaves((tr.params, tr.opt_state))
            tr._apply_update(grads)
            assert all(x.is_deleted() for x in before)
            assert not any(x.is_deleted()
                           for x in jax.tree.leaves((tr.params, tr.opt_state)))
            updates, state = opt.update(grads, state, params)
            params = apply_updates(params, updates)
        assert jax.tree.structure(tr.params) == jax.tree.structure(params)
        assert jax.tree.structure(tr.opt_state) \
            == jax.tree.structure(state)
        assert int(tr.opt_state["step"]) == 3
        for k in params:
            _close(tr.params[k], params[k], f"params {k}")
            _close(tr.opt_state["m"][k], state["m"][k], f"m {k}")
            _close(tr.opt_state["v"][k], state["v"][k], f"v {k}")
    finally:
        tr.close()


def _eager_mean(trees, weights):
    total = sum(weights)
    scaled = [jax.tree.map(lambda g: g * (w / total), t)
              for t, w in zip(trees, weights)]
    avg = scaled[0]
    for s in scaled[1:]:
        avg = jax.tree.map(lambda a, b: a + b, avg, s)
    return avg


@pytest.mark.parametrize("weights", [(96.0, 32.0, 8.0), (40.0, 0.0, 24.0)],
                         ids=["unequal", "dead-trainer"])
def test_weighted_mean_matches_eager_formula(weights):
    params = init_params(jax.random.PRNGKey(0), SAGE)
    trees = [jax.tree.map(lambda p, k=jax.random.PRNGKey(10 + i):
                          jax.random.normal(k, p.shape, p.dtype), params)
             for i in range(len(weights))]
    sync = Synchronizer(len(trees), device=jax.devices()[0])
    for i, (t, w) in enumerate(zip(trees, weights)):
        sync.submit(i, t, w)
    avg = sync.all_reduce()
    want = _eager_mean(trees, weights)
    assert jax.tree.structure(avg) == jax.tree.structure(want)
    for k in want:
        _close(avg[k], want[k], k)
        assert avg[k].dtype == want[k].dtype


def test_share_changes_build_no_new_program(dataset):
    """``train(6)``'s iterations, the CPU share moved between calls: after
    the first iteration neither the weighted mean nor the update step
    builds another program (the shares are traced, not constants)."""
    tr = _trainer(dataset, SAGE)
    try:
        a = tr.runtime.drm.assign
        a.cpu_batch, a.accel_batch = 8, 24
        tr.train(1)
        sync_programs = protocol._weighted_sum._cache_size()
        assert tr._update_step._cache_size() == 1
        shares = []
        for cpu in (16, 24, 8, 16, 24):
            a.cpu_batch, a.accel_batch = cpu, 32 - cpu
            shares.append(tr.train(1)[-1].assignment)
        assert shares == [(c, 32 - c) for c in (16, 24, 8, 16, 24)]
        assert protocol._weighted_sum._cache_size() == sync_programs
        assert tr._update_step._cache_size() == 1
    finally:
        tr.close()


def test_benchmark_usage_pattern(dataset):
    """As the benchmark's runner uses the trainer: params and optimizer
    state replaced after construction, ``_apply_update`` wrapped to read
    the first moment right after the first update, the wrapper deleted;
    a checkpoint callback is handed live arrays it can snapshot."""
    tr = _trainer(dataset, SAGE, ckpt_every=1)
    try:
        b1 = 0.9
        dev = next(iter(jax.tree.leaves(tr.params)[0].devices()))
        tr.params = jax.device_put(
            init_params(jax.random.PRNGKey(7), SAGE), dev)
        tr.opt_state = tr.optimizer.init(tr.params)
        seen, first_m = [], {}
        orig = tr._apply_update

        def update(grads):
            seen.append({k: np.array(v) for k, v in grads.items()})
            out = orig(grads)
            if not first_m:
                first_m.update({k: np.array(v)
                                for k, v in tr.opt_state["m"].items()})
            return out

        saved = []

        def ckpt(step, params, opt_state):
            leaves = jax.tree.leaves((params, opt_state))
            assert not any(x.is_deleted() for x in leaves)
            saved.append((step, jax.device_get((params, opt_state))))

        tr.set_checkpoint_callback(ckpt)
        tr._apply_update = update
        try:
            tr.train(1)
        finally:
            del tr._apply_update
        assert len(seen) == 1
        for k, g in seen[0].items():
            _close(first_m[k], (1 - b1) * g, k)
        tr.train(2)
        # one per iteration (each ``train`` call counts from 0), each the
        # state of its own step, not a later one
        assert [s for s, _ in saved] == [0, 0, 1]
        assert [int(o["step"]) for _, (_, o) in saved] == [1, 2, 3]
        np.testing.assert_array_equal(saved[0][1][1]["m"]["w1"],
                                      first_m["w1"])
        np.testing.assert_array_equal(saved[-1][1][0]["w1"],
                                      np.asarray(tr.params["w1"]))
    finally:
        tr.close()
