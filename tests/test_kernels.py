"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle,
over shapes × dtypes, forward and backward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [
    # (n_dst, fanout, f_in, f_out)
    (8, 3, 16, 8),
    (64, 5, 100, 47),       # ogbn-products dims
    (128, 25, 128, 256),    # papers100M layer-1 dims
    (17, 3, 33, 9),         # ragged/padded path
    (256, 10, 256, 172),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _inputs(d, fan, f, o, dtype, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32).astype(dtype)
    return dict(x_self=mk(d, f), x_nbr=mk(d * fan, f), w_edge=mk(d * fan),
                self_scale=mk(d), w_self=mk(f, o) * 0.1, w_agg=mk(f, o) * 0.1,
                bias=mk(o))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_segment_sum_kernel(shape, dtype):
    d, fan, f, o = shape
    i = _inputs(d, fan, f, o, dtype)
    got = ops.segment_weighted_sum_regular(i["x_nbr"], i["w_edge"], fan)
    want = ref.segment_weighted_sum_regular(i["x_nbr"], i["w_edge"], fan)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_update_kernel(shape, dtype):
    d, fan, f, o = shape
    i = _inputs(d, fan, f, o, dtype)
    got = ops.fused_gnn_update(i["x_self"], i["x_nbr"], i["w_edge"],
                               i["self_scale"], i["w_self"], i["w_agg"],
                               i["bias"], fan)
    want = ref.fused_gnn_update(i["x_self"], i["x_nbr"], i["w_edge"],
                                i["self_scale"], i["w_self"], i["w_agg"],
                                i["bias"], fan)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_fused_kernel_grads_match_oracle(shape):
    d, fan, f, o = shape
    i = _inputs(d, fan, f, o, jnp.float32)
    args = (i["x_self"], i["x_nbr"], i["w_edge"], i["self_scale"],
            i["w_self"], i["w_agg"], i["bias"])

    gk = jax.grad(lambda a: ops.fused_gnn_update(*a, fan).sum())(args)
    gr = jax.grad(lambda a: ref.fused_gnn_update(*a, fanout=fan).sum())(args)
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_segment_sum_grads():
    d, fan, f = 16, 4, 24
    i = _inputs(d, fan, f, 8, jnp.float32)
    gk = jax.grad(lambda a: ops.segment_weighted_sum_regular(
        a[0], a[1], fan).sum())((i["x_nbr"], i["w_edge"]))
    gr = jax.grad(lambda a: ref.segment_weighted_sum_regular(
        a[0], a[1], fan).sum())((i["x_nbr"], i["w_edge"]))
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------- cache scatter update (refresh path)

UPDATE_CASES = [
    # (cache_rows, feat_dim, n_updates)
    (8, 16, 3),
    (64, 128, 12),       # aligned dims
    (17, 33, 9),         # ragged rows/cols (padded F path)
    (300, 100, 40),
    (5, 7, 1),
]


@pytest.mark.parametrize("case", UPDATE_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("use_pallas", [False, True])
def test_cache_update_matches_oracle(case, dtype, use_pallas):
    """Scatter-update parity: both dispatch paths must reproduce the
    sequential (last-writer-wins) oracle bit-for-bit — the update is a
    pure row copy, so equality is exact even in bf16.  Slots are drawn
    with replacement, so update sets routinely alias the same slot."""
    k, f, m = case
    rng = np.random.default_rng(k * 1000 + f)
    cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32).astype(dtype)
    rows = jnp.asarray(rng.normal(size=(m, f)), jnp.float32).astype(dtype)
    slots = rng.integers(0, k, m).astype(np.int32)
    want = ref.cache_update(cache, rows, jnp.asarray(slots))
    got = ops.update_cache_rows(cache, np.asarray(rows), slots,
                                use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert got.dtype == cache.dtype


@pytest.mark.parametrize("use_pallas", [False, True])
def test_cache_update_all_aliased_one_slot(use_pallas):
    """Every update row targeting one slot: the last row must win."""
    cache = jnp.zeros((6, 8), jnp.float32)
    rows = jnp.arange(1, 5, dtype=jnp.float32)[:, None] * jnp.ones((4, 8))
    slots = np.full(4, 3, np.int32)
    got = np.asarray(ops.update_cache_rows(cache, np.asarray(rows), slots,
                                           use_pallas=use_pallas))
    want = np.asarray(ref.cache_update(cache, rows, jnp.asarray(slots)))
    np.testing.assert_array_equal(got, want)
    assert np.all(got[3] == 4.0)
    assert np.all(np.delete(got, 3, axis=0) == 0.0)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_cache_update_empty_is_identity(use_pallas):
    cache = jnp.asarray(np.random.default_rng(0).normal(size=(9, 5)),
                        jnp.float32)
    got = ops.update_cache_rows(cache, np.zeros((0, 5), np.float32),
                                np.zeros(0, np.int32),
                                use_pallas=use_pallas)
    assert got is cache       # no-op refresh never touches the device
    want = ref.cache_update(cache, jnp.zeros((0, 5)), jnp.zeros(0, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------- feature combine; pipelined (multi-buffered DMA) update

ASSEMBLE_CASES = [
    # (cache_rows, feat_dim, n_positions, n_miss)
    (64, 100, 130, 9),
    (128, 128, 257, 33),    # ragged position tail
    (17, 33, 41, 5),        # ragged rows/cols (padded F path)
    (256, 64, 512, 48),
]
PIPELINE_DEPTHS = [1, 2, 3, 4]


def _assemble_case(k, f, n, m, dtype, seed=0):
    rng = np.random.default_rng(seed + k * 31 + n)
    cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32).astype(dtype)
    miss = jnp.asarray(rng.normal(size=(m, f)), jnp.float32).astype(dtype)
    # slots drawn with replacement: many positions alias one cached row /
    # one shipped miss row (the dedup fan-out the combine exists for)
    slots = rng.integers(-1, k, n).astype(np.int32)
    miss_index = rng.integers(0, m, n).astype(np.int32)
    return cache, miss, slots, miss_index


@pytest.mark.parametrize("case", ASSEMBLE_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_assemble_pipelined_matches_oracle_all_depths(case, dtype):
    """The combine rebuilds every position from its cache slot or its
    shipped row bit for bit, in the rows' own dtype."""
    k, f, n, m = case
    cache, miss, slots, miss_index = _assemble_case(k, f, n, m, dtype)
    got = ops.assemble_features(cache, miss, slots, miss_index)
    want = np.where((slots >= 0)[:, None], np.asarray(cache)[slots],
                    np.asarray(miss)[miss_index])
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("case", UPDATE_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("depth", PIPELINE_DEPTHS[1:])
def test_cache_update_pipelined_matches_oracle(case, dtype, depth):
    """Pipelined scatter-update parity: slots drawn with replacement, so
    aliased update sets exercise the host-side keep-last compaction the
    concurrent write DMAs require — still bit-identical to the
    sequential last-writer-wins oracle and the depth-1 kernel."""
    k, f, m = case
    rng = np.random.default_rng(k * 1000 + f)
    cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32).astype(dtype)
    rows = jnp.asarray(rng.normal(size=(m, f)), jnp.float32).astype(dtype)
    slots = rng.integers(0, k, m).astype(np.int32)
    want = ref.cache_update(cache, rows, jnp.asarray(slots))
    d1 = ops.update_cache_rows(cache, np.asarray(rows), slots,
                               use_pallas=True)
    got = ops.update_cache_rows(cache, np.asarray(rows), slots,
                                use_pallas=True, pipeline_depth=depth)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(d1, np.float32))
    assert got.dtype == cache.dtype


@pytest.mark.parametrize("depth", PIPELINE_DEPTHS[1:])
def test_cache_update_pipelined_all_aliased_one_slot(depth):
    cache = jnp.zeros((6, 8), jnp.float32)
    rows = jnp.arange(1, 5, dtype=jnp.float32)[:, None] * jnp.ones((4, 8))
    slots = np.full(4, 3, np.int32)
    got = np.asarray(ops.update_cache_rows(cache, np.asarray(rows), slots,
                                           use_pallas=True,
                                           pipeline_depth=depth))
    assert np.all(got[3] == 4.0)
    assert np.all(np.delete(got, 3, axis=0) == 0.0)


def test_pipelined_kernels_reject_bad_depth():
    from repro.kernels import gather_scatter_mm as gsm
    cache = jnp.zeros((8, 128), jnp.float32)
    rows = jnp.zeros((8, 128), jnp.float32)
    mask = jnp.ones((8, 1), jnp.int32)
    blocks = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="depth must be >= 1"):
        gsm.cache_update_pipelined_kernel_call(cache, rows, mask, blocks,
                                               depth=0)


def test_vmem_scratch_budget():
    """The update kernel's depth-4 blocks (8-row f32 blocks of a 128-lane
    tile) fit the VMEM scratch budget; an over-budget request raises with
    the knobs to turn, and the kernel entry point enforces it."""
    from repro.kernels import gather_scatter_mm as gsm
    # depth 4: 4 slots x (8 rows x 128 cols) x 4 B
    target = 4 * gsm.sublane_rows(jnp.float32) * 128 * 4
    assert target <= gsm.VMEM_SCRATCH_BUDGET_BYTES
    gsm.check_vmem_scratch(target, "update depth=4")     # must not raise
    with pytest.raises(ValueError, match="exceeds the"):
        gsm.check_vmem_scratch(gsm.VMEM_SCRATCH_BUDGET_BYTES + 1, "probe")
    # the update entry point itself rejects an over-budget config:
    # depth 33 x 8x8192 f32 blocks = 8.25 MiB > 8 MiB
    cache = jnp.zeros((8, 8192), jnp.float32)
    mask = jnp.ones((8, 1), jnp.int32)
    blocks = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="exceeds the"):
        gsm.cache_update_pipelined_kernel_call(cache, cache, mask, blocks,
                                               t_f=8192, depth=33)


@pytest.mark.parametrize("shape", [(2, 32, 2, 2, 16), (1, 64, 1, 4, 32)])
def test_flash_attention_matches_blocked(shape):
    from repro.models.layers import attention
    b, s, hkv, g, d = shape
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, hkv * g, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    blocked = attention(q, k, v, q_block=16, impl="blocked")
    flash = attention(q, k, v, q_block=16, impl="flash")
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(flash),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grads():
    from repro.models.layers import attention
    key = jax.random.PRNGKey(3)
    b, s, h, d = 2, 32, 2, 16
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))

    def loss(impl):
        return jax.grad(lambda a: (attention(*a, q_block=16,
                                             impl=impl) ** 2).sum())((q, k, v))

    for a, b_ in zip(loss("blocked"), loss("flash")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)
