"""Frontier-deduplication coverage: unique/inverse round trips, the
feature combine, the transfer stage's bucket padding, traffic-accounting
invariants, dedup-vs-legacy loss bit identity, the perf-model duplication
factor, and the measured-hit-rate feedback loop."""
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import HybridConfig, HybridGNNTrainer, WorkloadSpec
from repro.core.perfmodel import (PLATFORMS, initial_task_mapping, t_load,
                                  t_trans)
from repro.graph import (FeatureCache, FeatureLoader, GNNConfig,
                         HashedFeatures, NumpySampler, build_cache,
                         compact_lookup, make_dataset)
from repro.graph.featload import MissBlock
from repro.kernels import ops


def _toy_cache(n=200, f=8, capacity=50, seed=0):
    src = HashedFeatures(n, f, seed=seed)
    hotness = np.arange(n, 0, -1, dtype=np.float64)  # node 0 hottest
    return src, FeatureCache(src, hotness, capacity)


# --------------------------------------------- unique / inverse round trip


@given(st.integers(1, 400), st.integers(2, 500), st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_compact_lookup_round_trip(size, universe, seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, universe, size=size).astype(np.int64)
    look = compact_lookup(ids)
    # inverse map reconstructs the positional frontier exactly
    assert np.array_equal(look.unique_ids[look.inverse], ids)
    assert np.array_equal(look.unique_ids, np.unique(ids))
    # cache-less: every unique id is a miss, in sorted unique order
    assert np.array_equal(look.miss_ids, look.unique_ids)
    assert look.num_hit == 0
    assert np.array_equal(look.miss_ids[look.miss_index], ids)
    # counting identities behind the byte accounting
    assert look.num_rows == look.num_miss + look.dup_miss_rows
    assert look.dup_factor >= 1.0


@given(st.integers(1, 300), st.integers(1, 199), st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_cached_compact_lookup_round_trip(size, capacity, seed):
    rng = np.random.default_rng(seed)
    src, cache = _toy_cache(capacity=capacity, seed=1)
    ids = rng.integers(0, 200, size=size).astype(np.int64)
    look = cache.lookup(ids)
    hit = look.slots >= 0
    # every position resolves to its own id's feature row
    out = np.empty((size, 8), np.float32)
    out[hit] = src.take(cache.cached_ids)[look.slots[hit]]
    out[~hit] = src.take(look.miss_ids)[look.miss_index[~hit]]
    assert np.array_equal(out, src.take(ids))
    # hit/miss position counts + unique-miss compaction are consistent
    assert look.num_hit + look.miss_positions == look.num_rows
    assert look.num_miss == np.unique(ids[~hit]).shape[0] if (~hit).any() \
        else look.num_miss == 0


# ------------------------------------------------------ feature combine


def _combined(cache, miss, slots, mi):
    """The positional block the combine must build, in plain numpy."""
    miss = np.asarray(miss)
    if cache is None:
        return miss[mi]
    return np.where((slots >= 0)[:, None], np.asarray(cache)[slots],
                    miss[mi])


def _combine_case(k, m, n, f, hits):
    """A cache of ``k`` rows, ``m`` miss rows and ``n`` positions that
    hit the cache ("all-hit"), read the miss rows ("all-miss") or either
    ("mixed"); past ``k + m`` positions many share one row."""
    rng = np.random.default_rng(n * 7 + f)
    cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32)
    miss = jnp.asarray(rng.normal(size=(m, f)), jnp.float32)
    lo = 0 if hits == "all-hit" else -1
    hi = 0 if hits == "all-miss" else k
    slots = rng.integers(lo, hi, size=n).astype(np.int32)
    mi = np.where(slots < 0, rng.integers(0, m, size=n), 0).astype(np.int32)
    return cache, miss, slots, mi


@pytest.mark.parametrize("k,m,n,f,hits", [
    # everything ragged
    pytest.param(31, 9, 57, 12, "mixed", id="31-9-57-12"),
    # single output row
    pytest.param(64, 1, 1, 100, "mixed", id="64-1-1-100"),
    # tiny cache
    pytest.param(1, 3, 8, 8, "mixed", id="1-3-8-8"),
    # odd feature dim, n just past a tile
    pytest.param(200, 7, 129, 257, "mixed", id="200-7-129-257"),
    # fully tile-aligned
    pytest.param(128, 128, 512, 128, "mixed", id="128-128-512-128"),
    # many positions to one row: every position hits, misses or either
    pytest.param(24, 6, 700, 100, "all-hit", id="many-to-one-all-hit"),
    pytest.param(24, 6, 700, 100, "all-miss", id="many-to-one-all-miss"),
    pytest.param(24, 6, 700, 100, "mixed", id="many-to-one-mixed"),
])
def test_tiled_combine_matches_ref_and_legacy_kernel(k, m, n, f, hits):
    """The combine (XLA's gather and select) equals the numpy
    reconstruction bit for bit, from host or device index tables."""
    cache, miss, slots, mi = _combine_case(k, m, n, f, hits)
    want = _combined(cache, miss, slots, mi)
    x = ops.assemble_features(cache, miss, slots, mi)
    assert np.array_equal(np.asarray(x), want)
    y = ops.assemble_features(cache, miss, jnp.asarray(slots),
                              jnp.asarray(mi))
    assert np.array_equal(np.asarray(y), want)


def test_tiled_combine_duplicated_rows_and_no_cache():
    """Many positions -> one shipped row (the dedup expansion contract)."""
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.normal(size=(6, 40)), jnp.float32)
    inverse = rng.integers(0, 6, size=333).astype(np.int32)
    slots = np.full(333, -1, np.int32)
    out = ops.assemble_features(None, rows, slots, inverse)
    assert np.array_equal(np.asarray(out), np.asarray(rows)[inverse])
    x = ops.assemble_features(None, rows, jnp.asarray(slots),
                              jnp.asarray(inverse))
    assert np.array_equal(np.asarray(x), np.asarray(out))


def test_jnp_combine_ignores_the_distinct_hit_count():
    """The combine's program is keyed by the cache shape, the miss
    block's rows and the position count: two batches that differ only in
    how many distinct cache rows they hit share it."""
    k, m, n, f = 400, 128, 600, 16
    rng = np.random.default_rng(3)
    cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32)
    miss = jnp.asarray(rng.normal(size=(m, f)), jnp.float32)
    batches = []
    for distinct in (5, 300):
        slots = rng.choice(distinct, size=n).astype(np.int32)
        slots[::4] = -1
        mi = np.where(slots < 0, rng.integers(0, m, size=n), 0
                      ).astype(np.int32)
        assert np.unique(slots[slots >= 0]).shape[0] > distinct // 2
        batches.append((slots, mi))
    ops.assemble_features(cache, miss, *batches[0])
    jnp_programs = ops._assemble_ref._cache_size()
    for slots, mi in batches:
        a = ops.assemble_features(cache, miss, slots, mi)
        assert np.array_equal(np.asarray(a),
                              _combined(cache, miss, slots, mi))
    assert ops._assemble_ref._cache_size() == jnp_programs


def test_tiled_combine_bf16_bit_identical():
    rng = np.random.default_rng(9)
    cache = jnp.asarray(rng.normal(size=(33, 20)), jnp.bfloat16)
    miss = jnp.asarray(rng.normal(size=(5, 20)), jnp.bfloat16)
    slots = rng.integers(-1, 33, size=90).astype(np.int32)
    mi = np.where(slots < 0, rng.integers(0, 5, size=90), 0).astype(np.int32)
    out = ops.assemble_features(cache, miss, slots, mi)
    want = _combined(cache, miss, slots, mi)
    assert out.dtype == want.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(out), want)


# -------------------------------------------- traffic-stats invariants


def _loss_list(tr):
    return [m.loss for m in tr.history]


def _run_trainer(ds, g, *, dedup, frac, hybrid=False, iters=4, seed=0,
                 n_accel=2, total_batch=128, use_drm=False):
    cfg = HybridConfig(total_batch=total_batch, n_accel=n_accel,
                       hybrid=hybrid, use_drm=use_drm, tfp_depth=2,
                       seed=seed, cache_fraction=frac, dedup=dedup)
    tr = HybridGNNTrainer(ds, g, cfg)
    tr.train(iters)
    return tr


@pytest.fixture(scope="module")
def small_ds():
    ds = make_dataset("ogbn-products", scale=0.003, seed=0)
    g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                  num_classes=47)
    return ds, g


@pytest.mark.parametrize("dedup,frac", [(True, 0.0), (True, 0.2),
                                        (False, 0.2)])
def test_traffic_accounting_sums_to_legacy_baseline(small_ds, dedup, frac):
    ds, g = small_ds
    tr = _run_trainer(ds, g, dedup=dedup, frac=frac)
    s = tr.loader.stats
    row_bytes = ds.feat_dim * 4
    # every transfer-path frontier position is accounted exactly once:
    # shipped (minus padding) + cache-saved + dedup-saved == positional
    # baseline
    assert (s.bytes - s.padding_bytes) + s.saved_bytes \
        + s.dedup_saved_bytes == s.total_rows * row_bytes
    # row-level identity matching the byte identity
    gathered_rows = (s.bytes - s.padding_bytes) // row_bytes
    assert gathered_rows + s.hit_rows \
        + (s.dedup_saved_bytes // row_bytes) == s.total_rows
    tf = tr.feature_traffic()
    assert tf["reduction"] >= 1.0
    if dedup:
        assert s.unique_rows < s.total_rows
        assert tf["dup_factor"] > 1.0
    else:
        assert s.dedup_saved_bytes == 0


def test_dedup_ships_fewer_bytes_than_legacy_smoke(small_ds):
    """tier1 smoke: deduped shipped bytes < legacy shipped bytes on the
    synthetic power-law graph, cache on or off."""
    ds, g = small_ds
    legacy = _run_trainer(ds, g, dedup=False, frac=0.0)
    dedup = _run_trainer(ds, g, dedup=True, frac=0.0)
    assert dedup.loader.stats.bytes < legacy.loader.stats.bytes
    legacy_c = _run_trainer(ds, g, dedup=False, frac=0.2)
    dedup_c = _run_trainer(ds, g, dedup=True, frac=0.2)
    assert dedup_c.loader.stats.bytes < legacy_c.loader.stats.bytes


# ------------------------------------------------ loss bit-identity


def test_dedup_loss_bit_identical_to_legacy(small_ds):
    """Dedup reshapes the transfer, never the math: losses must be
    bit-identical to the legacy positional path, cached and uncached."""
    ds, g = small_ds
    legacy_uncached = _run_trainer(ds, g, dedup=False, frac=0.0)
    dedup_uncached = _run_trainer(ds, g, dedup=True, frac=0.0)
    assert _loss_list(legacy_uncached) == _loss_list(dedup_uncached)
    legacy_cached = _run_trainer(ds, g, dedup=False, frac=0.2)
    dedup_cached = _run_trainer(ds, g, dedup=True, frac=0.2)
    assert _loss_list(legacy_cached) == _loss_list(dedup_cached)
    # and the cache itself is semantically invisible as before
    assert _loss_list(legacy_uncached) == _loss_list(dedup_cached)


@pytest.mark.parametrize("m,positions", [(1, 5), (127, 400), (128, 300),
                                         (129, 200)],
                         ids=["1", "127", "128", "129"])
def test_assemble_pads_to_the_bucket(small_ds, m, positions):
    """The trainer's transfer stage pads ``m`` unique miss rows up to the
    128-row bucket, clamped to the frontier's position count, charges the
    padding rows to the shipped stats, and the combine still rebuilds
    exactly the frontier's feature rows."""
    ds, g = small_ds
    tr = HybridGNNTrainer(ds, g, HybridConfig(
        total_batch=128, n_accel=1, hybrid=False, use_drm=False,
        cache_fraction=0.2, dedup=True, seed=0))
    try:
        rng = np.random.default_rng(m)
        slot_of = tr.cache.slot_of
        miss_ids = rng.choice(np.flatnonzero(slot_of < 0), m, replace=False)
        hit_ids = np.flatnonzero(slot_of >= 0)
        n_hit = (positions - m) // 2
        frontier = rng.permutation(np.concatenate([
            miss_ids, rng.choice(miss_ids, positions - m - n_hit),
            rng.choice(hit_ids, n_hit)])).astype(np.int64)
        look = tr.cache.lookup(frontier, dedup=True, record=False, pin=True)
        assert look.num_miss == m and look.num_rows == positions
        rows = ds.take_features(look.miss_ids).astype(np.float32)
        before = tr.loader.snapshot_stats()
        x = tr._assemble(MissBlock(rows=rows, lookup=look),
                         tr.accel_devices[0])
        after = tr.loader.snapshot_stats()
        assert np.array_equal(np.asarray(x), ds.take_features(frontier))
        bucket = min(-(-m // 128) * 128, positions)
        row_bytes = ds.feat_dim * 4
        assert after.padding_bytes - before.padding_bytes == \
            (bucket - m) * row_bytes
        assert after.rows - before.rows == bucket - m
    finally:
        tr.close()


# ------------------------------------------------ loader / pool details


def test_persistent_gather_pool_reused(small_ds):
    ds, _ = small_ds
    loader = FeatureLoader(ds, num_threads=4)
    rows = np.arange(0, ds.num_nodes, 2, dtype=np.int64)
    a, _ = loader._gather(rows)
    pool = loader._pool
    assert pool is not None
    b, _ = loader._gather(rows)
    assert loader._pool is pool          # reused, not rebuilt per call
    assert np.array_equal(a, b)
    assert np.array_equal(a, ds.take_features(rows))
    loader.num_threads = 2               # DRM knob change -> new pool
    loader._gather(rows)
    assert loader._pool is not pool
    loader.close()
    assert loader._pool is None


def test_load_compact_without_cache(small_ds):
    ds, _ = small_ds
    loader = FeatureLoader(ds)
    sampler = NumpySampler(ds.graph, fanouts=(4, 3), seed=1)
    tgt = np.random.default_rng(0).integers(0, ds.num_nodes, 64)
    mb = sampler.sample(tgt, ds.labels[tgt])
    block = loader.load_compact(mb)
    frontier = np.asarray(mb.frontier(2))
    assert block.lookup.num_hit == 0
    assert block.rows.shape[0] == np.unique(frontier).shape[0]
    assert np.array_equal(
        block.rows[block.lookup.miss_index], ds.take_features(frontier))
    assert loader.stats.unique_rows == block.rows.shape[0]
    assert loader.stats.dedup_saved_bytes == \
        (frontier.shape[0] - block.rows.shape[0]) * ds.feat_dim * 4


# ------------------------------------- perf model: duplication factor


def test_perfmodel_dedup_factor_scales_eq7_eq8():
    host, accel = PLATFORMS["epyc-7763"], PLATFORMS["tpu-v5e"]
    w_full = WorkloadSpec(1024, (25, 10), (100, 256, 47))
    w_half = WorkloadSpec(1024, (25, 10), (100, 256, 47), dedup_factor=0.5)
    assert abs(t_load(w_half, host, 1) / t_load(w_full, host, 1) - 0.5) < 1e-9
    assert abs(t_trans(w_half, accel) / t_trans(w_full, accel) - 0.5) < 1e-9
    # composes multiplicatively with the cache term
    w_both = WorkloadSpec(1024, (25, 10), (100, 256, 47),
                          cache_hit_rate=0.5, dedup_factor=0.5)
    assert abs(t_trans(w_both, accel) / t_trans(w_full, accel) - 0.25) < 1e-9


def test_mapping_shifts_toward_accel_with_dedup():
    host, accel = PLATFORMS["epyc-7763"], PLATFORMS["rtx-a5000"]
    kw = dict(n_accel=1, total_batch=1024, fanouts=(25, 10),
              layer_dims=(100, 256, 47))
    base = initial_task_mapping(host, accel, **kw)
    deduped = initial_task_mapping(host, accel, dedup_factor=0.3, **kw)
    # cheaper transfer -> the accelerator can absorb at least as much work
    assert deduped["accel_each"] >= base["accel_each"]
    assert deduped["cpu"] + deduped["accel_each"] <= 1024


def test_trainer_probes_dup_factor(small_ds):
    ds, g = small_ds
    # the probe runs only when its consumer (the hybrid mapping) exists
    tr = _run_trainer(ds, g, dedup=True, frac=0.0, hybrid=True, iters=2)
    assert 0.0 < tr.measured_dedup_alpha < 1.0
    legacy = _run_trainer(ds, g, dedup=False, frac=0.0, hybrid=True, iters=2)
    assert legacy.measured_dedup_alpha == 1.0
    accel_only = _run_trainer(ds, g, dedup=True, frac=0.0, iters=2)
    assert accel_only.measured_dedup_alpha == 1.0


def test_probe_alpha_consults_cache(small_ds):
    """Design-time alpha must exclude cached positions from both the
    numerator and the denominator: hub ids are the most-cached AND the
    most-duplicated, so the old unique/total ratio double-counted the
    overlap the mapping's (1 - h) cache term already removed."""
    ds, g = small_ds
    uncached = _run_trainer(ds, g, dedup=True, frac=0.0, hybrid=True,
                            iters=1)
    cached = _run_trainer(ds, g, dedup=True, frac=0.3, hybrid=True, iters=1)
    # caching the hot hubs removes the most-duplicated ids from the miss
    # traffic, so the residual alpha is strictly larger (less duplicated)
    assert cached.measured_dedup_alpha > uncached.measured_dedup_alpha
    assert 0.0 < cached.measured_dedup_alpha <= 1.0


def test_init_and_refresh_alpha_agree_on_same_traffic(small_ds):
    """The init-time probe (compact_lookup against cache.slot_of) and the
    refresh-time loader-stats formula must compute the same alpha =
    unique-miss / positional-miss rows for the same measured traffic."""
    ds, g = small_ds
    cache = build_cache(ds, 0.2)
    loader = FeatureLoader(ds, cache=cache)
    sampler = NumpySampler(ds.graph, g.fanouts, seed=17)
    rng = np.random.default_rng(17)
    tgt = rng.integers(0, ds.num_nodes, 64)
    mb = sampler.sample(tgt, ds.labels[tgt])
    loader.load_compact(mb)
    # refresh-time definition (_maybe_refresh_mapping, from LoadStats)
    s = loader.stats
    miss_positions = s.total_rows - s.hit_rows
    refresh_alpha = (1.0 - (s.dedup_saved_bytes // cache.row_bytes)
                     / miss_positions)
    # init-time definition (_probe_dup_factor, from compact_lookup)
    frontier = np.asarray(mb.frontier(len(g.fanouts)))
    look = compact_lookup(frontier, cache.slot_of)
    probe_alpha = look.num_miss / look.miss_positions
    assert probe_alpha == pytest.approx(refresh_alpha)
    loader.close()


# ------------------------------------------- measured-hit-rate feedback


def test_hit_rate_feedback_refreshes_mapping(small_ds):
    ds, g = small_ds
    tr = _run_trainer(ds, g, dedup=True, frac=0.2, hybrid=True, iters=3,
                      total_batch=256)
    # force a drift far beyond the 5-point threshold and refresh
    tr._model_hit_rate = 0.99
    before = tr._model_hit_rate
    assert tr._maybe_refresh_mapping()
    assert tr._model_hit_rate == tr.loader.stats.hit_rate != before
    a = tr.runtime.assignment
    assert a.cpu_batch + a.accel_batch * a.n_accel == 256
    # within the threshold: no refresh
    assert not tr._maybe_refresh_mapping()


def test_hit_rate_feedback_noop_without_cache_or_hybrid(small_ds):
    ds, g = small_ds
    tr = _run_trainer(ds, g, dedup=True, frac=0.0, hybrid=True, iters=2)
    assert not tr._maybe_refresh_mapping()
    tr2 = _run_trainer(ds, g, dedup=True, frac=0.2, hybrid=False, iters=2)
    assert not tr2._maybe_refresh_mapping()


# ----------------------------------------------- accel device indexing


def test_accel_device_indexed_by_ordinal(small_ds):
    """accel0 must map to accel_devices[0] even when the CPU trainer is
    active (the enumeration index used to count the cpu entry)."""
    ds, g = small_ds
    cfg = HybridConfig(total_batch=256, n_accel=2, hybrid=True,
                       use_drm=False, tfp_depth=0, seed=0)
    tr = HybridGNNTrainer(ds, g, cfg)
    assert tr._accel_device("accel0") is tr.accel_devices[0]
    assert tr._accel_device("accel1") is tr.accel_devices[1]
