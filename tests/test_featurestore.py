"""Data-layer coverage: FeatureSource backend parity, the device-resident
hot-feature cache, the cache + miss combine, and end-to-end loss
equivalence of cached vs uncached training."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridConfig, HybridGNNTrainer
from repro.graph import (DenseFeatures, FeatureCache, FeatureLoader,
                         GNNConfig, HashedFeatures, NumpySampler,
                         PartitionedFeatures, as_feature_source, build_cache,
                         make_dataset)
from repro.kernels import ops


def _rows(rng, n, size):
    # duplicates + arbitrary order on purpose
    return rng.integers(0, n, size=size).astype(np.int64)


# ------------------------------------------------------- backend parity


def test_feature_backends_byte_identical():
    n, f = 1000, 32
    hashed = HashedFeatures(n, f, seed=3)
    dense = DenseFeatures(hashed.take(np.arange(n)))
    part = PartitionedFeatures.from_source(hashed, partition_rows=96)
    assert part.num_partitions == -(-n // 96)
    rng = np.random.default_rng(0)
    for size in (1, 7, 500):
        rows = _rows(rng, n, size)
        a, b, c = hashed.take(rows), dense.take(rows), part.take(rows)
        assert a.tobytes() == b.tobytes() == c.tobytes()
        assert a.dtype == b.dtype == c.dtype


def test_make_dataset_backends_agree():
    for backend in ("dense", "hashed", "partitioned"):
        ds = make_dataset("ogbn-products", scale=0.001, seed=0,
                          feature_backend=backend, partition_rows=500)
        rows = np.arange(0, ds.num_nodes, 7)
        x = ds.take_features(rows)
        assert x.shape == (rows.shape[0], ds.feat_dim)
        if backend == "dense":
            ref_x = x
    ds_h = make_dataset("ogbn-products", scale=0.001, seed=0,
                        feature_backend="hashed")
    assert np.array_equal(ds_h.take_features(rows), ref_x)


def test_as_feature_source_rejects_garbage():
    with pytest.raises(TypeError):
        as_feature_source(42)


# ------------------------------------------------------------- the cache


def _toy_cache(n=200, f=8, capacity=50, seed=0):
    src = HashedFeatures(n, f, seed=seed)
    hotness = np.arange(n, 0, -1, dtype=np.float64)  # node 0 hottest
    return src, FeatureCache(src, hotness, capacity)


def test_cache_picks_hottest_and_lookup_partitions():
    src, cache = _toy_cache()
    # hotness is strictly decreasing, so the cache holds exactly [0, 50)
    assert np.array_equal(np.sort(cache.cached_ids), np.arange(50))
    ids = np.array([0, 49, 50, 199, 0, 150], dtype=np.int64)
    look = cache.lookup(ids)
    assert look.num_rows == 6 and look.num_hit == 3 and look.num_miss == 3
    # dedup path: miss block holds the *sorted unique* miss ids
    assert np.array_equal(look.miss_ids, [50, 150, 199])
    assert np.array_equal(look.unique_ids, [0, 49, 50, 150, 199])
    assert np.array_equal(look.unique_ids[look.inverse], ids)
    # slots point at the right cached rows
    hit = look.slots >= 0
    got = src.take(cache.cached_ids)[look.slots[hit]]
    assert np.array_equal(got, src.take(ids[hit]))
    # miss_index maps each miss position at its unique row
    assert np.array_equal(look.miss_ids[look.miss_index[~hit]], ids[~hit])
    # stats accounting (positional hits/misses; dup hit position 0 saved
    # twice by the cache, no duplicate misses here)
    assert cache.stats.hit_rows == 3 and cache.stats.miss_rows == 3
    assert cache.stats.saved_bytes == 3 * 8 * 4
    assert cache.stats.dedup_saved_bytes == 0
    assert cache.stats.unique_rows == 5
    assert cache.expected_hit_rate > 0.25  # top quarter of a linear ramp


def test_legacy_lookup_matches_pr1_layout():
    src, cache = _toy_cache()
    ids = np.array([0, 49, 50, 199, 0, 150], dtype=np.int64)
    look = cache.lookup(ids, dedup=False)
    # one miss row per miss *position*, in frontier order
    assert np.array_equal(look.miss_ids, [50, 199, 150])
    hit = look.slots >= 0
    assert np.array_equal(look.miss_index[~hit], [0, 1, 2])
    assert look.num_unique == look.num_rows
    assert look.dup_miss_rows == 0


def test_lookup_dedup_compacts_duplicate_misses():
    src, cache = _toy_cache()
    ids = np.array([60, 60, 60, 7, 60, 80, 80], dtype=np.int64)
    look = cache.lookup(ids)
    assert look.num_hit == 1                 # node 7 (positional)
    assert look.miss_positions == 6
    assert look.num_miss == 2                # unique misses {60, 80}
    assert look.dup_miss_rows == 4
    assert np.array_equal(look.miss_ids, [60, 80])
    # reconstruction: every position resolves to its own id's row
    hit = look.slots >= 0
    assert np.array_equal(look.miss_ids[look.miss_index[~hit]], ids[~hit])
    assert cache.stats.dedup_saved_bytes == 4 * 8 * 4


def test_cache_capacity_clamped_and_build_cache_off():
    src, cache = _toy_cache(capacity=10_000)
    assert cache.capacity == 200  # clamped to |V|
    ds = make_dataset("ogbn-products", scale=0.001, seed=0)
    assert build_cache(ds, 0.0) is None


# ------------------------------------------------------------ assemble


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_assemble_features_reconstructs_rows(dtype):
    rng = np.random.default_rng(1)
    src, cache = _toy_cache(n=300, f=16, capacity=64)
    ids = _rows(rng, 300, 128)
    look = cache.lookup(ids)
    miss = jnp.asarray(src.take(look.miss_ids).astype(dtype))
    out = ops.assemble_features(
        jnp.asarray(src.take(cache.cached_ids).astype(dtype)), miss,
        jnp.asarray(look.slots), jnp.asarray(look.miss_index))
    assert out.dtype == dtype
    assert np.array_equal(np.asarray(out), src.take(ids).astype(dtype))


def test_assemble_all_hits_empty_miss_block():
    src, cache = _toy_cache(n=100, f=8, capacity=100)
    ids = np.arange(40, dtype=np.int64)
    look = cache.lookup(ids)
    assert look.num_miss == 0
    out = ops.assemble_features(
        jnp.asarray(src.take(cache.cached_ids)),
        jnp.zeros((0, 8), jnp.float32),
        jnp.asarray(look.slots), jnp.asarray(look.miss_index))
    assert np.array_equal(np.asarray(out), src.take(ids))


def test_ref_assemble_matches_kernel_fuzz():
    rng = np.random.default_rng(2)
    for _ in range(3):
        k, m, n, f = 31, 9, 57, 12
        cache = jnp.asarray(rng.normal(size=(k, f)), jnp.float32)
        miss = jnp.asarray(rng.normal(size=(m, f)), jnp.float32)
        slots = rng.integers(-1, k, size=n).astype(np.int32)
        mi = np.where(slots < 0, rng.integers(0, m, size=n), 0).astype(np.int32)
        want = np.where((slots >= 0)[:, None], np.asarray(cache)[slots],
                        np.asarray(miss)[mi])
        b = ops.assemble_features(cache, miss, jnp.asarray(slots),
                                  jnp.asarray(mi))
        assert np.array_equal(np.asarray(b), want)


# ------------------------------------------------------- loader + trainer


def test_loader_miss_only_gather_and_stats():
    ds = make_dataset("ogbn-products", scale=0.002, seed=0)
    cache = build_cache(ds, 0.2)
    loader = FeatureLoader(ds, cache=cache)
    sampler = NumpySampler(ds.graph, fanouts=(4, 3), seed=1)
    rng = np.random.default_rng(0)
    tgt = rng.integers(0, ds.num_nodes, 64)
    mb = sampler.sample(tgt, ds.labels[tgt])
    block = loader.load_misses(mb)
    frontier = np.asarray(mb.frontier(2))
    assert block.num_rows == frontier.shape[0]
    assert block.rows.shape[0] == block.lookup.num_miss < frontier.shape[0]
    # the miss block holds exactly the uncached frontier rows
    assert np.array_equal(block.rows, ds.take_features(block.lookup.miss_ids))
    s = loader.stats
    assert s.total_rows == frontier.shape[0]
    assert s.rows == block.lookup.num_miss
    assert s.bytes == block.rows.nbytes
    assert s.saved_bytes == block.lookup.num_hit * ds.feat_dim * 4
    assert 0.0 < s.hit_rate < 1.0


def test_cached_training_loss_equivalent_and_saves_bytes():
    """Same seed => identical losses with and without the cache, while the
    cache cuts shipped feature bytes (the tentpole acceptance check)."""
    ds = make_dataset("ogbn-products", scale=0.003, seed=0)
    g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                  num_classes=47)

    def run(frac):
        cfg = HybridConfig(total_batch=128, n_accel=2, hybrid=False,
                           use_drm=False, tfp_depth=2, seed=0,
                           cache_fraction=frac)
        tr = HybridGNNTrainer(ds, g, cfg)
        tr.train(4)
        return tr

    base, cached = run(0.0), run(0.2)
    assert [m.loss for m in base.history] == [m.loss for m in cached.history]
    tf_base, tf_cached = base.feature_traffic(), cached.feature_traffic()
    # frac=0 still dedups (default): no cache savings, but dedup savings
    assert tf_base["saved_bytes"] == 0.0
    assert tf_base["dedup_saved_bytes"] > 0.0
    assert tf_base["reduction"] > 1.0 and tf_base["dup_factor"] > 1.0
    # cache on top of dedup: strictly less shipped than dedup alone
    assert tf_cached["reduction"] > tf_base["reduction"]
    assert tf_cached["saved_bytes"] > 0.0
    assert tf_cached["shipped_bytes"] < tf_base["shipped_bytes"]
    assert cached.history[-1].cache_hit_rate > 0.3


def test_cached_training_with_cpu_trainer_and_drm():
    """Hybrid mode: the CPU trainer reads the full frontier (dense path)
    while accelerators run miss-only; DRM keeps the batch conserved."""
    ds = make_dataset("ogbn-products", scale=0.003, seed=0)
    g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                  num_classes=47)
    cfg = HybridConfig(total_batch=256, n_accel=2, hybrid=True, use_drm=True,
                       tfp_depth=2, share_quantum=32, seed=0,
                       cache_fraction=0.2)
    tr = HybridGNNTrainer(ds, g, cfg)
    hist = tr.train(6)
    assert all(np.isfinite(m.loss) for m in hist)
    for m in hist:
        cpu_b, accel_b = m.assignment
        assert cpu_b + accel_b * cfg.n_accel == cfg.total_batch
    assert tr.loader.stats.saved_bytes > 0
