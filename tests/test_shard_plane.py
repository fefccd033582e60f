"""Sharded hot-feature plane (CPU-mesh parity suite).

Placement invariants (disjoint shards, full coverage, for both hash and
degree-range policies), union-lookup classification counts against a
brute-force oracle, the union-gather's strict byte reduction and stats
identity, the peer-exchange collective + shard-aware assemble (f32 and
bf16 rows), sharded-vs-replicated loss bit-identity end to
end (including a dynamic refresh mid-run), and the cross-iteration
recent-rows LRU (skip / count / invalidate-on-refresh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridConfig, HybridGNNTrainer
from repro.dist.collectives import exchange_peer_rows, ring_order
from repro.graph import (FeatureLoader, GNNConfig, HashedFeatures,
                         ShardMissBlock, ShardPlacement, ShardedFeatureCache,
                         make_dataset)
from repro.kernels.ops import assemble_features_sharded, gather_rows

N, F = 400, 12


def _plane(n_shards=2, capacity=30, placement="hash", seed=0,
           dtype=np.float32):
    src = HashedFeatures(N, F, seed=seed, dtype=dtype)
    hotness = np.arange(N, 0, -1, dtype=np.float64)  # node 0 hottest
    return src, ShardedFeatureCache(src, hotness, capacity, n_shards,
                                    placement=placement)


def _ds():
    return make_dataset("ogbn-products", scale=0.002, seed=0)


def _gcfg(ds):
    return GNNConfig(model="sage", layer_dims=(ds.feat_dim, 32, 47),
                     fanouts=(4, 3), num_classes=47)


# ------------------------------------------------- placement invariants


@pytest.mark.parametrize("policy", ShardPlacement.POLICIES)
@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_placement_disjoint_and_exhaustive(policy, n_shards):
    hotness = np.arange(N, 0, -1, dtype=np.float64)
    pl = ShardPlacement(N, n_shards, policy, hotness)
    assert pl.owner.shape == (N,)
    # every node owned by exactly one shard, every shard non-trivial
    assert pl.owner.min() >= 0 and pl.owner.max() < n_shards
    assert len(np.unique(pl.owner)) == n_shards
    assert np.array_equal(pl.owner_of(np.arange(N)), pl.owner)


def test_degree_placement_is_contiguous_rank_ranges():
    hotness = np.arange(N, 0, -1, dtype=np.float64)
    pl = ShardPlacement(N, 4, "degree", hotness)
    span = -(-N // 4)
    # hotness is rank order here, so ownership follows id blocks
    assert np.array_equal(pl.owner, np.arange(N) // span)


@pytest.mark.parametrize("policy", ShardPlacement.POLICIES)
def test_shards_are_disjoint_and_owned(policy):
    _, plane = _plane(n_shards=3, capacity=40, placement=policy)
    all_ids = np.concatenate([s.cached_ids for s in plane.shards])
    assert len(np.unique(all_ids)) == len(all_ids), "shards must be disjoint"
    for d, s in enumerate(plane.shards):
        assert np.all(plane.placement.owner[s.cached_ids] == d), \
            "a shard may only pin ids it owns"
    # n x effective capacity at the same per-device budget
    assert plane.capacity == sum(s.capacity for s in plane.shards)
    assert plane.capacity > max(s.capacity for s in plane.shards)


def test_shards_stay_disjoint_after_refresh():
    _, plane = _plane(n_shards=2, capacity=30)
    plane.track_hotness = True
    rng = np.random.default_rng(0)
    for _ in range(6):
        plane.lookup_union(
            {"accel0": rng.integers(0, N, 200),
             "accel1": rng.integers(0, N, 200)},
            {"accel0": 0, "accel1": 1})
    assert plane.refresh(max_swap=8) >= 0
    all_ids = np.concatenate([s.cached_ids for s in plane.shards])
    assert len(np.unique(all_ids)) == len(all_ids)
    for d, s in enumerate(plane.shards):
        assert np.all(plane.placement.owner[s.cached_ids] == d)


# ------------------------------------- union classification vs brute force


def test_union_lookup_classification_counts():
    src, plane = _plane(n_shards=3, capacity=35)
    rng = np.random.default_rng(1)
    frontiers = {f"accel{i}": rng.integers(0, N, 150) for i in range(3)}
    ordinals = {f"accel{i}": i for i in range(3)}
    union = plane.lookup_union(frontiers, ordinals, record=False)
    owner = plane.placement.owner
    cached = [set(s.cached_ids.tolist()) for s in plane.shards]
    for name, sl in union.per_trainer.items():
        me = ordinals[name]
        ids = frontiers[name]
        uniq = np.unique(ids)
        exp_local = [i for i in uniq if owner[i] == me and i in cached[me]]
        exp_peer = [i for i in uniq
                    if owner[i] != me and i in cached[owner[i]]]
        exp_fresh = [i for i in uniq
                     if i not in cached[owner[i]]]
        look = sl.look
        # num_hit counts POSITIONS served by the local shard
        exp_local_pos = int(np.isin(ids, np.asarray(exp_local)).sum())
        assert look.num_hit == exp_local_pos
        assert sl.local_positions == exp_local_pos
        assert sl.peer_rows == len(exp_peer)
        assert look.num_miss == len(exp_fresh)
        assert sorted(look.miss_ids.tolist()) == sorted(exp_fresh)
        # peer requests follow ring order with correct owners
        order = [p for p, _, _ in sl.peer_requests]
        assert order == [p for p in ring_order(3, me)
                         if any(owner[i] == p for i in exp_peer)]
        # position counts partition the frontier
        assert (sl.local_positions + sl.peer_positions
                + int(np.isin(ids, np.asarray(exp_fresh)).sum())
                == ids.shape[0])


# ------------------------------------------- union gather + stats identity


def _loader_with_plane(n_shards=2, capacity=40):
    ds = _ds()
    plane = ShardedFeatureCache(ds.feature_source, ds.feature_hotness(),
                                capacity, n_shards)
    loader = FeatureLoader(ds, cache=plane)
    return ds, plane, loader


class _FakeBatch:
    """Minimal MiniBatch stand-in: only the last-hop frontier is read."""

    fanouts = (1,)

    def __init__(self, ids):
        self._ids = np.asarray(ids, dtype=np.int64)

    def frontier(self, depth):
        return self._ids


def test_union_ships_strictly_fewer_bytes_than_per_trainer_dedup():
    _, plane, loader = _loader_with_plane()
    rng = np.random.default_rng(2)
    shared = rng.integers(0, 2000, 300)   # heavy overlap between trainers
    b0 = _FakeBatch(np.concatenate([shared, rng.integers(0, 2000, 100)]))
    b1 = _FakeBatch(np.concatenate([shared, rng.integers(0, 2000, 100)]))
    blocks = loader.load_union({"accel0": b0, "accel1": b1},
                               {"accel0": 0, "accel1": 1})
    s = loader.stats
    per_trainer_rows = sum(b.lookup.num_miss for b in blocks.values())
    assert s.rows < per_trainer_rows, \
        "union gather must ship strictly fewer rows than per-trainer dedup"
    assert s.union_saved_bytes == \
        (per_trainer_rows - s.rows) * plane.row_bytes
    assert s.ici_bytes >= s.union_saved_bytes


def test_union_stats_identity():
    """Every requested frontier position is accounted exactly once:
    positions x row_bytes = local + peer + dedup + union + shipped."""
    _, plane, loader = _loader_with_plane()
    rng = np.random.default_rng(3)
    for _ in range(3):
        shared = rng.integers(0, 2000, 200)
        loader.load_union(
            {"accel0": _FakeBatch(np.concatenate(
                [shared, rng.integers(0, 2000, 150)])),
             "accel1": _FakeBatch(np.concatenate(
                [shared, rng.integers(0, 2000, 150)]))},
            {"accel0": 0, "accel1": 1})
    s = loader.stats
    assert s.total_rows * plane.row_bytes == (
        s.saved_bytes + s.peer_saved_bytes + s.dedup_saved_bytes
        + s.union_saved_bytes + s.recent_saved_bytes
        + (s.bytes - s.padding_bytes))


def test_union_multicast_slices_match_source():
    """Each trainer's block carries exactly its fresh rows (its slice of
    the one union gather), value-identical to a direct source gather."""
    ds, plane, loader = _loader_with_plane()
    rng = np.random.default_rng(4)
    batches = {f"accel{i}": _FakeBatch(rng.integers(0, 2000, 250))
               for i in range(2)}
    blocks = loader.load_union(batches, {"accel0": 0, "accel1": 1})
    for name, block in blocks.items():
        assert isinstance(block, ShardMissBlock)
        want = ds.feature_source.take(block.lookup.miss_ids)
        assert np.array_equal(block.rows, want.astype(block.rows.dtype))


# --------------------------------------- peer exchange + sharded assemble


def test_gather_rows_jnp_pallas_parity():
    rng = np.random.default_rng(5)
    block = jnp.asarray(rng.normal(size=(64, F)).astype(np.float32))
    slots = rng.integers(0, 64, 17).astype(np.int32)
    want = np.asarray(block)[slots]
    assert np.array_equal(np.asarray(gather_rows(block, slots)), want)
    assert np.array_equal(
        np.asarray(gather_rows(block, jnp.asarray(slots))), want)
    assert gather_rows(block, slots[:0]).shape == (0, F)


def test_exchange_peer_rows_preserves_request_order():
    rng = np.random.default_rng(6)
    blocks = {d: jnp.asarray(rng.normal(size=(32, F)).astype(np.float32))
              for d in (1, 2)}
    reqs = [(1, np.array([3, 0, 7], np.int32), 0),
            (2, np.array([5, 5], np.int32), 0)]
    dev = jax.devices()[0]
    out = exchange_peer_rows(reqs, lambda p, v: blocks[p], dev)
    assert len(out) == 2
    assert np.array_equal(np.asarray(out[0]),
                          np.asarray(blocks[1])[[3, 0, 7]])
    assert np.array_equal(np.asarray(out[1]), np.asarray(blocks[2])[[5, 5]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sharded_assemble_reconstructs_frontier(dtype):
    """Local block + ring-ordered peer rows + fresh host rows must
    assemble into exactly the positional [frontier, F] source rows."""
    src, plane = _plane(n_shards=3, capacity=35, dtype=dtype)
    rng = np.random.default_rng(7)
    frontiers = {f"accel{i}": rng.integers(0, N, 120) for i in range(3)}
    ordinals = {f"accel{i}": i for i in range(3)}
    union = plane.lookup_union(frontiers, ordinals, pin=True, record=False)
    dev = jax.devices()[0]
    for name, sl in union.per_trainer.items():
        look = sl.look
        local = plane.shards[sl.shard].data_on(dev, version=look.version)
        peers = exchange_peer_rows(
            sl.peer_requests,
            lambda p, v: plane.shards[p].data_on(dev, version=v),
            dev)
        fresh = jnp.asarray(src.take(look.miss_ids))
        x = assemble_features_sharded(local, peers + [fresh], look.slots,
                                      look.miss_index)
        want = src.take(frontiers[name])
        assert x.dtype == want.dtype == dtype
        assert np.array_equal(np.asarray(x), want)
        plane.release_union(sl)


# -------------------------------------------- end-to-end trainer parity


def _losses(ds, g, iters=5, **kw):
    cfg = HybridConfig(total_batch=128, n_accel=2, hybrid=False,
                       use_drm=False, tfp_depth=2, cache_fraction=0.05,
                       seed=0, **kw)
    tr = HybridGNNTrainer(ds, g, cfg)
    hist = tr.train(iters)
    tr.close()
    return [m.loss for m in hist], tr


@pytest.mark.parametrize("placement", ShardPlacement.POLICIES)
def test_sharded_replicated_losses_bit_identical(placement):
    ds = _ds()
    g = _gcfg(ds)
    l_rep, _ = _losses(ds, g)
    l_sh, tr = _losses(ds, g, cache_sharding="sharded",
                       shard_placement=placement)
    assert l_rep == l_sh, "sharding must only move bytes, never values"
    ft = tr.feature_traffic()
    assert ft["union_saved_bytes"] > 0 or ft["peer_saved_bytes"] > 0


def test_sharded_bit_identical_with_refresh_mid_run():
    """A dynamic refresh (per-shard stage/commit under the pin protocol)
    mid-pipeline must stay bit-invisible on the sharded plane too."""
    ds = _ds()
    g = _gcfg(ds)
    kw = dict(cache_refresh=True, cache_drift_threshold=0.0)
    l_rep, _ = _losses(ds, g, iters=6, **kw)
    l_sh, tr = _losses(ds, g, iters=6, cache_sharding="sharded", **kw)
    assert l_rep == l_sh
    assert tr.cache.version > 0, "the refresh must actually have fired"


def test_sharded_reduces_shipped_bytes_at_4_accel():
    """The acceptance gate's quantity at small scale: >= 1.5x fewer
    host->device bytes at n_accel=4 vs the replicated plane at equal
    per-device capacity, losses bit-identical."""
    ds = _ds()
    g = _gcfg(ds)
    base = dict(total_batch=256, n_accel=4, hybrid=False, use_drm=False,
                tfp_depth=1, cache_fraction=0.05, seed=0)
    t1 = HybridGNNTrainer(ds, g, HybridConfig(**base))
    h1 = t1.train(4)
    t1.close()
    t2 = HybridGNNTrainer(ds, g, HybridConfig(
        **base, cache_sharding="sharded"))
    h2 = t2.train(4)
    t2.close()
    assert [m.loss for m in h1] == [m.loss for m in h2]
    rep = t1.feature_traffic()["shipped_bytes"]
    sh = t2.feature_traffic()["shipped_bytes"]
    assert rep >= 1.5 * sh


# ------------------------------------------------ recent-rows LRU (PCIe)


def _compact_loader(recent_batches):
    ds = _ds()
    from repro.graph import build_cache
    cache = build_cache(ds, 0.05)
    return ds, cache, FeatureLoader(ds, cache=cache,
                                    recent_batches=recent_batches)


def test_recent_lru_skips_resident_rows_and_counts_them():
    ds, cache, loader = _compact_loader(recent_batches=2)
    rng = np.random.default_rng(8)
    ids = rng.integers(0, ds.num_nodes, 300)
    b1 = loader.load_compact(_FakeBatch(ids), recent_key="accel0")
    assert b1.shipped is not None and b1.recent == []
    shipped_first = b1.rows.shape[0]
    # same frontier again: every unique miss is already device-resident
    b2 = loader.load_compact(_FakeBatch(ids), recent_key="accel0")
    assert b2.rows.shape[0] == 0, "resident rows must not re-ship"
    assert len(b2.recent) == 1
    entry, idx = b2.recent[0]
    assert entry is b1.shipped and idx.shape[0] == shipped_first
    s = loader.stats
    assert s.recent_rows == shipped_first
    assert s.recent_saved_bytes == shipped_first * cache.row_bytes
    # stats identity holds with the recent term
    assert s.total_rows * cache.row_bytes == (
        s.saved_bytes + s.dedup_saved_bytes + s.recent_saved_bytes
        + (s.bytes - s.padding_bytes))


def test_recent_lru_is_per_consumer_and_bounded():
    ds, _, loader = _compact_loader(recent_batches=1)
    rng = np.random.default_rng(9)
    ids_a = rng.integers(0, ds.num_nodes, 200)
    loader.load_compact(_FakeBatch(ids_a), recent_key="accel0")
    # a different consumer never matches another's residency
    b = loader.load_compact(_FakeBatch(ids_a), recent_key="accel1")
    assert b.recent == [] and b.rows.shape[0] > 0
    # depth-1 history: an intervening disjoint batch evicts the first
    ids_b = rng.integers(0, ds.num_nodes, 200)
    loader.load_compact(_FakeBatch(ids_b), recent_key="accel0")
    b2 = loader.load_compact(_FakeBatch(ids_a), recent_key="accel0")
    overlap = np.intersect1d(np.unique(ids_a), np.unique(ids_b))
    matched = sum(idx.shape[0] for _, idx in b2.recent)
    assert matched <= len(overlap), \
        "evicted history must not serve rows (only ids also in batch b)"


def test_recent_lru_invalidated_on_cache_refresh():
    ds, cache, loader = _compact_loader(recent_batches=4)
    cache.track_hotness = True
    rng = np.random.default_rng(10)
    ids = rng.integers(0, ds.num_nodes, 300)
    loader.load_compact(_FakeBatch(ids), recent_key="accel0")
    for _ in range(4):
        cache.lookup(rng.integers(0, ds.num_nodes, 400))
    assert cache.refresh(max_swap=16) > 0
    b = loader.load_compact(_FakeBatch(ids), recent_key="accel0")
    assert b.recent == [], \
        "a cache refresh must invalidate cross-iteration residency"
    assert b.rows.shape[0] > 0


def test_recent_lru_trainer_bit_identical_and_saves_bytes():
    ds = _ds()
    g = _gcfg(ds)
    base = dict(total_batch=128, n_accel=2, hybrid=False, use_drm=False,
                tfp_depth=2, cache_fraction=0.05, seed=0)
    t1 = HybridGNNTrainer(ds, g, HybridConfig(**base))
    h1 = t1.train(6)
    t1.close()
    t2 = HybridGNNTrainer(ds, g, HybridConfig(**base, recent_rows_batches=3))
    h2 = t2.train(6)
    t2.close()
    assert [m.loss for m in h1] == [m.loss for m in h2]
    f1, f2 = t1.feature_traffic(), t2.feature_traffic()
    assert f2["recent_saved_bytes"] > 0
    assert f2["shipped_bytes"] < f1["shipped_bytes"]
