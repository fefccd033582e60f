"""Out-of-core MmapFeatures backend: byte parity with the RAM backends
(duplicates, arbitrary order, empty requests, partition-boundary ids,
ragged last partition), bounded-RAM spill + reopen round trip, the
FeatureCache-over-mmap composition, the loader's partition-aligned
chunked gather, and end-to-end loss bit-identity vs the dense backend."""
import gc
import os

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import HybridConfig, HybridGNNTrainer
from repro.graph import (DenseFeatures, FeatureCache, FeatureLoader,
                         GNNConfig, HashedFeatures, MmapFeatures,
                         NumpySampler, make_dataset)

N, F, PROWS = 1000, 32, 96  # deliberately ragged: 1000 % 96 != 0

# module-level singleton (not a fixture: the hypothesis property test
# below cannot take fixture arguments under the deterministic shim).
# The spill lives in an owned temp dir, removed at GC/interpreter exit.
_CACHED = None


def _sources():
    global _CACHED
    if _CACHED is None:
        hashed = HashedFeatures(N, F, seed=3)
        dense = DenseFeatures(hashed.take(np.arange(N)))
        mm = MmapFeatures.spill(hashed, partition_rows=PROWS)
        _CACHED = (dense, mm)
    return _CACHED


@pytest.fixture(scope="module")
def sources():
    return _sources()


# ------------------------------------------------------------ byte parity


@given(st.lists(st.integers(0, N - 1), min_size=0, max_size=400))
@settings(max_examples=30, deadline=None)
def test_mmap_parity_property(rows):
    dense, mm = _sources()
    rows = np.asarray(rows, dtype=np.int64)
    a, b = dense.take(rows), mm.take(rows)
    assert a.tobytes() == b.tobytes()
    assert a.dtype == b.dtype and a.shape == b.shape


def test_mmap_parity_partition_boundaries(sources):
    dense, mm = sources
    # first/last row of each window, the ragged tail, dups, reverse order
    edges = []
    for pid in range(mm.num_partitions):
        lo = pid * PROWS
        edges += [lo, min(lo + PROWS, N) - 1]
    rows = np.array(edges + [N - 1, N - 1, 0] + edges[::-1], dtype=np.int64)
    assert dense.take(rows).tobytes() == mm.take(rows).tobytes()


def test_mmap_empty_request(sources):
    dense, mm = sources
    rows = np.empty(0, dtype=np.int64)
    out = mm.take(rows)
    assert out.shape == (0, F) and out.dtype == dense.dtype


def test_mmap_out_of_range_raises(sources):
    _, mm = sources
    with pytest.raises(IndexError):
        mm.take(np.array([N], dtype=np.int64))
    with pytest.raises(IndexError):
        mm.take(np.array([-1], dtype=np.int64))


# ------------------------------------------- spill writer + reopen


def test_spill_bounded_ram_and_layout(sources):
    _, mm = sources
    # the bounded-RAM guarantee: never more than one partition buffered
    assert 0 < mm.spill_peak_buffered_rows <= PROWS
    assert mm.num_partitions == -(-N // PROWS)
    assert mm.shape == (N, F)
    assert mm.nbytes_on_disk == N * F * 4
    # ragged last partition holds exactly the leftover rows
    last = mm._part(mm.num_partitions - 1)
    assert last.shape[0] == N - (mm.num_partitions - 1) * PROWS


def test_spill_reopen_round_trip(sources):
    dense, mm = sources
    reopened = MmapFeatures(mm.spill_dir)
    assert reopened.shape == mm.shape
    assert reopened.dtype == mm.dtype
    assert reopened.partition_rows == mm.partition_rows
    rows = np.arange(0, N, 3, dtype=np.int64)
    assert reopened.take(rows).tobytes() == dense.take(rows).tobytes()


def test_lazy_windows_and_touch_accounting(sources):
    _, mm = sources
    fresh = MmapFeatures(mm.spill_dir)
    assert fresh.resident_window_bytes == 0          # nothing mapped yet
    fresh.take(np.arange(8, dtype=np.int64))         # touches window 0 only
    assert fresh.resident_window_bytes == PROWS * F * 4
    assert 0 < fresh.last_gather_page_bytes <= PROWS * F * 4 + 4096
    assert fresh.touched_page_bytes >= fresh.last_gather_page_bytes
    fresh.reset_touch_stats()
    assert fresh.touched_page_bytes == 0
    fresh.close()
    assert fresh.resident_window_bytes == 0


def test_madvise_random_on_window_open(sources):
    """Every lazily-opened partition window gets the MADV_RANDOM readahead
    hint (where the platform supports it), and the hint changes nothing
    about gather results — madvise is advisory, byte parity must hold."""
    import mmap as mmap_mod
    dense, mm = sources
    fresh = MmapFeatures(mm.spill_dir)
    assert fresh.madvise_calls == 0                  # nothing mapped yet
    rows = np.arange(0, N, 7, dtype=np.int64)        # touches every window
    assert fresh.take(rows).tobytes() == dense.take(rows).tobytes()
    if hasattr(mmap_mod, "MADV_RANDOM"):             # guarded platforms
        assert fresh.madvise_calls == len(fresh._parts) > 0
        # reuse of an already-open window does not re-hint
        before = fresh.madvise_calls
        fresh.take(rows[:5])
        assert fresh.madvise_calls == before
    fresh.close()


# ----------------------------------------------- window LRU + prefetch

_LRU = None


def _lru_sources():
    global _LRU
    if _LRU is None:
        hashed = HashedFeatures(N, F, seed=3)
        dense = DenseFeatures(hashed.take(np.arange(N)))
        mm = MmapFeatures.spill(hashed, partition_rows=PROWS)
        _LRU = (dense, mm)
    return _LRU


def _window_nbytes(mm, pid):
    rows = min(mm.partition_rows, mm.shape[0] - pid * mm.partition_rows)
    return rows * mm.shape[1] * mm.dtype.itemsize


@given(st.integers(1, 5),
       st.lists(st.integers(0, -(-N // PROWS) - 1), min_size=1,
                max_size=60))
@settings(max_examples=30, deadline=None)
def test_window_lru_bound_order_and_accounting(k, pids):
    """Window-LRU properties against an exact model: the open-window
    count never exceeds ``lru_windows``, eviction order is LRU (the model
    is an ordered dict with move-to-front-on-access), and
    ``evicted_window_bytes`` accounting is exact (ragged last window
    included)."""
    dense, base = _lru_sources()
    mm = MmapFeatures(base.spill_dir, lru_windows=k)
    model: dict = {}            # insertion order == recency
    expect_evicted = expect_count = 0
    for pid in pids:
        mm.take(np.array([pid * PROWS], dtype=np.int64))
        model.pop(pid, None)
        model[pid] = True
        while len(model) > k:
            old = next(iter(model))
            del model[old]
            expect_evicted += _window_nbytes(mm, old)
            expect_count += 1
        assert mm.open_windows == len(model) <= k
        assert list(mm._parts) == list(model)       # exact LRU order
    assert mm.evicted_window_bytes == expect_evicted
    assert mm.window_evictions == expect_count
    # re-opened (previously evicted) windows reproduce gathers bit-for-bit
    rows = np.arange(0, N, 3, dtype=np.int64)
    assert mm.take(rows).tobytes() == dense.take(rows).tobytes()
    assert mm.open_windows <= max(k, 1)
    mm.close()


def test_window_lru_eviction_issues_dontneed(sources):
    import mmap as mmap_mod
    _, base = sources
    mm = MmapFeatures(base.spill_dir, lru_windows=1)
    for pid in range(3):
        mm.take(np.array([pid * PROWS], dtype=np.int64))
    assert mm.window_evictions == 2
    if hasattr(mmap_mod, "MADV_DONTNEED"):
        assert mm.madvise_dontneed_calls == 2
    mm.close()


def test_window_lru_tightened_after_open_trims_on_access(sources):
    """Setting ``lru_windows`` after windows are already mapped (the
    trainer wires the bound before the cache boot gather, but users can
    tighten it any time) takes effect on the next access."""
    _, base = sources
    mm = MmapFeatures(base.spill_dir)
    rows = np.arange(0, N, 7, dtype=np.int64)          # touches every window
    mm.take(rows)
    assert mm.open_windows == mm.num_partitions
    mm.lru_windows = 2
    mm.take(np.array([0], dtype=np.int64))
    assert mm.open_windows <= 2
    mm.close()


def test_window_lru_zero_is_unbounded_legacy(sources):
    _, base = sources
    mm = MmapFeatures(base.spill_dir)                  # lru_windows=0
    mm.take(np.arange(0, N, 7, dtype=np.int64))
    assert mm.window_evictions == 0
    assert mm.evicted_window_bytes == 0
    assert mm.open_windows == mm.num_partitions
    mm.close()


def test_prefetch_rows_warms_pages_and_counters(sources):
    dense, base = sources
    mm = MmapFeatures(base.spill_dir, lru_windows=4)
    rng = np.random.default_rng(11)
    rows = np.unique(rng.integers(0, 2 * PROWS, 120)).astype(np.int64)
    new = mm.prefetch_rows(rows)
    assert new > 0 and mm.prefetched_window_bytes == new
    cold0 = mm.cold_fault_page_bytes
    out = mm.take(rows)
    assert out.tobytes() == dense.take(rows).tobytes()
    assert mm.cold_fault_page_bytes == cold0           # fully pre-faulted
    assert mm.prefetch_hit_rate == 1.0
    # an unprefetched window is a cold fault + prefetch miss
    mm.take(np.array([3 * PROWS], dtype=np.int64))
    assert mm.cold_fault_page_bytes > cold0
    assert mm.prefetch_miss_windows == 1
    # re-prefetching already-resident pages faults nothing new
    assert mm.prefetch_rows(rows) == 0
    mm.reset_prefetch_stats()
    assert mm.prefetched_window_bytes == 0
    assert mm.prefetch_hit_rate == 0.0
    mm.close()


def test_prefetch_rows_out_of_range_raises(sources):
    _, base = sources
    mm = MmapFeatures(base.spill_dir)
    with pytest.raises(IndexError):
        mm.prefetch_rows(np.array([N], dtype=np.int64))
    mm.close()


def test_eviction_makes_pages_cold_again(sources):
    """An evicted window's pages were dropped: the next gather of the
    same rows must account them cold again (and still be bit-correct)."""
    dense, base = sources
    mm = MmapFeatures(base.spill_dir, lru_windows=1)
    rows = np.arange(8, dtype=np.int64)                 # window 0
    mm.take(rows)
    cold1 = mm.cold_fault_page_bytes
    mm.take(rows)                                       # warm: no new cold
    assert mm.cold_fault_page_bytes == cold1
    mm.take(np.array([PROWS], dtype=np.int64))          # evicts window 0
    out = mm.take(rows)                                 # re-fault: cold again
    assert mm.cold_fault_page_bytes > cold1
    assert out.tobytes() == dense.take(rows).tobytes()
    mm.close()


def test_prefetch_pinned_window_survives_lru_pressure(sources):
    """Regression at LRU bound == prefetched-working-set size: windows a
    prefetch pre-faulted are pinned until their first post-prefetch
    gather, so unrelated accesses squeezing the LRU cannot throw the
    prefetch work away right before its consumer arrives.  The LRU runs
    transiently over-bound instead (counted), and re-trims once the
    gather releases the pins."""
    dense, base = sources
    mm = MmapFeatures(base.spill_dir, lru_windows=2)
    rng = np.random.default_rng(5)
    # the prefetched working set spans exactly lru_windows windows {0, 1}
    rows = np.unique(rng.integers(0, 2 * PROWS, 100)).astype(np.int64)
    mm.prefetch_rows(rows)
    # unrelated accesses push past the bound: the unpinned newcomer is
    # the only legal victim, the pinned prefetched windows must survive
    mm.take(np.array([2 * PROWS], dtype=np.int64))
    mm.take(np.array([3 * PROWS], dtype=np.int64))
    assert 0 in mm._parts and 1 in mm._parts
    assert mm.pin_blocked_evictions >= 1
    assert mm.open_windows == 3                  # transiently over-bound
    # the consumer's gather: zero cold faults (the pinned pages survived),
    # bit-identical bytes, and the pins release
    cold0 = mm.cold_fault_page_bytes
    out = mm.take(rows)
    assert out.tobytes() == dense.take(rows).tobytes()
    assert mm.cold_fault_page_bytes == cold0
    assert mm.prefetch_hit_windows >= 2
    assert not mm._pinned
    # with the pins gone the next access re-trims under the bound
    mm.take(np.array([4 * PROWS], dtype=np.int64))
    assert mm.open_windows <= 2
    mm.close()


def test_unpinned_eviction_order_unchanged(sources):
    """Without a prefetch in flight the pin set is empty: eviction stays
    plain LRU and the bound holds exactly (the pre-pinning contract)."""
    _, base = sources
    mm = MmapFeatures(base.spill_dir, lru_windows=2)
    for pid in range(4):
        mm.take(np.array([pid * PROWS], dtype=np.int64))
        assert mm.open_windows <= 2
    assert mm.window_evictions == 2
    assert mm.pin_blocked_evictions == 0
    mm.close()


def test_owned_tempdir_spill_cleans_up_on_gc():
    mm = MmapFeatures.spill(HashedFeatures(64, 4, seed=0), partition_rows=16)
    spill = mm.spill_dir
    assert os.path.exists(os.path.join(spill, "manifest.json"))
    del mm
    gc.collect()
    assert not os.path.exists(spill)


def test_reopen_rejects_non_spill_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        MmapFeatures(str(tmp_path))


# --------------------------------------------------- composition layers


def test_feature_cache_over_mmap(sources):
    dense, mm = sources
    hotness = np.arange(N, 0, -1, dtype=np.float64)  # node 0 hottest
    cache = FeatureCache(mm, hotness, capacity=64)
    assert np.array_equal(np.sort(cache.cached_ids), np.arange(64))
    ids = np.array([0, 63, 64, N - 1, 0, 500], dtype=np.int64)
    look = cache.lookup(ids)
    hit = look.slots >= 0
    got = np.empty((ids.shape[0], F), np.float32)
    got[hit] = cache._host_rows[look.slots[hit]]
    got[~hit] = mm.take(look.miss_ids)[look.miss_index[~hit]]
    assert np.array_equal(got, dense.take(ids))


def test_make_dataset_mmap_matches_dense(tmp_path):
    kw = dict(scale=0.001, seed=0, partition_rows=512)
    ds_m = make_dataset("ogbn-products", feature_backend="mmap",
                        spill_dir=str(tmp_path / "spill"), **kw)
    ds_d = make_dataset("ogbn-products", feature_backend="dense",
                        scale=0.001, seed=0)
    assert isinstance(ds_m.features, MmapFeatures)
    rows = np.arange(0, ds_m.num_nodes, 7, dtype=np.int64)
    assert np.array_equal(ds_m.take_features(rows), ds_d.take_features(rows))


def test_loader_partition_aligned_chunks_disjoint(tmp_path):
    ds = make_dataset("ogbn-products", scale=0.002, seed=0,
                      feature_backend="mmap",
                      spill_dir=str(tmp_path / "spill"), partition_rows=256)
    loader = FeatureLoader(ds, num_threads=4)
    rng = np.random.default_rng(1)
    rows = rng.integers(0, ds.num_nodes, 4000).astype(np.int64)
    # chunks cut at partition boundaries -> threads fault disjoint windows
    chunks, order = loader._split_chunks(rows)
    assert order is not None
    touched = [set(np.unique(c // 256).tolist()) for c in chunks]
    for i in range(len(touched)):
        for j in range(i + 1, len(touched)):
            assert not (touched[i] & touched[j]), "windows overlap"
    assert sum(c.shape[0] for c in chunks) == rows.shape[0]
    # and the gather stays byte-identical to the single-thread path
    assert np.array_equal(loader._gather(rows)[0], ds.take_features(rows))
    loader.close()


def test_loader_unpartitioned_split_unchanged():
    ds = make_dataset("ogbn-products", scale=0.001, seed=0,
                      feature_backend="dense")
    loader = FeatureLoader(ds, num_threads=3)
    rows = np.arange(300, dtype=np.int64)[::-1].copy()
    chunks, order = loader._split_chunks(rows)
    assert order is None          # legacy order-preserving array_split
    assert np.array_equal(np.concatenate(chunks), rows)
    assert np.array_equal(loader._gather(rows)[0], ds.take_features(rows))
    loader.close()


# ------------------------------------------------ end-to-end bit identity


def test_mmap_training_loss_bit_identical(tmp_path):
    """The acceptance check at test scale: training over the mmap backend
    is bit-identical to the dense backend at the same seed (the backend is
    purely a capacity knob), and the trainer prices the disk tier."""
    g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                  num_classes=47)

    def run(backend, **kw):
        ds = make_dataset("ogbn-products", scale=0.003, seed=0,
                          feature_backend=backend, **kw)
        cfg = HybridConfig(total_batch=128, n_accel=2, hybrid=False,
                           use_drm=False, tfp_depth=2, seed=0,
                           cache_fraction=0.2)
        tr = HybridGNNTrainer(ds, g, cfg)
        tr.train(4)
        return tr

    dense = run("dense")
    mmap = run("mmap", spill_dir=str(tmp_path / "spill"),
               partition_rows=1024)
    assert [m.loss for m in dense.history] == [m.loss for m in mmap.history]
    assert mmap.feature_tier == "disk" and dense.feature_tier == "ram"
    # the mmap run's gather working set stayed a strict subset of the
    # matrix: only touched pages are resident
    src = mmap.dataset.features
    assert 0 < src.touched_page_bytes
    mmap.loader.close()
    dense.loader.close()


def test_hybrid_mapping_prices_disk_tier(tmp_path):
    """feature_tier plumbing: a hybrid trainer over mmap features prices
    Eq. 7 at storage bandwidth; shares stay conserved."""
    g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
                  num_classes=47)
    ds = make_dataset("ogbn-products", scale=0.003, seed=0,
                      feature_backend="mmap",
                      spill_dir=str(tmp_path / "spill"), partition_rows=1024)
    cfg = HybridConfig(total_batch=256, n_accel=2, hybrid=True,
                       use_drm=True, tfp_depth=2, share_quantum=32, seed=0,
                       cache_fraction=0.2)
    tr = HybridGNNTrainer(ds, g, cfg)
    assert tr.feature_tier == "disk"
    hist = tr.train(4)
    assert all(np.isfinite(m.loss) for m in hist)
    for m in hist:
        cpu_b, accel_b = m.assignment
        assert cpu_b + accel_b * cfg.n_accel == cfg.total_batch
    tr.loader.close()
