"""Device-resident hot-feature cache (static, degree-ordered) + frontier
deduplication.

HyScale-GNN hides host->device feature traffic behind prefetching; the
complementary levers (DistDGL-style hybrid systems, and the dominant ones
on feature-traffic-bound workloads) are to *not send* rows at all:

  * power-law frontiers are dominated by hub nodes, so pinning the top-K
    hottest node features in device memory converts most of each
    iteration's gather into a device-local lookup, and
  * with-replacement neighbor sampling re-references the same vertices
    many times per mini-batch, so gathering/shipping one row per *unique*
    node id (the paper's Feature-Duplicator rationale, Section IV-C:
    fetch once, duplicate locally) removes the remaining redundancy.

The cache *boots* static: hotness is the expected gather frequency under
neighbor sampling (``GraphDataset.feature_hotness`` — in-edge mass + 1),
known at dataset-build time.  On workloads where the sampled hub set
drifts (or on graphs whose degree distribution is a poor hotness proxy)
the boot-time snapshot decays, so the cache also supports DistDGL-style
*dynamic admission*: with hotness tracking enabled (opt-in), every lookup
accumulates per-slot hit counters and a decayed hotness estimate for the
uncached ids it missed on, and
``refresh()`` evicts the coldest slots in favor of strictly-hotter
uncached nodes — updating the device-resident block in place with the
``cache_update`` scatter kernel (one sublane-aligned row block rewritten
per block that holds admitted rows) instead of re-uploading all K rows.

Refreshing while the TFP pipeline has batches in flight needs a
consistency protocol: a lookup classified against the slot table at
version v must be combined against the *version-v* device block, or the
positional slot indices would read rows that were since evicted.  The
cache therefore keeps a monotonically increasing ``version``; every
``CacheLookup`` records the version it was classified against, old
versions are reconstructable for the last ``keep_versions`` bumps (sized
to the pipeline depth by the trainer), and ``data_on(device,
version=...)`` serves the matching block.  A refresh can thus never
corrupt batches already past the load stage.  Retention is an
O(swapped_rows) *undo log*, not full blocks: each version bump stores
only the evicted rows (slot indices + old row values), and an old host
block is rebuilt on demand by applying the log backwards from the
current one — device blocks already placed for an in-flight version stay
memoized until the pin protocol (or the ``keep_versions`` window)
retires them.

Components:

  * ``slot_of``  — vectorized id->slot lookup, one int32 per node, -1 for
    uncached.  4 B/node of host memory buys O(1) batch partitioning
    (papers100M scale: ~440 MB, far below the feature matrix it indexes).
    Refresh swaps in a rebuilt table atomically; lookups snapshot the
    reference, so a concurrent refresh can never tear a classification.
  * ``data_on(device, version=None)`` — the [K, F] hot-row block resident
    on ``device`` at the requested (default: current) version.
  * ``stage()`` / ``commit()`` — the refresh split into its expensive and
    cheap halves: ``stage`` plans the evict-coldest / admit-hottest swap
    (with an admission-hysteresis margin against boundary thrash) and
    gathers the admitted rows from the FeatureSource *outside* the cache
    lock — on the disk tier that gather used to block an iteration
    boundary, and can now run in a background thread; ``commit`` only
    swaps tables / scatter-updates device blocks, bumps ``version`` and
    resets the epoch stats window.  ``refresh()`` = stage + commit.
  * ``compact_lookup(ids)`` — cache-free frontier deduplication: unique
    ids + int32 inverse map, shared by cached and uncached transfer paths.
  * ``lookup(ids, dedup=True)`` — deduplicates the frontier, classifies
    only the uniques against the cache, and returns (slots, miss_index,
    miss_ids) where ``miss_ids`` holds one entry per *unique* miss and the
    positional tables point many frontier positions at one shipped row.

The loader (``featload.FeatureLoader``) gathers only ``miss_ids`` on the
host; the transfer stage ships the unique misses and a combine step
(Pallas tiled ``cache_combine`` kernel or its jnp reference) expands them
back into the dense positional layer-0 input on device — the duplication
happens after the interconnect, for free.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.annotations import guarded_by, requires_lock

from .storage import FeatureSource, as_feature_source

__all__ = ["CacheLookup", "CacheStats", "FeatureCache", "ShardLookup",
           "ShardPlacement", "ShardedFeatureCache", "UnionLookup",
           "build_cache", "build_sharded_cache", "compact_lookup",
           "wire_row_bytes"]


def wire_row_bytes(feat_dim: int, transfer_dtype: str) -> int:
    """Bytes one feature row occupies on the wire (the transfer dtype) —
    the single definition both the cache and the loader account with."""
    return int(feat_dim) * np.dtype(
        np.float32 if transfer_dtype == "float32" else transfer_dtype
    ).itemsize


@dataclasses.dataclass
class CacheLookup:
    """Result of partitioning one frontier against the cache.

    The positional tables (``slots``/``miss_index``) always describe the
    full [N]-row frontier the GNN consumes.  Under deduplication the miss
    block is compacted to one row per unique miss id, so several positions
    share a ``miss_index`` entry — the on-device combine expands them.
    """
    ids: np.ndarray         # int64 [N] the queried node ids (positional)
    slots: np.ndarray       # int32 [N] cache slot per position, -1 = miss
    miss_index: np.ndarray  # int32 [N] row into the miss block (0 for hits)
    miss_ids: np.ndarray    # int64 [M] node ids to gather on the host
    unique_ids: np.ndarray  # int64 [U] deduped frontier (sorted; == ids
                            #   when dedup is off)
    inverse: np.ndarray     # int32 [N] position -> row in unique_ids
    version: int = 0        # cache version this lookup was classified
                            #   against — the combine stage must pair the
                            #   slot table with the same-version device
                            #   block (0 for cache-less lookups)

    @property
    def num_rows(self) -> int:
        return int(self.ids.shape[0])

    @property
    def num_unique(self) -> int:
        return int(self.unique_ids.shape[0])

    @property
    def num_miss(self) -> int:
        """Rows in the miss block (unique misses under dedup)."""
        return int(self.miss_ids.shape[0])

    @property
    def num_hit(self) -> int:
        """Frontier *positions* served by the cache."""
        return int(np.count_nonzero(self.slots >= 0))

    @property
    def miss_positions(self) -> int:
        return self.num_rows - self.num_hit

    @property
    def dup_miss_rows(self) -> int:
        """Positional miss rows that alias an already-shipped unique row."""
        return self.miss_positions - self.num_miss

    @property
    def hit_rate(self) -> float:
        return self.num_hit / max(self.num_rows, 1)

    @property
    def dup_factor(self) -> float:
        """Frontier duplication factor (positions per unique id, >= 1)."""
        return self.num_rows / max(self.num_unique, 1)


@dataclasses.dataclass
class CacheStats:
    lookups: int = 0
    hit_rows: int = 0        # frontier positions served by the cache
    miss_rows: int = 0       # frontier positions not in the cache
    unique_rows: int = 0     # unique ids across lookups (== total when
                             #   dedup is off)
    saved_bytes: int = 0     # host->device bytes avoided by cache hits
    dedup_saved_bytes: int = 0  # bytes avoided by shipping unique misses

    @property
    def total_rows(self) -> int:
        return self.hit_rows + self.miss_rows

    @property
    def hit_rate(self) -> float:
        return self.hit_rows / max(self.total_rows, 1)

    def merge(self, other: "CacheStats") -> None:
        self.lookups += other.lookups
        self.hit_rows += other.hit_rows
        self.miss_rows += other.miss_rows
        self.unique_rows += other.unique_rows
        self.saved_bytes += other.saved_bytes
        self.dedup_saved_bytes += other.dedup_saved_bytes


def compact_lookup(ids: np.ndarray,
                   slot_of: Optional[np.ndarray] = None) -> CacheLookup:
    """Deduplicate a frontier and (optionally) classify it against a cache.

    Computes the frontier's unique ids + int32 inverse map once
    (``np.unique``-based), classifies only the uniques against ``slot_of``
    (all-miss when ``None``), and builds the positional ``slots`` /
    ``miss_index`` tables by broadcasting the per-unique verdicts back
    through the inverse map — so the miss block holds one row per unique
    miss and many positions point at the same shipped row.
    """
    ids = np.asarray(ids, dtype=np.int64)
    unique_ids, inverse = np.unique(ids, return_inverse=True)
    inverse = inverse.astype(np.int32)
    if slot_of is None:
        uniq_slots = np.full(unique_ids.shape[0], -1, dtype=np.int32)
    else:
        uniq_slots = slot_of[unique_ids]
    is_miss = uniq_slots < 0
    # rank of each unique miss among the misses = its row in the miss block
    uniq_miss_index = np.cumsum(is_miss, dtype=np.int32)
    uniq_miss_index = np.where(is_miss, uniq_miss_index - 1, 0
                               ).astype(np.int32)
    return CacheLookup(ids=ids, slots=uniq_slots[inverse],
                       miss_index=uniq_miss_index[inverse],
                       miss_ids=unique_ids[is_miss],
                       unique_ids=unique_ids, inverse=inverse)


@dataclasses.dataclass
class _StagedRefresh:
    """A planned-and-gathered refresh awaiting its cheap ``commit()``.

    ``base_version`` pins the slot table the plan was computed against: a
    commit (from any path) bumps the version, so a plan staged against an
    older table is stale and discarded instead of applied."""
    base_version: int
    top: np.ndarray       # admitted candidate ids (may be empty)
    cold: np.ndarray      # victim slot indices, int64, same length
    rows: np.ndarray      # gathered admitted rows in transfer dtype


# one lock covers the (slot_of, version) pair, the hotness counters, the
# stats windows, the staged plan and the version-retention state (undo
# log + floor + memoized device blocks).
# Deliberately undeclared: capacity/feat_dim/row_bytes (immutable),
# track_hotness/keep_versions/use_pallas_update/kernel_pipeline_depth/
# refresh_* (config knobs, set before any worker thread starts).
@guarded_by("_lock", "slot_of", "version", "cached_ids", "stats",
            "epoch_stats", "stage_failures", "refreshes",
            "refresh_swapped_rows", "_staged", "_slot_hot", "_node_hot",
            "_host_rows", "_undo", "_floor", "_device_data", "_devices",
            "_inflight")
class FeatureCache:
    """Top-K hot-row cache over any ``FeatureSource``.

    Boots static: ``capacity`` rows are chosen by descending ``hotness``
    and the hot block is materialized once on the host (in
    ``transfer_dtype``) and placed per device on first use.  From there
    every lookup feeds decayed hotness counters, and ``refresh()`` adapts
    the resident set to the *observed* access distribution (DistDGL-style
    admission) with versioned device snapshots for in-flight consistency.
    """

    def __init__(self, source: "FeatureSource | np.ndarray",
                 hotness: np.ndarray, capacity: int,
                 transfer_dtype: str = "float32",
                 refresh_decay: float = 0.5,
                 max_refresh_frac: float = 0.25,
                 refresh_hysteresis: float = 1.25):
        source = as_feature_source(source)
        num_nodes, feat_dim = source.shape
        capacity = int(max(0, min(capacity, num_nodes)))
        hotness = np.asarray(hotness, dtype=np.float64)
        if hotness.shape[0] != num_nodes:
            raise ValueError("hotness must have one entry per node")
        # stable order so equal-hotness ties are deterministic across runs
        order = np.argsort(-hotness, kind="stable")[:capacity]
        self.source = source
        self.transfer_dtype = transfer_dtype
        self.cached_ids = np.ascontiguousarray(order.astype(np.int64))
        self.capacity = capacity
        self.num_nodes = int(num_nodes)
        self.feat_dim = int(feat_dim)
        self.row_bytes = wire_row_bytes(feat_dim, transfer_dtype)
        self.slot_of = np.full(num_nodes, -1, dtype=np.int32)
        self.slot_of[self.cached_ids] = np.arange(capacity, dtype=np.int32)
        # the boot gather is maintenance, not load-stage traffic: exclude
        # it from a storage tier's stall/prefetch-hit counters
        self._host_rows = np.ascontiguousarray(
            self._cast_rows(self._maintenance_take(self.cached_ids)))
        self._expected_hit_rate = (float(hotness[self.cached_ids].sum())
                                   / max(float(hotness.sum()), 1e-12))
        self.stats = CacheStats()        # lifetime totals (traffic accounting)
        self.epoch_stats = CacheStats()  # since the last refresh (feedback)
        # ---- dynamic-refresh state -------------------------------------
        # one lock covers the (slot_of, version) pair, the hotness
        # counters, and the stats windows: lookups snapshot the table +
        # version together, refresh swaps them together
        self._lock = threading.RLock()
        self.version = 0
        self.keep_versions = 2           # trainer sizes this to tfp_depth+2
        self.use_pallas_update = False   # scatter-update kernel dispatch
        self.kernel_pipeline_depth = 1   # >1: multi-buffered scatter DMAs
        self.refresh_decay = float(refresh_decay)
        self.max_refresh_frac = float(max_refresh_frac)
        # admission hysteresis: a candidate must be hotter than its victim
        # by this factor to swap — a hub set oscillating right at the
        # admission boundary would otherwise thrash (swap in/out every
        # window).  1.0 reproduces the plain strictly-hotter policy.
        self.refresh_hysteresis = float(refresh_hysteresis)
        self.refreshes = 0               # refresh() calls that moved rows
        self.refresh_swapped_rows = 0
        self.fault_injector = None       # optional FaultInjector (hook:
                                         #   "refresh.stage")
        self.stage_failures = 0          # stage() attempts that raised
        self._staged: Optional[_StagedRefresh] = None
        # decayed hotness estimates: frontier *positions* observed per
        # cached slot / per uncached node since (decay-weighted) forever.
        # float32 keeps the uncached estimate at 4 B/node — same budget as
        # slot_of.  Tracking is opt-in (refresh-aware paths — the trainer
        # under its cache_refresh knob, the policy benchmark — switch it
        # on): a static cache pays neither the per-lookup scattered adds
        # nor the full-length estimate, which allocates lazily on the
        # first tracked lookup.
        self.track_hotness = False
        self._slot_hot = np.zeros(capacity, dtype=np.float32)
        self._node_hot: Optional[np.ndarray] = None
        # version retention: an O(swapped_rows) undo log instead of full
        # [K, F] blocks per version.  ``_undo[v]`` holds (victim slots,
        # their version-v row values) — the delta that rebuilds the
        # version-v host block from version v+1.  ``_floor`` is the
        # lowest still-reconstructable version; a device that never
        # placed a block before a refresh can still materialize any
        # retained version an in-flight lookup was classified against.
        self._undo: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._floor = 0
        self._device_data: Dict[Tuple[int, int], jax.Array] = {}
        self._devices: Dict[int, Any] = {}   # id(device) -> device handle
        # in-flight lookup pins: version -> count of pinned lookups not
        # yet released.  Pinning (lookup(pin=True) + release_lookup) is
        # the opt-in eager-retirement protocol: once every pin at a
        # version is released and a newer version exists, its full [K, F]
        # blocks are retired immediately instead of lingering for the
        # whole keep_versions window (ROADMAP undo-log item, cheap half).
        # keep_versions stays the hard retention bound either way, so
        # callers that never pin keep the PR-4 semantics exactly.
        self._inflight: Dict[int, int] = {}
        self._pin_used = False

    def _cast_rows(self, rows: np.ndarray) -> np.ndarray:
        if self.transfer_dtype != "float32":
            import jax.numpy as jnp
            rows = rows.astype(jnp.dtype(self.transfer_dtype))
        return rows

    def _maintenance_take(self, rows: np.ndarray) -> np.ndarray:
        """Gather rows as cache maintenance: on sources with stall
        accounting (``MmapFeatures``), excluded from the cold/warm and
        prefetch-hit counters — boot and refresh-admission gathers are
        not load-stage traffic and must not skew the stall metrics the
        task mapping re-prices on."""
        ctx = getattr(self.source, "untracked_gathers", None)
        if ctx is None:
            return self.source.take(rows)
        with ctx():
            return self.source.take(rows)

    # ------------------------------------------------------------- plumbing

    @property
    def nbytes(self) -> int:
        """Device bytes pinned by the hot block (per trainer device)."""
        with self._lock:
            return self._host_rows.nbytes

    @property
    def expected_hit_rate(self) -> float:
        """Design-time hit-rate estimate (hotness mass covered) — feeds the
        performance model's Eq. 7/8 cache term before any measurement."""
        return self._expected_hit_rate

    def measured_hit_rate(self) -> float:
        """Measured positional hit rate over the *current epoch window*
        (reset by ``refresh()``), so feedback consumers see the
        post-refresh rate instead of a lifetime average that still carries
        pre-refresh epochs; lifetime totals stay in ``stats``.

        Snapshotted under the cache lock: ``record_lookup`` merges the
        windows from the pipeline's load-stage thread, and an unlocked
        read could observe a half-merged (hit_rows bumped, miss_rows not
        yet) window — a torn hit rate that feedback consumers would act
        on."""
        with self._lock:
            if self.epoch_stats.total_rows:
                return self.epoch_stats.hit_rate
            return self.stats.hit_rate

    def slot_hotness(self) -> np.ndarray:
        """Decayed per-slot hotness estimate (copy, for tests/policy)."""
        with self._lock:
            return self._slot_hot.copy()

    def uncached_hotness(self, ids: np.ndarray) -> np.ndarray:
        """Decayed hotness estimate of (uncached) node ids (copy)."""
        ids = np.asarray(ids, dtype=np.int64)
        with self._lock:
            if self._node_hot is None:
                return np.zeros(ids.shape[0], dtype=np.float32)
            return self._node_hot[ids].copy()

    def data_on(self, device, version: Optional[int] = None) -> jax.Array:
        """The [K, F] hot block resident on ``device`` at ``version``
        (default: current).  Blocks are placed lazily: an old version's
        host block is rebuilt by applying the O(swapped_rows) undo log
        backwards from the current block — a device that never placed a
        block before a refresh can still materialize any retained version
        an in-flight lookup was classified against.  Versions older than
        the retention floor are gone for good: asking for one is a
        consistency bug and raises instead of silently serving
        mismatched rows."""
        with self._lock:
            ver = self.version if version is None else int(version)
            key = (id(device), ver)
            arr = self._device_data.get(key)
            if arr is None:
                if ver < self._floor or ver > self.version:
                    raise RuntimeError(
                        f"cache version {ver} retired (current "
                        f"{self.version}, keep_versions="
                        f"{self.keep_versions}): a lookup outlived the "
                        f"refresh retention window — raise keep_versions")
                host = self._host_rows
                if ver < self.version:
                    # walk the undo log backwards: each entry restores
                    # the rows its version bump evicted
                    host = host.copy()
                    for v in range(self.version - 1, ver - 1, -1):
                        slots, old_rows = self._undo[v]
                        host[slots] = old_rows
                # deliberate device dispatch under the lock: lazy
                # placement is memoized, so this runs once per (device,
                # version) — serializing it prevents two threads from
                # shipping the same [K, F] block twice
                arr = jax.device_put(host, device)  # noqa: RPR103 - memoized once per (device, version)
                self._device_data[key] = arr
                self._devices[id(device)] = device
        return arr

    # --------------------------------------------------------------- lookup

    def lookup(self, ids: np.ndarray, dedup: bool = True,
               record: bool = True, pin: bool = False) -> CacheLookup:
        """Partition one frontier into cached slots and miss rows.

        ``dedup=True`` (the default) classifies only the frontier's unique
        ids and compacts the miss block to one row per unique miss;
        ``dedup=False`` reproduces the legacy positional path (one miss
        row per frontier position, in frontier order).

        Hit/miss stats always count frontier *positions* so the measured
        ``hit_rate`` stays comparable to ``expected_hit_rate`` regardless
        of dedup; the bytes dedup avoids are in ``dedup_saved_bytes``.

        The (slot table, version) pair is snapshotted atomically, so a
        concurrent ``refresh()`` can never tear a classification; the
        returned lookup's ``version`` tells the combine stage which device
        snapshot to pair it with.  Each lookup also feeds the refresh
        policy's decayed hotness counters (positions per slot / per
        uncached id) — unless ``record=False``, in which case the caller
        classifies first and accounts later via ``record_lookup`` (the
        loader uses this so a gather that fails mid-way never leaves
        half-recorded stats behind).

        ``pin=True`` additionally registers the classification version as
        *in flight* — atomically with the snapshot, so a concurrent
        commit can never land between the two — and the caller promises
        exactly one ``release_lookup(look)`` once the dependent combine
        consumed its device block.  Pinned versions retire eagerly on
        release (see ``release_lookup``); unpinned callers keep the plain
        ``keep_versions`` retention window.
        """
        ids = np.asarray(ids, dtype=np.int64)
        slot_of, ver = self.snapshot(pin=1 if pin else 0)
        if dedup:
            look = compact_lookup(ids, slot_of)
        else:
            slots = slot_of[ids]
            is_miss = slots < 0
            miss_index = np.cumsum(is_miss, dtype=np.int32)
            miss_index = np.where(is_miss, miss_index - 1, 0
                                  ).astype(np.int32)
            look = CacheLookup(
                ids=ids, slots=slots, miss_index=miss_index,
                miss_ids=ids[is_miss], unique_ids=ids,
                inverse=np.arange(ids.shape[0], dtype=np.int32))
        look.version = ver
        if record:
            self.record_lookup(look)
        return look

    def snapshot(self, pin: int = 0) -> Tuple[np.ndarray, int]:
        """Atomically snapshot the (slot table, version) pair.  ``pin``
        registers that many in-flight references at the snapshot version
        (each owing one ``release_version``) — atomic with the snapshot,
        so a concurrent commit can never land between the two.  The
        sharded plane snapshots every shard once per union lookup and
        pins one reference per trainer."""
        with self._lock:
            if pin:
                self._pin_used = True
                self._inflight[self.version] = \
                    self._inflight.get(self.version, 0) + int(pin)
            # refresh swaps the slot_of reference, never mutates the
            # array in place, so the returned table is immutable
            return self.slot_of, self.version

    def release_lookup(self, look: CacheLookup) -> None:
        """Release one ``lookup(pin=True)`` registration.

        When the last pin at a version drops and a newer version exists,
        every retained block/undo entry of versions below the minimum
        still-in-flight one is retired immediately — the pipelined
        trainer holds at most tfp_depth lookups in flight, so device
        memory returns to one block per device as soon as the pipeline
        drains instead of after ``keep_versions`` further refreshes.
        Idempotence is the caller's job (exactly one release per pinned
        lookup); releasing an unpinned lookup is a no-op."""
        self.release_version(int(look.version))

    def release_version(self, version: int) -> None:
        """Release one pinned reference at ``version`` (the primitive
        behind ``release_lookup``; the sharded plane releases per-shard
        pins through it directly)."""
        with self._lock:
            ver = int(version)
            n = self._inflight.get(ver)
            if n is None:
                return
            if n > 1:
                self._inflight[ver] = n - 1
            else:
                del self._inflight[ver]
            self._retire_below_floor()

    @requires_lock("_lock")
    def _retire_below_floor(self) -> None:
        # caller holds _lock.  Retire versions no pinned lookup can still
        # reference; without any pinning opt-in the keep_versions window
        # in commit() remains the only retirement (PR-4 semantics).
        if not self._pin_used:
            return
        floor = min(self._inflight) if self._inflight else self.version
        floor = min(floor, self.version)   # never retire the current block
        if floor > self._floor:
            self._floor = floor
        for key in [k for k in self._device_data if k[1] < self._floor]:
            del self._device_data[key]
        for v in [v for v in self._undo if v < self._floor]:
            del self._undo[v]

    def retained_versions(self) -> list:
        """Sorted cache versions still reconstructable (the current one
        always included) — observability for tests/health."""
        with self._lock:
            return list(range(self._floor, self.version + 1))

    def retained_bytes(self) -> int:
        """Host bytes held by the version-retention undo log —
        O(swapped_rows per retained version), NOT full [K, F] blocks.
        The live current block is working state, not retention, and is
        excluded."""
        with self._lock:
            return sum(slots.nbytes + rows.nbytes
                       for slots, rows in self._undo.values())

    def record_lookup(self, look: CacheLookup) -> None:
        """Account one classified lookup: stats windows + hotness
        counters, applied atomically under the cache lock.  Split out of
        ``lookup`` so deferred-accounting callers (``record=False``) can
        commit the stats only once the dependent gather succeeded."""
        delta = CacheStats(
            lookups=1, hit_rows=look.num_hit,
            miss_rows=look.miss_positions, unique_rows=look.num_unique,
            saved_bytes=look.num_hit * self.row_bytes,
            dedup_saved_bytes=look.dup_miss_rows * self.row_bytes)
        hit = look.slots >= 0
        with self._lock:
            self.stats.merge(delta)
            self.epoch_stats.merge(delta)
            # hotness accounting: one count per frontier *position* (the
            # quantity the measured hit rate is defined over).  A lookup
            # classified at an older version lands its counts on the
            # current tables — bounded noise, the admission policy only
            # compares decayed estimates.  Gated so static-cache runs
            # (refresh off) keep the old lookup cost and never allocate
            # the full-length estimate.
            if self.track_hotness:
                if self._node_hot is None:
                    self._node_hot = np.zeros(self.num_nodes,
                                              dtype=np.float32)
                if self.capacity:
                    np.add.at(self._slot_hot, look.slots[hit],
                              np.float32(1.0))
                np.add.at(self._node_hot, look.ids[~hit], np.float32(1.0))

    def record_access(self, hit_slots: np.ndarray, hit_counts: np.ndarray,
                      miss_ids: np.ndarray, miss_counts: np.ndarray,
                      lookups: int = 1) -> None:
        """Account a pre-aggregated, position-weighted access pattern.

        The sharded plane classifies whole frontiers against their owner
        shards and records each shard's share in one call: ``hit_slots``
        / ``miss_ids`` are unique entries, ``*_counts`` carry how many
        frontier positions referenced each — the same position-weighted
        quantities ``record_lookup`` derives from a ``CacheLookup``, so
        hit rates and hotness estimates stay comparable across modes."""
        hit_rows = int(hit_counts.sum()) if hit_counts.size else 0
        miss_rows = int(miss_counts.sum()) if miss_counts.size else 0
        delta = CacheStats(
            lookups=int(lookups), hit_rows=hit_rows, miss_rows=miss_rows,
            unique_rows=int(hit_slots.shape[0] + miss_ids.shape[0]),
            saved_bytes=hit_rows * self.row_bytes)
        with self._lock:
            self.stats.merge(delta)
            self.epoch_stats.merge(delta)
            if self.track_hotness:
                if self._node_hot is None:
                    self._node_hot = np.zeros(self.num_nodes,
                                              dtype=np.float32)
                if self.capacity and hit_slots.size:
                    np.add.at(self._slot_hot, hit_slots,
                              hit_counts.astype(np.float32))
                if miss_ids.size:
                    np.add.at(self._node_hot, miss_ids,
                              miss_counts.astype(np.float32))

    def stats_snapshot(self) -> Tuple[CacheStats, CacheStats]:
        """(lifetime, epoch-window) stats copies, taken atomically —
        aggregation across shards must not observe half-merged windows."""
        with self._lock:
            return (dataclasses.replace(self.stats),
                    dataclasses.replace(self.epoch_stats))

    # -------------------------------------------------------------- refresh

    @property
    def staged_ready(self) -> bool:
        """True when a staged refresh awaits its ``commit()``."""
        with self._lock:
            return self._staged is not None

    @property
    def staged_swaps(self) -> int:
        """Swap count of the currently staged plan (0 when none)."""
        with self._lock:
            return 0 if self._staged is None else \
                int(self._staged.top.shape[0])

    def stage(self, max_swap: Optional[int] = None) -> int:
        """Plan the next refresh and gather its admitted rows OFF the
        critical path.

        Everything expensive happens here: the candidate scan + pairing
        under the lock (cheap), then the admitted-row gather from the
        ``FeatureSource`` with the lock RELEASED — on the disk tier that
        gather is the part that used to block an iteration boundary, and
        it can now run in a background thread while lookups proceed.  The
        plan is pinned to the slot-table version it was computed against;
        if another commit lands before the gather finishes, the stale
        plan is discarded (never applied against a reshuffled table).

        Candidate policy (unchanged from the one-shot ``refresh()``): the
        hottest uncached candidates pair hottest-first against the
        coldest-first slots; a pair swaps only while the candidate is
        hotter than ``refresh_hysteresis`` × its victim (the hysteresis
        margin keeps a boundary hub set from thrashing), so a refresh
        never replaces a row with a hotter-or-equal one evicted.  At most
        ``max_swap`` rows move (default ``max_refresh_frac`` of
        capacity).  Returns the planned swap count.

        Failure model: a stage that raises (source gather failure, or an
        injected ``refresh.stage`` fault) increments ``stage_failures``
        and leaves NO staged plan behind — the cache keeps serving the
        current version and a supervising trainer simply retries at the
        next drift boundary."""
        if self.fault_injector is not None:
            try:
                self.fault_injector.fire("refresh.stage")
            except BaseException:
                # counted under the lock: health() reads this from the
                # main thread while an async stage runs in the background
                with self._lock:
                    self.stage_failures += 1
                raise
        with self._lock:
            if self.capacity == 0:
                return 0
            cap = self.capacity
            k_max = max(1, int(round(cap * self.max_refresh_frac)))
            if max_swap is not None:
                k_max = int(max_swap)
            k_max = max(0, min(k_max, cap))
            # candidates: observed-miss ids that are (still) uncached
            if self._node_hot is None:       # no tracked traffic yet
                cand = np.zeros(0, dtype=np.int64)
            else:
                cand = np.flatnonzero(self._node_hot > 0.0).astype(np.int64)
                cand = cand[self.slot_of[cand] < 0]
            top = cold = np.zeros(0, dtype=np.int64)
            n_swap = 0
            if k_max and cand.shape[0]:
                k = min(k_max, cand.shape[0])
                top = cand[np.argpartition(-self._node_hot[cand], k - 1)[:k]]
                # hottest first, ties broken by id for determinism
                top = top[np.lexsort((top, -self._node_hot[top]))]
                # coldest slots first, ties broken by cached id
                cold = np.lexsort((self.cached_ids, self._slot_hot)
                                  )[:k].astype(np.int64)
                # admit_hot desc vs evict_hot asc: the hotter-by-a-factor
                # predicate is monotone, so the swap set is a prefix
                n_swap = int(np.count_nonzero(
                    self._node_hot[top] > np.float32(self.refresh_hysteresis)
                    * self._slot_hot[cold]))
            top, cold = top[:n_swap], cold[:n_swap]
            base = self.version
            host_dtype = self._host_rows.dtype
        # EXPENSIVE: the admitted-row gather runs OUTSIDE the lock —
        # concurrent lookups never wait on the storage tier (and it is
        # maintenance traffic: excluded from the load-stall counters it
        # would otherwise race when staged in a background thread)
        if n_swap:
            try:
                rows = np.ascontiguousarray(
                    self._cast_rows(self._maintenance_take(top)))
            except Exception:
                # failed admission gather: count it and propagate with no
                # staged plan left behind (the old version keeps serving)
                with self._lock:
                    self.stage_failures += 1
                raise
        else:
            rows = np.zeros((0, self.feat_dim), host_dtype)
        with self._lock:
            if self.version != base:
                # a commit landed while we gathered: victims/candidates
                # were computed against a retired table — drop the plan
                self._staged = None
                return 0
            self._staged = _StagedRefresh(base, top, cold, rows)
            return n_swap

    def discard_staged(self) -> int:
        """Drop a staged-but-uncommitted refresh plan (degraded-mode
        cleanup after a failed/suspect stage): the cache keeps serving
        the current version unchanged.  Returns the number of swaps
        discarded (0 when nothing was staged)."""
        with self._lock:
            plan, self._staged = self._staged, None
            return 0 if plan is None else int(plan.top.shape[0])

    def commit(self) -> int:
        """Apply the staged refresh: the cheap synchronous half.

        Only table swaps and device row-block scatters happen here — no
        FeatureSource access, so on the disk tier an iteration boundary
        pays O(swapped rows) DMAs instead of a storage gather.  The
        admission predicate is re-validated pair-by-pair against the
        *commit-time* counters (lookups kept accumulating while the
        staged gather ran), so the never-admit-colder guarantee holds at
        the moment the swap becomes visible.  Every commit of a staged
        plan is a hotness window boundary (counters decay); a stale or
        absent plan returns 0 and changes nothing.

        When rows move: ``version`` is bumped, each device-resident
        current-version block is scatter-updated in place (the aligned
        row blocks holding admitted rows, via ``kernels.ops
        .update_cache_rows``; snapshots older than ``keep_versions`` are
        retired), and the epoch stats window resets so measured-rate
        consumers see the post-refresh rate.  Returns the number of rows
        swapped."""
        from repro.kernels.ops import update_cache_rows
        with self._lock:
            plan, self._staged = self._staged, None
            if plan is None or plan.base_version != self.version:
                return 0
            top, cold, rows = plan.top, plan.cold, plan.rows
            n_swap = int(top.shape[0])
            if n_swap:
                # re-validate against commit-time counters: a pair whose
                # victim heated up (or candidate cooled) past the
                # hysteresis margin while the gather ran no longer swaps
                keep = (self._node_hot[top]
                        > np.float32(self.refresh_hysteresis)
                        * self._slot_hot[cold])
                top, cold, rows = top[keep], cold[keep], rows[keep]
                n_swap = int(top.shape[0])
            if n_swap:
                evicted = self.cached_ids[cold].copy()
                new_slot_of = self.slot_of.copy()
                new_slot_of[evicted] = -1
                new_slot_of[top] = cold.astype(np.int32)
                new_cached = self.cached_ids.copy()
                new_cached[cold] = top
                # copy-on-write, never in place: on the CPU backend
                # jax.device_put can alias the host buffer, so mutating
                # _host_rows would corrupt previously-placed (old-version)
                # device blocks that in-flight payloads still combine with
                new_host = self._host_rows.copy()
                new_host[cold] = rows
                # O(swapped) undo entry: the evicted rows at their victim
                # slots rebuild this (old) version from the new block
                slots32 = cold.astype(np.int32)
                self._undo[self.version] = (
                    slots32, self._host_rows[cold].copy())
                # estimates travel with their nodes
                admit_est = self._node_hot[top].copy()
                self._node_hot[evicted] = self._slot_hot[cold]
                self._slot_hot[cold] = admit_est
                self._node_hot[top] = 0.0
                new_ver = self.version + 1
                # deliberate device dispatch under the lock: commit IS
                # the designed cheap half — O(swapped rows) scatter DMAs
                # that must be atomic with the table/version swap, or a
                # concurrent lookup could pair the new table with an
                # un-updated block
                for dev_key, dev in self._devices.items():
                    cur = self._device_data.get((dev_key, self.version))
                    if cur is not None:
                        self._device_data[(dev_key, new_ver)] = \
                            update_cache_rows(
                                cur, jax.device_put(rows, dev), slots32,  # noqa: RPR103 - atomic O(swap) commit by design
                                use_pallas=self.use_pallas_update,
                                pipeline_depth=self.kernel_pipeline_depth)
                self.slot_of = new_slot_of
                self.cached_ids = new_cached
                self._host_rows = new_host
                self.version = new_ver
                # retire snapshots no in-flight lookup can still reference
                low = new_ver - max(int(self.keep_versions), 1) + 1
                if low > self._floor:
                    self._floor = low
                for key in [key for key in self._device_data
                            if key[1] < self._floor]:
                    del self._device_data[key]
                for v in [v for v in self._undo if v < self._floor]:
                    del self._undo[v]
                # pins that leaked past the retention window (a batch
                # dropped by a pipeline failure never reaches its
                # release) can no longer be served anyway — age them out
                # so one leak does not disable eager retirement forever
                for v in [v for v in self._inflight if v < low]:
                    del self._inflight[v]
                # pinned-lookup protocol: drained versions retire NOW
                # instead of aging out of the keep_versions window
                self._retire_below_floor()
                self.epoch_stats = CacheStats()
                self.refreshes += 1
                self.refresh_swapped_rows += n_swap
            # window boundary: old hotness fades relative to the next epoch
            self._slot_hot *= np.float32(self.refresh_decay)
            if self._node_hot is not None:
                self._node_hot *= np.float32(self.refresh_decay)
            return n_swap

    def refresh(self, max_swap: Optional[int] = None) -> int:
        """One-shot refresh: ``stage()`` + ``commit()`` back to back.

        Semantics are unchanged from the pre-staged implementation (same
        plan, same swap, one counter decay per call); the split exists so
        ``async_refresh`` runs the expensive ``stage()`` gather in a
        background thread and keeps only the cheap ``commit()`` on the
        iteration boundary.  Returns the number of rows swapped."""
        self.stage(max_swap)
        return self.commit()


def build_cache(dataset, fraction: float,
                transfer_dtype: str = "float32",
                refresh_decay: float = 0.5,
                max_refresh_frac: float = 0.25,
                refresh_hysteresis: float = 1.25) -> Optional[FeatureCache]:
    """Cache of ``fraction`` of the dataset's nodes (None when <= 0)."""
    if fraction <= 0.0:
        return None
    capacity = int(round(dataset.num_nodes * min(fraction, 1.0)))
    if capacity == 0:
        return None
    return FeatureCache(dataset.feature_source, dataset.feature_hotness(),
                        capacity, transfer_dtype=transfer_dtype,
                        refresh_decay=refresh_decay,
                        max_refresh_frac=max_refresh_frac,
                        refresh_hysteresis=refresh_hysteresis)


# ====================================================================
# Sharded hot-feature plane: disjoint per-accelerator shards + the
# union-gather classification (DistDGL/P3 partitioned feature server
# collapsed into one node).
# ====================================================================


def _mix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer: deterministic avalanching id hash so hash
    placement spreads hub nodes uniformly across shards (consecutive ids
    land on unrelated shards)."""
    z = x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


class ShardPlacement:
    """Disjoint, exhaustive node-id -> shard ownership.

    ``hash``: SplitMix64-mixed id modulo ``n_shards`` — hubs spread
    uniformly, so every shard caches a same-shaped slice of the hot set
    (the default; best effective capacity at equal per-shard size).
    ``degree``: contiguous hotness-rank ranges — shard 0 owns the
    hottest ceil(N/n) nodes, shard 1 the next range, and so on
    (locality-style placement; per-shard hit rates are skewed by
    construction, trainers on high shards serve mostly peers).

    Both are pure functions of (num_nodes, n_shards, policy, hotness):
    every shard and every trainer derives the identical owner table."""

    POLICIES = ("hash", "degree")

    def __init__(self, num_nodes: int, n_shards: int,
                 policy: str = "hash",
                 hotness: Optional[np.ndarray] = None):
        if policy not in self.POLICIES:
            raise ValueError(f"unknown shard placement {policy!r} "
                             f"(choose from {self.POLICIES})")
        self.num_nodes = int(num_nodes)
        self.n_shards = int(max(1, n_shards))
        self.policy = policy
        if policy == "hash":
            ids = np.arange(self.num_nodes, dtype=np.uint64)
            owner = (_mix64(ids) % np.uint64(self.n_shards)).astype(np.int32)
        else:
            if hotness is None:
                raise ValueError("degree placement needs a hotness vector")
            hotness = np.asarray(hotness, dtype=np.float64)
            # stable order: equal-hotness ties deterministic across runs
            rank = np.argsort(-hotness, kind="stable")
            span = max(1, -(-self.num_nodes // self.n_shards))
            owner = np.empty(self.num_nodes, dtype=np.int32)
            owner[rank] = (np.arange(self.num_nodes) // span
                           ).astype(np.int32)
        self.owner = owner

    def owner_of(self, ids: np.ndarray) -> np.ndarray:
        """Owning shard ordinal per id (int32, vectorized)."""
        return self.owner[np.asarray(ids, dtype=np.int64)]


@dataclasses.dataclass
class ShardLookup:
    """One trainer's frontier classified against the sharded plane.

    ``look`` is a ``CacheLookup`` against the trainer's LOCAL shard:
    ``slots`` index the local [K_me, F] device block (-1 otherwise),
    ``miss_index`` points into the combined transfer source
    ``[peer rows (ring order) | fresh host rows]`` and ``miss_ids``
    holds only the FRESH unique ids the host must gather.
    ``peer_requests`` name the rows to pull over ICI from each peer
    shard, pinned at that shard's classification version."""
    look: CacheLookup
    shard: int                    # the trainer's own shard ordinal
    peer_requests: List[Tuple[int, np.ndarray, int]]
    pinned: List[Tuple[int, int]]  # (shard, version) pins to release
    peer_rows: int = 0            # unique rows pulled over ICI
    peer_positions: int = 0       # frontier positions served by peers
    local_positions: int = 0      # frontier positions served locally


@dataclasses.dataclass
class UnionLookup:
    """All trainers' classifications for one pipeline batch, plus the
    per-shard accounting payload deferred until the union gather
    succeeds (mirrors the ``record=False`` protocol of ``lookup``)."""
    per_trainer: Dict[str, ShardLookup]
    record_payload: List[tuple]


# the lock only covers the memoized merged slot table; the shards guard
# their own state, and placement/row_bytes/shards are immutable after
# construction.
@guarded_by("_lock", "_merged_key", "_merged_table")
class ShardedFeatureCache:
    """Partitioned hot-feature plane: ``n_shards`` disjoint per-device
    ``FeatureCache`` shards over one source, giving n× effective
    capacity at the same per-device budget.

    A frontier position resolves in priority order: local shard hit
    (device-resident) → peer shard hit (one row hop over ICI via
    ``repro.dist.collectives.exchange_peer_rows``) → host miss.  Host
    misses are gathered once for the *union* of all trainers'
    fresh-miss sets (``FeatureLoader.load_union``) and each row is
    multicast only to the devices that need it.

    Each shard keeps its own version/pin protocol; a union lookup
    snapshots every shard once and pins one reference per trainer, so a
    mid-pipeline refresh of any shard stays semantically invisible
    exactly as in the replicated plane."""

    def __init__(self, source: "FeatureSource | np.ndarray",
                 hotness: np.ndarray, capacity_per_shard: int,
                 n_shards: int, placement: str = "hash",
                 transfer_dtype: str = "float32", **refresh_kw):
        source = as_feature_source(source)
        num_nodes, feat_dim = source.shape
        hotness = np.asarray(hotness, dtype=np.float64)
        if hotness.shape[0] != num_nodes:
            raise ValueError("hotness must have one entry per node")
        self.num_nodes = int(num_nodes)
        self.feat_dim = int(feat_dim)
        self.n_shards = int(max(1, n_shards))
        self.transfer_dtype = transfer_dtype
        self.row_bytes = wire_row_bytes(feat_dim, transfer_dtype)
        self.placement = ShardPlacement(num_nodes, self.n_shards,
                                        placement, hotness)
        hmin = float(hotness.min()) if num_nodes else 0.0
        self.shards: List[FeatureCache] = []
        for d in range(self.n_shards):
            owned = self.placement.owner == d
            # shift owned hotness strictly positive and zero the rest:
            # the shard's top-K pick can then never leak a non-owned id
            # (disjointness by construction), capped at the owned count
            h_d = np.where(owned, hotness - hmin + 1.0, 0.0)
            cap_d = int(min(int(capacity_per_shard), int(owned.sum())))
            self.shards.append(
                FeatureCache(source, h_d, cap_d,
                             transfer_dtype=transfer_dtype, **refresh_kw))
        mass = sum(float(hotness[s.cached_ids].sum()) for s in self.shards)
        self._expected_hit_rate = mass / max(float(hotness.sum()), 1e-12)
        self._lock = threading.RLock()
        self._merged_key: Optional[tuple] = None
        self._merged_table: Optional[np.ndarray] = None

    # ------------------------------------------------------------ plumbing

    @property
    def capacity(self) -> int:
        """Total resident rows across shards (the n× effective capacity)."""
        return sum(s.capacity for s in self.shards)

    @property
    def nbytes(self) -> int:
        """Device bytes pinned across ALL shards (one shard per device;
        the per-device budget is a single shard's block)."""
        return sum(s.nbytes for s in self.shards)

    @property
    def expected_hit_rate(self) -> float:
        """Hotness mass covered by the UNION of the shards — the plane's
        design-time (local + peer) hit estimate for Eq. 7/8."""
        return self._expected_hit_rate

    @property
    def version(self) -> int:
        """Monotone aggregate version (sum of shard versions): bumps
        whenever any shard refreshes, for drift/metrics consumers."""
        return sum(s.snapshot()[1] for s in self.shards)

    @property
    def slot_of(self) -> np.ndarray:
        """Merged id -> slot table (slot within the OWNER shard's block;
        >= 0 means resident somewhere in the plane).  Consumers — the
        prefetch submit filter, the dup-factor probe — only ask "cached
        anywhere?"; memoized per shard-version vector."""
        snaps = [s.snapshot() for s in self.shards]
        key = tuple(v for _, v in snaps)
        with self._lock:
            if key == self._merged_key and self._merged_table is not None:
                return self._merged_table
        merged = np.full(self.num_nodes, -1, dtype=np.int32)
        for table, _ in snaps:
            resident = table >= 0
            # shards own disjoint id sets: blind scatter cannot collide
            merged[resident] = table[resident]
        with self._lock:
            self._merged_key, self._merged_table = key, merged
            return self._merged_table

    # config knobs forwarded to every shard ------------------------------

    @property
    def keep_versions(self) -> int:
        return self.shards[0].keep_versions

    @keep_versions.setter
    def keep_versions(self, value: int) -> None:
        for s in self.shards:
            s.keep_versions = value

    @property
    def track_hotness(self) -> bool:
        return self.shards[0].track_hotness

    @track_hotness.setter
    def track_hotness(self, value: bool) -> None:
        for s in self.shards:
            s.track_hotness = value

    @property
    def use_pallas_update(self) -> bool:
        return self.shards[0].use_pallas_update

    @use_pallas_update.setter
    def use_pallas_update(self, value: bool) -> None:
        for s in self.shards:
            s.use_pallas_update = value

    @property
    def kernel_pipeline_depth(self) -> int:
        return self.shards[0].kernel_pipeline_depth

    @kernel_pipeline_depth.setter
    def kernel_pipeline_depth(self, value: int) -> None:
        for s in self.shards:
            s.kernel_pipeline_depth = value

    @property
    def fault_injector(self):
        return self.shards[0].fault_injector

    @fault_injector.setter
    def fault_injector(self, value) -> None:
        for s in self.shards:
            s.fault_injector = value

    # aggregated health/observability ------------------------------------

    @property
    def stage_failures(self) -> int:
        return sum(s.stage_failures for s in self.shards)

    @property
    def refreshes(self) -> int:
        return sum(s.refreshes for s in self.shards)

    @property
    def refresh_swapped_rows(self) -> int:
        return sum(s.refresh_swapped_rows for s in self.shards)

    @property
    def staged_ready(self) -> bool:
        return any(s.staged_ready for s in self.shards)

    def measured_hit_rate(self) -> float:
        """Aggregate positional (local + peer) hit rate over the shards'
        current epoch windows, falling back to lifetime totals — the
        same feedback quantity the replicated cache reports."""
        epoch_hit = epoch_tot = life_hit = life_tot = 0
        for s in self.shards:
            life, epoch = s.stats_snapshot()
            epoch_hit += epoch.hit_rows
            epoch_tot += epoch.total_rows
            life_hit += life.hit_rows
            life_tot += life.total_rows
        if epoch_tot:
            return epoch_hit / epoch_tot
        return life_hit / max(life_tot, 1)

    def retained_versions(self) -> Dict[int, list]:
        """Per-shard retained-version ranges (observability)."""
        return {d: s.retained_versions()
                for d, s in enumerate(self.shards)}

    def retained_bytes(self) -> int:
        """Undo-log retention bytes summed across shards."""
        return sum(s.retained_bytes() for s in self.shards)

    # ------------------------------------------------------ union lookup

    def lookup_union(self, frontiers: Dict[str, np.ndarray],
                     ordinals: Dict[str, int], pin: bool = False,
                     record: bool = True) -> UnionLookup:
        """Classify every trainer's frontier against the plane in one
        pass: local-shard hits, peer-shard hits (grouped per owner in
        ring order from each trainer's ordinal) and fresh host misses.

        Every shard is snapshotted once (atomically per shard) and, with
        ``pin=True``, pinned once per trainer — the trainer releases all
        of a batch's pins via ``release_union`` after its combine.  With
        ``record=False`` the per-shard stats/hotness accounting is
        returned in the payload and applied later by ``record_union``
        (the loader defers it past the union gather, mirroring the
        replicated ``record=False`` protocol)."""
        from repro.dist.collectives import ring_order
        npin = len(frontiers) if pin else 0
        snaps = [s.snapshot(pin=npin) for s in self.shards]
        tables = [t for t, _ in snaps]
        vers = [v for _, v in snaps]
        owner_all = self.placement.owner
        acc = [{"hs": [], "hc": [], "mi": [], "mc": [], "lk": 0}
               for _ in range(self.n_shards)]
        per: Dict[str, ShardLookup] = {}
        for name in sorted(frontiers):
            me = int(ordinals[name])
            ids = np.asarray(frontiers[name], dtype=np.int64)
            uniq, inverse = np.unique(ids, return_inverse=True)
            inverse = inverse.astype(np.int32)
            counts = np.bincount(inverse, minlength=uniq.shape[0])
            owner = owner_all[uniq]
            uslots = np.full(uniq.shape[0], -1, dtype=np.int32)
            for d in range(self.n_shards):
                sel = owner == d
                if sel.any():
                    uslots[sel] = tables[d][uniq[sel]]
            hit = uslots >= 0
            # combined transfer-source index per unique: peer rows first
            # (ring order from me, each group in sorted-id order), then
            # the fresh host-gathered rows — deterministic layout shared
            # with the transfer stage's source concatenation
            u_midx = np.zeros(uniq.shape[0], dtype=np.int32)
            base = 0
            peer_requests: List[Tuple[int, np.ndarray, int]] = []
            peer_rows = peer_pos = 0
            for p in ring_order(self.n_shards, me):
                sel = hit & (owner == p)
                k = int(np.count_nonzero(sel))
                if k:
                    u_midx[sel] = base + np.arange(k, dtype=np.int32)
                    peer_requests.append(
                        (p, uslots[sel].astype(np.int32), vers[p]))
                    peer_rows += k
                    peer_pos += int(counts[sel].sum())
                    base += k
            fresh = ~hit
            n_fresh = int(np.count_nonzero(fresh))
            if n_fresh:
                u_midx[fresh] = base + np.arange(n_fresh, dtype=np.int32)
            local_sel = hit & (owner == me)
            slots_u = np.where(local_sel, uslots,
                               np.int32(-1)).astype(np.int32)
            look = CacheLookup(
                ids=ids, slots=slots_u[inverse],
                miss_index=u_midx[inverse], miss_ids=uniq[fresh],
                unique_ids=uniq, inverse=inverse, version=vers[me])
            per[name] = ShardLookup(
                look=look, shard=me, peer_requests=peer_requests,
                pinned=([(d, vers[d]) for d in range(self.n_shards)]
                        if pin else []),
                peer_rows=peer_rows, peer_positions=peer_pos,
                local_positions=int(counts[local_sel].sum()))
            # hotness/stats land on the OWNER shard (position-weighted):
            # refresh admission then only ever considers owned ids, so
            # shard disjointness survives every refresh
            for d in range(self.n_shards):
                seld = owner == d
                h = seld & hit
                m = seld & fresh
                a = acc[d]
                a["lk"] += 1
                if h.any():
                    a["hs"].append(uslots[h])
                    a["hc"].append(counts[h])
                if m.any():
                    a["mi"].append(uniq[m])
                    a["mc"].append(counts[m])
        payload = []
        for d, a in enumerate(acc):
            payload.append((
                d,
                np.concatenate(a["hs"]) if a["hs"] else
                np.zeros(0, dtype=np.int32),
                np.concatenate(a["hc"]) if a["hc"] else
                np.zeros(0, dtype=np.int64),
                np.concatenate(a["mi"]) if a["mi"] else
                np.zeros(0, dtype=np.int64),
                np.concatenate(a["mc"]) if a["mc"] else
                np.zeros(0, dtype=np.int64),
                a["lk"]))
        union = UnionLookup(per_trainer=per, record_payload=payload)
        if record:
            self.record_union(union)
        return union

    def record_union(self, union: UnionLookup) -> None:
        """Apply a deferred union lookup's per-shard accounting."""
        for d, hs, hc, mi, mc, lk in union.record_payload:
            self.shards[d].record_access(hs, hc, mi, mc, lookups=lk)
        union.record_payload = []

    def release_union(self, shard_look: ShardLookup) -> None:
        """Release one trainer's per-shard pins for one batch."""
        for d, ver in shard_look.pinned:
            self.shards[d].release_version(ver)
        shard_look.pinned = []

    # ------------------------------------------------------------ refresh

    def stage(self, max_swap: Optional[int] = None) -> int:
        return sum(s.stage(max_swap) for s in self.shards)

    def commit(self) -> int:
        return sum(s.commit() for s in self.shards)

    def discard_staged(self) -> int:
        return sum(s.discard_staged() for s in self.shards)

    def refresh(self, max_swap: Optional[int] = None) -> int:
        self.stage(max_swap)
        return self.commit()


def build_sharded_cache(dataset, fraction: float, n_shards: int,
                        placement: str = "hash",
                        transfer_dtype: str = "float32",
                        refresh_decay: float = 0.5,
                        max_refresh_frac: float = 0.25,
                        refresh_hysteresis: float = 1.25
                        ) -> Optional[ShardedFeatureCache]:
    """Sharded plane at the SAME per-device budget as ``build_cache``:
    ``fraction`` of the dataset's nodes *per shard*, so n shards hold up
    to n× the replicated row count (None when the budget rounds to 0)."""
    if fraction <= 0.0 or n_shards < 1:
        return None
    capacity = int(round(dataset.num_nodes * min(fraction, 1.0)))
    if capacity == 0:
        return None
    return ShardedFeatureCache(
        dataset.feature_source, dataset.feature_hotness(), capacity,
        n_shards, placement=placement, transfer_dtype=transfer_dtype,
        refresh_decay=refresh_decay, max_refresh_frac=max_refresh_frac,
        refresh_hysteresis=refresh_hysteresis)
