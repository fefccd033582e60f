"""Feature Loader (paper Section III-A) — cache- and dedup-aware host gather.

Runs on the host ("Feature Loading is only performed on the CPUs ... the
feature matrix X is stored in the CPU memory").  Given a sampled MiniBatch
it gathers feature rows from the dataset's ``FeatureSource`` into a
contiguous buffer ready for the Data Transfer stage.  When the source is
partitioned (``PartitionedFeatures`` / out-of-core ``MmapFeatures``) the
multi-threaded gather splits the request at partition boundaries so each
thread faults a disjoint set of mmap windows in parallel.

The unit of the transfer path is the *unique node id*, not the frontier
position: with-replacement sampling on power-law graphs makes most frontier
positions duplicates of a small hub set, so the loader gathers and ships
one row per unique id and lets the on-device combine step duplicate rows
back into the positional [frontier, F] layer-0 layout (the paper's Feature
Duplicator, moved to the far side of the interconnect).

Gather modes:

  * ``load``         — the full positional frontier (CPU trainers, whose
    "device" is host memory and who read rows in place, and legacy
    dedup-off/cache-off accelerator runs),
  * ``load_compact`` — the deduped transfer path: unique ids are computed
    once per mini-batch (``featcache.compact_lookup``), only uniques are
    classified against the optional device-resident ``FeatureCache``, and
    only *unique miss* rows are gathered and shipped.  The transfer stage
    sends (unique miss rows, slots, miss_index); the combine expands them.
  * ``load_misses``  — back-compat alias of ``load_compact`` that requires
    a cache (honours the loader's ``dedup`` flag).

Two further levers stack on top of the compact path:

  * ``load_union`` — the sharded-plane load: ALL accelerator trainers'
    frontiers are classified against the ``ShardedFeatureCache`` in one
    union lookup, the host gathers the *union* of their fresh-miss sets
    once, and each union row is multicast only to the devices that need
    it.  Accounting models the physical route: a union row crosses PCIe
    once (``stats.bytes``); its extra device copies and the peer-shard
    row hops ride the accelerator interconnect (``ici_bytes``).  The
    PCIe bytes the union dedup avoids vs per-trainer gathers land in
    ``union_saved_bytes``; peer-shard hits in ``peer_saved_bytes``.
  * recent-rows LRU (``recent_batches`` > 0 + a ``recent_key``) —
    cross-iteration device-side dedup: ``load_compact`` remembers the
    unique ids shipped to each consumer over the last few batches, skips
    re-gathering/re-shipping rows still resident on the device (their
    device arrays are re-read by the combine), and drops the history
    whenever the cache version moves.  Savings in
    ``recent_saved_bytes``.

Every gather writes into a host buffer the loader lends out: the smallest
released one that holds the rows, else a new one whose row count is rounded
up to ``_ROW_QUANTUM``.  The caller hands it back with ``release`` once
nothing reads the rows any more (the hybrid trainer: after the update of the
batch that read them, since a CPU trainer may read them in place).  A buffer
that is never released is only freed with its last reference, so a missed
release costs a fresh allocation, never a reuse.  A reused buffer's pages are
already faulted in, and the pool threads fill their slices of it in place,
with no concatenate: a full-size batch's frontier is 40-65 MB, above
glibc's mmap threshold, so a fresh array per gather is a fresh mapping
faulted page by page.  ``reused_gathers`` / ``fresh_gathers`` count them.

Supports optional on-the-fly down-cast to bf16 ("data quantization to
relieve the stress on the PCIe bandwidth" — the paper's §VIII future-work
item) and reports rows/bytes statistics consumed by the DRM engine and the
performance model.  ``stats.bytes`` counts only bytes actually *shipped*
host->device (the quantity Eq. 7/8 model); every avoided ship is
attributed to exactly one counter (``saved_bytes`` cache hits,
``peer_saved_bytes`` peer-shard hits, ``dedup_saved_bytes`` in-batch
duplicates, ``union_saved_bytes`` cross-trainer union dedup,
``recent_saved_bytes`` cross-iteration residency) — the counters always
sum back to the legacy one-row-per-position baseline (plus bucket
padding, tracked separately in ``padding_bytes``).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.analysis.annotations import guarded_by
from repro.core.spans import span

from .featcache import (CacheLookup, FeatureCache, ShardLookup,
                        compact_lookup, wire_row_bytes)
from .sampler import MiniBatch
from .storage import GraphDataset

__all__ = ["FeatureLoader", "LoadStats", "MissBlock", "ShardMissBlock"]

_BF16 = jnp.bfloat16  # numpy-compatible via ml_dtypes under the hood
# a gather buffer's rows are rounded up to a multiple of this, so that
# sampling noise in a batch's row count still finds a released buffer
_ROW_QUANTUM = 1024


@dataclasses.dataclass
class LoadStats:
    rows: int = 0            # rows shipped (gathered uniques + any padding)
    bytes: int = 0           # bytes shipped host->device
    seconds: float = 0.0
    total_rows: int = 0      # frontier positions requested (hits + misses)
    unique_rows: int = 0     # unique ids among the requested positions
    hit_rows: int = 0        # positions served from the device cache
    saved_bytes: int = 0     # transfer bytes avoided by LOCAL cache hits
    dedup_saved_bytes: int = 0  # transfer bytes avoided by deduplication
    padding_bytes: int = 0   # share of `bytes` that is shape-bucket padding
    peer_rows: int = 0       # unique rows pulled from peer shards over ICI
    peer_saved_bytes: int = 0   # PCIe bytes avoided by peer-shard hits
    union_saved_bytes: int = 0  # PCIe bytes avoided by the cross-trainer
                             #   union gather (each shared row ships once)
    ici_bytes: int = 0       # bytes crossing the accelerator interconnect
                             #   (peer row hops + multicast fan-out copies)
    recent_rows: int = 0     # unique rows skipped: still device-resident
                             #   from a recent batch (cross-iteration LRU)
    recent_saved_bytes: int = 0  # PCIe bytes those skips avoided
    stall_seconds: float = 0.0  # aggregate gather-thread seconds spent
                             #   faulting cold storage pages (disk-tier
                             #   mmap gathers the window prefetcher did
                             #   not pre-warm); summed across the chunked
                             #   gather's pool threads, so it can exceed
                             #   the wall-clock `seconds`.  0 on
                             #   RAM-resident sources
    reused_gathers: int = 0  # gathers filled into a released buffer
    fresh_gathers: int = 0   # gathers that had to allocate their buffer

    @property
    def hit_rate(self) -> float:
        return self.hit_rows / max(self.total_rows, 1)

    @property
    def dup_factor(self) -> float:
        """Measured duplication factor (positions per unique id, >= 1)."""
        return self.total_rows / max(self.unique_rows, 1)

    def merge(self, other: "LoadStats") -> None:
        self.rows += other.rows
        self.bytes += other.bytes
        self.seconds += other.seconds
        self.total_rows += other.total_rows
        self.unique_rows += other.unique_rows
        self.hit_rows += other.hit_rows
        self.saved_bytes += other.saved_bytes
        self.dedup_saved_bytes += other.dedup_saved_bytes
        self.padding_bytes += other.padding_bytes
        self.peer_rows += other.peer_rows
        self.peer_saved_bytes += other.peer_saved_bytes
        self.union_saved_bytes += other.union_saved_bytes
        self.ici_bytes += other.ici_bytes
        self.recent_rows += other.recent_rows
        self.recent_saved_bytes += other.recent_saved_bytes
        self.stall_seconds += other.stall_seconds
        self.reused_gathers += other.reused_gathers
        self.fresh_gathers += other.fresh_gathers


@dataclasses.dataclass
class _ShippedBlock:
    """Recent-rows LRU entry: the unique ids one batch freshly shipped to
    a consumer device, plus (once the transfer stage ran) the device
    array holding them.  ``array`` is written exactly once by the
    transfer stage and only read by LATER batches' transfer stages —
    pipeline stages process batches in order, so a batch that matched
    this entry at load time is guaranteed to find ``array`` filled by
    the time its own combine runs."""
    ids: np.ndarray          # sorted unique ids of the shipped fresh rows
    version: int             # cache version the ship was classified at
    array: Optional[object] = None  # [>=len(ids), F] device rows


@dataclasses.dataclass
class MissBlock:
    """Host-side output of a compact (dedup/cache-aware) load.

    ``rows`` is the [M, F] unique-miss block; ``lookup`` carries the
    positional slot / miss-index tables the on-device combine consumes
    (see ``kernels.ops.assemble_features``) — under dedup many positions
    point at the same row of ``rows``.

    With the recent-rows LRU active, ``miss_index`` addresses the
    combined source ``[recent segments... | fresh rows]``: ``recent``
    lists (entry, row indices) pairs to re-read from device-resident
    arrays of earlier batches, and ``shipped`` is this batch's own LRU
    entry whose ``array`` the transfer stage must fill.
    """
    rows: np.ndarray
    lookup: CacheLookup
    recent: List[Tuple[_ShippedBlock, np.ndarray]] = \
        dataclasses.field(default_factory=list)
    shipped: Optional[_ShippedBlock] = None

    @property
    def num_rows(self) -> int:
        return self.lookup.num_rows


@dataclasses.dataclass
class ShardMissBlock(MissBlock):
    """Per-trainer output of the sharded-plane ``load_union``: ``rows``
    holds only the trainer's slice of the union gather (its fresh host
    misses), ``lookup`` indexes the local shard block + the combined
    ``[peer rows | fresh rows]`` source, and ``shard`` carries the peer
    requests and per-shard version pins the transfer stage resolves."""
    shard: Optional[ShardLookup] = None


# the load and transfer pipeline stages run in different threads and both
# account into the same stats windows; every merge resolves its
# destination and runs under _stats_lock
# the load stage lends gather buffers, the train loop releases them
@guarded_by("_stats_lock", "stats", "window", "host_stats")
@guarded_by("_buf_lock", "_free", "_lent")
class FeatureLoader:
    def __init__(self, dataset: GraphDataset, transfer_dtype: str = "float32",
                 num_threads: int = 1,
                 cache: Optional[FeatureCache] = None,
                 dedup: bool = True, recent_batches: int = 0):
        self.dataset = dataset
        self.source = dataset.feature_source
        self.transfer_dtype = transfer_dtype
        self.num_threads = max(1, int(num_threads))  # DRM's balance_thread knob
        self.cache = cache   # FeatureCache or ShardedFeatureCache (union path)
        self.dedup = dedup
        self.recent_batches = max(0, int(recent_batches))
        # cross-iteration residency history: consumer key -> deque of the
        # last `recent_batches` _ShippedBlock entries.  The structure is
        # touched by the load stage (match/append/invalidate) and by
        # drop_recent (failure cleanup from other threads), so the tiny
        # dedicated _recent_lock guards the dict/deques; the entries'
        # `array` field is deliberately outside it (single writer — the
        # transfer stage, in batch order — and only read by later batches
        # of that same stage).
        self._recent: Dict[object, deque] = {}
        self._recent_lock = threading.Lock()
        self.stats = LoadStats()       # transfer path (rows that cross PCIe)
        self.window = LoadStats()      # transfer path since the last cache
                                       #   refresh (windowed feedback: the
                                       #   perf-model re-pricing must see
                                       #   the post-refresh rate, not a
                                       #   lifetime average)
        self.host_stats = LoadStats()  # CPU-trainer direct host reads
        # the load and transfer pipeline stages run in different threads
        # and both account into `stats` (gathers vs bucket padding)
        self._stats_lock = threading.Lock()
        # chunked-gather pool: created lazily, reused across load calls
        # (executor construction/teardown per call costs more than the
        # chunked gather saves on small frontiers)
        self._pool = None
        self._pool_size = 0
        # gather buffers: released ones, and id -> weak reference of each
        # one lent out (weak, so a buffer never released is still freed)
        self._free: List[np.ndarray] = []
        self._lent: Dict[int, weakref.ref] = {}
        self._buf_lock = threading.Lock()
        self._row_bytes = wire_row_bytes(dataset.feat_dim, transfer_dtype)

    def _account(self, dest: str, delta: LoadStats) -> None:
        # `dest` names the window ("stats" / "host_stats") instead of
        # passing the object: resolving it under the lock keeps even the
        # destination *read* inside the guarded region (reset_window may
        # rebind `window` concurrently)
        with self._stats_lock:
            target: LoadStats = getattr(self, dest)
            target.merge(delta)
            if dest == "stats":        # transfer path also feeds the window
                self.window.merge(delta)

    def reset_window(self) -> None:
        """Start a fresh measurement window (called after a cache refresh
        so drift/feedback consumers see only post-refresh traffic)."""
        with self._stats_lock:
            self.window = LoadStats()

    def snapshot_stats(self) -> LoadStats:
        """Consistent copy of the cumulative transfer-path stats — the
        knob autotuner diffs consecutive snapshots to get per-window
        traffic without resetting the window the drift feedback reads."""
        with self._stats_lock:
            return dataclasses.replace(self.stats)

    def _get_pool(self):
        import concurrent.futures as cf
        if self._pool is None or self._pool_size != self.num_threads:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            self._pool = cf.ThreadPoolExecutor(
                self.num_threads, thread_name_prefix="featload")
            self._pool_size = self.num_threads
        return self._pool

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
            self._pool_size = 0
        # buffers still lent out are never taken back after this
        with self._buf_lock:
            self._free.clear()
            self._lent.clear()

    def _acquire(self, n: int) -> Tuple[np.ndarray, bool]:
        """A buffer of at least ``n`` rows for a gather, and whether it was
        a released one: the smallest released buffer that holds ``n``
        rows, else a new one.  The new one takes the place of the
        smallest released buffer (none holds ``n`` rows), so the loader
        never keeps more buffers than were lent out at once."""
        width, dtype = self.source.shape[1], np.dtype(self.source.dtype)
        with self._buf_lock:
            mine = [(b.shape[0], i) for i, b in enumerate(self._free)
                    if b.shape[1] == width and b.dtype == dtype]
            fits = [m for m in mine if m[0] >= n]
            pick = min(fits or mine, default=None)
            buf = self._free.pop(pick[1]) if pick is not None else None
            if not fits:
                buf = None
                # forget buffers lent and never released (a failed batch)
                self._lent = {k: r for k, r in self._lent.items()
                              if r() is not None}
        reused = buf is not None
        if buf is None:
            cap = -(-max(n, 1) // _ROW_QUANTUM) * _ROW_QUANTUM
            buf = np.empty((cap, width), dtype)
        with self._buf_lock:
            self._lent[id(buf)] = weakref.ref(buf)
        return buf, reused

    def release(self, arrays) -> None:
        """Hand back the buffers behind rows a gather returned, for later
        gathers to refill.  Call it only once nothing reads those rows
        any more.  Arrays that no lent buffer backs, or whose buffer was
        handed back already, are ignored."""
        with self._buf_lock:
            for a in arrays:
                if not isinstance(a, np.ndarray):
                    continue
                buf = a if a.base is None else a.base
                ref = self._lent.pop(id(buf), None)
                if ref is not None and ref() is buf:
                    self._free.append(buf)

    def __del__(self):  # pragma: no cover - GC timing dependent
        try:
            self.close()
        except Exception:
            pass

    def _source_stall(self) -> float:
        """Cumulative cold-page-fault seconds reported by the source (0
        for RAM-resident sources) — deltas around a gather give the share
        of its wall time that was a storage stall.  Pool threads finish
        inside the gather call, so the delta is race-free as long as
        loads run from one stage thread (the pipeline's contract)."""
        return float(getattr(self.source, "cold_gather_seconds", 0.0))

    def _split_chunks(self, rows: np.ndarray):
        """Split a gather into per-thread chunks.

        For partitioned/mmap sources (anything exposing ``partition_rows``)
        the split is *partition-aligned*: rows are grouped by partition and
        cut only at partition boundaries, so each pool thread faults a
        disjoint set of mmap windows (the point of the chunked gather —
        naive ``array_split`` on an arbitrary-order frontier makes every
        thread touch every window).  Returns ``(chunks, order)`` where
        ``order`` is the permutation that sorted the rows (``None`` for the
        legacy order-preserving split).
        """
        prows = int(getattr(self.source, "partition_rows", 0) or 0)
        if prows <= 0:
            return np.array_split(rows, self.num_threads), None
        part_id = rows // prows
        order = np.argsort(part_id, kind="stable")
        sorted_rows = rows[order]
        n = rows.shape[0]
        # candidate cut positions = partition boundaries in the sorted
        # stream; pick the one at/after each equal-share target
        bounds = np.flatnonzero(np.diff(part_id[order])) + 1
        cand = np.concatenate([bounds, [n]])
        targets = np.arange(1, self.num_threads) * n // self.num_threads
        cuts = np.unique(cand[np.searchsorted(cand, targets)])
        chunks = [c for c in np.split(sorted_rows, cuts) if c.shape[0]]
        return chunks, order

    def _gather(self, rows: np.ndarray) -> Tuple[np.ndarray, bool]:
        """Every host row gather, into a lent buffer (``release`` hands it
        back), and whether that buffer was a released one: the span
        ``hyscale.load.gather`` (``reused=0|1``), on the calling thread
        only.  A gather that raises keeps its buffer: a chunk still being
        filled must not land in a buffer lent out again."""
        n = int(rows.shape[0])
        buf, reused = self._acquire(n)
        out = buf[:n]
        with span("hyscale.load.gather", reused=int(reused)):
            if self.num_threads == 1 or n < 2 * self.num_threads:
                self.source.take(rows, out=out)
                return out, reused
            # chunked gather: each pool thread fills its chunk's rows of
            # `out` (in place where the chunk is a contiguous run of the
            # request; partition-aligned chunks scatter back)
            chunks, order = self._split_chunks(rows)
            ends = np.cumsum([c.shape[0] for c in chunks]).tolist()

            def fill(job):
                chunk, lo, hi = job
                if order is None:
                    self.source.take(chunk, out=out[lo:hi])
                else:
                    out[order[lo:hi]] = self.source.take(chunk)

            jobs = zip(chunks, [0] + ends[:-1], ends)
            list(self._get_pool().map(fill, jobs))
            return out, reused

    def _cast(self, x: np.ndarray) -> np.ndarray:
        if self.transfer_dtype == "bfloat16":
            y = x.astype(_BF16)
            self.release([x])      # the copy holds the rows now
            return y
        return x

    @staticmethod
    def _gathers(reused: bool) -> Dict[str, int]:
        return {"reused_gathers": int(reused),
                "fresh_gathers": int(not reused)}

    def _frontier(self, batch: MiniBatch) -> np.ndarray:
        return np.asarray(batch.frontier(len(batch.fanouts)))

    def load(self, batch: MiniBatch, to_device: bool = True) -> np.ndarray:
        """Gather features for the innermost frontier (layer-0 inputs).

        ``to_device=False`` marks a CPU-trainer load: the rows are consumed
        in place from host memory and never cross the interconnect, so they
        are accounted in ``host_stats`` instead of the transfer-path
        ``stats``.
        """
        t0 = time.perf_counter()
        stall0 = self._source_stall()
        frontier = self._frontier(batch)
        x, reused = self._gather(frontier)
        x = self._cast(x)
        dt = time.perf_counter() - t0
        dest = "stats" if to_device else "host_stats"
        self._account(dest, LoadStats(rows=x.shape[0], bytes=x.nbytes,
                                      seconds=dt, total_rows=x.shape[0],
                                      unique_rows=x.shape[0],
                                      stall_seconds=self._source_stall()
                                      - stall0, **self._gathers(reused)))
        return x

    def note_transfer_padding(self, rows: int, nbytes: int) -> None:
        """Account padding rows the transfer stage ships beyond the gathered
        misses (shape-bucketing): they cross PCIe, so they count as shipped
        traffic even though no host gather produced them."""
        self._account("stats", LoadStats(rows=rows, bytes=nbytes,
                                         padding_bytes=nbytes))

    def drop_recent(self, key: object = None) -> None:
        """Drop the recent-rows residency history for ``key`` (all
        consumers when ``None``) — failure cleanup: a consumer whose
        transfer stage stopped filling its entries must never be matched
        against again."""
        with self._recent_lock:
            if key is None:
                self._recent.clear()
            else:
                self._recent.pop(key, None)

    def _match_recent(self, key: object, look: CacheLookup):
        """Split ``look``'s unique misses into device-resident rows (in
        the consumer's recent shipped blocks, at the SAME cache version)
        and fresh ids, and remap the positional ``miss_index`` onto the
        combined ``[recent segments... | fresh]`` source layout.  Pure
        planning — ``look`` itself is not mutated here."""
        miss = look.miss_ids
        with self._recent_lock:
            dq = self._recent.get(key)
            entries = [e for e in (dq or ())
                       if e.version == look.version and e.ids.shape[0]]
            if dq is not None and len(entries) != len(dq):
                # a cache refresh moved the version: the old rows are
                # value-identical (the source is immutable) but the
                # conservative contract invalidates residency across
                # refreshes — accounting must never outlive its pricing
                dq.clear()
                dq.extend(entries)
        taken = np.zeros(miss.shape[0], dtype=bool)
        combined = np.empty(miss.shape[0], dtype=np.int32)
        sources: List[Tuple[_ShippedBlock, np.ndarray]] = []
        base = 0
        # newest entry first: consecutive batches share the most rows
        for e in reversed(entries):
            if bool(taken.all()):
                break
            pos = np.searchsorted(e.ids, miss)
            pos = np.minimum(pos, e.ids.shape[0] - 1)
            m = (~taken) & (e.ids[pos] == miss)
            k = int(np.count_nonzero(m))
            if not k:
                continue
            sources.append((e, pos[m].astype(np.int32)))
            combined[m] = base + np.arange(k, dtype=np.int32)
            base += k
            taken |= m
        fresh_mask = ~taken
        n_fresh = int(np.count_nonzero(fresh_mask))
        combined[fresh_mask] = base + np.arange(n_fresh, dtype=np.int32)
        new_miss_index = np.where(
            look.slots >= 0, np.int32(0),
            combined[look.miss_index]).astype(np.int32)
        return miss[fresh_mask], sources, new_miss_index

    def load_compact(self, batch: MiniBatch, pin: bool = False,
                     recent_key: object = None) -> MissBlock:
        """Deduped transfer-path load: gather one row per unique miss id.

        Works with or without a device cache.  With a cache, only the
        frontier's unique ids are classified against it and only unique
        *miss* rows are gathered; without one, every unique id is a miss.
        When the loader was built with ``dedup=False`` (legacy positional
        path) a cache is required and one row per miss position ships.

        Failure model: the lookup only *classifies* here
        (``record=False``); cache stats/hotness and loader stats are
        committed together after the miss gather succeeded.  A gather
        that raises (storage fault past the retry/fallback budget, a
        pool-thread exception) therefore surfaces exactly once and
        leaves every stats window untouched — no half-recorded batch.

        ``pin=True`` registers the classification version as in flight
        (``FeatureCache.lookup`` pinning protocol); the consumer of the
        returned block must call ``cache.release_lookup(block.lookup)``
        exactly once after the combine — the pipelined trainer does this
        in its transfer stage so drained versions retire eagerly.

        ``recent_key`` (with ``recent_batches`` > 0) engages the
        cross-iteration device-side dedup: unique misses still resident
        on the consumer's device from its last few batches are split off
        and NOT gathered/shipped again — the block's ``recent`` list
        tells the combine where to re-read them, and ``shipped``
        registers this batch's fresh rows for future reuse.
        """
        t0 = time.perf_counter()
        stall0 = self._source_stall()
        frontier = self._frontier(batch)
        if self.cache is not None:
            with span("hyscale.load.lookup"):
                look = self.cache.lookup(frontier, dedup=self.dedup,
                                         record=False, pin=pin)
            row_bytes = self.cache.row_bytes
        else:
            if not self.dedup:
                raise RuntimeError(
                    "load_compact without a FeatureCache requires dedup")
            with span("hyscale.load.lookup"):
                look = compact_lookup(frontier)
            row_bytes = self._row_bytes
        use_recent = (recent_key is not None and self.recent_batches > 0
                      and self.dedup)
        if use_recent:
            fresh_ids, recent_src, new_miss_index = \
                self._match_recent(recent_key, look)
        else:
            fresh_ids, recent_src, new_miss_index = look.miss_ids, [], None
        rows, reused = self._gather(fresh_ids)
        rows = self._cast(rows)
        dt = time.perf_counter() - t0
        # deferred accounting commits only after the gather succeeded,
        # and against the ORIGINAL classification — the recent-LRU split
        # below only rewrites the transfer plan, not the hit/miss truth
        if self.cache is not None:
            self.cache.record_lookup(look)
        n_recent = look.num_miss - int(fresh_ids.shape[0])
        self._account("stats", LoadStats(
            rows=rows.shape[0], bytes=rows.nbytes, seconds=dt,
            total_rows=look.num_rows, unique_rows=look.num_unique,
            hit_rows=look.num_hit,
            saved_bytes=look.num_hit * row_bytes,
            dedup_saved_bytes=look.dup_miss_rows * row_bytes,
            recent_rows=n_recent,
            recent_saved_bytes=n_recent * row_bytes,
            stall_seconds=self._source_stall() - stall0,
            **self._gathers(reused)))
        shipped = None
        if use_recent:
            # rewrite the lookup onto the combined source layout and
            # register this batch's fresh rows for future reuse
            look.miss_ids = fresh_ids
            look.miss_index = new_miss_index
            shipped = _ShippedBlock(ids=fresh_ids, version=look.version)
            with self._recent_lock:
                dq = self._recent.get(recent_key)
                if dq is None or dq.maxlen != self.recent_batches:
                    dq = deque(dq or (), maxlen=self.recent_batches)
                    self._recent[recent_key] = dq
                dq.append(shipped)
        return MissBlock(rows=rows, lookup=look, recent=recent_src,
                         shipped=shipped)

    def load_union(self, batches: Dict[str, MiniBatch],
                   ordinals: Dict[str, int],
                   pin: bool = False) -> Dict[str, "ShardMissBlock"]:
        """Sharded-plane load: ONE host gather for the union of every
        accelerator trainer's fresh-miss set.

        Requires the loader's cache to be a ``ShardedFeatureCache``.
        All frontiers are classified in one ``lookup_union`` (local /
        peer / fresh per trainer, every shard pinned once per trainer
        when ``pin``), the union of the fresh sets is gathered once, and
        each trainer's block receives only its slice (the multicast:
        each union row is replicated only to the devices that need it).

        Accounting models the physical route on real hardware: a union
        row crosses PCIe once (``bytes``); the extra copies for trainers
        sharing it, and the peer-shard row hops, ride the accelerator
        interconnect (``ici_bytes``).  ``union_saved_bytes`` is the PCIe
        traffic avoided vs n independent per-trainer dedup gathers —
        the quantity the bench/CI gate compares.  Deferred accounting:
        per-shard stats/hotness commit only after the gather succeeded
        (``record_union``), mirroring ``load_compact``."""
        cache = self.cache
        if cache is None or not hasattr(cache, "lookup_union"):
            raise RuntimeError("load_union requires a ShardedFeatureCache")
        t0 = time.perf_counter()
        stall0 = self._source_stall()
        frontiers = {name: self._frontier(b) for name, b in batches.items()}
        with span("hyscale.load.lookup"):
            union = cache.lookup_union(frontiers, ordinals, pin=pin,
                                       record=False)
        fresh_sets = [sl.look.miss_ids
                      for sl in union.per_trainer.values()
                      if sl.look.miss_ids.shape[0]]
        if fresh_sets:
            union_ids = np.unique(np.concatenate(fresh_sets))
        else:
            union_ids = np.zeros(0, dtype=np.int64)
        rows, reused = self._gather(union_ids)
        rows = self._cast(rows)
        dt = time.perf_counter() - t0
        cache.record_union(union)
        row_bytes = cache.row_bytes
        out: Dict[str, ShardMissBlock] = {}
        tot_pos = tot_uniq = tot_local = 0
        tot_peer_pos = tot_peer_rows = tot_fresh = dup_pos = 0
        for name in sorted(union.per_trainer):
            sl = union.per_trainer[name]
            look = sl.look
            # the trainer's multicast slice: union rows are sorted by id
            # and miss_ids is a sorted subset, so searchsorted is exact
            idx = np.searchsorted(union_ids, look.miss_ids)
            out[name] = ShardMissBlock(rows=rows[idx], lookup=look,
                                       shard=sl)
            tot_pos += look.num_rows
            tot_uniq += look.num_unique
            tot_local += look.num_hit
            tot_peer_pos += sl.peer_positions
            tot_peer_rows += sl.peer_rows
            tot_fresh += look.num_miss
            dup_pos += (look.miss_positions - sl.peer_positions
                        - look.num_miss)
        self.release([rows])       # every trainer holds a copy of its slice
        multicast_extra = tot_fresh - int(union_ids.shape[0])
        self._account("stats", LoadStats(
            rows=int(union_ids.shape[0]), bytes=rows.nbytes, seconds=dt,
            total_rows=tot_pos, unique_rows=tot_uniq,
            hit_rows=tot_local + tot_peer_pos,
            saved_bytes=tot_local * row_bytes,
            dedup_saved_bytes=dup_pos * row_bytes,
            peer_rows=tot_peer_rows,
            peer_saved_bytes=tot_peer_pos * row_bytes,
            union_saved_bytes=multicast_extra * row_bytes,
            ici_bytes=(tot_peer_rows + multicast_extra) * row_bytes,
            stall_seconds=self._source_stall() - stall0,
            **self._gathers(reused)))
        return out

    def load_misses(self, batch: MiniBatch) -> MissBlock:
        """Gather only the frontier rows the device cache does not hold
        (deduped unless the loader was built with ``dedup=False``)."""
        if self.cache is None:
            raise RuntimeError("load_misses requires a FeatureCache")
        return self.load_compact(batch)
