"""Hybrid GNN training system (paper Sections III + IV glued together).

``HybridGNNTrainer`` wires every logical component of Fig. 3/4 into the
pipelined runtime:

  Mini-batch Sampler (CPU numpy / accelerator jit)      -> Stage "sample"
  Feature Loader (host gather, thread knob)             -> Stage "load"
  Data Transfer (host->device, per accelerator)         -> Stage "transfer"
  GNN Trainers (CPU + n accelerators, unequal shares)   -> consumer
  Synchronizer (weighted all-reduce, Listing-1 handshake)
  Runtime + DRM (per-stage times -> next-iteration assignment)

Ablation knobs reproduce Fig. 11 exactly:
  * ``hybrid=False``                       -> the "baseline" (accel-only),
  * ``hybrid=True,  use_drm=False``        -> "+hybrid" (static perf-model map),
  * ``use_drm=True``                       -> "+DRM",
  * ``tfp_depth>=1``                       -> "+TFP" (two-stage prefetch),
  * ``cache_fraction>0``                   -> "+cache": top-K hot node
    features pinned per accelerator (graph/featcache.py); the load stage
    gathers only cache misses, the transfer stage ships them, and the
    on-device combine (``kernels.ops.assemble_features``, XLA's gather)
    assembles the dense layer-0 input.  The perf model's Eq. 7/8 carry the
    matching (1 - hit_rate) traffic term, so the initial task mapping already
    leans on the cheaper transfer; the DRM then refines from measured
    times as usual.
  * ``dedup=True`` (default)               -> the unit of the whole
    host->device feature path is the *unique node id*: the load stage
    deduplicates each frontier once (np.unique + int32 inverse map),
    classifies only uniques against the cache, gathers/ships only unique
    miss rows, and the on-device combine expands them back into the
    positional [frontier, F] layer-0 layout (the paper's §IV-C Feature
    Duplicator, moved to the far side of the interconnect).  A probe
    mini-batch measures the duplication factor alpha at design time so
    Eq. 7/8 price load/transfer off deduped traffic.  Works with or
    without the cache; ``dedup=False`` reproduces the legacy positional
    path bit-for-bit.

  * ``cache_refresh=True``                 -> dynamic cache: lookups feed
    decayed hotness counters and, on the measured-vs-priced drift signal,
    the coldest cache slots are swapped for strictly-hotter observed
    uncached nodes (DistDGL-style admission).  The device block is
    scatter-updated in place (cache_update kernel: only the aligned row
    blocks holding admitted rows move) and every in-flight TFP payload
    combines against the cache *version* its lookup was classified at, so a
    refresh can never corrupt batches already past the load stage —
    losses are bit-identical with refresh on or off.

  * ``cache_sharding="sharded"``           -> the distributed hot-feature
    plane: each accelerator pins a *disjoint* hot shard (hash or
    degree-range placement), n× effective capacity at the same per-device
    budget.  A frontier row missing locally is pulled from the peer shard
    owning it over the accelerator interconnect (ring-ordered
    ``dist.collectives.exchange_peer_rows``) before falling back to the
    host, and the load stage gathers the *union* of all trainers' miss
    sets once, multicasting each row only to the devices that need it
    (one host gather instead of n).  Losses stay bit-identical to the
    replicated plane — only where bytes travel changes.

  * ``recent_rows_batches>0``              -> cross-iteration device-side
    dedup (replicated path): unique rows shipped in the last N batches
    stay addressable on their device and are re-gathered there instead
    of re-shipped over PCIe; invalidated by any cache refresh.

  * ``prefetch_windows>0`` / ``mmap_lru_windows>0`` / ``async_refresh``
    -> the background storage-I/O subsystem for the disk tier: the sample
    stage hands batch i+1's frontier to a ``WindowPrefetcher`` thread
    that pre-faults its mmap partition windows while batch i loads (so
    the load stage never blocks on cold disk reads; the residual stall
    is DRM-visible as ``StageTimes.t_load_stall``), the window LRU evicts
    with MADV_DONTNEED to bound page-cache residency, and the dynamic
    cache refresh stages its admitted-row gather in a background thread —
    the iteration boundary only pays the cheap ``commit()``.  All three
    are bit-invisible to training losses.

Measured-hit-rate feedback: when the loader's measured cache hit rate
(over the post-refresh window) drifts more than ``cache_drift_threshold``
from the estimate the task mapping was priced with, the initial task
mapping is re-run with the measured rate (and measured alpha) and the
refreshed shares handed to the runtime — the DRM keeps fine-tuning from
there.

Devices (``resolve_trainer_devices``): on a host with TPUs the CPU
trainer runs on the host CPU device and accelerator trainer i on the i-th
TPU.  On the CPU-only backend (``JAX_PLATFORMS=cpu``, the test setting)
host devices stand in for the accelerators; the protocol, queues and
measurements are the same — device kind only changes the programming
layer underneath (paper Section III-C).

Failure model & degraded modes
------------------------------

With ``degrade_on_failure=True`` (default) the trainer survives permanent
failures of its *advisory* background subsystems instead of dying
mid-run: a prefetch worker dead past ``prefetch_restart_budget`` restarts
stops being fed (loads degrade to synchronous cold gathers and the
mapping's ``prefetch_overlap`` re-prices to 0 via the usual overlap-drift
feedback); a failed refresh ``stage()`` discards its plan, keeps serving
the old cache version and retries at the next drift boundary, until
``refresh_failure_budget`` consecutive failures disable refresh for the
run; the storage tier retries transient I/O and falls back to the spill's
backing source for unreadable blobs (see ``graph/storage.py``).  Every
degradation is recorded and surfaced through ``health()`` — never silent.
``degrade_on_failure=False`` restores the legacy fail-fast raises.
``pipeline_watchdog_seconds > 0`` converts a wedged TFP stage into a
diagnostic ``PipelineStallError`` naming the stage and queue depths.
Deterministic chaos testing injects faults at every one of these seams
via the ``fault_injector`` constructor hook (``graph/faults.py``).
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.annotations import guarded_by
from repro.dist.collectives import exchange_peer_rows
from repro.graph import (FeatureLoader, GNNConfig, GraphDataset, MiniBatch,
                         MissBlock, NumpySampler, ShardMissBlock,
                         WindowPrefetcher, build_cache, build_sharded_cache,
                         compact_lookup, init_params, loss_fn,
                         sample_minibatch_jax)
from repro.kernels.ops import assemble_features, assemble_features_sharded
from repro.optim import (CompressionSpec, Optimizer, adamw, compress_grads,
                         decompress_grads)
from repro.optim.optimizers import apply_updates

from .drm import Assignment, KnobAutoTuner, StageTimes
from .perfmodel import (PLATFORMS, CalibratedKnobModel, KnobBounds,
                        KnobState, SignalSnapshot, initial_task_mapping,
                        platform_for_device_kind)
from .pipeline import PipelineItem, PrefetchPipeline, Stage
from .protocol import Runtime, Synchronizer, TrainerHandle, device_of
from .spans import span, step

__all__ = ["HybridConfig", "HybridGNNTrainer", "IterationMetrics",
           "resolve_trainer_devices"]

PyTree = Any


def resolve_trainer_devices(n_accel: int, cpu_devices: Sequence[Any],
                            accel_devices: Sequence[Any]
                            ) -> Tuple[Any, List[Any]]:
    """(CPU trainer's device, [device of accelerator trainer i]).

    With accelerators present the CPU trainer takes the host CPU device
    and trainer i the i-th accelerator; asking for more trainers than
    there are accelerators is an error, never a silent fold of two
    trainers onto one chip.  Without accelerators (the CPU-only backend)
    the host devices stand in: the CPU trainer takes device 0 and trainer
    i device (i + 1) modulo their count, so tests on forced host devices
    keep the CPU trainer and accel0 apart.
    """
    if accel_devices:
        if n_accel > len(accel_devices):
            raise ValueError(
                f"n_accel={n_accel} accelerator trainers but only "
                f"{len(accel_devices)} accelerator devices on this host")
        return cpu_devices[0], list(accel_devices[:n_accel])
    return cpu_devices[0], [cpu_devices[(i + 1) % len(cpu_devices)]
                            for i in range(n_accel)]


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    total_batch: int = 1024
    n_accel: int = 1
    hybrid: bool = True               # CPU trainer participates
    use_drm: bool = True
    tfp_depth: int = 2                # 0 = sequential (no TFP)
    use_accel_sampler: bool = True
    compression: str = "none"         # sync-path gradient compression
    feature_dtype: str = "float32"    # transfer-path compression ("bfloat16")
    cache_fraction: float = 0.0       # device hot-feature cache (0 = off)
    cache_sharding: str = "replicated"  # "replicated" = one identical cache
                                      #   per accelerator (legacy, bit-exact);
                                      #   "sharded" = disjoint hot shard per
                                      #   accelerator (n× effective capacity,
                                      #   peer rows over ICI, union-gather
                                      #   multicast).  Falls back to
                                      #   replicated below 2 accelerators.
    shard_placement: str = "hash"     # sharded-plane placement policy:
                                      #   "hash" (SplitMix64 of the node id)
                                      #   or "degree" (contiguous
                                      #   hotness-rank ranges)
    recent_rows_batches: int = 0      # cross-iteration device-side dedup:
                                      #   rows shipped in the last N batches
                                      #   stay addressable on the device and
                                      #   are not re-shipped (0 = off;
                                      #   replicated/dedup path only)
    kernel_pipeline_depth: int = 1    # Pallas cache-update DMA pipeline
                                      #   depth: 1 = single-buffered, 2..4 =
                                      #   multi-buffered DMA/compute overlap
                                      #   (bit-identical output either way)
    cache_refresh: bool = False       # dynamic cache refresh (DistDGL-style
                                      #   admission on the drift signal)
    cache_refresh_frac: float = 0.25  # max fraction of slots swapped per
                                      #   refresh
    cache_refresh_decay: float = 0.5  # hotness-counter decay per refresh
                                      #   window
    cache_drift_threshold: float = 0.05  # measured-vs-priced hit-rate drift
                                      #   (points) that triggers a cache
                                      #   refresh and a mapping re-price
    cache_refresh_hysteresis: float = 1.25  # admit only when hotter than the
                                      #   victim by this factor (boundary
                                      #   hub sets stop thrashing)
    async_refresh: bool = False       # stage the refresh gather in a
                                      #   background thread; the iteration
                                      #   boundary only pays the cheap
                                      #   table/device-block commit()
    prefetch_windows: int = 0         # background window prefetch queue
                                      #   depth: the sample stage enqueues
                                      #   batch i+1's frontier so its mmap
                                      #   windows are warm when the load
                                      #   stage gathers (0 = off; needs the
                                      #   mmap feature backend)
    prefetch_dedup_history: int = 2   # cross-batch prefetch dedup: remember
                                      #   the last N submitted frontiers and
                                      #   strip already-warm rows from new
                                      #   submits (0 = off)
    mmap_lru_windows: int = 0         # bound on simultaneously open mmap
                                      #   windows; LRU eviction issues
                                      #   MADV_DONTNEED so page-cache use
                                      #   stays O(lru * window_bytes)
                                      #   (0 = unbounded)
    dedup: bool = True                # ship unique rows only (False = legacy
                                      #   one-row-per-frontier-position)
    degrade_on_failure: bool = True   # advisory background subsystems
                                      #   (prefetcher, async refresh) degrade
                                      #   on permanent failure instead of
                                      #   killing the run; False = legacy
                                      #   fail-fast raises
    prefetch_restart_budget: int = 2  # background prefetch-worker respawns
                                      #   (with backoff) before the
                                      #   prefetcher is declared dead
    refresh_failure_budget: int = 3   # consecutive refresh stage() failures
                                      #   before dynamic refresh is disabled
                                      #   for the rest of the run
    pipeline_watchdog_seconds: float = 0.0  # TFP stage-stall watchdog: a
                                      #   stage busy on one item past this
                                      #   deadline raises PipelineStallError
                                      #   instead of hanging (0 = off)
    cache_refresh_period: int = 1     # iteration boundaries between drift
                                      #   checks (refresh cadence; 1 = every
                                      #   boundary, the legacy behaviour)
    auto_tune: bool = False           # model-predictive knob search: the
                                      #   DRM proposes bounded moves in the
                                      #   performance knobs (prefetch queue,
                                      #   window LRU, stage threads, refresh
                                      #   cadence/fraction) from the
                                      #   calibrated Eq. 7/8 model, applies
                                      #   them through the re-price/refresh
                                      #   machinery and rolls back measured
                                      #   regressions.  Never touches RNG
                                      #   streams, batch composition or
                                      #   workload shares: losses stay
                                      #   bit-identical to a static-knob run
    autotune_interval: int = 3        # iterations per measurement window
    autotune_hysteresis: float = 0.10 # measured regression (relative) that
                                      #   rolls a trial move back
    autotune_min_gain: float = 0.02   # predicted gain required to try a move
    autotune_warmup_windows: int = 1  # windows observed before the first
                                      #   proposal (JIT warmup pollutes the
                                      #   earliest measurements)
    initial_threads: Optional[Tuple[int, int, int]] = None
                                      # (sample, load, train) stage-thread
                                      #   start point; None = (2, 2, 2).
                                      #   Benchmarks use this to start the
                                      #   autotuner from a skewed layout
    lr: float = 1e-3
    share_quantum: int = 64
    drm_damping: float = 0.25
    seed: int = 0
    host_platform: str = "epyc-7763"
    accel_platform: str = "tpu-v5e"
    ckpt_every: int = 0               # 0 = disabled
    ckpt_dir: Optional[str] = None


@dataclasses.dataclass
class IterationMetrics:
    iteration: int
    loss: float
    acc: float
    times: StageTimes
    t_sync: float
    edges: int
    assignment: Tuple[int, int]       # (cpu_batch, accel_batch_each)
    grad_devices: Dict[str, str] = dataclasses.field(default_factory=dict)
                                      # trainer -> "platform:id" of the
                                      #   device its gradients came from
    cache_hit_rate: float = 0.0       # measured (epoch-window) cache hit rate
    cache_version: int = 0            # cache version after this iteration
                                      #   (> 0 once a dynamic refresh fired)
    t_wall: float = 0.0               # the iteration's hyscale.step span:
                                      #   batch boundary to batch boundary

    @property
    def iter_time(self) -> float:
        """Wall time of the iteration on the train loop's clock (the DRM
        prices the modelled ``times.iteration_time()`` instead)."""
        return self.t_wall

    @property
    def mteps(self) -> float:
        t = self.iter_time
        return self.edges / t / 1e6 if t > 0 else 0.0


class _TrainerFailure(RuntimeError):
    pass


def _reads_in_place(x: jax.Array, rows: np.ndarray) -> bool:
    """Whether ``x``, just ``device_put`` from ``rows``, reads ``rows``'
    memory in place: XLA's CPU client keeps a host array whose address is
    64-byte aligned instead of copying it."""
    if rows.size == 0 or next(iter(x.devices())).platform != "cpu":
        return False
    lo = rows.ctypes.data
    return lo <= x.unsafe_buffer_pointer() < lo + rows.nbytes


def _grad_fn(gnn_cfg: GNNConfig) -> Callable:
    def _grad(params, batch: MiniBatch, x0):
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, gnn_cfg, batch, x0)
        return grads, {"loss": loss, "acc": acc}

    return jax.jit(_grad)


def _compiled_update(optimizer: Optimizer) -> Callable:
    """``(params, opt_state, grads) -> (params, opt_state)``: the
    optimizer's update and ``apply_updates`` as one program, params and
    state donated (one dispatch, not one per operation and leaf)."""
    def _update(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state

    return jax.jit(_update, donate_argnums=(0, 1))


# Deliberately UNGUARDED shared state: _fail_at (written once before the
# run by the failure-injection test hook, read-only during it),
# _refresh_failures / _refresh_disabled / _staged_feedback /
# _refresh_thread (only ever touched at iteration boundaries on the
# training thread — the refresh worker writes nothing but
# _refresh_error, which IS declared), and everything the pipeline hands
# through PipelineItem payloads (queue happens-before).
@guarded_by("_state_lock", "_failed", "_degraded", "_refresh_error")
class HybridGNNTrainer:
    _assemble_pallas = False  # bench/runners/gnn_hybrid.py prints it

    def __init__(self, dataset: GraphDataset, gnn_cfg: GNNConfig,
                 cfg: HybridConfig, fault_injector=None):
        self.dataset = dataset
        self.gnn_cfg = gnn_cfg
        self.cfg = cfg
        self.fault_injector = fault_injector
        self._rng = np.random.default_rng(cfg.seed)
        self._epoch_perm = self._rng.permutation(dataset.num_nodes)
        self._cursor = 0
        self._failed: set = set()
        self._fail_at: Dict[str, int] = {}
        # degraded-mode record: component -> event dict, surfaced by
        # health(); idempotent per component (first failure wins)
        self._degraded: Dict[str, Dict[str, Any]] = {}
        # guards the failure/degradation record: trainer worker threads
        # add to _failed, pipeline stage threads note degradation, the
        # refresh worker latches _refresh_error — while the training
        # thread (and health()) iterate the same containers
        self._state_lock = threading.Lock()
        self._refresh_failures = 0        # consecutive stage() failures
        self._refresh_disabled = False    # budget spent: refresh is off

        self.cpu_device, self.accel_devices = resolve_trainer_devices(
            cfg.n_accel, jax.devices("cpu"),
            [d for d in jax.devices() if d.platform != "cpu"])
        # the perf model prices the accelerator that is there: a TPU's
        # peaks come from its device kind, the configured platform only
        # stands in for host devices
        self.accel_platform = cfg.accel_platform
        if self.accel_devices and self.accel_devices[0].platform != "cpu":
            self.accel_platform = platform_for_device_kind(
                self.accel_devices[0].device_kind)

        # --- parameters / optimizer (single authoritative copy) -------------
        # committed to their device, as the compiled update returns them,
        # so that its first call builds the program every later call reuses
        key = jax.random.PRNGKey(cfg.seed)
        params = init_params(key, gnn_cfg)
        home = device_of(params)
        self.params = jax.device_put(params, home)
        self.optimizer = adamw(cfg.lr)
        self.opt_state = jax.device_put(self.optimizer.init(params), home)
        self._update_step = _compiled_update(self.optimizer)
        self.compression = CompressionSpec(cfg.compression)

        # --- samplers --------------------------------------------------------
        self.cpu_sampler = NumpySampler(dataset.graph, gnn_cfg.fanouts,
                                        seed=cfg.seed + 1)
        self._dev_topology = None
        if cfg.use_accel_sampler and dataset.graph.nbytes() < (1 << 30):
            self._dev_topology = (jnp.asarray(dataset.graph.indptr),
                                  jnp.asarray(dataset.graph.indices))
            self._jax_sample = jax.jit(partial(sample_minibatch_jax,
                                               fanouts=gnn_cfg.fanouts))
        self._sample_key = jax.random.PRNGKey(cfg.seed + 2)

        # --- background storage I/O (disk tier) ------------------------------
        # the window LRU bounds the page cache; the prefetcher pre-faults
        # batch i+1's windows while batch i trains.  Both are no-ops on
        # RAM-resident sources (nothing to fault, nothing to evict).
        # Wired BEFORE the cache: its boot gather streams through the
        # source and must already respect the window bound.
        src = dataset.feature_source
        if cfg.mmap_lru_windows > 0 and hasattr(src, "lru_windows"):
            src.lru_windows = int(cfg.mmap_lru_windows)
        if fault_injector is not None and hasattr(src, "fault_injector"):
            src.fault_injector = fault_injector
        self.prefetcher: Optional[WindowPrefetcher] = \
            self._build_prefetcher(cfg.prefetch_windows)

        # --- feature store: device hot cache + dedup/miss-only loader --------
        # "sharded" partitions the hot set across the accelerators
        # (disjoint per-device shards, peer rows over ICI, one union
        # gather per batch); below 2 accelerators there is nothing to
        # partition and the plane falls back to the replicated cache.
        if (cfg.cache_sharding == "sharded" and cfg.n_accel >= 2
                and cfg.cache_fraction > 0.0):
            self.cache = build_sharded_cache(
                dataset, cfg.cache_fraction, n_shards=cfg.n_accel,
                placement=cfg.shard_placement,
                transfer_dtype=cfg.feature_dtype,
                refresh_decay=cfg.cache_refresh_decay,
                max_refresh_frac=cfg.cache_refresh_frac,
                refresh_hysteresis=cfg.cache_refresh_hysteresis)
        else:
            self.cache = build_cache(dataset, cfg.cache_fraction,
                                     transfer_dtype=cfg.feature_dtype,
                                     refresh_decay=cfg.cache_refresh_decay,
                                     max_refresh_frac=cfg.cache_refresh_frac,
                                     refresh_hysteresis=cfg
                                     .cache_refresh_hysteresis)
        self._sharded = self.cache is not None and hasattr(self.cache,
                                                           "shards")
        self.loader = FeatureLoader(dataset, transfer_dtype=cfg.feature_dtype,
                                    cache=self.cache, dedup=cfg.dedup,
                                    recent_batches=cfg.recent_rows_batches)
        # design-time Eq. 7 overlap estimate: a running prefetcher is
        # assumed to hide the storage stream (the same design assumption
        # TFP makes for the whole load stage); re-pricing uses the
        # measured prefetch hit rate instead, and an overlap drift alone
        # (an underperforming prefetcher with a stable cache rate) also
        # triggers a re-price — see _maybe_refresh_mapping
        self.prefetch_overlap = 1.0 if self.prefetcher is not None else 0.0
        self._model_prefetch_overlap = self.prefetch_overlap
        # async staged refresh: one stage() gather in flight at most
        self._refresh_thread: Optional[threading.Thread] = None
        self._refresh_error: Optional[BaseException] = None
        self._staged_feedback: Optional[Tuple[float, float]] = None
        if self.cache is not None:
            if fault_injector is not None:
                self.cache.fault_injector = fault_injector
            # the cache update takes the Pallas kernel where it runs on a TPU
            self.cache.use_pallas_update = any(
                d.platform == "tpu" for d in self.accel_devices)
            self.cache.kernel_pipeline_depth = cfg.kernel_pipeline_depth
            # hotness tracking costs two scattered adds per lookup and a
            # 4 B/node estimate array: only pay it when the refresh policy
            # will consume it
            self.cache.track_hotness = cfg.cache_refresh
            # a refresh must retain every device snapshot an in-flight
            # payload can still reference: with TFP depth d at most d
            # batches sit between load (classification) and transfer
            # (combine), and at most one refresh fires per consumed
            # iteration, so d+2 versions always cover the window
            self.cache.keep_versions = max(2, cfg.tfp_depth + 2)
        # out-of-core features (MmapFeatures) gather through host storage,
        # not RAM: Eq. 7 must be priced at storage bandwidth
        self.feature_tier = ("disk" if getattr(self.loader.source,
                                               "is_disk_resident", False)
                             else "ram")
        # measured duplication factor alpha = unique-miss / positional-miss
        # frontier rows, from one probe mini-batch classified against the
        # cache (dedicated sampler + rng so the probe never perturbs the
        # training-path RNG streams: dedup on/off runs stay bit-identical).
        # Only the hybrid task mapping consumes alpha, so accel-only runs
        # skip the probe cost.
        self.measured_dedup_alpha = (
            self._probe_dup_factor() if (cfg.dedup and cfg.hybrid) else 1.0)

        # --- initial task mapping from the performance model (design time) ---
        host = PLATFORMS[cfg.host_platform]
        accel = PLATFORMS[self.accel_platform]
        hit_rate = self.cache.expected_hit_rate if self.cache else 0.0
        self._model_hit_rate = hit_rate   # rate the current mapping is priced on
        if cfg.hybrid and cfg.n_accel == 0:
            # CPU-only degenerate case: the model would otherwise assign
            # work to phantom accelerators (their stages cost nothing in
            # Eq. 7/8) and leave the CPU trainer with an empty share
            mapping = {"cpu": cfg.total_batch, "accel_each": 0}
        elif cfg.hybrid:
            mapping = initial_task_mapping(
                host, accel, cfg.n_accel, cfg.total_batch,
                gnn_cfg.fanouts, gnn_cfg.layer_dims, model=gnn_cfg.model,
                heads=gnn_cfg.heads, share_quantum=cfg.share_quantum,
                cache_hit_rate=hit_rate,
                dedup_factor=self.measured_dedup_alpha,
                feature_tier=self.feature_tier,
                prefetch_overlap=self.prefetch_overlap)
        else:
            mapping = {"cpu": 0,
                       "accel_each": cfg.total_batch // max(cfg.n_accel, 1)}
        thr = cfg.initial_threads or (2, 2, 2)
        assignment = Assignment(
            cpu_batch=mapping["cpu"], accel_batch=mapping["accel_each"],
            n_accel=cfg.n_accel, sample_frac_accel=0.5 if self._dev_topology
            else 0.0,
            threads={"sample": int(thr[0]), "load": int(thr[1]),
                     "train": int(thr[2])})
        self.runtime = Runtime(assignment, use_drm=cfg.use_drm,
                               damping=cfg.drm_damping,
                               share_quantum=cfg.share_quantum)

        # --- model-predictive knob auto-tuning (closes the DRM loop) ---------
        # refresh cadence / admission bookkeeping exists with or without
        # the autotuner: Eq. 7/8 carry the admission term whenever the
        # dynamic cache runs
        self._refresh_period = max(1, int(cfg.cache_refresh_period))
        self._iters_done = 0
        self._iters_since_refresh = 0
        self._refresh_bytes_per_iter = 0.0
        self._hit_decay_per_iter = 0.0
        self._last_load_stats = self.loader.snapshot_stats()
        self._last_windows_touched = int(
            getattr(src, "gather_windows_touched", 0))
        self.autotuner: Optional[KnobAutoTuner] = None
        self._knobs = KnobState(
            prefetch_windows=(cfg.prefetch_windows
                              if self.prefetcher is not None else 0),
            mmap_lru_windows=int(getattr(src, "lru_windows", 0)),
            sample_threads=int(thr[0]), load_threads=int(thr[1]),
            train_threads=int(thr[2]),
            refresh_period=self._refresh_period,
            refresh_frac=float(cfg.cache_refresh_frac))
        if cfg.auto_tune:
            can_prefetch = hasattr(src, "prefetch_rows")
            can_lru = hasattr(src, "lru_windows")
            lru0 = self._knobs.mmap_lru_windows
            refresh_on = cfg.cache_refresh and self.cache is not None
            bounds = KnobBounds(
                prefetch_windows=(0, 64) if can_prefetch else (0, 0),
                # lru == 0 means unbounded: the search may bound it, but
                # never below one window
                mmap_lru_windows=(1, 4096) if can_lru else (lru0, lru0),
                min_stage_threads=1,
                total_threads=self._knobs.total_threads,
                refresh_period=((1, 16) if refresh_on
                                else (self._refresh_period,
                                      self._refresh_period)),
                refresh_frac=((0.05, 0.5) if refresh_on
                              else (self._knobs.refresh_frac,
                                    self._knobs.refresh_frac)))
            self.autotuner = KnobAutoTuner(
                self.runtime.drm, bounds,
                interval=cfg.autotune_interval,
                hysteresis=cfg.autotune_hysteresis,
                min_gain=cfg.autotune_min_gain,
                warmup_windows=cfg.autotune_warmup_windows)

        # --- jit'd gradient functions (one per aggregation path) -------------
        # each call runs on the device its committed inputs live on; the
        # CPU trainer aggregates with jnp, since Pallas kernels run only
        # on TPU arrays
        self._grad_jit = _grad_fn(gnn_cfg)
        self._grad_jit_cpu = (
            _grad_fn(dataclasses.replace(gnn_cfg, agg_impl="dense"))
            if gnn_cfg.agg_impl.startswith("pallas") else self._grad_jit)
        self.history: List[IterationMetrics] = []
        self._ckpt_cb: Optional[Callable[[int, PyTree, PyTree], None]] = None

    # ------------------------------------------------------------ utilities

    def _build_prefetcher(self, windows: int) -> Optional[WindowPrefetcher]:
        """Construct the background window prefetcher (or None when the
        knob is off / the source cannot page-fault).  Shared by __init__
        and the knob autotuner's prefetch_windows moves."""
        src = self.dataset.feature_source
        if windows <= 0 or not hasattr(src, "prefetch_rows"):
            return None
        return WindowPrefetcher(
            src, max_queue=int(windows),
            dedup_history=self.cfg.prefetch_dedup_history,
            restart_budget=self.cfg.prefetch_restart_budget,
            raise_on_failure=not self.cfg.degrade_on_failure,
            fault_injector=self.fault_injector)

    def _probe_dup_factor(self) -> float:
        """Measure alpha = unique-miss / positional-miss frontier rows from
        one probe mini-batch at the accel-only share (the transfer-path
        batch size Eq. 7/8 price).  The probe frontier is classified
        against the device cache exactly like the transfer path: hub ids
        are both the most-cached and the most-duplicated, so the naive
        unique/total ratio would double-count the overlap the model's
        (1 - h) cache term already removed (the definition
        ``_maybe_refresh_mapping`` uses at runtime — both mappings price
        the same alpha for the same traffic).  Uses a throwaway
        sampler/rng so training RNG streams are untouched."""
        probe_n = max(1, self.cfg.total_batch // max(self.cfg.n_accel, 1))
        rng = np.random.default_rng(self.cfg.seed + 17)
        tgt = rng.integers(0, self.dataset.num_nodes, probe_n)
        sampler = NumpySampler(self.dataset.graph, self.gnn_cfg.fanouts,
                               seed=self.cfg.seed + 17)
        mb = sampler.sample(tgt, self.dataset.labels[tgt])
        frontier = np.asarray(mb.frontier(len(self.gnn_cfg.fanouts)))
        look = compact_lookup(
            frontier, self.cache.slot_of if self.cache is not None else None)
        if look.miss_positions == 0:      # fully cached probe: no traffic
            return 1.0
        return look.num_miss / look.miss_positions

    def inject_failure(self, trainer_name: str, at_iteration: int) -> None:
        """Fault-tolerance test hook: trainer dies at the given iteration."""
        self._fail_at[trainer_name] = at_iteration

    def set_checkpoint_callback(self, cb) -> None:
        self._ckpt_cb = cb

    def _next_targets(self, n: int) -> np.ndarray:
        if self._cursor + n > len(self._epoch_perm):
            self._epoch_perm = self._rng.permutation(self.dataset.num_nodes)
            self._cursor = 0
        out = self._epoch_perm[self._cursor:self._cursor + n]
        self._cursor += n
        return out

    def _active_trainers(self) -> List[Tuple[str, str]]:
        """[(name, kind)] excluding failed trainers."""
        out = []
        cpu_b, accel_b = self.runtime.quantized_shares()
        with self._state_lock:
            failed = set(self._failed)
        if cpu_b > 0 and "cpu" not in failed:
            out.append(("cpu", "cpu"))
        for i in range(self.cfg.n_accel):
            name = f"accel{i}"
            if name not in failed and accel_b > 0:
                out.append((name, "accel"))
        return out

    # ------------------------------------------------------- pipeline stages

    def _make_payload(self, it: int) -> PipelineItem:
        cpu_b, accel_b = self.runtime.quantized_shares()
        shares: Dict[str, int] = {}
        for name, kind in self._active_trainers():
            shares[name] = cpu_b if kind == "cpu" else accel_b
        payload = {"iteration": it, "shares": shares, "targets": {},
                   "minibatch": {}, "features": {}, "t": {}}
        for name, n in shares.items():
            payload["targets"][name] = self._next_targets(n)
        return PipelineItem(seq=it, payload=payload)

    def _stage_sample(self, item: PipelineItem) -> PipelineItem:
        p = item.payload
        frac = self.runtime.assignment.sample_frac_accel
        names = list(p["targets"].keys())
        # half up: a lone trainer's batch at the design-time 0.5 is
        # sampled on the accelerator
        n_accel_sampled = (int(frac * len(names) + 0.5)
                           if self._dev_topology is not None else 0)
        t_sc = t_sa = 0.0
        for i, name in enumerate(names):
            tgt = p["targets"][name]
            labels = self.dataset.labels[tgt]
            if i < n_accel_sampled:
                with span("hyscale.sample.accel") as s:
                    self._sample_key, sub = jax.random.split(
                        self._sample_key)
                    mb = self._jax_sample(sub, *self._dev_topology,
                                          jnp.asarray(tgt),
                                          jnp.asarray(labels))
                    mb = jax.block_until_ready(mb)
                t_sa += s.seconds
            else:
                with span("hyscale.sample.cpu") as s:
                    mb = self.cpu_sampler.sample(tgt, labels)
                t_sc += s.seconds
            p["minibatch"][name] = mb
        p["t"]["t_sc"], p["t"]["t_sa"] = t_sc, t_sa
        # TFP lookahead -> background storage I/O: this batch's frontier
        # is known here, one pipeline stage BEFORE its load-stage gather
        # runs, so hand the ids the gather will actually touch (unique,
        # minus rows the device cache will serve) to the window
        # prefetcher.  By the time _stage_load reaches this batch its
        # mmap windows are warm and the gather never blocks on cold disk
        # reads.  submit() never blocks (full queue = drop).  Failure
        # handling depends on degrade_on_failure: legacy fail-fast raises
        # here (surfacing through the pipeline's stage-failure protocol);
        # under degradation a worker that died past its restart budget
        # just stops being fed — loads fall back to synchronous (cold)
        # gathers, the overlap term re-prices to 0, and health() reports
        # the component.
        # snapshot the prefetcher reference: the knob autotuner may swap
        # or drop it from the training thread while this stage runs in a
        # pipeline thread (submit() on a closed prefetcher safely drops)
        pf = self.prefetcher
        if pf is not None and p["minibatch"] and not pf.failed:
            depth = len(self.gnn_cfg.fanouts)
            parts = []
            for name, mb in p["minibatch"].items():
                ids = np.unique(np.asarray(mb.frontier(depth)))
                # the device cache only serves accelerator trainers (the
                # CPU trainer reads its FULL frontier from the source),
                # so only accel frontiers drop their cache-hit rows
                if name != "cpu" and self.cache is not None:
                    ids = ids[self.cache.slot_of[ids] < 0]
                parts.append(ids)
            pf.submit(np.unique(np.concatenate(parts)))
            if pf.failed:
                self._note_degraded(
                    "prefetcher",
                    pf.errors[0] if pf.errors else None,
                    action="window prefetch disabled; loads run "
                           "synchronously and prefetch_overlap re-prices "
                           "to 0")
        return item

    def _stage_load(self, item: PipelineItem) -> PipelineItem:
        p = item.payload
        self.loader.num_threads = self.runtime.assignment.threads.get("load", 1)
        stall0 = self.loader.stats.stall_seconds \
            + self.loader.host_stats.stall_seconds
        # sharded plane: ONE union lookup + host gather covers every
        # accelerator trainer of this batch (each unique miss row is
        # gathered/shipped once and multicast to the devices needing it)
        accel_mbs = {n: mb for n, mb in p["minibatch"].items() if n != "cpu"}
        if self._sharded and accel_mbs:
            ordinals = {n: int(n[len("accel"):]) for n in accel_mbs}
            p["features"].update(
                self.loader.load_union(accel_mbs, ordinals, pin=True))
        for name, mb in p["minibatch"].items():
            if self._sharded and name != "cpu":
                continue      # served by the union gather above
            # accelerator trainers get the compact transfer path (unique
            # miss rows against the on-device hot cache, or plain unique
            # rows when uncached); the CPU trainer's "device" is host
            # memory, so it reads the full positional frontier straight
            # from the FeatureSource and nothing crosses an interconnect.
            if name != "cpu" and (self.cache is not None or self.cfg.dedup):
                # pin the classification version while the block is in
                # flight: the transfer stage releases it after the
                # combine, so drained versions retire device blocks
                # eagerly instead of aging out of keep_versions
                p["features"][name] = self.loader.load_compact(
                    mb, pin=self.cache is not None,
                    recent_key=(name if self.cfg.recent_rows_batches > 0
                                else None))
            else:
                p["features"][name] = self.loader.load(
                    mb, to_device=(name != "cpu"))
        # the loader's buffers behind these rows go back to it once this
        # batch's update has run (_train_step): a CPU trainer may read
        # them in place.  A block the recent-rows LRU registered keeps its
        # rows, since later batches re-read what they were shipped into.
        p["host_rows"] = {
            name: f.rows if isinstance(f, MissBlock) else f
            for name, f in p["features"].items()
            if not (isinstance(f, MissBlock) and f.shipped is not None)}
        # storage-I/O stall share of the load stage (cold mmap faults the
        # prefetcher did not hide) — DRM-visible via StageTimes
        p["t"]["t_load_stall"] = (self.loader.stats.stall_seconds
                                  + self.loader.host_stats.stall_seconds
                                  - stall0)
        return item

    def _assemble(self, block: MissBlock, dev) -> jax.Array:
        """Ship the unique-miss rows + index tables; combine with the
        cached rows and expand back into the dense positional layer-0
        input on the destination device (the on-device duplication step).

        The unique-miss count varies per mini-batch, so the block is
        padded up to a 128-row bucket: the jit'd combine sees a handful of
        distinct shapes instead of one per iteration (sampling noise moves
        the unique-miss count by far less than a bucket), while padding
        waste stays bounded by the bucket size.  Padding rows are zeros no
        miss_index entry points at, and they are charged to the
        shipped-byte stats.
        """
        look = block.lookup
        rows = block.rows
        m = rows.shape[0]
        # never pad beyond the frontier size: the bucket must stay strictly
        # cheaper than the legacy full-frontier transfer
        bucket = min(-(-m // 128) * 128, look.num_rows)
        if m < bucket:
            with span("hyscale.transfer.schedule"):
                pad = bucket - m
                rows = np.concatenate(
                    [rows, np.zeros((pad, rows.shape[1]), rows.dtype)], 0)
            # padding rows cross PCIe too: keep the shipped-byte stats honest
            self.loader.note_transfer_padding(
                pad, pad * rows.shape[1] * rows.dtype.itemsize)
        with span("hyscale.transfer.ship"):
            miss = jax.device_put(rows, dev)
        if block.shipped is not None:
            # publish the device-resident rows for the recent-rows LRU:
            # a later batch's load stage plans against the ids/version
            # (already registered at load time); only the transfer stage
            # — strictly in pipeline order — reads this array, so the
            # single-writer fill is race-free.  Padding rows sit past
            # every recent index (< len(shipped.ids)).
            block.shipped.array = miss
        if block.recent:
            # rows still resident from recent batches: re-gather them on
            # the device instead of re-shipping over PCIe, and lay them
            # out ahead of the fresh block ([recent segments | fresh] —
            # the combined layout load_compact's miss_index addresses)
            segs = [jnp.take(e.array, jnp.asarray(idx), axis=0)
                    for e, idx in block.recent]
            miss = jnp.concatenate(segs + [miss], axis=0)
        # pin the combine to the cache version the lookup was classified
        # against: a dynamic refresh between _stage_load and here must not
        # re-bind the slot indices to a newer (reshuffled) device block
        cache_data = (self.cache.data_on(dev, version=look.version)
                      if self.cache else None)
        if self.cache is not None:
            # the combine holds its own reference to the version block;
            # releasing the pin here lets a fully-drained old version
            # retire its [K, F] snapshots immediately
            self.cache.release_lookup(look)
        # XLA's gather indexes the tables on the device
        with span("hyscale.transfer.ship"):
            slots, miss_index = jax.device_put(
                (look.slots, look.miss_index), dev)
        return assemble_features(cache_data, miss, slots, miss_index)

    def _assemble_sharded(self, block: ShardMissBlock, dev) -> jax.Array:
        """Sharded-plane combine: the dense layer-0 input is assembled
        from the LOCAL shard block (slot hits), rows pulled from peer
        shards over the ICI (ring order), and the fresh host rows the
        union gather shipped — the combined transfer source layout
        ``[peer rows | fresh rows]`` the union lookup's miss_index
        addresses.  Every shard block is resolved at the version the
        lookup pinned, so refreshes mid-pipeline stay bit-invisible."""
        sl = block.shard
        look = block.lookup
        rows = block.rows
        m = rows.shape[0]
        bucket = min(-(-m // 128) * 128, max(look.num_rows, 1))
        if m < bucket:
            pad = bucket - m
            rows = np.concatenate(
                [rows, np.zeros((pad, rows.shape[1]), rows.dtype)], 0)
            self.loader.note_transfer_padding(
                pad, pad * rows.shape[1] * rows.dtype.itemsize)
        miss = jax.device_put(rows, dev)
        me = sl.shard
        local = self.cache.shards[me].data_on(dev, version=look.version)
        # pull peer rows: gather on the owner's device at the pinned
        # version, ship only the requested rows here (the ICI hop)
        peers = exchange_peer_rows(
            sl.peer_requests,
            lambda p, v: self.cache.shards[p].data_on(
                self._accel_device(f"accel{p}"), version=v),
            dev)
        x = assemble_features_sharded(local, peers + [miss], look.slots,
                                      look.miss_index)
        # combine + peer gathers hold their own block references: release
        # every shard pin so drained versions retire eagerly
        self.cache.release_union(sl)
        return x

    def _accel_device(self, name: str):
        """Device of accelerator trainer ``name`` ("accelN" -> ordinal N).

        Indexed by the trainer's own ordinal, not its position in the
        active-trainer list: that list starts with the CPU trainer when it
        is active, which used to shift every accelerator onto its
        neighbour's device.
        """
        return self.accel_devices[int(name[len("accel"):])]

    def _stage_transfer(self, item: PipelineItem) -> PipelineItem:
        p = item.payload
        # iterate the payload's own trainer set, not _active_trainers():
        # with TFP prefetch in flight the DRM may have re-quantized a
        # share to 0 since this batch was sampled — the batch still
        # belongs to the trainers it was sampled for
        with self._state_lock:
            failed = set(self._failed)
        for name in list(p["features"]):
            if name in failed:
                continue
            kind = "cpu" if name == "cpu" else "accel"
            dev = (self.cpu_device if kind == "cpu"
                   else self._accel_device(name))
            feat = p["features"][name]
            if isinstance(feat, ShardMissBlock):
                x = self._assemble_sharded(feat, dev)
            elif isinstance(feat, MissBlock):
                x = self._assemble(feat, dev)
            else:
                with span("hyscale.transfer.ship"):
                    x = jax.device_put(feat, dev)
                if _reads_in_place(x, feat):
                    # the array, and whoever keeps it past this batch,
                    # owns those rows now: the loader never refills them
                    p["host_rows"].pop(name, None)
            with span("hyscale.transfer.ship"):
                mb = jax.device_put(p["minibatch"][name], dev)
            p["features"][name] = x
            p["minibatch"][name] = mb
        with span("hyscale.transfer.wait"):
            jax.block_until_ready([p["features"][n] for n in p["features"]])
        return item

    # ------------------------------------------------------------- training

    def _run_trainers(self, item: PipelineItem
                      ) -> Tuple[PyTree, Dict[str, float], Dict[str, float]]:
        p = item.payload
        # the payload records which trainers this batch was sampled for
        # (and their shares at sampling time); run exactly those, minus
        # any that have since failed.  Intersecting with the *current*
        # assignment instead can come up empty when the DRM re-quantizes
        # a share to 0 while prefetched batches are still in flight.
        with self._state_lock:
            failed = set(self._failed)
        active = [(n, "cpu" if n == "cpu" else "accel")
                  for n in p["minibatch"] if n not in failed]
        if not active:        # every trainer of this batch has died
            zero = jax.tree.map(jnp.zeros_like, self.params)
            return (zero, {"t_tc": 0.0, "t_ta": 0.0},
                    {"loss": float("nan"), "acc": float("nan"),
                     "grad_devices": {}})
        # gradients are summed where the authoritative params live
        sync = Synchronizer(len(active),
                            device=device_of(self.params))
        results: Dict[str, Dict[str, Any]] = {}
        errors: List[Exception] = []

        def work(idx: int, name: str, kind: str):
            if self._fail_at.get(name) == p["iteration"]:
                with self._state_lock:
                    self._failed.add(name)
                zero = jax.tree.map(jnp.zeros_like, self.params)
                sync.submit(idx, zero, 0.0)     # dead trainer: zero weight
                results[name] = {"loss": jnp.nan, "acc": jnp.nan,
                                 "t_train": 0.0, "failed": True}
                return
            handle = TrainerHandle(
                name=name, kind=kind,
                device=(self.cpu_device if kind == "cpu"
                        else self._accel_device(name)),
                grad_fn=(self._grad_jit_cpu if kind == "cpu"
                         else self._grad_jit), index=idx,
                iteration=p["iteration"])
            weight = float(p["shares"][name])
            try:
                metrics = handle.run(sync, self.params, weight,
                                     p["minibatch"][name],
                                     p["features"][name])
            except Exception as e:
                # a trainer whose step raises must not leave the
                # synchronizer waiting for it: hand in nothing, and the
                # error is raised once every trainer has returned
                errors.append(e)
                sync.submit(idx, jax.tree.map(jnp.zeros_like, self.params),
                            0.0)
                return
            results[name] = metrics

        threads = [threading.Thread(target=work, args=(i, n, k))
                   for i, (n, k) in enumerate(active)]
        for t in threads:
            t.start()
        try:
            avg = sync.all_reduce()
        finally:
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        # stage-time bookkeeping for the DRM engine
        t_tc = max((m["t_train"] for n, m in results.items()
                    if n == "cpu"), default=0.0)
        t_ta = max((m["t_train"] for n, m in results.items()
                    if n != "cpu"), default=0.0)
        ok = {n: m for n, m in results.items() if not m.get("failed")}
        w = {n: float(p["shares"][n]) for n in ok}
        wsum = max(sum(w.values()), 1e-9)
        loss = float(sum(float(m["loss"]) * w[n] for n, m in ok.items()) / wsum)
        acc = float(sum(float(m["acc"]) * w[n] for n, m in ok.items()) / wsum)
        return avg, {"t_tc": t_tc, "t_ta": t_ta}, {
            "loss": loss, "acc": acc,
            "grad_devices": {n: m["device"] for n, m in ok.items()}}

    def _window_alpha(self, stats) -> float:
        """Eq. 7/8 alpha from measured window stats: unique-miss /
        positional-miss rows (hub ids are both the most-cached and the
        most-duplicated, so the naive unique/total ratio would
        double-count the overlap the model's (1 - h) cache term already
        removed)."""
        miss_positions = stats.total_rows - stats.hit_rows
        if not (self.cfg.dedup and miss_positions > 0):
            return 1.0
        dedup_saved_rows = stats.dedup_saved_bytes // self.cache.row_bytes
        return 1.0 - dedup_saved_rows / miss_positions

    def _measured_prefetch_overlap(self) -> float:
        """Eq. 7 overlap term from measurement: the fraction of load-stage
        window touches the background prefetcher served warm (falls back
        to the design-time estimate before any disk-tier traffic)."""
        if self.prefetcher is None:
            return 0.0
        if self.prefetcher.failed:
            # a dead prefetcher hides nothing: every future disk touch is
            # a cold fault, so the mapping must price the full storage
            # penalty (this is what drives the re-price-to-0 on failure)
            return 0.0
        src = self.loader.source
        touches = (getattr(src, "prefetch_hit_windows", 0)
                   + getattr(src, "prefetch_miss_windows", 0))
        if touches == 0:
            return self.prefetch_overlap
        return float(src.prefetch_hit_rate)

    def _sharded_pricing(self, measured: float) -> Tuple[float, float, float]:
        """Split the measured hit rate into (local, peer) components and
        derive the union multicast factor from window stats — the
        sharded-plane Eq. 7/8 terms.  The window's ``hit_rate`` counts
        local AND peer-served positions (neither touches the host), so
        the model's ``cache_hit_rate`` gets only the local share."""
        if not self._sharded:
            return measured, 0.0, 1.0
        win = self.loader.window
        if win.total_rows == 0:
            return measured, 0.0, 1.0
        rb = self.cache.row_bytes
        peer = (win.peer_saved_bytes / rb) / win.total_rows
        shipped = win.bytes - win.padding_bytes
        denom = shipped + win.union_saved_bytes
        uf = shipped / denom if denom > 0 else 1.0
        return max(measured - peer, 0.0), peer, uf

    def _reprice_mapping(self, measured: float, alpha: float) -> None:
        """Re-run the initial task mapping with a measured hit rate +
        alpha and hand the refreshed shares to the runtime (the DRM keeps
        fine-tuning from there)."""
        overlap = self._measured_prefetch_overlap()
        local, peer, uf = self._sharded_pricing(measured)
        mapping = initial_task_mapping(
            PLATFORMS[self.cfg.host_platform],
            PLATFORMS[self.accel_platform],
            self.cfg.n_accel, self.cfg.total_batch,
            self.gnn_cfg.fanouts, self.gnn_cfg.layer_dims,
            model=self.gnn_cfg.model, heads=self.gnn_cfg.heads,
            share_quantum=self.cfg.share_quantum, cache_hit_rate=local,
            dedup_factor=alpha, feature_tier=self.feature_tier,
            prefetch_overlap=overlap, peer_hit_rate=peer,
            union_factor=uf,
            refresh_bytes_per_iter=self._refresh_bytes_per_iter)
        self._model_prefetch_overlap = overlap
        a = self.runtime.assignment
        n = max(self.cfg.n_accel, 1)
        a.accel_batch = mapping["accel_each"]
        a.cpu_batch = self.cfg.total_batch - a.accel_batch * n
        self._model_hit_rate = measured
        self.measured_dedup_alpha = alpha

    def _maybe_refresh_cache(self) -> bool:
        """Dynamic cache refresh on the drift signal (tentpole of the
        refresh subsystem): when the *windowed* measured hit rate drifts
        past ``cache_drift_threshold`` from the rate the mapping was
        priced with — the same signal ``_maybe_refresh_mapping`` acts on —
        the static snapshot no longer matches the observed access
        distribution, so swap the coldest slots for the hottest observed
        uncached nodes.  When rows actually move the mapping is re-priced
        *immediately* on the drifted (pre-refresh) measurement — under
        sustained drift the window resets every refresh, so deferring the
        re-price to ``_maybe_refresh_mapping`` would starve it forever —
        and then the measurement window resets so subsequent feedback
        sees only post-refresh traffic.  Returns True when the refresh
        moved rows.
        """
        if self.cache is None or not self.cfg.cache_refresh \
                or self._refresh_disabled:
            return False
        if self.cfg.async_refresh:
            return self._async_refresh_step()
        win = self.loader.window
        if win.total_rows == 0:
            return False
        measured = win.hit_rate
        if abs(measured - self._model_hit_rate) <= \
                self.cfg.cache_drift_threshold:
            return False
        try:
            swapped = self.cache.refresh()
        except Exception as e:
            # degraded mode: keep serving the current cache version and
            # retry at the next drift boundary (bounded by the budget)
            self._handle_refresh_failure(e)
            return False
        self._refresh_failures = 0
        self._finish_refresh(swapped, measured, self._window_alpha(win))
        return swapped > 0

    def _handle_refresh_failure(self, err: BaseException,
                                context: Optional[str] = None) -> None:
        """Shared refresh-failure protocol (sync and async paths): discard
        any staged plan (the current cache version keeps serving), count
        the consecutive failure, and either re-raise (legacy fail-fast,
        ``degrade_on_failure=False``) or degrade — retry at the next
        drift boundary until ``refresh_failure_budget`` consecutive
        failures disable dynamic refresh for the rest of the run."""
        self._refresh_failures += 1
        if self.cache is not None:
            self.cache.discard_staged()
        if not self.cfg.degrade_on_failure:
            if context is not None:
                raise RuntimeError(context) from err
            raise err
        if self._refresh_failures >= self.cfg.refresh_failure_budget \
                and not self._refresh_disabled:
            self._refresh_disabled = True
            self._note_degraded(
                "refresh", err,
                action=f"dynamic cache refresh disabled after "
                       f"{self._refresh_failures} consecutive stage "
                       f"failures; serving cache version "
                       f"{self.cache.version if self.cache else 0}")

    def _finish_refresh(self, swapped: int, measured: float,
                        alpha: float) -> None:
        """Post-refresh bookkeeping shared by the sync and async paths:
        re-price the mapping (or anchor the drift signal) and reset the
        measurement window when rows moved."""
        with self._state_lock:
            any_failed = bool(self._failed)
        reprice = (self.cfg.hybrid and self.cfg.n_accel > 0
                   and not any_failed)
        if swapped:
            # Eq. 7/8 admission term + staleness signal, both measured:
            # the swapped rows crossed host->device once, amortized over
            # the iterations since the previous refresh; the hit-rate gap
            # the refresh just closed, per iteration, is how fast the
            # cached set goes stale at the current cadence
            iters = max(self._iters_since_refresh, 1)
            self._refresh_bytes_per_iter = (
                swapped * self.cache.row_bytes / iters)
            self._hit_decay_per_iter = (
                max(self._model_hit_rate - measured, 0.0) / iters)
            self._iters_since_refresh = 0
            if reprice:
                self._reprice_mapping(measured, alpha)
            else:
                # accel-only (or degenerate) runs have no mapping to
                # re-price; still anchor the drift signal on the measured
                # rate so a converged cache stops re-triggering
                self._model_hit_rate = measured
            self.loader.reset_window()
        elif not reprice:
            # nothing was hotter uncached: the cache already matches the
            # observed distribution, so anchor the drift signal here too —
            # otherwise the armed signal re-runs the O(num_nodes) candidate
            # scan every iteration forever.  Hybrid runs skip this: the
            # mapping feedback (called right after) must still see the
            # drift, and its re-price anchors the same signal.
            self._model_hit_rate = measured

    def _async_refresh_step(self) -> bool:
        """One iteration-boundary step of the staged (off-critical-path)
        refresh.  State machine:

          idle + drift       -> snapshot the drifted measurement, kick the
                                expensive ``stage()`` gather in a
                                background thread, return (no stall);
          stage in flight    -> return (the boundary pays nothing);
          stage finished     -> ``commit()`` (cheap table/device swap) and
                                run the usual post-refresh bookkeeping on
                                the measurement snapshotted at stage time.

        Losses are bit-identical to the sync path (and to refresh off):
        whatever iteration the commit lands on, in-flight TFP payloads
        combine against the cache version their lookup was classified at.
        """
        t = self._refresh_thread
        if t is not None:
            if t.is_alive():
                return False
            self._refresh_thread = None
            with self._state_lock:
                err, self._refresh_error = self._refresh_error, None
            if err is not None:
                self._staged_feedback = None
                self._handle_refresh_failure(
                    err, context="async cache-refresh stage() failed")
                return False
            measured, alpha = self._staged_feedback
            self._staged_feedback = None
            swapped = self.cache.commit()
            self._refresh_failures = 0
            self._finish_refresh(swapped, measured, alpha)
            return swapped > 0
        win = self.loader.window
        if win.total_rows == 0:
            return False
        measured = win.hit_rate
        if abs(measured - self._model_hit_rate) <= \
                self.cfg.cache_drift_threshold:
            return False
        self._staged_feedback = (measured, self._window_alpha(win))

        def run_stage():
            try:
                self.cache.stage()
            except BaseException as e:  # surfaced at the next boundary
                with self._state_lock:
                    self._refresh_error = e

        self._refresh_thread = threading.Thread(
            target=run_stage, daemon=True, name="cache-refresh-stage")
        self._refresh_thread.start()
        return False

    def _maybe_refresh_mapping(self) -> bool:
        """Measured-hit-rate feedback into the perf model (ROADMAP item).

        Eq. 7/8 were priced with the design-time ``expected_hit_rate``;
        when the loader's *measured* transfer-path hit rate drifts more
        than ``cache_drift_threshold`` from the rate the current mapping
        used, re-run ``initial_task_mapping`` with the measured rate (and
        measured duplication factor) and hand the refreshed shares to the
        runtime.  The DRM keeps fine-tuning from the refreshed point.
        The measurement is the post-refresh *window*, not the lifetime
        average: a dynamic cache refresh resets the window, so the mapping
        is re-priced on the rate the refreshed cache actually serves.
        The measured prefetch overlap carries its own drift trigger: an
        underperforming prefetcher (queue-full drops, windows evicted
        before their gather) must re-price the storage penalty even when
        the cache hit rate sits rock-stable inside its threshold.
        Returns True when a refresh happened.
        """
        with self._state_lock:
            any_failed = bool(self._failed)
        if not (self.cfg.hybrid and self.cache is not None) or any_failed:
            return False
        stats = self.loader.window
        if stats.total_rows == 0:
            return False
        measured = stats.hit_rate
        hit_drift = abs(measured - self._model_hit_rate) > \
            self.cfg.cache_drift_threshold
        overlap_drift = (
            self.prefetcher is not None
            and abs(self._measured_prefetch_overlap()
                    - self._model_prefetch_overlap)
            > self.cfg.cache_drift_threshold)
        if not (hit_drift or overlap_drift):
            return False
        self._reprice_mapping(measured, self._window_alpha(stats))
        return True

    # ------------------------------------------- model-predictive knob loop

    def _build_knob_model(self, mean_times: StageTimes,
                          iters: int) -> CalibratedKnobModel:
        """Calibrate the Eq. 7/8 knob model on one measured window: the
        mean stage times anchor the model at the CURRENT knob state, and
        the measured traffic signals (dup factor, prefetch hit/drop
        rates, touched windows, refresh admission, hit-rate decay) let
        ``predict`` re-price only the knob-sensitive components."""
        src = self.loader.source
        cum = self.loader.snapshot_stats()
        prev = self._last_load_stats
        self._last_load_stats = cum
        d_total = max(cum.total_rows - prev.total_rows, 0)
        d_unique = max(cum.unique_rows - prev.unique_rows, 1)
        d_hit = max(cum.hit_rows - prev.hit_rows, 0)
        wt = int(getattr(src, "gather_windows_touched", 0))
        d_windows = max(wt - self._last_windows_touched, 0)
        self._last_windows_touched = wt
        pf = self.prefetcher
        drop_rate = 0.0
        if pf is not None and pf.submitted + pf.dropped > 0:
            drop_rate = pf.dropped / (pf.submitted + pf.dropped)
        row_bytes = (self.cache.row_bytes if self.cache is not None
                     else self.dataset.feat_dim * 4)
        return CalibratedKnobModel(
            host=PLATFORMS[self.cfg.host_platform],
            accel=PLATFORMS[self.accel_platform],
            ref=self._knobs,
            signals=SignalSnapshot(
                t_sc=mean_times.t_sc, t_sa=mean_times.t_sa,
                t_load=mean_times.t_load,
                t_load_stall=mean_times.t_load_stall,
                t_tran=mean_times.t_tran, t_tc=mean_times.t_tc,
                t_ta=mean_times.t_ta,
                dup_factor=(d_total / d_unique if d_total else 1.0),
                hit_rate=(d_hit / d_total if d_total else 0.0),
                prefetch_hit_rate=self._measured_prefetch_overlap(),
                prefetch_drop_rate=drop_rate,
                touched_windows=max(d_windows // max(iters, 1), 1),
                loaded_rows_per_iter=d_unique / max(iters, 1),
                refresh_bytes_per_iter=self._refresh_bytes_per_iter,
                hit_decay_per_iter=self._hit_decay_per_iter,
                row_bytes=int(row_bytes),
                disk_tier=(self.feature_tier == "disk")))

    def _apply_knobs(self, k: KnobState) -> None:
        """Apply one accepted (or rolled-back) knob state through the
        existing machinery: stage threads via the assignment (the loader
        pool rebuilds on its next gather), prefetch queue via
        resize/rebuild/close, window LRU via the source's immediate
        trim, refresh cadence/fraction via the boundary gate and the
        cache's admission bound.  Deliberately never touches workload
        shares, RNG streams or batch composition — losses must stay
        bit-identical to a static-knob run."""
        prev, self._knobs = self._knobs, k
        a = self.runtime.assignment
        a.threads["sample"] = k.sample_threads
        a.threads["load"] = k.load_threads
        a.threads["train"] = k.train_threads
        src = self.loader.source
        if k.mmap_lru_windows != prev.mmap_lru_windows:
            if hasattr(src, "set_lru_windows"):
                src.set_lru_windows(k.mmap_lru_windows)
            elif hasattr(src, "lru_windows"):
                src.lru_windows = int(k.mmap_lru_windows)
        if k.prefetch_windows != prev.prefetch_windows:
            with self._state_lock:
                pf_dead = "prefetcher" in self._degraded
            if k.prefetch_windows <= 0:
                pf, self.prefetcher = self.prefetcher, None
                if pf is not None:
                    pf.close()
            elif self.prefetcher is not None:
                self.prefetcher.resize(k.prefetch_windows)
            elif not pf_dead:
                self.prefetcher = self._build_prefetcher(k.prefetch_windows)
        self._refresh_period = max(1, k.refresh_period)
        if (self.cache is not None
                and k.refresh_frac != prev.refresh_frac):
            shards = self.cache.shards if self._sharded else [self.cache]
            for sh in shards:
                sh.max_refresh_frac = float(k.refresh_frac)

    def _maybe_autotune(self, times: StageTimes) -> None:
        """One iteration-boundary step of the knob autotuner: feed the
        measured StageTimes; when a window closes the tuner may hand back
        a knob state to apply — a new trial move, or the exact pre-move
        state of a trial whose measured iteration time regressed past the
        hysteresis band (rollback)."""
        if self.autotuner is None:
            return
        nxt = self.autotuner.step(times, self._build_knob_model,
                                  self._knobs)
        if nxt is not None:
            self._apply_knobs(nxt)

    def autotune_report(self) -> Dict[str, Any]:
        """Autotuner trajectory + the knob state it converged to."""
        out: Dict[str, Any] = {
            "enabled": self.autotuner is not None,
            "knobs": dataclasses.asdict(self._knobs),
        }
        if self.autotuner is not None:
            out.update(self.autotuner.report())
        return out

    def _apply_update(self, grads: PyTree) -> None:
        if self.compression.method != "none":
            comp = compress_grads(grads, self.compression)
            grads = decompress_grads(comp, self.compression, self.params)
        self.params, self.opt_state = self._update_step(
            self.params, self.opt_state, grads)
        jax.block_until_ready(self.params)

    # ----------------------------------------------------------------- train

    def _train_step(self, item: PipelineItem) -> IterationMetrics:
        """Train one pipelined batch, update, and do the iteration's
        bookkeeping; the train loop's spans after ``hyscale.wait_batch``."""
        p = item.payload
        i = p["iteration"]
        with span("hyscale.train", iteration=i):
            grads, ttimes, metrics = self._run_trainers(item)
        with span("hyscale.update", iteration=i) as up:
            self._apply_update(grads)
            # release the gradient buffers here, not when the frame ends:
            # under a profiler that takes milliseconds, which would fall
            # outside every loop span of the step
            del grads
            # the batch is read: its host feature rows can be refilled
            self.loader.release(p.pop("host_rows", {}).values())
        with span("hyscale.drm", iteration=i):
            times = StageTimes(
                t_sa=p["t"].get("t_sa", 0.0), t_sc=p["t"].get("t_sc", 0.0),
                t_load=item.timings.get("load", 0.0),
                t_tran=item.timings.get("transfer", 0.0),
                t_tc=ttimes["t_tc"], t_ta=ttimes["t_ta"],
                t_load_stall=p["t"].get("t_load_stall", 0.0))
            # account for failures: drop trainers, DRM rebalances the rest
            with self._state_lock:
                failed = set(self._failed)
            if failed:
                a = self.runtime.assignment
                dead_accel = sum(1 for n in failed if n != "cpu")
                if dead_accel and a.n_accel > self.cfg.n_accel - dead_accel:
                    a.cpu_batch += a.accel_batch * dead_accel
                    a.n_accel = self.cfg.n_accel - dead_accel
                # a dead trainer's recent-rows history will never be
                # matched (or filled) again: free it
                for n in failed:
                    self.loader.drop_recent(n)
            self.runtime.end_iteration(times)
            self._iters_done += 1
            self._iters_since_refresh += 1
            # refresh the cache first: when it moves rows it resets the
            # measurement window, so the mapping re-price (next iterations)
            # sees the post-refresh rate instead of a stale average.  The
            # cadence knob gates how often the drift check runs at all
            # (legacy period 1 = every boundary).
            if self._iters_done % self._refresh_period == 0:
                self._maybe_refresh_cache()
            self._maybe_refresh_mapping()
            self._maybe_autotune(times)
            edges = sum(mb.edges_traversed()
                        for mb in p["minibatch"].values())
            m = IterationMetrics(
                iteration=i, loss=metrics["loss"], acc=metrics["acc"],
                times=times, t_sync=up.seconds, edges=edges,
                assignment=self.runtime.quantized_shares(),
                grad_devices=metrics["grad_devices"],
                cache_hit_rate=(self.cache.measured_hit_rate()
                                if self.cache else 0.0),
                cache_version=self.cache.version if self.cache else 0)
            self.history.append(m)
            if (self.cfg.ckpt_every and self._ckpt_cb
                    and (i + 1) % self.cfg.ckpt_every == 0):
                self._ckpt_cb(i, self.params, self.opt_state)
        return m

    def train(self, num_iterations: int) -> List[IterationMetrics]:
        stages = [Stage("sample", self._stage_sample),
                  Stage("load", self._stage_load),
                  Stage("transfer", self._stage_transfer)]
        pipe = PrefetchPipeline(
            stages, depth=self.cfg.tfp_depth,
            watchdog_seconds=self.cfg.pipeline_watchdog_seconds,
            fault_injector=self.fault_injector)
        payloads = (self._make_payload(i) for i in range(num_iterations))
        batches = pipe.run(payloads)
        for i in range(num_iterations):
            with step("hyscale.step", step_num=i) as st:
                with span("hyscale.wait_batch", iteration=i):
                    item = next(batches, None)
                if item is None:        # the batch source ended early
                    break
                m = self._train_step(item)
            m.t_wall = st.seconds
        # the pipeline ends after its last batch: join its threads and
        # surface a stage failure
        next(batches, None)
        # a background failure after the last iteration boundary (final
        # staged gather, final prefetch) would otherwise vanish
        self._raise_background_errors()
        return self.history

    def _raise_background_errors(self) -> None:
        """Surface latched background-I/O failures — a prefetch worker or
        an async ``stage()`` gather that died after its last chance to
        raise in-line (e.g. during the final iterations).  Called at the
        end of ``train()`` and by ``close()``.  Legacy fail-fast mode
        raises (a broken storage tier must never fail silently); in
        degraded mode (``degrade_on_failure=True``) the failures are
        consumed into the ``health()`` record instead — the advisory
        subsystems already degraded, the run is complete, and the state
        is visible rather than fatal."""
        if (self._refresh_thread is None
                or not self._refresh_thread.is_alive()):
            self._refresh_thread = None
            with self._state_lock:
                err, self._refresh_error = self._refresh_error, None
            if err is not None:
                self._handle_refresh_failure(
                    err, context="async cache-refresh stage() failed")
        if self.prefetcher is not None and self.prefetcher.error is not None:
            if not self.cfg.degrade_on_failure:
                err, self.prefetcher.error = self.prefetcher.error, None
                raise RuntimeError(
                    "window prefetch worker failed; storage tier is broken"
                ) from err
            if self.prefetcher.failed:
                self._note_degraded(
                    "prefetcher",
                    self.prefetcher.errors[0] if self.prefetcher.errors
                    else self.prefetcher.error,
                    action="window prefetch disabled; loads run "
                           "synchronously")

    def close(self) -> None:
        """Release background resources (loader pool, window prefetcher,
        any in-flight staged-refresh thread), then surface any failure
        they latched.  Idempotent once the latched errors have raised."""
        if self.prefetcher is not None:
            self.prefetcher.close()
        t = self._refresh_thread
        if t is not None:
            t.join(timeout=30.0)
            self._refresh_thread = None
        self.loader.close()
        self._raise_background_errors()

    # ------------------------------------------------------------- reporting

    def _note_degraded(self, component: str,
                       error: Optional[BaseException],
                       action: str = "") -> None:
        """Record one component's permanent degradation (idempotent: the
        first failure per component wins).  The record feeds ``health()``
        — degraded mode must be visible, never silent.  Callable from any
        thread (pipeline stages note failures too): the check-and-insert
        is atomic under the state lock."""
        with self._state_lock:
            if component in self._degraded:
                return
            self._degraded[component] = {
                "component": component,
                "error": repr(error) if error is not None else "",
                "action": action,
                "iteration": len(self.history),
            }

    def health(self) -> Dict[str, Any]:
        """Degraded-mode / fault-tolerance report.

        ``status`` is ``"ok"`` until any component permanently degraded,
        then ``"degraded"``; ``events`` carries one record per degraded
        component (error, mitigation, iteration).  ``components`` holds
        live per-subsystem counters: prefetcher supervision (restarts /
        errors / healthy), dynamic-refresh failure budget, and the
        storage tier's retry/fallback/hint-failure counters."""
        comp: Dict[str, Any] = {}
        if self.prefetcher is not None:
            comp["prefetcher"] = {
                "healthy": self.prefetcher.healthy,
                "failed": self.prefetcher.failed,
                "restarts": int(self.prefetcher.restarts),
                "errors": len(self.prefetcher.errors),
            }
        if self.cache is not None and self.cfg.cache_refresh:
            comp["refresh"] = {
                "enabled": not self._refresh_disabled,
                "stage_failures": int(self.cache.stage_failures),
                "consecutive_failures": int(self._refresh_failures),
            }
        src = self.loader.source
        if hasattr(src, "io_retries"):
            comp["storage"] = {
                "io_errors": int(src.io_errors),
                "io_retries": int(src.io_retries),
                "io_retry_seconds": float(src.io_retry_seconds),
                "fallback_gathers": int(src.fallback_gathers),
                "fallback_rows": int(src.fallback_rows),
                "madvise_failures": int(src.madvise_failures),
                "fadvise_failures": int(src.fadvise_failures),
            }
        # snapshot under the lock: a trainer thread adding to _failed (or
        # a pipeline stage noting degradation) while this iterates would
        # raise "changed size during iteration"
        with self._state_lock:
            failed = sorted(self._failed)
            degraded = sorted(self._degraded)
            events = [dict(e) for e in self._degraded.values()]
        if failed:
            comp["trainers"] = {"failed": failed}
        return {
            "status": "degraded" if degraded else "ok",
            "degraded": degraded,
            "events": events,
            "components": comp,
        }

    def storage_io(self) -> Dict[str, float]:
        """Background storage-I/O accounting (zeros on RAM tiers):
        prefetch/eviction counters from the mmap source plus the
        cumulative load-stage stall the prefetcher did not hide."""
        src = self.loader.source
        out = {
            "load_stall_seconds": self.loader.stats.stall_seconds
            + self.loader.host_stats.stall_seconds,
            "cold_fault_page_bytes":
                float(getattr(src, "cold_fault_page_bytes", 0)),
            "prefetched_window_bytes":
                float(getattr(src, "prefetched_window_bytes", 0)),
            "evicted_window_bytes":
                float(getattr(src, "evicted_window_bytes", 0)),
            "window_evictions": float(getattr(src, "window_evictions", 0)),
            "pin_blocked_evictions":
                float(getattr(src, "pin_blocked_evictions", 0)),
            "open_windows": float(getattr(src, "open_windows", 0)),
            "prefetch_hit_rate":
                float(getattr(src, "prefetch_hit_rate", 0.0)),
            # fault-tolerance counters (module docstring: failure model)
            "io_retries": float(getattr(src, "io_retries", 0)),
            "io_retry_seconds": float(getattr(src, "io_retry_seconds", 0.0)),
            "io_errors": float(getattr(src, "io_errors", 0)),
            "fallback_gathers": float(getattr(src, "fallback_gathers", 0)),
            "fallback_rows": float(getattr(src, "fallback_rows", 0)),
            "madvise_failures": float(getattr(src, "madvise_failures", 0)),
            "fadvise_failures": float(getattr(src, "fadvise_failures", 0)),
        }
        if self.prefetcher is not None:
            out["prefetch_submitted"] = float(self.prefetcher.submitted)
            out["prefetch_completed"] = float(self.prefetcher.completed)
            out["prefetch_dropped"] = float(self.prefetcher.dropped)
            out["resubmitted_rows_skipped"] = float(
                self.prefetcher.resubmitted_rows_skipped)
        return out

    def mean_mteps(self, skip: int = 2) -> float:
        hist = self.history[skip:] or self.history
        return float(np.mean([m.mteps for m in hist]))

    def mean_iter_time(self, skip: int = 2) -> float:
        hist = self.history[skip:] or self.history
        return float(np.mean([m.iter_time for m in hist]))

    def feature_traffic(self) -> Dict[str, float]:
        """Cumulative feature-movement accounting for the whole run.

        ``shipped_bytes`` is what actually crossed host->device (gathered
        unique misses plus any shape-bucket padding); ``saved_bytes`` is
        what the device cache absorbed; ``dedup_saved_bytes`` what
        frontier deduplication absorbed; ``host_read_bytes`` is the CPU
        trainer's direct host-memory reads (never on PCIe, tracked
        separately).  ``hit_rate``/``reduction`` therefore describe the
        transfer path only; gathered + cache-saved + dedup-saved bytes
        always reconstruct the legacy one-row-per-position baseline.
        """
        s = self.loader.stats
        # legacy baseline = every requested frontier position shipped
        # (= gathered unique-miss bytes + bytes the cache absorbed + bytes
        # dedup absorbed + bytes peer shards / the union multicast / the
        # recent-rows LRU absorbed; padding is an artifact of the compact
        # path, not part of the baseline).  The sharded/recent terms are 0
        # on the replicated path, so legacy runs reconstruct exactly.
        baseline = ((s.bytes - s.padding_bytes) + s.saved_bytes
                    + s.dedup_saved_bytes + s.peer_saved_bytes
                    + s.union_saved_bytes + s.recent_saved_bytes)
        return {
            "shipped_rows": float(s.rows),
            "shipped_bytes": float(s.bytes),
            "saved_bytes": float(s.saved_bytes),
            "dedup_saved_bytes": float(s.dedup_saved_bytes),
            "peer_rows": float(s.peer_rows),
            "peer_saved_bytes": float(s.peer_saved_bytes),
            "union_saved_bytes": float(s.union_saved_bytes),
            "ici_bytes": float(s.ici_bytes),
            "recent_rows": float(s.recent_rows),
            "recent_saved_bytes": float(s.recent_saved_bytes),
            "padding_bytes": float(s.padding_bytes),
            "host_read_bytes": float(self.loader.host_stats.bytes),
            "hit_rate": s.hit_rate,
            "dup_factor": s.dup_factor,
            "reduction": baseline / max(s.bytes, 1),
        }
