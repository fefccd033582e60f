"""End-to-end driver (deliverable b): train a 2-layer GraphSAGE/GCN with
hidden 256 — the paper's model setup — for a few hundred iterations on a
synthetic papers100M-scaled graph, exercising the FULL system: hybrid
trainers, DRM, two-stage prefetching, checkpointing, fault injection.

    PYTHONPATH=src python examples/hybrid_gnn_training.py \
        --model sage --iters 200 --scale 2e-4
"""
import argparse
import os
import tempfile

import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.core import HybridConfig, HybridGNNTrainer
from repro.graph import GNNConfig, make_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="sage", choices=["sage", "gcn"])
    ap.add_argument("--dataset", default="ogbn-papers100M")
    ap.add_argument("--scale", type=float, default=2e-4)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--fanouts", default="10,5")
    ap.add_argument("--n-accel", type=int, default=2)
    ap.add_argument("--agg-impl", default="dense",
                    choices=["dense", "segsum", "pallas", "pallas_fused"])
    ap.add_argument("--cache-fraction", type=float, default=0.0,
                    help="pin this fraction of the hottest node features "
                         "on each accelerator (0 = off)")
    ap.add_argument("--cache-sharding", default="replicated",
                    choices=["replicated", "sharded"],
                    help="'sharded' partitions the hot set into disjoint "
                         "per-accelerator shards (n x effective capacity "
                         "at the same per-device budget): local misses "
                         "are served from peer shards over the device "
                         "interconnect before host PCIe, and the host "
                         "gathers the union of all trainers' miss sets "
                         "once, multicasting per-device slices "
                         "(losses stay bit-identical to replicated)")
    ap.add_argument("--shard-placement", default="hash",
                    choices=["hash", "degree"],
                    help="shard placement policy: 'hash' spreads rows "
                         "uniformly (balanced occupancy), 'degree' keeps "
                         "contiguous hotness-rank ranges co-resident")
    ap.add_argument("--recent-rows-batches", type=int, default=0,
                    help="cross-iteration device-side dedup: remember the "
                         "last N batches' shipped rows per accelerator "
                         "and reuse the device-resident copies instead "
                         "of re-shipping over PCIe (invalidated on cache "
                         "refresh; 0 = off)")
    ap.add_argument("--cache-refresh", action="store_true",
                    help="dynamic cache refresh: track observed per-slot / "
                         "uncached hotness and swap the coldest slots for "
                         "strictly-hotter uncached nodes whenever the "
                         "measured hit rate drifts from the rate the task "
                         "mapping was priced with (DistDGL-style "
                         "admission; versioned lookups keep in-flight TFP "
                         "batches bit-identical)")
    ap.add_argument("--cache-refresh-frac", type=float, default=0.25,
                    help="max fraction of cache slots swapped per refresh")
    ap.add_argument("--cache-refresh-decay", type=float, default=0.5,
                    help="hotness-counter decay applied at each refresh "
                         "window boundary (1.0 = never forget, smaller = "
                         "adapt faster to drift)")
    ap.add_argument("--cache-drift-threshold", type=float, default=0.05,
                    help="measured-vs-priced hit-rate drift (in rate "
                         "points) that triggers a cache refresh and a "
                         "task-mapping re-price")
    ap.add_argument("--feature-backend", default="auto",
                    choices=["auto", "dense", "hashed", "partitioned",
                             "mmap"],
                    help="feature storage tier: dense/hashed/partitioned "
                         "are RAM-resident; 'mmap' spills per-partition "
                         "blobs to disk (bounded spill RAM, lazily mapped "
                         "windows) for graphs larger than host memory")
    ap.add_argument("--spill-dir", default=None,
                    help="where 'mmap' places its partition blobs "
                         "(default: a private temp dir, removed on exit)")
    ap.add_argument("--prefetch-windows", type=int, default=0,
                    help="background window-prefetch queue depth: the "
                         "sample stage hands batch i+1's frontier to a "
                         "prefetch thread that pre-faults its mmap "
                         "partition windows while batch i trains, so the "
                         "load stage never blocks on cold disk reads "
                         "(0 = off; requires --feature-backend mmap)")
    ap.add_argument("--prefetch-dedup-history", type=int, default=2,
                    help="cross-batch prefetch dedup: the prefetcher "
                         "remembers the last N submitted frontiers and "
                         "strips already-warm rows from new submits, "
                         "cutting background read volume by the "
                         "cross-batch duplication factor (0 = off)")
    ap.add_argument("--kernel-pipeline-depth", type=int, default=1,
                    help="Pallas cache-update DMA pipeline depth: 1 = "
                         "single-buffered, 2-4 = multi-buffered "
                         "DMA/compute overlap (output stays "
                         "bit-identical at every depth)")
    ap.add_argument("--mmap-lru-windows", type=int, default=0,
                    help="bound on simultaneously open mmap partition "
                         "windows: the LRU evicts with MADV_DONTNEED so "
                         "page-cache residency stays "
                         "O(lru_windows x window_bytes) instead of "
                         "trusting kernel reclaim (0 = unbounded)")
    ap.add_argument("--async-refresh", action="store_true",
                    help="stage the dynamic cache refresh's admitted-row "
                         "gather in a background thread; the iteration "
                         "boundary only pays the cheap table/device-block "
                         "commit (losses stay bit-identical — versioned "
                         "lookups)")
    ap.add_argument("--auto-tune", action="store_true",
                    help="model-predictive knob auto-tuning: the DRM "
                         "proposes bounded moves in prefetch depth, "
                         "window LRU, stage threads and refresh "
                         "cadence/fraction from the calibrated Eq. 7/8 "
                         "model, verifying each against measured "
                         "iteration time and rolling back regressions "
                         "(losses stay bit-identical — knobs never touch "
                         "RNG streams or batch composition)")
    ap.add_argument("--autotune-interval", type=int, default=3,
                    help="iterations per autotuner measurement window")
    ap.add_argument("--cache-refresh-period", type=int, default=1,
                    help="iteration boundaries between cache drift "
                         "checks (the refresh-cadence knob; 1 = every "
                         "boundary)")
    ap.add_argument("--inject-failure", type=int, default=0,
                    help="kill accel0 at this iteration (0 = off)")
    ap.add_argument("--fault-schedule", default=None,
                    help="JSON fault schedule for the data plane (a list "
                         "of FaultSpec dicts or {'seed':..,'schedule':..}) "
                         "— injects transient/permanent I/O errors, "
                         "delays or worker kills at named hooks "
                         "(storage.take, prefetch.worker, refresh.stage, "
                         "pipeline.<stage>, ...); deterministic per-op "
                         "call indexing makes every run replayable")
    ap.add_argument("--pipeline-watchdog", type=float, default=0.0,
                    help="TFP stage-stall watchdog (seconds): a pipeline "
                         "stage wedged past this deadline raises a "
                         "diagnostic PipelineStallError naming the stage "
                         "and queue depths instead of hanging (0 = off)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    ds = make_dataset(args.dataset, scale=args.scale, seed=0,
                      feature_backend=args.feature_backend,
                      spill_dir=args.spill_dir,
                      mmap_lru_windows=args.mmap_lru_windows)
    print(f"{ds.name}: |V|={ds.num_nodes:,} |E|={ds.num_edges:,} "
          f"dims={ds.layer_dims}")
    if args.feature_backend == "mmap":
        src = ds.features
        print(f"out-of-core features: {src.num_partitions} partitions of "
              f"{src.partition_rows} rows under {src.spill_dir} "
              f"(spill buffered <= {src.spill_peak_buffered_rows} rows)")
    gnn = GNNConfig(model=args.model, layer_dims=ds.layer_dims,
                    fanouts=fanouts, num_classes=ds.num_classes,
                    agg_impl=args.agg_impl)
    hcfg = HybridConfig(total_batch=args.batch, n_accel=args.n_accel,
                        hybrid=True, use_drm=True, tfp_depth=2, lr=3e-3,
                        cache_fraction=args.cache_fraction,
                        cache_sharding=args.cache_sharding,
                        shard_placement=args.shard_placement,
                        recent_rows_batches=args.recent_rows_batches,
                        cache_refresh=args.cache_refresh,
                        cache_refresh_frac=args.cache_refresh_frac,
                        cache_refresh_decay=args.cache_refresh_decay,
                        cache_drift_threshold=args.cache_drift_threshold,
                        async_refresh=args.async_refresh,
                        prefetch_windows=args.prefetch_windows,
                        prefetch_dedup_history=args.prefetch_dedup_history,
                        kernel_pipeline_depth=args.kernel_pipeline_depth,
                        mmap_lru_windows=args.mmap_lru_windows,
                        pipeline_watchdog_seconds=args.pipeline_watchdog,
                        auto_tune=args.auto_tune,
                        autotune_interval=args.autotune_interval,
                        cache_refresh_period=args.cache_refresh_period,
                        ckpt_every=50 if args.ckpt_dir else 0)
    injector = None
    if args.fault_schedule:
        from repro.graph import FaultInjector
        injector = FaultInjector.from_json(args.fault_schedule)
        print(f"!! fault schedule armed: {len(injector.schedule)} specs "
              f"(seed {injector.seed}) from {args.fault_schedule}")
    tr = HybridGNNTrainer(ds, gnn, hcfg, fault_injector=injector)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        tr.set_checkpoint_callback(
            lambda step, p, o: mgr.save(step, {"params": p, "opt": o}))
    if args.inject_failure:
        tr.inject_failure("accel0", args.inject_failure)
        print(f"!! will inject accel0 failure at iter {args.inject_failure}")

    hist = tr.train(args.iters)
    for m in hist[:: max(args.iters // 10, 1)]:
        t = m.times
        print(f"it {m.iteration:4d} loss {m.loss:.3f} acc {m.acc:.3f} "
              f"| samp {t.t_sc*1e3:5.1f} load {t.t_load*1e3:5.1f} "
              f"tran {t.t_tran*1e3:5.1f} tc {t.t_tc*1e3:6.1f} "
              f"ta {t.t_ta*1e3:6.1f} ms | {m.mteps:6.2f} MTEPS "
              f"| shares {m.assignment}")
    accs = [m.acc for m in hist[-20:]]
    print(f"\nfinal: loss {hist[-1].loss:.3f}  acc(last20) "
          f"{np.mean(accs):.3f}  mean {tr.mean_mteps():.2f} MTEPS")
    if tr.cache is not None:
        tf = tr.feature_traffic()
        print(f"feature cache: hit {tf['hit_rate']:.3f} "
              f"(model {tr.cache.expected_hit_rate:.3f}), shipped "
              f"{tf['shipped_bytes']/1e6:.1f} MB, saved "
              f"{tf['saved_bytes']/1e6:.1f} MB "
              f"({tf['reduction']:.2f}x reduction)")
        if args.cache_sharding == "sharded" and hasattr(tr.cache, "shards"):
            print(f"sharded plane: {len(tr.cache.shards)} shards "
                  f"({args.shard_placement}), {tr.cache.capacity} resident "
                  f"rows, peer-served {tf['peer_rows']:.0f} rows "
                  f"({tf['peer_saved_bytes']/1e6:.1f} MB off PCIe), union "
                  f"gather saved {tf['union_saved_bytes']/1e6:.1f} MB, "
                  f"ICI {tf['ici_bytes']/1e6:.1f} MB")
        if args.recent_rows_batches:
            print(f"recent-rows LRU: {tf['recent_rows']:.0f} rows reused "
                  f"on device ({tf['recent_saved_bytes']/1e6:.1f} MB not "
                  f"re-shipped)")
        if args.cache_refresh:
            print(f"cache refresh: {tr.cache.refreshes} refreshes moved "
                  f"{tr.cache.refresh_swapped_rows} rows "
                  f"(version {tr.cache.version}, windowed hit "
                  f"{tr.cache.measured_hit_rate():.3f})")
    if args.prefetch_windows or args.mmap_lru_windows:
        io = tr.storage_io()
        print(f"storage I/O: stall {io['load_stall_seconds']*1e3:.1f} ms "
              f"({io['cold_fault_page_bytes']/1e6:.1f} MB cold), prefetch "
              f"hit {io['prefetch_hit_rate']:.2f} "
              f"({io['prefetched_window_bytes']/1e6:.1f} MB pre-faulted), "
              f"evicted {io['evicted_window_bytes']/1e6:.1f} MB over "
              f"{io['window_evictions']:.0f} window evictions")
        if "resubmitted_rows_skipped" in io:
            print(f"prefetch dedup: "
                  f"{io['resubmitted_rows_skipped']:.0f} already-warm rows "
                  f"stripped from resubmits")
    if args.auto_tune:
        rep = tr.autotune_report()
        k = rep["knobs"]
        print(f"autotune: {rep['trials']} trials, {rep['accepted']} "
              f"accepted, {rep['rollbacks']} rolled back -> prefetch "
              f"{k['prefetch_windows']}, lru {k['mmap_lru_windows']}, "
              f"threads {k['sample_threads']}/{k['load_threads']}/"
              f"{k['train_threads']}, refresh 1/{k['refresh_period']} "
              f"@ {k['refresh_frac']:.2f}")
        for mv in rep.get("moves", []):
            print(f"  + {mv['move']}: predicted "
                  f"{mv['baseline_predicted']*1e3:.2f} -> "
                  f"{mv['predicted']*1e3:.2f} ms, measured "
                  f"{mv['baseline_wall']*1e3:.2f} -> "
                  f"{mv['measured_wall']*1e3:.2f} ms")
    if tr._failed:
        print(f"survived failures: {sorted(tr._failed)}")
    h = tr.health()
    line = f"health: {h['status']}"
    if h["events"]:
        line += " — " + "; ".join(
            f"{e['component']} (it {e['iteration']}): {e['action']}"
            for e in h["events"])
    st = h["components"].get("storage", {})
    if st.get("io_errors") or st.get("fallback_gathers"):
        line += (f" | storage: {st['io_errors']} I/O errors, "
                 f"{st['io_retries']} retried, "
                 f"{st['fallback_gathers']} fallback gathers")
    print(line)
    if injector is not None:
        print(f"faults injected: {injector.report()}")
    tr.close()


if __name__ == "__main__":
    main()
