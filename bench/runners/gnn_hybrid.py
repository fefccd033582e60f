"""Runner of the ``gnn_hybrid`` kind: one run of a cell of the hybrid
CPU + TPU GNN trainer (``repro.core.HybridGNNTrainer``).

Set-up, in order (all of it is ``setup_s``):

1. the configuration's graph (fixed by its ``graph_seed``), and features
   and labels from ``--seed`` (``datagen.py``), handed to the program as
   materialised host arrays;
2. the trainer, built with the traffic's ``HybridConfig`` fields; its
   weights are replaced by the benchmark's own (``reference.init_params``
   from the seed) before the first step;
3. the checked steps: the first ``CHECKED_STEPS`` iterations in one
   ``train`` call, pipelined as the window's are (later batches are
   sampled, loaded and shipped while earlier ones train), with what the
   trainer fed its compiled steps and the first gradient its optimizer
   got recorded for the comparison (``compare.py``);
4. the warm-up: ``train`` calls of ``SETTLE_ITERS`` iterations until one
   of them builds no program, or the traffic's ``warmup_max_iters`` in
   all; the last call's iteration times size the window.

The trainer must run the regime the traffic states (the sampler it
names, a device cache where it asks for one, the features a host
array): a run that finds another refuses to go on.

The window is one ``train(N)`` call, with ``N`` chosen so that it lasts
about ``--seconds``.  After it the device's peak memory is read, the
trainer is closed and freed, and the reference replays the checked steps.
"""
from __future__ import annotations

import gc
import os
import resource
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import compare, datagen, flops, peaks, reference, trace
from bench.compiles import CompileClock

CHECKED_STEPS = 3
# the warm-up ends after a train call of this many iterations in which no
# program was built (the combine compiles a program for each new bucket
# of distinct rows, and rare buckets keep coming for hundreds of batches)
SETTLE_ITERS = 100


def _say(msg: str) -> None:
    print(msg, flush=True)


class Recorder:
    """Keeps what the trainer fed its compiled steps while attached: for
    each iteration and trainer, the sampled ids, its share and its
    layer-0 input (host copies)."""

    def __init__(self, tr) -> None:
        self.tr = tr
        self.steps: List[Dict[str, dict]] = []
        self._orig = tr._run_trainers
        tr._run_trainers = self._run

    def _run(self, item):
        out = self._orig(item)
        p = item.payload
        self.steps.append({
            name: {"targets": np.asarray(mb.targets, np.int64),
                   "hop_src": [np.asarray(s, np.int64) for s in mb.hop_src],
                   "x0": np.asarray(p["features"][name]),
                   "share": int(p["shares"][name])}
            for name, mb in p["minibatch"].items()})
        return out

    def detach(self) -> None:
        del self.tr._run_trainers


class Ticks:
    """Wall-clock time and programs built so far at each iteration
    boundary while attached."""

    def __init__(self, runtime, clock: CompileClock) -> None:
        self.runtime = runtime
        self.clock = clock
        self.stamps: List[float] = []
        self.builds: List[int] = []
        self._orig = runtime.end_iteration
        runtime.end_iteration = self._end

    def _end(self, times):
        self.stamps.append(time.perf_counter())
        self.builds.append(self.clock.builds)
        return self._orig(times)

    def steady_seconds(self, last: int) -> float:
        """Mean iteration time over the ``last`` boundaries (one ``train``
        call), leaving out iterations in which a program was built."""
        last = max(last, 2)
        dt = np.diff(self.stamps[-last:])
        built = np.diff(self.builds[-last:]) > 0
        quiet = dt[~built]
        return float(np.mean(quiet if quiet.size else dt))

    def detach(self) -> None:
        del self.runtime.end_iteration


def make_data(config: dict, seed: int) -> dict:
    """The configuration's graph (one per configuration, from its
    ``graph_seed``, as a dataset is fixed), and the features and labels
    of ``seed``."""
    t0 = time.perf_counter()
    g = datagen.make_graph(config["num_nodes"], config["num_edges"],
                           config["hub_exponent"], config["graph_seed"],
                           undirected=config["undirected"])
    t1 = time.perf_counter()
    x = datagen.make_features(config["num_nodes"], config["layer_dims"][0],
                              seed)
    labels = datagen.make_labels(config["num_nodes"], config["num_classes"],
                                 seed)
    t2 = time.perf_counter()
    _say(f"setup: graph |V|={g.num_nodes:,} |E|={g.num_edges:,} in "
         f"{t1 - t0:.3f} s; features {x.shape} {x.dtype} "
         f"({x.nbytes / 1e9:.3f} GB, materialised in host RAM) and labels "
         f"in {t2 - t1:.3f} s")
    return {"graph": g, "x": x, "labels": labels, "gen_s": t2 - t0}


def build_trainer(config: dict, traffic: dict, data: dict, seed: int,
                  overrides: Optional[dict] = None):
    """The trainer on the cell's data, with the benchmark's weights;
    returns (trainer, initial weights as host arrays)."""
    import jax
    from repro.core import HybridConfig, HybridGNNTrainer
    from repro.graph import CSRGraph, GNNConfig, GraphDataset
    g = data["graph"]
    dims = tuple(config["layer_dims"])
    ds = GraphDataset(name=config["name"],
                      graph=CSRGraph(indptr=g.indptr, indices=g.indices),
                      features=data["x"], labels=data["labels"],
                      num_classes=config["num_classes"], feat_dim=dims[0],
                      layer_dims=dims)
    gnn = GNNConfig(model=config["model"], layer_dims=dims,
                    fanouts=tuple(config["fanouts"]),
                    num_classes=config["num_classes"])
    fields = {"feature_dtype": config["feature_dtype"], **traffic["hybrid"],
              **(overrides or {})}
    tr = HybridGNNTrainer(ds, gnn, HybridConfig(
        total_batch=config["batch"], seed=datagen.sub_seed(seed, 1),
        **fields))
    params0 = reference.init_params(
        jax.random.PRNGKey(datagen.sub_seed(seed, 2)), config["model"], dims)
    dev = next(iter(jax.tree.leaves(tr.params)[0].devices()))
    tr.params = jax.device_put(params0, dev)
    tr.opt_state = tr.optimizer.init(tr.params)
    return tr, {k: np.asarray(v, np.float64) for k, v in params0.items()}


def checked_steps(tr, params0: dict) -> dict:
    """The first steps through one ``train`` call, recorded; the
    program's side of the comparison.  The first gradient is read from
    the optimizer's first moment right after the first update."""
    b1 = reference.ADAMW["b1"]
    rec = Recorder(tr)
    first_grad: Dict[str, np.ndarray] = {}
    orig = tr._apply_update

    def update(grads):
        out = orig(grads)
        if not first_grad:
            first_grad.update({k: np.asarray(v, np.float64) / (1 - b1)
                               for k, v in tr.opt_state["m"].items()})
        return out

    tr._apply_update = update
    try:
        hist = tr.train(CHECKED_STEPS)[-CHECKED_STEPS:]
    finally:
        rec.detach()
        del tr._apply_update
    losses = [float(m.loss) for m in hist]
    return {"losses": losses, "first_grad": first_grad, "params0": params0,
            "params": {k: np.asarray(v, np.float64)
                       for k, v in tr.params.items()},
            "steps": rec.steps}


def reference_blocks(steps: List[Dict[str, dict]], data: dict,
                     fanouts, x0_of=None) -> list:
    """Per step, one block ``(x0, degrees, labels)`` of all its trainers'
    targets, from the benchmark's own data (``x0_of`` may alter the
    layer-0 rows)."""
    deg = data["graph"].degrees()
    out = []
    for blocks in steps:
        targets, hops = reference.merge_blocks(
            [(b["targets"], b["hop_src"]) for b in blocks.values()], fanouts)
        ids, d = reference.block_arrays(targets, hops, deg)
        x0 = data["x"][ids]
        if x0_of is not None:
            x0 = x0_of(x0)
        out.append([(x0, d.astype(np.float32),
                     data["labels"][targets].astype(np.int32))])
    return out


def x0_gap(steps: List[Dict[str, dict]], data: dict) -> float:
    worst = 0.0
    deg = data["graph"].degrees()
    for blocks in steps:
        for b in blocks.values():
            ids, _ = reference.block_arrays(b["targets"], b["hop_src"], deg)
            want = data["x"][ids]
            if b["x0"].shape != want.shape:
                return float("inf")
            diff = np.abs(b["x0"].astype(np.float64) - want)
            worst = max(worst, float(diff.max(initial=0.0)))
    return worst


def compared(config: dict, prog: dict, data: dict, ref: dict) -> dict:
    g = data["graph"]
    return compare.numbers(prog, ref, prog["steps"],
                           x0_gap(prog["steps"], data), g.indptr, g.indices,
                           config["fanouts"], config["layer_dims"][0],
                           config["batch"])


def run_reference(config: dict, prog: dict, data: dict, dtype=None,
                  precision: str = "highest", **kw) -> dict:
    import jax.numpy as jnp
    return reference.run_steps(prog["params0"], config["model"],
                               config["fanouts"],
                               reference_blocks(prog["steps"], data,
                                                config["fanouts"], **kw),
                               dtype=dtype or jnp.float32,
                               precision=precision)


def _stage_means(hist) -> Dict[str, float]:
    def mean(f):
        return float(np.mean([f(m) for m in hist])) if hist else 0.0
    return {
        "sample_s": mean(lambda m: m.times.t_sa + m.times.t_sc),
        "load_s": mean(lambda m: m.times.t_load),
        "transfer_s": mean(lambda m: m.times.t_tran),
        "train_accel_s": mean(lambda m: m.times.t_ta),
        "train_cpu_s": mean(lambda m: m.times.t_tc),
        "update_s": mean(lambda m: m.t_sync),
        "cpu_share": mean(lambda m: m.assignment[0]
                          / (m.assignment[0] + m.assignment[1])),
    }


def check_regime(tr, traffic: dict) -> str:
    """What the trainer runs, in words; raises where that is not the
    regime the traffic states."""
    h = traffic["hybrid"]
    on_device = tr._dev_topology is not None
    cached = tr.cache is not None
    host_array = isinstance(tr.dataset.features, np.ndarray)
    said = (f"sampler {'device (CSR in HBM)' if on_device else 'host'}; "
            f"features {type(tr.dataset.features).__name__}"
            f"{' in host RAM' if host_array else ''}; cache "
            f"{f'{tr.cache.capacity:,} rows' if cached else 'off'}")
    if (on_device != bool(h["use_accel_sampler"])
            or cached != (h["cache_fraction"] > 0) or not host_array):
        raise RuntimeError(
            f"the trainer runs {said}, not the regime traffic "
            f"{traffic['name']!r} states (use_accel_sampler "
            f"{h['use_accel_sampler']}, cache_fraction "
            f"{h['cache_fraction']}, features a host array)")
    return said


def warm_up(tr, traffic: dict, ticks: Ticks) -> int:
    """``train`` calls of ``SETTLE_ITERS`` iterations until one builds no
    program, at most ``warmup_max_iters`` in all; returns the length of
    the last call."""
    cap = int(traffic["warmup_max_iters"])
    done = 0
    while True:
        n = min(SETTLE_ITERS, cap - done)
        before = ticks.clock.builds
        tr.train(n)
        done += n
        if ticks.clock.builds == before or done >= cap:
            return n


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run(ctx) -> Dict[str, Any]:
    import jax
    cfg, trf, args = ctx.cell.config, ctx.cell.traffic, ctx.args
    clock = CompileClock()
    data = make_data(cfg, args.seed)
    b0, m0 = clock.builds, clock.misses
    t0 = time.perf_counter()
    tr, params0 = build_trainer(cfg, trf, data, args.seed)
    build_s = time.perf_counter() - t0
    try:
        regime = check_regime(tr, trf)
        _say(f"setup: trainer built in {build_s:.3f} s; cpu trainer on "
             f"{tr.cpu_device}, accel on "
             f"{[str(d) for d in tr.accel_devices]}; {regime}; Pallas "
             f"combine {tr._assemble_pallas}; design-time shares "
             f"{tr.runtime.quantized_shares()}")
        t0 = time.perf_counter()
        prog = checked_steps(tr, params0)
        checked_s = time.perf_counter() - t0
        _say(f"setup: {CHECKED_STEPS} checked steps in {checked_s:.3f} s, "
             f"losses {prog['losses']}")
        ticks = Ticks(tr.runtime, clock)
        b_warm = clock.builds
        t0 = time.perf_counter()
        try:
            last = warm_up(tr, trf, ticks)
        finally:
            ticks.detach()
        warm_s = time.perf_counter() - t0
        est = ticks.steady_seconds(last)
        per_iter = np.diff([b_warm] + ticks.builds).tolist()
        _say(f"setup: programs built in each warm-up iteration {per_iter}")
        n_iters = max(1, int(round(args.seconds / est)))
        setup_s = time.perf_counter() - ctx.t_start
        _say(f"setup: warm-up {len(ticks.stamps)} iterations in "
             f"{warm_s:.3f} s (steady {est:.4f} s each over the last "
             f"{last}); programs built "
             f"{clock.builds - b0}, {clock.misses - m0} of them missing the "
             f"compile cache ({clock.seconds:.3f} s in all); generation "
             f"{data['gen_s']:.3f} s; set-up "
             f"{setup_s:.3f} s; window of {n_iters} iterations")

        stats0 = tr.loader.snapshot_stats()
        b_win, m_win = clock.builds, clock.misses
        h0 = len(tr.history)
        trace_dir = os.path.join(ctx.out_dir, "trace")
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options())
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0 = time.perf_counter()
            tr.train(n_iters)
            window_s = time.perf_counter() - t0
        if args.trace:
            jax.profiler.stop_trace()
        compiles_in_window = clock.builds - b_win
        misses_in_window = clock.misses - m_win
        stats1 = tr.loader.snapshot_stats()
        hist = tr.history[h0:]
        for m in hist:
            t = m.times
            _say(f"iter {m.iteration}: loss {m.loss!r} next shares "
                 f"(cpu, accel) {m.assignment} | sample "
                 f"{t.t_sc + t.t_sa:.4f} load {t.t_load:.4f} transfer "
                 f"{t.t_tran:.4f} train cpu {t.t_tc:.4f} accel "
                 f"{t.t_ta:.4f} update {m.t_sync:.4f} s")
        accel = tr.accel_devices
        mem = [d.memory_stats() or {} for d in accel]
        peak = max((int(s.get("peak_bytes_in_use", 0)) for s in mem),
                   default=0)
        has_cache = tr.cache is not None
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    finally:
        tr.close()
    del tr
    gc.collect()

    traffic = {
        "positions": stats1.total_rows - stats0.total_rows,
        "hit_rows": stats1.hit_rows - stats0.hit_rows,
        "shipped_bytes": stats1.bytes - stats0.bytes,
        "padding_bytes": stats1.padding_bytes - stats0.padding_bytes,
    }
    losses = [m.loss for m in hist]
    failed = int(sum(not np.isfinite(l) for l in losses))
    stages = _stage_means(hist)
    devices = jax.devices()
    dev = devices[0]
    kind_peaks = peaks.peaks_for(dev.device_kind) if ctx.require_tpu else None
    _say(f"window: {n_iters} iterations in {window_s:.6f} s "
         f"({window_s / n_iters:.6f} s each); programs built in window "
         f"{compiles_in_window} ({misses_in_window} missing the compile "
         f"cache); stage means (s) {stages}; traffic "
         f"{traffic}; peak_bytes_in_use {peak}; host peak RSS {rss:.2f} GiB")

    summary = None
    if args.trace:
        t0 = time.perf_counter()
        events = trace.load(trace_dir)
        summary = trace.reduce(events)
        if summary is None and ctx.require_tpu:
            raise RuntimeError(
                f"the trace under {trace_dir} holds no {trace.WINDOW_SPAN!r} "
                f"span with a device operation inside it ({len(events)} "
                f"events read, host lines named {trace.main_thread_name()!r})")
        if summary is not None:
            _say(f"trace: read in {time.perf_counter() - t0:.3f} s; window "
                 f"{summary['window_s']:.6f} s, device busy "
                 f"{summary['busy_s']:.6f} s; modules {summary['modules']}")

    t0 = time.perf_counter()
    ref = run_reference(cfg, prog, data)
    values = compared(cfg, prog, data, ref)
    _say(f"reference: {time.perf_counter() - t0:.3f} s, losses "
         f"{ref['losses']}")
    correct, checks = compare.verdict(values, cfg["limits"])

    record: Dict[str, Any] = {
        "correct": correct, "checks": checks,
        "attempted": n_iters, "failed": failed,
        "setup_s": setup_s, "window_s": window_s, "iters": n_iters,
        "compiles_in_window": compiles_in_window,
        "stages": stages, "traffic": traffic, "has_cache": has_cache,
        "feat_dim": cfg["layer_dims"][0],
        "flops_per_iter": flops.train_flops(cfg["model"], cfg["layer_dims"],
                                            cfg["fanouts"], cfg["batch"]),
        "peaks": kind_peaks, "chips": ctx.cell.chips, "trace": summary,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if summary is not None:
        record["device"]["busy_s"] = summary["busy_s"]
        record["device"]["window_s"] = summary["window_s"]
        record["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    return record
