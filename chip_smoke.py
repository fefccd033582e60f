"""Smoke run of the hybrid CPU + TPU GNN trainer on the chip.

Trains ``sage-products`` (src/repro/configs/hyscale_gnn.py) at the
paper's full widths — f0 = 100, hidden 256, 47 classes, fanouts (25, 10),
batch 1024 — on the full-size synthetic ogbn-products graph (2.45M
nodes and about 61M edges, generated from ``--seed``), through
``HybridGNNTrainer``, its normal entry point: the CPU trainer on the host
CPU, accel0 on the TPU, a 20% hot-feature cache on the device and XLA's
gather assembling the layer-0 input.  Weights are random.

    python chip_smoke.py              # one chip: the hybrid trainer
    python chip_smoke.py --chips 4    # four chips: n_accel=4 with the
                                      # sharded, then the replicated
                                      # hot-feature plane, and nothing else

Checks: every loss is finite; accel trainers' gradients come from
distinct TPUs and the CPU trainer's (when it has a share) from the host
CPU; the trainer picks XLA's gather for the combine and the Pallas
cache-update kernel; on one real batch the Pallas combine, forced and
compiled, equals ``kernels/ref.py``'s ``assemble_features`` exactly;
``health()`` is ok;
with ``--chips 4`` the sharded and replicated planes give the same
losses.  The last line of the output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time


ONE_CHIP_STEPS = 8
PLANE_STEPS = 4                 # per hot-feature plane on four chips
DEADLINE_SECONDS = 1150         # a trainer thread that dies leaves the
                                # synchronizer waiting: dump every
                                # thread's stack and exit instead


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Backend compiles seen by JAX: count and seconds (set-up, not step
    time)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def build_dataset(scale: float, seed: int):
    from repro.configs.hyscale_gnn import PAPER_BATCH, PAPER_CONFIGS
    from repro.graph import make_dataset
    name, gnn = PAPER_CONFIGS["sage-products"]
    t0 = time.perf_counter()
    ds = make_dataset(name, scale=scale, seed=seed)
    print(f"setup: {ds.name} |V|={ds.num_nodes:,} |E|={ds.num_edges:,} "
          f"dims={ds.layer_dims} fanouts={gnn.fanouts} batch={PAPER_BATCH} "
          f"generated in {time.perf_counter() - t0:.3f} s", flush=True)
    return ds, gnn, PAPER_BATCH


def train_steps(tr, steps: int, clock: CompileClock, tag: str):
    """``steps`` iterations through the trainer's entry point, one call
    each so every step is timed on its own (``train`` returns after the
    optimizer update, which blocks until the parameters are ready)."""
    import numpy as np
    out = []
    for step in range(steps):
        c0, s0 = clock.count, clock.seconds
        t0 = time.perf_counter()
        m = tr.train(1)[-1]
        dt = time.perf_counter() - t0
        t = m.times
        print(f"{tag} step {step}: {dt:.6f} s (compiles {clock.count - c0}, "
              f"{clock.seconds - s0:.3f} s) loss {m.loss!r} "
              f"next shares (cpu, accel_each) {m.assignment} "
              f"grads from {m.grad_devices} | sample {t.t_sc + t.t_sa:.4f} "
              f"load {t.t_load:.4f} transfer {t.t_tran:.4f} "
              f"train cpu {t.t_tc:.4f} accel {t.t_ta:.4f} s", flush=True)
        check(bool(np.isfinite(m.loss)), f"{tag} step {step}: loss {m.loss}")
        out.append(m)
    return out


def check_grad_devices(hist, accel_platform: str, tag: str) -> None:
    for i, m in enumerate(hist):
        accel = {n: d for n, d in m.grad_devices.items() if n != "cpu"}
        check(all(d.startswith(accel_platform + ":")
                  for d in accel.values()),
              f"{tag} step {i}: accelerator gradients from {accel}")
        check(len(set(accel.values())) == len(accel),
              f"{tag} step {i}: two accelerators shared a device: {accel}")
        if "cpu" in m.grad_devices:
            check(m.grad_devices["cpu"].startswith("cpu:"),
                  f"{tag} step {i}: CPU trainer gradients from "
                  f"{m.grad_devices['cpu']}")
    check(any(any(n != "cpu" for n in m.grad_devices) for m in hist),
          f"{tag}: no accelerator trainer ever ran")


def check_combine(tr, gnn, seed: int, accel_platform: str) -> None:
    """One real batch through the trainer's loader, assembled by the
    Pallas combine on accel0 and by the jnp reference; they must agree
    bit for bit (the kernel is a one-hot f32 matmul: exact for finite
    rows)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph import NumpySampler
    from repro.kernels import ops, ref

    ds = tr.dataset
    rng = np.random.default_rng(seed + 99)
    tgt = rng.choice(ds.num_nodes, tr.runtime.quantized_shares()[1] or 512,
                     replace=False)
    mb = NumpySampler(ds.graph, gnn.fanouts, seed=seed + 99).sample(
        tgt, ds.labels[tgt])
    block = tr.loader.load_compact(mb, pin=True)
    look = block.lookup
    dev = tr.accel_devices[0]
    cache = tr.cache.data_on(dev, version=look.version)
    miss = jax.device_put(block.rows, dev)
    depth = tr.cfg.kernel_pipeline_depth

    def combine(c, m):
        return ops.assemble_features(c, m, look.slots, look.miss_index,
                                     use_pallas=True, pipeline_depth=depth)

    lowered = jax.jit(combine).lower(cache, miss).as_text()
    got = np.asarray(combine(cache, miss))
    want = np.asarray(jax.jit(ref.assemble_features)(
        cache, miss, jax.device_put(jnp.asarray(look.slots), dev),
        jax.device_put(jnp.asarray(look.miss_index), dev)))
    tr.cache.release_lookup(look)
    diff = float(np.max(np.abs(got - want))) if got.size else 0.0
    hits = int((look.slots >= 0).sum())
    print(f"combine check: {got.shape[0]} positions ({hits} cache hits, "
          f"{block.rows.shape[0]} shipped unique rows) on {dev}, "
          f"Mosaic kernel in program: {'tpu_custom_call' in lowered}, "
          f"bit-identical to ref: {np.array_equal(got, want)}, "
          f"max |diff| {diff!r}", flush=True)
    if accel_platform == "tpu":
        check("tpu_custom_call" in lowered,
              "the combine was not lowered to the Mosaic kernel")
    check(got.shape == want.shape and np.array_equal(got, want),
          f"Pallas combine differs from the reference (max |diff| {diff})")


def one_chip_phase(ds, gnn, batch: int, steps: int, seed: int,
                   accel_platform: str, clock: CompileClock) -> None:
    from repro.core import HybridConfig, HybridGNNTrainer
    t0 = time.perf_counter()
    tr = HybridGNNTrainer(ds, gnn, HybridConfig(
        total_batch=batch, n_accel=1, hybrid=True, cache_fraction=0.2,
        tfp_depth=2, seed=seed))
    print(f"setup: trainer built in {time.perf_counter() - t0:.3f} s; "
          f"cpu trainer on {tr.cpu_device}, accel0 on {tr.accel_devices[0]}"
          f", peaks of {tr.accel_platform}, Pallas combine "
          f"{tr._assemble_pallas}, design-time shares "
          f"{tr.runtime.quantized_shares()}", flush=True)
    try:
        if accel_platform == "tpu":
            check(not tr._assemble_pallas,
                  "the trainer picked the Pallas combine, not XLA's gather")
            check(tr.cache.use_pallas_update,
                  "the trainer picked the jnp cache update, not Pallas")
        hist = train_steps(tr, steps, clock, "1-chip")
        check_grad_devices(hist, accel_platform, "1-chip")
        if not any("cpu" in m.grad_devices for m in hist):
            print("note: the CPU trainer got no share in any step",
                  flush=True)
        check_combine(tr, gnn, seed, accel_platform)
        health = tr.health()
        print(f"health: {health['status']} {health['events']}", flush=True)
        check(health["status"] == "ok", f"health: {health}")
    finally:
        tr.close()


def four_chip_phase(ds, gnn, batch: int, steps: int, seed: int,
                    accel_platform: str, clock: CompileClock) -> None:
    """n_accel = 4, accelerators only and fixed shares (no CPU trainer,
    no DRM), so the sharded and the replicated plane train on the same
    batches and must give the same losses."""
    from repro.core import HybridConfig, HybridGNNTrainer
    losses = {}
    for plane in ("sharded", "replicated"):
        tr = HybridGNNTrainer(ds, gnn, HybridConfig(
            total_batch=batch, n_accel=4, hybrid=False, use_drm=False,
            cache_fraction=0.2, cache_sharding=plane, tfp_depth=2,
            seed=seed))
        print(f"setup: {plane} plane, accelerators on "
              f"{[str(d) for d in tr.accel_devices]}", flush=True)
        try:
            hist = train_steps(tr, steps, clock, plane)
            check_grad_devices(hist, accel_platform, plane)
            check(all(len(m.grad_devices) == 4 for m in hist),
                  f"{plane}: not all four accelerators trained")
            health = tr.health()
            check(health["status"] == "ok", f"{plane} health: {health}")
            if plane == "sharded":
                tf = tr.feature_traffic()
                print(f"sharded plane: {tf['peer_rows']:.0f} rows from peer "
                      f"shards, {tf['ici_bytes']:.0f} B over the "
                      f"interconnect", flush=True)
        finally:
            tr.close()
        losses[plane] = [m.loss for m in hist]
    diff = max(abs(a - b) for a, b in zip(losses["sharded"],
                                          losses["replicated"]))
    print(f"sharded vs replicated losses: {losses['sharded']} vs "
          f"{losses['replicated']}, max |diff| {diff!r}", flush=True)
    check(losses["sharded"] == losses["replicated"],
          f"sharded and replicated losses differ by up to {diff}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, features, labels and weights")
    args = ap.parse_args()
    faulthandler.dump_traceback_later(DEADLINE_SECONDS, exit=True)
    steps = ONE_CHIP_STEPS if args.chips == 1 else PLANE_STEPS

    # the CPU trainer needs the host CPU backend beside the TPU one
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default devices: "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    ds, gnn, batch = build_dataset(1.0, args.seed)
    phase = one_chip_phase if args.chips == 1 else four_chip_phase
    try:
        phase(ds, gnn, batch, steps, args.seed, "tpu", clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"compiles: {clock.count} backend compiles, {clock.seconds:.3f} s "
          f"(set-up); tpu0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}", flush=True)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
