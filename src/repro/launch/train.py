"""LM training driver: ``python -m repro.launch.train --arch smollm-135m
--reduced --steps 50``.

Integrates the paper's system pieces end-to-end on the LM substrate:
  * two-stage prefetching input pipeline (repro.data.TokenPipeline),
  * perf-model-style share quantization is not needed here (homogeneous
    devices) but the DRM-style straggler log is kept per step,
  * checkpoint/restart (elastic: restore re-shards onto the current mesh),
  * optional local mesh (data×model) when multiple devices exist.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.data.tokens import TokenPipeline
from repro.dist import params_shardings, use_mesh
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, make_train_step, param_count
from repro.optim import adamw, cosine_warmup_schedule


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="TFP window; 0 disables the two-stage prefetch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, reduced=args.reduced)
    mesh = (make_local_mesh(model=args.model_parallel)
            if jax.device_count() > 1 else None)
    print(f"arch={cfg.name} devices={jax.device_count()} "
          f"mesh={None if mesh is None else dict(mesh.shape)}")

    with use_mesh(mesh):
        key = jax.random.PRNGKey(args.seed)
        params = init_params(key, cfg)
        if mesh is not None:
            params = jax.device_put(params, params_shardings(params, mesh))
        sched = cosine_warmup_schedule(args.lr, args.steps // 10 + 1,
                                       args.steps)
        opt = adamw(sched)
        opt_state = opt.init(params)
        print(f"params: {param_count(params)/1e6:.1f}M")

        step_fn = jax.jit(make_train_step(cfg, opt,
                                          microbatches=args.microbatches),
                          donate_argnums=(0, 1))

        start_step = 0
        mgr = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=2)
            restored = mgr.restore_latest({"params": params,
                                           "opt": opt_state})
            if restored is not None:
                start_step, tree = restored
                params, opt_state = tree["params"], tree["opt"]
                print(f"restored checkpoint at step {start_step}")

        pipe = TokenPipeline(cfg, args.batch, args.seq, seed=args.seed,
                             depth=args.prefetch_depth)
        times = []
        t_prev = time.perf_counter()
        for step, batch in enumerate(pipe.batches(args.steps - start_step),
                                     start=start_step):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            now = time.perf_counter()
            dt = now - t_prev
            t_prev = now
            times.append(dt)
            tok_s = args.batch * args.seq / dt
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"{dt*1e3:7.1f} ms/step  {tok_s:9.0f} tok/s")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
        if mgr:
            mgr.save(args.steps, {"params": params, "opt": opt_state})
            mgr.finalize()
        med = float(np.median(times[2:])) if len(times) > 3 else float("nan")
        print(f"done: median {med*1e3:.1f} ms/step, final loss {loss:.4f}")


if __name__ == "__main__":
    main()
