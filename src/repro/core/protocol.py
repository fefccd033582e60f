"""Processor-Accelerator Training Protocol (paper Section III-C, Listing 1).

Defines how processors and accelerators interact and synchronize:

* ``Synchronizer`` — the condition-variable DONE handshake of Listing 1:
  each Trainer increments DONE when its gradients are staged; when DONE
  equals the number of Trainers the Synchronizer gathers, averages
  (weighted by mini-batch share — sync SGD over unequal shares), and the
  averaged gradients are broadcast back.
* ``TrainerHandle`` — one logical GNN Trainer bound to a device and a jit'd
  gradient function; it computes on its own copy of the parameters, on
  its own device; ``kind`` distinguishes the CPU trainer from
  accelerator trainers (the protocol's application layer is accelerator
  agnostic — GPU/FPGA/TPU only changes the programming layer underneath,
  which for us is always XLA).
* ``Runtime`` — collects per-stage execution times each iteration and feeds
  the DRM engine (Section IV-A), exactly as in Fig. 5 ("the Runtime system
  collects the execution time of each stage to fine-tune the workload
  assignment in the next iteration").
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.annotations import guarded_by

from .drm import Assignment, DRMEngine, StageTimes
from .spans import span

__all__ = ["Synchronizer", "TrainerHandle", "Runtime", "device_of"]

PyTree = Any


@guarded_by("_cond", "_done", "_slots")
class Synchronizer:
    """Listing-1 handshake: pthread cond/mutex -> threading.Condition."""

    def __init__(self, n_trainers: int, device: Any) -> None:
        self.n_trainers = n_trainers
        self.device = device          # where the average is formed
        self._cond = threading.Condition()
        self._done = 0
        self._slots: List[Optional[Tuple[PyTree, float]]] = [None] * n_trainers

    def submit(self, trainer_idx: int, grads: PyTree, weight: float) -> None:
        """Trainer side: stage gradients, increment DONE, signal."""
        with self._cond:
            self._slots[trainer_idx] = (grads, weight)
            self._done += 1
            self._cond.notify_all()

    def all_reduce(self) -> PyTree:
        """Synchronizer side: wait until DONE == n, then weighted-average.

        Weighted by mini-batch share so that hybrid training with unequal
        shares is algorithmically identical to single-device large-batch
        SGD (paper Section II-B).  Every trainer's gradients are first
        brought to ``device`` (the authoritative parameters' device):
        trainers on different devices cannot be summed in place.  The
        averaging, after the last trainer reported, is the span
        ``hyscale.sync``.
        """
        with self._cond:
            while self._done != self.n_trainers:       # Listing 1 line 24
                self._cond.wait()
            slots = list(self._slots)                  # gather_data()
            self._done = 0
            self._slots = [None] * self.n_trainers
        with span("hyscale.sync"):
            total_w = sum(w for _, w in slots)
            trees = [jax.device_put(g, self.device) for g, _ in slots]
            shares = np.asarray([w / total_w for _, w in slots], np.float32)
            avg = _weighted_sum(trees, shares)          # average_gradients()
        return avg


@jax.jit
def _weighted_sum(trees: List[PyTree], shares: jax.Array) -> PyTree:
    """``Σ trees[i] · shares[i]``, summed in trainer order, as one program.
    The shares are traced, so new mini-batch shares (or a dead trainer's
    0) reuse it; only another trainer count or tree structure builds
    another."""
    avg = None
    for i, tree in enumerate(trees):
        w = shares[i]
        scaled = jax.tree.map(lambda g: g * w.astype(g.dtype), tree)
        avg = scaled if avg is None else jax.tree.map(jnp.add, avg, scaled)
    return avg


@dataclasses.dataclass
class TrainerHandle:
    """One logical GNN Trainer (paper Section III-A)."""
    name: str
    kind: str                    # "cpu" | "accel"
    device: Any                  # jax.Device
    grad_fn: Callable[..., Tuple[PyTree, Dict[str, Any]]]
    index: int
    iteration: int = 0           # the batch's, carried by the span

    def run(self, sync: Synchronizer, params: PyTree, weight: float,
            *args: Any) -> Dict[str, Any]:
        """One training step, the span ``hyscale.train.<kind>``, whose
        seconds are ``metrics["t_train"]``; then DONE++."""
        with span("hyscale.train." + self.kind,
                  iteration=self.iteration) as s:
            params = jax.device_put(params, self.device)
            grads, metrics = self.grad_fn(params, *args)
            grads = jax.block_until_ready(grads)
        sync.submit(self.index, grads, weight)          # DONE++, signal
        metrics = dict(metrics)
        metrics["t_train"] = s.seconds
        dev = device_of(grads)
        metrics["device"] = f"{dev.platform}:{dev.id}"
        return metrics


def device_of(tree: PyTree) -> Any:
    """The device holding ``tree``'s first leaf."""
    return next(iter(jax.tree.leaves(tree)[0].devices()))


class Runtime:
    """Collects stage times, runs the DRM engine between iterations."""

    def __init__(self, assignment: Assignment, use_drm: bool = True,
                 damping: float = 0.25, share_quantum: int = 64) -> None:
        self.drm = DRMEngine(assignment, damping=damping)
        self.use_drm = use_drm
        self.share_quantum = max(1, int(share_quantum))
        self.history: List[StageTimes] = []

    @property
    def assignment(self) -> Assignment:
        return self.drm.assign

    def quantized_shares(self) -> Tuple[int, int]:
        """(cpu_batch, accel_batch_each), rounded to the share quantum.

        Quantization bounds the number of distinct mini-batch shapes the
        jit cache must hold (an XLA-specific constraint the paper's
        CUDA/HLS trainers do not have); the total batch is conserved by
        folding the remainder into the CPU share.
        """
        a = self.drm.assign
        q = self.share_quantum
        accel = (a.accel_batch // q) * q
        cpu = a.total_batch - accel * a.n_accel
        return cpu, accel

    def end_iteration(self, times: StageTimes) -> Assignment:
        self.history.append(times)
        if self.use_drm:
            return self.drm.step(times)
        return self.drm.assign

    def mean_iteration_time(self, skip: int = 1) -> float:
        xs = [t.iteration_time() for t in self.history[skip:]] or [0.0]
        return float(np.mean(xs))
