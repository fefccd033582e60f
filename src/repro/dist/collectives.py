"""Hierarchical gradient-mean collective schedule.

A flat ``psum`` over every device sends whole-gradient traffic across the
slow pod interconnect.  The hierarchical schedule does the classic three
phases instead:

  1. reduce-scatter *within* each pod (over the fast local axes), so each
     device owns a 1/k shard of the local sum,
  2. all-reduce the shards *across* pods (only 1/k of the bytes cross the
     slow links),
  3. all-gather within the pod to rebuild the full mean.

Leaves whose leading dim the local axes do not divide (scalars, small
biases) fall back to a flat psum — same result, negligible bytes.

This module also hosts the *peer feature exchange* for the sharded
hot-feature plane (``graph.featcache.ShardedFeatureCache``): each
accelerator pins a disjoint hot shard, and a frontier row that misses
locally but is resident on a peer shard is served with one on-peer
gather plus one row hop over the accelerator interconnect (ICI) instead
of a host PCIe ship.  ``exchange_peer_rows`` walks the requests in
deterministic ring order (me+1, me+2, ..., wrap) — the schedule every
trainer derives identically, so an all-to-all of such exchanges never
deadlocks and the combined transfer-source layout is reproducible.
"""
from __future__ import annotations

from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import current_mesh, shard_map_compat

__all__ = ["exchange_peer_rows", "hierarchical_psum_mean",
           "peer_gather_rows", "ring_order"]


def ring_order(n: int, me: int) -> List[int]:
    """Deterministic ring schedule of the other ``n - 1`` ordinals as
    seen from ``me``: (me+1) % n, (me+2) % n, ...  Step s of the
    all-to-all pairs every trainer with a distinct peer (i talks to
    i+s while i-s talks to i), so no link is oversubscribed and every
    participant derives the same global schedule locally."""
    n = int(n)
    me = int(me) % max(n, 1)
    return [(me + s) % n for s in range(1, n)]


def peer_gather_rows(block: jax.Array, slots, dest_device) -> jax.Array:
    """Serve one peer request: gather ``slots`` rows out of the owner
    shard's device-resident ``block`` (on the owner's device, one XLA
    take via ``kernels.ops.gather_rows``), then ship only those rows to
    ``dest_device`` in one hop (the ICI transfer; on the CPU test mesh
    the hop is a same-backend ``device_put``)."""
    from repro.kernels.ops import gather_rows
    return jax.device_put(gather_rows(block, slots), dest_device)


def exchange_peer_rows(requests: Sequence[Tuple[int, Any, int]],
                       block_of: Callable[[int, int], jax.Array],
                       dest_device) -> List[jax.Array]:
    """Pull the requested rows from each peer shard, in the ring order
    the requests were built in.

    ``requests`` is one trainer's ``ShardLookup.peer_requests`` —
    ``(peer ordinal, slots into the peer block, peer version)`` tuples —
    and ``block_of(peer, version)`` resolves the peer shard's
    device-resident block at the pinned version (the caller holds the
    pins, so the block cannot be retired mid-exchange).  Returns one
    row-block per request, in request order: exactly the leading
    segments of the combined transfer source the union lookup's
    ``miss_index`` addresses."""
    return [peer_gather_rows(block_of(int(peer), int(version)), slots,
                             dest_device)
            for peer, slots, version in requests]


def hierarchical_psum_mean(tree: Any) -> Any:
    """Mean of the per-device values of ``tree`` (replicated in, replicated
    out), scheduled reduce-scatter -> cross-pod all-reduce -> all-gather.

    Must run under ``use_mesh`` (jit-traced against the ambient mesh).
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return tree
    local = tuple(n for n in mesh.axis_names if n != "pod")
    pod = "pod" if "pod" in mesh.axis_names else None
    local_size = 1
    for n in local:
        local_size *= mesh.shape[n]
    n_total = mesh.size

    def body(*leaves):
        out = []
        for v in leaves:
            if (local and local_size > 1 and v.ndim >= 1
                    and v.shape[0] % local_size == 0):
                s = jax.lax.psum_scatter(v, local, scatter_dimension=0,
                                         tiled=True)
                if pod is not None:
                    s = jax.lax.psum(s, pod)
                s = jax.lax.all_gather(s, local, axis=0, tiled=True)
            else:
                axes = local + ((pod,) if pod is not None else ())
                s = jax.lax.psum(v, axes)
            out.append((s / n_total).astype(v.dtype))
        return tuple(out)

    leaves, treedef = jax.tree.flatten(tree)
    specs = tuple(P() for _ in leaves)
    fn = shard_map_compat(body, mesh, in_specs=specs, out_specs=specs)
    return jax.tree.unflatten(treedef, fn(*leaves))
