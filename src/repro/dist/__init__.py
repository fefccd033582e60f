"""Sharding context + constraint helpers shared by every model layer.

The model code never talks to ``jax.sharding`` directly: layers call
``constrain``/``constrain_act``/``constrain_proj`` with *logical* axis
tuples (e.g. ``("pod", "data")`` for the batch dim) and this module decides
what survives on the current mesh:

  * axes absent from the active mesh are dropped (a single-host run with no
    mesh turns every constraint into the identity — zero overhead on the
    CPU container),
  * a mesh axis is never used twice inside one ``PartitionSpec`` (first
    occurrence wins), so composed specs like ``(("pod","data"), ("data",
    "model"))`` stay valid on any mesh shape,
  * dims whose size the mesh does not divide fall back to replicated.

The active mesh and parallelism policy are ambient context (``use_mesh`` /
``use_policy``), mirroring how the launch layer builds cells: the same
model source lowers to pure-DP, FSDPxTP ("tp2d"), weight-stationary decode
("serve2d") or expert-parallel ("ep") programs purely by context.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "use_mesh", "current_mesh", "use_policy", "current_policy",
    "pspec", "constrain", "constrain_act", "constrain_act_serve",
    "constrain_proj", "params_shardings", "shard_map_compat",
]

AxisDim = Union[None, str, Tuple[str, ...]]

_ctx = threading.local()


def _stack(name: str) -> list:
    st = getattr(_ctx, name, None)
    if st is None:
        st = []
        setattr(_ctx, name, st)
    return st


def current_mesh() -> Optional[Mesh]:
    """The ambient mesh set by ``use_mesh`` (None on single-host runs)."""
    st = _stack("mesh")
    return st[-1] if st else None


def current_policy() -> str:
    """The ambient parallelism policy ('tp2d' | 'dp' | 'serve2d' | 'ep')."""
    st = _stack("policy")
    return st[-1] if st else "tp2d"


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Set the ambient mesh.  ``use_mesh(None)`` is a supported no-op so
    callers can wrap single-device paths unconditionally."""
    _stack("mesh").append(mesh)
    try:
        yield mesh
    finally:
        _stack("mesh").pop()


@contextlib.contextmanager
def use_policy(policy: str):
    _stack("policy").append(policy)
    try:
        yield policy
    finally:
        _stack("policy").pop()


# ------------------------------------------------------------------- pspec


def _norm_dim(dim: AxisDim, mesh: Optional[Mesh], used: set) -> AxisDim:
    """Filter one PartitionSpec entry against the mesh + already-used axes."""
    if dim is None or mesh is None:
        return None
    names = (dim,) if isinstance(dim, str) else tuple(dim)
    names = tuple(n for n in names
                  if n in mesh.axis_names and n not in used)
    used.update(names)
    if not names:
        return None
    return names[0] if len(names) == 1 else names


def pspec(*dims: AxisDim) -> P:
    """Build a ``PartitionSpec``, dropping axes the current mesh lacks and
    deduplicating axes across dims (first occurrence wins).  With no
    ambient mesh every entry degrades to ``None`` (fully replicated)."""
    mesh = current_mesh()
    used: set = set()
    return P(*(_norm_dim(d, mesh, used) for d in dims))


def _axes_size(mesh: Mesh, dim: AxisDim) -> int:
    if dim is None:
        return 1
    names = (dim,) if isinstance(dim, str) else dim
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def _fit_spec(mesh: Mesh, shape: Sequence[int], spec: P) -> P:
    """Replace entries that do not divide the dim size with None."""
    out = []
    for size, dim in zip(shape, tuple(spec) + (None,) * len(shape)):
        out.append(dim if dim is None or size % _axes_size(mesh, dim) == 0
                   else None)
    return P(*out)


# --------------------------------------------------------------- constrain


def constrain(x: jax.Array, *dims: AxisDim) -> jax.Array:
    """``with_sharding_constraint`` against the ambient mesh; identity when
    no mesh is active (or the mesh is trivial)."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = _fit_spec(mesh, x.shape, pspec(*dims))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_proj(x: jax.Array, n_heads: int) -> jax.Array:
    """Constraint for attention projections [B, S, H*hd]: the head dim is
    model-sharded only when the head count divides the model axis."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    msize = mesh.shape.get("model", 1)
    h_ax = "model" if msize > 1 and n_heads % msize == 0 else None
    return constrain(x, ("pod", "data"), None, h_ax)


def constrain_act(x: jax.Array) -> jax.Array:
    """Block-boundary activation constraint for [B, S, d] streams.

    tp2d: batch over (pod, data) and sequence over model — the remat
    residuals each layer saves shrink by 1/(dp*tp).  When the batch does
    not divide the dp axes (long-context, batch=1) the sequence absorbs
    them instead.  'dp' keeps activations batch-sharded only.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or x.ndim < 3:
        return x
    policy = current_policy()
    b, s = x.shape[0], x.shape[1]
    dp_size = _axes_size(mesh, tuple(n for n in ("pod", "data")
                                     if n in mesh.axis_names))
    if b % max(dp_size, 1) == 0:
        b_ax: AxisDim = ("pod", "data")
        s_ax: AxisDim = None if policy == "dp" else "model"
    else:
        b_ax = None
        s_ax = (("pod", "data") if policy == "dp"
                else ("pod", "data", "model"))
    return constrain(x, b_ax, s_ax, *([None] * (x.ndim - 3)))


def constrain_act_serve(x: jax.Array) -> jax.Array:
    """Decode-time activation constraint for [B, 1, d] token streams.

    Under 'serve2d' the batch keeps only the pod axis (the freed data axis
    splits the KV-cache length, see launch/cellspecs._cache_pspec);
    otherwise the batch spans (pod, data).
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    b_ax: AxisDim = (("pod",) if current_policy() == "serve2d"
                     else ("pod", "data"))
    return constrain(x, b_ax, *([None] * (x.ndim - 1)))


# ------------------------------------------------------- parameter shardings


def params_shardings(tree: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree for parameters / optimizer state: the
    ``sharding.param_pspec`` rule table applied leaf-by-leaf."""
    from .sharding import param_pspec
    with use_mesh(mesh):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(mesh, param_pspec(path, leaf)),
            tree)


# ---------------------------------------------------------------- shard_map


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
