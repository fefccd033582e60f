"""jit'd public wrappers around the Pallas kernels.

Handles padding to MXU-aligned tile multiples, 2-D reshaping of vector
operands (TPU lanes want >=2-D), and dispatch between the Pallas path and
the pure-jnp reference (``use_pallas=False`` or non-TPU-friendly shapes).
The layer-0 feature combine (``assemble_features``) has no Pallas kernel:
it is XLA's gather and select on every platform.

Each kernel call is compiled to Mosaic where the enclosing computation is
lowered for a TPU and runs in ``interpret=True`` mode (the kernel body
executed op by op, for correctness validation) where it is lowered for
the CPU of a host without a TPU.  The choice is made per lowering
(``_on_device``), not once per process.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span

from . import ref
from .flash_attention import flash_attention_call
from .gather_scatter_mm import (cache_update_kernel_call,
                                cache_update_pipelined_kernel_call,
                                fused_update_kernel_call,
                                segment_sum_kernel_call, sublane_rows)

__all__ = ["segment_weighted_sum_regular", "fused_gnn_update",
           "flash_attention", "assemble_features",
           "assemble_features_sharded", "gather_rows",
           "update_cache_rows"]


@functools.lru_cache(maxsize=None)
def _tpu_host() -> bool:
    return jax.default_backend() == "tpu"


def _on_device(call, *args, **static):
    """Run the Pallas kernel ``call`` on ``args``: compiled where the
    enclosing computation is lowered for a TPU, interpreted where it is
    lowered for another platform.  ``lax.platform_dependent`` keeps only
    the branch of the platform being lowered, so a CPU trainer's jit and a
    TPU trainer's jit in one process each get theirs.  On a host that has
    a TPU the other branch is not interpreted either: Pallas lowers only
    interpret mode for the CPU, so a kernel lowered there fails loudly
    instead of quietly running a slow path."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(call, interpret=False, **static),
        default=functools.partial(call, interpret=not _tpu_host(), **static))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_tile(dim: int, pref: int = 128, floor: int = 8) -> int:
    """Largest power-of-two tile <= pref that keeps padding waste < 2x."""
    t = pref
    while t > floor and _round_up(dim, t) >= 2 * dim and dim > 0:
        t //= 2
    return max(t, floor)


def assemble_features(cache: Optional[jax.Array], miss: jax.Array,
                      slots, miss_index) -> jax.Array:
    """Assemble the dense positional layer-0 feature block from the
    device-resident hot cache + the transferred unique-miss rows (see
    graph/featcache.py).  Under frontier dedup the index tables point many
    positions at one shipped row, so this step *is* the paper's Feature
    Duplicator, run on the destination device after the interconnect.

    ``cache=None`` marks the cache-less dedup path (every position reads
    the miss block).

    ``slots``/``miss_index`` are host numpy (the cache lookup makes them
    on the host) or device arrays.  The combine is one jitted XLA gather
    and select that indexes them on the device: the caller puts them on
    the destination device, and the program specialises only on the
    cache shape, the miss block's rows (the caller's bucket) and the
    position count.  The call into it is the span
    ``hyscale.transfer.dispatch``.

    No VJP needed: layer-0 inputs are data, not parameters, so this sits
    outside the autodiff region of the train step.
    """
    slots, miss_index = jnp.asarray(slots), jnp.asarray(miss_index)
    with span("hyscale.transfer.dispatch"):
        return _assemble_ref(cache, miss, slots, miss_index)


def gather_rows(block: jax.Array, slots) -> jax.Array:
    """Gather ``slots`` rows out of a device-resident [K, F] block —
    the peer-serve half of the sharded plane's row exchange (the owner
    shard reads the requested rows before the ICI hop).  One XLA take."""
    return _gather_ref(block, jnp.asarray(slots, dtype=jnp.int32))


@jax.jit
def _gather_ref(block: jax.Array, slots: jax.Array) -> jax.Array:
    return jnp.take(block, slots, axis=0)


def assemble_features_sharded(cache: Optional[jax.Array], sources,
                              slots, miss_index) -> jax.Array:
    """Shard-aware assemble: like ``assemble_features`` but the miss
    source arrives as an ordered list of device-resident row blocks —
    the peer-fetched segments (ring order) followed by the fresh
    host-shipped rows.  They are concatenated on device into the one
    combined source the union lookup's ``miss_index`` addresses, then
    combined by ``assemble_features``; ``cache`` is the trainer's LOCAL
    shard block."""
    sources = [s for s in sources if int(s.shape[0])]
    if not sources:
        miss = None
    elif len(sources) == 1:
        miss = sources[0]
    else:
        miss = jnp.concatenate(sources, axis=0)
    if miss is None:
        f = cache.shape[1] if cache is not None else 1
        dtype = cache.dtype if cache is not None else jnp.float32
        miss = jnp.zeros((1, f), dtype)
    return assemble_features(cache, miss, slots, miss_index)


@jax.jit
def _assemble_ref(cache: Optional[jax.Array], miss: jax.Array,
                  slots: jax.Array, miss_index: jax.Array) -> jax.Array:
    if cache is None:
        cache = jnp.zeros((1, miss.shape[1]), miss.dtype)
    if miss.shape[0] == 0:
        # keep the gather well-defined when every row hits the cache
        miss = jnp.zeros((1, cache.shape[1]), cache.dtype)
    return ref.assemble_features(cache, miss, slots, miss_index)


def update_cache_rows(cache: jax.Array, rows, slots,
                      use_pallas: bool = False,
                      pipeline_depth: int = 1) -> jax.Array:
    """Scatter admitted rows into a device-resident hot block during a
    dynamic cache refresh: ``out = cache; out[slots[i]] = rows[i]`` (last
    writer wins on aliased slots — all paths and the oracle agree).

    ``rows``/``slots`` are accepted as host numpy (refresh builds them on
    the host); an empty update returns the input block unchanged so a
    no-op refresh never touches the device.  Aliased slots are first
    compacted keep-last on the host, so the jnp path's XLA scatter
    (duplicate-index order unspecified) stays deterministic and the Pallas
    kernels see every slot once.  The Pallas path then groups the slots
    by their sublane-aligned row block (a one-row DMA is not a legal TPU
    tile) and rewrites each touched block once, with the cache aliased
    into the output.

    ``pipeline_depth > 1`` (Pallas path only) moves the touched blocks
    through ``depth`` VMEM slots by hand, overlapping block t+depth's read
    with block t's write-back — bit-identical to depth 1 and the oracle.
    """
    slots = np.asarray(slots, dtype=np.int32)
    if slots.shape[0] == 0:
        return cache
    rows = jnp.asarray(rows, dtype=cache.dtype)
    # keep-last dedupe: unique() keeps the first occurrence, so scan the
    # reversed slot list and map indices back
    _, first_in_rev = np.unique(slots[::-1], return_index=True)
    keep = np.sort(slots.shape[0] - 1 - first_in_rev).astype(np.int32)
    if not use_pallas:
        return _update_ref(cache, rows[keep], jnp.asarray(slots[keep]))
    src, mask, blocks = _update_schedule(slots[keep], keep,
                                         sublane_rows(cache.dtype))
    return _update_pallas(cache, rows, src, mask, blocks,
                          depth=int(pipeline_depth))


def _update_schedule(slots: np.ndarray, src_rows: np.ndarray, rb: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Group unique ``slots`` by their ``rb``-row block: the touched
    blocks in ascending order, and for row r of the t-th block the update
    row it takes (``src[t*rb + r]``, an index into the caller's rows) and
    whether it takes one at all (``mask``)."""
    blocks, inv = np.unique(slots // rb, return_inverse=True)
    pos = inv * rb + slots % rb
    src = np.zeros(blocks.shape[0] * rb, np.int32)
    mask = np.zeros(blocks.shape[0] * rb, np.int32)
    src[pos] = src_rows
    mask[pos] = 1
    return src, mask, blocks.astype(np.int32)


@jax.jit
def _update_ref(cache: jax.Array, rows: jax.Array,
                slots: jax.Array) -> jax.Array:
    return cache.at[slots].set(rows)


@functools.partial(jax.jit, static_argnames=("depth",))
def _update_pallas(cache: jax.Array, rows: jax.Array, src: jax.Array,
                   mask: jax.Array, blocks: jax.Array,
                   depth: int) -> jax.Array:
    k, f = cache.shape
    t_f = _pick_tile(f)
    fp = _round_up(f, t_f)
    kp = _round_up(k, sublane_rows(cache.dtype))
    cp = jnp.pad(cache, ((0, kp - k), (0, fp - f)))
    upd = jnp.pad(jnp.take(rows, src, axis=0), ((0, 0), (0, fp - f)))
    if depth > 1:
        out = _on_device(cache_update_pipelined_kernel_call, cp, upd,
                         mask[:, None], blocks, t_f=t_f, depth=depth)
    else:
        out = _on_device(cache_update_kernel_call, cp, upd, mask[:, None],
                         blocks, t_f=t_f)
    return out[:k, :f]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_weighted_sum_regular(x_nbr: jax.Array, w_edge: jax.Array,
                                 fanout: int) -> jax.Array:
    """Pallas-backed regular-layout weighted segment sum.

    x_nbr: [D*fanout, F]; w_edge: [D*fanout] -> [D, F].
    Differentiable: backward pass is analytic (broadcast + reduce), so the
    kernel composes with ``jax.grad`` in the training step.
    """
    return _segsum_fwd_impl(x_nbr, w_edge, fanout)


@functools.partial(jax.jit, static_argnames=("fanout",))
def _segsum_fwd_impl(x_nbr: jax.Array, w_edge: jax.Array,
                     fanout: int) -> jax.Array:
    d = x_nbr.shape[0] // fanout
    f = x_nbr.shape[1]
    t_d = _pick_tile(d, 128 if d >= 128 else 8)
    t_f = _pick_tile(f)
    dp, fp = _round_up(d, t_d), _round_up(f, t_f)
    xn = jnp.pad(x_nbr.reshape(d, fanout, f),
                 ((0, dp - d), (0, 0), (0, fp - f))).reshape(dp * fanout, fp)
    we = jnp.pad(w_edge.reshape(d, fanout), ((0, dp - d), (0, 0))
                 ).reshape(dp * fanout, 1)
    out = _on_device(segment_sum_kernel_call, xn, we, fanout=fanout,
                     t_d=t_d, t_f=t_f)
    return out[:d, :f]


def _segsum_vjp_fwd(x_nbr, w_edge, fanout):
    return _segsum_fwd_impl(x_nbr, w_edge, fanout), (x_nbr, w_edge)


def _segsum_vjp_bwd(fanout, res, g):
    x_nbr, w_edge = res
    d = x_nbr.shape[0] // fanout
    g_rep = jnp.repeat(g, fanout, axis=0,
                       total_repeat_length=d * fanout).astype(jnp.float32)
    d_xn = (g_rep * w_edge.astype(jnp.float32)[:, None]).astype(x_nbr.dtype)
    d_we = (g_rep * x_nbr.astype(jnp.float32)).sum(-1).astype(w_edge.dtype)
    return d_xn, d_we


segment_weighted_sum_regular.defvjp(_segsum_vjp_fwd, _segsum_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def fused_gnn_update(x_self: jax.Array, x_nbr: jax.Array, w_edge: jax.Array,
                     self_scale: jax.Array, w_self: jax.Array,
                     w_agg: jax.Array, bias: Optional[jax.Array],
                     fanout: int) -> jax.Array:
    """Fused aggregate+update GNN layer (paper Section IV-C datapath).

    out = (self_scale ⊙ x_self) @ w_self + segsum(w_edge ⊙ x_nbr) @ w_agg + b
    Differentiable via an analytic custom VJP (forward runs the fused Pallas
    kernel; backward re-aggregates once and uses plain matmuls).
    """
    return _fused_fwd_impl(x_self, x_nbr, w_edge, self_scale, w_self, w_agg,
                           bias, fanout)


@functools.partial(jax.jit, static_argnames=("fanout",))
def _fused_fwd_impl(x_self: jax.Array, x_nbr: jax.Array, w_edge: jax.Array,
                    self_scale: jax.Array, w_self: jax.Array,
                    w_agg: jax.Array, bias: Optional[jax.Array],
                    fanout: int) -> jax.Array:
    d, f = x_self.shape
    o = w_self.shape[1]
    t_d = _pick_tile(d, 128 if d >= 128 else 8)
    t_f = _pick_tile(f)
    t_o = _pick_tile(o)
    dp, fp, op = _round_up(d, t_d), _round_up(f, t_f), _round_up(o, t_o)

    xs = jnp.pad(x_self, ((0, dp - d), (0, fp - f)))
    xn = jnp.pad(x_nbr.reshape(d, fanout, f),
                 ((0, dp - d), (0, 0), (0, fp - f))).reshape(dp * fanout, fp)
    we = jnp.pad(w_edge.reshape(d, fanout), ((0, dp - d), (0, 0))
                 ).reshape(dp * fanout, 1)
    ss = jnp.pad(self_scale.reshape(d, 1), ((0, dp - d), (0, 0)))
    ws = jnp.pad(w_self, ((0, fp - f), (0, op - o)))
    wa = jnp.pad(w_agg, ((0, fp - f), (0, op - o)))
    b = (jnp.zeros((1, op), x_self.dtype) if bias is None
         else jnp.pad(bias.reshape(1, o), ((0, 0), (0, op - o))))
    out = _on_device(fused_update_kernel_call, xs, xn, we, ss, ws, wa, b,
                     fanout=fanout, t_d=t_d, t_f=t_f, t_o=t_o)
    return out[:d, :o]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_block: int = 512, pos0: int = 0) -> jax.Array:
    """Causal flash attention (Pallas fwd kernel, analytic jnp bwd).

    q: [B, S, Hkv, G, D]; k/v: [B, S, Hkv, D] -> [B, S, Hkv, G, D].
    """
    return _on_device(flash_attention_call, q, k, v, q_block=q_block,
                      pos0=pos0)


def _attn_probs(q, k, pos0):
    s = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = pos0 + jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


def _flash_vjp_fwd(q, k, v, q_block, pos0):
    return flash_attention(q, k, v, q_block, pos0), (q, k, v)


def _flash_vjp_bwd(q_block, pos0, res, g):
    # standard attention backward with recompute (scores re-materialized
    # by XLA here; a bwd flash kernel is a further perf iteration)
    q, k, v = res
    p = _attn_probs(q, k, pos0)                                   # [B,H,G,S,S]
    g32 = g.astype(jnp.float32)
    d_v = jnp.einsum("bhgqk,bqhgd->bkhd", p, g32).astype(v.dtype)
    d_p = jnp.einsum("bqhgd,bkhd->bhgqk", g32, v.astype(jnp.float32))
    row = jnp.sum(d_p * p, axis=-1, keepdims=True)
    d_s = p * (d_p - row)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    d_q = (jnp.einsum("bhgqk,bkhd->bqhgd", d_s, k.astype(jnp.float32))
           * scale).astype(q.dtype)
    d_k = (jnp.einsum("bhgqk,bqhgd->bkhd", d_s, q.astype(jnp.float32))
           * scale).astype(k.dtype)
    return d_q, d_k, d_v


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _fused_vjp_fwd(x_self, x_nbr, w_edge, self_scale, w_self, w_agg, bias,
                   fanout):
    out = _fused_fwd_impl(x_self, x_nbr, w_edge, self_scale, w_self, w_agg,
                          bias, fanout)
    return out, (x_self, x_nbr, w_edge, self_scale, w_self, w_agg,
                 bias is not None)


def _fused_vjp_bwd(fanout, res, g):
    x_self, x_nbr, w_edge, self_scale, w_self, w_agg, has_bias = res
    d = x_self.shape[0]
    g32 = g.astype(jnp.float32)
    xs32 = x_self.astype(jnp.float32)
    ss32 = self_scale.astype(jnp.float32)
    # recompute the aggregation once (cheap relative to matmuls)
    agg = ref.segment_weighted_sum_regular(x_nbr, w_edge, fanout
                                           ).astype(jnp.float32)
    gws = g32 @ w_self.astype(jnp.float32).T            # [D, F]
    d_xs = (gws * ss32[:, None]).astype(x_self.dtype)
    d_ss = (gws * xs32).sum(-1).astype(self_scale.dtype)
    d_wself = ((xs32 * ss32[:, None]).T @ g32).astype(w_self.dtype)
    d_wagg = (agg.T @ g32).astype(w_agg.dtype)
    d_agg = g32 @ w_agg.astype(jnp.float32).T           # [D, F]
    d_agg_rep = jnp.repeat(d_agg, fanout, axis=0,
                           total_repeat_length=d * fanout)
    d_xn = (d_agg_rep * w_edge.astype(jnp.float32)[:, None]
            ).astype(x_nbr.dtype)
    d_we = (d_agg_rep * x_nbr.astype(jnp.float32)).sum(-1
            ).astype(w_edge.dtype)
    d_b = g32.sum(0).astype(w_self.dtype) if has_bias else None
    return d_xs, d_xn, d_we, d_ss, d_wself, d_wagg, d_b


fused_gnn_update.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)
