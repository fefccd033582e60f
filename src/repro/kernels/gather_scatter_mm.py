"""Pallas TPU kernels — the paper's hardware kernel design (Section IV-C),
adapted from FPGA scatter-gather PEs + systolic MLP to the TPU memory
hierarchy (HBM -> VMEM -> MXU).

Mapping of the paper's ideas:

* *Edges sorted so same-vertex features are reused back-to-back; the Feature
  Duplicator keeps the fetched feature in PE-local memory* -> edges arrive
  destination-sorted in a regular ``fanout`` layout; each grid step DMAs one
  (T_D × fanout, T_F) tile of neighbor rows HBM->VMEM **once** and reuses it
  across the whole output tile (VMEM plays the PE-local memory role).
* *Systolic-array update kernel* -> the MXU matmul, fed directly from the
  VMEM-resident aggregation result.
* *Customized datapath: intermediate results never written back to external
  memory* -> the aggregated tile is consumed by the matmul inside the same
  kernel invocation; only the final update output is written to HBM.  The
  f32 accumulator lives in a VMEM scratch buffer across the F-reduction grid
  axis.

Tile sizes default to MXU-aligned 128×128 blocks; callers (ops.py) pad
inputs to tile multiples.  Grid iteration order is (D, O, F) with F
innermost, so each output tile's accumulator stays resident in VMEM for the
whole reduction — the TPU analogue of the paper's (n, m) PE parallelism
knobs (Table IV).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_sum_kernel_call", "fused_update_kernel_call",
           "cache_combine_kernel_call", "cache_combine_tiled_kernel_call",
           "cache_combine_pipelined_kernel_call",
           "cache_update_kernel_call", "cache_update_pipelined_kernel_call",
           "VMEM_SCRATCH_BUDGET_BYTES", "check_vmem_scratch",
           "sublane_rows"]


# Multi-buffered kernels hold ``depth`` in-flight tile windows in VMEM
# scratch.  Half of a 16 MB TPU VMEM is reserved for scratch; the other
# half stays available to the pipeline machinery (output tiles, scalar
# tables).  The budget is enforced at call time so a misconfigured
# (depth, tile, feature-width) combination fails loudly instead of
# spilling on a real device.
VMEM_SCRATCH_BUDGET_BYTES = 8 * 1024 * 1024


def check_vmem_scratch(nbytes: int, what: str) -> None:
    """Raise when a pipelined kernel's scratch would not fit the VMEM
    scratch budget (callers shrink depth or tile sizes instead)."""
    if nbytes > VMEM_SCRATCH_BUDGET_BYTES:
        raise ValueError(
            f"{what}: {nbytes} B of VMEM scratch exceeds the "
            f"{VMEM_SCRATCH_BUDGET_BYTES} B budget; lower pipeline_depth "
            "or the tile sizes")


# --------------------------------------------------------- segment sum only


def _segsum_kernel(x_ref, w_ref, o_ref, *, fanout: int):
    # x_ref: [T_D * fanout, T_F]; w_ref: [T_D * fanout, 1]; o_ref: [T_D, T_F]
    td = o_ref.shape[0]
    x = x_ref[...].astype(jnp.float32).reshape(td, fanout, -1)
    w = w_ref[...].astype(jnp.float32).reshape(td, fanout, 1)
    o_ref[...] = (x * w).sum(axis=1).astype(o_ref.dtype)


def segment_sum_kernel_call(x_nbr: jax.Array, w_edge2d: jax.Array,
                            fanout: int, t_d: int = 128, t_f: int = 128,
                            interpret: bool = True) -> jax.Array:
    """x_nbr: [D*fanout, F] (D % t_d == 0, F % t_f == 0); w: [D*fanout, 1]."""
    d = x_nbr.shape[0] // fanout
    f = x_nbr.shape[1]
    grid = (d // t_d, f // t_f)
    return pl.pallas_call(
        functools.partial(_segsum_kernel, fanout=fanout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_d * fanout, t_f), lambda i, j: (i, j)),
            pl.BlockSpec((t_d * fanout, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t_d, t_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, f), x_nbr.dtype),
        interpret=interpret,
    )(x_nbr, w_edge2d)


# ------------------------------------------------- fused aggregate + update


def _fused_kernel(xs_ref, xn_ref, we_ref, ss_ref, ws_ref, wa_ref, b_ref,
                  o_ref, acc_ref, *, fanout: int, nf: int):
    # grid = (D, O, F); F innermost (accumulation axis)
    f_idx = pl.program_id(2)

    @pl.when(f_idx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    td = o_ref.shape[0]
    # aggregation stage (scatter-gather PEs): VMEM-resident weighted reduce
    xn = xn_ref[...].astype(jnp.float32).reshape(td, fanout, -1)
    we = we_ref[...].astype(jnp.float32).reshape(td, fanout, 1)
    agg = (xn * we).sum(axis=1)                       # [T_D, T_F]
    xs = xs_ref[...].astype(jnp.float32) * ss_ref[...].astype(jnp.float32)
    # update stage (systolic array -> MXU), fused: agg never leaves VMEM
    acc_ref[...] += jax.lax.dot(
        xs, ws_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    acc_ref[...] += jax.lax.dot(
        agg, wa_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(f_idx == nf - 1)
    def _flush():
        o_ref[...] = (acc_ref[...]
                      + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def fused_update_kernel_call(x_self: jax.Array, x_nbr: jax.Array,
                             w_edge2d: jax.Array, self_scale2d: jax.Array,
                             w_self: jax.Array, w_agg: jax.Array,
                             bias2d: jax.Array, fanout: int,
                             t_d: int = 128, t_f: int = 128, t_o: int = 128,
                             interpret: bool = True) -> jax.Array:
    """Fused GNN layer tile kernel.

    x_self: [D, F]; x_nbr: [D*fanout, F]; w_edge2d: [D*fanout, 1];
    self_scale2d: [D, 1]; w_self/w_agg: [F, O]; bias2d: [1, O] -> [D, O].
    All dims must be multiples of their tile sizes (ops.py pads).
    """
    d, f = x_self.shape
    o = w_self.shape[1]
    grid = (d // t_d, o // t_o, f // t_f)
    nf = grid[2]
    return pl.pallas_call(
        functools.partial(_fused_kernel, fanout=fanout, nf=nf),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_d, t_f), lambda i, j, k: (i, k)),            # x_self
            pl.BlockSpec((t_d * fanout, t_f), lambda i, j, k: (i, k)),   # x_nbr
            pl.BlockSpec((t_d * fanout, 1), lambda i, j, k: (i, 0)),     # w_edge
            pl.BlockSpec((t_d, 1), lambda i, j, k: (i, 0)),              # self_scale
            pl.BlockSpec((t_f, t_o), lambda i, j, k: (k, j)),            # w_self
            pl.BlockSpec((t_f, t_o), lambda i, j, k: (k, j)),            # w_agg
            pl.BlockSpec((1, t_o), lambda i, j, k: (0, j)),              # bias
        ],
        out_specs=pl.BlockSpec((t_d, t_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, o), x_self.dtype),
        # f32 accumulator resident in VMEM across the F reduction axis
        scratch_shapes=[pltpu.VMEM((t_d, t_o), jnp.float32)],
        interpret=interpret,
    )(x_self, x_nbr, w_edge2d, self_scale2d, w_self, w_agg, bias2d)


# -------------------------------------------- cache combine (hot + misses)


def _cache_combine_kernel(sel_ref, row_ref, cache_ref, miss_ref, o_ref):
    # one output row per grid step; the BlockSpec index maps (driven by
    # the scalar-prefetched sel/row tables) already DMA'd the right cache
    # row and miss row — the body just picks the live one.
    i = pl.program_id(0)
    take_cache = sel_ref[i] == 0
    o_ref[...] = jnp.where(take_cache, cache_ref[...], miss_ref[...])


def cache_combine_kernel_call(cache: jax.Array, miss: jax.Array,
                              sel: jax.Array, row: jax.Array,
                              interpret: bool = True) -> jax.Array:
    """Legacy one-row-per-grid-step combine (kept as a parity baseline —
    the trainer path uses ``cache_combine_tiled_kernel_call``).

    The TPU analogue of the paper's Feature-Duplicator gather PEs applied
    to the device-resident hot cache: ``out[i] = cache[row[i]]`` when
    ``sel[i] == 0`` else ``miss[row[i]]``.  ``sel``/``row`` arrive via
    scalar prefetch so each grid step's BlockSpec index map can steer the
    HBM->VMEM DMA at *row* granularity — a data-dependent gather the
    dense BlockSpec machinery cannot express.  Both sources stay in HBM;
    only the selected row per step is pulled into VMEM.

    cache: [K, F]; miss: [M, F] (M >= 1; callers pad empty miss blocks);
    sel/row: int32 [N] -> out [N, F].
    """
    n = sel.shape[0]
    f = cache.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(
                (1, f),
                lambda i, sel_ref, row_ref: (
                    jnp.where(sel_ref[i] == 0, row_ref[i], 0), 0)),
            pl.BlockSpec(
                (1, f),
                lambda i, sel_ref, row_ref: (
                    jnp.where(sel_ref[i] == 0, 0, row_ref[i]), 0)),
        ],
        out_specs=pl.BlockSpec((1, f), lambda i, sel_ref, row_ref: (i, 0)),
    )
    return pl.pallas_call(
        _cache_combine_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, f), cache.dtype),
        interpret=interpret,
    )(sel, row, cache, miss)


# ----------------------------------- cache scatter update (refresh path)


def sublane_rows(dtype) -> int:
    """Rows of one native (sublane, lane) tile of ``dtype``: 8 for 32-bit
    types, 16 for 16-bit ones (two rows pack into a sublane).  DMAs and
    blocks along the row axis must start and end on this boundary."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _cache_update_kernel(blocks_ref, upd_ref, mask_ref, cache_ref, o_ref):
    # grid = (T, F tiles): step (i, j) rewrites the F-tile j of the
    # aligned R-row cache block blocks[i].  Rows the mask marks take the
    # staged update row, the rest keep the cache's bytes.  The cache is
    # aliased to the output and every block appears once, so the
    # BlockSpec pipeline never reads a block it has yet to write back.
    o_ref[...] = jnp.where(mask_ref[...] != 0, upd_ref[...], cache_ref[...])


def cache_update_kernel_call(cache: jax.Array, upd: jax.Array,
                             mask: jax.Array, blocks: jax.Array,
                             t_f: int = 128,
                             interpret: bool = True) -> jax.Array:
    """In-place scatter of admitted rows into the device-resident hot
    block, one sublane-aligned R-row block per grid step
    (R = ``sublane_rows(dtype)``).

    The dynamic cache refresh admits a handful of rows per epoch; this
    kernel updates the [K, F] device block by rewriting only the aligned
    row blocks that hold admitted rows, instead of re-uploading all K rows
    over PCIe.  A single-row block is not a legal TPU tile, so the host
    (ops.update_cache_rows) groups the keep-last-deduped slots by block:
    block ``blocks[t]`` takes ``upd[t*R + r]`` wherever
    ``mask[t*R + r] != 0``.  ``blocks`` arrives via scalar prefetch so
    the BlockSpec index maps steer the read and the aliased write-back to
    data-dependent blocks — the scatter dual of the combine kernels'
    gather below.

    cache: [K, Fp] (K % R == 0, Fp % t_f == 0; callers pad);
    upd: [T*R, Fp]; mask: int32 [T*R, 1]; blocks: int32 [T], unique
    -> out [K, Fp].
    """
    rb = sublane_rows(cache.dtype)
    t = blocks.shape[0]
    f = cache.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, f // t_f),
        in_specs=[
            pl.BlockSpec((rb, t_f), lambda i, j, b: (i, j)),
            pl.BlockSpec((rb, 1), lambda i, j, b: (i, 0)),
            pl.BlockSpec((rb, t_f), lambda i, j, b: (b[i], j)),
        ],
        out_specs=pl.BlockSpec((rb, t_f), lambda i, j, b: (b[i], j)),
    )
    return pl.pallas_call(
        _cache_update_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operand order is (blocks, upd, mask, cache): alias the cache
        # into the output so untouched blocks are never moved
        input_output_aliases={3: 0},
        interpret=interpret,
    )(blocks, upd, mask, cache)


# ------------------------------------ tiled cache combine (multi-row DMA)


def _expand_window(loc: jax.Array, win: jax.Array) -> jax.Array:
    # loc: [T_N, 1] int32 row offsets into win: [4W, T_F] -> [T_N, T_F].
    # The duplication of shipped rows back into the positional layout is a
    # one-hot matmul, so it runs on the MXU instead of as a scalar gather.
    # The offsets sit one per sublane, so the compare against the lane
    # iota needs no relayout.
    onehot = (loc == jax.lax.broadcasted_iota(
        jnp.int32, (loc.shape[0], win.shape[0]), 1)).astype(jnp.float32)
    return jax.lax.dot(onehot, win.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)


def _cache_combine_tiled_kernel(base_ref, loc_ref,
                                s0_ref, s1_ref, s2_ref, s3_ref, o_ref):
    # One grid step materializes T_N output rows from a 4W-row VMEM window
    # (four consecutive aligned W-blocks of the dense source — enough to
    # cover any tile's monotone rank span, see
    # cache_combine_tiled_kernel_call).
    win = jnp.concatenate([s[...].astype(jnp.float32)
                           for s in (s0_ref, s1_ref, s2_ref, s3_ref)],
                          axis=0)                               # [4W, T_F]
    o_ref[...] = _expand_window(loc_ref[...], win).astype(o_ref.dtype)


def cache_combine_tiled_kernel_call(src: jax.Array, base: jax.Array,
                                    local: jax.Array,
                                    t_n: int = 128, t_f: int = 128,
                                    interpret: bool = True) -> jax.Array:
    """Multi-row tiled Feature-Duplicator expansion: T_N rows per grid step.

    Replaces the one-row-per-step combine on the trainer path.  ``src`` is
    the *dense* per-batch source (the distinct referenced cache rows
    compacted ahead of the unique shipped misses, see
    ops.assemble_features): every source row below the per-source pad gaps
    is referenced by at least one output position.  With output positions
    pre-sorted by source rank, a tile of T_N rows reads monotonically
    nondecreasing ranks with at most T_N distinct values, and density
    means its whole span (distinct rows + at most one bounded pad gap)
    fits inside four consecutive aligned W-row blocks (W = T_N).  Per tile
    the caller scalar-prefetches the aligned block index of the window
    and blocks a T_N-row column of offsets into it into VMEM (a vector
    table cannot be read back out of scalar memory); the body expands the
    4W-row VMEM window through a one-hot MXU matmul.  Grid steps drop from N to
    N/T_N (~128x less grid overhead) and every DMA is a dense MXU-aligned
    (W, T_F) block instead of a single row.

    src: [Sp, Fp] with Sp % W == 0 and >= (base.max() + 4) * W rows (the
    caller pads three spare blocks past the last referenced row so blocks
    b..b+3 always exist); base: int32 [G] aligned W-block index of each
    tile's window; local: int32 [G, T_N] offsets into the 4W window
    -> out [G*T_N, Fp].
    """
    g = base.shape[0]
    fp = src.shape[1]
    w = t_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, fp // t_f),
        in_specs=[
            pl.BlockSpec((t_n, 1), lambda i, j, b: (i, 0)),
            pl.BlockSpec((w, t_f), lambda i, j, b: (b[i], j)),
            pl.BlockSpec((w, t_f), lambda i, j, b: (b[i] + 1, j)),
            pl.BlockSpec((w, t_f), lambda i, j, b: (b[i] + 2, j)),
            pl.BlockSpec((w, t_f), lambda i, j, b: (b[i] + 3, j)),
        ],
        out_specs=pl.BlockSpec((t_n, t_f), lambda i, j, b: (i, j)),
    )
    return pl.pallas_call(
        _cache_combine_tiled_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g * t_n, fp), src.dtype),
        interpret=interpret,
    )(base, local.reshape(g * t_n, 1), src, src, src, src)


# ------------------- multi-buffered pipelined combine (DMA/compute overlap)


def _cache_combine_pipelined_kernel(base_ref, loc_ref, src_ref, o_ref,
                                    win_ref, sem_ref, *, window: int,
                                    t_f: int, depth: int, nf: int,
                                    nsteps: int):
    # Same math as _cache_combine_tiled_kernel, but the window DMAs are
    # issued by hand: ``src`` stays in HBM (memory_space=ANY) and each
    # grid step's 4W-row window is copied into one of ``depth`` VMEM
    # scratch slots by an async copy started ``depth`` steps ahead.  The
    # TPU grid runs steps sequentially, so while step s's one-hot matmul
    # occupies the MXU the copy for step s+1..s+depth-1 is already in
    # flight — the DMA latency the single-buffered kernel serializes
    # before every tile is hidden behind the previous tiles' compute.
    i = pl.program_id(0)
    j = pl.program_id(1)
    s = i * nf + j

    def window_dma(step, slot):
        ti = step // nf
        tj = jax.lax.rem(step, nf)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(base_ref[ti] * window, 4 * window),
                       pl.ds(tj * t_f, t_f)],
            win_ref.at[slot], sem_ref.at[slot])

    @pl.when(s == 0)
    def _warmup():      # fill every slot before the first compute
        for d in range(min(depth, nsteps)):
            window_dma(jnp.int32(d), d).start()

    slot = jax.lax.rem(s, depth)
    window_dma(s, slot).wait()
    o_ref[...] = _expand_window(loc_ref[...],
                                win_ref[slot]).astype(o_ref.dtype)

    @pl.when(s + depth < nsteps)
    def _prefetch_next():   # the slot is free again: refill depth ahead
        window_dma(s + depth, slot).start()


def cache_combine_pipelined_kernel_call(src: jax.Array, base: jax.Array,
                                        local: jax.Array,
                                        t_n: int = 128, t_f: int = 128,
                                        depth: int = 2,
                                        interpret: bool = True) -> jax.Array:
    """Multi-buffered tiled Feature-Duplicator expansion (paper §IV
    two-stage prefetching applied *inside* the kernel).

    Contract and output are identical to
    ``cache_combine_tiled_kernel_call`` (bit-identical: the same one-hot
    f32 MXU matmul over the same window values), but instead of four
    BlockSpec-driven block DMAs serialized before each tile's compute,
    ``depth`` (2-4) tile windows are held in VMEM scratch and tile
    s+depth's HBM->VMEM copy is started as soon as its slot frees — i.e.
    while tiles s+1..s+depth-1 still compute.  ``depth=1`` degenerates to
    issue-wait-compute per tile; callers (ops.assemble_features) keep the
    single-buffered kernel selectable for that.

    src: [Sp, Fp] dense padded source (see cache_combine_tiled_kernel_call
    for the window guarantees); base: int32 [G]; local: int32 [G, T_N]
    -> out [G*T_N, Fp].
    """
    if depth < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {depth}")
    g = base.shape[0]
    fp = src.shape[1]
    w = t_n
    nf = fp // t_f
    check_vmem_scratch(
        depth * 4 * w * t_f * src.dtype.itemsize,
        f"cache_combine_pipelined(depth={depth}, t_n={t_n}, t_f={t_f})")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, nf),
        in_specs=[pl.BlockSpec((t_n, 1), lambda i, j, b: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((t_n, t_f), lambda i, j, b: (i, j)),
        scratch_shapes=[pltpu.VMEM((depth, 4 * w, t_f), src.dtype),
                        pltpu.SemaphoreType.DMA((depth,))],
    )
    return pl.pallas_call(
        functools.partial(_cache_combine_pipelined_kernel, window=w,
                          t_f=t_f, depth=depth, nf=nf, nsteps=g * nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g * t_n, fp), src.dtype),
        interpret=interpret,
    )(base, local.reshape(g * t_n, 1), src)


# ------------------ multi-buffered pipelined scatter update (refresh path)


def _cache_update_pipelined_kernel(blocks_ref, upd_ref, mask_ref, cache_ref,
                                   o_ref, blk_ref, rd_sem, wr_sem, *,
                                   row_block: int, t_f: int, depth: int,
                                   nf: int, nsteps: int):
    # Same merge as _cache_update_kernel, but the cache blocks move by
    # hand: the cache stays in HBM (memory_space=ANY) and the read of the
    # R-row block step s+depth rewrites is in flight in one of ``depth``
    # VMEM slots while step s merges and writes its block back.  Every
    # (block, F tile) pair appears once, so no read can overtake the
    # write-back of the bytes it reads.
    i = pl.program_id(0)
    j = pl.program_id(1)
    s = i * nf + j

    def cache_tile(step):
        return (pl.ds(blocks_ref[step // nf] * row_block, row_block),
                pl.ds(jax.lax.rem(step, nf) * t_f, t_f))

    def block_read(step, slot):
        return pltpu.make_async_copy(cache_ref.at[cache_tile(step)],
                                     blk_ref.at[slot], rd_sem.at[slot])

    @pl.when(s == 0)
    def _warmup():
        for d in range(min(depth, nsteps)):
            block_read(jnp.int32(d), d).start()

    slot = jax.lax.rem(s, depth)
    block_read(s, slot).wait()
    blk_ref[slot] = jnp.where(mask_ref[...] != 0, upd_ref[...],
                              blk_ref[slot])
    write = pltpu.make_async_copy(blk_ref.at[slot], o_ref.at[cache_tile(s)],
                                  wr_sem.at[slot])
    write.start()
    write.wait()                      # the slot is free again

    @pl.when(s + depth < nsteps)
    def _prefetch_next():
        block_read(s + depth, slot).start()


def cache_update_pipelined_kernel_call(cache: jax.Array, upd: jax.Array,
                                       mask: jax.Array, blocks: jax.Array,
                                       t_f: int = 128, depth: int = 2,
                                       interpret: bool = True) -> jax.Array:
    """Multi-buffered in-place scatter of admitted rows into the hot block.

    Contract and output match ``cache_update_kernel_call`` (same operands,
    same per-block merge), but the aligned R-row cache blocks move through
    ``depth`` VMEM slots by hand: block t+depth streams HBM->VMEM while
    block t merges and writes back into the aliased cache.

    cache: [K, Fp] (K % R == 0, Fp % t_f == 0); upd: [T*R, Fp];
    mask: int32 [T*R, 1]; blocks: int32 [T], unique -> out [K, Fp].
    """
    if depth < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {depth}")
    rb = sublane_rows(cache.dtype)
    t = blocks.shape[0]
    fp = cache.shape[1]
    nf = fp // t_f
    check_vmem_scratch(
        depth * rb * t_f * cache.dtype.itemsize,
        f"cache_update_pipelined(depth={depth}, row_block={rb}, t_f={t_f})")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, nf),
        in_specs=[pl.BlockSpec((rb, t_f), lambda i, j, b: (i, j)),
                  pl.BlockSpec((rb, 1), lambda i, j, b: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((depth, rb, t_f), cache.dtype),
                        pltpu.SemaphoreType.DMA((depth,)),
                        pltpu.SemaphoreType.DMA((depth,))],
    )
    return pl.pallas_call(
        functools.partial(_cache_update_pipelined_kernel, row_block=rb,
                          t_f=t_f, depth=depth, nf=nf, nsteps=t * nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operand order is (blocks, upd, mask, cache): alias cache -> output
        input_output_aliases={3: 0},
        interpret=interpret,
    )(blocks, upd, mask, cache)
