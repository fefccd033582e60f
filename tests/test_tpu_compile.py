"""The main path's Pallas kernels and its XLA feature combines, compiled
by the TPU compiler for a described (not attached) TPU v5e at the paper's
real widths.

Interpret mode cannot catch what only Mosaic refuses: vector reads out of
scalar memory, blocks and DMA slices off the (8, 128) tile, lane widths
that are not multiples of 128 (f0 = 100 and 756).  Each kernel test
lowers one kernel call for one chip of a ``v5e:2x2`` topology and checks
the compiled program holds the Mosaic kernel; each combine test checks
that its program holds none and fits one chip.  Nothing runs.

Shapes follow ``sage-products`` at batch 1024 with half the batch on one
accelerator (fanouts (25, 10)): 146,432 layer-0 positions, a 20% hot
cache of 489,806 rows, hidden 256.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gather_scatter_mm as gsm
from repro.kernels import ops

WIDTHS = [100, 128, 756, 256]        # f0 of the three datasets; hidden
SHARE = 512                          # targets on one accelerator
FANOUTS = (25, 10)
POSITIONS = SHARE * (1 + FANOUTS[0]) * (1 + FANOUTS[1])
CACHE_ROWS = 489_806


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _padded(f: int) -> int:
    return -(-f // 128) * 128


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _compile_xla(fn, sharding, *shapes):
    """Compile ``fn`` for one chip: no Mosaic kernel, and its arguments,
    output and temporaries fit the chip's 16 GiB."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30


def _update_shapes(f0, dtype=jnp.float32, blocks=64):
    rb = gsm.sublane_rows(dtype)
    k = -(-CACHE_ROWS // rb) * rb
    return (((k, _padded(f0)), dtype), ((blocks * rb, _padded(f0)), dtype),
            ((blocks * rb, 1), jnp.int32), ((blocks,), jnp.int32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_cache_update_compiles(one_chip, f0):
    _compile(lambda c, u, m, b: gsm.cache_update_kernel_call(
        c, u, m, b, interpret=False), one_chip, *_update_shapes(f0))


@pytest.mark.parametrize("f0", WIDTHS)
def test_cache_update_pipelined_compiles(one_chip, f0):
    _compile(lambda c, u, m, b: gsm.cache_update_pipelined_kernel_call(
        c, u, m, b, depth=2, interpret=False), one_chip,
        *_update_shapes(f0))


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_cache_update_16bit_rows_compile(one_chip, dtype):
    """16-bit rows pack two to a sublane: the blocks are 16 rows."""
    _compile(lambda c, u, m, b: gsm.cache_update_pipelined_kernel_call(
        c, u, m, b, depth=2, interpret=False), one_chip,
        *_update_shapes(756, dtype))


def _layer(f0):
    # layer 1 reads hop 2 (fanout 10); layer 2 (f0 = hidden) reads hop 1
    if f0 == 256:
        return SHARE, FANOUTS[0]
    return SHARE * (1 + FANOUTS[0]), FANOUTS[1]


@pytest.mark.parametrize("f0", WIDTHS)
def test_segment_sum_compiles(one_chip, f0):
    d, fan = _layer(f0)
    fp = _padded(f0)
    _compile(lambda x, w: gsm.segment_sum_kernel_call(
        x, w, fan, interpret=False), one_chip,
        ((d * fan, fp), jnp.float32), ((d * fan, 1), jnp.float32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_fused_update_compiles(one_chip, f0):
    d, fan = _layer(f0)
    fp, o = _padded(f0), 256
    _compile(lambda *a: gsm.fused_update_kernel_call(
        *a, fanout=fan, interpret=False), one_chip,
        ((d, fp), jnp.float32), ((d * fan, fp), jnp.float32),
        ((d * fan, 1), jnp.float32), ((d, 1), jnp.float32),
        ((fp, o), jnp.float32), ((fp, o), jnp.float32),
        ((1, o), jnp.float32))


@pytest.mark.parametrize("f0", [100, 756])
def test_model_entry_points_pick_the_compiled_kernel(one_chip, f0):
    """Lowered for a TPU, the differentiable wrappers the GNN layers call
    select the Mosaic kernel inside a training step (their backward
    passes are plain jnp)."""
    d, fan = _layer(f0)

    def step(x_self, x_nbr, w_edge, w_self, w_agg):
        agg = ops.segment_weighted_sum_regular(x_nbr, w_edge, fan)
        ones = jnp.ones((d,), x_self.dtype)
        out = ops.fused_gnn_update(x_self, x_nbr, w_edge, ones, w_self,
                                   w_agg, None, fan)
        return agg.sum() + out.sum()

    _compile(jax.value_and_grad(step, argnums=(0, 3)), one_chip,
             ((d, f0), jnp.float32), ((d * fan, f0), jnp.float32),
             ((d * fan,), jnp.float32), ((f0, 256), jnp.float32),
             ((f0, 256), jnp.float32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_xla_combine_compiles(one_chip, f0):
    """The trainer's combine, XLA's gather and select, for a real batch
    layout with the index tables on the device: 20% of the hot cache's
    rows and a quarter of the positions as shipped unique rows."""
    _compile_xla(ops._assemble_ref, one_chip,
                 ((CACHE_ROWS, f0), jnp.float32),
                 ((POSITIONS // 4, f0), jnp.float32),
                 ((POSITIONS,), jnp.int32), ((POSITIONS,), jnp.int32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_xla_combine_without_cache_compiles(one_chip, f0):
    """The cache-less combine of ``sage-products.no-cache``: every
    position reads the shipped unique rows."""
    _compile_xla(ops._assemble_ref, one_chip, (None, None),
                 ((POSITIONS // 2, f0), jnp.float32),
                 ((POSITIONS,), jnp.int32), ((POSITIONS,), jnp.int32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_sharded_combine_compiles(one_chip, f0):
    """The sharded plane's combine on one of four chips: its local shard
    of the hot set, one segment of rows pulled from a peer shard and the
    fresh host rows, concatenated on the device and combined."""
    seg = POSITIONS // 8

    def combine(local, peer, fresh, slots, miss_index):
        return ops.assemble_features_sharded(local, [peer, fresh], slots,
                                             miss_index)

    _compile_xla(jax.jit(combine), one_chip,
                 ((CACHE_ROWS // 4, f0), jnp.float32),
                 ((seg, f0), jnp.float32), ((seg, f0), jnp.float32),
                 ((POSITIONS,), jnp.int32), ((POSITIONS,), jnp.int32))


@pytest.mark.parametrize("f0", WIDTHS)
def test_peer_gather_compiles(one_chip, f0):
    """The owner's half of a peer request: the requested rows gathered out
    of its shard block before the hop."""
    _compile_xla(jax.jit(ops.gather_rows), one_chip,
                 ((CACHE_ROWS // 4, f0), jnp.float32),
                 ((POSITIONS // 8,), jnp.int32))


def test_gat_train_step_compiles(one_chip):
    """The accelerator trainer's jitted GAT step (``core/hybrid.py``
    ``_grad_fn``) at the ``gat-products`` cell's widths, 100 → 4×128 →
    4×128 → 47 with fanouts (10, 10, 10), for the TPU's share of the batch
    (all 512 targets: the design-time map gives the host none), 681,472
    layer-0 positions: it fits one chip, and its operations carry the two
    scopes the benchmark's readers attribute device time by."""
    from repro.core.hybrid import _grad_fn
    from repro.graph import GNNConfig, MiniBatch, frontier_sizes, init_params
    cfg = GNNConfig(model="gat", layer_dims=(100, 512, 512, 47),
                    fanouts=(10, 10, 10), num_classes=47, heads=4)
    share = 512
    sizes = frontier_sizes(share, cfg.fanouts)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    hops = tuple(sds((n * f,), jnp.int32)
                 for n, f in zip(sizes, cfg.fanouts))
    mb = MiniBatch(targets=sds((share,), jnp.int32),
                   labels=sds((share,), jnp.int32), hop_src=hops,
                   hop_src_deg=hops, hop_dst_deg=hops, fanouts=cfg.fanouts)
    compiled = _grad_fn(cfg).lower(
        params, mb, sds((sizes[-1], 100), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30
    text = compiled.as_text()
    assert "gat.project" in text and "gat.attention" in text


@pytest.mark.parametrize("model,dims,heads", [
    ("sage", (100, 256, 47), 1), ("gat", (100, 512, 512, 47), 4)])
def test_sync_and_update_compile(one_chip, model, dims, heads):
    """The loop's two programs after the trainers, at the benchmark's
    widths: the weighted mean of two trainers' gradients
    (``core/protocol.py`` ``_weighted_sum``, shares traced) and the AdamW
    step (``core/hybrid.py`` ``_compiled_update``), whose params and
    optimizer state are donated: each of their leaves aliases an output."""
    from repro.core.hybrid import _compiled_update
    from repro.core.protocol import _weighted_sum
    from repro.graph import GNNConfig, init_params
    from repro.optim import adamw
    cfg = GNNConfig(model=model, layer_dims=dims,
                    fanouts=(10,) * (len(dims) - 1), num_classes=47,
                    heads=heads)
    opt = adamw(1e-3)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(sds, jax.eval_shape(opt.init, params))
    shares = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    _weighted_sum.lower([params, params], shares).compile()
    text = _compiled_update(opt).lower(params, state, params).compile() \
        .as_text()
    n_donated = len(jax.tree.leaves((params, state)))
    assert text.split("\n", 1)[0].count("may-alias") == n_donated
