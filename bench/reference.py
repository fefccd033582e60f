"""Plain reference of the two GNN configurations: GCN and GraphSAGE in
straightforward ``jax.numpy``, with their loss, gradients and AdamW step.

Written from the models' equations (HyScale-GNN arXiv:2303.00158 §II-A:
Eq. 3 for GCN, Eq. 4 for GraphSAGE), over the sampled blocks in their
regular layout: frontier ``l + 1`` is frontier ``l`` followed by the
sampled neighbours of hop ``l + 1``, ``fanout`` of them per node of
frontier ``l``, node-major.  Neighbours are drawn with replacement, so the
GCN sum over a node's true neighbourhood is estimated as ``deg / fanout``
times the sum over the sampled ones, and each term is normalised by
``1 / sqrt(D(u) D(v))`` with ``D = out-degree + 1`` (a self loop); the
self term is ``h_v / D(v)``.  GraphSAGE concatenates ``h_v`` with the mean
of the sampled neighbours.  Each layer is ``ReLU(a W + b)``, the last
without ReLU; the loss is the mean negative log-likelihood of the targets'
labels under a softmax.

Nothing here imports the program under test or takes anything it made:
the weights come from ``init_params`` and the seed, the features, labels
and degrees from the benchmark's own data, and only the sampled node ids
are read from the run (and checked against the graph, ``compare.py``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import frontier_sizes

Params = Dict[str, jax.Array]

ADAMW = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 0.0}


def param_shapes(model: str, layer_dims: Sequence[int]
                 ) -> Dict[str, Tuple[int, ...]]:
    shapes: Dict[str, Tuple[int, ...]] = {}
    for l, (fin, fout) in enumerate(zip(layer_dims[:-1], layer_dims[1:]),
                                    start=1):
        shapes[f"w{l}"] = ((2 * fin if model == "sage" else fin), fout)
        shapes[f"b{l}"] = (fout,)
    return shapes


def init_params(key: jax.Array, model: str, layer_dims: Sequence[int]
                ) -> Params:
    """Weights N(0, 1 / fan_in), biases 0, float32: one jitted call."""
    shapes = param_shapes(model, tuple(layer_dims))

    @jax.jit
    def make(key):
        out = {}
        for name, shape in sorted(shapes.items()):
            if name.startswith("w"):
                key, sub = jax.random.split(key)
                out[name] = (jax.random.normal(sub, shape, jnp.float32)
                             / jnp.sqrt(jnp.float32(shape[0])))
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return make(key)


def merge_blocks(blocks: Sequence[Tuple[np.ndarray, Sequence[np.ndarray]]],
                 fanouts: Sequence[int]
                 ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """One block in the regular layout from several ``(targets, hop_src)``,
    their targets concatenated.  A frontier is a sequence of parts (the
    targets, then each hop), and the next hop lists the sampled neighbours
    in the order of those parts; so part by part, the merged hop lists
    every block's neighbours of that part."""
    parts = [[np.asarray(t, np.int64)] for t, _ in blocks]
    merged_hops: List[np.ndarray] = []
    for hop, f in enumerate(fanouts):
        chunks = []
        for (_, hops), ps in zip(blocks, parts):
            src = np.asarray(hops[hop], np.int64)
            bounds = np.cumsum([0] + [p.shape[0] * int(f) for p in ps])
            chunks.append([src[lo:hi] for lo, hi in zip(bounds[:-1],
                                                        bounds[1:])])
            ps.append(src)
        merged_hops.append(np.concatenate(
            [c[k] for k in range(hop + 1) for c in chunks]))
    return np.concatenate([ps[0] for ps in parts]), merged_hops


def block_arrays(targets: np.ndarray, hop_src: Sequence[np.ndarray],
                 degrees: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(innermost frontier ids, out-degree of every frontier position)."""
    frontier = np.concatenate([np.asarray(targets, np.int64)]
                              + [np.asarray(s, np.int64) for s in hop_src])
    return frontier, degrees[frontier]


def forward(params: Params, model: str, fanouts: Sequence[int],
            x0: jax.Array, deg: jax.Array, dtype) -> jax.Array:
    """Logits of the batch targets.  ``x0`` holds the features of the
    innermost frontier, ``deg`` the out-degree of each of its nodes (the
    inner frontiers are its prefixes)."""
    n_layers = len(fanouts)
    batch = x0.shape[0] // int(np.prod([1 + f for f in fanouts]))
    sizes = frontier_sizes(batch, fanouts)
    h = x0.astype(dtype)
    d = deg.astype(dtype)
    for layer in range(1, n_layers + 1):
        hop = n_layers - layer           # hop whose edges this layer reads
        n_dst, fan = sizes[hop], int(fanouts[hop])
        h_self = h[:n_dst]
        h_nbr = h[n_dst:sizes[hop + 1]].reshape(n_dst, fan, -1)
        if model == "sage":
            a = jnp.concatenate([h_self, h_nbr.mean(axis=1)], axis=-1)
        elif model == "gcn":
            d_v = d[:n_dst] + 1
            d_u = d[n_dst:sizes[hop + 1]].reshape(n_dst, fan) + 1
            w_uv = (d[:n_dst, None] / fan) / jnp.sqrt(d_u * d_v[:, None])
            a = (h_nbr * w_uv[..., None]).sum(axis=1) \
                + h_self / d_v[:, None]
        else:
            raise ValueError(f"unknown model {model!r}")
        z = a @ params[f"w{layer}"].astype(dtype) \
            + params[f"b{layer}"].astype(dtype)
        h = jax.nn.relu(z) if layer < n_layers else z
    return h


def nll_sum(params: Params, model: str, fanouts: Sequence[int],
            x0: jax.Array, deg: jax.Array, labels: jax.Array,
            dtype) -> jax.Array:
    logits = forward(params, model, fanouts, x0, deg, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def adamw_step(params: Params, state: Dict, grads: Params, dtype
               ) -> Tuple[Params, Dict]:
    h = ADAMW
    step = state["step"] + 1
    out_p, m, v = {}, {}, {}
    for k in params:
        g = grads[k].astype(dtype)
        m[k] = (h["b1"] * state["m"][k] + (1 - h["b1"]) * g).astype(dtype)
        v[k] = (h["b2"] * state["v"][k] + (1 - h["b2"]) * g * g).astype(dtype)
        mhat = m[k] / (1 - h["b1"] ** step)
        vhat = v[k] / (1 - h["b2"] ** step)
        u = -h["lr"] * (mhat / (jnp.sqrt(vhat) + h["eps"])
                        + h["weight_decay"] * params[k])
        out_p[k] = (params[k] + u).astype(dtype)
    return out_p, {"step": step, "m": m, "v": v}


def run_steps(params0: Params, model: str, fanouts: Sequence[int],
              steps: Sequence[Sequence[Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]]],
              dtype=jnp.float32, precision: str = "highest"
              ) -> Dict[str, object]:
    """Train from ``params0`` through ``steps``, each a list of trainer
    blocks ``(x0, deg, labels)`` whose targets together make the batch.
    The step's loss is the mean over all its targets, and its gradient
    that of the mean, which is what a share-weighted mean of per-trainer
    mean gradients equals.  Returns each step's loss, the first step's
    gradient, and the parameters after the last step (host numpy)."""
    fanouts = tuple(int(f) for f in fanouts)
    grad_fn = jax.jit(jax.value_and_grad(nll_sum),
                      static_argnums=(1, 2, 6))
    params = {k: jnp.asarray(v).astype(dtype) for k, v in params0.items()}
    state = {"step": 0,
             "m": {k: jnp.zeros_like(v) for k, v in params.items()},
             "v": {k: jnp.zeros_like(v) for k, v in params.items()}}
    losses: List[float] = []
    first_grad = None
    with jax.default_matmul_precision(precision):
        for blocks in steps:
            n = sum(int(lab.shape[0]) for _, _, lab in blocks)
            total = None
            grads = None
            for x0, deg, lab in blocks:
                val, g = grad_fn(params, model, fanouts, jnp.asarray(x0),
                                 jnp.asarray(deg), jnp.asarray(lab), dtype)
                total = val if total is None else total + val
                grads = g if grads is None else jax.tree.map(
                    jnp.add, grads, g)
            loss = (total / n).astype(dtype)
            grads = jax.tree.map(lambda x: (x / n).astype(dtype), grads)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: np.asarray(v, np.float64)
                              for k, v in grads.items()}
            params, state = adamw_step(params, state, grads, dtype)
    return {"losses": losses, "first_grad": first_grad,
            "params": {k: np.asarray(v, np.float64)
                       for k, v in params.items()}}
