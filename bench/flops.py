"""Operations and bytes the GNN training step needs, from its shapes.

``train_flops`` counts one forward and backward pass of the regular
layout (``frontier_sizes(batch, fanouts)`` rows per hop) for GCN and
GraphSAGE, as ``reference.py`` writes them:

* a product of an ``[m, k]`` by a ``[k, n]`` matrix is ``2 m k n``;
* aggregation: GraphSAGE's mean ``n_dst * fanout * f`` (adds and the one
  division per element), GCN's weighted sum ``2 * n_dst * fanout * f``
  plus its self term ``2 * n_dst * f``;
* a bias add is ``n_dst * f_out``; ReLU and the loss are not counted;
* backward: the weight and bias gradients of every layer, and the input
  gradient (product and aggregation) of every layer but the first, since
  layer-0 features are data and need no gradient.

``combine_bytes``: the combine writes each layer-0 position once and
reads it once (from the device cache or the shipped rows), whatever
implements it.
"""
from __future__ import annotations

from typing import Sequence


def frontier_sizes(batch: int, fanouts: Sequence[int]) -> list:
    out = [int(batch)]
    for f in fanouts:
        out.append(out[-1] * (1 + int(f)))
    return out


def train_flops(model: str, layer_dims: Sequence[int],
                fanouts: Sequence[int], batch: int) -> int:
    sizes = frontier_sizes(batch, fanouts)
    n_layers = len(fanouts)
    total = 0
    for layer in range(1, n_layers + 1):
        hop = n_layers - layer
        n_dst, fan = sizes[hop], int(fanouts[hop])
        fin, fout = int(layer_dims[layer - 1]), int(layer_dims[layer])
        if model == "sage":
            agg, k = n_dst * fan * fin, 2 * fin
        elif model == "gcn":
            agg, k = 2 * n_dst * fan * fin + 2 * n_dst * fin, fin
        else:
            raise ValueError(f"unknown model {model!r}")
        matmul = 2 * n_dst * k * fout
        bias = n_dst * fout
        total += agg + matmul + bias            # forward
        total += matmul + bias                  # weight and bias gradients
        if layer > 1:
            total += matmul + agg               # input gradient
    return total


def combine_bytes(positions: int, feat_dim: int, itemsize: int = 4) -> int:
    return 2 * int(positions) * int(feat_dim) * int(itemsize)
