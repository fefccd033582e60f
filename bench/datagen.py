"""The benchmark's own data generator: graph, features and labels from a seed.

A configuration names a graph by its size (``num_nodes``, ``num_edges``)
and the shape of its degree distribution; this module makes that graph,
its feature matrix and its labels from ``--seed``, with nothing read from
the program under test.

The graph is a power-law multigraph in CSR form (out-neighbours), drawn
as |E| directed edges:

* out-degrees are Pareto(1.3)-shaped, clamped at a quarter of |V|, and
  topped up uniformly so that |E| is exactly the configuration's count;
* each edge's destination is ``perm[floor(|V| * u ** hub_exponent)]`` for a
  uniform ``u`` and a random permutation ``perm``: a few hub nodes receive
  most edges, as in the OGB graphs.

An undirected graph (as OGB lists ogbn-products) holds each of those
edges in both directions: 2 |E| CSR entries, a node's out-edges first and
then the sources of its in-edges.

Features are float32 draws from U[-1, 1), materialised in host RAM; labels
are uniform over the classes.  Every array is filled in fixed-size chunks,
each from its own generator spawned from the seed, on a small thread pool:
the result depends on the seed alone, never on the number of threads.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import os
from typing import Callable, List

import numpy as np

EDGE_CHUNK = 1 << 22          # edges per generator chunk
ROW_CHUNK = 1 << 16           # feature rows per generator chunk


@dataclasses.dataclass
class Graph:
    indptr: np.ndarray        # int64 [num_nodes + 1]
    indices: np.ndarray       # int32 [num_edges]

    @property
    def num_nodes(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.indices.shape[0])

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def _streams(seed: int, tag: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators for stream ``tag`` of ``seed``."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), tag])
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def _parallel(fn: Callable[[int], None], n: int, threads: int) -> None:
    with cf.ThreadPoolExecutor(max_workers=threads) as ex:
        for fut in [ex.submit(fn, i) for i in range(n)]:
            fut.result()


def default_threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


def make_graph(num_nodes: int, num_edges: int, hub_exponent: float,
               seed: int, threads: int = 0, undirected: bool = False
               ) -> Graph:
    threads = threads or default_threads()
    n, m = int(num_nodes), int(num_edges)
    if m < 2 * n:
        raise ValueError(f"|E| = {m} is under 2 |V| = {2 * n}: every node "
                         f"has at least one out-edge and the top-up needs "
                         f"room")
    rng = _streams(seed, 1, 1)[0]
    raw = rng.pareto(1.3, size=n) + 1.0
    deg = np.floor(raw * ((m - n) / raw.sum())).astype(np.int64)
    np.clip(deg, 1, max(8, n // 4), out=deg)
    short = m - int(deg.sum())        # > 0: floor and clamp only remove
    deg += np.bincount(rng.integers(0, n, short), minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    perm = rng.permutation(n).astype(np.int32)
    indices = np.empty(m, np.int32)
    chunks = -(-m // EDGE_CHUNK)
    gens = _streams(seed, 2, chunks)

    def fill(i: int) -> None:
        lo, hi = i * EDGE_CHUNK, min(m, (i + 1) * EDGE_CHUNK)
        u = gens[i].random(hi - lo)
        np.power(u, hub_exponent, out=u)
        u *= n
        rank = u.astype(np.int64)
        np.minimum(rank, n - 1, out=rank)
        np.take(perm, rank, out=indices[lo:hi])

    _parallel(fill, chunks, threads)
    if undirected:
        return _both_directions(indptr, indices, threads)
    return Graph(indptr=indptr, indices=indices)


def _both_directions(indptr: np.ndarray, indices: np.ndarray,
                     threads: int) -> Graph:
    """The CSR that holds every edge of ``(indptr, indices)`` in both
    directions: each node's out-edges in their order, then the sources of
    its in-edges in edge order (a counting sort, chunk by chunk)."""
    n, m = int(indptr.shape[0] - 1), int(indices.shape[0])
    deg_out = np.diff(indptr)
    chunks = -(-m // EDGE_CHUNK)
    orders: List[np.ndarray] = [np.empty(0, np.int64)] * chunks
    counts = np.empty((chunks, n), np.int64)

    def count(i: int) -> None:
        dst = indices[i * EDGE_CHUNK:(i + 1) * EDGE_CHUNK]
        orders[i] = np.argsort(dst, kind="stable")
        counts[i] = np.bincount(dst, minlength=n)

    _parallel(count, chunks, threads)
    both = np.zeros(n + 1, np.int64)
    np.cumsum(deg_out + counts.sum(axis=0), out=both[1:])
    src = np.repeat(np.arange(n, dtype=np.int32), deg_out)
    shift = np.repeat(both[:-1] - indptr[:-1], deg_out)
    # where chunk i's in-edges of node v start: after v's out-edges and
    # the in-edges of v that earlier chunks hold
    starts = np.cumsum(counts, axis=0)
    starts -= counts
    starts += both[:-1] + deg_out
    out = np.empty(2 * m, np.int32)

    def place(i: int) -> None:
        lo, hi = i * EDGE_CHUNK, min(m, (i + 1) * EDGE_CHUNK)
        out[np.arange(lo, hi) + shift[lo:hi]] = indices[lo:hi]
        dst = indices[lo:hi][orders[i]]
        first = np.cumsum(counts[i]) - counts[i]
        rank = np.arange(hi - lo) - first[dst]
        out[starts[i][dst] + rank] = src[lo:hi][orders[i]]

    _parallel(place, chunks, threads)
    return Graph(indptr=both, indices=out)


def make_features(num_nodes: int, feat_dim: int, seed: int,
                  threads: int = 0) -> np.ndarray:
    threads = threads or default_threads()
    x = np.empty((int(num_nodes), int(feat_dim)), np.float32)
    chunks = -(-x.shape[0] // ROW_CHUNK)
    gens = _streams(seed, 3, chunks)

    def fill(i: int) -> None:
        lo, hi = i * ROW_CHUNK, min(x.shape[0], (i + 1) * ROW_CHUNK)
        part = x[lo:hi]
        gens[i].random(dtype=np.float32, out=part)
        part *= 2.0
        part -= 1.0

    _parallel(fill, chunks, threads)
    return x


def make_labels(num_nodes: int, num_classes: int, seed: int) -> np.ndarray:
    return _streams(seed, 4, 1)[0].integers(
        0, num_classes, size=int(num_nodes), dtype=np.int32)


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed for stream ``tag`` of ``seed`` (for APIs that take a
    small integer, such as the trainer's configuration)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), 100 + tag])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)
