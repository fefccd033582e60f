"""The generator's undirected graph holds every drawn edge both ways."""
from collections import Counter

import numpy as np
import pytest

from bench import datagen


def _pairs(g):
    src = np.repeat(np.arange(g.num_nodes), g.degrees())
    return Counter(zip(src.tolist(), g.indices.tolist()))


@pytest.mark.parametrize("chunk", [datagen.EDGE_CHUNK, 7000])
def test_undirected_graph_holds_each_edge_both_ways(monkeypatch, chunk):
    monkeypatch.setattr(datagen, "EDGE_CHUNK", chunk)
    d = datagen.make_graph(3000, 60000, 2.5, 11)
    u = datagen.make_graph(3000, 60000, 2.5, 11, undirected=True)
    src = np.repeat(np.arange(d.num_nodes), d.degrees())
    back = Counter(zip(d.indices.tolist(), src.tolist()))
    assert u.num_edges == 2 * d.num_edges
    assert _pairs(u) == _pairs(d) + back
    for x in range(d.num_nodes):
        out = d.indices[d.indptr[x]:d.indptr[x + 1]]
        np.testing.assert_array_equal(
            u.indices[u.indptr[x]:u.indptr[x] + out.shape[0]], out)


def test_same_seed_same_graph():
    a = datagen.make_graph(3000, 60000, 2.5, 2**33 + 1, undirected=True)
    b = datagen.make_graph(3000, 60000, 2.5, 2**33 + 1, undirected=True)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
