"""The benchmark's graph generators: fixed work for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphs

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def small(**kw):
    spec = {"num_nodes": 500, "mean_degree": 12.5, "degree_sigma": 1.4}
    spec.update(kw)
    return spec


@pytest.mark.parametrize("mean,sigma,cap", [(12.5, 1.1, 500), (40.0, 1.4, 60), (1.0, 2.0, 5)])
def test_degrees_add_up_to_the_mean_within_the_cap(mean, sigma, cap):
    deg = graphs.lognormal_degrees(500, mean, sigma, cap, seed=3)
    assert deg.sum() == round(mean * 500)
    assert deg.min() >= 1 and deg.max() <= cap


def test_degrees_refuse_an_impossible_mean():
    with pytest.raises(ValueError):
        graphs.lognormal_degrees(100, 50.0, 1.0, 10, seed=0)


def test_every_seed_relabels_one_graph():
    a, b = (graphs.make_graph(small(), s) for s in (1, 2**31 + 11))
    assert a.nnz == b.nnz == round(12.5 * 500)
    assert np.array_equal(np.sort(a.degrees()), np.sort(b.degrees()))
    in_a, in_b = (np.bincount(g.colind, minlength=g.n) for g in (a, b))
    assert np.array_equal(np.sort(in_a), np.sort(in_b))
    assert not np.array_equal(a.colind, b.colind)


def test_relabelling_keeps_the_edges():
    a, b = (graphs.make_graph(small(), s) for s in (3, 4))
    # a node of degree d in a has a node of degree d in b with the same
    # multiset of neighbour degrees
    def signature(g):
        deg = g.degrees()
        return sorted(tuple(sorted(deg[g.colind[g.rowptr[r]:g.rowptr[r + 1]]]))
                      for r in range(g.n))
    assert signature(a) == signature(b)


def test_a_seed_gives_the_same_graph():
    a, b = (graphs.make_graph(small(), 2**33 + 5) for _ in range(2))
    assert np.array_equal(a.rowptr, b.rowptr) and np.array_equal(a.colind, b.colind)


def test_columns_are_sorted_within_rows_and_in_range():
    g = graphs.make_graph(small(), 7)
    rows = np.repeat(np.arange(g.n), g.degrees())
    assert g.colind.min() >= 0 and g.colind.max() < g.n
    assert np.all((np.diff(rows) > 0) | (np.diff(g.colind) >= 0))
    assert g.rowptr.dtype == np.int32 and g.colind.dtype == np.int32


@pytest.mark.parametrize("name,nodes,edges", [
    ("products-sage", 122451, 6183776), ("reddit-sage", 23296, 11461632)])
def test_cell_degree_sequences_keep_the_published_mean(name, nodes, edges):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    n = cfg["num_nodes"]
    deg = graphs.lognormal_degrees(n, cfg["mean_degree"], cfg["degree_sigma"], n,
                                   graphs.GRAPH_SEED)
    assert (deg.shape[0], int(deg.sum())) == (nodes, edges)
    assert deg.max() <= n
