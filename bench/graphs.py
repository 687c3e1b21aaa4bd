"""Graph generators of the benchmark, copied from the program's
``repro.sparse.generators`` (``products_like``, ``reddit_like``) so that the
yardstick does not move when the program does, with two changes:

- the graph is drawn once, from ``GRAPH_SEED``, and a run's seed only
  relabels its nodes by a random permutation: every seed gets the same
  graph up to isomorphism, with the same number of
  edges and the same in- and out-degree histograms, and therefore the
  same work. (Columns drawn anew per seed changed the largest in-degree,
  which sets the padded width of a row-ELL backward, and with it the
  step time by 0.5% from seed to seed on the chip);
- the degrees add up to exactly ``round(mean_degree * num_nodes)`` edges,
  so the published mean degree holds after rounding and after capping
  each row at the node count (the program's ``reddit_like`` gives a mean
  of 221 at scale 0.05).

Columns are drawn uniformly with replacement, as in the program's
generators, so a row may hold an edge twice; the mean aggregator counts it
twice, in the program and in the reference alike. Columns are sorted
within each row.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np

GRAPH_SEED = 0  # the one graph of each configuration


class Graph(NamedTuple):
    rowptr: np.ndarray  # (n + 1,) int32
    colind: np.ndarray  # (nnz,) int32, sorted within each row
    n: int

    @property
    def nnz(self) -> int:
        return int(self.colind.shape[0])

    def degrees(self) -> np.ndarray:
        return np.diff(self.rowptr).astype(np.int64)


def lognormal_degrees(n: int, mean_degree: float, sigma: float, cap: int,
                      seed: int) -> np.ndarray:
    """Per-row degrees drawn lognormal(0, sigma), scaled so that, after
    flooring, a floor of 1 and a cap of ``cap``, they add up to exactly
    ``round(mean_degree * n)``."""
    target = int(round(mean_degree * n))
    if not n <= target <= n * cap:
        raise ValueError(f"{target} edges cannot be spread over {n} rows "
                         f"of 1 to {cap} edges")
    raw = np.random.default_rng(seed).lognormal(0.0, sigma, n)

    def degrees_at(scale: float) -> np.ndarray:
        return np.clip(np.floor(raw * scale), 1, cap).astype(np.int64)

    lo, hi = 0.0, float(cap) / raw.min() + 1.0  # degrees_at(hi) is all cap
    for _ in range(200):  # the largest scale whose sum stays <= target
        mid = (lo + hi) / 2
        if degrees_at(mid).sum() <= target:
            lo = mid
        else:
            hi = mid
    deg = degrees_at(lo)
    short = target - int(deg.sum())
    # the rest go one each to the uncapped rows nearest their next integer
    frac = np.where(deg < cap, raw * lo - np.floor(raw * lo), -1.0)
    deg[np.argsort(-frac, kind="stable")[:short]] += 1
    assert int(deg.sum()) == target
    return deg


def make_graph(spec: Dict, seed: int) -> Graph:
    """The graph of one run: the configuration's graph (its degrees and
    uniform random columns, both from ``GRAPH_SEED``), with its nodes
    relabelled by a permutation drawn from ``seed``."""
    n = int(spec["num_nodes"])
    deg = lognormal_degrees(n, float(spec["mean_degree"]), float(spec["degree_sigma"]), n,
                            GRAPH_SEED)
    base = np.random.default_rng([GRAPH_SEED, 1])
    cols = base.integers(0, n, size=int(deg.sum()), dtype=np.int64)
    label = np.random.default_rng(seed).permutation(n).astype(np.int64)
    rows = label[np.repeat(np.arange(n, dtype=np.int64), deg)]
    key = np.sort(rows * n + label[cols])  # by row, then column
    rowptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=rowptr[1:])
    return Graph(rowptr.astype(np.int32), (key % n).astype(np.int32), n)
