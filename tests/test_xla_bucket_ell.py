"""The degree-bucketed, slot-major ELL SpMM (``bucket_ell``): the same
answers as the searching oracle to float32 round-off, a layout that holds
every edge once and pads with zeros, and its place among the scheduler's
SpMM candidates."""
import itertools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AutoSage, ScheduleCache, obs, registry
from repro.core.features import HardwareSpec, InputFeatures
from repro.kernels import ref
from repro.kernels import xla as kx
from repro.sparse import CSR, hub_skew

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.trace import SPMM_FRAME  # noqa: E402


def _from_degrees(deg, n_cols: int, seed: int, weighted: bool = False,
                  cols=None) -> CSR:
    rng = np.random.default_rng(seed)
    rowptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    nnz = int(rowptr[-1])
    colind = (rng.integers(0, n_cols, nnz) if cols is None else cols).astype(np.int32)
    val = rng.standard_normal(nnz).astype(np.float32) if weighted else None
    return CSR(rowptr, colind, val, len(deg), n_cols)


def _duplicates() -> CSR:
    # every row reads columns 0-3 twice over, some three times
    deg = [8, 12, 8, 0, 12, 9]
    cols = np.concatenate([np.arange(d) % 4 for d in deg])
    return _from_degrees(deg, 6, 3, weighted=True, cols=cols)


GRAPHS = {
    # runs of empty rows first, last and between; columns 40-49 unread
    "zero_degree_rows": lambda: _from_degrees(
        [0, 0, 3, 1, 0, 0, 0, 5, 2, 0, 7, 0], 50, 1),
    # one row holds 3,000 of the edges; 40 distinct degrees besides, so
    # the widths come from the bucket search
    "hub_row": lambda: _from_degrees(
        np.concatenate([[3000], np.arange(40), np.arange(40)[::-1]]), 300, 2),
    "duplicate_edges": _duplicates,
    "rectangular": lambda: _from_degrees(
        np.random.default_rng(4).integers(0, 30, 64), 17, 4),
    "weighted": lambda: _from_degrees(
        np.random.default_rng(5).integers(1, 90, 120), 120, 5, weighted=True),
}


def _want(csr: CSR, b: jax.Array) -> np.ndarray:
    return np.asarray(ref.spmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind),
                                   jnp.asarray(csr.values_or_ones(np.float32)), b))


def _check(got, want):
    scale = max(1.0, float(np.abs(want).max(initial=0.0)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("f", [1, 47, 256])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_bucket_ell_matches_the_reference(graph, f):
    csr = GRAPHS[graph]()
    b = jnp.asarray(np.random.default_rng(7).standard_normal(
        (csr.n_cols, f)).astype(np.float32))
    aux = registry._dev(kx.prepare_bucket_ell(csr))
    got = registry._spmm_bucket_ell_jit(aux, b)
    assert got.shape == (csr.n_rows, f) and got.dtype == jnp.float32
    _check(got, _want(csr, b))


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_bucket_ell_layout(graph):
    csr = GRAPHS[graph]()
    aux = kx.prepare_bucket_ell(csr)
    deg, inv = csr.degrees, aux["inv"]
    assert len(aux["cols"]) == len(aux["slots"]) <= kx.MAX_BUCKETS
    assert np.all(inv[deg == 0] == 0)  # a zero row
    got, at, width = [], 8, 0
    for cols, vals, k in zip(aux["cols"], aux["vals"], aux["slots"]):
        assert cols.dtype == np.int32 and vals.shape == cols.shape
        n = cols.size // k
        assert cols.size == n * k and n % 8 == 0
        # (rows/8, K, 8) slot-major by groups of 8 rows, as (rows, K)
        cols = cols.reshape(-1, k, 8).transpose(0, 2, 1).reshape(n, k)
        vals = vals.reshape(-1, k, 8).transpose(0, 2, 1).reshape(n, k)
        rows = np.flatnonzero((inv >= at) & (inv < at + n))
        assert rows.size > n - 8 and deg[rows].max() == k and deg[rows].min() > width
        at, width = at + n, k
        d = np.zeros(n, np.int64)
        d[inv[rows] - (at - n)] = deg[rows]
        real = np.arange(k)[None, :] < d[:, None]
        # padded slots and rows: past the last column, value 0
        assert np.all(cols[~real] == csr.n_cols) and np.all(vals[~real] == 0)
        r = np.zeros(n, np.int64)
        r[inv[rows] - (at - n)] = rows
        got += zip(np.broadcast_to(r[:, None], (n, k))[real], cols[real], vals[real])
    # every row of degree > 0 has its own place, in a bucket
    placed = inv[deg > 0]
    assert np.unique(placed).size == placed.size and np.all(placed >= 8)
    assert np.all(placed < at)
    # every edge exactly once
    val = csr.values_or_ones(np.float32)
    want = zip(np.repeat(np.arange(csr.n_rows), deg), csr.colind, val)
    assert sorted(got) == sorted(want)

    # chunks: a small budget (8 of the widest row's gathered rows at 128
    # lanes) splits every bucket that exceeds it, in whole groups of 8
    # rows, and no chunk exceeds it
    budget = 8 * max(aux["slots"]) * 128 * 4
    chunks = kx.bucket_chunks(aux, 47, budget)
    over = sum(c.size * 128 * 4 > budget for c in aux["cols"])
    assert len(chunks) >= len(aux["cols"]) + over
    for i, s, e in chunks:
        assert s % 8 == 0 and e % 8 == 0
        assert aux["slots"][i] * (e - s) * 128 * 4 <= budget
    covered = sorted((i, r) for i, s, e in chunks for r in range(s, e))
    assert covered == [(i, r) for i, (c, k) in enumerate(zip(aux["cols"], aux["slots"]))
                       for r in range(c.size // k)]

    # b is NaN at every column no edge reads, and the padded slots read
    # none of them: the output is the reference's, finite
    b = np.random.default_rng(8).standard_normal((csr.n_cols, 47)).astype(np.float32)
    b[np.setdiff1d(np.arange(csr.n_cols), csr.colind)] = np.nan
    b = jnp.asarray(b)
    got = registry._spmm_bucket_ell_jit(registry._dev(aux), b, chunk_bytes=budget)
    assert np.all(np.isfinite(np.asarray(got)))
    _check(got, _want(csr, b))


@pytest.mark.parametrize("max_buckets", [1, 2, 3, 5, 13])
def test_bucket_bounds_are_the_least_padding(max_buckets):
    """The bucket search against every choice of at most
    ``max_buckets`` bounds, on degrees of more distinct values."""
    deg = np.random.default_rng(9).integers(0, 14, 200)
    distinct = np.unique(deg[deg > 0])

    def padded(bounds):  # each bucket's rows in whole groups of 8
        rows = np.bincount(np.searchsorted(bounds, deg[deg > 0]), minlength=len(bounds))
        return int(np.sum(np.asarray(bounds) * (-(-rows // 8) * 8)))

    bounds = kx.bucket_bounds(deg, max_buckets)
    assert len(bounds) <= max_buckets and bounds[-1] == distinct[-1]
    least = min(padded(list(c) + [distinct[-1]])
                for n in range(max_buckets)
                for c in itertools.combinations(distinct[:-1], n))
    assert padded(bounds) == least


def test_bucket_ell_is_a_candidate_and_its_choice_is_counted():
    hw = HardwareSpec.cpu()
    g = hub_skew(600, 6, 0.02, 150, seed=11).dedup_edges()
    for op in ("spmm", "spmm_bwd_b"):
        names = {v.name for v in registry.candidates(
            InputFeatures.from_csr(g, 32, op), hw, include_pallas=False)}
        assert "bucket_ell" in names and "row_ell" not in names, names
        names = {v.name for v in registry.candidates(
            InputFeatures.from_csr(g, 32, op, heads=4), hw, include_pallas=False)}
        assert "bucket_ell" not in names, names

    sage = AutoSage(cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=20)
    feat = InputFeatures.from_csr(g, 32, "spmm")
    _, short = sage.shortlist(feat, registry.candidates(feat, hw, include_pallas=False))
    assert "bucket_ell" in {v.name for v in short}

    def counted(**choice):
        return obs.REGISTRY.total("autosage_decide_choice_total", op="spmm", **choice)

    before = counted()
    d = sage.decide(g, 32, "spmm")
    assert counted() == before + 1
    once = counted(choice=d.choice)
    assert once >= 1
    sage.decide(g, 32, "spmm")  # a cache hit is a decide too
    assert counted(choice=d.choice) == once + 1 and counted() == before + 2


def test_bucket_ell_runner_is_named_for_the_spmm_frame_and_never_scatters():
    g = GRAPHS["hub_row"]()
    aux = registry._dev(kx.prepare_bucket_ell(g))
    b = jnp.zeros((g.n_cols, 64), jnp.float32)
    # inside a step, as the training loop calls it
    hlo = jax.jit(lambda b: registry._spmm_bucket_ell_jit(aux, b) * 2).lower(
        b).compile().as_text()
    names = [n for ln in hlo.splitlines() if re.search(r"\s(gather|reduce)\(", ln)
             for n in re.findall(r'op_name="([^"]*)"', ln)]
    assert names and all(SPMM_FRAME.search(n) for n in names), names
    assert "jit(spmm_bucket_ell)" in hlo
    assert not re.search(r"\sscatter\(", hlo)
