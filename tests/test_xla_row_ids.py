"""The XLA CSR variants read each edge's row from ``edge_row``, prepared on
the host: the same answers as the oracles in kernels/ref.py, which
binary-search rowptr, bit for bit, and no search left in the compiled
programs."""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import AutoSage, ScheduleCache, registry
from repro.kernels import ref
from repro.kernels import xla as kx
from repro.sparse import CSR, fixed_degree, power_law
from repro.train.gnn import make_data, train_full

SPMM_FRAME = re.compile(r"jit\([^()/]*spmm[^()/]*\)")


def _weighted(csr: CSR, seed: int) -> CSR:
    val = np.random.default_rng(seed).standard_normal(csr.nnz).astype(np.float32)
    return CSR(csr.rowptr, csr.colind, val, csr.n_rows, csr.n_cols)


def _from_degrees(deg, n_cols: int, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    rowptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    colind = rng.integers(0, n_cols, int(rowptr[-1])).astype(np.int32)
    return _weighted(CSR(rowptr, colind, None, len(deg), n_cols), seed)


def _transposed(csr: CSR) -> CSR:
    """A^T as the SpMM backward (``spmm_bwd_b``) runs it."""
    return csr.transpose_with_perm()[0]


GRAPHS = {
    # empty first and last rows, and a run of three in between
    "empty_rows": lambda: _from_degrees([0, 3, 1, 0, 0, 0, 4, 2, 0], 7, 1),
    "single_row": lambda: _from_degrees([5], 6, 2),
    "no_edges": lambda: _from_degrees([0, 0, 0, 0], 4, 3),
    "power_law": lambda: _weighted(power_law(160, 1.3, avg_deg=6, seed=4), 4),
    "power_law_transposed": lambda: _transposed(
        _weighted(power_law(160, 1.3, avg_deg=6, seed=4), 4)),
    "empty_rows_transposed": lambda: _transposed(
        _from_degrees([0, 3, 1, 0, 0, 0, 4, 2, 0], 7, 1)),
}


def _dense(rng, n, f):
    return jax.numpy.asarray(rng.standard_normal((n, f)).astype(np.float32))


def _spmm(csr, rng):
    aux = registry._dev(kx.prepare_csr(csr))
    b = _dense(rng, csr.n_cols, 5)
    return (registry._spmm_gather_jit(aux, b),
            jax.jit(ref.spmm_ref)(aux["rowptr"], aux["colind"], aux["val"], b))


def _spmm_dyn(csr, rng):
    aux = registry._dev(registry._prep_csr_structural(csr))
    vals = _dense(rng, csr.nnz, 1)[:, 0]
    b = _dense(rng, csr.n_cols, 5)
    return (registry._spmm_gather_dyn_jit(aux, vals, b),
            jax.jit(ref.spmm_ref)(aux["rowptr"], aux["colind"], vals, b))


def _sddmm(csr, rng):
    aux = registry._dev(kx.prepare_csr(csr))
    x, y = _dense(rng, csr.n_rows, 6), _dense(rng, csr.n_cols, 6)
    return (registry._sddmm_gather_jit(aux, x, y),
            jax.jit(ref.sddmm_ref)(aux["rowptr"], aux["colind"], x, y))


def _attention(csr, rng):
    aux = registry._dev(kx.prepare_csr(csr))
    q, k, v = (_dense(rng, n, 4) for n in (csr.n_rows, csr.n_cols, csr.n_cols))
    return (registry._attn_csr_jit(aux, q, k, v),
            jax.jit(ref.csr_attention_ref)(aux["rowptr"], aux["colind"], q, k, v))


def _row_softmax(csr, rng):
    aux = registry._dev(kx.prepare_csr(csr))
    val = _dense(rng, 1, csr.nnz)[0]
    return (jax.jit(kx.row_softmax)(aux, val),
            jax.jit(ref.row_softmax_ref)(aux["rowptr"], aux["colind"], val))


def _row_softmax_bwd(csr, rng):
    aux = registry._dev(kx.prepare_csr(csr))
    probs, grad = _dense(rng, 1, csr.nnz)[0], _dense(rng, 1, csr.nnz)[0]
    return (jax.jit(kx.row_softmax_bwd)(aux, probs, grad),
            jax.jit(ref.row_softmax_bwd_ref)(aux["rowptr"], aux["colind"], probs, grad))


KERNELS = {"spmm_gather_segsum": _spmm, "spmm_gather_dyn": _spmm_dyn,
           "sddmm_gather_dot": _sddmm, "attention_csr": _attention,
           "row_softmax": _row_softmax, "row_softmax_bwd": _row_softmax_bwd}


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_csr_variants_match_their_oracles_bit_for_bit(kernel, graph):
    csr = GRAPHS[graph]()
    got, want = KERNELS[kernel](csr, np.random.default_rng(7))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_prepared_row_ids_are_the_searched_ones(graph):
    csr = GRAPHS[graph]()
    rows = kx.prepare_csr(csr)["edge_row"]
    assert rows.dtype == np.int32
    np.testing.assert_array_equal(rows, kx.prepare_edge_slots(csr)["edge_row"])
    np.testing.assert_array_equal(rows, registry._prep_csr_structural(csr)["edge_row"])
    searched = ref._row_ids(jax.numpy.asarray(csr.rowptr, np.int32), csr.nnz)
    np.testing.assert_array_equal(rows, np.asarray(searched))


def _spmm_whiles(hlo: str):
    """Lines of a compiled HLO module that are ``while`` ops under a
    ``jit(...spmm...)`` frame."""
    return [ln for ln in hlo.splitlines()
            if re.search(r"\swhile\(", ln)
            and any(SPMM_FRAME.search(n) for n in re.findall(r'op_name="([^"]*)"', ln))]


def test_no_row_search_in_the_baseline_runner_or_the_training_step():
    g = fixed_degree(96, 4, seed=5)
    aux = registry._dev(kx.prepare_csr(g))
    runner = registry._spmm_gather_jit.lower(aux, _dense(np.random.default_rng(0), 96, 8))
    hlo = runner.compile().as_text()
    assert "jit(spmm_gather_segsum)" in hlo
    assert not re.search(r"\swhile\(", hlo)

    # top_k=0: no challenger is shortlisted, so every SpMM of the step,
    # forward and backward, runs the baseline
    sage = AutoSage(cache=ScheduleCache(path=None), top_k=0, probe_iters=1,
                    probe_cap_ms=20)
    arch = dataclasses.replace(get_config("gnn_sage"), n_layers=3, d_model=16)
    x, y = make_data(g, 4, 8)
    step = train_full(arch, g, x, y, 4, 0, sage).compiled.as_text()
    assert re.search(r"/jvp\(autosage\.fwd\.spmm\.f\d+\.baseline\)/jit\(spmm_gather_segsum\)/", step)
    assert re.search(r"transpose\(jvp\(autosage\.bwd\.spmm_bwd_b\.f\d+\.baseline\)\)"
                     r"/jit\(spmm_gather_segsum\)/", step)
    assert _spmm_whiles(step) == []


def test_the_while_guard_sees_a_row_search():
    """The guard above is not vacuous: the oracle's searchsorted, under a
    jitted name with ``spmm`` in it, compiles to a while loop it finds."""
    g = fixed_degree(32, 3, seed=6)
    aux = registry._dev(kx.prepare_csr(g))

    def spmm_searched(aux, b):
        return ref.spmm_ref(aux["rowptr"], aux["colind"], aux["val"], b)

    hlo = jax.jit(spmm_searched).lower(
        aux, _dense(np.random.default_rng(0), 32, 8)).compile().as_text()
    assert _spmm_whiles(hlo)
