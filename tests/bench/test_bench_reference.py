"""The plain reference against a tiny CPU step of the program, and the
stand-ins (the reference in bfloat16, with its SpMM operands in bfloat16,
or over half the batch) against the cells' limits."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchcell
from bench import calibrate, data, graphs
from bench.models import graphsage_mean as ref

LIMITS = Path(__file__).resolve().parents[2] / "bench" / "limits"


@pytest.fixture(scope="module")
def case():
    cfg = dict(benchcell.TINY)
    g = graphs.make_graph(cfg, 5)
    x, y, w0 = data.inputs(5, g.n, data.dims(cfg))
    return g, x, y, w0


def test_reference_matches_a_program_step(case):
    from repro.models.gnn import sage_forward
    from repro.sparse.csr import CSR
    from repro.train.gnn import _xent

    g, x, y, w0 = case
    csr = CSR(g.rowptr, g.colind, None, g.n, g.n)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: _xent(sage_forward(p, csr, x), y))(w0)
    r_losses, r_grads, r_states = ref.train(w0, x, y, g.rowptr, g.colind, 0.05, 1, "highest")
    assert r_losses[0] == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(r_grads)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-7)
    for a, b, w in zip(jax.tree.leaves(r_states[0]), jax.tree.leaves(grads), jax.tree.leaves(w0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w) - 0.05 * np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_reference_loss_falls(case):
    g, x, y, w0 = case
    losses, _, _ = ref.train(w0, x, y, g.rowptr, g.colind, 0.5, 3, "default")
    assert losses[2] < losses[1] < losses[0]


def test_scatter_spmm_chunks_match_a_dense_product():
    rng = np.random.default_rng(0)
    rowptr = np.array([0, 2, 2, 5], np.int32)
    colind = np.array([0, 2, 1, 1, 2], np.int32)
    rows, cols, vals = ref.edges(rowptr, colind)
    assert rows.shape[0] % ref.CHUNK == 0
    b = rng.standard_normal((3, 4)).astype(np.float32)
    dense = np.zeros((3, 3), np.float32)
    for r in range(3):
        for c in colind[rowptr[r]:rowptr[r + 1]]:
            dense[r, c] += 1.0 / max(rowptr[r + 1] - rowptr[r], 1)
    out = ref.scatter_spmm(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                           jnp.asarray(b), 3)
    np.testing.assert_allclose(np.asarray(out), dense @ b, rtol=1e-6)


@pytest.mark.parametrize("cell", ["products-sage.full", "reddit-sage.full"])
@pytest.mark.parametrize("stand_in", sorted(calibrate.STAND_INS))
def test_control_and_planted_fault_fail_the_cells_limits(tmp_path_factory, cell, stand_in):
    tree = benchcell.build_tree(tmp_path_factory.mktemp("checkout"))
    limits = json.loads((LIMITS / f"{cell}.json").read_text())
    got = calibrate.seed_readings(benchcell.cpu_cell(tree), 7)[stand_in]
    assert any(got[k] > limits[k] for k in limits), got
