"""AutoSAGE scheduler properties: guardrail non-regression (Prop. 1),
cache determinism, replay-only mode, estimate sanity."""
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # offline container; CI installs the real thing
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    AutoSage,
    HardwareSpec,
    InputFeatures,
    ReplayMiss,
    ScheduleCache,
    apply_guardrail,
)
from repro.core import estimate as est
from repro.core import registry
from repro.core.probe import induced_subgraph
from repro.kernels import ref
from repro.sparse import erdos_renyi, hub_skew


# ---------------------------------------------------------- Proposition 1
@given(
    t_best=st.floats(1e-6, 1e4),
    t_base=st.floats(1e-6, 1e4),
    alpha=st.floats(0.5, 1.0),
)
@settings(max_examples=200, deadline=None)
def test_guardrail_never_regresses(t_best, t_base, alpha):
    d = apply_guardrail("cand", t_best, t_base, alpha)
    t_chosen = t_best if d.accepted else t_base
    assert t_chosen <= t_base + 1e-12  # Prop. 1: t_chosen <= t_b


def test_guardrail_alpha_gt_one_rejected():
    with pytest.raises(AssertionError):
        apply_guardrail("cand", 1.0, 1.0, alpha=1.1)


def test_guardrail_accepts_clear_win_rejects_marginal():
    assert apply_guardrail("c", 0.5, 1.0, 0.95).accepted
    assert not apply_guardrail("c", 0.99, 1.0, 0.95).accepted
    # paper §8.3: larger alpha prefers baseline more often
    assert apply_guardrail("c", 0.96, 1.0, 0.98).accepted
    assert not apply_guardrail("c", 0.96, 1.0, 0.95).accepted


# ------------------------------------------------------------- decisions
@pytest.fixture(scope="module")
def sage():
    return AutoSage(
        cache=ScheduleCache(path=None), probe_iters=2, probe_cap_ms=200,
        probe_frac=0.05,
    )


def test_spmm_decision_correct_any_choice(sage):
    """Whatever the scheduler picks, the result must equal the oracle."""
    csr = hub_skew(4000, 4, 0.02, 300, seed=3)
    b = np.random.default_rng(0).standard_normal((csr.n_cols, 32)).astype(np.float32)
    out, d = sage.spmm(csr, b)
    exp = ref.spmm_ref(jnp.array(csr.rowptr), jnp.array(csr.colind), None, jnp.array(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3)
    assert d.choice in d.probe_ms or d.choice == "baseline"


def test_sddmm_decision_correct(sage):
    csr = erdos_renyi(3000, 1e-3, seed=1)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((csr.n_rows, 64)).astype(np.float32)
    y = rng.standard_normal((csr.n_cols, 64)).astype(np.float32)
    out, d = sage.sddmm(csr, x, y)
    exp = ref.sddmm_ref(jnp.array(csr.rowptr), jnp.array(csr.colind), jnp.array(x), jnp.array(y))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3)


def test_cache_hit_and_replay(tmp_path):
    path = str(tmp_path / "cache.json")
    sage = AutoSage(cache=ScheduleCache(path=path), probe_iters=2, probe_cap_ms=100)
    csr = erdos_renyi(2000, 1e-3, seed=5)
    b = np.zeros((2000, 32), np.float32)
    _, d1 = sage.spmm(csr, b)
    assert not d1.from_cache
    _, d2 = sage.spmm(csr, b)
    assert d2.from_cache and d2.choice == d1.choice
    # replay-only from a fresh process-like state: cached key works
    sage_r = AutoSage(cache=ScheduleCache(path=path, replay_only=True))
    d3 = sage_r.decide(csr, 32, "spmm")
    assert d3.from_cache and d3.choice == d1.choice
    # replay-only on an unseen key raises (deterministic replay contract)
    other = erdos_renyi(2001, 1e-3, seed=6)
    with pytest.raises(ReplayMiss):
        sage_r.decide(other, 32, "spmm")


def test_cache_key_includes_alpha(tmp_path):
    path = str(tmp_path / "cache.json")
    csr = erdos_renyi(1500, 1e-3, seed=7)
    b = np.zeros((1500, 32), np.float32)
    s95 = AutoSage(alpha=0.95, cache=ScheduleCache(path=path), probe_iters=2)
    s98 = AutoSage(alpha=0.98, cache=ScheduleCache(path=path), probe_iters=2)
    s95.spmm(csr, b)
    d = s98.decide(csr, 32, "spmm")
    assert not d.from_cache  # different alpha => different key => re-probe


def test_induced_subgraph_sampling():
    csr = hub_skew(10000, 4, 0.1, 100, seed=0)
    sub = induced_subgraph(csr, frac=0.02, min_rows=512)
    assert sub.n_rows >= 512
    # degree distribution is preserved (stride sampling)
    assert abs(sub.degrees.mean() - csr.degrees.mean()) < 0.3 * csr.degrees.mean()


def test_estimate_ranks_dense_correctly():
    hw = HardwareSpec.cpu()
    # tiny dense-ish graph: dense variant should rank well
    feat_dense = InputFeatures(
        n_rows=100, n_cols=100, nnz=5000, avg_deg=50, deg_p50=50, deg_p90=50,
        deg_p99=50, deg_max=50, skew=1.0, density=0.5, f=64, op="spmm",
        graph_sig="x", f_mod_4=True,
    )
    t_dense = est.estimate(feat_dense, hw, "dense", {})
    # huge sparse graph: dense must be catastrophically worse
    feat_sparse = InputFeatures(
        n_rows=200_000, n_cols=200_000, nnz=800_000, avg_deg=4, deg_p50=4,
        deg_p90=5, deg_p99=6, deg_max=10, skew=1.5, density=2e-5, f=64,
        op="spmm", graph_sig="y", f_mod_4=True,
    )
    t_dense_big = est.estimate(feat_sparse, hw, "dense", {})
    t_seg_big = est.estimate(feat_sparse, hw, "gather_segsum", {})
    assert t_dense_big > 100 * t_seg_big
    assert t_dense < 1.0


def test_registry_applicability_gates():
    hw = HardwareSpec.cpu()
    feat = InputFeatures(
        n_rows=200_000, n_cols=200_000, nnz=800_000, avg_deg=4, deg_p50=4,
        deg_p90=5, deg_p99=6, deg_max=2000, skew=1.5, density=2e-5, f=64,
        op="spmm", graph_sig="z", f_mod_4=True,
    )
    names = {v.name for v in registry.candidates(feat, hw, include_pallas=False)}
    assert "dense" not in names        # 4e10 dense elements: gated out
    assert "row_ell" not in names      # deg_max >> avg: padding explosion
    assert "bucket_ell" in names       # skewed tail: degree buckets apply
    assert "gather_segsum" in names


@pytest.mark.parametrize("n_rows, fits", [(122_451, False), (40_000, True)])
def test_row_ell_gather_gate_counts_lanes(n_rows, fits):
    """Products' transpose at F=47: row_ell's (n, 86, 47) gather takes
    128 lanes a row on a TPU, 5.4 GB, over a quarter of a v5e's HBM; a
    third of the rows fits. bucket_ell applies either way."""
    feat = InputFeatures(
        n_rows=n_rows, n_cols=n_rows, nnz=int(n_rows * 50.5), avg_deg=50.5,
        deg_p50=50, deg_p90=60, deg_p99=68, deg_max=86, skew=1.7,
        density=50.5 / n_rows, f=47, op="spmm_bwd_b", graph_sig="t",
        f_mod_4=False,
    )
    names = {v.name for v in registry.candidates(feat, HardwareSpec.tpu_v5e(),
                                                 include_pallas=False)}
    assert ("row_ell" in names) == fits and "bucket_ell" in names
