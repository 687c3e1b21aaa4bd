"""Pipeline-level CSR attention scheduling through `AutoSage.decide`:
composed vs fused numerical agreement, joint-decision caching,
replay-only mode, estimate/registry wiring, and the per-stage breakdown
diagnostic (core/pipeline.py)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    AutoSage,
    HardwareSpec,
    InputFeatures,
    ReplayMiss,
    ScheduleCache,
    device_sig,
)
from repro.core import estimate as est
from repro.core import obs, registry
from repro.core.pipeline import probe_stage_breakdown
from repro.kernels import ref
from repro.sparse import hub_skew


def _skewed_csr(n=256, base=3, hub_frac=0.1, hub_deg=12, seed=1):
    """Skewed synthetic graph, deduplicated: the generators sample columns
    with replacement, and attention mask semantics need set-of-edges."""
    return hub_skew(n, base, hub_frac, hub_deg, seed=seed).dedup_edges()


def _qkv(csr, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((csr.n_rows, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((csr.n_cols, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((csr.n_cols, d)).astype(np.float32))
    return q, k, v


def test_all_attention_candidates_match_oracle(monkeypatch):
    """Every registered pipeline — the four composed {sddmm x spmm} pairs
    AND the fused Pallas kernel — computes the same attention output."""
    monkeypatch.setenv("AUTOSAGE_PROBE_PALLAS", "1")  # include fused on CPU
    csr = _skewed_csr()
    d = 32
    q, k, v = _qkv(csr, d)
    exp = np.asarray(ref.csr_attention_ref(
        jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), q, k, v
    ))
    feat = InputFeatures.from_csr(csr, d, "attention")
    cands = registry.candidates(feat, HardwareSpec.cpu())
    names = {c.full_name() for c in cands}
    assert any(c.name == "fused_attention_pallas" for c in cands), names
    assert sum(c.name == "pipe" for c in cands) == 4, names
    for cand in cands:
        run = cand.build(cand.prepare(csr))
        out = np.asarray(run(q, k, v))
        np.testing.assert_allclose(
            out, exp, rtol=2e-3, atol=2e-3,
            err_msg=f"variant {cand.full_name()} diverges from oracle",
        )


def test_zero_weight_edges_stay_in_mask(monkeypatch):
    """Attention uses the sparsity pattern only: an explicitly stored edge
    with value 0.0 (e.g. from dedup_edges summing +w/-w) must stay in the
    softmax for every candidate, as the CSR baseline ignores values."""
    monkeypatch.setenv("AUTOSAGE_PROBE_PALLAS", "1")
    base = _skewed_csr()
    vals = np.ones(base.nnz, np.float32)
    vals[:: 7] = 0.0  # scatter explicit zeros across rows
    from repro.sparse import CSR

    csr = CSR(base.rowptr, base.colind, vals, base.n_rows, base.n_cols)
    d = 32
    q, k, v = _qkv(csr, d)
    exp = np.asarray(ref.csr_attention_ref(
        jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), q, k, v
    ))
    feat = InputFeatures.from_csr(csr, d, "attention")
    for cand in registry.candidates(feat, HardwareSpec.cpu()):
        out = np.asarray(cand.build(cand.prepare(csr))(q, k, v))
        np.testing.assert_allclose(
            out, exp, rtol=2e-3, atol=2e-3,
            err_msg=f"variant {cand.full_name()} drops zero-weight edges",
        )


def test_fused_gated_out_on_duplicate_edges():
    """Multigraphs: block-ELL merges duplicate edges into one mask entry,
    so the fused kernel computes a different function — it must not be a
    candidate there (the composed pipelines all agree with the oracle)."""
    csr = hub_skew(256, 3, 0.1, 12, seed=1)  # no dedup: duplicates likely
    assert csr.has_duplicate_edges()
    feat = InputFeatures.from_csr(csr, 32, "attention")
    cands = registry.candidates(feat, HardwareSpec.cpu(), include_pallas=True)
    assert not any(c.name == "fused_attention_pallas" for c in cands)


def test_attention_decision_correct_any_choice():
    """Whatever the pipeline scheduler picks, output equals the oracle."""
    csr = _skewed_csr(n=1200, hub_deg=20, seed=3)
    sage = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=2, probe_cap_ms=200,
        probe_frac=0.3,
    )
    q, k, v = _qkv(csr, 32)
    out, d = sage.attention(csr, q, k, v)
    exp = ref.csr_attention_ref(
        jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), q, k, v
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3
    )
    assert d.op == "attention"
    assert d.choice in d.probe_ms or d.choice == "baseline"
    # end-to-end probing covers baseline plus the shortlisted pipelines
    assert "baseline" in d.probe_ms


def test_attention_cache_hit_and_replay(tmp_path):
    path = str(tmp_path / "cache.json")
    sage = AutoSage(
        cache=ScheduleCache(path=path), probe_iters=2, probe_cap_ms=100,
        probe_frac=0.3,
    )
    csr = _skewed_csr(n=1000, seed=5)
    q, k, v = _qkv(csr, 16)
    _, d1 = sage.attention(csr, q, k, v)
    assert not d1.from_cache
    _, d2 = sage.attention(csr, q, k, v)
    assert d2.from_cache and d2.choice == d1.choice
    # fresh process-like state replays the joint decision from disk
    sage_r = AutoSage(cache=ScheduleCache(path=path, replay_only=True))
    d3 = sage_r.decide(csr, 16, "attention")
    assert d3.from_cache and d3.choice == d1.choice
    # the attention entry is keyed under its own op
    assert any("|attention|" in k2 for k2 in sage.cache.keys_for_op("attention"))


def test_attention_replay_miss_env(tmp_path, monkeypatch):
    """AUTOSAGE_REPLAY_ONLY=1 raises ReplayMiss on an unseen attention key."""
    path = str(tmp_path / "cache.json")
    # seed the cache with one graph's decision
    sage = AutoSage(
        cache=ScheduleCache(path=path), probe_iters=1, probe_cap_ms=50,
        probe_frac=0.3,
    )
    csr = _skewed_csr(n=1000, seed=5)
    sage.decide(csr, 16, "attention")
    # replay-only via the env contract: cached key replays, unseen raises
    monkeypatch.setenv("AUTOSAGE_REPLAY_ONLY", "1")
    sage_r = AutoSage(cache=ScheduleCache(path=path))
    assert sage_r.cache.replay_only
    assert sage_r.decide(csr, 16, "attention").from_cache
    other = _skewed_csr(n=999, seed=6)
    with pytest.raises(ReplayMiss):
        sage_r.decide(other, 16, "attention")


def test_attention_entry_with_op_and_stage_ms_replays(tmp_path, monkeypatch):
    """Entries written before attention shared the decide body carry an
    "op" and a "stage_ms" that no path writes now: under
    AUTOSAGE_REPLAY_ONLY=1 they still replay their pinned choice."""
    csr = _skewed_csr(n=1000, seed=5)
    feat = InputFeatures.from_csr(csr, 16, "attention")
    choice = next(v.full_name() for v in registry.candidates(feat, HardwareSpec.cpu())
                  if not v.is_baseline)
    path = str(tmp_path / "cache.json")
    ScheduleCache(path=path).put(
        ScheduleCache.key(device_sig(), feat.graph_sig, 16, "attention", 0.95),
        {"choice": choice, "probe_ms": {"baseline": 2.0, choice: 1.0},
         "estimates_ms": {}, "op": "attention",
         "stage_ms": {"sddmm": 0.3, "softmax": 0.4, "spmm": 0.3}},
    )
    monkeypatch.setenv("AUTOSAGE_REPLAY_ONLY", "1")
    d = AutoSage(cache=ScheduleCache(path=path), alpha=0.95,
                 hw=HardwareSpec.cpu()).decide(csr, 16, "attention")
    assert d.from_cache and d.choice == choice == d.variant.full_name()


def test_attention_stage_breakdown():
    csr = _skewed_csr(n=1000, seed=7)
    sage = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=100,
        probe_frac=0.3,
    )
    d = sage.decide(csr, 16, "attention")
    stage_ms = probe_stage_breakdown(sage, csr, 16, d.variant)
    assert set(stage_ms) == {"sddmm", "softmax", "spmm"} or set(stage_ms) == {"fused"}
    assert all(ms >= 0 for ms in stage_ms.values())


def test_attention_decide_counts_choice_and_syncs_breaker(monkeypatch):
    """The model path's attention decide is accounted like every other
    op's: it counts its choice, and its cold path folds in peers'
    quarantines before shortlisting."""
    from repro import api

    csr = _skewed_csr(n=400, seed=8)
    q, k, v = _qkv(csr, 16)
    sage = AutoSage(cache=ScheduleCache(path=None), top_k=0)
    syncs = []
    monkeypatch.setattr(sage.breaker, "maybe_sync", lambda: syncs.append(1))
    before = obs.REGISTRY.total(
        "autosage_decide_choice_total", op="attention", choice="baseline")
    api.attention(csr, q, k, v, sage=sage, differentiable=False)
    assert syncs
    assert obs.REGISTRY.total(
        "autosage_decide_choice_total", op="attention", choice="baseline"
    ) == before + 1


def test_pipeline_estimate_charges_roundtrips():
    """The composed-pipeline roofline must charge the inter-stage HBM
    round-trips (logits w+r, probs w+r) the fused kernel avoids."""
    hw = HardwareSpec.tpu_v5e()
    feat = InputFeatures(
        n_rows=100_000, n_cols=100_000, nnz=2_000_000, avg_deg=20, deg_p50=20,
        deg_p90=24, deg_p99=30, deg_max=40, skew=1.5, density=2e-4, f=64,
        op="attention", graph_sig="t", f_mod_4=True,
    )
    t_pipe = est.estimate(feat, hw, "pipe",
                          {"sddmm": "gather_dot", "spmm": "gather_segsum"})
    # strictly more than its per-op parts: softmax + 4 nnz-sized transfers
    t_parts = (est.estimate_sddmm(feat, hw, "gather_dot", {})
               + est.estimate_spmm(feat, hw, "gather_segsum", {}))
    roundtrip = 4.0 * feat.nnz * est.BYTES_F32 / hw.hbm_bw
    assert t_pipe >= t_parts + roundtrip
    # at wide F (bandwidth-bound on k/v traffic) the fused kernel's
    # block-granular reads undercut the composed pipeline's per-nnz
    # gathers + round-trips, so the estimate must rank fused first there
    feat_wide = dataclasses_replace_f(feat, 512)
    t_pipe_w = est.estimate(feat_wide, hw, "pipe",
                            {"sddmm": "gather_dot", "spmm": "gather_segsum"})
    t_fused_w = est.estimate(feat_wide, hw, "fused_attention_pallas",
                             {"rb": 8, "bc": 8, "padding_waste": 1.0})
    assert t_fused_w < t_pipe_w
    # mixed layouts pay a conversion penalty over matched layouts
    t_matched = est.estimate(feat, hw, "pipe",
                             {"sddmm": "row_ell", "spmm": "row_ell"})
    t_mixed = est.estimate(feat, hw, "pipe",
                           {"sddmm": "row_ell", "spmm": "gather_segsum"})
    assert t_mixed > min(t_matched, t_pipe) - 1e-12


def dataclasses_replace_f(feat: InputFeatures, f: int) -> InputFeatures:
    import dataclasses

    return dataclasses.replace(feat, f=f, f_mod_4=(f % 4 == 0))


def test_gat_layer_through_scheduler():
    """models/gnn.py attention path runs through AutoSage.attention."""
    from repro.configs.base import get_config
    from repro.models.gnn import gat_layer, init_gat
    import jax

    csr = _skewed_csr(n=600, seed=9)
    cfg = get_config("gnn_sage")
    params = init_gat(cfg, jax.random.PRNGKey(0), in_dim=8)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((csr.n_rows, 8)).astype(np.float32)
    )
    sage = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=50,
        probe_frac=0.3,
    )
    out_sched = gat_layer(params, csr, x, sage=sage)
    out_ref = gat_layer(params, csr, x)
    np.testing.assert_allclose(
        np.asarray(out_sched), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )
