"""Drift detection properties: the detector fires on a regime-shifted
subgraph stream (power-law alpha ramp), never on a stationary one;
re-probes respect the probe budget and decayed priority; the windowed
EWMA is permutation-invariant inside its startup window.

Observed runtimes are fed from a deterministic cost model of the pinned
choice (row-ELL padded work, n_rows * deg_max): the detector consumes
`observe()` values, so the properties are exact and seed-stable instead
of hostage to CPU timer noise. Real-kernel drift (wall-clock observe,
decision flip) is covered by the slow test at the bottom and by the
`shared_smoke` benchmark gate.
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_fallback import given, settings, st

from repro.core import AutoSage, BatchScheduler, InputFeatures, ScheduleCache
from repro.sparse import fixed_degree, hub_skew, regime_shift_stream


def _tiny_bs(probe_budget_ms=60_000, **knobs):
    bs = BatchScheduler(
        AutoSage(cache=ScheduleCache(path=None), probe_iters=1,
                 probe_cap_ms=25, probe_frac=0.25),
        probe_budget_ms=probe_budget_ms,
    )
    for k, v in knobs.items():
        setattr(bs, k, v)
    return bs


def _pinned_cost_ms(g) -> float:
    """Deterministic stand-in for the observed runtime of the uniform-
    regime winner (row-ELL): padded work is n_rows x deg_max."""
    return g.n_rows * max(float(g.degrees.max()), 1.0) / 1e3


def _run_stream(stream, bs, f=16):
    for g in stream:
        bs.decide(g, f, "spmm")
        bs.observe(bs.bucket_of(g, f, "spmm"), _pinned_cost_ms(g))
    return bs


# ------------------------------------------------------ fires / no-fire
@pytest.mark.parametrize("seed", [0, 2, 3])
def test_drift_fires_on_alpha_ramp(seed):
    """A power-law alpha ramp that stays inside the coarse schedule bins
    (0.2 -> 0.45 keeps the skew bin while deg_max roughly doubles) must
    trip the runtime-drift detector and spend probe budget on a
    re-probe. Observations are deterministic, so firing is seed-exact."""
    stream = regime_shift_stream(
        96, 256, n=1024, alpha_lo=0.2, alpha_hi=0.45, avg_deg=8, seed=seed
    )
    bs = _run_stream(stream, _tiny_bs(drift_min_obs=3, drift_ratio=1.4))
    s = bs.stats()
    assert s["drift_flags"] >= 1, s
    assert s["drift_reprobes"] >= 1, s
    # the re-probe actually drew from the shared probe budget
    assert s["probes_run"] > s["buckets"], s


@pytest.mark.parametrize("alpha", [0.0, 0.2])
def test_drift_never_fires_on_stationary_stream(alpha):
    """Same knobs, no regime shift: sampling jitter alone (deg_max moves
    ~1.4x between subgraphs of one parent) must stay under the detector's
    threshold — the EWMA exists to smooth exactly this."""
    stream = regime_shift_stream(
        96, 256, n=1024, alpha_lo=alpha, alpha_hi=alpha, avg_deg=8, seed=0
    )
    bs = _run_stream(stream, _tiny_bs(drift_min_obs=3, drift_ratio=1.4))
    s = bs.stats()
    assert s["drift_flags"] == 0, s
    assert s["drift_reprobes"] == 0, s


# -------------------------------------------------------- budget + decay
def _force_flag(bs, g, f=16):
    """Probe one bucket, then feed observations that depart from the
    calibrated reference so the runtime detector flags it. Returns the
    bucket and the choice that was pinned before the flag."""
    bs.decide(g, f, "spmm")
    bucket = bs.bucket_of(g, f, "spmm")
    pinned = bs._by_bucket[bucket].decision.choice
    for _ in range(bs.drift_min_obs):
        bs.observe(bucket, 1.0)  # calibration: the fresh decision's pace
    for _ in range(bs.ewma_window):
        bs.observe(bucket, 50.0)  # the regime underneath shifted
    return bucket, pinned


def test_reprobe_respects_probe_budget():
    """A drift-flagged bucket re-enters the pending queue but must NOT
    re-probe while the shared budget is exhausted; the stale decision
    keeps serving (guardrail-safe), and the re-probe runs once budget
    arrives."""
    bs = _tiny_bs()
    _force_flag(bs, fixed_degree(1024, 18, seed=0))
    bs.decide(fixed_degree(1024, 18, seed=3), 16, "spmm")  # auto-pump
    assert bs.stats()["drift_reprobes"] >= 1  # sanity: budget allows it

    bs2 = _tiny_bs()
    _, pinned = _force_flag(bs2, fixed_degree(1024, 18, seed=1))
    bs2.probe_budget_ms = bs2.probe_spent_ms  # budget exhausted NOW
    assert bs2.pump() == 0
    s = bs2.stats()
    assert s["drift_flags"] == 1 and s["drift_reprobes"] == 0
    assert s["pending_buckets"] == 1
    d = bs2.decide(fixed_degree(1024, 18, seed=2), 16, "spmm")
    assert d.choice == pinned  # stale-but-safe decision still serves
    bs2.probe_budget_ms += 10_000  # budget arrives
    assert bs2.pump() >= 1
    assert bs2.stats()["drift_reprobes"] == 1


def test_reprobe_priority_decays():
    """With equal traffic and headroom, a bucket that has already been
    re-probed ranks strictly below a fresh pending bucket — flapping
    buckets cannot starve never-probed ones."""
    bs = _tiny_bs(probe_budget_ms=0.0)  # keep both buckets pending
    a = fixed_degree(2048, 12, seed=0)
    b = fixed_degree(2048, 48, seed=1)
    bs.decide(a, 16, "spmm")
    bs.decide(b, 16, "spmm")
    sa = bs._by_bucket[bs.bucket_of(a, 16, "spmm")]
    sb = bs._by_bucket[bs.bucket_of(b, 16, "spmm")]
    # same traffic, same estimated gain: only the re-probe count differs
    sb.hits = sa.hits
    sb.est_gain_ms = sa.est_gain_ms = 1.0
    sb.has_challengers = sa.has_challengers = True
    assert sa.priority() == sb.priority()
    sb.reprobes = 1
    assert sb.priority() < sa.priority()
    # ...and the pump picks the fresh bucket first once budget arrives
    bs.probe_budget_ms = 10_000
    assert bs.pump(1) == 1
    assert sa.probed and not sb.probed


@given(hits=st.integers(1, 10**6), reprobes=st.integers(0, 10))
@settings(max_examples=30)
def test_priority_decay_monotone(hits, reprobes):
    """priority() is strictly decreasing in the re-probe count and a
    drift-flagged zero-headroom bucket still outranks an idle
    zero-headroom one (the observed runtime contradicts the estimate)."""
    base = dict(
        bucket=None, key="k", rep_csr=None, rep_feat=None, base=None,
        by_name={}, estimates_ms={}, est_gain_ms=2.5, has_challengers=True,
        hits=hits,
    )
    from repro.core.batch import _BucketState

    fresh = _BucketState(**base, reprobes=reprobes)
    worn = _BucketState(**base, reprobes=reprobes + 1)
    assert worn.priority() < fresh.priority()
    flagged = _BucketState(**{**base, "est_gain_ms": 0.0}, drift_flagged=True)
    idle = _BucketState(**{**base, "est_gain_ms": 0.0})
    assert flagged.priority() > idle.priority()


# ------------------------------------------------------------------ EWMA
@given(n_obs=st.integers(2, 16), seed=st.integers(0, 10**6))
@settings(max_examples=25)
def test_ewma_permutation_invariant_within_window(n_obs, seed):
    """For the first `ewma_window` observations the EWMA is the exact
    arithmetic mean, so any arrival-order permutation yields the same
    value — early drift verdicts cannot depend on minibatch ordering."""
    rng = np.random.default_rng(seed)
    obs = rng.uniform(0.1, 20.0, size=n_obs)
    perm = rng.permutation(obs)

    def ewma_of(seq):
        bs = _tiny_bs(probe_budget_ms=0.0)  # no probing needed for stats
        g = fixed_degree(512, 12, seed=0)
        bs.decide(g, 16, "spmm")
        bucket = bs.bucket_of(g, 16, "spmm")
        for x in seq:
            bs.observe(bucket, float(x))
        return bs._by_bucket[bucket].ewma_ms  # unrounded

    assert ewma_of(obs) == pytest.approx(ewma_of(perm), rel=1e-9)
    assert ewma_of(obs) == pytest.approx(float(obs.mean()), rel=1e-9)


def test_ewma_forgets_old_regime_beyond_window():
    """Past the window the EWMA is exponential: a long-steady new level
    dominates regardless of ancient history (staleness must not be
    masked by the early regime forever)."""
    bs = _tiny_bs(probe_budget_ms=0.0)
    g = fixed_degree(512, 12, seed=0)
    bs.decide(g, 16, "spmm")
    bucket = bs.bucket_of(g, 16, "spmm")
    for _ in range(16):
        bs.observe(bucket, 1.0)
    for _ in range(80):
        bs.observe(bucket, 10.0)
    ewma = bs.bucket_stats()[0]["ewma_ms"]
    assert ewma > 9.0, ewma


def test_observe_routes_by_full_bucket_not_sig():
    """Buckets for two ops (or two F) share a sig() — the shape bins —
    but observations must land on the op/F the caller named, never on a
    same-shape sibling."""
    bs = _tiny_bs(probe_budget_ms=0.0)
    g = fixed_degree(512, 12, seed=0)
    bs.decide(g, 16, "spmm")
    bs.decide(g, 16, "sddmm")
    b_spmm = bs.bucket_of(g, 16, "spmm")
    b_sddmm = bs.bucket_of(g, 16, "sddmm")
    assert b_spmm.sig() == b_sddmm.sig()  # the collision under test
    bs.observe(b_spmm, 7.0)
    assert bs._by_bucket[b_spmm].obs == 1
    assert bs._by_bucket[b_spmm].ewma_ms == 7.0
    assert bs._by_bucket[b_sddmm].obs == 0
    assert bs._by_bucket[b_sddmm].ewma_ms is None
    # a bare sig string is ambiguous here: ignored, not misattributed
    bs.observe(b_spmm.sig(), 99.0)
    assert bs._by_bucket[b_spmm].obs == 1
    assert bs._by_bucket[b_sddmm].obs == 0


# --------------------------------------------------------- waste drift
def test_waste_bin_shift_flags_drift():
    """A probed bucket whose incoming traffic crosses a padding-waste
    bin boundary (vs the probe representative's waste) is flagged even
    without runtime observations — the decide_events audit signal from
    PR 3, acted on. Within-process buckets can't normally cross bins
    (waste_bin is part of the sig), so this models a shared-cache entry
    probed by a peer under a different padding regime."""
    bs = _tiny_bs()
    g = fixed_degree(1024, 18, seed=0)
    bs.decide(g, 16, "spmm")
    stt = bs._by_bucket[bs.bucket_of(g, 16, "spmm")]
    assert stt.probed
    stt.waste_at_probe = 0.2  # peer probed a low-padding representative
    feat = dataclasses.replace(
        InputFeatures.from_csr(g, 16, "spmm"), padding_waste=0.8
    )
    bs._check_waste_drift(stt, feat)
    assert stt.drift_flagged and not stt.probed
    assert "padding_waste" in stt.drift_reason
    # same-bin movement is NOT drift
    bs2 = _tiny_bs()
    bs2.decide(g, 16, "spmm")
    st2 = bs2._by_bucket[bs2.bucket_of(g, 16, "spmm")]
    st2.waste_at_probe = 0.55
    bs2._check_waste_drift(
        st2, dataclasses.replace(InputFeatures.from_csr(g, 16, "spmm"),
                                 padding_waste=0.7)
    )
    assert not st2.drift_flagged


# ------------------------------------------------- real-kernel flip (slow)
@pytest.mark.slow
def test_drift_reprobe_flips_decision_real_kernels(monkeypatch):
    """End-to-end with wall-clock observations: a uniform deg-18 stream
    pins row_ell; the same bucket then fills with hidden-hub graphs
    (deg_max 140 — bins unchanged, row_ell still applicable, its padded
    width 7.8x wider); the observed runtime drifts, and the re-probe on
    the new representative flips the decision to a non-row_ell kernel."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import registry

    # bucket_ell pads neither stream (one bucket of width 18, then
    # buckets around the hubs) and can win both on a CPU, so the flip
    # need not happen: the pool here is the one whose best kernel the
    # hubs change
    pool = registry._spmm_variants
    monkeypatch.setattr(registry, "_spmm_variants", lambda feat: [
        v for v in pool(feat) if v.name != "bucket_ell"])

    # at F=8 row_ell's one (n, K, F) gather and dense reduce beat the
    # baseline's gather and scatter-add about 1.5x on the deg-18 rows (a
    # CPU), a margin that holds only for a probe of the whole graph
    # (no slope between two noisy small subgraphs) with 50 iterations
    f = 8
    stream = [fixed_degree(2048, 18, seed=i) for i in range(8)] + [
        hub_skew(2048, 18, 0.004, 140, seed=100 + i) for i in range(10)
    ]
    bs = BatchScheduler(
        AutoSage(cache=ScheduleCache(path=None), probe_iters=50,
                 probe_cap_ms=500, probe_frac=1.0),
        probe_budget_ms=60_000,
    )
    rng = np.random.default_rng(0)
    choices = []
    for g in stream:
        b = jnp.asarray(rng.standard_normal((g.n_cols, f)).astype(np.float32))
        d = bs.decide(g, f, "spmm")
        run = bs.build_runner(g, d)
        run(b)  # warm-up absorbs compilation
        times = []
        for _ in range(3):  # median shields the observe feed from
            t0 = time.perf_counter()  # scheduler-noise outliers
            jax.block_until_ready(run(b))
            times.append((time.perf_counter() - t0) * 1e3)
        bs.observe(bs.bucket_of(g, f, "spmm"), sorted(times)[1])
        choices.append(d.choice)
    s = bs.stats()
    assert s["buckets"] == 1, s  # the whole point: the bins can't see it
    assert choices[0] == "row_ell", choices
    assert s["drift_reprobes"] >= 1, s
    assert s["drift_flips"] >= 1, s
    assert choices[-1] != "row_ell", choices
