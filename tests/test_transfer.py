"""Cross-device schedule portability: estimate-space decision transfer
(core/transfer.py) — plan-level re-ranking/calibration invariants, the
BatchScheduler transfer tier (confident zero-probe accepts, budgeted
confirm-or-flip probes), exact-key transfer in AutoSage.decide,
peer-entry lookup, deterministic replay of transferred decisions, and
the device-sig/hw-profile simulation knobs the CI device matrix uses."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AutoSage,
    BatchScheduler,
    HardwareSpec,
    InputFeatures,
    ScheduleCache,
    device_sig,
    features_from_neutral,
)
from repro.core import registry, telemetry
from repro.core import transfer as transfer_mod
from repro.kernels import ref
from repro.sparse import fixed_degree, hub_skew, sample_subgraph_stream

F = 16
ALPHA = 0.95


@dataclasses.dataclass
class _FakeVariant:
    """Just enough Variant surface for plan_transfer: a real estimate-
    model name plus knobs, so local re-estimation is exact and the probe
    numbers in the donor entry can be handcrafted."""

    name: str
    knobs: dict = dataclasses.field(default_factory=dict)

    def full_name(self) -> str:
        if not self.knobs:
            return self.name
        ks = ",".join(f"{k}={v}" for k, v in sorted(self.knobs.items()))
        return f"{self.name}[{ks}]"


def _feat(seed=0, n=1024, deg=12) -> InputFeatures:
    return InputFeatures.from_csr(fixed_degree(n, deg, seed=seed), F, "spmm")


def _entry(ranking, choice, probed_at=100.0):
    return {
        "choice": choice,
        "probed": True,
        "neutral": {"ranking": ranking},
        "stats": {"probed_at": probed_at, "probes": 1},
    }


def _names():
    base = _FakeVariant("gather_segsum")
    a = _FakeVariant("row_ell")
    b = _FakeVariant("bucket_ell")
    by_name = {a.full_name(): a, b.full_name(): b}
    return base, a, b, by_name


# ------------------------------------------------------------ plan level
def test_same_roofline_transfer_reproduces_peer_ranking():
    """When source and local rooflines are identical, pred = est_local *
    probe/est_src = probe: the transfer must reproduce the donor's probed
    winner exactly (the calibration term carries the measurement over)."""
    feat, hw = _feat(), HardwareSpec.cpu()
    base, a, b, by_name = _names()
    est = lambda v: transfer_mod.est_mod.estimates_for(feat, hw, [v]).popitem()[1]
    # donor est_ms == local est (same roofline); probes say b wins
    ranking = [
        {"name": b.full_name(), "probe_ms": 1.0, "est_ms": est(b)},
        {"name": a.full_name(), "probe_ms": 2.0, "est_ms": est(a)},
        {"name": "baseline", "probe_ms": 5.0, "est_ms": est(base)},
    ]
    plan = transfer_mod.plan_transfer(
        "bucket|peer|r10.z13.s0.d-2.w0.simple|F=16|spmm|a=0.95",
        _entry(ranking, b.full_name()), feat, hw, by_name, base, ALPHA,
    )
    assert plan is not None
    assert plan.choice == b.full_name()
    assert plan.top1_agrees
    assert plan.rank_agreement == 1.0
    assert plan.source_device == "peer"
    np.testing.assert_allclose(plan.predicted_ms[b.full_name()], 1.0)
    np.testing.assert_allclose(plan.predicted_ms["baseline"], 5.0)


def test_unit_residuals_rerank_by_local_roofline():
    """probe == est_src everywhere (residual 1): the prediction reduces
    to the LOCAL estimate, so the transfer winner is the local roofline's
    winner even when the donor's probed order disagreed."""
    feat = _feat()
    base, a, b, by_name = _names()
    hw = HardwareSpec.cpu()
    est = lambda v: transfer_mod.est_mod.estimates_for(feat, hw, [v]).popitem()[1]
    local_best = a if est(a) < est(b) else b
    local_worst = b if local_best is a else a
    # donor probes put the LOCAL loser first — residuals are all 1, so
    # the local re-rank must overrule the donor's order
    ranking = [
        {"name": local_worst.full_name(), "probe_ms": 1.0, "est_ms": 1.0},
        {"name": local_best.full_name(), "probe_ms": 2.0, "est_ms": 2.0},
        {"name": "baseline", "probe_ms": 50.0, "est_ms": 50.0},
    ]
    plan = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", _entry(ranking, local_worst.full_name()),
        feat, hw, by_name, base, ALPHA,
    )
    assert plan.choice == local_best.full_name()
    assert not plan.top1_agrees  # disagreed with the donor's pinned choice
    assert not plan.confident  # ...so it must be probe-confirmed


def test_predicted_space_guardrail_falls_back_to_baseline():
    """A transferred choice is never predicted to regress: when every
    challenger's prediction exceeds alpha * baseline, the plan serves
    the baseline."""
    feat, hw = _feat(), HardwareSpec.cpu()
    base, a, _, by_name = _names()
    # challenger probed 100x slower than baseline on the donor
    ranking = [
        {"name": "baseline", "probe_ms": 1.0, "est_ms": 1.0},
        {"name": a.full_name(), "probe_ms": 100.0, "est_ms": 1.0},
    ]
    plan = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", _entry(ranking, "baseline"),
        feat, hw, by_name, base, ALPHA,
    )
    assert plan.choice == "baseline"
    assert not plan.guardrail.accepted
    assert plan.top1_agrees


def test_unconstructible_candidates_skipped():
    feat, hw = _feat(), HardwareSpec.cpu()
    base, a, _, by_name = _names()
    ranking = [
        {"name": "imaginary_pallas[z=1]", "probe_ms": 0.1, "est_ms": 0.1},
        {"name": a.full_name(), "probe_ms": 1.0, "est_ms": 1.0},
        {"name": "baseline", "probe_ms": 5.0, "est_ms": 5.0},
    ]
    plan = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95",
        _entry(ranking, "imaginary_pallas[z=1]"), feat, hw, by_name, base,
        ALPHA,
    )
    assert plan is not None
    assert "imaginary_pallas[z=1]" in plan.skipped
    assert plan.choice == a.full_name()  # best constructible challenger


def test_v4_entry_without_neutral_synthesizes_ranking():
    """A schema-v4 donor (probe_ms/estimates_ms, no "neutral") still
    transfers: the ranking is synthesized, with the baseline's estimate
    joined from its full variant name."""
    base, a, _, _ = _names()
    entry = {
        "choice": a.full_name(),
        "probe_ms": {"baseline": 4.0, a.full_name(): 1.0},
        "estimates_ms": {base.full_name(): 3.5, a.full_name(): 0.9},
    }
    ranking = transfer_mod.ranking_of(entry, base.full_name())
    assert [r["name"] for r in ranking] == [a.full_name(), "baseline"]
    assert ranking[1]["est_ms"] == 3.5  # baseline est via its full name


def test_never_probed_entry_donates_nothing():
    base = _names()[0]
    assert transfer_mod.ranking_of({"choice": "baseline"}, base.full_name()) == []
    plan = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", {"choice": "baseline", "probe_ms": {}},
        _feat(), HardwareSpec.cpu(), {}, base, ALPHA,
    )
    assert plan is None


def test_confirm_margin_controls_confidence(monkeypatch):
    feat, hw = _feat(), HardwareSpec.cpu()
    base, a, _, by_name = _names()
    ranking = [
        {"name": a.full_name(), "probe_ms": 1.0, "est_ms": 1.0},
        {"name": "baseline", "probe_ms": 5.0, "est_ms": 5.0},
    ]
    entry = _entry(ranking, a.full_name())
    lenient = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", entry, feat, hw, by_name, base, ALPHA,
        margin=1.0,
    )
    assert lenient.confident
    strict = transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", entry, feat, hw, by_name, base, ALPHA,
        margin=1e9,
    )
    assert strict.top1_agrees and not strict.confident
    # the env knob reaches the default margin
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1e9")
    assert not transfer_mod.plan_transfer(
        "k|peer|sig|F=16|spmm|a=0.95", entry, feat, hw, by_name, base, ALPHA,
    ).confident


def test_peer_entries_match_regime_modulo_device(tmp_path):
    c = ScheduleCache(path=str(tmp_path / "c.json"))
    key = ScheduleCache.bucket_key("devB", "r10.z13.s0.d-2.w0.simple", 16, "spmm", 0.95)
    same = ScheduleCache.bucket_key("devA", "r10.z13.s0.d-2.w0.simple", 16, "spmm", 0.95)
    newer = ScheduleCache.bucket_key("devC", "r10.z13.s0.d-2.w0.simple", 16, "spmm", 0.95)
    other_f = ScheduleCache.bucket_key("devA", "r10.z13.s0.d-2.w0.simple", 32, "spmm", 0.95)
    other_alpha = ScheduleCache.bucket_key("devA", "r10.z13.s0.d-2.w0.simple", 16, "spmm", 0.98)
    exact_kind = ScheduleCache.key("devA", "r10.z13.s0.d-2.w0.simple", 16, "spmm", 0.95)
    c.put(same, {"choice": "x", "stats": {"probed_at": 1.0}})
    c.put(newer, {"choice": "y", "stats": {"probed_at": 2.0}})
    c.put(other_f, {"choice": "x"})
    c.put(other_alpha, {"choice": "x"})
    c.put(exact_kind, {"choice": "x"})
    c.put(key, {"choice": "self"})
    peers = c.peer_entries(key)
    assert [k for k, _ in peers] == [newer, same]  # freshest probe first


# ------------------------------------------------- scheduler integration
def _tiny_sage(path=None, **kw):
    return AutoSage(
        cache=ScheduleCache(path=path, **kw), probe_iters=1, probe_cap_ms=25,
        probe_frac=0.25,
    )


def _stream(n=6, seed=4):
    parents = [
        fixed_degree(2048, 12, seed=1),
        fixed_degree(2048, 48, seed=2),
        hub_skew(2048, 6, 0.10, 60, seed=3),
    ]
    return sample_subgraph_stream(parents, n, rows_per_graph=256, seed=seed)


def _warm_peer(monkeypatch, path, sig="simA", profile="cpu", stream=None):
    """Finalize a device-A BatchScheduler over the stream into ``path``."""
    monkeypatch.setenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", sig)
    monkeypatch.setenv("AUTOSAGE_HW_PROFILE", profile)
    with BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000) as bs:
        for g in stream or _stream():
            bs.decide(g, F, "spmm")
    assert bs.stats()["probes_run"] >= 1
    return bs


def _as_device_b(monkeypatch, sig="simB", profile="cpu_wide"):
    monkeypatch.setenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", sig)
    monkeypatch.setenv("AUTOSAGE_HW_PROFILE", profile)


def test_batch_transfer_tier_beats_cold_start(monkeypatch, tmp_path):
    """The acceptance shape in-process: warm peer cache on device A, a
    second device class completes the stream with strictly fewer probes
    than its own cold start, and every transfer resolves."""
    path = str(tmp_path / "fleet.json")
    stream = _stream(8)
    a = _warm_peer(monkeypatch, path, stream=stream)
    cold_probes = a.stats()["probes_run"]

    _as_device_b(monkeypatch)
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs.decide(g, F, "spmm")
    bs.finalize()
    s = bs.stats()
    assert s["transfers"] >= 1
    assert s["probes_run"] < cold_probes
    assert s["transfers_pending"] == 0  # ample budget resolves them all
    assert s["transfers_confirmed"] + s["transfers_flipped"] == s["transfers"]
    assert any(ev["source"] in ("transfer", "transfer-pending")
               for ev in bs.trace)


def test_confident_transfer_costs_zero_probes(monkeypatch, tmp_path):
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1.0")
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs.decide(g, F, "spmm")
    bs.finalize()
    s = bs.stats()
    # with margin 1.0 any top-1 agreement is confident: at least one
    # bucket must accept probe-free, and every probe-free accept counts
    # as confirmed
    assert s["transfer_probe_free"] >= 1
    assert s["transfer_probe_free"] <= s["transfers_confirmed"]
    assert s["probes_run"] + s["transfer_probe_free"] <= s["buckets"]


def test_pending_transfer_confirmed_or_flipped_by_one_budgeted_probe(
    monkeypatch, tmp_path
):
    """With an impossible confirm margin every transfer is pending: the
    transferred choice serves immediately (guardrail-safe prediction),
    then exactly one budgeted probe per bucket resolves the verdict."""
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1e9")
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs.decide(g, F, "spmm")
    bs.finalize()
    s = bs.stats()
    assert s["transfers"] >= 1
    assert s["transfer_probe_free"] == 0
    # one confirm probe per transferred bucket, charged to the budget
    assert s["probes_run"] == s["buckets"]
    assert bs.probe_spent_ms > 0
    assert s["transfers_confirmed"] + s["transfers_flipped"] == s["transfers"]
    for row in bs.bucket_stats():
        if row["transferred"]:
            assert row["transfer_verdict"] in ("confirmed", "flipped")
            assert row["transfer_source"] == "simA"


def test_zero_budget_pending_transfer_keeps_serving_prediction(
    monkeypatch, tmp_path
):
    """No budget for the confirm probe: the bucket keeps serving the
    transferred (predicted-guardrail-safe) choice and finalize pins it
    with verdict "pending" — zero probes paid."""
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    a = _warm_peer(monkeypatch, path, stream=stream)
    peer_rows = {r["bucket"]: r["choice"] for r in a.bucket_stats()}
    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1e9")
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=0.0)
    for g in stream:
        d = bs.decide(g, F, "spmm")
        assert d.transfer is not None or d.choice == "baseline"
    bs.finalize()
    s = bs.stats()
    assert s["probes_run"] == 0
    assert s["transfers"] >= 1
    assert s["transfers_pending"] == s["transfers"]
    assert {ev["source"] for ev in bs.trace} <= {
        "transfer-pending", "provisional"
    }
    del peer_rows


def test_transferred_decisions_replay_bit_identically(monkeypatch, tmp_path):
    path = str(tmp_path / "fleet.json")
    stream = _stream(8)
    _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    choices = [bs.decide(g, F, "spmm").choice for g in stream]
    bs.finalize()

    def replay():
        rbs = BatchScheduler(
            AutoSage(cache=ScheduleCache(path=path, replay_only=True))
        )
        out = [rbs.decide(g, F, "spmm").choice for g in stream]
        assert rbs.stats()["probes_run"] == 0
        return out

    assert replay() == choices
    assert replay() == choices


def test_warm_reopen_adopts_confirmed_transfer(monkeypatch, tmp_path):
    """A later device-B process opens a pinned transferred-confirmed
    bucket warm (no probe, no fresh transfer): the transfer verdict
    travels with the entry."""
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1.0")
    bs1 = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs1.decide(g, F, "spmm")
    bs1.finalize()
    assert bs1.stats()["transfer_probe_free"] >= 1

    bs2 = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs2.decide(g, F, "spmm")
    s2 = bs2.stats()
    assert s2["probes_run"] == 0
    assert s2["warm_cache_opens"] == s2["buckets"]
    assert s2["transfers"] == 0  # adopted, not re-transferred


def test_exact_key_transfer_in_autosage_decide(monkeypatch, tmp_path):
    """The SAME graph decided on device A then device B: the exact-key
    transfer serves B without a probe when confident, with provenance on
    the decision and in the pinned entry."""
    path = str(tmp_path / "exact.json")
    csr = fixed_degree(1024, 12, seed=5)
    monkeypatch.setenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", "simA")
    monkeypatch.setenv("AUTOSAGE_HW_PROFILE", "cpu")
    a = _tiny_sage(path)
    da = a.decide(csr, F, "spmm")
    assert da.probe_ms  # measured on A
    a.cache.flush()

    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER_MARGIN", "1.0")
    b = _tiny_sage(path)
    db = b.decide(csr, F, "spmm")
    assert db.transfer is not None
    assert db.transfer["source_device"] == "simA"
    if db.transfer["verdict"] == "confirmed" and not db.probe_ms:
        # confident: zero probes, pinned for replay
        key = ScheduleCache.key(
            device_sig(), InputFeatures.from_csr(csr, F, "spmm").graph_sig,
            F, "spmm", b.alpha,
        )
        entry = b.cache.get(key)
        assert entry["transfer"]["source_device"] == "simA"
        assert entry["probed"] is False
    # re-decide is a plain cache hit either way
    db2 = b.decide(csr, F, "spmm")
    assert db2.from_cache and db2.choice == db.choice


def test_transfer_disabled_by_env(monkeypatch, tmp_path):
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    a = _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    monkeypatch.setenv("AUTOSAGE_TRANSFER", "0")
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    for g in stream:
        bs.decide(g, F, "spmm")
    bs.finalize()
    s = bs.stats()
    assert s["transfers"] == 0
    assert s["probes_run"] == a.stats()["probes_run"]  # full cold start


def test_transferred_spmm_matches_oracle(monkeypatch, tmp_path):
    """Conformance for the transfer tier: whatever the re-rank picks,
    the scheduled result equals the reference oracle."""
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    _warm_peer(monkeypatch, path, stream=stream)
    _as_device_b(monkeypatch)
    bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
    rng = np.random.default_rng(0)
    for g in stream[:3]:
        b_mat = jnp.asarray(
            rng.standard_normal((g.n_cols, F)).astype(np.float32)
        )
        out, d = bs.spmm(g, b_mat)
        exp = ref.spmm_ref(
            jnp.asarray(g.rowptr), jnp.asarray(g.colind), None, b_mat
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3,
            err_msg=f"transferred choice {d.choice}",
        )
    assert bs.stats()["transfers"] >= 1


def test_decide_events_record_transfer_provenance(monkeypatch, tmp_path):
    """decide_events.jsonl carries source_device, verdict and rank
    agreement for transferred decisions (the ISSUE's audit contract)."""
    tele = tmp_path / "tele"
    monkeypatch.setenv("AUTOSAGE_TELEMETRY_DIR", str(tele))
    path = str(tmp_path / "fleet.json")
    stream = _stream(6)
    try:
        _warm_peer(monkeypatch, path, stream=stream)
        _as_device_b(monkeypatch)
        bs = BatchScheduler(_tiny_sage(path), probe_budget_ms=10_000)
        for g in stream:
            bs.decide(g, F, "spmm")
        bs.finalize()
        assert bs.stats()["transfers"] >= 1
    finally:
        telemetry.close_streams()
    events = [
        json.loads(line)
        for line in (tele / "decide_events.jsonl").read_text().splitlines()
    ]
    transfers = [e for e in events if e["kind"] == "transfer"]
    assert transfers, "transfer decide events must be emitted"
    for e in transfers:
        assert e["transfer"]["source_device"] == "simA"
        assert e["transfer"]["verdict"] in ("confirmed", "pending", "flipped")
        assert 0.0 <= e["transfer"]["rank_agreement"] <= 1.0


# --------------------------------------------------- simulation knobs
def test_device_sig_override(monkeypatch):
    # compute the hardware truth first: the CI device matrix may already
    # be running this very test under an external override
    monkeypatch.delenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", raising=False)
    real = device_sig()
    assert real.count(":") >= 2  # platform:kind:jax<version>
    monkeypatch.setenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", "sim-x")
    assert device_sig() == "sim-x"
    monkeypatch.delenv("AUTOSAGE_DEVICE_SIG_OVERRIDE")
    assert device_sig() == real


def test_hw_profile_override(monkeypatch):
    monkeypatch.setenv("AUTOSAGE_HW_PROFILE", "cpu_wide")
    hw = HardwareSpec.current()
    assert hw.name == "cpu_wide"
    assert hw.hbm_bw > HardwareSpec.cpu().hbm_bw
    with pytest.raises(KeyError):
        HardwareSpec.from_profile("not-a-profile")


def test_neutral_features_roundtrip():
    feat = _feat()
    neutral = feat.to_neutral()
    assert json.loads(json.dumps(neutral)) == neutral  # JSON-serializable
    back = features_from_neutral(neutral)
    assert back == feat
    # unknown future fields are dropped, missing required ones raise
    assert features_from_neutral({**neutral, "future_field": 1}) == feat
    with pytest.raises(ValueError):
        features_from_neutral({"n_rows": 4})


def test_v5_entry_carries_neutral_ranking(tmp_path):
    """Every probed decision pins the transferable neutral part: input
    features + the probed ranking with probe AND estimate ms."""
    sage = _tiny_sage(str(tmp_path / "c.json"))
    csr = fixed_degree(1024, 12, seed=6)
    d = sage.decide(csr, F, "spmm")
    assert d.probe_ms
    key = ScheduleCache.key(
        device_sig(), InputFeatures.from_csr(csr, F, "spmm").graph_sig, F,
        "spmm", sage.alpha,
    )
    entry = sage.cache.get(key)
    neutral = entry["neutral"]
    assert neutral["op"] == "spmm" and neutral["f"] == F
    assert features_from_neutral(neutral["features"]).nnz == csr.nnz
    names = [r["name"] for r in neutral["ranking"]]
    assert "baseline" in names
    probed_names = set(d.probe_ms)
    assert set(names) == probed_names
    for r in neutral["ranking"]:
        assert r["probe_ms"] > 0
        assert r["est_ms"] is not None and r["est_ms"] > 0
