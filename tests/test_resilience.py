"""Chaos conformance: under every injected fault class, every scheduler
surface must still return a runnable decision whose output matches the
kernels/ref.py oracle — scheduling faults may change speed, never values,
and never kill a training step.

The matrix mirrors test_conformance.py ({AutoSage, BatchScheduler,
shared-fleet BatchScheduler} x {spmm, sddmm, attention}) crossed with the
fault taxonomy of core/faultinject.py:

  - prepare-fault: every variant prepare raises OOM (permanent) — the
    fallback chain must reach a runnable stage;
  - run-fault: every non-reference runner raises forever — the terminal
    reference-oracle stage is injection-immune, so outputs are
    BIT-IDENTICAL to the oracle;
  - probe-timeout: every probe hangs past the watchdog — decide still
    lands (baseline), nothing wedges;
  - lock-fault: shared-cache lock acquisition raises — decisions still
    serve, no lockfile leaks, the cache file stays loadable.

Plus the circuit-breaker lifecycle (quarantine -> fleet sync -> TTL
half-open -> recovery), the replay contract (quarantined pin ->
ReplayMiss, never a silent substitute), the batch fault-retire path
(satellite of the fallback chain: a pinned choice that faults at run
re-opens its bucket), and a kill -9 mid-probe against the shared cache.
"""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AutoSage, BatchScheduler, ScheduleCache
from repro.core import faultinject, resilience
from repro.core.cache import ReplayMiss
from repro.kernels import ref
from repro.sparse import fixed_degree, hub_skew

OPS = ("spmm", "sddmm", "attention")
SCHEDULERS = ("autosage", "batch", "batch-shared")

# fault-class name -> env to set; "exact" marks classes whose outputs
# must be bit-identical to the oracle (all non-reference stages dead)
FAULTS = {
    "prepare-fault": {"env": {"AUTOSAGE_FAULT": "prepare::oom:"}, "exact": True},
    "run-fault": {"env": {"AUTOSAGE_FAULT": "run::raise:"}, "exact": True},
    "probe-timeout": {
        "env": {
            "AUTOSAGE_FAULT": "probe::hang:",
            "AUTOSAGE_FAULT_HANG_S": "0.5",
            "AUTOSAGE_PROBE_TIMEOUT_S": "0.1",
        },
        "exact": False,
    },
    "lock-fault": {"env": {"AUTOSAGE_FAULT": "lock::raise:"}, "exact": False},
}


@pytest.fixture(autouse=True)
def _clean_injection(monkeypatch):
    """Every test starts and ends with no compiled fault spec."""
    faultinject.reset()
    yield
    faultinject.reset()


def _graph(seed=0):
    return hub_skew(600, 4, 0.05, 24, seed=seed).dedup_edges()


# A graph on which a challenger (row_ell) beats the baseline on its
# merits on a CPU: one (n, K, F) gather and a dense reduce against a
# gather and a scatter-add, about 1.5x at F=8 on uniform deg-18 rows.
# Probing the whole graph 50 times resolves that margin; the default
# slope between two small subgraphs does not.
CHALLENGER_F = 8
CHALLENGER_PROBE = dict(probe_iters=50, probe_cap_ms=500, probe_frac=1.0)


def _challenger_graph():
    return fixed_degree(2048, 18, seed=0)


def _make_scheduler(kind, tmp_path):
    def sage(path=None, shared=False):
        return AutoSage(
            cache=ScheduleCache(path=path, shared=shared), probe_iters=1,
            probe_cap_ms=25, probe_frac=0.25,
        )

    if kind == "autosage":
        return sage()
    if kind == "batch":
        return BatchScheduler(sage(), probe_budget_ms=10_000)
    if kind == "batch-shared":
        return BatchScheduler(
            sage(path=str(tmp_path / "shared.json"), shared=True),
            probe_budget_ms=10_000,
        )
    raise KeyError(kind)


def _run_op(sched, csr, op, f, rng):
    rowptr, colind = jnp.asarray(csr.rowptr), jnp.asarray(csr.colind)
    if op == "spmm":
        b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        out, d = sched.spmm(csr, b)
        oracle = ref.spmm_ref(rowptr, colind, None, b)
    elif op == "sddmm":
        x = jnp.asarray(rng.standard_normal((csr.n_rows, f)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        out, d = sched.sddmm(csr, x, y)
        oracle = ref.sddmm_ref(rowptr, colind, x, y)
    elif op == "attention":
        q = jnp.asarray(rng.standard_normal((csr.n_rows, f)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        out, d = sched.attention(csr, q, k, v)
        oracle = ref.csr_attention_ref(rowptr, colind, q, k, v)
    else:
        raise KeyError(op)
    return out, d, oracle


# ------------------------------------------------- the chaos matrix
@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("kind", SCHEDULERS)
def test_chaos_decide_still_runnable_and_correct(
    kind, op, fault, tmp_path, monkeypatch
):
    spec = FAULTS[fault]
    for k, v in spec["env"].items():
        monkeypatch.setenv(k, v)
    faultinject.reset()
    sched = _make_scheduler(kind, tmp_path)
    rng = np.random.default_rng(0)
    out, d, oracle = _run_op(sched, _graph(), op, 16, rng)
    assert d is not None and d.choice
    assert np.isfinite(np.asarray(out)).all()
    if spec["exact"]:
        # all injectable stages dead -> the injection-immune reference
        # oracle served: outputs bit-identical, not merely close
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(oracle),
            err_msg=f"{kind}/{op}/{fault} chose {d.choice}",
        )
    else:
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(oracle), rtol=5e-3, atol=5e-3,
            err_msg=f"{kind}/{op}/{fault} chose {d.choice}",
        )
    # a faulting candidate must never be pinned for replay: whatever got
    # cached is either the baseline or a candidate the breaker still
    # trusts (quarantined names are excluded from pinning)
    sage = sched.sage if isinstance(sched, BatchScheduler) else sched
    for key in sage.cache._data:
        entry = sage.cache._data.get(key)
        if isinstance(entry, dict) and "quarantine" not in entry:
            choice = entry.get("choice")
            if isinstance(choice, str):
                assert not sage.breaker.is_quarantined(choice), (
                    f"{fault}: quarantined {choice!r} pinned at {key}"
                )
    if fault == "lock-fault" and kind == "batch-shared":
        if isinstance(sched, BatchScheduler):
            sched.finalize()  # guarded flush must swallow the lock fault
        path = tmp_path / "shared.json"
        assert not list(tmp_path.glob("*.lock")), "leaked lockfile"
        if path.exists():
            assert isinstance(json.load(open(path)), dict)


def test_chaos_injection_actually_fired(tmp_path, monkeypatch):
    """Guard against the matrix silently testing nothing: each fault
    spec must actually trigger at its site on the spmm path."""
    for fault, spec in FAULTS.items():
        if fault == "lock-fault":
            continue  # only fires on shared flush, checked below
        for k, v in spec["env"].items():
            monkeypatch.setenv(k, v)
        faultinject.reset()
        sched = _make_scheduler("autosage", tmp_path)
        rng = np.random.default_rng(0)
        _run_op(sched, _graph(), "spmm", 16, rng)
        site = spec["env"]["AUTOSAGE_FAULT"].split(":")[0]
        assert any(s == site for s, _ in faultinject.fired()), (
            f"{fault} never fired"
        )
        for k in spec["env"]:
            monkeypatch.delenv(k)
    monkeypatch.setenv("AUTOSAGE_FAULT", "lock::raise:")
    faultinject.reset()
    sched = _make_scheduler("batch-shared", tmp_path)
    rng = np.random.default_rng(0)
    _run_op(sched, _graph(), "spmm", 16, rng)
    sched.finalize()
    assert any(s == "lock" for s, _ in faultinject.fired())


# ------------------------------------------------ fault-injection DSL
def test_fault_spec_counts_and_match(monkeypatch):
    monkeypatch.setenv("AUTOSAGE_FAULT", "run:row_ell:raise:2")
    faultinject.reset()
    for _ in range(2):
        with pytest.raises(faultinject.InjectedFault):
            faultinject.fault_point("run", name="row_ell.v1", op="spmm")
    faultinject.fault_point("run", name="row_ell.v1")  # count exhausted
    faultinject.fault_point("run", name="gather")  # match miss
    faultinject.fault_point("probe", name="row_ell.v1")  # site miss
    assert faultinject.fired() == {("run", "raise"): 2}


def test_fault_spec_wildcard_and_classes(monkeypatch):
    monkeypatch.setenv("AUTOSAGE_FAULT", "*::oom:1;probe::raise:1")
    faultinject.reset()
    with pytest.raises(faultinject.InjectedFault) as ei:
        faultinject.fault_point("prepare", name="x")
    assert ei.value.permanent
    assert resilience.classify(ei.value) == resilience.PERMANENT
    with pytest.raises(faultinject.InjectedFault) as ei:
        faultinject.fault_point("probe", name="x")
    assert not ei.value.permanent


def test_fault_prob_mode_is_seed_deterministic(monkeypatch):
    def run():
        faultinject.reset()
        hits = []
        for i in range(200):
            try:
                faultinject.fault_point("run", name=f"c{i}")
                hits.append(0)
            except faultinject.InjectedFault:
                hits.append(1)
        return hits

    monkeypatch.setenv("AUTOSAGE_FAULT", "prob:0.1:seed=8")
    a, b = run(), run()
    assert a == b and 0 < sum(a) < 200


def test_resilience_kill_switch(monkeypatch, tmp_path):
    """AUTOSAGE_RESILIENCE=0: faults propagate raw (debugging mode)."""
    monkeypatch.setenv("AUTOSAGE_RESILIENCE", "0")
    monkeypatch.setenv("AUTOSAGE_FAULT", "run::raise:")
    faultinject.reset()
    sched = _make_scheduler("autosage", tmp_path)
    csr = _graph()
    d = sched.decide(csr, 16, "spmm")
    runner = sched.build_runner(csr, d)
    if d.choice != "baseline":
        pass  # run fault_point only fires through the chain; raw path
    assert runner(jnp.ones((csr.n_cols, 16))) is not None


# ------------------------------------------------- circuit breaker
def test_breaker_quarantine_excludes_and_persists(tmp_path):
    path = str(tmp_path / "c.json")
    cache = ScheduleCache(path=path)
    br = resilience.CircuitBreaker(cache=cache, threshold=3)
    assert not br.record_failure("v1", site="run", op="spmm")
    assert not br.record_failure("v1", site="run", op="spmm")
    assert br.record_failure("v1", site="run", op="spmm")  # tips at 3
    assert br.is_quarantined("v1") and br.excluded_names() == {"v1"}
    # permanent faults skip the threshold
    assert br.record_failure("v2", site="prepare", op="spmm", permanent=True)
    # the baseline is exempt no matter what
    for _ in range(10):
        assert not br.record_failure("baseline", site="run", op="spmm")
    cache.flush()
    peer = resilience.CircuitBreaker(cache=ScheduleCache(path=path))
    peer.maybe_sync()
    assert peer.is_quarantined("v1") and peer.is_quarantined("v2")


def test_breaker_ttl_half_open_recovery(tmp_path, monkeypatch):
    monkeypatch.setenv("AUTOSAGE_QUARANTINE_TTL_S", "0.05")
    cache = ScheduleCache(path=str(tmp_path / "c.json"))
    br = resilience.CircuitBreaker(cache=cache, threshold=1)
    br.record_failure("v1", site="run", op="spmm")
    assert br.is_quarantined("v1")
    time.sleep(0.06)
    # past TTL: half-open, gets its one recovery probe back
    assert not br.is_quarantined("v1") and not br.is_excluded("v1")
    br.record_success("v1")  # recovery probe passed: cleared for good
    assert not br.is_quarantined("v1")
    recs = dict(cache.quarantine_records())
    assert [r["state"] for r in recs.values()] == ["cleared"]
    # and the flip side: a failed recovery probe re-quarantines at once
    br.record_failure("v2", site="run", op="spmm")
    time.sleep(0.06)
    assert not br.is_quarantined("v2")
    br.record_failure("v2", site="run", op="spmm")
    assert br.is_quarantined("v2")
    assert br.active_quarantine("v2")["reason"] == "recovery_failed"


def test_breaker_success_resets_consecutive_count(tmp_path):
    br = resilience.CircuitBreaker(
        cache=ScheduleCache(path=None), threshold=3
    )
    br.record_failure("v1")
    br.record_failure("v1")
    br.record_success("v1")
    assert not br.record_failure("v1")  # count restarted, not tipped
    assert not br.is_quarantined("v1")


def test_repeated_run_faults_quarantine_and_serve_reference(
    tmp_path, monkeypatch
):
    """End to end: a pinned candidate faulting at every run crosses the
    breaker threshold, lands in the shared cache's blacklist, and later
    schedulers exclude it from the shortlist outright."""
    path = str(tmp_path / "shared.json")
    csr = _challenger_graph()
    b = jnp.ones((csr.n_cols, CHALLENGER_F), jnp.float32)
    monkeypatch.setenv("AUTOSAGE_FAULT", "run::raise:")
    faultinject.reset()
    s1 = AutoSage(
        cache=ScheduleCache(path=path, shared=True), **CHALLENGER_PROBE
    )
    d1 = s1.decide(csr, CHALLENGER_F, "spmm")
    runner = s1.build_runner(csr, d1)
    for _ in range(4):
        runner(b)
    s1.cache.flush()
    if d1.choice == "baseline":
        pytest.skip("probe pinned the baseline; nothing to quarantine")
    assert s1.breaker.is_quarantined(d1.choice)
    monkeypatch.delenv("AUTOSAGE_FAULT")
    faultinject.reset()
    s2 = AutoSage(
        cache=ScheduleCache(path=path, shared=True), **CHALLENGER_PROBE
    )
    d2 = s2.decide(csr, 24, "spmm")  # different F: fresh decision
    assert d2.choice != d1.choice


# ------------------------------------------------- replay contract
def test_replay_of_quarantined_pin_raises_replaymiss(tmp_path, monkeypatch):
    path = str(tmp_path / "c.json")
    csr = _challenger_graph()
    sage = AutoSage(cache=ScheduleCache(path=path), **CHALLENGER_PROBE)
    d = sage.decide(csr, CHALLENGER_F, "spmm")
    if d.choice == "baseline":
        pytest.skip("baseline pins are never quarantined")
    # quarantine the pinned choice (e.g. a peer blacklisted it)
    for _ in range(3):
        sage.breaker.record_failure(d.choice, site="run", op="spmm")
    sage.cache.flush()

    replay_sage = AutoSage(cache=ScheduleCache(path=path, replay_only=True))
    with pytest.raises(ReplayMiss, match="quarantined"):
        replay_sage.decide(csr, CHALLENGER_F, "spmm")
    # outside replay the same state re-decides honestly instead
    fresh = AutoSage(cache=ScheduleCache(path=path), **CHALLENGER_PROBE)
    fresh.breaker.maybe_sync()
    d2 = fresh.decide(csr, CHALLENGER_F, "spmm")
    assert d2.choice != d.choice


# ------------------------------------------- batch fault-retire path
def test_batch_reopens_bucket_when_pinned_choice_faults(tmp_path, monkeypatch):
    """Satellite fix: a (possibly transferred) choice that is
    constructible but faults at first run must not serve its fallback
    forever under the pinned name — the breaker signal re-opens the
    bucket and the next pump re-probes honestly."""
    csr = _challenger_graph()
    sage = AutoSage(cache=ScheduleCache(path=None), **CHALLENGER_PROBE)
    bs = BatchScheduler(sage, probe_budget_ms=10_000)
    b = jnp.ones((csr.n_cols, CHALLENGER_F), jnp.float32)
    out, d = bs.spmm(csr, b)
    if d.choice == "baseline":
        pytest.skip("probe pinned the baseline; no run-fault path")
    probes_before = bs.stats()["probes_run"]
    # the pinned choice faults at run past the retry budget (retries=1
    # -> 2 attempts): the chain serves the baseline and the breaker
    # records a run-site failure
    monkeypatch.setenv("AUTOSAGE_FAULT", f"run:{d.choice}:raise:2")
    faultinject.reset()
    runner = sage.build_runner(csr, d)
    runner(b)
    assert sage.breaker.run_failures(d.choice) > 0
    monkeypatch.delenv("AUTOSAGE_FAULT")
    faultinject.reset()
    # next decide sees the run failure, flags the bucket, and the pump
    # re-probes it within the same call
    out2, d2 = bs.spmm(csr, b)
    assert bs.stats()["probes_run"] > probes_before
    assert sage.breaker.run_failures(d.choice) == 0  # signal consumed
    np.testing.assert_allclose(
        np.asarray(out2),
        np.asarray(ref.spmm_ref(jnp.asarray(csr.rowptr),
                                jnp.asarray(csr.colind), None, b)),
        rtol=5e-3, atol=5e-3,
    )


# ------------------------------------------------ fault observability
def test_faults_jsonl_and_metrics_emitted(tmp_path, monkeypatch):
    monkeypatch.setenv("AUTOSAGE_TELEMETRY_DIR", str(tmp_path / "tel"))
    monkeypatch.setenv("AUTOSAGE_FAULT", "run::raise:")
    faultinject.reset()
    from repro.core import obs

    before_faults = obs.REGISTRY.total("autosage_faults_total")
    before_fb = obs.REGISTRY.total("autosage_fallback_total")
    sched = _make_scheduler("autosage", tmp_path)
    rng = np.random.default_rng(0)
    _run_op(sched, _graph(), "spmm", 16, rng)
    fpath = tmp_path / "tel" / "faults.jsonl"
    assert fpath.exists()
    events = [json.loads(x) for x in fpath.read_text().splitlines() if x]
    assert any(e.get("site") == "run" for e in events)
    assert obs.REGISTRY.total("autosage_faults_total", site="run") > 0
    assert obs.REGISTRY.total("autosage_faults_total") > before_faults
    assert obs.REGISTRY.total("autosage_fallback_total") > before_fb


def test_explain_shows_quarantine_provenance(tmp_path, monkeypatch):
    from repro import obs_cli

    monkeypatch.setenv("AUTOSAGE_DEVICE_SIG_OVERRIDE", "explain-dev")
    path = str(tmp_path / "c.json")
    cache = ScheduleCache(path=path)
    key = ScheduleCache.key("explain-dev", "abc123", 16, "spmm", 0.95)
    cache.put(key, {"choice": "row_ell", "probed": True,
                    "stats": {"probed_at": 5.0}})
    br = resilience.CircuitBreaker(cache=cache, threshold=1)
    br.record_failure("row_ell", site="run", op="spmm")
    cache.flush()
    text = obs_cli.explain(key, cache_path=path)
    assert "quarantine records" in text
    assert "row_ell: active" in text
    assert "ReplayMiss" in text
    qkey = ScheduleCache.quarantine_key("explain-dev", "row_ell")
    qtext = obs_cli.explain(qkey, cache_path=path)
    assert "active" in qtext and "row_ell" in qtext


# ------------------------------------------------- lock backoff knobs
def test_lock_backoff_grows_and_caps(monkeypatch):
    from repro.core import cache as cache_mod

    monkeypatch.setenv("AUTOSAGE_LOCK_BACKOFF_BASE_MS", "2")
    monkeypatch.setenv("AUTOSAGE_LOCK_BACKOFF_MAX_MS", "16")
    monkeypatch.setenv("AUTOSAGE_LOCK_BACKOFF_JITTER", "0")
    waits = [cache_mod._lock_backoff_s(a) for a in range(8)]
    assert waits[:4] == [0.002, 0.004, 0.008, 0.016]
    assert all(w == 0.016 for w in waits[3:])  # capped
    monkeypatch.setenv("AUTOSAGE_LOCK_BACKOFF_JITTER", "0.5")
    jittered = [cache_mod._lock_backoff_s(0) for _ in range(50)]
    assert all(0.002 <= w <= 0.003 + 1e-12 for w in jittered)
    assert len(set(jittered)) > 1


def test_lock_contention_counts_metric(tmp_path):
    from repro.core import obs

    path = str(tmp_path / "c.json")
    a = ScheduleCache(path=path, shared=True)
    a.put("k", {"choice": "x", "stats": {"probed_at": 1.0}})
    a.flush()
    series = obs.REGISTRY.hist_series("autosage_cache_lock_wait_ms")
    outcomes = {dict(lk).get("outcome") for lk in series}
    assert outcomes & {"immediate", "waited"}


# ------------------------------------------- kill -9 mid-probe worker
_KILL_SCRIPT = """
import os, sys
sys.path.insert(0, {src!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from repro.core import AutoSage, BatchScheduler, ScheduleCache
from repro.sparse import fixed_degree, hub_skew
import jax.numpy as jnp
csr = hub_skew(600, 4, 0.05, 24, seed=0).dedup_edges()
sage = AutoSage(cache=ScheduleCache(path=sys.argv[1], shared=True),
                probe_iters=50, probe_cap_ms=60_000, probe_frac=1.0)
print("probing", flush=True)
sage.decide(csr, 64, "spmm")
sage.cache.flush()
print("done", flush=True)
"""


def test_kill_mid_probe_leaves_shared_cache_loadable(tmp_path):
    """SIGKILL a fleet worker while it probes: the shared cache file (if
    any) must stay valid JSON, and no .lock / tmp debris may survive to
    wedge the next worker."""
    path = str(tmp_path / "shared.json")
    script = _KILL_SCRIPT.format(
        src=str(Path(__file__).resolve().parent.parent / "src")
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("AUTOSAGE_FAULT", None)
    env.pop("AUTOSAGE_REPLAY_ONLY", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", script, path], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    assert proc.stdout.readline().strip() == "probing"
    time.sleep(0.3)  # let it get into the probe loop
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    leftovers = [
        p.name for p in tmp_path.iterdir() if p.name != "shared.json"
    ]
    assert not any(n.endswith(".lock") for n in leftovers), leftovers
    if os.path.exists(path):
        assert isinstance(json.load(open(path)), dict)
    # the next worker proceeds unharmed on the same cache
    sage = AutoSage(
        cache=ScheduleCache(path=path, shared=True), probe_iters=1,
        probe_cap_ms=25, probe_frac=0.25,
    )
    d = sage.decide(_graph(), 16, "spmm")
    assert d.choice
    sage.cache.flush()
    assert isinstance(json.load(open(path)), dict)
