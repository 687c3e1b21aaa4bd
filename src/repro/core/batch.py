"""Batched multi-graph scheduling: bucketed decisions under one probe budget.

`AutoSage.decide` is priced for one graph at a time: feature extraction
is cheap, but every cache miss pays an induced-subgraph probe. The
workload the paper targets — minibatched GNN training — serves thousands
of induced subgraphs per epoch, each slightly different, so per-graph
probing either dominates step time or (with per-graph exact cache keys)
never warms the cache at all. Dai et al. ("Heuristic Adaptability to
Input Dynamics for SpMM on GPUs") and ParamSpMM both show the winning
mapping is stable across coarse feature regimes; `BatchScheduler`
exploits exactly that:

  1. every incoming graph's `InputFeatures` canonicalize into a coarse
     `ScheduleBucket` (log-binned n_rows/nnz, quantized skew/density,
     exact F/op/device — core/features.py), so near-identical sampled
     subgraphs share one decision;
  2. probing is amortized under a shared per-stream probe-time budget:
     unprobed buckets run the vendor baseline provisionally (guardrail-
     safe — the provisional choice is exactly the guardrail fallback),
     pending buckets are prioritized by traffic-weighted estimated gain
     (hits x roofline headroom), and each bucket's decision upgrades in
     place once its probe completes;
  3. every decide is recorded in a stream trace, and `finalize()` pins
     all bucket decisions into the cache (schema v4 bucket keys,
     core/cache.py) so an entire epoch of bucketed decisions replays
     deterministically under AUTOSAGE_REPLAY_ONLY=1;
  4. a pinned decision is NOT trusted forever: `observe(bucket, ms)`
     feeds each bucket a windowed EWMA of the runtimes the trainer
     actually saw, and the **drift detector** re-enqueues a bucket on
     the probe budget (with decayed priority) when that EWMA departs
     from the probe-time estimate by AUTOSAGE_DRIFT_RATIO, or when the
     incoming graphs' `padding_waste` crosses a waste-bin boundary away
     from the probe representative's — the exact stale-decision failure
     mode Dai et al. ("Heuristic Adaptability to Input Dynamics for
     SpMM on GPUs") show rule-based choices suffer. The re-probe runs
     on the *newest* graph seen in the bucket (the drifted regime's
     representative, not the stale one), and fused-vs-composed flips of
     attention pipelines are tracked per regime in the stream telemetry.

With a shared cache (AUTOSAGE_CACHE_SHARED=1), bucket entries carry the
running stats across processes: a fleet of trainers opens buckets warm
from peers' probes (probes-avoided-by-sharing), merges traffic counts on
flush, and the freshest re-probe of a drifted bucket wins fleet-wide.

On a HETEROGENEOUS fleet the cache alone shares nothing (keys pin
device_sig), so a third tier sits between warm-hit and cold-probe:
**decision transfer** (core/transfer.py). A regime probed on another
device class is re-ranked under the local roofline, calibrated by the
peer's observed-vs-estimated residuals; confident transfers are final
with zero probes, the rest serve the transferred choice while ONE
confirm probe (charged to the normal budget) confirms or flips it.
Transferred decisions pin into the cache with provenance
(source_device, verdict, rank agreement) and replay deterministically
under AUTOSAGE_REPLAY_ONLY=1 like any other pinned decision.

Entry points mirror the per-graph scheduler (`decide` / `build_runner` /
`spmm` / `sddmm` / `attention`), so model code written against `AutoSage`
(e.g. models/gnn.py) takes a `BatchScheduler` unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Union

from repro.core import obs
from repro.core import registry, resilience, telemetry
from repro.core import transfer as transfer_mod
from repro.core.cache import ReplayMiss, ScheduleCache
from repro.core.features import (
    InputFeatures,
    ScheduleBucket,
    device_sig,
    waste_bin,
)
from repro.core.scheduler import AutoSage, Decision
from repro.sparse.csr import CSR

DEFAULT_PROBE_BUDGET_MS = float(os.environ.get("AUTOSAGE_BATCH_BUDGET_MS", "2000"))
# observed-runtime EWMA: exact running mean for the first WINDOW
# observations (permutation-invariant startup), then exponential with
# beta = 1/WINDOW — recent regime shifts dominate, old regimes age out
DEFAULT_EWMA_WINDOW = int(os.environ.get("AUTOSAGE_EWMA_WINDOW", "16"))
# drift fires when ewma/probe_est leaves [1/ratio, ratio]
DEFAULT_DRIFT_RATIO = float(os.environ.get("AUTOSAGE_DRIFT_RATIO", "1.5"))
# ... but only after this many observations since the last (re-)probe
DEFAULT_DRIFT_MIN_OBS = int(os.environ.get("AUTOSAGE_DRIFT_MIN_OBS", "5"))
# each re-probe decays the bucket's pump priority by this factor, so a
# flapping bucket cannot starve never-probed buckets of the budget
DEFAULT_DRIFT_DECAY = float(os.environ.get("AUTOSAGE_DRIFT_DECAY", "0.5"))
# padding-waste drift: |live waste - waste_at_probe| >= this flags the
# bucket. One waste bin spans up to 0.5 of raw waste (bins 0.5/0.75),
# and dense-W padded work scales like 1/(1-waste) — a 0.75 -> 0.95 move
# inside bin 2 is a 5x work change the bin alone can never see
DEFAULT_DRIFT_WASTE_DELTA = float(
    os.environ.get("AUTOSAGE_DRIFT_WASTE_DELTA", "0.25")
)


@dataclasses.dataclass
class _BucketState:
    """Everything the stream knows about one schedule bucket."""

    bucket: ScheduleBucket
    key: str  # bucket-level cache key
    rep_csr: CSR  # first graph seen: the probe representative
    rep_feat: InputFeatures
    base: registry.Variant
    by_name: Dict[str, registry.Variant]
    estimates_ms: Dict[str, float]
    est_gain_ms: float  # roofline headroom: baseline est - best challenger est
    has_challengers: bool
    hits: int = 0
    probed: bool = False  # a final (probed or cached) decision exists
    probing: bool = False  # claimed by an in-flight (background) probe
    decision: Optional[Decision] = None  # None => provisional baseline
    provisional: Optional[Decision] = None
    probe_charge_ms: float = 0.0
    # --- online statistics + drift state (schema v4) ---
    obs: int = 0  # observations since the last (re-)probe
    ewma_ms: Optional[float] = None  # windowed EWMA of observed runtimes
    probe_est_ms: Optional[float] = None  # probe-measured ms of the choice
    waste_at_probe: Optional[float] = None  # rep padding_waste at probe time
    # the runtime-drift reference: the probe-time estimate *calibrated*
    # to steady-state wall clock by the first drift_min_obs observations
    # after the (re-)probe (raw slope-probe ms excludes per-call dispatch
    # overhead, so comparing it to wall times directly misfires). A
    # warm-opened bucket inherits the probing peer's EWMA instead — so a
    # trainer that never probed still notices the pinned choice going
    # stale under its own traffic.
    ref_ms: Optional[float] = None
    _first_sum: float = 0.0
    reprobes: int = 0  # completed drift re-probes
    drift_flagged: bool = False  # pending on the budget for a re-probe
    drift_reason: str = ""
    hits_flushed: int = 0  # hits already pushed into the cache
    # newest graph seen: the re-probe representative after a drift flag
    # (probing the stale rep would just re-measure the old regime)
    last_csr: Optional[CSR] = None
    last_feat: Optional[InputFeatures] = None
    # --- cross-device transfer state (core/transfer.py) ---
    transferred: bool = False  # opened from a peer device's probed ranking
    transfer_verdict: str = ""  # "confirmed" | "pending" | "flipped"
    transfer_choice: Optional[str] = None  # the re-ranked winner served
    transfer_info: Optional[Dict[str, Any]] = None  # provenance record

    def current(self) -> Decision:
        return self.decision if self.decision is not None else self.provisional

    def priority(self) -> tuple:
        """Traffic-weighted estimated gain; positive-headroom buckets
        always outrank zero-headroom ones, ties break on traffic. Every
        completed re-probe decays the weight, so drift-flapping buckets
        yield the budget to fresh ones."""
        decay = DEFAULT_DRIFT_DECAY ** self.reprobes
        gain = max(self.est_gain_ms, 0.0)
        if self.drift_flagged and gain == 0.0:
            # a drifted bucket re-enters the queue even when its original
            # estimate saw no headroom: the observed runtime says the
            # estimate is stale
            gain = 1e-6
        return (gain > 0.0, self.hits * gain * decay, self.hits * decay)


def _attention_family(choice: Optional[str]) -> str:
    """Coarse pipeline family of an attention choice, for flip telemetry:
    the interesting regime signal is fused <-> composed, not which exact
    layout pair won."""
    if choice is None:
        return "none"
    if choice == "baseline":
        return "baseline"
    if "attention" in choice:  # fused_attention_pallas / ragged_attention_*
        return "fused"
    return "composed"  # pipe[sddmm=...,spmm=...]


class BatchScheduler:
    """Serves a stream of graphs through bucketed, budgeted decisions.

    Wraps (and shares the cache/hardware spec of) an `AutoSage`. Use as a
    context manager — or call `finalize()` — at the end of a stream/epoch
    so every bucket decision (including still-provisional baselines) is
    pinned into the cache for deterministic replay.
    """

    def __init__(
        self,
        sage: Optional[AutoSage] = None,
        probe_budget_ms: float = DEFAULT_PROBE_BUDGET_MS,
        max_probes_per_decide: int = 1,
        auto_pump: bool = True,
        seed: int = 0,
    ):
        self.sage = sage if sage is not None else AutoSage()
        self.cache: ScheduleCache = self.sage.cache
        self.probe_budget_ms = probe_budget_ms
        self.max_probes_per_decide = max_probes_per_decide
        self.auto_pump = auto_pump
        self.seed = seed
        self.ewma_window = DEFAULT_EWMA_WINDOW
        self.drift_ratio = DEFAULT_DRIFT_RATIO
        self.drift_min_obs = DEFAULT_DRIFT_MIN_OBS
        self.drift_waste_delta = DEFAULT_DRIFT_WASTE_DELTA
        self._device = device_sig()
        # Serving-tier concurrency (launch/serve.py): request threads
        # decide under this lock while a background probe worker pumps.
        # The lock covers only O(feature/estimate) state transitions —
        # pump() releases it for the actual probe measurement, so a slow
        # (or fault-injected hung) probe can never stall a decide.
        self._lock = threading.RLock()
        # upgrade notification: called (outside the lock) with the probe
        # event dict every time a bucket's decision upgrades in place —
        # the serving tier counts/announces background upgrades with it.
        self.on_upgrade: Optional[Callable[[Dict[str, Any]], None]] = None
        # per-decide results (last_bucket / last_source /
        # last_inline_probes) are THREAD-LOCAL: N serving threads decide
        # concurrently, and each must read back its own request's bucket
        # and tier, not a neighbour's.
        self._decide_tls = threading.local()
        self._buckets: Dict[str, _BucketState] = {}
        # observe() routing: keyed by the FULL bucket (sig() alone omits
        # op/F/device, so same-shape buckets for different ops would
        # swallow each other's runtime observations)
        self._by_bucket: Dict[ScheduleBucket, _BucketState] = {}
        self.probe_spent_ms = 0.0
        self.trace: List[Dict[str, Any]] = []
        # One accounting path (core/obs.py): every stream counter is a
        # ScopedCounter — the instance-local .value backs stats() exactly
        # as the old plain ints did, and each inc also lands on the named
        # process-wide registry series, so Prometheus snapshots aggregate
        # across scheduler instances without a second bookkeeping path.
        # Bucket probe passes get their own metric name: the inner
        # AutoSage.decide already counts real probe passes under
        # autosage_probe_passes_total, and a bucket pass can be satisfied
        # probe-free by an exact-key hit.
        self._decides = obs.ScopedCounter("autosage_decides_total")
        self._probe_passes = obs.ScopedCounter(
            "autosage_bucket_probe_passes_total"
        )
        self._decide_wall_ms = 0.0
        # buckets opened final from the (shared) cache
        self._warm_opens = obs.ScopedCounter(
            "autosage_bucket_warm_opens_total"
        )
        self._drift_flags = obs.ScopedCounter("autosage_drift_events_total")
        self._drift_reprobes = obs.ScopedCounter("autosage_drift_events_total")
        self._drift_flips = obs.ScopedCounter("autosage_drift_events_total")
        # cross-device transfer accounting (core/transfer.py)
        self._transfers = obs.ScopedCounter("autosage_transfers_total")
        self._transfers_confirmed = obs.ScopedCounter(
            "autosage_transfer_verdict_total"
        )
        self._transfers_flipped = obs.ScopedCounter(
            "autosage_transfer_verdict_total"
        )
        self._transfer_probe_free = obs.ScopedCounter(
            "autosage_transfer_probe_free_total"
        )

    # per-decide views, thread-local to the deciding thread
    @property
    def last_bucket(self) -> Optional[ScheduleBucket]:
        """Zero-cost handle for "observe the decide I just made": the
        features were already extracted, don't pay them again."""
        return getattr(self._decide_tls, "bucket", None)

    @property
    def last_source(self) -> Optional[str]:
        """Tier label the calling thread's last decide() served from:
        "bucket-cache" / "transfer" / "transfer-pending" / "probe" /
        "drift-pending" / "provisional" / "gated" (the bucket's pinned
        variant exceeds this graph's on-chip gates; the baseline served)."""
        return getattr(self._decide_tls, "source", None)

    @property
    def last_inline_probes(self) -> int:
        """Bucket probes the calling thread's last decide() ran inline
        (always 0 with auto_pump=False — the serving tier's probe-stall
        detector reads exactly this)."""
        return getattr(self._decide_tls, "inline_probes", 0)

    # counter views: the names tests/benchmarks read (`bs.transfers`,
    # `bs.drift_flags`, ...) stay plain ints backed by the registry path
    @property
    def drift_flags(self) -> int:
        return self._drift_flags.value

    @property
    def drift_reprobes(self) -> int:
        return self._drift_reprobes.value

    @property
    def drift_flips(self) -> int:
        return self._drift_flips.value

    @property
    def transfers(self) -> int:
        return self._transfers.value

    @property
    def transfers_confirmed(self) -> int:
        return self._transfers_confirmed.value

    @property
    def transfers_flipped(self) -> int:
        return self._transfers_flipped.value

    @property
    def transfer_probe_free(self) -> int:
        return self._transfer_probe_free.value

    # ---------------------------------------------------------- decide
    def decide(self, csr: CSR, f: int, op: str, heads: int = 1) -> Decision:
        """Bucketed decide: O(feature extraction) on the hot path; any
        probing is pulled from the shared budget (at most
        `max_probes_per_decide` bucket probes per call). Buckets hold
        single-head ops only: a multi-head op is decided per graph, by
        `AutoSage`."""
        if heads != 1:
            raise NotImplementedError(
                f"{op} with {heads} heads: buckets hold single-head ops; "
                "decide it with AutoSage")
        t0 = time.perf_counter()
        with obs.span("decide", op=op, f=f, scheduler="batch"):
            with obs.span("features", op=op):
                feat = InputFeatures.from_csr(csr, f, op)
            bucket = ScheduleBucket.from_features(feat, self._device)
            key = ScheduleCache.bucket_key(
                self._device, bucket.sig(), f, op, self.sage.alpha
            )
            with self._lock:
                st = self._buckets.get(key)
                if st is None:
                    if (
                        self.cache.shared and not self.cache.replay_only
                        and not self.cache.contains(key)
                    ):
                        # a fleet peer may have probed this bucket since we
                        # loaded: one cheap mtime stat before paying a probe.
                        # Never in replay mode — replay serves the file AS
                        # LOADED or two replays of one stream could differ
                        self.cache.maybe_reload()
                    st = self._open_bucket(bucket, key, csr, feat)
                    self._buckets[key] = st
                    self._by_bucket[bucket] = st
                st.hits += 1
                st.last_csr, st.last_feat = csr, feat
                self._decide_tls.bucket = bucket
                self._check_waste_drift(st, feat)
                self._check_fault_retire(st)
            # probing happens OUTSIDE the state lock: the trainer path
            # (auto_pump) blocks here by design, while the serving tier
            # sets auto_pump=False and runs pump() on a background
            # probe-worker thread instead — a request never waits on one
            inline_probes = 0
            if self.auto_pump and not self.cache.replay_only:
                inline_probes = self.pump(self.max_probes_per_decide)
            self._decide_tls.inline_probes = inline_probes
            with self._lock:
                d = st.current()
                # buckets bin shapes coarsely: the variant pinned by the
                # graph that opened the bucket may exceed the on-chip gates
                # (SMEM, VMEM, HBM) for this one. It gets the baseline.
                gated = (
                    d.variant is not None and not d.variant.is_baseline
                    and not d.variant.applicable(feat, self.sage.hw)
                )
                if gated:
                    d = Decision(
                        op=feat.op, choice="baseline", variant=st.base,
                        guardrail=None, from_cache=False, probe_ms={},
                        probe_overhead_ms=0.0, probe_iter_ms=0.0,
                        estimates_ms={},
                    )
                    source = "gated"
                elif st.probed and st.decision is not None and st.decision.from_cache:
                    source = "bucket-cache"
                elif (
                    st.probed and st.decision is not None
                    and st.decision.transfer is not None
                    and not st.decision.probe_ms
                ):
                    # confident cross-device transfer: final, no local probe
                    source = "transfer"
                elif st.probed:
                    source = "probe"
                elif st.transferred and st.transfer_verdict == "pending":
                    # transferred choice serving while its confirm probe waits
                    # on the budget
                    source = "transfer-pending"
                elif st.decision is not None:
                    # flagged bucket awaiting its re-probe: still serves the
                    # last pinned decision, not the provisional baseline
                    source = "drift-pending"
                else:
                    source = "provisional"
        self._decide_tls.source = source
        wall_ms = (time.perf_counter() - t0) * 1e3
        self._decide_wall_ms += wall_ms
        obs.REGISTRY.observe(
            "autosage_decide_ms", wall_ms, op=op, scheduler="batch"
        )
        self._record(st, d, source)
        return d

    def _open_bucket(
        self, bucket: ScheduleBucket, key: str, csr: CSR, feat: InputFeatures
    ) -> _BucketState:
        cands = registry.candidates(feat, self.sage.hw)
        base = registry.baseline(feat, self.sage.hw)
        by_name = {v.full_name(): v for v in cands}
        by_name["baseline"] = base

        # replay / warm-start: a pinned bucket decision ends the story.
        # In replay-only mode a miss raises ReplayMiss — the contract.
        cached = self.cache.get(key)
        # A quarantined pinned choice (circuit breaker, core/resilience.py)
        # is a third unusable shape: serving it would re-run a known-
        # faulting candidate bucket-wide. Replay raises instead of
        # silently substituting — the replay contract.
        if (
            resilience.enabled() and isinstance(cached, dict)
            and cached.get("choice") not in (None, "baseline")
        ):
            self.sage.breaker.maybe_sync()
            if self.sage.breaker.is_quarantined(cached["choice"]):
                if self.cache.replay_only:
                    raise ReplayMiss(
                        f"pinned choice {cached['choice']!r} for {key} is "
                        "quarantined (AUTOSAGE_REPLAY_ONLY=1 forbids "
                        "substituting)"
                    )
                cached = None  # fall through to an honest local re-probe
        # Two cached shapes must NOT be adopted as final outside replay:
        #  - a peer's never-probed provisional baseline ("probed": False,
        #    pinned by its finalize) — a worker WITH budget treats it as
        #    pending and probes, and its probed_at > 0 wins the merge.
        #    Exception: a transferred entry whose verdict is "confirmed"
        #    was accepted by the transfer policy (zero-probe by design)
        #    and is served as final; a transfer still "pending" its
        #    confirm probe is re-opened pending instead;
        #  - a choice this process cannot construct (peer probed under
        #    AUTOSAGE_PROBE_PALLAS or different gates) — silently running
        #    baseline while reporting the peer's choice would corrupt
        #    trace/telemetry AND calibrate drift against the wrong
        #    variant's reference. Probing fresh re-pins it honestly.
        # Replay mode still serves both as final (replay is immutable;
        # an unconstructible choice degrades to the baseline variant).
        transfer_confirmed = (
            isinstance(cached, dict)
            and (cached.get("transfer") or {}).get("verdict") == "confirmed"
        )
        cached_unusable = (
            cached is not None and not self.cache.replay_only
            and (
                (cached.get("probed") is False and not transfer_confirmed)
                or cached["choice"] not in by_name
            )
        )
        if cached is not None and not cached_unusable:
            choice = cached["choice"]
            decision = Decision(
                op=feat.op, choice=choice, variant=by_name.get(choice, base),
                guardrail=None, from_cache=True, probe_ms={},
                probe_overhead_ms=0.0, probe_iter_ms=0.0, estimates_ms={},
            )
            self._warm_opens.inc(op=feat.op)
            stats = cached.get("stats") or {}
            return _BucketState(
                bucket=bucket, key=key, rep_csr=csr, rep_feat=feat, base=base,
                by_name=by_name, estimates_ms={}, est_gain_ms=0.0,
                has_challengers=False, probed=True, decision=decision,
                # drift references travel with the shared entry: a trainer
                # that never probed this bucket itself can still detect
                # that the pinned choice went stale under ITS traffic
                probe_est_ms=stats.get("probe_est_ms"),
                waste_at_probe=stats.get("waste_at_probe"),
                ref_ms=stats.get("ewma_ms"),
                reprobes=max(int(stats.get("probes") or 1) - 1, 0),
            )

        estimates, short = self.sage.shortlist(feat, cands)
        gain = 0.0
        if short:
            t_base_est = estimates.get(base.full_name(), float("inf"))
            t_best_est = min(estimates[v.full_name()] for v in short)
            gain = t_base_est - t_best_est
        provisional = Decision(
            op=feat.op, choice="baseline", variant=base, guardrail=None,
            from_cache=False, probe_ms={}, probe_overhead_ms=0.0,
            probe_iter_ms=0.0, estimates_ms=estimates,
        )
        st = _BucketState(
            bucket=bucket, key=key, rep_csr=csr, rep_feat=feat, base=base,
            by_name=by_name, estimates_ms=estimates, est_gain_ms=gain,
            has_challengers=bool(short), provisional=provisional,
        )
        if not short:
            # no applicable challengers: baseline is final, never probe
            st.probed = True
            st.decision = provisional
            return st

        # --- transfer tier: between warm-hit and cold-probe ------------
        # No local entry, but a peer DEVICE CLASS may have probed this
        # regime: re-rank its probed candidate set under the local
        # roofline (calibrated by the peer's observed-vs-estimated
        # residuals) and serve the winner instead of the blind baseline.
        # Confident transfers are final (zero probes); the rest keep
        # serving the transferred choice while one confirm probe waits
        # on the normal budget.
        if transfer_mod.enabled() and not self.cache.replay_only:
            plan = transfer_mod.best_plan(
                self.cache.peer_entries(key), feat, self.sage.hw, by_name,
                base, self.sage.alpha,
                excluded=self.sage.breaker.excluded_names(),
            )
            if plan is not None:
                verdict = "confirmed" if plan.confident else "pending"
                d = Decision(
                    op=feat.op, choice=plan.choice,
                    variant=by_name.get(plan.choice, base),
                    guardrail=plan.guardrail, from_cache=False, probe_ms={},
                    probe_overhead_ms=0.0, probe_iter_ms=0.0,
                    estimates_ms=estimates,
                    transfer=plan.provenance(verdict),
                )
                st.decision = d
                st.transferred = True
                st.transfer_verdict = verdict
                st.transfer_choice = plan.choice
                st.transfer_info = d.transfer
                # the padding regime the transfer was accepted under: the
                # waste-drift detector fires off it like off a probe's
                st.waste_at_probe = feat.padding_waste
                self._transfers.inc(op=feat.op)
                if plan.confident:
                    st.probed = True  # final: the confirm probe is waived
                    self._transfers_confirmed.inc(verdict="confirmed")
                    self._transfer_probe_free.inc(op=feat.op)
                else:
                    obs.REGISTRY.inc(
                        "autosage_transfer_verdict_total", verdict="pending"
                    )
                telemetry.emit_batch_event(
                    {
                        "event": "transfer",
                        "bucket": bucket.sig(),
                        "op": feat.op,
                        "f": feat.f,
                        "choice": plan.choice,
                        "source_device": plan.source_device,
                        "verdict": verdict,
                        "rank_agreement": plan.rank_agreement,
                        "confident": plan.confident,
                        "peer_choice": plan.peer_choice,
                    }
                )
                telemetry.emit_decide_event(d, feat, kind="transfer")
        return st

    # ----------------------------------------------------------- probes
    def pending(self) -> List[_BucketState]:
        with self._lock:  # decide() may be inserting concurrently
            return [s for s in self._buckets.values() if not s.probed]

    def pump(self, max_probes: Optional[int] = None) -> int:
        """Probe the highest-priority pending buckets while budget
        remains; returns how many bucket probes ran. Decisions upgrade
        in place: later decides on a pumped bucket see its probed
        choice.

        Thread-safe: bucket selection happens under the state lock and
        claims the bucket (``probing``) so concurrent pumpers never
        double-probe, but the probe itself runs with the lock RELEASED —
        concurrent decides keep serving the bucket's current (provisional
        or stale-pinned) decision until the upgrade commits."""
        if self.cache.replay_only:
            return 0
        ran = 0
        while max_probes is None or ran < max_probes:
            with self._lock:
                if self.probe_spent_ms >= self.probe_budget_ms:
                    break
                pend = [
                    s for s in self._buckets.values()
                    if not s.probed and not s.probing
                ]
                if not pend:
                    break
                st = max(pend, key=_BucketState.priority)
                st.probing = True
            try:
                self._probe_bucket(st)
            finally:
                st.probing = False
            ran += 1
        return ran

    def _probe_bucket(self, st: _BucketState) -> None:
        """Run the full per-graph decision procedure on the bucket's
        representative graph and pin the outcome for the whole bucket.
        On a drift re-probe the representative is refreshed to the newest
        graph seen (the drifted regime), the candidate pool and estimates
        are re-derived from its features, and an old->new choice flip is
        recorded."""
        was_drift = st.drift_flagged
        old_choice = st.decision.choice if st.decision is not None else None
        if was_drift and st.last_csr is not None:
            st.rep_csr, st.rep_feat = st.last_csr, st.last_feat
            cands = registry.candidates(st.rep_feat, self.sage.hw)
            st.base = registry.baseline(st.rep_feat, self.sage.hw)
            st.by_name = {v.full_name(): v for v in cands}
            st.by_name["baseline"] = st.base
            st.estimates_ms, short = self.sage.shortlist(st.rep_feat, cands)
            st.has_challengers = bool(short)
        if was_drift:
            # count the re-probe BEFORE deriving the seed, so even the
            # first re-probe measures under fresh probe RNG (reprobes is
            # 0 until here — seed would repeat the original probe's)
            st.reprobes += 1
            self._drift_reprobes.inc(event="reprobe")
        was_pending_transfer = (
            st.transferred and st.transfer_verdict == "pending"
        )
        seed = self._bucket_seed(st) + st.reprobes
        reprobe_span = (
            obs.span(
                "drift.reprobe", bucket=st.bucket.sig(), op=st.rep_feat.op,
                reason=st.drift_reason,
            )
            if was_drift
            else contextlib.nullcontext()
        )
        # a faulted flush (lock contention, injected chaos) must not
        # lose the probed decision: cache_guard swallows the write
        # failure, the entry stays dirty for the next flush, and the
        # bucket still serves d
        flush_guard = (
            resilience.cache_guard(op=st.rep_feat.op)
            if resilience.enabled()
            else contextlib.nullcontext()
        )
        d = st.current()
        # defer flushing inside: exact + bucket puts -> one write
        with reprobe_span, flush_guard, self.cache:
            # allow_transfer=False: this IS the measurement that confirms
            # (or flips) a transferred choice and re-pins drifted buckets
            # — an estimate-space shortcut here would be circular
            d = self.sage.decide(
                st.rep_csr, st.rep_feat.f, st.rep_feat.op, seed=seed,
                allow_transfer=False,
            )
            if was_pending_transfer:
                st.transfer_verdict = (
                    "confirmed" if d.choice == st.transfer_choice else "flipped"
                )
                if st.transfer_verdict == "confirmed":
                    self._transfers_confirmed.inc(verdict="confirmed")
                else:
                    self._transfers_flipped.inc(verdict="flipped")
                if st.transfer_info is not None:
                    st.transfer_info = dict(
                        st.transfer_info, verdict=st.transfer_verdict
                    )
                    d.transfer = st.transfer_info
            with self._lock:
                st.decision = d
                st.probe_est_ms = d.probe_ms.get(d.choice)
                st.waste_at_probe = st.rep_feat.padding_waste
                # the new probe resets the regime: statistics restart, and
                # the drift reference re-calibrates from upcoming traffic
                st.obs, st.ewma_ms = 0, None
                st.ref_ms, st._first_sum = None, 0.0
                if was_drift:
                    st.drift_flagged = False
                # the decision commits BEFORE probed flips: a concurrent
                # decide that observes probed=True must also observe the
                # upgraded decision (the in-place upgrade the serving
                # tier's background prober relies on)
                st.probed = True
            if resilience.enabled() and d.choice != "baseline":
                # the re-probe answered the fault signal: clear the
                # breaker's consecutive/run-failure counts for the
                # re-pinned choice so _check_fault_retire does not
                # re-flag off a stale count (they re-accrue on the next
                # real fault)
                self.sage.breaker.record_success(d.choice)
            self.cache.put(st.key, self._bucket_entry(st, d))
            self._push_stats(st)
        with self._lock:
            st.probe_charge_ms = d.probe_overhead_ms  # 0 on an exact-key hit
            self.probe_spent_ms += st.probe_charge_ms
        self._probe_passes.inc(op=st.rep_feat.op)
        flipped = was_drift and old_choice is not None and d.choice != old_choice
        if flipped:
            self._drift_flips.inc(event="flip")
        event = {
            "event": "drift_reprobe" if was_drift else "bucket_probe",
            "bucket": st.bucket.sig(),
            "op": st.rep_feat.op,
            "f": st.rep_feat.f,
            "choice": d.choice,
            "probe_overhead_ms": d.probe_overhead_ms,
            "budget_spent_ms": self.probe_spent_ms,
            "budget_ms": self.probe_budget_ms,
        }
        if was_pending_transfer:
            event.update(
                transfer_verdict=st.transfer_verdict,
                transfer_choice=st.transfer_choice,
                source_device=(st.transfer_info or {}).get("source_device"),
            )
        if was_drift:
            event.update(
                old_choice=old_choice, flipped=flipped, reason=st.drift_reason,
                reprobes=st.reprobes,
            )
            if st.rep_feat.op == "attention":
                # fused-vs-composed flips are the regime signal the
                # pipeline scheduler cares about (§8.7): label both sides
                event.update(
                    old_family=_attention_family(old_choice),
                    new_family=_attention_family(d.choice),
                )
        telemetry.emit_batch_event(event)
        if self.on_upgrade is not None:
            # notify outside every lock: the callback may emit telemetry
            # or bump metrics, and must never be able to deadlock a decide
            try:
                self.on_upgrade(dict(event))
            except Exception:
                obs.REGISTRY.inc("autosage_serve_upgrade_cb_errors_total")

    # ------------------------------------------------- online statistics
    def bucket_of(self, csr: CSR, f: int, op: str) -> ScheduleBucket:
        """The schedule bucket this graph canonicalizes into (the handle
        `observe` takes)."""
        return ScheduleBucket.from_features(
            InputFeatures.from_csr(csr, f, op), self._device
        )

    def observe(
        self, bucket: Union[ScheduleBucket, str], runtime_ms: float
    ) -> None:
        """Feed one observed runtime (ms) of the bucket's pinned decision
        back into its statistics. Takes a `ScheduleBucket` (from
        `bucket_of`, or `last_bucket` right after a decide); a sig()
        string is accepted only while it is unambiguous — sigs omit
        op/F/device, so once two ops share a shape regime a string would
        mis-attribute the runtime, and is ignored instead. Unknown
        buckets are ignored too (a trainer may observe work scheduled
        before a restart).

        The EWMA is windowed: for the first `ewma_window` observations it
        is the exact arithmetic mean (so early drift verdicts do not
        depend on arrival order), after which it decays exponentially
        with beta = 1/window."""
        with self._lock:
            if isinstance(bucket, ScheduleBucket):
                st = self._by_bucket.get(bucket)
            else:
                matches = [
                    s for b, s in self._by_bucket.items() if b.sig() == bucket
                ]
                st = matches[0] if len(matches) == 1 else None
        if st is None or runtime_ms < 0:
            return
        st.obs += 1
        beta = 1.0 / min(st.obs, self.ewma_window)
        st.ewma_ms = (
            runtime_ms if st.ewma_ms is None
            else st.ewma_ms + beta * (runtime_ms - st.ewma_ms)
        )
        # estimate-accuracy scorecard: every observed runtime of a probed
        # decision scores its roofline estimate against live ground truth
        # (warm-opened buckets carry no estimates and feed nothing)
        d = st.decision
        if st.probed and d is not None and st.estimates_ms:
            est_name = (
                st.base.full_name() if d.choice == "baseline" else d.choice
            )
            obs.record_estimate(
                st.bucket.op, d.choice, st.estimates_ms.get(est_name),
                runtime_ms, source="observe",
            )
        if st.ref_ms is None:
            # calibrate the drift reference from the first min_obs
            # observations delivered by the freshly probed decision
            st._first_sum += runtime_ms
            if st.obs >= self.drift_min_obs:
                st.ref_ms = st._first_sum / st.obs
        self._check_runtime_drift(st)

    def _check_runtime_drift(self, st: _BucketState) -> None:
        """Flag the bucket when the observed-runtime EWMA departs from
        the calibrated probe-time reference by more than drift_ratio
        (either direction: slower means the pinned choice is losing,
        faster means a cheaper regime where a different choice may now
        win)."""
        if (
            st.drift_flagged or not st.probed or st.decision is None
            or st.ref_ms is None or st.ewma_ms is None
            or st.obs < self.drift_min_obs
        ):
            return
        ratio = st.ewma_ms / max(st.ref_ms, 1e-9)
        if ratio > self.drift_ratio or ratio < 1.0 / self.drift_ratio:
            self._flag_drift(
                st, f"runtime_ewma {st.ewma_ms:.3f}ms vs reference "
                f"{st.ref_ms:.3f}ms (x{ratio:.2f})"
            )

    def _check_waste_drift(self, st: _BucketState, feat: InputFeatures) -> None:
        """Flag the bucket when incoming graphs' padding_waste departs
        from the probe representative's by more than drift_waste_delta,
        or crosses a waste-bin boundary — the block-ELL padding regime
        the decision was probed under no longer describes the traffic
        (PR 3's decide_events audit signal, acted on).

        The raw-delta test is the one reachable in-process: waste_bin is
        part of the bucket sig, so same-bucket traffic can never change
        bins, but bins are coarse (up to 0.5 wide, and bin 2 is open
        toward 1.0 where dense-W work diverges) — waste can move a long
        way inside one. The bin test additionally covers entries whose
        waste_at_probe predates a re-binning (older cache schema, foreign
        writer)."""
        if st.drift_flagged or not st.probed or st.waste_at_probe is None:
            return
        if (
            abs(feat.padding_waste - st.waste_at_probe) >= self.drift_waste_delta
            or waste_bin(feat.padding_waste) != waste_bin(st.waste_at_probe)
        ):
            self._flag_drift(
                st, f"padding_waste {feat.padding_waste:.3f} departed the "
                f"probe-time regime (waste_at_probe={st.waste_at_probe:.3f})"
            )

    def _flag_drift(self, st: _BucketState, reason: str) -> None:
        """Re-enqueue a probed bucket on the probe budget. The stale
        decision keeps serving until the re-probe lands (guardrail-safe:
        it was the best known mapping, just possibly no longer), and
        priority() decays per completed re-probe."""
        if self.cache.replay_only:
            return  # replay is immutable by contract
        st.drift_flagged = True
        st.probed = False
        st.drift_reason = reason
        self._drift_flags.inc(event="flag")
        telemetry.emit_batch_event(
            {
                "event": "drift_flag",
                "bucket": st.bucket.sig(),
                "op": st.bucket.op,
                "f": st.bucket.f,
                "choice": st.decision.choice if st.decision else "baseline",
                "reason": reason,
                "obs": st.obs,
                "ewma_ms": st.ewma_ms,
                "probe_est_ms": st.probe_est_ms,
            }
        )

    def _check_fault_retire(self, st: _BucketState) -> None:
        """Route run-time faults back into the bucket stream. A pinned or
        transferred choice that is constructible but faults at first run
        emits no drift signal — the fallback chain (core/resilience.py)
        silently serves the baseline under the pinned name forever. The
        circuit breaker records those run faults; this check re-opens the
        bucket so the next pump re-probes honestly (allow_transfer=False
        there, so a faulting peer import cannot be re-imported)."""
        if not resilience.enabled() or st.drift_flagged or not st.probed:
            return
        d = st.decision
        if d is None or d.choice == "baseline":
            return
        br = self.sage.breaker
        if br.is_quarantined(d.choice):
            self._flag_fault(
                st, f"pinned choice {d.choice} is quarantined"
            )
        elif br.run_failures(d.choice) > 0:
            self._flag_fault(
                st, f"pinned choice {d.choice} faulted at run time"
            )

    def _flag_fault(self, st: _BucketState, reason: str) -> None:
        """Like _flag_drift, but triggered by breaker state instead of
        runtime statistics: the pinned decision keeps serving (its
        fallback chain guarantees a runnable result) while the re-probe
        waits on the normal budget."""
        if self.cache.replay_only:
            return  # replay is immutable by contract
        st.drift_flagged = True
        st.probed = False
        st.drift_reason = reason
        obs.REGISTRY.inc("autosage_quarantine_total", event="bucket_reopen")
        telemetry.emit_batch_event(
            {
                "event": "fault_flag",
                "bucket": st.bucket.sig(),
                "op": st.bucket.op,
                "f": st.bucket.f,
                "choice": st.decision.choice if st.decision else "baseline",
                "reason": reason,
                "transferred": st.transferred,
            }
        )
        telemetry.emit_fault_event(
            {
                "event": "bucket_reopen",
                "bucket": st.bucket.sig(),
                "op": st.bucket.op,
                "choice": st.decision.choice if st.decision else "baseline",
                "reason": reason,
            }
        )

    def _push_stats(self, st: _BucketState) -> None:
        """Fold this bucket's local traffic + observations into its cache
        entry (hit deltas merge-sum across the fleet)."""
        self.cache.add_hits(st.key, st.hits - st.hits_flushed)
        st.hits_flushed = st.hits
        self.cache.update_stats(
            st.key, obs=st.obs, ewma_ms=st.ewma_ms,
            probe_est_ms=st.probe_est_ms, waste_at_probe=st.waste_at_probe,
        )

    def _bucket_seed(self, st: _BucketState) -> int:
        """Deterministic per-bucket probe seed (stable across runs and
        stream orderings, unlike an arrival counter)."""
        return (self.seed * 2654435761 + zlib.crc32(st.key.encode())) % (2**31)

    def _bucket_entry(self, st: _BucketState, d: Decision) -> Dict[str, Any]:
        entry = {
            "choice": d.choice,
            "op": st.rep_feat.op,
            "bucket": st.bucket.sig(),
            "rep_graph_sig": st.rep_feat.graph_sig,
            "probe_ms": d.probe_ms,
            "estimates_ms": st.estimates_ms,
            # probed=False marks a pinned-provisional baseline OR a
            # zero-probe transfer: peers and replays can tell "measured
            # winner" from "budget never got here" / "accepted in
            # estimate space" (the transfer dict disambiguates the two)
            "probed": bool(d.probe_ms) or d.from_cache,
            # the schema-v5 device-neutral half: what a peer device class
            # needs to re-rank this decision under its own roofline.
            # Empty ranking for never-probed entries — an unmeasured
            # decision donates nothing (transfers must not chain)
            "neutral": {
                "features": st.rep_feat.to_neutral(),
                "ranking": transfer_mod.build_ranking(
                    d.probe_ms, st.estimates_ms or d.estimates_ms,
                    st.base.full_name(),
                ),
                "op": st.rep_feat.op,
                "f": st.rep_feat.f,
                "waste_bin": st.bucket.waste_bin,
            },
            "stats": {
                "probe_est_ms": st.probe_est_ms,
                "waste_at_probe": st.waste_at_probe,
                # an exact-key revalidation counts as a fresh pin too —
                # only never-probed entries (provisional baselines and
                # zero-probe transfers) stay at 0.0 and lose every merge
                # against a measured peer entry
                "probed_at": time.time() if (d.probe_ms or d.from_cache) else 0.0,
                "probes": st.reprobes + (1 if d.probe_ms else 0),
                "obs": st.obs,
                "ewma_ms": st.ewma_ms,
            },
        }
        if st.transfer_info is not None:
            entry["transfer"] = dict(st.transfer_info)
        return entry

    # ----------------------------------------------------- finalization
    def finalize(self) -> Dict[str, Any]:
        """Pin every bucket decision (probed or provisional-baseline)
        into the cache and flush once; after this, replaying the same
        stream under AUTOSAGE_REPLAY_ONLY=1 serves identical choices
        without a single probe. Returns the stream stats. No-op writes
        in replay mode (the cache is read-only there)."""
        if not self.cache.replay_only:
            flush_guard = (
                resilience.cache_guard(op="finalize")
                if resilience.enabled()
                else contextlib.nullcontext()
            )
            with flush_guard:
                with self._lock:
                    snapshot = list(self._buckets.values())
                with self.cache:
                    for st in snapshot:
                        if not self.cache.contains(st.key):
                            self.cache.put(
                                st.key, self._bucket_entry(st, st.current())
                            )
                        self._push_stats(st)
                self.cache.flush()
        stats = self.stats()
        telemetry.emit_batch_event({"event": "finalize", **stats})
        return stats

    def __enter__(self) -> "BatchScheduler":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.finalize()

    # ------------------------------------------------------------ stats
    def stats(self) -> Dict[str, Any]:
        return {
            "decides": self._decides.value,
            "buckets": len(self._buckets),
            "probes_run": self._probe_passes.value,
            "probes_avoided": self._decides.value - self._probe_passes.value,
            "probe_spent_ms": round(self.probe_spent_ms, 3),
            "probe_budget_ms": self.probe_budget_ms,
            "decide_wall_ms": round(self._decide_wall_ms, 3),
            "pending_buckets": len(self.pending()),
            # fleet sharing: buckets opened final from a (shared) cache,
            # i.e. probes another process (or a previous run) paid for
            "warm_cache_opens": self._warm_opens.value,
            "drift_flags": self.drift_flags,
            "drift_reprobes": self.drift_reprobes,
            "drift_flips": self.drift_flips,
            # cross-device transfers: buckets opened from a peer device
            # class's probed ranking; confirmed = probe-free accepts +
            # confirm probes that agreed; probe_free = probes avoided
            # outright by confident transfers
            "transfers": self.transfers,
            "transfers_confirmed": self.transfers_confirmed,
            "transfers_flipped": self.transfers_flipped,
            "transfers_pending": (
                self.transfers - self.transfers_confirmed
                - self.transfers_flipped
            ),
            "transfer_probe_free": self.transfer_probe_free,
        }

    def bucket_stats(self) -> List[Dict[str, Any]]:
        """Per-bucket telemetry rows, heaviest traffic first."""
        rows = []
        with self._lock:
            snapshot = list(self._buckets.values())
        for st in sorted(snapshot, key=lambda s: -s.hits):
            d = st.current()
            rows.append(
                {
                    "bucket": st.bucket.sig(),
                    "op": st.bucket.op,
                    "f": st.bucket.f,
                    "hits": st.hits,
                    "probed": st.probed,
                    "choice": d.choice,
                    "est_gain_ms": round(st.est_gain_ms, 4),
                    "probe_charge_ms": round(st.probe_charge_ms, 3),
                    "rep_n_rows": st.rep_feat.n_rows,
                    "rep_nnz": st.rep_feat.nnz,
                    "obs": st.obs,
                    "ewma_ms": None if st.ewma_ms is None else round(st.ewma_ms, 4),
                    "probe_est_ms": (
                        None if st.probe_est_ms is None else round(st.probe_est_ms, 4)
                    ),
                    "ref_ms": None if st.ref_ms is None else round(st.ref_ms, 4),
                    "drift_flagged": st.drift_flagged,
                    "reprobes": st.reprobes,
                    "transferred": st.transferred,
                    "transfer_verdict": st.transfer_verdict or None,
                    "transfer_source": (
                        (st.transfer_info or {}).get("source_device")
                    ),
                }
            )
        return rows

    def _record(self, st: _BucketState, d: Decision, source: str) -> None:
        # the one place stream decides are counted: instance total for
        # stats(), op/tier-labelled registry series for the exporters
        self._decides.inc(op=d.op, tier=source, scheduler="batch")
        event = {
            "i": self._decides.value - 1,
            "bucket": st.bucket.sig(),
            "key": st.key,
            "op": d.op,
            "f": st.bucket.f,
            "choice": d.choice,
            "source": source,
        }
        self.trace.append(event)
        telemetry.emit_batch_event({"event": "decide", **event})

    def write_trace(self, path: str) -> None:
        """Dump the stream trace as JSONL (one decide per line); replaces
        any existing file so repeated dumps never duplicate events."""
        import json
        from pathlib import Path

        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "w") as f:
            for event in self.trace:
                json.dump(event, f, sort_keys=True)
                f.write("\n")

    # ----------------------------------------- AutoSage-compatible API
    def build_runner(self, csr: CSR, decision: Decision) -> Callable:
        return self.sage.build_runner(csr, decision)

    def spmm(self, csr: CSR, b):
        d = self.decide(csr, int(b.shape[1]), "spmm")
        return self.build_runner(csr, d)(b), d

    def sddmm(self, csr: CSR, x, y):
        d = self.decide(csr, int(x.shape[1]), "sddmm")
        return self.build_runner(csr, d)(x, y), d

    def attention(self, csr: CSR, q, k, v):
        d = self.decide(csr, int(q.shape[1]), "attention")
        return self.build_runner(csr, d)(q, k, v), d
