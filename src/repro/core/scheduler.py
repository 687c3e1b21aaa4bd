"""AutoSAGE scheduler: estimate -> micro-probe -> guardrail -> cache.

Faithful implementation of the paper's §4.2 decision procedure
(`autosage_decide`), including the persistent cache fast-path, induced
subgraph probing with identical sampling per candidate, top-k shortlist by
roofline estimate, and the non-regression guardrail (Prop. 1).
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np


def _warn_deprecated(old: str, new: str) -> None:
    """One-time DeprecationWarning (Python's default filter dedups per
    call site) pointing legacy call styles at the repro.api facade."""
    warnings.warn(
        f"{old} is deprecated; use {new} (see repro.api)",
        DeprecationWarning,
        stacklevel=3,
    )

from repro.core import estimate as est
from repro.core import features as features_mod
from repro.core import obs
from repro.core import probe as probe_mod
from repro.core import registry
from repro.core import resilience
from repro.core import telemetry
from repro.core import transfer as transfer_mod
from repro.core.cache import ReplayMiss, ScheduleCache
from repro.core.features import (
    HardwareSpec,
    InputFeatures,
    device_sig,
    waste_bin,
)
from repro.core.guardrail import GuardrailDecision, apply_guardrail
from repro.sparse.csr import CSR


@dataclasses.dataclass
class ProbeOutcome:
    """Result of one slope-probe pass over a candidate shortlist."""

    probe_ms: Dict[str, float]  # candidate full-name -> effective cost
    best_name: Optional[str]
    t_best_ms: float
    t_baseline_ms: float
    overhead_ms: float  # wall time incl. prepare + compile
    iter_ms: float  # steady-state probe iterations only


def default_probe_args(op: str, f: int, seed: int = 0,
                       heads: int = 1) -> Callable[[CSR], tuple]:
    """Random dense operands of width f, shaped for ``op``, per subgraph;
    with ``heads`` > 1, (n, heads, f) operands and (heads, nnz) values.

    Grad ops route through their structural compute kind, so the slope
    probe times cotangent-shaped operands: for "spmm_bwd_b" (an SpMM over
    the transposed CSR) the operand is the (n_cols, F_grad) cotangent,
    and dynamic-vals ops additionally get a random nnz-length value
    vector standing in for the per-edge cotangent. The old forward-only
    shapes silently probed the wrong F for grad-side decisions.
    """
    kind = features_mod.op_kind(op)
    dynamic = features_mod.op_dynamic_vals(op)
    per_node = (f,) if heads == 1 else (heads, f)

    def fn(sub: CSR) -> tuple:
        # per-subgraph stream: the 1x and 2x slope-probe subgraphs share
        # n_cols, so a single seed would hand both probes byte-identical
        # operands and let the 2x probe read them out of a warm cache,
        # biasing the slope low
        rng = np.random.default_rng((seed, sub.n_rows, sub.nnz))
        def draw(*shape: int) -> np.ndarray:
            return rng.standard_normal(shape).astype(np.float32)

        if kind == "spmm":
            args = (draw(sub.n_cols, *per_node),)
            if dynamic:
                per_edge = (sub.nnz,) if heads == 1 else (heads, sub.nnz)
                return (draw(*per_edge),) + args
            return args
        if kind == "sddmm":
            return (draw(sub.n_rows, *per_node), draw(sub.n_cols, *per_node))
        if kind == "attention":
            return (draw(sub.n_rows, *per_node), draw(sub.n_cols, *per_node),
                    draw(sub.n_cols, *per_node))
        raise KeyError(op)

    return fn


@dataclasses.dataclass
class Decision:
    op: str
    choice: str  # "baseline" or variant full-name
    variant: registry.Variant  # the variant to run (baseline if fallback)
    guardrail: Optional[GuardrailDecision]
    from_cache: bool
    probe_ms: Dict[str, float]  # candidate -> median ms (empty if cached)
    probe_overhead_ms: float  # total warm-up: prepare + compile + iters
    probe_iter_ms: float  # steady-state probe iterations only
    estimates_ms: Dict[str, float]
    # cross-device provenance (core/transfer.py): set when this decision
    # was transferred from a peer device's probed ranking instead of (or
    # before) being probed locally — source_device, verdict
    # (confirmed/pending/flipped), rank_agreement, predicted_ms
    transfer: Optional[Dict[str, Any]] = None

    def to_cache_entry(self) -> Dict[str, Any]:
        entry: Dict[str, Any] = {
            "choice": self.choice,
            "probe_ms": self.probe_ms,
            "estimates_ms": self.estimates_ms,
        }
        if self.transfer is not None:
            entry["transfer"] = dict(self.transfer)
        return entry


def entry_with_stats(
    decision: "Decision",
    feat: InputFeatures,
    base_full_name: Optional[str] = None,
) -> Dict[str, Any]:
    """Cache entry + running stats + the schema-v5 device-neutral part.

    The stats are what the drift detector (core/batch.py) compares live
    traffic against, and `probed_at` is the fleet merge tiebreaker
    (last-probe-wins; a transferred-but-unprobed entry keeps 0.0 so any
    real measurement beats it). The "neutral" dict is the transferable
    half: input features plus the probed ranking with each candidate's
    slope-probe ms and estimate ms at probe time — everything a peer
    device class needs to re-rank this decision under its own roofline
    (core/transfer.py)."""
    entry = decision.to_cache_entry()
    probed = bool(decision.probe_ms)
    entry["probed"] = probed
    entry["neutral"] = {
        "features": feat.to_neutral(),
        "ranking": transfer_mod.build_ranking(
            decision.probe_ms, decision.estimates_ms,
            base_full_name or "baseline",
        ),
        "op": decision.op,
        "f": feat.f,
        "waste_bin": waste_bin(feat.padding_waste),
    }
    entry["stats"] = {
        "probe_est_ms": decision.probe_ms.get(decision.choice),
        "waste_at_probe": feat.padding_waste,
        "probed_at": time.time() if probed else 0.0,
        "probes": 1 if probed else 0,
    }
    return entry


class AutoSage:
    """Holds the cache + hardware spec; one instance per process."""

    def __init__(
        self,
        alpha: Optional[float] = None,
        top_k: Optional[int] = None,
        cache: Optional[ScheduleCache] = None,
        hw: Optional[HardwareSpec] = None,
        probe_frac: Optional[float] = None,
        probe_iters: Optional[int] = None,
        probe_cap_ms: Optional[float] = None,
    ):
        self.alpha = float(os.environ.get("AUTOSAGE_ALPHA", 0.95)) if alpha is None else alpha
        self.top_k = int(os.environ.get("AUTOSAGE_TOPK", 3)) if top_k is None else top_k
        self.cache = cache if cache is not None else ScheduleCache()
        self.hw = hw or HardwareSpec.current()
        self.probe_frac = probe_frac if probe_frac is not None else probe_mod.DEFAULT_FRAC
        self.probe_iters = probe_iters if probe_iters is not None else probe_mod.DEFAULT_ITERS
        self.probe_cap_ms = probe_cap_ms if probe_cap_ms is not None else probe_mod.DEFAULT_CAP_MS
        # built-runner memo: prepare() is O(nnz) host work + device upload,
        # paid once per (graph, op, choice) instead of per forward call.
        # LRU-bounded: a minibatch stream (core/batch.py) feeds thousands
        # of one-shot subgraphs, each pinning O(nnz) device buffers —
        # unbounded memoization is a memory leak there
        self._runners: Dict[tuple, Callable] = {}
        self._runner_cap = int(os.environ.get("AUTOSAGE_RUNNER_CACHE", "64"))
        # per-(candidate, device) circuit breaker (core/resilience.py):
        # exhausted failures quarantine a candidate out of shortlist /
        # probe / transfer; the blacklist persists through the schedule
        # cache so fleet workers share it
        self.breaker = resilience.CircuitBreaker(cache=self.cache)
        obs.listen_compiles()  # autosage_xla_compile_ms{phase}

    # ------------------------------------------------------------------
    def probe_candidates(
        self,
        csr: CSR,
        base: registry.Variant,
        shortlist: List[registry.Variant],
        args_fn: Callable[[CSR], tuple],
        seed: int = 0,
    ) -> ProbeOutcome:
        """Slope-mode micro-probe of baseline + shortlist (paper §4.2).

        Times every candidate on TWO induced subgraphs (1x and 2x rows)
        with identical sampling. Comparing the cost *slope* between the
        two sizes cancels each variant's fixed dispatch/launch overhead,
        which otherwise makes small probes mispredict full-graph
        performance (a failure mode of the paper's single-point probe we
        hit on ER; see EXPERIMENTS.md "probe-scale bias").
        AUTOSAGE_PROBE_MODE=point restores the paper's single-point
        behaviour. An "attention" decide probes whole pipelines, so
        composed candidates are timed end to end under this protocol.
        """
        mode = os.environ.get("AUTOSAGE_PROBE_MODE", "slope")
        t_probe0 = time.perf_counter()
        sub1 = probe_mod.induced_subgraph(csr, frac=self.probe_frac, seed=seed)
        subs = [sub1]
        if mode == "slope" and sub1.n_rows * 2 <= csr.n_rows:
            subs.append(
                probe_mod.induced_subgraph(csr, seed=seed, n_rows=sub1.n_rows * 2)
            )
        args_per_sub = [args_fn(s) for s in subs]
        probe_ms: Dict[str, float] = {}
        iter_ms_total = [0.0]

        def _time(v: registry.Variant) -> float:
            """Effective cost: slope between the two probe sizes (ms per
            full-graph-equivalent), or plain median in point mode."""
            times = []
            with obs.span("probe.candidate", candidate=v.full_name()):
                for sub, args in zip(subs, args_per_sub):
                    aux = v.timed_prepare(sub)
                    run = v.build(aux)
                    res = probe_mod.time_callable(
                        lambda: run(*args), iters=self.probe_iters,
                        cap_ms=self.probe_cap_ms, name=v.full_name(),
                    )
                    iter_ms_total[0] += sum(res.times_ms)
                    times.append(res.median_ms)
            if len(times) == 2:
                slope = (times[1] - times[0]) / max(subs[1].n_rows - subs[0].n_rows, 1)
                if slope > 0:
                    return slope * csr.n_rows  # extrapolated marginal cost
            return times[-1]

        def _sandboxed_time(v: registry.Variant) -> Optional[float]:
            """Probe one candidate under a watchdog; a candidate that
            raises or hangs is excluded from this pass (None) instead of
            aborting the whole probe, and its failure feeds the breaker.
            Deliberately NOT written into probe_ms — a fault is not a
            measurement (and inf does not survive strict JSON)."""
            name = v.full_name()
            if not resilience.enabled():
                return _time(v)
            try:
                t = resilience.run_with_timeout(
                    lambda: _time(v),
                    resilience.policy_for("probe").timeout_s,
                    "probe", name=name,
                )
                if not v.is_baseline:
                    self.breaker.record_success(name)
                return t
            except Exception as exc:
                resilience.record_fault("probe", name, v.op, exc)
                if not v.is_baseline:  # the lifeline is never blacklisted
                    self.breaker.record_failure(
                        name, site="probe", op=v.op,
                        permanent=resilience.classify(exc)
                        == resilience.PERMANENT,
                    )
                return None

        tb = _sandboxed_time(base)
        if tb is not None:
            probe_ms["baseline"] = tb
        else:
            # a faulting baseline probe must not veto a working
            # challenger: an infinite reference cost accepts whichever
            # candidate measured clean (and the run-time fallback chain
            # still guards the actual execution)
            tb = float("inf")
        best_name, t_star = None, float("inf")
        for v in shortlist:
            t = _sandboxed_time(v)
            if t is None:
                continue
            probe_ms[v.full_name()] = t
            if t < t_star:
                best_name, t_star = v.full_name(), t
        return ProbeOutcome(
            probe_ms=probe_ms,
            best_name=best_name,
            t_best_ms=t_star,
            t_baseline_ms=tb,
            overhead_ms=(time.perf_counter() - t_probe0) * 1e3,
            iter_ms=iter_ms_total[0],
        )

    def shortlist(
        self, feat: InputFeatures, cands: List[registry.Variant]
    ) -> tuple:
        """Estimate stage: (estimates_ms, top-k non-baseline candidates)."""
        with obs.span("estimate", op=feat.op, n_candidates=len(cands)):
            estimates = est.estimates_for(feat, self.hw, cands)
        with obs.span("shortlist", op=feat.op, top_k=self.top_k):
            short = sorted(
                (
                    v for v in cands
                    if not v.is_baseline
                    and not self.breaker.is_excluded(v.full_name())
                ),
                key=lambda v: estimates[v.full_name()],
            )[: self.top_k]
        return estimates, short

    # ------------------------------------------------------------------
    def decide(
        self,
        csr: CSR,
        f: int,
        op: str,
        probe_args_fn: Optional[Callable[[CSR], tuple]] = None,
        seed: int = 0,
        allow_transfer: bool = True,
        heads: int = 1,
    ) -> Decision:
        """The paper's `autosage_decide(features, F, op)`.

        probe_args_fn(sub_csr) -> dense args for one probe invocation;
        defaults to random dense operands of width F. ``heads`` > 1
        decides one op over (n, heads, F) operands, keyed by its head
        count too. ``op="attention"`` decides whole SDDMM -> softmax ->
        SpMM pipelines (the composed candidates and the fused kernel of
        core/registry.py), F being the head width.

        On an exact-key miss, a peer device class's probed entry for the
        SAME graph can short-circuit the probe (estimate-space transfer,
        core/transfer.py): a confident re-rank under the local roofline
        is pinned and served with zero probes; a non-confident one runs
        the normal probe, which then confirms or flips the transferred
        prediction (provenance lands in the entry + decide_events).
        ``allow_transfer=False`` forces a real local measurement — the
        batch scheduler's confirm/drift re-probes use it.
        """
        t0 = time.perf_counter()
        with obs.span("decide", op=op, f=f, heads=heads, scheduler="exact"):
            try:
                decision, tier = self._decide_impl(
                    csr, f, op, probe_args_fn=probe_args_fn, seed=seed,
                    allow_transfer=allow_transfer, heads=heads,
                )
            except ReplayMiss:
                raise  # the replay contract stays loud — never rescued
            except Exception as exc:
                if not resilience.enabled():
                    raise
                # last-ditch rescue: whatever faulted inside the decision
                # machinery, a provisional-baseline decision is always
                # constructible and always runnable (its run path still
                # has the reference-oracle fallback under it)
                resilience.record_fault("decide", "", op, exc)
                decision = self._rescue_decision(csr, f, op, heads)
                tier = "fault"
        obs.REGISTRY.inc(
            "autosage_decides_total", op=op, tier=tier, scheduler="exact"
        )
        obs.REGISTRY.inc(
            "autosage_decide_choice_total", op=op, choice=decision.choice
        )
        obs.REGISTRY.observe(
            "autosage_decide_ms", (time.perf_counter() - t0) * 1e3,
            op=op, scheduler="exact",
        )
        return decision

    def _rescue_decision(self, csr: CSR, f: int, op: str,
                         heads: int = 1) -> Decision:
        """Provisional baseline decision for the decide-path rescue: not
        cached (the fault may be environmental and transient), never a
        poisoned pin."""
        feat = InputFeatures.from_csr(csr, f, op, heads)
        base = registry.baseline(feat, self.hw)
        return Decision(
            op=op, choice="baseline", variant=base, guardrail=None,
            from_cache=False, probe_ms={}, probe_overhead_ms=0.0,
            probe_iter_ms=0.0, estimates_ms={},
        )

    def _decide_impl(
        self,
        csr: CSR,
        f: int,
        op: str,
        probe_args_fn: Optional[Callable[[CSR], tuple]] = None,
        seed: int = 0,
        allow_transfer: bool = True,
        heads: int = 1,
    ) -> tuple:
        """decide() body; returns (Decision, tier) where tier is the
        accounting label "cache" | "transfer" | "probe"."""
        with obs.span("features", op=op):
            feat = InputFeatures.from_csr(csr, f, op, heads)
        key = ScheduleCache.key(device_sig(), feat.graph_sig, f, op, self.alpha,
                                heads)

        cands = registry.candidates(feat, self.hw)
        base = registry.baseline(feat, self.hw)
        by_name = {v.full_name(): v for v in cands}
        by_name["baseline"] = base

        cached = self.cache.get(key) if self.cache is not None else None
        if cached is not None and resilience.enabled():
            choice = cached.get("choice")
            self.breaker.maybe_sync()
            if choice not in (None, "baseline") and self.breaker.is_quarantined(
                choice
            ):
                if self.cache.replay_only:
                    # the replay contract: a quarantined pin is a MISS,
                    # loudly — never a silent substitute choice
                    raise ReplayMiss(
                        f"pinned choice {choice!r} for {key} is quarantined "
                        "(AUTOSAGE_REPLAY_ONLY=1 forbids substituting)"
                    )
                cached = None  # re-decide without the quarantined pin
        if cached is not None:
            choice = cached["choice"]
            variant = by_name.get(choice, base)
            decision = Decision(
                op=op, choice=choice, variant=variant, guardrail=None,
                from_cache=True, probe_ms={}, probe_overhead_ms=0.0,
                probe_iter_ms=0.0, estimates_ms={},
            )
            # cache hits are emitted too: auditing stale decisions means
            # comparing a *cached* choice against the current input's
            # padding_waste (see telemetry.emit_decide_event)
            telemetry.emit_decide_event(decision, feat)
            return decision, "cache"

        if resilience.enabled():
            # cold path: fold in any quarantines peers persisted since
            # our last look before shortlisting/transferring
            self.breaker.maybe_sync()
        estimates, short = self.shortlist(feat, cands)
        plan = None
        if (
            allow_transfer and short and transfer_mod.enabled()
            and self.cache is not None and not self.cache.replay_only
        ):
            plan = transfer_mod.best_plan(
                self.cache.peer_entries(key), feat, self.hw, by_name, base,
                self.alpha, excluded=self.breaker.excluded_names(),
            )
        if plan is not None and plan.confident:
            decision = Decision(
                op=op, choice=plan.choice,
                variant=by_name.get(plan.choice, base),
                guardrail=plan.guardrail, from_cache=False, probe_ms={},
                probe_overhead_ms=0.0, probe_iter_ms=0.0,
                estimates_ms=estimates,
                transfer=plan.provenance("confirmed"),
            )
            with resilience.cache_guard(op=op):
                self.cache.put(
                    key, entry_with_stats(decision, feat, base.full_name())
                )
            obs.REGISTRY.inc(
                "autosage_transfer_verdict_total", verdict="confirmed"
            )
            telemetry.emit_decide_event(decision, feat, kind="transfer")
            return decision, "transfer"

        if short:
            with obs.span("probe", op=op, n_candidates=len(short) + 1):
                outcome = self.probe_candidates(
                    csr, base, short,
                    probe_args_fn or default_probe_args(op, f, seed, heads),
                    seed=seed,
                )
            obs.REGISTRY.inc("autosage_probe_passes_total", op=op)
            obs.REGISTRY.observe(
                "autosage_probe_ms", outcome.overhead_ms, op=op
            )
            obs.record_probe_estimates(
                op, outcome.probe_ms, estimates, base.full_name()
            )
        else:
            # no challengers: the decision can only be baseline, skip the
            # subgraph extraction + compile + timing entirely
            outcome = ProbeOutcome({}, None, float("inf"), 0.0, 0.0, 0.0)

        with obs.span("guardrail", op=op):
            gr = apply_guardrail(
                outcome.best_name, outcome.t_best_ms, outcome.t_baseline_ms,
                self.alpha,
            )
        variant = by_name[gr.choice] if gr.accepted else base
        decision = Decision(
            op=op, choice=gr.choice, variant=variant, guardrail=gr,
            from_cache=False, probe_ms=outcome.probe_ms,
            probe_overhead_ms=outcome.overhead_ms,
            probe_iter_ms=outcome.iter_ms, estimates_ms=estimates,
        )
        if plan is not None:
            # the probe doubles as the transfer's confirm measurement
            verdict = "confirmed" if gr.choice == plan.choice else "flipped"
            decision.transfer = plan.provenance(verdict)
            obs.REGISTRY.inc("autosage_transfer_verdict_total", verdict=verdict)
        if self.cache is not None:
            with resilience.cache_guard(op=op):
                self.cache.put(
                    key, entry_with_stats(decision, feat, base.full_name())
                )
        telemetry.emit_decide_event(decision, feat)
        return decision, "probe"

    # ------------------------------------------------------------------
    def build_runner(self, csr: CSR, decision: Decision) -> Callable:
        """Prepare the chosen variant on the FULL graph and return the
        jitted callable (memoized per graph/op/choice). With resilience
        on, the returned callable is the fallback chain — chosen variant
        -> xla baseline -> reference oracle — so a choice that raises at
        prepare or run time degrades instead of crashing the request
        (core/resilience.py), and its failures feed the breaker."""
        from repro.sparse.csr import graph_signature

        key = (graph_signature(csr), decision.op, decision.choice)
        runner = self._runners.pop(key, None)
        if runner is None:
            if resilience.enabled():
                runner = self._build_chain(csr, decision, graph_sig=key[0])
            else:
                runner = self._build_raw(csr, decision, graph_sig=key[0])
            while len(self._runners) >= max(self._runner_cap, 1):
                self._runners.pop(next(iter(self._runners)))
        self._runners[key] = runner  # (re)insert at MRU position
        return runner

    def _build_raw(
        self, csr: CSR, decision: Decision, graph_sig: str
    ) -> Callable:
        # build_runner is reached from inside jit/grad traces (the
        # custom_vjp fwd/bwd rules in core/autodiff.py decide at
        # trace time). The prepared layout tables must be CONCRETE
        # device arrays, not trace-scoped constants — a memoized
        # runner closing over tracers poisons every later trace.
        with obs.span(
            "prepare", op=decision.op, choice=decision.choice
        ), jax.ensure_compile_time_eval():
            aux = decision.variant.timed_prepare(csr)
            runner = decision.variant.build(aux)
        padding = {
            k: float(v) for k, v in aux.items()
            if k.endswith("padding_frac")
        }
        if padding:
            # exact (per-partition) dense-W padding measured by the
            # block-ELL conversion on the full graph — the audit
            # counterpart of the feature-estimated padding_waste
            telemetry.emit_decide_event(
                decision, padding=padding, graph_sig=graph_sig,
                kind="prepare",
            )
        return runner

    def _build_chain(
        self, csr: CSR, decision: Decision, graph_sig: str
    ) -> Callable:
        """Fallback-chain runner. Stage 0 (the pinned choice) reuses the
        raw build — including padding telemetry — so the no-fault path
        behaves exactly like the unwrapped runner."""

        def build_choice(args):
            return self._build_raw(csr, decision, graph_sig)

        stages = []
        if decision.choice != "baseline":
            stages.append((decision.choice, build_choice, True))
            stages += resilience.fallback_stages(
                csr, decision.op, "baseline", None, self.hw
            )
        else:
            # choice IS the baseline: it fronts the chain (with its
            # padding telemetry), backed only by the oracle
            stages.append(("baseline", build_choice, True))
            stages.append(
                (
                    "reference",
                    lambda args: resilience.reference_runner(csr, decision.op),
                    False,
                )
            )
        return resilience.chain_runner(
            stages, decision.op, breaker=self.breaker
        )

    def spmm(self, csr: CSR, b, seed: int = 0):
        """Deprecated one-call convenience (paper's autosage::spmm_csr
        binding). Use `repro.api.spmm(csr, b, sage=...)` — the facade is
        keyword-consistent and differentiable; advanced callers needing
        the Decision use `decide` + `build_runner` directly."""
        _warn_deprecated("AutoSage.spmm", "repro.api.spmm(csr, b, sage=...)")
        d = self.decide(csr, int(b.shape[1]), "spmm", seed=seed)
        return self.build_runner(csr, d)(b), d

    def sddmm(self, csr: CSR, x, y, seed: int = 0):
        """Deprecated; use `repro.api.sddmm(csr, x, y, sage=...)`."""
        _warn_deprecated("AutoSage.sddmm", "repro.api.sddmm(csr, x, y, sage=...)")
        d = self.decide(csr, int(x.shape[1]), "sddmm", seed=seed)
        return self.build_runner(csr, d)(x, y), d

    def attention(self, csr: CSR, q, k, v, seed: int = 0):
        """Deprecated; use `repro.api.attention(csr, q, k, v, sage=...)`."""
        _warn_deprecated(
            "AutoSage.attention", "repro.api.attention(csr, q, k, v, sage=...)"
        )
        d = self.decide(csr, int(q.shape[-1]), "attention", seed=seed)
        return self.build_runner(csr, d)(q, k, v), d
