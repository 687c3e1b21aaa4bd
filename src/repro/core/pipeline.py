"""Pipeline-level scheduling for CSR attention (SDDMM -> softmax -> SpMM).

`AutoSage.decide` picks a variant per op, so a per-op view can never
justify the fused flash-style kernel in kernels/attention_pallas.py: its
benefit — logits/probs never round-trip HBM — lies *between* the ops.
This module decides at pipeline granularity instead (the direction
ParamSpMM and "Heuristic Adaptability to Input Dynamics" argue for: the
best mapping flips with degree skew and feature width, so the decision
procedure must see the whole composed workload):

  1. enumerate composed candidates {sddmm variant x softmax x spmm
     variant} plus the fused Pallas kernel, registered as first-class
     op="attention" Variants in core/registry.py;
  2. shortlist by the pipeline roofline in core/estimate.py, which
     charges composed candidates the two inter-stage HBM round-trips the
     fused kernel avoids;
  3. micro-probe the shortlist end-to-end on the same induced subgraphs
     via the slope-mode machinery in core/scheduler.py;
  4. guardrail (Prop. 1) against the 3-kernel gather/segsum baseline and
     cache the joint decision under an op="attention" key with
     deterministic replay (core/cache.py).

Entry points are `AutoSage.attention(csr, q, k, v)` and
`AutoSage.decide_attention(csr, d)`; models/gnn.py's attention path and
benchmarks/tables.py run through them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core import probe as probe_mod
from repro.core import registry, resilience, telemetry
from repro.core import transfer as transfer_mod
from repro.core.cache import ReplayMiss, ScheduleCache
from repro.core.features import InputFeatures, device_sig
from repro.core.guardrail import apply_guardrail
from repro.core.scheduler import (
    AutoSage,
    Decision,
    ProbeOutcome,
    default_probe_args,
    entry_with_stats,
)
from repro.kernels import xla as kx
from repro.sparse.csr import CSR


@dataclasses.dataclass
class AttentionDecision(Decision):
    """A joint pipeline decision, plus a per-stage timing breakdown of the
    chosen candidate (probe-subgraph medians; empty unless requested)."""

    stage_ms: Dict[str, float] = dataclasses.field(default_factory=dict)

    def to_cache_entry(self) -> Dict:
        entry = super().to_cache_entry()
        entry["op"] = "attention"
        if self.stage_ms:
            entry["stage_ms"] = dict(self.stage_ms)
        return entry


def decide_attention(
    sage: AutoSage,
    csr: CSR,
    d: int,
    seed: int = 0,
    stage_breakdown: bool = False,
    allow_transfer: bool = True,
    heads: int = 1,
) -> AttentionDecision:
    """estimate -> end-to-end probe -> guardrail -> cache, at pipeline
    granularity. ``d`` is the head dimension (the F of the cache key);
    ``heads`` attention heads run as one op, one decision per
    (graph, heads, d).
    Like the per-op decide, an exact-key miss consults peer device
    classes' probed rankings first (core/transfer.py) — a confident
    re-rank under the local roofline skips the end-to-end probe."""
    t0 = time.perf_counter()
    with obs.span("decide", op="attention", f=d, heads=heads, scheduler="exact"):
        try:
            decision, tier = _decide_attention_impl(
                sage, csr, d, seed=seed, stage_breakdown=stage_breakdown,
                allow_transfer=allow_transfer, heads=heads,
            )
        except ReplayMiss:
            raise  # the replay contract stays loud — never rescued
        except Exception as exc:
            if not resilience.enabled():
                raise
            # pipeline-level rescue mirror of AutoSage.decide: a faulting
            # decision machinery still yields a runnable 3-kernel
            # baseline decision (uncached — never a poisoned pin)
            resilience.record_fault("decide", "", "attention", exc)
            decision, tier = _rescue_attention(sage, csr, d, heads), "fault"
    obs.REGISTRY.inc(
        "autosage_decides_total", op="attention", tier=tier, scheduler="exact"
    )
    obs.REGISTRY.observe(
        "autosage_decide_ms", (time.perf_counter() - t0) * 1e3,
        op="attention", scheduler="exact",
    )
    return decision


def _rescue_attention(sage: AutoSage, csr: CSR, d: int,
                      heads: int = 1) -> "AttentionDecision":
    feat = InputFeatures.from_csr(csr, d, "attention", heads)
    base = registry.baseline(feat, sage.hw)
    return AttentionDecision(
        op="attention", choice="baseline", variant=base, guardrail=None,
        from_cache=False, probe_ms={}, probe_overhead_ms=0.0,
        probe_iter_ms=0.0, estimates_ms={},
    )


def _decide_attention_impl(
    sage: AutoSage,
    csr: CSR,
    d: int,
    seed: int = 0,
    stage_breakdown: bool = False,
    allow_transfer: bool = True,
    heads: int = 1,
) -> tuple:
    """decide_attention body; returns (decision, accounting tier)."""
    with obs.span("features", op="attention"):
        feat = InputFeatures.from_csr(csr, d, "attention", heads)
    key = ScheduleCache.key(device_sig(), feat.graph_sig, d, "attention", sage.alpha,
                            heads)

    cands = registry.candidates(feat, sage.hw)
    base = registry.baseline(feat, sage.hw)
    by_name = {v.full_name(): v for v in cands}
    by_name["baseline"] = base

    cached = sage.cache.get(key) if sage.cache is not None else None
    if cached is not None and resilience.enabled():
        choice = cached.get("choice")
        sage.breaker.maybe_sync()
        if choice not in (None, "baseline") and sage.breaker.is_quarantined(
            choice
        ):
            if sage.cache.replay_only:
                raise ReplayMiss(
                    f"pinned choice {choice!r} for {key} is quarantined "
                    "(AUTOSAGE_REPLAY_ONLY=1 forbids substituting)"
                )
            cached = None  # re-decide without the quarantined pin
    if cached is not None:
        choice = cached["choice"]
        decision = AttentionDecision(
            op="attention", choice=choice, variant=by_name.get(choice, base),
            guardrail=None, from_cache=True, probe_ms={},
            probe_overhead_ms=0.0, probe_iter_ms=0.0, estimates_ms={},
            stage_ms=dict(cached.get("stage_ms", {})),
        )
        telemetry.emit_attention_decision(decision)
        return decision, "cache"

    estimates, short = sage.shortlist(feat, cands)
    plan = None
    if (
        allow_transfer and short and transfer_mod.enabled()
        and sage.cache is not None and not sage.cache.replay_only
    ):
        plan = transfer_mod.best_plan(
            sage.cache.peer_entries(key), feat, sage.hw, by_name, base,
            sage.alpha, excluded=sage.breaker.excluded_names(),
        )
    if plan is not None and plan.confident:
        decision = AttentionDecision(
            op="attention", choice=plan.choice,
            variant=by_name.get(plan.choice, base), guardrail=plan.guardrail,
            from_cache=False, probe_ms={}, probe_overhead_ms=0.0,
            probe_iter_ms=0.0, estimates_ms=estimates,
            transfer=plan.provenance("confirmed"),
        )
        with resilience.cache_guard(op="attention"):
            sage.cache.put(
                key, entry_with_stats(decision, feat, base.full_name())
            )
        obs.REGISTRY.inc("autosage_transfer_verdict_total", verdict="confirmed")
        telemetry.emit_decide_event(decision, feat, kind="transfer")
        telemetry.emit_attention_decision(decision)
        return decision, "transfer"
    if short:
        with obs.span("probe", op="attention", n_candidates=len(short) + 1):
            outcome = sage.probe_candidates(
                csr, base, short, default_probe_args("attention", d, seed, heads),
                seed=seed,
            )
        obs.REGISTRY.inc("autosage_probe_passes_total", op="attention")
        obs.REGISTRY.observe(
            "autosage_probe_ms", outcome.overhead_ms, op="attention"
        )
        obs.record_probe_estimates(
            "attention", outcome.probe_ms, estimates, base.full_name()
        )
    else:
        # no challengers: only the 3-kernel baseline applies, skip probing
        outcome = ProbeOutcome({}, None, float("inf"), 0.0, 0.0, 0.0)
    with obs.span("guardrail", op="attention"):
        gr = apply_guardrail(
            outcome.best_name, outcome.t_best_ms, outcome.t_baseline_ms,
            sage.alpha,
        )
    variant = by_name[gr.choice] if gr.accepted else base

    stage_ms: Dict[str, float] = {}
    if stage_breakdown:
        stage_ms = probe_stage_breakdown(sage, csr, d, variant, seed=seed,
                                         heads=heads)

    decision = AttentionDecision(
        op="attention", choice=gr.choice, variant=variant, guardrail=gr,
        from_cache=False, probe_ms=outcome.probe_ms,
        probe_overhead_ms=outcome.overhead_ms, probe_iter_ms=outcome.iter_ms,
        estimates_ms=estimates, stage_ms=stage_ms,
    )
    if plan is not None:
        # the end-to-end probe doubles as the transfer's confirm pass
        verdict = "confirmed" if gr.choice == plan.choice else "flipped"
        decision.transfer = plan.provenance(verdict)
        obs.REGISTRY.inc("autosage_transfer_verdict_total", verdict=verdict)
    if sage.cache is not None:
        # same v5 stats + neutral treatment as per-op decisions: the
        # batch scheduler's drift detector tracks fused-vs-composed
        # staleness per regime through these fields, and the neutral
        # ranking makes the pipeline decision transferable across
        # device classes
        with resilience.cache_guard(op="attention"):
            sage.cache.put(
                key, entry_with_stats(decision, feat, base.full_name())
            )
    telemetry.emit_attention_decision(decision)
    return decision, "probe"


def attention_forward(sage: AutoSage, csr: CSR, q, k, v, seed: int = 0):
    """decide + prepare + run on the full graph; returns (out, decision)."""
    d = decide_attention(sage, csr, int(q.shape[1]), seed=seed)
    return sage.build_runner(csr, d)(q, k, v), d


# ---------------------------------------------------------------------
def probe_stage_breakdown(
    sage: AutoSage, csr: CSR, d: int, variant: registry.Variant, seed: int = 0,
    heads: int = 1,
) -> Dict[str, float]:
    """Median per-stage ms of ``variant`` on the probe subgraph.

    For composed pipelines the three stages run in each stage's own
    layout with its inputs pre-materialized, so the numbers isolate
    stage cost (mixed-layout conversion overhead is visible only in the
    end-to-end probe_ms, not here). The fused kernel is one stage.
    """
    sub = probe_mod.induced_subgraph(csr, frac=sage.probe_frac, seed=seed)
    q, k, v = default_probe_args("attention", d, seed, heads)(sub)
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

    def _med(fn, name):
        return probe_mod.time_callable(
            fn, iters=sage.probe_iters, cap_ms=sage.probe_cap_ms, name=name
        ).median_ms

    if variant.name == "fused_attention_pallas":
        run = variant.build(variant.prepare(sub))
        return {"fused": _med(lambda: run(q, k, v), "fused")}

    scale = 1.0 / (d ** 0.5)
    s_impl = variant.knobs.get("sddmm", "gather_dot")
    m_impl = variant.knobs.get("spmm", "gather_segsum")
    out: Dict[str, float] = {}

    csr_aux = registry._dev(kx.prepare_csr(sub))
    ell = (registry._prepare_attn_ell(sub)
           if "row_ell" in (s_impl, m_impl) else None)
    ell_colind = None if ell is None else jnp.asarray(ell["colind"])
    ell_mask = None if ell is None else jnp.asarray(ell["val"] != 0)

    # -- SDDMM stage (+ the softmax in the same layout)
    if s_impl == "row_ell":
        sddmm_fn = jax.jit(
            lambda q, k: jnp.einsum("nf,nkf->nk", q, k[ell_colind]) * scale
        )
        softmax_fn = jax.jit(lambda lg: kx.ell_masked_softmax(lg, ell_mask))
    else:
        sddmm_fn = jax.jit(lambda q, k: kx.sddmm_csr(csr_aux, q, k) * scale)
        softmax_fn = jax.jit(lambda lg: kx.row_softmax(csr_aux, lg))
    out["sddmm"] = _med(lambda: sddmm_fn(q, k), "sddmm")
    logits = jax.block_until_ready(sddmm_fn(q, k))
    out["softmax"] = _med(lambda: softmax_fn(logits), "softmax")
    probs = jax.block_until_ready(softmax_fn(logits))

    # -- value-SpMM stage, consuming probs in its own layout
    if m_impl == "row_ell":
        if probs.ndim == 1:  # CSR probs -> ELL table
            slots = kx.prepare_edge_slots(sub)
            er, es = jnp.asarray(slots["edge_row"]), jnp.asarray(slots["edge_slot"])
            probs = jax.block_until_ready(
                jnp.zeros(ell_colind.shape, probs.dtype).at[er, es].set(probs)
            )
        spmm_fn = jax.jit(
            lambda p, v: jnp.einsum("nk,nkf->nf", p, v[ell_colind].astype(p.dtype))
        )
    else:
        if probs.ndim == 2:  # ELL probs -> CSR values
            slots = kx.prepare_edge_slots(sub)
            er, es = jnp.asarray(slots["edge_row"]), jnp.asarray(slots["edge_slot"])
            probs = jax.block_until_ready(probs[er, es])
        spmm_fn = jax.jit(lambda p, v: kx.spmm_csr(csr_aux, p, v))
    out["spmm"] = _med(lambda: spmm_fn(probs, v), "spmm")
    return out
