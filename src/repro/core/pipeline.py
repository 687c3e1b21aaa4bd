"""Per-stage timing diagnostic for CSR attention decisions.

`AutoSage.decide(csr, d, "attention")` decides CSR attention as one op
over whole pipelines: the composed {sddmm x softmax x spmm} candidates
and the fused Pallas kernel (core/registry.py), probed end to end.
`probe_stage_breakdown` times the stages of one such variant on the
probe subgraph, for the tables and diagnostics that want to see where a
pipeline's time goes.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import probe as probe_mod
from repro.core import registry
from repro.core.scheduler import AutoSage, default_probe_args
from repro.kernels import xla as kx
from repro.sparse.csr import CSR


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
