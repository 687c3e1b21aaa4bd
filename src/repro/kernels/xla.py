"""XLA-native kernel variants.

These are (a) the guardrail *baseline* ("vendor kernel" role: what JAX/XLA
gives you without this work) and (b) additional scheduler candidates that
run on any backend. Each variant is a ``prepare`` (host-side format
conversion, done once and amortized — analogous to the paper's cache
warm-up) plus a jit-friendly ``run``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import F32_MXU, ref
from repro.sparse.csr import CSR


# ---------------------------------------------------------------- SpMM
def prepare_csr(csr: CSR) -> Dict[str, np.ndarray]:
    return {
        "rowptr": np.asarray(csr.rowptr, np.int32),
        "colind": np.asarray(csr.colind, np.int32),
        "val": csr.values_or_ones(np.float32),
    }


def spmm_gather_segsum(aux: Dict, b: jax.Array) -> jax.Array:
    """Baseline SpMM: gather + segment-sum (cuSPARSE stand-in)."""
    return ref.spmm_ref(aux["rowptr"], aux["colind"], aux["val"], b)


def prepare_dense(csr: CSR) -> Dict[str, np.ndarray]:
    return {"a": csr.to_dense()}


def spmm_dense(aux: Dict, b: jax.Array) -> jax.Array:
    """Densified matmul — wins only for tiny/dense A; estimate gates it."""
    return jnp.matmul(aux["a"], b.astype(aux["a"].dtype), precision=F32_MXU)


def prepare_row_ell(csr: CSR, k: int | None = None) -> Dict[str, np.ndarray]:
    """Pad every row to K slots (row-ELL). Padded slots: col 0, val 0."""
    deg = csr.degrees
    kmax = int(deg.max()) if deg.size else 1
    k = kmax if k is None else min(k, kmax)
    k = max(k, 1)
    n = csr.n_rows
    colind = np.zeros((n, k), np.int32)
    val = np.zeros((n, k), np.float32)
    v = csr.values_or_ones(np.float32)
    # vectorized scatter of the first k entries of each row
    take = np.minimum(deg, k)
    rows = np.repeat(np.arange(n), take)
    slot = np.arange(take.sum()) - np.repeat(
        np.concatenate([[0], np.cumsum(take)[:-1]]), take
    )
    pos = np.repeat(csr.rowptr[:-1], take) + slot
    colind[rows, slot] = csr.colind[pos]
    val[rows, slot] = v[pos]
    # overflow entries (deg > k) handled by caller choosing k = kmax;
    # truncating preparers must not be used for exact ops.
    assert int(take.sum()) == csr.nnz or k < kmax
    return {"colind": colind, "val": val}


def spmm_row_ell(aux: Dict, b: jax.Array) -> jax.Array:
    """ELL SpMM: uniform-width gather + dense reduce. Wins when degree
    variance is low (no tail padding); the 'warp-per-row, feature-tiled'
    analogue."""
    gathered = b[aux["colind"]]  # (n, K, F)
    return jnp.einsum("nk,nkf->nf", aux["val"], gathered.astype(aux["val"].dtype),
                      precision=F32_MXU)


def prepare_hub_split_ell(csr: CSR, hub_threshold: int) -> Dict[str, np.ndarray]:
    """Two ELL partitions split by degree (CTA-per-hub analogue)."""
    from repro.sparse.bsr import hub_split

    hub_rows, light_rows = hub_split(csr, hub_threshold)
    aux: Dict[str, np.ndarray] = {
        "hub_rows": hub_rows.astype(np.int32),
        "light_rows": light_rows.astype(np.int32),
        "n_rows": np.int32(csr.n_rows),
    }
    if hub_rows.size:
        sub = csr.row_slice(hub_rows)
        h = prepare_row_ell(sub)
        aux["hub_colind"], aux["hub_val"] = h["colind"], h["val"]
    if light_rows.size:
        sub = csr.row_slice(light_rows)
        l = prepare_row_ell(sub)
        aux["light_colind"], aux["light_val"] = l["colind"], l["val"]
    return aux


def spmm_hub_split_ell(aux: Dict, b: jax.Array) -> jax.Array:
    n = int(aux["n_rows"])
    out = jnp.zeros((n, b.shape[1]), jnp.float32)
    if "hub_colind" in aux:
        part = spmm_row_ell({"colind": aux["hub_colind"], "val": aux["hub_val"]}, b)
        out = out.at[aux["hub_rows"]].set(part)
    if "light_colind" in aux:
        part = spmm_row_ell(
            {"colind": aux["light_colind"], "val": aux["light_val"]}, b
        )
        out = out.at[aux["light_rows"]].set(part)
    return out


# --------------------------------------------------------------- SDDMM
def sddmm_gather_dot(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Paper's SDDMM baseline: gather both sides, dot."""
    return ref.sddmm_ref(aux["rowptr"], aux["colind"], x, y)


def sddmm_row_ell(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Row-ELL SDDMM: (n,K) uniform gather; returns padded (n,K) values.

    NOTE: returns ELL layout, converted back by the ops layer when CSR
    layout is required.
    """
    gathered = y[aux["colind"]]  # (n, K, F)
    out = jnp.einsum("nf,nkf->nk", x.astype(gathered.dtype), gathered,
                     precision=F32_MXU)
    return out * (aux["val"] != 0)


def row_softmax(aux: Dict, val: jax.Array) -> jax.Array:
    return ref.row_softmax_ref(aux["rowptr"], aux["colind"], val)


def csr_attention(
    aux: Dict, q: jax.Array, k: jax.Array, v: jax.Array, scale=None
) -> jax.Array:
    return ref.csr_attention_ref(aux["rowptr"], aux["colind"], q, k, v, scale)


# ------------------------------------------- composed attention pipelines
# The pipeline scheduler (core/pipeline.py) selects among these whole
# SDDMM -> row-softmax -> SpMM compositions; each stays in one sparse
# layout per stage, with explicit layout conversion for mixed pairs.

def prepare_edge_slots(csr: CSR) -> Dict[str, np.ndarray]:
    """(row, slot-within-row) of every nnz entry — the scatter/gather
    indices that convert per-edge CSR values to/from the (n, K) ELL table."""
    deg = csr.degrees
    rows = np.repeat(np.arange(csr.n_rows), deg).astype(np.int32)
    slot = (np.arange(csr.nnz) - np.repeat(csr.rowptr[:-1], deg)).astype(np.int32)
    return {"edge_row": rows, "edge_slot": slot}


def ell_masked_softmax(logits: jax.Array, mask: jax.Array) -> jax.Array:
    """Row softmax over the (n, K) ELL table; padded slots -> 0."""
    neg = jnp.finfo(logits.dtype).min
    masked = jnp.where(mask, logits, neg)
    m = jnp.max(masked, axis=1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(masked - m) * mask
    return e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)


def attention_csr(aux: Dict, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """gather_dot SDDMM -> CSR softmax -> gather_segsum SpMM (baseline)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = ref.sddmm_ref(aux["rowptr"], aux["colind"], q, k) * scale
    probs = ref.row_softmax_ref(aux["rowptr"], aux["colind"], logits)
    return ref.spmm_ref(aux["rowptr"], aux["colind"], probs, v)


def attention_ell(aux: Dict, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """row_ell SDDMM -> ELL softmax -> row_ell SpMM; uniform-width gathers
    throughout (wins when degree variance is low, as with spmm row_ell)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    colind = aux["colind"]  # (n, K)
    mask = aux["val"] != 0
    gathered_k = k[colind]  # (n, K, F)
    logits = jnp.einsum("nf,nkf->nk", q.astype(gathered_k.dtype), gathered_k,
                        precision=F32_MXU) * scale
    probs = ell_masked_softmax(logits, mask)
    return jnp.einsum("nk,nkf->nf", probs, v[colind].astype(probs.dtype),
                      precision=F32_MXU)


def attention_ell_to_csr(aux: Dict, q, k, v) -> jax.Array:
    """row_ell SDDMM/softmax -> (ELL->CSR gather) -> gather_segsum SpMM."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    colind = aux["ell_colind"]
    mask = aux["ell_val"] != 0
    gathered_k = k[colind]
    logits = jnp.einsum("nf,nkf->nk", q.astype(gathered_k.dtype), gathered_k,
                        precision=F32_MXU) * scale
    probs = ell_masked_softmax(logits, mask)
    probs_csr = probs[aux["edge_row"], aux["edge_slot"]]
    return ref.spmm_ref(aux["rowptr"], aux["colind"], probs_csr, v)


def attention_csr_to_ell(aux: Dict, q, k, v) -> jax.Array:
    """gather_dot SDDMM/softmax -> (CSR->ELL scatter) -> row_ell SpMM."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = ref.sddmm_ref(aux["rowptr"], aux["colind"], q, k) * scale
    probs = ref.row_softmax_ref(aux["rowptr"], aux["colind"], logits)
    ell_colind = aux["ell_colind"]  # (n, K)
    probs_ell = jnp.zeros(ell_colind.shape, probs.dtype).at[
        aux["edge_row"], aux["edge_slot"]
    ].set(probs)
    return jnp.einsum("nk,nkf->nf", probs_ell, v[ell_colind].astype(probs.dtype),
                      precision=F32_MXU)
