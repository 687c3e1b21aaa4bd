"""Pure-jnp oracles for every kernel. Ground truth for tests and the
guardrail baseline semantics.

CSR device representation: rowptr int32[n+1], colind int32[nnz],
val float[nnz] (or None => ones). Multi-head operands carry heads as an
axis: dense (n, H, D), per-edge values (H, nnz), one row per head.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _row_ids(rowptr: jax.Array, nnz: int) -> jax.Array:
    """row id of each nnz entry, from rowptr."""
    return jnp.searchsorted(rowptr, jnp.arange(nnz, dtype=rowptr.dtype), side="right") - 1


def _per_edge(val: jax.Array, gathered: jax.Array) -> jax.Array:
    """Edge values against gathered rows: (nnz,) against (nnz, F), and
    (H, nnz) against (nnz, H, D)."""
    return (val.T if gathered.ndim == 3 else val)[..., None].astype(gathered.dtype)


def _heads_major(e: jax.Array) -> jax.Array:
    """Per-edge sums of (nnz,) or (nnz, H) as the edge values: (H, nnz)."""
    return e.T if e.ndim == 2 else e


def spmm_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    val: Optional[jax.Array],
    b: jax.Array,
) -> jax.Array:
    """C = A @ B for CSR A (n_rows x n_cols), dense B (n_cols x F)."""
    n_rows = rowptr.shape[0] - 1
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    gathered = b[colind]  # (nnz, F)
    if val is not None:
        gathered = gathered * _per_edge(val, gathered)
    return jax.ops.segment_sum(gathered, rows, num_segments=n_rows)


def sddmm_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    x: jax.Array,
    y: jax.Array,
) -> jax.Array:
    """A~_ij = <X_i, Y_j> for (i,j) in S(A); returns val-vector[nnz]."""
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    return _heads_major(jnp.sum(x[rows] * y[colind], axis=-1))


def row_softmax_ref(
    rowptr: jax.Array, colind: jax.Array, val: jax.Array
) -> jax.Array:
    """Numerically stable softmax within each CSR row (over its nnz);
    (H, nnz) values head by head."""
    if val.ndim == 2:
        return jnp.stack([row_softmax_ref(rowptr, colind, v) for v in val])
    n_rows = rowptr.shape[0] - 1
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    row_max = jax.ops.segment_max(val, rows, num_segments=n_rows)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    shifted = jnp.exp(val - row_max[rows])
    denom = jax.ops.segment_sum(shifted, rows, num_segments=n_rows)
    return shifted / jnp.maximum(denom[rows], 1e-30)


def csr_attention_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """SDDMM -> row-softmax -> SpMM (the paper's pipeline, §8.7)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = sddmm_ref(rowptr, colind, q, k) * scale
    probs = row_softmax_ref(rowptr, colind, logits)
    return spmm_ref(rowptr, colind, probs, v)


# ---- backward oracles (ground truth for core/autodiff.py) ------------
# Closed-form VJPs of the forward oracles, written with the same
# segment-op primitives. These are what tests/test_autodiff.py checks the
# scheduled custom_vjp gradients against, and they document the math each
# grad op lowers to: SpMM's backward is an SDDMM (grad w.r.t. vals) plus
# a transposed SpMM (grad w.r.t. B) — expressed here as a segment-sum
# over colind, which IS A^T @ grad without materializing the transpose.


def spmm_bwd_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    val: Optional[jax.Array],
    b: jax.Array,
    grad_c: jax.Array,
) -> tuple:
    """VJP of spmm_ref w.r.t. (val, b): returns (grad_val[nnz], grad_b)."""
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    # dL/dval_ij = <grad_C_i, B_j>  (an SDDMM on the forward pattern)
    grad_val = _heads_major(jnp.sum(grad_c[rows] * b[colind], axis=-1))
    # dL/dB_j = sum_i val_ij * grad_C_i  (SpMM on the transposed CSR)
    contrib = grad_c[rows]
    if val is not None:
        contrib = contrib * _per_edge(val, contrib)
    grad_b = jax.ops.segment_sum(contrib, colind, num_segments=b.shape[0])
    return grad_val, grad_b


def sddmm_bwd_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    x: jax.Array,
    y: jax.Array,
    grad_e: jax.Array,
) -> tuple:
    """VJP of sddmm_ref w.r.t. (x, y): two SpMMs whose sparse values are
    the per-edge cotangent — one on A, one on A^T."""
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    gy, gx = y[colind], x[rows]
    grad_x = jax.ops.segment_sum(_per_edge(grad_e, gy) * gy, rows, num_segments=x.shape[0])
    grad_y = jax.ops.segment_sum(_per_edge(grad_e, gx) * gx, colind, num_segments=y.shape[0])
    return grad_x, grad_y


def row_softmax_bwd_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    probs: jax.Array,
    grad_probs: jax.Array,
) -> jax.Array:
    """VJP of row_softmax_ref given its *output* probs: per row,
    grad_logits = p * (grad_p - <p, grad_p>); (H, nnz) values head by
    head."""
    if probs.ndim == 2:
        return jnp.stack([row_softmax_bwd_ref(rowptr, colind, p, g)
                          for p, g in zip(probs, grad_probs)])
    n_rows = rowptr.shape[0] - 1
    nnz = colind.shape[0]
    rows = _row_ids(rowptr, nnz)
    tmp = probs * grad_probs
    row_dot = jax.ops.segment_sum(tmp, rows, num_segments=n_rows)
    return tmp - probs * row_dot[rows]


def csr_attention_bwd_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    grad_out: jax.Array,
    scale: Optional[float] = None,
) -> tuple:
    """VJP of csr_attention_ref w.r.t. (q, k, v): recompute probs, then
    compose spmm/sddmm/softmax backward pieces."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = sddmm_ref(rowptr, colind, q, k) * scale
    probs = row_softmax_ref(rowptr, colind, logits)
    # out = SpMM(A(probs), v): grads w.r.t. probs (per edge) and v
    grad_probs, grad_v = spmm_bwd_ref(rowptr, colind, probs, v, grad_out)
    grad_logits = row_softmax_bwd_ref(rowptr, colind, probs, grad_probs)
    grad_q, grad_k = sddmm_bwd_ref(rowptr, colind, q, k, grad_logits * scale)
    return grad_q, grad_k, grad_v


# ---- block-ELL oracles (TPU-native format; DESIGN.md §2) -------------


def spmm_block_ell_ref(
    colblk: jax.Array,  # int32 (nrb, W)
    vals: jax.Array,  # f32 (nrb, W, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F), pre-padded
    bc: int,
) -> jax.Array:
    """Returns (nrb*rb, F). Padded slots have zero vals => no masking."""
    n_col_blocks = b.shape[0] // bc
    b_blocks = b.reshape(n_col_blocks, bc, b.shape[1])
    gathered = b_blocks[colblk]  # (nrb, W, bc, F)
    out = jnp.einsum("swrb,swbf->srf", vals, gathered.astype(vals.dtype))
    return out.reshape(-1, b.shape[1])


def sddmm_block_ell_ref(
    colblk: jax.Array,
    mask: jax.Array,  # (nrb, W, rb, bc) structural 0/1 (incl. slot padding)
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    bc: int,
) -> jax.Array:
    """Block-ELL SDDMM: per stored micro-tile, X_i @ Y_j^T, masked."""
    nrb, w = colblk.shape
    rb = mask.shape[2]
    xb = x.reshape(nrb, rb, x.shape[1])
    yb = y.reshape(-1, bc, y.shape[1])[colblk]  # (nrb, W, bc, F)
    tiles = jnp.einsum("srf,swbf->swrb", xb, yb)
    return tiles * mask


def row_softmax_block_ell_ref(
    vals: jax.Array,  # (nrb, W, rb, bc) logits
    mask: jax.Array,  # structural mask, same shape
) -> jax.Array:
    """Softmax per padded row (axis over (W, bc)), masked positions -> 0."""
    neg = jnp.finfo(vals.dtype).min
    masked = jnp.where(mask > 0, vals, neg)
    m = jnp.max(masked, axis=(1, 3), keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(masked - m) * (mask > 0)
    denom = jnp.sum(e, axis=(1, 3), keepdims=True)
    return e / jnp.maximum(denom, 1e-30)


def spmm_ragged_ell_ref(
    slot_rowblk: jax.Array,  # int32 (n_slots,)
    slot_colblk: jax.Array,  # int32 (n_slots,)
    slot_vals: jax.Array,  # f32 (n_slots, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F), pre-padded
    n_row_blocks: int,
    bc: int,
) -> jax.Array:
    """Slot-compacted SpMM oracle: returns (n_row_blocks*rb, F)."""
    rb = slot_vals.shape[1]
    b_blocks = b.reshape(-1, bc, b.shape[1])
    gathered = b_blocks[slot_colblk]  # (S, bc, F)
    tiles = jnp.einsum("srb,sbf->srf", slot_vals, gathered.astype(slot_vals.dtype))
    out = jax.ops.segment_sum(tiles, slot_rowblk, num_segments=n_row_blocks)
    return out.reshape(n_row_blocks * rb, b.shape[1])


def sddmm_ragged_ell_ref(
    slot_rowblk: jax.Array,
    slot_colblk: jax.Array,
    mask: jax.Array,  # (n_slots, rb, bc) structural 0/1
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    bc: int,
) -> jax.Array:
    """Slot-compacted SDDMM oracle: per-slot masked X_i @ Y_j^T tiles."""
    rb = mask.shape[1]
    xb = x.reshape(-1, rb, x.shape[1])[slot_rowblk]  # (S, rb, F)
    yb = y.reshape(-1, bc, y.shape[1])[slot_colblk]  # (S, bc, F)
    tiles = jnp.einsum("srf,sbf->srb", xb, yb)
    return tiles * mask


def spmm_merge_path_ref(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_colblk: jax.Array,  # int32 (padded_slots,) tail-padded
    tile_vals: jax.Array,  # f32 (n_tiles, tile_slots, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F), pre-padded
    n_slots: int,
    bc: int,
) -> jax.Array:
    """Merge-path SpMM oracle: the tiling is a pure reshape of the ragged
    slot stream, so the oracle is the ragged oracle on the unpadded
    slots, with slot row blocks recovered from blkptr."""
    n_row_blocks = blkptr.shape[0] - 1
    rb = tile_vals.shape[2]
    slot_vals = tile_vals.reshape(-1, rb, tile_vals.shape[3])[:n_slots]
    slot_rowblk = (
        jnp.searchsorted(
            blkptr, jnp.arange(n_slots, dtype=blkptr.dtype), side="right"
        )
        - 1
    )
    return spmm_ragged_ell_ref(
        slot_rowblk, slot_colblk[:n_slots], slot_vals, b, n_row_blocks, bc
    )


def sddmm_merge_path_ref(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_colblk: jax.Array,  # int32 (padded_slots,) tail-padded
    tile_mask: jax.Array,  # f32 (n_tiles, tile_slots, rb, bc)
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    n_slots: int,
    bc: int,
) -> jax.Array:
    """Merge-path SDDMM oracle: ragged oracle over the unpadded slots."""
    rb = tile_mask.shape[2]
    mask = tile_mask.reshape(-1, rb, tile_mask.shape[3])[:n_slots]
    slot_rowblk = (
        jnp.searchsorted(
            blkptr, jnp.arange(n_slots, dtype=blkptr.dtype), side="right"
        )
        - 1
    )
    return sddmm_ragged_ell_ref(slot_rowblk, slot_colblk[:n_slots], mask, x, y, bc)


def csr_attention_block_ell_ref(
    colblk: jax.Array,
    mask: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bc: int,
    scale: Optional[float] = None,
) -> jax.Array:
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = sddmm_block_ell_ref(colblk, mask, q, k, bc) * scale
    probs = row_softmax_block_ell_ref(logits, mask)
    return spmm_block_ell_ref(colblk, probs, v, bc)
