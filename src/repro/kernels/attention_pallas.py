"""Pallas TPU kernel: fused sparse CSR attention (beyond-paper).

The paper composes SDDMM -> row-softmax -> SpMM as three kernels, which
round-trips the (nrb, W, rb, bc) logits/probs through HBM twice. On TPU
the natural improvement is a flash-style fusion: one grid pass over
(row_block, ell_slot) with an online-softmax carried in VMEM scratch —
logits never touch HBM. This is the optimized variant registered next to
the faithful 3-kernel pipeline; the scheduler chooses between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import F32_MXU


def _fused_attn_kernel(
    colblk_ref, q_ref, k_ref, v_ref, mask_ref, out_ref,
    m_scr, l_scr, acc_scr, *, scale, n_slots,
):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]  # (rb, D)
    k = k_ref[...]  # (bc, D)
    mask = mask_ref[0, 0]  # (rb, bc)
    logits = jnp.dot(
        q, k.T, preferred_element_type=jnp.float32, precision=F32_MXU
    ) * scale
    logits = jnp.where(mask > 0, logits, -jnp.inf)

    m_prev = m_scr[:, :1]  # (rb, 1)
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked-so-far rows
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe) * (mask > 0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=F32_MXU,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(s == n_slots - 1)
    def _finish():
        out_ref[...] = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def fused_csr_attention(
    colblk: jax.Array,  # int32 (nrb, W)
    mask: jax.Array,  # f32 (nrb, W, rb, bc)
    q: jax.Array,  # (nrb*rb, D)
    k: jax.Array,  # (n_col_blocks*bc, D)
    v: jax.Array,  # (n_col_blocks*bc, D)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    nrb, w, rb, bc = mask.shape
    d = q.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    grid = (nrb, w)

    return pl.pallas_call(
        functools.partial(_fused_attn_kernel, scale=scale, n_slots=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rb, d), lambda i, s, cb: (i, 0)),
                pl.BlockSpec((bc, d), lambda i, s, cb: (cb[i, s], 0)),
                pl.BlockSpec((bc, d), lambda i, s, cb: (cb[i, s], 0)),
                pl.BlockSpec((1, 1, rb, bc), lambda i, s, cb: (i, s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((rb, d), lambda i, s, cb: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((rb, 128), jnp.float32),
                pltpu.VMEM((rb, 128), jnp.float32),
                pltpu.VMEM((rb, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * rb, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(colblk, q, k, v, mask)


def _fused_ragged_attn_kernel(
    blkptr_ref, rowblk_ref, colblk_ref, q_ref, k_ref, v_ref, mask_ref,
    out_ref, m_scr, l_scr, acc_scr, *, scale,
):
    s = pl.program_id(0)
    i = rowblk_ref[s]

    @pl.when(s == blkptr_ref[i])
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]  # (rb, D)
    k = k_ref[...]  # (bc, D)
    mask = mask_ref[0]  # (rb, bc)
    logits = jnp.dot(
        q, k.T, preferred_element_type=jnp.float32, precision=F32_MXU
    ) * scale
    logits = jnp.where(mask > 0, logits, -jnp.inf)

    m_prev = m_scr[:, :1]  # (rb, 1)
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked-so-far rows (incl. the dummy slot of an empty
    # row block, whose mask is all zero: out falls through to 0)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe) * (mask > 0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=F32_MXU,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(s == blkptr_ref[i + 1] - 1)
    def _finish():
        out_ref[...] = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def fused_ragged_attention(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_rowblk: jax.Array,  # int32 (n_slots,)
    slot_colblk: jax.Array,  # int32 (n_slots,)
    mask: jax.Array,  # f32 (n_slots, rb, bc)
    q: jax.Array,  # (nrb*rb, D)
    k: jax.Array,  # (n_col_blocks*bc, D)
    v: jax.Array,  # (n_col_blocks*bc, D)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Slot-compacted fused attention: grid = (n_slots,) over actual
    slots instead of (row_blocks, W). The online-softmax state lives in
    VMEM scratch across the slots of one row block; `blkptr` gives both
    the init (first slot of block) and emit (last slot of block)
    conditions. A hub row block streams its many K/V tiles while a light
    row block finishes after one — no W-padded zero work.
    """
    n_slots, rb, bc = mask.shape
    nrb = blkptr.shape[0] - 1
    d = q.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if nrb == 0 or n_slots == 0:
        return jnp.zeros((nrb * rb, d), jnp.float32)
    grid = (n_slots,)

    return pl.pallas_call(
        functools.partial(_fused_ragged_attn_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rb, d), lambda s, bp, rbk, cb: (rbk[s], 0)),
                pl.BlockSpec((bc, d), lambda s, bp, rbk, cb: (cb[s], 0)),
                pl.BlockSpec((bc, d), lambda s, bp, rbk, cb: (cb[s], 0)),
                pl.BlockSpec((1, rb, bc), lambda s, bp, rbk, cb: (s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((rb, d), lambda s, bp, rbk, cb: (rbk[s], 0)),
            scratch_shapes=[
                pltpu.VMEM((rb, 128), jnp.float32),
                pltpu.VMEM((rb, 128), jnp.float32),
                pltpu.VMEM((rb, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * rb, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(blkptr, slot_rowblk, slot_colblk, q, k, v, mask)
