"""The public functional surface for scheduled sparse ops.

One call style for every op, replacing three divergent ones (see the
README deprecation table): graph first, dense operands next, scheduler
and options keyword-only.

    from repro import api
    c   = api.spmm(csr, b, sage=sage)          # scheduled + differentiable
    e   = api.sddmm(csr, x, y, sage=sage)
    out = api.attention(csr, q, k, v, sage=sage)

Routing, per call:

- ``sage=None`` — the pure-jnp reference oracles (kernels/ref.py). No
  scheduling, naturally differentiable through jax; the right default
  for tests and tiny graphs.
- ``sage`` given, ``differentiable=True`` (default) — the custom_vjp
  wrappers in core/autodiff.py: forward AND backward each run as
  first-class scheduled ops with their own cache keys ("spmm" and
  "spmm_bwd_b" are distinct decisions).
- ``sage`` given, ``differentiable=False`` — forward-only scheduling
  (decide + memoized runner), for inference / benchmarking where
  tracing a custom_vjp is wasted work.

``sage`` is anything exposing ``decide(csr, f, op)`` and
``build_runner(csr, decision)`` — the per-graph `AutoSage` or the
`BatchScheduler` that amortizes probing over a subgraph stream.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import autodiff
from repro.kernels import ref
from repro.sparse.csr import CSR

__all__ = ["spmm", "sddmm", "attention"]


def spmm(
    csr: CSR,
    b: jax.Array,
    *,
    sage=None,
    vals: Optional[jax.Array] = None,
    differentiable: bool = True,
) -> jax.Array:
    """C = A @ B for CSR A (n_rows x n_cols), dense B (n_cols x F).

    ``vals``: optional runtime edge values (jax array, may be traced —
    e.g. learned edge weights) overriding A's stored values; gradients
    flow to them. Without it, A's values are baked constants and only
    grad_B flows.
    """
    if sage is None:
        v = vals if vals is not None else (
            None if csr.val is None else jnp.asarray(csr.val)
        )
        return ref.spmm_ref(
            jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), v, b
        )
    if differentiable:
        return autodiff.spmm(csr, b, sched=sage, vals=vals)
    if vals is not None:
        return autodiff._scheduled(
            sage, csr.structural(), b.shape[1], "spmm_dyn",
            jnp.asarray(vals), b,
        )
    return autodiff._scheduled(sage, csr, b.shape[1], "spmm", b)


def sddmm(
    csr: CSR,
    x: jax.Array,
    y: jax.Array,
    *,
    sage=None,
    differentiable: bool = True,
) -> jax.Array:
    """A~_ij = <X_i, Y_j> for (i, j) in S(A); CSR-ordered nnz vector."""
    if sage is None:
        return ref.sddmm_ref(
            jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), x, y
        )
    if differentiable:
        return autodiff.sddmm(csr, x, y, sched=sage)
    return autodiff._scheduled(
        sage, csr.structural(), x.shape[1], "sddmm", x, y
    )


def attention(
    csr: CSR,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    sage=None,
    scale: Optional[float] = None,
    differentiable: bool = True,
) -> jax.Array:
    """CSR attention: SDDMM -> row-softmax -> SpMM on S(A).

    q/k/v are (n, D), one head, or (n, H, D): H heads in one op, each
    with its own softmax over a row's edges, and an (n, H, D) result.
    The scheduled path makes one joint pipeline-level decision per
    (graph, H, D) (composed 3-kernel candidates vs the fused Pallas
    kernel; only variants with a head axis apply at H > 1) and assumes
    the default ``scale = 1/sqrt(D)``; a custom ``scale`` routes to the
    reference pipeline (still differentiable) since the fused kernels
    bake the default.
    """
    if q.ndim == 3 and q.shape[1] == 1:  # one head: the (n, D) op
        return attention(csr, q[:, 0], k[:, 0], v[:, 0], sage=sage, scale=scale,
                         differentiable=differentiable)[:, None]
    if sage is None or scale is not None:
        return ref.csr_attention_ref(
            jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), q, k, v, scale
        )
    if differentiable:
        return autodiff.attention(csr, q, k, v, sched=sage)
    return autodiff._scheduled(
        sage, csr.structural(), q.shape[-1], "attention", q, k, v,
        heads=1 if q.ndim == 2 else q.shape[1],
    )
