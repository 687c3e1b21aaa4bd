"""Differentiable scheduled sparse ops: `jax.custom_vjp` wrappers whose
backward passes are first-class scheduled ops.

Forward-only scheduling covers at most half a training step — the
backward of every sparse op is itself a sparse op with *different* shapes
and inverted skew (SpMM's backward is an SDDMM on the forward pattern
plus an SpMM on the transposed CSR, whose degree distribution is the
in-degree histogram, not the out-degree one). Each backward op therefore
gets its own decision: its own `InputFeatures`, cache key (distinct `op`
strings like "spmm_bwd_b" with the cotangent-side F), `ScheduleBucket`,
and the full estimate -> probe -> guardrail -> cache/replay path through
`AutoSage.decide` or `BatchScheduler.decide`. Op taxonomy (which compute
family each grad op draws candidates from, and whether its sparse values
are a runtime operand) lives in core/features.py; the dynamic-vals
variant family in core/registry.py.

Layout amortization: the transposed CSR comes from the memoized
`CSR.transpose_with_perm()` (sparse/csr.py), and `build_runner` memoizes
the prepared backward layout per (transposed graph, op, choice) — after
step 1 a training loop re-converts nothing.

Entry point for users is the `repro.api` facade; models/gnn.py routes
through it.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.kernels import xla as kx
from repro.sparse.csr import CSR


def _scheduled(sched, csr: CSR, f: int, op: str, *args, heads: int = 1):
    """decide + (memoized) prepare + run one scheduled op; ``f`` is the
    width per head, and ``heads`` > 1 runs (n, heads, f) operands.

    The ``fwd.<op>``/``bwd.<op>`` obs span times the host side: under
    jit, trace time (decide, probe, prepare and the tracing of the
    runner), the scheduler overhead the flight recorder audits. The
    device time of the op is named, not timed, here: the runner is
    called under `obs.op_scope`, a named scope
    ``autosage.<fwd|bwd>.<op>.f<F>.<choice>`` (``reference`` for the
    rescue) that every op it traces carries in the compiled program's
    ``op_name`` metadata, so a profile's device ops can be summed by
    direction, op, width and choice. A fallback stage of the chain
    nests its own scope inside (`resilience.chain_runner`).
    """
    kind = "bwd" if "_bwd" in op else "fwd"
    with obs.span(f"{kind}.{op}", op=op, heads=heads):
        try:
            d = sched.decide(csr, int(f), op, heads=heads)
            runner = sched.build_runner(csr, d)
        except Exception as exc:
            # defense in depth for non-AutoSage scheds (duck-typed custom
            # schedulers have no fallback chain of their own): a training
            # step's bwd op must never die on a scheduling fault. The
            # reference oracle is always runnable. ReplayMiss stays loud
            # — the replay contract forbids silent substitution — and so
            # does a scheduler that cannot decide the op at all.
            from repro.core import resilience
            from repro.core.cache import ReplayMiss

            if (isinstance(exc, (ReplayMiss, NotImplementedError))
                    or not resilience.enabled()):
                raise
            resilience.record_fault("decide", "", op, exc)
            resilience.record_fallback("scheduler", "reference", op)
            runner = resilience.reference_runner(csr, op)
            with obs.op_scope(kind, op, f, "reference"):
                return runner(*args)
        with obs.op_scope(kind, op, f, d.choice):
            return runner(*args)


# ----------------------------------------------------------------- SpMM
def spmm(csr: CSR, b: jax.Array, *, sched, vals: Optional[jax.Array] = None):
    """C = A @ B through the scheduler, differentiable.

    vals=None (the GNN training path): A's stored values are constants,
    the forward runs the baked scheduled runner, and the only cotangent
    is grad_B — one scheduled SpMM over the memoized transpose under
    op="spmm_bwd_b" (no wasted SDDMM for a grad nobody asked for).

    vals given: runtime edge values (a jax array; may be traced). The
    forward runs the dynamic-vals family (op="spmm_dyn") and the backward
    returns both cotangents: grad_vals is a scheduled SDDMM on the
    forward pattern (op="spmm_bwd_vals"), grad_B a dynamic-vals SpMM on
    the transpose (op="spmm_bwd_b_dyn") with the permuted cotangent
    values.
    """
    if vals is None:
        @jax.custom_vjp
        def _f(b):
            return _scheduled(sched, csr, b.shape[1], "spmm", b)

        def _fwd(b):
            return _f(b), None

        def _bwd(_, g):
            t, _ = csr.transpose_with_perm()
            gb = _scheduled(sched, t, g.shape[1], "spmm_bwd_b", g)
            return (gb.astype(g.dtype),)

        _f.defvjp(_fwd, _bwd)
        return _f(b)

    vals = jnp.asarray(vals)
    s = csr.structural()

    @jax.custom_vjp
    def _f(vals, b):
        return _scheduled(sched, s, b.shape[1], "spmm_dyn", vals, b)

    def _fwd(vals, b):
        return _f(vals, b), (vals, b)

    def _bwd(res, g):
        vals_r, b_r = res
        gv = _scheduled(sched, s, b_r.shape[1], "spmm_bwd_vals", g, b_r)
        t, perm = s.transpose_with_perm()
        gb = _scheduled(
            sched, t, g.shape[1], "spmm_bwd_b_dyn", vals_r[perm], g
        )
        return gv.astype(vals_r.dtype), gb.astype(b_r.dtype)

    _f.defvjp(_fwd, _bwd)
    return _f(vals, b)


# ---------------------------------------------------------------- SDDMM
def sddmm(csr: CSR, x: jax.Array, y: jax.Array, *, sched):
    """Per-edge <X_i, Y_j> on S(A) through the scheduler, differentiable.

    The backward scatters the per-edge cotangent through the pattern:
    grad_X = A(g) @ Y (op="sddmm_bwd_x"), grad_Y = A^T(g) @ X
    (op="sddmm_bwd_y") — both dynamic-vals SpMMs, since g is a traced
    cotangent that changes every step while the prepared layout does not.
    """
    s = csr.structural()

    @jax.custom_vjp
    def _f(x, y):
        return _scheduled(sched, s, x.shape[1], "sddmm", x, y)

    def _fwd(x, y):
        return _f(x, y), (x, y)

    def _bwd(res, g):
        x_r, y_r = res
        gx = _scheduled(sched, s, y_r.shape[1], "sddmm_bwd_x", g, y_r)
        t, perm = s.transpose_with_perm()
        gy = _scheduled(sched, t, x_r.shape[1], "sddmm_bwd_y", g[perm], x_r)
        return gx.astype(x_r.dtype), gy.astype(y_r.dtype)

    _f.defvjp(_fwd, _bwd)
    return _f(x, y)


# ------------------------------------------------------------ attention
def _row_ids(s: CSR) -> Dict:
    """``rowptr`` and the prepared ``edge_row`` of ``s`` for the backward's
    softmax pieces (`kx.row_softmax`, `kx.row_softmax_bwd`), made once
    per graph: every layer's backward closes over the same device array."""
    memo = getattr(s, "_row_ids_memo", None)
    if memo is None:
        with jax.ensure_compile_time_eval():
            memo = {"rowptr": np.asarray(s.rowptr, np.int32),
                    "edge_row": jnp.asarray(kx.edge_rows(s))}
        object.__setattr__(s, "_row_ids_memo", memo)
    return memo


def attention(csr: CSR, q: jax.Array, k: jax.Array, v: jax.Array, *, sched):
    """CSR attention (SDDMM -> row-softmax -> SpMM), decided as one
    op="attention" pipeline by the scheduler, differentiable.

    q/k/v are (n, D) for one head or (n, H, D) for H heads computed as
    one op, with (H, nnz) edge values in every piece and one decision
    per (graph, H, D). The forward is the joint op="attention" decision
    (fused Pallas kernel or a composed 3-kernel pipeline). There is no
    fused backward kernel, so the backward decomposes into its sparse
    pieces, each scheduled in its own right: logits recompute and
    grad-of-probs are pattern-only SDDMMs ("attention_bwd_e" /
    "attention_bwd_p"), the q/k/v grads are dynamic-vals SpMMs
    ("attention_bwd_q"/"_k"/"_v") whose sparse values are the probs /
    softmax-VJP'd logits; the softmax and its VJP are segment ops on the
    prepared row ids, each under its own scope
    (``autosage.bwd.attention_softmax…``, ``…attention_softmax_bwd…``).
    Scale is the pipeline's default 1/sqrt(D).
    """
    s = csr.structural()
    heads = 1 if q.ndim == 2 else int(q.shape[1])
    d = int(q.shape[-1])

    @jax.custom_vjp
    def _f(q, k, v):
        return _scheduled(sched, s, d, "attention", q, k, v, heads=heads)

    def _fwd(q, k, v):
        return _f(q, k, v), (q, k, v)

    def _bwd(res, g):
        # With heads, each piece's gathered (H*D)-wide rows are live only
        # inside it: the barriers keep the compiler from merging a piece's
        # gathers with identical ones of the forward or of another piece
        # (which would hold them from one to the other) and run the pieces
        # one after another.
        barrier = jax.lax.optimization_barrier if heads > 1 else (lambda a: a)
        q_r, k_r, v_r = barrier(res)
        scale = 1.0 / (d ** 0.5)
        rows = _row_ids(s)
        dv = int(g.shape[-1])
        # recompute the probs (the fused forward never materializes them)
        e = _scheduled(sched, s, d, "attention_bwd_e", q_r, k_r, heads=heads)
        with obs.op_scope("bwd", "attention_softmax", d, "baseline"):
            probs = kx.row_softmax(rows, e * scale)
        t, perm = s.transpose_with_perm()
        # grad_V = A^T(probs) @ g
        probs, g = barrier((probs, g))
        gv = _scheduled(sched, t, dv, "attention_bwd_v", probs[..., perm], g,
                        heads=heads)
        # grad w.r.t. probs: per-edge <g_i, V_j>, then the softmax VJP
        gv, g, v_r = barrier((gv, g, v_r))
        gp = _scheduled(sched, s, dv, "attention_bwd_p", g, v_r, heads=heads)
        with obs.op_scope("bwd", "attention_softmax_bwd", d, "baseline"):
            gl = kx.row_softmax_bwd(rows, probs, gp) * scale
        gl, k_r = barrier((gl, k_r))
        gq = _scheduled(sched, s, d, "attention_bwd_q", gl, k_r, heads=heads)
        gq, gl, q_r = barrier((gq, gl, q_r))
        gk = _scheduled(sched, t, d, "attention_bwd_k", gl[..., perm], q_r,
                        heads=heads)
        return gq.astype(q_r.dtype), gk.astype(k_r.dtype), gv.astype(v_r.dtype)

    _f.defvjp(_fwd, _bwd)
    return _f(q, k, v)
