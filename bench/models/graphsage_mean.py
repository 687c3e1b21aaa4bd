"""Plain reference of full-graph GraphSAGE training (mean aggregator) with
plain SGD, written from the published equations and nothing of the
program:

    layer i:  Z_i = A (X_i W_agg,i) + X_i W_self,i
              X_{i+1} = relu(Z_i)            (every layer but the last)
    loss:     mean over all nodes of -log softmax(Z_last)[label]
    update:   W <- W - lr * dloss/dW

A is the row-normalised adjacency: A[r, c] = (edges r->c) / deg(r). The
backward pass is written out by hand (no autodiff), and every sparse
product is a scatter-add over the edges in fixed-size chunks, so that no
(nnz, F) operand is ever whole in memory.

Precision: the configuration's. Values are held in ``dtype`` and every
dense matmul runs at ``precision`` (JAX's name: ``"default"`` is one
bfloat16 pass on a TPU, ``"highest"`` full float32); every sum over edges
adds products of ``dtype``, never through a matmul. The stand-ins that
set the limits of ``correct``: ``dtype=bfloat16`` holds and computes
everything (weights, features, edge values, activations, gradients and
the update) in bfloat16, the precision below the configuration's float32;
``spmm_operand=bfloat16`` rounds only the operands of each sum over edges
(the edge values and the dense matrix) to bfloat16 and adds their
products in float32, as a one-pass bfloat16 contraction of the SpMM would.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 20  # edges per scatter-add


def edges(rowptr: np.ndarray, colind: np.ndarray):
    """(rows, cols, vals) of every edge with vals = 1/deg(row), padded
    with zero-valued edges of node 0 to a whole number of chunks."""
    deg = np.diff(rowptr).astype(np.int64)
    n = deg.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), deg)
    vals = (1.0 / np.maximum(deg, 1)).astype(np.float32)[rows]
    pad = -rows.shape[0] % CHUNK
    z = np.zeros(pad, np.int32)
    return (np.concatenate([rows, z]), np.concatenate([colind.astype(np.int32), z]),
            np.concatenate([vals, np.zeros(pad, np.float32)]))


def scatter_spmm(dst, src, vals, b, n_out: int, operand=None):
    """out[dst[e]] += vals[e] * b[src[e]] over every edge, chunk by chunk;
    with ``operand`` ("bfloat16"), vals and b are first rounded to it.
    (``reduce_precision`` rounds where a cast there and back would not: a
    compiler may drop such a pair of casts.)"""
    n_chunks = dst.shape[0] // CHUNK
    if operand is not None:
        f = jnp.finfo(operand)
        vals, b = (jax.lax.reduce_precision(a, f.nexp, f.nmant) for a in (vals, b))

    def body(i, acc):
        d = jax.lax.dynamic_slice(dst, (i * CHUNK,), (CHUNK,))
        s = jax.lax.dynamic_slice(src, (i * CHUNK,), (CHUNK,))
        v = jax.lax.dynamic_slice(vals, (i * CHUNK,), (CHUNK,))
        return acc.at[d].add(b[s] * v[:, None])

    init = jnp.zeros((n_out, b.shape[1]), b.dtype)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _step(params: Dict[str, List[jax.Array]], x, y, rows, cols, vals, lr,
          half_batch: bool, spmm_operand):
    """One SGD step: (loss, gradients, new parameters)."""
    n = x.shape[0]
    n_layers = len(params["w_agg"])
    xs, zs = [], []
    h = x
    for i in range(n_layers):
        xs.append(h)
        agg = scatter_spmm(rows, cols, vals, h @ params["w_agg"][i], n, spmm_operand)
        z = agg + h @ params["w_self"][i]
        zs.append(z)
        h = jax.nn.relu(z) if i < n_layers - 1 else z
    # loss over every node; the planted fault of a half batch keeps the
    # first half and takes the mean over it
    m = n // 2 if half_batch else n
    logits, labels = zs[-1][:m], y[:m]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
    onehot = jax.nn.one_hot(labels, logits.shape[1], dtype=logits.dtype)
    dz = (jnp.exp(logp) - onehot) / jnp.asarray(m, logits.dtype)
    dz = jnp.concatenate([dz, jnp.zeros((n - m, dz.shape[1]), dz.dtype)])
    grads = {"w_agg": [None] * n_layers, "w_self": [None] * n_layers}
    for i in reversed(range(n_layers)):
        grads["w_self"][i] = xs[i].T @ dz
        dh = scatter_spmm(cols, rows, vals, dz, n, spmm_operand)  # A^T dz
        grads["w_agg"][i] = xs[i].T @ dh
        if i > 0:
            dx = dh @ params["w_agg"][i].T + dz @ params["w_self"][i].T
            dz = dx * (zs[i - 1] > 0).astype(dx.dtype)
    new = jax.tree.map(lambda p, g: p - jnp.asarray(lr, p.dtype) * g, params, grads)
    return loss, grads, new


@functools.partial(jax.jit, static_argnames=("steps", "half_batch", "spmm_operand"))
def _train(params, x, y, rows, cols, vals, lr, steps: int, half_batch: bool, spmm_operand):
    losses, states, first_grads = [], [], None
    for _ in range(steps):
        loss, grads, params = _step(params, x, y, rows, cols, vals, lr, half_batch,
                                    spmm_operand)
        losses.append(loss)
        states.append(params)
        first_grads = grads if first_grads is None else first_grads
    return jnp.stack(losses), first_grads, states


def train(params, x, y, rowptr: np.ndarray, colind: np.ndarray, lr: float,
          steps: int, precision: str, dtype=jnp.float32, spmm_operand=None,
          half_batch: bool = False) -> Tuple[np.ndarray, Dict, List[Dict]]:
    """``steps`` SGD steps from ``params``: the loss of each step (float64
    on the host), the gradients of the first step, and the parameters
    after each step, in ``dtype`` on the host."""
    rows, cols, vals = edges(rowptr, colind)
    cast = lambda a: jnp.asarray(a, dtype)
    params = jax.tree.map(cast, params)
    with jax.default_matmul_precision(precision):
        losses, grads, states = _train(
            params, cast(x), jnp.asarray(y), jnp.asarray(rows), jnp.asarray(cols),
            cast(vals), lr, steps=steps, half_batch=half_batch, spmm_operand=spmm_operand)
    grads, states = jax.device_get((grads, states))
    return np.asarray(losses, np.float64), grads, states
