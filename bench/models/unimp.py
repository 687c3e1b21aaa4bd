"""Plain reference of full-graph UniMP training with plain SGD, written
from the published equations (Shi et al., arXiv:2009.03509; PyG's
``TransformerConv(heads, beta=True, root_weight=True)`` and its UniMP
example) and nothing of the program:

    input:    h = x + E[y] on the label-fed nodes, x elsewhere
    layer l, head c of H (width D):
              q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v, head c's columns
              a_ij = exp(s_ij) / sum_{j' in N(i)} exp(s_ij'),  s_ij = q_i . k_j / sqrt(D)
              m_i = sum_{j in N(i)} a_ij v_j
              m = heads side by side (every layer but the last), their mean (last)
              r = h W_skip + b_skip;  b = sigmoid([m, r, m - r] w_beta)
              z = b r + (1 - b) m
              h = relu(LayerNorm(z))           (every layer but the last)
    loss:     mean over the nodes not label-fed of -log softmax(z_last)[y]
    update:   W <- W - lr * dloss/dW

N(i) is row i of the CSR pattern, an edge counted as often as it
appears. Each head is its own loop iteration, and within it each block
of whole rows (at most BLOCK_EDGES edges, or one row) its own, with
row ids found by a binary search of rowptr; gradients come from
`jax.grad`, each block's edge work under `jax.checkpoint`, so that one
block's (edges, D) operands are live at a time.

Precision: the configuration's. Values are held in ``dtype`` and every
dense matmul runs at ``precision`` (JAX's name: ``"default"`` is one
bfloat16 pass on a TPU, ``"highest"`` full float32); every sum over
edges adds values of ``dtype``, never through a matmul. The stand-ins
that set the limits of ``correct``: ``dtype=bfloat16`` holds and
computes everything in bfloat16, the precision below the configuration's
float32; ``attn_operand=bfloat16`` rounds only the operands of the edge
sums (q, k, v and the attention weights) to bfloat16, as one-pass
bfloat16 SDDMM and SpMM contractions would; ``half_batch`` takes the
loss over the first half of the loss nodes only.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# edges of a block of rows: a block's gathered (edges, D) rows take 1 GiB
# (D padded to 128 lanes on a TPU), and the step about 7.7 GB at
# ogbn-products' mean degree and 6.2M edges
BLOCK_EDGES = 1 << 21


def _round(a, operand):
    """``a`` rounded to ``operand``'s precision (``reduce_precision``
    rounds where a cast there and back would not: a compiler may drop
    such a pair of casts)."""
    if operand is None:
        return a
    f = jnp.finfo(operand)
    return jax.lax.reduce_precision(a, f.nexp, f.nmant)


def block_attention(q, k, v, rows, cols, r0: int, r1: int, operand=None):
    """One head on rows [r0, r1), whose edges are ``rows`` and ``cols``:
    sum over each row's edges of softmax(q_i . k_j / sqrt(D)) v_j, for
    (n, D) q, k, v."""
    q, k, v = (_round(a, operand) for a in (q, k, v))
    s = jnp.sum(q[rows] * k[cols], axis=-1) / jnp.sqrt(jnp.asarray(q.shape[1], q.dtype))
    local, n = rows - r0, r1 - r0
    # softmax is unchanged by a shift, so the row max carries no gradient
    top = jax.lax.stop_gradient(jax.ops.segment_max(s, local, num_segments=n))
    e = jnp.exp(s - top[local])
    a = e / jax.ops.segment_sum(e, local, num_segments=n)[local]
    return jax.ops.segment_sum(_round(a, operand)[:, None] * v[cols], local, num_segments=n)


def head_attention(q, k, v, rows, cols, blocks, operand=None):
    """One head over every row, block of rows by block of rows, each
    after the last (the barrier), forward and backward."""
    out = []
    for r0, r1, e0, e1 in blocks:
        block = jax.checkpoint(functools.partial(block_attention, r0=r0, r1=r1, operand=operand))
        m = block(q, k, v, rows[e0:e1], cols[e0:e1])
        m, q, k, v = jax.lax.optimization_barrier((m, q, k, v))
        out.append(m)
    return jnp.concatenate(out)


def row_blocks(rowptr: np.ndarray, block_edges: int) -> Tuple[Tuple[int, int, int, int], ...]:
    """(first row, end row, first edge, end edge) of consecutive blocks of
    whole rows, each of at most ``block_edges`` edges or a single row."""
    blocks, r0, n = [], 0, len(rowptr) - 1
    while r0 < n:
        r1 = max(r0 + 1, int(np.searchsorted(rowptr, rowptr[r0] + block_edges, "right")) - 1)
        r1 = min(r1, n)
        blocks.append((r0, r1, int(rowptr[r0]), int(rowptr[r1])))
        r0 = r1
    return tuple(blocks)


def layer_norm(z, eps: float = 1e-5):
    mu = jnp.mean(z, axis=-1, keepdims=True)
    var = jnp.mean((z - mu) ** 2, axis=-1, keepdims=True)
    return (z - mu) / jnp.sqrt(var + eps)


def forward(params: Dict, x, y, label_mask, rows, cols, heads: int, blocks, operand=None):
    """Logits (n, classes) of every node."""
    h = jnp.where(label_mask[:, None], x + params["emb"][y], x)
    att = functools.partial(head_attention, blocks=blocks, operand=operand)
    n_layers = len(params["layers"])
    for i, p in enumerate(params["layers"]):
        q, k, v = (h @ p[f"w{c}"] + p[f"b{c}"] for c in "qkv")
        d = q.shape[1] // heads
        ms = []
        for c in range(heads):  # head after head (the barrier)
            m = att(q[:, c * d:(c + 1) * d], k[:, c * d:(c + 1) * d], v[:, c * d:(c + 1) * d],
                    rows, cols)
            m, q, k, v = jax.lax.optimization_barrier((m, q, k, v))
            ms.append(m)
        last = i == n_layers - 1
        m = sum(ms) / heads if last else jnp.concatenate(ms, axis=1)
        r = h @ p["w_skip"] + p["b_skip"]
        b = jax.nn.sigmoid(jnp.concatenate([m, r, m - r], axis=1) @ p["w_beta"])
        z = b * r + (1 - b) * m
        if last:
            return z
        g = params["norms"][i]
        h = jax.nn.relu(layer_norm(z) * g["scale"] + g["bias"])


def _loss(params, x, y, label_mask, rows, cols, heads, blocks, half_batch, operand):
    logits = forward(params, x, y, label_mask, rows, cols, heads, blocks, operand)
    counted = ~label_mask
    if half_batch:  # a planted fault: the first half of the loss nodes only
        counted = counted & (jnp.cumsum(counted) <= jnp.sum(counted) // 2)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], 1)[:, 0]
    return -jnp.sum(jnp.where(counted, logp, 0)) / jnp.sum(counted).astype(logp.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "blocks", "half_batch", "operand"))
def _step(params, x, y, label_mask, rows, cols, lr, heads: int, blocks, half_batch: bool,
          operand):
    """One SGD step: (loss, gradients, new parameters)."""
    loss, grads = jax.value_and_grad(_loss)(params, x, y, label_mask, rows, cols, heads,
                                            blocks, half_batch, operand)
    return loss, grads, jax.tree.map(lambda p, g: p - jnp.asarray(lr, p.dtype) * g,
                                     params, grads)


def train(params, x, y, label_mask, rowptr: np.ndarray, colind: np.ndarray, lr: float,
          steps: int, precision: str, heads: int, dtype=jnp.float32, attn_operand=None,
          half_batch: bool = False) -> Tuple[np.ndarray, Dict, List[Dict]]:
    """``steps`` SGD steps from ``params``: the loss of each step (float64
    on the host), the gradients of the first step, and the parameters
    after each step, in ``dtype`` on the host."""
    blocks = row_blocks(np.asarray(rowptr), BLOCK_EDGES)
    rowptr = jnp.asarray(rowptr, jnp.int32)
    nnz = int(colind.shape[0])
    rows = jnp.searchsorted(rowptr, jnp.arange(nnz, dtype=jnp.int32), side="right") - 1
    cast = lambda a: jnp.asarray(a, dtype)
    params = jax.tree.map(cast, params)
    args = (cast(x), jnp.asarray(y), jnp.asarray(label_mask), rows,
            jnp.asarray(colind, jnp.int32), lr)
    losses, states, first_grads = [], [], None
    with jax.default_matmul_precision(precision):
        for _ in range(steps):  # one compiled step, called ``steps`` times
            loss, grads, params = _step(params, *args, heads=heads, blocks=blocks,
                                        half_batch=half_batch, operand=attn_operand)
            losses.append(loss)
            states.append(jax.device_get(params))
            first_grads = jax.device_get(grads) if first_grads is None else first_grads
    return np.asarray(jax.device_get(losses), np.float64), first_grads, states
