"""The paper's own workload: GNN layers over CSR graphs, with
AutoSAGE-scheduled sparse aggregation.

GraphSAGE (mean aggregator): H' = act(A_norm @ H @ W_agg + H @ W_self)
GAT-style CSR attention:     H' = CSR_attention(A, HW_q, HW_k, HW_v)
                             (SDDMM -> row-softmax -> SpMM, §8.7)
UniMP (Shi et al., arXiv:2009.03509): multi-head TransformerConv layers
                             with a gated skip, over masked label input
"""
from __future__ import annotations

from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs.base import ArchConfig
from repro.core.batch import BatchScheduler
from repro.core.scheduler import AutoSage
from repro.models.modules import dense_init
from repro.sparse.csr import CSR

# Any scheduler exposing decide(csr, f, op) / build_runner(csr, decision):
# the per-graph AutoSage, or the BatchScheduler that amortizes probing
# across a stream of sampled subgraphs (minibatch training).
SchedulerLike = Union[AutoSage, BatchScheduler]


def init_gnn(cfg: ArchConfig, key, in_dim: int, n_classes: int, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    dims = [in_dim] + [d] * (cfg.n_layers - 1) + [n_classes]
    ks = jax.random.split(key, 2 * cfg.n_layers)
    return {
        "w_agg": [dense_init(ks[2 * i], dims[i], dims[i + 1], dtype) for i in range(cfg.n_layers)],
        "w_self": [dense_init(ks[2 * i + 1], dims[i], dims[i + 1], dtype) for i in range(cfg.n_layers)],
    }


def _norm_csr(csr: CSR) -> CSR:
    """Row-normalized adjacency (mean aggregator)."""
    deg = np.maximum(csr.degrees, 1).astype(np.float32)
    val = csr.values_or_ones(np.float32) / np.repeat(deg, csr.degrees)
    return CSR(csr.rowptr, csr.colind, val, csr.n_rows, csr.n_cols)


def sage_forward(
    params: Dict,
    csr: CSR,
    x: jax.Array,
    sage: Optional[SchedulerLike] = None,
) -> jax.Array:
    """GraphSAGE forward; aggregation runs through the AutoSAGE scheduler
    (per-graph or batched) when one is supplied, else the XLA baseline.

    Every aggregation goes through `repro.api.spmm`, so with a scheduler
    the op is differentiable end-to-end: jax.grad through this forward
    emits scheduled backward ops (op="spmm_bwd_b" on the memoized
    transpose) with their own cache keys. Decisions and prepared runners
    are memoized inside the scheduler, so the per-layer call costs one
    dict hit after the first step (hidden layers share one F-keyed
    decision; the head layer gets its own)."""
    a = _norm_csr(csr)
    n_layers = len(params["w_agg"])
    for i in range(n_layers):
        h = x @ params["w_agg"][i]
        agg = api.spmm(a, h, sage=sage)
        x = agg.astype(x.dtype) + x @ params["w_self"][i]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def sage_minibatch_forward(
    params: Dict,
    sub: CSR,
    batch_rows: np.ndarray,
    x_full: jax.Array,
    sage: Optional[SchedulerLike] = None,
) -> jax.Array:
    """One minibatch step of 1-hop sampled GraphSAGE.

    ``sub`` is a *rectangular* induced adjacency (batch_rows x all
    nodes), e.g. one element of `sparse.sample_subgraph_stream`: each
    sampled row aggregates over its full neighborhood in the parent
    graph. Layer 0 is the scheduled sparse aggregation; the remaining
    layers act on the batch rows only (dense head), which is the
    standard shape of sampled-neighborhood training. With a
    `BatchScheduler` supplied, thousands of per-step subgraphs share
    bucketed schedule decisions instead of each paying a probe.
    """
    a = _norm_csr(sub)
    h = x_full @ params["w_agg"][0]
    agg = api.spmm(a, h, sage=sage)
    xb = x_full[jnp.asarray(np.asarray(batch_rows))]
    out = agg.astype(xb.dtype) + xb @ params["w_self"][0]
    n_layers = len(params["w_agg"])
    for i in range(1, n_layers):
        out = jax.nn.relu(out)
        out = out @ params["w_agg"][i] + out @ params["w_self"][i]
    return out


def init_gat(cfg: ArchConfig, key, in_dim: int, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "wq": dense_init(ks[0], in_dim, d, dtype),
        "wk": dense_init(ks[1], in_dim, d, dtype),
        "wv": dense_init(ks[2], in_dim, d, dtype),
    }


def gat_layer(
    params: Dict, csr: CSR, x: jax.Array, sage: Optional[SchedulerLike] = None
) -> jax.Array:
    """Dot-product graph attention = the paper's CSR-attention pipeline.

    With a scheduler supplied, the whole SDDMM -> softmax -> SpMM
    composition goes through the pipeline-level decision via
    `repro.api.attention` (composed 3-kernel candidates vs the fused
    Pallas kernel, per input) and is differentiable — the backward
    decomposes into its own scheduled sparse ops (core/autodiff.py).
    Without a scheduler, the XLA reference pipeline runs.
    """
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    return api.attention(csr, q, k, v, sage=sage).astype(x.dtype)


def init_unimp(cfg: ArchConfig, key, in_dim: int, n_classes: int,
               dtype=jnp.float32) -> Dict:
    """UniMP weights, as PyG's ``TransformerConv(heads, beta=True)`` layers
    of its UniMP example: ``cfg.n_heads`` heads of width
    d_model / n_heads, concatenated, in every layer but the last, whose
    heads have the classes' width and are averaged. Per layer: query,
    key, value and skip projections with biases, and the gate's w_beta
    (3 x the layer's output width, to 1); a LayerNorm after every layer
    but the last; the label embedding (n_classes, in_dim)."""
    h = cfg.n_heads
    ks = iter(jax.random.split(key, 5 * cfg.n_layers + 1))
    layers, norms, d_in = [], [], in_dim
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        d_head = n_classes if last else cfg.d_model // h
        d_out = d_head if last else h * d_head
        zeros = lambda n: jnp.zeros((n,), dtype)
        layers.append({
            "wq": dense_init(next(ks), d_in, h * d_head, dtype), "bq": zeros(h * d_head),
            "wk": dense_init(next(ks), d_in, h * d_head, dtype), "bk": zeros(h * d_head),
            "wv": dense_init(next(ks), d_in, h * d_head, dtype), "bv": zeros(h * d_head),
            "w_skip": dense_init(next(ks), d_in, d_out, dtype), "b_skip": zeros(d_out),
            "w_beta": dense_init(next(ks), 3 * d_out, 1, dtype),
        })
        if not last:
            norms.append({"scale": jnp.ones((d_out,), dtype), "bias": zeros(d_out)})
        d_in = d_out
    emb = jax.random.normal(next(ks), (n_classes, in_dim)).astype(dtype)
    return {"emb": emb, "layers": layers, "norms": norms}


def _layer_norm(x: jax.Array, p: Dict, eps: float = 1e-5) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def unimp_forward(
    params: Dict,
    csr: CSR,
    x: jax.Array,
    sage: Optional[SchedulerLike] = None,
    *,
    heads: int,
    labels: jax.Array,
    label_mask: jax.Array,
) -> jax.Array:
    """UniMP logits (n, n_classes). The label-fed nodes (``label_mask``)
    take ``x + E[label]`` as input. Each layer, with H = ``heads``:

        q, k, v = x W_q + b_q, x W_k + b_k, x W_v + b_v      (n, H, D)
        m_i = sum_j softmax_j(q_i . k_j / sqrt(D)) v_j       per head
        m   = concat over heads (hidden layers), mean (last layer)
        r   = x W_skip + b_skip
        b   = sigmoid([m, r, m - r] w_beta);  out = b r + (1 - b) m

    with LayerNorm then ReLU between layers. The attention is one
    scheduled multi-head `repro.api.attention` per layer: forward and
    backward pieces are decided, probed and cached per (graph, H, D)."""
    x = x + jnp.where(label_mask[:, None], params["emb"][labels], 0).astype(x.dtype)
    n = x.shape[0]
    n_layers = len(params["layers"])
    for i, p in enumerate(params["layers"]):
        q, k, v = ((x @ p[f"w{c}"] + p[f"b{c}"]).reshape(n, heads, -1) for c in "qkv")
        m = api.attention(csr, q, k, v, sage=sage).astype(x.dtype)
        m = m.mean(axis=1) if i == n_layers - 1 else m.reshape(n, -1)
        r = x @ p["w_skip"] + p["b_skip"]
        beta = jax.nn.sigmoid(jnp.concatenate([m, r, m - r], axis=-1) @ p["w_beta"])
        x = beta * r + (1 - beta) * m
        if i < n_layers - 1:
            x = jax.nn.relu(_layer_norm(x, params["norms"][i]))
    return x
