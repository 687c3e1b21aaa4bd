"""Operations and compulsory bytes of a UniMP training step, from shapes.

The counts belong to the algorithm, whatever kernel runs it. One head of
width D over a pattern of nnz edges: the SDDMM q_i . k_j takes 2*nnz*D
operations (a multiply and an add per edge and column), and the SpMM
sum_j a_ij v_j another 2*nnz*D. The backward takes 2*nnz*D for each of
its four products: dv (A^T(a) @ dm), da (dm_i . v_j), dq (A(ds) @ k)
and dk (A^T(ds) @ q). The logits the backward recomputes are not
counted, nor the softmax's elementwise work.

Bytes are what a fused kernel must move, one that keeps the logits and
the weights on chip: the pattern once (rowptr and colind; attention
reads no edge values), and each dense operand once. Forward: q, k, v
read, the output written. Backward: q, k, v and the output's gradient
read, dq, dk, dv written. So no implementation can read over 100%.
"""
from __future__ import annotations

from typing import List, Sequence

from bench.data_attn import Layer
from bench.work import F32, Work, matmul


def _pattern_bytes(n: int, nnz: int) -> float:
    return (n + 1 + nnz) * F32


def attention_fwd(n: int, nnz: int, heads: int, d: int) -> Work:
    """Forward of ``heads`` heads of width ``d`` on a square n-node graph."""
    return Work(4.0 * nnz * heads * d, _pattern_bytes(n, nnz) + 4 * n * heads * d * F32)


def attention_bwd(n: int, nnz: int, heads: int, d: int) -> Work:
    """Backward to q, k and v of the same attention."""
    return Work(8.0 * nnz * heads * d, _pattern_bytes(n, nnz) + 7 * n * heads * d * F32)


def attentions(n: int, nnz: int, layers: Sequence[Layer]) -> List[Work]:
    """The attentions of one full-graph training step, forward and
    backward, per layer."""
    out = []
    for s in layers:
        out += [attention_fwd(n, nnz, s.heads, s.d_head),
                attention_bwd(n, nnz, s.heads, s.d_head)]
    return out


def unimp_step_flops(n: int, nnz: int, layers: Sequence[Layer]) -> float:
    """Model operations of one full-graph UniMP training step.

    Per layer, forward: the q, k, v and skip projections and the gate's
    (3 d_out x 1) product, and the attention. Backward: the gradients of
    those weights and of the layer's input (every layer's, the first's
    too, since the label embedding is trained through it), so three
    times the forward's dense operations, and the attention's backward.
    Elementwise work (LayerNorm, softmax, activations, loss, update) is
    left out as it is from every count of model FLOPs."""
    total = 0.0
    for s in layers:
        dense = (3 * matmul(n, s.d_in, s.heads * s.d_head).flops
                 + matmul(n, s.d_in, s.d_out).flops + matmul(n, 3 * s.d_out, 1).flops)
        total += 3 * dense
        total += (attention_fwd(n, nnz, s.heads, s.d_head).flops
                  + attention_bwd(n, nnz, s.heads, s.d_head).flops)
    return total
