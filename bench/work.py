"""Operations and compulsory bytes of the step's parts, from shapes.

The counts belong to the algorithm, not to the kernel that runs it: an
f32 SpMM C = A @ B with A (n_rows x n_cols, nnz entries) and B
(n_cols x F) needs 2*nnz*F operations (a multiply and an add per entry
and column), and must at least read the CSR once (rowptr, colind,
values) and B once, and write C once. A dense (m x k) @ (k x n) needs
2*m*k*n operations. Nothing recomputed is counted.
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence

F32 = 4  # bytes of a float32 or an int32


class Work(NamedTuple):
    flops: float
    bytes: float

    def least_s(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The least time the chip could take: the larger of operations
        over peak compute and bytes over peak bandwidth."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def spmm(n_rows: int, n_cols: int, nnz: int, f: int) -> Work:
    """f32 SpMM with a valued CSR: rowptr (n_rows+1), colind and values
    (nnz each) read once, B (n_cols x f) read once, C (n_rows x f)
    written once."""
    csr = (n_rows + 1 + 2 * nnz) * F32
    return Work(2.0 * nnz * f, csr + (n_cols + n_rows) * f * F32)


def matmul(m: int, k: int, n: int) -> Work:
    """f32 dense (m x k) @ (k x n): both operands read once, the result
    written once."""
    return Work(2.0 * m * k * n, (m * k + k * n + m * n) * F32)


def sage_spmms(n: int, nnz: int, dims: Sequence[int]) -> List[Work]:
    """The SpMMs of one full-graph GraphSAGE training step on a square
    graph: per layer, the forward aggregation A @ (H W_agg) at the
    layer's output width, and its backward A^T @ dZ at the same width."""
    out = []
    for f in dims[1:]:
        out += [spmm(n, n, nnz, f), spmm(n, n, nnz, f)]
    return out


def sage_step_flops(n: int, nnz: int, dims: Sequence[int]) -> float:
    """Model operations of one full-graph GraphSAGE training step (mean
    aggregator, layer i: Z = A (X W_agg) + X W_self).

    Forward: two matmuls and one SpMM per layer. Backward: the gradient
    of both weights (two matmuls), the SpMM on the transpose, and the
    gradient of the layer's input (two matmuls) for every layer but the
    first, whose input is data. Elementwise work (activations, loss,
    update) is left out as it is from every count of model FLOPs."""
    total = 0.0
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        mm = matmul(n, d_in, d_out).flops
        sp = spmm(n, n, nnz, d_out).flops
        total += 2 * mm + sp  # forward
        total += 2 * mm + sp  # weight gradients, SpMM on the transpose
        if i > 0:
            total += 2 * mm  # gradient of the layer's input
    return total
