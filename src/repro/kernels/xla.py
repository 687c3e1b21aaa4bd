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

from repro.kernels import F32_MXU
from repro.sparse.csr import CSR


# ------------------------------------------------------- CSR, row ids
# The CSR variants read each edge's row from ``edge_row``, made once on
# the host, where ref.py's oracles binary-search rowptr on every call.
# Each body below is its oracle's with the search taken out, op for op,
# so the two agree bit for bit (tests/test_xla_row_ids.py). Heads are an
# axis: an (n, H, D) operand is gathered as (H*D)-wide rows and its edge
# values are (H, nnz), one row per head. (A TPU lays out the last two
# axes of an array in (8, 128) tiles, so a gathered (nnz, H, D) operand
# would take 8*128/(H*D) times its bytes, and (nnz, H) values 128/H.)
# The multi-head SDDMM and SpMM gather in chunks of edges, one chunk
# after another, so their gathered rows take at most EDGE_CHUNK_BYTES
# whatever the edge count, and the multi-head softmax sums rows by scans:
# they agree with the oracles head by head to float32 round-off
# (tests/test_unimp.py).

# Bytes of one chunk's gathered rows, their lanes padded to 128 as a TPU
# lays them out.
EDGE_CHUNK_BYTES = 1 << 30


def _rows(a: jax.Array) -> jax.Array:
    """(n, H, D) as (n, H*D): one row per node."""
    return a.reshape(a.shape[0], -1) if a.ndim == 3 else a


def _edge_chunks(nnz: int, width: int):
    """[start, stop) edge ranges whose gathered float32 rows of ``width``
    take at most EDGE_CHUNK_BYTES (at least one edge each)."""
    lanes = -(-width // 128) * 128
    size = max(1, EDGE_CHUNK_BYTES // (4 * lanes))
    return [(s, min(s + size, nnz)) for s in range(0, nnz, size)]


def edge_rows(csr: CSR) -> np.ndarray:
    """Row id of every nnz entry, int32[nnz]."""
    return np.repeat(np.arange(csr.n_rows), csr.degrees).astype(np.int32)


def prepare_csr(csr: CSR) -> Dict[str, np.ndarray]:
    return {
        "rowptr": np.asarray(csr.rowptr, np.int32),
        "colind": np.asarray(csr.colind, np.int32),
        "val": csr.values_or_ones(np.float32),
        "edge_row": edge_rows(csr),
    }


def spmm_csr(aux: Dict, val: jax.Array, b: jax.Array) -> jax.Array:
    """`ref.spmm_ref` on prepared row ids; ``val`` per edge, (nnz,) with
    b (n, F) or (H, nnz) with b (n, H, D)."""
    n_rows = aux["rowptr"].shape[0] - 1
    val = val.astype(b.dtype)
    if b.ndim == 2:
        gathered = b[aux["colind"]] * val[:, None]
        return jax.ops.segment_sum(gathered, aux["edge_row"], num_segments=n_rows)
    heads, d = b.shape[1], b.shape[2]
    rows, cols, br = aux["edge_row"], aux["colind"], _rows(b)
    out = jnp.zeros((n_rows, heads * d), b.dtype)
    for s, e in _edge_chunks(cols.shape[0], heads * d):
        g = br[cols[s:e]]
        # each head's D columns by its own value
        scaled = jnp.concatenate([g[:, h * d:(h + 1) * d] * val[h, s:e, None]
                                  for h in range(heads)], axis=1)
        # the next chunk gathers once this one is summed
        out, br = jax.lax.optimization_barrier((out.at[rows[s:e]].add(scaled), br))
    return out.reshape(n_rows, heads, d)


def sddmm_csr(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """`ref.sddmm_ref` on prepared row ids; (H, nnz) values for (n, H, D)
    operands, each head summing its own D columns of the gathered rows."""
    if x.ndim == 2:
        return jnp.sum(x[aux["edge_row"]] * y[aux["colind"]], axis=-1)
    heads, d = x.shape[1], x.shape[2]
    rows, cols, xr, yr = aux["edge_row"], aux["colind"], _rows(x), _rows(y)
    parts = [jnp.zeros((heads, 0), jnp.result_type(x, y))]
    for s, e in _edge_chunks(cols.shape[0], heads * d):
        gx, gy = xr[rows[s:e]], yr[cols[s:e]]
        part = jnp.stack([jnp.sum(gx[:, h * d:(h + 1) * d] * gy[:, h * d:(h + 1) * d], axis=-1)
                          for h in range(heads)])
        # the next chunk gathers once this one is summed
        part, xr, yr = jax.lax.optimization_barrier((part, xr, yr))
        parts.append(part)
    return jnp.concatenate(parts, axis=1)


def row_softmax(aux: Dict, val: jax.Array) -> jax.Array:
    """`ref.row_softmax_ref` on prepared row ids; (H, nnz) values by row
    scans (`_row_total`)."""
    if val.ndim == 2:
        row_max = _row_total(aux, val, jnp.maximum)
        shifted = jnp.exp(val - jnp.where(jnp.isfinite(row_max), row_max, 0.0))
        return shifted / jnp.maximum(_row_total(aux, shifted, jnp.add), 1e-30)
    rows, n_rows = aux["edge_row"], aux["rowptr"].shape[0] - 1
    row_max = jax.ops.segment_max(val, rows, num_segments=n_rows)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    shifted = jnp.exp(val - row_max[rows])
    denom = jax.ops.segment_sum(shifted, rows, num_segments=n_rows)
    return shifted / jnp.maximum(denom[rows], 1e-30)


def row_softmax_bwd(aux: Dict, probs: jax.Array, grad_probs: jax.Array) -> jax.Array:
    """`ref.row_softmax_bwd_ref` on prepared row ids; (H, nnz) values by
    row scans (`_row_total`)."""
    tmp = probs * grad_probs
    if probs.ndim == 2:
        return tmp - probs * _row_total(aux, tmp, jnp.add)
    rows, n_rows = aux["edge_row"], aux["rowptr"].shape[0] - 1
    row_dot = jax.ops.segment_sum(tmp, rows, num_segments=n_rows)
    return tmp - probs * row_dot[rows]


def _row_total(aux: Dict, x: jax.Array, op) -> jax.Array:
    """``op`` (max or add) over each row's edges of (H, nnz) values, at
    every edge of the row. Rows are runs of edges, so two passes of
    log2(nnz) shifted elementwise steps do it, with no scatter or gather:
    a segmented scan leaves each row's total at its last edge, and a
    second pass copies that back along the row. (A TPU scatters (nnz, H)
    values edge by edge, with H of 128 lanes used.) The barrier keeps the
    compiler from folding each step's row mask into a constant of its
    own."""
    rows, nnz = jax.lax.optimization_barrier(aux["edge_row"]), x.shape[1]
    k = 1
    while k < nnz:  # edge e: op over its row's edges in (e - 2k, e]
        same = rows[k:] == rows[:-k]
        x = jnp.concatenate([x[:, :k], jnp.where(same, op(x[:, :-k], x[:, k:]), x[:, k:])],
                            axis=1)
        k *= 2
    k = 1
    while k < nnz:  # edge e: the value at its row's last edge
        same = rows[k:] == rows[:-k]
        x = jnp.concatenate([jnp.where(same, x[:, k:], x[:, :-k]), x[:, nnz - k:]], axis=1)
        k *= 2
    return x


# ---------------------------------------------------------------- SpMM
def spmm_gather_segsum(aux: Dict, b: jax.Array) -> jax.Array:
    """Baseline SpMM: gather + segment-sum (cuSPARSE stand-in)."""
    return spmm_csr(aux, aux["val"], b)


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


# ------------------------------------------------ degree-bucketed ELL
# Rows sorted by degree fall into at most MAX_BUCKETS buckets, each
# padded to its widest row (K slots) and to a multiple of 8 rows, and
# stored slot-major by groups of 8 rows: a flat (rows/8, K, 8) table, so
# that slot k of a group is one run of 8. A TPU lays out the last two
# axes of an array in (8, 128) tiles: a 2-D (K, rows) table of a narrow
# bucket would pad its rows to 128 lanes, while 8 rows of F columns are
# whole tiles, so the gathered (groups*K*8, F) rows reshape to
# (groups, K, 8, F) in place and the K slabs add elementwise. No
# scatter. Row chunks are runs of whole groups, contiguous in the
# table. The bucket outputs, in degree order, go back in row order by
# one gather.

MAX_BUCKETS = 24
# degrees above this are rounded up to a geometric grid of ratio
# _GRID_RATIO before the bucket search, which keeps the search small
# whatever the spread of degrees and costs at most 4% of padding
_EXACT_DEGREE = 32
_GRID_RATIO = 1.04
_GROUP = 8  # rows of a group: the sublanes of a TPU tile


@jax.tree_util.register_static
class Slots(tuple):
    """Slot counts (K) of a layout's buckets: static under jit, as the
    tables' shapes are."""


def bucket_bounds(deg: np.ndarray, max_buckets: int = MAX_BUCKETS) -> np.ndarray:
    """Upper degree bounds of the buckets of the rows of degree ``deg``
    (ascending, the last the largest degree), at most ``max_buckets``,
    chosen to minimise the padded slots: the sum over buckets of the
    bound times the bucket's rows rounded up to whole groups of 8. Rows
    of degree 0 belong to no bucket."""
    deg = np.asarray(deg, np.int64)
    if not np.any(deg > 0):
        return np.zeros(0, np.int64)
    top = int(deg.max())
    grid = np.ceil(_EXACT_DEGREE * _GRID_RATIO ** np.arange(
        1, np.log(top / _EXACT_DEGREE) / np.log(_GRID_RATIO) + 2)).astype(np.int64)
    grid = np.unique(np.concatenate([np.arange(1, _EXACT_DEGREE + 1), grid]))
    width, count = np.unique(grid[np.searchsorted(grid, deg[deg > 0])],
                             return_counts=True)
    width[-1] = top  # the last grid point may lie above the largest degree
    # best[j]: least padded slots of the rows up to the j-th width in as
    # many buckets as there have been rounds, the last ending at width
    # j - 1; cuts[r][j - 1] is where round r's last bucket starts
    m = width.size
    below = np.concatenate([[0], np.cumsum(count)])
    i, j = np.arange(m + 1)[:, None], np.arange(1, m + 1)[None, :]
    rows = -(-(below[j] - below[i]) // _GROUP) * _GROUP
    cost = np.where(i < j, width[j - 1] * rows, np.inf)
    best = np.where(np.arange(m + 1) == 0, 0.0, np.inf)
    cuts, least = [], []
    for _ in range(min(max_buckets, m)):
        total = best[:, None] + cost
        cuts.append(np.argmin(total, axis=0))
        best = np.concatenate([[np.inf], total[cuts[-1], np.arange(m)]])
        least.append(best[m])
    ends, j = [], m
    for cut in reversed(cuts[:int(np.argmin(least)) + 1]):
        ends.append(j)
        j = int(cut[j - 1])
    return width[np.array(ends[::-1]) - 1]


def prepare_bucket_ell(csr: CSR) -> Dict:
    """Degree-bucketed, slot-major ELL (`bucket_bounds`): per bucket a
    flat (rows/8, K, 8) table of column ids and one of values, rows in
    degree order; padded slots and rows hold column ``n_cols`` (past the
    last, where the run puts a zero row) and value 0. ``inv`` gives each
    row its place in the run's output: 0 (a zero row) for degree 0, else
    its row of its bucket, the buckets one after another after 8 zero
    rows."""
    deg = csr.degrees.astype(np.int64)
    order = np.argsort(deg, kind="stable")
    bounds = bucket_bounds(deg)
    val = csr.values_or_ones(np.float32)
    rowptr = np.asarray(csr.rowptr, np.int64)
    sorted_deg = deg[order]
    edges = np.searchsorted(sorted_deg, np.concatenate([[1], bounds + 1]))
    inv = np.zeros(csr.n_rows, np.int32)
    cols, vals, slots, at = [], [], [], _GROUP
    for s, e in zip(edges[:-1], edges[1:]):
        rows, k = order[s:e], int(sorted_deg[e - 1])
        n = -(-rows.size // _GROUP) * _GROUP
        inv[rows] = at + np.arange(rows.size)
        at += n
        d, start = np.zeros(n, np.int64), np.zeros(n, np.int64)
        d[:rows.size], start[:rows.size] = deg[rows], rowptr[rows]
        d, start = d.reshape(-1, 1, _GROUP), start.reshape(-1, 1, _GROUP)
        slot = np.arange(k)[None, :, None]
        real = slot < d
        pos = np.where(real, start + slot, 0)
        cols.append(np.where(real, csr.colind[pos], csr.n_cols).astype(np.int32).ravel())
        vals.append(np.where(real, val[pos], 0.0).astype(np.float32).ravel())
        slots.append(k)
    return {"inv": inv, "cols": tuple(cols), "vals": tuple(vals), "slots": Slots(slots)}


def bucket_chunks(aux: Dict, width: int, chunk_bytes: int = EDGE_CHUNK_BYTES):
    """(bucket, start, stop) row ranges of each bucket, whole groups of
    8 rows, whose gathered float32 rows of ``width``, lanes padded to
    128, take at most ``chunk_bytes`` (at least one group each)."""
    lanes = -(-width // 128) * 128
    out = []
    for i, (cols, k) in enumerate(zip(aux["cols"], aux["slots"])):
        rows = cols.shape[0] // k
        size = max(1, chunk_bytes // (4 * lanes * k * _GROUP)) * _GROUP
        out += [(i, s, min(s + size, rows)) for s in range(0, rows, size)]
    return out


def spmm_bucket_ell(aux: Dict, b: jax.Array,
                    chunk_bytes: int = EDGE_CHUNK_BYTES) -> jax.Array:
    """SpMM on `prepare_bucket_ell`'s layout: per row chunk of a bucket,
    gather its rows of b, scale them and add the K slabs in ``b``'s
    dtype; padded slots gather a zero row at value 0."""
    f = b.shape[1]
    bz = jnp.concatenate([b, jnp.zeros((1, f), b.dtype)])
    parts = [jnp.zeros((_GROUP, f), b.dtype)]
    for i, s, e in bucket_chunks(aux, f, chunk_bytes):
        k = aux["slots"][i]
        cols, val = aux["cols"][i][s * k:e * k], aux["vals"][i][s * k:e * k]
        g = (bz[cols] * val[:, None].astype(b.dtype)).reshape(-1, k, _GROUP, f)
        parts.append(jnp.sum(g, axis=1).reshape(e - s, f))
    return jnp.concatenate(parts)[aux["inv"]]


# --------------------------------------------------------------- SDDMM
def sddmm_gather_dot(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Paper's SDDMM baseline: gather both sides, dot."""
    return sddmm_csr(aux, x, y)


def sddmm_row_ell(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Row-ELL SDDMM: (n,K) uniform gather; returns padded (n,K) values.

    NOTE: returns ELL layout, converted back by the ops layer when CSR
    layout is required.
    """
    gathered = y[aux["colind"]]  # (n, K, F)
    out = jnp.einsum("nf,nkf->nk", x.astype(gathered.dtype), gathered,
                     precision=F32_MXU)
    return out * (aux["val"] != 0)


# ------------------------------------------- composed attention pipelines
# An op="attention" decide selects among these whole
# SDDMM -> row-softmax -> SpMM compositions; each stays in one sparse
# layout per stage, with explicit layout conversion for mixed pairs.

def prepare_edge_slots(csr: CSR) -> Dict[str, np.ndarray]:
    """(row, slot-within-row) of every nnz entry — the scatter/gather
    indices that convert per-edge CSR values to/from the (n, K) ELL table."""
    slot = np.arange(csr.nnz) - np.repeat(csr.rowptr[:-1], csr.degrees)
    return {"edge_row": edge_rows(csr), "edge_slot": slot.astype(np.int32)}


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
    logits = sddmm_csr(aux, q, k) * scale
    probs = row_softmax(aux, logits)
    return spmm_csr(aux, probs, v)


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
    return spmm_csr(aux, probs_csr, v)


def attention_csr_to_ell(aux: Dict, q, k, v) -> jax.Array:
    """gather_dot SDDMM/softmax -> (CSR->ELL scatter) -> row_ell SpMM."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = sddmm_csr(aux, q, k) * scale
    probs = row_softmax(aux, logits)
    ell_colind = aux["ell_colind"]  # (n, K)
    probs_ell = jnp.zeros(ell_colind.shape, probs.dtype).at[
        aux["edge_row"], aux["edge_slot"]
    ].set(probs)
    return jnp.einsum("nk,nkf->nf", probs_ell, v[ell_colind].astype(probs.dtype),
                      precision=F32_MXU)
