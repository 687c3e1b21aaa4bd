"""Pallas TPU kernels: block-ELL SpMM (C = A @ B, A sparse).

TPU adaptation of the paper's SpMM templates (DESIGN.md §2):
  - grid = (row_blocks, f_tiles, ell_slots); one MXU matmul per micro-tile
  - scalar-prefetched ``colblk`` drives the B-operand index_map — the
    block-granular analogue of the CUDA warp's per-row column gather
  - knobs: rb (rows/block), bc (cols/block), f_tile (feature tile — the
    vec4 analogue is a wide f_tile), hub-split handled by running two
    partitions of the BlockELL format

Padded slots carry zero values and colblk=0, so they contribute nothing
(no masking needed in the hot loop).

Three layouts share this file:
  - dense-W (`spmm_block_ell`): every row block runs the full ELL width
    W = max(nslots), so one hub row block makes every light row block
    pay W MXU matmuls on zero tiles;
  - ragged (`spmm_ragged_ell`): the grid's slot dimension covers the
    *flat* slot list of RaggedBlockELL, so compute and B-tile traffic
    scale with actual stored tiles. Scalar-prefetched `slot_rowblk`
    drives the output index_map and `blkptr` the init-on-first-slot
    condition; consecutive slots of one row block revisit the same
    output block, so the accumulator stays resident in VMEM.
  - merge-path (`spmm_merge_path`): the slot stream is cut into equal
    `tile_slots` tiles (sparse/merge.py precomputes the per-tile start
    (row block, offset) coordinates); rows are recovered in-kernel via
    binary search over the prefetched blkptr, so grid work is
    nnz-balanced even when one hub row owns most of the stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import F32_MXU


def _bisect_rowblk(blkptr_ref, s, lo0, hi0, n_iter):
    """Largest i with blkptr[i] <= s (bisect_right - 1), seeded at lo0.

    Fixed-trip guarded binary search over the scalar-prefetched blkptr:
    each step is a no-op once the interval has shrunk to one row block,
    so n_iter only needs to be an upper bound. Requires blkptr[lo0] <= s
    (the merge-path table guarantees it: lo0 is the tile's start row).
    """

    def step(_, lohi):
        lo, hi = lohi
        mid = jax.lax.div(lo + hi, jnp.int32(2))
        go = hi - lo > 1
        le = blkptr_ref[mid] <= s
        lo = jnp.where(go & le, mid, lo)
        hi = jnp.where(go & jnp.logical_not(le), mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, n_iter, step, (lo0, jnp.int32(hi0)))
    return lo


def _spmm_kernel(colblk_ref, vals_ref, b_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_tile = vals_ref[0, 0]  # (rb, bc) f32
    b_tile = b_ref[...]  # (bc, f_tile)
    out_ref[...] += jnp.dot(
        a_tile, b_tile.astype(a_tile.dtype),
        preferred_element_type=jnp.float32, precision=F32_MXU,
    )


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def spmm_block_ell(
    colblk: jax.Array,  # int32 (nrb, W)
    vals: jax.Array,  # f32 (nrb, W, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F) — F % f_tile == 0
    f_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    nrb, w, rb, bc = vals.shape
    n_b_rows, f = b.shape
    assert f % f_tile == 0, (f, f_tile)
    assert n_b_rows % bc == 0
    grid = (nrb, f // f_tile, w)

    out = pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rb, bc), lambda i, j, k, cb: (i, k, 0, 0)),
                pl.BlockSpec((bc, f_tile), lambda i, j, k, cb: (cb[i, k], j)),
            ],
            out_specs=pl.BlockSpec((rb, f_tile), lambda i, j, k, cb: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * rb, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(colblk, vals, b)
    return out


def _spmm_ragged_kernel(blkptr_ref, rowblk_ref, colblk_ref, vals_ref, b_ref, out_ref):
    s = pl.program_id(1)
    i = rowblk_ref[s]

    @pl.when(s == blkptr_ref[i])
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_tile = vals_ref[0]  # (rb, bc) f32
    b_tile = b_ref[...]  # (bc, f_tile)
    out_ref[...] += jnp.dot(
        a_tile, b_tile.astype(a_tile.dtype),
        preferred_element_type=jnp.float32, precision=F32_MXU,
    )


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def spmm_ragged_ell(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_rowblk: jax.Array,  # int32 (n_slots,)
    slot_colblk: jax.Array,  # int32 (n_slots,)
    slot_vals: jax.Array,  # f32 (n_slots, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F) — F % f_tile == 0
    f_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Slot-compacted SpMM: grid = (f_tiles, n_slots) over actual slots.

    Slots are sorted by row block, so each output block is revisited
    only by consecutive grid steps; `pl.when(s == blkptr[rowblk[s]])`
    zero-initializes it on its first slot. Accumulation order matches
    the dense-W kernel exactly (padded slots there add exact zeros), so
    outputs are value-identical, not merely close.
    """
    n_slots, rb, bc = slot_vals.shape
    nrb = blkptr.shape[0] - 1
    n_b_rows, f = b.shape
    assert f % f_tile == 0, (f, f_tile)
    assert n_b_rows % bc == 0
    if nrb == 0 or n_slots == 0:
        # empty row subset (RaggedBlockELL guarantees >= 1 slot per
        # block otherwise): nothing to launch
        return jnp.zeros((nrb * rb, f), jnp.float32)
    grid = (f // f_tile, n_slots)

    out = pl.pallas_call(
        _spmm_ragged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rb, bc), lambda j, s, bp, rbk, cb: (s, 0, 0)),
                pl.BlockSpec((bc, f_tile), lambda j, s, bp, rbk, cb: (cb[s], j)),
            ],
            out_specs=pl.BlockSpec(
                (rb, f_tile), lambda j, s, bp, rbk, cb: (rbk[s], j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * rb, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(blkptr, slot_rowblk, slot_colblk, slot_vals, b)
    return out


def _spmm_merge_kernel(
    blkptr_ref,
    colblk_ref,
    tile_rowblk_ref,
    tile_nslots_ref,
    vals_ref,
    b_ref,
    out_ref,
    *,
    tile_slots,
    n_row_blocks,
    n_bisect,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rb = vals_ref.shape[2]
    bc = vals_ref.shape[3]
    n_live = tile_nslots_ref[t]
    lo0 = tile_rowblk_ref[t]

    def body(k, carry):
        s = t * tile_slots + k
        i = _bisect_rowblk(blkptr_ref, s, lo0, n_row_blocks, n_bisect)
        a_tile = vals_ref[0, pl.ds(k, 1)][0]  # (rb, bc)
        cb = colblk_ref[s]
        b_blk = b_ref[pl.ds(cb * bc, bc), :]  # (bc, f_tile)
        cur = out_ref[pl.ds(i * rb, rb), :]
        upd = cur + jnp.dot(
            a_tile, b_blk.astype(a_tile.dtype),
            preferred_element_type=jnp.float32, precision=F32_MXU,
        )
        # tail-padded slots of the last tile leave the row untouched
        out_ref[pl.ds(i * rb, rb), :] = jnp.where(k < n_live, upd, cur)
        return carry

    jax.lax.fori_loop(0, tile_slots, body, 0)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def spmm_merge_path(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_colblk: jax.Array,  # int32 (n_tiles * tile_slots,) tail-padded
    tile_rowblk: jax.Array,  # int32 (n_tiles,) merge start row block
    tile_nslots: jax.Array,  # int32 (n_tiles,) live slots per tile
    tile_vals: jax.Array,  # f32 (n_tiles, tile_slots, rb, bc)
    b: jax.Array,  # (n_col_blocks*bc, F) — F % f_tile == 0
    f_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """nnz-balanced SpMM: grid = (f_tiles, n_tiles) over equal slot tiles.

    Work per grid cell is a fixed ``tile_slots`` slots regardless of how
    the slots spread over rows, so one mega-hub row block costs
    deg/tile_slots cells instead of serializing a single cell — the
    merge-path answer to the all-hub regime the row-partitioned kernels
    degrade in. Each slot's owning row block is recovered with a guarded
    binary search over the scalar-prefetched ``blkptr``, seeded at the
    host-precomputed tile start coordinate (``tile_rowblk``).

    The carry/fixup pass is implicit: the whole output column panel is
    VMEM-resident across the sequential tile dimension, so a row block
    split across tiles accumulates its partial sums in slot order — the
    exact per-slot dot order of `spmm_ragged_ell` — and outputs are
    value-identical to the ragged and dense-W kernels, not merely close.
    """
    n_tiles, tile_slots, rb, bc = tile_vals.shape
    nrb = blkptr.shape[0] - 1
    n_b_rows, f = b.shape
    assert f % f_tile == 0, (f, f_tile)
    assert n_b_rows % bc == 0
    if nrb == 0 or n_tiles == 0:
        return jnp.zeros((nrb * rb, f), jnp.float32)
    grid = (f // f_tile, n_tiles)
    n_bisect = max(nrb, 2).bit_length() + 1

    out = pl.pallas_call(
        functools.partial(
            _spmm_merge_kernel,
            tile_slots=tile_slots,
            n_row_blocks=nrb,
            n_bisect=n_bisect,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, tile_slots, rb, bc), lambda j, t, *_: (t, 0, 0, 0)
                ),
                pl.BlockSpec((n_b_rows, f_tile), lambda j, t, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((nrb * rb, f_tile), lambda j, t, *_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * rb, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(blkptr, slot_colblk, tile_rowblk, tile_nslots, tile_vals, b)
    return out
