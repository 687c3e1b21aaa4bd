"""Pallas TPU kernels: block-ELL SDDMM (A~_ij = <X_i, Y_j> on S(A)).

Dense-W (`sddmm_block_ell`): grid = (row_blocks, ell_slots, f_chunks);
accumulates the X@Y^T micro-tile over feature chunks and applies the
structural mask on the last chunk. Same scalar-prefetch mechanism and
knobs as the SpMM kernel — and the same padding tax: every row block
pays W = max(nslots) tile products.

Ragged (`sddmm_ragged_ell`): grid = (n_slots, f_chunks) over the flat
RaggedBlockELL slot list; per-slot output tiles, so compute and X/Y tile
traffic scale with stored tiles, not n_row_blocks x W. Scalar-prefetched
`slot_rowblk`/`slot_colblk` drive the X and Y index_maps.

Merge-path (`sddmm_merge_path`): same flat slot stream cut into equal
`tile_slots` tiles (sparse/merge.py); each grid cell runs one tile and
recovers slot row blocks with a binary search over the prefetched
blkptr. SDDMM has no cross-row reduction, so the merge carry is vacuous
— the family exists so the scheduler can pick one nnz-balanced layout
for both ops of a fused SpMM/SDDMM pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import F32_MXU
from repro.kernels.spmm_pallas import _bisect_rowblk


def _sddmm_kernel(colblk_ref, x_ref, y_ref, mask_ref, out_ref, *, n_f_chunks):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x_tile = x_ref[...]  # (rb, fc)
    y_tile = y_ref[...]  # (bc, fc)
    out_ref[...] += jnp.dot(
        x_tile, y_tile.T, preferred_element_type=jnp.float32, precision=F32_MXU
    )[None, None]

    @pl.when(j == n_f_chunks - 1)
    def _mask():
        out_ref[...] *= mask_ref[...]


@functools.partial(jax.jit, static_argnames=("f_chunk", "interpret"))
def sddmm_block_ell(
    colblk: jax.Array,  # int32 (nrb, W)
    mask: jax.Array,  # f32 (nrb, W, rb, bc) structural 0/1
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    f_chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    nrb, w, rb, bc = mask.shape
    f = x.shape[1]
    assert f % f_chunk == 0, (f, f_chunk)
    n_f_chunks = f // f_chunk
    grid = (nrb, w, n_f_chunks)

    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, n_f_chunks=n_f_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rb, f_chunk), lambda i, k, j, cb: (i, j)),
                pl.BlockSpec((bc, f_chunk), lambda i, k, j, cb: (cb[i, k], j)),
                pl.BlockSpec((1, 1, rb, bc), lambda i, k, j, cb: (i, k, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rb, bc), lambda i, k, j, cb: (i, k, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrb, w, rb, bc), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(colblk, x, y, mask)
    return out


def _sddmm_ragged_kernel(
    rowblk_ref, colblk_ref, x_ref, y_ref, mask_ref, out_ref, *, n_f_chunks
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x_tile = x_ref[...]  # (rb, fc)
    y_tile = y_ref[...]  # (bc, fc)
    out_ref[...] += jnp.dot(
        x_tile, y_tile.T, preferred_element_type=jnp.float32, precision=F32_MXU
    )[None]

    @pl.when(j == n_f_chunks - 1)
    def _mask():
        out_ref[...] *= mask_ref[...]


@functools.partial(jax.jit, static_argnames=("f_chunk", "interpret"))
def sddmm_ragged_ell(
    slot_rowblk: jax.Array,  # int32 (n_slots,)
    slot_colblk: jax.Array,  # int32 (n_slots,)
    mask: jax.Array,  # f32 (n_slots, rb, bc) structural 0/1
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    f_chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Slot-compacted SDDMM: one (rb, bc) output tile per actual slot.

    Returns f32 (n_slots, rb, bc) tiles in RaggedBlockELL slot order;
    dummy slots of empty row blocks come out all-zero (their mask is 0).
    Tile values equal the dense-W kernel's at the corresponding
    (row block, in-block slot) — the f-chunk accumulation order is the
    same — so outputs are value-identical where slots correspond.
    """
    n_slots, rb, bc = mask.shape
    f = x.shape[1]
    assert f % f_chunk == 0, (f, f_chunk)
    if n_slots == 0:
        return jnp.zeros((0, rb, bc), jnp.float32)
    n_f_chunks = f // f_chunk
    grid = (n_slots, n_f_chunks)

    out = pl.pallas_call(
        functools.partial(_sddmm_ragged_kernel, n_f_chunks=n_f_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rb, f_chunk), lambda s, j, rbk, cb: (rbk[s], j)),
                pl.BlockSpec((bc, f_chunk), lambda s, j, rbk, cb: (cb[s], j)),
                pl.BlockSpec((1, rb, bc), lambda s, j, rbk, cb: (s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, rb, bc), lambda s, j, rbk, cb: (s, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, rb, bc), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(slot_rowblk, slot_colblk, x, y, mask)
    return out


def _sddmm_merge_kernel(
    blkptr_ref,
    colblk_ref,
    tile_rowblk_ref,
    x_ref,
    y_ref,
    mask_ref,
    out_ref,
    *,
    tile_slots,
    n_row_blocks,
    n_bisect,
    n_f_chunks,
):
    j = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((j == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rb = out_ref.shape[2]
    bc = out_ref.shape[3]
    lo0 = tile_rowblk_ref[t]

    def body(k, carry):
        s = t * tile_slots + k
        i = _bisect_rowblk(blkptr_ref, s, lo0, n_row_blocks, n_bisect)
        x_blk = x_ref[pl.ds(i * rb, rb), :]  # (rb, fc)
        cb = colblk_ref[s]
        y_blk = y_ref[pl.ds(cb * bc, bc), :]  # (bc, fc)
        part = jnp.dot(
            x_blk, y_blk.T, preferred_element_type=jnp.float32, precision=F32_MXU
        )
        cur = out_ref[pl.ds(t, 1), pl.ds(k, 1)]
        out_ref[pl.ds(t, 1), pl.ds(k, 1)] = cur + part[None, None]
        return carry

    jax.lax.fori_loop(0, tile_slots, body, 0)

    @pl.when(j == n_f_chunks - 1)
    def _mask():
        out_ref[pl.ds(t, 1)] = out_ref[pl.ds(t, 1)] * mask_ref[...]


@functools.partial(jax.jit, static_argnames=("f_chunk", "interpret"))
def sddmm_merge_path(
    blkptr: jax.Array,  # int32 (nrb + 1,)
    slot_colblk: jax.Array,  # int32 (n_tiles * tile_slots,) tail-padded
    tile_rowblk: jax.Array,  # int32 (n_tiles,) merge start row block
    tile_mask: jax.Array,  # f32 (n_tiles, tile_slots, rb, bc) structural 0/1
    x: jax.Array,  # (nrb*rb, F)
    y: jax.Array,  # (n_col_blocks*bc, F)
    f_chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """nnz-balanced SDDMM: grid = (f_chunks, n_tiles), tile_slots per cell.

    The f-chunk dimension is OUTER so the X/Y feature panels are fetched
    once per chunk (not once per tile); the full tile-grid output stays
    VMEM-resident across the whole grid and is written back once.

    Returns f32 (n_tiles, tile_slots, rb, bc) tiles in merge-tile order —
    reshape to (-1, rb, bc) and drop the tail padding to recover
    `sddmm_ragged_ell`'s slot order. Per-slot tiles run the same f-chunk
    accumulation as the ragged kernel on the same operands, so live tiles
    are value-identical; tail-padded slots carry a zero mask and come out
    all-zero.
    """
    n_tiles, tile_slots, rb, bc = tile_mask.shape
    nrb = blkptr.shape[0] - 1
    f = x.shape[1]
    assert f % f_chunk == 0, (f, f_chunk)
    if n_tiles == 0:
        return jnp.zeros((0, tile_slots, rb, bc), jnp.float32)
    n_f_chunks = f // f_chunk
    grid = (n_f_chunks, n_tiles)
    n_bisect = max(nrb, 2).bit_length() + 1
    n_x_rows = x.shape[0]
    n_y_rows = y.shape[0]

    out = pl.pallas_call(
        functools.partial(
            _sddmm_merge_kernel,
            tile_slots=tile_slots,
            n_row_blocks=nrb,
            n_bisect=n_bisect,
            n_f_chunks=n_f_chunks,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n_x_rows, f_chunk), lambda j, t, *_: (0, j)),
                pl.BlockSpec((n_y_rows, f_chunk), lambda j, t, *_: (0, j)),
                pl.BlockSpec(
                    (1, tile_slots, rb, bc), lambda j, t, *_: (t, 0, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (n_tiles, tile_slots, rb, bc), lambda j, t, *_: (0, 0, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, tile_slots, rb, bc), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(blkptr, slot_colblk, tile_rowblk, x, y, tile_mask)
    return out
