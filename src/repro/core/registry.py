"""Kernel-variant registry: the candidate pool the scheduler selects from.

A Variant bundles:
  prepare(csr, **knobs) -> aux dict       (host-side format conversion,
                                           amortized; analogous to cache
                                           warm-up cost in the paper)
  build(aux) -> JITTED callable(*dense)   (the timed/chosen runtime —
                                           compiled once per shape; the
                                           probe's warm-up call absorbs
                                           compilation, as the paper's
                                           protocol excludes it)
  applicable(feat, hw) -> bool            (hard constraints, e.g. vec4's
                                           F%4==0 / VMEM fit)
  estimate via core.estimate              (roofline shortlist)

The XLA `gather_segsum` / `gather_dot` variants are the guardrail
baselines. Pallas variants join the pool on TPU backends (or when
AUTOSAGE_PROBE_PALLAS=1 forces interpret-mode probing).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimate import hub_rows_bound
from repro.core.features import (
    HardwareSpec,
    InputFeatures,
    op_dynamic_vals,
    op_kind,
)
from repro.kernels import ref
from repro.kernels import xla as kx
from repro.sparse.bsr import block_ell_edge_index, csr_to_block_ell, hub_split
from repro.sparse.merge import build_merge_path
from repro.sparse.csr import CSR


@dataclasses.dataclass
class Variant:
    name: str
    op: str
    prepare: Callable[..., Dict]
    build: Callable[[Dict], Callable]
    applicable: Callable[[InputFeatures, HardwareSpec], bool]
    knobs: Dict = dataclasses.field(default_factory=dict)
    is_baseline: bool = False

    def full_name(self) -> str:
        if not self.knobs:
            return self.name
        ks = ",".join(f"{k}={v}" for k, v in sorted(self.knobs.items()))
        return f"{self.name}[{ks}]"

    def timed_prepare(self, csr: CSR, **kwargs) -> Dict:
        """prepare() with the host-side conversion cost accounted to
        ``autosage_prepare_ms{op,variant}`` — layout build time is part
        of the amortized cost story (paper's cache warm-up) and the obs
        flight recorder charges it per variant family."""
        from repro.core import faultinject, obs

        faultinject.fault_point("prepare", name=self.full_name(), op=self.op)
        t0 = time.perf_counter()
        aux = self.prepare(csr, **kwargs)
        obs.REGISTRY.observe(
            "autosage_prepare_ms", (time.perf_counter() - t0) * 1e3,
            op=self.op, variant=self.name,
        )
        return aux


def _dev(aux: Dict) -> Dict:
    return {
        k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
        for k, v in aux.items()
    }


# jitted once per function; aux dicts are pytree arguments so each new
# shape compiles once and repeated calls hit the executable cache
_spmm_gather_jit = jax.jit(kx.spmm_gather_segsum)
_spmm_dense_jit = jax.jit(kx.spmm_dense)
_spmm_ell_jit = jax.jit(kx.spmm_row_ell)
_sddmm_gather_jit = jax.jit(kx.sddmm_gather_dot)


@functools.partial(jax.jit, static_argnums=0)
def _spmm_hub_jit(n_rows: int, aux: Dict, b: jax.Array) -> jax.Array:
    out = jnp.zeros((n_rows, b.shape[1]), jnp.float32)
    if "hub_colind" in aux:
        part = kx.spmm_row_ell({"colind": aux["hub_colind"], "val": aux["hub_val"]}, b)
        out = out.at[aux["hub_rows"]].set(part)
    if "light_colind" in aux:
        part = kx.spmm_row_ell(
            {"colind": aux["light_colind"], "val": aux["light_val"]}, b
        )
        out = out.at[aux["light_rows"]].set(part)
    return out


@jax.jit
def _sddmm_ell_jit(aux: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    ell = kx.sddmm_row_ell(
        {"colind": aux["ell_colind"], "val": aux["ell_val"]}, x, y
    )
    return kx_ell_to_csr(ell, aux)


def kx_ell_to_csr(ell_vals: jax.Array, aux: Dict) -> jax.Array:
    rowptr = aux["rowptr"]
    nnz = aux["colind"].shape[0]
    rows = (
        jnp.searchsorted(rowptr, jnp.arange(nnz, dtype=rowptr.dtype), side="right")
        - 1
    )
    slot = jnp.arange(nnz, dtype=rowptr.dtype) - rowptr[rows]
    return ell_vals[rows, slot]


def _gather_fits(rows_x_width: float, f: InputFeatures, hw: HardwareSpec) -> bool:
    """The XLA row-ELL kernels gather a (rows, width, F) f32 operand and
    XLA materializes it in HBM (a compile for a described v5e refused
    row_ell and hub_split_ell on a 122k-node Products-shaped graph at
    F=256 for 21.6 GB and 44.6 GB). A training step keeps a forward and
    a backward op's buffers plus activations live, so one gather may
    take at most a quarter of HBM."""
    return rows_x_width * f.f * 4 <= hw.hbm_bytes // 4


def _ell_applicable(f: InputFeatures, hw: HardwareSpec) -> bool:
    """Uniform-padding gates shared by every row-ELL variant (spmm, sddmm,
    and the attention pipelines): padding explodes under skew, and the
    padded table and its gathered operand must fit device memory."""
    return (f.deg_max <= max(32.0, 8 * max(f.avg_deg, 1.0))
            and f.n_rows * f.deg_max <= 512_000_000
            and _gather_fits(f.n_rows * f.deg_max, f, hw))


# ----------------------------------------------------------------- SpMM
def _spmm_variants(feat: InputFeatures) -> List[Variant]:
    vs = [
        Variant(
            name="gather_segsum",
            op="spmm",
            prepare=kx.prepare_csr,
            build=lambda aux: (lambda b, a=_dev(aux): _spmm_gather_jit(a, b)),
            applicable=lambda f, hw: True,
            is_baseline=True,
        ),
        Variant(
            name="dense",
            op="spmm",
            prepare=kx.prepare_dense,
            build=lambda aux: (lambda b, a=_dev(aux): _spmm_dense_jit(a, b)),
            # densify only for small AND genuinely dense-ish A — a scaled
            # small graph with 3% density must not leak 'dense' into a
            # benchmark standing in for a 0.2%-dense production graph
            applicable=lambda f, hw: f.n_rows * f.n_cols <= 64_000_000
            and f.density > 0.02,
        ),
        Variant(
            name="row_ell",
            op="spmm",
            prepare=kx.prepare_row_ell,
            build=lambda aux: (lambda b, a=_dev(aux): _spmm_ell_jit(a, b)),
            applicable=lambda f, hw: _ell_applicable(f, hw),
        ),
    ]
    hub_t = int(os.environ.get("AUTOSAGE_HUB_T", feat.hub_threshold()))
    vs.append(
        Variant(
            name="hub_split_ell",
            op="spmm",
            prepare=lambda csr, t=hub_t: kx.prepare_hub_split_ell(csr, t),
            build=lambda aux: (
                lambda b, a=_dev(aux), n=int(aux["n_rows"]): _spmm_hub_jit(n, a, b)
            ),
            # heavy tail: a small set of rows dominates the work (the
            # p99-based skew misses 1%-hub graphs like Table 10's)
            applicable=lambda f, hw, t=hub_t: f.deg_max > 4 * max(f.avg_deg, 1.0)
            and f.deg_max > 2 * max(f.deg_p50, 1.0)
            # hub rows gather deg_max slots each, light rows at most t
            and _gather_fits(
                f.n_rows * t + hub_rows_bound(f, t) * f.deg_max, f, hw
            ),
            knobs={"hub_threshold": hub_t},
        )
    )
    return vs


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _spmm_hub_ragged_jit(n_rows: int, f_tile: int, interpret: bool,
                         aux: Dict, b: jax.Array) -> jax.Array:
    from repro.kernels.spmm_pallas import spmm_ragged_ell

    out = jnp.zeros((n_rows, b.shape[1]), jnp.float32)
    for tag in ("hub", "light"):
        if f"{tag}_blkptr" in aux:
            rows = aux[f"{tag}_rows"]
            part = spmm_ragged_ell(
                aux[f"{tag}_blkptr"], aux[f"{tag}_slot_rowblk"],
                aux[f"{tag}_slot_colblk"], aux[f"{tag}_slot_vals"],
                b, f_tile=f_tile, interpret=interpret,
            )
            out = out.at[rows].set(part[: rows.shape[0]])
    return out


# ------------------------------------------------------- on-chip gates
# What the TPU compiler refuses, reckoned from shapes before anything is
# prepared. Each boundary was found by compiling the kernels for a
# described v5e; tests/test_tpu_compile.py compiles the largest shape
# each gate admits and checks that the next refused shape is gated out.
_LANE_BYTES = 128 * 4  # one f32/int32 row of 128 lanes
# SMEM the compiler keeps for its own scalars next to the tables
SMEM_RESERVED_BYTES = 1024


def smem_table_bytes(shape) -> int:
    """SMEM bytes of one scalar-prefetched int32 table. The compiler
    lays a 1-D table of n words out as ceil(n/128) rows of 128 lanes and
    a 2-D (a, b) table as a rows of b lanes, pads lanes to 128 and rows
    to 8 (a one-row table stays one row)."""
    if len(shape) == 1:
        rows, lanes = -(-shape[0] // 128), 128
    else:
        rows, lanes = shape
    if rows > 1:
        rows = -(-rows // 8) * 8
    return rows * -(-lanes // 128) * _LANE_BYTES


def prefetch_tables(family: str, nrb: int, w: int, n_slots: int,
                    tile_slots: int = 8) -> List[tuple]:
    """Shapes of the tables a kernel family scalar-prefetches:
    "dense" (block-ELL SpMM/SDDMM, fused attention), "ragged" (ragged
    SpMM, ragged attention), "ragged_sddmm", "merge" (merge-path SpMM)
    and "merge_sddmm"."""
    n_tiles = -(-n_slots // tile_slots)
    return {
        "dense": [(nrb, w)],
        "ragged": [(nrb + 1,), (n_slots,), (n_slots,)],
        "ragged_sddmm": [(n_slots,), (n_slots,)],
        "merge": [(nrb + 1,), (n_tiles * tile_slots,), (n_tiles,), (n_tiles,)],
        "merge_sddmm": [(nrb + 1,), (n_tiles * tile_slots,), (n_tiles,)],
    }[family]


def smem_fits(tables, hw: HardwareSpec) -> bool:
    return (sum(smem_table_bytes(s) for s in tables) + SMEM_RESERVED_BYTES
            <= hw.smem_bytes)


def _smem_gate(f: InputFeatures, hw: HardwareSpec, family: str, rb: int = 8,
               bc: int = 8) -> bool:
    """SMEM gate on the feature-derived upper bounds of the layout."""
    nrb, w, n_slots = f.block_ell_bounds(rb, bc)
    return smem_fits(prefetch_tables(family, nrb, w, n_slots), hw)


def merge_vmem_fits(n_rows: int, n_cols: int, tile_slots: int,
                    hw: HardwareSpec, resident_slots: int = 0) -> bool:
    """Merge-path VMEM gate. The kernels keep a whole (rows x 128) f32
    output or X panel and a whole (cols x 128) operand panel resident,
    and the pipeline double-buffers both, as it does the streamed
    (tile_slots, 8, 8) value tile, whose 8 lanes pad to 128. That must
    fit scoped VMEM. The SDDMM kernel also keeps its whole output grid of
    ``resident_slots`` (8, 8) tiles resident, single-buffered, in the
    VMEM beyond the scoped part."""
    rows = -(-n_rows // 8) * 8
    cols = -(-n_cols // 8) * 8
    scoped = 2 * (rows + cols + tile_slots * 8) * _LANE_BYTES
    resident = resident_slots * 8 * _LANE_BYTES
    return (scoped <= hw.vmem_bytes
            and resident <= hw.vmem_total_bytes - hw.vmem_bytes)


def _merge_gate(f: InputFeatures, hw: HardwareSpec, tile_slots: int,
                sddmm: bool = False) -> bool:
    nrb, _, n_slots = f.block_ell_bounds(8, 8)
    tables = prefetch_tables("merge_sddmm" if sddmm else "merge", nrb, 0,
                             n_slots, tile_slots)
    resident = -(-n_slots // tile_slots) * tile_slots if sddmm else 0
    return smem_fits(tables, hw) and merge_vmem_fits(
        f.n_rows, f.n_cols, tile_slots, hw, resident_slots=resident
    )


def _pad_b(b: jax.Array, pad_rows: int, pad_f: int) -> jax.Array:
    # hot path: steady-state calls with a known-static F hit pad_f == 0
    # (see _pallas_spmm_variants) and skip the pad op entirely
    if pad_rows or pad_f:
        return jnp.pad(b, ((0, pad_rows), (0, pad_f)))
    return b


def _pallas_spmm_variants(feat: InputFeatures, interpret: bool) -> List[Variant]:
    """Dense-W and ragged (slot-compacted) block-ELL SpMM variants.

    The dense-W grid runs W = max(nslots) slots for every row block; the
    ragged grid runs the actual slot list of RaggedBlockELL, so its cost
    tracks nnz_dense_tiles. Hub-split composes with ragged: each
    partition gets its own slot-compacted layout (hub rows no longer
    inflate the light partition's W *or* its slot count).
    """
    out = []
    f_static = feat.f  # F is known at decide time: pad width is hoisted
    # f_tile wide variant = the vec4 analogue (needs F % f_tile == 0)
    for ragged in (False, True):
        rbcs = ((8, 8), (16, 8), (8, 16)) if ragged else ((8, 8), (16, 8))
        for rb, bc in rbcs:
            for f_tile in (128, 256):
                def _prep(csr, rb=rb, bc=bc, ragged=ragged):
                    bell = csr_to_block_ell(csr, rb=rb, bc=bc)
                    aux = {
                        "bc": bc,
                        "n_rows": csr.n_rows,
                        "n_col_blocks": bell.n_col_blocks,
                        "padding_frac": bell.padding_frac,
                    }
                    if ragged:
                        rag = bell.to_ragged()
                        aux.update(
                            blkptr=rag.blkptr,
                            slot_rowblk=rag.slot_rowblk,
                            slot_colblk=rag.slot_colblk,
                            slot_vals=rag.slot_vals,
                        )
                    else:
                        aux.update(colblk=bell.colblk, vals=bell.vals)
                    return aux

                def _build(aux, f_tile=f_tile, interpret=interpret,
                           ragged=ragged, f_static=f_static):
                    from repro.kernels.spmm_pallas import (
                        spmm_block_ell,
                        spmm_ragged_ell,
                    )

                    dev = _dev(aux)
                    bc = aux["bc"]
                    n = int(aux["n_rows"])
                    padded_cols = aux["n_col_blocks"] * bc
                    pad_f_static = (-f_static) % f_tile

                    def run(b):
                        f = b.shape[1]
                        pad_f = (pad_f_static if f == f_static
                                 else (-f) % f_tile)
                        bp = _pad_b(b, padded_cols - b.shape[0], pad_f)
                        if ragged:
                            o = spmm_ragged_ell(
                                dev["blkptr"], dev["slot_rowblk"],
                                dev["slot_colblk"], dev["slot_vals"],
                                bp, f_tile=f_tile, interpret=interpret,
                            )
                        else:
                            o = spmm_block_ell(
                                dev["colblk"], dev["vals"], bp,
                                f_tile=f_tile, interpret=interpret,
                            )
                        return o[:n, :f]

                    return run

                out.append(
                    Variant(
                        name="ragged_ell_pallas" if ragged else "block_ell_pallas",
                        op="spmm",
                        prepare=_prep,
                        build=_build,
                        applicable=lambda f, hw, rb=rb, bc=bc, ragged=ragged: (
                            f.f >= 32 and _smem_gate(
                                f, hw, "ragged" if ragged else "dense", rb, bc)
                        ),
                        knobs={"rb": rb, "bc": bc, "f_tile": f_tile,
                               **({"ragged": True} if ragged else {})},
                    )
                )
    # merge-path: nnz-balanced slot tiling (sparse/merge.py); whole B
    # column panel + whole output panel stay VMEM-resident, so the
    # variant is gated on panel fit — outside it, the ragged family
    # takes over
    for tile_slots in (8, 16):
        def _prep_merge(csr, tile_slots=tile_slots):
            bell = csr_to_block_ell(csr, rb=8, bc=8)
            mp = build_merge_path(bell.to_ragged(), tile_slots=tile_slots)
            return {
                "bc": 8,
                "n_rows": csr.n_rows,
                "n_col_blocks": mp.n_col_blocks,
                "padding_frac": bell.padding_frac,
                "blkptr": mp.blkptr,
                "slot_colblk": mp.slot_colblk,
                "tile_rowblk": mp.tile_rowblk,
                "tile_nslots": mp.tile_nslots,
                "tile_vals": mp.tile_vals,
            }

        def _build_merge(aux, interpret=interpret, f_static=f_static):
            from repro.kernels.spmm_pallas import spmm_merge_path

            dev = _dev(aux)
            n = int(aux["n_rows"])
            padded_cols = aux["n_col_blocks"] * aux["bc"]
            pad_f_static = (-f_static) % 128

            def run(b):
                f = b.shape[1]
                pad_f = pad_f_static if f == f_static else (-f) % 128
                bp = _pad_b(b, padded_cols - b.shape[0], pad_f)
                o = spmm_merge_path(
                    dev["blkptr"], dev["slot_colblk"], dev["tile_rowblk"],
                    dev["tile_nslots"], dev["tile_vals"], bp,
                    f_tile=128, interpret=interpret,
                )
                return o[:n, :f]

            return run

        out.append(
            Variant(
                name="merge_path_pallas",
                op="spmm",
                prepare=_prep_merge,
                build=_build_merge,
                applicable=lambda f, hw, ts=tile_slots: f.f >= 32
                and _merge_gate(f, hw, ts),
                knobs={"rb": 8, "bc": 8, "f_tile": 128,
                       "tile_slots": tile_slots, "ragged": True},
            )
        )
    # hub-split x ragged: per-partition slot compaction
    hub_t = int(os.environ.get("AUTOSAGE_HUB_T", feat.hub_threshold()))

    def _prep_hub_ragged(csr, t=hub_t):
        hub, light = hub_split(csr, t)
        aux = {"n_rows": csr.n_rows, "bc": 8,
               "n_col_blocks": -(-csr.n_cols // 8)}
        for tag, rows in (("hub", hub), ("light", light)):
            if rows.size == 0:
                continue
            bell = csr_to_block_ell(csr, rb=8, bc=8, rows=rows)
            rag = bell.to_ragged()
            aux.update({
                f"{tag}_blkptr": rag.blkptr,
                f"{tag}_slot_rowblk": rag.slot_rowblk,
                f"{tag}_slot_colblk": rag.slot_colblk,
                f"{tag}_slot_vals": rag.slot_vals,
                f"{tag}_rows": rows.astype(np.int32),
                # dense-W padding this partition's compaction avoided —
                # recorded for the decide-event audit trail
                f"{tag}_padding_frac": bell.padding_frac,
            })
        return aux

    def _build_hub_ragged(aux, interpret=interpret, f_static=f_static):
        dev = _dev(aux)
        n = int(aux["n_rows"])
        padded_cols = aux["n_col_blocks"] * aux["bc"]
        pad_f_static = (-f_static) % 128

        def run(b):
            f = b.shape[1]
            pad_f = pad_f_static if f == f_static else (-f) % 128
            bp = _pad_b(b, padded_cols - b.shape[0], pad_f)
            return _spmm_hub_ragged_jit(n, 128, interpret, dev, bp)[:, :f]

        return run

    out.append(
        Variant(
            name="hub_ragged_pallas",
            op="spmm",
            prepare=_prep_hub_ragged,
            build=_build_hub_ragged,
            # each partition's tables are bounded by the whole graph's
            applicable=lambda f, hw: f.f >= 32
            and f.deg_max > 4 * max(f.avg_deg, 1.0)
            and _smem_gate(f, hw, "ragged"),
            knobs={"rb": 8, "bc": 8, "f_tile": 128, "ragged": True,
                   "hub_threshold": hub_t},
        )
    )
    return out


# ------------------------------------------------ dynamic-vals SpMM
# Runtime-valued SpMM variants for the grad ops (core/autodiff.py):
# sddmm/attention backward scatter the *cotangent* through the sparsity
# pattern, so the sparse values are a traced jax array that changes per
# step and cannot be baked into the prepared layout. These runners take
# (vals, b): prepare converts the structure once (memoizable), and each
# call scatters the nnz-vector into the layout's value table on device.

@jax.jit
def _spmm_gather_dyn_jit(aux: Dict, vals: jax.Array, b: jax.Array) -> jax.Array:
    return ref.spmm_ref(aux["rowptr"], aux["colind"], vals, b)


@jax.jit
def _spmm_ell_dyn_jit(aux: Dict, vals: jax.Array, b: jax.Array) -> jax.Array:
    # each edge owns one (row, slot) cell, so duplicates keep distinct
    # slots and .set preserves accumulate-on-duplicate SpMM semantics
    table = (
        jnp.zeros(aux["colind"].shape, jnp.float32)
        .at[aux["edge_row"], aux["edge_slot"]]
        .set(vals.astype(jnp.float32))
    )
    return kx.spmm_row_ell({"colind": aux["colind"], "val": table}, b)


def _prep_csr_structural(csr: CSR) -> Dict[str, np.ndarray]:
    return {
        "rowptr": np.asarray(csr.rowptr, np.int32),
        "colind": np.asarray(csr.colind, np.int32),
    }


def _prep_row_ell_dyn(csr: CSR) -> Dict[str, np.ndarray]:
    s = csr.structural()
    ell = kx.prepare_row_ell(s)
    return {"colind": ell["colind"], **kx.prepare_edge_slots(s)}


def _spmm_dyn_variants(feat: InputFeatures) -> List[Variant]:
    return [
        Variant(
            name="gather_segsum",
            op=feat.op,
            prepare=_prep_csr_structural,
            build=lambda aux: (
                lambda vals, b, a=_dev(aux): _spmm_gather_dyn_jit(a, vals, b)
            ),
            applicable=lambda f, hw: True,
            is_baseline=True,
        ),
        Variant(
            name="row_ell",
            op=feat.op,
            prepare=_prep_row_ell_dyn,
            build=lambda aux: (
                lambda vals, b, a=_dev(aux): _spmm_ell_dyn_jit(a, vals, b)
            ),
            applicable=lambda f, hw: _ell_applicable(f, hw),
        ),
    ]


def _pallas_spmm_dyn_variants(feat: InputFeatures, interpret: bool) -> List[Variant]:
    """Slot-compacted ragged variant with a per-call value scatter: the
    block-ELL edge index maps each CSR edge to its (slot, r, c) cell, and
    .add accumulates duplicates exactly like the segment-sum baseline."""
    out = []
    f_static = feat.f
    for rb, bc in ((8, 8), (16, 8)):
        def _prep(csr, rb=rb, bc=bc):
            s_csr = csr.structural()
            bell = csr_to_block_ell(s_csr, rb=rb, bc=bc)
            rag = bell.to_ragged()
            idx = block_ell_edge_index(s_csr, bell)
            return {
                "rb": rb,
                "bc": bc,
                "n_rows": csr.n_rows,
                "n_col_blocks": bell.n_col_blocks,
                "n_slots": int(rag.slot_vals.shape[0]),
                "padding_frac": bell.padding_frac,
                "blkptr": rag.blkptr,
                "slot_rowblk": rag.slot_rowblk,
                "slot_colblk": rag.slot_colblk,
                "edge_slot": (
                    rag.blkptr[idx["edge_blkrow"]] + idx["edge_slot"]
                ).astype(np.int32),
                "edge_r": idx["edge_r"],
                "edge_c": idx["edge_c"],
            }

        def _build(aux, interpret=interpret, f_static=f_static):
            from repro.kernels.spmm_pallas import spmm_ragged_ell

            dev = _dev(aux)
            rb, bc = aux["rb"], aux["bc"]
            n = int(aux["n_rows"])
            n_slots = int(aux["n_slots"])
            padded_cols = aux["n_col_blocks"] * bc
            pad_f_static = (-f_static) % 128

            def run(vals, b):
                f = b.shape[1]
                pad_f = pad_f_static if f == f_static else (-f) % 128
                bp = _pad_b(b, padded_cols - b.shape[0], pad_f)
                slot_vals = (
                    jnp.zeros((n_slots, rb, bc), jnp.float32)
                    .at[dev["edge_slot"], dev["edge_r"], dev["edge_c"]]
                    .add(vals.astype(jnp.float32))
                )
                o = spmm_ragged_ell(
                    dev["blkptr"], dev["slot_rowblk"], dev["slot_colblk"],
                    slot_vals, bp, f_tile=128, interpret=interpret,
                )
                return o[:n, :f]

            return run

        out.append(
            Variant(
                name="ragged_ell_pallas",
                op=feat.op,
                prepare=_prep,
                build=_build,
                applicable=lambda f, hw, rb=rb, bc=bc: f.f >= 32
                and f.nnz * rb * bc * 4 <= 512_000_000
                and _smem_gate(f, hw, "ragged", rb, bc),
                knobs={"rb": rb, "bc": bc, "f_tile": 128, "ragged": True},
            )
        )

    # merge-path with a per-call value scatter: the runtime cotangent
    # lands in a flat (padded_slots, rb, bc) table that reshapes into the
    # merge tiling (the tiling is a pure reshape of the slot stream)
    def _prep_merge_dyn(csr):
        s_csr = csr.structural()
        bell = csr_to_block_ell(s_csr, rb=8, bc=8)
        rag = bell.to_ragged()
        mp = build_merge_path(rag, tile_slots=8)
        idx = block_ell_edge_index(s_csr, bell)
        return {
            "n_rows": csr.n_rows,
            "n_col_blocks": mp.n_col_blocks,
            "n_tiles": mp.n_tiles,
            "tile_slots": mp.tile_slots,
            "padding_frac": bell.padding_frac,
            "blkptr": mp.blkptr,
            "slot_colblk": mp.slot_colblk,
            "tile_rowblk": mp.tile_rowblk,
            "tile_nslots": mp.tile_nslots,
            "edge_slot": (
                rag.blkptr[idx["edge_blkrow"]] + idx["edge_slot"]
            ).astype(np.int32),
            "edge_r": idx["edge_r"],
            "edge_c": idx["edge_c"],
        }

    def _build_merge_dyn(aux, interpret=interpret, f_static=f_static):
        from repro.kernels.spmm_pallas import spmm_merge_path

        dev = _dev(aux)
        n = int(aux["n_rows"])
        n_tiles = int(aux["n_tiles"])
        tile_slots = int(aux["tile_slots"])
        padded_cols = aux["n_col_blocks"] * 8
        pad_f_static = (-f_static) % 128

        def run(vals, b):
            f = b.shape[1]
            pad_f = pad_f_static if f == f_static else (-f) % 128
            bp = _pad_b(b, padded_cols - b.shape[0], pad_f)
            tile_vals = (
                jnp.zeros((n_tiles * tile_slots, 8, 8), jnp.float32)
                .at[dev["edge_slot"], dev["edge_r"], dev["edge_c"]]
                .add(vals.astype(jnp.float32))
                .reshape(n_tiles, tile_slots, 8, 8)
            )
            o = spmm_merge_path(
                dev["blkptr"], dev["slot_colblk"], dev["tile_rowblk"],
                dev["tile_nslots"], tile_vals, bp,
                f_tile=128, interpret=interpret,
            )
            return o[:n, :f]

        return run

    out.append(
        Variant(
            name="merge_path_pallas",
            op=feat.op,
            prepare=_prep_merge_dyn,
            build=_build_merge_dyn,
            applicable=lambda f, hw: f.f >= 32
            and f.nnz * 8 * 8 * 4 <= 512_000_000
            and _merge_gate(f, hw, 8),
            knobs={"rb": 8, "bc": 8, "f_tile": 128, "tile_slots": 8,
                   "ragged": True},
        )
    )
    return out


# ---------------------------------------------------------------- SDDMM
def _sddmm_variants(feat: InputFeatures) -> List[Variant]:
    return [
        Variant(
            name="gather_dot",
            op="sddmm",
            prepare=kx.prepare_csr,
            build=lambda aux: (
                lambda x, y, a=_dev(aux): _sddmm_gather_jit(a, x, y)
            ),
            applicable=lambda f, hw: True,
            is_baseline=True,
        ),
        Variant(
            name="row_ell",
            op="sddmm",
            # NOTE: distinct key names — the CSR dict also has 'colind'
            # (flat nnz), which must not clobber the (n, K) ELL table
            prepare=lambda csr: {
                **{f"ell_{k}": v for k, v in kx.prepare_row_ell(csr).items()},
                **kx.prepare_csr(csr),
            },
            build=lambda aux: (
                lambda x, y, a=_dev(aux): _sddmm_ell_jit(a, x, y)
            ),
            applicable=lambda f, hw: _ell_applicable(f, hw),
        ),
    ]


def _sddmm_chunk(f: int) -> tuple:
    """(padded_f, f_chunk) for the SDDMM kernels: pad F to a multiple of
    32 (not always 128 — an F=16 input padded to 128 would do 8x the
    real compute and X/Y traffic) and pick the largest chunk in
    {128, 64, 32} that divides it."""
    padded = -(-max(f, 1) // 32) * 32
    for chunk in (128, 64, 32):
        if padded % chunk == 0:
            return padded, chunk
    return padded, 32


def _pallas_sddmm_variants(feat: InputFeatures, interpret: bool) -> List[Variant]:
    """Block-ELL SDDMM variants (dense-W and ragged) that return the
    baseline's CSR-ordered nnz vector: the kernel emits (rb, bc) tiles
    and a precomputed per-edge index gathers each edge's cell back out.
    The mask is built from structure alone (values dropped), so
    explicitly zero-weighted edges still get their <X_i, Y_j> — matching
    gather_dot semantics exactly.
    """
    out = []
    f_static = feat.f
    for ragged in (False, True):
        for rb, bc in ((8, 8), (16, 8)):
            def _prep(csr, rb=rb, bc=bc, ragged=ragged):
                s_csr = CSR(csr.rowptr, csr.colind, None, csr.n_rows, csr.n_cols)
                bell = csr_to_block_ell(s_csr, rb=rb, bc=bc)
                idx = block_ell_edge_index(s_csr, bell)
                aux = {
                    "bc": bc,
                    "padded_rows": bell.padded_rows,
                    "n_col_blocks": bell.n_col_blocks,
                    "padding_frac": bell.padding_frac,
                    "edge_r": idx["edge_r"],
                    "edge_c": idx["edge_c"],
                }
                if ragged:
                    rag = bell.to_ragged()
                    aux.update(
                        slot_rowblk=rag.slot_rowblk,
                        slot_colblk=rag.slot_colblk,
                        mask=(rag.slot_vals != 0).astype(np.float32),
                        edge_slot=(
                            rag.blkptr[idx["edge_blkrow"]] + idx["edge_slot"]
                        ).astype(np.int32),
                    )
                else:
                    aux.update(
                        colblk=bell.colblk,
                        mask=(bell.vals != 0).astype(np.float32),
                        edge_blkrow=idx["edge_blkrow"],
                        edge_slot=idx["edge_slot"],
                    )
                return aux

            def _build(aux, interpret=interpret, ragged=ragged, f_static=f_static):
                from repro.kernels.sddmm_pallas import (
                    sddmm_block_ell,
                    sddmm_ragged_ell,
                )

                dev = _dev(aux)
                bc = aux["bc"]
                padded_rows = aux["padded_rows"]
                padded_cols = aux["n_col_blocks"] * bc
                padded_f_static, chunk_static = _sddmm_chunk(f_static)

                def run(x, y):
                    f = x.shape[1]
                    padded_f, chunk = (
                        (padded_f_static, chunk_static) if f == f_static
                        else _sddmm_chunk(f)
                    )
                    xp = _pad_b(x, padded_rows - x.shape[0], padded_f - f)
                    yp = _pad_b(y, padded_cols - y.shape[0], padded_f - f)
                    if ragged:
                        tiles = sddmm_ragged_ell(
                            dev["slot_rowblk"], dev["slot_colblk"],
                            dev["mask"], xp, yp, f_chunk=chunk,
                            interpret=interpret,
                        )
                        return tiles[dev["edge_slot"], dev["edge_r"], dev["edge_c"]]
                    tiles = sddmm_block_ell(
                        dev["colblk"], dev["mask"], xp, yp, f_chunk=chunk,
                        interpret=interpret,
                    )
                    return tiles[
                        dev["edge_blkrow"], dev["edge_slot"],
                        dev["edge_r"], dev["edge_c"],
                    ]

                return run

            out.append(
                Variant(
                    name="ragged_ell_pallas" if ragged else "block_ell_pallas",
                    op="sddmm",
                    prepare=_prep,
                    build=_build,
                    applicable=(
                        # tile-table memory, per-variant blocking: ragged
                        # holds <= nnz slots of rb*bc*4 bytes; the dense-W
                        # (nrb, W, rb, bc) table is ~n_rows * W * bc * 4
                        # bytes with W up to deg_max under skew
                        (lambda f, hw, rb=rb, bc=bc: f.f >= 16
                         and f.nnz * rb * bc * 4 <= 512_000_000
                         and _smem_gate(f, hw, "ragged_sddmm", rb, bc))
                        if ragged
                        else (lambda f, hw, rb=rb, bc=bc: f.f >= 16
                              and f.n_rows * f.deg_max * bc * 4 <= 512_000_000
                              and _smem_gate(f, hw, "dense", rb, bc))
                    ),
                    knobs={"rb": rb, "bc": bc,
                           **({"ragged": True} if ragged else {})},
                )
            )

    # merge-path: nnz-balanced slot tiles; the flat reshape of the tile
    # output is slot-ordered, so the ragged family's per-edge gather
    # indices apply unchanged
    for tile_slots in (8, 16):
        def _prep_merge(csr, tile_slots=tile_slots):
            s_csr = CSR(csr.rowptr, csr.colind, None, csr.n_rows, csr.n_cols)
            bell = csr_to_block_ell(s_csr, rb=8, bc=8)
            rag = bell.to_ragged()
            mp = build_merge_path(rag, tile_slots=tile_slots)
            idx = block_ell_edge_index(s_csr, bell)
            return {
                "bc": 8,
                "padded_rows": mp.padded_rows,
                "n_col_blocks": mp.n_col_blocks,
                "n_slots": mp.n_slots,
                "padding_frac": bell.padding_frac,
                "blkptr": mp.blkptr,
                "slot_colblk": mp.slot_colblk,
                "tile_rowblk": mp.tile_rowblk,
                "tile_mask": (mp.tile_vals != 0).astype(np.float32),
                "edge_slot": (
                    rag.blkptr[idx["edge_blkrow"]] + idx["edge_slot"]
                ).astype(np.int32),
                "edge_r": idx["edge_r"],
                "edge_c": idx["edge_c"],
            }

        def _build_merge(aux, interpret=interpret, f_static=f_static):
            from repro.kernels.sddmm_pallas import sddmm_merge_path

            dev = _dev(aux)
            padded_rows = aux["padded_rows"]
            padded_cols = aux["n_col_blocks"] * aux["bc"]
            padded_f_static, chunk_static = _sddmm_chunk(f_static)

            def run(x, y):
                f = x.shape[1]
                padded_f, chunk = (
                    (padded_f_static, chunk_static) if f == f_static
                    else _sddmm_chunk(f)
                )
                xp = _pad_b(x, padded_rows - x.shape[0], padded_f - f)
                yp = _pad_b(y, padded_cols - y.shape[0], padded_f - f)
                tiles = sddmm_merge_path(
                    dev["blkptr"], dev["slot_colblk"], dev["tile_rowblk"],
                    dev["tile_mask"], xp, yp, f_chunk=chunk,
                    interpret=interpret,
                )
                flat = tiles.reshape(-1, 8, 8)
                return flat[dev["edge_slot"], dev["edge_r"], dev["edge_c"]]

            return run

        out.append(
            Variant(
                name="merge_path_pallas",
                op="sddmm",
                prepare=_prep_merge,
                build=_build_merge,
                applicable=lambda f, hw, ts=tile_slots: f.f >= 16
                and f.nnz * 8 * 8 * 4 <= 512_000_000
                and _merge_gate(f, hw, ts, sddmm=True),
                knobs={"rb": 8, "bc": 8, "tile_slots": tile_slots,
                       "ragged": True},
            )
        )
    return out


# ------------------------------------------ attention (whole pipelines)
# Composed SDDMM -> row-softmax -> SpMM candidates, one Variant per
# {sddmm layout x spmm layout} pair, plus the fused flash-style Pallas
# kernel. The pipeline scheduler (core/pipeline.py) probes these
# end-to-end; a per-op decide can never justify the fused kernel because
# its benefit (no logits/probs HBM round-trip) lies *between* ops.

_attn_csr_jit = jax.jit(kx.attention_csr)
_attn_ell_jit = jax.jit(kx.attention_ell)
_attn_ell_csr_jit = jax.jit(kx.attention_ell_to_csr)
_attn_csr_ell_jit = jax.jit(kx.attention_csr_to_ell)


def _structural(csr: CSR) -> CSR:
    """Attention uses the sparsity pattern only. Drop stored values so the
    ELL/block-ELL masks (built from val != 0) keep explicitly zero-weighted
    edges — the CSR baseline ignores values and includes them."""
    return CSR(csr.rowptr, csr.colind, None, csr.n_rows, csr.n_cols)


def _prepare_attn_ell(csr: CSR) -> Dict:
    return kx.prepare_row_ell(_structural(csr))


def _prepare_attn_mixed(csr: CSR) -> Dict:
    return {
        **kx.prepare_csr(csr),
        **{f"ell_{k}": v for k, v in _prepare_attn_ell(csr).items()},
        **kx.prepare_edge_slots(csr),
    }


def _prepare_attn_fused(csr: CSR, rb: int, bc: int) -> Dict:
    bell = csr_to_block_ell(_structural(csr), rb=rb, bc=bc)
    return {
        "colblk": bell.colblk,
        "mask": (bell.vals != 0).astype(np.float32),
        "padded_rows": bell.padded_rows,
        "n_col_pad": bell.n_col_blocks * bc,
        "n_rows": bell.n_rows,
    }


def _build_attn_fused(aux: Dict, interpret: bool) -> Callable:
    from repro.kernels.attention_pallas import fused_csr_attention

    colblk = jnp.asarray(aux["colblk"])
    mask = jnp.asarray(aux["mask"])
    pr, ncp, n = int(aux["padded_rows"]), int(aux["n_col_pad"]), int(aux["n_rows"])

    def run(q, k, v):
        qp = jnp.pad(q, ((0, pr - q.shape[0]), (0, 0)))
        kp = jnp.pad(k, ((0, ncp - k.shape[0]), (0, 0)))
        vp = jnp.pad(v, ((0, ncp - v.shape[0]), (0, 0)))
        return fused_csr_attention(colblk, mask, qp, kp, vp, interpret=interpret)[:n]

    return run


def _prepare_attn_ragged(csr: CSR, rb: int, bc: int) -> Dict:
    bell = csr_to_block_ell(_structural(csr), rb=rb, bc=bc)
    rag = bell.to_ragged()
    return {
        "blkptr": rag.blkptr,
        "slot_rowblk": rag.slot_rowblk,
        "slot_colblk": rag.slot_colblk,
        "mask": (rag.slot_vals != 0).astype(np.float32),
        "padded_rows": rag.padded_rows,
        "n_col_pad": rag.n_col_blocks * bc,
        "n_rows": rag.n_rows,
        "padding_frac": bell.padding_frac,
    }


def _build_attn_ragged(aux: Dict, interpret: bool) -> Callable:
    from repro.kernels.attention_pallas import fused_ragged_attention

    blkptr = jnp.asarray(aux["blkptr"])
    rowblk = jnp.asarray(aux["slot_rowblk"])
    colblk = jnp.asarray(aux["slot_colblk"])
    mask = jnp.asarray(aux["mask"])
    pr, ncp, n = int(aux["padded_rows"]), int(aux["n_col_pad"]), int(aux["n_rows"])

    def run(q, k, v):
        qp = jnp.pad(q, ((0, pr - q.shape[0]), (0, 0)))
        kp = jnp.pad(k, ((0, ncp - k.shape[0]), (0, 0)))
        vp = jnp.pad(v, ((0, ncp - v.shape[0]), (0, 0)))
        return fused_ragged_attention(
            blkptr, rowblk, colblk, mask, qp, kp, vp, interpret=interpret
        )[:n]

    return run


def _attention_variants(feat: InputFeatures, include_pallas: bool,
                        interpret: bool) -> List[Variant]:
    stage_impls = {
        ("gather_dot", "gather_segsum"): (kx.prepare_csr, _attn_csr_jit),
        ("row_ell", "row_ell"): (_prepare_attn_ell, _attn_ell_jit),
        ("row_ell", "gather_segsum"): (_prepare_attn_mixed, _attn_ell_csr_jit),
        ("gather_dot", "row_ell"): (_prepare_attn_mixed, _attn_csr_ell_jit),
    }
    vs = []
    for (s, m), (prep, jit_fn) in stage_impls.items():
        needs_ell = "row_ell" in (s, m)
        vs.append(
            Variant(
                name="pipe",
                op="attention",
                prepare=prep,
                build=lambda aux, j=jit_fn: (
                    lambda q, k, v, a=_dev(aux): j(a, q, k, v)
                ),
                applicable=(
                    (lambda f, hw: _ell_applicable(f, hw)) if needs_ell
                    else (lambda f, hw: True)
                ),
                knobs={"sddmm": s, "spmm": m},
                is_baseline=(s == "gather_dot" and m == "gather_segsum"),
            )
        )
    if include_pallas:
        rb, bc = 8, 8
        vs.append(
            Variant(
                name="fused_attention_pallas",
                op="attention",
                prepare=lambda csr, rb=rb, bc=bc: _prepare_attn_fused(csr, rb, bc),
                build=lambda aux, interpret=interpret: _build_attn_fused(aux, interpret),
                # duplicate edges merge in block-ELL masking (different
                # function than the pipeline computes); mask tile memory
                # grows with n * deg_max under skew
                applicable=lambda f, hw: not f.dup_edges
                and f.n_rows * f.deg_max * bc <= 512_000_000
                and _smem_gate(f, hw, "dense", rb, bc),
                knobs={"rb": rb, "bc": bc},
            )
        )
        vs.append(
            Variant(
                name="ragged_attention_pallas",
                op="attention",
                prepare=lambda csr, rb=rb, bc=bc: _prepare_attn_ragged(csr, rb, bc),
                build=lambda aux, interpret=interpret: _build_attn_ragged(aux, interpret),
                # same duplicate-edge gate as the dense fused kernel, but
                # the mask table scales with actual slots (<= nnz tiles),
                # not n_rows x deg_max — skew no longer blows up memory
                applicable=lambda f, hw: not f.dup_edges
                and f.nnz * rb * bc * 4 <= 512_000_000
                and _smem_gate(f, hw, "ragged", rb, bc),
                knobs={"rb": rb, "bc": bc, "ragged": True},
            )
        )
    return vs


# ------------------------------------------------------------ registry
def candidates(
    feat: InputFeatures, hw: HardwareSpec, include_pallas: Optional[bool] = None
) -> List[Variant]:
    if include_pallas is None:
        on_tpu = jax.devices()[0].platform == "tpu"
        include_pallas = on_tpu or os.environ.get("AUTOSAGE_PROBE_PALLAS") == "1"
    interpret = jax.devices()[0].platform != "tpu"
    # grad ops (core/autodiff.py) route through their structural compute
    # kind: e.g. "spmm_bwd_b" draws SpMM candidates (it runs on the
    # transposed CSR), "spmm_bwd_vals" draws SDDMM candidates. Ops with
    # runtime (cotangent-dependent) sparse values get the dynamic-vals
    # family, whose runners take (vals, b).
    kind = op_kind(feat.op)
    if kind == "spmm" and op_dynamic_vals(feat.op):
        vs = _spmm_dyn_variants(feat)
        if include_pallas:
            vs += _pallas_spmm_dyn_variants(feat, interpret)
    elif kind == "spmm":
        vs = _spmm_variants(feat)
        if include_pallas:
            vs += _pallas_spmm_variants(feat, interpret)
    elif kind == "sddmm":
        vs = _sddmm_variants(feat)
        if include_pallas:
            vs += _pallas_sddmm_variants(feat, interpret)
    elif kind == "attention":
        vs = _attention_variants(feat, include_pallas, interpret)
    else:
        raise KeyError(feat.op)
    return [v for v in vs if v.applicable(feat, hw)]


def baseline(feat: InputFeatures, hw: HardwareSpec) -> Variant:
    for v in candidates(feat, hw, include_pallas=False):
        if v.is_baseline:
            return v
    raise RuntimeError(f"no baseline for op {feat.op}")
