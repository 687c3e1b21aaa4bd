"""Roofline-style candidate estimates (paper §4.2 'shortlist candidates
with a roofline-style estimate').

For each variant we model bytes moved and FLOPs as a function of the
input features, then t_est = max(bytes / hbm_bw, flops / peak_flops).
The estimate only needs to *rank* candidates well enough that the true
winner lands in the probed top-k; the guardrail absorbs estimate error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable

from repro.core.features import (
    HardwareSpec,
    InputFeatures,
    op_dynamic_vals,
    op_kind,
)

BYTES_F32 = 4
# padded slots over nnz of kernels/xla.py's degree buckets (bucket_ell):
# 1.01-1.14 on the benchmark's Products- and Reddit-shaped graphs and
# their transposes
BUCKET_ELL_PADDING = 1.1


def estimates_for(
    feat: InputFeatures, hw: HardwareSpec, variants: Iterable
) -> Dict[str, float]:
    """Roofline estimate (ms) per variant full name, on ``hw``.

    The one place estimates are derived for a candidate pool: the
    shortlist stage (core/scheduler.py) and the cross-device transfer
    re-rank (core/transfer.py) both call it, so a peer's `est_ms` at
    probe time and the local re-estimate are guaranteed to come from the
    same model — the residual probe/est is then a pure device+input
    calibration term, not a model-version artifact. H heads of width F
    are costed as one op of width H*F."""
    if feat.heads != 1:
        feat = dataclasses.replace(feat, f=feat.f * feat.heads, heads=1)
    return {
        v.full_name(): estimate(feat, hw, v.name, v.knobs) * 1e3
        for v in variants
    }


def _roofline(bytes_moved: float, flops: float, hw: HardwareSpec) -> float:
    return max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops)


def _block_ell_elems(
    feat: InputFeatures, knobs: Dict, ragged: bool, variant: str = ""
) -> float:
    """Estimated padded *elements* a block-ELL kernel touches:
    n_row_blocks x W x rb x bc for dense-W, the actual slot mass for
    ragged. This asymmetry — dense-W pays max(nslots) everywhere, ragged
    pays sum(nslots) — is the whole point of the slot-compacted family,
    and exposing it here lets decide rank ragged above dense-W on skewed
    inputs without spending a probe.

    The element mass is modeled at the canonical rb=bc=8 blocking and
    treated as blocking-invariant (re-tiling repartitions roughly the
    same padded mass); the knob-dependent quantity is the *step count*,
    which scales inversely with tile size — see _block_ell_steps. This
    keeps non-canonical (rb, bc) variants comparable instead of charging
    them rb*bc/64 times the canonical mass.

    Hand-built features without degree data (ell_width_est == 0) fall
    back, in order: a caller-supplied ``padding_waste`` knob (legacy
    padded-elems/nnz multiplier), the feature's own measured
    ``padding_waste`` fraction, and only then the magic nnz-multiplier
    guess — which is counted in the metrics registry so a silently
    mis-ranked estimate shows up in telemetry instead of nowhere.
    """
    if feat.ell_width_est > 0:
        tiles8 = feat.ragged_tiles_est() if ragged else feat.dense_tiles_est()
        elems = tiles8 * 64.0
    elif "padding_waste" in knobs:
        elems = feat.nnz * knobs["padding_waste"]  # legacy multiplier
        if ragged:
            elems /= 4.0
    elif feat.padding_waste > 0.0:
        # measured waste fraction but no width estimate: ragged kernels
        # run ~the stored mass; dense-W pays it back up through the
        # padding fraction (waste = 1 - stored/padded)
        frac = min(feat.padding_waste, 0.98)
        elems = feat.nnz if ragged else feat.nnz / (1.0 - frac)
    else:
        from repro.core import obs  # late import: obs pulls no deps, but
        # estimate is imported by nearly everything — keep startup flat

        obs.REGISTRY.inc(
            "autosage_estimate_magic_fallback_total",
            op=feat.op,
            variant=variant or "?",
        )
        elems = feat.nnz * 8.0  # magic: padded elems / nnz
        if ragged:
            elems /= 4.0  # unknown structure: assume moderate compaction
    return max(elems, 64.0)


def _block_ell_steps(elems: float, knobs: Dict) -> float:
    """Grid steps = padded elements / tile size: a (16, 8) tile halves
    the step count of an (8, 8) tile over the same element mass."""
    return elems / (knobs.get("rb", 8) * knobs.get("bc", 8))


# Modeled effective parallelism of the slot-grid dimension. Row-
# partitioned kernels run each row('s block)'s whole slot chain in one
# grid cell; with ~_P_EFF cells in flight, a chain longer than the fair
# share nnz/_P_EFF serializes the excess. Coarse on purpose — like the
# rest of the roofline it only has to *rank*: the boundary it draws
# (merge-path overtakes at deg_max/deg_mean >= ~64) is what
# features.balance_bin quantizes.
_P_EFF = 16.0


def _row_serial_penalty(
    feat: InputFeatures, hw: HardwareSpec, knobs: Dict, weight: float = 1.0
) -> float:
    """Serialization tax of row-partitioned families under degree skew.

    The heaviest row's slot chain (deg_max/bc slots) runs in ONE grid
    cell; whatever exceeds the fair per-cell share (nnz/_P_EFF elements)
    is pure critical-path extension, charged at the per-slot step time.
    Merge-path variants split the nnz stream instead, so they never pay
    this term — that asymmetry is what ranks them first on hub-dominated
    inputs without spending a probe. ``weight`` < 1 for hub-split
    variants, which already peel the heavy rows into their own partition.
    """
    if feat.balance() < 8.0:
        return 0.0
    rb = knobs.get("rb", 8)
    bc = knobs.get("bc", 8)
    max_chain = feat.deg_max / bc  # slots of the heaviest row's chain
    fair = feat.nnz / _P_EFF / (rb * bc)
    excess = max(0.0, max_chain - fair)
    step_t = 2.0 * rb * bc * feat.f / hw.peak_flops + 2e-7
    return weight * excess * step_t


def estimate_spmm(feat: InputFeatures, hw: HardwareSpec, variant: str,
                  knobs: Dict) -> float:
    n, f, nnz = feat.n_rows, feat.f, feat.nnz
    out_bytes = n * f * BYTES_F32
    if variant == "gather_segsum":
        # gather B rows per nnz + indices + output, plus segment bookkeeping
        bytes_moved = nnz * (f * BYTES_F32 + 8) + out_bytes * 2.0
        flops = 2.0 * nnz * f
    elif variant == "dense":
        bytes_moved = (feat.n_rows * feat.n_cols + feat.n_cols * f) * BYTES_F32 + out_bytes
        flops = 2.0 * feat.n_rows * feat.n_cols * f
    elif variant == "bucket_ell":
        # gather the padded slots, sum them, put the rows back in order
        padded = nnz * BUCKET_ELL_PADDING
        bytes_moved = padded * (f * BYTES_F32 + 8) + out_bytes * 2.0
        flops = 2.0 * padded * f
    elif variant == "row_ell":
        k = max(feat.deg_max, 1.0)  # uniform pad to max degree
        padded = n * k
        bytes_moved = padded * (f * BYTES_F32 + 8) + out_bytes
        flops = 2.0 * padded * f
        return _roofline(bytes_moved, flops, hw) + _row_serial_penalty(
            feat, hw, knobs
        )
    elif variant in ("block_ell_pallas", "ragged_ell_pallas", "hub_ragged_pallas"):
        ragged = variant != "block_ell_pallas"
        bc = knobs.get("bc", 8)
        f_tile = knobs.get("f_tile", 128)
        eff = _block_ell_elems(feat, knobs, ragged, variant)
        bytes_moved = eff * (f * BYTES_F32 / bc + BYTES_F32) + out_bytes
        if variant == "hub_ragged_pallas":
            # two partitions: extra output scatter + per-partition launch
            bytes_moved += out_bytes * 0.4
        flops = 2.0 * eff * f
        # per-grid-step overhead (pipeline bubbles, index prefetch):
        # wider f_tile halves the step count — the "vec4" advantage.
        # Ragged variants run fewer steps by construction: eff tracks
        # sum(nslots) instead of n_row_blocks x max(nslots).
        n_steps = _block_ell_steps(eff, knobs) * max(f / f_tile, 1.0)
        penalty = _row_serial_penalty(
            feat, hw, knobs,
            weight=0.5 if variant == "hub_ragged_pallas" else 1.0,
        )
        return _roofline(bytes_moved, flops, hw) + n_steps * 2e-7 + penalty
    elif variant == "merge_path_pallas":
        # nnz-balanced slot tiling: same slot mass as ragged, plus the
        # whole-B column panel held resident (fetched once per f_tile
        # panel) and a per-tile bookkeeping step (binary-search seeds,
        # carry across the tile boundary). Crucially NO
        # _row_serial_penalty: the serialization term the other families
        # pay under skew is exactly what the nnz split removes.
        bc = knobs.get("bc", 8)
        f_tile = knobs.get("f_tile", 128)
        tile_slots = knobs.get("tile_slots", 8)
        eff = _block_ell_elems(feat, knobs, True, variant)
        bytes_moved = eff * (f * BYTES_F32 / bc + BYTES_F32) + out_bytes
        bytes_moved += feat.n_cols * f * BYTES_F32  # resident B panel
        flops = 2.0 * eff * f
        slot_steps = _block_ell_steps(eff, knobs) * max(f / f_tile, 1.0)
        tile_steps = slot_steps / max(tile_slots, 1)
        return _roofline(bytes_moved, flops, hw) + (
            slot_steps + tile_steps
        ) * 2e-7
    else:
        raise KeyError(variant)
    return _roofline(bytes_moved, flops, hw)


def estimate_sddmm(feat: InputFeatures, hw: HardwareSpec, variant: str,
                   knobs: Dict) -> float:
    n, f, nnz = feat.n_rows, feat.f, feat.nnz
    if variant == "gather_dot":
        bytes_moved = nnz * (2 * f * BYTES_F32 + 8 + BYTES_F32)
        flops = 2.0 * nnz * f
    elif variant == "row_ell":
        padded = n * max(feat.deg_max, 1.0)
        bytes_moved = padded * (f * BYTES_F32 + 8) + n * f * BYTES_F32
        flops = 2.0 * padded * f
        return _roofline(bytes_moved, flops, hw) + _row_serial_penalty(
            feat, hw, knobs
        )
    elif variant == "dense":
        bytes_moved = (n * f + feat.n_cols * f + n * feat.n_cols) * BYTES_F32
        flops = 2.0 * n * feat.n_cols * f
    elif variant in ("block_ell_pallas", "ragged_ell_pallas"):
        ragged = variant == "ragged_ell_pallas"
        bc = knobs.get("bc", 8)
        f_chunk = knobs.get("f_chunk", 128)
        eff = _block_ell_elems(feat, knobs, ragged, variant)
        # x/y tile streams + tile output, plus the per-edge gather that
        # converts tiles back to the baseline's CSR-ordered nnz vector
        bytes_moved = eff * (2.0 * f * BYTES_F32 / bc + BYTES_F32)
        bytes_moved += nnz * (BYTES_F32 + 12)
        flops = 2.0 * eff * f
        n_steps = _block_ell_steps(eff, knobs) * max(f / f_chunk, 1.0)
        # a hub row block's slots all re-gather the same X panel tile
        # through one contended stream — same serialization shape as the
        # SpMM chain, same fix (the merge variant doesn't pay it)
        penalty = _row_serial_penalty(feat, hw, knobs)
        return _roofline(bytes_moved, flops, hw) + n_steps * 2e-7 + penalty
    elif variant == "merge_path_pallas":
        bc = knobs.get("bc", 8)
        f_chunk = knobs.get("f_chunk", 128)
        tile_slots = knobs.get("tile_slots", 8)
        eff = _block_ell_elems(feat, knobs, True, variant)
        bytes_moved = eff * (2.0 * f * BYTES_F32 / bc + BYTES_F32)
        bytes_moved += nnz * (BYTES_F32 + 12)
        bytes_moved += (n + feat.n_cols) * f * BYTES_F32  # resident X/Y
        flops = 2.0 * eff * f
        slot_steps = _block_ell_steps(eff, knobs) * max(f / f_chunk, 1.0)
        tile_steps = slot_steps / max(tile_slots, 1)
        return _roofline(bytes_moved, flops, hw) + (
            slot_steps + tile_steps
        ) * 2e-7
    else:
        raise KeyError(variant)
    return _roofline(bytes_moved, flops, hw)


# layout each attention stage works in; a mismatch inside a composed
# pipeline costs an extra nnz-sized scatter/gather between stages
_ATTN_STAGE_LAYOUT = {
    "gather_dot": "csr",
    "gather_segsum": "csr",
    "row_ell": "ell",
}


def estimate_attention(feat: InputFeatures, hw: HardwareSpec, variant: str,
                       knobs: Dict) -> float:
    """Pipeline-granularity roofline for CSR attention (op="attention").

    Composed "pipe" candidates pay two inter-stage HBM round-trips that a
    per-op estimate never sees: SDDMM writes logits which softmax reads
    back, and softmax writes probs which the value-SpMM reads back
    (4 * nnz * 4B of traffic). The fused flash-style kernel keeps
    logits/probs in VMEM, so its estimate has no inter-stage term — this
    asymmetry is exactly what makes the decision input-dependent (the
    round-trips dominate at small F, tile padding waste at large skew).
    """
    nnz, f = feat.nnz, feat.f
    if variant == "pipe":
        s, m = knobs["sddmm"], knobs["spmm"]
        t = estimate_sddmm(feat, hw, s, {})
        # softmax: read logits + mask bookkeeping, write probs; few flops
        t += 2.0 * nnz * BYTES_F32 / hw.hbm_bw + 6.0 * nnz / hw.peak_flops
        t += estimate_spmm(feat, hw, m, {})
        # the two inter-stage round-trips (logits w+r, probs w+r)
        t += 4.0 * nnz * BYTES_F32 / hw.hbm_bw
        if _ATTN_STAGE_LAYOUT[s] != _ATTN_STAGE_LAYOUT[m]:
            # CSR<->ELL conversion: one nnz-sized gather/scatter + indices
            t += nnz * (BYTES_F32 + 8) / hw.hbm_bw
        return t
    if variant in ("fused_attention_pallas", "ragged_attention_pallas"):
        ragged = variant == "ragged_attention_pallas"
        bc = knobs.get("bc", 8)
        eff = _block_ell_elems(feat, knobs, ragged, variant)  # padded tile work
        # q/k/v/out streamed once; k,v tiles re-fetched per stored block;
        # structural mask read once; NO logits/probs HBM round-trips
        bytes_moved = (feat.n_rows * 2 + feat.n_cols * 2) * f * BYTES_F32
        bytes_moved += eff * BYTES_F32  # mask tiles
        bytes_moved += eff * (2.0 * f * BYTES_F32 / bc)  # k/v block gathers
        flops = 4.0 * eff * f + 8.0 * eff  # sddmm + spmm + online softmax
        n_steps = _block_ell_steps(eff, knobs)
        return _roofline(bytes_moved, flops, hw) + n_steps * 2e-7
    raise KeyError(variant)


def estimate(feat: InputFeatures, hw: HardwareSpec, variant: str,
             knobs: Dict) -> float:
    """Dispatch on the op's structural compute kind: grad ops
    (core/autodiff.py) reuse the forward models — "spmm_bwd_b" is an
    SpMM roofline over the transposed features, "spmm_bwd_vals" an SDDMM
    one. Dynamic-vals ops pay one extra nnz-sized scatter (the runtime
    cotangent values landing in the prepared layout's value table)."""
    kind = op_kind(feat.op)
    if kind == "spmm":
        t = estimate_spmm(feat, hw, variant, knobs)
        if op_dynamic_vals(feat.op):
            t += feat.nnz * (BYTES_F32 + 8) / hw.hbm_bw
        return t
    if kind == "sddmm":
        return estimate_sddmm(feat, hw, variant, knobs)
    if feat.op == "attention":
        return estimate_attention(feat, hw, variant, knobs)
    if feat.op == "csr_attention":
        # legacy per-op path (pre-pipeline-scheduler); kept for old keys
        t = estimate_sddmm(feat, hw, variant, knobs)
        t += feat.nnz * 3 * BYTES_F32 / hw.hbm_bw
        t += estimate_spmm(feat, hw, variant if variant != "gather_dot" else "gather_segsum", knobs)
        return t
    raise KeyError(feat.op)
