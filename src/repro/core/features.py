"""Input feature extraction (paper §4.2: "#rows/nnz, degree quantiles, F,
device caps"). These drive the estimate stage and the cache key, and —
coarsened into `ScheduleBucket`s — the batch scheduler's shared decisions
(core/batch.py).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Optional

import jax
import numpy as np

from repro.sparse.csr import CSR, graph_signature


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Device capability summary: the roofline peaks the estimate ranks
    with, and the on-chip memory sizes the kernel gates
    (core/registry.py) check prefetch tables and resident panels
    against."""

    name: str
    peak_flops: float  # FLOP/s
    hbm_bw: float  # bytes/s
    link_bw: float  # bytes/s per ICI link
    vmem_bytes: int = 16 * 2**20  # scoped VMEM a Pallas kernel may fill
    hbm_bytes: int = 16 * 10**9
    smem_bytes: int = 2**20  # scalar memory holding prefetched tables
    vmem_total_bytes: int = 128 * 2**20
    source: str = ""

    @staticmethod
    def tpu_v5e() -> "HardwareSpec":
        return TPU_BY_DEVICE_KIND["TPU v5 lite"]

    @staticmethod
    def cpu() -> "HardwareSpec":
        return HardwareSpec("cpu", 5e10, 2e10, 1e9, vmem_bytes=32 * 2**20,
                            source="rough calibration of interpret-mode probes")

    @staticmethod
    def cpu_wide() -> "HardwareSpec":
        """A memory-rich CPU-class roofline (4x the HBM bandwidth at the
        same peak): bandwidth-bound candidates rank relatively cheaper
        than on `cpu`. Exists so the cross-device transfer path can be
        exercised — and CI-gated — on a single physical machine by
        pairing it with an AUTOSAGE_DEVICE_SIG_OVERRIDE."""
        return HardwareSpec("cpu_wide", 5e10, 8e10, 1e9, vmem_bytes=32 * 2**20,
                            source="cpu with 4x the bandwidth")

    @staticmethod
    def from_profile(name: str) -> "HardwareSpec":
        profiles: Dict[str, HardwareSpec] = {
            "tpu_v5e": HardwareSpec.tpu_v5e(),
            "cpu": HardwareSpec.cpu(),
            "cpu_wide": HardwareSpec.cpu_wide(),
        }
        try:
            return profiles[name]
        except KeyError:
            raise KeyError(
                f"unknown hardware profile {name!r}; known: {sorted(profiles)}"
            ) from None

    @staticmethod
    def for_device(device) -> "HardwareSpec":
        """Profile of one JAX device. A TPU is looked up by its
        ``device_kind``; a kind missing from TPU_BY_DEVICE_KIND is an
        error, never another chip's numbers."""
        if device.platform != "tpu":
            return HardwareSpec.cpu()
        kind = getattr(device, "device_kind", "?")
        try:
            return TPU_BY_DEVICE_KIND[kind]
        except KeyError:
            raise KeyError(
                f"no hardware profile for TPU device_kind {kind!r}; "
                f"known: {sorted(TPU_BY_DEVICE_KIND)}"
            ) from None

    @staticmethod
    def current() -> "HardwareSpec":
        """Roofline profile of this process. AUTOSAGE_HW_PROFILE pins a
        named profile regardless of the physical backend (used together
        with AUTOSAGE_DEVICE_SIG_OVERRIDE to simulate a heterogeneous
        fleet on one machine)."""
        override = os.environ.get("AUTOSAGE_HW_PROFILE")
        if override:
            return HardwareSpec.from_profile(override)
        return HardwareSpec.for_device(jax.devices()[0])


# TPU profiles keyed by jax's ``device_kind``. Peaks are published
# figures; the SMEM and scoped-VMEM sizes are what the XLA:TPU compiler
# reports when a kernel overflows them, and the total VMEM is the most
# a resident Pallas output block was admitted to take (scoped VMEM on
# top), both found by compiling for a described chip.
TPU_BY_DEVICE_KIND: Dict[str, HardwareSpec] = {
    "TPU v5 lite": HardwareSpec(
        "tpu_v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
        vmem_bytes=16 * 2**20, hbm_bytes=16 * 10**9, smem_bytes=2**20,
        vmem_total_bytes=128 * 2**20,
        source="peaks: Google Cloud TPU v5e docs (197 bf16 TFLOP/s, "
               "819 GB/s, 16 GB HBM, 1,600 Gbit/s ICI); SMEM 1 MiB and "
               "scoped VMEM 16 MiB: XLA:TPU compile errors",
    ),
}


def device_sig() -> str:
    """Device identity embedded in every cache key. The env override
    exists for heterogeneous-fleet simulation and the CI device matrix:
    two processes on one physical box can act as two device classes (pair
    it with AUTOSAGE_HW_PROFILE so their rooflines differ too)."""
    override = os.environ.get("AUTOSAGE_DEVICE_SIG_OVERRIDE")
    if override:
        return override
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', '?')}:jax{jax.__version__}"


# ---------------------------------------------------------------- ops
# Op taxonomy. Forward ops plus the first-class backward ("grad") ops
# introduced by core/autodiff.py: every op string is its own cache-key /
# ScheduleBucket dimension (backward shapes invert skew and carry the
# cotangent-side F, so a forward decision must never be handed down), but
# candidates, roofline estimates, and probe operands are derived from the
# *structural compute kind* — e.g. "spmm_bwd_b" IS an SpMM (on the
# transposed CSR), "spmm_bwd_vals" IS an SDDMM (on the forward pattern).
# `dynamic_vals` marks ops whose sparse values are a runtime operand
# (cotangent-dependent, traced under jax.grad) rather than baked into the
# prepared layout: their runners take (vals, b) and stay valid across
# steps, so AutoSage's runner memo applies to backward kernels too.
_OP_TAXONOMY = {
    # op                  (kind,        dynamic_vals)
    "spmm": ("spmm", False),
    "sddmm": ("sddmm", False),
    "attention": ("attention", False),
    "csr_attention": ("attention", False),  # legacy per-op attention keys
    # grad of spmm(A, B): dvals = SDDMM(grad, B) on S(A); dB = A^T @ grad
    "spmm_bwd_b": ("spmm", False),
    "spmm_bwd_b_dyn": ("spmm", True),  # runtime-valued A (vals traced)
    "spmm_bwd_vals": ("sddmm", False),
    "spmm_dyn": ("spmm", True),  # forward spmm with runtime edge values
    # grad of sddmm(A, X, Y): dX = A(g) @ Y; dY = A^T(g) @ X
    "sddmm_bwd_x": ("spmm", True),
    "sddmm_bwd_y": ("spmm", True),
    # grad of attention(A, Q, K, V): logits recompute + probs grad are
    # pattern-only SDDMMs; q/k/v grads are runtime-valued SpMMs
    "attention_bwd_e": ("sddmm", False),
    "attention_bwd_p": ("sddmm", False),
    "attention_bwd_q": ("spmm", True),
    "attention_bwd_k": ("spmm", True),
    "attention_bwd_v": ("spmm", True),
}

GRAD_OPS = tuple(op for op in _OP_TAXONOMY if "_bwd_" in op)


def op_kind(op: str) -> str:
    """Structural compute family of ``op`` ("spmm"|"sddmm"|"attention")."""
    try:
        return _OP_TAXONOMY[op][0]
    except KeyError:
        raise KeyError(f"unknown op {op!r}") from None


def op_dynamic_vals(op: str) -> bool:
    """True if the op's sparse values arrive per call (cotangent-shaped
    runtime operand) instead of being baked at prepare time."""
    try:
        return _OP_TAXONOMY[op][1]
    except KeyError:
        raise KeyError(f"unknown op {op!r}") from None


@dataclasses.dataclass(frozen=True)
class InputFeatures:
    """Everything the scheduler is allowed to look at."""

    n_rows: int
    n_cols: int
    nnz: int
    avg_deg: float
    deg_p50: float
    deg_p90: float
    deg_p99: float
    deg_max: float
    skew: float  # p99 / max(p50, 1) — heavy-tail indicator
    density: float
    f: int  # feature width F (for grad ops: the cotangent-side F)
    op: str  # any key of _OP_TAXONOMY: "spmm" | "sddmm" | "attention"
    #         | grad ops like "spmm_bwd_b" (see op_kind/op_dynamic_vals)
    graph_sig: str
    f_mod_4: bool  # paper's vec4 applicability bit (lane-align analogue)
    # duplicate (row, col) entries change attention-mask semantics (the
    # fused kernel merges them, the 3-kernel pipeline does not), so the
    # registry gates fused attention on this bit
    dup_edges: bool = False
    # block-ELL padding pressure, estimated from degrees alone (no
    # conversion): fraction of the dense-W (n_row_blocks x W) slot grid
    # that would be padding at the canonical rb=bc=8 blocking, in [0, 1).
    # This is what separates the ragged kernels (pay per slot) from the
    # dense-W kernels (pay n_row_blocks x W) in the roofline estimate,
    # and — quantized — a ScheduleBucket axis.
    padding_waste: float = 0.0
    # estimated dense-W ELL width at rb=bc=8 (0 = unknown: estimates
    # fall back to the legacy nnz-multiplier model)
    ell_width_est: float = 0.0

    @staticmethod
    def from_csr(csr: CSR, f: int, op: str) -> "InputFeatures":
        qs = csr.degree_quantiles((0.5, 0.9, 0.99, 1.0))
        nnz = csr.nnz
        waste, w_est = _block_padding_estimate(csr)
        return InputFeatures(
            n_rows=csr.n_rows,
            n_cols=csr.n_cols,
            nnz=nnz,
            avg_deg=nnz / max(csr.n_rows, 1),
            deg_p50=float(qs[0]),
            deg_p90=float(qs[1]),
            deg_p99=float(qs[2]),
            deg_max=float(qs[3]),
            skew=float(qs[2] / max(qs[0], 1.0)),
            density=nnz / max(csr.n_rows * csr.n_cols, 1),
            f=f,
            op=op,
            graph_sig=graph_signature(csr),
            f_mod_4=(f % 4 == 0),
            dup_edges=(csr.has_duplicate_edges() if op == "attention" else False),
            padding_waste=waste,
            ell_width_est=w_est,
        )

    def hub_threshold(self) -> int:
        """Default hubT: degrees beyond p99 are 'hubs' (paper sweeps this;
        AUTOSAGE_HUB_T overrides)."""
        return int(max(self.deg_p99, 4 * max(self.avg_deg, 1.0)))

    def balance(self) -> float:
        """Load-imbalance ratio deg_max / deg_mean (>= 1). This is the
        serialization exposure of row-partitioned kernels: the heaviest
        row's slot chain runs in ONE grid cell while the mean row bounds
        the work the other cells got — merge-path's nnz-split removes
        exactly this term."""
        return self.deg_max / max(self.avg_deg, 1.0)

    # ---- derived block-ELL work estimates (canonical rb=bc=8) --------
    def n_row_blocks8(self) -> int:
        return -(-self.n_rows // 8)

    def dense_tiles_est(self) -> float:
        """Estimated slot-grid size n_row_blocks x W a dense-W kernel runs."""
        return self.n_row_blocks8() * max(self.ell_width_est, 1.0)

    def ragged_tiles_est(self) -> float:
        """Estimated actual slot count a ragged kernel runs (>= one dummy
        slot per row block)."""
        return max(
            self.dense_tiles_est() * (1.0 - self.padding_waste),
            float(self.n_row_blocks8()),
        )

    def block_ell_bounds(self, rb: int, bc: int) -> tuple:
        """Upper bounds on (n_row_blocks, W, n_slots) of the (rb, bc)
        block-ELL layout, from features alone, so the kernel gates run
        before any conversion. The rb=bc=8 estimates already bound the
        8x8 layout from above (a block's slot count is at most its summed
        degree); a taller block merges rb/8 of them, so W grows by at
        most that factor and the slot count does not grow; wider column
        blocks only merge slots."""
        nrb = -(-self.n_rows // rb)
        ncb = max(1, -(-self.n_cols // bc))
        if self.ell_width_est > 0:
            w = -(-rb // 8) * self.ell_width_est
            slots = math.ceil(self.ragged_tiles_est())
        else:  # hand-built features: bound from degrees
            w = rb * max(self.deg_max, 1.0)
            slots = self.nnz + nrb
        return nrb, int(min(w, ncb)), int(min(slots, nrb * ncb))

    # ---- device-neutral serialization (cache schema v5) --------------
    def to_neutral(self) -> Dict[str, object]:
        """The device-free half of a schedule-cache entry: everything the
        scheduler looked at that describes the *input*, none of what
        describes the machine. A peer device reconstructs features from
        this dict (`features_from_neutral`) to re-rank a probed candidate
        set under its own roofline without ever seeing the graph."""
        return dataclasses.asdict(self)


def features_from_neutral(neutral: Dict[str, object]) -> InputFeatures:
    """Inverse of InputFeatures.to_neutral(); unknown fields from newer
    writers are dropped, missing ones take the dataclass defaults."""
    known = {f.name: f for f in dataclasses.fields(InputFeatures)}
    kwargs = {k: v for k, v in neutral.items() if k in known}
    missing = [
        n for n, f in known.items()
        if n not in kwargs and f.default is dataclasses.MISSING
        and f.default_factory is dataclasses.MISSING  # type: ignore[misc]
    ]
    if missing:
        raise ValueError(f"neutral features missing required fields: {missing}")
    return InputFeatures(**kwargs)


def _block_padding_estimate(csr: CSR) -> tuple:
    """(padding_waste, ell_width_est) at rb=bc=8, from degrees alone.

    Upper-bounds each 8-row block's slot count by its summed degree
    (no intra-block column sharing), capped at n_col_blocks. Exact slot
    counts need the conversion; this O(n) proxy only has to *rank*
    dense-W against ragged, and it is exact in the regime that matters
    (sparse rows hitting mostly-distinct column blocks).
    """
    n = csr.n_rows
    if n == 0 or csr.nnz == 0:
        return 0.0, 0.0
    deg = csr.degrees.astype(np.int64)
    nrb = -(-n // 8)
    ncb = max(1, -(-csr.n_cols // 8))
    block_deg = np.add.reduceat(deg, np.arange(0, n, 8))
    slots = np.minimum(np.maximum(block_deg, 1), ncb).astype(np.float64)
    w_est = float(slots.max())
    waste = 1.0 - float(slots.sum()) / (nrb * w_est)
    return waste, w_est


# ---------------------------------------------------------------------
# Schedule buckets: coarse feature canonicalization for batched decide.
#
# Minibatched GNN training serves thousands of induced subgraphs per
# epoch that differ only in which rows got sampled; ParamSpMM and
# "Heuristic Adaptability to Input Dynamics" both observe that the best
# SpMM mapping is stable across coarse feature regimes. A bucket keeps
# exactly the features that flip decisions — op, F, device, and
# log/decade-binned shape statistics — so near-identical subgraphs share
# one probed decision instead of each paying their own probe.

def _log2_bin(x: float) -> int:
    """floor(log2(x)) with x<=1 clamped to bin 0 — monotone in x."""
    return int(math.floor(math.log2(x))) if x > 1.0 else 0


def _log10_bin(x: float) -> int:
    """floor(log10(x)) for densities in (0, 1]; 0 maps below every real
    density — monotone in x."""
    if x <= 0.0:
        return -99
    return max(-12, int(math.floor(math.log10(x))))


@dataclasses.dataclass(frozen=True)
class ScheduleBucket:
    """Canonical coarse regime of one (graph, F, op) on one device.

    Hashable and order-free: equal buckets (and only equal buckets)
    share a batch-scheduler decision and a bucket-level cache entry.
    """

    op: str
    f: int
    device: str
    rows_bin: int  # floor(log2(n_rows))
    nnz_bin: int  # floor(log2(nnz))
    skew_bin: int  # floor(log2(skew)) — heavy-tail regime
    density_bin: int  # floor(log10(density))
    dup_edges: bool  # flips fused-attention applicability
    # block-ELL padding regime: 0 (< 0.5), 1 (< 0.75), 2 (>= 0.75).
    # Coarse on purpose — 0.75 is where ragged kernels pull >= 2x ahead
    # of dense-W, so this is the boundary that flips decisions; finer
    # bins would fragment hub-regime subgraph streams into extra probes.
    waste_bin: int = 0
    # load-imbalance regime (deg_max/deg_mean): 0 (< 16), 1 (< 64),
    # 2 (>= 64). 64 is where the estimate's serialization penalty makes
    # merge-path overtake the row-partitioned families, so this is the
    # other boundary that flips decisions.
    balance_bin: int = 0

    @staticmethod
    def from_features(feat: "InputFeatures", device: Optional[str] = None) -> "ScheduleBucket":
        return ScheduleBucket(
            op=feat.op,
            f=feat.f,
            device=device if device is not None else device_sig(),
            rows_bin=_log2_bin(feat.n_rows),
            nnz_bin=_log2_bin(feat.nnz),
            skew_bin=_log2_bin(feat.skew),
            density_bin=_log10_bin(feat.density),
            dup_edges=feat.dup_edges,
            waste_bin=_waste_bin(feat.padding_waste),
            balance_bin=balance_bin(feat.balance()),
        )

    def sig(self) -> str:
        """Stable string form used inside bucket-level cache keys (the
        key carries device/F/op/alpha as separate structured fields, so
        the sig encodes only the binned shape regime)."""
        dup = "dup" if self.dup_edges else "simple"
        return (
            f"r{self.rows_bin}.z{self.nnz_bin}.s{self.skew_bin}"
            f".d{self.density_bin}.w{self.waste_bin}.b{self.balance_bin}.{dup}"
        )


def waste_bin(waste: float) -> int:
    """Monotone 3-level quantization of padding_waste: 0 (< 0.5),
    1 (< 0.75), 2 (>= 0.75). Public because the drift detector
    (core/batch.py) compares live inputs' waste against the bin the
    bucket was probed under."""
    if waste >= 0.75:
        return 2
    if waste >= 0.5:
        return 1
    return 0


_waste_bin = waste_bin  # internal alias kept for older call sites


def balance_bin(balance: float) -> int:
    """Monotone 3-level quantization of deg_max/deg_mean: 0 (< 32),
    1 (< 256), 2 (>= 256). The lower boundary sits well above the
    roofline penalty's onset (balance 8) so mild hidden-hub drift within
    a bucket stays a drift-detection problem, while hub-dominated inputs
    (merge-path territory, balance >= 64) land in a separate bucket from
    uniform ones."""
    if balance >= 256.0:
        return 2
    if balance >= 32.0:
        return 1
    return 0
