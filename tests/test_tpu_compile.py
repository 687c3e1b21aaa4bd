"""Compile the registry's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a chip that is
described and not attached. Each family compiles at F=256, rb=bc=8 at
the largest shape its gate in core/registry.py admits, and the next
shape the compiler refuses is one the gate rejects. The boundaries are
the SMEM holding scalar-prefetched tables and the VMEM holding the
merge-path kernels' resident panels.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import registry
from repro.core.features import HardwareSpec, InputFeatures
from repro.kernels import attention_pallas as ap
from repro.kernels import sddmm_pallas as sd
from repro.kernels import softmax_pallas as smx
from repro.kernels import spmm_pallas as sp

HW = HardwareSpec.tpu_v5e()
F = 256
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, dev):
    """The compiled program's text, or None if the compiler ran out of
    on-chip memory."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" in str(e):
            return None
        raise


# Each case: the kernel, its operand shapes at size n, the tables it
# prefetches at size n (None: a VMEM-bound case), the largest n the gate
# admits, and the next n.
def _dense_spmm(n):  # n row blocks, W=16
    return (lambda cb, v, b: sp.spmm_block_ell(cb, v, b, f_tile=128),
            [((n, 16), I32), ((n, 16, 8, 8), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("dense", n, 16, 0))


def _dense_sddmm(n):
    return (lambda cb, m, x, y: sd.sddmm_block_ell(cb, m, x, y, f_chunk=128),
            [((n, 16), I32), ((n, 16, 8, 8), jnp.float32),
             ((n * 8, F), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("dense", n, 16, 0))


def _fused_attention(n):
    return (lambda cb, m, q, k, v: ap.fused_csr_attention(cb, m, q, k, v),
            [((n, 16), I32), ((n, 16, 8, 8), jnp.float32), ((n * 8, F), jnp.float32),
             ((1024, F), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("dense", n, 16, 0))


def _ragged_spmm(n):  # n slots over 8 row blocks
    return (lambda bp, r, c, v, b: sp.spmm_ragged_ell(bp, r, c, v, b, f_tile=128),
            [((9,), I32), ((n,), I32), ((n,), I32), ((n, 8, 8), jnp.float32),
             ((1024, F), jnp.float32)],
            registry.prefetch_tables("ragged", 8, 0, n))


def _ragged_sddmm(n):
    return (lambda r, c, m, x, y: sd.sddmm_ragged_ell(r, c, m, x, y, f_chunk=128),
            [((n,), I32), ((n,), I32), ((n, 8, 8), jnp.float32),
             ((64, F), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("ragged_sddmm", 8, 0, n))


def _ragged_attention(n):
    return (lambda bp, r, c, m, q, k, v: ap.fused_ragged_attention(bp, r, c, m, q, k, v),
            [((9,), I32), ((n,), I32), ((n,), I32), ((n, 8, 8), jnp.float32),
             ((64, F), jnp.float32), ((1024, F), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("ragged", 8, 0, n))


def _merge_spmm(n):  # n tiles of 8 slots over 8 row blocks
    return (lambda bp, c, tr, tn, v, b: sp.spmm_merge_path(bp, c, tr, tn, v, b, f_tile=128),
            [((9,), I32), ((n * 8,), I32), ((n,), I32), ((n,), I32),
             ((n, 8, 8, 8), jnp.float32), ((1024, F), jnp.float32)],
            registry.prefetch_tables("merge", 8, 0, n * 8))


def _merge_sddmm(n):  # bound by the resident output grid in VMEM
    return (lambda bp, c, tr, m, x, y: sd.sddmm_merge_path(bp, c, tr, m, x, y, f_chunk=128),
            [((9,), I32), ((n * 8,), I32), ((n,), I32), ((n, 8, 8, 8), jnp.float32),
             ((64, F), jnp.float32), ((1024, F), jnp.float32)],
            None)


def _merge_spmm_panels(n):  # n rows = n cols resident, 4,096 tiles
    return (lambda bp, c, tr, tn, v, b: sp.spmm_merge_path(bp, c, tr, tn, v, b, f_tile=128),
            [((n // 8 + 1,), I32), ((4096 * 8,), I32), ((4096,), I32), ((4096,), I32),
             ((4096, 8, 8, 8), jnp.float32), ((n, F), jnp.float32)],
            None)


def _merge_sddmm_panels(n):
    return (lambda bp, c, tr, m, x, y: sd.sddmm_merge_path(bp, c, tr, m, x, y, f_chunk=128),
            [((n // 8 + 1,), I32), ((64 * 8,), I32), ((64,), I32), ((64, 8, 8, 8), jnp.float32),
             ((n, F), jnp.float32), ((n, F), jnp.float32)],
            None)


def _gate(case, n) -> bool:
    """What the registry's gate says about the case at size n."""
    _, _, tables = case(n)
    if tables is not None:
        return registry.smem_fits(tables, HW)
    if case is _merge_sddmm:
        return registry.merge_vmem_fits(64, 1024, 8, HW, resident_slots=n * 8)
    return registry.merge_vmem_fits(n, n, 8, HW)


# (case, largest admitted n, next n): the next n is the first the
# compiler refuses, so the gate is exact at these boundaries
BOUNDARIES = [
    (_dense_spmm, 2040, 2041),
    (_dense_sddmm, 2040, 2041),
    (_fused_attention, 2040, 2041),
    (_ragged_spmm, 130048, 130049),
    (_ragged_sddmm, 130048, 130049),
    (_ragged_attention, 130048, 130049),
    (_merge_spmm, 25984, 25985),
    (_merge_sddmm, 3584, 3585),
    (_merge_spmm_panels, 8160, 8168),
]


@pytest.mark.parametrize("case,largest,refused", BOUNDARIES,
                         ids=[c.__name__.strip("_") for c, _, _ in BOUNDARIES])
def test_gate_admits_largest_compilable_shape(one_chip, case, largest, refused):
    assert _gate(case, largest) and not _gate(case, refused)
    fn, shapes, _ = case(largest)
    text = _compile(fn, shapes, one_chip)
    assert text is not None, f"{case.__name__} refused at the admitted n={largest}"
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case,largest,refused", BOUNDARIES,
                         ids=[c.__name__.strip("_") for c, _, _ in BOUNDARIES])
def test_gate_rejects_first_refused_shape(one_chip, case, largest, refused):
    fn, shapes, _ = case(refused)
    assert _compile(fn, shapes, one_chip) is None
    assert not _gate(case, refused)


def test_merge_sddmm_panels_within_gate_compile(one_chip):
    """The SDDMM merge kernel keeps X and Y panels resident like the SpMM
    one; the panel gate holds for it too (the compiler admitted a few
    more rows here, so this boundary is conservative)."""
    assert registry.merge_vmem_fits(8160, 8160, 8, HW)
    fn, shapes, _ = _merge_sddmm_panels(8160)
    assert _compile(fn, shapes, one_chip) is not None
    assert not registry.merge_vmem_fits(8232, 8232, 8, HW)
    fn, shapes, _ = _merge_sddmm_panels(8232)
    assert _compile(fn, shapes, one_chip) is None


def test_row_softmax_compiles(one_chip):
    fn = lambda v, m: smx.row_softmax_block_ell(v, m)
    shapes = [((256, 16, 8, 8), jnp.float32)] * 2
    assert "tpu_custom_call" in _compile(fn, shapes, one_chip)


def _hand_features(n_rows, w, op="spmm"):
    """Features of a uniform graph whose rb=bc=8 layout has n_rows/8 row
    blocks of w slots each."""
    nnz = n_rows * w
    return InputFeatures(
        n_rows=n_rows, n_cols=n_rows, nnz=nnz, avg_deg=float(w),
        deg_p50=float(w), deg_p90=float(w), deg_p99=float(w), deg_max=float(w),
        skew=1.0, density=nnz / n_rows**2, f=F, op=op, graph_sig="hand",
        f_mod_4=True, padding_waste=0.0, ell_width_est=float(w),
    )


@pytest.mark.parametrize("op", ["spmm", "sddmm", "attention", "sddmm_bwd_x"])
def test_candidates_drop_pallas_families_beyond_the_gates(op):
    """The gates run on features, before any layout is built: a graph
    inside every boundary keeps its Pallas families, one whose tables
    overflow SMEM keeps none, and neither raises."""
    small = registry.candidates(_hand_features(1024, 8, op), HW, include_pallas=True)
    assert any(v.name.endswith("_pallas") for v in small)
    big = registry.candidates(_hand_features(2_000_000, 8, op), HW, include_pallas=True)
    assert not [v.name for v in big if v.name.endswith("_pallas")]
    assert any(v.is_baseline for v in big)


def test_smem_table_bytes_pads_like_the_compiler():
    # measured layouts: s32[9] -> 512 B, s32[25985] -> 104 KiB,
    # s32[207880] -> 816 KiB, s32[2040, 16] -> 1,020 KiB
    assert registry.smem_table_bytes((9,)) == 512
    assert registry.smem_table_bytes((25985,)) == 104 * 1024
    assert registry.smem_table_bytes((207880,)) == 816 * 1024
    assert registry.smem_table_bytes((2040, 16)) == 2040 * 128 * 4
    assert np.isclose(HW.smem_bytes, 2**20)
