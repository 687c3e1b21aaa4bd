"""Merge-path (nnz-balanced) kernel family: partition-table invariants,
bit-identity with the ragged kernels and the CSR oracles on hub-dominated
extremes (fwd + dynamic-vals bwd), the roofline's row-serialization
penalty that ranks merge-path first under skew without a probe, and the
satellite bugfixes that rode along (many-hub ranking, padding-waste
fallback telemetry, int32 layout guards, balance bucketing)."""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import registry
from repro.core import estimate as est_mod
from repro.core.estimate import (
    _block_ell_elems,
    _row_serial_penalty,
    estimate,
)
from repro.core.features import (
    HardwareSpec,
    InputFeatures,
    ScheduleBucket,
    balance_bin,
)
from repro.kernels import ref
from repro.sparse import (
    build_merge_path,
    csr_to_block_ell,
    hub_skew,
    power_law,
    single_hub,
)
from repro.sparse.bsr import BlockELL


def _rng():
    return np.random.default_rng(0)


def _canonical_picks(csr, f, op="spmm"):
    feat = InputFeatures.from_csr(csr, f, op)
    fn = {
        "spmm": registry._pallas_spmm_variants,
        "sddmm": registry._pallas_sddmm_variants,
        "spmm_dyn": registry._pallas_spmm_dyn_variants,
    }[op]
    picks = {}
    for v in fn(feat, interpret=True):
        if v.knobs.get("rb") == 8 and v.knobs.get("bc") == 8 \
                and v.knobs.get("f_tile", 128) == 128 \
                and v.knobs.get("tile_slots", 8) == 8:
            picks[v.name] = v
    return feat, picks


# ------------------------------------------------- partition table
def test_merge_partition_invariants():
    for csr in (power_law(300, 1.6, 4, seed=2),
                single_hub(256, nnz_frac=0.9, seed=0)):
        rag = csr_to_block_ell(csr, rb=8, bc=8).to_ragged()
        for tile_slots in (3, 8, 16):
            mp = build_merge_path(rag, tile_slots=tile_slots)
            n_slots = rag.slot_vals.shape[0]
            assert mp.n_slots == n_slots
            assert mp.n_tiles == -(-n_slots // tile_slots)
            # tile_vals is a pure (tail-padded) reshape of the slot stream
            flat = mp.tile_vals.reshape(-1, 8, 8)
            assert np.array_equal(flat[:n_slots], rag.slot_vals)
            assert not flat[n_slots:].any()
            assert np.array_equal(mp.slot_colblk[:n_slots], rag.slot_colblk)
            # merge start coordinates: blkptr[rowblk] + offset == start slot
            starts = np.arange(mp.n_tiles) * tile_slots
            assert np.array_equal(
                mp.blkptr[mp.tile_rowblk] + mp.tile_offset, starts
            )
            # every start row block actually owns its start slot
            assert (mp.blkptr[mp.tile_rowblk] <= starts).all()
            assert (starts < mp.blkptr[mp.tile_rowblk + 1]).all()
            # live-slot counts partition the stream; only the last tile
            # can be partial
            assert int(mp.tile_nslots.sum()) == n_slots
            assert (mp.tile_nslots[:-1] == tile_slots).all()


def test_merge_partition_rejects_bad_tile_slots():
    rag = csr_to_block_ell(power_law(64, 1.0, 4, seed=1), rb=8, bc=8).to_ragged()
    try:
        build_merge_path(rag, tile_slots=0)
        raise AssertionError("tile_slots=0 must raise")
    except ValueError:
        pass


# ------------------------------------------- all-hub bit-identity
def test_allhub_spmm_merge_bit_identical():
    """One row owns 90% of nnz — the row-partitioned worst case. Merge
    output must be bitwise equal to ragged (same slots, same order) and
    allclose vs both CSR and merge oracles."""
    csr = single_hub(256, nnz_frac=0.9, seed=0)
    hub_nnz = csr.rowptr[1] - csr.rowptr[0]
    assert hub_nnz / csr.nnz >= 0.85
    f = 64
    _, picks = _canonical_picks(csr, f, "spmm")
    b = jnp.asarray(_rng().standard_normal((csr.n_cols, f)).astype(np.float32))
    out_r = np.asarray(picks["ragged_ell_pallas"].build(
        picks["ragged_ell_pallas"].prepare(csr))(b))
    out_m = np.asarray(picks["merge_path_pallas"].build(
        picks["merge_path_pallas"].prepare(csr))(b))
    assert np.array_equal(out_r, out_m)
    exp = ref.spmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), None, b)
    np.testing.assert_allclose(out_m, np.asarray(exp), rtol=2e-3, atol=2e-3)
    # the standalone merge oracle agrees with the padded kernel output
    rag = csr_to_block_ell(csr, rb=8, bc=8).to_ragged()
    mp = build_merge_path(rag, tile_slots=8)
    bp = jnp.zeros((mp.n_col_blocks * 8, f), jnp.float32)
    bp = bp.at[: csr.n_cols].set(b)
    oracle = ref.spmm_merge_path_ref(
        jnp.asarray(mp.blkptr), jnp.asarray(mp.slot_colblk),
        jnp.asarray(mp.tile_vals), bp, mp.n_slots, 8,
    )
    np.testing.assert_allclose(
        np.asarray(oracle)[: csr.n_rows], out_m, rtol=2e-3, atol=2e-3
    )


def test_allhub_sddmm_merge_bit_identical():
    csr = single_hub(200, nnz_frac=0.9, seed=4)
    f = 32
    _, picks = _canonical_picks(csr, f, "sddmm")
    rng = _rng()
    x = jnp.asarray(rng.standard_normal((csr.n_rows, f)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    out_r = np.asarray(picks["ragged_ell_pallas"].build(
        picks["ragged_ell_pallas"].prepare(csr))(x, y))
    out_m = np.asarray(picks["merge_path_pallas"].build(
        picks["merge_path_pallas"].prepare(csr))(x, y))
    assert np.array_equal(out_r, out_m)
    exp = ref.sddmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), x, y)
    np.testing.assert_allclose(out_m, np.asarray(exp), rtol=2e-3, atol=2e-3)


def test_allhub_spmm_dyn_merge_bit_identical():
    """Dynamic-vals (bwd-op) family: runtime edge values scattered into
    the merge tiling must reproduce the ragged dyn variant bitwise."""
    csr = single_hub(192, nnz_frac=0.9, seed=2)
    f = 32
    feat, picks = _canonical_picks(csr, f, "spmm_dyn")
    assert "merge_path_pallas" in picks
    rng = _rng()
    vals = jnp.asarray(rng.standard_normal((csr.nnz,)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    out_r = np.asarray(picks["ragged_ell_pallas"].build(
        picks["ragged_ell_pallas"].prepare(csr))(vals, b))
    out_m = np.asarray(picks["merge_path_pallas"].build(
        picks["merge_path_pallas"].prepare(csr))(vals, b))
    assert np.array_equal(out_r, out_m)
    exp = ref.spmm_ref(csr.rowptr, csr.colind, np.asarray(vals), np.asarray(b))
    np.testing.assert_allclose(out_m, np.asarray(exp), rtol=2e-3, atol=2e-3)


# ------------------------------------------------- estimate ranking
def test_estimate_ranks_merge_first_under_extreme_skew():
    """At deg_max/deg_mean >= 64 the row-serialization penalty must push
    every row-partitioned Pallas family below merge-path — no probe."""
    hw = HardwareSpec.tpu_v5e()
    csr = single_hub(1024, nnz_frac=0.9, seed=0)
    for op in ("spmm", "sddmm"):
        feat = InputFeatures.from_csr(csr, 64, op)
        assert feat.balance() >= 64
        knobs = {"rb": 8, "bc": 8, "f_tile": 128}
        t_merge = estimate(feat, hw, "merge_path_pallas",
                           {**knobs, "tile_slots": 8, "ragged": True})
        t_ragged = estimate(feat, hw, "ragged_ell_pallas",
                            {**knobs, "ragged": True})
        t_dense = estimate(feat, hw, "block_ell_pallas", knobs)
        assert t_merge < t_ragged, (op, t_merge, t_ragged)
        assert t_merge < t_dense, (op, t_merge, t_dense)


def test_estimate_keeps_ragged_first_when_balanced():
    """Uniform degrees: no serialization exposure, merge-path's binary-
    search/resident-panel overhead must not displace ragged."""
    hw = HardwareSpec.tpu_v5e()
    csr = power_law(1024, 0.0, avg_deg=4, seed=0)
    feat = InputFeatures.from_csr(csr, 64, "spmm")
    assert feat.balance() < 8
    assert _row_serial_penalty(feat, hw, {"rb": 8, "bc": 8}) == 0.0
    knobs = {"rb": 8, "bc": 8, "f_tile": 128}
    t_merge = estimate(feat, hw, "merge_path_pallas",
                       {**knobs, "tile_slots": 8, "ragged": True})
    t_ragged = estimate(feat, hw, "ragged_ell_pallas", {**knobs, "ragged": True})
    assert t_ragged <= t_merge


# ------------------------------------------- satellite: many-hub graph
def test_hub_split_estimate_improves_on_many_hub_graph():
    """On a 10%-hub graph bucket_ell's estimate must beat plain row_ell:
    its degree buckets pad the hub block and the light rows to their own
    widths, where row_ell pads every row to the hub degree."""
    hw = HardwareSpec.tpu_v5e()
    csr = hub_skew(2000, 4, 0.10, 1000, seed=1)
    feat = InputFeatures.from_csr(csr, 64, "spmm")
    t_bucket = estimate(feat, hw, "bucket_ell", {})
    t_row = estimate(feat, hw, "row_ell", {})
    assert t_bucket < t_row, (t_bucket, t_row)


# ----------------------------------- satellite: padding-waste fallback
def _hand_features(**over):
    base = dict(
        n_rows=1000, n_cols=1000, nnz=8000, avg_deg=8.0, deg_p50=8.0,
        deg_p90=8.0, deg_p99=8.0, deg_max=8.0, skew=1.0, density=8e-3,
        f=64, op="spmm", graph_sig="hand", f_mod_4=True,
        padding_waste=0.0, ell_width_est=0.0,
    )
    base.update(over)
    return InputFeatures(**base)


def test_block_ell_elems_fallback_ladder_and_telemetry():
    from repro.core import obs

    # measured padding_waste beats the magic multiplier
    feat = _hand_features(padding_waste=0.5)
    assert _block_ell_elems(feat, {}, ragged=True) == feat.nnz
    assert _block_ell_elems(feat, {}, ragged=False) == feat.nnz / 0.5
    # caller-supplied knob (legacy attention-pipeline path) wins over it
    assert _block_ell_elems(feat, {"padding_waste": 2.0}, False) == 2.0 * feat.nnz
    # magic fallback fires ONLY with no width, no waste — and is counted
    blind = _hand_features()
    before = obs.REGISTRY.get(
        "autosage_estimate_magic_fallback_total", op="spmm", variant="row_ell"
    ) or 0.0
    assert _block_ell_elems(blind, {}, False, variant="row_ell") \
        == blind.nnz * 8.0
    after = obs.REGISTRY.get(
        "autosage_estimate_magic_fallback_total", op="spmm", variant="row_ell"
    )
    assert after == before + 1.0
    # informed paths must NOT bump the counter
    _block_ell_elems(feat, {}, True, variant="row_ell")
    assert obs.REGISTRY.get(
        "autosage_estimate_magic_fallback_total", op="spmm", variant="row_ell"
    ) == after


# --------------------------------------- satellite: int32 layout guard
def test_to_ragged_int32_overflow_raises():
    huge = BlockELL(
        colblk=np.zeros((3, 1), np.int32),
        vals=np.zeros((3, 1, 8, 8), np.float32),
        nslots=np.array([2**30, 2**30, 2**30], np.int32),
        rb=8, bc=8, n_rows=24, n_cols=8,
    )
    try:
        huge.to_ragged()
        raise AssertionError("int32 slot-count overflow must raise")
    except ValueError as e:
        assert "int32" in str(e)


# ------------------------------------------- satellite: balance bucket
def test_balance_bin_and_bucket_sig():
    assert balance_bin(1.0) == 0
    assert balance_bin(31.9) == 0
    assert balance_bin(32.0) == 1
    assert balance_bin(256.0) == 2
    uni = InputFeatures.from_csr(power_law(512, 0.0, 4, seed=7), 64, "spmm")
    hub = InputFeatures.from_csr(single_hub(512, nnz_frac=0.9, seed=3), 64, "spmm")
    bu = ScheduleBucket.from_features(uni, device="d")
    bh = ScheduleBucket.from_features(hub, device="d")
    assert bu.balance_bin == 0 and bh.balance_bin == 2
    assert ".b0." in bu.sig() and ".b2." in bh.sig()
    assert bu.sig() != bh.sig()
