"""Run the scheduled GraphSAGE training step on one TPU chip and check it.

    python3 chip_smoke.py

Everything runs in this one process; it starts no child. Phases:

1. device: JAX must find a TPU whose ``device_kind`` is in the hardware
   table, and no environment knob may swap the device's identity or
   replay decisions made elsewhere;
2. kernels: every Pallas variant the registry offers on the chip for
   spmm, sddmm, attention and a dynamic-values backward op is prepared,
   built, checked against the f32 reference and shown to lower to a
   Mosaic kernel (``tpu_custom_call``);
3. full-graph training: the 3-layer GraphSAGE of configs/gnn_sage.py on
   an OGBN-Products-shaped graph at Products' widths, a jitted
   value_and_grad step whose forward and backward SpMMs are decided and
   probed on the chip;
4. minibatch training: the same model on 1,024-row sampled subgraphs
   through a BatchScheduler, where the Pallas families are probed;
5. guard: any fault, fallback, quarantine, rescue decide or transferred
   decision fails the run.

Each phase prints what it did. The last line of standard output is one
JSON object naming the device; a failed phase exits non-zero and prints
no such line.
"""
from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
IDENTITY_KNOBS = (
    "AUTOSAGE_HW_PROFILE", "AUTOSAGE_DEVICE_SIG_OVERRIDE", "AUTOSAGE_REPLAY_ONLY",
)
F = 256  # hidden width of configs/gnn_sage.py
IN_DIM, N_CLASSES = 100, 47  # OGBN-Products
KERNEL_SCALE = 0.0005  # products_like: 1,224 nodes, inside every gate
FULL_SCALE = 0.05  # 122,451 nodes; see PERF.md for the 12 GiB reckoning
FULL_STEPS = 5
MB_ROWS, MB_STEPS = 1024, 4
U = 2.0 ** -24  # f32 unit roundoff

# every Pallas family the registry holds, by the op that draws it
FAMILIES = {
    "spmm": {"block_ell_pallas", "ragged_ell_pallas", "merge_path_pallas",
             "hub_ragged_pallas"},
    "sddmm": {"block_ell_pallas", "ragged_ell_pallas", "merge_path_pallas"},
    "attention": {"fused_attention_pallas", "ragged_attention_pallas"},
    "sddmm_bwd_x": {"ragged_ell_pallas", "merge_path_pallas"},
}


class SmokeFailure(Exception):
    pass


def gamma(n) -> "float":
    """Bound on the relative error of an n-term f32 sum of products
    (Higham's gamma_n = n u / (1 - n u)); arrays welcome."""
    return n * U / (1 - n * U)


def median_ms(fn, iters: int = 5) -> float:
    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


# ------------------------------------------------------------------ 1
def phase_device():
    set_knobs = [k for k in IDENTITY_KNOBS if os.environ.get(k)]
    if set_knobs:
        raise SmokeFailure(f"unset {', '.join(set_knobs)}: they override the "
                           "device's identity or serve decisions made elsewhere")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found platform {dev.platform!r} "
                           f"({dev.device_kind})")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
        from repro.core.features import HardwareSpec
    except ImportError as exc:
        raise SmokeFailure(f"the repro package is not next to this script: {exc}")
    hw = HardwareSpec.for_device(dev)  # an unknown device_kind raises
    cache_dir = compile_cache.enable()
    warm = Path(cache_dir).is_dir() and any(Path(cache_dir).iterdir())
    print(f"[device] {dev.platform} {dev.device_kind!r} x{len(jax.devices())} "
          f"-> profile {hw.name} (SMEM {hw.smem_bytes} B, scoped VMEM "
          f"{hw.vmem_bytes} B); compile cache {cache_dir} "
          f"({'warm' if warm else 'cold'})")
    return dev, hw


# ------------------------------------------------------------------ 2
def _kernel_case(op, csr, rng):
    """(args, reference output, error bound) for one op on ``csr``.

    Bounds: each output is a sum of n products, n the row degree for
    SpMM and the feature width for SDDMM. The reference forms each
    product in f32 and adds them in f32, so it lies within gamma_n *
    sum|terms| of the exact value. The kernel contracts on the MXU at
    `Precision.HIGHEST` (F32_MXU), which on a TPU "performs float32
    computations in 6 bfloat16" passes (the `jax.lax.Precision`
    docstring; Pallas lowers it to Mosaic's contract_precision<fp32>):
    six bf16 partial products per term, accumulated in f32, so up to six
    roundings per term, within gamma_6n * sum|terms|. The two differ by
    at most the sum of the two.
    Attention adds the logits' error, which softmax turns into a
    relative error of twice its size on each probability (exp, then
    normalization), and a few roundings per probability."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref

    rowptr, colind = jnp.asarray(csr.rowptr), jnp.asarray(csr.colind)
    deg = jnp.asarray(np.diff(csr.rowptr))
    r = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    if op in ("spmm", "sddmm_bwd_x"):
        # spmm bakes the graph's values into its layout; the
        # dynamic-values op takes them per call
        vals = jnp.asarray(csr.val) if op == "spmm" else r(csr.nnz)
        b = r(csr.n_cols, F)
        out = ref.spmm_ref(rowptr, colind, vals, b)
        mag = ref.spmm_ref(rowptr, colind, jnp.abs(vals), jnp.abs(b))
        bound = (gamma(6 * deg) + gamma(deg))[:, None] * mag
        args = (b,) if op == "spmm" else (vals, b)
        return args, out, bound
    if op == "sddmm":
        x, y = r(csr.n_rows, F), r(csr.n_cols, F)
        out = ref.sddmm_ref(rowptr, colind, x, y)
        bound = (gamma(6 * F) + gamma(F)) * ref.sddmm_ref(
            rowptr, colind, jnp.abs(x), jnp.abs(y))
        return (x, y), out, bound
    q, k, v = r(csr.n_rows, F), r(csr.n_cols, F), r(csr.n_cols, F)
    scale = 1.0 / math.sqrt(F)
    out = ref.csr_attention_ref(rowptr, colind, q, k, v)
    logit_mag = ref.sddmm_ref(rowptr, colind, jnp.abs(q), jnp.abs(k)) * scale
    rows = jnp.repeat(jnp.arange(csr.n_rows), deg, total_repeat_length=csr.nnz)
    d_logit = (gamma(6 * F) + gamma(F)) * jnp.zeros(csr.n_rows).at[rows].max(
        logit_mag)
    probs = ref.row_softmax_ref(
        rowptr, colind, ref.sddmm_ref(rowptr, colind, q, k) * scale
    )
    pv = ref.spmm_ref(rowptr, colind, probs, jnp.abs(v))
    rel = 2 * d_logit + gamma(6 * deg) + gamma(deg) + 2 * (gamma(deg) + 8 * U)
    return (q, k, v), out, rel[:, None] * pv


def phase_kernels(hw):
    import jax
    import numpy as np

    from repro.core import registry
    from repro.core.features import InputFeatures
    from repro.sparse import products_like
    from repro.sparse.csr import CSR

    base = products_like(scale=KERNEL_SCALE, seed=0).dedup_edges()
    rng = np.random.default_rng(0)
    csr = CSR(base.rowptr, base.colind,
              rng.standard_normal(base.nnz).astype(np.float32),
              base.n_rows, base.n_cols)
    print(f"[kernels] products_like(scale={KERNEL_SCALE}): {csr.n_rows} nodes, "
          f"{csr.nnz} edges, F={F}")
    seen = {op: set() for op in FAMILIES}
    bad = []  # every variant is checked before the phase fails
    for op in FAMILIES:
        g = csr.structural() if op != "spmm" else csr
        feat = InputFeatures.from_csr(g, F, op)
        cands = [v for v in registry.candidates(feat, hw)
                 if v.name.endswith("_pallas")]
        with jax.default_matmul_precision("highest"):
            args, want, bound = _kernel_case(op, g, rng)
            want, bound = np.asarray(want), np.asarray(bound)
        for v in cands:
            run = v.build(v.timed_prepare(g))
            got = np.asarray(jax.block_until_ready(run(*args)))
            ratio = float(np.max(np.abs(got - want) / (bound + 1e-30)))
            mosaic = "tpu_custom_call" in jax.jit(run).lower(*args).as_text()
            ms = median_ms(lambda: run(*args))
            seen[op].add(v.name)
            print(f"[kernels] {op:11s} {v.full_name():62s} err/bound "
                  f"{ratio:.3g}  {ms:.3f} ms (information only)")
            if got.shape != want.shape or not np.isfinite(got).all():
                bad.append(f"{op} {v.full_name()}: shape {got.shape} vs "
                           f"{want.shape} or non-finite output")
            elif not ratio <= 1.0:
                bad.append(f"{op} {v.full_name()}: error {ratio:.3g}x the "
                           "f32 accumulation bound")
            if not mosaic:
                bad.append(f"{op} {v.full_name()}: no tpu_custom_call in the "
                           "lowered program")
    missing = {op: sorted(FAMILIES[op] - seen[op]) for op in FAMILIES
               if FAMILIES[op] - seen[op]}
    if missing:
        bad.append(f"families the registry did not offer: {missing}")
    if bad:
        raise SmokeFailure("; ".join(bad))
    print(f"[kernels] {sum(map(len, seen.values()))} op x family pairs "
          "lowered to tpu_custom_call and within bound")


# ------------------------------------------------------------------ 3
def _decisions(sage, ops, kind):
    from repro.core.cache import parse_key

    out = []
    for op in ops:
        for key in sage.cache.keys_for_op(op, kind=kind):
            e = sage.cache.get(key)
            out.append((op, parse_key(key).f, e["choice"], sorted(e["probe_ms"])))
    return out


def phase_full(hw):
    import numpy as np

    from repro.configs.base import get_config
    from repro.core import AutoSage, ScheduleCache
    from repro.sparse import products_like
    from repro.train.gnn import make_data, train_full

    cfg = get_config("gnn_sage")
    t0 = time.perf_counter()
    graph = products_like(scale=FULL_SCALE, seed=0)
    x, y = make_data(graph, N_CLASSES, IN_DIM)
    print(f"[full] products_like(scale={FULL_SCALE}): {graph.n_rows} nodes, "
          f"{graph.nnz} edges; {cfg.n_layers}-layer GraphSAGE d={cfg.d_model}, "
          f"in={IN_DIM}, classes={N_CLASSES} (built in "
          f"{time.perf_counter() - t0:.1f} s)")
    sage = AutoSage(cache=ScheduleCache(path=None), hw=hw)
    run = train_full(cfg, graph, x, y, N_CLASSES, FULL_STEPS, sage)
    ma = run.compiled.memory_analysis()
    print(f"[full] step 1: trace+decide+probe {run.trace_s:.2f} s, compile "
          f"{run.compile_s:.2f} s; program memory: args "
          f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
          f"out {ma.output_size_in_bytes} B")
    print(f"[full] step ms {[round(t, 3) for t in run.step_ms]}")
    print(f"[full] loss {[round(l, 5) for l in run.losses]}")
    if not np.isfinite(run.losses).all() or not run.losses[-1] < run.losses[0]:
        raise SmokeFailure(f"full-graph loss not finite and falling: {run.losses}")
    decided = _decisions(sage, ("spmm", "spmm_bwd_b"), "exact")
    for op, f, choice, probed in decided:
        # an empty probe list: no challenger passed the gates, so the
        # baseline was the only candidate and nothing needed timing
        print(f"[full] {op:10s} F={f:3d} -> {choice}; probed "
              f"{probed or 'nothing (only the baseline fits)'}")
    for op in ("spmm", "spmm_bwd_b"):
        if not any(d[0] == op for d in decided):
            raise SmokeFailure(f"the step made no {op} decision")
    if not any(d[3] for d in decided):
        raise SmokeFailure("no full-graph decision was probed on the chip")
    return graph, x, y


# ------------------------------------------------------------------ 4
def phase_minibatch(hw, graph, x, y):
    import numpy as np

    from repro.configs.base import get_config
    from repro.core import AutoSage, ScheduleCache
    from repro.train.gnn import train_minibatch

    sage = AutoSage(cache=ScheduleCache(path=None), hw=hw,
                    probe_iters=2, probe_cap_ms=200, probe_frac=0.25)
    run = train_minibatch(
        get_config("gnn_sage"), graph, x, y, N_CLASSES, sage, MB_ROWS,
        epochs=1, steps_per_epoch=MB_STEPS, probe_budget_ms=120_000.0,
    )
    print(f"[minibatch] {MB_STEPS} steps of {MB_ROWS} rows: step ms "
          f"{[round(t, 1) for t in run.step_ms]}; mean loss "
          f"{run.epoch_losses[0]:.5f}")
    if not np.isfinite(run.epoch_losses).all():
        raise SmokeFailure("minibatch loss is not finite")
    probed = set()
    # one line per schedule bucket: its probe also left per-graph entries
    for op, f, choice, names in _decisions(sage, ("spmm", "spmm_bwd_b"), "bucket"):
        print(f"[minibatch] {op:10s} F={f:3d} -> {choice}; probed {names}")
        probed.update(n for n in names if "_pallas" in n)
    print(f"[minibatch] bucket stats {run.scheduler.stats()}")
    if not probed:
        raise SmokeFailure("no Pallas candidate was probed in the minibatch phase")
    print(f"[minibatch] Pallas candidates probed without a fault: {sorted(probed)}")


# ------------------------------------------------------------------ 5
def guard(registry) -> dict:
    """Counters that must be zero after a clean run, with their values."""
    counts = {
        name: registry.total(name)
        for name in ("autosage_faults_total", "autosage_fallback_total",
                     "autosage_quarantine_total")
    }
    for tier in ("fault", "transfer", "transfer-pending"):
        counts[f"decides tier={tier}"] = registry.total(
            "autosage_decides_total", tier=tier
        )
    return {k: v for k, v in counts.items() if v}


def main() -> int:
    try:
        dev, hw = phase_device()
        phase_kernels(hw)
        graph, x, y = phase_full(hw)
        phase_minibatch(hw, graph, x, y)
        from repro.core import obs

        bad = guard(obs.REGISTRY)
        if bad:
            raise SmokeFailure(f"the run hid failures: {bad}")
        print("[guard] no faults, fallbacks, quarantines, rescue decides or "
              "transfers")
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
