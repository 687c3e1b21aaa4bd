"""One benchmark per paper table/figure (DESIGN.md §5 index).

Offline container: Reddit/Products are synthetic graphs matching their
published shape statistics, scaled down by default (--full for paper-size
graphs). All numbers are medians over warm iterations, as in §6 of the
paper. CSVs + .meta.json sidecars land in results/bench/.
"""
from __future__ import annotations

import statistics
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import AutoSage, BatchScheduler, ReplayMiss, ScheduleCache
from repro.core import estimate as est_mod
from repro.core.features import InputFeatures, HardwareSpec
from repro.core.guardrail import apply_guardrail
from repro.core.probe import time_callable
from repro.core.telemetry import append_jsonl, write_csv
from repro.core import registry
from repro.kernels import ref
from repro.sparse import (
    erdos_renyi,
    fixed_degree,
    hub_skew,
    power_law,
    products_like,
    reddit_like,
    sample_subgraph_stream,
    single_hub,
)
from repro.sparse.csr import CSR
from repro.sparse.generators import table10_graph

OUT = "results/bench"


def _fresh_sage(alpha=0.95, probe_iters=3, probe_cap_ms=400) -> AutoSage:
    return AutoSage(
        alpha=alpha, cache=ScheduleCache(path=None),
        probe_iters=probe_iters, probe_cap_ms=probe_cap_ms,
    )


def _measure_full(fn: Callable, iters: int = 5) -> float:
    """Median ms of fn() on the FULL graph (after warm-up)."""
    return time_callable(fn, iters=iters, cap_ms=60_000).median_ms


def _spmm_sweep(
    csr: CSR, fs: List[int], alpha: float, label: str
) -> List[Tuple]:
    """Reproduces the per-F (choice, baseline ms, chosen ms, speedup) rows
    of Tables 2/3/4/5/7/8."""
    rows = []
    rng = np.random.default_rng(0)
    for f in fs:
        sage = _fresh_sage(alpha=alpha)
        b = rng.standard_normal((csr.n_cols, f)).astype(np.float32)
        bj = jnp.asarray(b)
        decision = sage.decide(csr, f, "spmm")
        base_v = registry.baseline(
            InputFeatures.from_csr(csr, f, "spmm"), sage.hw
        )
        base_run = base_v.build(base_v.prepare(csr))
        t_base = _measure_full(lambda: base_run(bj))
        if decision.choice == "baseline":
            t_chosen = t_base
        else:
            chosen_run = sage.build_runner(csr, decision)
            t_chosen = _measure_full(lambda: chosen_run(bj))
        choice = "baseline" if decision.choice == "baseline" else "autosage"
        rows.append(
            (f, choice, decision.choice, round(t_base, 3), round(t_chosen, 3),
             round(t_base / max(t_chosen, 1e-9), 3))
        )
        print(f"  [{label}] F={f:4d} choice={choice:9s} ({decision.choice}) "
              f"baseline={t_base:8.3f}ms chosen={t_chosen:8.3f}ms "
              f"speedup={t_base/max(t_chosen,1e-9):.3f}")
    return rows


HEADER = ["F", "choice", "variant", "baseline_ms", "chosen_ms", "speedup"]


def table_reddit(full: bool = False) -> List[Tuple]:
    """Tables 2 & 7: Reddit feature-width sweep."""
    csr = reddit_like(scale=1.0 if full else 0.1)  # scale 0.1 keeps the
    # density regime (~0.7%) out of the dense-variant zone, unlike tiny scales
    fs = [32, 64, 96, 128, 192, 256, 512] if full else [32, 64, 128, 256]
    rows = _spmm_sweep(csr, fs, 0.95, "reddit")
    write_csv(f"{OUT}/table2_7_reddit.csv", HEADER, rows)
    return rows


def table_products(full: bool = False) -> List[Tuple]:
    """Tables 3 & 8: OGBN-Products feature-width sweep."""
    csr = products_like(scale=1.0 if full else 0.01)
    fs = [32, 64, 96, 128, 192, 256, 512] if full else [32, 64, 128, 256]
    rows = _spmm_sweep(csr, fs, 0.95, "products")
    write_csv(f"{OUT}/table3_8_products.csv", HEADER, rows)
    return rows


def table_er(full: bool = False) -> List[Tuple]:
    """Table 4: Erdos-Renyi stressor (N=200k, p=2e-5)."""
    csr = erdos_renyi(200_000 if full else 50_000, 2e-5)
    rows = _spmm_sweep(csr, [64, 128, 256], 0.95, "er")
    write_csv(f"{OUT}/table4_er.csv", HEADER, rows)
    return rows


def table_hub(full: bool = False) -> List[Tuple]:
    """Table 5: hub-skew stressor (N=200k, k=4, h=0.15)."""
    csr = hub_skew(200_000 if full else 50_000, 4, 0.15, 1000 if full else 400)
    rows = _spmm_sweep(csr, [64, 128, 256], 0.95, "hub")
    write_csv(f"{OUT}/table5_hub.csv", HEADER, rows)
    return rows


def table_guardrail(full: bool = False) -> List[Tuple]:
    """Table 6 / §8.3: guardrail sensitivity (alpha 0.95 vs 0.98)."""
    csr = reddit_like(scale=1.0 if full else 0.1)
    out = []
    for alpha in (0.95, 0.98):
        rows = _spmm_sweep(csr, [64, 128], alpha, f"guardrail a={alpha}")
        out += [(alpha,) + r for r in rows]
    write_csv(f"{OUT}/table6_guardrail.csv", ["alpha"] + HEADER, out)
    return out


def table_vec_ablation(full: bool = False) -> List[Tuple]:
    """Table 9: vectorization ablation. TPU mapping: wide f_tile (256) vs
    narrow (128) on the Pallas block-ELL kernel — compared by the roofline
    estimate (TPU target) — plus the CPU-measurable analogue: uniform
    contiguous ELL reads ("vectorized") vs per-nnz gather ("scalar")."""
    rows = []
    rng = np.random.default_rng(0)
    cases = [
        ("er", erdos_renyi(50_000, 2e-5)),
        ("reddit", reddit_like(scale=0.1)),
    ]
    for name, csr in cases:
        for f in (64, 128, 256):
            feat = InputFeatures.from_csr(csr, f, "spmm")
            hw = HardwareSpec.tpu_v5e()
            from repro.core.estimate import estimate
            t_narrow = estimate(feat, hw, "block_ell_pallas", {"bc": 8, "f_tile": 128})
            t_wide = estimate(feat, hw, "block_ell_pallas", {"bc": 8, "f_tile": 256})
            # CPU analogue: ell (contiguous) vs gather (scalar)
            b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
            segsum_v = [v for v in registry.candidates(feat, HardwareSpec.cpu(), include_pallas=False) if v.name == "gather_segsum"][0]
            t_scalar = _measure_full(lambda r=segsum_v.build(segsum_v.prepare(csr)): r(b), iters=3)
            ell_vs = [v for v in registry.candidates(feat, HardwareSpec.cpu(), include_pallas=False) if v.name == "row_ell"]
            if ell_vs:
                t_vec = _measure_full(lambda r=ell_vs[0].build(ell_vs[0].prepare(csr)): r(b), iters=3)
            else:
                t_vec = float("nan")  # gated out (padding explosion) = "moot"
            speedup = t_scalar / t_vec if t_vec == t_vec else float("nan")
            rows.append((name, f, round(t_scalar, 3), round(t_vec, 3),
                         round(speedup, 3), round(t_narrow * 1e3, 4), round(t_wide * 1e3, 4)))
            print(f"  [vec4] {name} F={f}: scalar={t_scalar:.3f}ms vec={t_vec:.3f}ms "
                  f"speedup={speedup:.3f} (tpu est narrow/wide ms: {t_narrow*1e3:.3f}/{t_wide*1e3:.3f})")
    write_csv(
        f"{OUT}/table9_vec.csv",
        ["graph", "F", "scalar_ms", "vec_ms", "speedup", "tpu_est_narrow_ms", "tpu_est_wide_ms"],
        rows,
    )
    return rows


def table_split(full: bool = False) -> List[Tuple]:
    """Table 10: CTA-per-hub split vs baseline on hub-skewed graphs, F=128."""
    rows = []
    cases = [
        ("N=20k,hub=5k,other=64", table10_graph(20_000, 5_000, 64)),
        ("N=20k,hub=12k,other=32", table10_graph(20_000, 12_000, 32)),
    ]
    rng = np.random.default_rng(0)
    for name, csr in cases:
        f = 128
        b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        feat = InputFeatures.from_csr(csr, f, "spmm")
        hw = HardwareSpec.cpu()
        base = registry.baseline(feat, hw)
        t_base = _measure_full(lambda r=base.build(base.prepare(csr)): r(b), iters=3)
        splits = [v for v in registry.candidates(feat, hw, include_pallas=False) if v.name == "hub_split_ell"]
        t_split = _measure_full(lambda r=splits[0].build(splits[0].prepare(csr)): r(b), iters=3)
        rows.append((name, round(t_base, 3), round(t_split, 3), round(t_base / t_split, 3)))
        print(f"  [split] {name}: baseline={t_base:.3f}ms split={t_split:.3f}ms speedup={t_base/t_split:.3f}")
    write_csv(f"{OUT}/table10_split.csv", ["setting", "baseline_ms", "split_ms", "speedup"], rows)
    return rows


def probe_overhead(full: bool = False) -> List[Tuple]:
    """§8.6: probe overhead as a fraction of one full-graph iteration."""
    csr = reddit_like(scale=0.1)
    rng = np.random.default_rng(0)
    f = 64
    b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    rows = []
    for frac, cap in ((0.03, 1000.0), (0.02, 500.0)):
        sage = AutoSage(
            cache=ScheduleCache(path=None), probe_frac=frac,
            probe_cap_ms=cap, probe_iters=3,
        )
        d = sage.decide(csr, f, "spmm")
        base = registry.baseline(InputFeatures.from_csr(csr, f, "spmm"), sage.hw)
        t_full = _measure_full(lambda r=base.build(base.prepare(csr)): r(b), iters=3)
        pct_iter = d.probe_iter_ms / t_full * 100
        pct_total = d.probe_overhead_ms / t_full * 100
        rows.append((frac, cap, round(d.probe_iter_ms, 2),
                     round(d.probe_overhead_ms, 2), round(t_full, 2),
                     round(pct_iter, 1), round(pct_total, 1)))
        print(f"  [probe] frac={frac} cap={cap}ms steady-probe={d.probe_iter_ms:.1f}ms "
              f"({pct_iter:.1f}% of a full iter); one-time warmup incl. "
              f"XLA compiles={d.probe_overhead_ms:.1f}ms ({pct_total:.0f}%)")
    write_csv(f"{OUT}/probe_overhead.csv",
              ["frac", "cap_ms", "probe_iter_ms", "warmup_total_ms",
               "full_iter_ms", "pct_iter", "pct_total"], rows)
    return rows


def csr_attention_pipeline(full: bool = False) -> List[Tuple]:
    """§8.7 at pipeline granularity: composed {sddmm x softmax x spmm}
    candidates and the fused Pallas kernel, decided jointly by
    AutoSage.attention; reports end-to-end candidate timings, the chosen
    pipeline's full-graph runtime vs the 3-kernel baseline, and the
    per-stage breakdown of the winner."""
    csr = products_like(scale=0.05 if full else 0.01).dedup_edges()
    rng = np.random.default_rng(0)
    f = 64
    q = jnp.asarray(rng.standard_normal((csr.n_rows, f)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))

    sage = _fresh_sage()
    # time the decision as a user pays it (no diagnostic breakdown), then
    # fetch the per-stage breakdown of the cached choice separately
    t0 = time.perf_counter()
    decision = sage.decide_attention(csr, f)
    t_decide = (time.perf_counter() - t0) * 1e3
    from repro.core.pipeline import probe_stage_breakdown
    decision.stage_ms.update(
        probe_stage_breakdown(sage, csr, f, decision.variant)
    )

    feat = InputFeatures.from_csr(csr, f, "attention")
    base_v = registry.baseline(feat, sage.hw)
    base_run = base_v.build(base_v.prepare(csr))
    t_base = _measure_full(lambda: base_run(q, k, v), iters=3)
    if decision.choice == "baseline":
        t_chosen = t_base
    else:
        chosen_run = sage.build_runner(csr, decision)
        t_chosen = _measure_full(lambda: chosen_run(q, k, v), iters=3)

    rows: List[Tuple] = [
        ("full", "baseline_3kernel", round(t_base, 3), 1.0),
        ("full", decision.choice, round(t_chosen, 3),
         round(t_base / max(t_chosen, 1e-9), 3)),
        ("decide", "probe+estimate overhead", round(t_decide, 3), "-"),
    ]
    for name, ms in sorted(decision.probe_ms.items(), key=lambda kv: kv[1]):
        rows.append(("probe", name, round(ms, 3), "-"))
    for stage, ms in decision.stage_ms.items():
        rows.append(("stage", stage, round(ms, 3), "-"))
    for kind, name, ms, sp in rows:
        print(f"  [csr-attn] {kind:7s} {name:42s} {ms:10.3f}ms speedup={sp}")
    write_csv(f"{OUT}/csr_attention.csv",
              ["kind", "name", "ms", "speedup"], rows)
    return rows


def _stream_regimes(n: int, seed: int = 0) -> List[CSR]:
    """<= 8 degree regimes, chosen mid-bin so sampled subgraphs of one
    regime canonicalize into one schedule bucket (log2/log10 binning)."""
    parents = [
        fixed_degree(n, d, seed=seed + i)
        for i, d in enumerate((3, 6, 12, 24, 48, 96))
    ]
    # two heavy-tailed regimes: the hub split / ELL-gating decisions flip
    parents.append(hub_skew(n, 6, 0.10, 60, seed=seed + 6))
    parents.append(hub_skew(n, 6, 0.10, 200, seed=seed + 7))
    return parents


def _run_stream(scheduler, stream, f: int, checkpoints) -> Dict[int, float]:
    """Decide the whole stream; cumulative decide wall-clock (ms) at each
    checkpoint stream length."""
    cum: Dict[int, float] = {}
    total = 0.0
    for i, g in enumerate(stream):
        t0 = time.perf_counter()
        scheduler.decide(g, f, "spmm")
        total += (time.perf_counter() - t0) * 1e3
        if (i + 1) in checkpoints:
            cum[i + 1] = total
    return cum


def batch_stream(full: bool = False) -> List[Tuple]:
    """Probe-overhead amortization: a stream of sampled subgraphs decided
    per-graph (every unseen graph_sig probes) vs through `BatchScheduler`
    (one probe per schedule bucket under a shared budget). Cumulative
    decide overhead at stream prefixes shows the batch path flattening
    once every bucket is probed — sub-linear in stream length — while the
    per-graph path stays linear."""
    n_graphs = 256 if full else 64
    parents = _stream_regimes(8192 if full else 4096)
    stream = sample_subgraph_stream(
        parents, n_graphs, rows_per_graph=1024 if full else 384, seed=1
    )
    f = 32
    checkpoints = {n_graphs // 4, n_graphs // 2, n_graphs}

    per_graph = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=50,
        probe_frac=0.25,
    )
    cum_pg = _run_stream(per_graph, stream, f, checkpoints)

    batch = BatchScheduler(
        AutoSage(cache=ScheduleCache(path=None), probe_iters=1,
                 probe_cap_ms=50, probe_frac=0.25),
        probe_budget_ms=10_000,
    )
    cum_b = _run_stream(batch, stream, f, checkpoints)
    stats = batch.finalize()

    rows: List[Tuple] = []
    for k in sorted(checkpoints):
        pg, b = cum_pg[k], cum_b[k]
        rows.append(
            ("per_graph", k, round(pg, 1), k, 0, round(k / max(pg, 1e-9) * 1e3, 1), "-")
        )
        rows.append(
            ("batched", k, round(b, 1), stats["probes_run"] if k == n_graphs else "-",
             k - stats["probes_run"] if k == n_graphs else "-",
             round(k / max(b, 1e-9) * 1e3, 1), round(pg / max(b, 1e-9), 3))
        )
    for mode, k, cum_ms, probes, avoided, dps, sp in rows:
        print(f"  [batch-stream] {mode:10s} k={k:4d} cum_decide={cum_ms:10.1f}ms "
              f"probes={probes} avoided={avoided} decides/s={dps} speedup={sp}")
    print(f"  [batch-stream] batched: {stats['buckets']} buckets over "
          f"{stats['decides']} decides, probe budget spent "
          f"{stats['probe_spent_ms']:.0f}/{stats['probe_budget_ms']:.0f}ms")
    for rec in batch.bucket_stats():
        append_jsonl(f"{OUT}/batch_stream_buckets.jsonl", rec)
    write_csv(
        f"{OUT}/batch_stream.csv",
        ["mode", "k", "cum_decide_ms", "probes", "probes_avoided",
         "decides_per_s", "speedup_vs_per_graph"],
        rows,
    )
    return rows


def batch_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast batched-decide check for CI: >= 64 sampled subgraphs
    from <= 8 regimes must cost <= 8 probe passes (one per bucket), give
    oracle-correct results, and replay bit-identically from the recorded
    bucket decisions under replay-only mode."""
    del full
    import tempfile

    parents = _stream_regimes(2048)[:4]
    stream = sample_subgraph_stream(parents, 64, rows_per_graph=256, seed=2)
    f = 16
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/cache.json"
        sage = AutoSage(
            cache=ScheduleCache(path=path), probe_iters=1, probe_cap_ms=25,
            probe_frac=0.25,
        )
        rng = np.random.default_rng(0)
        with BatchScheduler(sage, probe_budget_ms=10_000) as bs:
            for g in stream:
                bs.decide(g, f, "spmm")
            # scheduled result == oracle on one stream element
            g0 = stream[0]
            b = jnp.asarray(
                rng.standard_normal((g0.n_cols, f)).astype(np.float32)
            )
            out, _ = bs.spmm(g0, b)
            exp = ref.spmm_ref(
                jnp.asarray(g0.rowptr), jnp.asarray(g0.colind), None, b
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3
            )
        stats = bs.stats()
        assert stats["probes_run"] <= 8, stats
        assert stats["buckets"] <= 8, stats
        finals = {r["bucket"]: r["choice"] for r in bs.bucket_stats()}

        # replay: recorded bucket decisions serve the whole stream without
        # a single probe, bit-identically with the finalized choices
        replay = BatchScheduler(
            AutoSage(cache=ScheduleCache(path=path, replay_only=True))
        )
        for g in stream:
            replay.decide(g, f, "spmm")
        assert replay.stats()["probes_run"] == 0
        for ev in replay.trace:
            assert ev["choice"] == finals[ev["bucket"]], ev
        try:
            replay.decide(erdos_renyi(3000, 1e-3, seed=9), f, "spmm")
            raise AssertionError("replay-only decide on unseen bucket must raise")
        except ReplayMiss:
            pass

    rows = [
        ("batched", stats["decides"], stats["buckets"], stats["probes_run"],
         stats["probes_avoided"]),
        ("replay", 64, replay.stats()["buckets"], 0, 64),
    ]
    for mode, decides, buckets, probes, avoided in rows:
        print(f"  [batch-smoke] {mode:8s} decides={decides} buckets={buckets} "
              f"probes={probes} avoided={avoided}")
    write_csv(f"{OUT}/batch_smoke.csv",
              ["mode", "decides", "buckets", "probes", "probes_avoided"], rows)
    return rows


def _skew_variants(feat, interpret=True):
    """One dense-W, one ragged, the hub-ragged, and the merge-path Pallas
    SpMM variant at the canonical rb=bc=8, f_tile=128 knobs (kernel-level
    comparison; merge-path pinned at tile_slots=8)."""
    picks = {}
    for v in registry._pallas_spmm_variants(feat, interpret=interpret):
        if v.knobs.get("rb") == 8 and v.knobs.get("bc") == 8 \
                and v.knobs.get("f_tile") == 128 \
                and v.knobs.get("tile_slots", 8) == 8:
            picks[v.name] = v
    return picks


def skew_stress(full: bool = False) -> List[Tuple]:
    """Ragged vs dense-W kernel-level speedup curve over power-law skew
    alpha (the paper's skew stress, Fig-style): same block-ELL data, one
    kernel grids over n_row_blocks x W, the other over actual slots.
    Outputs are checked value-identical (same tiles, same accumulation
    order), so the speedup is pure padding-work elimination. The
    `est_ragged_wins` column confirms the roofline alone would already
    rank ragged first at that skew — no probe needed. A final all-hub
    extreme leg (one row owns 90% of nnz, balance >> 64) exercises the
    merge-path family: merge output must stay bit-identical to ragged,
    and the roofline must rank merge first there (`est_merge_wins`)."""
    n = 2048 if full else 768
    f = 64
    alphas = (0.0, 0.4, 0.8, 1.2, 1.6, 2.0) if full else (0.0, 0.8, 1.6)
    rng = np.random.default_rng(0)
    rows: List[Tuple] = []
    legs = [(f"{a:.1f}", power_law(n, a, avg_deg=4, seed=int(a * 10)))
            for a in alphas]
    legs.append(("allhub", single_hub(n, nnz_frac=0.9, seed=1)))
    for label, csr in legs:
        feat = InputFeatures.from_csr(csr, f, "spmm")
        picks = _skew_variants(feat)
        b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))

        base_v = registry.baseline(feat, HardwareSpec.cpu())
        t_base = _measure_full(lambda r=base_v.build(base_v.prepare(csr)): r(b),
                               iters=3)
        runs, outs = {}, {}
        for name, v in picks.items():
            runner = v.build(v.prepare(csr))
            outs[name] = np.asarray(runner(b))
            runs[name] = _measure_full(lambda r=runner: r(b), iters=3)
        # identical tiles accumulated in identical order: value-identical
        assert np.array_equal(outs["block_ell_pallas"], outs["ragged_ell_pallas"])
        if "merge_path_pallas" in outs:
            assert np.array_equal(outs["ragged_ell_pallas"],
                                  outs["merge_path_pallas"])
        hw = HardwareSpec.tpu_v5e()
        est_d = est_mod.estimate(feat, hw, "block_ell_pallas",
                                 picks["block_ell_pallas"].knobs)
        est_r = est_mod.estimate(feat, hw, "ragged_ell_pallas",
                                 picks["ragged_ell_pallas"].knobs)
        est_m = (est_mod.estimate(feat, hw, "merge_path_pallas",
                                  picks["merge_path_pallas"].knobs)
                 if "merge_path_pallas" in picks else float("inf"))
        if label == "allhub":
            assert est_m < min(est_r, est_d), (est_m, est_r, est_d)
        sp = runs["block_ell_pallas"] / max(runs["ragged_ell_pallas"], 1e-9)
        rows.append((
            label, round(feat.padding_waste, 3), round(feat.balance(), 1),
            round(t_base, 3),
            round(runs["block_ell_pallas"], 3),
            round(runs["ragged_ell_pallas"], 3),
            round(runs.get("hub_ragged_pallas", float("nan")), 3),
            round(runs.get("merge_path_pallas", float("nan")), 3),
            round(sp, 3), "yes" if est_r < est_d else "no",
            "yes" if est_m < min(est_r, est_d) else "no",
        ))
        print(f"  [skew] leg={label} waste={feat.padding_waste:.3f} "
              f"base={t_base:8.3f}ms denseW={runs['block_ell_pallas']:8.3f}ms "
              f"ragged={runs['ragged_ell_pallas']:8.3f}ms "
              f"merge={runs.get('merge_path_pallas', float('nan')):8.3f}ms "
              f"speedup={sp:.3f} est_ragged_wins={est_r < est_d} "
              f"est_merge_wins={est_m < min(est_r, est_d)}")
    write_csv(
        f"{OUT}/skew_stress.csv",
        ["alpha", "padding_waste", "balance", "baseline_ms", "dense_w_ms",
         "ragged_ms", "hub_ragged_ms", "merge_ms", "ragged_vs_dense_speedup",
         "est_ragged_wins", "est_merge_wins"],
        rows,
    )
    return rows


def skew_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast skew check for CI: at high power-law skew
    (padding_waste >= 0.75) the decide machinery must pick a ragged
    variant over dense-W within the Pallas family — by probe+guardrail
    AND by estimate alone — with value-identical outputs; at zero skew
    the two must tie (no padding to eliminate)."""
    del full
    f = 64
    rng = np.random.default_rng(0)
    rows: List[Tuple] = []
    sage = _fresh_sage(probe_iters=2, probe_cap_ms=200)
    for label, alpha in (("uniform", 0.0), ("skewed", 1.8)):
        csr = power_law(512, alpha, avg_deg=4, seed=7)
        feat = InputFeatures.from_csr(csr, f, "spmm")
        picks = _skew_variants(feat)
        dense_v, ragged_v = picks["block_ell_pallas"], picks["ragged_ell_pallas"]
        b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        out_d = np.asarray(dense_v.build(dense_v.prepare(csr))(b))
        out_r = np.asarray(ragged_v.build(ragged_v.prepare(csr))(b))
        assert np.array_equal(out_d, out_r), "ragged must be value-identical"
        exp = ref.spmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind),
                           None, b)
        np.testing.assert_allclose(out_r, np.asarray(exp), rtol=2e-3, atol=2e-3)

        hw = HardwareSpec.tpu_v5e()
        est_d = est_mod.estimate(feat, hw, dense_v.name, dense_v.knobs)
        est_r = est_mod.estimate(feat, hw, ragged_v.name, ragged_v.knobs)
        choice = "-"
        if alpha > 0:
            assert feat.padding_waste >= 0.75, feat.padding_waste
            # the estimate alone must rank ragged first (no probing)
            assert est_r < est_d, (est_r, est_d)
            # ...and the probe+guardrail decide machinery must agree,
            # measured within the Pallas family (dense-W as the family
            # baseline; on CPU both run in interpret mode)
            outcome = sage.probe_candidates(
                csr, dense_v, [ragged_v],
                lambda sub: (jnp.asarray(rng.standard_normal(
                    (sub.n_cols, f)).astype(np.float32)),),
            )
            gr = apply_guardrail(outcome.best_name, outcome.t_best_ms,
                                 outcome.t_baseline_ms, sage.alpha)
            assert gr.accepted and gr.choice.startswith("ragged_ell_pallas"), gr
            choice = gr.choice
        rows.append((label, alpha, round(feat.padding_waste, 3),
                     "yes" if est_r < est_d else "no", choice))
        print(f"  [skew-smoke] {label:8s} alpha={alpha} "
              f"waste={feat.padding_waste:.3f} est_ragged_wins={est_r < est_d} "
              f"decide={choice}")
    write_csv(f"{OUT}/skew_smoke.csv",
              ["regime", "alpha", "padding_waste", "est_ragged_wins",
               "decide_choice"], rows)
    return rows


def merge_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast merge-path check for CI: on a hub-dominated graph
    (one row owns 90% of nnz, deg_max/deg_mean >= 64) the roofline alone
    must rank merge-path first within the Pallas family — no probing —
    the probe+guardrail decide machinery must agree, and the merge output
    must be bit-identical to ragged (and allclose vs the CSR oracle). On
    a uniform graph the row-serialization penalty is zero and ragged must
    keep its rank (merge never wins on balanced inputs)."""
    del full
    f = 64
    rng = np.random.default_rng(0)
    rows: List[Tuple] = []
    sage = _fresh_sage(probe_iters=2, probe_cap_ms=200)
    hw = HardwareSpec.tpu_v5e()
    for label, csr in (("uniform", power_law(512, 0.0, avg_deg=4, seed=7)),
                       ("allhub", single_hub(512, nnz_frac=0.9, seed=3))):
        feat = InputFeatures.from_csr(csr, f, "spmm")
        picks = _skew_variants(feat)
        ragged_v = picks["ragged_ell_pallas"]
        merge_v = picks["merge_path_pallas"]
        b = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
        out_r = np.asarray(ragged_v.build(ragged_v.prepare(csr))(b))
        out_m = np.asarray(merge_v.build(merge_v.prepare(csr))(b))
        assert np.array_equal(out_r, out_m), "merge must be bit-identical"
        exp = ref.spmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind),
                           None, b)
        np.testing.assert_allclose(out_m, np.asarray(exp), rtol=2e-3, atol=2e-3)

        ests = {name: est_mod.estimate(feat, hw, v.name, v.knobs)
                for name, v in picks.items()}
        merge_first = ests["merge_path_pallas"] < min(
            t for name, t in ests.items() if name != "merge_path_pallas"
        )
        choice = "-"
        if label == "allhub":
            assert feat.balance() >= 64, feat.balance()
            # the estimate alone must rank merge-path first (no probe)
            assert merge_first, ests
            # ...and the probe+guardrail decide machinery must agree,
            # measured within the Pallas family (ragged as the family
            # baseline; on CPU both run in interpret mode)
            outcome = sage.probe_candidates(
                csr, ragged_v, [merge_v],
                lambda sub: (jnp.asarray(rng.standard_normal(
                    (sub.n_cols, f)).astype(np.float32)),),
            )
            gr = apply_guardrail(outcome.best_name, outcome.t_best_ms,
                                 outcome.t_baseline_ms, sage.alpha)
            assert gr.accepted, gr
            choice = gr.choice
        else:
            assert feat.balance() < 8, feat.balance()
            # balanced input: no serialization penalty, ragged keeps rank
            assert ests["ragged_ell_pallas"] <= ests["merge_path_pallas"], ests
        rows.append((label, round(feat.balance(), 1),
                     round(feat.padding_waste, 3),
                     "yes" if merge_first else "no", choice))
        print(f"  [merge-smoke] {label:8s} balance={feat.balance():.1f} "
              f"waste={feat.padding_waste:.3f} est_merge_wins={merge_first} "
              f"decide={choice}")
    write_csv(f"{OUT}/merge_smoke.csv",
              ["regime", "balance", "padding_waste", "est_merge_wins",
               "decide_choice"], rows)
    return rows


# ------------------------------------------------ fleet / shared cache
def _run_shared_worker(
    cache: str, shared: bool, seed: int, n_graphs: int = 32,
    replay: bool = False, device_sig: Optional[str] = None,
    hw_profile: Optional[str] = None, regimes: int = 4,
    no_transfer: bool = False,
) -> Dict:
    """One subprocess trainer (benchmarks/shared_worker.py); returns its
    stats JSON. Every worker (including replay) runs under the same
    pinned backend, so device_sig cache keys always line up — and a
    child never probes accelerator metadata. ``device_sig``/``hw_profile``
    simulate a device class (heterogeneous-fleet portability runs)."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    cmd = [
        sys.executable, "-m", "benchmarks.shared_worker",
        "--cache", cache, "--n-graphs", str(n_graphs), "--rows", "256",
        "--seed", str(seed), "--budget-ms", "10000",
        "--regimes", str(regimes),
    ]
    if shared:
        cmd.append("--shared")
    if replay:
        cmd.append("--replay")
    if device_sig:
        cmd += ["--device-sig", device_sig]
    if hw_profile:
        cmd += ["--hw-profile", hw_profile]
    if no_transfer:
        cmd.append("--no-transfer")
    # the fleet is a CPU rehearsal of cache sharing: workers run on the
    # CPU whatever this process runs on, and say so in their stats
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # ambient scheduler knobs must not leak into the measured workers:
    # the flags above are the only configuration a worker runs under
    for knob in (
        "AUTOSAGE_REPLAY_ONLY", "AUTOSAGE_DEVICE_SIG_OVERRIDE",
        "AUTOSAGE_HW_PROFILE", "AUTOSAGE_TRANSFER",
        "AUTOSAGE_TRANSFER_MARGIN",
    ):
        env.pop(knob, None)
    out = subprocess.run(
        cmd, capture_output=True, text=True, cwd=str(repo), env=env,
        check=True, timeout=600,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _drift_stream(n_stationary: int = 8, n_shifted: int = 10) -> List[CSR]:
    """Uniform deg-18 graphs, then the same bucket with 4 hidden hub rows
    (deg 400): bins identical (rows/nnz/skew/density/waste), but the
    padded row-ELL table explodes — the pinned uniform-regime choice
    goes stale mid-stream. n=1024 keeps density < 0.02 so the dense
    fallback is gated and the uniform pick (row_ell) is deterministic."""
    return [fixed_degree(1024, 18, seed=i) for i in range(n_stationary)] + [
        hub_skew(1024, 18, 0.004, 400, seed=100 + i) for i in range(n_shifted)
    ]


def _run_drift_stream(observe: bool = True) -> "BatchScheduler":
    """Decide + run + observe the drifting stream; returns the scheduler
    so callers can read drift counters and per-bucket state."""
    import time as _time

    f = 32
    sage = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=2, probe_cap_ms=50,
        probe_frac=0.5,
    )
    bs = BatchScheduler(sage, probe_budget_ms=60_000)
    rng = np.random.default_rng(0)
    for g in _drift_stream():
        b = jnp.asarray(rng.standard_normal((g.n_cols, f)).astype(np.float32))
        d = bs.decide(g, f, "spmm")
        bucket = bs.last_bucket  # decide() just derived it: don't re-pay
        run = bs.build_runner(g, d)
        run(b)  # warm-up absorbs compilation, as in the probe protocol
        t0 = _time.perf_counter()
        jax.block_until_ready(run(b))
        if observe:
            bs.observe(bucket, (_time.perf_counter() - t0) * 1e3)
    bs.finalize()
    return bs


def shared_cache(full: bool = False) -> List[Tuple]:
    """Fleet scheduling: N subprocess trainers over one merge-on-flush
    schedule cache vs the same trainers isolated. Reports probes avoided
    by sharing (warm bucket opens) and, from a regime-shifted stream,
    decisions flipped by the drift re-probe."""
    import tempfile

    n_workers = 4 if full else 2
    n_graphs = 64 if full else 32
    rows: List[Tuple] = []
    with tempfile.TemporaryDirectory() as tmp:
        iso_probes = 0
        for w in range(n_workers):
            r = _run_shared_worker(
                f"{tmp}/iso_{w}.json", shared=False, seed=w, n_graphs=n_graphs
            )
            iso_probes += r["stats"]["probes_run"]
            rows.append(("isolated", w, r["stats"]["probes_run"],
                         r["stats"]["warm_cache_opens"], r["stats"]["decides"]))
        sh_probes = 0
        for w in range(n_workers):
            r = _run_shared_worker(
                f"{tmp}/shared.json", shared=True, seed=w, n_graphs=n_graphs
            )
            sh_probes += r["stats"]["probes_run"]
            rows.append(("shared", w, r["stats"]["probes_run"],
                         r["stats"]["warm_cache_opens"], r["stats"]["decides"]))
    bs = _run_drift_stream()
    s = bs.stats()
    rows.append(("drift", "-", s["probes_run"], s["drift_reprobes"],
                 s["drift_flips"]))
    print(f"  [shared] isolated probes={iso_probes} shared probes={sh_probes} "
          f"(avoided {iso_probes - sh_probes}); drift re-probes="
          f"{s['drift_reprobes']} flips={s['drift_flips']}")
    write_csv(
        f"{OUT}/shared_cache.csv",
        ["mode", "worker", "probes_run", "warm_opens_or_reprobes",
         "decides_or_flips"],
        rows,
    )
    return rows


def shared_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast fleet check for CI: 2 subprocess trainers over 64
    sampled subgraphs against one shared cache must pay strictly fewer
    probes than 2 isolated trainers; the merged cache must replay the
    whole traffic bit-identically under AUTOSAGE_REPLAY_ONLY=1; and a
    regime-shifted stream must trigger >= 1 drift re-probe that flips
    the bucket's pinned decision."""
    del full
    import json as _json
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        # --- sharing: 2x32 subgraphs, isolated vs shared ---------------
        iso = [
            _run_shared_worker(f"{tmp}/iso_{w}.json", shared=False, seed=w)
            for w in range(2)
        ]
        shared_path = f"{tmp}/shared.json"
        sh = [
            _run_shared_worker(shared_path, shared=True, seed=w)
            for w in range(2)
        ]
        iso_probes = sum(r["stats"]["probes_run"] for r in iso)
        sh_probes = sum(r["stats"]["probes_run"] for r in sh)
        assert sh_probes < iso_probes, (sh_probes, iso_probes)
        assert sh[1]["stats"]["warm_cache_opens"] >= 1, sh[1]["stats"]

        # --- replay: merged cache serves both workers' traffic ---------
        # (replay runs in the same subprocess config as the trainers, so
        # device_sig keys match whatever backend the workers used)
        merged = _json.load(open(shared_path))
        for seed in range(2):  # both workers' streams
            r1 = _run_shared_worker(shared_path, shared=False, seed=seed,
                                    replay=True)
            r2 = _run_shared_worker(shared_path, shared=False, seed=seed,
                                    replay=True)
            assert r1["stats"]["probes_run"] == 0, r1["stats"]
            # bit-identical across replays...
            assert r1["trace_choices"] == r2["trace_choices"]
            # ...and pinned to the merged cache entries
            for key, choice in zip(r1["trace_keys"], r1["trace_choices"]):
                assert choice == merged[key]["choice"], (key, choice)

    # --- drift: regime shift re-probes and flips the decision ----------
    bs = _run_drift_stream()
    s = bs.stats()
    assert s["buckets"] == 1, s  # the shift hides inside ONE bucket
    assert s["drift_reprobes"] >= 1, s
    assert s["drift_flips"] >= 1, s
    first, last = bs.trace[0]["choice"], bs.trace[-1]["choice"]
    assert first != last, (first, last)

    rows = [
        ("isolated", iso_probes, "-", "-"),
        ("shared", sh_probes, sh[1]["stats"]["warm_cache_opens"], "-"),
        ("drift", s["probes_run"], s["drift_reprobes"], s["drift_flips"]),
    ]
    for mode, probes, warm, flips in rows:
        print(f"  [shared-smoke] {mode:9s} probes={probes} "
              f"warm_or_reprobes={warm} flips={flips}")
    write_csv(f"{OUT}/shared_smoke.csv",
              ["mode", "probes", "warm_opens_or_reprobes", "flips"], rows)
    return rows


# ------------------------------------------- cross-device portability
# Two device classes simulated on one box: distinct device signatures
# (AUTOSAGE_DEVICE_SIG_OVERRIDE) paired with distinct roofline profiles
# (AUTOSAGE_HW_PROFILE) — the "CPU probe box feeds the trainer fleet"
# topology from the ROADMAP, runnable (and CI-gated) without a second
# machine.
_PORTABILITY_A = ("sim-probe-box", "cpu")
_PORTABILITY_B = ("sim-trainer", "cpu_wide")

PORTABILITY_FLOOR_PATH = "benchmarks/portability_floor.json"
BENCH_PORTABILITY_JSON = f"{OUT}/BENCH_portability.json"


def _portability_floor() -> Dict:
    """The checked-in regression floor for the portability metrics (the
    perf-trajectory gate: CI fails when a PR pushes transfer quality
    below it)."""
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parent / "portability_floor.json"
    return json.load(open(path))


def _portability_run(n_graphs: int) -> Dict:
    """Warm device A over the 8-regime stream, then run device B three
    ways — cold (its own local probes: the oracle), warm off A's cache
    (the transfer tier), and replay-only twice — and distill the
    portability metrics."""
    import json as _json
    import tempfile

    sig_a, hw_a = _PORTABILITY_A
    sig_b, hw_b = _PORTABILITY_B
    with tempfile.TemporaryDirectory() as tmp:
        peer = f"{tmp}/peer.json"
        a = _run_shared_worker(
            peer, shared=True, seed=0, n_graphs=n_graphs, regimes=8,
            device_sig=sig_a, hw_profile=hw_a,
        )
        # the local-probe oracle: transfer disabled outright, so the
        # cold leg stays an honest baseline even if it ever runs
        # against a warm cache
        cold = _run_shared_worker(
            f"{tmp}/cold.json", shared=True, seed=1, n_graphs=n_graphs,
            regimes=8, device_sig=sig_b, hw_profile=hw_b, no_transfer=True,
        )
        warm = _run_shared_worker(
            peer, shared=True, seed=1, n_graphs=n_graphs, regimes=8,
            device_sig=sig_b, hw_profile=hw_b,
        )
        # replay B's stream twice from the merged cache: transferred
        # decisions must replay bit-identically, probe-free
        r1 = _run_shared_worker(
            peer, shared=False, seed=1, n_graphs=n_graphs, regimes=8,
            replay=True, device_sig=sig_b, hw_profile=hw_b,
        )
        r2 = _run_shared_worker(
            peer, shared=False, seed=1, n_graphs=n_graphs, regimes=8,
            replay=True, device_sig=sig_b, hw_profile=hw_b,
        )
        merged = _json.load(open(peer))

    ws, cs = warm["stats"], cold["stats"]
    shared_buckets = set(warm["bucket_choices"]) & set(cold["bucket_choices"])
    agree = sum(
        1 for b in shared_buckets
        if warm["bucket_choices"][b] == cold["bucket_choices"][b]
    )
    transfers = ws["transfers"]
    resolved = ws["transfers_confirmed"] + ws["transfers_flipped"]
    return {
        "n_graphs": n_graphs,
        "buckets": ws["buckets"],
        "peer_probes": a["stats"]["probes_run"],
        "cold_probes": cs["probes_run"],
        "warm_probes": ws["probes_run"],
        "probes_avoided": cs["probes_run"] - ws["probes_run"],
        "transfers": transfers,
        "transfers_confirmed": ws["transfers_confirmed"],
        "transfers_flipped": ws["transfers_flipped"],
        "transfers_pending": ws["transfers_pending"],
        "transfer_probe_free": ws["transfer_probe_free"],
        # of the regimes device B had to decide with challengers (its
        # cold probes), how many were served by transfer instead
        "transfer_accept_rate": round(
            transfers / max(cs["probes_run"], 1), 4
        ),
        "confirm_rate": round(
            ws["transfers_confirmed"] / max(resolved, 1), 4
        ),
        "top1_agreement": round(agree / max(len(shared_buckets), 1), 4),
        "replay_identical": r1["trace_choices"] == r2["trace_choices"],
        "replay_probes": r1["stats"]["probes_run"],
        "_warm": warm,
        "_cold": cold,
        "_replay": r1,
        "_merged": merged,
    }


def _write_portability_bench(metrics: Dict) -> None:
    """BENCH_portability.json: the machine-readable perf-trajectory
    artifact CI uploads nightly and gates the smoke lane on."""
    import json
    from pathlib import Path

    Path(OUT).mkdir(parents=True, exist_ok=True)
    payload = {k: v for k, v in metrics.items() if not k.startswith("_")}
    payload["floor"] = _portability_floor()
    with open(BENCH_PORTABILITY_JSON, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def portability(full: bool = False) -> List[Tuple]:
    """Cross-device schedule portability: a probe-box device class warms
    the cache over the 8-regime stream; a second device class (different
    signature AND roofline) then serves the same regimes through the
    estimate-space transfer tier. Reports probes avoided vs its own cold
    start, the transfer-accept rate, confirm-vs-flip split, and top-1
    agreement of transferred choices with the local-probe oracle."""
    m = _portability_run(64 if full else 32)
    rows: List[Tuple] = [
        ("peer_device", m["peer_probes"], "-", "-"),
        ("cold_local", m["cold_probes"], "-", "-"),
        ("transfer", m["warm_probes"], m["transfers"],
         f"avoided={m['probes_avoided']}"),
        ("verdicts", m["transfers_confirmed"], m["transfers_flipped"],
         f"probe_free={m['transfer_probe_free']}"),
        ("quality", m["transfer_accept_rate"], m["confirm_rate"],
         f"top1_agreement={m['top1_agreement']}"),
        ("replay", m["replay_probes"], "-",
         f"identical={m['replay_identical']}"),
    ]
    for name, x, y, note in rows:
        print(f"  [portability] {name:12s} {x!s:>8s} {y!s:>6s} {note}")
    write_csv(
        f"{OUT}/portability.csv",
        ["metric", "value_a", "value_b", "note"], rows,
    )
    _write_portability_bench(m)
    return rows


def portability_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast portability check for CI, enforcing the acceptance
    contract AND the checked-in perf floor: with a warm peer-device
    cache, the second device class must finish the 8-regime stream with
    strictly fewer probes than its own cold start, at least half of its
    transfers must confirm without flipping, transferred decisions must
    replay bit-identically (and probe-free) under AUTOSAGE_REPLAY_ONLY=1,
    and transfer-accept rate / probes-avoided must not regress below
    benchmarks/portability_floor.json."""
    del full
    m = _portability_run(24)
    floor = _portability_floor()
    # write the artifact BEFORE the gate: a failing floor check must
    # still leave the measured metrics on disk for the CI upload
    _write_portability_bench(m)

    assert m["transfers"] >= 1, m
    assert m["warm_probes"] < m["cold_probes"], (
        "transfer must beat cold start strictly", m,
    )
    resolved = m["transfers_confirmed"] + m["transfers_flipped"]
    assert 2 * m["transfers_confirmed"] >= resolved, (
        ">= half of transfers must confirm without flipping", m,
    )
    # deterministic replay of transferred decisions, pinned to the cache
    assert m["replay_identical"], m
    assert m["replay_probes"] == 0, m
    warm, replay, merged = m["_warm"], m["_replay"], m["_merged"]
    assert replay["trace_choices"] == warm["trace_choices"], (
        "replay must serve the transferred choices verbatim"
    )
    for key, choice in zip(replay["trace_keys"], replay["trace_choices"]):
        assert choice == merged[key]["choice"], (key, choice)
    # the checked-in perf-trajectory floor (first real regression gate)
    assert m["transfer_accept_rate"] >= floor["transfer_accept_rate"], (
        m["transfer_accept_rate"], floor,
    )
    assert m["probes_avoided"] >= floor["probes_avoided"], (
        m["probes_avoided"], floor,
    )
    assert m["confirm_rate"] >= floor["confirm_rate"], (
        m["confirm_rate"], floor,
    )

    rows = [
        ("cold", m["cold_probes"], "-", "-"),
        ("transfer", m["warm_probes"], m["transfers"],
         m["transfers_confirmed"]),
        ("replay", m["replay_probes"], "-", "-"),
    ]
    for mode, probes, transfers, confirmed in rows:
        print(f"  [portability-smoke] {mode:9s} probes={probes} "
              f"transfers={transfers} confirmed={confirmed}")
    write_csv(f"{OUT}/portability_smoke.csv",
              ["mode", "probes", "transfers", "confirmed"], rows)
    return rows


def smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast bit-rot check for CI (--smoke): one scheduled SpMM and
    one pipeline-level attention decision on tiny graphs, results checked
    finite and (for attention) against the reference oracle."""
    del full
    csr = hub_skew(2000, 4, 0.05, 24, seed=0).dedup_edges()
    rng = np.random.default_rng(0)
    sage = AutoSage(
        cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=50,
        probe_frac=0.25,
    )
    b = rng.standard_normal((csr.n_cols, 32)).astype(np.float32)
    d_spmm = sage.decide(csr, 32, "spmm")
    out = api.spmm(csr, jnp.asarray(b), sage=sage, differentiable=False)
    assert np.isfinite(np.asarray(out)).all()

    f = 16
    q = jnp.asarray(rng.standard_normal((csr.n_rows, f)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((csr.n_cols, f)).astype(np.float32))
    d_attn = sage.decide_attention(csr, f)
    out_a = api.attention(csr, q, k, v, sage=sage, differentiable=False)
    exp = ref.csr_attention_ref(
        jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), q, k, v
    )
    np.testing.assert_allclose(
        np.asarray(out_a), np.asarray(exp), rtol=5e-3, atol=5e-3
    )
    rows = [
        ("spmm", len(d_spmm.probe_ms), d_spmm.choice),
        ("attention", len(d_attn.probe_ms), d_attn.choice),
    ]
    for op, n_probed, choice in rows:
        print(f"  [smoke] {op:10s} choice={choice} candidates_probed={n_probed}")
    write_csv(f"{OUT}/smoke.csv", ["op", "candidates_probed", "choice"], rows)
    return rows


def _train_setup(scale: float):
    from repro.configs.base import get_config
    from repro.models.gnn import init_gnn

    cfg = get_config("gnn_sage")
    graph = reddit_like(scale=scale)
    rng = np.random.default_rng(0)
    in_dim, classes = 64, 16
    x = jnp.asarray(rng.standard_normal((graph.n_rows, in_dim)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, classes, graph.n_rows).astype(np.int32))
    params = init_gnn(cfg, jax.random.PRNGKey(0), in_dim, classes)
    return cfg, graph, x, y, params


def _train_loss(params, graph, x, y, sage):
    from repro.models.gnn import sage_forward

    logits = sage_forward(params, graph, x, sage=sage)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], 1).mean()


def train_step(full: bool = False) -> List[Tuple]:
    """Nightly: differentiable-scheduling cost accounting for a GNN
    training step (core/autodiff.py).

    Section 1 (full graph): forward-only loss vs the fully-scheduled
    value_and_grad step, both jitted — the step's backward SpMM runs as
    its own scheduled op (op="spmm_bwd_b" on the memoized transpose), so
    the comparison shows what scheduling the backward costs/buys relative
    to pure forward inference. All decides happen at trace time; the
    timed region re-probes nothing.

    Section 2 (minibatch stream): value_and_grad through a
    BatchScheduler over sampled subgraphs — forward AND backward decides
    bucket together, so probes are paid once per (bucket, op) and every
    later step's backward is probe-free (probes_avoided in the row).
    """
    cfg, graph, x, y, params = _train_setup(0.25 if full else 0.02)
    sage = _fresh_sage(probe_iters=2, probe_cap_ms=100)

    fwd = jax.jit(lambda p: _train_loss(p, graph, x, y, sage))
    step = jax.jit(jax.value_and_grad(lambda p: _train_loss(p, graph, x, y, sage)))
    t_fwd = _measure_full(lambda: fwd(params))
    t_step = _measure_full(lambda: step(params))
    n_bwd = len(sage.cache.keys_for_op("spmm_bwd_b"))

    from repro.sparse.csr import TRANSPOSE_STATS

    sage2 = _fresh_sage(probe_iters=2, probe_cap_ms=100)
    rng = np.random.default_rng(1)
    batch = max(64, graph.n_rows // 8)
    n_steps = 12 if full else 6
    from repro.models.gnn import sage_minibatch_forward

    with BatchScheduler(sage2, probe_budget_ms=2000.0) as bs:
        for _ in range(n_steps):
            rows_idx = np.sort(
                rng.choice(graph.n_rows, size=batch, replace=False)
            )
            sub = graph.row_slice(rows_idx)
            yb = y[jnp.asarray(rows_idx)]

            def loss_fn(p):
                logits = sage_minibatch_forward(p, sub, rows_idx, x, sage=bs)
                logp = jax.nn.log_softmax(logits)
                return -jnp.take_along_axis(logp, yb[:, None], 1).mean()

            loss, _ = jax.value_and_grad(loss_fn)(params)
            jax.block_until_ready(loss)
    s = bs.stats()

    rows: List[Tuple] = [
        ("full_fwd_only", round(t_fwd, 3), "-", "-"),
        ("full_train_step", round(t_step, 3), n_bwd,
         f"bwd_ops_cached={n_bwd}"),
        ("stream_decides", s["decides"], s["probes_run"],
         f"avoided={s['probes_avoided']}"),
        ("transpose_cache", TRANSPOSE_STATS["built"],
         TRANSPOSE_STATS["hits"],
         f"built={TRANSPOSE_STATS['built']} reused={TRANSPOSE_STATS['hits']}"),
    ]
    for name, a, b, note in rows:
        print(f"  [train_step] {name:16s} {a!s:>8s} {b!s:>6s} {note}")
    write_csv(f"{OUT}/train_step.csv", ["metric", "value_a", "value_b", "note"], rows)
    return rows


def train_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast CI gate on differentiable scheduling: one scheduled
    value_and_grad step must produce finite grads that match the
    reference-pipeline grads, cache distinct backward-op entries, and
    reuse (not rebuild) the transposed layout on the second step."""
    del full
    from repro.sparse.csr import TRANSPOSE_STATS, reset_transpose_stats

    cfg, graph, x, y, params = _train_setup(0.01)
    sage = _fresh_sage(probe_iters=1, probe_cap_ms=50)
    reset_transpose_stats()

    step = jax.jit(jax.value_and_grad(lambda p: _train_loss(p, graph, x, y, sage)))
    loss, g = step(params)
    built_after_first = TRANSPOSE_STATS["built"]
    loss2, g2 = step(params)
    assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
    flat = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in flat)
    n_bwd = len(sage.cache.keys_for_op("spmm_bwd_b"))
    assert n_bwd >= 1, "backward decisions must land in the cache"
    assert TRANSPOSE_STATS["built"] == built_after_first, (
        "second step must reuse the memoized transpose", TRANSPOSE_STATS,
    )
    # scheduled grads == reference grads (the custom_vjp contract)
    _, g_ref = jax.jit(
        jax.value_and_grad(lambda p: _train_loss(p, graph, x, y, None))
    )(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3
        )
    rows = [
        ("train_smoke", n_bwd, TRANSPOSE_STATS["built"],
         f"loss={float(loss):.4f}")
    ]
    print(f"  [train_smoke] bwd_ops={n_bwd} transposes_built="
          f"{TRANSPOSE_STATS['built']} grads_match_ref=True")
    write_csv(f"{OUT}/train_smoke.csv",
              ["metric", "bwd_ops", "transposes_built", "note"], rows)
    return rows


# --------------------------------------------------------- observability
OUT_OBS = "results/obs"


@contextmanager
def _env_overlay(**updates):
    """Set (str value) / unset (None) env vars around a child-worker leg,
    always restoring — the obs tables flip AUTOSAGE_OBS between legs and
    _run_shared_worker inherits the ambient environment."""
    import os

    old = {k: os.environ.get(k) for k in updates}
    try:
        for k, v in updates.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _warm_decide_wall_ms(tmp: str, on: bool, tag: str, trials: int = 3,
                         n_graphs: int = 32) -> float:
    """Min warm decide-path wall (ms) over ``trials`` subprocess runs
    against a pre-warmed private cache: every decide is a bucket-cache
    hit, so the wall is the pure decide path the obs spans sit on."""
    cache_p = f"{tmp}/oh_{tag}.json"
    with _env_overlay(AUTOSAGE_OBS="1" if on else None,
                      AUTOSAGE_OBS_DIR=f"{tmp}/oh_obs_{tag}"):
        _run_shared_worker(cache_p, shared=False, seed=3, n_graphs=n_graphs)
        return min(
            _run_shared_worker(cache_p, shared=False, seed=3,
                               n_graphs=n_graphs)["stats"]["decide_wall_ms"]
            for _ in range(trials)
        )


def obs_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast CI gate on the flight recorder: a 2-worker fleet run
    with AUTOSAGE_OBS=1 must drop a loadable Perfetto trace covering the
    decision procedure (>= 6 distinct span names, incl. cache.lock_wait
    and transfer), a parseable Prometheus snapshot with the headline
    series, and an `obs_cli explain` narrative for a pinned bucket that
    names its tier and chosen candidate; the same traffic with obs unset
    must create ZERO obs files and keep replay bit-exact; and the warm
    decide path with obs on must stay within 5% of obs off."""
    del full
    import json as _json
    import tempfile
    from pathlib import Path as _Path

    from repro import obs_cli
    from repro.core import obs

    with tempfile.TemporaryDirectory() as tmp:
        obs_dir = f"{tmp}/obs"
        off_dir = f"{tmp}/obs_off"
        shared_path = f"{tmp}/shared.json"

        # --- obs ON: 2-worker fleet over one merge-on-flush cache ------
        with _env_overlay(AUTOSAGE_OBS="1", AUTOSAGE_OBS_DIR=obs_dir):
            for w in range(2):
                _run_shared_worker(shared_path, shared=True, seed=w)
        obs.export_trace(f"{tmp}/trace_merged.json", directory=obs_dir)
        trace = _json.load(open(f"{tmp}/trace_merged.json"))
        names = {e["name"] for e in trace["traceEvents"]}
        assert len(names) >= 6, names
        assert "cache.lock_wait" in names and "transfer" in names, names
        prom = "".join(
            p.read_text() for p in _Path(obs_dir).glob("metrics_*.prom")
        )
        for series in ("autosage_decides_total", "autosage_probe_ms_bucket",
                       "autosage_est_abs_err_ms"):
            assert series in prom, f"missing Prometheus series: {series}"

        # --- explain: a pinned bucket names its tier + candidate -------
        cache = _json.load(open(shared_path))
        key = next(k for k in sorted(cache) if k.startswith("bucket|"))
        text = obs_cli.explain(key, cache_path=shared_path)
        assert "tier:" in text and any(
            t in text for t in ("probe", "transfer", "drift")
        ), text
        assert cache[key]["choice"] in text, text

        # --- obs OFF: zero files, replay still bit-exact ---------------
        with _env_overlay(AUTOSAGE_OBS=None, AUTOSAGE_OBS_DIR=off_dir,
                          AUTOSAGE_TELEMETRY_DIR=None):
            r1 = _run_shared_worker(shared_path, shared=False, seed=0,
                                    replay=True)
            r2 = _run_shared_worker(shared_path, shared=False, seed=0,
                                    replay=True)
        assert r1["stats"]["probes_run"] == 0, r1["stats"]
        assert r1["trace_choices"] == r2["trace_choices"]
        assert not _Path(off_dir).exists(), "obs wrote files while off"

        # --- overhead: warm decide path, min-of-3, re-measure on noise -
        off_ms = _warm_decide_wall_ms(tmp, on=False, tag="off")
        on_ms = _warm_decide_wall_ms(tmp, on=True, tag="on")
        for _ in range(2):
            if on_ms <= off_ms * 1.05 + 0.25:
                break
            off_ms = min(off_ms, _warm_decide_wall_ms(tmp, False, "off"))
            on_ms = min(on_ms, _warm_decide_wall_ms(tmp, True, "on"))
        assert on_ms <= off_ms * 1.05 + 0.25, (
            f"obs decide-path overhead: on={on_ms:.3f}ms off={off_ms:.3f}ms"
        )

    overhead_pct = (on_ms / off_ms - 1.0) * 100 if off_ms else 0.0
    rows = [
        ("trace_spans", len(names), ",".join(sorted(names))),
        ("decide_wall_obs_off_ms", round(off_ms, 3), "-"),
        ("decide_wall_obs_on_ms", round(on_ms, 3),
         f"overhead={overhead_pct:.1f}%"),
    ]
    for name, val, note in rows:
        print(f"  [obs-smoke] {name:24s} {val!s:>8s} {note}")
    write_csv(f"{OUT}/obs_smoke.csv", ["metric", "value", "note"], rows)
    return rows


def obs_overhead(full: bool = False) -> List[Tuple]:
    """Nightly flight-recorder overhead + artifact drop: measures the
    warm decide path obs-off vs obs-on over more trials than the smoke
    gate, runs a fleet leg with obs on, and publishes the merged
    Perfetto trace, Prometheus snapshot, and fleet summary under
    results/obs/ (uploaded by the nightly workflow)."""
    import json as _json
    import shutil
    import tempfile
    from pathlib import Path as _Path

    from repro import obs_cli
    from repro.core import obs

    n_workers = 4 if full else 2
    n_graphs = 64 if full else 32
    trials = 5 if full else 3
    out = _Path(OUT_OBS)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        obs_dir = f"{tmp}/obs"
        shared_path = f"{tmp}/shared.json"
        with _env_overlay(AUTOSAGE_OBS="1", AUTOSAGE_OBS_DIR=obs_dir):
            for w in range(n_workers):
                _run_shared_worker(shared_path, shared=True, seed=w,
                                   n_graphs=n_graphs)
        trace = obs.export_trace(str(out / "trace_merged.json"),
                                 directory=obs_dir)
        names = {e["name"] for e in trace["traceEvents"]}
        proms = sorted(_Path(obs_dir).glob("metrics_*.prom"))
        (out / "metrics.prom").write_text(
            "".join(p.read_text() for p in proms)
        )
        for p in _Path(obs_dir).glob("metrics_*.json"):
            shutil.copy(p, out / p.name)
        (out / "summary.txt").write_text(obs_cli.summary(obs_dir) + "\n")

        off_ms = _warm_decide_wall_ms(tmp, on=False, tag="off",
                                      trials=trials, n_graphs=n_graphs)
        on_ms = _warm_decide_wall_ms(tmp, on=True, tag="on",
                                     trials=trials, n_graphs=n_graphs)

    overhead_pct = (on_ms / off_ms - 1.0) * 100 if off_ms else 0.0
    snap = _json.loads((out / proms[0].name.replace(".prom", ".json"))
                       .read_text()) if proms else {}
    n_est_pairs = sum(
        r["value"]
        for r in snap.get("counters", {}).get("autosage_est_pairs_total", [])
    )
    rows = [
        ("fleet_workers", n_workers, f"spans={len(names)}"),
        ("decide_wall_obs_off_ms", round(off_ms, 3), "-"),
        ("decide_wall_obs_on_ms", round(on_ms, 3),
         f"overhead={overhead_pct:.1f}%"),
        ("scorecard_pairs_worker0", int(n_est_pairs), "-"),
    ]
    for name, val, note in rows:
        print(f"  [obs-overhead] {name:24s} {val!s:>8s} {note}")
    write_csv(f"{OUT}/obs_overhead.csv", ["metric", "value", "note"], rows)
    return rows


def chaos_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast CI gate on fault tolerance: (1) with every runner
    faulting forever the scheduled output is BIT-IDENTICAL to the
    kernels/ref oracle and nothing quarantined gets pinned; (2) with
    prepare faulting permanently the decision still lands; (3) a
    2-worker fleet leg under injected cache-lock faults finishes with a
    loadable shared cache and no leaked lockfile, with faults.jsonl
    dropped; (4) the resilience wrappers cost <= 2% on the warm decide
    path when no fault fires (vs AUTOSAGE_RESILIENCE=0)."""
    del full
    import json as _json
    import tempfile
    from pathlib import Path as _Path

    import jax.numpy as jnp

    from repro.core import AutoSage, ScheduleCache, faultinject
    from repro.kernels import ref
    from repro.sparse import hub_skew

    csr = hub_skew(800, 4, 0.05, 24, seed=0).dedup_edges()
    b = jnp.ones((csr.n_cols, 16), jnp.float32)
    oracle = np.asarray(
        ref.spmm_ref(jnp.asarray(csr.rowptr), jnp.asarray(csr.colind), None, b)
    )
    rows: List[Tuple] = []
    with tempfile.TemporaryDirectory() as tmp:
        # --- legs 1+2: deterministic injection, oracle-equal outputs ---
        for leg, spec in (("run_fault", "run::raise:"),
                          ("prepare_fault", "prepare::oom:")):
            with _env_overlay(AUTOSAGE_FAULT=spec,
                              AUTOSAGE_TELEMETRY_DIR=f"{tmp}/tel_{leg}"):
                faultinject.reset()
                sage = AutoSage(
                    cache=ScheduleCache(path=f"{tmp}/{leg}.json"),
                    probe_iters=1, probe_cap_ms=25, probe_frac=0.25,
                )
                d = sage.decide(csr, 16, "spmm")
                out = np.asarray(sage.build_runner(csr, d)(b))
                assert (out == oracle).all(), f"{leg}: output != oracle"
                for key, entry in sage.cache._data.items():
                    if isinstance(entry, dict) and "quarantine" not in entry:
                        ch = entry.get("choice")
                        assert not (
                            isinstance(ch, str) and sage.breaker.is_quarantined(ch)
                        ), f"{leg}: quarantined {ch!r} pinned at {key}"
                fj = _Path(f"{tmp}/tel_{leg}/faults.jsonl")
                assert fj.exists(), f"{leg}: no faults.jsonl"
                n_fired = int(sum(faultinject.fired().values()))
                assert n_fired > 0, f"{leg}: injection never fired"
                faultinject.reset()
            rows.append((leg, n_fired, "output==oracle"))

        # --- leg 3: fleet under lock chaos -----------------------------
        shared = f"{tmp}/shared.json"
        with _env_overlay(AUTOSAGE_FAULT="lock::raise:3",
                          AUTOSAGE_TELEMETRY_DIR=f"{tmp}/tel_fleet"):
            for w in range(2):
                _run_shared_worker(shared, shared=True, seed=w)
        assert not list(_Path(tmp).glob("*.lock")), "leaked lockfile"
        assert isinstance(_json.load(open(shared)), dict)
        rows.append(("fleet_lock_chaos", 2, "cache loadable, no .lock"))

        # --- leg 4: decide-path overhead of the wrappers ---------------
        with _env_overlay(AUTOSAGE_FAULT=None):
            with _env_overlay(AUTOSAGE_RESILIENCE="0"):
                off_ms = _warm_decide_wall_ms(tmp, on=False, tag="res_off")
            on_ms = _warm_decide_wall_ms(tmp, on=False, tag="res_on")
            for _ in range(2):
                if on_ms <= off_ms * 1.02 + 0.25:
                    break
                with _env_overlay(AUTOSAGE_RESILIENCE="0"):
                    off_ms = min(
                        off_ms, _warm_decide_wall_ms(tmp, False, "res_off"))
                on_ms = min(on_ms, _warm_decide_wall_ms(tmp, False, "res_on"))

    overhead_pct = (on_ms / off_ms - 1.0) * 100 if off_ms else 0.0
    rows += [
        ("decide_wall_resilience_off_ms", round(off_ms, 3), "-"),
        ("decide_wall_resilience_on_ms", round(on_ms, 3),
         f"overhead={overhead_pct:.1f}%"),
    ]
    for name, val, note in rows:
        print(f"  [chaos-smoke] {name:28s} {val!s:>8s} {note}")
    # artifact first: a failed gate still leaves the numbers for triage
    write_csv(f"{OUT}/chaos_smoke.csv", ["metric", "value", "note"], rows)
    assert on_ms <= off_ms * 1.02 + 0.25, (
        f"resilience decide-path overhead: on={on_ms:.3f}ms "
        f"off={off_ms:.3f}ms"
    )
    return rows


# --------------------------------------------------------------- serving
BENCH_SERVE_JSON = f"{OUT}/BENCH_serve.json"


def _write_serve_bench(stats: Dict) -> None:
    """BENCH_serve.json: machine-readable serving-SLO artifact CI uploads
    (nightly `serve_stream --full`, smoke lane `serve_smoke`)."""
    import json
    from pathlib import Path

    Path(OUT).mkdir(parents=True, exist_ok=True)
    payload = {k: v for k, v in stats.items() if not k.startswith("_")}
    with open(BENCH_SERVE_JSON, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def _serve_clients(server, stream, f, n_clients):
    """Stride-partition ``stream`` across ``n_clients`` threads submitting
    into one server; returns every ServeResult in completion order."""
    import threading

    results, lock = [], threading.Lock()

    def client(cid: int) -> None:
        for g in stream[cid::n_clients]:
            r = server.submit(g, f, "spmm")
            with lock:
                results.append(r)

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def serve_stream(full: bool = False) -> List[Tuple]:
    """Online-serving SLO table: concurrent client streams through
    `GNNServer` (launch/serve.py). Pass 1 is all cold admissions — every
    request still answers within the decision budget because probes run
    on the background worker — and pass 2 shows the same buckets served
    warm after their in-place upgrades. Reports per-tier counts and the
    p50/p99/max decision latency against AUTOSAGE_SERVE_BUDGET_MS."""
    from repro.core import obs
    from repro.launch.serve import run_serve_gnn

    stats = run_serve_gnn(
        clients=4,
        requests=128 if full else 48,
        passes=2,
        regimes=8 if full else 4,
        parent_rows=4096 if full else 2048,
        rows_per_graph=512 if full else 256,
        think_ms=0.5,
        quiet=True,
    )
    rows: List[Tuple] = [
        ("requests", stats["requests"], f"clients=4 buckets={stats['buckets']}"),
        ("tier_warm", stats["by_tier"].get("warm", 0), "-"),
        ("tier_transfer", stats["by_tier"].get("transfer", 0), "-"),
        ("tier_provisional", stats["by_tier"].get("provisional", 0),
         "probe exiled to background worker"),
        ("tier_cold", stats["by_tier"].get("cold", 0),
         "inline probe on request path (must be 0)"),
        ("probe_stalls", stats["stalls"], "-"),
        ("background_upgrades", stats["upgrades"], "-"),
        ("p50_ms", round(stats["p50_ms"], 3), "-"),
        ("p99_ms", round(stats["p99_ms"], 3),
         f"budget={stats['budget_ms']:.0f}ms"),
        ("max_ms", round(stats["max_ms"], 3),
         f"over_budget={stats['over_budget']}"),
    ]
    for name, val, note in rows:
        print(f"  [serve-stream] {name:20s} {val!s:>10s} {note}")
    for rec in obs.serve_latency_table():
        print(f"  [serve-stream] bucket {rec['bucket'][:44]:44s} "
              f"n={rec['requests']:<4d} p50={rec['p50_ms']:.3f}ms "
              f"p99={rec['p99_ms']:.3f}ms")
    write_csv(f"{OUT}/serve_stream.csv", ["metric", "value", "note"], rows)
    _write_serve_bench(stats)
    return rows


def serve_smoke(full: bool = False) -> List[Tuple]:
    """Seconds-fast serving-SLO gate for CI, enforcing the acceptance
    contract: zero probe-stalls on the hot path (no warm/transfer/
    provisional request ever pays an inline probe), p99 decision latency
    under AUTOSAGE_SERVE_BUDGET_MS, >= 1 cold bucket upgraded in place
    mid-stream by the background prober (provisional in pass 1, warm in
    pass 2), and bit-identical replay of the served decision stream
    under replay-only mode."""
    del full
    import tempfile

    from repro.core import obs
    from repro.launch.serve import GNNServer

    parents = _stream_regimes(2048)[:4]
    stream = sample_subgraph_stream(parents, 48, rows_per_graph=256, seed=3)
    f = 16
    with tempfile.TemporaryDirectory() as tmp, \
            _env_overlay(AUTOSAGE_SERVE_BUDGET_MS="250"):
        path = f"{tmp}/cache.json"
        sage = AutoSage(
            cache=ScheduleCache(path=path), probe_iters=1, probe_cap_ms=25,
            probe_frac=0.25,
        )
        bs = BatchScheduler(sage, probe_budget_ms=10_000)
        stalls0 = obs.REGISTRY.total(obs.PROBE_STALLS)
        server = GNNServer(bs)
        pass1 = _serve_clients(server, stream, f, n_clients=3)
        assert server.drain(timeout_s=60.0), "background prober never drained"
        pass2 = _serve_clients(server, stream, f, n_clients=3)
        stats = server.close()
        finals = {r["bucket"]: r["choice"] for r in bs.bucket_stats()}

        # replay: the pinned decision stream serves identically, probe-free
        replay_bs = BatchScheduler(
            AutoSage(cache=ScheduleCache(path=path, replay_only=True))
        )
        rserver = GNNServer(replay_bs)
        rres = [rserver.submit(g, f, "spmm") for g in stream]
        rserver.close(finalize=False)

    rows: List[Tuple] = [
        ("requests", stats["requests"], f"buckets={stats['buckets']}"),
        ("pass1_provisional",
         sum(r.tier == "provisional" for r in pass1), "cold admissions"),
        ("pass2_warm", sum(r.tier == "warm" for r in pass2),
         "after background upgrades"),
        ("probe_stalls", stats["stalls"], "gate: == 0"),
        ("upgrades", stats["upgrades"], "gate: >= 1"),
        ("p99_ms", round(stats["p99_ms"], 3),
         f"gate: < {stats['budget_ms']:.0f}ms"),
        ("replay_probes", replay_bs.stats()["probes_run"], "gate: == 0"),
        ("replay_identical",
         all(r.decision.choice == finals[r.bucket] for r in rres),
         "gate: True"),
    ]
    for name, val, note in rows:
        print(f"  [serve-smoke] {name:18s} {val!s:>8s} {note}")
    # artifact first: a failed gate still leaves the numbers for triage
    write_csv(f"{OUT}/serve_smoke.csv", ["metric", "value", "note"], rows)
    _write_serve_bench(stats)

    # the acceptance contract
    assert stats["stalls"] == 0, stats
    assert obs.REGISTRY.total(obs.PROBE_STALLS) == stalls0, "stall metric moved"
    assert stats["by_tier"].get("cold", 0) == 0, stats
    assert stats["p99_ms"] < stats["budget_ms"], stats
    assert stats["upgrades"] >= 1, stats
    # >= 1 bucket served provisional mid-stream then warm post-upgrade
    prov = {r.bucket for r in pass1 if r.tier == "provisional"}
    warm2 = {r.bucket for r in pass2 if r.tier == "warm"}
    assert prov & warm2, (prov, warm2)
    assert replay_bs.stats()["probes_run"] == 0
    assert all(r.tier == "warm" for r in rres), rres
    assert all(r.decision.choice == finals[r.bucket] for r in rres)
    return rows


ALL_TABLES = {
    "table2_7_reddit": table_reddit,
    "table3_8_products": table_products,
    "table4_er": table_er,
    "table5_hub": table_hub,
    "table6_guardrail": table_guardrail,
    "table9_vec": table_vec_ablation,
    "table10_split": table_split,
    "probe_overhead": probe_overhead,
    "csr_attention": csr_attention_pipeline,
    "batch_stream": batch_stream,
    "skew_stress": skew_stress,
    "shared_cache": shared_cache,
    "portability": portability,
    "train_step": train_step,
    "obs_overhead": obs_overhead,
    "serve_stream": serve_stream,
}

# run only via --smoke (CI) or --only <name>; not part of the default sweep
SMOKE_TABLES = {
    "smoke": smoke,
    "batch_smoke": batch_smoke,
    "skew_smoke": skew_smoke,
    "merge_smoke": merge_smoke,
    "shared_smoke": shared_smoke,
    "portability_smoke": portability_smoke,
    "train_smoke": train_smoke,
    "obs_smoke": obs_smoke,
    "chaos_smoke": chaos_smoke,
    "serve_smoke": serve_smoke,
}
