"""One fleet trainer, as a subprocess: decide a deterministic stream of
sampled subgraphs through a BatchScheduler against a (possibly shared)
schedule cache, then print one JSON line of stats.

Spawned by the `shared_cache`/`shared_smoke` benchmark tables and by
tests/test_shared_cache.py — the *same* worker binary measures both the
isolated and the shared configuration, so "probes avoided by sharing" is
an apples-to-apples count:

    python -m benchmarks.shared_worker --cache /tmp/c.json --shared \
        --n-graphs 32 --rows 256 --seed 1

Workers with different --seed sample different row subsets from the same
degree regimes, so they hit the SAME schedule buckets (that is the fleet
workload: peers serve the same traffic mix, not the same graphs).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent.parent / "src")


def build_stream(n_graphs: int, rows: int, seed: int, regimes: int = 4):
    """<= 4 (default) or 8 degree regimes, mid-bin so every worker's
    samples canonicalize into the same buckets (mirrors
    tables._stream_regimes; the 8-regime form is the portability
    acceptance stream)."""
    from repro.sparse import fixed_degree, hub_skew, sample_subgraph_stream

    if regimes == 8:
        parents = [
            fixed_degree(2048, d, seed=11 + i)
            for i, d in enumerate((3, 6, 12, 24, 48, 96))
        ] + [
            hub_skew(2048, 6, 0.10, 60, seed=17),
            hub_skew(2048, 6, 0.10, 200, seed=18),
        ]
    else:
        parents = [
            fixed_degree(2048, 3, seed=11),
            fixed_degree(2048, 12, seed=12),
            fixed_degree(2048, 48, seed=13),
            hub_skew(2048, 6, 0.10, 60, seed=14),
        ]
    return sample_subgraph_stream(
        parents, n_graphs, rows_per_graph=rows, seed=seed
    )


def main(argv=None) -> int:
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)
    ap = argparse.ArgumentParser()
    ap.add_argument("--cache", required=True)
    ap.add_argument("--shared", action="store_true")
    ap.add_argument("--replay", action="store_true",
                    help="serve the stream replay-only from the cache "
                         "(no probes; a miss raises ReplayMiss)")
    ap.add_argument("--n-graphs", type=int, default=32)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--f", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-ms", type=float, default=10_000.0)
    ap.add_argument("--regimes", type=int, default=4, choices=(4, 8),
                    help="degree regimes in the stream (8 = the "
                         "portability acceptance stream)")
    ap.add_argument("--device-sig", default=None,
                    help="simulate a device class: sets "
                         "AUTOSAGE_DEVICE_SIG_OVERRIDE for this worker")
    ap.add_argument("--hw-profile", default=None,
                    help="roofline profile for this worker "
                         "(AUTOSAGE_HW_PROFILE: cpu, cpu_wide, tpu_v5e)")
    ap.add_argument("--no-transfer", action="store_true",
                    help="disable the cross-device transfer tier "
                         "(AUTOSAGE_TRANSFER=0): the cold-start oracle "
                         "configuration")
    args = ap.parse_args(argv)

    import os
    if args.device_sig:
        os.environ["AUTOSAGE_DEVICE_SIG_OVERRIDE"] = args.device_sig
    if args.hw_profile:
        os.environ["AUTOSAGE_HW_PROFILE"] = args.hw_profile
    if args.no_transfer:
        os.environ["AUTOSAGE_TRANSFER"] = "0"

    import jax

    from repro.core import AutoSage, BatchScheduler, ScheduleCache

    sage = AutoSage(
        cache=ScheduleCache(path=args.cache, shared=args.shared,
                            replay_only=args.replay or None),
        probe_iters=1, probe_cap_ms=25, probe_frac=0.25,
    )
    stream = build_stream(args.n_graphs, args.rows, args.seed, args.regimes)
    bs = BatchScheduler(sage, probe_budget_ms=args.budget_ms, seed=args.seed)
    trace_choices = [bs.decide(g, args.f, "spmm").choice for g in stream]
    if not args.replay:
        bs.finalize()
    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "stats": bs.stats(),
        "bucket_choices": {
            r["bucket"]: r["choice"] for r in bs.bucket_stats()
        },
        "bucket_transfers": {
            r["bucket"]: r["transfer_verdict"] for r in bs.bucket_stats()
            if r["transferred"]
        },
        "trace_choices": trace_choices,
        "trace_keys": [ev["key"] for ev in bs.trace],
    }, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
