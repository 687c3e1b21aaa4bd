"""End-to-end GNN training on a synthetic OGBN-Products-shaped graph
(100 input features, 47 classes, as published) — the paper's own
workload, with AutoSAGE-scheduled aggregation.

Full-graph training (default):

    PYTHONPATH=src python examples/train_gnn.py [--epochs 30]

Minibatch training through the batch scheduler — every step samples an
induced subgraph, and `BatchScheduler` shares bucketed schedule
decisions and one probe budget across the whole stream instead of
probing per subgraph:

    PYTHONPATH=src python examples/train_gnn.py --minibatch 1024 \
        --epochs 5 --probe-budget-ms 2000

Fleet mode — N subprocess trainers share ONE schedule cache
(merge-on-flush under a lockfile; each trainer opens buckets warm from
its peers' probes and re-probes buckets whose observed runtime drifts).
The fleet is a CPU rehearsal of cache sharing: the workers run on the
CPU whatever this host has:

    PYTHONPATH=src python examples/train_gnn.py --minibatch 1024 \
        --epochs 2 --workers 4 --cache fleet_cache.json
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import jax

from repro import compile_cache
from repro.configs.base import get_config
from repro.core import AutoSage, ScheduleCache
from repro.sparse import products_like
from repro.sparse.csr import TRANSPOSE_STATS
from repro.train.gnn import make_data, train_full, train_minibatch

N_CLASSES, IN_DIM = 47, 100  # OGBN-Products


def run_full(args, cfg, graph, x, y):
    sage = AutoSage(cache=ScheduleCache(path=None))
    t0 = time.time()

    def log(step, loss, ms):
        if step % 5 == 0 or step == args.epochs - 1:
            print(f"epoch {step:3d} loss {loss:.4f} ({time.time()-t0:.1f}s)")

    run = train_full(cfg, graph, x, y, N_CLASSES, args.epochs, sage, on_step=log)
    print(f"step trace {run.trace_s:.1f}s, compile {run.compile_s:.1f}s")
    # show what the scheduler picked, fwd and bwd
    for op in ("spmm", "spmm_bwd_b"):
        for key in sage.cache.keys_for_op(op):
            print(f"scheduler choice {key}: {sage.cache.get(key)['choice']}")
    print(
        f"csr transposes built={TRANSPOSE_STATS['built']} "
        f"reused={TRANSPOSE_STATS['hits']}"
    )


def run_minibatch(args, cfg, graph, x, y):
    print(f"platform: {jax.devices()[0].platform}")
    sage = AutoSage(
        cache=ScheduleCache(path=args.cache or None, shared=args.shared or None),
        probe_iters=2, probe_cap_ms=200, probe_frac=0.25,
    )
    t0 = time.time()

    def log(epoch, loss, bs):
        print(
            f"epoch {epoch:3d} loss {loss:.4f} "
            f"({time.time()-t0:.1f}s)  stream={bs.stats()}"
        )

    bs = train_minibatch(
        cfg, graph, x, y, N_CLASSES, sage, args.minibatch, args.epochs,
        probe_budget_ms=args.probe_budget_ms, seed=args.worker_id,
        on_epoch=log,
    ).scheduler
    s = bs.stats()
    print(
        f"batched decide: {s['decides']} decides -> {s['buckets']} buckets, "
        f"{s['probes_run']} probes ({s['probes_avoided']} avoided, "
        f"{s['warm_cache_opens']} opened warm from the shared cache), "
        f"drift: {s['drift_flags']} flags / {s['drift_reprobes']} re-probes / "
        f"{s['drift_flips']} flips, probe budget spent "
        f"{s['probe_spent_ms']:.0f}/{s['probe_budget_ms']:.0f}ms"
    )
    for row in bs.bucket_stats():
        print(f"  bucket {row['bucket']}: hits={row['hits']} choice={row['choice']}")
    print(
        f"transposed layouts: built={TRANSPOSE_STATS['built']} "
        f"reused={TRANSPOSE_STATS['hits']} "
        "(backward SpMMs share the per-structure transpose cache)"
    )
    if args.stats_json:
        with open(args.stats_json, "w") as fh:
            json.dump(s, fh)


def train_fleet(args):
    """Spawn --workers subprocess trainers against ONE shared schedule
    cache: each worker re-runs this script in --minibatch mode with
    AUTOSAGE_CACHE_SHARED=1, so bucket probes paid by one worker are
    opened warm by the rest (merge-on-flush, core/cache.py). This process
    never touches JAX, and the workers run on the CPU, so none of them
    needs a chip another process holds."""
    print("fleet workers run on platform: cpu")
    cache = args.cache or "fleet_cache.json"
    procs, stats_paths = [], []
    for w in range(args.workers):
        stats_path = f"{cache}.worker{w}.stats.json"
        stats_paths.append(stats_path)
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--minibatch", str(args.minibatch), "--epochs", str(args.epochs),
            "--scale", str(args.scale), "--cache", cache, "--shared",
            "--probe-budget-ms", str(args.probe_budget_ms),
            "--worker-id", str(w), "--stats-json", stats_path,
        ]
        env = {**os.environ, "AUTOSAGE_CACHE_SHARED": "1",
               "JAX_PLATFORMS": "cpu"}
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [p.wait() for p in procs]
    if any(rcs):
        raise SystemExit(f"worker exit codes: {rcs}")
    totals = {"decides": 0, "probes_run": 0, "warm_cache_opens": 0,
              "drift_reprobes": 0, "drift_flips": 0}
    for sp in stats_paths:
        with open(sp) as fh:
            s = json.load(fh)
        for k in totals:
            totals[k] += s.get(k, 0)
        os.unlink(sp)
    print(
        f"fleet of {args.workers}: {totals['decides']} decides, "
        f"{totals['probes_run']} probes total, "
        f"{totals['warm_cache_opens']} buckets opened warm from peers, "
        f"{totals['drift_reprobes']} drift re-probes "
        f"({totals['drift_flips']} flipped); merged cache: {cache}"
    )


def finish_obs(args):
    """--obs epilogue: flush the flight-recorder artifacts and print the
    end-of-run metrics summary + estimate-accuracy scorecard (mean abs
    estimate error per op family). In fleet mode each worker flushes its
    own metrics_<pid> snapshot; aggregate them afterwards with
    `python -m repro.obs_cli summary`."""
    if not args.obs:
        return
    from repro.core import obs

    paths = obs.flush(force=True)
    print()
    print(obs.summary_text())
    if paths.get("trace"):
        print(
            f"[obs] artifacts in {os.path.dirname(paths['trace'])} "
            "(trace_*.json opens in ui.perfetto.dev; "
            "python -m repro.obs_cli summary/explain reads the rest)"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--minibatch", type=int, default=0,
                    help="rows per sampled subgraph; 0 = full-graph training")
    ap.add_argument("--probe-budget-ms", type=float, default=2000.0,
                    help="shared probe budget for the minibatch stream")
    ap.add_argument("--cache", default="",
                    help="schedule cache path (minibatch mode); empty = in-memory")
    ap.add_argument("--workers", type=int, default=0,
                    help="fleet mode: N subprocess trainers against one "
                         "shared cache (implies --minibatch)")
    ap.add_argument("--shared", action="store_true",
                    help="merge-on-flush shared cache "
                         "(set automatically in fleet workers)")
    ap.add_argument("--obs", action="store_true",
                    help="flight recorder: sets AUTOSAGE_OBS=1 (spans + "
                         "metrics + scorecard) and prints the end-of-run "
                         "summary; artifacts land in AUTOSAGE_OBS_DIR "
                         "(default results/obs)")
    ap.add_argument("--worker-id", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--stats-json", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.obs:
        # before any decide: fleet workers inherit it through the env
        os.environ["AUTOSAGE_OBS"] = "1"

    if args.workers:
        if not args.minibatch:
            args.minibatch = 1024
        train_fleet(args)
        finish_obs(args)
        return

    compile_cache.enable()
    cfg = get_config("gnn_sage")
    graph = products_like(scale=args.scale)
    x, y = make_data(graph, N_CLASSES, IN_DIM)

    if args.minibatch:
        run_minibatch(args, cfg, graph, x, y)
    else:
        run_full(args, cfg, graph, x, y)
    finish_obs(args)


if __name__ == "__main__":
    main()
