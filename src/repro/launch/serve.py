"""Online serving tier: schedule decisions under a per-request budget.

`serve_gnn` (the default subcommand) drives many concurrent client
streams of sampled subgraphs into one `GNNServer` process. The strict
tiering rule is the whole point:

  warm         a pinned bucket decision (local probe, warm cache open,
               or a drift-flagged bucket still serving its last pin) —
               answered inline, O(feature extraction)
  transfer     a peer device class's probed ranking re-ranked under the
               local roofline (core/transfer.py) — answered inline,
               estimate-space only
  provisional  a cold bucket: the guardrail-safe baseline is served
               IMMEDIATELY while the probe is exiled to the background
               probe-worker thread, which upgrades the bucket in place
               (`BatchScheduler.pump()`) — never on the request path
  cold         a request that paid a probe inline (auto_pump left on);
               the serving tier never does this, and
               `autosage_probe_stalls_total` counts any that slip by

Every request must return within `AUTOSAGE_SERVE_BUDGET_MS` (decision
latency, not kernel runtime). Per-bucket p50/p99 latency lands in
`autosage_serve_request_ms{bucket,tier}` (core/obs.py) and one JSONL
record per request/upgrade in serve_events.jsonl (core/telemetry.py).

    # serving demo: 4 clients, 2 passes over an 8-regime stream
    PYTHONPATH=src python -m repro.launch.serve serve_gnn \
        --clients 4 --requests 64

    # the legacy LLM prefill/decode demo moved behind a subcommand
    PYTHONPATH=src python -m repro.launch.serve demo-lm \
        --arch qwen3_14b --reduced --batch 4 --prompt-len 64 --gen 32

See docs/ARCHITECTURE.md ("The four serving tiers") for how the tiers
map onto the decision procedure, and docs/KNOBS.md for the env knobs.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from repro.core import obs, telemetry
from repro.core.batch import BatchScheduler
from repro.core.scheduler import Decision

DEFAULT_SERVE_BUDGET_MS = 50.0


def _budget_ms() -> float:
    """Per-request decision budget, read per call (tests rotate env)."""
    try:
        return float(
            os.environ.get("AUTOSAGE_SERVE_BUDGET_MS", DEFAULT_SERVE_BUDGET_MS)
        )
    except ValueError:
        return DEFAULT_SERVE_BUDGET_MS


@dataclasses.dataclass
class ServeResult:
    """One served request: the decision plus its admission accounting."""

    decision: Decision
    tier: str  # warm | transfer | provisional | cold
    source: str  # the BatchScheduler tier label behind the mapping
    bucket: str  # bucket sig the request was admitted into
    latency_ms: float
    stalled: bool  # a probe ran on this request's path (must not happen)


class GNNServer:
    """One serving process: admission-by-bucket over a `BatchScheduler`
    with probing exiled to a background worker thread.

    The wrapped scheduler runs with ``auto_pump=False`` — `submit()` is
    probe-free by construction. Cold buckets are opened inline (estimate
    space only), served their guardrail-safe provisional baseline, and
    enqueued for the probe worker, which calls `pump()` off the request
    path and upgrades each bucket's decision in place; the upgrade
    notification (`BatchScheduler.on_upgrade`) feeds the serve metrics
    and serve_events.jsonl. Use as a context manager or call `close()`
    so bucket decisions pin into the cache for deterministic replay."""

    _TIER_BY_SOURCE = {
        "bucket-cache": "warm",
        "probe": "warm",
        # a drift-flagged bucket keeps serving its last pinned decision
        # (guardrail-safe) while the re-probe waits in the background
        "drift-pending": "warm",
        # the bucket's pinned variant exceeds this graph's on-chip gates:
        # the baseline serves it, final, with no probe pending
        "gated": "warm",
        "transfer": "transfer",
        "transfer-pending": "transfer",
        "provisional": "provisional",
    }
    # sources whose bucket has a probe waiting on the budget: wake the
    # background worker after serving them
    _PENDING_SOURCES = ("provisional", "transfer-pending", "drift-pending")

    def __init__(
        self,
        scheduler: Optional[BatchScheduler] = None,
        budget_ms: Optional[float] = None,
        background_probes: bool = True,
    ):
        self.bs = scheduler if scheduler is not None else BatchScheduler()
        # probes never on the request path — non-negotiable for serving
        self.bs.auto_pump = False
        self.bs.on_upgrade = self._on_upgrade
        self.budget_ms = float(budget_ms) if budget_ms is not None else _budget_ms()
        self._stats_lock = threading.Lock()
        self.requests = 0
        self.tier_counts: Dict[str, int] = {}
        self.stalls = 0
        self.over_budget = 0
        self.upgrades = 0
        self.upgrade_events: List[Dict[str, Any]] = []
        self.latencies_ms: List[float] = []
        self._closed = False
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._worker: Optional[threading.Thread] = None
        if background_probes and not self.bs.cache.replay_only:
            self._worker = threading.Thread(
                target=self._probe_loop, name="autosage-probe-worker",
                daemon=True,
            )
            self._worker.start()

    # ------------------------------------------------------ request path
    def submit(self, csr, f: int, op: str = "spmm") -> ServeResult:
        """Serve one request: always answers within the decision budget
        (warm/transfer inline; cold buckets get the provisional baseline
        while their probe runs in the background)."""
        t0 = time.perf_counter()
        d = self.bs.decide(csr, f, op)
        latency_ms = (time.perf_counter() - t0) * 1e3
        source = self.bs.last_source or "provisional"
        stalled = self.bs.last_inline_probes > 0
        tier = "cold" if stalled else self._TIER_BY_SOURCE.get(source, "provisional")
        bucket = self.bs.last_bucket
        sig = bucket.sig() if bucket is not None else "?"
        with self._stats_lock:
            self.requests += 1
            self.tier_counts[tier] = self.tier_counts.get(tier, 0) + 1
            self.latencies_ms.append(latency_ms)
            if stalled:
                self.stalls += 1
            if latency_ms > self.budget_ms:
                self.over_budget += 1
        obs.record_serve_request(sig, tier, latency_ms, op=op)
        if stalled:
            obs.record_probe_stall(tier)
        telemetry.emit_serve_event(
            {
                "event": "request",
                "bucket": sig,
                "op": op,
                "f": f,
                "tier": tier,
                "source": source,
                "choice": d.choice,
                "latency_ms": round(latency_ms, 4),
                "budget_ms": self.budget_ms,
                "stalled": stalled,
            }
        )
        if source in self._PENDING_SOURCES:
            self._wake.set()
        return ServeResult(
            decision=d, tier=tier, source=source, bucket=sig,
            latency_ms=latency_ms, stalled=stalled,
        )

    def run(self, csr, decision: Decision):
        """Build the runner for a served decision (AutoSage-compatible)."""
        return self.bs.build_runner(csr, decision)

    # -------------------------------------------------- background probes
    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=0.05)
            self._wake.clear()
            if self._stop.is_set():
                break
            try:
                # drain: one bucket per pump so a stop lands between
                # probes, not after the whole queue
                while not self._stop.is_set() and self.bs.pump(1):
                    pass
            except Exception:
                # a faulting probe must never kill the worker — the
                # bucket keeps serving provisionally and resilience /
                # quarantine handle the candidate
                obs.REGISTRY.inc("autosage_serve_probe_errors_total")

    def _on_upgrade(self, event: Dict[str, Any]) -> None:
        """BatchScheduler upgrade notification: a background (or drift
        re-)probe just upgraded a bucket's decision in place."""
        with self._stats_lock:
            self.upgrades += 1
            self.upgrade_events.append(event)
        obs.REGISTRY.inc(
            "autosage_serve_upgrades_total", op=event.get("op", "?")
        )
        telemetry.emit_serve_event(
            {
                "event": "upgrade",
                "kind": event.get("event"),
                "bucket": event.get("bucket"),
                "op": event.get("op"),
                "choice": event.get("choice"),
                "probe_overhead_ms": event.get("probe_overhead_ms"),
            }
        )

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block until no bucket is pending a probe (or timeout). Serving
        continues meanwhile — this only waits on the background worker."""
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if not self.bs.pending():
                return True
            self._wake.set()
            time.sleep(0.005)
        return not self.bs.pending()

    # ------------------------------------------------------------ session
    def serve_stats(self) -> Dict[str, Any]:
        with self._stats_lock:
            lat = sorted(self.latencies_ms)
            tier_counts = dict(self.tier_counts)
            stats: Dict[str, Any] = {
                "requests": self.requests,
                "by_tier": tier_counts,
                "stalls": self.stalls,
                "over_budget": self.over_budget,
                "upgrades": self.upgrades,
                "budget_ms": self.budget_ms,
            }

        def q(p: float) -> Optional[float]:
            if not lat:
                return None
            return lat[min(int(p * len(lat)), len(lat) - 1)]

        stats.update(
            p50_ms=q(0.50), p95_ms=q(0.95), p99_ms=q(0.99),
            max_ms=lat[-1] if lat else None,
            pending_buckets=len(self.bs.pending()),
            buckets=self.bs.stats()["buckets"],
        )
        return stats

    def close(self, finalize: bool = True, timeout_s: float = 10.0) -> Dict[str, Any]:
        """Stop the probe worker, pin every bucket decision into the
        cache (deterministic replay), and emit the session summary. A
        hung probe (fault injection, wedged backend) cannot hang close:
        the worker is a daemon thread and finalize is skipped only if it
        failed to join."""
        if self._closed:
            return self.serve_stats()
        self._closed = True
        self._stop.set()
        self._wake.set()
        hung = False
        if self._worker is not None:
            self._worker.join(timeout_s)
            hung = self._worker.is_alive()
            if hung:
                obs.REGISTRY.inc("autosage_serve_hung_workers_total")
        if finalize and not hung:
            self.bs.finalize()
        stats = self.serve_stats()
        telemetry.emit_serve_event({"event": "summary", **stats})
        return stats

    def __enter__(self) -> "GNNServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(finalize=exc_type is None)


# ------------------------------------------------------------ serve_gnn


def _serve_parents(n: int, regimes: int, seed: int = 0):
    """<= 8 parent-graph regimes (mid-bin degrees + two heavy-tailed),
    mirroring the batched-stream benchmark so sampled subgraphs of one
    regime land in one schedule bucket."""
    from repro.sparse import fixed_degree, hub_skew

    parents = [
        fixed_degree(n, d, seed=seed + i)
        for i, d in enumerate((3, 6, 12, 24, 48, 96))
    ]
    parents.append(hub_skew(n, 6, 0.10, 60, seed=seed + 6))
    parents.append(hub_skew(n, 6, 0.10, 200, seed=seed + 7))
    return parents[:max(1, min(regimes, len(parents)))]


def run_serve_gnn(
    clients: int = 4,
    requests: int = 64,
    passes: int = 2,
    f: int = 16,
    op: str = "spmm",
    regimes: int = 4,
    parent_rows: int = 2048,
    rows_per_graph: int = 256,
    budget_ms: Optional[float] = None,
    probe_budget_ms: float = 10_000.0,
    cache_path: Optional[str] = None,
    replay: bool = False,
    think_ms: float = 1.0,
    seed: int = 0,
    quiet: bool = False,
) -> Dict[str, Any]:
    """Drive ``clients`` concurrent request streams through one
    `GNNServer`; returns the session stats. Each pass serves the same
    sampled-subgraph stream, so pass 1 exercises cold-admission +
    background upgrades and later passes the warm tier."""
    from repro.core import AutoSage, ScheduleCache
    from repro.sparse import sample_subgraph_stream

    parents = _serve_parents(parent_rows, regimes, seed=seed)
    stream = sample_subgraph_stream(
        parents, requests, rows_per_graph=rows_per_graph, seed=seed + 1
    )
    sage = AutoSage(
        cache=ScheduleCache(path=cache_path, replay_only=replay),
        probe_iters=1, probe_cap_ms=50, probe_frac=0.25,
    )
    bs = BatchScheduler(sage, probe_budget_ms=probe_budget_ms, auto_pump=False)
    server = GNNServer(bs, budget_ms=budget_ms)
    results: List[ServeResult] = []
    res_lock = threading.Lock()

    def client(cid: int) -> None:
        for g in stream[cid::clients]:
            r = server.submit(g, f, op)
            with res_lock:
                results.append(r)
            if think_ms > 0:
                time.sleep(think_ms / 1e3)

    for p in range(max(1, passes)):
        threads = [
            threading.Thread(target=client, args=(c,), name=f"client-{c}")
            for c in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # let the background prober finish this pass's cold buckets so
        # the next pass demonstrates the warm tier
        server.drain(timeout_s=60.0)

    stats = server.close(finalize=not replay)
    if not quiet:
        print(
            f"[serve] {stats['requests']} requests / {clients} clients / "
            f"{stats['buckets']} buckets  budget={stats['budget_ms']:.0f}ms"
        )
        for tier in ("warm", "transfer", "provisional", "cold"):
            n = stats["by_tier"].get(tier, 0)
            if n:
                print(f"[serve]   {tier:12s} {n}")
        print(
            f"[serve] latency p50={stats['p50_ms']:.3f}ms "
            f"p99={stats['p99_ms']:.3f}ms max={stats['max_ms']:.3f}ms  "
            f"stalls={stats['stalls']} over_budget={stats['over_budget']} "
            f"upgrades={stats['upgrades']}"
        )
        for row in obs.serve_latency_table():
            tiers = ",".join(f"{t}:{n}" for t, n in row["tiers"].items())
            print(
                f"[serve]   bucket {row['bucket'][:48]:48s} "
                f"n={row['requests']:<4d} p50={row['p50_ms']:.3f}ms "
                f"p99={row['p99_ms']:.3f}ms  [{tiers}]"
            )
    return stats


def serve_gnn_main(args: argparse.Namespace) -> int:
    stats = run_serve_gnn(
        clients=args.clients, requests=args.requests, passes=args.passes,
        f=args.f, op=args.op, regimes=args.regimes,
        rows_per_graph=args.rows, budget_ms=args.budget_ms,
        probe_budget_ms=args.probe_budget_ms, cache_path=args.cache,
        replay=args.replay, think_ms=args.think_ms, seed=args.seed,
    )
    return 0 if stats["stalls"] == 0 else 1


# -------------------------------------------------------------- demo-lm


def demo_lm_main(args: argparse.Namespace) -> int:
    """Legacy LLM serving demo: prefill a batch of prompts, then decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config, reduced as reduce_cfg
    from repro.launch.mesh import make_host_mesh
    from repro.models import api
    from repro.train.step import make_decode_step, make_prefill_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(args.seed)
    params = api.init_model(cfg, key, jnp.float32)
    max_len = args.prompt_len + args.gen
    cache = api.init_cache(cfg, args.batch, max_len, jnp.float32)

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (args.batch, cfg.vlm_patches, cfg.d_model)
        )
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_dec.enc_seq, cfg.d_model)
        )

    prefill = jax.jit(make_prefill_step(cfg, mesh))
    decode = jax.jit(
        make_decode_step(cfg, mesh, long_ctx=args.long_ctx), donate_argnums=(2,)
    )

    t0 = time.time()
    logits, cache = prefill(params, batch, cache)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    out = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"[serve] arch={cfg.name} batch={args.batch}")
    print(f"[serve] prefill {args.prompt_len} toks: {t_prefill*1e3:.1f} ms")
    print(
        f"[serve] decode {args.gen-1} steps: {t_decode*1e3:.1f} ms "
        f"({t_decode/(args.gen-1)*1e3:.2f} ms/tok)"
    )
    print(f"[serve] sample generations: {gen[:, :8].tolist()}")
    return 0


# ------------------------------------------------------------------ CLI

_COMMANDS = ("serve_gnn", "demo-lm")


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description=(
            "Online serving. Default subcommand: serve_gnn — concurrent "
            "client streams of sampled subgraphs answered within "
            "AUTOSAGE_SERVE_BUDGET_MS (cold probes run on a background "
            "worker, never on the request path)."
        ),
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    sg = sub.add_parser(
        "serve_gnn",
        help="serve schedule decisions to concurrent subgraph streams "
             "(the default subcommand)",
    )
    sg.add_argument("--clients", type=int, default=4,
                    help="concurrent client threads")
    sg.add_argument("--requests", type=int, default=64,
                    help="sampled subgraphs per pass (split across clients)")
    sg.add_argument("--passes", type=int, default=2,
                    help="passes over the stream (pass 1 cold, later warm)")
    sg.add_argument("--f", type=int, default=16, help="feature width")
    sg.add_argument("--op", default="spmm",
                    choices=("spmm", "sddmm", "attention"))
    sg.add_argument("--regimes", type=int, default=4,
                    help="parent-graph regimes (<= 8)")
    sg.add_argument("--rows", type=int, default=256,
                    help="rows per sampled subgraph")
    sg.add_argument("--budget-ms", type=float, default=None,
                    help="per-request decision budget "
                         "(default: AUTOSAGE_SERVE_BUDGET_MS, else 50)")
    sg.add_argument("--probe-budget-ms", type=float, default=10_000.0,
                    help="background probe budget for the whole session")
    sg.add_argument("--cache", default=None,
                    help="schedule-cache path (default: in-memory)")
    sg.add_argument("--replay", action="store_true",
                    help="serve pinned decisions only (AUTOSAGE_REPLAY_ONLY "
                         "semantics; unseen buckets raise)")
    sg.add_argument("--think-ms", type=float, default=1.0,
                    help="client think time between requests")
    sg.add_argument("--seed", type=int, default=0)
    sg.set_defaults(fn=serve_gnn_main)

    lm = sub.add_parser(
        "demo-lm", help="legacy LLM prefill/decode serving demo"
    )
    lm.add_argument("--arch", required=True)
    lm.add_argument("--reduced", action="store_true")
    lm.add_argument("--batch", type=int, default=4)
    lm.add_argument("--prompt-len", type=int, default=64)
    lm.add_argument("--gen", type=int, default=32)
    lm.add_argument("--long-ctx", action="store_true",
                    help="CSR window+sink attention")
    lm.add_argument("--seed", type=int, default=0)
    lm.set_defaults(fn=demo_lm_main)
    return ap


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # serve_gnn is the default: bare flags (or nothing) route to it,
    # except top-level -h/--help which shows the subcommand overview
    if not argv:
        argv = ["serve_gnn"]
    elif argv[0] not in _COMMANDS and argv[0] not in ("-h", "--help"):
        argv.insert(0, "serve_gnn")
    args = _build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    sys.exit(main())
