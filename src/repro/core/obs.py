"""Scheduler flight recorder: span tracing, metrics, estimate scorecard.

Three instruments:

  spans     nested, context-propagated spans over the decision procedure
            (``decide`` -> ``features``/``estimate``/``shortlist``/
            ``probe``/``guardrail``/``transfer``, ``prepare``,
            ``lower``/``compile`` of a training step, plus
            ``cache.lock_wait``/``cache.merge``, ``drift.reprobe`` and
            the fwd/bwd autodiff op spans) with monotonic durations.
            Two sinks. The profiler sink is always on: each span is a
            ``jax.profiler.TraceAnnotation`` named ``autosage.<name>``
            carrying the span's args, so a profile shows it on the
            device's clock (one TraceMe check when no profiler session
            runs). The file sink, behind `AUTOSAGE_OBS=1`, buffers spans
            in memory and exports them as Chrome/Perfetto
            ``trace_event`` JSON — a whole train step or batched epoch
            opens in ui.perfetto.dev.
  metrics   a process-wide registry of counters, gauges and log-bucketed
            histograms (p50/p95/p99 without sample storage), exported in
            Prometheus text format under stable names
            (``autosage_decides_total{op,tier}``, ``autosage_probe_ms``,
            ``autosage_cache_lock_wait_ms``,
            ``autosage_transfer_verdict_total{verdict}``, ...). This is
            the single accounting path: `BatchScheduler.stats()` and
            `sparse/csr.py`'s TRANSPOSE_STATS are views over it.
  scorecard every probe and every `BatchScheduler.observe()` feeds
            (candidate, est_ms, measured_ms) pairs into per-op-family
            error histograms (``autosage_est_abs_err_ms``) — the
            closed-loop measurement of roofline estimate quality that
            the transfer tier's residual calibration depends on.

The span stack lives in a `contextvars.ContextVar`, so a thread that
runs in a copy of its caller's context (`resilience.run_with_timeout`,
the probe watchdog) keeps its parent, depth and phase. The phase of a
moment is the innermost of ``probe``, ``prepare`` or ``compile`` on the
stack (``other`` when none is open); `listen_compiles` observes every
XLA backend compile (or persistent-cache retrieval) into
``autosage_xla_compile_ms{phase}``, and `registry.Variant.timed_prepare`
labels ``autosage_prepare_ms`` with it.

`op_scope` names one scheduled op in the compiled program: a
`jax.named_scope` ``autosage.<fwd|bwd>.<op>.f<F>.<choice>`` that lands in
the ``op_name`` metadata of every op traced under it.

Contract (the replay/fleet invariants the rest of the repo relies on):

  * `AUTOSAGE_OBS` unset  => no overhead beyond in-memory counter
    bumps, the span stack and the profiler sink's check, and NO files
    are ever created.
  * `AUTOSAGE_REPLAY_ONLY=1` => the file sink and file output are
    no-ops even with AUTOSAGE_OBS set, so replay-determinism runs stay
    bit-exact.
  * every line written to a ``.jsonl`` stream is ONE complete record in
    ONE ``write()`` on an O_APPEND descriptor (PR 4's atomicity rule) —
    N fleet workers interleave whole lines, never partial ones.

This module deliberately imports nothing from the rest of the package
(sparse/csr.py and core/cache.py sit below it in the import graph), and
imports jax only where a scope or listener needs it, or a span finds it
imported.
"""
from __future__ import annotations

import atexit
import bisect
import contextvars
import json
import math
import os
import re
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

OBS_SCHEMA = 1

# ---------------------------------------------------------------- gating


def enabled() -> bool:
    """Flight recording on? AUTOSAGE_OBS set (and not "0"/"") AND not a
    replay-determinism run. Read per call: tests rotate env between
    cases, and a stale module-import-time snapshot is exactly the bug
    class telemetry._meta() had."""
    env = os.environ
    if env.get("AUTOSAGE_OBS") in (None, "", "0"):
        return False
    return env.get("AUTOSAGE_REPLAY_ONLY") != "1"


def obs_dir() -> Path:
    """Where obs artifacts land: AUTOSAGE_OBS_DIR, else an ``obs/``
    subdirectory of AUTOSAGE_TELEMETRY_DIR, else results/obs."""
    d = os.environ.get("AUTOSAGE_OBS_DIR")
    if not d:
        t = os.environ.get("AUTOSAGE_TELEMETRY_DIR")
        d = str(Path(t) / "obs") if t else "results/obs"
    return Path(d)


# ---------------------------------------------------------------- spans

# completed spans buffered per process as raw tuples
#   (name, t0_ns, t1_ns, tid, parent, depth, args-or-None)
# and rendered to dict records only at flush/export time — the decide
# hot path pays no dict build, no lock (CPython list.append is atomic
# under the GIL) and no syscall per span
_SPAN_CAP = int(os.environ.get("AUTOSAGE_OBS_SPAN_CAP", "200000"))
_spans: List[Tuple] = []
_spans_lock = threading.Lock()  # flush/export/reset only, not the hot path
_spans_flushed = 0  # prefix of _spans already appended to spans.jsonl
_spans_dropped = 0
_active_dir: Optional[Path] = None  # obs dir captured at first record
# names of the open spans, innermost last
_stack: contextvars.ContextVar[Tuple[str, ...]] = contextvars.ContextVar(
    "autosage_span_stack", default=()
)
# wall-clock anchor: ts_us = anchor_wall + (perf_now - anchor_perf), so
# the hot path reads only the (cheaper, monotonic) perf counter
_ANCHOR_WALL_NS = time.time_ns()
_ANCHOR_PERF_NS = time.perf_counter_ns()


def _jsonable(v: Any) -> Any:
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


def _render(rec: Tuple) -> Dict[str, Any]:
    """Raw span tuple -> the stable on-disk record schema."""
    name, t0, t1, tid, parent, depth, args = rec
    out: Dict[str, Any] = {
        "schema": OBS_SCHEMA,
        "name": name,
        "ph": "X",
        "ts_us": (_ANCHOR_WALL_NS + (t0 - _ANCHOR_PERF_NS)) // 1000,
        "dur_us": max((t1 - t0) // 1000, 1),
        "t_mono": t0 / 1e9,
        "pid": os.getpid(),
        "tid": tid,
        "parent": parent,
        "depth": depth,
    }
    if args:
        out["args"] = {k: _jsonable(v) for k, v in args.items()}
    return out


_annotation = None  # jax.profiler.TraceAnnotation, once imported


def _profiler_span(name: str, args: Dict[str, Any]):
    """The profiler sink: a host span ``autosage.<name>`` in the
    profiler's trace (a TraceMe check, no more, outside a session)."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:  # no jax yet, so no profiler session
            return nullcontext()
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation("autosage." + name, **args)


@contextmanager
def span(name: str, **args: Any):
    """One nested span, in both sinks: always in the profiler's trace,
    and recorded for the files only when `enabled()`. The stack is a
    context variable, so a span opened inside another records its parent
    and depth, in a thread too when the thread runs in a copy of the
    caller's context; the Chrome trace nests them by containment."""
    stack = _stack.get()
    token = _stack.set(stack + (name,))
    try:
        with _profiler_span(name, args):
            if not enabled():
                yield None
                return
            t0 = time.perf_counter_ns()
            try:
                yield None
            finally:
                _record(name, t0, stack, args)
    finally:
        _stack.reset(token)


def _record(name: str, t0: int, stack: Tuple[str, ...], args: Dict[str, Any]) -> None:
    """The file sink: buffer one completed span (``stack`` holds the
    spans open around it)."""
    t1 = time.perf_counter_ns()
    global _spans_dropped, _active_dir
    if len(_spans) < _SPAN_CAP:
        _spans.append(
            (name, t0, t1, threading.get_ident(),
             stack[-1] if stack else None, len(stack), args or None)
        )
        if _active_dir is None:
            _active_dir = obs_dir()
    else:
        _spans_dropped += 1


# the spans that name a phase of set-up, for the compile and prepare
# counters
PHASES = ("probe", "prepare", "compile")


def phase() -> str:
    """The innermost phase span open in this context, or ``other``."""
    for name in reversed(_stack.get()):
        if name in PHASES:
            return name
    return "other"


# jax.monitoring's event for one XLA backend compile; it spans
# compile_or_get_cached, so a persistent-cache retrieval is one too
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
XLA_COMPILE_MS = "autosage_xla_compile_ms"
_listen_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration_secs: float, **_: Any) -> None:
    if event == BACKEND_COMPILE_EVENT:
        REGISTRY.observe(XLA_COMPILE_MS, duration_secs * 1e3, phase=phase())


def listen_compiles() -> None:
    """Observe every XLA backend compile of this process, on whatever
    thread compiles, into ``autosage_xla_compile_ms{phase}``. Registers
    one jax.monitoring listener, once (idempotent)."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


_UNSCOPED = re.compile(r"[^A-Za-z0-9_.]+")


def op_scope_name(kind: str, op: str, f: int, choice: str) -> str:
    """``autosage.<kind>.<op>.f<F>.<choice>`` with every run of other
    characters than ``[A-Za-z0-9_.]`` made one ``_`` (a choice is a
    variant's full name, ``row_ell[rb=8]``), so the name survives the
    compiled program's metadata and a trace reducer's patterns."""
    return _UNSCOPED.sub("_", f"autosage.{kind}.{op}.f{int(f)}.{choice}").rstrip("_")


def op_scope(kind: str, op: str, f: int, choice: str):
    """A `jax.named_scope` naming one scheduled op (`op_scope_name`): the
    ops traced under it carry the name in their ``op_name`` metadata
    (``jvp(<scope>)`` forward, ``transpose(jvp(<scope>))`` backward).
    Metadata only: the compiled program is otherwise the same."""
    import jax

    return jax.named_scope(op_scope_name(kind, op, f, choice))


# --------------------------------------------------------------- metrics

# log-spaced histogram bucket bounds (ms): sqrt(2) ratio from 1us-scale
# to ~1.5 minutes — percentile estimates are exact to within one bucket
# ratio without storing samples
_H_FACTOR = math.sqrt(2.0)
_H_BOUNDS: Tuple[float, ...] = tuple(
    1e-3 * _H_FACTOR ** i for i in range(54)
)


class Histogram:
    """Fixed log-bucket histogram: O(1) observe, O(buckets) quantile."""

    __slots__ = ("counts", "count", "sum", "vmin", "vmax")

    def __init__(self) -> None:
        self.counts = [0] * (len(_H_BOUNDS) + 1)
        self.count = 0
        self.sum = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[bisect.bisect_left(_H_BOUNDS, v)] += 1
        self.count += 1
        self.sum += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def quantile(self, q: float) -> Optional[float]:
        """Nearest-rank quantile, log-interpolated inside the landing
        bucket and clamped to the observed [min, max]."""
        if self.count == 0:
            return None
        rank = max(1, math.ceil(q * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum < rank:
                continue
            hi = _H_BOUNDS[i] if i < len(_H_BOUNDS) else self.vmax
            lo = _H_BOUNDS[i - 1] if i > 0 else min(self.vmin, hi)
            lo = max(lo, 1e-12)
            hi = max(hi, lo)
            frac = (rank - (cum - c)) / c
            est = lo * (hi / lo) ** frac
            return min(max(est, self.vmin), self.vmax)
        return self.vmax

    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    def merge(self, other: "Histogram") -> "Histogram":
        """Fold ``other`` into this histogram in place (bucket-wise sum);
        the aggregation step behind cross-label percentile views like
        `serve_latency_table`."""
        self.counts = [a + b for a, b in zip(self.counts, other.counts)]
        self.count += other.count
        self.sum += other.sum
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        return self


# call sites use literal label kwargs, so the (insertion-ordered) raw
# items tuple is a stable cache key for the sorted/stringified form —
# skips a sorted()+str() pass per counter bump on the decide hot path
_lk_cache: Dict[Tuple, Tuple[Tuple[str, str], ...]] = {}


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    try:
        raw = tuple(labels.items())
        lk = _lk_cache.get(raw)
        if lk is None:
            lk = tuple(sorted((k, str(v)) for k, v in labels.items()))
            if len(_lk_cache) < 8192:
                _lk_cache[raw] = lk
        return lk
    except TypeError:  # unhashable label value: compute directly
        return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _prom_labels(lk: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = []
    for k, v in lk:
        escaped = v.replace("\\", "\\\\").replace('"', '\\"')
        parts.append(k + '="' + escaped + '"')
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class MetricsRegistry:
    """Process-wide counters/gauges/histograms keyed by (name, labels).

    Always counts in memory (a labeled dict bump is ~1us, and
    `BatchScheduler.stats()` parity must hold regardless of
    AUTOSAGE_OBS); file output happens only through `flush()`/
    `prometheus_text()` callers, which the obs gate controls.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Dict[Tuple, float]] = {}
        self._gauges: Dict[str, Dict[Tuple, float]] = {}
        self._hists: Dict[str, Dict[Tuple, Histogram]] = {}

    # ---- writes ------------------------------------------------------
    def inc(self, name: str, n: float = 1.0, **labels: Any) -> None:
        lk = _label_key(labels)
        with self._lock:
            series = self._counters.setdefault(name, {})
            series[lk] = series.get(lk, 0.0) + n

    def set_counter(self, name: str, v: float, **labels: Any) -> None:
        """Direct counter assignment — only for reset paths (tests,
        reset_transpose_stats); live accounting goes through inc()."""
        with self._lock:
            self._counters.setdefault(name, {})[_label_key(labels)] = float(v)

    def set_gauge(self, name: str, v: float, **labels: Any) -> None:
        with self._lock:
            self._gauges.setdefault(name, {})[_label_key(labels)] = float(v)

    def observe(self, name: str, v: float, **labels: Any) -> None:
        lk = _label_key(labels)
        with self._lock:
            series = self._hists.setdefault(name, {})
            h = series.get(lk)
            if h is None:
                h = series[lk] = Histogram()
            h.observe(v)

    # ---- reads -------------------------------------------------------
    def get(self, name: str, **labels: Any) -> Optional[float]:
        lk = _label_key(labels)
        with self._lock:
            for store in (self._counters, self._gauges):
                if name in store and lk in store[name]:
                    return store[name][lk]
        return None

    def total(self, name: str, **labels: Any) -> float:
        """Sum of a counter over every label set matching ``labels``
        (subset match: total("x", op="spmm") sums all tiers)."""
        want = dict((k, str(v)) for k, v in labels.items())
        out = 0.0
        with self._lock:
            for lk, v in self._counters.get(name, {}).items():
                d = dict(lk)
                if all(d.get(k) == val for k, val in want.items()):
                    out += v
        return out

    def hist(self, name: str, **labels: Any) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get(name, {}).get(_label_key(labels))

    def hist_series(self, name: str) -> Dict[Tuple, Histogram]:
        with self._lock:
            return dict(self._hists.get(name, {}))

    def quantile(self, name: str, q: float, **labels: Any) -> Optional[float]:
        h = self.hist(name, **labels)
        return h.quantile(q) if h is not None else None

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    # ---- exporters ---------------------------------------------------
    def prometheus_text(self) -> str:
        """Prometheus text exposition format: counters/gauges as single
        samples, histograms as cumulative ``_bucket{le=...}`` series plus
        ``_sum``/``_count``."""
        lines: List[str] = []
        with self._lock:
            for name in sorted(self._counters):
                lines.append(f"# TYPE {name} counter")
                for lk in sorted(self._counters[name]):
                    v = self._counters[name][lk]
                    lines.append(f"{name}{_prom_labels(lk)} {_num(v)}")
            for name in sorted(self._gauges):
                lines.append(f"# TYPE {name} gauge")
                for lk in sorted(self._gauges[name]):
                    v = self._gauges[name][lk]
                    lines.append(f"{name}{_prom_labels(lk)} {_num(v)}")
            for name in sorted(self._hists):
                lines.append(f"# TYPE {name} histogram")
                for lk in sorted(self._hists[name]):
                    h = self._hists[name][lk]
                    cum = 0
                    for i, bound in enumerate(_H_BOUNDS):
                        cum += h.counts[i]
                        if cum == 0 and h.counts[i] == 0:
                            continue  # elide the empty low tail
                        le = 'le="{0:g}"'.format(bound)
                        lines.append(
                            f"{name}_bucket{_prom_labels(lk, le)} {cum}"
                        )
                    inf = 'le="+Inf"'
                    lines.append(
                        f"{name}_bucket{_prom_labels(lk, inf)} {h.count}"
                    )
                    lines.append(f"{name}_sum{_prom_labels(lk)} {_num(h.sum)}")
                    lines.append(f"{name}_count{_prom_labels(lk)} {h.count}")
        return "\n".join(lines) + "\n"

    def to_dict(self) -> Dict[str, Any]:
        """JSON snapshot (the machine-readable twin of the Prometheus
        text file; obs_cli `summary` aggregates these across workers)."""
        out: Dict[str, Any] = {
            "schema": OBS_SCHEMA,
            "t_mono": time.monotonic(),
            "pid": os.getpid(),
            "counters": {},
            "gauges": {},
            "histograms": {},
        }
        with self._lock:
            for name, series in self._counters.items():
                out["counters"][name] = [
                    {"labels": dict(lk), "value": v} for lk, v in sorted(series.items())
                ]
            for name, series in self._gauges.items():
                out["gauges"][name] = [
                    {"labels": dict(lk), "value": v} for lk, v in sorted(series.items())
                ]
            for name, series in self._hists.items():
                out["histograms"][name] = [
                    {
                        "labels": dict(lk),
                        "count": h.count,
                        "sum": h.sum,
                        "min": None if h.count == 0 else h.vmin,
                        "max": None if h.count == 0 else h.vmax,
                        "p50": h.quantile(0.50),
                        "p95": h.quantile(0.95),
                        "p99": h.quantile(0.99),
                    }
                    for lk, h in sorted(series.items())
                ]
        return out


def _num(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


REGISTRY = MetricsRegistry()


class ScopedCounter:
    """A per-instance counter mirrored into the process registry — the
    one accounting path for per-object stats like BatchScheduler's.
    ``value`` is the instance-local total (what `stats()` reports);
    every inc() also lands on the named registry counter with the given
    labels, so fleet-wide Prometheus series aggregate across instances."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0

    def inc(self, n: int = 1, **labels: Any) -> None:
        self.value += n
        REGISTRY.inc(self.name, n, **labels)


# ------------------------------------------------------------ scorecard


def _op_family(op: str) -> str:
    try:  # lazy: obs must not import the package at module level
        from repro.core.features import op_kind

        return op_kind(op)
    except Exception:
        return op


def record_estimate(
    op: str,
    candidate: str,
    est_ms: Optional[float],
    measured_ms: Optional[float],
    source: str = "probe",
) -> None:
    """One (candidate, est_ms, measured_ms) scorecard pair. ``source``
    is "probe" (roofline estimate vs slope-probe measurement) or
    "observe" (estimate vs the live runtime EWMA feed)."""
    if est_ms is None or measured_ms is None:
        return
    est_ms, measured_ms = float(est_ms), float(measured_ms)
    if not (math.isfinite(est_ms) and math.isfinite(measured_ms)):
        return
    fam = _op_family(op)
    abs_err = abs(measured_ms - est_ms)
    REGISTRY.observe("autosage_est_abs_err_ms", abs_err, family=fam, source=source)
    REGISTRY.observe(
        "autosage_est_rel_err", abs_err / max(measured_ms, 1e-9),
        family=fam, source=source,
    )
    REGISTRY.inc(
        "autosage_est_pairs_total", family=fam, source=source,
        candidate_kind="baseline" if candidate == "baseline" else "challenger",
    )


def record_probe_estimates(
    op: str,
    probe_ms: Dict[str, float],
    estimates_ms: Dict[str, float],
    baseline_name: str,
) -> None:
    """Scorecard-feed every probed candidate against its roofline
    estimate ("baseline" maps to the baseline variant's estimate key)."""
    for cand, measured in probe_ms.items():
        est = estimates_ms.get(baseline_name if cand == "baseline" else cand)
        record_estimate(op, cand, est, measured, source="probe")


def scorecard() -> Dict[str, Dict[str, Any]]:
    """Per-op-family estimate accuracy: pair count, mean/p95 absolute
    error (ms) and mean relative error, split by feed source."""
    out: Dict[str, Dict[str, Any]] = {}
    for lk, h in REGISTRY.hist_series("autosage_est_abs_err_ms").items():
        labels = dict(lk)
        key = f"{labels.get('family', '?')}/{labels.get('source', '?')}"
        rel = REGISTRY.hist("autosage_est_rel_err", **labels)
        out[key] = {
            "pairs": h.count,
            "mean_abs_err_ms": h.mean(),
            "p95_abs_err_ms": h.quantile(0.95),
            "mean_rel_err": rel.mean() if rel is not None else None,
        }
    return out


# -------------------------------------------------------------- serving

# stable metric names for the online serving tier (launch/serve.py):
#   autosage_serve_requests_total{tier,op}   request count by serving tier
#   autosage_serve_request_ms{bucket,tier}   per-bucket decision-latency
#                                            histograms (p50/p99 SLO view)
#   autosage_probe_stalls_total{tier}        requests that paid a probe
#                                            inline — must stay 0 for the
#                                            warm/transfer/provisional
#                                            tiers (the serve_smoke gate)
SERVE_REQUESTS = "autosage_serve_requests_total"
SERVE_REQUEST_MS = "autosage_serve_request_ms"
PROBE_STALLS = "autosage_probe_stalls_total"


def record_serve_request(
    bucket_sig: str, tier: str, ms: float, op: str = "?"
) -> None:
    """Account one served request: tier-labelled counter plus the
    per-bucket latency histogram the p50/p99 table reads."""
    REGISTRY.inc(SERVE_REQUESTS, tier=tier, op=op)
    REGISTRY.observe(SERVE_REQUEST_MS, ms, bucket=bucket_sig, tier=tier)


def record_probe_stall(tier: str) -> None:
    """A request paid a probe inline on the hot path."""
    REGISTRY.inc(PROBE_STALLS, tier=tier)


def serve_latency_table() -> List[Dict[str, Any]]:
    """Per-bucket request-latency percentiles, heaviest traffic first:
    one row per bucket aggregated across tiers, with the tier mix the
    bucket served under (a bucket that upgraded mid-stream shows both
    "provisional" and "warm")."""
    by_bucket: Dict[str, Histogram] = {}
    tiers: Dict[str, Dict[str, int]] = {}
    for lk, h in REGISTRY.hist_series(SERVE_REQUEST_MS).items():
        labels = dict(lk)
        b = labels.get("bucket", "?")
        agg = by_bucket.get(b)
        if agg is None:
            agg = by_bucket[b] = Histogram()
        agg.merge(h)
        t = labels.get("tier", "?")
        tiers.setdefault(b, {})[t] = tiers.get(b, {}).get(t, 0) + h.count
    rows = []
    for b, h in sorted(by_bucket.items(), key=lambda kv: -kv[1].count):
        rows.append(
            {
                "bucket": b,
                "requests": h.count,
                "p50_ms": h.quantile(0.50),
                "p95_ms": h.quantile(0.95),
                "p99_ms": h.quantile(0.99),
                "max_ms": None if h.count == 0 else h.vmax,
                "tiers": dict(sorted(tiers.get(b, {}).items())),
            }
        )
    return rows


# ------------------------------------------------------- file exporters


def _append_lines(path: Path, lines: List[str]) -> None:
    """Append each line as exactly one write() on an O_APPEND descriptor
    (PR 4's rule: concurrent workers interleave whole records only)."""
    path.parent.mkdir(parents=True, exist_ok=True)
    fd = os.open(str(path), os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
    try:
        for line in lines:
            os.write(fd, line.encode())
    finally:
        os.close(fd)


def _trace_events(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [
        {
            "name": r["name"],
            "cat": "autosage",
            "ph": "X",
            "ts": r["ts_us"],
            "dur": r["dur_us"],
            "pid": r["pid"],
            "tid": r["tid"],
            "args": r.get("args", {}),
        }
        for r in records
        if isinstance(r, dict) and r.get("ph") == "X"
    ]


def flush(directory: Optional[str] = None, force: bool = False) -> Dict[str, str]:
    """Write the flight-recorder state to disk:

      spans.jsonl        one span per line, appended (shared across
                         fleet workers; whole-line atomic appends)
      trace_<pid>.json   this process's spans as Chrome trace JSON
      metrics_<pid>.prom Prometheus text snapshot of the registry
      metrics_<pid>.json the same snapshot, machine-readable

    No-op (returns {}) unless obs is enabled or spans were recorded
    while it was (``force=True`` overrides, for explicit CLI/bench
    use). Returns the paths written."""
    global _spans_flushed
    with _spans_lock:
        recorded = bool(_spans) or _spans_flushed > 0
        base = _active_dir
    if not force and not (enabled() or recorded):
        return {}
    base = Path(directory) if directory else (base or obs_dir())
    pid = os.getpid()
    with _spans_lock:
        tail = _spans[_spans_flushed:]
        new = [_render(r) for r in tail]
        _spans_flushed += len(tail)
        all_spans = [_render(r) for r in _spans[:_spans_flushed]]
        dropped = _spans_dropped
    paths: Dict[str, str] = {}
    if new:
        _append_lines(
            base / "spans.jsonl",
            [json.dumps(r, sort_keys=True) + "\n" for r in new],
        )
    if all_spans or force:
        paths["spans"] = str(base / "spans.jsonl")
        trace = {
            "traceEvents": _trace_events(all_spans),
            "displayTimeUnit": "ms",
            "otherData": {"schema": OBS_SCHEMA, "dropped_spans": dropped},
        }
        tp = base / f"trace_{pid}.json"
        tp.parent.mkdir(parents=True, exist_ok=True)
        tp.write_text(json.dumps(trace))
        paths["trace"] = str(tp)
    base.mkdir(parents=True, exist_ok=True)
    (base / f"metrics_{pid}.prom").write_text(REGISTRY.prometheus_text())
    (base / f"metrics_{pid}.json").write_text(json.dumps(REGISTRY.to_dict()))
    paths["prom"] = str(base / f"metrics_{pid}.prom")
    paths["metrics"] = str(base / f"metrics_{pid}.json")
    return paths


def export_trace(
    out_path: str, directory: Optional[str] = None
) -> Dict[str, Any]:
    """Merge spans.jsonl (every worker's appends) plus this process's
    unflushed buffer into one Chrome/Perfetto trace JSON at
    ``out_path``; returns the trace object."""
    base = Path(directory) if directory else (_active_dir or obs_dir())
    records: List[Dict[str, Any]] = []
    spans_file = base / "spans.jsonl"
    if spans_file.exists():
        for line in spans_file.read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue  # torn tail from a crashed writer: skip, not crash
    with _spans_lock:
        records.extend(_render(r) for r in _spans[_spans_flushed:])
    trace = {
        "traceEvents": _trace_events(records),
        "displayTimeUnit": "ms",
        "otherData": {"schema": OBS_SCHEMA},
    }
    p = Path(out_path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(trace))
    return trace


def reset() -> None:
    """Clear spans + registry + the captured output dir (tests)."""
    global _spans_flushed, _spans_dropped, _active_dir
    with _spans_lock:
        _spans.clear()
        _spans_flushed = 0
        _spans_dropped = 0
        _active_dir = None
    REGISTRY.reset()
    _stack.set(())


def span_names() -> List[str]:
    """Distinct span names recorded so far in this process (tests and
    the obs_smoke gate)."""
    with _spans_lock:
        return sorted({r[0] for r in _spans})


def summary_text() -> str:
    """Human-readable end-of-run summary: headline counters, decide/probe
    latency percentiles, and the estimate-accuracy scorecard."""
    lines = ["== autosage obs summary =="]
    for name, label in (
        ("autosage_decides_total", "decides"),
        ("autosage_probe_passes_total", "probe passes"),
        ("autosage_transfers_total", "transfers"),
        ("autosage_drift_events_total", "drift events"),
        ("autosage_transpose_total", "csr transposes"),
        (SERVE_REQUESTS, "serve requests"),
        (PROBE_STALLS, "probe stalls"),
    ):
        total = REGISTRY.total(name)
        if total:
            lines.append(f"  {label:14s} {int(total)}")
    for name in ("autosage_decide_ms", "autosage_probe_ms",
                 "autosage_cache_lock_wait_ms", SERVE_REQUEST_MS):
        series = REGISTRY.hist_series(name)
        if not series:
            continue
        agg = Histogram()
        for h in series.values():
            agg.merge(h)
        lines.append(
            f"  {name}: n={agg.count} p50={agg.quantile(0.5):.3f}ms "
            f"p95={agg.quantile(0.95):.3f}ms p99={agg.quantile(0.99):.3f}ms"
        )
    card = scorecard()
    if card:
        lines.append("  estimate scorecard (|est - measured| per op family):")
        for key in sorted(card):
            row = card[key]
            lines.append(
                f"    {key:18s} pairs={row['pairs']:<4d} "
                f"mean_abs_err={row['mean_abs_err_ms']:.3f}ms "
                f"mean_rel_err={row['mean_rel_err']:.2f}"
            )
    return "\n".join(lines)


def _atexit_flush() -> None:
    try:
        flush()
    except Exception:
        pass  # never let telemetry take the interpreter down at exit


atexit.register(_atexit_flush)
