"""Fault-tolerant execution layer: taxonomy, retries, fallback chains,
and the per-(candidate, device) circuit breaker.

The guardrail (core/guardrail.py, Prop. 1) defends against *slow*
choices; this module defends against choices that *raise or hang* —
a Pallas lowering failure on a new jax version, an OOM on a hub-heavy
shard, a worker dying mid-probe. The contract is that every decide/run
path always returns a runnable result:

fault taxonomy
    transient  worth retrying in place (bounded retries + exponential
               backoff, per-site FaultPolicy)
    permanent  never retried: OOM, NotImplementedError/TypeError/
               ValueError (a lowering that will fail identically again),
               probe watchdog timeouts

fallback chain (ordered, per op)
    chosen variant -> xla baseline variant -> reference oracle
    The terminal reference-oracle stage is *injection-immune* (no
    fault_point fires on it) — it is the guaranteed lifeline, so even
    ``AUTOSAGE_FAULT="run::raise:"`` (fault every run forever) still
    terminates with output bit-identical to the oracle.

circuit breaker / quarantine
    A candidate that exhausts its retries ``AUTOSAGE_BREAKER_N`` times
    (or fails permanently once) is quarantined per (candidate,
    device_sig): excluded from shortlist, probe, and transfer, and
    persisted into the shared cache as a ``quarantine|{device}|{name}``
    entry so fleet workers share the blacklist. Quarantine expires after
    ``AUTOSAGE_QUARANTINE_TTL_S`` into a half-open state granting one
    recovery probe: success clears (a "cleared" record with a fresh
    event time beats stale "active" records in the fleet merge),
    failure re-quarantines immediately. The baseline is exempt — the
    lifeline is never blacklisted.

``AUTOSAGE_RESILIENCE=0`` disables every wrapper (the chaos benchmark's
overhead A/B and an operational escape hatch).
"""
from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import faultinject, obs, telemetry
from repro.core.cache import CacheLockTimeout, ScheduleCache
from repro.core.faultinject import InjectedFault

TRANSIENT = "transient"
PERMANENT = "permanent"

DEFAULT_RETRIES = 1
DEFAULT_BACKOFF_MS = 2.0
DEFAULT_BACKOFF_MAX_MS = 50.0
DEFAULT_PROBE_TIMEOUT_S = 30.0
DEFAULT_BREAKER_N = 3
DEFAULT_QUARANTINE_TTL_S = 3600.0


class ProbeTimeout(RuntimeError):
    """The watchdog gave up on a probe that outlived its timeout."""


def enabled() -> bool:
    """Resilience wrappers active? AUTOSAGE_RESILIENCE=0 disables."""
    return os.environ.get("AUTOSAGE_RESILIENCE", "1") != "0"


def classify(exc: BaseException) -> str:
    """TRANSIENT (retry in place) or PERMANENT (straight to fallback).

    Permanent: OOM, a lowering/shape error that will fail identically on
    retry, an injected permanent fault, and watchdog timeouts (retrying
    a hang just hangs the retry budget too)."""
    if isinstance(exc, InjectedFault):
        return PERMANENT if exc.permanent else TRANSIENT
    if isinstance(
        exc,
        (MemoryError, NotImplementedError, TypeError, ValueError, ProbeTimeout),
    ):
        return PERMANENT
    return TRANSIENT


def fault_kind(exc: BaseException) -> str:
    """Metrics label for one fault."""
    if isinstance(exc, InjectedFault):
        return exc.kind
    if isinstance(exc, ProbeTimeout):
        return "timeout"
    if isinstance(exc, MemoryError):
        return "oom"
    if isinstance(exc, CacheLockTimeout):
        return "lock_timeout"
    return type(exc).__name__.lower()


@dataclass(frozen=True)
class FaultPolicy:
    """Per-site retry/backoff/watchdog budget."""

    retries: int = DEFAULT_RETRIES  # retries beyond the first attempt
    backoff_ms: float = DEFAULT_BACKOFF_MS
    backoff_max_ms: float = DEFAULT_BACKOFF_MAX_MS
    timeout_s: Optional[float] = None  # watchdog budget (probe site only)


def policy_for(site: str) -> FaultPolicy:
    """Env-tunable policy: AUTOSAGE_FAULT_RETRIES / _BACKOFF_MS apply to
    every site; AUTOSAGE_PROBE_TIMEOUT_S arms the probe watchdog."""

    def _f(name: str, default: float) -> float:
        try:
            return float(os.environ.get(name, default))
        except ValueError:
            return default

    retries = int(_f("AUTOSAGE_FAULT_RETRIES", DEFAULT_RETRIES))
    backoff = _f("AUTOSAGE_FAULT_BACKOFF_MS", DEFAULT_BACKOFF_MS)
    timeout = None
    if site == "probe":
        timeout = _f("AUTOSAGE_PROBE_TIMEOUT_S", DEFAULT_PROBE_TIMEOUT_S)
    return FaultPolicy(retries=retries, backoff_ms=backoff, timeout_s=timeout)


def record_fault(
    site: str, name: str, op: str, exc: BaseException
) -> None:
    """One fault event into the observability layer: counter + span +
    faults.jsonl telemetry. Never raises."""
    kind = fault_kind(exc)
    try:
        obs.REGISTRY.inc("autosage_faults_total", site=site, kind=kind)
        # label is "candidate", not "name": span()'s first positional
        # parameter is the span name and would collide
        with obs.span("fault", site=site, kind=kind, candidate=name, op=op):
            pass
        telemetry.emit_fault_event(
            {
                "event": "fault",
                "site": site,
                "kind": kind,
                "name": name,
                "op": op,
                "error": f"{type(exc).__name__}: {exc}",
            }
        )
    except Exception:
        pass  # fault accounting must never mask the fault itself


def record_fallback(frm: str, to: str, op: str) -> None:
    # "from" is a Python keyword, hence the ** spelling
    obs.REGISTRY.inc("autosage_fallback_total", **{"from": frm, "to": to})
    telemetry.emit_fault_event(
        {"event": "fallback", "from": frm, "to": to, "op": op}
    )


def retry_call(
    fn: Callable[[], Any],
    site: str,
    name: str = "",
    op: str = "",
    policy: Optional[FaultPolicy] = None,
) -> Any:
    """Call ``fn`` with the site's retry budget: transient faults back
    off exponentially and retry; permanent faults (and budget
    exhaustion) re-raise for the caller's fallback chain. Every fault —
    including the retried-away ones — is recorded."""
    pol = policy or policy_for(site)
    attempt = 0
    while True:
        try:
            return fn()
        except Exception as exc:
            record_fault(site, name, op, exc)
            if classify(exc) == PERMANENT or attempt >= pol.retries:
                raise
            delay_ms = min(pol.backoff_ms * (2.0 ** attempt), pol.backoff_max_ms)
            time.sleep(delay_ms / 1e3)
            attempt += 1


def run_with_timeout(
    fn: Callable[[], Any], timeout_s: Optional[float], site: str, name: str = ""
) -> Any:
    """Watchdog: run ``fn`` on a daemon thread and give up after
    ``timeout_s`` with ProbeTimeout. The hung thread is abandoned (it
    holds no locks the caller needs); daemon status keeps it from
    blocking interpreter exit. ``timeout_s`` None/<=0 runs inline. The
    thread runs in a copy of the caller's context, so the obs spans it
    opens keep their parent and phase."""
    if not timeout_s or timeout_s <= 0:
        return fn()
    box: Dict[str, Any] = {}
    ctx = contextvars.copy_context()

    def _target() -> None:
        try:
            box["value"] = fn()
        except BaseException as exc:  # noqa: BLE001 - relayed below
            box["error"] = exc

    t = threading.Thread(target=ctx.run, args=(_target,), daemon=True,
                         name=f"watchdog-{site}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise ProbeTimeout(f"{site}:{name or '*'} exceeded {timeout_s}s")
    if "error" in box:
        raise box["error"]
    return box.get("value")


@contextlib.contextmanager
def cache_guard(op: str = ""):
    """Swallow cache persistence faults (lock contention past timeout,
    injected lock/flush faults, disk errors) so a computed decision is
    still returned; the cache stays dirty and the next flush retries.
    ReplayMiss is NOT caught — the replay contract must stay loud."""
    try:
        yield
    except (CacheLockTimeout, InjectedFault, OSError) as exc:
        site = "lock" if isinstance(exc, CacheLockTimeout) else getattr(
            exc, "site", "flush"
        )
        record_fault(site, "cache", op, exc)


# --------------------------------------------------------- circuit breaker


def _breaker_n() -> int:
    try:
        return int(os.environ.get("AUTOSAGE_BREAKER_N", DEFAULT_BREAKER_N))
    except ValueError:
        return DEFAULT_BREAKER_N


def _quarantine_ttl_s() -> float:
    try:
        return float(
            os.environ.get("AUTOSAGE_QUARANTINE_TTL_S", DEFAULT_QUARANTINE_TTL_S)
        )
    except ValueError:
        return DEFAULT_QUARANTINE_TTL_S


class CircuitBreaker:
    """Per-(candidate, device_sig) failure accounting + quarantine.

    In-memory state is per-process; quarantine events additionally
    persist into the schedule cache as ``quarantine|{device}|{name}``
    entries whose ``stats.probed_at`` is the event time, so the existing
    fleet last-probe-wins merge resolves conflicting records (a fresh
    "cleared" beats a stale "active" and vice versa) and
    ``sync_from_cache`` adopts peers' verdicts."""

    def __init__(
        self,
        cache: Optional[ScheduleCache] = None,
        threshold: Optional[int] = None,
        ttl_s: Optional[float] = None,
    ):
        self.cache = cache
        self._threshold = threshold
        self._ttl_s = ttl_s
        self._fails: Dict[str, int] = {}  # consecutive exhausted failures
        self._run_fails: Dict[str, int] = {}  # run-site failures (drift signal)
        self._active: Dict[str, Dict[str, Any]] = {}  # name -> quarantine rec
        self._half_open: set = set()  # granted one recovery probe
        self._cleared_at: Dict[str, float] = {}  # name -> clear event time
        self._synced_mtime: Optional[int] = None

    @property
    def threshold(self) -> int:
        return self._threshold if self._threshold is not None else _breaker_n()

    @property
    def ttl_s(self) -> float:
        return self._ttl_s if self._ttl_s is not None else _quarantine_ttl_s()

    # ---- queries ------------------------------------------------------
    def is_quarantined(self, name: str) -> bool:
        """Actively quarantined (TTL-checked). A record past its TTL
        transitions to half-open — one recovery probe is allowed."""
        rec = self._active.get(name)
        if rec is None:
            return False
        ttl = float(rec.get("ttl_s") or self.ttl_s)
        if time.time() - float(rec.get("since") or 0.0) > ttl:
            self._active.pop(name, None)
            self._half_open.add(name)
            obs.REGISTRY.inc(
                "autosage_quarantine_total", event="recovery_probe"
            )
            telemetry.emit_fault_event(
                {"event": "recovery_probe", "name": name}
            )
            return False
        return True

    def is_excluded(self, name: str) -> bool:
        """Exclude from shortlist/probe/transfer? Half-open candidates
        are NOT excluded — that is their recovery probe."""
        return self.is_quarantined(name)

    def excluded_names(self) -> set:
        return {n for n in list(self._active) if self.is_quarantined(n)}

    def run_failures(self, name: str) -> int:
        """Run-site failures seen for this candidate (the batch
        scheduler's re-open signal for faulting transferred choices)."""
        return self._run_fails.get(name, 0)

    def active_quarantine(self, name: str) -> Optional[Dict[str, Any]]:
        return self._active.get(name)

    # ---- state transitions -------------------------------------------
    def record_failure(
        self, name: str, site: str = "run", op: str = "", permanent: bool = False
    ) -> bool:
        """One exhausted (post-retry) failure. Returns True if it tipped
        the candidate into quarantine. The baseline is exempt."""
        if not name or name == "baseline":
            return False
        n = self._fails.get(name, 0) + 1
        self._fails[name] = n
        if site == "run":
            self._run_fails[name] = self._run_fails.get(name, 0) + 1
        if name in self._half_open:
            # failed its one recovery probe: straight back to quarantine
            self._half_open.discard(name)
            self._quarantine(name, site, op, "recovery_failed", n)
            return True
        if name in self._active:
            return True
        if permanent or n >= self.threshold:
            reason = "permanent" if permanent else f"{n}_failures"
            self._quarantine(name, site, op, reason, n)
            return True
        return False

    def record_success(self, name: str) -> None:
        """A clean call resets the consecutive-failure count; a success
        while half-open/quarantined clears the quarantine (persisted as
        a "cleared" record so the fleet un-blacklists too)."""
        if not name or name == "baseline":
            return
        self._fails.pop(name, None)
        self._run_fails.pop(name, None)
        if name in self._half_open or name in self._active:
            self._half_open.discard(name)
            old = self._active.pop(name, None)
            now = time.time()
            self._cleared_at[name] = now
            obs.REGISTRY.inc("autosage_quarantine_total", event="recover")
            telemetry.emit_fault_event(
                {"event": "recover", "name": name,
                 "was": (old or {}).get("reason")}
            )
            self._persist(
                {
                    "name": name,
                    "device": self._device(),
                    "state": "cleared",
                    "reason": "recovered",
                    "since": now,
                    "ttl_s": self.ttl_s,
                }
            )

    def _quarantine(
        self, name: str, site: str, op: str, reason: str, fails: int
    ) -> None:
        now = time.time()
        rec = {
            "name": name,
            "device": self._device(),
            "state": "active",
            "site": site,
            "op": op,
            "reason": reason,
            "fails": fails,
            "since": now,
            "ttl_s": self.ttl_s,
        }
        self._active[name] = rec
        self._half_open.discard(name)
        obs.REGISTRY.inc("autosage_quarantine_total", event="quarantine")
        telemetry.emit_fault_event({"event": "quarantine", **rec})
        self._persist(rec)

    # ---- persistence / fleet sync ------------------------------------
    @staticmethod
    def _device() -> str:
        from repro.core.features import device_sig

        return device_sig()

    def _persist(self, rec: Dict[str, Any]) -> None:
        cache = self.cache
        if cache is None or cache.replay_only:
            return
        key = ScheduleCache.quarantine_key(rec["device"], rec["name"])
        entry = {
            "choice": rec["name"],
            "quarantine": rec,
            # event time as probed_at: the fleet merge's last-probe-wins
            # rule then resolves conflicting records by recency
            "stats": {"probed_at": rec["since"]},
        }
        with cache_guard(op=rec.get("op", "")):
            cache.put(key, entry)

    def maybe_sync(self) -> None:
        """Cheap sync: re-scan the cache's quarantine records only when
        its on-disk state changed since the last scan (or on first use).
        In-process events are already in memory — this is how a peer
        worker's quarantine reaches us."""
        cache = self.cache
        if cache is None:
            return
        mtime = getattr(cache, "_disk_mtime_ns", None)
        if self._synced_mtime is not None and mtime == self._synced_mtime:
            return
        self._synced_mtime = mtime
        self.sync_from_cache()

    def sync_from_cache(self) -> None:
        """Adopt quarantine records for THIS device from the cache,
        last-event-wins against local state."""
        cache = self.cache
        if cache is None:
            return
        dev = self._device()
        for _key, rec in cache.quarantine_records(device=dev):
            name = rec.get("name")
            if not name:
                continue
            since = float(rec.get("since") or 0.0)
            if rec.get("state") == "active":
                mine = self._active.get(name)
                newer_than_clear = since > self._cleared_at.get(name, -1.0)
                if newer_than_clear and (
                    mine is None or since > float(mine.get("since") or 0.0)
                ):
                    self._active[name] = dict(rec)
                    self._half_open.discard(name)
            elif rec.get("state") == "cleared":
                mine = self._active.get(name)
                if mine is not None and since > float(mine.get("since") or 0.0):
                    self._active.pop(name, None)
                    self._fails.pop(name, None)
                    self._run_fails.pop(name, None)
                self._cleared_at[name] = max(
                    self._cleared_at.get(name, 0.0), since
                )


# --------------------------------------------------------- fallback chain


def _infer_f(op: str, args: tuple) -> int:
    """Feature width (per head) from the runtime operands (the fallback
    stages are built lazily, after the decision object is long gone)."""
    from repro.core import features as features_mod

    kind = features_mod.op_kind(op)
    if kind == "spmm":
        return int(args[-1].shape[-1])
    return int(args[0].shape[-1])


def _infer_heads(args: tuple) -> int:
    """Heads of the runtime operands: the last is dense, (n, F) or
    (n, H, F), for every op kind."""
    return int(args[-1].shape[1]) if args[-1].ndim == 3 else 1


def reference_runner(csr, op: str) -> Callable:
    """The chain's terminal stage: the pure-jnp oracle for ``op``'s
    structural kind. No fault_point fires here — this is the lifeline
    whose output the chaos conformance suite compares against. Eager on
    purpose (no jax.jit): jit fusion reorders reductions enough to break
    bit-identity with the oracle the suite asserts against, and the
    lifeline optimizes for trustworthiness, not speed."""
    import jax.numpy as jnp

    from repro.core import features as features_mod
    from repro.kernels import ref

    kind = features_mod.op_kind(op)
    dynamic = features_mod.op_dynamic_vals(op)
    rowptr = jnp.asarray(csr.rowptr)
    colind = jnp.asarray(csr.colind)
    val = None if csr.val is None else jnp.asarray(csr.val)
    if kind == "spmm" and dynamic:
        return lambda vals, b: ref.spmm_ref(rowptr, colind, vals, b)
    if kind == "spmm":
        return lambda b: ref.spmm_ref(rowptr, colind, val, b)
    if kind == "sddmm":
        return lambda x, y: ref.sddmm_ref(rowptr, colind, x, y)
    if kind == "attention":
        return lambda q, k, v: ref.csr_attention_ref(rowptr, colind, q, k, v)
    raise KeyError(op)


def fallback_stages(csr, op: str, choice: str, variant, hw) -> List[Tuple]:
    """Ordered (name, build(args)->runner, injectable) stages:
    chosen variant -> xla baseline -> reference oracle. The baseline
    stage is resolved lazily (it needs features, which need the runtime
    F); the oracle stage is injection-immune."""
    import jax

    stages: List[Tuple] = []

    if choice != "baseline":

        def build_choice(args, _v=variant):
            with obs.span("prepare", op=op, choice=choice), \
                    jax.ensure_compile_time_eval():
                aux = _v.timed_prepare(csr)
                return _v.build(aux)

        stages.append((choice, build_choice, True))

    def build_baseline(args):
        from repro.core import registry
        from repro.core.features import InputFeatures

        feat = InputFeatures.from_csr(csr, _infer_f(op, args), op,
                                      _infer_heads(args))
        base = registry.baseline(feat, hw)
        with obs.span("prepare", op=op, choice="baseline"), \
                jax.ensure_compile_time_eval():
            aux = base.timed_prepare(csr)
            return base.build(aux)

    stages.append(("baseline", build_baseline, True))
    stages.append(("reference", lambda args: reference_runner(csr, op), False))
    return stages


def chain_runner(
    stages: List[Tuple],
    op: str,
    breaker: Optional[CircuitBreaker] = None,
    on_stage_fault: Optional[Callable[[str, str, BaseException], None]] = None,
) -> Callable:
    """Runnable that walks the fallback chain: each call tries the first
    live stage (with the run-site retry budget) and falls through on an
    exhausted or permanent fault. A faulted stage is NOT collapsed for
    good — the breaker records each exhausted failure, and once the
    candidate crosses the quarantine threshold the stage is skipped via
    ``is_excluded`` (zero per-call cost) until its TTL half-opens it
    again. Without a breaker the stage IS dropped permanently (nothing
    would bound the re-attempt cost). The terminal stage has no
    fault_point and no further fallback. A stage after the first runs
    under its own `obs.op_scope`, nested in the caller's, so ops a
    fallback traced carry the name of the stage that served."""

    state: Dict[str, Any] = {"dead": set(), "runners": {}}
    kind = "bwd" if "_bwd" in op else "fwd"

    def run(*args):
        last_exc: Optional[BaseException] = None
        prev_fault: Optional[str] = None
        for i, (name, build, injectable) in enumerate(stages):
            if name in state["dead"]:
                continue
            if (
                breaker is not None and injectable
                and breaker.is_excluded(name)
            ):
                continue  # quarantined: skip without re-paying the fault
            if prev_fault is not None:
                record_fallback(prev_fault, name, op)
                prev_fault = None
            runner = state["runners"].get(name)
            site = "prepare" if runner is None else "run"
            scope = (obs.op_scope(kind, op, _infer_f(op, args), name) if i
                     else contextlib.nullcontext())
            try:
                with scope:
                    if runner is None:
                        if injectable:
                            runner = retry_call(
                                lambda: build(args), "prepare", name=name, op=op
                            )
                        else:
                            runner = build(args)
                        state["runners"][name] = runner
                    if injectable:

                        def attempt(_r=runner, _n=name):
                            faultinject.fault_point("run", name=_n, op=op)
                            return _r(*args)

                        out = retry_call(attempt, "run", name=name, op=op)
                    else:
                        out = runner(*args)
                    if breaker is not None and injectable:
                        breaker.record_success(name)
                    return out
            except Exception as exc:
                last_exc = exc
                if breaker is not None:
                    breaker.record_failure(
                        name, site=site, op=op,
                        permanent=classify(exc) == PERMANENT,
                    )
                else:
                    state["dead"].add(name)
                if on_stage_fault is not None:
                    on_stage_fault(name, site, exc)
                prev_fault = name
        if last_exc is not None:
            raise last_exc  # unreachable in practice: oracle cannot fault
        raise RuntimeError(f"no runnable stage left for {op}")

    return run
