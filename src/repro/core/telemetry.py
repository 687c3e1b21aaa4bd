"""CSV + JSON telemetry (paper §10: every CSV gets a .meta.json sidecar
with device, software versions, and the AUTOSAGE_* env snapshot).

JSONL streams are multi-process safe: each stream keeps ONE unbuffered
O_APPEND handle per process (not an open/append/close per event), and
every record lands as a single write() of one full line — POSIX appends
at this size are atomic, so N worker processes interleave whole records,
never partial lines (the fleet harness tails decide_events.jsonl live).
"""
from __future__ import annotations

import atexit
import csv
import json
import os
import platform
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax

from repro.core.features import device_sig

# version stamp for every JSONL record this module writes; bump when a
# stream's field layout changes so downstream readers (obs_cli, the
# nightly artifact tooling) can branch instead of guessing
JSONL_SCHEMA = 1


def _env_snapshot() -> Dict[str, str]:
    """The AUTOSAGE_* env AT THIS CALL — never cached at import: tests
    and the fleet harness rotate AUTOSAGE_* between cases, and a stale
    module-level snapshot would attribute records to the wrong config."""
    return {k: v for k, v in os.environ.items() if k.startswith("AUTOSAGE_")}


def _meta() -> Dict:
    return {
        "device_sig": device_sig(),
        "jax_version": jax.__version__,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "env": _env_snapshot(),
    }


def write_csv(path: str, header: Sequence[str], rows: List[Sequence]) -> None:
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    with open(str(p) + ".meta.json", "w") as f:
        json.dump(_meta(), f, indent=1)


# one appending handle per stream path, opened lazily and reused for the
# process lifetime (an open/close per event costs ~3 syscalls/event and
# lets a buffered writer split a record across appends from two workers)
_handles: Dict[str, object] = {}
_handles_lock = threading.Lock()


def _handle(path: str):
    p = str(Path(path))
    with _handles_lock:
        f = _handles.get(p)
        if f is None or f.closed:
            Path(p).parent.mkdir(parents=True, exist_ok=True)
            # binary + unbuffered: each write() below is exactly one
            # O_APPEND syscall carrying one complete line
            f = open(p, "ab", buffering=0)
            _handles[p] = f
        return f


def close_streams() -> None:
    """Close every cached JSONL handle (tests that rotate
    AUTOSAGE_TELEMETRY_DIR between cases, and process exit)."""
    with _handles_lock:
        for f in _handles.values():
            try:
                f.close()
            except OSError:
                pass
        _handles.clear()


atexit.register(close_streams)


def append_jsonl(path: str, record: Dict) -> None:
    """Append one JSON record (tagged with the device signature, the
    stream schema version, and a monotonic timestamp for in-process
    ordering) to a .jsonl stream; creates parent dirs on first write.
    The record is serialized first and written with a single write() so
    concurrent writer processes cannot interleave partial lines."""
    line = json.dumps(
        {
            "schema": JSONL_SCHEMA,
            "t_mono": time.monotonic(),
            "device_sig": device_sig(),
            **record,
        },
        sort_keys=True,
    ) + "\n"
    _handle(path).write(line.encode())


def emit_batch_event(event: Dict) -> Optional[str]:
    """Batch-scheduler stream telemetry (per-decide events, bucket probes,
    finalize summaries) as one JSONL stream per run.

    No-op unless AUTOSAGE_TELEMETRY_DIR is set — the batched decide hot
    path must not touch the filesystem by default. Returns the path
    written."""
    out = os.environ.get("AUTOSAGE_TELEMETRY_DIR")
    if not out:
        return None
    path = str(Path(out) / "batch_stream.jsonl")
    append_jsonl(path, event)
    return path


def emit_fault_event(event: Dict) -> Optional[str]:
    """Resilience-layer stream (faults.jsonl): fault/fallback/quarantine/
    recovery events from core/resilience.py, one record per event —
    the chaos lane's artifact and obs_cli's provenance source.

    No-op unless AUTOSAGE_TELEMETRY_DIR is set. Returns the path
    written."""
    out = os.environ.get("AUTOSAGE_TELEMETRY_DIR")
    if not out:
        return None
    path = str(Path(out) / "faults.jsonl")
    append_jsonl(path, event)
    return path


def emit_serve_event(event: Dict) -> Optional[str]:
    """Online-serving stream (serve_events.jsonl): per-request records
    (tier, bucket, decision latency), background-probe bucket upgrades,
    and end-of-session summaries from the serving tier
    (launch/serve.py). One line per event, whole-line atomic appends —
    client threads and the probe worker share the stream.

    No-op unless AUTOSAGE_TELEMETRY_DIR is set — the request hot path
    must not touch the filesystem by default. Returns the path written."""
    out = os.environ.get("AUTOSAGE_TELEMETRY_DIR")
    if not out:
        return None
    path = str(Path(out) / "serve_events.jsonl")
    append_jsonl(path, event)
    return path


def emit_decide_event(
    decision,
    feat=None,
    padding: Optional[Dict] = None,
    graph_sig: Optional[str] = None,
    kind: str = "decide",
) -> Optional[str]:
    """Per-op decide/prepare events (decide_events.jsonl), keyed so cached
    decisions can be audited against skew after the fact: a "decide"
    event records the input's estimated `padding_waste` next to the
    choice; a "prepare" event (emitted by build_runner) records the
    exact per-partition `padding_frac` the block-ELL conversion
    measured. A cached dense-W choice showing up against
    padding_waste >= 0.75 inputs is drift — the ROADMAP's stale-decision
    detector reads exactly this stream.

    No-op unless AUTOSAGE_TELEMETRY_DIR is set. Returns the path written.
    """
    out = os.environ.get("AUTOSAGE_TELEMETRY_DIR")
    if not out:
        return None
    path = str(Path(out) / "decide_events.jsonl")
    rec = {
        "kind": kind,
        "op": decision.op,
        "choice": decision.choice,
        "from_cache": decision.from_cache,
    }
    tr = getattr(decision, "transfer", None)
    if tr:
        # cross-device provenance: which peer donated the ranking, how
        # the local re-rank agreed with it, and whether a local probe
        # confirmed or flipped the transferred choice
        rec["transfer"] = {
            k: tr[k]
            for k in (
                "source_device", "verdict", "rank_agreement", "top1_agrees",
                "peer_choice",
            )
            if k in tr
        }
    if feat is not None:
        rec.update(
            graph_sig=feat.graph_sig,
            n_rows=feat.n_rows,
            nnz=feat.nnz,
            f=feat.f,
            skew=feat.skew,
            padding_waste=feat.padding_waste,
            ell_width_est=feat.ell_width_est,
        )
    if graph_sig is not None:
        rec["graph_sig"] = graph_sig
    if padding:
        rec["padding_frac"] = padding
    append_jsonl(path, rec)
    return path

