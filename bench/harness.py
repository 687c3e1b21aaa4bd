"""Resolve a cell of ``BENCHMARK.json`` to its files, run it, and turn
the run's record into the result line.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by name:

- ``bench/configs/<config>.json``: the configuration as it is run
  (``BENCHMARK.json`` names the file); ``reference`` names the plain
  reference beside it, ``bench/models/<reference>.py``;
- ``bench/traffic/<traffic>.json``: the mix's parameters; its ``kind``
  names the generator that reads them, ``bench/kinds/<kind>.py``, whose
  ``run(cell)`` returns the run's record;
- ``bench/limits/<workload>.json``: the limit of each number the cell
  compares to decide ``correct``;
- ``bench/metrics/<metric>.py``: ``read(record)`` returns the metric's
  value from the record, or None where the run has nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# environment knobs of the program that swap the device's identity or
# serve decisions made elsewhere: a run under any of them is not a run
# of this chip
IDENTITY_KNOBS = ("AUTOSAGE_HW_PROFILE", "AUTOSAGE_DEVICE_SIG_OVERRIDE",
                  "AUTOSAGE_REPLAY_ONLY")


class BenchError(Exception):
    """A run that cannot give a result; it exits non-zero."""


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    mix: Dict
    limits: Dict
    metrics: List[Dict]  # the BENCHMARK.json entries this run reports
    chips: int
    seed: int
    seconds: float
    trace: bool
    t0: float  # perf_counter at process start
    root: Path = ROOT
    device: object = None
    peaks: object = None


def _load_json(path: Path) -> Dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import one of the benchmark's files by its path."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, seed: int, seconds: float, trace: bool, t0: float,
            root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its
    configuration, mix, limits and the metrics this run reports."""
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    mix = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{workload}.json")
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind] if workload in m.get("workloads", [workload])]
    return Cell(workload, config, mix, limits, metrics, int(w["chips"]), seed,
                seconds, trace, t0, root)


def check_device(cell: Cell) -> None:
    """The chip the cell asks for, or BenchError naming what was found."""
    set_knobs = [k for k in IDENTITY_KNOBS if os.environ.get(k)]
    if set_knobs:
        raise BenchError(f"unset {', '.join(set_knobs)}: they override the device's "
                         "identity or serve decisions made elsewhere")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {dev.platform!r} ({dev.device_kind})")
    if len(devices) < cell.chips:
        raise BenchError(f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
    from bench import peaks

    try:
        cell.peaks = peaks.for_kind(dev.device_kind)
    except KeyError as exc:
        raise BenchError(str(exc)) from None
    cell.device = dev


def memory_peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def execute(cell: Cell) -> Dict:
    """Run the cell's mix and build the result line."""
    kind = load_module(cell.root / "bench" / "kinds" / f"{cell.mix['kind']}.py")
    rec = kind.run(cell)
    checks = rec["checks"]
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    metrics = {}
    for m in cell.metrics:
        v = load_module(cell.root / "bench" / "metrics" / f"{m['name']}.py").read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    import jax

    dev = cell.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": rec["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": metrics, "device": device}
    if cell.trace:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    line["checks"] = checks  # last: each number compared, beside its limit
    return line


def report(line: Dict) -> None:
    """The result: each number compared, beside its limit, as the last
    lines of standard error, and the line as the last of standard out."""
    for name, c in line["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
