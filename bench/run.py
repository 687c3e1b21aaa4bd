"""The chip benchmark of this repository: one cell of ``BENCHMARK.json``,
one run, one process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine whose JAX finds the
chips the cell asks for (``chips``); anywhere else it exits non-zero,
naming the platform it found, and prints no result. The last line of
standard output is the result, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown`` of the top
device ops and the longest idle gaps, and last ``checks``: every number
compared to decide ``correct``, beside its limit. The same numbers are
the last lines of standard error.

Adding to the benchmark takes files and no edit (see ``bench/harness.py``):

- a configuration: ``bench/configs/<name>.json`` with the model's sizes,
  the graph generator's parameters (``num_nodes``, ``mean_degree``,
  ``degree_sigma``), the precision it states (``dtype``,
  ``matmul_precision``), the ``reference`` model in ``bench/models/``,
  ``source``, ``reduced`` and ``assumed``; and its entry under
  ``configs`` in ``BENCHMARK.json``;
- a traffic mix: ``bench/traffic/<name>.json``, whose ``kind`` names the
  generator in ``bench/kinds/`` that reads it;
- a cell: its entry under ``workloads`` and ``bench/limits/<cell>.json``;
- a metric: its entry in ``BENCHMARK.json`` and ``bench/metrics/<name>.py``
  with ``read(record)``, which returns None where the run holds nothing
  for it to read (then the metric is left out of the line, never 0).

The trace reducer (``bench/trace.py``) attributes a device op to the
scheduled SpMM when its XLA name stack passes through a jitted function
whose name holds ``spmm``: the program's kernels are such functions.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        ap.error("--seed must be a whole number of 0 or more")
    try:
        cell = harness.resolve(a.workload, a.seed, a.seconds, bool(a.trace), T0)
        harness.check_device(cell)
        line = harness.execute(cell)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
