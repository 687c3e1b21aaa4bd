"""Readings that set a UniMP cell's limits, from the plain reference
put in the program's place, at the cell's own size.

    python3 bench/calibrate_attn.py --workload <name> --seeds <n> [<n> ...]
        [--stand-ins <name> ...]

For each seed, one JSON line per stand-in, each with the cell's compared
numbers (``bench/compare.py``) against the reference at the
configuration's precision, and the numbers that exceed the cell's limit:

- ``control``: the reference computed in bfloat16 (the precision below
  the configuration's float32): its readings must exceed a limit;
- ``attn_bf16``: the reference with the operands of every sum over edges
  (q, k, v and the attention weights) rounded to bfloat16, as one-pass
  bfloat16 SDDMM and SpMM contractions would;
- ``half_batch``: the reference with the loss over the first half of
  the loss nodes only (a planted fault);
- ``state_unchanged`` needs no run: a step that returns its state
  unchanged reads 1 as ``update_gap``.

The program's own readings come from the benchmark's runs (``checks`` in
their result lines). This script is run by hand on the chip; the
benchmark's runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import compare, data_attn, graphs, harness  # noqa: E402

STAND_INS = {"control": {"dtype": "bfloat16"}, "attn_bf16": {"attn_operand": "bfloat16"},
             "half_batch": {"half_batch": True}}


def seed_readings(cell: harness.Cell, seed: int,
                  names=tuple(STAND_INS)) -> Dict[str, Dict[str, float]]:
    """Readings of each named stand-in for one seed, at the cell's size."""
    cfg, lr, k = cell.config, float(cell.mix["lr"]), int(cell.mix["check_steps"])
    ref = harness.load_module(cell.root / "bench" / "models" / f"{cfg['reference']}.py")
    g = graphs.make_graph(cfg, seed)
    x, y, fed, w0 = data_attn.inputs(seed, g.n, cfg)
    stated = {"precision": cfg["matmul_precision"], "heads": cfg["heads"], "dtype": cfg["dtype"]}
    truth = ref.train(w0, x, y, fed, g.rowptr, g.colind, lr, k, **stated)
    out = {}
    for name in names:
        kw = STAND_INS[name]
        losses, _, states = ref.train(w0, x, y, fed, g.rowptr, g.colind, lr, k,
                                      **dict(stated, **kw))
        out[name] = compare.readings(losses, w0, states[0], states[-1], lr,
                                     truth[0], truth[1], truth[2][-1])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--stand-ins", nargs="+", choices=sorted(STAND_INS), default=list(STAND_INS))
    a = ap.parse_args(argv)
    cell = harness.resolve(a.workload, 0, 0.0, False, time.perf_counter())
    try:
        harness.check_device(cell)
    except harness.BenchError as exc:
        print(f"calibrate_attn: {exc}", file=sys.stderr)
        return 2
    for seed in a.seeds:
        for name, r in seed_readings(cell, seed, a.stand_ins).items():
            over = sorted(k for k, v in r.items() if v > cell.limits[k])
            print(json.dumps({"workload": a.workload, "seed": seed, "stand_in": name, **r,
                              "over_limit": over}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
