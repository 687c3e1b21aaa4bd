"""Benchmark harness: one function per paper table (see tables.py).

    PYTHONPATH=src python -m benchmarks.run [--only table4_er] [--full]
    python -m benchmarks.run --smoke   # seconds-fast harness bit-rot check

Prints ``name,us_per_call,derived`` CSV summary lines at the end, plus
per-table detail while running. Full CSVs + .meta.json sidecars are
written to results/bench/.

Importable without side effects: all work happens in main(), guarded
under __main__, so CI can import-check this module and tests can call
main() with explicit argv.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent.parent / "src")


def main(argv=None) -> int:
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)
    from benchmarks.tables import ALL_TABLES, SMOKE_TABLES

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated table names")
    ap.add_argument("--full", action="store_true",
                    help="paper-size graphs (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end pass over every subsystem the "
                         "tables exercise; finishes in seconds (CI)")
    args = ap.parse_args(argv)

    tables = {**ALL_TABLES, **SMOKE_TABLES}
    if args.smoke and args.only:
        ap.error("--smoke and --only are mutually exclusive")
    if args.smoke:
        names = list(SMOKE_TABLES)
    elif args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in tables]
        if unknown:
            ap.error(
                f"unknown table(s): {', '.join(unknown)}; "
                f"available: {', '.join(tables)}"
            )
    else:
        names = list(ALL_TABLES)
    summary = []
    for name in names:
        fn = tables[name]
        print(f"[bench] {name}")
        t0 = time.perf_counter()
        rows = fn(full=args.full)
        dt = (time.perf_counter() - t0) * 1e6
        derived = ""
        try:
            # headline derived metric: max speedup in the table
            sp = [r[-1] for r in rows if isinstance(r[-1], (int, float))]
            if sp:
                derived = f"max_speedup={max(sp):.3f}"
        except Exception:
            pass
        summary.append((name, dt / max(len(rows), 1), derived))
    print("\nname,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")
    return 0


if __name__ == "__main__":
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)
    from repro import compile_cache

    compile_cache.enable()
    sys.exit(main())
