"""Full-graph training: the program's own compiled GraphSAGE step over
the whole graph, one step at a time.

Set-up (counted in ``setup_s``): the graph from the seed on the host;
features, labels and weights from the seed on the device in one jitted
call; the program's ``repro.train.gnn.train_full`` with no steps of its
own (trace, decide, probe, prepare and compile of the step, every
decision made and probed on the chip with an in-memory schedule cache);
then the check's first steps through the compiled step itself.

Window: the same compiled step, continued from the state the check's
steps left, each step ending in ``block_until_ready`` and ``float(loss)``,
until ``seconds`` have passed; whole steps only. Python's garbage
collector runs at the end of set-up and not inside the window.

Check (after the window, the program's state freed): the configuration's
plain reference, at the configuration's ``dtype`` and
``matmul_precision``, takes the same first steps from the same weights, and
``bench/compare.py``'s numbers are compared, each against the cell's
limit. The run is also not correct when the program counts a fault, a
fallback, a quarantine, a rescue decide or a transferred decide.

Parameters (the mix's file): ``lr``, ``check_steps``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import tempfile
import time
from typing import Dict

from bench import compare, data, graphs, harness, trace, work

# counters of the program that must stay at zero for a run to count
_GUARDED = ("autosage_faults_total", "autosage_fallback_total",
            "autosage_quarantine_total")
_GUARDED_TIERS = ("fault", "transfer", "transfer-pending")


def _counts(obs) -> Dict[str, float]:
    """The program's guarded counters and its decide and probe time (ms),
    as the process has counted them so far."""
    reg = obs.REGISTRY
    out = {name: reg.total(name) for name in _GUARDED}
    out.update({f"decides_{t}": reg.total("autosage_decides_total", tier=t)
                for t in _GUARDED_TIERS})
    for name in ("autosage_decide_ms", "autosage_probe_ms"):
        out[name] = sum(h.sum for h in reg.hist_series(name).values())
    return out


def run(cell: harness.Cell) -> Dict:
    import jax

    span = jax.profiler.TraceAnnotation  # a host span in the trace; free untraced
    cfg, mix = cell.config, cell.mix
    widths = data.dims(cfg)
    lr, n_check = float(mix["lr"]), int(mix["check_steps"])
    rec: Dict = {}

    with span("setup.program"):
        sys.path.insert(0, str(cell.root / "src"))
        try:
            from repro import compile_cache
            from repro.configs.base import get_config
            from repro.core import AutoSage, ScheduleCache, obs
            from repro.core.cache import parse_key
            from repro.core.features import HardwareSpec
            from repro.sparse.csr import CSR
            from repro.train.gnn import train_full
        except ImportError as exc:
            raise harness.BenchError(f"the program is not in {cell.root / 'src'}: {exc}")
        compile_cache.enable()
        before = _counts(obs)
    with span("setup.graph"):
        g = graphs.make_graph(cfg, cell.seed)
    with span("setup.inputs"):
        x, y, w0 = data.inputs(cell.seed, g.n, widths)
        jax.block_until_ready(w0)
    with span("setup.train_full"):
        arch = dataclasses.replace(get_config("gnn_sage"), n_layers=cfg["num_layers"],
                                   d_model=cfg["hidden"])
        sage = AutoSage(cache=ScheduleCache(path=None), hw=HardwareSpec.for_device(cell.device))
        fr = train_full(arch, CSR(g.rowptr, g.colind, None, g.n, g.n), x, y,
                        cfg["num_classes"], 0, sage, lr=lr)
    step = fr.compiled
    # the check's first steps go through the window's own call
    with span("setup.check_steps"):
        params, losses, states = w0, [], []
        for _ in range(n_check):
            loss, params = step(params)
            jax.block_until_ready((loss, params))
            losses.append(float(loss))
            states.append(params)
        w0, w1, wk = jax.device_get((w0, states[0], states[-1]))
        del states
    # set-up leaves many objects behind (traced programs, decisions,
    # probes): collect them here, keep them out of later collections, and
    # let no collection run inside the window, which times the steps alone
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    print(f"[run] gc: {gc.get_freeze_count()} objects frozen, collected in "
          f"{time.perf_counter() - t_gc:.3f} s", file=sys.stderr)
    rec["setup_s"] = time.perf_counter() - cell.t0

    tracer = trace.Recorder(tempfile.mkdtemp(prefix="bench-trace-")) if cell.trace else None
    if tracer:
        tracer.start()
    steps, window_losses, ends = 0, [], []
    gc.disable()
    t_start = time.perf_counter()
    with span("window"):
        while True:
            with span("window.step"):
                loss, params = step(params)
                jax.block_until_ready((loss, params))
                window_losses.append(float(loss))
            steps += 1
            ends.append(time.perf_counter())
            if ends[-1] - t_start >= cell.seconds:
                break
    elapsed = ends[-1] - t_start
    gc.unfreeze()
    gc.enable()
    if tracer:
        tracer.stop()
    rec["window"] = {"steps": steps, "seconds": elapsed}
    rec["attempted"] = steps
    rec["failed"] = sum(not math.isfinite(v) for v in window_losses)
    rec["memory_peak_bytes"] = harness.memory_peak_bytes(cell.device)
    # what this run added to the program's counts
    counted = {k: v - before[k] for k, v in _counts(obs).items()}
    rec["program"] = {"decide_s": counted.pop("autosage_decide_ms") / 1e3,
                      "probe_s": counted.pop("autosage_probe_ms") / 1e3,
                      "compile_s": fr.compile_s}
    for op in ("spmm", "spmm_bwd_b"):
        for k in sage.cache.keys_for_op(op, kind="exact"):
            e = sage.cache.get(k)
            print(f"[run] {op} F={parse_key(k).f} -> {e['choice']}; probed "
                  f"{sorted(e['probe_ms'])}", file=sys.stderr)
    ma = fr.compiled.memory_analysis()
    print(f"[run] step program: temp {ma.temp_size_in_bytes} B, arguments "
          f"{ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B; "
          f"window {steps} steps in {elapsed:.3f} s, each (ms) "
          f"{[round((b - a) * 1e3, 1) for a, b in zip([t_start] + ends, ends)]}",
          file=sys.stderr)
    least = sum(w.least_s(cell.peaks.bf16_flops, cell.peaks.hbm_bytes_per_s)
                for w in work.sage_spmms(g.n, g.nnz, widths))
    rec["work"] = {"spmm_least_s_per_step": least, "peak_flops": cell.peaks.bf16_flops,
                   "model_flops_per_step": work.sage_step_flops(g.n, g.nnz, widths)}
    if tracer:
        names = trace.op_names(fr.compiled.as_text())
        rec["trace"] = dict(tracer.reduce("window", names), steps=steps)

    # free the program's state before the reference runs, so the
    # reference neither sets the memory peak nor runs short of memory
    del step, fr, sage, params, loss
    gc.collect()

    with span("check.reference"):
        t_ref = time.perf_counter()
        ref = harness.load_module(cell.root / "bench" / "models" / f"{cfg['reference']}.py")
        ref_losses, ref_grads, ref_states = ref.train(
            w0, x, y, g.rowptr, g.colind, lr, n_check, cfg["matmul_precision"], cfg["dtype"])
        print(f"[run] reference: {n_check} steps in {time.perf_counter() - t_ref:.1f} s",
              file=sys.stderr)
    checks = compare.readings(losses, w0, w1, wk, lr, ref_losses, ref_grads, ref_states[-1])
    checks.update(counted)
    # a guarded counter's limit is 0; the gaps' limits are the cell's
    rec["checks"] = {k: {"value": float(v), "limit": float(cell.limits.get(k, 0.0))}
                     for k, v in checks.items()}
    print(f"[run] losses {losses}, reference {list(ref_losses)}", file=sys.stderr)
    return rec
