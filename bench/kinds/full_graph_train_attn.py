"""Full-graph training of a graph transformer: the program's own compiled
UniMP step (``repro.models.gnn.unimp_forward`` through
``repro.train.gnn.train_full``) over the whole graph, one step at a time.
It runs as ``full_graph_train`` runs GraphSAGE:

Set-up (counted in ``setup_s``): the graph from the seed on the host;
features, labels, the label-fed nodes and weights from the seed on the
device (``bench/data_attn.py``); ``train_full`` with no steps of its own
(trace, decide, probe, prepare and compile of the step: per layer one
multi-head attention and its five backward pieces, each decided on the
chip with an in-memory schedule cache); then the check's first steps
through the compiled step itself.

Window: the same compiled step, continued from the state the check's
steps left, each step ending in ``block_until_ready`` and ``float(loss)``,
until ``seconds`` have passed; whole steps only. Python's garbage
collector runs at the end of set-up and not inside the window. A traced
run reduces the window's trace twice, by the top device ops and by the
program's scopes (``bench/scopes.py``), before removing it.

Check (after the window, the program's state freed): the configuration's
plain reference, at the configuration's ``dtype`` and
``matmul_precision``, takes the same first steps from the same weights,
and ``bench/compare.py``'s numbers are compared, each against the cell's
limit. The run is also not correct when the program counts a fault, a
fallback, a quarantine, a rescue decide or a transferred decide.

A program without UniMP cannot run the cell: the run stops at once, with
no result.

Parameters (the mix's file): ``lr``, ``check_steps``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict

from bench import compare, data_attn, graphs, harness, scopes, trace, work_attn
from bench.kinds.full_graph_train import _counts

# the program's scheduled ops of the step, forward and backward
ATTENTION_OPS = ("attention", "attention_bwd_e", "attention_bwd_v", "attention_bwd_p",
                 "attention_bwd_q", "attention_bwd_k")


def run(cell: harness.Cell) -> Dict:
    import jax

    span = jax.profiler.TraceAnnotation  # a host span in the trace; free untraced
    cfg, mix = cell.config, cell.mix
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
            from repro.models.gnn import init_unimp, unimp_forward
            from repro.sparse.csr import CSR
            from repro.train.gnn import train_full
        except ImportError as exc:
            raise harness.BenchError(
                f"the program in {cell.root / 'src'} cannot train UniMP: {exc}")
        compile_cache.enable()
        before = _counts(obs)
    with span("setup.graph"):
        g = graphs.make_graph(cfg, cell.seed)
    with span("setup.inputs"):
        x, y, fed, w0 = data_attn.inputs(cell.seed, g.n, cfg)
        jax.block_until_ready(w0)
    layers = data_attn.layers(cfg)
    heads = cfg["heads"]
    with span("setup.train_full"):
        arch = dataclasses.replace(get_config("gnn_sage"), n_layers=cfg["num_layers"],
                                   d_model=cfg["hidden"], n_heads=heads)
        sage = AutoSage(cache=ScheduleCache(path=None), hw=HardwareSpec.for_device(cell.device))
        forward = functools.partial(unimp_forward, heads=heads, labels=y, label_mask=fed)
        fr = train_full(arch, CSR(g.rowptr, g.colind, None, g.n, g.n), x, y,
                        cfg["num_classes"], 0, sage, lr=lr, init=init_unimp,
                        forward=forward, loss_mask=~fed)
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
    # as full_graph_train: collect set-up's objects now, and none in the window
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    print(f"[run] gc: {gc.get_freeze_count()} objects frozen, collected in "
          f"{time.perf_counter() - t_gc:.3f} s", file=sys.stderr)
    rec["setup_s"] = time.perf_counter() - cell.t0

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if cell.trace else None
    tracer = trace.Recorder(log_dir) if cell.trace else None
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
    counted = {k: v - before[k] for k, v in _counts(obs).items()}
    rec["program"] = {"decide_s": counted.pop("autosage_decide_ms") / 1e3,
                      "probe_s": counted.pop("autosage_probe_ms") / 1e3,
                      "compile_s": fr.compile_s}
    for op in ATTENTION_OPS:
        for k in sage.cache.keys_for_op(op, kind="exact"):
            e, key = sage.cache.get(k), parse_key(k)
            print(f"[run] {op} H={key.heads} D={key.f} -> {e['choice']}; probed "
                  f"{sorted(e['probe_ms'])}", file=sys.stderr)
    ma = fr.compiled.memory_analysis()
    print(f"[run] step program: temp {ma.temp_size_in_bytes} B, arguments "
          f"{ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B; "
          f"window {steps} steps in {elapsed:.3f} s, each (ms) "
          f"{[round((b - a) * 1e3, 1) for a, b in zip([t_start] + ends, ends)]}",
          file=sys.stderr)
    peaks = cell.peaks
    rec["work"] = {
        "attention_least_s_per_step": sum(
            w.least_s(peaks.bf16_flops, peaks.hbm_bytes_per_s)
            for w in work_attn.attentions(g.n, g.nnz, layers)),
        "peak_flops": peaks.bf16_flops,
        "model_flops_per_step": work_attn.unimp_step_flops(g.n, g.nnz, layers)}
    if tracer:
        names = trace.op_names(fr.compiled.as_text())
        (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
        try:
            rec["trace"] = dict(trace.reduce_file(path, "window", names), steps=steps,
                                scopes=scopes.reduce_file(path, "window", names))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        by_scope = rec["trace"]["scopes"]
        attn = sum(s for k, s in by_scope.items() if k.split(".", 1)[-1].startswith("attention"))
        print(f"[run] device time by scope (s): {by_scope}; attention scopes "
              f"{attn / max(rec['trace']['busy_s'], 1e-30):.1%} of busy", file=sys.stderr)

    # free the program's state before the reference runs, so the
    # reference neither sets the memory peak nor runs short of memory
    del step, fr, sage, params, loss, forward
    gc.collect()

    with span("check.reference"):
        t_ref = time.perf_counter()
        ref = harness.load_module(cell.root / "bench" / "models" / f"{cfg['reference']}.py")
        ref_losses, ref_grads, ref_states = ref.train(
            w0, x, y, fed, g.rowptr, g.colind, lr, n_check, cfg["matmul_precision"], heads,
            cfg["dtype"])
        print(f"[run] reference: {n_check} steps in {time.perf_counter() - t_ref:.1f} s",
              file=sys.stderr)
    checks = compare.readings(losses, w0, w1, wk, lr, ref_losses, ref_grads, ref_states[-1])
    checks.update(counted)
    rec["checks"] = {k: {"value": float(v), "limit": float(cell.limits.get(k, 0.0))}
                     for k, v in checks.items()}
    print(f"[run] losses {losses}, reference {list(ref_losses)}", file=sys.stderr)
    return rec
