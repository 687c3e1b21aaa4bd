"""The program names its own work: a named scope per scheduled op in the
compiled step, obs spans in the profiler's trace, and compile and
prepare time counted by phase (core/obs.py)."""
import contextlib
import dataclasses
import glob
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core import AutoSage, ScheduleCache, obs, resilience
from repro.sparse import fixed_degree
from repro.train.gnn import make_data, train_full

SPMM_FRAME = re.compile(r"jit\([^()/]*spmm[^()/]*\)")


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch, tmp_path):
    monkeypatch.delenv("AUTOSAGE_OBS", raising=False)
    monkeypatch.delenv("AUTOSAGE_REPLAY_ONLY", raising=False)
    monkeypatch.setenv("AUTOSAGE_OBS_DIR", str(tmp_path / "obs"))
    obs.reset()
    yield
    obs.reset()


def _compile_ms(phase):
    h = obs.REGISTRY.hist(obs.XLA_COMPILE_MS, phase=phase)
    return (h.count, h.sum) if h is not None else (0, 0.0)


def _strip(hlo: str) -> str:
    """The program without its source metadata: every ``metadata={...}``
    and the tables of files and frames the metadata points into."""
    return re.sub(r",? metadata=\{[^}]*\}", "", hlo.split("\nFileNames")[0])


def _op_names(hlo: str):
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _train(graph, sage, **kw):
    arch = dataclasses.replace(get_config("gnn_sage"), n_layers=3, d_model=16)
    x, y = make_data(graph, 4, 8)
    return train_full(arch, graph, x, y, 4, 0, sage, **kw)


@pytest.fixture(scope="module")
def steps():
    """The same small step compiled with the scopes and with the scope
    helper made a null context; the second trace replays the first's
    decisions and runners from the same scheduler."""
    g = fixed_degree(128, 4, seed=0)
    sage = AutoSage(cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=20)
    scoped = _train(g, sage).compiled.as_text()
    real = obs.op_scope
    obs.op_scope = lambda *a: contextlib.nullcontext()
    try:
        plain = _train(g, sage).compiled.as_text()
    finally:
        obs.op_scope = real
    return scoped, plain


@pytest.mark.parametrize("args,name", [
    (("fwd", "spmm", 256, "baseline"), "autosage.fwd.spmm.f256.baseline"),
    (("bwd", "spmm_bwd_b", 47, "row_ell"), "autosage.bwd.spmm_bwd_b.f47.row_ell"),
    (("fwd", "spmm", 16, "row_ell[bc=8,rb=8]"), "autosage.fwd.spmm.f16.row_ell_bc_8_rb_8"),
    (("fwd", "attention", 8, 'a{"x"}/b'), "autosage.fwd.attention.f8.a_x_b"),
    (("bwd", "spmm_bwd_b", 4, "merge[frac=0.5]"), "autosage.bwd.spmm_bwd_b.f4.merge_frac_0.5"),
])
def test_op_scope_names_keep_only_safe_characters(args, name):
    assert obs.op_scope_name(*args) == name


def test_the_scopes_change_only_the_metadata_of_the_compiled_step(steps):
    scoped, plain = steps
    assert scoped != plain
    assert _strip(scoped) == _strip(plain)
    assert not any("autosage." in n for n in _op_names(plain))


def test_every_spmm_op_of_the_step_carries_its_scope(steps):
    names = [n for n in _op_names(steps[0]) if SPMM_FRAME.search(n)]
    assert names
    for n in names:
        # forward: jvp(<scope>)/jit(<runner>); backward: transpose(jvp(<scope>))
        assert re.search(r"(^|/)jvp\(autosage\.fwd\.spmm\.f\d+\.\w+\)/jit\(", n) or \
            re.search(r"transpose\(jvp\(autosage\.bwd\.spmm_bwd_b\.f\d+\.\w+\)\)/jit\(", n), n
    assert {re.search(r"autosage\.(\w+)\.", n).group(1) for n in names} == {"fwd", "bwd"}


def test_lower_and_compile_are_spans_and_prepares_are_split_by_phase(monkeypatch):
    monkeypatch.setenv("AUTOSAGE_OBS", "1")
    sage = AutoSage(cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=20)
    before = _compile_ms("compile")[0]
    fr = _train(fixed_degree(96, 4, seed=1), sage)
    assert fr.trace_s > 0 and fr.compile_s > 0
    recs = {r[0]: r for r in obs._spans}
    assert {"lower", "compile", "fwd.spmm", "bwd.spmm_bwd_b", "prepare"} <= set(recs)
    assert "run" not in recs  # the op is named in the device trace, not timed here
    assert recs["compile"][4] is None and recs["fwd.spmm"][4] == "lower"
    assert _compile_ms("compile")[0] == before + 1  # the step, once
    phases = {dict(lk)["phase"] for lk in obs.REGISTRY.hist_series("autosage_prepare_ms")}
    assert "prepare" in phases and phases <= {"probe", "prepare"}


def test_a_compile_on_the_probe_watchdog_counts_under_probe(monkeypatch):
    monkeypatch.setenv("AUTOSAGE_OBS", "1")
    obs.listen_compiles()
    count0, _ = _compile_ms("probe")
    caller = threading.get_ident()

    def probe_work():
        with obs.span("timed"):
            assert obs.phase() == "probe"
            f = jax.jit(lambda a: jnp.tanh(a) * 3.0 + 0.125)
            f.lower(jax.ShapeDtypeStruct((3, 5), jnp.float32)).compile()
        return threading.get_ident()

    with obs.span("decide"):
        with obs.span("probe"):
            worker = resilience.run_with_timeout(probe_work, 60, "probe")
        assert obs.phase() == "other"
    assert worker != caller
    count1, ms = _compile_ms("probe")
    assert count1 == count0 + 1 and ms > 0
    rec = next(r for r in obs._spans if r[0] == "timed")
    assert rec[3] == worker and rec[4] == "probe" and rec[5] == 2


def test_listen_compiles_registers_one_listener():
    from jax._src import monitoring

    obs.listen_compiles()
    obs.listen_compiles()
    AutoSage(cache=ScheduleCache(path=None))
    mine = [cb for cb in monitoring.get_event_duration_listeners() if cb is obs._on_duration]
    assert len(mine) == 1


def test_a_fallback_stage_runs_under_its_own_scope():
    def broken(args):
        raise ValueError("prepare failed")

    stages = [("row_ell[rb=8]", broken, True),
              ("baseline", lambda args: (lambda b: jnp.sin(b) * 2.0), True)]
    run = resilience.chain_runner(stages, "spmm")

    def f(b):
        with obs.op_scope("fwd", "spmm", b.shape[1], "row_ell[rb=8]"):
            return run(b)

    names = _op_names(jax.jit(f).lower(jnp.ones((4, 6))).compile().as_text())
    assert "jit(f)/autosage.fwd.spmm.f6.row_ell_rb_8/autosage.fwd.spmm.f6.baseline/sin" in names


def test_spans_are_in_the_profilers_trace_and_write_no_file(tmp_path):
    from jax.profiler import ProfileData

    log = tmp_path / "profile"
    jax.profiler.start_trace(str(log))
    try:
        with obs.span("decide", op="spmm", f=16):
            with obs.span("probe", choice="row_ell[bc=8,rb=8]"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log / "**" / "*.xplane.pb"), recursive=True)
    events = {ev.name: ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("autosage.")}
    assert set(events) == {"autosage.decide", "autosage.probe"}
    decide, probe = events["autosage.decide"], events["autosage.probe"]
    assert dict(decide.stats) == {"op": "spmm", "f": 16}
    assert dict(probe.stats) == {"choice": "row_ell[bc=8,rb=8]"}
    assert decide.start_ns <= probe.start_ns
    assert probe.start_ns + probe.duration_ns <= decide.start_ns + decide.duration_ns
    # AUTOSAGE_OBS unset: the file sink recorded nothing and wrote nothing
    assert obs.span_names() == [] and obs.flush() == {}
    assert not (tmp_path / "obs").exists()


def test_obs_spans_without_jax_import_nothing():
    import subprocess
    import sys
    from pathlib import Path

    src = Path(obs.__file__)
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('obs', {str(src)!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "with m.span('probe'):\n"
        "    assert m.phase() == 'probe'\n"
        "assert 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
