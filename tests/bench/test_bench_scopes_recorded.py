"""The reduction by the program's named scopes on traces recorded on a
TPU v5e: five full-graph GraphSAGE steps at 4,096 nodes (Products
widths), each with the compiled step's HLO text. The scoped trace was
recorded from a program that names its scheduled ops; the older one
from a program that did not, so all of its time is unscoped."""
import gzip
import re
from pathlib import Path

import pytest

from bench import scopes, trace

DATA = Path(__file__).resolve().parent / "data"
SPMM_FRAME = re.compile(r"jit\([^()/]*spmm[^()/]*\)")


def _names(stem):
    with gzip.open(DATA / f"{stem}.hlo.txt.gz", "rt") as fh:
        return trace.op_names(fh.read())


@pytest.fixture(scope="module")
def names():
    return _names("tpu_v5e_sage_4096_scoped")


@pytest.fixture(scope="module")
def path():
    return str(DATA / "tpu_v5e_sage_4096_scoped.xplane.pb")


@pytest.fixture(scope="module")
def red(path, names):
    return trace.reduce_file(path, "window", names)


@pytest.fixture(scope="module")
def sched(path, names):
    return scopes.reduce_file(path, "window", names)


def test_window_busy_and_spmm_time(red):
    assert red["window_s"] == pytest.approx(0.207430188, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.196008676, rel=1e-9)
    assert red["spmm_s"] == pytest.approx(0.190999406, rel=1e-9)


def test_device_time_by_scope(sched):
    # forward: hub_split_ell at both widths; backward: row_ell at both
    assert sched == pytest.approx({"fwd.spmm": 0.161295118, "bwd.spmm_bwd_b": 0.029704288,
                                   "unscoped": 0.005061507}, rel=1e-6)


def test_the_scoped_spmm_time_is_what_the_spmm_frames_count(red, sched):
    assert sched["fwd.spmm"] + sched["bwd.spmm_bwd_b"] == pytest.approx(red["spmm_s"], rel=1e-9)


def test_every_spmm_op_of_the_program_carries_a_scope(names):
    spmm = [n for n in names.values() if SPMM_FRAME.search(n)]
    assert len(spmm) == 312
    assert {scopes.innermost(n) for n in spmm} == {"fwd.spmm", "bwd.spmm_bwd_b"}


def test_top_ops_name_their_scope_and_runner(red):
    label, seconds = red["device_ops"][0]
    assert label == ("while.2 jit(step)/jvp(autosage.fwd.spmm.f47.hub_split_ell_hub_threshold_365)"
                     "/jit(_spmm_hub_jit)/gather")
    assert seconds == pytest.approx(0.026571939, rel=1e-6)


def test_a_program_without_scopes_is_all_unscoped():
    older = _names("tpu_v5e_sage_4096")
    sched = scopes.reduce_file(str(DATA / "tpu_v5e_sage_4096.xplane.pb"), "window", older)
    assert set(sched) == {"unscoped"}
    assert sched["unscoped"] == pytest.approx(0.196613554, rel=1e-6)
