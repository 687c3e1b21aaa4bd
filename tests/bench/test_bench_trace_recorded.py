"""The trace reducer on a trace recorded on a TPU v5e: five full-graph
GraphSAGE steps at 4,096 nodes (Products widths), with the compiled
step's HLO text that names its ops. Recorded by a run of the full-graph
mix with a 0.2 s window."""
import gzip
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def names():
    with gzip.open(DATA / "tpu_v5e_sage_4096.hlo.txt.gz", "rt") as fh:
        return trace.op_names(fh.read())


@pytest.fixture(scope="module")
def red(names):
    return trace.reduce_file(str(DATA / "tpu_v5e_sage_4096.xplane.pb"), "window", names)


def test_window_and_busy_union(red):
    assert red["window_s"] == pytest.approx(0.208500475, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.196561325, rel=1e-9)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(0.0573, abs=1e-4)


def test_spmm_ops_are_attributed(red):
    # the scheduled runners here are hub_split_ell (forward) and row_ell
    # (backward); the compiler's own loops and copies go with their data
    assert red["spmm_s"] == pytest.approx(0.191555842, rel=1e-9)
    assert red["spmm_s"] < red["busy_s"]


def test_top_ops_carry_their_name_stacks(red):
    label, seconds = red["device_ops"][0]
    assert label == "while.2 jit(step)/jvp(jit(_spmm_hub_jit))/gather"
    assert seconds == pytest.approx(0.0265666, rel=1e-4)
    assert len(red["device_ops"]) == 10


def test_idle_gaps_fall_between_host_spans(red):
    assert len(red["idle_gaps"]) == 10
    assert {name for name, _ in red["idle_gaps"]} <= {"window", "window.step"}
    assert red["idle_gaps"][0][1] == pytest.approx(0.002585564, rel=1e-6)


def test_every_program_op_has_a_name_stack(names):
    assert names["while.2"] == "jit(step)/jvp(jit(_spmm_hub_jit))/gather"
    assert sum("spmm" in n for n in names.values()) > 100
