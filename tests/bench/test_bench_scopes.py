"""The reduction of device time by the program's named scopes
(``bench/scopes.py``), on planes made by hand: forward and backward
scopes, a loop the compiler shared between two SpMMs, the backward's
``transpose(jvp(...))`` stack, unscoped dense ops and sanitised choice
names."""
from types import SimpleNamespace as NS

import pytest

from bench import scopes, trace

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=f"%{name} = f32[8]{{0}} op(%a)", start_ns=start_ms * MS,
              duration_ns=dur_ms * MS, stats={})


def planes(device_events, n_devices=1):
    host = [NS(name="window", start_ns=10 * MS, duration_ns=100 * MS, stats={}),
            NS(name="autosage.decide", start_ns=0, duration_ns=5 * MS, stats={})]
    return [NS(name="/host:CPU", lines=[NS(name="python3", events=host)])] + [
        NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=device_events)])
        for i in range(n_devices)]


FWD = "jit(step)/jvp(autosage.fwd.spmm.f256.baseline)/jit(spmm_gather_segsum)"
BWD = "jit(step)/transpose(jvp(autosage.bwd.spmm_bwd_b.f47.row_ell_rb_8))/jit(spmm_row_ell)"
HLO = f"""
  %fusion.1 = f32[8,4]{{1,0}} fusion(%p0), kind=kLoop, calls=%fc, metadata={{op_name="{FWD}/mul"}}
  %while.2 = (s32[], f32[8]) while(%t), condition=%c, body=%b, metadata={{op_name="{FWD}/jit(searchsorted)/while"}}
  %fusion.3 = f32[8]{{0}} fusion(%p1), kind=kLoop, calls=%fb, metadata={{op_name="{FWD}/add"}}
  %fusion.4 = f32[8,4]{{1,0}} dot(%a, %b), metadata={{op_name="jit(step)/transpose(jvp())/dot_general"}}
  %fusion.5 = f32[8,4]{{1,0}} fusion(%x), kind=kLoop, calls=%fd, metadata={{op_name="{BWD}/dot_general"}}
  %fusion.6 = f32[8,4]{{1,0}} fusion(%y), kind=kLoop, calls=%fe, metadata={{op_name="jit(step)/sub"}}
  %copy.8 = f32[8,4]{{1,0}} copy(%fusion.5)
"""
NAMES = trace.op_names(HLO)
DEVICE = [
    ev("fusion.1", 5, 20),   # clipped to 15 ms by the window
    ev("while.2", 30, 20),   # the row-id search both forward SpMMs share
    ev("fusion.3", 35, 5),   # inside while.2: counted in it, not again
    ev("fusion.4", 55, 10),  # a dense matmul of the backward
    ev("fusion.5", 70, 20),
    ev("copy.8", 90, 5),     # no metadata: takes fusion.5's stack
    ev("fusion.6", 105, 20),  # clipped to 5 ms
]


@pytest.mark.parametrize("stack,key", [
    (f"{FWD}/mul", "fwd.spmm"),
    (f"{BWD}/dot_general", "bwd.spmm_bwd_b"),
    ("jit(step)/jvp(autosage.fwd.spmm.f16.row_ell_rb_8)/autosage.fwd.spmm_dyn.f16.baseline/sin",
     "fwd.spmm_dyn"),
    ("jit(step)/jvp(autosage.fwd.attention.f8.merge_frac_0.5)/jit(fused)/exp", "fwd.attention"),
    ("jit(step)/transpose(jvp())/dot_general", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope_names_direction_and_op(stack, key):
    assert scopes.innermost(stack) == key


def test_device_time_by_scope():
    red = scopes.reduce_planes(planes(DEVICE), "window", NAMES)
    assert red == pytest.approx({"fwd.spmm": 0.035, "bwd.spmm_bwd_b": 0.025,
                                 "unscoped": 0.015})


def test_scoped_time_is_what_the_spmm_frames_count():
    red = scopes.reduce_planes(planes(DEVICE), "window", NAMES)
    spmm_s = trace.reduce_planes(planes(DEVICE), "window", NAMES)["spmm_s"]
    assert red["fwd.spmm"] + red["bwd.spmm_bwd_b"] == pytest.approx(spmm_s)


def test_devices_are_averaged():
    red = scopes.reduce_planes(planes(DEVICE, n_devices=2), "window", NAMES)
    assert red["fwd.spmm"] == pytest.approx(0.035)


def test_without_the_programs_names_everything_is_unscoped():
    assert scopes.reduce_planes(planes(DEVICE), "window", {}) == \
        pytest.approx({"unscoped": 0.075})


def test_a_trace_without_the_window_or_a_device_fails():
    with pytest.raises(RuntimeError, match="host span"):
        scopes.reduce_planes(planes(DEVICE), "window.step", NAMES)
    with pytest.raises(RuntimeError, match="no TPU device plane"):
        scopes.reduce_planes(planes(DEVICE)[:1], "window", NAMES)
