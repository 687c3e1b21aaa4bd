"""The trace reducer on planes made by hand: busy union, idle share, top
ops, SpMM attribution through the compiled program's names, and idle
gaps named by host spans."""
from types import SimpleNamespace as NS

import pytest

from bench import trace

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    """A device event as the profiler names it: by the instruction's text."""
    return NS(name=f"%{name} = f32[8]{{0}} op(%a)", start_ns=start_ms * MS,
              duration_ns=dur_ms * MS, stats={})


def host(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS, stats={})


def planes(device_events, host_events):
    return [
        NS(name="/host:CPU", lines=[NS(name="python3", events=host_events)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_step", 0, 100)]),
            NS(name="XLA Ops", events=device_events)]),
    ]


HLO = """
  %fusion.1 = f32[8,4]{1,0} fusion(%p0, %c1), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(jit(spmm_gather_segsum))/mul" stack_frame_id=3}
  %while.2 = (s32[], f32[8]) while(%tuple.7), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(jit(spmm_gather_segsum))/jit(searchsorted)/while"}
  %fusion.3 = f32[8]{0} fusion(%p1), kind=kLoop, calls=%fb, metadata={op_name="jit(step)/jvp(jit(spmm_gather_segsum))/add"}
  %fusion.4 = f32[8,4]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp())/dot_general" stack_frame_id=9}
  %fusion.5 = f32[8,4]{1,0} fusion(%x), kind=kLoop, calls=%fd, metadata={op_name="jit(step)/transpose(jvp(jit(spmm_row_ell)))/dot_general"}
  %fusion.6 = f32[8,4]{1,0} fusion(%y), kind=kLoop, calls=%fe, metadata={op_name="jit(step)/sub"}
  %copy.8 = f32[8,4]{1,0} copy(%fusion.5)
  %tuple.9 = (f32[8,4], f32[8,4]) tuple(%copy.8, %fusion.6)
  ROOT %while.10 = (f32[8,4], f32[8,4]) while(%tuple.9), condition=%c2, body=%b2
  %while.11 = (f32[8,4]) while(%param.3), condition=%c3, body=%b3
  %fusion.12 = f32[8]{0} fusion(%param.4), kind=kLoop, calls=%ff, metadata={op_name="jit(step)/jvp(jit(spmm_gather_segsum))/mul"}
"""
NAMES = trace.op_names(HLO)
HOST = [host("window", 10, 100), host("window.step", 10, 50), host("window.step", 64, 46),
        host("setup.graph", 0, 5)]
DEVICE = [
    ev("fusion.1", 5, 20),
    ev("while.2", 30, 20),
    ev("fusion.3", 35, 5),  # inside while.2
    ev("fusion.4", 45, 10),  # starts inside while.2, ends after it: not nested
    ev("fusion.5", 70, 30),
    ev("fusion.6", 105, 20),  # runs past the window
]


@pytest.fixture(scope="module")
def red():
    return trace.reduce_planes(planes(DEVICE, HOST), "window", NAMES)


def test_op_names_come_from_the_compiled_programs_metadata():
    assert NAMES["fusion.1"] == "jit(step)/jvp(jit(spmm_gather_segsum))/mul"
    assert NAMES["while.2"].endswith("jit(searchsorted)/while")
    assert "param.3" not in NAMES and "while.11" not in NAMES


def test_an_instruction_without_metadata_takes_its_first_named_operand():
    # copy.8 <- fusion.5; tuple.9 <- copy.8; while.10 <- tuple.9
    for name in ("copy.8", "tuple.9", "while.10"):
        assert NAMES[name] == NAMES["fusion.5"]


def test_window_is_the_host_span(red):
    assert red["window_s"] == pytest.approx(0.100)


def test_busy_is_the_union_clipped_to_the_window(red):
    # [10,25] + [30,55] + [70,100] + [105,110] = 15 + 25 + 30 + 5 ms
    assert red["busy_s"] == pytest.approx(0.075)


def test_spmm_time_counts_top_level_ops_under_a_spmm_frame(red):
    # fusion.1 clipped to 15, while.2 20 (fusion.3 inside it), fusion.5 30
    assert red["spmm_s"] == pytest.approx(0.065)


def test_top_ops_are_sorted_named_by_instruction_and_count_nested_ops_once(red):
    labels = [n for n, _ in red["device_ops"]]
    assert labels[0] == "fusion.5 jit(step)/transpose(jvp(jit(spmm_row_ell)))/dot_general"
    assert not any(n.startswith("fusion.3 ") for n in labels)
    assert dict(red["device_ops"])["fusion.4 jit(step)/transpose(jvp())/dot_general"] == \
        pytest.approx(0.010)


def test_a_loop_without_a_name_takes_its_first_nested_ops():
    dev = [ev("while.11", 20, 30), ev("fusion.12", 25, 10)]
    red = trace.reduce_planes(planes(dev, HOST), "window", NAMES)
    assert red["spmm_s"] == pytest.approx(0.030)


def test_idle_gaps_are_named_by_the_innermost_host_span(red):
    # gaps: 25..30 (step 1), 55..70 (between the steps: window), 100..105 (step 2)
    assert red["idle_gaps"][0] == ("window", pytest.approx(0.015))
    assert sorted(g for _, g in red["idle_gaps"]) == pytest.approx([0.005, 0.005, 0.015])
    assert {n for n, _ in red["idle_gaps"]} == {"window", "window.step"}


def test_no_spmm_op_reports_no_spmm_time():
    dev = [ev("fusion.6", 10, 50)]
    assert trace.reduce_planes(planes(dev, HOST), "window", NAMES)["spmm_s"] is None


def test_without_the_programs_names_nothing_is_attributed():
    assert trace.reduce_planes(planes(DEVICE, HOST), "window", {})["spmm_s"] is None


def test_a_trace_without_the_window_span_fails():
    with pytest.raises(RuntimeError, match="host span"):
        trace.reduce_planes(planes(DEVICE, HOST[1:]), "window", NAMES)


def test_a_trace_without_a_device_plane_fails():
    with pytest.raises(RuntimeError, match="no TPU device plane"):
        trace.reduce_planes(planes(DEVICE, HOST)[:1], "window", NAMES)
