"""Record a profiler trace of the measured window and reduce it to the
numbers the per-layer metrics read.

The reduction, from the ``.xplane.pb`` that ``jax.profiler`` writes:

- window: the host span the benchmark opened around the measured steps
  (``jax.profiler.TraceAnnotation``), on the same clock as the device;
- device busy time: on each device plane (``/device:TPU:<i>``), the union
  of the intervals of its "XLA Ops" events, clipped to the window,
  averaged over the devices;
- time per op: the top-level ops of that line (an op that lies inside
  another op of the line, such as the body of a while loop, is counted
  in its parent and not again), summed by name;
- SpMM time: the top-level ops whose XLA name stack passes through a
  jitted function whose name contains ``spmm`` (the scheduled runners are
  jitted functions such as ``spmm_gather_segsum`` or ``spmm_row_ell``;
  their ops carry ``jit(spmm_...)`` in the name stack, forward and
  backward). A device event is named by its HLO instruction's text; its
  name stack is the ``op_name`` metadata of that instruction in the
  compiled program (a fusion carries its root's; an instruction the
  compiler made without one takes its first operand's, and a loop with
  none takes the first nested op's). Where no op matches, the reducer
  reports no SpMM time, and a metric that needs it reports nothing:
  never 0;
- idle gaps: the stretches of the window in which the device ran no op,
  each named by the innermost host span of the benchmark that covers it.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Tuple

SPMM_FRAME = re.compile(r"jit\([^()/]*spmm[^()/]*\)", re.IGNORECASE)
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the benchmark's own host spans, by the prefix of their names
HOST_SPAN = re.compile(r"^(setup|window|check)(\.|$)")


class Recorder:
    """One profiler session into ``log_dir``, reduced and removed."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from TraceAnnotation
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self, window_name: str, names: Dict[str, str]) -> Dict:
        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file in {self.log_dir}, found {files}")
        try:
            return reduce_file(files[0], window_name, names)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$", re.MULTILINE)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([^\s,(){}=]+)")


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the name stack),
    from the text of a compiled program. An instruction the compiler made
    without one (a copy, a tuple, a loop that moves data) takes the name
    stack of its first operand that has one, so the data movement an op
    needs is counted with the op."""
    own: Dict[str, str] = {}
    for name, rhs in _HLO_LINE.findall(hlo_text):  # operands come first
        m = _OP_NAME.search(rhs)
        if m:
            own[name] = m.group(1)
            continue
        inherited = next((own[o] for o in _OPERAND.findall(rhs) if o in own), None)
        if inherited:
            own[name] = inherited
    return own


_INSTR = re.compile(r"^%?([^\s=]+)")


def instruction(ev) -> str:
    """The HLO instruction a device event ran: its events are named by the
    instruction's text, ``%fusion.18 = f32[...] fusion(...)``."""
    m = _INSTR.match(ev.name)
    return m.group(1) if m else ev.name


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _top_level(events: List[Tuple[float, float, object]]) -> List[Tuple[float, float, List]]:
    """Events not inside an earlier event of the same line, each with the
    events nested inside it (such as the body of a while loop)."""
    out: List[Tuple[float, float, List]] = []
    end = float("-inf")
    for s, e, ev in sorted(events, key=lambda t: (t[0], -t[1])):
        if e <= end:
            out[-1][2].append(ev)  # nested in the op before it
            continue
        out.append((s, e, [ev]))
        end = max(end, e)
    return out


def _op_name(evs: List, names: Dict[str, str]) -> str:
    """The name stack of a top-level op: its instruction's, or, for an op
    the compiler made without one (a loop it emitted), the first nested
    op's that has one."""
    for ev in evs:
        name = names.get(instruction(ev))
        if name:
            return name
    return ""


def reduce_planes(planes, window_name: str, names: Dict[str, str]) -> Dict:
    """Reduce the planes of one trace (see the module's docstring)."""
    host_spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if HOST_SPAN.match(ev.name):
                    s = float(ev.start_ns)
                    host_spans.append((s, s + float(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, name in host_spans if name == window_name]
    if len(windows) != 1:
        raise RuntimeError(f"expected one host span {window_name!r} in the trace, "
                           f"found {len(windows)}")
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    w0, w1 = windows[0]
    busy, spmm, ops = [], 0.0, {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(float(ev.start_ns), w0)
                e = min(float(ev.start_ns) + float(ev.duration_ns), w1)
                if e > s:
                    events.append((s, e, ev))
        merged = _union((s, e) for s, e, _ in events)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for s, e, evs in _top_level(events):
            stack = _op_name(evs, names)
            key = f"{instruction(evs[0])} {stack}".strip()[:200]
            ops[key] = ops.get(key, 0.0) + (e - s)
            if SPMM_FRAME.search(stack):
                spmm += e - s
    n_dev = len(devices)

    def host_span_at(t: float) -> str:
        cover = [(e - s, name) for s, e, name in host_spans if s <= t <= e]
        return min(cover)[1] if cover else "no benchmark span"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "spmm_s": spmm / n_dev / 1e9 if spmm > 0 else None,
        "device_ops": sorted(((k, v / n_dev / 1e9) for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [(host_span_at((a + b) / 2), (b - a) / 1e9) for a, b in gaps[:10]],
    }


def reduce_file(path: str, window_name: str, names: Dict[str, str]) -> Dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_name, names)

