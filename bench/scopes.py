"""Device time of a profiler trace by the program's own names.

The program names every scheduled op it runs (``repro.core.obs.op_scope``):
the ops a scheduled runner traced carry ``autosage.<dir>.<op>.f<F>.<choice>``
in the ``op_name`` metadata of the compiled program, as
``jvp(<scope>)`` forward and ``transpose(jvp(<scope>))`` backward. This
reduction sums the device time of the window's top-level ops (as
``bench/trace.py`` finds them and names their stacks) by the innermost
such scope's ``<dir>.<op>``, for example ``fwd.spmm`` or
``bwd.spmm_bwd_b``; the ops under no scope go to ``unscoped``. Attribution
by direction and op is exact. An op that the compiler shared between
several scheduled ops (common-subexpression elimination) carries the
scope of the first, so a finer split by width or choice would be
approximate, and is not made.
"""
from __future__ import annotations

import re
from typing import Dict

from bench import trace

SCOPE = re.compile(r"autosage\.(fwd|bwd)\.([A-Za-z0-9_]+)\.f\d+\.[A-Za-z0-9_.]*")
UNSCOPED = "unscoped"


def innermost(stack: str) -> str:
    """``<dir>.<op>`` of the innermost ``autosage.`` scope of a name
    stack, or ``unscoped``."""
    found = SCOPE.findall(stack)
    return f"{found[-1][0]}.{found[-1][1]}" if found else UNSCOPED


def reduce_planes(planes, window_name: str, names: Dict[str, str]) -> Dict[str, float]:
    """Seconds of device time in the window by innermost scope, averaged
    over the devices, as the host span ``window_name`` clips them."""
    planes = list(planes)  # a trace file's planes can be read once
    windows = [(float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
               for plane in planes if not trace.DEVICE_PLANE.match(plane.name)
               for line in plane.lines for ev in line.events if ev.name == window_name]
    if len(windows) != 1:
        raise RuntimeError(f"expected one host span {window_name!r} in the trace, "
                           f"found {len(windows)}")
    w0, w1 = windows[0]
    devices = [p for p in planes if trace.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    out: Dict[str, float] = {}
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s = max(float(ev.start_ns), w0)
                e = min(float(ev.start_ns) + float(ev.duration_ns), w1)
                if e > s:
                    events.append((s, e, ev))
        for s, e, evs in trace._top_level(events):
            key = innermost(trace._op_name(evs, names))
            out[key] = out.get(key, 0.0) + (e - s) / len(devices) / 1e9
    return out


def reduce_file(path: str, window_name: str, names: Dict[str, str]) -> Dict[str, float]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_name, names)
