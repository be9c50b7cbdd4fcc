"""The sparse pass's device operations, told apart by what the trace prints.

The profiler names a device operation by its whole HLO line, e.g.

    %fusion.68 = f32[28311552]{0:T(1024)} fusion(%get-tuple-element.2239, %broadcast_clamp_fusion.8), kind=kCustom, ...
    %fusion.72 = f32[54686453]{0:T(1024)} fusion(...), kind=kCustom, ...
    %gather.9 = f32[2359296,12]{...} gather(...)   /   %scatter-add.18 = f32[54686453]{...} scatter(...)

``benchmark/trace.py`` keeps the part left of `` = `` only, and there the
TPU compiler's gather and scatter are both ``fusion.<n>``. This file reads the
same ``.xplane.pb`` again and keeps the line, so that a reader can tell a
GATHER (an operation that gathers, or a custom fusion whose result has one
element a slot) from a SCATTER (an operation that scatters, a custom fusion
whose result has one element a column, or the ``sort`` of (index, update)
pairs the TPU's scatter-add runs first: 47-63 ms of a 0.3 s scatter at 28.3M
slots, my chip run, PR 34; nothing else in an L-BFGS solve sorts). Everything works on plain
``(text, start_s, end_s, module)`` tuples so it can be tested without a trace;
``load`` is the only function that touches the file. A trace whose lines read
otherwise gives no kinds, and every reader built on this returns None.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as trace_mod

OpEvent = Tuple[str, float, float, str]  # HLO line, start_s, end_s, module ("" when none encloses it)

# the modules whose operations are value-and-gradient passes of the solve: the
# solver's loop and the pass at zero that sets its tolerances
PASS_MODULES = ("jit__solve", "jit__abs_tolerances_impl")

_SHAPE = re.compile(r" = \(?[a-z]+\d*\[([\d,]*)\]")
_OPCODE = re.compile(r"\]?(?:\{[^ ]*\})?\)? ([a-z\-]+)\(")


def load(path: str) -> List[OpEvent]:
    """The first chip's operations with their whole lines and the module
    execution that encloses each, on the trace's own clock."""
    import bisect

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            continue
        span = lambda e: (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)  # noqa: E731
        ops = [(e.name, *span(e)) for ln in plane.lines if ln.name == trace_mod.OPS_LINE for e in ln.events]
        modules = sorted(
            ((e.name, *span(e)) for ln in plane.lines if ln.name == trace_mod.MODULES_LINE for e in ln.events),
            key=lambda m: m[1],
        )
        if not ops:
            continue
        starts = [m[1] for m in modules]
        out = []
        for text, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            module = trace_mod.module_name(modules[i][0]) if i >= 0 and modules[i][2] >= b else ""
            out.append((text, a, b, module))
        return out
    return []


def shifted(events: Sequence[OpEvent], offset_s: float) -> List[OpEvent]:
    return [(t, a + offset_s, b + offset_s, m) for t, a, b, m in events]


def elements(text: str) -> Optional[int]:
    """Elements of the operation's (first) result, from its line."""
    m = _SHAPE.search(text)
    if not m:
        return None
    n = 1
    for dim in m.group(1).split(","):
        if dim:
            n *= int(dim)
    return n


def opcode(text: str) -> Optional[str]:
    head = text.split(" = ", 1)
    m = _OPCODE.search(head[1]) if len(head) == 2 else None
    return m.group(1) if m else None


def kind(text: str, slots: int, dim: int) -> Optional[str]:
    """"gather", "scatter" or None for one operation's line, given the
    layout's slots and the coefficient dimension."""
    code = opcode(text)
    if code == "gather":
        return "gather"
    if code in ("scatter", "sort"):
        return "scatter"
    if code == "fusion" and "kind=kCustom" in text:
        n = elements(text)
        if n == slots and slots != dim:
            return "gather"
        if n == dim:
            return "scatter"
    return None


def pass_seconds(events: Sequence[OpEvent], window: Tuple[float, float], slots: int,
                 dim: int) -> Optional[Dict[str, float]]:
    """{gather_s, scatter_s, passes} of the solve's value-and-gradient passes
    inside ``window``: every pass scatters exactly once (``rmatvec``), so the
    scatters count the passes. None when the trace shows no scatter."""
    secs = {"gather": 0.0, "scatter": 0.0}
    passes = 0
    for text, a, b, module in events:
        if module not in PASS_MODULES or a < window[0] or b > window[1]:
            continue
        k = kind(text, slots, dim)
        if k is None:
            continue
        secs[k] += b - a
        passes += k == "scatter" and opcode(text) != "sort"
    if not passes:
        return None
    return {"gather_s": secs["gather"], "scatter_s": secs["scatter"], "passes": passes}


def per_pass(obs) -> Optional[Dict[str, float]]:
    """Device seconds of one pass's gather and scatter, from the job's kept
    lines; None on a job that kept none (or on a run without a device trace)."""
    events = getattr(obs.job, "device_ops", None)
    if not events or not obs.fit_windows:
        return None
    shape = obs.job.pass_shape  # {"slots": n*k, "dim": d, ...}
    found = pass_seconds(events, obs.traced_window, shape["slots"], shape["dim"])
    if found is None:
        return None
    n = found["passes"]
    return {"gather_s": found["gather_s"] / n, "scatter_s": found["scatter_s"] / n, "passes": n}
