"""Reduction of a jax profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU trace has one
plane per chip (``/device:TPU:<i>``) whose ``XLA Ops`` line holds one event per
executed HLO operation (start, duration, in nanoseconds; a ``while`` holds its
body's events nested inside its own), and host planes whose lines are threads;
``jax.profiler.TraceAnnotation`` events land on the thread that opened them.

All reductions work on plain ``(name, start_s, end_s)`` tuples so they can be
tested without a trace; ``load`` is the only function that touches the file.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start_s, end_s

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute")
# ops that only contain other ops: their time is their children's
CONTAINER_OPS = ("while", "conditional", "call")


@dataclasses.dataclass
class DeviceTrace:
    chips: Dict[str, List[Event]]  # device plane name -> op events
    marks: List[Event]  # host TraceAnnotation events whose name starts "bench."

    def shifted(self, offset_s: float) -> "DeviceTrace":
        """The same trace on another clock (trace time + offset)."""
        move = lambda evs: [(n, a + offset_s, b + offset_s) for n, a, b in evs]  # noqa: E731
        return DeviceTrace({k: move(v) for k, v in self.chips.items()}, move(self.marks))


MODULES_LINE = "XLA Modules"


def op_name(text: str) -> str:
    """``%fused_value_grad.8 = (f32[1,1]...) custom-call(...)`` -> ``fused_value_grad.8``
    (the trace names an operation by its whole HLO line)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text: str) -> str:
    """``jit__solve(3517158820482080362)`` -> ``jit__solve``."""
    return text.split("(", 1)[0]


def label_ops(ops: Sequence[Event], modules: Sequence[Event]) -> List[Event]:
    """Ops renamed ``<module>/<op>`` by the module execution that encloses
    them in time (``<op>`` alone when none does)."""
    import bisect

    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for text, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        name = op_name(text)
        if i >= 0 and modules[i][2] >= b:
            name = module_name(modules[i][0]) + "/" + name
        out.append((name, a, b))
    return out


def load(path: str, mark_prefix: str = "bench.") -> DeviceTrace:
    from jax.profiler import ProfileData

    def events(line) -> List[Event]:
        return [
            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events
        ]

    data = ProfileData.from_file(path)
    chips: Dict[str, List[Event]] = {}
    marks: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [e for ln in plane.lines if ln.name == OPS_LINE for e in events(ln)]
            modules = [e for ln in plane.lines if ln.name == MODULES_LINE for e in events(ln)]
            if ops:
                chips[plane.name] = label_ops(ops, modules)
        else:
            for ln in plane.lines:
                marks.extend(e for e in events(ln) if e[0].startswith(mark_prefix))
    return DeviceTrace(chips=chips, marks=sorted(marks, key=lambda m: m[1]))


# -- interval arithmetic -------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]) -> List[Interval]:
    """Parts of (merged) ``intervals`` not covered by (merged) ``holes``."""
    out: List[Interval] = []
    holes = merge(holes)
    for a, b in merge(intervals):
        cur = a
        for ha, hb in holes:
            if hb <= cur:
                continue
            if ha >= b:
                break
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
        if cur < b:
            out.append((cur, b))
    return out


# -- reductions ------------------------------------------------------------------


def is_container(name: str) -> bool:
    return name.split("/")[-1].split(".")[0] in CONTAINER_OPS


def is_collective(name: str) -> bool:
    return any(mark in name for mark in COLLECTIVE_MARKS)


def busy_seconds(events: Sequence[Event], window: Interval) -> float:
    """Seconds of ``window`` in which some operation ran on this chip."""
    return total(clip(merge((a, b) for _, a, b in events), window))


def mean_busy_seconds(trace: DeviceTrace, window: Interval) -> float:
    """Busy seconds averaged over the chips of the trace."""
    per_chip = [busy_seconds(evs, window) for evs in trace.chips.values()]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def idle_share(trace: DeviceTrace, window: Interval) -> float:
    return 1.0 - mean_busy_seconds(trace, window) / (window[1] - window[0])


def kernel_seconds(trace: DeviceTrace, name_part: str, window: Interval) -> Tuple[float, int]:
    """(seconds, calls) of the events whose name contains ``name_part``, inside
    the window, averaged over chips (every chip runs its shard of each call)."""
    secs, calls = [], []
    for evs in trace.chips.values():
        hit = [(a, b) for n, a, b in evs if name_part in n and a >= window[0] and b <= window[1]]
        secs.append(total(hit))
        calls.append(len(hit))
    if not secs:
        return 0.0, 0
    return sum(secs) / len(secs), round(sum(calls) / len(calls))


def top_ops(trace: DeviceTrace, window: Interval, k: int = 10) -> List[List[object]]:
    """The k operations with most device time (leaf operations only: a
    ``while`` would count its body twice), averaged over chips."""
    acc: Dict[str, float] = {}
    for evs in trace.chips.values():
        for n, a, b in evs:
            if a >= window[0] and b <= window[1] and not is_container(n):
                acc[n] = acc.get(n, 0.0) + (b - a)
    chips = max(len(trace.chips), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s / chips] for n, s in ranked]


def idle_gaps_by_span(
    trace: DeviceTrace, window: Interval, spans: Sequence[Event], k: int = 10,
    outside: str = "fit_host",
) -> List[List[object]]:
    """Idle seconds of the first chip, split by what the host was doing: each
    idle interval is cut at span borders and each piece goes to the INNERMOST
    (shortest) span covering it; pieces under no span go to ``outside``."""
    if not trace.chips:
        return []
    first = next(iter(trace.chips.values()))
    idle = subtract([window], [(a, b) for _, a, b in first])
    acc: Dict[str, float] = {}
    by_length = sorted(spans, key=lambda s: s[2] - s[1])
    for gap in idle:
        rest = [gap]
        for name, a, b in by_length:
            inside = clip(rest, (a, b))
            if inside:
                acc[name] = acc.get(name, 0.0) + total(inside)
                rest = subtract(rest, [(a, b)])
            if not rest:
                break
        if rest:
            acc[outside] = acc.get(outside, 0.0) + total(rest)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s] for n, s in ranked]


def collective_exposed_seconds(trace: DeviceTrace, window: Interval) -> float:
    """Collective time during which no other operation runs on that chip,
    averaged over chips."""
    per_chip = []
    for evs in trace.chips.values():
        coll = clip([(a, b) for n, a, b in evs if is_collective(n)], window)
        other = [(a, b) for n, a, b in evs if not is_collective(n) and not is_container(n)]
        per_chip.append(total(subtract(coll, other)))
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def clock_offset(trace: DeviceTrace, mark_name: str, perf_starts: Sequence[float]) -> Optional[float]:
    """perf_counter minus trace time, from marks the harness opened at known
    perf_counter instants (median over the marks)."""
    import statistics

    starts = [a for n, a, _ in trace.marks if n == mark_name]
    if not starts or len(starts) != len(perf_starts):
        return None
    return statistics.median(p - t for p, t in zip(perf_starts, starts))
