"""The device operations of a sparse pass and of the collectives around it, on
EVERY chip of a cell whose solver state is split over the chips
(``jobs/fit_sharded_sparse.py``).

``benchmark/sparse_ops.py`` reads the first chip's operations with their whole
HLO lines and tells a gather from a scatter by what the line prints; this file
reads the same ``.xplane.pb`` for every chip and uses its functions
(``opcode``, ``elements``, ``shifted``) for the pass, with what a split state
changes: a chip gathers for its own rows (a gather's result is a CHIP's slots,
n k / chips elements) and scatter-adds them into a local target of the whole
width the solve runs over (d_pad elements, at least d). Beside the pass it
tells the state's collectives apart:

- an ALL-GATHER: an operation whose opcode starts ``all-gather`` (the vector a
  gather reads, gathered whole from its quarters);
- a REDUCE-SCATTER: an operation whose opcode starts ``reduce-scatter``, a
  custom fusion that calls the TPU's ``all-reduce-scatter`` (what the v5e's
  compiler makes of one: compiled for a v5e 2x2, PERF.md PR 40), and the
  ``collective-permute``s that move the rows its output lays past a chip's
  edge (a few MB a pass).

Everything works on plain ``(text, start_s, end_s, module)`` tuples so it can
be tested without a trace; ``load`` is the only function that touches the file,
and it is the traced run's ONE reading of it (``jobs/fit_sharded_sparse.py``).
A trace whose lines read otherwise gives no kinds, and every reader built on
this returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import sparse_ops, trace as trace_mod
from .sparse_ops import OpEvent

SOLVE_MODULE = "jit__solve"
# the local scatter target is the solve's width, d rounded up to whole rows of
# 128 on every chip and to whole tiles: under d + this many columns
PAD_SLACK = 1 << 16


def load(path: str, mark_prefix: str = "bench.") -> Tuple[trace_mod.DeviceTrace, Dict[str, List[OpEvent]]]:
    """ONE reading of the trace file: the ``DeviceTrace`` ``trace.load`` gives
    (every chip's operations named ``<module>/<op>``, the host's marks), and
    beside it every chip's operations with their whole lines and the module
    execution that encloses each, on the trace's own clock."""
    import bisect

    from jax.profiler import ProfileData

    span = lambda e: (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)  # noqa: E731
    chips: Dict[str, list] = {}
    marks: list = []
    out: Dict[str, List[OpEvent]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            marks += [(e.name, *span(e)) for ln in plane.lines for e in ln.events if e.name.startswith(mark_prefix)]
            continue
        ops = [(e.name, *span(e)) for ln in plane.lines if ln.name == trace_mod.OPS_LINE for e in ln.events]
        modules = sorted(
            ((e.name, *span(e)) for ln in plane.lines if ln.name == trace_mod.MODULES_LINE for e in ln.events),
            key=lambda m: m[1],
        )
        if not ops:
            continue
        chips[plane.name] = trace_mod.label_ops(ops, modules)
        starts = [m[1] for m in modules]
        events = []
        for text, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            module = trace_mod.module_name(modules[i][0]) if i >= 0 and modules[i][2] >= b else ""
            events.append((text, a, b, module))
        out[plane.name] = events
    return trace_mod.DeviceTrace(chips=chips, marks=sorted(marks, key=lambda m: m[1])), out


shifted = sparse_ops.shifted


def kind(text: str, chip_slots: int, dim: int) -> Optional[str]:
    """"gather", "scatter", "all_gather", "reduce_scatter" or None for one
    operation's line, given a chip's slots and the coefficient dimension d."""
    code = sparse_ops.opcode(text) or ""
    if code.startswith("all-gather"):
        return "all_gather"
    if code.startswith("reduce-scatter") or code.startswith("collective-permute"):
        return "reduce_scatter"
    if code == "fusion" and "all-reduce-scatter" in text:
        return "reduce_scatter"
    if code == "gather":
        return "gather"
    if code in ("scatter", "sort"):
        return "scatter"
    if code == "fusion" and "kind=kCustom" in text:
        n = sparse_ops.elements(text)
        if n == chip_slots and not dim <= n < dim + PAD_SLACK:
            return "gather"
        if n is not None and dim <= n < dim + PAD_SLACK:
            return "scatter"
    return None


def chip_seconds(events: Sequence[OpEvent], window: Tuple[float, float], chip_slots: int,
                 dim: int) -> Dict[str, float]:
    """Device seconds by kind inside ``window`` of the solver's module, and
    ``passes``: every pass scatters exactly once (a scatter that is not the
    TPU's ``sort`` of its pairs)."""
    secs = {"gather": 0.0, "scatter": 0.0, "all_gather": 0.0, "reduce_scatter": 0.0}
    passes = 0
    for text, a, b, module in events:
        if module != SOLVE_MODULE or a < window[0] or b > window[1]:
            continue
        k = kind(text, chip_slots, dim)
        if k is None:
            continue
        secs[k] += b - a
        passes += k == "scatter" and sparse_ops.opcode(text) != "sort"
    secs["passes"] = passes
    return secs


def per_chip(obs) -> Optional[List[Dict[str, float]]]:
    """``chip_seconds`` of every chip whose lines show the solve's passes, over
    the traced fits; None on a job that kept no lines (or a run without a
    device trace). A chip whose lines name no pass is left out: on the v5e the
    first chip's plane read ``region.<n>`` for its operations, with no module
    around them (my chip run, PR 40), while the other three read as compiled."""
    by_chip = getattr(obs.job, "device_ops_by_chip", None)
    if not by_chip or not obs.fit_windows:
        return None
    shape = obs.job.pass_shape  # {"slots": n k over all chips, "dim": d, ...}
    chip_slots = shape["slots"] // obs.chips
    chips = [chip_seconds(events, obs.traced_window, chip_slots, shape["dim"]) for events in by_chip.values()]
    return [c for c in chips if c["passes"]] or None


def pass_seconds(obs) -> Optional[float]:
    """Gather + scatter-add seconds of one pass of the solve, mean over chips."""
    chips = per_chip(obs)
    if not chips:
        return None
    return sum((c["gather"] + c["scatter"]) / c["passes"] for c in chips) / len(chips)


def collective_seconds(obs) -> Optional[float]:
    """All-gather and reduce-scatter seconds inside the solve, a fit, mean over
    chips: each chip's seconds a pass times the passes of a fit (the most any
    chip shows), so a plane that names only part of its operations (the v5e's
    first chip named half its passes ``region.<n>``: my chip run, PR 40) does
    not pull the mean down."""
    chips = per_chip(obs)
    if not chips:
        return None
    secs = [(c["all_gather"] + c["reduce_scatter"]) / c["passes"] for c in chips]
    if not any(secs):
        return None
    return sum(secs) / len(secs) * max(c["passes"] for c in chips) / obs.n_fits


def summary(obs) -> Optional[dict]:
    """What a traced run prints under ``notes``: each chip's seconds by kind a
    fit and its passes, and the longest collective lines of a chip that reads
    as compiled (how the compiler named them)."""
    chips = per_chip(obs)
    if not chips:
        return None
    fits = max(obs.n_fits, 1)
    out = {"chips_read": len(chips), "per_chip_per_fit": [{k: v / fits for k, v in c.items()} for c in chips]}
    events = max(obs.job.device_ops_by_chip.values(),
                 key=lambda evs: sum(module == SOLVE_MODULE for *_, module in evs))
    lo, hi = obs.traced_window
    coll: Dict[str, float] = {}
    for text, a, b, module in events:
        if module == SOLVE_MODULE and lo <= a and b <= hi and trace_mod.is_collective(text):
            key = text[:160]
            coll[key] = coll.get(key, 0.0) + (b - a)
    out["collective_lines"] = sorted(([k, v / fits] for k, v in coll.items()), key=lambda kv: -kv[1])[:8]
    return out
