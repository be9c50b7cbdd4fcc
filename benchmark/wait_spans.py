"""The host's side of a fenced phase, for the readers that share it.

Since PR 36 every span the program fences (``Span.sync``) says how long the
host worked before the fence (``enqueue_s``: cuts, dispatch) and how long it
then waited (``wait_s``), a ``re.bucket`` how much of its enqueue was the cut
(``cut_s``), and every blocking fetch is a ``fetch`` leaf span (``site``,
``bytes``) counted in ``photon_device_fetch_seconds_total{site}``. A traced
fit's wall is then three things: the seconds the host waited at fences, the
seconds it waited in fetches, and its own work, the floor of ``fit_s`` that
no kernel lowers.

Every number is a SUM over one fit's tree, median over the traced fits, as in
``fit_spans.py``; ``coordinate`` keeps one coordinate's spans (``fit-3coord``
has two random effects). A program without the attributes (any commit before
PR 36) gives nothing here, and the readers return None.
"""

from __future__ import annotations

import statistics
from typing import List, Optional

from . import fit_spans, trace

FETCH = "fetch"


def _named(tree, name: str, coordinate: Optional[str]) -> list:
    return [
        s for s in tree
        if s.name == name and (coordinate is None or s.attrs.get("coordinate") == coordinate)
    ]


def per_fit_attr_sum_s(obs, name: str, attr: str, coordinate: Optional[str] = None) -> Optional[float]:
    """The attribute ``attr`` (seconds) of the spans called ``name``, summed
    per fit, median over the fits; None when no such span carries it."""
    sums = [
        [s.attrs[attr] for s in _named(tree, name, coordinate) if attr in s.attrs]
        for _, tree in fit_spans.fits(obs)
    ]
    if not any(sums):
        return None
    return statistics.median(sum(values) for values in sums)


def wait_intervals(tree, coordinate: Optional[str] = None) -> List[trace.Interval]:
    """Where the host stood at a fence: from ``enqueue_s`` after a fenced
    span's start, for ``wait_s`` (every span of the program fences once)."""
    return [
        (s.start + s.attrs["enqueue_s"], s.start + s.attrs["enqueue_s"] + s.attrs["wait_s"])
        for s in tree
        if "wait_s" in s.attrs  # Span.sync sets the two together
        and (coordinate is None or s.attrs.get("coordinate") == coordinate)
    ]


def _fenced_fits(obs, coordinate: Optional[str] = None) -> list:
    """(root, tree, the union of the tree's wait intervals inside the root)
    for each traced fit of a program that splits its fenced spans."""
    out = []
    for root, tree in fit_spans.fits(obs):
        waits = wait_intervals(tree, coordinate)
        if waits:
            out.append((root, tree, trace.merge(trace.clip(waits, (root.start, root.end)))))
    return out


def fence_wait_s(obs, coordinate: Optional[str] = None) -> Optional[float]:
    """Seconds of a fit the host stood at fences: the UNION of the wait
    intervals (``fe.tolerances`` lies inside ``fe.solve``: a sum would count
    a nested wait twice), median over the fits."""
    fits = _fenced_fits(obs, coordinate)
    return statistics.median(trace.total(union) for _, _, union in fits) if fits else None


def enqueue_s(obs) -> Optional[float]:
    """The ``fit`` root minus the fence waits minus the ``fetch`` spans
    outside them: the host's own work a fit, median over the fits."""
    out = []
    for root, tree, union in _fenced_fits(obs):
        fetches = trace.clip([(s.start, s.end) for s in tree if s.name == FETCH], (root.start, root.end))
        out.append(
            (root.end - root.start) - trace.total(union) - trace.total(trace.subtract(fetches, union))
        )
    return statistics.median(out) if out else None


def device_s(obs, name: str, coordinate: Optional[str] = None) -> Optional[float]:
    """The device's busy seconds inside the intervals of the spans called
    ``name`` (the device trace sits on the spans' ``perf_counter`` clock),
    mean over chips, summed per fit, median over the fits; None without a
    trace or without such spans."""
    if obs.trace is None or not obs.trace.chips:
        return None
    busy = [trace.merge((a, b) for _, a, b in events) for events in obs.trace.chips.values()]
    sums = []
    for _, tree in fit_spans.fits(obs):
        spans = _named(tree, name, coordinate)
        sums.append(
            [sum(trace.total(trace.clip(chip, (s.start, s.end))) for chip in busy) / len(busy) for s in spans]
        )
    if not any(sums):
        return None
    return statistics.median(sum(seconds) for seconds in sums)
