"""Per-fit sums and counter shares of ONE coordinate, for the readers of a
cell with several random effects (``fit_spans.py`` sums over all of them).

Since PR 28 the program's ``fe.*`` / ``re.*`` phase spans carry the
``coordinate`` of the ``cd.coordinate`` span above them. A program without the
attribute, or a cell without the coordinate, gives nothing here, and every
reader returns None.
"""

from __future__ import annotations

import statistics
from typing import Optional

from . import fit_spans


def per_fit_sum_s(obs, name: str, coordinate: str) -> Optional[float]:
    """Seconds under the spans called ``name`` of ``coordinate``, summed per
    fit, median over the traced fits; None when no fit has such a span."""
    sums = [
        [s.end - s.start for s in tree if s.name == name and s.attrs.get("coordinate") == coordinate]
        for _, tree in fit_spans.fits(obs)
    ]
    if not any(sums):
        return None
    return statistics.median(sum(durations) for durations in sums)


def counter_share(obs, counter: str, coordinate: str, part: str, other: str) -> Optional[float]:
    """100 * part / (part + other) of the ``kind`` series of ``counter`` for
    ``coordinate``; None when the program has neither series."""
    a = fit_spans.counter_total(obs, counter, coordinate=coordinate, kind=part)
    b = fit_spans.counter_total(obs, counter, coordinate=coordinate, kind=other)
    if a is None or b is None or a + b == 0:
        return None
    return 100.0 * a / (a + b)
