"""Per-fit sums over the program's span tree, for the readers that share them.

Since PR 26 every ``GameEstimator.fit`` call opens one tree of ``obs.span``s
whose spans all carry the root's id (``attrs["root_id"]``). A traced run's
per-fit metric is a SUM over one fit's tree, median over the traced fits, so
that the rows of a breakdown add up to a fit's wall (a median per span hides
a skew that a sum per fit shows). A program without the tree (any commit
before PR 26) gives no fits here, and every reader returns None.

Counters come from the traced part's fresh registry: a total per fit is the
total over the number of traced fits.
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

ROOT = "fit"


def fits(obs) -> List[Tuple[object, list]]:
    """(root span, the other spans of its tree) for each traced fit window
    that holds a whole ``fit`` root span."""
    roots = [s for s in obs.spans if s.name == ROOT and "root_id" in s.attrs]
    out = []
    for start, end in obs.fit_windows:
        for root in roots:
            if start <= root.start and root.end <= end:
                tree = [
                    s for s in obs.spans
                    if s is not root and s.attrs.get("root_id") == root.attrs["root_id"]
                ]
                out.append((root, tree))
                break
    return out


def per_fit_sum_s(obs, *names: str) -> Optional[float]:
    """Seconds under the spans called ``names``, summed per fit, median over
    the fits; None when no fit has such a span."""
    trees = fits(obs)
    if not any(s.name in names for _, tree in trees for s in tree):
        return None
    return statistics.median(
        sum(s.end - s.start for s in tree if s.name in names) for _, tree in trees
    )


def root_attr(obs, key: str) -> Optional[float]:
    """An attribute of the ``fit`` root (absent reads 0), median over fits."""
    trees = fits(obs)
    return statistics.median(float(root.attrs.get(key, 0.0)) for root, _ in trees) if trees else None


def self_s(obs) -> Optional[float]:
    """The root's duration minus the union of its tree (its direct children
    cover their own descendants), median over fits: what no span names."""
    from . import trace

    out = []
    for root, tree in fits(obs):
        window = (root.start, root.end)
        covered = trace.total(trace.merge(trace.clip([(s.start, s.end) for s in tree], window)))
        out.append((root.end - root.start) - covered)
    return statistics.median(out) if out else None


def _series(obs, name: str, **labels) -> list:
    return [
        m for m in obs.counters
        if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items())
    ]


def counter_total(obs, name: str, **labels) -> Optional[float]:
    """Sum of the matching counter series; None when the program has none."""
    found = _series(obs, name, **labels)
    return sum(m.get("value", 0.0) for m in found) if found else None


def counter_per_fit(obs, name: str, **labels) -> Optional[float]:
    total = counter_total(obs, name, **labels)
    return total / obs.n_fits if total is not None and obs.n_fits else None


def summary_sum_per_fit(obs, name: str, **labels) -> Optional[float]:
    found = _series(obs, name, **labels)
    return sum(m["sum"] for m in found) / obs.n_fits if found and obs.n_fits else None

