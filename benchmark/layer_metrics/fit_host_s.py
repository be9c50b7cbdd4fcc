"""Fit wall minus the union of its cd.sweep spans, median over the traced fits: the host work
of GameEstimator.fit outside coordinate descent (validation context, fresh coordinates, per-lambda set-up)."""

import statistics

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import trace

    out = []
    for start, end in obs.fit_windows:
        sweeps = [(s.start, s.end) for s in obs.spans_named("cd.sweep") if start <= s.start and s.end <= end]
        if sweeps:
            out.append((end - start) - trace.total(trace.merge(sweeps)))
    return statistics.median(out) if out else None
