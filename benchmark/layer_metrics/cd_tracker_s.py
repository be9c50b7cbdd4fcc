"""Seconds under cd.tracker, summed per fit, median over the traced fits: record_tracker_metrics and
its fetches, which exist only with a sink attached - the sink's own cost inside the sweep."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "CD loop"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "cd.tracker")
