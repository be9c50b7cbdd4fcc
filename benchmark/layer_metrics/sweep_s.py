"""Median cd.sweep span."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "CD loop"
MOVES = "fit_s"


def read(obs):
    return obs.median_span_s("cd.sweep")
