"""Median cd.eval span."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eval"
MOVES = "fit_s"


def read(obs):
    return obs.median_span_s("cd.eval")
