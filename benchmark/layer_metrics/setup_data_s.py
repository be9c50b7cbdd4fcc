"""Benchmark span around data generation and dataset build."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(obs):
    return sum(obs.setup_spans.values()) if obs.setup_spans else None
