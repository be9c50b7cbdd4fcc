"""Seconds under cd.guard, summed per fit, median over the traced fits: the divergence guard's one
blocking fetch per update (in an untraced run, where the host waits for the device)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "CD loop"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "cd.guard")
