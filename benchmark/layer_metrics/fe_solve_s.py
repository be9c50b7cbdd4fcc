"""Seconds under fe.solve (fenced; fe.tolerances included), summed per fit, median over the traced fits."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fe.solve")
