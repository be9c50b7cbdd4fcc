"""Seconds under fe.tolerances (fenced), summed per fit, median over the traced fits: the objective
pass at zero coefficients that turns relative tolerances into absolute ones before every solve."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fe.tolerances")
