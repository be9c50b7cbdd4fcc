"""Seconds under fe.normalization (fenced: the model's way into the standardized space before a solve
and out of it after), summed per fit, median over the traced fits."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fe.normalization")
