"""Seconds under fe.score (fenced), summed per fit, median over the traced fits: the matvec X.w after
every fixed-effect update and for every warm-started combo."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fe.score")
