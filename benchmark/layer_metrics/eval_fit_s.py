"""Seconds under cd.eval, summed per fit, median over the traced fits (eval_s is the median span)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eval"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "cd.eval")
