"""Seconds under re.exchange (fenced), summed per fit, median over the traced fits: the residual
gather into the entity blocks' offsets."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "re.exchange")
