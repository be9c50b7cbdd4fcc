"""Seconds under the per-item coordinate's re.exchange spans (fenced), summed per fit, median over the
traced fits: the residual gather into the item blocks' offsets."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import game_spans

    return game_spans.per_fit_sum_s(obs, "re.exchange", COORDINATE)
