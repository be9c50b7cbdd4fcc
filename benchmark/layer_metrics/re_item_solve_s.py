"""Seconds under the per-item coordinate's re.bucket spans (fenced, one per (K, S) bucket), summed per
fit, median over the traced fits: a few hundred to two thousand fat lanes a bucket."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import game_spans

    return game_spans.per_fit_sum_s(obs, "re.bucket", COORDINATE)
