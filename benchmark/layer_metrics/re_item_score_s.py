"""Seconds under the per-item coordinate's re.score spans (fenced), summed per fit, median over the
traced fits: every row's score, the passive rows' included."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import game_spans

    return game_spans.per_fit_sum_s(obs, "re.score", COORDINATE)
