"""Seconds under the per-user coordinate's re.bucket spans (fenced), summed per fit, median over the
traced fits: tens of thousands of thin lanes, beside re_item_solve_s."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-user"


def read(obs):
    from benchmark import game_spans

    return game_spans.per_fit_sum_s(obs, "re.bucket", COORDINATE)
