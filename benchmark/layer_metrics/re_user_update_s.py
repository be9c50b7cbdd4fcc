"""Seconds under the per-user coordinate's cd.coordinate spans, summed per fit, median over the traced
fits: its whole update (exchange, solve, collect, score, guard) beside re_item_update_s."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-user"


def read(obs):
    from benchmark import game_spans

    return game_spans.per_fit_sum_s(obs, "cd.coordinate", COORDINATE)
