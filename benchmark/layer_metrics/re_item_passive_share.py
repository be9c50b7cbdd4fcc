"""Share of the rows that the per-item coordinate only scores and never trains on: rows over an
item's active cap (photon_re_rows_total of that coordinate, passive against active)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import game_spans

    return game_spans.counter_share(obs, "photon_re_rows_total", COORDINATE, "passive", "active")
