"""Padded share of the entity-block row slots the per-item solves were handed
(photon_re_block_slots_total of that coordinate)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import game_spans

    return game_spans.counter_share(obs, "photon_re_block_slots_total", COORDINATE, "padded", "real")
