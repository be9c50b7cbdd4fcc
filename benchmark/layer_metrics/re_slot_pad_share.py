"""Padded share of the entity-block row slots the random-effect solver was handed, from the program's
own photon_re_block_slots_total (re_pad_share rebuilds it from private helpers)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    real = fit_spans.counter_total(obs, "photon_re_block_slots_total", kind="real")
    padded = fit_spans.counter_total(obs, "photon_re_block_slots_total", kind="padded")
    if real is None or padded is None or real + padded == 0:
        return None
    return 100.0 * padded / (real + padded)
