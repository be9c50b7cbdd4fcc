"""Padded share of the entity-block FEATURE CELLS the random-effect solver was handed: 1 - real / all of
photon_re_subspace_cells_total (real = an entity's rows x its OWN subspace; the rest is what a bucket's K_b
and S_b cost: re_slot_pad_share sees the rows alone). None on a program without the counter."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    real = fit_spans.counter_total(obs, "photon_re_subspace_cells_total", kind="real")
    padded = fit_spans.counter_total(obs, "photon_re_subspace_cells_total", kind="padded")
    if real is None or padded is None or real + padded == 0:
        return None
    return 100.0 * padded / (real + padded)
