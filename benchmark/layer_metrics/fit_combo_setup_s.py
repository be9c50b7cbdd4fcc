"""Seconds under fit.make_coordinates and cd.init, summed per fit, median over the traced fits: the
set-up every reg-weight combo pays before its first sweep (fresh coordinates, warm-start scores)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fit.make_coordinates", "cd.init")
