"""Seconds under the re.warm_start spans (fenced: the solver's [E, S] state, a warm start and a prior through
the model projection and its two coordinate.project_layout fetches), summed per fit, median over the traced fits."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "re.warm_start")
