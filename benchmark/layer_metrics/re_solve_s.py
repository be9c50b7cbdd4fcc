"""Seconds under the re.bucket spans (fenced, one per (K, S) bucket), summed per fit, median over the
traced fits: the bucketed per-entity solves."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "re.bucket")
