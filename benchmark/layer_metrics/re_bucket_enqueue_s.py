"""Host seconds of the re.bucket spans before their fence (enqueue_s: the eager cuts and the solve's
dispatch), summed per fit, median over the traced fits: what of re_solve_s no faster solve takes out."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.per_fit_attr_sum_s(obs, "re.bucket", "enqueue_s")
