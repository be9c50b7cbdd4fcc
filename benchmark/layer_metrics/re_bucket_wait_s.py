"""Seconds the host stood at the re.bucket spans' fences (wait_s), summed per fit, median over the traced
fits: the device time of the buckets that their own enqueue did not cover."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.per_fit_attr_sum_s(obs, "re.bucket", "wait_s")
