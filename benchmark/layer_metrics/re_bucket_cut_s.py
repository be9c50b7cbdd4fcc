"""Host seconds inside _bucket_operands (cut_s of the re.bucket spans: a bucket's operands cut from the
blocks), summed per fit, median over the traced fits: enqueue minus cut is the solve's dispatch."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.per_fit_attr_sum_s(obs, "re.bucket", "cut_s")
