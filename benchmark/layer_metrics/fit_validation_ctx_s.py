"""Seconds under fit.validation_context, per fit, median over the traced fits: the validation suite,
the f64 COO to device batches, uploaded on every GameEstimator.fit call."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.per_fit_sum_s(obs, "fit.validation_context")
