"""The fit root span's duration minus the union of the spans below it, median over the traced fits:
what of GameEstimator.fit is STILL unnamed."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.self_s(obs)
