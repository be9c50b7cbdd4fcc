"""Seconds of a fit the host stood at the fences of its phase spans: the UNION of the tree's wait intervals
(a nested fence counts once), median over the traced fits. Only a traced run fences."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.fence_wait_s(obs)
