"""The fit root minus the fence waits minus the fetch spans outside them, median over the traced fits:
the host's own work a fit (cuts, dispatch, Python), the floor of fit_s that no kernel lowers."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.enqueue_s(obs)
