"""Iterations of a WARM fixed-effect solve (fe.solve spans with warm=True: the solves of sweeps 2 and on,
started from the last sweep's model under new residual offsets), mean over the traced part; fe_solver_iters is
the mean over all solves. None where no solve is warm or the span carries no count (TRON; before PR 38)."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    counts = [
        s.attrs["iterations"] for s in obs.spans_named("fe.solve", warm=True) if "iterations" in s.attrs
    ]
    return sum(counts) / len(counts) if counts else None
