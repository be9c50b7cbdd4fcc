"""retrace_s of the fit root span, median over the traced fits: host seconds jax spent tracing
functions again inside one fit (/jax/core/compile/jaxpr_trace_duration, fed by the program's hook)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.root_attr(obs, "retrace_s")
