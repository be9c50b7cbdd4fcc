"""jaxpr traces per fit inside the window (/jax/core/compile/jaxpr_trace_duration events):
functions the host traced again, each answered by a cache. Host cost of the program, not a compile."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    return obs.listener.retraces("window") / obs.n_fits if obs.n_fits else None
