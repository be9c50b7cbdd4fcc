"""Device seconds of the gather and the scatter-add of ONE value-and-gradient pass of a fixed-effect
solve whose rows and coefficient-length state are split over the chips (a chip gathers for its own
rows from the vector it all-gathered, and scatter-adds them into a local target), inside ``jit__solve``,
mean over the passes and over the chips (benchmark/sharded_ops.py). The state's collectives around the
pass are ``fe_state_collective_s``; the solver's quarter-length work is in neither."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sharded_ops

    return sharded_ops.pass_seconds(obs)
