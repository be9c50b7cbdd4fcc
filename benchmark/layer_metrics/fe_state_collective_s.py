"""Device seconds a fit of the collectives of a fixed-effect solve whose coefficient-length state is
split over the chips: the all-gather of the vector before every gather and the reduce-scatter of the
scatter-add's local target after every one (the v5e's all-reduce-scatter fusion and its edge
permutes), inside ``jit__solve``, mean over the chips (benchmark/sharded_ops.py). Busy seconds of the
collective operations, overlapped with other work or not."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "collectives"
MOVES = "fit_s"


def read(obs):
    from benchmark import sharded_ops

    return sharded_ops.collective_seconds(obs)
