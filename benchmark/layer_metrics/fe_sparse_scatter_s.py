"""Device seconds of the scatter-add of one value-and-gradient pass (rmatvec: one update a slot
into the d-length gradient), mean over the traced fits' passes (benchmark/sparse_ops.py)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sparse_ops

    found = sparse_ops.per_pass(obs)
    return None if found is None else found["scatter_s"]
