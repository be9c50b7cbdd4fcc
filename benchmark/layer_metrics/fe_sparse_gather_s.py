"""Device seconds of the gather of one value-and-gradient pass (matvec: one coefficient a slot out
of the d-length vector), mean over the traced fits' passes (benchmark/sparse_ops.py)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sparse_ops

    found = sparse_ops.per_pass(obs)
    return None if found is None else found["gather_s"]
