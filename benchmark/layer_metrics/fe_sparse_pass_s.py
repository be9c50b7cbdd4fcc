"""Device seconds of the gather and the scatter-add of ONE value-and-gradient pass of the sparse
fixed-effect solve (ops/features.py matvec and rmatvec), mean over the passes of the traced fits;
the passes are counted by their scatters (benchmark/sparse_ops.py). The pointwise work between the
two and the solver's d-length work are not in it."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sparse_ops

    found = sparse_ops.per_pass(obs)
    return None if found is None else found["gather_s"] + found["scatter_s"]
