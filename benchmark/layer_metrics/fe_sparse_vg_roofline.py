"""One sparse value-and-gradient pass: least time at the HBM peak (the pass's bytes STREAMED at
819 GB/s, benchmark/shapes_sparse.py) over the device time of its gather and scatter-add. Memory-
bound by far (a flop a byte); but a gathered coefficient or a scattered update moves 4 useful bytes
of a whole HBM transaction, so random access into a 219 MB vector cannot come near the streaming
bound: single digits here are the layout's distance from streaming, not a kernel left untuned."""

from benchmark import shapes, shapes_sparse

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sparse_ops

    found = sparse_ops.per_pass(obs)
    if found is None:
        return None
    shape = obs.job.pass_shape
    if shape["layout"] != "ell":
        return None  # the byte function is the ELL pass's
    n, k, d = shape["rows"], shape["width"], shape["dim"]
    return shapes.roofline_share(
        shapes_sparse.ell_value_grad_bytes(n, k, d), shapes_sparse.ell_value_grad_flops(n, k, d),
        found["gather_s"] + found["scatter_s"], obs.peak,
    )["share"]
