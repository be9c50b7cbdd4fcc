"""fused_hessian_vector: least time at the HBM peak over the kernel's device time. Memory-bound:
one read of X against 819 GB/s, with three dots' worth of work on each tile."""

from benchmark import shapes

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    return obs.kernel_roofline("fused_hessian_vector", shapes.hessian_vector_bytes, shapes.hessian_vector_flops)
