"""fused_value_grad: least time at the HBM peak over the kernel's device time. Memory-bound: one
read of X against 819 GB/s; the flops are 0.3% of the time at the bf16 peak."""

from benchmark import shapes

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    return obs.kernel_roofline("fused_value_grad", shapes.value_grad_bytes, shapes.value_grad_flops)
