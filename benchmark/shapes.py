"""Bytes and operations the fused GLM kernels need, from their shapes.

Counted from the operands of the ``pallas_call``s in
``photon_ml_tpu/ops/pallas_glm.py`` (copied here so a later PR cannot move the
yardstick): each kernel reads X exactly once.
"""

from __future__ import annotations


def value_grad_bytes(n: int, d: int, x_itemsize: int = 4, scalar_itemsize: int = 4) -> int:
    """fused_value_grad: reads X[n, d], coef[d], labels/offsets/weights[n];
    writes grad[d] and two scalars."""
    reads = n * d * x_itemsize + d * x_itemsize + 3 * n * scalar_itemsize
    writes = (d + 2) * scalar_itemsize
    return reads + writes


def hessian_vector_bytes(n: int, d: int, x_itemsize: int = 4, scalar_itemsize: int = 4) -> int:
    """fused_hessian_vector: reads X[n, d], coef[d], v[d],
    labels/offsets/weights[n], vshift; writes hv[d] and one scalar."""
    reads = n * d * x_itemsize + 2 * d * x_itemsize + (3 * n + 1) * scalar_itemsize
    writes = (d + 1) * scalar_itemsize
    return reads + writes


def value_grad_flops(n: int, d: int) -> int:
    """Two dots over X (margin, gradient accumulation): 2*n*d each."""
    return 4 * n * d


def hessian_vector_flops(n: int, d: int) -> int:
    """Three dots over X (X w, X v, X^T(c u)): 2*n*d each."""
    return 6 * n * d


def roofline_share(bytes_: int, flops: int, seconds: float, peak: dict) -> dict:
    """Least time the chip could take over the time it took, in percent, and
    which peak bounds it. ``peak`` is one entry of peaks.json."""
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flops_per_s"]
    return {
        "share": 100.0 * max(t_mem, t_flop) / seconds,
        "bound": "memory" if t_mem >= t_flop else "compute",
    }
