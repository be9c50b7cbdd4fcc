"""Bytes and operations one sparse value-and-gradient pass needs, from its
shapes: the ELL layout of ``photon_ml_tpu/ops/features.py`` (``idx`` i32 and
``val`` f32 of ``[n, k]``) under ``ops/glm.py``'s two-pass objective, counted
here so that a later PR cannot move the yardstick.

One pass = ``matvec`` (for every slot: read its index, its value and ONE
gathered coefficient; row-sum) + the pointwise loss over the rows + ``rmatvec``
(for every slot: its index and value again, the row's multiplier, ONE scattered
update into the gradient) + the ``d``-length work the scatter stands on (the
zero-filled gradient written once, the ridge term reading ``w`` and the
gradient and writing it).

The bound this gives is the time to STREAM those bytes at the HBM peak. Random
access cannot reach it: a gathered coefficient or a scattered update moves 4
useful bytes of a 32- or 64-byte HBM transaction, so a pass whose slots hit a
219 MB table at random is bounded nearer 1/8 - 1/16 of the peak on the slot
traffic alone; the share this reader reports says how far the layout is from
streaming, not from that lower ceiling.
"""

from __future__ import annotations


def ell_value_grad_bytes(n: int, k: int, d: int, index_itemsize: int = 4, value_itemsize: int = 4,
                         scalar_itemsize: int = 4) -> int:
    """Bytes of one ELL value-and-gradient pass over ``n`` rows of ``k`` slots
    into ``d`` columns."""
    slots = n * k
    gather = slots * (index_itemsize + value_itemsize + scalar_itemsize)  # idx, val, w[idx]
    scatter = slots * (index_itemsize + value_itemsize + scalar_itemsize)  # idx, val, the update
    rows = (3 + 2 + 1) * n * scalar_itemsize  # labels/offsets/weights read; margins written and read; multiplier
    columns = (1 + 3) * d * scalar_itemsize  # zeros written; ridge: w and gradient read, gradient written
    return gather + scatter + rows + columns


def ell_value_grad_flops(n: int, k: int, d: int) -> int:
    """A multiply and an add a slot in each of the two sums, about 12 a row for
    the logistic loss and its derivative, 2 a column for the ridge term."""
    return 4 * n * k + 12 * n + 2 * d


def slot_bytes(n: int, k: int, index_itemsize: int = 4, value_itemsize: int = 4,
               scalar_itemsize: int = 4) -> int:
    """The per-slot part of :func:`ell_value_grad_bytes` (what grows with the
    rows; the ``d``-length part does not)."""
    return 2 * n * k * (index_itemsize + value_itemsize + scalar_itemsize)
