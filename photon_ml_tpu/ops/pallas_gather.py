"""Pallas TPU gather of an ELL batch's slots from a table held in VMEM.

``FeatureMatrix.matvec`` of a wide one-device ELL batch gathers its margins
from ``w[cols]``, the table of the columns its rows hold (ops/features.py).
In ``fit-sparse`` that table is 1,712,040 columns, 6.85 MB: small enough to
sit whole in VMEM (128 MiB on a v5e), where a kernel can read any word of it
without a trip to HBM. XLA's own gather keeps it in HBM.

The v5e has no vector gather from memory (``tpu.dynamic_gather`` shuffles
within one (8, 128) vreg), so the kernel takes one slot at a time where it
must and works on whole vregs where it can. The table lies as ``[R, 128]``;
slot j's word is row j >> 7, lane j & 127. For each 128 slots of one slot
column:

1. the scalar unit reads each slot's index from SMEM and copies its table
   row, a (1, 128) load at a dynamic sublane, into row t of a (128, 128)
   scratch;
2. one lane gather per vreg picks lane j_x & 127 of every row for column x,
   and the diagonal (row x, column x) is slot x's word: a select and a sum
   over the rows.

What it costs (a stand-alone probe on a TPU v5 lite at
``fit-sparse``'s 1,179,648 rows x 12 slots, 14.16M slots, from its 6.85 MB
table): 4.07 ns a slot (57.5 ms), against 8.92 ns for XLA's take from the
same table and 18.9 ns for XLA's take from the 219 MB vector. One loop runs
over every (slot column, chunk) with only the 128 slot copies unrolled: with
the slot columns unrolled too a slot took 3.76 ns, but the kernel's code and
its compile grew with the slots a row (6 s on the chip at 12, not 2.4 s).

The kernel returns the gathered words, ``[k, n]``, not the margins: the
caller's multiply and row sum stay the XLA expression they were, so the
margins are those of the global gather bit for bit.

Mode: ``PHOTON_PALLAS`` as for the GLM kernels (``pallas_glm.mode``):
``auto`` runs it on the TPU, ``interpret`` everywhere under the interpreter
(tests), ``off`` never. A table over ``MAX_TABLE_BYTES``, rows of more
than ``MAX_SLOTS`` slots, or a table of any dtype but float32 (Mosaic has no
64-bit vectors: a float64 solve's table), take XLA's take.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_glm
from .pallas_glm import LANE

Array = jax.Array

# the VMEM a table may take: whole, single-buffered, beside a few blocks
MAX_TABLE_BYTES = 64 << 20
# the rows a grid step takes: eight chunks of 128, one (8, 128) tile of each
# slot column's indices and words
TILE = 1024
# the slots a row may have: a step's indices, double-buffered, take
# 8 KiB a slot of the 1 MiB of SMEM
MAX_SLOTS = 64
# VMEM beyond the table: the index and output blocks, double-buffered, and the
# (128, 128) scratch
_VMEM_SLACK = 8 << 20


def route(table_len: int, slots: int, dtype) -> Optional[str]:
    """How ``matvec`` gathers ``slots`` words a row from a table of
    ``table_len``: ``compiled`` or ``interpret`` (this kernel), None (XLA's
    take)."""
    mode = pallas_glm.mode()
    rows = -(-table_len // (8 * LANE)) * 8
    fits = rows * LANE * jnp.dtype(dtype).itemsize <= MAX_TABLE_BYTES and slots <= MAX_SLOTS
    if mode == "off" or not fits or jnp.dtype(dtype) != jnp.float32:
        return None
    if mode == "interpret":
        return "interpret"
    return "compiled" if jax.default_backend() == "tpu" else None


def _lane_gather(x: Array, lanes: Array) -> Array:
    """``x[r, lanes[r, c]]``: ``jnp.take_along_axis(x, lanes, axis=1)`` as the
    one lane shuffle Mosaic lowers, its indices kept int32 (under
    ``jax_enable_x64`` ``take_along_axis`` widens them, which Mosaic refuses)."""
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return jax.lax.gather(x, lanes[..., None], dims, slice_sizes=(1, 1),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _gather_kernel(idx_smem, idx_vmem, table_hbm, out_ref, table_ref, rows_ref, sem):
    k, chunks, _ = idx_vmem.shape

    @pl.when(pl.program_id(0) == 0)
    def _():
        # the table comes into VMEM once, before the first tile, and stays
        copy = pltpu.make_async_copy(table_hbm, table_ref, sem)
        copy.start()
        copy.wait()

    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1))

    def chunk(i, carry):
        # 128 slots of slot column s; the 3-D blocks keep s on a major axis
        # and the chunk on the sublanes, where Mosaic indexes at run time
        s, c = jax.lax.div(i, jnp.int32(chunks)), jax.lax.rem(i, jnp.int32(chunks))
        first = pl.multiple_of(c * LANE, LANE)
        for t in range(LANE):
            j = idx_smem[s, first + t]
            rows_ref[pl.ds(t, 1), :] = table_ref[pl.ds(j >> 7, 1), :]
        lanes = jnp.broadcast_to(idx_vmem[s, pl.ds(c, 1), :] & (LANE - 1), (LANE, LANE))
        picked = _lane_gather(rows_ref[...], lanes)
        out_ref[s, pl.ds(c, 1), :] = jnp.sum(jnp.where(diagonal, picked, 0), axis=0, keepdims=True)
        return carry

    # int32 bounds: Mosaic has no 64-bit scalars, whatever jax_enable_x64 says
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k * chunks), chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather(table: Array, idx: Array, interpret: bool = False) -> Array:
    """``table[idx.T]``, ``[k, n]``, for a ``[d_loc]`` table and ``[n, k]``
    positions in it."""
    n, k = idx.shape
    n_pad = -(-n // TILE) * TILE
    rows = -(-table.shape[0] // (8 * LANE)) * 8
    table2 = jnp.pad(table, (0, rows * LANE - table.shape[0])).reshape(rows, LANE)
    idx_t = jnp.pad(idx.T, ((0, 0), (0, n_pad - n)))
    table_bytes = rows * LANE * table.dtype.itemsize
    # the i-th tile of rows of every slot column (int32 block indices, as above)
    flat = pl.BlockSpec((k, TILE), lambda i: (jnp.int32(0), i), memory_space=pltpu.SMEM)
    tiled = pl.BlockSpec((k, TILE // LANE, LANE), lambda i: (jnp.int32(0), i, jnp.int32(0)))
    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_pad // TILE,),
        in_specs=[flat, tiled, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tiled,
        out_shape=jax.ShapeDtypeStruct((k, n_pad // LANE, LANE), table.dtype),
        scratch_shapes=[pltpu.VMEM((rows, LANE), table.dtype), pltpu.VMEM((LANE, LANE), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
        # sequential: the first tile brings the table in for every later one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=table_bytes + _VMEM_SLACK),
        interpret=interpret,
        name="ell_table_gather",
    )(idx_t, idx_t.reshape(k, n_pad // LANE, LANE), table2)
    return out.reshape(k, n_pad)[:, :n]
