"""Pallas TPU kernels for the dense GLM hot ops: fused single-pass value+grad
and Hessian-vector.

Why a hand-written kernel when XLA already fuses elementwise ops into GEMMs:
the two-pass structure of the dense objective cannot be fused by XLA at all.
``value_and_grad`` is

    z = X @ w          (read X)
    dz = l'(z, y)      (elementwise)
    g = X^T (wt * dz)  (read X again)

— two GEMVs over the same X with a data dependency between them, so XLA
schedules two full HBM sweeps of X. At GLM shapes (n >> d, X is hundreds of
times larger than every other operand combined) that path is purely
HBM-bandwidth-bound, so those two sweeps ARE the cost. The kernels here tile
X over rows once and compute the margin dot, the pointwise loss, and the
gradient accumulation per tile while it sits in VMEM — one HBM sweep, i.e. an
asymptotic 2x on value+grad.

The Hessian-vector product wins more: the objective-level composition

    hv = X^T [ (wt * l''(X @ w)) * (X @ v) ]       (GLMObjective.hessian_vector)

costs THREE X sweeps per call (z for the curvature weights, u = X v, and the
transpose accumulation), and it is the inner-loop op of TRON's conjugate
gradient (optimize/tron.py:85). Every per-row quantity (z_i, u_i, c_i) depends
only on row i, so the fused kernel computes all three in one sweep, no
caching or solver changes needed. The Hessian-diagonal aggregates for SIMPLE
variances (s2 = (x*x)^T c, plus s1/s0 under normalization shifts) get the
same one-sweep treatment (_hd_kernel).

What the sweep costs on the chip (PR 33, a stand-alone timing on a TPU v5
lite at [1,572,864 x 1024] f32, 6.44 GB, 512-row tiles, logistic): an f32 tile
at Precision.HIGHEST is not bound by its bytes but by the number of
dot_generals it goes through, each one a split of the whole tile into bf16
parts and six MXU passes, whichever side of the dot X sits on. The read alone
takes 8.6 ms (any number of dots at DEFAULT, or one at HIGHEST; 819 GB/s
allow 7.9 ms); two dots take 10.4 ms, three took 16.6 ms (5.2-5.5 ms a dot
over the whole X, hidden under the read only while there is one). So both
kernels pass each tile through exactly TWO dots: _vg_kernel one
for the margins and one for the gradient, _hv_kernel one STACKED dot
[coef; v][2, d] . x^T -> [2, TN] for both margins and one for the
accumulation. With a dot for each margin the Hv kernel took 16.6 ms a call
(47.6% of the roofline in every cell of the benchmark); stacked it takes
10.4 ms, the time of fused_value_grad (75.8% against 76.2%;
tests/test_pallas_glm.py pins the count of dots in both bodies). A [2, d]
block needs no padding to the sublane tile: Mosaic takes it for f32 and bf16
at every width the gate admits. 512 rows is the best tile of those that fit
(256: 11.4 ms, 384: 10.7 ms, 768 and up: out of scoped VMEM). The rows of the
stacked product are not bit-equal to two M = 1 products (1.3e-7 of max|hv|).

Reference parity: these kernels compute exactly the RAW aggregates of the
reference's ValueAndGradientAggregator / HessianVectorAggregator
(photon-lib .../function/glm/ValueAndGradientAggregator.scala:137-161,
HessianVectorAggregator.scala:38-173): (sum_i wt_i l_i, X^T(wt*dz),
sum_i wt_i dz_i) and (X^T(c*u), sum_i c_i u_i). Normalization algebra
(shift/factor identities) and L2 stay in ops/glm.py on [d]-sized vectors —
they are free compared to the X sweep and keeping them outside the kernel
keeps one numerics path for every layout.

Gating (game/problem.py decides per objective): dense layout, d a multiple of
128 (the TPU lane width; no silent feature-dim padding — callers that want
the fused path align d), any row count (the last partial tile is select-
masked in-kernel). Placement: single-device batches call the kernel
directly; DATA-axis-sharded batches run it per-shard under an explicit
shard_map + psum (sharded_value_grad / sharded_hessian_vector) because a
bare pallas_call has no GSPMD partitioning rule. Model-axis-sharded dense
batches keep the jnp two-pass path. On non-TPU backends the same kernels run
under ``interpret=True`` for tests.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .losses import PointwiseLoss

Array = jax.Array

# Lane width: the feature dim must be a multiple (MXU/VPU tile constraint).
LANE = 128
# Per-tile VMEM budget for the X block (bytes); Mosaic double-buffers input
# blocks, and the f32 path's Precision.HIGHEST dots need multi-pass scratch
# proportional to the tile, so f32 runs at half the bf16 budget (a 4MB f32
# tile OOMs scoped VMEM at HIGHEST — measured).
_X_TILE_BYTES_BF16 = 4 * 1024 * 1024
_X_TILE_BYTES_F32 = 2 * 1024 * 1024
_MAX_TILE_ROWS = 2048
# row tiles are also the LANE dim of the [1, tn] label/offset/weight blocks,
# which Mosaic requires to be a multiple of 128
_MIN_TILE_ROWS = 128
# VMEM ceiling on the feature dim: the (tile, d) X block at the MINIMUM tile
# of 128 rows must fit the dtype budget (f32 additionally pays the
# Precision.HIGHEST multi-pass scratch — a 4MB f32 tile OOMs scoped VMEM).
# Both ceilings compile and run on a v5e under libtpu 0.0.34 (chip_smoke.py's
# kernels phase checks every edge this gate admits).
MAX_FUSED_DIM_F32 = 4096
MAX_FUSED_DIM_BF16 = 8192
# Below this many rows the dispatch overhead beats the saved HBM sweep.
MIN_FUSED_ROWS = 4096


def tile_rows(d: int, itemsize: int = 4, parts: int = 1) -> int:
    """Row-tile size for feature dim d at the X dtype's ``itemsize``: fill
    the dtype's VMEM budget, stay in [128, 2048], multiple of 128 (the
    [1, tn] per-row blocks use tn as their LANE dim, which Mosaic requires
    to be a multiple of 128; that also covers the f32 (8, 128) and bf16
    (16, 128) sublane constraints on the X block). ``parts`` divides the
    budget for kernels holding extra tile-sized temporaries (the
    Hessian-stats kernel materializes x*x alongside x)."""
    budget = (_X_TILE_BYTES_BF16 if itemsize == 2 else _X_TILE_BYTES_F32) // parts
    rows = budget // (itemsize * max(d, 1))
    rows = max(_MIN_TILE_ROWS, min(_MAX_TILE_ROWS, rows))
    return (rows // 128) * 128


def mode() -> str:
    """Fusion mode from PHOTON_PALLAS: 'auto' (fuse on TPU), 'off',
    'interpret' (fuse everywhere, interpreter backend — for tests)."""
    m = os.environ.get("PHOTON_PALLAS", "auto").lower()
    if m not in ("auto", "off", "interpret"):
        raise ValueError(f"PHOTON_PALLAS must be auto|off|interpret, got {m!r}")
    return m


def eligible(n_rows: int, dim: int, dtype) -> bool:
    """Shape/dtype eligibility for the fused kernels. Any row count works
    (partial last tile is masked in-kernel); n_rows only gates the
    worthwhile-at-all threshold."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.bfloat16):
        max_dim = MAX_FUSED_DIM_BF16
    elif dt == jnp.dtype(jnp.float32):
        max_dim = MAX_FUSED_DIM_F32
    else:
        return False
    return (
        dim >= LANE
        and dim % LANE == 0
        and dim <= max_dim
        and n_rows >= MIN_FUSED_ROWS
    )


def _dot_precision(x_dtype):
    """f32 X -> Precision.HIGHEST: Mosaic's DEFAULT lowers f32 dot operands
    to a SINGLE bf16 MXU pass (measured: f32 and bf16 X produced bit-identical
    results under the default — a silent drop to bf16 input precision,
    ~2.6e-3 relative gradient error), while XLA's jnp GEMV path keeps full
    f32. HIGHEST restores exact-f32 passes (measured 1.1e-6 gradient
    agreement with the jnp path). Its price goes with the dots a tile passes
    through (PR 33, the module header's timing): at DEFAULT the kernels read
    6.44 GB in 8.6 ms whatever they compute, at HIGHEST a two-dot kernel
    takes 10.4 ms (1.21x) and a three-dot one took 16.6 ms (1.93x), still
    under the two and three sweeps of the jnp path. A bf16 X keeps DEFAULT:
    bf16 is the MXU's native single-pass input type, and bf16 storage is
    the explicit opt-in fast path."""
    if x_dtype == jnp.bfloat16:
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def _load_tile(rem: int, tn: int, masked: bool, x_ref, y_ref, off_ref, wt_ref):
    """Load one row tile; with ``masked``, neutralize rows >= rem.

    The grid is cdiv(n, tn), so when tn does not divide n the LAST tile reads
    past the array — Pallas pads boundary blocks with UNSPECIFIED values
    (possibly inf/nan, which would poison the accumulating dots even at
    weight 0, since 0*nan=nan). Only that one tile takes the masked load; all
    full tiles skip the selects entirely (the split is static, see callers).
    """
    if not masked:
        return x_ref[...], y_ref[...], off_ref[...], wt_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1) < rem
    sub = jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0) < rem
    # typed zeros: a python 0.0 would silently promote a bf16 x tile to f32
    x = jnp.where(sub, x_ref[...], jnp.zeros((), x_ref.dtype))  # [TN, d]
    y = jnp.where(lane, y_ref[...], jnp.zeros((), y_ref.dtype))  # [1, TN]
    off = jnp.where(lane, off_ref[...], jnp.zeros((), off_ref.dtype))
    wt = jnp.where(lane, wt_ref[...], jnp.zeros((), wt_ref.dtype))
    return x, y, off, wt


def _vg_kernel(loss: PointwiseLoss, n: int, tn: int, x_ref, coef_ref, y_ref,
               off_ref, wt_ref, loss_ref, grad_ref, wdz_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        loss_ref[...] = jnp.zeros_like(loss_ref)
        grad_ref[...] = jnp.zeros_like(grad_ref)
        wdz_ref[...] = jnp.zeros_like(wdz_ref)

    def accumulate(masked):
        x, y, off, wt = _load_tile(n % tn, tn, masked, x_ref, y_ref, off_ref, wt_ref)
        # z^T = coef[1,d] . x^T -> [1, TN]: margins for this row tile
        prec = _dot_precision(x.dtype)
        z = jax.lax.dot_general(
            coef_ref[...], x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) + off
        l, dz = loss.loss_and_dz(z, y)
        wdz = wt * dz  # [1, TN] f32
        loss_ref[...] += jnp.sum(wt * l).reshape(1, 1)
        wdz_ref[...] += jnp.sum(wdz).reshape(1, 1)
        # grad += wdz[1,TN] . x[TN,d] -> [1, d]; on a bf16 X the per-sample
        # weighted dz rounds to bf16 too (MXU-native bf16xbf16->f32), the
        # accumulation stays f32
        grad_ref[...] += jax.lax.dot_general(
            wdz.astype(x.dtype), x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    if n % tn == 0:
        accumulate(False)
    else:
        last = pl.cdiv(n, tn) - 1
        pl.when(i < last)(lambda: accumulate(False))
        pl.when(i == last)(lambda: accumulate(True))


def _hv_kernel(loss: PointwiseLoss, n: int, tn: int, x_ref, cv_ref, y_ref,
               off_ref, wt_ref, vshift_ref, hv_ref, csum_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        hv_ref[...] = jnp.zeros_like(hv_ref)
        csum_ref[...] = jnp.zeros_like(csum_ref)

    def accumulate(masked):
        x, y, off, wt = _load_tile(n % tn, tn, masked, x_ref, y_ref, off_ref, wt_ref)
        prec = _dot_precision(x.dtype)
        # [coef; v][2,d] . x^T -> [2, TN]: both margins of this row tile from
        # ONE pass of the tile through the MXU (see the module header)
        zu = jax.lax.dot_general(
            cv_ref[...], x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        z = zu[0:1] + off
        u = zu[1:2] + vshift_ref[...]
        cu = wt * loss.d2z(z, y) * u  # [1, TN] f32
        csum_ref[...] += jnp.sum(cu).reshape(1, 1)
        hv_ref[...] += jax.lax.dot_general(
            cu.astype(x.dtype), x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    if n % tn == 0:
        accumulate(False)
    else:
        last = pl.cdiv(n, tn) - 1
        pl.when(i < last)(lambda: accumulate(False))
        pl.when(i == last)(lambda: accumulate(True))


def _hd_kernel(loss: PointwiseLoss, n: int, tn: int, need_shifts: bool,
               x_ref, coef_ref, y_ref, off_ref, wt_ref, s2_ref, *shift_refs):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        s2_ref[...] = jnp.zeros_like(s2_ref)
        for r in shift_refs:
            r[...] = jnp.zeros_like(r)

    def accumulate(masked):
        x, y, off, wt = _load_tile(n % tn, tn, masked, x_ref, y_ref, off_ref, wt_ref)
        prec = _dot_precision(x.dtype)
        z = jax.lax.dot_general(
            coef_ref[...], x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) + off
        c = wt * loss.d2z(z, y)  # [1, TN] f32
        cx = c.astype(x.dtype)
        # s2 += c . (x*x): square in-register, same single HBM sweep
        s2_ref[...] += jax.lax.dot_general(
            cx, x * x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        if need_shifts:  # static: unnormalized models skip the s1 dot
            s1_ref, s0_ref = shift_refs
            s1_ref[...] += jax.lax.dot_general(
                cx, x, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            s0_ref[...] += jnp.sum(c).reshape(1, 1)

    if n % tn == 0:
        accumulate(False)
    else:
        last = pl.cdiv(n, tn) - 1
        pl.when(i < last)(lambda: accumulate(False))
        pl.when(i == last)(lambda: accumulate(True))


def _row_specs(tn: int, d: int):
    """(x, coef-like [1,d]..., per-row [1,n]...) block specs for a row grid."""
    x_spec = pl.BlockSpec((tn, d), lambda i: (i, 0))
    d_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    n_spec = pl.BlockSpec((1, tn), lambda i: (0, i))
    out_d = pl.BlockSpec((1, d), lambda i: (0, 0))
    out_s = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return x_spec, d_spec, n_spec, out_d, out_s


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def fused_value_grad(
    x: Array,
    eff_coef: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
) -> Tuple[Array, Array, Array]:
    """One-sweep (sum_i wt_i l_i, X^T(wt*dz), sum_i wt_i dz_i) over dense X.

    ``offsets`` must already include the normalization margin shift. Any row
    count works: the last (partial) tile is select-masked in-kernel. A bf16
    X runs the MXU-native bf16xbf16->f32 path (coefficients round to bf16 at
    the dot inputs; every accumulator and output stays f32).
    """
    n, d = x.shape
    tn = tile_rows(d, jnp.dtype(x.dtype).itemsize)
    out_dt = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    x_spec, d_spec, n_spec, out_d, out_s = _row_specs(tn, d)
    loss_sum, grad, wdz_sum = pl.pallas_call(
        functools.partial(_vg_kernel, loss, n, tn),
        grid=(pl.cdiv(n, tn),),
        in_specs=[x_spec, d_spec, n_spec, n_spec, n_spec],
        out_specs=[out_s, out_d, out_s],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), out_dt),
            jax.ShapeDtypeStruct((1, d), out_dt),
            jax.ShapeDtypeStruct((1, 1), out_dt),
        ],
        interpret=interpret,
    )(
        x,
        eff_coef.astype(x.dtype).reshape(1, d),
        labels.astype(out_dt).reshape(1, n),
        offsets.astype(out_dt).reshape(1, n),
        weights.astype(out_dt).reshape(1, n),
    )
    return loss_sum[0, 0], grad[0], wdz_sum[0, 0]


def _shard_psum_call(mesh, inner, rep_mask, n_out, args):
    """Shared shell of the sharded_* wrappers: run ``inner`` per data shard
    under shard_map and psum each of its ``n_out`` outputs over the data axis
    (pallas_call has no GSPMD partitioning rule, so collective placement is
    explicit). ``rep_mask[i]`` marks argument i replicated; non-replicated
    args are row-sharded (arg 0 is the 2-D X, the rest are [n] vectors)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS  # lazy: parallel imports ops

    def g(*a):
        return tuple(jax.lax.psum(o, DATA_AXIS) for o in inner(*a))

    in_specs = tuple(
        P() if rep else (P(DATA_AXIS, None) if i == 0 else P(DATA_AXIS))
        for i, rep in enumerate(rep_mask)
    )
    return shard_map(
        g,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(),) * n_out,
        # pallas_call cannot annotate vma on its out_shape structs
        check_vma=False,
    )(*args)


def sharded_value_grad(
    mesh,
    x: Array,
    eff_coef: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
) -> Tuple[Array, Array, Array]:
    """fused_value_grad over a DATA-axis-sharded batch: each device sweeps its
    own row shard with the Pallas kernel, the three raw aggregates psum over
    the data axis (the reference's treeAggregate, SURVEY.md P1).
    mesh=None delegates to the single-device kernel, so callers keep ONE call
    site for both placements."""
    if mesh is None:
        return fused_value_grad(
            x, eff_coef, labels, offsets, weights, loss, interpret=interpret
        )

    def inner(x_l, eff_l, y_l, off_l, wt_l):
        return fused_value_grad(x_l, eff_l, y_l, off_l, wt_l, loss, interpret=interpret)

    return _shard_psum_call(
        mesh, inner, (False, True, False, False, False), 3,
        (x, eff_coef, labels, offsets, weights),
    )


def sharded_hessian_vector(
    mesh,
    x: Array,
    eff_coef: Array,
    eff_v: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    vshift: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """fused_hessian_vector over a DATA-axis-sharded batch (see
    sharded_value_grad). mesh=None delegates to the single-device kernel."""
    if mesh is None:
        return fused_hessian_vector(
            x, eff_coef, eff_v, labels, offsets, weights, vshift, loss,
            interpret=interpret,
        )

    def inner(x_l, eff_l, v_l, y_l, off_l, wt_l, vs_l):
        return fused_hessian_vector(
            x_l, eff_l, v_l, y_l, off_l, wt_l, vs_l, loss, interpret=interpret
        )

    return _shard_psum_call(
        mesh, inner, (False, True, True, False, False, False, True), 2,
        (x, eff_coef, eff_v, labels, offsets, weights,
         jnp.asarray(vshift, jnp.float32)),
    )


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def fused_hessian_vector(
    x: Array,
    eff_coef: Array,
    eff_v: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    vshift: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """One-sweep (X^T(c*u), sum_i c_i u_i) with c = wt*l''(z), u = X v + vshift.

    Replaces the three-sweep composition in GLMObjective.hessian_vector for
    dense X — the TRON CG inner-loop op.
    """
    n, d = x.shape
    tn = tile_rows(d, jnp.dtype(x.dtype).itemsize)
    out_dt = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    x_spec, _, n_spec, out_d, out_s = _row_specs(tn, d)
    hv, csum = pl.pallas_call(
        functools.partial(_hv_kernel, loss, n, tn),
        grid=(pl.cdiv(n, tn),),
        in_specs=[
            x_spec, pl.BlockSpec((2, d), lambda i: (0, 0)),
            n_spec, n_spec, n_spec, out_s,
        ],
        out_specs=[out_d, out_s],
        out_shape=[
            jax.ShapeDtypeStruct((1, d), out_dt),
            jax.ShapeDtypeStruct((1, 1), out_dt),
        ],
        interpret=interpret,
    )(
        x,
        jnp.stack([eff_coef, eff_v]).astype(x.dtype),
        labels.astype(out_dt).reshape(1, n),
        offsets.astype(out_dt).reshape(1, n),
        weights.astype(out_dt).reshape(1, n),
        jnp.asarray(vshift, out_dt).reshape(1, 1),
    )
    return hv[0], csum[0, 0]


@functools.partial(jax.jit, static_argnames=("loss", "interpret", "need_shifts"))
def fused_hessian_stats(
    x: Array,
    eff_coef: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
    need_shifts: bool = False,
) -> Tuple[Array, Array, Array]:
    """One-sweep Hessian-diagonal aggregates with c = wt*l''(z):

        s2 = (x*x)^T c,   and with ``need_shifts``: s1 = x^T c, s0 = sum c

    — everything GLMObjective.hessian_diagonal needs (s1/s0 only under
    normalization shifts; without them the extra dot is skipped statically),
    replacing up to three X sweeps (z, sq_rmatvec, rmatvec) with one.
    ``offsets`` must already include the margin shift. Returns
    (s2, s1-or-None, s0-or-None). The tile budget is halved (parts=2): the
    kernel holds an x*x temporary alongside the x tile.
    """
    n, d = x.shape
    tn = tile_rows(d, jnp.dtype(x.dtype).itemsize, parts=2)
    out_dt = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    x_spec, d_spec, n_spec, out_d, out_s = _row_specs(tn, d)
    out_specs = [out_d] + ([out_d, out_s] if need_shifts else [])
    out_shape = [jax.ShapeDtypeStruct((1, d), out_dt)] + (
        [jax.ShapeDtypeStruct((1, d), out_dt), jax.ShapeDtypeStruct((1, 1), out_dt)]
        if need_shifts
        else []
    )
    outs = pl.pallas_call(
        functools.partial(_hd_kernel, loss, n, tn, need_shifts),
        grid=(pl.cdiv(n, tn),),
        in_specs=[x_spec, d_spec, n_spec, n_spec, n_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(
        x,
        eff_coef.astype(x.dtype).reshape(1, d),
        labels.astype(out_dt).reshape(1, n),
        offsets.astype(out_dt).reshape(1, n),
        weights.astype(out_dt).reshape(1, n),
    )
    if need_shifts:
        s2, s1, s0 = outs
        return s2[0], s1[0], s0[0, 0]
    return outs[0][0], None, None


def sharded_hessian_stats(
    mesh,
    x: Array,
    eff_coef: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    loss: PointwiseLoss,
    interpret: bool = False,
    need_shifts: bool = False,
) -> Tuple[Array, Array, Array]:
    """fused_hessian_stats over a DATA-axis-sharded batch (see
    sharded_value_grad). mesh=None delegates to the single-device kernel."""
    if mesh is None:
        return fused_hessian_stats(
            x, eff_coef, labels, offsets, weights, loss,
            interpret=interpret, need_shifts=need_shifts,
        )

    def inner(x_l, eff_l, y_l, off_l, wt_l):
        outs = fused_hessian_stats(
            x_l, eff_l, y_l, off_l, wt_l, loss,
            interpret=interpret, need_shifts=need_shifts,
        )
        return tuple(o for o in outs if o is not None)

    n_out = 3 if need_shifts else 1
    outs = _shard_psum_call(
        mesh, inner, (False, True, False, False, False), n_out,
        (x, eff_coef, labels, offsets, weights),
    )
    return outs + (None,) * (3 - n_out)
