"""Columnar batch containers for GLM training data.

A sample's journey (SURVEY.md §7.1): raw row -> (sparse features, label,
offset, weight). On TPU the batch is a struct-of-arrays in one of two layouts:

- ``dense``: ``x[n, d]`` — margins are a single MXU matmul. Right layout for
  small/medium d and for per-entity projected subspace blocks.
- ``ELL (padded sparse)``: ``idx[n, k] i32`` + ``val[n, k] f32`` with per-row
  padding (idx=0, val=0). Margins are a gather + row-sum; gradient
  accumulation is a scatter-add (segment sum). Right layout for wide, sparse
  feature spaces where densification is impossible.

(A column-sharded solve builds its own tiles from the triplets:
parallel/sparse.py.)

What a sparse pass costs, measured on a v5e at twice the rows of the
benchmark's sparse cell (2,359,296 rows x 12 one-hot slots = 28.3M slots into
d = 54,686,453, f32, the cell's column law; my
chip run, PR 34, a stand-alone probe of one value-and-gradient pass):

    layout   pass     gather (matvec)            scatter-add (rmatvec)
    ELL      0.80 s   0.47 s = 16.8 ns a slot    0.32 s = 0.25 s + a 0.06 s index sort
    COO      1.52 s   1.02 s (0.80 s gather of w by column + 0.22 s
                      UNSORTED scatter into rows)  0.49 s (0.24 s gather + 0.25 s)

So a slot costs 16-19 ns to gather from a 219 MB vector and 9-11 ns to
scatter into one (about 25 cycles the pair at 940 MHz, not the 7 cycles an
element an older note here gave): 0.3% of what streaming the same bytes at the
HBM peak would take. ``indices_are_sorted`` buys nothing at this width: the
scatter into columns takes 0.248 s sorted (COO) or unsorted (ELL, whose
scatter sorts its (index, update) pairs first, 0.05-0.06 s), and COO pays a
second gather and an unsorted scatter for its margins. ``auto`` therefore stays
ELL for every d above the dense limit. The older conclusion stands: one chip's
sparse throughput is bound by serialized random access (no HBM cache, no
vectorized VMEM gather before SparseCore), and the design answer is to
*divide* that cost across devices by (data x model) tiling, not to chase a
magic kernel for the whole vector: tpu.dynamic_gather only shuffles within
one (8, 128) vreg, and a 219 MB table lives in HBM. The sorted COO layout,
measured 1.8-1.9x slower than ELL above, has been removed.

The margins need not read the whole vector, though: the rows of one chip hold
few of its columns. A one-device ELL batch at least ``LOCAL_MAP_MIN_DIM`` wide
keeps a local column map (``cols``, the sorted held columns; ``idx_local``,
each slot's position among them), and ``matvec`` gathers ``w[cols]`` once and
the slots from that table. ``fit-sparse``'s rows hold 1,712,040 of the 54.7M
columns, a 6.85 MB table (2.9M, 11.6 MB, at the whole one-chip share of
2,359,296 rows). What a slot costs from each (a stand-alone probe on a TPU
v5 lite at the cell's 14.16M slots, every variant's margins bit for bit the
global gather's):

    XLA take from the 219 MB vector, with the multiply and row sum   18.9 ns  268.1 ms
    w[cols] (1.71M sorted unique columns)                              --      31.2 ms
    XLA take from the 6.85 MB table                                   8.9 ns  126.2 ms
    the Pallas kernel, the table in VMEM (ops/pallas_gather.py)       4.1 ns   57.5 ms
    the whole matvec through the kernel                               5.9 ns   84.0 ms

So a slot's price does fall with the table, and further in VMEM, which a
6.85 MB table fits. The scatter-adds (``rmatvec``, ``sq_rmatvec``,
``rmatmat``) and ``to_dense`` keep the global ``idx``.

The ELL sums are written over ``[k, n]`` views (``idx.T``, ``val.T``: the row
axis minor, which is how the TPU lays a tall ``[n, k]`` array out anyway). Over
``[n, k]`` intermediates the compiler padded k = 12 to 128 lanes, 1.2 GB a
temporary and three alive at once inside the L-BFGS loop: 15.2 GB for a solve
that needs 10.7 (tests/test_tpu_compile.py), on a 16 GB chip. What it costs
(my chip runs, PR 34): the gather reads its indices slot-major and is 2.6%
slower a pass in the benchmark's cell (0.2216 s against 0.2159 at 14.2M
slots), a whole fit +0.5% at 1.18M rows and -2.1% at 2.36M.

Zero-valued padding entries contribute nothing to margins or gradients, so no
separate mask is needed; padded *rows* carry weight 0.

This replaces the reference's per-datum axpy hot loop
(ValueAndGradientAggregator.scala:137-161) with batched XLA ops.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Array = jax.Array

# An ELL batch built for one device at least this wide carries a local column
# map (``cols``, ``idx_local``): its margins gather from the held columns'
# table, not from the whole coefficient vector (module docstring).
LOCAL_MAP_MIN_DIM = 1 << 20

# ELL index planes (``idx``, ``idx_local``, ``cols``) are int32
_INDEX_ITEMSIZE = int(np.dtype(np.int32).itemsize)


def carries_local_map(dim: int, one_device: bool) -> bool:
    """Whether ``batch_from_coo`` gives an ELL batch of this width its local
    column map."""
    return one_device and dim >= LOCAL_MAP_MIN_DIM


def feature_bytes(
    n_rows: int, dim: int, layout: str, width: int = 0, itemsize: int = 4,
    one_device: bool = False,
) -> int:
    """Device bytes of the feature matrix ``batch_from_dense`` (``dense``) or
    ``batch_from_coo`` (``ell``, ``width`` slots a row) builds for ``n_rows``
    rows, its values ``itemsize`` bytes each. An ELL batch that carries its
    local column map also holds ``idx_local``, one more index a slot, and
    ``cols``, at most one index a slot or a column."""
    if layout == "dense":
        return n_rows * dim * itemsize
    if layout != "ell":
        raise ValueError(f"feature_bytes: layout must be dense|ell, got {layout!r}")
    slots = n_rows * width
    nbytes = slots * (itemsize + _INDEX_ITEMSIZE)
    if carries_local_map(dim, one_device):
        nbytes += (slots + min(slots, dim)) * _INDEX_ITEMSIZE
    return nbytes


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FeatureMatrix:
    """A batch of feature vectors: dense ``[n, d]`` or padded-sparse (ELL).

    Exactly one of ``dense`` / (``idx``, ``val``) is set. ``dim`` is the
    feature-space dimension d (static so jitted shapes are known).

    An ELL matrix may carry a local column map: ``cols`` (the sorted unique
    columns its slots hold, padding included) and ``idx_local`` (each slot's
    position in ``cols``). ``matvec`` then gathers the margins from
    ``w[cols]``; every other operation reads the global ``idx``.
    """

    dim: int = dataclasses.field(metadata=dict(static=True))
    dense: Optional[Array] = None
    idx: Optional[Array] = None
    val: Optional[Array] = None
    cols: Optional[Array] = None  # i32[d_loc], sorted unique columns of idx
    idx_local: Optional[Array] = None  # i32[n, k]: idx == cols[idx_local]

    def __post_init__(self):
        if (self.dense is None) == (self.idx is None):
            raise ValueError("exactly one of dense / (idx, val) must be provided")
        if self.idx is not None and self.val is None:
            raise ValueError("ELL layout requires both idx and val")
        if (self.cols is None) != (self.idx_local is None):
            raise ValueError("a local column map needs both cols and idx_local")
        if self.cols is not None and self.idx is None:
            raise ValueError("a local column map belongs to the ELL layout")

    @property
    def layout(self) -> str:
        return "dense" if self.dense is not None else "ell"

    @property
    def is_dense(self) -> bool:
        return self.dense is not None

    @property
    def n_rows(self) -> int:
        return (self.dense if self.dense is not None else self.idx).shape[0]

    @property
    def slots(self) -> int:
        """Entries the layout stores, padding included: what one pass over the
        matrix touches (n*d dense, n*k ELL). Host-known from shapes."""
        n, width = (self.dense if self.dense is not None else self.idx).shape
        return n * width

    @property
    def gather(self) -> str:
        """Where ``matvec`` reads coefficients from: ``local`` (the table of
        the held columns) or ``global`` (the whole vector). Host-known."""
        return "global" if self.cols is None else "local"

    @property
    def gather_columns(self) -> int:
        """The length of the vector ``matvec`` gathers from. Host-known."""
        return self.dim if self.cols is None else self.cols.shape[0]

    def matvec(self, w: Array) -> Array:
        """x @ w -> [n]."""
        if self.dense is not None:
            return self.dense @ w
        if self.cols is None:
            return jnp.sum(self.val.T * jnp.take(w, self.idx.T, axis=0), axis=0)
        # Pallas loads with the first batch that has a map, as the GLM
        # kernels load with their first fused call (ops/glm.py)
        from . import pallas_gather

        table = jnp.take(w, self.cols, axis=0, indices_are_sorted=True, unique_indices=True)
        how = pallas_gather.route(table.shape[0], self.idx_local.shape[1], table.dtype)
        if how is None:
            words = jnp.take(table, self.idx_local.T, axis=0)
        else:
            words = pallas_gather.gather(table, self.idx_local, interpret=how == "interpret")
        return jnp.sum(self.val.T * words, axis=0)

    def matmat(self, w: Array) -> Array:
        """x @ w -> [n, L] for lane-stacked coefficients w[d, L].

        The lambda-lane axis of batched hyperparameter sweeps: all L lanes
        share this one feature residency and one fused kernel instead of L
        separate matvec dispatches."""
        if self.dense is not None:
            return self.dense @ w
        # take -> [n, k, L]; ELL values broadcast over the lane axis
        return jnp.sum(self.val[:, :, None] * jnp.take(w, self.idx, axis=0), axis=1)

    def rmatvec(self, c: Array) -> Array:
        """x^T @ c -> [d]: the gradient-accumulation kernel."""
        if self.dense is not None:
            return self.dense.T @ c
        contrib = c[None, :] * self.val.T
        return jnp.zeros(self.dim, dtype=contrib.dtype).at[
            self.idx.T.reshape(-1)
        ].add(contrib.reshape(-1))

    def matvec_gathered(self, w: Array, sharding) -> Array:
        """``matvec`` of an ELL matrix whose rows are sharded over the axis
        ``sharding`` (a ``NamedSharding`` of a ``[d]`` vector) splits ``w``
        over: each device all-gathers ``w`` once and gathers for its own rows.
        Written under ``shard_map``: left to GSPMD, a gather or scatter whose
        operand and indices are split over one axis all-gathers the INDICES
        (a [k, n] array a pass) and runs every row on every device."""
        axis = sharding.spec[0]

        def local(w_part, idx, val):
            whole = jax.lax.all_gather(w_part, axis, tiled=True)
            return jnp.sum(val.T * jnp.take(whole, idx.T, axis=0), axis=0)

        return jax.shard_map(
            local, mesh=sharding.mesh, out_specs=P(axis),
            in_specs=(P(axis), P(axis, None), P(axis, None)),
        )(w, self.idx, self.val)

    def rmatvec_scattered(self, c: Array, sharding) -> Array:
        """``rmatvec`` of a row-sharded ELL matrix, split as ``sharding``
        splits the result: each device scatter-adds its own rows into a local
        ``[d]`` target, and the targets are reduce-scattered, every device
        keeping the sum over all rows of the part it owns. ``dim`` must be
        whole rows of 128 on every device (``lbfgs.history_row_width``): the
        sum is reduce-scattered as ``[d / 128, 128]``, which the v5e's
        compiler runs as its reduce-scatter fusion, where a ``[d]`` vector
        becomes an all-reduce of all d and a slice (compiled for a v5e 2x2,
        PERF.md, PR 40)."""
        axis = sharding.spec[0]
        dim = self.dim

        def local(c_part, idx, val):
            contrib = c_part[None, :] * val.T
            g = jnp.zeros(dim, dtype=contrib.dtype).at[idx.T.reshape(-1)].add(contrib.reshape(-1))
            rows = jax.lax.psum_scatter(g.reshape(-1, 128), axis, scatter_dimension=0, tiled=True)
            return rows.reshape(-1)

        return jax.shard_map(
            local, mesh=sharding.mesh, out_specs=P(axis),
            in_specs=(P(axis), P(axis, None), P(axis, None)),
        )(c, self.idx, self.val)

    def rmatmat(self, c: Array) -> Array:
        """x^T @ c -> [d, L] for lane-stacked per-row weights c[n, L]: the
        gradient-accumulation kernel of the lambda-lane sweep path."""
        if self.dense is not None:
            return self.dense.T @ c
        contrib = c[:, None, :] * self.val[:, :, None]  # [n, k, L]
        L = c.shape[1]
        return jnp.zeros((self.dim, L), dtype=contrib.dtype).at[
            self.idx.reshape(-1)
        ].add(contrib.reshape(-1, L))

    def sq_rmatvec(self, c: Array) -> Array:
        """(x*x)^T @ c -> [d]: Hessian-diagonal accumulation."""
        if self.dense is not None:
            return (self.dense * self.dense).T @ c
        contrib = c[None, :] * self.val.T * self.val.T
        return jnp.zeros(self.dim, dtype=contrib.dtype).at[
            self.idx.T.reshape(-1)
        ].add(contrib.reshape(-1))

    def to_dense(self) -> Array:
        if self.dense is not None:
            return self.dense
        n = self.idx.shape[0]
        out = jnp.zeros((n, self.dim), dtype=self.val.dtype)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], self.idx.shape)
        return out.at[rows.reshape(-1), self.idx.reshape(-1)].add(
            self.val.reshape(-1)
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LabeledBatch:
    """Batch equivalent of the reference's ``RDD[LabeledPoint]``
    (photon-lib .../data/LabeledPoint.scala:30-86): label/features/offset/weight.

    Padded rows carry ``weight == 0`` and are invisible to the objective.
    """

    features: FeatureMatrix
    labels: Array
    offsets: Array
    weights: Array

    @property
    def n_rows(self) -> int:
        return self.features.n_rows

    @property
    def dim(self) -> int:
        return self.features.dim

    def with_offsets(self, offsets: Array) -> "LabeledBatch":
        return dataclasses.replace(self, offsets=offsets)

    def margins(self, coef: Array) -> Array:
        """features.coef + offset (LabeledPoint.computeMargin semantics)."""
        return self.features.matvec(coef) + self.offsets


def batch_from_dense(
    x: np.ndarray,
    y: np.ndarray,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    dtype=jnp.float32,
    feature_dtype=None,
) -> LabeledBatch:
    """``feature_dtype`` (e.g. bfloat16) stores ONLY the feature matrix in a
    narrower type — labels/offsets/weights and all solver state stay
    ``dtype``. On TPU a bf16 X halves the HBM traffic of the bandwidth-bound
    dense objective sweeps (MXU-native bf16xbf16->f32)."""
    n, d = x.shape
    return LabeledBatch(
        features=FeatureMatrix(dim=d, dense=jnp.asarray(x, feature_dtype or dtype)),
        labels=jnp.asarray(y, dtype),
        offsets=jnp.zeros(n, dtype) if offsets is None else jnp.asarray(offsets, dtype),
        weights=jnp.ones(n, dtype) if weights is None else jnp.asarray(weights, dtype),
    )


def _local_column_map(idx: np.ndarray, dim: int):
    """The sorted columns ``idx`` holds and each slot's position among them,
    by a mask over the columns: at ``fit-sparse``'s 14.16M slots 0.63 s on a
    v5e machine's host, where ``np.unique(idx, return_inverse=True)`` took
    2.66 s. Only the pages of ``pos`` that a held column falls in are
    written."""
    seen = np.zeros(dim, bool)
    seen[idx] = True
    held = np.flatnonzero(seen).astype(np.int32)
    pos = np.empty(dim, np.int32)
    pos[held] = np.arange(len(held), dtype=np.int32)
    return held, pos[idx]


def batch_from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    y: np.ndarray,
    dim: int,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    max_nnz: Optional[int] = None,
    dtype=jnp.float32,
    feature_dtype=None,
    one_device: bool = False,
) -> LabeledBatch:
    """Build an ELL batch from COO triplets (host-side, numpy).

    ``one_device``: the batch stays on one device, so an ELL batch at least
    ``LOCAL_MAP_MIN_DIM`` wide also gets its local column map.
    ``feature_dtype`` (e.g. bfloat16) stores ONLY the feature VALUES in a
    narrower type — indices, labels/offsets/weights and all solver state
    stay wide; elementwise products promote back to ``dtype`` on the fly.
    """
    n = len(y)
    counts = np.bincount(rows, minlength=n)
    k = int(max_nnz if max_nnz is not None else (counts.max() if n else 0))
    k = max(k, 1)
    idx = np.zeros((n, k), dtype=np.int32)
    val = np.zeros((n, k), dtype=np.float64)
    # stable row sort preserves input order within each row, so max_nnz
    # truncation keeps the FIRST entries in input order (matching the
    # documented contract; a column sort here would silently keep the
    # lowest-column entries instead)
    order = np.argsort(rows, kind="stable")
    r_s, c_s, v_s = rows[order], cols[order], vals[order]
    starts = np.cumsum(np.concatenate([[0], np.bincount(r_s, minlength=n)[:-1]]))
    within = np.arange(len(r_s)) - starts[r_s]
    keep = within < k
    idx[r_s[keep], within[keep]] = c_s[keep]
    val[r_s[keep], within[keep]] = v_s[keep]
    local = {}
    if carries_local_map(dim, one_device):
        held, idx_local = _local_column_map(idx, dim)
        local = dict(cols=jnp.asarray(held, np.int32), idx_local=jnp.asarray(idx_local, np.int32))
    feats = FeatureMatrix(
        dim=dim, idx=jnp.asarray(idx, np.int32), val=jnp.asarray(val, feature_dtype or dtype), **local
    )
    return LabeledBatch(
        features=feats,
        labels=jnp.asarray(y, dtype),
        offsets=jnp.zeros(n, dtype) if offsets is None else jnp.asarray(offsets, dtype),
        weights=jnp.ones(n, dtype) if weights is None else jnp.asarray(weights, dtype),
    )


def pad_batch(batch: LabeledBatch, target_rows: int) -> LabeledBatch:
    """Pad a batch with zero-weight rows up to ``target_rows`` (static shapes
    for jit; also used to make row counts divisible by the device mesh)."""
    n = batch.n_rows
    if n == target_rows:
        return batch
    if n > target_rows:
        raise ValueError(f"batch has {n} rows > target {target_rows}")
    extra = target_rows - n
    pad1 = lambda a: jnp.concatenate([a, jnp.zeros((extra,), a.dtype)])
    f = batch.features
    if f.dense is not None:
        feats = FeatureMatrix(
            dim=f.dim,
            dense=jnp.concatenate([f.dense, jnp.zeros((extra, f.dim), f.dense.dtype)]),
        )
    else:
        pad2 = lambda a: jnp.concatenate([a, jnp.zeros((extra, a.shape[1]), a.dtype)])
        feats = FeatureMatrix(
            dim=f.dim, idx=pad2(f.idx), val=pad2(f.val), cols=f.cols,
            idx_local=None if f.cols is None else pad2(f.idx_local),
        )
    return LabeledBatch(
        features=feats,
        labels=pad1(batch.labels),
        offsets=pad1(batch.offsets),
        weights=pad1(batch.weights),
    )
