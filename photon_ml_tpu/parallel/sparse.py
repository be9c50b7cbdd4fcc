"""(data x model)-tiled sparse feature matrix: the huge-d fixed-effect path.

This is the TPU answer to the reference's claim of scaling to "hundreds of
billions of coefficients" (/root/reference/README.md:56) for the *fixed
effect*: the coefficient vector is sharded over a "model" mesh axis and the
sample rows over a "data" axis, so the batch gradient

    g = X^T c     (ValueAndGradientAggregator.scala:137-161's hot axpy loop)

becomes, per device tile, a local sorted scatter over that device's column
range followed by a psum over the data axis — the exact analogue of the
reference's treeAggregate all-reduce (SURVEY.md P1), with the model axis
adding what Spark never had: a partitioned coefficient vector.

Why tiling (and not GSPMD auto-sharding): unstructured gather/scatter on TPU
executes serially, 16-19 ns a slot to gather from and 9-11 ns to scatter
into a 219 MB vector on a v5e (my chip run, PR 34: the table in
ops/features.py; there is no HBM cache and pre-SparseCore hardware has no
vectorized large-table gather), so the single-chip sparse kernel is
serialization-bound. Partitioning the nnz by
(row-range, column-range) divides that serial cost by the device count on
both the gather (c by row) and scatter (g by column) sides — sparse
throughput scales linearly with chips, which is the property that matters at
pod scale. Collectives ride ICI: z partials psum over the model axis,
gradient partials psum over the data axis.

Layout contract per tile (host-built, static): triplets sorted by local
column (so the column axis partitions contiguously; on one chip at d = 54.7M
the sorted scatter was no faster than the unsorted one, ops/features.py); padding entries carry lcol = d_local - 1,
lval = 0, lrow = 0.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.features import LabeledBatch
from .mesh import DATA_AXIS, MODEL_AXIS

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TiledSparseMatrix:
    """FeatureMatrix-compatible sparse matrix tiled over a (data, model) mesh.

    Arrays are [n_data, n_model, m_tile], sharded P(data, model, None): each
    device holds exactly its tile. ``dim`` / ``n_rows`` are the padded global
    sizes (multiples of the mesh axes).
    """

    dim: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    lcol: Optional[Array] = None  # i32[D, M, m_tile], sorted per tile
    lrow: Optional[Array] = None  # i32[D, M, m_tile]
    lval: Optional[Array] = None  # f[D, M, m_tile]
    # the UNPADDED feature dim (0 = unknown): lets consumers distinguish
    # structural mesh padding from real-but-inactive features
    dim_true: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def layout(self) -> str:
        return "tiled"

    @property
    def is_dense(self) -> bool:
        return False

    @property
    def n_local_rows(self) -> int:
        return self.n_rows // self.mesh.shape[DATA_AXIS]

    @property
    def d_local(self) -> int:
        return self.dim // self.mesh.shape[MODEL_AXIS]

    def matvec(self, w: Array) -> Array:
        """x @ w -> [n] (sharded over data). w: [dim], sharded over model."""
        n_loc = self.n_local_rows

        def f(lcol, lrow, lval, w_loc):
            lc, lr, lv = lcol[0, 0], lrow[0, 0], lval[0, 0]
            wv = jnp.take(w_loc, lc) * lv
            z = jnp.zeros(n_loc, wv.dtype).at[lr].add(wv)
            return jax.lax.psum(z, MODEL_AXIS)

        return shard_map(
            f,
            mesh=self.mesh,
            in_specs=(
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(MODEL_AXIS),
            ),
            out_specs=P(DATA_AXIS),
        )(self.lcol, self.lrow, self.lval, w)

    def _rmat(self, c: Array, square: bool) -> Array:
        d_loc = self.d_local

        def f(lcol, lrow, lval, c_loc):
            lc, lr, lv = lcol[0, 0], lrow[0, 0], lval[0, 0]
            if square:
                lv = lv * lv
            contrib = jnp.take(c_loc, lr) * lv
            g = jnp.zeros(d_loc, contrib.dtype).at[lc].add(
                contrib, indices_are_sorted=True
            )
            return jax.lax.psum(g, DATA_AXIS)

        return shard_map(
            f,
            mesh=self.mesh,
            in_specs=(
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS),
            ),
            out_specs=P(MODEL_AXIS),
        )(self.lcol, self.lrow, self.lval, c)

    def rmatvec(self, c: Array) -> Array:
        """x^T @ c -> [dim] (sharded over model). c: [n], sharded over data."""
        return self._rmat(c, square=False)

    def sq_rmatvec(self, c: Array) -> Array:
        return self._rmat(c, square=True)

    def to_dense(self) -> Array:
        # photon: ignore[R10] — internal API guard on a layout class, not a
        # user-facing configuration refusal; the supported paths are named
        # in the message, and no config combination routes here
        raise NotImplementedError(
            "TiledSparseMatrix is for huge d; densification is not supported "
            "(use variance_type SIMPLE, or FULL which runs the chunked "
            "sharded xtcx path without materializing X)"
        )

    def xtcx(self, c: Array, row_chunk: Optional[int] = None) -> Array:
        """X^T diag(c) X -> [dim, dim], sharded over the model axis on dim 0:
        the FULL-variance Hessian on the tiled layout
        (reference: HessianMatrixAggregator.scala:92-128 — per-partition outer
        products tree-aggregated; here per-tile chunked outer products psum'd
        over the data axis).

        Each device scans its rows in ``row_chunk`` windows: densify the local
        (chunk x d_local) tile, all-gather the chunk's full feature rows over
        the model axis, and accumulate the device's [d_local, dim] Hessian
        row-block — so peak memory is O(row_chunk * dim + d_local * dim), never
        O(n * dim). The dim ceiling is enforced by the caller
        (ops/glm.py: MAX_FULL_VARIANCE_DIM) since [dim, dim] must be
        invertible on one device afterwards.

        Cost note: every scan step masks the tile's whole nnz array (entries
        are column-sorted for rmatvec's fast path, so a chunk's rows are not
        contiguous), i.e. scatter work is O(m_tile * n_chunks). To bound that
        multiplier, the DEFAULT ``row_chunk`` (None) is auto-raised so
        n_chunks <= 64 as long as the chunk's gathered rows stay under
        ~256 MB — a once-per-train trade of memory for the serialized-scatter
        constant. An explicitly passed ``row_chunk`` is respected as-is so
        memory-constrained callers can cap the peak below the heuristic.
        """
        d_loc, n_loc = self.d_local, self.n_local_rows
        if row_chunk is None:
            row_itemsize = np.dtype(self.lval.dtype).itemsize
            mem_cap_rows = max(
                (256 << 20) // (row_itemsize * max(self.dim, 1)), 1024
            )
            row_chunk = max(4096, min(-(-n_loc // 64), mem_cap_rows))
        chunk = min(row_chunk, n_loc)
        n_chunks = -(-n_loc // chunk)
        n_pad = n_chunks * chunk
        dim = self.dim

        def f(lcol, lrow, lval, c_loc):
            lc, lr, lv = lcol[0, 0], lrow[0, 0], lval[0, 0]
            c_pad = jnp.pad(c_loc, (0, n_pad - n_loc))

            def body(h, k):
                start = k * chunk
                in_r = (lr >= start) & (lr < start + chunk)
                xt = (
                    jnp.zeros((chunk, d_loc), lv.dtype)
                    .at[jnp.where(in_r, lr - start, 0), lc]
                    .add(jnp.where(in_r, lv, 0.0))
                )
                xg = jax.lax.all_gather(xt, MODEL_AXIS, axis=1, tiled=True)
                cc = jax.lax.dynamic_slice_in_dim(c_pad, start, chunk)
                return h + xt.T @ (cc[:, None] * xg), None

            h0 = jax.lax.pcast(
                jnp.zeros((d_loc, dim), lv.dtype),
                (DATA_AXIS, MODEL_AXIS),
                to="varying",
            )
            h, _ = jax.lax.scan(body, h0, jnp.arange(n_chunks))
            return jax.lax.psum(h, DATA_AXIS)

        return shard_map(
            f,
            mesh=self.mesh,
            in_specs=(
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS, MODEL_AXIS, None),
                P(DATA_AXIS),
            ),
            out_specs=P(MODEL_AXIS, None),
        )(self.lcol, self.lrow, self.lval, c)


def tile_sparse_matrix(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    dim: int,
    mesh: Mesh,
    dtype=jnp.float32,
) -> TiledSparseMatrix:
    """Host-side one-time tiling (the analogue of the reference's dataset
    partitioning shuffle, SURVEY.md P13). Pads n and d to mesh multiples and
    each tile's nnz to the max tile size.

    Multi-process: ``rows``/``n_rows`` are this process's LOCAL row slice.
    Each process owns a contiguous block of the data axis with every model
    column, so tiles build locally from local COO — the only cross-host
    agreement is the max tile size (one scalar allgather). The global row
    space is the concatenation of the per-process padded slices, matching
    the padded global sample space of the other coordinates.
    """
    from . import multihost

    D = mesh.shape[DATA_AXIS]
    M = mesh.shape[MODEL_AXIS]
    n_proc = jax.process_count()
    if D % n_proc != 0:
        raise ValueError(
            f"tiled layout: data axis ({D}) must divide evenly across "
            f"{n_proc} processes"
        )
    D_local = D // n_proc
    # pad LOCAL rows to the local share of the data axis; the global padded
    # row count is the sum of the (equal) per-process shares
    n_loc_rows = max(((n_rows + D_local - 1) // D_local) * D_local, D_local)
    n_pad = n_loc_rows * n_proc
    d_pad = max(((dim + M - 1) // M) * M, M)
    n_loc, d_loc = n_loc_rows // D_local, d_pad // M

    tile_r = rows // n_loc
    tile_c = cols // d_loc
    key = tile_r * M + tile_c
    order = np.lexsort((cols, key))
    r_s, c_s, v_s, k_s = rows[order], cols[order], vals[order], key[order]
    counts = np.bincount(k_s, minlength=D_local * M)
    m_local = max(int(counts.max()) if len(counts) else 0, 1)
    m_tile = max(t for t in multihost.allgather_object(m_local))

    lcol = np.full((D_local * M, m_tile), d_loc - 1, dtype=np.int32)
    lrow = np.zeros((D_local * M, m_tile), dtype=np.int32)
    lval = np.zeros((D_local * M, m_tile), dtype=np.float64)
    if len(k_s):
        starts = np.cumsum(np.concatenate([[0], counts[:-1]]))
        within = np.arange(len(k_s)) - starts[k_s]
        lcol[k_s, within] = c_s % d_loc
        lrow[k_s, within] = r_s % n_loc
        lval[k_s, within] = v_s

    spec = P(DATA_AXIS, MODEL_AXIS, None)
    put = lambda a: multihost.put_global(a, mesh, spec)
    return TiledSparseMatrix(
        dim=d_pad,
        n_rows=n_pad,
        mesh=mesh,
        lcol=put(lcol.reshape(D_local, M, m_tile)),
        lrow=put(lrow.reshape(D_local, M, m_tile)),
        lval=put(lval.reshape(D_local, M, m_tile).astype(np.dtype(dtype))),
        dim_true=dim,
    )


def tiled_sparse_batch(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    y: np.ndarray,
    dim: int,
    mesh: Mesh,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    dtype=jnp.float32,
) -> LabeledBatch:
    """Build a LabeledBatch whose features are mesh-tiled; labels/offsets/
    weights are zero-padded to the mesh row multiple and sharded over the
    data axis (padded rows carry weight 0)."""
    from . import multihost

    n = len(y)
    feats = tile_sparse_matrix(rows, cols, vals, n, dim, mesh, dtype=dtype)
    # per-process local share of the padded global row space
    n_loc_pad = feats.n_rows // jax.process_count()

    def pad1(a, fill=0.0):
        out = np.full(n_loc_pad, fill, dtype=np.float64)
        out[:n] = a
        return multihost.put_global(
            np.asarray(out, np.dtype(dtype)), mesh, P(DATA_AXIS)
        )

    return LabeledBatch(
        features=feats,
        labels=pad1(y),
        offsets=pad1(np.zeros(n) if offsets is None else offsets),
        weights=pad1(np.ones(n) if weights is None else weights, fill=0.0),
    )


def replicated_coefficients(w: np.ndarray, mesh: Mesh, dtype=jnp.float32) -> Array:
    """Place a [dim]-padded coefficient vector sharded over the model axis
    (multi-process: every process passes the full host vector and contributes
    its devices' slices)."""
    from . import multihost

    return multihost.put_global_from_full(
        np.asarray(w, np.dtype(dtype)), mesh, P(MODEL_AXIS)
    )
