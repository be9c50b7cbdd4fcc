"""GAME datasets: fixed-effect batches and entity-blocked random-effect data.

Reference: photon-api .../data/ — FixedEffectDataset.scala,
RandomEffectDataset.scala:51-600 (build pipeline: key-by-entity -> subspace
projectors -> project -> reservoir-cap -> passive split), LocalDataset.scala,
RandomEffectDatasetPartitioner.scala (entity sharding), and the
LinearSubspaceProjector (photon-api .../projector/LinearSubspaceProjector.scala:37-90).

TPU re-design (SURVEY.md §7.3): instead of an RDD of per-entity iterables,
a random-effect dataset is a set of *dense entity blocks*, LOGICALLY

    features  f[E, K, S]   per-entity rows projected into the entity's
    labels    f[E, K]      feature subspace (S = max subspace dim,
    weights   f[E, K]      K = max (capped) rows per entity; zero-padded)
    offsets   f[E, K]
    proj_cols i32[E, S]    local dim -> global feature column (-1 pad)
    active_rows i32[E, K]  global sample row of each block cell (-1 pad)

and STORED ragged: the five arrays with a K axis are one array a size bucket
(:class:`BucketedArray`; ``size_buckets``: the entities of one power-of-two
row count K_b, at the power-of-two subspace width S_b their largest needs),
``features f[chunks * (end - start), K_b, S_b]`` and the others at
``[..., K_b]``, rows chunk-major. No ``E x K x S`` array exists on the host
or the device: a long-tailed entity law (a few entities at the cap with
hundreds of columns, most with a handful of rows and columns) costs what its
buckets hold, not what its largest entity would at every entity. Only the
``[E, S]`` tables stay planes.

Per-entity local solves then become one vmapped masked solver call a bucket —
the MXU-friendly replacement for the reference's per-entity sequential L-BFGS
fan-out (RandomEffectCoordinate.scala:273-329). Entity order doubles as the
sharding axis: shard dim 0 over the mesh and each device owns a contiguous
range of block rows. Built for ``m`` devices (``pad_entities_to_multiple``),
the size-sorted entities are DEALT over ``m`` chunks of block rows, so each
device's range is size-sorted in itself and carries the same load (the
bin-packing partitioner's role, P5; ``_entity_plan``).

Active/passive split parity: entities with more than ``active_cap`` samples
train on a deterministic hash-priority reservoir of ``active_cap`` rows with
weights rescaled by count/cap (RandomEffectDataset.scala:403-506,
MinHeapWithFixedCapacity semantics); the remaining *passive* rows are scored
but never trained on. Entities with fewer than ``active_lower_bound`` samples
are dropped from training entirely (scored as zeros until some other
coordinate explains them).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.features import FeatureMatrix, LabeledBatch, feature_bytes
from ..io.data import RawDataset

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class HostRowBatch:
    """Host-resident row-major fixed-effect training data for the streamed
    (out-of-core) path: the row axis slices trivially for both supported
    layouts (dense ``[n, d]`` and ELL ``idx/val [n, F]``), which is what lets
    game/fe_streaming.py stage budget-sized row windows through the chip.
    The tiled (mesh) layout is NOT row-sliceable and is refused upstream
    (GameEstimator)."""

    dim: int
    labels: np.ndarray  # f[n] solve dtype
    offsets: np.ndarray  # f[n]
    weights: np.ndarray  # f[n]
    dense: Optional[np.ndarray] = None  # f[n, d] feature dtype
    ell_idx: Optional[np.ndarray] = None  # i32[n, F]
    ell_val: Optional[np.ndarray] = None  # f[n, F] feature dtype

    @property
    def n_rows(self) -> int:
        return int(self.labels.shape[0])

    @property
    def layout(self) -> str:
        return "dense" if self.dense is not None else "ell"

    def feature_row_nbytes(self) -> int:
        if self.dense is not None:
            return feature_bytes(1, self.dim, "dense", itemsize=self.dense.dtype.itemsize)
        return feature_bytes(
            1, self.dim, "ell", width=self.ell_idx.shape[1],
            itemsize=self.ell_val.dtype.itemsize,
        )


@dataclasses.dataclass(frozen=True)
class FixedEffectDataset:
    """All samples' features from one shard (FixedEffectDataset.scala:26-152).

    ``true_dim`` / ``true_n_rows`` are the UNPADDED shard dimension and sample
    count: mesh-tiled layouts pad both to device multiples, but models and
    exchanged score vectors live in the true space (trim/pad happens at the
    coordinate boundary).

    Out-of-core mode (game/fe_streaming.py): when ``streamed`` is set,
    ``batch`` is None and ``host_batch`` holds the row-major host arrays;
    training/scoring pipeline double-buffered row slices through the chip
    under ``hbm_budget_bytes`` — the FE twin of the streamed random effects
    (reference: DISK_ONLY spill + treeAggregate,
    CoordinateDescent.scala:262,404 / AvroDataReader.scala:165-209)."""

    coordinate_id: str
    feature_shard: str
    batch: Optional[LabeledBatch]
    true_dim: Optional[int] = None
    true_n_rows: Optional[int] = None
    host_batch: Optional[HostRowBatch] = None
    streamed: bool = False
    hbm_budget_bytes: Optional[int] = None
    # streamed + mesh/multi-process: host_batch holds THIS host's row slice;
    # the mesh is kept so scoring can reassemble the global row-sharded
    # score vector (n_rows stays the LOCAL true row count)
    mesh: Optional[object] = None
    # entries the shard's COO held when the batch was built (None for a
    # dataset assembled around a ready device matrix): against the layout's
    # ``slots`` it says how much of every pass is padding
    nnz: Optional[int] = None

    @property
    def n_rows(self) -> int:
        if self.true_n_rows is not None:
            return self.true_n_rows
        return self.batch.n_rows if self.batch is not None else self.host_batch.n_rows

    @property
    def dim(self) -> int:
        if self.true_dim is not None:
            return self.true_dim
        return self.batch.dim if self.batch is not None else self.host_batch.dim


Segment = Tuple[int, int, int, int]  # (start, end, K_b, S_b): rows of ONE chunk


def _pow2_ceil(x: np.ndarray) -> np.ndarray:
    """Exact elementwise 2**ceil(log2(max(x, 1))) for int64 inputs < 2^53
    (frexp exponents of exactly-represented ints are bit_lengths)."""
    v = np.maximum(np.asarray(x, dtype=np.int64), 1) - 1
    return np.int64(1) << np.frexp(v.astype(np.float64))[1].astype(np.int64)


def size_buckets(
    counts: np.ndarray,  # i64[E] active rows per block row
    subspace_dims: np.ndarray,  # i64[E] real subspace width per block row
    K: int,
    S: int,
    chunks: int,
    min_dim: int = 8,
) -> Optional[List[Segment]]:
    """Chunk-local entity segments with power-of-2-rounded (K, S) block shapes:
    the shapes the entity blocks are STORED at and the solver runs at.

    Returns [(start, end, K_b, S_b)], or None when bucketing cannot shrink
    anything (one bucket of the whole [K, S] extent). ``start``/``end`` are
    rows of ONE chunk: the block rows are ``chunks`` equal chunks, each
    size-sorted descending and dealt the same size profile (``_entity_plan``),
    and a bucket is rows [start, end) of EVERY chunk. One set of bounds serves
    all chunks: the row count at a local position is taken as the largest over
    the chunks, so an entity a chunk reaches one position early fits the
    larger K of the bucket before it. With one chunk the segments are plain
    block-row ranges. Rounding to powers of two (floored at ``min_dim``)
    bounds the number of distinct compiled solver shapes at O(log^2) while
    removing the bulk of the padding.

    Fully vectorized (no per-entity Python work: this also runs on every
    train() call, potentially over millions of entities)."""
    if len(counts) == 0:
        return None
    # per local position, the largest over the chunks
    counts = np.asarray(counts, dtype=np.int64).reshape(chunks, -1).max(axis=0)
    sv = np.asarray(subspace_dims, dtype=np.int64).reshape(chunks, -1).max(axis=0)
    chunk_rows = len(counts)

    kb_of = np.minimum(np.maximum(_pow2_ceil(counts), min_dim), K)
    bounds = np.flatnonzero(np.diff(kb_of)) + 1  # starts of new equal-K runs
    starts = np.concatenate([[0], bounds])
    ends = np.concatenate([bounds, [chunk_rows]])

    sb_of = np.minimum(
        np.maximum(_pow2_ceil(np.maximum.reduceat(sv, starts)), min_dim), S
    )
    segments = [
        (
            int(s),
            int(e),
            int(kb_of[s]),  # counts non-increasing => max K of the segment
            int(sb),
        )
        for s, e, sb in zip(starts, ends, sb_of)
    ]
    if len(segments) == 1 and segments[0][2] >= K and segments[0][3] >= S:
        return None
    return segments


@jax.tree_util.register_pytree_node_class
class BucketedArray:
    """A logical ``[E, K]`` / ``[E, K, S]`` entity-block array stored ragged:
    ``parts[b]`` holds rows [start_b, end_b) of every one of the ``chunks``
    chunks of block rows, chunk-major, cut to the bucket's ``[K_b(, S_b)]``:
    ``[chunks * (end_b - start_b), K_b(, S_b)]``. Outside a bucket's extent
    the logical array is ``fill`` (0, or -1 for ``active_rows``) and is stored
    nowhere.

    It answers ``shape`` (the LOGICAL one), ``dtype`` and ``sharding`` as the
    plane it replaces did, and is a pytree over its parts, so
    ``shard_entity_blocks`` places every part by the chunk-dealt rule (a
    part's leading axis over ``data``: chunk c of every bucket on the device
    that holds chunk c). ``plane()`` / ``np.asarray`` assemble the logical
    array on demand: for tests, tools and the paths that still want one
    (trial lanes; ROADMAP.md Design), never on the resident train path, and
    never one larger than the host's memory (``MemoryError``)."""

    def __init__(self, parts, segments: Sequence[Segment], chunks: int, shape, fill=0):
        self.parts = tuple(parts)
        self.segments = tuple(tuple(int(v) for v in seg) for seg in segments)
        self.chunks = int(chunks)
        self.shape = tuple(int(v) for v in shape)
        self.fill = fill

    def tree_flatten(self):
        return self.parts, (self.segments, self.chunks, self.shape, self.fill)

    @classmethod
    def tree_unflatten(cls, aux, parts):
        return cls(parts, *aux)

    def __repr__(self) -> str:
        return (
            f"BucketedArray(shape={self.shape}, dtype={self.dtype}, "
            f"buckets={[tuple(np.shape(p)) for p in self.parts]})"
        )

    @property
    def dtype(self):
        return self.parts[0].dtype

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def sharding(self):
        # host-numpy parts carry none: AttributeError, as a host plane's did
        return self.parts[0].sharding

    @property
    def nbytes(self) -> int:
        """Bytes the store holds (the plane's would be prod(shape) * itemsize)."""
        return int(sum(int(np.prod(np.shape(p))) * p.dtype.itemsize for p in self.parts))

    def plane(self):
        """The logical array, assembled from the buckets: host numpy from
        host parts, a device array from device parts."""
        host = isinstance(self.parts[0], np.ndarray)
        # (a host that overcommits hands out any size and dies touching it)
        logical = int(np.prod(self.shape)) * self.dtype.itemsize
        if logical > os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"):
            raise MemoryError(
                f"the logical {self.shape} plane is {logical / 1e9:.0f} GB ({self.nbytes / 1e9:.2f} GB "
                "stored): past this host's memory, it is not assembled"
            )
        out = (np if host else jnp).full(self.shape, self.fill, self.dtype)
        chunk_rows = self.shape[0] // self.chunks
        for part, (start, end, *dims) in zip(self.parts, self.segments):
            n_b = end - start
            cut = tuple(slice(None, d) for d in dims[: self.ndim - 1])
            for c in range(self.chunks):
                rows = (slice(c * chunk_rows + start, c * chunk_rows + end),) + cut
                piece = part[c * n_b : (c + 1) * n_b]
                if host:
                    out[rows] = piece
                else:
                    out = out.at[rows].set(piece)
        return out

    def __array__(self, dtype=None, copy=None):
        plane = np.asarray(self.plane())
        return plane if dtype is None else plane.astype(dtype)


def _chunk_rows(a, chunks: int, start: int, end: int, *dims: int):
    """Rows [start, end) of every one of the ``chunks`` equal chunks of
    ``a``'s leading axis, chunk-major, the trailing axes cut to ``dims``.
    One chunk: the plain slice. (Slices joined, not a reshape sliced: behind a
    reshape the TPU compiler re-lays the whole array out before it cuts.)"""
    cut = tuple(slice(None, d) for d in dims)
    rows = a.shape[0] // chunks
    parts = [
        a[(slice(c * rows + start, c * rows + end),) + cut] for c in range(chunks)
    ]
    if chunks == 1:
        return parts[0]
    return (np if isinstance(a, np.ndarray) else jnp).concatenate(parts)


def bucket_plane(plane, segments: Sequence[Segment], chunks: int, fill=0) -> BucketedArray:
    """A ``[E, K(, S)]`` plane cut into its buckets (host slices of a host
    plane, device slices of a device one). For data sets that arrive as
    planes (hand-built, the multi-process build; ROADMAP.md Design): the
    resident build never holds one."""
    trailing = len(plane.shape) - 1
    parts = [
        _chunk_rows(plane, chunks, start, end, *dims[:trailing])
        for start, end, *dims in segments
    ]
    return BucketedArray(parts, segments, chunks, plane.shape, fill)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EntityBlocks:
    """Entity-blocked training data (see module docstring). The comments give
    each field's LOGICAL shape; the five with a K axis are stored one array a
    size bucket (:class:`BucketedArray`) on the resident path, and as plain
    host planes when the data set is streamed (game/streaming.py slices them)
    or built across processes (game/data_mp.py; cut at the first train)."""

    features: Union[Array, BucketedArray]  # f[E, K, S]; part b: [chunks * n_b, K_b, S_b]
    labels: Union[Array, BucketedArray]  # f[E, K]; part b: [chunks * n_b, K_b]
    offsets: Union[Array, BucketedArray]  # f[E, K] (base offsets only; residuals added at train time)
    weights: Union[Array, BucketedArray]  # f[E, K]; 0 = padding
    proj_cols: Array  # i32[E, S] (a plane); -1 = padding
    active_rows: Union[Array, BucketedArray]  # i32[E, K]; -1 = padding

    @property
    def num_entities(self) -> int:
        return self.features.shape[0]

    @property
    def rows_per_entity(self) -> int:
        return self.features.shape[1]

    @property
    def subspace_dim(self) -> int:
        return self.features.shape[2]

    @property
    def bucketed(self) -> bool:
        return isinstance(self.features, BucketedArray)

    @property
    def store_bytes(self) -> int:
        """Bytes of the five K-axis arrays as stored."""
        return int(
            sum(
                a.nbytes
                for a in (self.features, self.labels, self.offsets, self.weights, self.active_rows)
            )
        )


def bucket_blocks(
    blocks: EntityBlocks, segments: Sequence[Segment], chunks: int, cut=None
) -> EntityBlocks:
    """``blocks``, handed over as planes, with its five K-axis arrays cut into
    ``segments``. ``cut(planes, start, end, dims)`` returns one bucket's rows
    of all five (the coordinate passes one program a bucket, per device under
    a mesh); the default cuts each plane by plain slices."""
    planes = (blocks.features, blocks.labels, blocks.offsets, blocks.weights, blocks.active_rows)
    if cut is None:
        cut = lambda planes, start, end, dims: tuple(  # noqa: E731
            _chunk_rows(p, chunks, start, end, *d) for p, d in zip(planes, dims)
        )
    pieces = [
        cut(planes, start, end, ((kb, sb),) + ((kb,),) * 4) for start, end, kb, sb in segments
    ]
    features, labels, offsets, weights, active_rows = (
        BucketedArray([piece[f] for piece in pieces], segments, chunks, plane.shape, fill)
        for f, (plane, fill) in enumerate(zip(planes, (0, 0, 0, 0, -1)))
    )
    return EntityBlocks(
        features=features, labels=labels, offsets=offsets, weights=weights,
        proj_cols=blocks.proj_cols, active_rows=active_rows,
    )


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """Entity-blocked random-effect dataset + full-row scoring arrays."""

    coordinate_id: str
    feature_shard: str
    random_effect_type: str
    entity_ids: np.ndarray  # object[E], order = block row
    blocks: EntityBlocks
    # scoring representation for ALL rows of the full dataset (ELL, global space)
    row_entity: Array  # i32[n] block row per sample, -1 = entity dropped/unseen
    ell_idx: Array  # i32[n, F]
    ell_val: Array  # f[n, F]
    passive_rows: np.ndarray  # i64[*] rows not in any active block (info only)
    # host-side per-entity stats: the size buckets the blocks are STORED and
    # solved at (size_buckets) follow from them, so small entities pay
    # neither the rows nor the columns of the largest. The block rows are
    # ``entity_chunks`` equal chunks, each size-sorted descending in itself
    # and dealt the same size profile (_entity_plan): every size bucket has
    # an equal share in every chunk, and so on every chip the chunks shard
    # over (the TPU analogue of the reference's size-aware partitioning,
    # RandomEffectDatasetPartitioner.scala:117-180)
    entity_counts: Optional[np.ndarray] = None  # i64[E] active rows per entity
    entity_subspace_dims: Optional[np.ndarray] = None  # i64[E] real S per entity
    entity_chunks: int = 1  # size-sorted chunks the block rows hold (1: one sorted run)
    # multi-process: host copy of blocks.proj_cols (the device array is
    # entity-sharded across processes, so not host-addressable); model
    # projection / warm-start layout checks read this instead
    host_proj_cols: Optional[np.ndarray] = None
    # out-of-core mode (game/streaming.py): blocks hold HOST numpy arrays and
    # training/scoring stream entity slices through the chip under this HBM
    # budget — the product path for models bigger than device memory
    # (reference: DISK_ONLY spill, CoordinateDescent.scala:262,404)
    streamed: bool = False
    hbm_budget_bytes: Optional[int] = None
    # streamed + multi-process (game/data_mp.py): blocks hold only THIS
    # host's contiguous [lo, hi) block-row range; entity-level host tables
    # (entity_ids / counts / host_proj_cols) stay GLOBAL. ``mesh`` is kept so
    # scoring can reassemble the global row-sharded score vector.
    entity_shard_range: Optional[Tuple[int, int]] = None
    mesh: Optional[object] = None

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)


@dataclasses.dataclass(frozen=True)
class _EntityPlan:
    """The deterministic entity layout every process must agree on: which
    entities train, their block order, the padded block count, the per-entity
    active cap, and weight rescales. The order is the stable descending size
    sort, dealt over ``chunks`` equal chunks of block rows (sorted entity j to
    chunk j mod chunks), so each chunk is size-sorted in itself and carries
    the same load; one chunk is the plain sort. The ``E - E_real`` pad rows
    are the tail of the block rows in either case.
    Computed from the (possibly cross-process-merged) per-entity counts alone,
    so identical inputs give identical plans on every host."""

    kept_entities: np.ndarray  # i64[E_real] indices into uniq, in block-row order
    old_to_block: np.ndarray  # i64[len(uniq)] -> block row or -1
    E_real: int
    E: int  # padded block count
    chunks: int  # size-sorted chunks of E // chunks block rows each
    cap: int
    K: int  # block row capacity
    weight_scale: np.ndarray  # f8[E] count/cap rescale for capped entities


def _entity_plan(
    counts: np.ndarray,
    active_lower_bound: int,
    active_cap: Optional[int],
    pad_entities_to_multiple: int,
) -> _EntityPlan:
    kept_mask = counts >= active_lower_bound
    kept_entities = np.nonzero(kept_mask)[0]
    # order entities by descending size: the order the deal below bin-packs
    kept_entities = kept_entities[np.argsort(-counts[kept_entities], kind="stable")]
    E_real = len(kept_entities)
    E = max(
        ((E_real + pad_entities_to_multiple - 1) // pad_entities_to_multiple)
        * pad_entities_to_multiple,
        pad_entities_to_multiple,
    )
    chunks = pad_entities_to_multiple
    if chunks > 1:
        dealt = np.empty_like(kept_entities)
        dealt[_deal_block_rows(E_real, E // chunks, chunks)] = kept_entities
        kept_entities = dealt
    old_to_block = np.full(len(counts), -1, dtype=np.int64)
    old_to_block[kept_entities] = np.arange(E_real)
    cap = active_cap if active_cap is not None else int(counts.max() if len(counts) else 1)
    K = int(min(int(counts[kept_entities].max()) if E_real else 1, cap)) or 1
    weight_scale = np.ones(E)
    if E_real:
        counts_kept = counts[kept_entities].astype(np.float64)
        weight_scale[:E_real] = np.where(counts_kept > cap, counts_kept / cap, 1.0)
    return _EntityPlan(
        kept_entities=kept_entities,
        old_to_block=old_to_block,
        E_real=E_real,
        E=E,
        chunks=chunks,
        cap=cap,
        K=K,
        weight_scale=weight_scale,
    )


def _deal_block_rows(n: int, chunk_rows: int, chunks: int) -> np.ndarray:
    """Block row of each of ``n`` size-sorted entities when they are dealt
    over ``chunks`` chunks of ``chunk_rows`` block rows, laid out chunk-major:
    sorted entity j goes to chunk j mod chunks, position j div chunks (the
    reference's partitioner balances load per partition the same way,
    RandomEffectDatasetPartitioner.scala:117-180). Every chunk is then
    size-sorted in itself, and the chunks' loads differ by at most one entity
    a round. The rows are a permutation of [0, n): the pad rows stay the tail
    of the last chunk(s), so a chunk that the tail has filled sits out the
    later rounds."""
    capacity = np.clip(n - np.arange(chunks) * chunk_rows, 0, chunk_rows)
    # chunks still open at each position: capacities are non-increasing, so
    # the open chunks of a round are the first ``open_at[p]``
    open_at = np.searchsorted(-capacity, -np.arange(chunk_rows), side="left")
    round_start = np.concatenate([[0], np.cumsum(open_at)])
    j = np.arange(n)
    position = np.searchsorted(round_start, j, side="right") - 1
    return (j - round_start[position]) * chunk_rows + position


def _hash64(a: np.ndarray, seed: int) -> np.ndarray:
    """Deterministic splitmix64-style mix of row ids (the reservoir priority;
    plays the role of byteswap64(hash ^ uniqueId), RandomEffectDataset.scala:483-491)."""
    x = a.astype(np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _rows_to_ell(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n: int,
    width: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """COO -> per-row padded (idx, val) with idx=0/val=0 padding. Vectorized.
    ``width`` overrides the ELL width (multi-process: the GLOBAL max row nnz,
    so per-host shapes agree)."""
    order = np.lexsort((cols, rows))
    r, c, v = rows[order], cols[order], vals[order]
    counts = np.bincount(r, minlength=n)
    F = width if width is not None else max(int(counts.max()) if n else 1, 1)
    idx = np.zeros((n, F), dtype=np.int32)
    val = np.zeros((n, F), dtype=np.float64)
    if len(r):
        starts = np.cumsum(np.concatenate([[0], counts[:-1]]))
        within = np.arange(len(r)) - starts[r]
        idx[r, within] = c
        val[r, within] = v
    return idx, val


def build_fixed_effect_dataset(
    raw: RawDataset,
    coordinate_id: str,
    feature_shard: str,
    dtype=jnp.float32,
    layout: str = "auto",
    mesh=None,
    feature_dtype=None,
    hbm_budget_bytes: Optional[int] = None,
) -> FixedEffectDataset:
    """``hbm_budget_bytes``: when set and the resident device batch would
    exceed this many bytes, the dataset is built STREAMED — features stay in
    host numpy (dense or ELL rows) and training/scoring stream row slices
    (game/fe_streaming.py). Under a mesh / multi-process topology ``raw`` is
    this host's row slice, so the budget governs the PER-HOST stream (the
    planner's streamed+sharded routing); the tiled layout is refused by
    the execution planner before this point."""
    d = raw.shard_dims[feature_shard]
    if hbm_budget_bytes is not None:
        eff_layout = layout
        if eff_layout == "auto":
            # same rule as RawDataset.to_batch's auto resolution
            eff_layout = "dense" if d <= 4096 else "ell"
        if eff_layout not in ("dense", "ell"):
            raise ValueError(
                f"coordinate {coordinate_id}: hbm_budget_mb on a fixed effect "
                f"requires a row-sliceable layout (auto|dense|ell), got "
                f"layout={layout!r}"
            )
        from .fe_streaming import estimate_fe_batch_bytes

        fdt = np.dtype(jnp.zeros((), feature_dtype or dtype).dtype)
        sdt = np.dtype(jnp.zeros((), dtype).dtype)
        rows, cols, vals = raw.shard_coo[feature_shard]
        n = raw.n_rows
        if eff_layout == "ell":
            counts = np.bincount(rows, minlength=n) if n else np.zeros(0, np.int64)
            width = max(int(counts.max()) if n else 1, 1)
        else:
            width = 0
        est = estimate_fe_batch_bytes(
            n, d, eff_layout, ell_width=width,
            feature_itemsize=fdt.itemsize, scalar_itemsize=sdt.itemsize,
            one_device=mesh is None,
        )
        if est > hbm_budget_bytes:
            if eff_layout == "dense":
                dense = np.zeros((n, d), np.float64)
                np.add.at(dense, (rows, cols), vals)
                host = HostRowBatch(
                    dim=d,
                    labels=raw.labels.astype(sdt),
                    offsets=raw.offsets.astype(sdt),
                    weights=raw.weights.astype(sdt),
                    dense=dense.astype(fdt),
                )
            else:
                ell_idx, ell_val = _rows_to_ell(rows, cols, vals, n, width=width)
                host = HostRowBatch(
                    dim=d,
                    labels=raw.labels.astype(sdt),
                    offsets=raw.offsets.astype(sdt),
                    weights=raw.weights.astype(sdt),
                    ell_idx=ell_idx,
                    ell_val=ell_val.astype(fdt),
                )
            # multi-process: the coordinate's row space is the padded GLOBAL
            # row space (scores/residuals stay [N_global], matching the
            # resident multi-process batch); host_batch keeps the LOCAL rows
            n_true = n
            if mesh is not None and jax.process_count() > 1:
                from ..parallel.mesh import DATA_AXIS

                n_proc = jax.process_count()
                chunk = max(mesh.shape[DATA_AXIS] // n_proc, 1)
                n_true = (-(-n // chunk) * chunk) * n_proc
            return FixedEffectDataset(
                coordinate_id=coordinate_id,
                feature_shard=feature_shard,
                batch=None,
                true_dim=d,
                true_n_rows=n_true,
                host_batch=host,
                streamed=True,
                hbm_budget_bytes=hbm_budget_bytes,
                mesh=mesh,
            )
    return FixedEffectDataset(
        coordinate_id=coordinate_id,
        feature_shard=feature_shard,
        batch=raw.to_batch(
            feature_shard, dtype=dtype, layout=layout, mesh=mesh,
            feature_dtype=feature_dtype,
        ),
        true_dim=raw.shard_dims[feature_shard],
        true_n_rows=raw.n_rows,
        nnz=len(raw.shard_coo[feature_shard][0]),
    )


def build_fixed_effect_dataset_from_disk(
    path,
    shard_configs,
    coordinate_id: str,
    feature_shard: str,
    hbm_budget_bytes: int,
    *,
    index_maps=None,
    id_tag_columns=(),
    response_column: str = "label",
    columns=None,
    reader_schema=None,
    dtype=jnp.float32,
    layout: str = "auto",
    feature_dtype=None,
    workers=None,
    pool=None,
    ingest_budget_bytes: Optional[int] = None,
    prefetch_depth: int = 2,
):
    """Disk → :class:`HostRowBatch` without ever materializing the full
    ``RawDataset``: part files decode across the ingest worker pool
    (``io/data.read_avro_part_pieces``) and each part's rows are written
    straight into the preallocated host feature planes, so rows go
    disk → decode → stage → chip with peak record residency of one part
    plus the decode pipeline. Returns ``(dataset, index_maps)`` with the
    dataset ALWAYS in streamed form (``game/fe_streaming.py`` row slices
    under ``hbm_budget_bytes``) — this path exists to feed the streamed
    fixed effect; use ``read_avro_dataset_chunked`` +
    :func:`build_fixed_effect_dataset` when a resident batch is wanted.

    Bitwise parity with the in-memory builder: parts arrive in file order
    with contiguous ascending row blocks, so the per-part
    ``np.add.at`` / ``_rows_to_ell(width=global)`` fills produce arrays
    identical to the global constructions on the concatenated COO, and
    scalar planes are filled elementwise (``astype`` commutes with
    concatenation). The dense layout truly streams (one part's COO alive
    at a time); the ELL layout buffers each part's compact COO arrays
    until the global row-nnz width is known — O(nnz) host memory, still
    never the record dicts or a concatenated ``RawDataset``.

    ``workers``/``pool``/``ingest_budget_bytes``/``prefetch_depth`` pass
    through to the decode pool; the pool's RSS backpressure
    (``ingest_budget_bytes``, compressed bytes in flight) composes with the
    ``hbm_budget_bytes`` slice accounting the streamed objective applies
    on the device side."""
    from .. import obs
    from ..io.avro import count_avro_rows, list_avro_parts
    from ..io.data import read_avro_part_pieces, scan_index_maps_pipelined

    paths = [path] if isinstance(path, str) else list(path)
    parts = [part for p in paths for part in list_avro_parts(p)]
    if not parts:
        raise ValueError(f"no .avro part files under {paths!r}")

    with obs.span("ingest.disk_slice", n_parts=len(parts)):
        if index_maps is None:
            index_maps = scan_index_maps_pipelined(
                parts, shard_configs, reader_schema,
                prefetch_depth=prefetch_depth, workers=workers, pool=pool,
                ingest_budget_bytes=ingest_budget_bytes,
            )
        d = len(index_maps[feature_shard])
        eff_layout = layout
        if eff_layout == "auto":
            # same rule as RawDataset.to_batch's auto resolution
            eff_layout = "dense" if d <= 4096 else "ell"
        if eff_layout not in ("dense", "ell"):
            raise ValueError(
                f"coordinate {coordinate_id}: the disk-to-slice ingest path "
                f"requires a row-sliceable layout (auto|dense|ell), got "
                f"layout={layout!r}"
            )
        # header-only row counts: block counts, no decompression
        n = sum(count_avro_rows(part) for part in parts)
        fdt = np.dtype(jnp.zeros((), feature_dtype or dtype).dtype)
        sdt = np.dtype(jnp.zeros((), dtype).dtype)

        labels = np.empty(n, sdt)
        offsets = np.empty(n, sdt)
        weights = np.empty(n, sdt)
        row0 = 0
        if eff_layout == "dense":
            # f64 accumulator, cast once at the end — identical to the
            # in-memory streamed branch's global np.add.at + astype
            dense = np.zeros((n, d), np.float64)

            def _drain(_i, piece) -> None:
                nonlocal row0
                np_rows = piece.n_rows
                labels[row0:row0 + np_rows] = piece.labels.astype(sdt)
                offsets[row0:row0 + np_rows] = piece.offsets.astype(sdt)
                weights[row0:row0 + np_rows] = piece.weights.astype(sdt)
                rows, cols, vals = piece.shard_coo[feature_shard]
                np.add.at(dense[row0:row0 + np_rows], (rows, cols), vals)
                row0 += np_rows

            read_avro_part_pieces(
                paths, shard_configs, _drain, index_maps,
                id_tag_columns=id_tag_columns,
                response_column=response_column, columns=columns,
                reader_schema=reader_schema, prefetch_depth=prefetch_depth,
                workers=workers, pool=pool,
                ingest_budget_bytes=ingest_budget_bytes,
            )
            host = HostRowBatch(
                dim=d, labels=labels, offsets=offsets, weights=weights,
                dense=dense.astype(fdt),
            )
        else:
            # ELL needs the GLOBAL max row nnz before allocation: buffer
            # each part's compact COO (O(nnz)), then fill per part with the
            # shared width — bit-identical to the global _rows_to_ell
            # because row blocks are contiguous and ascending
            coo_parts = []

            def _buffer(_i, piece) -> None:
                nonlocal row0
                np_rows = piece.n_rows
                labels[row0:row0 + np_rows] = piece.labels.astype(sdt)
                offsets[row0:row0 + np_rows] = piece.offsets.astype(sdt)
                weights[row0:row0 + np_rows] = piece.weights.astype(sdt)
                coo_parts.append((np_rows, piece.shard_coo[feature_shard]))
                row0 += np_rows

            read_avro_part_pieces(
                paths, shard_configs, _buffer, index_maps,
                id_tag_columns=id_tag_columns,
                response_column=response_column, columns=columns,
                reader_schema=reader_schema, prefetch_depth=prefetch_depth,
                workers=workers, pool=pool,
                ingest_budget_bytes=ingest_budget_bytes,
            )
            width = 1
            for np_rows, (rows, _c, _v) in coo_parts:
                counts = np.bincount(rows, minlength=np_rows)
                if np_rows:
                    width = max(width, int(counts.max()))
            ell_idx = np.zeros((n, width), np.int32)
            ell_val = np.zeros((n, width), np.float64)
            r0 = 0
            for np_rows, (rows, cols, vals) in coo_parts:
                idx_p, val_p = _rows_to_ell(rows, cols, vals, np_rows, width=width)
                ell_idx[r0:r0 + np_rows] = idx_p
                ell_val[r0:r0 + np_rows] = val_p
                r0 += np_rows
            del coo_parts
            host = HostRowBatch(
                dim=d, labels=labels, offsets=offsets, weights=weights,
                ell_idx=ell_idx, ell_val=ell_val.astype(fdt),
            )

        reg = obs.current_run().registry
        reg.counter(
            "photon_ingest_parts_total",
            "part files decoded by the chunked reader",
        ).labels(mode="disk_slice").inc(len(parts))
        reg.counter(
            "photon_ingest_rows_total", "rows produced by the chunked reader"
        ).labels(mode="disk_slice").inc(n)

    dataset = FixedEffectDataset(
        coordinate_id=coordinate_id,
        feature_shard=feature_shard,
        batch=None,
        true_dim=d,
        true_n_rows=n,
        host_batch=host,
        streamed=True,
        hbm_budget_bytes=hbm_budget_bytes,
    )
    return dataset, dict(index_maps)


def record_block_store(coordinate_id: str, store_bytes: int) -> None:
    """Gauge of the bytes a coordinate's entity-block store holds (the five
    K-axis arrays as stored, ``EntityBlocks.store_bytes``): set at the build
    and again by every resident train call, so a registry attached after the
    build reads it too."""
    from .. import obs

    obs.current_run().registry.gauge(
        "photon_re_block_store_bytes",
        "bytes of a random-effect coordinate's entity blocks as stored, bucket by bucket",
    ).labels(coordinate=coordinate_id).set(store_bytes)


def _pearson_keep_mask(
    feats: np.ndarray,  # f8[E, K, S] zero-padded per-entity features
    labels: np.ndarray,  # f8[E, K]
    row_mask: np.ndarray,  # bool[E, K] filled (active) slots
    proj_cols: np.ndarray,  # i32[E, S], -1 = padding
    ratio: float,
) -> np.ndarray:
    """Per-entity Pearson-correlation feature selection, vectorized over all
    entities at once.

    Reference: LocalDataset.filterFeaturesByPearsonCorrelationScore
    (photon-api .../data/LocalDataset.scala:103-130) keeps, per entity, the
    ceil(ratio * n_rows) features with the largest |Pearson(feature, label)|
    (stable one-pass scores, :180-258), where a constant feature with value
    1.0 — the intercept — scores 1.0 (first such column only) and other
    constant features score 0. Selection only applies when it would shrink
    the entity's active feature set.

    Returns bool[E, S]: True = keep the column.
    """
    E, K, S = feats.shape
    EPS = np.finfo(np.float64).eps
    n_e = row_mask.sum(axis=1)  # rows per entity
    n_safe = np.maximum(n_e, 1).astype(np.float64)

    mean_y = (labels * row_mask).sum(axis=1) / n_safe
    dy = (labels - mean_y[:, None]) * row_mask
    std_y = np.sqrt((dy * dy).sum(axis=1))

    mean_x = (feats * row_mask[:, :, None]).sum(axis=1) / n_safe[:, None]
    dx = (feats - mean_x[:, None, :]) * row_mask[:, :, None]
    cov = np.einsum("eks,ek->es", dx, dy)
    std_x = np.sqrt((dx * dx).sum(axis=1))  # sum over K -> [E, S]
    score = cov / (std_y[:, None] * std_x + EPS)

    # constant columns: intercept (value 1.0, first occurrence) scores 1.0,
    # any other constant scores 0 (LocalDataset.scala:225-236)
    const = std_x < np.sqrt(n_safe)[:, None] * EPS
    cand = const & (np.abs(mean_x - 1.0) < 1e-12) & (proj_cols >= 0)
    first_one = np.zeros_like(cand)
    has = cand.any(axis=1)
    first_one[np.nonzero(has)[0], np.argmax(cand, axis=1)[has]] = True
    score = np.where(const, np.where(first_one, 1.0, 0.0), score)

    n_active = (proj_cols >= 0).sum(axis=1)
    k_keep = np.ceil(ratio * n_e).astype(np.int64)
    k_keep = np.where(k_keep < n_active, k_keep, n_active)

    # rank columns by descending |score| (stable: earlier column wins ties).
    # |score| is quantized to a 1e-12 grid first: host-numpy and XLA f64
    # reductions can disagree in the last ulps (~1e-13), which would turn an
    # exact host tie into a device near-tie and flip which tied column is
    # kept — the grid collapses both onto the same key so the column-order
    # tie-break decides identically on both paths (determinism-for-recovery,
    # SURVEY §5 A2). Residual window: a score ~1 ulp from a grid midpoint can
    # still round apart — vanishing, not provably zero.
    absc = np.where(proj_cols >= 0, np.round(np.abs(score), 12), -1.0)
    order = np.argsort(-absc, axis=1, kind="stable")
    rank = np.empty((E, S), dtype=np.int64)
    np.put_along_axis(rank, order, np.broadcast_to(np.arange(S), (E, S)), axis=1)
    return (rank < k_keep[:, None]) & (proj_cols >= 0)


def build_random_effect_dataset(
    raw: RawDataset,
    coordinate_id: str,
    feature_shard: str,
    random_effect_type: str,
    active_cap: Optional[int] = None,
    active_lower_bound: int = 1,
    seed: int = 0,
    dtype=jnp.float32,
    pad_entities_to_multiple: int = 1,
    features_to_samples_ratio: Optional[float] = None,
    feature_dtype=None,
    hbm_budget_bytes: Optional[int] = None,
) -> RandomEffectDataset:
    """Host-side dataset build (the one-time "shuffle" of SURVEY.md §2.1 P13).

    What is stored: the entity blocks bucket by bucket. For every size bucket
    ``(start, end, K_b, S_b)`` of ``size_buckets`` (the entities of one
    power-of-two row count, at the power-of-two subspace width their largest
    needs; rows [start, end) of every one of the ``pad_entities_to_multiple``
    chunks), ``blocks.features`` holds one ``f[chunks * (end - start), K_b,
    S_b]`` array and ``labels`` / ``offsets`` / ``weights`` / ``active_rows``
    one ``[chunks * (end - start), K_b]`` array each (:class:`BucketedArray`),
    built at those shapes on the host (float64: twice the store's bytes at
    the peak) and placed on the device as they are: no ``E x K_max x S_max``
    array exists at any point, so an entity law with a few wide, long
    entities and a long tail of small ones costs the sum of its buckets
    (0.69 GB where the plane is 125 GB: PERF.md §4). The
    ``[E, S_max]`` tables (``proj_cols``, the model's coefficients) stay
    planes, and so does a STREAMED data set's host copy (assembled from the
    buckets: game/streaming.py slices planes).

    active_cap: numActiveDataPointsUpperBound — reservoir-cap per entity with
    count/cap weight rescale. active_lower_bound: numActiveDataPointsLowerBound
    — entities with fewer samples are not trained.
    features_to_samples_ratio: numFeaturesToSamplesRatioUpperBound — per
    entity, keep only the ceil(ratio * n_rows) features with the largest
    |Pearson(feature, label)| (RandomEffectDataset.scala:553-565).
    feature_dtype: optional narrower storage type (e.g. bfloat16) for the
    entity-block FEATURES and the ELL scoring values only — labels, offsets,
    weights and all solver state stay ``dtype``; objective products promote
    on the fly (halves the HBM traffic of the RE solve, which dominates the
    GLMix sweep).
    hbm_budget_bytes: when set and the entity blocks would exceed this many
    device bytes, the dataset is built STREAMED: blocks stay in host numpy
    and training/scoring pipeline double-buffered entity slices through the
    chip (game/streaming.py) — the out-of-core path for models bigger than
    HBM.
    """
    n = raw.n_rows
    ids = raw.id_tags[random_effect_type]
    rows, cols, vals = raw.shard_coo[feature_shard]

    # --- group rows by entity ------------------------------------------------
    # unique in the ids' native dtype (string conversion of millions of int
    # ids costs more than the whole rest of the build); entity ids are
    # stringified only in the E-sized entity_ids output below
    ids_arr = np.asarray(ids)
    if ids_arr.dtype == object:
        ids_arr = ids_arr.astype(str)
    uniq, inv = np.unique(ids_arr, return_inverse=True)
    counts = np.bincount(inv, minlength=len(uniq))

    plan = _entity_plan(
        counts, active_lower_bound, active_cap, pad_entities_to_multiple
    )
    kept_entities, old_to_block = plan.kept_entities, plan.old_to_block
    E_real, E, cap, K = plan.E_real, plan.E, plan.cap, plan.K

    # --- per-entity active selection (deterministic reservoir) ---------------
    row_ids = np.arange(n, dtype=np.int64)
    priority = _hash64(row_ids, seed)
    # sort rows by (entity, priority): active set = first K rows of each group
    entity_of_row = old_to_block[inv]
    order = np.lexsort((priority, entity_of_row))
    sorted_rows = row_ids[order]
    sorted_entity = entity_of_row[order]
    # rank within entity group
    if E_real:
        starts = np.searchsorted(sorted_entity, np.arange(E_real))
        rank = np.arange(n) - starts[np.clip(sorted_entity, 0, E_real - 1)]
        is_active = (sorted_entity >= 0) & (rank < K)
    else:
        # every entity fell below active_lower_bound: empty (padded) blocks
        rank = np.zeros(n, dtype=np.int64)
        is_active = np.zeros(n, dtype=bool)

    sel = np.nonzero(is_active)[0]
    passive = sorted_rows[~is_active & (sorted_entity >= 0)]

    # --- ELL features for all rows (scoring path) ----------------------------
    ell_idx_np, ell_val_np = _rows_to_ell(rows, cols, vals, n)

    # --- per-entity subspace projection, fully vectorized --------------------
    # (reference pipeline: RandomEffectDataset.generateLinearSubspaceProjectors
    # + project, RandomEffectDataset.scala:255-360; the reference shuffled
    # per-entity iterables through Spark — here it is one sorted/segmented
    # numpy pass over the active nnz, no per-entity Python loop, so millions
    # of entities build in seconds.)
    ae = sorted_entity[sel]  # block row per active sample        [A]
    ak = rank[sel]  # slot within block                           [A]
    ar = sorted_rows[sel]  # global sample row                    [A]

    d_shard = raw.shard_dims[feature_shard]
    fi = ell_idx_np[ar]  # [A, F] global cols of active rows
    fv = ell_val_np[ar]  # [A, F]
    nz = fv != 0.0
    # unique (entity, col) pairs, entity-major and col-ascending: exactly the
    # per-entity sorted active-index union of LinearSubspaceProjector.scala:37-90
    keys = ae[:, None].astype(np.int64) * d_shard + fi  # [A, F]
    uniq_keys = np.unique(keys[nz])
    ent_of_key = (uniq_keys // d_shard).astype(np.int64)
    col_of_key = (uniq_keys % d_shard).astype(np.int32)
    per_entity_s = np.bincount(ent_of_key, minlength=E)
    S = max(int(per_entity_s.max()) if len(uniq_keys) else 1, 1)
    key_starts = np.concatenate([[0], np.cumsum(per_entity_s)[:-1]])
    pos_within = np.arange(len(uniq_keys)) - key_starts[ent_of_key]
    proj_cols_np = np.full((E, S), -1, dtype=np.int32)
    proj_cols_np[ent_of_key, pos_within] = col_of_key

    # --- the entity blocks, bucket by bucket ---------------------------------
    # Every K-axis array is built at its bucket's own [K_b(, S_b)] and no
    # wider: what a [E, K, S] plane would hold outside the buckets is padding
    # nothing reads. The buckets are size_buckets' (the shapes the solver runs
    # at): the K runs follow the row counts alone, so a feature selection that
    # narrows the subspaces below keeps the runs and only cuts their S_b.
    entity_counts = np.zeros(E, dtype=np.int64)
    entity_counts[:E_real] = np.minimum(counts[kept_entities], K)
    chunks = plan.chunks
    chunk_rows = E // chunks
    segments = size_buckets(entity_counts, per_entity_s, K, S, chunks) or [
        (0, chunk_rows, K, S)
    ]
    seg_starts = np.asarray([seg[0] for seg in segments], dtype=np.int64)
    seg_rows = np.asarray([seg[1] - seg[0] for seg in segments], dtype=np.int64)
    position = np.arange(E, dtype=np.int64) % chunk_rows
    bucket_of = np.searchsorted(seg_starts, position, side="right") - 1  # [E]
    # a block row's row inside its bucket's arrays (chunk-major, as
    # shard_entity_blocks deals them)
    local_of = (np.arange(E) // chunk_rows) * seg_rows[bucket_of] + (
        position - seg_starts[bucket_of]
    )

    weight_scale = plan.weight_scale
    sample_bucket = bucket_of[ae]
    aa, ff = np.nonzero(nz)  # active nnz coordinates (row-major, like the
    # assignment order of the loop implementation)
    loc = np.searchsorted(uniq_keys, keys[aa, ff]) - key_starts[ae[aa]]
    nnz_bucket = sample_bucket[aa]
    fdt = np.dtype(jnp.zeros((), feature_dtype or dtype).dtype)
    sdt = np.dtype(jnp.zeros((), dtype).dtype)

    host_parts = []  # per bucket: (features, labels, offsets, weights, active_rows)
    for b, (start, end, kb, sb) in enumerate(segments):
        n_b = chunks * (end - start)
        in_b = np.flatnonzero(sample_bucket == b)
        lb, kk, rr = local_of[ae[in_b]], ak[in_b], ar[in_b]
        active_b = np.full((n_b, kb), -1, dtype=np.int64)
        labels_b = np.zeros((n_b, kb))
        offsets_b = np.zeros((n_b, kb))
        weights_b = np.zeros((n_b, kb))
        active_b[lb, kk] = rr
        labels_b[lb, kk] = raw.labels[rr]
        offsets_b[lb, kk] = raw.offsets[rr]
        weights_b[lb, kk] = raw.weights[rr] * weight_scale[ae[in_b]]
        feats_b = np.zeros((n_b, kb, sb), dtype=np.float64)
        nz_b = np.flatnonzero(nnz_bucket == b)
        a_b = aa[nz_b]
        feats_b[local_of[ae[a_b]], ak[a_b], loc[nz_b]] = fv[a_b, ff[nz_b]]
        host_parts.append([feats_b, labels_b, offsets_b, weights_b, active_b])

    if features_to_samples_ratio is not None:
        # the selection is per entity: bucket by bucket, on the bucket's own
        # arrays; kept columns are compacted to the front (stable: column
        # order preserved) and the S extents shrink to the new subspaces
        for b, (_, _, _, sb) in enumerate(segments):
            feats_b, labels_b, _, _, active_b = host_parts[b]
            # the bucket's block rows, ascending: chunk-major, as its arrays' rows
            rows_b = np.flatnonzero(bucket_of == b)
            pc_b = proj_cols_np[rows_b, :sb]
            keep = _pearson_keep_mask(
                feats_b, labels_b, active_b >= 0, pc_b, features_to_samples_ratio
            )
            order = np.argsort(~keep, axis=1, kind="stable")
            proj_cols_np[rows_b, :sb] = np.take_along_axis(
                np.where(keep, pc_b, -1), order, axis=1
            )
            host_parts[b][0] = np.take_along_axis(
                np.where(keep[:, None, :], feats_b, 0.0), order[:, None, :], axis=2
            )
            per_entity_s[rows_b] = keep.sum(axis=1)
        S = max(int(per_entity_s.max()) if E_real else 1, 1)
        proj_cols_np = proj_cols_np[:, :S]
        narrowed = size_buckets(entity_counts, per_entity_s, K, S, chunks) or [
            (0, chunk_rows, K, S)
        ]
        for b, (_, _, _, sb) in enumerate(narrowed):
            host_parts[b][0] = host_parts[b][0][:, :, :sb]
        segments = narrowed

    streamed = False
    if hbm_budget_bytes is not None:
        from .streaming import estimate_block_bytes

        streamed = estimate_block_bytes(E, K, S, fdt.itemsize) > hbm_budget_bytes

    def stored(field: int, to, shape, fill=0) -> BucketedArray:
        return BucketedArray(
            [to(parts[field]) for parts in host_parts], segments, chunks, shape, fill
        )

    if streamed:
        # host-resident blocks: train/score stream slices of PLANES
        # (game/streaming.py), assembled here from the buckets
        blocks = EntityBlocks(
            features=stored(0, lambda a: a.astype(fdt), (E, K, S)).plane(),
            labels=stored(1, lambda a: a.astype(sdt), (E, K)).plane(),
            offsets=stored(2, lambda a: a.astype(sdt), (E, K)).plane(),
            weights=stored(3, lambda a: a.astype(sdt), (E, K)).plane(),
            proj_cols=proj_cols_np.astype(np.int32),
            active_rows=stored(4, lambda a: a.astype(np.int32), (E, K), -1).plane(),
        )
    else:
        blocks = EntityBlocks(
            features=stored(0, lambda a: jnp.asarray(a, feature_dtype or dtype), (E, K, S)),
            labels=stored(1, lambda a: jnp.asarray(a, dtype), (E, K)),
            offsets=stored(2, lambda a: jnp.asarray(a, dtype), (E, K)),
            weights=stored(3, lambda a: jnp.asarray(a, dtype), (E, K)),
            proj_cols=jnp.asarray(proj_cols_np),
            active_rows=stored(4, lambda a: jnp.asarray(a.astype(np.int32)), (E, K), -1),
        )
        record_block_store(coordinate_id, blocks.store_bytes)
    del host_parts

    row_entity = np.where(entity_of_row >= 0, entity_of_row, -1).astype(np.int32)
    kept_ids = uniq[kept_entities].astype(str)
    entity_ids = np.concatenate(
        [kept_ids, np.asarray([f"__pad{i}" for i in range(E - E_real)], dtype=object)]
    ) if E > E_real else kept_ids

    return RandomEffectDataset(
        coordinate_id=coordinate_id,
        feature_shard=feature_shard,
        random_effect_type=random_effect_type,
        entity_ids=entity_ids.astype(object),
        blocks=blocks,
        row_entity=jnp.asarray(row_entity),
        ell_idx=jnp.asarray(ell_idx_np),
        ell_val=jnp.asarray(ell_val_np, feature_dtype or dtype),
        passive_rows=passive,
        entity_counts=entity_counts,
        entity_subspace_dims=per_entity_s.astype(np.int64),
        entity_chunks=plan.chunks,
        streamed=streamed,
        hbm_budget_bytes=hbm_budget_bytes if streamed else None,
    )
