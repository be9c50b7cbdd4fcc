"""Device mesh + sharding helpers: the distributed runtime layer.

This replaces the reference's Spark wrappers (SURVEY.md §2.1 / L1:
RDDLike/BroadcastLike, treeAggregate, broadcast, partitioner-aware joins)
with JAX sharding primitives:

- ``treeAggregate`` of gradient accumulators  -> jit over a batch sharded on
  the DATA axis; ``jnp.sum``/``rmatvec`` reductions lower to ICI all-reduces.
- coefficient ``broadcast``                   -> replicated NamedSharding.
- entity-partitioned random effects (P5)     -> entity blocks sharded on dim 0
  (each device owns an entity range); the vmapped solver is embarrassingly
  parallel across lanes.
- huge-d coefficient vectors                  -> shard the FEATURE axis on a
  second mesh dim ("model"); margins become partial dots + psum, gradients
  reduce-scatter (the analogue of scaling "hundreds of billions of
  coefficients", README.md:56).

Multi-host: `jax.distributed.initialize()` (parallel/multihost.py) + the same
code — collectives ride ICI within a slice and DCN across slices. Placement
helpers route through ``multihost.put_global``: single-process they are plain
``device_put``; multi-process each process contributes its local block (its
per-host row range / entity range) and the result is one globally-sharded
``jax.Array``. In multi-process mode every process must contribute equal
local shapes (pad per-host shares to ``multihost.equal_host_share``), and
only DATA-axis sharding is supported — model-axis sharding across processes
would need per-host coefficient slices and is rejected explicitly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.features import FeatureMatrix, LabeledBatch, pad_batch
from .multihost import put_global

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    n_data: Optional[int] = None, n_model: int = 1, devices=None
) -> Mesh:
    """Build a (data[, model]) mesh over available devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = len(devices) // n_model
    use = n_data * n_model
    arr = np.asarray(devices[:use]).reshape(n_data, n_model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def data_parallel_mesh(n: Optional[int] = None, devices=None) -> Mesh:
    return make_mesh(n_data=n, n_model=1, devices=devices)


def pad_rows_for_mesh(batch: LabeledBatch, mesh: Mesh) -> LabeledBatch:
    """Zero-weight-pad the batch so the row count divides the data axis
    (multi-process: the LOCAL row count must divide the local share of the
    data axis)."""
    n_data = mesh.shape[DATA_AXIS]
    if jax.process_count() > 1:
        n_data = max(n_data // jax.process_count(), 1)
    n = batch.n_rows
    target = ((n + n_data - 1) // n_data) * n_data
    return pad_batch(batch, target)


def shard_batch(
    batch: LabeledBatch, mesh: Mesh, shard_features_dim: bool = False
) -> LabeledBatch:
    """Place a batch on the mesh: rows sharded over the data axis; feature
    columns optionally sharded over the model axis (dense layout only)."""
    batch = pad_rows_for_mesh(batch, mesh)
    row_spec = P(DATA_AXIS)
    put1 = lambda a: put_global(a, mesh, row_spec)
    f = batch.features
    if f.is_dense:
        if shard_features_dim:
            _reject_multiprocess_model_axis()
        spec = P(DATA_AXIS, MODEL_AXIS if shard_features_dim else None)
        feats = FeatureMatrix(dim=f.dim, dense=put_global(f.dense, mesh, spec))
    else:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "multi-process ELL sharding is not supported: the ELL width "
                "is the max nnz of the LOCAL rows, so per-host shapes (and "
                "the compiled programs) would disagree; use a dense layout "
                "(d <= 4096) for multi-process runs"
            )
        spec = P(DATA_AXIS, None)
        feats = FeatureMatrix(
            dim=f.dim,
            idx=put_global(f.idx, mesh, spec),
            val=put_global(f.val, mesh, spec),
        )
    return LabeledBatch(
        features=feats,
        labels=put1(batch.labels),
        offsets=put1(batch.offsets),
        weights=put1(batch.weights),
    )


def replicate(tree, mesh: Mesh):
    """Replicated placement (the reference's coefficient broadcast, P4).
    Multi-process: every process must hold the full (identical) array."""
    return jax.tree_util.tree_map(lambda a: put_global(a, mesh, P()), tree)


def _reject_multiprocess_model_axis():
    if jax.process_count() > 1:
        raise NotImplementedError(
            "model-axis sharding across processes is not supported yet: "
            "callers pass full arrays, but each process may only contribute "
            "its own model-axis slice; multi-process runs shard the data "
            "axis only"
        )


def shard_coefficients(w: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Shard a coefficient vector over the model axis (huge-d regime)."""
    _reject_multiprocess_model_axis()
    return put_global(w, mesh, P(MODEL_AXIS))


def shard_entity_blocks(blocks, mesh: Mesh):
    """Shard EntityBlocks on the entity dim over the data axis (P5): each
    device takes a contiguous range of block rows (of every bucket's array
    its own chunks' rows). A dataset built with
    ``pad_entities_to_multiple`` = the axis size holds one size-sorted chunk
    of equal load per device (game/data.py ``_entity_plan``)."""
    n_data = mesh.shape[DATA_AXIS]
    E = blocks.features.shape[0]
    if E % n_data != 0:
        raise ValueError(
            f"entity count {E} must divide the data axis ({n_data}); "
            f"build the dataset with pad_entities_to_multiple={n_data}, which "
            f"also deals the entities over the axis so that every device "
            f"holds the same load"
        )

    def put(a):
        # the blocks are stored one array a size bucket, chunk-major
        # (game/data.py BucketedArray, a pytree over its parts): a part's
        # leading axis over ``data`` puts chunk c of every bucket on the
        # device that holds chunk c
        if a.shape[0] % n_data != 0:
            raise ValueError(
                f"an entity-block bucket of {a.shape[0]} rows does not divide "
                f"the data axis ({n_data}); build the dataset with "
                f"pad_entities_to_multiple={n_data}, which deals every size "
                f"bucket over the axis in equal shares"
            )
        spec = P(*([DATA_AXIS] + [None] * (a.ndim - 1)))
        return put_global(a, mesh, spec)

    return jax.tree_util.tree_map(put, blocks)
