"""Multi-host (multi-process) runtime scaffolding.

The reference's cluster dimension is Spark executors + treeAggregate
(GameEstimator.scala:703 treeAggregateDepth); here it is JAX multi-process:
``jax.distributed.initialize`` connects P processes (one per host), each
process reads ITS OWN row range of the input (per-host IO, the analogue of
executors reading their HDFS splits), builds process-local arrays, and
assembles them into globally-sharded ``jax.Array``s with
``jax.make_array_from_process_local_data``. The jitted objective is unchanged
— XLA collectives ride ICI within a slice and DCN across slices.

Single-process behavior is identical to before: every helper degrades to the
local path when ``jax.process_count() == 1``.

A two-process CPU smoke test lives in ``tests/test_multihost.py`` (each
process gets 4 virtual CPU devices -> a global 8-device mesh); run it
directly with::

    python -m pytest tests/test_multihost.py -q
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """``jax.distributed.initialize`` entry path (no-op when single-process
    args are absent and no cluster env is configured).

    With no arguments, auto-detection (SLURM/TPU metadata/env vars) applies;
    explicit args support the 'coordinator=HOST:PORT,process=I,n=P' CLI spec.
    """
    # must not touch the XLA backend before initialize (jax.process_count()
    # would); is_initialized only reads coordination-service state
    if jax.distributed.is_initialized():
        return
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def initialize_from_spec(spec: str) -> None:
    """Parse 'coordinator=HOST:PORT,process=I,n=P' and initialize."""
    parts = dict(p.split("=", 1) for p in spec.split(",") if p)
    unknown = set(parts) - {"coordinator", "process", "n"}
    if unknown:
        raise ValueError(
            f"unknown --distributed keys {sorted(unknown)}; "
            "expected coordinator=HOST:PORT,process=I,n=P"
        )
    initialize(
        coordinator_address=parts.get("coordinator"),
        num_processes=int(parts["n"]) if "n" in parts else None,
        process_id=int(parts["process"]) if "process" in parts else None,
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    """True on process 0 — the only process that writes models/summaries
    (the reference's driver-writes-to-HDFS role)."""
    return jax.process_index() == 0


def host_row_range(
    n_rows: int, index: Optional[int] = None, count: Optional[int] = None
) -> Tuple[int, int]:
    """This process's contiguous [start, stop) slice of a global row count
    (per-host input split; balanced to within one row)."""
    i = process_index() if index is None else index
    p = process_count() if count is None else count
    base, rem = divmod(n_rows, p)
    start = i * base + min(i, rem)
    stop = start + base + (1 if i < rem else 0)
    return start, stop


def put_global(local: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    """Assemble a globally-sharded array from per-process local data.

    Single-process: plain ``device_put``. Multi-process: the local block is
    this process's slice along the sharded dims
    (``jax.make_array_from_process_local_data``).
    """
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(local))


def host_local_rows(arr: jax.Array) -> np.ndarray:
    """This process's contiguous block of a dim-0-sharded global array, as
    host numpy (the inverse of :func:`put_global` for the local slice).

    The streamed+sharded routing uses this to hand each host ITS rows /
    entities of a global array for host-resident streaming: addressable
    shards are concatenated in dim-0 index order, so the result is exactly
    the local block this process contributed. Replicated (or single-process)
    arrays come back whole."""
    shards = sorted(
        arr.addressable_shards, key=lambda s: (s.index[0].start or 0)
    )
    parts = []
    seen = set()
    for s in shards:
        key = (s.index[0].start or 0, s.index[0].stop)
        if key in seen:  # replicated over other axes: one copy per block
            continue
        seen.add(key)
        parts.append(jax.device_get(s.data))
    return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)


def equal_host_share(n_rows: int, count: Optional[int] = None) -> int:
    """The common per-host row count every process pads its share to:
    ``ceil(n_rows / P)``. All hosts must contribute equal local shapes to
    ``make_array_from_process_local_data``; ``host_row_range`` splits to
    within one row, so hosts pad their slice to this size (zero-weight rows,
    invisible to the objectives)."""
    p = process_count() if count is None else count
    return -(-n_rows // p)


def allgather_object(obj):
    """Gather one picklable object per process; returns the process-ordered
    list on every process (single-process: ``[obj]``).

    The payload rides the device collective fabric (ICI/DCN) via
    ``multihost_utils.process_allgather`` — two rounds: sizes, then
    max-size-padded uint8 payloads. Meant for *planning metadata* (entity
    tables, shape agreements — the analogue of the reference collecting
    (entityId -> count) to the driver, RandomEffectDatasetPartitioner.scala:
    117-180), NOT for bulk row data, which stays in globally-sharded arrays.
    """
    if jax.process_count() == 1:
        return [obj]
    import pickle

    from jax.experimental import multihost_utils

    from ..robust import distributed as robust_dist

    # bounded-time rendezvous before the blocking collective: if any peer is
    # dead this raises a typed DistributedTimeoutError within the armed
    # budget instead of hanging in process_allgather forever (no-op unarmed)
    robust_dist.guard_collective("allgather_object")
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    sizes = multihost_utils.process_allgather(
        np.asarray([payload.size], np.int64)
    ).reshape(-1)
    padded = np.zeros(int(sizes.max()), np.uint8)
    padded[: payload.size] = payload
    gathered = multihost_utils.process_allgather(padded)
    return [
        pickle.loads(gathered[i, : int(sizes[i])].tobytes())
        for i in range(jax.process_count())
    ]


def broadcast_object(obj):
    """One-to-all broadcast of a picklable object FROM the coordinator
    (process 0); non-coordinators' ``obj`` is ignored. Unlike
    :func:`allgather_object` (p padded copies per host), this ships exactly
    one copy — use it for coordinator-owned payloads like checkpointed
    models. Single-process: returns ``obj`` unchanged."""
    if jax.process_count() == 1:
        return obj
    import pickle

    from jax.experimental import multihost_utils

    from ..robust import distributed as robust_dist

    robust_dist.guard_collective("broadcast_object")
    payload = (
        np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        if jax.process_index() == 0
        else np.zeros(0, np.uint8)
    )
    size = int(
        multihost_utils.broadcast_one_to_all(
            np.asarray([payload.size], np.int64)
        )[0]
    )
    padded = np.zeros(size, np.uint8)
    padded[: payload.size] = payload[:size]
    data = multihost_utils.broadcast_one_to_all(padded)
    # broadcast_one_to_all may hand the psum result back in a promoted
    # integer dtype (uint8 -> int64 under x64); reinterpreting THAT buffer
    # as bytes interleaves zeros into the pickle stream — cast back first
    return pickle.loads(np.asarray(data).astype(np.uint8).tobytes())


@functools.lru_cache(maxsize=32)
def _replicate_fn(sharding: NamedSharding):
    # cached per sharding: jit keys on function identity, so a fresh lambda
    # per call would retrace/recompile the all-gather every invocation
    return jax.jit(lambda t: t, out_shardings=sharding)


def reshard(tree, mesh: Mesh, spec: P):
    """Device-side reshard via a cached jitted identity — no host round trip
    (multi-process: inputs may be process-local/uncommitted arrays holding
    identical values on every host, e.g. a freshly built coefficient vector;
    the jit places them under `spec` with collectives as needed)."""
    return _replicate_fn(NamedSharding(mesh, spec))(tree)


def fully_replicate(tree, mesh: Mesh):
    """Reshard a pytree of (possibly non-addressable, e.g. entity-sharded)
    global arrays to fully-replicated — an XLA all-gather — so every process
    can ``np.asarray`` the result (model saving, host-side trackers: the
    reference's collect-model-to-driver step). Single-process: identity."""
    if jax.process_count() == 1:
        return tree
    return _replicate_fn(NamedSharding(mesh, P()))(tree)


def put_global_from_full(full: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    """Place an array every process holds IN FULL onto the mesh with `spec`
    (each process contributes the shards its devices own). The complement of
    ``put_global``, which takes per-process *local* blocks."""
    sharding = NamedSharding(mesh, spec)
    full = np.asarray(full)
    if jax.process_count() == 1:
        return jax.device_put(full, sharding)
    return jax.make_array_from_callback(full.shape, sharding, lambda idx: full[idx])
