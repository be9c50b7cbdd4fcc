"""The residual exchange of ``RandomEffectCoordinate.train`` gathers only the
slots its buckets solve (``_bucket_offsets``, one program a train call), from
the entity blocks as they are STORED: one array a size bucket. The plain
reference is the expression it replaced: the residual gathered into the WHOLE
[E, K] plane (assembled here from the store, ``BucketedArray.plane``), from
which each bucket's rows were then cut. A gather is exact, so everything here
is compared bit for bit. CPU only; no timing."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from photon_ml_tpu import obs
from photon_ml_tpu.game import RandomEffectCoordinate, build_random_effect_dataset
from photon_ml_tpu.game.coordinate import (
    _bucket_offsets,
    _bucketed_blocks,
    _chunk_axis,
    _chunk_rows,
    _concat_results,
    _size_buckets,
    _train_blocks_packed,
)
from photon_ml_tpu.game.data import EntityBlocks, bucket_plane
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks
from photon_ml_tpu.robust import faults
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import EventListener

N_ROWS, ACTIVE_CAP = 4000, 32
CHUNKS = [1, 4, 8]


def _dataset(chunks, with_counts=True):
    """203 users under Zipf 1.2: the head is over the cap (passive rows), the
    tail has one row a user; dealt over ``chunks`` and sharded when > 1. The
    blocks' own offsets are made distinct in EVERY slot, padding included."""
    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=N_ROWS, d_fixed=4, re_specs={"userId": (203, 4)}, seed=3, entity_skew=1.2
        )
    )
    dataset = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_cap=ACTIVE_CAP,
        pad_entities_to_multiple=chunks, dtype=jnp.float64,
    )
    counts = np.asarray(dataset.entity_counts)
    assert len(dataset.passive_rows) > 0 and counts.max() == ACTIVE_CAP
    assert (counts == 1).sum() > 0
    blocks = dataset.blocks
    E, K, _ = blocks.features.shape
    own = 0.25 + np.arange(E * K, dtype=np.float64).reshape(E, K) / (4 * E * K)
    blocks = dataclasses.replace(
        blocks, offsets=bucket_plane(jnp.asarray(own), blocks.features.segments, chunks)
    )
    if not with_counts:
        # a data set handed over as PLANES with no per-entity statistics: one
        # whole-extent bucket, cut (trivially) at the first train
        blocks = EntityBlocks(
            features=blocks.features.plane(), labels=blocks.labels.plane(),
            offsets=blocks.offsets.plane(), weights=blocks.weights.plane(),
            proj_cols=blocks.proj_cols, active_rows=blocks.active_rows.plane(),
        )
    if chunks > 1:
        blocks = shard_entity_blocks(blocks, data_parallel_mesh(chunks))
    dataset = dataclasses.replace(dataset, blocks=blocks)
    assert dataset.entity_chunks == chunks
    if not with_counts:
        dataset = dataclasses.replace(
            dataset, entity_counts=None, entity_subspace_dims=None
        )
    return dataset


def _residual(dataset, chunks):
    """Distinct in every row, row 0 (where the -1 slots' clamped index points)
    far from zero; row-sharded over the mesh as the CD loop's is."""
    residual = jnp.asarray(3.0 + np.arange(N_ROWS, dtype=np.float64) / 7.0)
    if chunks > 1:
        mesh = dataset.blocks.features.sharding.mesh
        residual = jax.device_put(residual, NamedSharding(mesh, PartitionSpec("data")))
    return residual


def _plane(dataset, residual):
    """The parent's exchange: the whole [E, K] plane of solver offsets."""
    blocks = dataset.blocks
    active, own = np.asarray(blocks.active_rows), np.asarray(blocks.offsets)
    return own + np.asarray(residual)[np.maximum(active, 0)] * (active >= 0)


def _coordinate(dataset):
    config = GLMOptimizationConfig(
        optimizer=OptimizerConfig(tolerance=1e-6, max_iterations=10),
        regularization=RegularizationContext("L2"),
        reg_weight=1.0,
    )
    return RandomEffectCoordinate(
        dataset=dataset, task="logistic_regression", config=config
    )


def _exchange(dataset, residual):
    chunks = dataset.entity_chunks
    blocks, _ = _bucketed_blocks(dataset)
    return _bucket_offsets(
        blocks.active_rows.parts, blocks.offsets.parts, residual,
        chunks=chunks, sharded=_chunk_axis(blocks.features, chunks),
    )


@pytest.mark.parametrize("chunks", CHUNKS)
def test_every_bucket_gets_the_planes_bits(chunks):
    dataset = _dataset(chunks)
    residual = _residual(dataset, chunks)
    segments = _size_buckets(dataset)
    assert len(segments) >= 3
    plane = _plane(dataset, residual)
    active = np.asarray(dataset.blocks.active_rows)
    own = np.asarray(dataset.blocks.offsets)
    got = _exchange(dataset, residual)
    assert dataset.blocks.features.segments == tuple(segments)
    assert len(got) == len(segments)
    gathered = 0
    for b, ((start, end, kb, _), offsets) in enumerate(zip(segments, got)):
        assert offsets.shape == (chunks * (end - start), kb)
        assert offsets.dtype == dataset.blocks.offsets.dtype
        np.testing.assert_array_equal(
            np.asarray(offsets), _chunk_rows(plane, chunks, start, end, kb)
        )
        # a padding slot keeps the block's own offset: nothing of row 0's
        # residual, at which its clamped index points, is left in it
        pad = _chunk_rows(active, chunks, start, end, kb) < 0
        assert pad.any()
        np.testing.assert_array_equal(
            np.asarray(offsets)[pad], _chunk_rows(own, chunks, start, end, kb)[pad]
        )
        # every real row of the bucket is inside the columns it keeps
        assert (_chunk_rows(active, chunks, start, end)[:, kb:] < 0).all()
        gathered += offsets.size
        if chunks > 1:
            assert offsets.sharding.is_equivalent_to(
                dataset.blocks.offsets.parts[b].sharding, 2
            )
    E, K, _ = dataset.blocks.features.shape
    assert gathered < E * K // 2


@pytest.mark.parametrize("chunks", CHUNKS)
def test_without_a_residual_the_buckets_are_the_blocks_own_offsets(chunks):
    dataset = _dataset(chunks)
    segments = _size_buckets(dataset)
    own = np.asarray(dataset.blocks.offsets)
    for (start, end, kb, _), offsets in zip(segments, _exchange(dataset, None)):
        np.testing.assert_array_equal(
            np.asarray(offsets), _chunk_rows(own, chunks, start, end, kb)
        )


@pytest.mark.parametrize("chunks", CHUNKS)
def test_without_entity_statistics_the_one_segment_is_the_plane(chunks):
    dataset = _dataset(chunks, with_counts=False)
    assert _size_buckets(dataset) is None
    residual = _residual(dataset, chunks)
    E, K, S = dataset.blocks.features.shape
    (whole,) = _exchange(dataset, residual)
    np.testing.assert_array_equal(np.asarray(whole), _plane(dataset, residual))
    # and train() runs that one path: the span says all of the plane is gathered
    run, spans = obs.RunTelemetry(), []
    run.register_listener(_Spans(spans))
    with obs.use_run(run):
        _, result = _coordinate(dataset).train(residual)
    (exchange,) = [s for s in spans if s.name == "re.exchange"]
    assert exchange.attrs["slots"] == exchange.attrs["block_slots"] == E * K
    reference = _train_blocks_packed(
        dataset.blocks.features, dataset.blocks.labels,
        jax.device_put(_plane(dataset, residual), dataset.blocks.offsets.sharding),
        dataset.blocks.weights, *_zero_state(E, S),
        **_coordinate(dataset)._solver_kwargs(),
    )
    _assert_same_solve(result, reference)


class _Spans(EventListener):
    def __init__(self, into):
        self.into = into

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.into.append(event.span)


def _zero_state(E, S):
    # host numpy, as train() keeps w0 and the priors on the CPU backend
    return np.zeros((E, S)), np.zeros((E, S)), np.ones((E, S))


def _assert_same_solve(result, reference):
    for field in ("coefficients", "iterations", "reason", "loss"):
        np.testing.assert_array_equal(
            np.asarray(getattr(result, field)), np.asarray(getattr(reference, field)),
            err_msg=field,
        )


@pytest.mark.parametrize("chunks", CHUNKS)
def test_train_is_the_packed_solver_fed_the_planes_buckets(chunks):
    dataset = _dataset(chunks)
    residual = _residual(dataset, chunks)
    coordinate = _coordinate(dataset)
    run, spans = obs.RunTelemetry(), []
    run.register_listener(_Spans(spans))
    with obs.use_run(run):
        model, result = coordinate.train(residual)

    blocks = dataset.blocks
    E, K, S = blocks.features.shape
    sharded = _chunk_axis(blocks.features, chunks)
    plane = _plane(dataset, residual)
    # the parent's operands: every bucket cut from the planes, placed as the
    # store's own part is
    features, labels, weights = (
        np.asarray(a) for a in (blocks.features, blocks.labels, blocks.weights)
    )
    parts = []
    for b, (start, end, kb, sb) in enumerate(_size_buckets(dataset)):
        place = partial(jax.device_put, device=blocks.labels.parts[b].sharding)
        n_b = chunks * (end - start)
        parts.append(
            _train_blocks_packed(
                jax.device_put(
                    _chunk_rows(features, chunks, start, end, kb, sb),
                    blocks.features.parts[b].sharding,
                ),
                place(_chunk_rows(labels, chunks, start, end, kb)),
                place(_chunk_rows(plane, chunks, start, end, kb)),
                place(_chunk_rows(weights, chunks, start, end, kb)),
                *_zero_state(n_b, sb),
                **coordinate._solver_kwargs(),
            )
        )
    reference = _concat_results(parts, S, chunks, sharded)
    _assert_same_solve(result, reference)
    assert np.asarray(result.iterations).max() > 1
    np.testing.assert_array_equal(
        np.asarray(model.coef_values),
        np.where(np.asarray(blocks.proj_cols) >= 0, np.asarray(reference.coefficients), 0.0),
    )
    # the span counts what was gathered: the buckets' slots, of the plane's
    (exchange,) = [s for s in spans if s.name == "re.exchange"]
    solved = sum(s.attrs["slots"] for s in spans if s.name == "re.bucket")
    assert (exchange.attrs["slots"], exchange.attrs["block_slots"]) == (solved, E * K)
    assert solved == sum(chunks * (e - s) * kb for s, e, kb, _ in _size_buckets(dataset))


@pytest.mark.parametrize("chunks", CHUNKS)
def test_the_nan_fault_poisons_exactly_one_entity_lane(chunks):
    dataset = _dataset(chunks)
    residual = _residual(dataset, chunks)
    coordinate = _coordinate(dataset)
    _, clean = coordinate.train(residual)
    faults.configure("solver.value_and_grad:nan:1")
    try:
        with obs.use_run(obs.RunTelemetry()):
            _, result = coordinate.train(residual)
    finally:
        faults.clear()
    # entity 0, slot 0 (the largest entity of chunk 0) is flat index 0 of the
    # first bucket's offsets, as it was of the plane: its lane alone is lost
    loss = np.asarray(result.loss)
    assert not np.isfinite(loss[0]) and np.isfinite(loss[1:]).all()
    np.testing.assert_array_equal(loss[1:], np.asarray(clean.loss)[1:])
    np.testing.assert_array_equal(
        np.asarray(result.coefficients)[1:], np.asarray(clean.coefficients)[1:]
    )
