"""The entity-block store is ragged (PR 38): one array a size bucket, no
``[E, K, S]`` plane on the host or the device. Pinned here, on the CPU: the
build equals, bucket for bucket and bit for bit, the slices of the plane the
parent built (``_plane_build`` below is the parent's build, kept as the
oracle); a fit on the store is the fit on the plane's cuts; a long-tailed data
set builds in the memory its buckets need; the random-effect score in its
slot form equals the densified-subspace form; and the spans and counters say
what the store holds."""

import dataclasses
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.game import RandomEffectCoordinate, build_random_effect_dataset
from photon_ml_tpu.game.coordinate import (
    _bucketed_blocks,
    _chunk_rows,
    _score_cache,
    _size_buckets,
)
from photon_ml_tpu.game.data import (
    BucketedArray,
    EntityBlocks,
    _entity_plan,
    _hash64,
    _pearson_keep_mask,
    _rows_to_ell,
    bucket_plane,
    size_buckets,
)
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.io.data import RawDataset
from photon_ml_tpu.models.game import (
    ell_row_subspace,
    ell_slot_positions,
    ell_support_positions,
    score_entity_ell_at,
    score_entity_ell_at_lanes,
    score_entity_rows_dense,
    score_entity_rows_dense_lanes,
)
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import EventListener


def _plane_build(raw, feature_shard, id_tag, active_cap, chunks, ratio=None, seed=0):
    """The parent's ``build_random_effect_dataset`` up to its float64 host
    planes (commit 08c4cdd, game/data.py:684-789, comments dropped): the
    oracle the ragged build is held to."""
    n = raw.n_rows
    rows, cols, vals = raw.shard_coo[feature_shard]
    ids_arr = np.asarray(raw.id_tags[id_tag])
    if ids_arr.dtype == object:
        ids_arr = ids_arr.astype(str)
    uniq, inv = np.unique(ids_arr, return_inverse=True)
    counts = np.bincount(inv, minlength=len(uniq))
    plan = _entity_plan(counts, 1, active_cap, chunks)
    E_real, E, K = plan.E_real, plan.E, plan.K
    row_ids = np.arange(n, dtype=np.int64)
    entity_of_row = plan.old_to_block[inv]
    order = np.lexsort((_hash64(row_ids, seed), entity_of_row))
    sorted_rows, sorted_entity = row_ids[order], entity_of_row[order]
    starts = np.searchsorted(sorted_entity, np.arange(E_real))
    rank = np.arange(n) - starts[np.clip(sorted_entity, 0, E_real - 1)]
    is_active = (sorted_entity >= 0) & (rank < K)
    active = np.full((E, K), -1, dtype=np.int64)
    sel = np.nonzero(is_active)[0]
    active[sorted_entity[sel], rank[sel]] = sorted_rows[sel]
    ell_idx, ell_val = _rows_to_ell(rows, cols, vals, n)
    ae, ak, ar = sorted_entity[sel], rank[sel], sorted_rows[sel]
    labels, offsets, weights = np.zeros((E, K)), np.zeros((E, K)), np.zeros((E, K))
    labels[ae, ak] = raw.labels[ar]
    offsets[ae, ak] = raw.offsets[ar]
    weights[ae, ak] = raw.weights[ar] * plan.weight_scale[ae]
    d_shard = raw.shard_dims[feature_shard]
    fi, fv = ell_idx[ar], ell_val[ar]
    nz = fv != 0.0
    keys = ae[:, None].astype(np.int64) * d_shard + fi
    uniq_keys = np.unique(keys[nz])
    ent_of_key = (uniq_keys // d_shard).astype(np.int64)
    per_entity_s = np.bincount(ent_of_key, minlength=E)
    S = max(int(per_entity_s.max()) if len(uniq_keys) else 1, 1)
    key_starts = np.concatenate([[0], np.cumsum(per_entity_s)[:-1]])
    proj_cols = np.full((E, S), -1, dtype=np.int32)
    proj_cols[ent_of_key, np.arange(len(uniq_keys)) - key_starts[ent_of_key]] = (
        uniq_keys % d_shard
    ).astype(np.int32)
    feats = np.zeros((E, K, S), dtype=np.float64)
    aa, ff = np.nonzero(nz)
    feats[ae[aa], ak[aa], np.searchsorted(uniq_keys, keys[aa, ff]) - key_starts[ae[aa]]] = fv[aa, ff]
    if ratio is not None:
        keep = _pearson_keep_mask(feats, labels, active >= 0, proj_cols, ratio)
        order = np.argsort(~keep, axis=1, kind="stable")
        proj_cols = np.take_along_axis(np.where(keep, proj_cols, -1), order, axis=1)
        feats = np.take_along_axis(np.where(keep[:, None, :], feats, 0.0), order[:, None, :], axis=2)
        per_entity_s = keep.sum(axis=1).astype(np.int64)
        S = max(int(per_entity_s.max()) if E_real else 1, 1)
        proj_cols, feats = proj_cols[:, :S], feats[:, :, :S]
    return dict(features=feats, labels=labels, offsets=offsets, weights=weights,
                active_rows=active, proj_cols=proj_cols,
                entity_counts=np.sum(active >= 0, axis=1), entity_subspace_dims=per_entity_s)


def _dense_raw(seed=3, n=3000, users=151, d_re=6, skew=1.3):
    """The GLMix fixtures' shape: every user's subspace is the same dense
    columns, Zipf-skewed row counts (capped heads, one-row tails)."""
    return mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=n, d_fixed=4, re_specs={"userId": (users, d_re)}, seed=seed, entity_skew=skew
        )
    )


def _ragged_raw(seed=5, n=2500, users=120, dim=90, slots=4):
    """A sparse per-user shard: ``slots`` one-hot columns a row from ``dim``,
    skewed users, so subspaces run from a few columns to most of them."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, users + 1) ** 1.2
    user = rng.choice(users, size=n, p=p / p.sum())
    cols = np.concatenate(
        [rng.integers(0, dim - 1, size=(n, slots - 1)), np.full((n, 1), dim - 1)], axis=1
    )
    vals = rng.choice([-1.0, 1.0], size=(n, slots))
    vals[:, -1] = 1.0
    labels = (rng.random(n) < 0.3).astype(np.float64)
    return RawDataset(
        n_rows=n, labels=labels, offsets=rng.normal(size=n) * 0.1, weights=np.ones(n),
        shard_coo={"userShard": (np.repeat(np.arange(n), slots), cols.reshape(-1), vals.reshape(-1))},
        shard_dims={"userShard": dim}, id_tags={"userId": user},
    )


CASES = [
    ("dense", 1, 32, None), ("dense", 4, 32, None), ("dense", 1, None, None), ("dense", 4, 16, 0.5),
    ("ragged", 1, 32, None), ("ragged", 4, 32, None), ("ragged", 8, 64, None), ("ragged", 1, 32, 0.6),
]


def _case(kind, chunks, cap, ratio, dtype=jnp.float64):
    raw = _dense_raw() if kind == "dense" else _ragged_raw()
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_cap=cap,
        pad_entities_to_multiple=chunks, features_to_samples_ratio=ratio, dtype=dtype,
    )
    return raw, ds, _plane_build(raw, "userShard", "userId", cap, chunks, ratio)


@pytest.mark.parametrize("kind,chunks,cap,ratio", CASES)
def test_the_store_is_the_parents_plane_bucket_for_bucket_bit_for_bit(kind, chunks, cap, ratio):
    _, ds, plane = _case(kind, chunks, cap, ratio)
    blocks = ds.blocks
    E, K, S = plane["features"].shape
    assert blocks.bucketed and blocks.features.shape == (E, K, S)
    assert blocks.labels.shape == blocks.active_rows.shape == (E, K)
    np.testing.assert_array_equal(np.asarray(blocks.proj_cols), plane["proj_cols"])
    np.testing.assert_array_equal(ds.entity_counts, plane["entity_counts"])
    np.testing.assert_array_equal(ds.entity_subspace_dims, plane["entity_subspace_dims"])
    segments = tuple(_size_buckets(ds) or [(0, E // chunks, K, S)])
    assert blocks.features.segments == segments and ds.entity_chunks == chunks
    stored = 0
    for b, (start, end, kb, sb) in enumerate(segments):
        for field in ("features", "labels", "offsets", "weights", "active_rows"):
            part = getattr(blocks, field).parts[b]
            dims = (kb, sb) if field == "features" else (kb,)
            assert part.shape == (chunks * (end - start),) + dims
            np.testing.assert_array_equal(
                np.asarray(part), _chunk_rows(plane[field], chunks, start, end, *dims), err_msg=field
            )
        # what lies outside the bucket's extent is padding nothing reads
        outside = _chunk_rows(plane["features"], chunks, start, end)
        assert not outside[:, kb:, :].any() and not outside[:, :, sb:].any()
        assert (_chunk_rows(plane["active_rows"], chunks, start, end)[:, kb:] < 0).all()
        stored += blocks.features.parts[b].size
    assert stored == sum(p.size for p in blocks.features.parts) <= E * K * S
    # the logical planes, assembled on demand, are the parent's
    for field in ("features", "labels", "offsets", "weights", "active_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(blocks, field)), plane[field], err_msg=field)


def _config():
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(tolerance=1e-6, max_iterations=15),
        regularization=RegularizationContext("L2"), reg_weight=1.0,
    )


@pytest.mark.parametrize("kind,chunks,cap,ratio", [c for c in CASES if c[3] is None])
def test_a_fit_on_the_store_is_the_fit_on_the_planes_cuts(kind, chunks, cap, ratio):
    """The same data handed over as PLANES (the parent's arrays, as a
    hand-assembled or multi-process data set arrives) is cut once at the
    first train and solves to the same bits, warm start and residual
    included."""
    raw, ds, plane = _case(kind, chunks, cap, ratio)
    as_planes = dataclasses.replace(ds, blocks=EntityBlocks(
        features=jnp.asarray(plane["features"]), labels=jnp.asarray(plane["labels"]),
        offsets=jnp.asarray(plane["offsets"]), weights=jnp.asarray(plane["weights"]),
        proj_cols=jnp.asarray(plane["proj_cols"]),
        active_rows=jnp.asarray(plane["active_rows"].astype(np.int32)),
    ))
    assert not as_planes.blocks.bucketed and _bucketed_blocks(as_planes)[0].bucketed
    residual = jnp.asarray(np.random.default_rng(1).normal(size=raw.n_rows) * 0.3)
    outs = []
    for dataset in (ds, as_planes):
        coord = RandomEffectCoordinate(dataset=dataset, task="logistic_regression", config=_config())
        model, first = coord.train(residual)
        _, second = coord.train(residual * 0.5, initial_model=model)
        outs.append((np.asarray(model.coef_values), first, second, np.asarray(coord.score(model))))
    (coef_a, first_a, second_a, score_a), (coef_b, first_b, second_b, score_b) = outs
    np.testing.assert_array_equal(coef_a, coef_b)
    np.testing.assert_array_equal(score_a, score_b)
    for a, b in ((first_a, first_b), (second_a, second_b)):
        for field in ("coefficients", "iterations", "reason", "loss"):
            np.testing.assert_array_equal(np.asarray(getattr(a, field)), np.asarray(getattr(b, field)))
    assert np.asarray(first_a.iterations).max() > 1 and np.abs(coef_a).max() > 0


def test_a_long_tailed_data_set_builds_in_the_memory_its_buckets_need():
    """2,000 one-row users and one user of 256 rows over 500 columns: the
    plane is 2,001 x 256 x 500 x 8 = 2.05 GB in float64; the buckets are
    1 x 256 x 500 and 2,000 x 8 x 8."""
    rng = np.random.default_rng(0)
    n_small, k_big, dim = 2000, 256, 500
    n = n_small + k_big
    user = np.concatenate([np.arange(1, n_small + 1), np.zeros(k_big, np.int64)])
    # the big user's rows walk all 500 columns, two a row; a small user holds two
    cols = np.concatenate([rng.integers(0, dim, size=(n_small, 2)),
                           np.stack([np.arange(k_big) % dim, (np.arange(k_big) + 250) % dim], axis=1)])
    raw = RawDataset(
        n_rows=n, labels=(rng.random(n) < 0.5).astype(np.float64), offsets=np.zeros(n),
        weights=np.ones(n),
        shard_coo={"userShard": (np.repeat(np.arange(n), 2), cols.reshape(-1), np.ones(2 * n))},
        shard_dims={"userShard": dim}, id_tags={"userId": user},
    )
    build = lambda: build_random_effect_dataset(  # noqa: E731
        raw, "per-user", "userShard", "userId", active_cap=256, dtype=jnp.float32
    )
    build()  # imports and jit caches outside the measured build
    tracemalloc.start()
    try:
        ds = build()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    E, K, S = ds.blocks.features.shape
    assert (E, K) == (n_small + 1, 256) and S >= 400
    assert E * K * S * 8 > 1.6e9 and peak < 100e6, peak
    assert [p.shape for p in ds.blocks.features.parts] == [(1, 256, S), (n_small, 8, 8)]
    assert ds.blocks.store_bytes < 2e6
    # and it trains and scores: no plane on the way
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_config())
    tracemalloc.start()
    try:
        model, result = coord.train(None)
        scores = np.asarray(coord.score(model))
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 100e6 and np.isfinite(scores).all() and np.asarray(result.iterations).max() > 0
    assert _score_cache(ds)[0] == "slots"


# -- the container -----------------------------------------------------------------------


def test_a_bucketed_array_answers_as_the_plane_it_replaces():
    plane = np.arange(8 * 6 * 5, dtype=np.float32).reshape(8, 6, 5)
    segments = [(0, 1, 6, 5), (1, 4, 2, 3)]  # rows of ONE of two chunks of 4
    kept = np.zeros_like(plane)
    for c in range(2):
        kept[c * 4 : c * 4 + 1] = plane[c * 4 : c * 4 + 1]
        kept[c * 4 + 1 : c * 4 + 4, :2, :3] = plane[c * 4 + 1 : c * 4 + 4, :2, :3]
    for make in (np.asarray, jnp.asarray):
        store = bucket_plane(make(plane), segments, chunks=2)
        assert store.shape == (8, 6, 5) and store.dtype == np.float32 and store.ndim == 3
        assert [p.shape for p in store.parts] == [(2, 6, 5), (6, 2, 3)]
        assert store.nbytes == (2 * 6 * 5 + 6 * 2 * 3) * 4 < plane.nbytes
        np.testing.assert_array_equal(np.asarray(store), kept)
        np.testing.assert_array_equal(np.asarray(store.plane()), kept)
        leaves, treedef = jax.tree_util.tree_flatten(store)
        assert len(leaves) == 2
        again = jax.tree_util.tree_unflatten(treedef, leaves)
        assert again.segments == store.segments and again.chunks == 2 and again.shape == store.shape
    rows = bucket_plane(np.full((8, 6), 7, np.int32), [(0, 1, 6, 5), (1, 4, 2, 3)], 2, fill=-1)
    assert (np.asarray(rows)[1:4, 2:] == -1).all() and (np.asarray(rows)[0] == 7).all()
    with pytest.raises(AttributeError):
        bucket_plane(plane, segments, 2).sharding  # host parts, as a host plane
    assert "BucketedArray(shape=(8, 6, 5)" in repr(bucket_plane(plane, segments, 2))


@pytest.mark.parametrize("make", [np.asarray, jnp.asarray])
def test_a_plane_past_the_hosts_memory_is_not_assembled(make):
    """The cell's own logical extent (278,177 x 256 x 439, 125 GB in float32
    times 8 here) over a store of a few bytes: ``plane()`` and ``np.asarray``
    raise before anything is allocated (an overcommitting host would hand the
    array out and die touching it)."""
    from photon_ml_tpu.game.data import BucketedArray

    store = BucketedArray([make(np.ones((2, 8, 8), np.float32))], [(0, 2, 8, 8)], 1, (8 * 278_177, 256, 439))
    assert store.shape == (8 * 278_177, 256, 439) and store.nbytes == 2 * 8 * 8 * 4
    with pytest.raises(MemoryError, match="is not assembled"):
        store.plane()
    with pytest.raises(MemoryError, match="1000 GB"):
        np.asarray(store)


@pytest.mark.parametrize("chunks", [1, 4])
def test_size_buckets_is_the_coordinates_rule(chunks):
    _, ds, _ = _case("ragged", chunks, 32, None)
    _, K, S = ds.blocks.features.shape
    direct = size_buckets(ds.entity_counts, ds.entity_subspace_dims, K, S, chunks)
    assert direct == _size_buckets(ds) and len(direct) >= 3
    assert {sb for _, _, _, sb in direct} != {S}  # subspaces that differ: S_b does too
    assert size_buckets(np.zeros(0, np.int64), np.zeros(0, np.int64), 1, 1, 1) is None


def test_sharding_deals_every_buckets_part_over_the_axis():
    from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks

    _, ds, _ = _case("ragged", 4, 32, None)
    mesh = data_parallel_mesh(4)
    blocks = shard_entity_blocks(ds.blocks, mesh)
    assert blocks.bucketed and blocks.features.segments == ds.blocks.features.segments
    assert blocks.features.sharding.mesh == mesh and len(blocks.features.sharding.device_set) == 4
    for part, host in zip(blocks.features.parts, ds.blocks.features.parts):
        n_b = part.shape[0] // 4
        for shard in part.addressable_shards:
            assert shard.data.shape[0] == n_b  # one chunk's rows of the bucket a device
        np.testing.assert_array_equal(np.asarray(part), np.asarray(host))
    _, one, _ = _case("ragged", 1, 32, None)
    with pytest.raises(ValueError, match="pad_entities_to_multiple=4"):
        shard_entity_blocks(one.blocks, mesh)


# -- the score's two forms ----------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dense", "ragged"])
def test_the_slot_form_of_the_score_is_the_subspace_forms(kind):
    _, ds, _ = _case(kind, 1, 32, None)
    proj, rows, idx, val = ds.blocks.proj_cols, ds.row_entity, ds.ell_idx, ds.ell_val
    pos, hit = ell_slot_positions(proj, rows, idx)
    want_pos, want_hit = ell_support_positions(proj, rows, idx)
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(want_hit))
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))
    rng = np.random.default_rng(2)
    table = jnp.asarray(np.where(np.asarray(proj) >= 0, rng.normal(size=proj.shape), 0.0))
    x_sub = ell_row_subspace(proj, rows, idx, val)
    dense = np.asarray(score_entity_rows_dense(table, rows, x_sub))
    slots = np.asarray(score_entity_ell_at(table, rows, pos, hit, val))
    np.testing.assert_allclose(slots, dense, rtol=1e-12, atol=1e-12)
    assert np.abs(dense).max() > 0.1
    lanes = jnp.stack([table, 2.0 * table, -table], axis=-1)
    np.testing.assert_allclose(
        np.asarray(score_entity_ell_at_lanes(lanes, rows, pos, hit, val)),
        np.asarray(score_entity_rows_dense_lanes(lanes, rows, x_sub)), rtol=1e-12, atol=1e-12,
    )


def test_the_form_is_what_the_shapes_say():
    for kind, want in (("dense", "subspace"), ("ragged", "slots")):
        _, ds, _ = _case(kind, 1, 32, None)
        S, F = ds.blocks.proj_cols.shape[1], ds.ell_idx.shape[1]
        assert (S <= F) == (want == "subspace")
        form, cache = _score_cache(ds)
        assert form == want and _score_cache(ds)[1] is cache  # resolved once a dataset
        coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_config())
        model, _ = coord.train(None)
        general = model.score_ell_rows(ds.row_entity, ds.ell_idx, ds.ell_val)
        np.testing.assert_allclose(np.asarray(coord.score(model)), np.asarray(general), rtol=1e-10, atol=1e-12)
        lanes = jnp.stack([model.coef_values, 0.5 * model.coef_values], axis=-1)
        np.testing.assert_allclose(
            np.asarray(coord.score_lanes(lanes))[:, 1], 0.5 * np.asarray(general), rtol=1e-10, atol=1e-12
        )


# -- what the spans and counters say --------------------------------------------------------


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


@pytest.mark.parametrize("chunks", [1, 4])
def test_buckets_count_their_cells_and_the_store_its_bytes(chunks):
    _, ds, _ = _case("ragged", chunks, 32, None)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_config())
    with obs.use_run(run):
        model, _ = coord.train(None)
        coord.train(None, initial_model=model)
        coord.score(model)
    buckets = [s for s in sink.spans if s.name == "re.bucket"]
    segments = _size_buckets(ds)
    assert len(buckets) == 2 * len(segments)
    real = int((ds.entity_counts * ds.entity_subspace_dims).sum())
    for span, (start, end, kb, sb) in zip(buckets, segments):
        assert span.attrs["cells"] == chunks * (end - start) * kb * sb == span.attrs["slots"] * sb
        assert 0 < span.attrs["real_cells"] <= span.attrs["cells"] and "cut_s" in span.attrs
    assert sum(s.attrs["real_cells"] for s in buckets[: len(segments)]) == real
    snapshot = {(m["name"], m["labels"].get("kind")): m["value"] for m in run.registry.snapshot()
                if m["name"].startswith("photon_re_")}
    stored = sum(p.size for p in ds.blocks.features.parts)
    assert snapshot[("photon_re_subspace_cells_total", "real")] == 2 * real
    assert snapshot[("photon_re_subspace_cells_total", "padded")] == 2 * (stored - real)
    assert snapshot[("photon_re_block_store_bytes", None)] == ds.blocks.store_bytes
    E, K, S = ds.blocks.features.shape
    assert ds.blocks.store_bytes < E * K * S * 8
    (score,) = [s for s in sink.spans if s.name == "re.score"]
    assert score.attrs["form"] == "slots"
    warm = [s.attrs["warm"] for s in sink.spans if s.name == "re.warm_start"]
    assert warm == [False, True]


def test_the_build_sets_the_stores_gauge():
    run = obs.RunTelemetry()
    with obs.use_run(run):
        _, ds, _ = _case("dense", 1, 32, None)
    (gauge,) = [m for m in run.registry.snapshot() if m["name"] == "photon_re_block_store_bytes"]
    assert gauge["labels"] == {"coordinate": "per-user"} and gauge["value"] == ds.blocks.store_bytes


def test_a_fixed_effect_solve_says_whether_it_was_warm_and_under_residuals():
    from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
    from photon_ml_tpu.game.data import build_fixed_effect_dataset

    raw = _dense_raw(n=600, users=20)
    ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
    coord = FixedEffectCoordinate(dataset=ds, task="logistic_regression", config=_config())
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        model, _ = coord.train(None)
        coord.train(jnp.asarray(np.random.default_rng(0).normal(size=raw.n_rows)), initial_model=model)
    solves = [s for s in sink.spans if s.name == "fe.solve"]
    assert [(s.attrs["warm"], s.attrs["offsets"]) for s in solves] == [(False, False), (True, True)]
    assert all(s.attrs["iterations"] >= 1 for s in solves)  # sink only: rides the counts' fetch
