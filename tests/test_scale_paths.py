"""Round-3 scale-path regressions: vectorized model projection at
non-trivial sizes, and the dealt entity layout's chunk-local
size buckets."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.data import build_random_effect_dataset
from photon_ml_tpu.models.game import RandomEffectModel
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def test_project_model_values_general_path_large(rng):
    """Vectorized sorted-key projection == naive per-entity loop, with a
    permuted-entity model whose support layout differs from the dataset's."""
    from photon_ml_tpu.game.coordinate import _project_model_values

    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=6000, d_fixed=4, re_specs={"userId": (400, 12)}, seed=3, entity_skew=1.3
        )
    )
    ds = build_random_effect_dataset(raw, "re", "userShard", "userId")
    E, S = ds.blocks.proj_cols.shape
    d_shard = raw.shard_dims["userShard"]

    # model over a permutation of the dataset's entities (plus some unseen),
    # each with its own random support
    perm = rng.permutation(E)
    model_ids = np.concatenate(
        [np.asarray(ds.entity_ids, dtype=object)[perm], np.asarray(["ghost1", "ghost2"], dtype=object)]
    )
    Em, Sm = len(model_ids), 9
    idx = np.full((Em, Sm), -1, dtype=np.int32)
    val = np.zeros((Em, Sm))
    for e in range(Em):
        k = int(rng.integers(1, Sm + 1))
        idx[e, :k] = np.sort(rng.choice(d_shard, size=k, replace=False))
        val[e, :k] = rng.normal(size=k)
    model = RandomEffectModel(
        random_effect_type="userId",
        feature_shard="userShard",
        task="logistic_regression",
        entity_ids=model_ids,
        coef_indices=jnp.asarray(idx),
        coef_values=jnp.asarray(val, jnp.float32),
    )

    got = np.asarray(
        _project_model_values(ds, model, model.coef_values, jnp.float32)
    )

    # naive reference
    rows = model.rows_for(ds.entity_ids)
    pc = np.asarray(ds.blocks.proj_cols)
    vals32 = np.asarray(model.coef_values)
    expected = np.zeros((E, S), dtype=np.float32)
    for e in range(E):
        r = rows[e]
        if r < 0:
            continue
        lookup = {int(c): vals32[r, j] for j, c in enumerate(idx[r]) if c >= 0}
        for j, c in enumerate(pc[e]):
            if c >= 0 and int(c) in lookup:
                expected[e, j] = lookup[int(c)]
    np.testing.assert_allclose(got, expected, rtol=1e-6)


def _skewed_re_dataset(m, n=3000, entities=64, seed=5, skew=1.8):
    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=n, d_fixed=4, re_specs={"userId": (entities, 8)}, seed=seed,
            entity_skew=skew,
        )
    )
    return build_random_effect_dataset(
        raw, "re", "userShard", "userId", active_cap=64, pad_entities_to_multiple=m
    )


@pytest.mark.parametrize("m", [1, 4, 8])
def test_size_buckets_are_chunk_local(m):
    """One set of bounds over the rows of ONE chunk: a bucket is the same
    local range of every chunk, and every entity fits its bucket's K and S."""
    from photon_ml_tpu.game.coordinate import _entity_shard_align, _size_buckets

    ds = _skewed_re_dataset(m)
    assert ds.entity_chunks == m
    segments = _size_buckets(ds)
    assert segments is not None and len(segments) > 1
    E = ds.blocks.features.shape[0]
    chunk_rows = E // m
    # the segments tile [0, E / m): every row of every chunk exactly once
    assert segments[0][0] == 0 and segments[-1][1] == chunk_rows
    assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
    counts = np.asarray(ds.entity_counts).reshape(m, chunk_rows)
    sdims = np.asarray(ds.entity_subspace_dims).reshape(m, chunk_rows)
    for start, end, kb, sb in segments:
        assert counts[:, start:end].max(initial=0) <= kb
        assert sdims[:, start:end].max(initial=0) <= sb
    # K shrinks along the chunk, and no bucket could take the next smaller K
    ks = [kb for _, _, kb, _ in segments]
    assert ks == sorted(ks, reverse=True) and len(set(ks)) == len(ks)
    for start, end, kb, _ in segments[:-1]:
        assert counts[:, start:end].max() > kb // 2
    # the old per-device snap is accepted and changes nothing, sharded or not
    assert _size_buckets(ds, align=chunk_rows) == segments
    if m > 1:
        from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks

        sharded = dataclasses.replace(
            ds, blocks=shard_entity_blocks(ds.blocks, data_parallel_mesh(m))
        )
        assert _entity_shard_align(sharded.blocks) == chunk_rows
        assert _size_buckets(sharded, align=chunk_rows) == segments


@pytest.mark.parametrize("m", [1, 4, 8])
def test_contiguous_segments_cover_every_block_row_once(m):
    """The streamed solve's view of the same buckets: every chunk's copy of
    every bucket as a block-row range, whole or clipped to one host's range."""
    from photon_ml_tpu.game.coordinate import _contiguous_segments, _size_buckets

    ds = _skewed_re_dataset(m)
    E = ds.blocks.features.shape[0]
    local = _size_buckets(ds)
    counts = np.asarray(ds.entity_counts)
    sdims = np.asarray(ds.entity_subspace_dims)
    for lo, hi in [(0, E), (E // 2, E), (3, E - 5)]:
        segments = _contiguous_segments(ds, entity_range=(lo, hi))
        assert segments[0][0] == 0 and segments[-1][1] == hi - lo
        assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
        for start, end, kb, sb in segments:
            assert counts[lo + start : lo + end].max(initial=0) <= kb
            assert sdims[lo + start : lo + end].max(initial=0) <= sb
    whole = _contiguous_segments(ds)
    assert whole == _contiguous_segments(ds, entity_range=(0, E))
    assert len(whole) == m * len(local)
    assert {(kb, sb) for _, _, kb, sb in whole} == {(kb, sb) for _, _, kb, sb in local}


def _stub_dataset(counts, m, cap=256, s=8):
    """What _size_buckets reads of a dataset, from a count vector alone."""
    import types

    from photon_ml_tpu.game.data import _entity_plan

    plan = _entity_plan(counts, 1, cap, m)
    by_row = np.zeros(plan.E, np.int64)
    by_row[: plan.E_real] = np.minimum(counts[plan.kept_entities], cap)
    return types.SimpleNamespace(
        entity_counts=by_row,
        entity_subspace_dims=np.where(by_row > 0, s, 0),
        entity_chunks=plan.chunks,
        blocks=types.SimpleNamespace(
            features=np.broadcast_to(np.zeros(()), (plan.E, plan.K, s))
        ),
    )


@pytest.mark.parametrize("m", [4, 8])
def test_chunk_local_padded_share_is_the_one_chunk_share(m):
    """A Zipf-1.1 size profile, 20,000 users, 25 rows a user: dealing them over
    m chunks costs at most m entities a bucket over the one sorted run."""
    from photon_ml_tpu.game.coordinate import _size_buckets

    rng = np.random.default_rng(11)
    n_users = 20_000 + 3  # not a multiple of m: pad rows ride in the last chunk
    counts = rng.permutation(
        np.floor(75_000.0 / np.arange(1, n_users + 1) ** 1.1).astype(np.int64) + 1
    )

    def padded_share(chunks):
        ds = _stub_dataset(counts, chunks)
        segments = _size_buckets(ds)
        slots = sum(chunks * (end - start) * kb for start, end, kb, _ in segments)
        return segments, 100.0 * (1.0 - ds.entity_counts.sum() / slots)

    one_segments, one = padded_share(1)
    m_segments, dealt = padded_share(m)
    assert [kb for _, _, kb, _ in m_segments] == [kb for _, _, kb, _ in one_segments]
    for (s1, e1, _, _), (sm, em, _, _) in zip(one_segments, m_segments):
        assert abs(m * (em - sm) - (e1 - s1)) < m + m  # each bound moves by under m rows
    assert one <= dealt < one + 1.0


def test_estimator_tiled_fixed_effect_matches_dense():
    """The huge-d product path: GameEstimator with layout='tiled' on a
    (data=4 x model=2) mesh + two random effects == the single-device dense
    run, through the public fit() surface."""
    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
    from photon_ml_tpu.game import GLMOptimizationConfig
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel import make_mesh

    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=800,
            d_fixed=10,
            re_specs={"userId": (24, 5), "itemId": (12, 4)},
            seed=21,
        )
    )

    def coords(layout):
        cfg = GLMOptimizationConfig(
            optimizer=OptimizerConfig(tolerance=1e-8, max_iterations=40),
            regularization=RegularizationContext("L2"),
            reg_weight=1.0,
        )
        return [
            CoordinateConfig(
                name="global", feature_shard="global", config=cfg, layout=layout
            ),
            CoordinateConfig(
                name="per-user",
                feature_shard="userShard",
                config=cfg,
                random_effect_type="userId",
            ),
            CoordinateConfig(
                name="per-item",
                feature_shard="itemShard",
                config=cfg,
                random_effect_type="itemId",
            ),
        ]

    ref = GameEstimator(
        task="logistic_regression", coordinate_configs=coords("dense"), n_cd_iterations=2
    ).fit(raw)[-1]

    mesh = make_mesh(n_data=4, n_model=2)
    tiled = GameEstimator(
        task="logistic_regression",
        coordinate_configs=coords("tiled"),
        n_cd_iterations=2,
        mesh=mesh,
    ).fit(raw)[-1]

    w_ref = np.asarray(ref.model["global"].model.coefficients.means)
    w_tiled = np.asarray(tiled.model["global"].model.coefficients.means)
    assert w_tiled.shape == w_ref.shape  # padding trimmed back to true d
    np.testing.assert_allclose(w_tiled, w_ref, rtol=2e-3, atol=2e-3)
    # the mesh run deals the entities over its data chunks: compare by id
    got, want = _by_entity_id(tiled.model["per-user"]), _by_entity_id(ref.model["per-user"])
    assert got.keys() == want.keys()
    for entity, coefs in want.items():
        assert got[entity].keys() == coefs.keys()
        np.testing.assert_allclose(
            [got[entity][c] for c in coefs], list(coefs.values()), rtol=2e-3, atol=2e-3
        )


def test_cli_trains_ell_layout(tmp_path):
    """A CLI run trains an ELL fixed effect end-to-end: the sparse layout
    is reachable from the command line, not only from the estimator."""
    from photon_ml_tpu.cli.train import run as train_run
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(
        n=400, d_fixed=8, re_specs={"userId": (10, 4)}, seed=2
    )
    schema = {
        **TRAINING_EXAMPLE_AVRO,
        "fields": TRAINING_EXAMPLE_AVRO["fields"]
        + [
            {
                "name": "userFeatures",
                "type": {"type": "array", "items": "FeatureAvro"},
                "default": [],
            }
        ],
    }
    train_path = str(tmp_path / "train.avro")
    write_avro_file(train_path, schema, generate_game_records(data))

    out = str(tmp_path / "out")
    summary = train_run(
        [
            "--input-data", train_path,
            "--validation-data", train_path,
            "--task", "logistic_regression",
            "--feature-shard", "name=global,bags=features",
            "--feature-shard", "name=userShard,bags=userFeatures",
            "--coordinate",
            "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1,layout=ell",
            "--coordinate",
            "name=per-user,shard=userShard,re.type=userId,reg.type=L2,reg.weights=1",
            "--evaluators", "AUC",
            "--output-dir", out,
        ]
    )
    assert summary["best"]["metrics"]["AUC"] > 0.6


def _re_config(tolerance=1e-9, max_iterations=50, reg_weight=0.5):
    from photon_ml_tpu.game import GLMOptimizationConfig
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optimize import OptimizerConfig

    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(tolerance=tolerance, max_iterations=max_iterations),
        regularization=RegularizationContext("L2"),
        reg_weight=reg_weight,
    )


def _by_entity_id(model):
    """{entity id: (column, value) pairs of its coefficients}, pads dropped."""
    idx, vals = np.asarray(model.coef_indices), np.asarray(model.coef_values)
    return {
        str(e): {int(c): v for c, v in zip(idx[i], vals[i]) if c >= 0}
        for i, e in enumerate(model.entity_ids)
        if not str(e).startswith("__pad")
    }


def _bucket_shape_by_entity_id(ds):
    """{entity id: the (K_b, S_b) of the bucket that solves it}."""
    from photon_ml_tpu.game.coordinate import _contiguous_segments

    return {
        str(e): (kb, sb)
        for start, end, kb, sb in _contiguous_segments(ds)
        for e in ds.entity_ids[start:end]
    }


def _assert_same_model_per_entity(got, want, exact_for):
    """Coefficients per entity id (f64 data): to rounding for the ids in
    ``exact_for``, to the solver's tolerance for the rest (different bucket
    shapes tile the reductions differently, and the L-BFGS iterations amplify
    that up to where the solver stops)."""
    assert got.keys() == want.keys()
    for entity, coefs in want.items():
        assert got[entity].keys() == coefs.keys()
        a = np.asarray([got[entity][c] for c in coefs])
        b = np.asarray(list(coefs.values()))
        if entity in exact_for:
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-10, err_msg=entity)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=entity)


def _same_bucket_shape(ds_a, ds_b, solver):
    """The entity ids the two layouts must solve bit for bit: on the vmapped
    solver (each lane's ops depend on its own bucket shape alone) those whose
    bucket has the same (K_b, S_b) in both — all but the few a chunk takes one
    position early into the larger K of the bucket before; none on the packed
    solver, whose reductions run across the bucket's lanes."""
    if solver != "vmapped":
        return set()
    a, b = _bucket_shape_by_entity_id(ds_a), _bucket_shape_by_entity_id(ds_b)
    same = {e for e in a if not e.startswith("__pad") and a[e] == b[e]}
    assert len(same) > 0.6 * len(a)
    return same


def test_bucket_operands_keep_every_row_on_its_chip():
    """A bucket's stored part holds rows [start, end) of every chunk; under
    the P(data) sharding each chip holds exactly its own chunk's rows of it,
    a state table is cut the same way, and the results go back the same way."""
    from photon_ml_tpu.game.coordinate import (
        _chunk_axis,
        _concat_results,
        _size_buckets,
        _state_rows,
    )
    from photon_ml_tpu.optimize import SolverResult
    from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks

    m = 8
    ds = _skewed_re_dataset(m)
    mesh = data_parallel_mesh(m)
    blocks = shard_entity_blocks(ds.blocks, mesh)
    sharded = _chunk_axis(blocks.features, m)
    assert sharded == (mesh, "data") and _chunk_axis(ds.blocks.features, m) is None
    E, K, S = blocks.features.shape
    chunk_rows = E // m
    host = np.asarray(ds.blocks.features)  # the logical plane, assembled
    assert blocks.features.segments == tuple(_size_buckets(ds))
    w0 = jax.device_put(
        np.arange(E * S, dtype=np.float64).reshape(E, S),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")),
    )
    parts = []
    for b, (start, end, kb, sb) in enumerate(_size_buckets(ds)):
        feats, labels = blocks.features.parts[b], blocks.labels.parts[b]
        w0_b = _state_rows(w0, m, sharded, start, end, sb)
        w0_host = _state_rows(np.asarray(w0), m, sharded, start, end, sb)
        n_b = end - start
        assert feats.shape == (m * n_b, kb, sb) and labels.shape == (m * n_b, kb)
        assert isinstance(w0_host, np.ndarray) and w0_b.shape == (m * n_b, sb)
        expected = host.reshape(m, chunk_rows, K, S)[:, start:end, :kb, :sb]
        np.testing.assert_array_equal(
            np.asarray(feats), expected.reshape(m * n_b, kb, sb)
        )
        want_w0 = np.asarray(w0).reshape(m, chunk_rows, S)[:, start:end, :sb]
        np.testing.assert_array_equal(w0_host, want_w0.reshape(-1, sb))
        np.testing.assert_array_equal(np.asarray(w0_b), w0_host)
        home = {s.device: s.index[0] for s in w0.addressable_shards}
        for shard in feats.addressable_shards:
            chunk = home[shard.device].start // chunk_rows
            assert shard.index[0] == slice(chunk * n_b, (chunk + 1) * n_b)
            np.testing.assert_array_equal(np.asarray(shard.data), expected[chunk])
        for shard in w0_b.addressable_shards:
            chunk = home[shard.device].start // chunk_rows
            assert shard.index[0] == slice(chunk * n_b, (chunk + 1) * n_b)
            np.testing.assert_array_equal(np.asarray(shard.data), want_w0[chunk])
        rows = np.arange(E).reshape(m, chunk_rows)[:, start:end].reshape(-1)
        lane = jnp.asarray(rows, jnp.int32)
        wide = jnp.asarray(np.repeat(rows[:, None], sb, axis=1), jnp.float64)
        hist = jnp.asarray(np.repeat(rows[:, None], 3, axis=1), jnp.float64)
        parts.append(
            SolverResult(
                coefficients=wide, loss=lane.astype(jnp.float64), gradient=wide,
                iterations=lane, reason=lane, loss_history=hist,
                grad_norm_history=hist, cg_iterations=lane,
            )
        )
    # every field back in block-row order, the narrow buckets zero-padded to S
    back = _concat_results(parts, S, m, sharded)
    np.testing.assert_array_equal(np.asarray(back.iterations), np.arange(E))
    np.testing.assert_array_equal(np.asarray(back.loss), np.arange(E))
    np.testing.assert_array_equal(np.asarray(back.loss_history)[:, 2], np.arange(E))
    coef = np.asarray(back.coefficients)
    assert coef.shape == (E, S)
    np.testing.assert_array_equal(coef[:, 0], np.arange(E))
    assert np.all((coef == np.arange(E)[:, None]) | (coef == 0))


@pytest.mark.parametrize("solver", ["vmapped", "packed"])
def test_chunked_bucket_solve_matches_one_chunk(use_re_solver, solver):
    """The dealt layout only reorders the block rows and reshapes the buckets:
    per entity id the solve is the one-chunk solve, sharded or not."""
    from photon_ml_tpu.game import RandomEffectCoordinate
    from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks

    use_re_solver(solver)
    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=2000, d_fixed=4, re_specs={"userId": (48, 8)}, seed=9, entity_skew=1.6
        )
    )
    cfg = _re_config()

    def train(m, mesh=None):
        ds = build_random_effect_dataset(
            raw, "re", "userShard", "userId", active_cap=64, pad_entities_to_multiple=m,
            dtype=jnp.float64,
        )
        if mesh is not None:
            ds = dataclasses.replace(ds, blocks=shard_entity_blocks(ds.blocks, mesh))
        coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=cfg)
        model, result = coord.train(None)
        assert result.iterations.shape == (ds.num_entities,)
        return ds, _by_entity_id(model), np.asarray(coord.score(model))

    ds_one, one, one_scores = train(1)
    dealt = train(8)
    # unsharded, a chunk a device, two chunks a device
    for ds, got, scores in (
        dealt, train(8, data_parallel_mesh(8)), train(8, data_parallel_mesh(4))
    ):
        exact_for = _same_bucket_shape(ds_one, ds, solver)
        _assert_same_model_per_entity(got, one, exact_for)
        np.testing.assert_allclose(scores, one_scores, rtol=1e-5, atol=1e-5)
        exact_rows = np.isin(ds_one.entity_ids[np.asarray(ds_one.row_entity)], list(exact_for))
        np.testing.assert_allclose(
            scores[exact_rows], one_scores[exact_rows], rtol=0, atol=1e-10
        )
    if solver == "vmapped":
        # the same layout, sharded or not: the same lanes in the same buckets
        np.testing.assert_allclose(scores, dealt[2], rtol=0, atol=1e-10)


@pytest.mark.parametrize("solver", ["vmapped", "packed"])
def test_mesh_fit_matches_one_device_fit_per_entity(use_re_solver, solver):
    """The same raw data through GameEstimator.fit with and without mesh=:
    per entity id the same coefficients, and the same score on every row
    (eight virtual devices: eight dealt chunks, one a device)."""
    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
    from photon_ml_tpu.game import RandomEffectCoordinate
    from photon_ml_tpu.parallel import make_mesh

    use_re_solver(solver)
    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=1800, d_fixed=4, re_specs={"userId": (53, 6)}, seed=17, entity_skew=1.5
        )
    )
    cfg = _re_config(tolerance=1e-10)
    coords = [
        CoordinateConfig(
            name="per-user", feature_shard="userShard", config=cfg,
            random_effect_type="userId", active_cap=48,
        )
    ]

    def fit(mesh):
        est = GameEstimator(
            task="logistic_regression", coordinate_configs=coords,
            n_cd_iterations=2, mesh=mesh, dtype=jnp.float64,
        )
        datasets = est.prepare_datasets(raw)
        model = est.fit(raw, datasets=datasets)[-1].model["per-user"]
        coord = RandomEffectCoordinate(
            dataset=datasets["per-user"], task="logistic_regression", config=cfg
        )
        return datasets["per-user"], _by_entity_id(model), np.asarray(coord.score(model))

    ds_one, one, one_scores = fit(None)
    ds_mesh, got, scores = fit(make_mesh(n_data=8))
    assert (ds_one.entity_chunks, ds_mesh.entity_chunks) == (1, 8)
    assert len(ds_mesh.blocks.features.sharding.device_set) == 8
    assert len(one) > 40
    exact_for = _same_bucket_shape(ds_one, ds_mesh, solver)
    _assert_same_model_per_entity(got, one, exact_for)
    assert scores.shape == one_scores.shape == (raw.n_rows,)
    np.testing.assert_allclose(scores, one_scores, rtol=1e-5, atol=1e-5)
    exact_rows = np.isin(ds_one.entity_ids[np.asarray(ds_one.row_entity)], list(exact_for))
    np.testing.assert_allclose(scores[exact_rows], one_scores[exact_rows], rtol=0, atol=1e-10)
