"""GAME engine tests: entity-blocked datasets, batched random-effect solves,
coordinate descent with residual exchange, locked coordinates, warm starts.

Mirrors the reference's photon-api integTest strategy (GameTestUtils-style
synthetic mixed-effect data + exact per-entity cross-checks vs scipy)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.evaluation import area_under_roc_curve, build_suite
from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectCoordinate,
    GLMOptimizationConfig,
    ModelCoordinate,
    RandomEffectCoordinate,
    ValidationContext,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset


def _cfg(l2=1.0, tol=1e-9, iters=200, opt="LBFGS"):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType(opt), tolerance=tol, max_iterations=iters
        ),
        regularization=RegularizationContext("L2"),
        reg_weight=l2,
    )


@pytest.fixture(scope="module")
def mixed():
    data = generate_mixed_effect_data(
        n=1500, d_fixed=8, re_specs={"userId": (30, 4)}, seed=7, entity_skew=1.2
    )
    raw = mixed_data_to_raw_dataset(data)
    return data, raw


def test_re_dataset_structure(mixed):
    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    E = ds.num_entities
    assert E == 30
    blocks = ds.blocks
    # every non-padded block cell must reproduce its source row's features
    ar = np.asarray(blocks.active_rows)
    feats = np.asarray(blocks.features)
    pc = np.asarray(blocks.proj_cols)
    rows, cols, vals = raw.shard_coo["userShard"]
    dense = np.zeros((raw.n_rows, raw.shard_dims["userShard"]))
    dense[rows, cols] = vals
    checked = 0
    for e in range(min(E, 5)):
        for k in range(blocks.rows_per_entity):
            r = ar[e, k]
            if r < 0:
                continue
            proj = np.zeros(raw.shard_dims["userShard"])
            m = pc[e] >= 0
            proj[pc[e][m]] = feats[e, k][m]
            np.testing.assert_allclose(proj, dense[r], atol=1e-12)
            checked += 1
    assert checked > 10
    # row_entity consistent with id tags
    re_ids = raw.id_tags["userId"]
    row_entity = np.asarray(ds.row_entity)
    for i in range(0, raw.n_rows, 97):
        e = row_entity[i]
        assert str(ds.entity_ids[e]) == str(re_ids[i])
    # all rows active (no cap) -> no passive rows
    assert len(ds.passive_rows) == 0


def test_re_dataset_active_cap_and_weights(mixed):
    data, raw = mixed
    cap = 20
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_cap=cap, dtype=jnp.float64
    )
    blocks = ds.blocks
    assert blocks.rows_per_entity == cap
    counts = {}
    for i, e in enumerate(raw.id_tags["userId"]):
        counts[str(e)] = counts.get(str(e), 0) + 1
    w = np.asarray(blocks.weights)
    ar = np.asarray(blocks.active_rows)
    for e in range(ds.num_entities):
        ent = str(ds.entity_ids[e])
        cnt = counts[ent]
        n_active = int((ar[e] >= 0).sum())
        if cnt > cap:
            assert n_active == cap
            # weight rescale count/cap (reservoir semantics)
            np.testing.assert_allclose(w[e][ar[e] >= 0], cnt / cap, rtol=1e-12)
        else:
            assert n_active == cnt
    # passive rows = total - sum(active)
    assert len(ds.passive_rows) == raw.n_rows - int((ar >= 0).sum())


def test_re_dataset_lower_bound(mixed):
    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_lower_bound=30, dtype=jnp.float64
    )
    counts = {}
    for e in raw.id_tags["userId"]:
        counts[str(e)] = counts.get(str(e), 0) + 1
    kept = {str(i) for i in ds.entity_ids if not str(i).startswith("__pad")}
    assert kept == {k for k, v in counts.items() if v >= 30}
    # rows of dropped entities have row_entity == -1
    row_entity = np.asarray(ds.row_entity)
    for i in range(0, raw.n_rows, 131):
        if str(raw.id_tags["userId"][i]) not in kept:
            assert row_entity[i] == -1


def test_re_coordinate_matches_per_entity_scipy(mixed):
    """The vmapped batched solver must reach each entity's own optimum."""
    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    lam = 0.5
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_cfg(l2=lam))
    model, result = coord.train(None, None)

    # check a few entities against scipy on their exact local data
    rows_all, cols_all, vals_all = raw.shard_coo["userShard"]
    dense = np.zeros((raw.n_rows, raw.shard_dims["userShard"]))
    dense[rows_all, cols_all] = vals_all
    ids = raw.id_tags["userId"]
    for e in [0, 7, 19]:
        ent = str(ds.entity_ids[e])
        m = np.asarray([str(i) == ent for i in ids])
        x_e, y_e = dense[m], raw.labels[m]

        def f(w):
            z = x_e @ w
            v = np.sum(np.log1p(np.exp(-np.abs(z))) + np.maximum(z, 0) - y_e * z)
            g = x_e.T @ (1 / (1 + np.exp(-z)) - y_e)
            return v + 0.5 * lam * w @ w, g + lam * w

        r = scipy.optimize.minimize(
            f, np.zeros(x_e.shape[1]), jac=True, method="L-BFGS-B",
            options=dict(maxiter=500, ftol=1e-15, gtol=1e-12),
        )
        w_ref = r.x
        pc = np.asarray(ds.blocks.proj_cols)[e]
        w_impl = np.zeros(x_e.shape[1])
        mvalid = pc >= 0
        w_impl[pc[mvalid]] = np.asarray(model.coef_values)[e][mvalid]
        np.testing.assert_allclose(w_impl, w_ref, atol=2e-4)

    # scoring: row scores match manual dot products
    scores = np.asarray(coord.score(model))
    w_dense = model.dense_coefficients(raw.shard_dims["userShard"])
    erow = model.rows_for([str(i) for i in ids])
    expected = np.einsum("nd,nd->n", dense, w_dense[np.maximum(erow, 0)])
    expected[erow < 0] = 0.0
    np.testing.assert_allclose(scores, expected, atol=1e-8)


def test_coordinate_descent_fixed_plus_random(mixed):
    data, raw = mixed
    fe_ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
    re_ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    coords = {
        "global": FixedEffectCoordinate(
            dataset=fe_ds, task="logistic_regression", config=_cfg(l2=1.0)
        ),
        "per-user": RandomEffectCoordinate(
            dataset=re_ds, task="logistic_regression", config=_cfg(l2=1.0)
        ),
    }
    suite = build_suite(["AUC"], raw.labels)
    validation = ValidationContext(
        suite=suite,
        score_fns={
            "global": lambda m: coords["global"].score(m),
            "per-user": lambda m: coords["per-user"].score(m),
        },
        offsets=raw.offsets,
    )
    cd = CoordinateDescent(coords, n_iterations=2, validation=validation)
    result = cd.run()
    assert set(result.model.coordinates()) == {"global", "per-user"}
    assert len(result.evaluations) == 4  # 2 iters x 2 coordinates

    # GAME model must beat fixed-effect-only AUC (random effects explain the
    # per-entity structure the fixed model can't)
    fixed_only, _ = coords["global"].train(None, None)
    auc_fixed = area_under_roc_curve(coords["global"].score(fixed_only), raw.labels)
    auc_game = result.best_evaluation.primary_metric
    assert auc_game > auc_fixed + 0.03
    # and clear an absolute bar
    assert auc_game > 0.75


def test_coordinate_descent_residuals_improve_loss(mixed):
    """Second CD iteration must not degrade the training objective."""
    data, raw = mixed
    fe_ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
    re_ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    coords = {
        "global": FixedEffectCoordinate(
            dataset=fe_ds, task="logistic_regression", config=_cfg(l2=1.0)
        ),
        "per-user": RandomEffectCoordinate(
            dataset=re_ds, task="logistic_regression", config=_cfg(l2=1.0)
        ),
    }
    suite = build_suite(["LOGISTIC_LOSS"], raw.labels)
    validation = ValidationContext(
        suite=suite,
        score_fns={
            "global": lambda m: coords["global"].score(m),
            "per-user": lambda m: coords["per-user"].score(m),
        },
        offsets=raw.offsets,
    )
    cd = CoordinateDescent(coords, n_iterations=3, validation=validation)
    result = cd.run()
    losses = [r.primary_metric for _, r in result.evaluations]
    # loss after the full first sweep should improve or hold across sweeps
    assert losses[-1] <= losses[1] + 1e-6


def test_locked_coordinate_partial_retrain(mixed):
    data, raw = mixed
    fe_ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
    re_ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    fe = FixedEffectCoordinate(dataset=fe_ds, task="logistic_regression", config=_cfg())
    re = RandomEffectCoordinate(dataset=re_ds, task="logistic_regression", config=_cfg())
    pretrained, _ = fe.train(None, None)
    locked = ModelCoordinate(inner=fe, locked_model=pretrained)
    cd = CoordinateDescent({"global": locked, "per-user": re}, n_iterations=1)
    result = cd.run()
    # locked model passes through unchanged
    np.testing.assert_allclose(
        np.asarray(result.model["global"].model.coefficients.means),
        np.asarray(pretrained.model.coefficients.means),
    )

    # all-locked must be rejected (checkInvariants parity)
    with pytest.raises(ValueError):
        CoordinateDescent({"global": locked}, n_iterations=1)


def test_warm_start_same_layout(mixed):
    data, raw = mixed
    re_ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    coord = RandomEffectCoordinate(dataset=re_ds, task="logistic_regression", config=_cfg())
    m1, r1 = coord.train(None, None)
    # warm start from the optimum: should converge almost immediately
    m2, r2 = coord.train(None, m1)
    assert int(np.asarray(r2.iterations).max()) <= 3
    np.testing.assert_allclose(
        np.asarray(m2.coef_values), np.asarray(m1.coef_values), atol=1e-4
    )


def test_down_sampling_smoke(mixed):
    data, raw = mixed
    fe_ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
    cfg = dataclasses.replace(_cfg(l2=1.0), down_sampling_rate=0.5)
    coord = FixedEffectCoordinate(dataset=fe_ds, task="logistic_regression", config=cfg)
    model, _ = coord.train(None, None)
    auc = area_under_roc_curve(coord.score(model), raw.labels)
    assert auc > 0.6  # still learns on half the negatives


def test_re_score_with_reordered_model_entities(mixed):
    """A model whose entity-row order differs from the dataset's must still
    score rows by entity id (review regression: warm-start/locked models)."""
    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_cfg())
    model, _ = coord.train(None, None)
    base = np.asarray(coord.score(model))

    # permute the model's entity rows
    perm = np.random.default_rng(0).permutation(model.num_entities)
    shuffled = type(model)(
        random_effect_type=model.random_effect_type,
        feature_shard=model.feature_shard,
        task=model.task,
        entity_ids=model.entity_ids[perm],
        coef_indices=model.coef_indices[perm],
        coef_values=model.coef_values[perm],
    )
    np.testing.assert_allclose(np.asarray(coord.score(shuffled)), base, atol=1e-12)


def test_re_score_cached_positions_match_general_path(mixed):
    """The CD hot path densifies row features into entity-subspace layout
    once per dataset (models/game.py ell_row_subspace); it must equal the
    general searchsorted-per-call path (same values summed in subspace
    instead of ELL order — f64 tolerance at 1e-12), on first AND repeat
    calls."""
    from photon_ml_tpu.models.game import score_entity_ell

    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_cfg())
    model, _ = coord.train(None, None)
    assert coord._support_layout_matches(model)
    general = np.asarray(
        score_entity_ell(
            model.coef_indices,
            jnp.asarray(model.coef_values, ds.ell_val.dtype),
            ds.row_entity,
            ds.ell_idx,
            ds.ell_val,
        )
    )
    first = np.asarray(coord.score(model))
    again = np.asarray(coord.score(model))  # cache hit
    assert getattr(ds, "_score_form_cache", None) is not None
    np.testing.assert_allclose(first, general, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(again, general, rtol=1e-12, atol=1e-12)

    # a second trained model (new values, same layout) reuses the cache
    model2, _ = coord.train(coord.score(model), initial_model=model)
    np.testing.assert_allclose(
        np.asarray(coord.score(model2)),
        np.asarray(
            score_entity_ell(
                model2.coef_indices,
                jnp.asarray(model2.coef_values, ds.ell_val.dtype),
                ds.row_entity,
                ds.ell_idx,
                ds.ell_val,
            )
        ),
        rtol=1e-12,
        atol=1e-12,
    )


def test_re_dataset_all_entities_below_lower_bound(mixed):
    """No entity meeting the lower bound must yield empty padded blocks, not a
    crash (review regression)."""
    data, raw = mixed
    ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_lower_bound=10**9,
        dtype=jnp.float64,
    )
    assert np.all(np.asarray(ds.row_entity) == -1)
    assert np.all(np.asarray(ds.blocks.weights) == 0.0)
    # scoring a model trained on the empty dataset gives zeros
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_cfg())
    m, _ = coord.train(None, None)
    np.testing.assert_allclose(np.asarray(coord.score(m)), 0.0)


def test_random_effect_model_pickles_after_training(mixed):
    """Trained RE models carry a weakref provenance mark for the scoring fast
    path; pickling must drop it (weakrefs are unpicklable) and the unpickled
    model must still score identically via the fallback layout check
    (ADVICE r4: game/coordinate.py weakref attr)."""
    import pickle

    data, raw = mixed
    ds = build_random_effect_dataset(raw, "per-user", "userShard", "userId")
    coord = RandomEffectCoordinate(dataset=ds, task="logistic_regression", config=_cfg())
    model, _ = coord.train(None, None)
    assert getattr(model, "_support_layout_of", None) is not None
    clone = pickle.loads(pickle.dumps(model))
    assert not hasattr(clone, "_support_layout_of")
    np.testing.assert_allclose(
        np.asarray(coord.score(clone)), np.asarray(coord.score(model)), atol=1e-12
    )
