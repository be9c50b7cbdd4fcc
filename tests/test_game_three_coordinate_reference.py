"""A three-coordinate ``GameEstimator.fit`` (fixed + per-user + per-item, the
update sequence global, per-user, per-item) against the plain reference
``benchmark/reference/game.py`` on seeded data, with capped and uncapped
entities in BOTH random effects: every block's coefficients and the whole
model's objective. Small, on the CPU, the fused kernels in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct_game
from benchmark.reference import game as ref_game
from photon_ml_tpu.estimators import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.data import _hash64
from photon_ml_tpu.game.problem import GLMOptimizationConfig, _fusion_mode
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset

N, D = 4608, 128
EFFECTS = {  # name -> (shard, id, (entities, d_re), cap, l2)
    "per-user": ("userShard", "userId", (60, 6), 48, 1.0),
    "per-item": ("itemShard", "itemId", (12, 4), 256, 2.0),
}
SWEEPS = 3
L2_FIXED = 1.5


def _config(optimizer, max_iterations, reg_weight):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=1e-6, max_iterations=max_iterations),
        regularization=RegularizationContext("L2"), reg_weight=reg_weight,
    )


@pytest.fixture(scope="module")
def fitted():
    mp = pytest.MonkeyPatch()
    mp.setenv("PHOTON_PALLAS", "interpret")
    try:
        data = generate_mixed_effect_data(
            n=N, d_fixed=D, re_specs={v[1]: v[2] for v in EFFECTS.values()}, seed=28,
        )
        raw = mixed_data_to_raw_dataset(data)
        configs = [CoordinateConfig(name="global", feature_shard="global",
                                    config=_config(OptimizerType.TRON, 10, L2_FIXED))]
        configs += [
            CoordinateConfig(name=name, feature_shard=shard, config=_config(OptimizerType.LBFGS, 30, l2),
                             random_effect_type=id_, active_cap=cap)
            for name, (shard, id_, _, cap, l2) in EFFECTS.items()
        ]
        estimator = GameEstimator(task="logistic_regression", coordinate_configs=configs,
                                  n_cd_iterations=SWEEPS, dtype=jnp.float32)
        datasets = estimator.prepare_datasets(raw)
        fusion = _fusion_mode(datasets["global"].batch)[0]
        model = estimator.fit(raw, datasets=datasets)[-1].model
    finally:
        mp.undo()
    return data, datasets, fusion, model


def _reference(data):
    x = jnp.asarray(data.global_x, jnp.float32)
    y = jnp.asarray(data.labels, jnp.float32)
    priority = _hash64(np.arange(N, dtype=np.int64), 0)
    blocks = []
    for name, (_, id_, (n_entities, _), cap, l2) in EFFECTS.items():
        entity = np.asarray([int(e[1:]) for e in data.entity_ids[id_]])
        blocks.append(ref_game.Block(
            name=name, features=jnp.asarray(data.entity_x[id_], jnp.float32),
            entity=jnp.asarray(entity, jnp.int32), n_entities=n_entities, l2=l2,
            weights=jnp.asarray(ref_game.active_weights(entity, priority, cap, n_entities)),
        ))
    w, tables = ref_game.coordinate_descent(
        x, y, L2_FIXED, blocks, SWEEPS, [ref_game.FIXED, "per-user", "per-item"]
    )
    return x, y, blocks, w, tables


def _table(model, n_entities, d_re):
    dense = model.dense_coefficients(d_re)
    table = np.zeros((n_entities, d_re), np.float32)
    for row, entity_id in enumerate(model.entity_ids):
        table[int(str(entity_id)[1:])] = dense[row]
    return table


def test_the_data_has_capped_and_uncapped_entities_in_both_effects(fitted):
    data, datasets, fusion, _ = fitted
    assert fusion == "interpret"  # the fused kernels, not the jnp path
    for name, (_, id_, (n_entities, _), cap, _) in EFFECTS.items():
        counts = np.unique(data.entity_ids[id_], return_counts=True)[1]
        assert (counts > cap).any() and (counts <= cap).any(), name
        ds = datasets[name]
        # the reference's rule names the program's active rows, row for row
        entity = np.asarray([int(e[1:]) for e in data.entity_ids[id_]])
        weights = ref_game.active_weights(entity, _hash64(np.arange(N, dtype=np.int64), 0), cap, n_entities)
        assert set(np.flatnonzero(weights == 0)) == set(np.asarray(ds.passive_rows).tolist())
        active = np.asarray(ds.blocks.active_rows)
        got = np.zeros(N, np.float32)
        got[active[active >= 0]] = np.asarray(ds.blocks.weights)[active >= 0]
        np.testing.assert_allclose(got, weights, rtol=1e-6)


def test_three_block_cd_agrees_with_the_plain_reference(fitted):
    data, _, _, model = fitted
    x, y, blocks, w_ref, t_ref = _reference(data)
    w_sys = np.asarray(jax.device_get(model["global"].model.coefficients.means))
    assert correct_game.correct.rel_err(w_sys, w_ref) <= correct_game.GAME_FIXED_COEF_TOL
    t_sys = {}
    for name, (_, _, (n_entities, d_re), _, _) in EFFECTS.items():
        t_sys[name] = _table(model[name], n_entities, d_re)
        err = correct_game.correct.rel_err(t_sys[name], t_ref[name])
        assert err <= correct_game.ENTITY_COEF_TOL, (name, err)
    f_sys = float(ref_game.model_objective(
        jnp.asarray(w_sys), {k: jnp.asarray(v) for k, v in t_sys.items()}, x, y, L2_FIXED, blocks))
    f_ref = float(ref_game.model_objective(w_ref, t_ref, x, y, L2_FIXED, blocks))
    assert abs(f_sys - f_ref) / abs(f_ref) <= correct_game.OBJECTIVE_TOL
    # and the comparison is not vacuous: the reference WITHOUT the cap's
    # weights (every row trained at weight 1) is far outside the limits
    unweighted = [ref_game.Block(b.name, b.features, b.entity, b.n_entities, b.l2, jnp.ones_like(b.weights))
                  for b in blocks]
    _, t_other = ref_game.coordinate_descent(
        x, y, L2_FIXED, unweighted, SWEEPS, [ref_game.FIXED, "per-user", "per-item"]
    )
    for name in EFFECTS:
        assert correct_game.correct.rel_err(t_sys[name], t_other[name]) > 3 * correct_game.ENTITY_COEF_TOL
