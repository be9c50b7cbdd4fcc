"""A fixed effect whose rows AND coefficient-length solver state are split over
the chips of a data mesh (``game/problem.py`` ``state_sharding``, PR 40): the
rule fires only where it should, the history's rows fall on the shards' edges,
the gather and the scatter-add over ``shard_map`` are the plain ones, and the
state-sharded solve on 4 and 8 virtual devices lands where the one-device
solve and the float64 optimum do. Small and seeded: d just past 2^20, a few
thousand rows of dense count columns and one-hot ids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from benchmark.reference import glm_sparse as ref
from photon_ml_tpu import obs
from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem, state_sharding
from photon_ml_tpu.ops.features import batch_from_coo
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType, lbfgs
from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch
from photon_ml_tpu.plan import planner
from photon_ml_tpu.utils.events import EventListener

WIDE = lbfgs.HISTORY_ROWS_MIN_DIM + 37
DENSE, IDS = 5, 6  # count columns in every row, one-hot ids a row (and the intercept)
L2 = 1.0


def _rows(d, n=3000, seed=0):
    """(rows, cols, vals, y): DENSE count columns log(1 + x) in every row, IDS
    ids from a pool of 400 columns spread over the width (the last ones in the
    width's last tile), the intercept last."""
    rng = np.random.default_rng(seed)
    pool = np.append(rng.choice(np.arange(DENSE, d - 2), size=399, replace=False), d - 2)
    cols = np.concatenate([np.tile(np.arange(DENSE), (n, 1)), rng.choice(pool, size=(n, IDS)),
                           np.full((n, 1), d - 1)], axis=1)
    vals = np.concatenate([np.log1p(rng.poisson(3.0, size=(n, DENSE))), np.ones((n, IDS + 1))], axis=1)
    beta = rng.normal(size=d) * 0.3
    z = (vals * beta[cols]).sum(1) - 1.0
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return np.repeat(np.arange(n), cols.shape[1]), cols.reshape(-1), vals.reshape(-1), y


def _config(optimizer=OptimizerType.LBFGS, tolerance=1e-9):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=tolerance, max_iterations=100),
        regularization=RegularizationContext("L2"), reg_weight=L2)


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _solve(batch, config=None):
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        model, result = GLMProblem(task="logistic_regression", config=config or _config()).run(
            batch, coordinate="global")
    span, = [s for s in sink.spans if s.name == "fe.solve"]
    return model, result, span, run.registry


@pytest.fixture(scope="module")
def wide():
    rows, cols, vals, y = _rows(WIDE)
    one = batch_from_coo(rows, cols, vals, y, WIDE, dtype=jnp.float64)
    model, result, span, _ = _solve(one)
    return (rows, cols, vals, y), one, model, result, span


# -- the rule -------------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 2, 4, 8, 16])
def test_the_history_rows_fall_on_the_shards_edges(shards):
    """d_pad is whole (8, 128) tiles and whole rows of 128 on every shard; one
    shard keeps PR 39's rounding, so no one-chip program moves."""
    for d in (WIDE, lbfgs.HISTORY_ROWS_MIN_DIM, 54_686_453, 187_767_413):
        d_pad = lbfgs.history_row_width((d,), False, shards)
        assert d_pad >= d and d_pad % 1024 == 0 and (d_pad // shards) % 128 == 0
        assert d_pad - d < max(1024, 128 * shards)
        assert d_pad == lbfgs.history_row_width((d,), False) or shards > 8
        layout, held = lbfgs.history_account(d, 10, 4, shards)
        assert (layout, held) == ("rows", 2 * 10 * (d_pad // shards) * 4)
    # the cell's: 46,941,952 columns a chip, 3,755,356,160 bytes of history
    assert lbfgs.history_account(187_767_413, 10, 4, 4) == ("rows", 3_755_356_160)
    assert lbfgs.history_row_width((187_767_413,), False, 4) == 187_767_808


def test_the_rule_fires_only_for_a_wide_row_sharded_ell_solve_that_keeps_a_history(wide):
    (rows, cols, vals, y), one, *_ = wide
    mesh = make_mesh(n_data=4, n_model=1, devices=jax.devices()[:4])
    lbfgs_config = _config().solver_config()
    fired = state_sharding(shard_batch(one, mesh), lbfgs_config)
    assert fired == NamedSharding(mesh, PartitionSpec("data"))
    # one device, a narrow width, TRON, a dense batch: the parent's programs
    assert state_sharding(one, lbfgs_config) is None
    narrow = batch_from_coo(rows, cols % 4000, vals, y, 4000, dtype=jnp.float64)
    assert state_sharding(shard_batch(narrow, mesh), lbfgs_config) is None
    assert state_sharding(shard_batch(one, mesh), _config(OptimizerType.TRON).solver_config()) is None
    one_device = make_mesh(n_data=1, n_model=1, devices=jax.devices()[:1])
    assert state_sharding(shard_batch(one, one_device), lbfgs_config) is None


def test_a_solve_on_one_device_says_its_state_is_whole(wide):
    *_, span = wide
    assert (span.attrs["state_sharding"], span.attrs["state_shards"]) == ("replicated", 1)
    assert span.attrs["history_bytes"] == lbfgs.history_account(WIDE, 10, 8)[1]
    assert "collective_bytes" not in span.attrs


# -- the pass over shard_map ----------------------------------------------------------------


@pytest.mark.parametrize("shards", [4, 8])
def test_the_gather_and_the_scatter_add_are_the_plain_ones(wide, shards):
    _, one, *_ = wide
    mesh = make_mesh(n_data=shards, n_model=1, devices=jax.devices()[:shards])
    d_pad = lbfgs.history_row_width((WIDE,), False, shards)
    f = shard_batch(one, mesh).features
    f = type(f)(dim=d_pad, idx=f.idx, val=f.val)
    vec = NamedSharding(mesh, PartitionSpec("data"))
    rng = np.random.default_rng(3)
    w = rng.normal(size=d_pad)
    w[WIDE:] = 0.0
    c = rng.normal(size=f.n_rows)
    z = f.matvec_gathered(jax.device_put(jnp.asarray(w), vec), vec)
    g = f.rmatvec_scattered(jax.device_put(jnp.asarray(c), NamedSharding(mesh, PartitionSpec("data"))), vec)
    assert g.sharding.is_equivalent_to(vec, 1) and g.shape == (d_pad,)
    plain = type(f)(dim=d_pad, idx=one.features.idx, val=one.features.val)
    n = one.n_rows
    np.testing.assert_allclose(np.asarray(z)[:n], np.asarray(plain.matvec(jnp.asarray(w))), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g), np.asarray(plain.rmatvec(jnp.asarray(c[:n]))), rtol=1e-12, atol=1e-12)


# -- the solve ------------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [4, 8])
def test_the_state_sharded_solve_is_the_one_device_solve_and_the_float64_optimum(wide, shards):
    """Same iterations and trials judged as the one-device solve, coefficients
    within f32 rounding of each other (float64 here: 5e-9 apart), the model back at the batch's
    own d, whole on every chip; and both at the float64 Newton-CG optimum over
    the touched columns (an independent solver, benchmark/reference)."""
    (rows, cols, vals, y), one, model1, result1, _ = wide
    mesh = make_mesh(n_data=shards, n_model=1, devices=jax.devices()[:shards])
    model, result, span, registry = _solve(shard_batch(one, mesh))

    assert int(result.iterations) == int(result1.iterations) > 10
    assert int(result.line_search_evals) == int(result1.line_search_evals)
    # from no model: the state at zero is the start, one scatter-add and no gather
    assert int(result.matvecs) + 1 == int(result.rmatvecs) == int(result.iterations) + 1
    d_pad = lbfgs.history_row_width((WIDE,), False, shards)
    assert result.coefficients.shape == (d_pad,)
    assert result.coefficients.sharding.is_equivalent_to(NamedSharding(mesh, PartitionSpec("data")), 1)
    means = model.coefficients.means
    assert means.shape == (WIDE,) and means.sharding.is_fully_replicated
    w, w1 = np.asarray(means), np.asarray(model1.coefficients.means)
    scale = float(np.max(np.abs(w1)))
    np.testing.assert_allclose(w, w1, rtol=0, atol=1e-7 * scale)  # f32 rounding
    assert np.all(np.asarray(result.coefficients)[WIDE:] == 0.0)

    touched, w_ref, info = ref.solve(rows, cols, vals, y, np.zeros(len(y)), np.ones(len(y)), L2)
    assert info["residual"] <= 1e-8
    # the solver stops on its relative tolerance, the reference at a gradient of 1e-9 of its start
    np.testing.assert_allclose(w[touched], w_ref, rtol=0, atol=1e-3 * float(np.max(np.abs(w_ref))))
    _, local = ref.compact(cols)
    value = ref.objective64(w[touched], local, rows, vals, y, np.zeros(len(y)), np.ones(len(y)), L2)
    assert abs(value - info["value"]) <= 1e-8 * abs(info["value"])
    untouched = np.ones(WIDE, bool)
    untouched[touched] = False
    assert not np.any(w[untouched])

    # what the span and the sink-only counter say, from shapes and the solve's own counts
    assert (span.attrs["state_sharding"], span.attrs["state_shards"], span.attrs["history"]) == ("data", shards, "rows")
    assert span.attrs["history_bytes"] == lbfgs.history_account(WIDE, 10, 8, shards)[1]
    per_pass = (shards - 1) * (d_pad // shards) * 8
    assert span.attrs["collective_bytes"] == per_pass
    moved = {m["labels"]["kind"]: m["value"] for m in registry.snapshot()
             if m["name"] == "photon_fe_collective_bytes_total"}
    assert moved == {"all_gather": int(result.matvecs) * per_pass, "reduce_scatter": int(result.rmatvecs) * per_pass}


def test_the_history_is_split_as_the_coefficients_are(wide):
    """The lowered solve pins its ``[m, d_pad / 128, 128]`` history to the
    data axis: each device holds its own rows of every pair, none whole."""
    _, one, *_ = wide
    mesh = make_mesh(n_data=4, n_model=1, devices=jax.devices()[:4])
    batch = shard_batch(one, mesh)
    problem = GLMProblem(task="logistic_regression", config=_config())
    objective, state = problem.solve_objective(batch)
    from photon_ml_tpu.ops.glm import margin_fns, vg_fn
    from photon_ml_tpu.optimize.common import MarginFns, as_partial, walking_tolerances

    d_pad = objective.batch.dim
    w0 = jax.device_put(jnp.zeros(d_pad), state)
    *_, start = walking_tolerances(margin_fns(objective), w0, 1e-6, from_zero=True)
    text = lbfgs._solve.lower(
        as_partial(vg_fn(objective)), w0, jnp.asarray(1e-9), jnp.asarray(1e-9), 100, 10, None, 25, False,
        w0, w0, False, True, MarginFns(*margin_fns(objective)), state, start,
    ).as_text()
    rows = d_pad // 128
    pinned = [ln for ln in text.splitlines() if f"tensor<10x{rows}x128xf64>" in ln and "sharding_constraint" in ln]
    assert len(pinned) >= 4 and all('[{}, {"data"}, {}]' in ln for ln in pinned)


# -- the plan ------------------------------------------------------------------------------


def test_the_planner_names_the_state_sharding():
    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig

    cc = CoordinateConfig(name="global", feature_shard="g", config=_config())
    plan = planner.resolve([cc], mesh={"data": 4}, dims={"g": 187_767_413})
    c, = plan.coordinates
    assert c.sharding == "row-sharded, state-sharded"
    assert c.geometry == {"state_shards": 4, "state_columns": 187_767_808, "state_columns_per_chip": 46_941_952,
                          "history_bytes_per_chip": 3_755_356_160}
    # under 2^20 columns, with no width known, under TRON, on one device: as before
    assert planner.resolve([cc], mesh={"data": 4}, dims={"g": 4000}).coordinates[0].sharding == "row-sharded"
    assert planner.resolve([cc], mesh={"data": 4}).coordinates[0].sharding == "row-sharded"
    tron = CoordinateConfig(name="global", feature_shard="g", config=_config(OptimizerType.TRON))
    assert planner.resolve([tron], mesh={"data": 4}, dims={"g": WIDE}).coordinates[0].sharding == "row-sharded"
    assert planner.resolve([cc], dims={"g": WIDE}).coordinates[0].sharding == "single-device"
