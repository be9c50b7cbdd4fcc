"""The sparse fixed effect: the program's ELL passes, with and without the
local column map, held to the plain triplet reference
(benchmark/reference/glm_sparse.py), the whole fit under ``layout="auto"``
on the benchmark's one-hot law held to the float64 optimum, and what a sink
sees of such a solve: the passes a plain L-BFGS counted, the slots its
layout pads, and a final gradient NORM instead of the gradient."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data_sparse as gen
from benchmark.reference import glm_sparse as ref
from photon_ml_tpu import obs
from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem
from photon_ml_tpu.io.data import RawDataset
from photon_ml_tpu.ops.features import FeatureMatrix, _local_column_map, batch_from_coo
from photon_ml_tpu.ops.glm import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType, solve_lbfgs
from photon_ml_tpu.utils.events import EventListener

FIELDS = [40000, 36000, 9000, 7000, 4000, 2000, 1200, 140, 32, 8, 4]
D = sum(FIELDS) + 1
N = 16384
SHARD = "globalShard"


@pytest.fixture(scope="module")
def law_rows():
    law = gen.draw_law(34, FIELDS, N, 1.1)
    cols = gen.draw_columns(34, law)
    gen.set_intercept(law, cols, 0.05)
    return law, gen.draw_rows(34, law)


def _raw(rows, signs, n=None):
    n = len(rows.labels) if n is None else n
    cols, labels = rows.cols[:n], rows.labels[:n]
    return RawDataset(n_rows=n, labels=labels.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
                      shard_coo={SHARD: gen.triplets(cols, signs)}, shard_dims={SHARD: D}, id_tags={})


def _with_local_map(batch):
    """The batch with the local column map ``batch_from_coo`` gives a one-device
    ELL batch at least ``LOCAL_MAP_MIN_DIM`` wide, built at this batch's width."""
    f = batch.features
    cols, idx_local = _local_column_map(np.asarray(f.idx), f.dim)
    features = dataclasses.replace(f, cols=jnp.asarray(cols), idx_local=jnp.asarray(idx_local))
    return dataclasses.replace(batch, features=features)


def _config(reg_weight, optimizer=OptimizerType.LBFGS, tolerance=1e-6, max_iterations=100):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=tolerance, max_iterations=max_iterations),
        regularization=RegularizationContext("L2"), reg_weight=reg_weight)


# -- the passes against the plain reference ----------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
@pytest.mark.parametrize("layout", ["ell", "ell_local"])
def test_value_gradient_and_hv_match_the_triplet_reference(law_rows, layout, dtype):
    law, rows = law_rows
    rng = np.random.default_rng(3)
    signs = gen.draw_signs(2**31 + 9, D)
    r, c, v = gen.triplets(rows.cols, signs)
    batch = batch_from_coo(r, c, v, rows.labels, D, dtype=dtype)
    if layout == "ell_local":
        batch = _with_local_map(batch)
    assert batch.features.gather == ("local" if layout == "ell_local" else "global")
    w = jnp.asarray(rng.standard_normal(D) / np.sqrt(12), dtype)
    u = jnp.asarray(rng.standard_normal(D) / np.sqrt(12), dtype)
    objective = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=0.75)
    value, grad = objective.value_and_grad(w)
    hv = objective.hessian_vector(w, u)
    y = jnp.asarray(rows.labels, dtype)
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    dev = (jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32), jnp.asarray(v, dtype))
    # blocks that do not divide the triplets: the tail is padded with zeros
    want_value, want_grad = ref.value_grad(w, *dev, y, zeros, ones, 0.75, block=50_000)
    want_hv = ref.hessian_vector(w, u, *dev, y, zeros, ones, 0.75, block=50_000)
    tol = 2e-5 if dtype == np.float32 else 1e-12
    assert abs(float(value) - float(want_value)) <= tol * abs(float(want_value))
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad), rtol=0, atol=tol * float(jnp.max(jnp.abs(want_grad))))
    np.testing.assert_allclose(np.asarray(hv), np.asarray(want_hv), rtol=0, atol=tol * float(jnp.max(jnp.abs(want_hv))))


def test_a_bfloat16_gather_is_far_outside_the_kernel_tolerance(law_rows):
    """What the benchmark's comparison must catch (benchmark/correct_sparse.py):
    per entry, against the sum of its terms' magnitudes."""
    law, rows = law_rows
    r, c, v = gen.triplets(rows.cols, np.ones(D, np.float32))
    dev = (jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32), jnp.asarray(v, jnp.float32))
    w = jnp.asarray(np.random.default_rng(1).standard_normal(D) / np.sqrt(12), jnp.float32)
    y = jnp.asarray(rows.labels, jnp.float32)
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    _, want = ref.value_grad(w, *dev, y, zeros, ones, 1e-3)
    _, low = ref.value_grad(w, *dev, y, zeros, ones, 1e-3, gather_dtype=jnp.bfloat16)
    p = jax.nn.sigmoid(ref.margins(w, *dev, n_rows=len(y)))
    scale = ref.rmatvec(jnp.abs(p - y), *dev, dim=D) + 1e-3 * jnp.abs(w)
    err = float(jnp.max(jnp.abs(low - want) / scale))
    assert 2e-4 < err < 2e-2


def test_the_reference_solver_reaches_a_zero_gradient_on_the_touched_columns(law_rows):
    law, rows = law_rows
    r, c, v = gen.triplets(rows.cols, np.ones(D, np.float32))
    n = len(rows.labels)
    touched, w, info = ref.solve(r, c, v, rows.labels, np.zeros(n), np.ones(n), 7.0)
    assert info["residual"] <= 1e-9 and info["touched"] == len(touched) == int(gen.columns_seen(rows.cols, D).sum())
    full = np.zeros(D)
    full[touched] = w
    y = jnp.asarray(rows.labels, jnp.float64)
    _, grad = ref.value_grad(jnp.asarray(full), jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32),
                             jnp.asarray(v), y, jnp.zeros_like(y), jnp.ones_like(y), 7.0)
    assert float(jnp.linalg.norm(grad)) <= 1e-8 * np.sqrt(n)
    _, local = ref.compact(c)
    assert ref.objective64(w, local, r, v, rows.labels, np.zeros(n), np.ones(n), 7.0) == pytest.approx(info["value"], rel=1e-12)


# -- the whole fit under layout="auto" -------------------------------------------------------


def _estimator(reg_weight, layout="auto"):
    return GameEstimator(
        task="logistic_regression",
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(reg_weight),
                                             reg_weights=(reg_weight,), layout=layout)],
        n_cd_iterations=1, evaluator_specs=["AUC"], validation_frequency="SWEEP", dtype=jnp.float32)


@pytest.fixture(scope="module")
def auto_fit(law_rows):
    law, rows = law_rows
    signs = gen.draw_signs(2**31 + 9, D)
    raw = _raw(rows, signs)
    val = gen.draw_rows(34, law, n_sample=512, stream=1)
    val_raw = RawDataset(n_rows=512, labels=val.labels.astype(np.float64), offsets=np.zeros(512), weights=np.ones(512),
                         shard_coo={SHARD: gen.triplets(val.cols, signs)}, shard_dims={SHARD: D}, id_tags={})
    lam = 1000.0 * N / 2359296
    est = _estimator(lam)
    datasets = est.prepare_datasets(raw)
    result, = est.fit(None, validation=val_raw, datasets=datasets)
    return raw, datasets, result, lam, signs


def test_auto_builds_ell_at_this_width_and_knows_its_slots(auto_fit):
    raw, datasets, result, lam, signs = auto_fit
    ds = datasets["global"]
    assert ds.batch.features.layout == "ell" and ds.batch.features.idx.shape == (N, 12)
    assert ds.nnz == N * 12 == ds.batch.features.slots
    assert ds.batch.features.val.dtype == jnp.float32


def test_the_fit_lands_on_the_float64_optimum(auto_fit, law_rows):
    law, rows = law_rows
    raw, datasets, result, lam, signs = auto_fit
    r, c, v = raw.shard_coo[SHARD]
    touched, w_ref, info = ref.solve(r, c, v, rows.labels, np.zeros(N), np.ones(N), lam)
    assert info["residual"] <= 1e-9
    w = np.asarray(result.model["global"].model.coefficients.means, np.float64)
    assert np.max(np.abs(w[touched] - w_ref)) <= 1e-2 * np.max(np.abs(w_ref))
    _, local = ref.compact(c)
    f = ref.objective64(w[touched], local, r, v, rows.labels, np.zeros(N), np.ones(N), lam)
    assert 0 <= (f - info["value"]) / info["value"] <= 3e-5
    # a column no row holds never moves from zero
    seen = gen.columns_seen(rows.cols, D)
    assert np.count_nonzero(w[~seen]) == 0 and np.count_nonzero(w[seen]) == int(seen.sum())
    assert 0.55 < result.evaluation.metrics["AUC"] < 0.95
    res = result.trackers["global"].result
    assert int(res.line_search_evals) > int(res.iterations) > 5


def test_the_mirror_leaves_every_count_and_the_auc_alone(auto_fit, law_rows):
    law, rows = law_rows
    raw, datasets, result, lam, signs = auto_fit
    est = _estimator(lam)
    plain = np.ones(D, np.float32)
    other, = est.fit(None, datasets=est.prepare_datasets(_raw(rows, plain)))
    a, b = result.trackers["global"].result, other.trackers["global"].result
    assert (int(a.iterations), int(a.line_search_evals), float(a.loss)) == (
        int(b.iterations), int(b.line_search_evals), float(b.loss))
    np.testing.assert_array_equal(np.asarray(result.model["global"].model.coefficients.means) * signs,
                                  np.asarray(other.model["global"].model.coefficients.means))


# -- what the solve counts and what a sink sees ---------------------------------------------


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _series(registry, name, **labels):
    return [m for m in registry.snapshot()
            if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items())]


@pytest.fixture(scope="module")
def ragged_batch():
    """An ELL batch whose rows hold 1 to 5 entries: 5 slots a row, some padded."""
    rng = np.random.default_rng(11)
    n, d = 600, 400
    counts = rng.integers(1, 6, n)
    r = np.repeat(np.arange(n), counts)
    c = rng.integers(0, d, len(r))
    v = rng.standard_normal(len(r))
    y = (rng.random(n) < 0.4).astype(np.float64)
    raw = RawDataset(n_rows=n, labels=y, offsets=np.zeros(n), weights=np.ones(n),
                     shard_coo={SHARD: (r, c, v)}, shard_dims={SHARD: d}, id_tags={})
    return raw, int(counts.max()), len(r)


def test_a_plain_lbfgs_fixed_effect_solve_counts_its_passes(ragged_batch):
    raw, width, nnz = ragged_batch
    batch = raw.to_batch(SHARD, dtype=jnp.float32, layout="ell")
    model, result = GLMProblem(task="logistic_regression", config=_config(0.5)).run(batch, coordinate="global")
    assert result.line_search_evals is not None and result.orthant_zeroed is None and result.nonzeros is None
    assert int(result.line_search_evals) > int(result.iterations) > 0

    # the count is the passes the solver's program made: an objective that
    # counts its own executions agrees with it. Under l2 = 0.5 every search
    # takes the full step (one pass an iteration, none thrown away); the weaker
    # penalty makes some searches try a second length
    executed = []
    obj = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=0.1)

    def counting(w):
        jax.debug.callback(lambda: executed.append(1))
        return obj.value_and_grad(w)

    tol = jnp.asarray(1e-7, jnp.float32)
    r = solve_lbfgs(counting, jnp.zeros(batch.dim, jnp.float32), tol, tol, count_evals=True)
    jax.effects_barrier()
    assert int(r.line_search_evals) == len(executed) > int(r.iterations) + 1


def test_counting_changes_no_float(ragged_batch):
    raw, width, nnz = ragged_batch
    batch = raw.to_batch(SHARD, dtype=jnp.float32, layout="ell")
    obj = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=0.5)
    from photon_ml_tpu.ops.glm import vg_fn

    tol = jnp.asarray(1e-7, jnp.float32)
    w0 = jnp.zeros(batch.dim, jnp.float32)
    plain = solve_lbfgs(vg_fn(obj), w0, tol, tol)
    counted = solve_lbfgs(vg_fn(obj), w0, tol, tol, count_evals=True)
    assert plain.line_search_evals is None and int(counted.line_search_evals) > int(counted.iterations)
    for a, b in ((plain.coefficients, counted.coefficients), (plain.gradient, counted.gradient),
                 (plain.loss_history, counted.loss_history)):
        assert hashlib.sha256(np.asarray(a).tobytes()).hexdigest() == hashlib.sha256(np.asarray(b).tobytes()).hexdigest()
    assert int(plain.iterations) == int(counted.iterations)


@pytest.mark.parametrize("optimizer", [OptimizerType.LBFGS, OptimizerType.TRON], ids=["lbfgs", "tron"])
def test_a_sink_gets_the_gradients_norm_and_not_the_gradient(ragged_batch, optimizer):
    raw, width, nnz = ragged_batch
    batch = raw.to_batch(SHARD, dtype=jnp.float32, layout="ell")
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        model, result = GLMProblem(task="logistic_regression", config=_config(0.5, optimizer)).run(
            batch, coordinate="global", nnz=nnz)
    solver = "lbfgs" if optimizer == OptimizerType.LBFGS else "tron"
    norm, = _series(run.registry, "photon_solver_final_grad_norm", solver=solver)
    want = float(np.linalg.norm(np.asarray(result.gradient, np.float64)))
    assert norm["stat"]["count"] == 1 and norm["stat"]["mean"] == pytest.approx(want, rel=1e-5)
    fetched, = _series(run.registry, "photon_device_fetch_bytes_total", site=f"solver.{solver}")
    history = np.asarray(result.grad_norm_history).nbytes
    assert fetched["value"] <= history + 64 < np.asarray(result.gradient).nbytes
    span, = [s for s in sink.spans if s.name == "fe.solve"]
    assert (span.attrs["layout"], span.attrs["dim"], span.attrs["slots"], span.attrs["nnz"]) == (
        "ell", 400, 600 * width, nnz)
    evals = _series(run.registry, "photon_fe_line_search_evals_total", coordinate="global")
    if optimizer == OptimizerType.LBFGS:
        assert span.attrs["line_search_evals"] == int(result.line_search_evals) == evals[0]["value"]
        assert "nonzeros" not in span.attrs  # OWL-QN's alone
        assert not _series(run.registry, "photon_fe_orthant_zeroed_total")
    else:
        assert "line_search_evals" not in span.attrs and not evals


def test_the_coordinate_counts_real_and_padded_slots(ragged_batch):
    raw, width, nnz = ragged_batch
    est = GameEstimator(
        task="logistic_regression",
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(0.5),
                                             layout="ell")],
        n_cd_iterations=1, dtype=jnp.float32)
    run = obs.RunTelemetry()
    with obs.use_run(run):
        datasets = est.prepare_datasets(raw)
        assert datasets["global"].nnz == nnz
        est.fit(None, datasets=datasets)
    real, = _series(run.registry, "photon_fe_slots_total", coordinate="global", kind="real")
    padded, = _series(run.registry, "photon_fe_slots_total", coordinate="global", kind="padded")
    assert (real["value"], padded["value"]) == (nnz, 600 * width - nnz) and padded["value"] > 0


def test_a_dataset_without_a_build_counts_no_slots(ragged_batch):
    """The benchmark's dense cells wrap a ready device matrix: no ``nnz``, no series."""
    from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
    from photon_ml_tpu.game.data import FixedEffectDataset

    raw, width, nnz = ragged_batch
    batch = raw.to_batch(SHARD, dtype=jnp.float32, layout="ell")
    ds = FixedEffectDataset(coordinate_id="global", feature_shard=SHARD, batch=batch)
    run = obs.RunTelemetry()
    with obs.use_run(run):
        FixedEffectCoordinate(dataset=ds, task="logistic_regression", config=_config(0.5)).train(None)
    assert ds.nnz is None and not _series(run.registry, "photon_fe_slots_total")


@pytest.mark.parametrize("layout,slots", [("dense", 7 * 5), ("ell", 7 * 2), ("ell_local", 7 * 2)])
def test_slots_are_what_a_pass_touches(layout, slots):
    """A local column map adds no slots: a pass still touches n*k."""
    r = np.array([0, 0, 1, 2, 3, 3, 4, 5, 6])
    c = np.array([0, 4, 1, 2, 3, 0, 4, 4, 1])
    raw = RawDataset(n_rows=7, labels=np.zeros(7), offsets=np.zeros(7), weights=np.ones(7),
                     shard_coo={SHARD: (r, c, np.ones(9))}, shard_dims={SHARD: 5}, id_tags={})
    batch = raw.to_batch(SHARD, layout=layout.removesuffix("_local"))
    f = (_with_local_map(batch) if layout == "ell_local" else batch).features
    assert isinstance(f, FeatureMatrix) and f.layout == layout.removesuffix("_local") and f.slots == slots


# -- the search that walks margins (PR 37): who gets it, what it counts -----------------------


def _ragged(n, d, seed=37):
    """An ELL batch of a shape no other test of this file compiles for."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, 6, n)
    r = np.repeat(np.arange(n), counts)
    raw = RawDataset(n_rows=n, labels=(rng.random(n) < 0.4).astype(np.float64), offsets=np.zeros(n),
                     weights=np.ones(n), shard_coo={SHARD: (r, rng.integers(0, d, len(r)), rng.standard_normal(len(r)))},
                     shard_dims={SHARD: d}, id_tags={})
    return raw


def _count_passes(monkeypatch):
    """Make ``FeatureMatrix.matvec`` / ``rmatvec`` note every execution."""
    executed = {"matvec": [], "rmatvec": []}
    for name in executed:
        real = getattr(FeatureMatrix, name)

        def counted(self, x, real=real, name=name):
            jax.debug.callback(lambda name=name: executed[name].append(1))
            return real(self, x)

        monkeypatch.setattr(FeatureMatrix, name, counted)
    return executed


def test_a_walking_solve_passes_over_the_features_once_an_iteration_each_way(monkeypatch):
    """The twin of the points path's count above: here the objective's
    evaluations are not passes. ``matvec`` and ``rmatvec`` count their own
    executions: one each at the start, one each an iteration, however many
    step lengths the searches tried."""
    from photon_ml_tpu.ops.glm import margin_fns, vg_fn

    executed = _count_passes(monkeypatch)
    batch = _ragged(601, 401).to_batch(SHARD, dtype=jnp.float32, layout="ell")
    obj = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=0.1)
    tol = jnp.asarray(1e-7, jnp.float32)
    r = solve_lbfgs(vg_fn(obj), jnp.zeros(batch.dim, jnp.float32), tol, tol, count_evals=True, margins=margin_fns(obj))
    jax.effects_barrier()
    assert len(executed["matvec"]) == int(r.matvecs) == int(r.iterations) + 1 > 4
    assert len(executed["rmatvec"]) == int(r.rmatvecs) == int(r.iterations) + 1
    assert int(r.line_search_evals) > int(r.iterations) + 1  # trials judged, not passes


@pytest.mark.parametrize("warm", [False, True], ids=["from-no-model", "warm"])
def test_a_walking_solve_from_no_model_gathers_nothing_before_its_first_iteration(monkeypatch, warm):
    """What ``GLMProblem.run`` executes around a walking solve, counted by the
    passes themselves: the tolerance pass is the state at zero, one scatter-add
    and no gather (its margins are the offsets); a solve from no model starts
    from it, so it runs ``iterations`` matvecs and ``iterations`` + 1 rmatvecs
    in all; a warm one is handed margins(w0) and its gradient from the same
    program, one more of each. The sink's counter and spans say the same."""
    # a shape of its own each: a program compiled before the stub went in counts nothing
    raw = _ragged(608 if warm else 606, 408 if warm else 406)
    batch = raw.to_batch(SHARD, dtype=jnp.float32, layout="ell")
    problem = GLMProblem(task="logistic_regression", config=_config(0.1))
    initial = None
    if warm:
        initial, _ = GLMProblem(task="logistic_regression", config=_config(0.1, max_iterations=3)).run(batch)
    executed = _count_passes(monkeypatch)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        _, result = problem.run(batch, initial_model=initial, coordinate="global")
    jax.effects_barrier()
    iterations = int(result.iterations)
    want = (iterations + 1, iterations + 2) if warm else (iterations, iterations + 1)
    assert (len(executed["matvec"]), len(executed["rmatvec"])) == want and iterations > 4
    passes = {m["labels"]["kind"]: m["value"]
              for m in _series(run.registry, "photon_fe_feature_passes_total", coordinate="global")}
    assert (passes["matvec"], passes["rmatvec"]) == want
    # the solve's own count: its start's passes and its iterations'
    assert (int(result.matvecs), int(result.rmatvecs)) == (want[0], iterations + 1)
    solve, = [s for s in sink.spans if s.name == "fe.solve"]
    tolerances, = [s for s in sink.spans if s.name == "fe.tolerances"]
    assert solve.attrs["start"] == ("evaluated" if warm else "zero_state") and solve.attrs["warm"] is warm
    assert tolerances.attrs["matvec"] is False and tolerances.parent_id == solve.span_id


def test_a_fit_from_no_model_and_then_warm_compiles_one_walking_solve():
    """Coordinate descent over one fixed effect: the first sweep's solve starts
    from the state at zero, the second warm from the first's model. Both are
    handed their start, so both run the one compiled ``_solve``."""
    from photon_ml_tpu.optimize import lbfgs

    raw = _ragged(607, 407)
    est = GameEstimator(
        task="logistic_regression",
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(0.1), layout="ell")],
        n_cd_iterations=2, dtype=jnp.float32)
    datasets = est.prepare_datasets(raw)
    programs = lbfgs._solve._cache_size()
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        est.fit(None, datasets=datasets)
    assert lbfgs._solve._cache_size() == programs + 1
    solves = [(s.attrs["warm"], s.attrs["start"]) for s in sink.spans if s.name == "fe.solve"]
    assert solves == [(False, "zero_state"), (True, "evaluated")]


class _CountingSteps:
    """``MarginFns`` of a quadratic that note every time one of them is traced."""

    def __init__(self):
        self.traced = []

    def fns(self, shape):
        def note(name, out):
            def fn(*args):
                self.traced.append(name)
                return out(*args)
            return fn

        return (note("margins", lambda w: w), note("direction_margins", lambda p: p),
                note("value_and_slope", lambda z, u, t, w, p: (0.5 * jnp.sum((z + t * u) ** 2, axis=0),
                                                                 jnp.sum((z + t * u) * u, axis=0))),
                note("grad_from_margins", lambda z, w: (0.5 * jnp.sum(z * z, axis=0), z)),
                note("zero_margins", lambda: jnp.zeros(shape)))


@pytest.mark.parametrize("mode", ["plain", "owlqn", "box", "batched"])
def test_only_a_plain_one_lane_solve_walks_the_margin_functions(mode):
    """OWL-QN projects its trial points and L-BFGS-B clips them (their margins
    are not affine in the step length), and the packed lanes keep their search:
    handed margin functions, such a solve neither traces nor calls them and
    returns what it returned without them, bit for bit."""
    shape = (7, 3) if mode == "batched" else (7,)
    w0 = jnp.asarray(np.random.default_rng(2).normal(size=shape))
    kwargs = {"owlqn": dict(l1_weight=0.3), "box": dict(box_constraints=(w0 * 0 - 0.2, w0 * 0 + 0.4)),
              "batched": dict(batched=True)}.get(mode, {})
    tol = jnp.full(shape[1:], 1e-9)
    vg = lambda w: (0.5 * jnp.sum(w * w, axis=0), w)  # noqa: E731
    steps = _CountingSteps()
    with_steps = solve_lbfgs(vg, w0, tol, tol, margins=steps.fns(shape), **kwargs)
    if mode == "plain":
        assert set(steps.traced) == {"margins", "direction_margins", "value_and_slope", "grad_from_margins"}
        np.testing.assert_allclose(np.asarray(with_steps.coefficients), 0.0, atol=1e-8)
        return
    assert steps.traced == []
    without = solve_lbfgs(vg, w0, tol, tol, **kwargs)
    for a, b in zip(jax.tree_util.tree_leaves(with_steps), jax.tree_util.tree_leaves(without)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["fused", "tron", "owlqn"])
def test_a_solve_that_cannot_walk_says_points_and_is_handed_no_margin_functions(case, monkeypatch):
    """A fused objective has no margin steps (``GLMProblem.run`` builds none);
    TRON has no line search; OWL-QN is handed them and leaves them alone."""
    from photon_ml_tpu.ops import batch_from_dense, glm, pallas_glm
    from photon_ml_tpu.optimize import lbfgs

    built = []
    real = glm.margin_fns
    monkeypatch.setattr(glm, "margin_fns", lambda obj: built.append(1) or real(obj))
    walked = []
    search = lbfgs._margin_search
    monkeypatch.setattr(lbfgs, "_margin_search", lambda *a, **k: walked.append(1) or search(*a, **k))
    rng = np.random.default_rng(4)
    if case == "fused":
        monkeypatch.setenv("PHOTON_PALLAS", "interpret")
        n, d = pallas_glm.MIN_FUSED_ROWS, pallas_glm.LANE
        batch = batch_from_dense(rng.standard_normal((n, d)), (rng.random(n) < 0.4).astype(np.float64), dtype=jnp.float32)
        config = _config(0.5, max_iterations=3)
    else:
        batch = _ragged(602, 402).to_batch(SHARD, dtype=jnp.float32, layout="ell")
        config = _config(0.5, OptimizerType.TRON if case == "tron" else OptimizerType.LBFGS, max_iterations=5)
        if case == "owlqn":
            config = GLMOptimizationConfig(optimizer=config.optimizer, regularization=RegularizationContext("L1"),
                                           reg_weight=0.5)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        GLMProblem(task="logistic_regression", config=config).run(batch, coordinate="global")
    span, = [s for s in sink.spans if s.name == "fe.solve"]
    # TRON has no line search and its span no such attribute
    assert span.attrs.get("line_search") == (None if case == "tron" else "points")
    # a tolerance pass over the features, and a start the solve evaluates
    tolerances, = [s for s in sink.spans if s.name == "fe.tolerances"]
    assert span.attrs["start"] == "evaluated" and tolerances.attrs["matvec"] is True
    assert len(built) == (0 if case == "fused" else 1) and not walked
    # only a solve that walked margins counts passes of its own
    assert not _series(run.registry, "photon_fe_feature_passes_total")


@pytest.mark.parametrize("case", ["wide", "narrow", "owlqn", "tron"])
def test_the_solve_span_says_how_the_history_is_kept(case):
    """``history`` and ``history_bytes`` on ``fe.solve`` are host-known, from
    the one function the solver decides by: ``rows`` (2 m d_pad elements) for
    an L-BFGS or OWL-QN solve at least ``HISTORY_ROWS_MIN_DIM`` wide, ``tiled``
    (the TPU's 16 padded rows of d rounded up to 128) under it; TRON keeps no
    history and says nothing."""
    from photon_ml_tpu.optimize import lbfgs

    d = lbfgs.HISTORY_ROWS_MIN_DIM + 37 if case == "wide" else 403
    batch = _ragged(604, d).to_batch(SHARD, dtype=jnp.float32, layout="ell")
    config = _config(0.5, OptimizerType.TRON if case == "tron" else OptimizerType.LBFGS, max_iterations=3)
    if case == "owlqn":
        config = GLMOptimizationConfig(optimizer=config.optimizer, regularization=RegularizationContext("L1"),
                                       reg_weight=0.5)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        GLMProblem(task="logistic_regression", config=config).run(batch, coordinate="global")
    span, = [s for s in sink.spans if s.name == "fe.solve"]
    assert span.attrs["dim"] == d
    if case == "tron":
        assert "history" not in span.attrs and "history_bytes" not in span.attrs
    elif case == "wide":
        d_pad = lbfgs.HISTORY_ROWS_MIN_DIM + 1024
        assert (span.attrs["history"], span.attrs["history_bytes"]) == ("rows", 2 * 10 * d_pad * 4)
        assert span.attrs["line_search"] == "margins"
    else:
        assert (span.attrs["history"], span.attrs["history_bytes"]) == ("tiled", 2 * 16 * 512 * 4)


def _ell_estimator():
    return GameEstimator(
        task="logistic_regression",
        # under this penalty some search tries a second length (0.5: none does)
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(0.1), layout="ell")],
        n_cd_iterations=1, dtype=jnp.float32)


def test_a_fit_over_an_ell_shard_compiles_one_solver_and_walks_margins():
    """What the benchmark's sparse cell requires of a first fit
    (``benchmark/jobs/fit_sparse.py`` ``solver_programs``), and what a sink
    sees of the walk: ``line_search=margins`` on ``fe.solve``, and for this
    solve from no model iterations matvecs and iterations + 1 rmatvecs (the
    state at zero's scatter-add its start), riding the one fetch a sink
    already made."""
    import logging

    from photon_ml_tpu.optimize import lbfgs

    raw = _ragged(603, 403)
    est = _ell_estimator()
    datasets = est.prepare_datasets(raw)
    programs = lbfgs._solve._cache_size()
    first, = est.fit(None, datasets=datasets)
    assert lbfgs._solve._cache_size() == programs + 1
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    logger = logging.getLogger("photon_ml_tpu")
    level = logger.level
    logger.setLevel(logging.WARNING)  # the INFO summary fetches on its own account
    try:
        with obs.use_run(run):
            second, = _ell_estimator().fit(None, datasets=datasets)
        quiet = obs.RunTelemetry()
        with obs.use_run(quiet):
            _ell_estimator().fit(None, datasets=datasets)
    finally:
        logger.setLevel(level)
    assert lbfgs._solve._cache_size() == programs + 1
    a, b = first.trackers["global"].result, second.trackers["global"].result
    assert (int(a.iterations), int(a.line_search_evals), float(a.loss)) == (
        int(b.iterations), int(b.line_search_evals), float(b.loss))
    span, = [s for s in sink.spans if s.name == "fe.solve"]
    assert span.attrs["line_search"] == "margins" and span.attrs["layout"] == "ell"
    assert span.attrs["line_search_evals"] == int(b.line_search_evals)
    passes = {m["labels"]["kind"]: m["value"]
              for m in _series(run.registry, "photon_fe_feature_passes_total", coordinate="global")}
    assert passes == {"matvec": int(b.iterations), "rmatvec": int(b.iterations) + 1}
    assert int(b.matvecs) + 1 == int(b.rmatvecs) == int(b.iterations) + 1 < int(b.line_search_evals)
    # one fetch for the solve's counts, two int32 wider than it was; none with no sink
    fetches = [s for s in sink.spans if s.name == "fetch" and s.attrs["site"] == "solver.lbfgs"]
    history = np.asarray(b.grad_norm_history).nbytes
    assert len(fetches) == 1 and fetches[0].attrs["bytes"] == history + 5 * 4
    assert not _series(quiet.registry, "photon_device_fetch_bytes_total", site="solver.lbfgs")
    assert not _series(quiet.registry, "photon_fe_feature_passes_total")
    sites = lambda reg: {m["labels"]["site"] for m in _series(reg, "photon_device_fetch_bytes_total")}  # noqa: E731
    assert sites(quiet.registry) == sites(run.registry) - {"solver.lbfgs", "tracker_metrics", "tracker_aggregates"}
