"""The count-response GLM path (PR 32): Poisson loss, elastic net through
OWL-QN with the l1 weight as an OPERAND, standardisation from statistics taken
on the device, against the plain reference of benchmark/reference/glm_enet.py.
Small seeded data on the CPU; no number here is a timing."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_enet as ref
from photon_ml_tpu import obs
from photon_ml_tpu.game import coordinate
from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem
from photon_ml_tpu.io.data import RawDataset
from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch
from photon_ml_tpu.ops.glm import GLMObjective
from photon_ml_tpu.ops.normalization import build_normalization
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType, lbfgs, solve_lbfgs
from photon_ml_tpu.optimize.common import ConvergenceReason
from photon_ml_tpu.utils import stats as stats_mod
from photon_ml_tpu.utils.events import EventListener

N, D, SUPPORT = 4096, 24, 6
INTERCEPT = D - 1


def _data(signs=None, dtype=np.float64):
    """Columns with means and scales of their own, Poisson counts from a
    sparse truth in the standardised space, intercept last."""
    rng = np.random.default_rng(32)
    sigma = 10.0 ** rng.uniform(-1, 1, D)
    mu = sigma * rng.standard_normal(D)
    eps = rng.standard_normal((N, D))
    eps[:, -1], sigma[-1], mu[-1] = 1.0, 1.0, 0.0
    beta = np.zeros(D)
    beta[rng.choice(D - 1, SUPPORT, replace=False)] = 0.3 * rng.standard_normal(SUPPORT)
    beta[-1] = 0.2
    y = rng.poisson(np.exp(eps @ beta)).astype(dtype)
    x = (mu + sigma * eps).astype(dtype)
    if signs is not None:
        x = x * signs
    return x, y


def _batch(x, y):
    n = len(y)
    return LabeledBatch(
        features=FeatureMatrix(dim=x.shape[1], dense=jnp.asarray(x)), labels=jnp.asarray(y),
        offsets=jnp.zeros(n, x.dtype), weights=jnp.ones(n, x.dtype),
    )


def _standardization(batch, dtype):
    st = stats_mod.compute_feature_statistics(batch)
    return build_normalization("STANDARDIZATION", st["mean"], st["variance"], st["max_magnitude"],
                               intercept_index=INTERCEPT, dtype=dtype)


def _config(optimizer, reg_type, lam, alpha=0.5, tolerance=1e-10):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=tolerance, max_iterations=200),
        regularization=RegularizationContext(reg_type, alpha), reg_weight=lam,
    )


@pytest.fixture(scope="module")
def problem_data():
    x, y = _data()
    batch = _batch(x, y)
    return x, y, batch, _standardization(batch, jnp.float64)


# -- the objective against the plain reference, factors and shifts on -------------


@pytest.mark.parametrize("what", ["value", "gradient", "hessian_vector"])
def test_objective_matches_the_reference_under_standardization(problem_data, what):
    x, y, batch, norm = problem_data
    rng = np.random.default_rng(1)
    w = jnp.asarray(0.3 * rng.standard_normal(D))
    v = jnp.asarray(rng.standard_normal(D))
    problem = GLMProblem(task="poisson_regression", config=_config(OptimizerType.TRON, "L2", 2.0),
                         normalization=norm)
    objective = problem.objective(batch)
    zeros, ones = jnp.zeros(N), jnp.ones(N)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    value_ref, grad_ref = ref.value_grad(w, xj, yj, zeros, ones, 2.0, norm.factors, norm.shifts, block=1000)
    if what == "hessian_vector":
        got = objective.hessian_vector(w, v)
        want = ref.hessian_vector(w, v, xj, yj, zeros, ones, 2.0, norm.factors, norm.shifts, block=1000)
    else:
        value, grad = objective.value_and_grad(w)
        got, want = (value, value_ref) if what == "value" else (grad, grad_ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-9)
    # the reference's blocks and its float64 twin agree with each other too
    xt = ref.transformed(x, norm.factors, norm.shifts)
    v64, g64, _ = ref._smooth64(np.asarray(w), xt, y, np.zeros(N), np.ones(N), 2.0)
    np.testing.assert_allclose(float(value_ref), v64, rtol=1e-10)
    np.testing.assert_allclose(np.asarray(grad_ref), g64, rtol=1e-8, atol=1e-8)


def test_fused_kernels_take_poisson_with_factors_and_shifts(monkeypatch):
    """The Mosaic kernels in interpret mode: ``exp`` in ``_vg_kernel`` with the
    shift operand on, in float32 as on the chip."""
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    rng = np.random.default_rng(3)
    n, d = 4096, 128
    sigma = 10.0 ** rng.uniform(-1, 1, d)
    x = (sigma * rng.standard_normal(d) + sigma * rng.standard_normal((n, d))).astype(np.float32)
    x[:, -1] = 1.0
    y = rng.poisson(1.5, n).astype(np.float32)
    batch = _batch(x, y)
    st = stats_mod.compute_feature_statistics(batch)
    norm = build_normalization("STANDARDIZATION", st["mean"], st["variance"], st["max_magnitude"], d - 1)
    problem = GLMProblem(task="poisson_regression", config=_config(OptimizerType.TRON, "L2", 1.0),
                         normalization=norm)
    objective = problem.objective(batch, fused="interpret")
    w = jnp.asarray(0.3 * rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    v = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    value, grad = jax.jit(GLMObjective.value_and_grad)(objective, w)
    hv = jax.jit(GLMObjective.hessian_vector)(objective, w, v)
    zeros, ones = jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32)
    value_ref, grad_ref = ref.value_grad(w, batch.features.dense, batch.labels, zeros, ones, 1.0,
                                         norm.factors, norm.shifts)
    hv_ref = ref.hessian_vector(w, v, batch.features.dense, batch.labels, zeros, ones, 1.0,
                                norm.factors, norm.shifts)
    for got, want in ((value, value_ref), (grad, grad_ref), (hv, hv_ref)):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert np.max(np.abs(got - want)) <= 2e-5 * np.max(np.abs(want))


# -- the solvers against the independent one --------------------------------------


def _lambda_max(x, y, norm):
    xt = ref.transformed(x, norm.factors, norm.shifts)
    g0 = ref._smooth64(np.zeros(D), xt, y, np.zeros(N), np.ones(N), 0.0)[1]
    return ref.lambda_max(g0, 0.5, INTERCEPT), xt


def test_owlqn_path_matches_the_independent_solver(problem_data):
    x, y, batch, norm = problem_data
    lmax, xt = _lambda_max(x, y, norm)
    lambdas = [lmax * 10 ** (-k / 2) for k in (1, 2, 3)]
    path = ref.solve_path(xt, y, np.zeros(N), np.ones(N), lambdas, 0.5)
    model, supports = None, []
    for lam, (w_ref, res) in zip(lambdas, path):
        assert res <= 1e-9
        problem = GLMProblem(task="poisson_regression",
                             config=_config(OptimizerType.LBFGS, "ELASTIC_NET", lam), normalization=norm)
        model, result = problem.run(batch, initial_model=model)
        w = np.asarray(norm.model_to_transformed_space(model.coefficients.means))
        assert int(result.nonzeros) == int(np.sum(np.asarray(result.coefficients) != 0))
        assert np.max(np.abs(w - w_ref)) <= 1e-4 * np.max(np.abs(w_ref))
        assert list(np.flatnonzero(np.abs(w) > 1e-9)) == list(np.flatnonzero(w_ref))
        assert int(result.line_search_evals) > int(result.iterations) > 0
        supports.append(int(result.nonzeros))
    assert supports == sorted(supports) and supports[0] < supports[-1]


def test_tron_l2_poisson_matches_the_independent_solver(problem_data):
    x, y, batch, norm = problem_data
    xt = ref.transformed(x, norm.factors, norm.shifts)
    w_ref, res = ref.solve_enet(xt, y, np.zeros(N), np.ones(N), 0.0, 3.0)
    assert res <= 1e-9
    problem = GLMProblem(task="poisson_regression", config=_config(OptimizerType.TRON, "L2", 3.0),
                         normalization=norm)
    model, result = problem.run(batch)
    assert result.nonzeros is None and result.line_search_evals is None  # OWL-QN's alone
    w = np.asarray(norm.model_to_transformed_space(model.coefficients.means))
    assert np.max(np.abs(w - w_ref)) <= 1e-6 * np.max(np.abs(w_ref))


def test_mirrored_data_does_the_same_arithmetic():
    """A seed's mirror (benchmark/data_glm.py): the same iteration counts,
    evaluations and support, bit for bit, and reflected coefficients."""
    rng = np.random.default_rng(9)
    signs = (2 * rng.integers(0, 2, D) - 1).astype(np.float32)
    signs[-1] = 1.0
    runs = []
    for s in (None, signs):
        x, y = _data(s, np.float32)
        batch = _batch(x, y)
        norm = _standardization(batch, jnp.float32)
        lmax, _ = _lambda_max(x.astype(np.float64), y.astype(np.float64), norm)
        model, out = None, []
        for k in (1, 3):
            problem = GLMProblem(
                task="poisson_regression", normalization=norm,
                config=_config(OptimizerType.LBFGS, "ELASTIC_NET", float(np.float32(lmax * 10 ** (-k / 2))),
                               tolerance=1e-6))
            model, r = problem.run(batch, initial_model=model)
            out.append((int(r.iterations), int(r.line_search_evals), int(r.nonzeros), float(r.loss),
                        np.asarray(model.coefficients.means)))
        runs.append(out)
    for plain, mirrored in zip(*runs):
        assert plain[:4] == mirrored[:4]
        np.testing.assert_array_equal(plain[4] * signs, mirrored[4])


# -- the l1 weight is an operand ----------------------------------------------------


def _quadratic(d=8, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((32, d)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(32), jnp.float32)
    return jax.tree_util.Partial(lambda a, b, w: (0.5 * jnp.sum((a @ w - b) ** 2), a.T @ (a @ w - b)), a, b)


def test_one_compiled_solver_for_every_positive_l1_weight():
    vg = _quadratic()
    tol = jnp.asarray(1e-6, jnp.float32)
    w0 = jnp.zeros(8, jnp.float32)
    before = lbfgs._solve._cache_size()
    results = [solve_lbfgs(vg, w0, tol, tol, l1_weight=l1) for l1 in (0.5, 2.0, 7.5)]
    assert lbfgs._solve._cache_size() - before == 1
    supports = [int(r.nonzeros) for r in results]
    assert supports[0] >= supports[1] >= supports[2] and supports[0] > supports[2]
    # the L2 choice is another program, with no OWL-QN output in it
    plain = solve_lbfgs(vg, w0, tol, tol, l1_weight=0.0)
    assert lbfgs._solve._cache_size() - before == 2
    assert plain.nonzeros is None and plain.line_search_evals is None and plain.orthant_zeroed is None


def _lowered_text(fn, *args, **kwargs) -> str:
    return hashlib.sha256(fn.lower(*args, **kwargs).as_text().encode()).hexdigest()


def test_an_l2_solve_lowers_to_the_program_it_was_before_the_l1_operand():
    """Golden hashes recorded on PR 35's commit (the child of 3bcf70e: the line
    search that judges a trial before it evaluates the next one lowers both
    solves to another program than PR 32's parent 02bdbd5 did) under this
    conftest (CPU, x64 on, explicit f32 shapes): the random effects' packed
    solve and a scalar L-BFGS solve carry no pseudo-gradient, orthant or
    counter op when the choice is L2."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    e, k, s = 16, 8, 4
    packed = _lowered_text(
        coordinate._train_blocks_packed,
        f32(e, k, s), f32(e, k), f32(e, k), f32(e, k), f32(e, s), f32(e, s), f32(e, s),
        task="logistic_regression", l2=1.0, l1=0.0, optimizer_type="LBFGS", tolerance=1e-6,
        max_iterations=30, num_corrections=10, max_cg_iterations=20, max_improvement_failures=5)
    assert packed == "1ff3a8032e03746d3011e3908ed46616ea962b9b087966d2577b3adb34ad1631"

    def run(a, b, w0):
        vg = lambda w: (0.5 * jnp.sum((a @ w - b) ** 2), a.T @ (a @ w - b))
        r = solve_lbfgs(vg, w0, jnp.asarray(1e-6, jnp.float32), jnp.asarray(1e-6, jnp.float32),
                        max_iterations=20, l1_weight=0.0)
        return r.coefficients, r.iterations

    scalar = _lowered_text(jax.jit(run), f32(32, 8), f32(32), f32(8))
    assert scalar == "cb2dd8466a55007b5e73f6ace917fe3478136c8f977688a4ea1ff896473560f0"


@pytest.mark.parametrize("kind, golden", [
    ("owlqn", "f16ca238344247a728f9f5e44470df3941321c67ad2b01846edc333f479a1f34"),
    ("box", "864fbb005d966c5715b2f616cf836613636bde4306e5f10b5bd2e5995f5a8936"),
    ("counted", "a6be5db6f185cda1daa20af1167357deb4708b2bd9aaf7e1321b938d173f3656"),
])
def test_a_points_solve_lowers_to_the_program_it_was_before_the_margin_walk(kind, golden):
    """Golden hashes recorded on PR 36's commit f182e32, PR 37's parent, under
    this conftest: the verdict the two searches share (``lbfgs._verdict``)
    moved no operation of an OWL-QN solve, of an L-BFGS-B solve or of a
    counting plain solve that is handed no margin functions. With the two
    hashes of the test above (the packed lanes and the counter-free scalar
    solve) these are every program of ``_solve`` that the cells which must not
    move compile."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def run(a, b, w0):
        vg = lambda w: (0.5 * jnp.sum((a @ w - b) ** 2), a.T @ (a @ w - b))
        tol = jnp.asarray(1e-6, jnp.float32)
        kwargs = {"owlqn": dict(l1_weight=0.5), "box": dict(box_constraints=(w0 - 0.25, w0 + 0.5)),
                  "counted": dict(count_evals=True)}[kind]
        r = solve_lbfgs(vg, w0, tol, tol, max_iterations=20, **kwargs)
        return r.coefficients, r.iterations

    assert _lowered_text(jax.jit(run), f32(32, 8), f32(32), f32(8)) == golden


@pytest.mark.parametrize("fused, which, golden", [
    (None, "value_and_grad", "c95a577d8eb1abd9990956eda865c1eef8181c42e8b6f3252f98bc6154b99102"),
    (None, "hessian_vector", "d6ed461a397a6ff279068382c808dce7a3f2647b3d55e25eef61700d5b86dd2c"),
    (None, "hessian_diagonal", "a2cbc3d99ee6f28492e1ab4c7fd41b4fdc978efbb6544aaa2132739caea15caf"),
    ("interpret", "value_and_grad", "ebc166d84da1557159d91fe19f004dc2f8443626a29fd05e515bd04f5072ac25"),
    ("interpret", "hessian_vector", "4e204271f09727b3910a18f3972dd635c883177df72861aa429c48010e698da8"),
    ("interpret", "hessian_diagonal", "c1ff393ab597f8ade4af2343e30502a04d5728e8a45140b4705eace283791af9"),
])
def test_the_objective_lowers_to_the_programs_it_was_before_it_came_as_steps(fused, which, golden):
    """Golden hashes recorded on PR 36's commit f182e32, PR 37's parent, under
    this conftest: ``value_and_grad`` written as ``grad_from_margins(margins(w),
    w)``, and ``hessian_vector`` over ``direction_margins``, are the parent's
    operations in the parent's order on the two-pass path (normalization with
    shifts and a prior with precisions on), and the fused path's three programs
    are untouched: what TRON and the tolerance pass compile in the cells that
    must not move."""
    from photon_ml_tpu.ops import GLMObjective, NormalizationContext, batch_from_dense, get_loss
    from photon_ml_tpu.ops.glm import hvp_fn, vg_fn

    n, d = 1024, 128
    rng = np.random.default_rng(0)
    batch = batch_from_dense(rng.normal(size=(n, d)), (rng.uniform(size=n) < 0.5).astype(float), dtype=jnp.float32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    obj = GLMObjective(
        loss=get_loss("logistic_regression"), batch=batch, l2=0.5,
        norm=NormalizationContext(factors=f32(rng.uniform(0.5, 2, d)), shifts=f32(rng.normal(size=d))),
        prior_mean=None if fused else f32(rng.normal(size=d)),
        prior_precision=None if fused else f32(rng.uniform(0.5, 2, d)), fused=fused)
    w = jnp.zeros(d, jnp.float32)
    if which == "value_and_grad":
        text = _lowered_text(jax.jit(lambda fn, a: fn(a)), vg_fn(obj), w)
    elif which == "hessian_vector":
        text = _lowered_text(jax.jit(lambda fn, a, b: fn(a, b)), hvp_fn(obj), w, w + 1)
    else:
        text = _lowered_text(jax.jit(lambda o, a: o.hessian_diagonal(a)), obj, w)
    assert text == golden


# -- a non-finite trial value is a failed step --------------------------------------


@pytest.mark.parametrize("l1", [0.0, 0.5], ids=["wolfe", "owlqn-armijo"])
@pytest.mark.parametrize("poison", [np.inf, np.nan], ids=["inf", "nan"])
def test_a_non_finite_trial_backtracks(l1, poison):
    """Poisson's ``exp`` overflows past z = 88 in f32: the trial's value is
    ``inf`` (or ``NaN`` once ``inf - inf`` appears). Here every point further
    than 0.75 from the start reads so: the search must halve its step, never
    accept such a point, and still reach the minimiser at distance 0.5."""
    target = jnp.asarray([0.5, 0.0, 0.0, 0.0], jnp.float32)

    def vg(w):
        f = 0.5 * jnp.sum((w - target) ** 2) * 100.0
        g = (w - target) * 100.0
        bad = jnp.sqrt(jnp.sum(w * w)) > 0.75
        return jnp.where(bad, poison, f), jnp.where(bad, poison, g)

    tol = jnp.asarray(1e-12, jnp.float32)
    r = solve_lbfgs(vg, jnp.zeros(4, jnp.float32), tol, tol, l1_weight=l1, max_iterations=50)
    assert np.all(np.isfinite(np.asarray(r.coefficients))) and np.isfinite(float(r.loss))
    assert int(r.reason) != int(ConvergenceReason.NUMERICAL_DIVERGENCE)
    # with l1 the minimiser is soft-thresholded: 0.5 - l1 / 100
    np.testing.assert_allclose(np.asarray(r.coefficients), [0.5 - l1 / 100.0, 0, 0, 0], atol=1e-4)
    if l1:
        assert int(r.line_search_evals) > int(r.iterations) + 1


# -- statistics on the device ---------------------------------------------------------


@pytest.fixture(scope="module")
def stats_case():
    rng = np.random.default_rng(5)
    n, d = 4500, 16
    sigma = 10.0 ** rng.uniform(-1, 1, d)
    x = (3.0 * sigma * rng.standard_normal(d) + sigma * rng.standard_normal((n, d))).astype(np.float32)
    x[:, -1] = 1.0
    x[::7, 3] = 0.0
    return x


@pytest.mark.parametrize("chunk", [1000, 4500, 65536], ids=["chunks-and-tail", "one-chunk", "default"])
def test_device_statistics_match_a_float64_two_pass(stats_case, monkeypatch, chunk):
    monkeypatch.setattr(stats_mod, "_DEVICE_CHUNK_ROWS", chunk)
    x = stats_case
    st = stats_mod.compute_feature_statistics(_batch(x, np.zeros(len(x), np.float32)))
    x64 = x.astype(np.float64)
    mean = x64.mean(0)
    np.testing.assert_allclose(st["mean"], mean, rtol=1e-5)
    np.testing.assert_allclose(st["variance"], ((x64 - mean) ** 2).mean(0), rtol=1e-5, atol=1e-12)
    np.testing.assert_array_equal(st["min"], x64.min(0))
    np.testing.assert_array_equal(st["max"], x64.max(0))
    np.testing.assert_array_equal(st["num_nonzeros"], (x != 0).sum(0))
    np.testing.assert_array_equal(st["max_magnitude"], np.abs(x64).max(0))
    np.testing.assert_array_equal(st["count"], np.full(x.shape[1], len(x)))


def test_device_and_host_statistics_build_the_same_normalization(stats_case):
    x = stats_case
    n, d = x.shape
    rows, cols = np.nonzero(x)
    raw = RawDataset(n_rows=n, labels=np.zeros(n), offsets=np.zeros(n), weights=np.ones(n),
                     shard_coo={"g": (rows, cols, x[rows, cols].astype(np.float64))},
                     shard_dims={"g": d}, id_tags={})
    host = stats_mod.compute_feature_statistics(raw, "g")
    device = stats_mod.compute_feature_statistics(_batch(x, np.zeros(n, np.float32)))
    assert set(host) == set(device)
    for key in host:
        np.testing.assert_allclose(device[key], host[key], rtol=1e-5, atol=1e-12, err_msg=key)
    built = [
        build_normalization("STANDARDIZATION", s["mean"], s["variance"], s["max_magnitude"], d - 1)
        for s in (host, device)
    ]
    np.testing.assert_allclose(built[0].factors, built[1].factors, rtol=1e-5)
    np.testing.assert_allclose(built[0].shifts, built[1].shifts, rtol=1e-5, atol=1e-7)
    assert float(built[1].factors[-1]) == 1.0 and float(built[1].shifts[-1]) == 0.0


def test_statistics_refuse_what_they_cannot_read(stats_case):
    n, d = stats_case.shape
    raw = RawDataset(n_rows=n, labels=np.zeros(n), offsets=np.zeros(n), weights=np.ones(n),
                     shard_coo={"g": (np.zeros(0, np.int64),) * 2 + (np.zeros(0),)}, shard_dims={"g": d},
                     id_tags={})
    with pytest.raises(TypeError, match="shard's name"):
        stats_mod.compute_feature_statistics(raw)
    with pytest.raises(TypeError, match="dense LabeledBatch alone"):
        stats_mod.compute_feature_statistics(_batch(stats_case, np.zeros(n, np.float32)), "g")


# -- what a sink sees -------------------------------------------------------------------


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _series(registry, name):
    return [m for m in registry.snapshot() if m["name"] == name]


def test_owlqn_solve_reports_its_path_to_a_sink(problem_data):
    x, y, batch, norm = problem_data
    lmax, _ = _lambda_max(x, y, norm)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    model = None
    with obs.use_run(run):
        results = []
        for lam in (lmax / 10, lmax / 100):
            problem = GLMProblem(task="poisson_regression", normalization=norm,
                                 config=_config(OptimizerType.LBFGS, "ELASTIC_NET", lam))
            model, r = problem.run(batch, initial_model=model, coordinate="global")
            results.append(r)
    solves = [s for s in sink.spans if s.name == "fe.solve"]
    assert len(solves) == 2
    for s, r, lam in zip(solves, results, (lmax / 10, lmax / 100)):
        assert s.attrs["optimizer"] == "OWLQN" and s.attrs["coordinate"] == "global"
        assert s.attrs["l1_weight"] == pytest.approx(0.5 * lam) and s.attrs["l2_weight"] == pytest.approx(0.5 * lam)
        assert s.attrs["nonzeros"] == int(r.nonzeros)
        assert s.attrs["line_search_evals"] == int(r.line_search_evals)
    norms = [s for s in sink.spans if s.name == "fe.normalization"]
    # out of the standardised space after each solve, into it before the warm-started one
    assert [s.attrs["direction"] for s in norms] == ["out", "in", "out"]
    assert all(s.attrs["coordinate"] == "global" for s in norms)
    evals, = _series(run.registry, "photon_fe_line_search_evals_total")
    zeroed, = _series(run.registry, "photon_fe_orthant_zeroed_total")
    support, = _series(run.registry, "photon_fe_nonzero_coefficients")
    assert evals["labels"] == zeroed["labels"] == support["labels"] == {"coordinate": "global"}
    assert evals["value"] == sum(int(r.line_search_evals) for r in results)
    assert zeroed["value"] == sum(int(r.orthant_zeroed) for r in results)
    assert support["value"] == int(results[-1].nonzeros)


def test_without_a_sink_the_path_fetches_and_records_nothing(problem_data):
    x, y, batch, norm = problem_data
    lmax, _ = _lambda_max(x, y, norm)
    run = obs.RunTelemetry()  # passive: no listener
    with obs.use_run(run):
        problem = GLMProblem(task="poisson_regression", normalization=norm,
                             config=_config(OptimizerType.LBFGS, "ELASTIC_NET", lmax / 10))
        problem.run(batch, coordinate="global")
    names = {m["name"] for m in run.registry.snapshot()}
    assert not names & {"photon_fe_line_search_evals_total", "photon_fe_orthant_zeroed_total",
                        "photon_fe_nonzero_coefficients", "photon_device_fetch_bytes_total"}
