"""The validation context is built once per validation set: a fit that presents
a ``RawDataset`` some fit has presented before, under the same evaluators,
dtype and coordinate shards, re-uses the suite, the device batches and the
evaluator's program; anything else runs the build. A tiny fixed + per-user
model on the CPU; no number here is a timing."""

import concurrent.futures
import copy
import gc
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.estimators import CoordinateConfig, GameEstimator
from photon_ml_tpu.estimators import game_estimator
from photon_ml_tpu.game.descent import CoordinateDescent
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import EventListener

SPAN = "fit.validation_context"
COUNTER = "photon_validation_context_total"


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _coordinate(name, shard, optimizer=OptimizerType.LBFGS, reg_weight=1.0, **kw):
    return CoordinateConfig(
        name=name, feature_shard=shard, reg_weights=(reg_weight,),
        config=GLMOptimizationConfig(
            optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=1e-8, max_iterations=30),
            regularization=RegularizationContext("L2"),
        ),
        **kw,
    )


def _estimator(reg_weight=1.0, fixed_only=False, **kw):
    coordinates = [_coordinate("global", "global", OptimizerType.TRON, reg_weight)]
    if not fixed_only:
        coordinates.append(
            _coordinate("per-user", "userShard", reg_weight=reg_weight, random_effect_type="userId")
        )
    kw.setdefault("evaluator_specs", ["AUC"])
    kw.setdefault("dtype", jnp.float64)
    return GameEstimator(
        task="logistic_regression", coordinate_configs=coordinates, n_cd_iterations=2,
        validation_frequency="SWEEP", **kw,
    )


@pytest.fixture(scope="module")
def data():
    full = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(n=900, d_fixed=5, re_specs={"userId": (40, 4)}, seed=11)
    )
    train = full.subset(np.arange(700))
    return train, full, _estimator().prepare_datasets(train)


@pytest.fixture
def val(data):
    """A validation set of this test's own: no earlier test has presented it."""
    return data[1].subset(np.arange(700, 900))


def _equal_copy(raw):
    """Another data set of equal content, sharing no array with ``raw``."""
    return copy.deepcopy(raw)


class _Traced:
    """One run with a span listener; every fit made through it is recorded."""

    def __init__(self, data):
        self.train, _, self.datasets = data
        self.run, self.listener = obs.RunTelemetry(), _Spans()
        self.run.register_listener(self.listener)

    def fit(self, estimator, validation, datasets=None):
        with obs.use_run(self.run):
            return estimator.fit(
                self.train, validation=validation,
                datasets=self.datasets if datasets is None else datasets,
            )

    def contexts(self):
        return [s for s in self.listener.spans if s.name == SPAN]

    def roots(self):
        return [s for s in self.listener.spans if s.name == "fit"]

    def count(self, kind):
        return _counter(self.run.registry.snapshot(), COUNTER, kind=kind)


def _counter(snapshot, name, **labels):
    return sum(
        m["value"] for m in snapshot
        if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items())
    )


def _entries(raw):
    return [k for k, v in game_estimator._VALIDATION_CONTEXTS.items() if v[0]() is raw]


def _coefficients(result):
    fixed, per_user = result.model.models["global"], result.model.models["per-user"]
    return [
        np.asarray(jax.device_get(x))
        for x in (fixed.model.coefficients.means, per_user.coef_values, per_user.coef_indices)
    ]


# -- a hit: the same validation object, presented again -----------------------


@pytest.fixture(scope="module")
def three_fits(data):
    """built, reused (the same estimator and object), and the parent's path: a
    fresh estimator on an equal-content copy, which shares nothing and builds."""
    val = data[1].subset(np.arange(700, 900))
    traced, evaluations = _Traced(data), []
    real_run = CoordinateDescent.run

    def recording_run(self, *args, **kw):
        out = real_run(self, *args, **kw)
        evaluations.append([(name, dict(res.metrics)) for name, res in out.evaluations])
        return out

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(CoordinateDescent, "run", recording_run)
        estimator = _estimator()
        results = [
            traced.fit(estimator, val)[0],
            traced.fit(estimator, val)[0],
            traced.fit(_estimator(), _equal_copy(val))[0],
        ]
    return results, evaluations, traced


WHAT = {
    "coefficients": lambda results, evaluations, i: _coefficients(results[i]),
    "evaluations": lambda results, evaluations, i: evaluations[i],
    "best_evaluation": lambda results, evaluations, i: [
        results[i].evaluation.primary_name, dict(results[i].evaluation.metrics)
    ],
}


@pytest.mark.parametrize("what", sorted(WHAT))
def test_a_reusing_fit_is_bit_equal_to_a_building_one(three_fits, what):
    results, evaluations, _ = three_fits
    built, reused, fresh = (WHAT[what](results, evaluations, i) for i in range(3))
    assert len(evaluations[0]) == 2  # one a sweep
    for other in (reused, fresh):
        if what == "coefficients":
            for a, b in zip(built, other):
                np.testing.assert_array_equal(a, b)
        else:
            assert built == other


def test_the_span_says_reused_and_uploads_nothing(three_fits):
    _, _, traced = three_fits
    built, reused, fresh = traced.contexts()
    assert [s.attrs["reused"] for s in (built, reused, fresh)] == [False, True, False]
    assert all(s.attrs["rows"] == 200 for s in (built, reused, fresh))
    # the attribute is there on a hit too, so the series is in the registry
    assert reused.attrs["put_bytes"] == 0
    assert built.attrs["put_bytes"] == fresh.attrs["put_bytes"] > 0
    # every fit opens the span under its own root
    assert [s.parent_id for s in (built, reused, fresh)] == [s.span_id for s in traced.roots()]


def test_a_reusing_fit_traces_no_function_again(three_fits):
    """The suite's lazily built evaluator (a ``jit`` closure a suite) lives with
    the context: every re-trace of a warm fit was its."""
    _, _, traced = three_fits
    built, reused, fresh = traced.roots()
    assert "retraces" not in reused.attrs and "retrace_s" not in reused.attrs
    # a fit that builds makes a new evaluator and traces it, warm or not
    assert fresh.attrs["retraces"] > 0
    assert built.attrs["retraces"] >= fresh.attrs["retraces"]


def test_the_counter_counts_one_a_call(three_fits):
    _, _, traced = three_fits
    assert (traced.count("built"), traced.count("reused")) == (2, 1)


def test_a_reusing_fit_fetches_what_a_building_fit_fetches(data, val, caplog):
    # at INFO the optimization summary fetches on its own account
    caplog.set_level(logging.WARNING, logger="photon_ml_tpu")
    fetched = []
    for _ in range(2):
        run = obs.RunTelemetry()  # a registry, no listener: the benchmark's untraced fit
        with obs.use_run(run):
            _estimator().fit(data[0], validation=val, datasets=data[2])
        fetched.append({
            m["labels"]["site"]: m["value"] for m in run.registry.snapshot()
            if m["name"] == "photon_device_fetch_bytes_total"
        })
        assert _counter(run.registry.snapshot(), COUNTER, kind="reused") == len(fetched) - 1
    assert fetched[0] == fetched[1] and fetched[0]


def test_a_fit_without_validation_asks_for_no_context(data):
    traced = _Traced(data)
    (result,) = traced.fit(_estimator(), None)
    assert result.evaluation is None
    assert traced.contexts() == []
    assert not [m for m in traced.run.registry.snapshot() if m["name"] == COUNTER]


# -- misses: each runs the build ----------------------------------------------


def _reassign_labels(val):
    val.labels = val.labels.copy()
    return val


def _reassign_shard(val):
    rows, cols, vals = val.shard_coo["userShard"]
    val.shard_coo["userShard"] = (rows, cols, vals.copy())
    return val


MISSES = {
    "equal_copy": lambda val: (_estimator(), _equal_copy(val)),
    "reassigned_labels": lambda val: (_estimator(), _reassign_labels(val)),
    "reassigned_shard": lambda val: (_estimator(), _reassign_shard(val)),
    "other_evaluators": lambda val: (_estimator(evaluator_specs=["AUC", "LOGISTIC_LOSS"]), val),
    "other_dtype": lambda val: (_estimator(dtype=jnp.float32), val),
    "other_coordinates": lambda val: (_estimator(fixed_only=True), val),
}


@pytest.mark.parametrize("case", sorted(MISSES))
def test_a_miss_runs_the_build(data, val, case):
    traced = _Traced(data)
    first = traced.fit(_estimator(), val)[0]
    estimator, presented = MISSES[case](val)
    datasets = estimator.prepare_datasets(data[0]) if case in ("other_dtype", "other_coordinates") else None
    second = traced.fit(estimator, presented, datasets=datasets)[0]
    built, missed = traced.contexts()
    assert (built.attrs["reused"], missed.attrs["reused"]) == (False, False)
    assert missed.attrs["put_bytes"] > 0
    assert (traced.count("built"), traced.count("reused")) == (2, 0)
    assert traced.roots()[1].attrs["retraces"] > 0
    if case in ("equal_copy", "reassigned_labels", "reassigned_shard"):
        # the same content read anew: the same model, bit for bit
        for a, b in zip(_coefficients(first), _coefficients(second)):
            np.testing.assert_array_equal(a, b)
        assert first.evaluation == second.evaluation
    # a re-read replaces its entry; another signature or data set adds one
    n_entries = len(_entries(val)) + (len(_entries(presented)) if presented is not val else 0)
    assert n_entries == (1 if case.startswith("reassigned") else 2)


def test_writing_in_place_is_not_seen(data, val):
    """The contract's other side: the data set is read once."""
    traced = _Traced(data)
    first = traced.fit(_estimator(), val)[0]
    val.labels[:] = 1.0 - val.labels
    second = traced.fit(_estimator(), val)[0]
    assert traced.contexts()[1].attrs["reused"] is True
    assert first.evaluation == second.evaluation


# -- who shares a context -----------------------------------------------------


def test_estimators_of_one_signature_share_one_context(data, val):
    """The tuner's shape: a new estimator a trial, other regularisation weights
    and sweep counts, one validation set."""
    traced = _Traced(data)
    a, b = _estimator(reg_weight=1.0), _estimator(reg_weight=10.0, pipeline_depth=1)
    b.n_cd_iterations = 1
    first, second = traced.fit(a, val)[0], traced.fit(b, val)[0]
    assert [s.attrs["reused"] for s in traced.contexts()] == [False, True]
    assert a._validation_context(val)[0] is b._validation_context(val)[0]
    assert len(_entries(val)) == 1
    # the shared context decides nothing about the model
    assert not np.array_equal(_coefficients(first)[0], _coefficients(second)[0])


def test_fit_lanes_hits_the_context_a_fit_built(data, val):
    train, _, datasets = data
    traced = _Traced(data)
    traced.fit(_estimator(), val)
    combos = [{"global": w, "per-user": w} for w in (0.5, 5.0)]
    with obs.use_run(traced.run):
        lanes = _estimator().fit_lanes(train, combos, validation=val, datasets=datasets)
    assert [s.attrs["reused"] for s in traced.contexts()] == [False, True]
    assert all(r.evaluation is not None for r in lanes)


def _future(val):
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        return pool.submit(lambda: val)


@pytest.mark.parametrize("defer", [_future, lambda val: (lambda: val)], ids=["future", "callable"])
def test_a_deferred_validation_hits(data, val, defer):
    traced = _Traced(data)
    first = traced.fit(_estimator(), val)[0]
    second = traced.fit(_estimator(), defer(val))[0]
    assert [s.attrs["reused"] for s in traced.contexts()] == [False, True]
    assert first.evaluation == second.evaluation


# -- lifetime -----------------------------------------------------------------


def test_the_context_goes_when_the_validation_set_goes(data):
    """Nothing but the caller keeps a context alive: not the estimator, not the
    fit's results, not the run. (Other tests' data sets may be alive in this
    process, so "empty" is said of this set's entries.)"""
    memo = game_estimator._VALIDATION_CONTEXTS
    val = data[1].subset(np.arange(700, 900))
    before = set(memo)
    estimator = _estimator()
    results = estimator.fit(data[0], validation=val, datasets=data[2])
    _estimator(evaluator_specs=["AUC", "RMSE"]).fit(data[0], validation=val, datasets=data[2])
    mine = set(memo) - before
    assert len(mine) == 2 and {k[0] for k in mine} == {id(val)}
    del val
    gc.collect()
    assert not mine & set(memo)
    assert all(entry[0]() is not None for entry in memo.values())
    assert results[0].evaluation is not None and estimator is not None


# -- the benchmark's readers over a re-using fit ------------------------------


def test_the_readers_read_numbers_over_a_reusing_fit(data, val):
    """``fit_validation_ctx_s`` and ``fit_put_bytes`` return None when the span
    or the series is absent, and a null on an accepted line is refused: the
    traced part of a cell sees hits only, in a fresh registry."""
    from benchmark import fit_spans
    from benchmark.layer_metrics import fit_put_bytes, fit_validation_ctx_s
    from benchmark.observe import Observations, SpanCollector

    train, _, datasets = data
    estimator = _estimator()
    estimator.fit(train, validation=val, datasets=datasets)  # the warm-up fit builds
    run, collector = obs.RunTelemetry(), SpanCollector()  # the traced part: a fresh registry
    run.register_listener(collector)
    windows = []
    with obs.use_run(run):
        for _ in range(2):
            start = time.perf_counter()
            estimator.fit(train, validation=val, datasets=datasets)
            windows.append((start, time.perf_counter()))
    observations = Observations(
        fit_windows=windows, spans=collector.spans, counters=run.registry.snapshot(),
        listener=None, setup_spans={}, job=None, peak={}, chips=1, memory_peak_bytes=0,
    )
    assert all(s.attrs["reused"] for s in observations.spans_named(SPAN))
    seconds = fit_spans.per_fit_sum_s(observations, SPAN)
    assert seconds is not None and seconds == fit_validation_ctx_s.read(observations)
    assert 0.0 <= seconds < 0.05  # a look-up, no build: the CPU's clock, a bound and no timing
    assert fit_spans.counter_per_fit(observations, "photon_device_put_bytes_total") == 0.0
    assert fit_put_bytes.read(observations) == 0.0
    assert fit_spans.root_attr(observations, "retraces") == 0.0
    assert fit_spans.counter_per_fit(observations, COUNTER, kind="reused") == 1.0
    assert fit_spans.counter_per_fit(observations, COUNTER, kind="built") is None
