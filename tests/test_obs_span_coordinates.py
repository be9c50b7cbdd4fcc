"""Every ``fe.*`` / ``re.*`` phase span of a fit says whose phase it is: it
carries the ``coordinate`` of the ``cd.coordinate`` span above it, so a reader
can tell one random effect's solve from another's; and the per-coordinate row
counter is recorded where the slot counter is. One- and two-random-effect fits
alike, tiny, on the CPU; no number here is a timing."""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.estimators import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import EventListener

PHASES = {
    "fe.solve", "fe.tolerances", "fe.score",
    "re.exchange", "re.warm_start", "re.bucket", "re.collect", "re.score",
}
RANDOM_EFFECTS = {
    "per-user": ("userShard", "userId", (40, 4), 16),
    "per-item": ("itemShard", "itemId", (6, 3), 64),
}


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _traced_fit(effects):
    def coordinate(name, shard, optimizer, **kw):
        return CoordinateConfig(
            name=name, feature_shard=shard, reg_weights=(1.0,),
            config=GLMOptimizationConfig(
                optimizer=OptimizerConfig(optimizer_type=optimizer, tolerance=1e-8, max_iterations=30),
                regularization=RegularizationContext("L2"),
            ),
            **kw,
        )

    configs = [coordinate("global", "global", OptimizerType.TRON)] + [
        coordinate(name, RANDOM_EFFECTS[name][0], OptimizerType.LBFGS,
                   random_effect_type=RANDOM_EFFECTS[name][1], active_cap=RANDOM_EFFECTS[name][3])
        for name in effects
    ]
    full = mixed_data_to_raw_dataset(generate_mixed_effect_data(
        n=900, d_fixed=5, re_specs={RANDOM_EFFECTS[n][1]: RANDOM_EFFECTS[n][2] for n in effects}, seed=11,
    ))
    train, val = full.subset(np.arange(700)), full.subset(np.arange(700, 900))
    estimator = GameEstimator(
        task="logistic_regression", coordinate_configs=configs, n_cd_iterations=2,
        evaluator_specs=["AUC"], dtype=jnp.float64, validation_frequency="SWEEP",
    )
    run, spans = obs.RunTelemetry(), _Spans()
    run.register_listener(spans)
    with obs.use_run(run):
        datasets = estimator.prepare_datasets(train)
        estimator.fit(train, validation=val, datasets=datasets)
    return spans.spans, run.registry.snapshot(), datasets


@pytest.fixture(scope="module", params=[("per-user",), ("per-user", "per-item")], ids=["one-re", "two-re"])
def traced(request):
    return (request.param, *_traced_fit(request.param))


def _coordinate_above(span, by_id):
    while span.parent_id is not None:
        span = by_id[span.parent_id]
        if span.name == "cd.coordinate":
            return span.attrs["coordinate"]
    return None


def test_phase_spans_carry_the_coordinate_above_them(traced):
    effects, spans, _, _ = traced
    by_id = {s.span_id: s for s in spans}
    phases = [s for s in spans if s.name.startswith(("fe.", "re."))]
    assert {s.name for s in phases} == PHASES
    seen = set()
    for s in phases:
        above = _coordinate_above(s, by_id)
        assert above is not None, s.name  # no phase span outside an update
        assert s.attrs["coordinate"] == above, (s.name, s.attrs.get("coordinate"), above)
        assert s.name.startswith("fe.") == (above == "global")
        seen.add((s.name.split(".")[0], above))
    assert seen == {("fe", "global")} | {("re", name) for name in effects}


def test_rows_counter_is_per_coordinate_and_host_known(traced):
    effects, _, snapshot, datasets = traced

    def total(name, **labels):
        return sum(m["value"] for m in snapshot
                   if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items()))

    trains = 2  # one train call per sweep
    for name in effects:
        ds = datasets[name]
        active, passive = int(ds.entity_counts.sum()), len(ds.passive_rows)
        assert active + passive == 700 and passive > 0  # the cap bites in both effects
        assert total("photon_re_rows_total", coordinate=name, kind="active") == trains * active
        assert total("photon_re_rows_total", coordinate=name, kind="passive") == trains * passive
        # beside the slot counter: the same train calls
        assert total("photon_re_block_slots_total", coordinate=name, kind="real") == trains * active
    assert total("photon_re_rows_total") == trains * 700 * len(effects)
