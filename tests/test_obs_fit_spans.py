"""One span tree per ``GameEstimator.fit``: stable names, one ``root_id`` per
fit, the device-phase spans below ``cd.train`` / ``cd.score`` with their
attributes, the counters recorded at the same boundaries, and the promise
that with no listener attached none of it waits for, or fetches from, the
device. A tiny fixed + per-user fit on the CPU; no number here is a timing."""

import collections
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.estimators import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.coordinate import _size_buckets
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import EventListener

N_SWEEPS = 2
# what a sink's presence adds to the fetch ledger, and nothing else may
SINK_ONLY_FETCH_SITES = {
    "re.bucket_iterations", "tracker_metrics", "tracker_aggregates", "solver.tron",
}


# the device phases: each waits for its result before it closes
FENCED = {
    "fe.solve", "fe.tolerances", "fe.score",
    "re.exchange", "re.warm_start", "re.bucket", "re.collect", "re.score",
}


# where the untraced fit of this file fetches (and so waits for the device)
# (coordinate.host_state is the CPU backend's: the solver's state as host numpy)
UNTRACED_FETCH_SITES = {
    "cd.update_guard", "coordinate.project_layout", "coordinate.host_state",
    "evaluation.device_metrics",
}


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


def _estimator():
    def coordinate(name, shard, optimizer, **kw):
        return CoordinateConfig(
            name=name, feature_shard=shard, reg_weights=(1.0,),
            config=GLMOptimizationConfig(
                optimizer=OptimizerConfig(
                    optimizer_type=optimizer, tolerance=1e-8, max_iterations=30
                ),
                regularization=RegularizationContext("L2"),
            ),
            **kw,
        )

    return GameEstimator(
        task="logistic_regression",
        coordinate_configs=[
            coordinate("global", "global", OptimizerType.TRON),
            coordinate("per-user", "userShard", OptimizerType.LBFGS, random_effect_type="userId"),
        ],
        n_cd_iterations=N_SWEEPS,
        evaluator_specs=["AUC"],
        dtype=jnp.float64,
        validation_frequency="SWEEP",
    )


@pytest.fixture(scope="module")
def data():
    full = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(n=900, d_fixed=5, re_specs={"userId": (40, 4)}, seed=11)
    )
    train, val = full.subset(np.arange(700)), full.subset(np.arange(700, 900))
    return train, val, _estimator().prepare_datasets(train)


def _fit(data, run, n_fits=1):
    train, val, datasets = data
    with obs.use_run(run):
        return [_estimator().fit(train, validation=val, datasets=datasets) for _ in range(n_fits)]


@pytest.fixture(scope="module")
def traced(data):
    """Two fits with a collecting listener and the timeline recorder. (The
    INFO optimization summary fetches on its own account, under
    cd.coordinate, and an earlier test of the process may have left INFO on.)"""
    run, spans, timeline = obs.RunTelemetry(), _Spans(), obs.TimelineRecorder()
    run.register_listener(spans)
    run.register_listener(timeline)
    logger = logging.getLogger("photon_ml_tpu")
    level = logger.level
    logger.setLevel(logging.WARNING)
    try:
        results = _fit(data, run, n_fits=2)
    finally:
        logger.setLevel(level)
    return results, spans.spans, run.registry.snapshot(), timeline


def _by_root(spans):
    trees = collections.defaultdict(list)
    for s in spans:
        trees[s.root_id].append(s)
    return trees


def _counter(snapshot, name, **labels):
    return sum(
        m["value"] for m in snapshot
        if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items())
    )


def test_tree_shape_and_stable_names(traced):
    _, spans, _, _ = traced
    by_id = {s.span_id: s for s in spans}
    parents = collections.defaultdict(set)
    for s in spans:
        parents[s.name].add(by_id[s.parent_id].name if s.parent_id else None)
    assert dict(parents) == {
        "fit": {None},
        "fit.validation_context": {"fit"},
        "fit.combo": {"fit"},
        "fit.make_coordinates": {"fit.combo"},
        "cd.init": {"fit.combo"},
        "cd.sweep": {"fit.combo"},
        "cd.coordinate": {"cd.sweep"},
        "cd.eval": {"cd.sweep"},
        "evaluate.device": {"cd.eval"},
        "cd.train": {"cd.coordinate"},
        "cd.tracker": {"cd.coordinate"},
        "cd.score": {"cd.coordinate"},
        "cd.guard": {"cd.coordinate"},
        "fe.solve": {"cd.train"},
        "fe.tolerances": {"fe.solve"},
        "fe.score": {"cd.score"},
        "re.exchange": {"cd.train"},
        "re.warm_start": {"cd.train"},
        "re.bucket": {"cd.train"},
        "re.collect": {"cd.train"},
        "re.score": {"cd.score"},
        # a blocking fetch is a leaf under the span that waited for it: the
        # TRON solve's own metrics, the projection's layout, a bucket's
        # iterations, the tracker's reductions, the guard, the metric
        "fetch": {"fe.solve", "re.warm_start", "cd.train", "cd.tracker", "cd.guard", "evaluate.device"},
    }
    # everything variable is an attribute, never part of a name
    fit = next(s for s in spans if s.name == "fit")
    assert fit.attrs["n_combos"] == 1
    combo = next(s for s in spans if s.name == "fit.combo")
    assert combo.attrs["index"] == 0
    assert combo.attrs["reg_weights"] == {"global": 1.0, "per-user": 1.0}
    train = [s for s in spans if s.name == "cd.train" and s.root_id == fit.root_id]
    assert [(s.attrs["iteration"], s.attrs["coordinate"], s.attrs["phase"]) for s in train] == [
        (it, name, "solve") for it in range(N_SWEEPS) for name in ("global", "per-user")
    ]
    solve = next(s for s in spans if s.name == "fe.solve")
    assert (solve.attrs["optimizer"], solve.attrs["reg_weight"]) == ("TRON", 1.0)
    # the device phases were fenced (a sink is attached) and say so; the
    # spans that only group them carry no such mark, and no new span below
    # cd.train / cd.score carries a phase
    for s in spans:
        assert (s.attrs.get("device") is True) == (s.name in FENCED), s.name
        if s.name in FENCED:
            assert "phase" not in s.attrs
    warm = [s for s in spans if s.name == "re.warm_start" and s.root_id == fit.root_id]
    # the first sweep starts from a zero model, every later one from the last
    assert [(s.attrs["coordinate"], s.attrs["warm"], s.attrs["priors"]) for s in warm] == [
        ("per-user", it > 0, False) for it in range(N_SWEEPS)
    ]
    val_ctx = next(s for s in spans if s.name == "fit.validation_context")
    assert val_ctx.attrs["rows"] == 200 and val_ctx.attrs["put_bytes"] > 0


def test_a_fenced_span_splits_into_enqueue_and_wait(traced):
    """``Span.sync`` stamps the span it fences: the host's seconds up to the
    fence, the seconds inside it, and nothing left over but the span's close;
    a bucket also says how much of its enqueue was the cut."""
    _, spans, _, _ = traced
    assert {s.name for s in spans if "wait_s" in s.attrs} == FENCED
    for s in spans:
        if s.name not in FENCED:
            assert not {"enqueue_s", "wait_s", "cut_s"} & set(s.attrs), s.name
            continue
        assert 0.0 <= s.attrs["enqueue_s"] and 0.0 <= s.attrs["wait_s"], s.name
        assert s.attrs["enqueue_s"] + s.attrs["wait_s"] <= s.duration_s, s.name
        if s.name == "re.bucket":
            assert 0.0 <= s.attrs["cut_s"] <= s.attrs["enqueue_s"]
        else:
            assert "cut_s" not in s.attrs


def test_a_fence_twice_sums_the_waits_and_keeps_the_first_enqueue():
    run = obs.RunTelemetry()
    run.register_listener(_Spans())
    with obs.use_run(run), obs.span("phase") as sp:
        sp.sync(jnp.ones(3))
        first = dict(sp.attrs)
        sp.sync(jnp.ones(3))
    assert sp.attrs["enqueue_s"] == first["enqueue_s"]
    assert sp.attrs["wait_s"] > first["wait_s"]
    assert sp.attrs["enqueue_s"] + sp.attrs["wait_s"] <= sp.duration_s


def test_fetch_spans_and_the_seconds_counter_agree_with_the_bytes_counter(traced):
    """Every blocking fetch is one ``fetch`` leaf with its site and bytes, and
    ``photon_device_fetch_seconds_total`` has the bytes counter's sites."""
    _, spans, snapshot, _ = traced
    fetches = [s for s in spans if s.name == "fetch"]
    by_site = collections.Counter()
    for s in fetches:
        by_site[s.attrs["site"]] += s.attrs["bytes"]
        assert s.attrs["bytes"] > 0 and s.duration_s >= 0.0
    parents = {s.parent_id for s in spans}
    assert not any(s.span_id in parents for s in fetches)  # leaves
    counted = {
        name: {m["labels"]["site"]: m["value"] for m in snapshot if m["name"] == name}
        for name in ("photon_device_fetch_bytes_total", "photon_device_fetch_seconds_total")
    }
    assert dict(by_site) == counted["photon_device_fetch_bytes_total"]
    seconds = counted["photon_device_fetch_seconds_total"]
    assert set(seconds) == set(by_site)
    for site, total in seconds.items():
        assert total == pytest.approx(sum(s.duration_s for s in fetches if s.attrs["site"] == site))
    # the warm start's fetches are the projection's (and, on the CPU backend,
    # the projected state's way to host numpy): it is their only parent
    by_id = {s.span_id: s for s in spans}
    for site in ("coordinate.project_layout", "coordinate.host_state"):
        assert {by_id[s.parent_id].name for s in fetches if s.attrs["site"] == site} == {"re.warm_start"}


def test_a_fetch_outside_any_span_is_counted_and_makes_no_span():
    """The serving worker fetches once a batch under no span: the counters
    have it, the sinks are not sent a leaf with no tree."""
    from photon_ml_tpu.utils.transfer import logged_fetch

    run, spans = obs.RunTelemetry(), _Spans()
    run.register_listener(spans)
    with obs.use_run(run):
        logged_fetch("somewhere", jnp.ones(4))
        with obs.span("phase"):
            logged_fetch("somewhere", jnp.ones(4))
            logged_fetch("somewhere", np.ones(4))  # host numpy: no fetch at all
    assert [(s.name, s.attrs.get("site")) for s in spans.spans] == [("fetch", "somewhere"), ("phase", None)]
    snapshot = run.registry.snapshot()
    assert _counter(snapshot, "photon_device_fetch_bytes_total", site="somewhere") == 2 * jnp.ones(4).nbytes
    assert _counter(snapshot, "photon_device_fetch_seconds_total", site="somewhere") > 0.0


def test_a_random_effects_train_call_is_covered_by_its_children(traced):
    """``re.exchange``, ``re.warm_start``, the buckets and their sink-only
    fetches, ``re.collect``: what is left of ``cd.train`` is the loop's own
    Python (most of a CPU fit at this size; the chip's figure is PERF.md's)."""
    _, spans, _, _ = traced
    trains = [s for s in spans if s.name == "cd.train" and s.attrs["coordinate"] == "per-user"]
    assert len(trains) == 2 * N_SWEEPS
    for train in trains:
        children = [s for s in spans if s.parent_id == train.span_id]
        assert [s.name for s in children if s.name != "fetch"][:2] == ["re.exchange", "re.warm_start"]
        assert children[-1].name == "re.collect"
        self_s = train.duration_s - sum(s.duration_s for s in children)
        assert 0.0 <= self_s < 0.2 * train.duration_s


def test_one_root_id_per_fit(traced):
    _, spans, _, _ = traced
    trees = _by_root(spans)
    assert len(trees) == 2
    for root_id, tree in trees.items():
        roots = [s for s in tree if s.parent_id is None]
        assert [(s.name, s.span_id) for s in roots] == [("fit", root_id)]
        assert all(s.attrs["root_id"] == root_id for s in tree)  # what sinks keep
        assert sum(s.name == "cd.sweep" for s in tree) == N_SWEEPS
    a, b = (sorted(s.name for s in tree) for tree in trees.values())
    assert a == b


def _assert_buckets_agree_with_the_dataset(dataset, buckets, snapshot, n_trains):
    """``re.bucket`` spans and photon_re_block_slots_total against the
    dataset's own segmentation: a bucket is rows [start, end) of every chunk,
    and every slot handed to the solver (chunks * n_b * K_b) is counted."""
    chunks = dataset.entity_chunks
    n_entities = dataset.blocks.features.shape[0]
    real_rows = int(dataset.entity_counts.sum())
    segments = _size_buckets(dataset)
    assert segments is not None and len(segments) > 1
    assert len(buckets) == n_trains * len(segments)
    one_train = buckets[: len(segments)]
    assert [(s.attrs["k"], s.attrs["s"], s.attrs["entities"]) for s in one_train] == [
        (kb, sb, chunks * (end - start)) for start, end, kb, sb in segments
    ]
    assert all(s.attrs["chunks"] == chunks for s in one_train)
    assert sum(s.attrs["entities"] for s in one_train) == n_entities
    assert sum(s.attrs["real_rows"] for s in one_train) == real_rows
    by_chunk = dataset.entity_counts.reshape(chunks, -1)
    assert [s.attrs["max_chunk_real_rows"] for s in one_train] == [
        by_chunk[:, start:end].sum(axis=1).max() for start, end, _, _ in segments
    ]
    padded_slots = sum(chunks * (end - start) * kb for start, end, kb, _ in segments)
    assert sum(s.attrs["slots"] for s in one_train) == padded_slots
    label = dict(coordinate=dataset.coordinate_id)
    real = _counter(snapshot, "photon_re_block_slots_total", kind="real", **label)
    padded = _counter(snapshot, "photon_re_block_slots_total", kind="padded", **label)
    assert real == n_trains * real_rows
    assert real + padded == n_trains * padded_slots


def test_bucket_attributes_and_slot_counter_agree_with_the_dataset(traced, data):
    _, spans, snapshot, _ = traced
    dataset = data[2]["per-user"]
    assert dataset.entity_chunks == 1
    buckets = [s for s in spans if s.name == "re.bucket"]
    _assert_buckets_agree_with_the_dataset(dataset, buckets, snapshot, 2 * N_SWEEPS)
    # one chunk holds every real row of a bucket
    assert all(s.attrs["max_chunk_real_rows"] == s.attrs["real_rows"] for s in buckets)
    # the exchange gathers the slots its train call's buckets solve, and says
    # beside them how many the [E, K] plane they are cut from holds
    n_entities, k, _ = dataset.blocks.features.shape
    exchanges = [s for s in spans if s.name == "re.exchange"]
    assert len(exchanges) == 2 * N_SWEEPS
    for exchange in exchanges:
        train = exchange.parent_id
        solved = sum(s.attrs["slots"] for s in buckets if s.parent_id == train)
        assert 0 < solved < n_entities * k
        assert exchange.attrs["entities"] == n_entities
        assert (exchange.attrs["slots"], exchange.attrs["block_slots"]) == (solved, n_entities * k)


@pytest.mark.parametrize("chunks", [4, 8])
def test_bucket_spans_of_a_dealt_dataset_give_the_chips_balance(chunks):
    """Under a mesh the dataset holds one size-sorted chunk a chip: a bucket's
    span says how many chunks it draws on and the largest chunk's real rows,
    so that sum(real_rows) / sum(chunks * max_chunk_real_rows) is the balance."""
    from photon_ml_tpu.game import RandomEffectCoordinate, build_random_effect_dataset
    from photon_ml_tpu.parallel import data_parallel_mesh, shard_entity_blocks
    import dataclasses

    raw = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=4000, d_fixed=4, re_specs={"userId": (203, 4)}, seed=3, entity_skew=1.2
        )
    )
    dataset = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", active_cap=32,
        pad_entities_to_multiple=chunks, dtype=jnp.float64,
    )
    dataset = dataclasses.replace(
        dataset, blocks=shard_entity_blocks(dataset.blocks, data_parallel_mesh(chunks))
    )
    assert dataset.entity_chunks == chunks
    config = GLMOptimizationConfig(
        optimizer=OptimizerConfig(tolerance=1e-6, max_iterations=10),
        regularization=RegularizationContext("L2"),
        reg_weight=1.0,
    )
    run, spans = obs.RunTelemetry(), _Spans()
    run.register_listener(spans)
    with obs.use_run(run):
        RandomEffectCoordinate(
            dataset=dataset, task="logistic_regression", config=config
        ).train(None)
    buckets = [s for s in spans.spans if s.name == "re.bucket"]
    _assert_buckets_agree_with_the_dataset(dataset, buckets, run.registry.snapshot(), 1)
    balance = 100.0 * sum(s.attrs["real_rows"] for s in buckets) / sum(
        s.attrs["chunks"] * s.attrs["max_chunk_real_rows"] for s in buckets
    )
    # an even deal reads 100; 200 users over 4 or 8 chunks: one user a bucket off
    assert 85.0 < balance <= 100.0


def test_lane_iterations_are_useful_over_issued(traced):
    results, _, snapshot, _ = traced
    useful = _counter(snapshot, "photon_re_lane_iterations_total", coordinate="per-user", kind="useful")
    issued = _counter(snapshot, "photon_re_lane_iterations_total", coordinate="per-user", kind="issued")
    assert 0 < useful <= issued
    # the last update's share of it is what that update's tracker holds
    last = np.asarray(jax.device_get(results[-1][0].trackers["per-user"].result.iterations))
    assert useful >= last.sum()


def test_cg_count_equals_the_hessian_vector_products_made(data, monkeypatch):
    """``photon_cd_cg_iterations`` against a count taken where the work is:
    every CG iteration makes exactly one Hessian-vector product."""
    from photon_ml_tpu.ops import glm

    calls = []

    def counting_hvp(inner, w, v):
        jax.debug.callback(lambda: calls.append(1))
        return inner(w, v)

    real = glm.hvp_fn
    monkeypatch.setattr(
        glm, "hvp_fn", lambda objective: jax.tree_util.Partial(counting_hvp, real(objective))
    )
    run = obs.RunTelemetry()
    run.register_listener(_Spans())
    results = _fit(data, run)[0]
    jax.effects_barrier()
    (summary,) = [m for m in run.registry.snapshot() if m["name"] == "photon_cd_cg_iterations"]
    assert summary["labels"] == {"coordinate": "global"}
    assert summary["stat"]["count"] == N_SWEEPS
    assert summary["sum"] == len(calls) > 0
    # L-BFGS has no inner CG: the per-user result carries zeros
    per_user = results[0].trackers["per-user"].result
    assert not np.asarray(jax.device_get(per_user.cg_iterations)).any()
    assert per_user.cg_iterations.shape == per_user.iterations.shape


def test_no_listener_no_fence_no_fetch_and_the_same_model(traced, data, monkeypatch, caplog):
    # the INFO optimization summary fetches on its own account (tracker_summary,
    # tracker_aggregates), and an earlier test of the process may have left INFO on
    caplog.set_level(logging.WARNING, logger="photon_ml_tpu")
    fences = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (fences.append(1), real(x))[1])
    quiet = obs.RunTelemetry()  # a registry, no listener
    recorded = []
    real_record = obs.tracing.record_span
    monkeypatch.setattr(
        obs.tracing, "record_span",
        lambda *a, **kw: (recorded.append(real_record(*a, **kw)), recorded[-1])[1],
    )
    synced = []
    real_sync = obs.Span.sync
    monkeypatch.setattr(
        obs.Span, "sync", lambda self, *arrays: (real_sync(self, *arrays), synced.append(self))[0]
    )
    untraced = _fit(data, quiet)[0]
    assert fences == []
    # every fetch still passes the one place a ``fetch`` span would be made,
    # and none is; every phase still calls ``sync``, and none is stamped
    assert recorded and set(recorded) == {None}
    assert {s.name for s in synced} == FENCED
    assert not any({"enqueue_s", "wait_s", "cut_s", "device"} & set(s.attrs) for s in synced)
    sites = {
        m["labels"]["site"] for m in quiet.registry.snapshot()
        if m["name"] == "photon_device_fetch_bytes_total"
    }
    assert sites == UNTRACED_FETCH_SITES
    # the one always-on addition: the seconds of those same fetches
    assert sites == {
        m["labels"]["site"] for m in quiet.registry.snapshot()
        if m["name"] == "photon_device_fetch_seconds_total"
    }
    assert not [m for m in quiet.registry.snapshot() if m["name"].startswith("photon_re_lane_")]
    # with a listener the spans do fence, and the sink-only sites are exactly
    # what the ledger gains
    run = obs.RunTelemetry()
    run.register_listener(_Spans())
    _fit(data, run)
    assert fences
    traced_sites = {
        m["labels"]["site"] for m in run.registry.snapshot()
        if m["name"] == "photon_device_fetch_bytes_total"
    }
    assert traced_sites - sites == SINK_ONLY_FETCH_SITES
    # tracing changes no arithmetic: bit-identical models
    for a, b in zip(_coefficients(untraced[0].model), _coefficients(traced[0][0][0].model)):
        np.testing.assert_array_equal(a, b)


def _coefficients(game_model):
    fixed, per_user = game_model.models["global"], game_model.models["per-user"]
    return [
        np.asarray(jax.device_get(x))
        for x in (fixed.model.coefficients.means, per_user.coef_values, per_user.coef_indices)
    ]


def test_phase_attribution_reads_what_it_read(traced):
    """The timeline counts outermost phase spans only: cd.train / cd.score /
    cd.eval carry the phases the ``timed`` sections carried, and the new
    spans below them carry none."""
    _, _, _, timeline = traced
    report = timeline.phase_attribution()
    assert report["n_sweeps"] == 2 * N_SWEEPS
    for sweep in report["sweeps"]:
        assert set(sweep["phases"]) == {"solve", "score", "eval"}
        assert sweep["nested_phases"] == {}
        assert {c: set(p) for c, p in sweep["coordinates"].items()} == {
            "global": {"solve", "score"}, "per-user": {"solve", "score", "eval"},
        }
        assert sweep["critical_path_seconds"] + sweep["other_seconds"] == pytest.approx(
            sweep["wall_seconds"]
        )
        assert sweep["overlap_factor"] == pytest.approx(0.0, abs=1e-9)
