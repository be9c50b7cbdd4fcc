"""Observability layer tests: metrics registry semantics, span nesting,
sink output schemas, EventEmitter error isolation, the jax compile hook,
the CD hot-loop zero-fetch invariant, and the cli.train --metrics-out
integration surface (metrics.jsonl / metrics.prom / run_summary.json)."""

import json
import logging
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.estimators import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.problem import GLMOptimizationConfig
from photon_ml_tpu.obs.metrics import MetricsRegistry, render_prometheus
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.optimize.trackers import StatCounter
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.events import Event, EventListener


# ---------------------------------------------------------------- registry


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests").labels(path="/train")
    c.inc()
    c.inc(4)
    g = reg.gauge("queue_depth", "depth")
    g.set(7.5)
    g.inc(-2.5)
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m for m in reg.snapshot()}
    assert snap[("requests_total", (("path", "/train"),))]["value"] == 5
    assert snap[("queue_depth", ())]["value"] == 5.0


def test_counter_same_labels_same_child():
    reg = MetricsRegistry()
    a = reg.counter("c", "").labels(x="1", y="2")
    b = reg.counter("c", "").labels(y="2", x="1")
    assert a is b


def test_histogram_bucket_cumulation():
    reg = MetricsRegistry()
    h = reg.histogram("latency", "l", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    (m,) = reg.snapshot()
    assert m["count"] == 5
    assert m["sum"] == pytest.approx(56.05)
    # buckets are cumulative: le=0.1 -> 1, le=1.0 -> 3, le=10.0 -> 4
    assert m["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4]]


def test_summary_merge_stat_matches_statcounter_of_concat():
    a = np.array([3.0, 7.0, 7.0, 11.0])
    b = np.array([1.0, 2.0, 20.0])
    reg = MetricsRegistry()
    s = reg.summary("iters", "")
    for st in (StatCounter.of(a), StatCounter.of(b)):
        s.merge_stat(st.count, st.mean, st.stdev, st.max, st.min)
    got = s.stat()
    want = StatCounter.of(np.concatenate([a, b]))
    assert got["count"] == want.count
    assert got["mean"] == pytest.approx(want.mean)
    assert got["stdev"] == pytest.approx(want.stdev)
    assert got["max"] == want.max and got["min"] == want.min


def test_summary_observe_many():
    reg = MetricsRegistry()
    s = reg.summary("s", "")
    s.observe_many([1.0, 2.0, 3.0])
    s.observe(10.0)
    assert s.stat()["count"] == 4
    assert s.stat()["max"] == 10.0


def test_reregister_different_kind_raises():
    reg = MetricsRegistry()
    reg.counter("x", "")
    with pytest.raises(TypeError):
        reg.gauge("x", "")


def test_render_prometheus_escaping_and_shapes():
    reg = MetricsRegistry()
    reg.counter("hits_total", "all the hits").labels(path='a"b\\c\nd').inc(3)
    reg.histogram("lat", "lat", buckets=(1.0,)).observe(0.5)
    reg.summary("iters", "it").observe_many([2.0, 4.0])
    text = render_prometheus(reg.snapshot())
    assert '# TYPE hits_total counter' in text
    assert 'path="a\\"b\\\\c\\nd"' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 0.5" in text and "lat_count 1" in text
    assert "iters_sum 6" in text and "iters_count 2" in text
    assert "iters_mean 3" in text


def test_render_prometheus_help_lines_and_hostile_labels():
    """Exposition grammar: # HELP precedes # TYPE per family, help text
    escapes backslash-then-newline, and hostile label values (quotes,
    backslashes, newlines, unicode) survive the escaping round trip."""
    reg = MetricsRegistry()
    reg.histogram(
        "train_lat", "help with\nnewline and \\ backslash", buckets=(0.1, 1.0)
    ).observe(0.5)
    hostile = 'per"user\\x\ny\tzé'
    reg.counter("c_total", "counts").labels(coordinate=hostile).inc()
    text = render_prometheus(reg.snapshot())
    lines = text.splitlines()
    assert "# HELP c_total counts" in lines
    assert lines.index("# HELP c_total counts") + 1 == lines.index(
        "# TYPE c_total counter"
    )
    # help escaping: \ -> \\ first, then newline -> \n (no raw newlines)
    assert "# HELP train_lat help with\\nnewline and \\\\ backslash" in lines
    assert 'c_total{coordinate="per\\"user\\\\x\\ny\tzé"} 1' in lines
    # exposition grammar: every non-comment line is `name{labels} value`
    # with no unescaped newline inside a label value
    for line in lines:
        if not line or line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert name and name[0].isalpha()
        assert line.count(" ") >= 1


def test_quantile_gauges_for_all_histograms():
    """p50/p95/p99 gauges render for every histogram family, not just
    photon_serving_*."""
    reg = MetricsRegistry()
    h = reg.histogram(
        "photon_stream_slice_stage_seconds", "stage wall", buckets=(0.1, 1.0, 10.0)
    )
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE photon_stream_slice_stage_seconds_p50 gauge" in text
    assert "photon_stream_slice_stage_seconds_p95" in text
    assert "photon_stream_slice_stage_seconds_p99" in text
    # p50 falls in the (0.1, 1.0] bucket under linear interpolation
    p50 = [
        float(l.split()[-1])
        for l in text.splitlines()
        if l.startswith("photon_stream_slice_stage_seconds_p50 ")
    ][0]
    assert 0.1 < p50 <= 1.0


# ------------------------------------------------------------ spans/tracing


class _Collector(EventListener):
    def __init__(self):
        self.events = []

    def handle(self, event: Event) -> None:
        self.events.append(event)


def test_span_nesting_parent_ids():
    run = obs.RunTelemetry()
    col = _Collector()
    run.register_listener(col)
    with obs.use_run(run):
        with obs.span("outer", k=1):
            with obs.span("inner"):
                pass
            with obs.span("inner2"):
                pass
    spans = {e.span.name: e.span for e in col.events if isinstance(e, obs.SpanEvent)}
    assert set(spans) == {"outer", "inner", "inner2"}
    assert spans["outer"].parent_id is None
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner2"].parent_id == spans["outer"].span_id
    assert spans["inner"].span_id != spans["inner2"].span_id
    assert spans["outer"].attrs["k"] == 1
    assert spans["outer"].duration_s >= spans["inner"].duration_s >= 0


def test_span_without_listeners_emits_nothing_and_is_cheap():
    # passive default run: span() must not emit or fail
    with obs.span("quiet"):
        assert obs.current_span().name == "quiet"
    assert obs.current_span() is None


def test_prepare_dataset_site_produces_span_and_log(caplog):
    """The sites that used ``utils.timed`` are plain ``obs.span``s under
    stable names now, and keep their "took %.3fs" log line."""
    run = obs.RunTelemetry()
    col = _Collector()
    run.register_listener(col)
    data = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(n=120, d_fixed=3, re_specs={"userId": (4, 2)}, seed=3)
    )
    with obs.use_run(run), caplog.at_level(logging.DEBUG, logger="photon_ml_tpu"):
        datasets = _small_estimator().prepare_datasets(data)
    assert set(datasets) == {"global", "per-user"}
    spans = [
        e.span for e in col.events
        if isinstance(e, obs.SpanEvent) and e.span.name == "fit.prepare_dataset"
    ]
    assert [s.attrs["coordinate"] for s in spans] == ["global", "per-user"]
    assert any(
        "prepare dataset per-user took" in r.getMessage() for r in caplog.records
    )


def test_device_transfer_counters_tagged_on_span():
    run = obs.RunTelemetry()
    col = _Collector()
    run.register_listener(col)
    with obs.use_run(run):
        with obs.span("xfer"):
            obs.add_device_fetch_bytes("test_site", 128)
            obs.add_device_fetch_bytes("test_site", 64)
            obs.add_device_put_bytes("test_site", 256)
        snap = {
            (m["name"], m["labels"].get("site")): m for m in run.registry.snapshot()
        }
    (ev,) = [e for e in col.events if isinstance(e, obs.SpanEvent)]
    assert ev.span.attrs["fetch_bytes"] == 192
    assert ev.span.attrs["put_bytes"] == 256
    assert snap[("photon_device_fetch_bytes_total", "test_site")]["value"] == 192
    assert snap[("photon_device_put_bytes_total", "test_site")]["value"] == 256


def test_use_run_restores_previous():
    before = obs.current_run()
    with obs.use_run(obs.RunTelemetry()) as run:
        assert obs.current_run() is run
    assert obs.current_run() is before


# ------------------------------------------------------------------- sinks


def test_jsonl_sink_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    run = obs.RunTelemetry()
    run.register_listener(obs.JsonlSink(path))
    with obs.use_run(run):
        with obs.span("a", coordinate="g"):
            with obs.span("b"):
                pass
        run.registry.counter("c_total", "").inc()
        run.flush_metrics()
    run.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) >= 3  # two spans + one metrics flush
    spans = {l["name"]: l for l in lines if l["type"] == "span"}
    assert spans["b"]["parent_id"] == spans["a"]["span_id"]
    assert spans["a"]["attrs"]["coordinate"] == "g"
    assert all("duration_s" in s and "start_unix" in s for s in spans.values())
    # one explicit flush plus the final flush from close()
    mlines = [l for l in lines if l["type"] == "metrics"]
    assert mlines
    assert {
        "name": "c_total", "kind": "counter", "help": "", "labels": {}, "value": 1
    } in mlines[0]["metrics"]


def test_jsonl_sink_stamps_host_identity(tmp_path):
    """Every JSONL event carries process_index + host so multi-host streams
    can be merged without guessing which process wrote which line."""
    import socket

    path = str(tmp_path / "m.jsonl")
    run = obs.RunTelemetry()
    run.register_listener(obs.JsonlSink(path))
    obs.set_process_index(3)
    try:
        with obs.use_run(run):
            with obs.span("a"):
                pass
            run.registry.counter("c_total", "").inc()
            run.flush_metrics()
        run.close()  # final flush happens while the index is still set
    finally:
        obs.set_process_index(0)
    lines = [json.loads(l) for l in open(path)]
    assert lines
    for line in lines:
        assert line["process_index"] == 3
        assert line["host"] == socket.gethostname()
    (span_line,) = [l for l in lines if l["type"] == "span"]
    assert isinstance(span_line["thread_id"], int)


def test_jsonl_sink_serializes_device_arrays_as_placeholders(tmp_path):
    # events carrying device arrays must neither crash nor force a fetch of
    # array *contents* into giant JSON blobs
    path = str(tmp_path / "m.jsonl")
    run = obs.RunTelemetry()
    run.register_listener(obs.JsonlSink(path))
    with obs.use_run(run):
        with obs.span("s", arr=jnp.zeros((4,))):
            pass
    run.close()
    (line,) = [
        l for l in (json.loads(x) for x in open(path)) if l["type"] == "span"
    ]
    assert line["attrs"]["arr"].startswith("<")  # placeholder, not the data


def test_prometheus_sink_writes_exposition(tmp_path):
    path = str(tmp_path / "m.prom")
    run = obs.RunTelemetry()
    run.register_listener(obs.PrometheusSink(path))
    run.registry.counter("photon_test_total", "t").inc(2)
    run.flush_metrics()
    run.close()
    text = open(path).read()
    assert "# TYPE photon_test_total counter" in text
    assert "photon_test_total 2" in text


class _RaisingSink(EventListener):
    def __init__(self):
        self.calls = 0

    def handle(self, event: Event) -> None:
        self.calls += 1
        raise RuntimeError("sink exploded")


def test_raising_sink_never_fails_training(game_fit_data, caplog):
    train, val = game_fit_data
    sink = _RaisingSink()
    run = obs.RunTelemetry()
    run.register_listener(sink)
    est = _small_estimator()
    est.register_listener(sink)
    with obs.use_run(run), caplog.at_level(logging.ERROR, logger="photon_ml_tpu"):
        results = est.fit(train, validation=val)
    assert results[0].evaluation.metrics["AUC"] > 0.6
    assert sink.calls > 0  # it was invoked and raised, yet training finished
    assert any("sink exploded" in str(r.exc_info) for r in caplog.records)


# ------------------------------------------------------------- compile hook


def test_compile_hook_feeds_spans_by_exact_event_name():
    """The hook goes in with a run's first listener. A span's ``retraces`` /
    ``retrace_s`` count jaxpr traces, ``compile_s`` is backend compile
    alone; time SAVED by the cache, trace and lowering detail never reach a
    span's time. The per-event registry series keep every "compile" event
    under its own name."""
    try:
        from jax._src import monitoring
    except ImportError:
        pytest.skip("jax._src.monitoring unavailable")
    run = obs.RunTelemetry()
    col = _Collector()
    run.register_listener(col)  # installs the hook
    before = obs.compile_seconds_total()
    with obs.use_run(run):
        with obs.span("traced"):
            monitoring.record_event_duration_secs(obs.tracing.JAXPR_TRACE_EVENT, 0.5)
            monitoring.record_event_duration_secs(obs.tracing.JAXPR_TRACE_EVENT, 0.25)
            monitoring.record_event_duration_secs(obs.tracing.BACKEND_COMPILE_EVENT, 0.125)
            monitoring.record_event_duration_secs(
                "/jax/compilation_cache/compile_time_saved_sec", 64.0
            )
            monitoring.record_event_duration_secs(
                "/jax/core/compile/jaxpr_to_mlir_module_duration", 32.0
            )
        with obs.span("quiet"):
            pass
    if obs.compile_seconds_total() == before:
        pytest.skip("jax monitoring hook unavailable in this jax build")
    assert obs.compile_seconds_total() == pytest.approx(before + 0.125)
    spans = {e.span.name: e.span for e in col.events if isinstance(e, obs.SpanEvent)}
    assert spans["traced"].attrs["retraces"] == 2
    assert spans["traced"].attrs["retrace_s"] == pytest.approx(0.75)
    assert spans["traced"].attrs["compile_s"] == pytest.approx(0.125)
    assert not {"retraces", "retrace_s", "compile_s"} & set(spans["quiet"].attrs)
    series = {
        (m["name"], m["labels"]["event"]): m
        for m in run.registry.snapshot()
        if m["name"].startswith("photon_jax_compile_")
    }
    assert series[("photon_jax_compile_total", obs.tracing.JAXPR_TRACE_EVENT)]["value"] == 2
    saved = series[
        ("photon_jax_compile_seconds", "/jax/compilation_cache/compile_time_saved_sec")
    ]
    assert saved["sum"] == pytest.approx(64.0)


# --------------------------------------------------- zero-fetch invariant


def _small_estimator(n_cd_iterations=1):
    opt = OptimizerConfig(tolerance=1e-8, max_iterations=30)
    return GameEstimator(
        task="logistic_regression",
        coordinate_configs=[
            CoordinateConfig(
                name="global",
                feature_shard="global",
                config=GLMOptimizationConfig(
                    optimizer=opt, regularization=RegularizationContext("L2")
                ),
                reg_weights=(1.0,),
            ),
            CoordinateConfig(
                name="per-user",
                feature_shard="userShard",
                random_effect_type="userId",
                config=GLMOptimizationConfig(
                    optimizer=opt, regularization=RegularizationContext("L2")
                ),
                reg_weights=(1.0,),
            ),
        ],
        n_cd_iterations=n_cd_iterations,
        evaluator_specs=["AUC"],
        dtype=jnp.float64,
    )


@pytest.fixture(scope="module")
def game_fit_data():
    full = mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=800, d_fixed=5, re_specs={"userId": (16, 4)}, seed=11
        )
    )
    return full.subset(np.arange(600)), full.subset(np.arange(600, 800))


def test_no_sink_means_no_tracker_fetch(game_fit_data, monkeypatch):
    """Lazy-aggregate invariant: with no telemetry sink registered and INFO
    logging off, the CD loop must never force the RE tracker's device
    fetch. A booby-trapped ``_aggregates`` proves it is not called."""
    from photon_ml_tpu.optimize.trackers import RandomEffectOptimizationTracker

    def _boom(self):
        raise AssertionError("device fetch inside CD hot loop with no sink")

    monkeypatch.setattr(RandomEffectOptimizationTracker, "_aggregates", _boom)
    train, val = game_fit_data
    logger = logging.getLogger("photon_ml_tpu")
    old = logger.level
    logger.setLevel(logging.WARNING)
    try:
        assert not obs.active()
        results = _small_estimator().fit(train, validation=val)
    finally:
        logger.setLevel(old)
    assert results[0].evaluation.metrics["AUC"] > 0.6


def test_active_sink_records_cd_metrics(game_fit_data):
    train, val = game_fit_data
    run = obs.RunTelemetry()
    run.register_listener(_Collector())
    with obs.use_run(run):
        _small_estimator().fit(train, validation=val)
        snap = {
            (m["name"], m["labels"].get("coordinate")): m
            for m in run.registry.snapshot()
        }
    per_user = snap[("photon_cd_iterations", "per-user")]
    assert per_user["stat"]["count"] >= 1
    assert ("photon_cd_iterations", "global") in snap
    reasons = [
        m
        for (name, _), m in snap.items()
        if name == "photon_cd_convergence_reason_total"
    ]
    assert reasons and all(m["value"] >= 1 for m in reasons)


# ------------------------------------------------------------ _DaemonFuture


def test_daemon_future_result_and_error():
    from photon_ml_tpu.cli.train import _DaemonFuture

    f = _DaemonFuture(lambda: 42)
    assert f.result(timeout=30) == 42
    assert f.done()

    def _bad():
        raise ValueError("decode failed")

    g = _DaemonFuture(_bad)
    with pytest.raises(ValueError, match="decode failed"):
        g.result(timeout=30)


def test_daemon_future_thread_is_daemon():
    from photon_ml_tpu.cli.train import _DaemonFuture

    gate = threading.Event()
    f = _DaemonFuture(gate.wait)  # blocks until released
    assert f._thread.daemon  # must not pin interpreter exit
    assert not f.done()
    gate.set()
    f.result(timeout=30)


# ------------------------------------------------- cli.train --metrics-out


@pytest.mark.slow
def test_cli_metrics_out_integration(tmp_path):
    from photon_ml_tpu.cli.train import run as train_run
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(
        n=400, d_fixed=5, re_specs={"userId": (16, 4)}, seed=4
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
    mdir = str(tmp_path / "metrics")
    n_sweeps = 2
    summary = train_run(
        [
            "--input-data", train_path,
            "--validation-data", train_path,
            "--task", "logistic_regression",
            "--feature-shard", "name=global,bags=features",
            "--feature-shard", "name=userShard,bags=userFeatures",
            "--coordinate",
            "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1",
            "--coordinate",
            "name=per-user,shard=userShard,re.type=userId,reg.type=L2,reg.weights=1",
            "--evaluators", "AUC",
            "--coordinate-descent-iterations", str(n_sweeps),
            "--output-dir", str(tmp_path / "out"),
            "--metrics-out", mdir,
        ]
    )
    assert summary["best"]["metrics"]["AUC"] > 0.6

    # metrics.jsonl: every line parses; >=1 span per coordinate per sweep
    # whose parent is a cd.sweep span
    lines = [json.loads(l) for l in open(os.path.join(mdir, "metrics.jsonl"))]
    spans = [l for l in lines if l["type"] == "span"]
    sweep_ids = {s["span_id"] for s in spans if s["name"] == "cd.sweep"}
    assert len(sweep_ids) == n_sweeps
    coord_spans = [s for s in spans if s["name"] == "cd.coordinate"]
    seen = {(s["attrs"]["iteration"], s["attrs"]["coordinate"]) for s in coord_spans}
    assert seen == {
        (it, c) for it in range(n_sweeps) for c in ("global", "per-user")
    }
    assert all(s["parent_id"] in sweep_ids for s in coord_spans)
    assert sum(1 for l in lines if l["type"] == "metrics") >= n_sweeps

    # metrics.prom: prometheus exposition present and non-trivial
    prom = open(os.path.join(mdir, "metrics.prom")).read()
    assert "photon_cd_iterations" in prom
    assert "photon_solver_iterations" in prom

    # run_summary.json: wall clock, per-coordinate iteration StatCounters,
    # convergence-reason histogram
    rs = json.load(open(os.path.join(mdir, "run_summary.json")))
    assert rs["total_wall_seconds"] > 0
    assert set(rs["coordinates"]) == {"global", "per-user"}
    for coord in rs["coordinates"].values():
        assert coord["iterations"]["count"] >= 1
        assert coord["convergence_reasons"]
        assert sum(coord["convergence_reasons"].values()) >= n_sweeps
    assert rs["best"]["metrics"]["AUC"] == summary["best"]["metrics"]["AUC"]
