"""Live introspection plane tests: Chrome-trace export schema, per-sweep
phase attribution, and the /metrics + /healthz + /statusz endpoints (including
a concurrent scrape while spans are being emitted)."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs.timeline import SWEEP_SPAN_NAME, TimelineRecorder
from photon_ml_tpu.obs.tracing import Span, SpanEvent

# ---------------------------------------------------------------- helpers


def _mk_span(name, span_id, parent_id, start, dur, **attrs):
    """Hand-built span on a synthetic monotonic clock (start_perf). The
    clock is offset from zero: start_perf == 0.0 means "not stamped" and
    would fall back to start_unix."""
    return Span(
        name=name,
        span_id=span_id,
        parent_id=parent_id,
        start_unix=1_700_000_000.0 + start,
        attrs=dict(attrs),
        duration_s=dur,
        start_perf=100.0 + start,
    )


def _feed(recorder, spans):
    # children close before parents in real runs; feed in that order too
    for s in spans:
        recorder.handle(SpanEvent(span=s))


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


# ------------------------------------------------------------ chrome trace


def test_chrome_trace_schema_from_real_spans(tmp_path):
    """Golden schema: the export is valid Chrome-trace JSON — "X" complete
    events with microsecond ts/dur, pid/tid lane ids, span identity under
    args, "M" lane-name metadata, ts-sorted, displayTimeUnit set."""
    run = obs.RunTelemetry()
    rec = TimelineRecorder()
    run.register_listener(rec)
    with obs.use_run(run):
        with obs.span(SWEEP_SPAN_NAME, iteration=0):
            with obs.span("cd.coordinate", iteration=0, coordinate="global"):
                with obs.span("solve", phase="solve", coordinate="global"):
                    time.sleep(0.002)
            with obs.span("cd.eval", phase="eval"):
                pass

    doc = rec.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    # round-trips through JSON (Perfetto ingests text)
    assert json.loads(json.dumps(doc)) == doc

    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {
        SWEEP_SPAN_NAME, "cd.coordinate", "solve", "cd.eval"
    }
    for e in xs:
        assert e["cat"] == "photon"
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["dur"] >= 0
        assert "span_id" in e["args"] and "parent_id" in e["args"]
    # all spans ran on this thread -> one lane, named by the M events
    assert {e["tid"] for e in xs} == {threading.get_ident()}
    assert {m["name"] for m in ms} == {"process_name", "thread_name"}
    # ts-sorted and nesting is consistent: the sweep starts first
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    by_name = {e["name"]: e for e in xs}
    solve = by_name["solve"]
    assert solve["dur"] >= 2000  # slept 2ms, dur is in microseconds
    assert solve["args"]["phase"] == "solve"

    out = tmp_path / "trace.json"
    rec.write_chrome_trace(str(out))
    ondisk = json.load(open(out))
    assert ondisk["traceEvents"]


def test_chrome_trace_lane_ids_across_threads():
    run = obs.RunTelemetry()
    rec = TimelineRecorder()
    run.register_listener(rec)

    def worker():
        with obs.use_run(run):
            with obs.span("bg-work"):
                pass

    with obs.use_run(run):
        with obs.span("fg-work"):
            t = threading.Thread(target=worker, name="photon-test-worker")
            t.start()
            t.join()
    xs = {e["name"]: e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X"}
    assert xs["fg-work"]["tid"] != xs["bg-work"]["tid"]
    lane_names = {
        m["args"]["name"]
        for m in rec.chrome_trace()["traceEvents"]
        if m["ph"] == "M" and m["name"] == "thread_name"
    }
    assert "photon-test-worker" in lane_names


# -------------------------------------------------------- phase attribution


def test_phase_attribution_serial_sweep_scores_zero_overlap():
    rec = TimelineRecorder()
    sweep = _mk_span(SWEEP_SPAN_NAME, "sw", None, 0.0, 10.0, iteration=0)
    _feed(
        rec,
        [
            _mk_span("solve", "a", "sw", 0.0, 4.0, phase="solve", coordinate="global"),
            _mk_span("score", "b", "sw", 4.0, 2.0, phase="score", coordinate="global"),
            _mk_span("eval", "c", "sw", 6.0, 1.0, phase="eval"),
            _mk_span("ckpt", "d", "sw", 7.0, 1.0, phase="checkpoint"),
            sweep,
        ],
    )
    att = rec.phase_attribution()
    assert att["n_sweeps"] == 1
    (rec0,) = att["sweeps"]
    assert rec0["iteration"] == 0
    assert rec0["wall_seconds"] == pytest.approx(10.0)
    assert rec0["phases"] == pytest.approx(
        {"solve": 4.0, "score": 2.0, "eval": 1.0, "checkpoint": 1.0}
    )
    assert rec0["coordinates"]["global"] == pytest.approx(
        {"solve": 4.0, "score": 2.0}
    )
    # serial: union == sum of phases, so overlap factor is exactly 0
    assert rec0["sum_of_phases_seconds"] == pytest.approx(8.0)
    assert rec0["critical_path_seconds"] == pytest.approx(8.0)
    assert rec0["overlap_factor"] == pytest.approx(0.0)
    # the attribution identity: critical path + unattributed == wall
    assert rec0["critical_path_seconds"] + rec0["other_seconds"] == pytest.approx(
        rec0["wall_seconds"]
    )
    assert att["total"]["overlap_factor"] == pytest.approx(0.0)


def test_phase_attribution_overlap_factor_rises_with_concurrency():
    rec = TimelineRecorder()
    _feed(
        rec,
        [
            _mk_span("solve", "a", "sw", 0.0, 4.0, phase="solve"),
            _mk_span("stage", "b", "sw", 2.0, 4.0, phase="stage"),
            _mk_span(SWEEP_SPAN_NAME, "sw", None, 0.0, 6.0, iteration=0),
        ],
    )
    (rec0,) = rec.phase_attribution()["sweeps"]
    # sum 8, union 6 -> 25% of phase time ran concurrently
    assert rec0["overlap_factor"] == pytest.approx(0.25)
    assert rec0["other_seconds"] == pytest.approx(0.0)


def test_phase_attribution_nested_phase_not_double_counted():
    """A phase span inside another phase span (fe_stream.stage dispatched
    from within the solve) is wall time its ancestor already owns — it must
    land in nested_phases, not inflate the overlap factor."""
    rec = TimelineRecorder()
    _feed(
        rec,
        [
            _mk_span("stage", "st", "so", 1.0, 2.0, phase="stage"),
            _mk_span("solve", "so", "sw", 0.0, 8.0, phase="solve"),
            _mk_span(SWEEP_SPAN_NAME, "sw", None, 0.0, 10.0, iteration=0),
        ],
    )
    (rec0,) = rec.phase_attribution()["sweeps"]
    assert rec0["phases"] == pytest.approx({"solve": 8.0})
    assert rec0["nested_phases"] == pytest.approx({"stage": 2.0})
    assert rec0["overlap_factor"] == pytest.approx(0.0)


def test_phase_attribution_clips_to_sweep_window():
    rec = TimelineRecorder()
    _feed(
        rec,
        [
            # starts before the sweep, ends inside: only [2, 5) attributes
            _mk_span("warm", "w", "sw", 0.0, 5.0, phase="solve"),
            # entirely outside the window: contributes nothing
            _mk_span("late", "l", "sw", 20.0, 1.0, phase="eval"),
            _mk_span(SWEEP_SPAN_NAME, "sw", None, 2.0, 6.0, iteration=0),
        ],
    )
    (rec0,) = rec.phase_attribution()["sweeps"]
    assert rec0["phases"] == pytest.approx({"solve": 3.0})
    assert "eval" not in rec0["phases"]


def test_phase_attribution_ignores_spans_of_other_sweeps():
    rec = TimelineRecorder()
    _feed(
        rec,
        [
            _mk_span("solve", "a0", "sw0", 0.0, 2.0, phase="solve"),
            _mk_span(SWEEP_SPAN_NAME, "sw0", None, 0.0, 3.0, iteration=0),
            _mk_span("solve", "a1", "sw1", 3.0, 4.0, phase="solve"),
            _mk_span(SWEEP_SPAN_NAME, "sw1", None, 3.0, 5.0, iteration=1),
        ],
    )
    att = rec.phase_attribution()
    assert att["n_sweeps"] == 2
    s0, s1 = att["sweeps"]
    assert s0["phases"] == pytest.approx({"solve": 2.0})
    assert s1["phases"] == pytest.approx({"solve": 4.0})
    assert att["total"]["wall_seconds"] == pytest.approx(8.0)
    assert att["total"]["phases"]["solve"] == pytest.approx(6.0)


# ------------------------------------------------------------ http endpoints


def test_endpoints_respond():
    run = obs.RunTelemetry()
    run.registry.counter("photon_test_total", "t").inc(3)
    run.status.update(sweep=1, coordinate="global")
    srv = obs.IntrospectionServer(run, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"

        status, ctype, body = _get(base + "/healthz")
        assert status == 200 and json.loads(body) == {"status": "ok"}

        status, ctype, body = _get(base + "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        text = body.decode("utf-8")
        assert "# TYPE photon_test_total counter" in text
        assert "photon_test_total 3" in text

        status, ctype, body = _get(base + "/statusz")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["status"] == "ok"
        assert doc["sweep"] == 1 and doc["coordinate"] == "global"

        with pytest.raises(urllib.error.HTTPError) as e:
            _get(base + "/nope")
        assert e.value.code == 404
    finally:
        srv.stop()


def test_statusz_serving_section_and_qps():
    from photon_ml_tpu.serving.batcher import SERVING_LATENCY_BUCKETS

    run = obs.RunTelemetry()
    reg = run.registry
    reg.counter("photon_serving_requests_total", "").inc(100)
    lat = reg.histogram(
        "photon_serving_request_latency_seconds", "", buckets=SERVING_LATENCY_BUCKETS
    )
    for _ in range(10):
        lat.observe(0.002)
    srv = obs.IntrospectionServer(run, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        doc = json.loads(_get(base + "/statusz")[2])
        assert doc["serving"]["requests_total"] == 100
        assert 0.001 <= doc["serving"]["latency_p50_seconds"] <= 0.0025
        # first scrape has no previous sample -> no qps yet
        assert "qps" not in doc["serving"]
        reg.counter("photon_serving_requests_total", "").inc(50)
        time.sleep(0.05)
        doc2 = json.loads(_get(base + "/statusz")[2])
        assert doc2["serving"]["requests_total"] == 150
        assert doc2["serving"]["qps"] > 0
    finally:
        srv.stop()


def test_healthz_503_while_refresh_in_progress():
    """The serving snapshot-refresh flip raises refresh_in_progress on the
    StatusBoard; /healthz answers 503 for exactly that window so a load
    balancer drains the replica mid-publish."""
    run = obs.RunTelemetry()
    srv = obs.IntrospectionServer(run, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        run.status.update(refresh_in_progress=True)
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(base + "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read()) == {"status": "refreshing"}
        run.status.update(refresh_in_progress=False)
        status, _, body = _get(base + "/healthz")
        assert status == 200 and json.loads(body) == {"status": "ok"}
    finally:
        srv.stop()


def test_statusz_memory_section():
    """/statusz carries live host RSS plus recorded device watermarks and
    hbm.budget headroom when the run sampled/streamed any."""
    run = obs.RunTelemetry()
    reg = run.registry
    obs.sample_memory(reg)
    reg.gauge("photon_mem_device_peak_bytes_in_use", "").labels(
        device="0"
    ).set(4096)
    reg.gauge("photon_stream_budget_bytes", "").labels(site="fe.train").set(
        2048
    )
    reg.gauge("photon_stream_budget_headroom_bytes", "").labels(
        site="fe.train"
    ).set(1024)
    srv = obs.IntrospectionServer(run, port=0)
    try:
        doc = json.loads(_get(f"http://127.0.0.1:{srv.port}/statusz")[2])
        mem = doc["memory"]
        assert mem["host"]["rss_bytes"] > 0  # live reading, not the sample
        assert mem["devices"]["0"]["peak_bytes_in_use"] == 4096
        assert mem["streaming"]["fe.train"]["hbm_budget_bytes"] == 2048
        assert mem["streaming"]["fe.train"]["hbm_budget_headroom_bytes"] == 1024
    finally:
        srv.stop()


def test_concurrent_scrape_during_span_storm():
    """Scrapes while another thread hammers spans + status updates: every
    response is complete, parseable, and never deadlocks the emitting
    thread."""
    run = obs.RunTelemetry()
    rec = TimelineRecorder()
    run.register_listener(rec)
    stop = threading.Event()

    def storm():
        with obs.use_run(run):
            i = 0
            while not stop.is_set():
                run.status.update(sweep=i, coordinate=f"c{i % 3}")
                with obs.span(SWEEP_SPAN_NAME, iteration=i):
                    with obs.span("solve", phase="solve", coordinate=f"c{i % 3}"):
                        run.registry.counter("photon_storm_total", "").inc()
                i += 1

    t = threading.Thread(target=storm, name="span-storm")
    t.start()
    srv = obs.IntrospectionServer(run, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        deadline = time.monotonic() + 10
        seen_sweeps = set()
        while time.monotonic() < deadline and len(seen_sweeps) < 3:
            doc = json.loads(_get(base + "/statusz")[2])
            assert doc["status"] == "ok"
            if "sweep" in doc:
                seen_sweeps.add(doc["sweep"])
            text = _get(base + "/metrics")[2].decode("utf-8")
            # exposition is complete: TYPE line present for emitted counters
            if "photon_storm_total" in text:
                assert "# TYPE photon_storm_total counter" in text
        assert len(seen_sweeps) >= 3  # observed live progress, not one frozen state
    finally:
        stop.set()
        t.join(timeout=10)
        srv.stop()
    assert not t.is_alive()
    assert rec.phase_attribution()["n_sweeps"] >= 3


def test_server_stop_releases_port():
    run = obs.RunTelemetry()
    srv = obs.IntrospectionServer(run, port=0)
    port = srv.port
    srv.stop()
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", port))  # must be rebindable after stop()
    finally:
        s.close()


# ------------------------------------------------- cli train live endpoints


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_cli_train_trace_out_and_live_status(tmp_path):
    """End-to-end acceptance: cli train --trace-out --status-port produces a
    Perfetto-loadable trace + phase attribution whose per-sweep identity
    critical_path + other == wall holds, while /statusz and /metrics answer
    live mid-training."""
    from photon_ml_tpu.cli.train import run as train_run
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing import generate_mixed_effect_data
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
    trace_path = str(tmp_path / "trace.json")
    port = _free_port()
    n_sweeps = 2

    result = {}

    def _train():
        result["summary"] = train_run(
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
                "--trace-out", trace_path,
                "--status-port", str(port),
            ]
        )

    t = threading.Thread(target=_train, name="cli-train")
    t.start()
    base = f"http://127.0.0.1:{port}"
    live_statusz = []
    live_metrics = False
    deadline = time.monotonic() + 300
    while t.is_alive() and time.monotonic() < deadline:
        try:
            doc = json.loads(_get(base + "/statusz", timeout=5)[2])
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.05)
            continue
        assert doc["status"] == "ok"
        if "coordinate" in doc:
            live_statusz.append(doc)
        if not live_metrics:
            text = _get(base + "/metrics", timeout=5)[2].decode("utf-8")
            live_metrics = "photon_" in text
        time.sleep(0.02)
    t.join(timeout=300)
    assert not t.is_alive()
    assert result["summary"]["best"]["metrics"]["AUC"] > 0.6

    # the endpoints answered mid-training with live progress
    assert live_statusz, "statusz never reported a live coordinate"
    assert live_metrics, "metrics exposition never reported photon_* families"
    assert {d["coordinate"] for d in live_statusz} <= {"global", "per-user"}

    # trace file is Perfetto-loadable chrome trace with the sweep spans
    trace = json.load(open(trace_path))
    assert trace["displayTimeUnit"] == "ms"
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert sum(1 for e in xs if e["name"] == "cd.sweep") == n_sweeps
    phases_seen = {e["args"].get("phase") for e in xs} - {None}
    assert "solve" in phases_seen and "score" in phases_seen

    # run_summary.json lands next to the trace when --metrics-out is absent
    rs = json.load(open(tmp_path / "run_summary.json"))
    tl = rs["timeline"]
    assert tl["n_sweeps"] == n_sweeps
    for sweep in tl["sweeps"]:
        assert sweep["critical_path_seconds"] + sweep["other_seconds"] == pytest.approx(
            sweep["wall_seconds"], rel=1e-6
        )
        assert set(sweep["phases"]) >= {"solve", "score"}
        assert 0.0 <= sweep["overlap_factor"] < 1.0
    assert tl["total"]["wall_seconds"] > 0
