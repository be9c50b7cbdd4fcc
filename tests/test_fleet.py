"""Fleet observability plane (photon_ml_tpu/obs/fleet + cli/fleetz):
exposition parse/render round trip, the merge rule-set (counters bit-exact,
histogram quantiles against a hand-merged oracle, gauges relabelled
per-process, summaries recombined through population moments), multi-process
trace stitching, the live aggregator front, the flight recorder's
exactly-one-dump-per-storm latch, and the 2-process --config scale parity
drill (slow)."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import fleet
from photon_ml_tpu.obs.metrics import (
    MetricsRegistry,
    histogram_quantile,
    render_prometheus,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_key(snapshot):
    return {
        (m["name"], tuple(sorted(m["labels"].items()))): m for m in snapshot
    }


# -- parse_prometheus: inverse of render_prometheus ---------------------------


def test_parse_render_roundtrip_exact():
    reg = MetricsRegistry()
    reg.counter("photon_x_total", "a counter").labels(site="a").inc(3)
    reg.counter("photon_x_total", "a counter").labels(site="b").inc(4)
    reg.gauge("photon_depth", "a gauge").set(7.25)
    h = reg.histogram("photon_lat_seconds", "a hist", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5):
        h.observe(v)
    s = reg.summary("photon_iters", "a summary")
    for v in (1.0, 2.0, 3.0, 4.0):
        s.observe(v)
    snap = reg.snapshot()
    parsed = parse_back = fleet.parse_prometheus(render_prometheus(snap))
    a, b = _by_key(snap), _by_key(parsed)
    assert set(a) == set(b)
    for key, m in a.items():
        p = b[key]
        assert p["kind"] == m["kind"]
        if m["kind"] in ("counter", "gauge"):
            assert p["value"] == m["value"]
        elif m["kind"] == "histogram":
            assert p["count"] == m["count"]
            assert p["sum"] == m["sum"]
            assert [list(x) for x in p["buckets"]] == [list(x) for x in m["buckets"]]
        else:
            for field in ("count", "mean", "stdev", "min", "max"):
                assert p["stat"][field] == pytest.approx(m["stat"][field])


def test_parse_prometheus_hostile_label_values():
    reg = MetricsRegistry()
    reg.counter("photon_esc_total", "h").labels(
        path='a"b\\c\nd', plain="ok"
    ).inc(2)
    parsed = fleet.parse_prometheus(render_prometheus(reg.snapshot()))
    (m,) = [e for e in parsed if e["name"] == "photon_esc_total"]
    assert m["labels"] == {"path": 'a"b\\c\nd', "plain": "ok"}
    assert m["value"] == 2.0


def test_parse_drops_derived_hist_gauges_and_folds_summary_moments():
    reg = MetricsRegistry()
    reg.histogram("photon_h_seconds", "h", buckets=(1.0, 5.0)).observe(0.5)
    s = reg.summary("photon_s", "s")
    for v in (1.0, 3.0):
        s.observe(v)
    parsed = fleet.parse_prometheus(render_prometheus(reg.snapshot()))
    names = [m["name"] for m in parsed]
    # the derived families fold back in; they never surface as gauges
    assert "photon_h_seconds_p50" not in names
    assert "photon_s_mean" not in names
    (summ,) = [m for m in parsed if m["name"] == "photon_s"]
    assert summ["stat"]["mean"] == 2.0
    assert summ["stat"]["min"] == 1.0
    assert summ["stat"]["max"] == 3.0


# -- merge rule-set -----------------------------------------------------------


def test_merge_counters_bit_exact():
    regs = [MetricsRegistry() for _ in range(3)]
    rng = np.random.default_rng(0)
    per = [rng.integers(1, 10_000, size=4) for _ in regs]
    for reg, counts in zip(regs, per):
        for j, c in enumerate(counts):
            reg.counter("photon_req_total", "h").labels(site=f"s{j}").inc(int(c))
    merged = fleet.merge_snapshots(
        [({"process": str(i)}, reg.snapshot()) for i, reg in enumerate(regs)]
    )
    got = _by_key(merged)
    for j in range(4):
        key = ("photon_req_total", (("site", f"s{j}"),))
        assert got[key]["value"] == float(sum(int(c[j]) for c in per))


def test_merge_gauges_keep_per_process_identity():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.gauge("photon_queue_depth", "h").set(3)
    b.gauge("photon_queue_depth", "h").set(9)
    merged = fleet.merge_snapshots(
        [({"process": "0"}, a.snapshot()),
         ({"process": "1", "replica": "west"}, b.snapshot())]
    )
    got = _by_key(merged)
    assert got[("photon_queue_depth", (("process", "0"),))]["value"] == 3.0
    key = ("photon_queue_depth", (("process", "1"), ("replica", "west")))
    assert got[key]["value"] == 9.0


def test_merge_histogram_quantiles_match_hand_merged_oracle():
    buckets = (0.001, 0.005, 0.025, 0.1, 0.5)
    rng = np.random.default_rng(7)
    obs_a = rng.exponential(0.01, size=400).tolist()
    obs_b = rng.exponential(0.05, size=300).tolist()
    a, b, oracle = (MetricsRegistry() for _ in range(3))
    for v in obs_a:
        a.histogram("photon_lat_seconds", "h", buckets=buckets).observe(v)
        oracle.histogram("photon_lat_seconds", "h", buckets=buckets).observe(v)
    for v in obs_b:
        b.histogram("photon_lat_seconds", "h", buckets=buckets).observe(v)
        oracle.histogram("photon_lat_seconds", "h", buckets=buckets).observe(v)
    merged = fleet.merge_snapshots(
        [({"process": "0"}, a.snapshot()), ({"process": "1"}, b.snapshot())]
    )
    (m,) = [e for e in merged if e["name"] == "photon_lat_seconds"]
    (o,) = [e for e in oracle.snapshot() if e["name"] == "photon_lat_seconds"]
    assert m["count"] == o["count"] == 700
    assert m["sum"] == pytest.approx(o["sum"])
    assert [list(x) for x in m["buckets"]] == [list(x) for x in o["buckets"]]
    for q in (0.5, 0.95, 0.99):
        assert histogram_quantile(m["buckets"], m["count"], q) == (
            histogram_quantile(o["buckets"], o["count"], q)
        )


def test_merge_summaries_match_concat_oracle():
    rng = np.random.default_rng(3)
    xs_a, xs_b = rng.normal(2.0, 1.0, 50).tolist(), rng.normal(5.0, 3.0, 80).tolist()
    a, b, oracle = (MetricsRegistry() for _ in range(3))
    for v in xs_a:
        a.summary("photon_iters", "h").observe(v)
        oracle.summary("photon_iters", "h").observe(v)
    for v in xs_b:
        b.summary("photon_iters", "h").observe(v)
        oracle.summary("photon_iters", "h").observe(v)
    merged = fleet.merge_snapshots(
        [({"process": "0"}, a.snapshot()), ({"process": "1"}, b.snapshot())]
    )
    (m,) = [e for e in merged if e["name"] == "photon_iters"]
    (o,) = [e for e in oracle.snapshot() if e["name"] == "photon_iters"]
    assert m["stat"]["count"] == o["stat"]["count"]
    for field in ("mean", "stdev", "min", "max"):
        assert m["stat"][field] == pytest.approx(o["stat"][field], rel=1e-12)


def test_identity_labels_read_from_build_info():
    reg = MetricsRegistry()
    reg.gauge("photon_build_info", "h").labels(
        version="0.1.0", jax="x", backend="cpu", process="3", replica="east"
    ).set(1)
    identity = fleet.identity_labels(reg.snapshot(), fallback_process="9")
    assert identity == {"process": "3", "replica": "east"}
    assert fleet.identity_labels([], fallback_process="9") == {"process": "9"}


# -- JSONL stream loading + trace stitching -----------------------------------


def _write_stream(path, process_index, replica=None, n_spans=2, t0=100.0):
    with open(path, "w") as f:
        header = {"process_index": process_index, "host": f"host{process_index}"}
        if replica is not None:
            header["replica"] = replica
        f.write(json.dumps(header) + "\n")
        for k in range(n_spans):
            f.write(json.dumps({
                "type": "span", "name": f"op{k}", "span_id": f"s{process_index}.{k}",
                "parent_id": None, "start_unix": t0 + process_index + 0.1 * k,
                "duration_s": 0.05, "thread_id": 1 + k,
                "process_index": process_index, "attrs": {"k": k},
            }) + "\n")
        f.write(json.dumps({"type": "metrics", "metrics": [
            {"name": "photon_req_total", "kind": "counter", "help": "h",
             "labels": {}, "value": 10.0 * (process_index + 1)},
        ]}) + "\n")
    return path


def test_load_metrics_jsonl_last_snapshot_wins_and_torn_tail(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"process_index": 1, "host": "h"}) + "\n")
        f.write(json.dumps({"type": "metrics", "metrics": [
            {"name": "c_total", "kind": "counter", "help": "", "labels": {},
             "value": 1.0}]}) + "\n")
        f.write(json.dumps({"type": "metrics", "metrics": [
            {"name": "c_total", "kind": "counter", "help": "", "labels": {},
             "value": 5.0}]}) + "\n")
        f.write('{"type": "metrics", "metr')  # torn tail of a crashed writer
    stream = fleet.load_metrics_jsonl(path)
    assert stream.process_index == 1
    assert stream.snapshot[0]["value"] == 5.0  # cumulative: last flush wins


def test_stitch_spans_two_pid_lanes_no_drops(tmp_path):
    s0 = fleet.load_metrics_jsonl(
        _write_stream(str(tmp_path / "metrics.jsonl"), 0, n_spans=3)
    )
    s1 = fleet.load_metrics_jsonl(
        _write_stream(str(tmp_path / "metrics.p1.jsonl"), 1, replica="r1",
                      n_spans=2)
    )
    trace = fleet.stitch_spans([s0, s1])
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    # no dropped spans: every span line of every stream is an X event
    assert len(events) == 5
    assert {e["pid"] for e in events} == {0, 1}
    # rebased onto the shared wall clock: earliest event at ts=0, and
    # cross-process ordering follows start_unix
    assert min(e["ts"] for e in events) == 0.0
    ordered = sorted(events, key=lambda e: e["ts"])
    assert [e["pid"] for e in ordered] == [0, 0, 0, 1, 1]
    names = {
        m["args"]["name"]
        for m in trace["traceEvents"]
        if m["ph"] == "M" and m["name"] == "process_name"
    }
    assert any("replica=r1" in n for n in names)
    assert trace["otherData"]["processes"] == [0, 1]


def test_discover_streams_globs_directories(tmp_path):
    _write_stream(str(tmp_path / "metrics.jsonl"), 0)
    _write_stream(str(tmp_path / "metrics.p1.jsonl"), 1)
    streams = fleet.discover_streams([str(tmp_path)])
    assert sorted(s.process_index for s in streams) == [0, 1]
    merged = fleet.merge_snapshots([(s.identity, s.snapshot) for s in streams])
    (c,) = [m for m in merged if m["name"] == "photon_req_total"]
    assert c["value"] == 30.0  # 10 + 20, bit-exact


# -- live aggregation front ---------------------------------------------------


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode("utf-8")


def test_fleet_aggregator_scrapes_introspection_server():
    run = obs.RunTelemetry()
    obs.record_build_info(run.registry)
    run.registry.counter("photon_serving_requests_total", "h").inc(42)
    srv = obs.IntrospectionServer(run, port=0)
    try:
        agg = fleet.FleetAggregator(targets=[f"http://127.0.0.1:{srv.port}"])
        assert agg.scrape_once() == 1
        merged = agg.merged_snapshot()
        got = _by_key(merged)
        assert got[("photon_serving_requests_total", ())]["value"] == 42.0
        # the aggregator's own meta-metrics ride along
        names = {m["name"] for m in merged}
        assert "photon_fleet_scrapes_total" in names
        assert "photon_fleet_processes_up" in names
    finally:
        srv.stop()


def test_fleet_aggregator_counts_down_replica_and_degrades():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    agg = fleet.FleetAggregator(
        targets=[f"http://127.0.0.1:{dead_port}"], timeout_s=0.2
    )
    assert agg.scrape_once() == 0
    snap = agg.registry.snapshot()
    errs = [m for m in snap if m["name"] == "photon_fleet_scrape_errors_total"]
    assert errs and errs[0]["value"] == 1.0


def test_fleet_server_endpoints(tmp_path):
    _write_stream(str(tmp_path / "metrics.jsonl"), 0)
    _write_stream(str(tmp_path / "metrics.p1.jsonl"), 1)
    agg = fleet.FleetAggregator()
    agg.add_streams(fleet.discover_streams([str(tmp_path)]))
    front = fleet.FleetServer(agg, port=0)
    try:
        text = _get(f"http://127.0.0.1:{front.port}/metrics")
        assert "photon_req_total 30" in text
        statusz = json.loads(_get(f"http://127.0.0.1:{front.port}/statusz"))
        assert statusz["fleet"]["processes_up"] == 2
        healthz = json.loads(_get(f"http://127.0.0.1:{front.port}/healthz"))
        assert healthz == {"status": "ok", "processes_up": 2}
    finally:
        front.stop()


# -- build info ---------------------------------------------------------------


def test_build_info_in_exposition_and_run_summary():
    run = obs.RunTelemetry()
    obs.set_replica_id("r7")
    try:
        info = obs.record_build_info(run.registry)
    finally:
        obs.set_replica_id(None)
    assert info["version"] == "0.1.0"
    assert info["replica"] == "r7"
    text = render_prometheus(run.registry.snapshot())
    assert 'photon_build_info{' in text
    assert 'version="0.1.0"' in text
    assert 'replica="r7"' in text
    doc = obs.build_run_summary(run.registry, total_wall_seconds=1.0)
    assert doc["build"]["version"] == "0.1.0"


# -- flight recorder ----------------------------------------------------------


def test_flight_recorder_shed_storm_exactly_one_dump(tmp_path):
    run = obs.RunTelemetry()
    rec = obs.FlightRecorder(
        str(tmp_path / "flight"), run=run,
        shed_rate_threshold=5.0, poll_interval_s=0.0, cooldown_s=60.0,
    )
    shed = run.registry.counter("photon_serving_shed_total", "h").labels(
        reason="deadline"
    )
    assert rec.poll(force=True) is None  # baseline sample, no rate yet
    time.sleep(0.05)
    shed.inc(500)  # storm: far above 5 sheds/second
    path = rec.poll(force=True)
    assert path is not None and os.path.exists(path)
    # the storm continues — the latch holds: still exactly one dump
    time.sleep(0.05)
    shed.inc(500)
    assert rec.poll(force=True) is None
    assert len(rec.dump_paths) == 1
    doc = json.load(open(path))
    assert doc["trigger"]["kind"] == "shed_spike"
    assert "identity" in doc and "metrics" in doc
    dumps = [
        m for m in run.registry.snapshot()
        if m["name"] == "photon_flightrec_dumps_total"
    ]
    assert dumps and dumps[0]["labels"]["trigger"] == "shed_spike"
    assert dumps[0]["value"] == 1.0


def test_flight_recorder_solver_divergence_and_rejection_triggers(tmp_path):
    run = obs.RunTelemetry()
    rec = obs.FlightRecorder(
        str(tmp_path / "flight"), run=run, poll_interval_s=0.0
    )
    rec.poll(force=True)  # baseline
    run.registry.counter(
        "photon_solver_diverged_lanes_total", "h"
    ).labels(solver="LBFGS").inc()
    assert rec.poll(force=True) is not None
    run.registry.counter(
        "photon_coordinate_rejections_total", "h"
    ).labels(coordinate="global").inc()
    assert rec.poll(force=True) is not None
    kinds = sorted(
        json.load(open(p))["trigger"]["kind"] for p in rec.dump_paths
    )
    assert kinds == ["coordinate_rejection", "solver_divergence"]


def test_flight_recorder_ring_rides_event_stream_and_windows(tmp_path):
    run = obs.RunTelemetry()
    rec = obs.FlightRecorder(
        str(tmp_path / "flight"), run=run, window_s=30.0, poll_interval_s=10.0
    )
    run.register_listener(rec)
    with obs.use_run(run):
        with obs.span("outer"):
            with obs.span("inner", coordinate="global"):
                pass
    path = rec.trigger("crash", detail="SimulatedKill: drill")
    doc = json.load(open(path))
    span_names = [e["name"] for e in doc["events"] if e["type"] == "span"]
    assert "inner" in span_names and "outer" in span_names
    assert doc["trigger"]["detail"] == "SimulatedKill: drill"
    # cooldown latches repeated crash triggers too
    assert rec.trigger("crash", detail="again") is None


# -- cli fleetz ---------------------------------------------------------------


def test_cli_fleetz_one_shot_stdout(tmp_path, capsys):
    from photon_ml_tpu.cli import fleetz

    _write_stream(str(tmp_path / "metrics.jsonl"), 0)
    _write_stream(str(tmp_path / "metrics.p1.jsonl"), 1)
    fleetz.run([str(tmp_path)])
    out = capsys.readouterr().out
    assert "photon_req_total 30" in out
    assert "photon_fleet_processes 2" in out


def test_cli_fleetz_artifacts_mode(tmp_path):
    from photon_ml_tpu.cli import fleetz

    _write_stream(str(tmp_path / "metrics.jsonl"), 0)
    _write_stream(str(tmp_path / "metrics.p1.jsonl"), 1, replica="r1")
    out_dir = str(tmp_path / "fleet")
    fleetz.run([str(tmp_path), "--out", out_dir])
    assert "photon_req_total 30" in open(os.path.join(out_dir, "fleet.prom")).read()
    trace = json.load(open(os.path.join(out_dir, "fleet_trace.json")))
    assert {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"} == {0, 1}
    summary = json.load(open(os.path.join(out_dir, "fleet_summary.json")))
    assert summary["fleet"]["processes_up"] == 2


def test_cli_fleetz_refuses_empty_input(tmp_path):
    from photon_ml_tpu.cli import fleetz

    with pytest.raises(SystemExit):
        fleetz.run([])
    with pytest.raises(SystemExit):
        fleetz.run([str(tmp_path / "nothing-here")])


def test_cli_fleetz_is_jax_free():
    """The aggregator must import (and run) with jax unimportable — the
    monitoring-sidecar contract lint R8 pins statically, checked dynamically."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "import photon_ml_tpu.cli.fleetz\n"
        "import photon_ml_tpu.obs.fleet\n"
        "print('JAXFREE_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr
    assert "JAXFREE_OK" in proc.stdout


# -- 2-process --config scale parity drill (slow) -----------------------------


_FLEET_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

from photon_ml_tpu.cli import train

train.run(sys.argv[1:])
print("WORKER_OK", jax.process_index())
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_fleet_merge_parity(tmp_path):
    """The acceptance drill: a 2-process run leaves per-process streams;
    fleet-merged counters equal the per-process sums exactly, and the
    stitched trace holds both pid lanes with no dropped spans."""
    from photon_ml_tpu.cli import index as index_cli
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO

    rng = np.random.default_rng(5)
    n, d = 320, 6
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(x @ w)))).astype(int)
    data = str(tmp_path / "train.avro")
    write_avro_file(
        data, TRAINING_EXAMPLE_AVRO,
        [{"label": float(y[i]),
          "features": [{"name": f"f{j}", "term": "", "value": float(x[i, j])}
                       for j in range(d)]} for i in range(n)],
    )
    index_dir = str(tmp_path / "index")
    metrics_dir = str(tmp_path / "metrics")
    common = ["--input-data", data, "--feature-shard", "name=global,bags=features"]
    index_cli.run(common + ["--output-dir", index_dir])

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    # 4 virtual CPU devices per process
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _FLEET_WORKER,
                *common,
                "--task", "logistic_regression",
                "--coordinate",
                "name=global,shard=global,optimizer=LBFGS,max.iter=40,"
                "reg.type=L2,reg.weights=1",
                "--feature-index-dir", index_dir,
                "--output-dir", str(tmp_path / "out"),
                "--metrics-out", metrics_dir,
                "--mesh-shape", "data=8",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process fleet drill timed out")
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out

    # every process streamed its own lane
    assert os.path.exists(os.path.join(metrics_dir, "metrics.jsonl"))
    assert os.path.exists(os.path.join(metrics_dir, "metrics.p1.jsonl"))
    streams = fleet.discover_streams([metrics_dir])
    assert sorted(s.process_index for s in streams) == [0, 1]

    # merged counters == per-process sums, bit-exact, for EVERY counter family
    merged = _by_key(
        fleet.merge_snapshots([(s.identity, s.snapshot) for s in streams])
    )
    per_process = [_by_key(s.snapshot) for s in streams]
    checked = 0
    for key, m in merged.items():
        if m["kind"] != "counter":
            continue
        expect = sum(
            float(pp[key]["value"]) for pp in per_process if key in pp
        )
        assert m["value"] == expect, f"counter {key} drifted in the merge"
        checked += 1
    assert checked > 0

    # stitched trace: both pid lanes, no dropped spans
    trace = fleet.stitch_spans(streams)
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(events) == sum(len(s.spans) for s in streams)
    assert {e["pid"] for e in events} == {0, 1}
