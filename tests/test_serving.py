"""Resident scoring service (photon_ml_tpu/serving): mmap store roundtrip,
cold-start fallback, batch/resident bitwise parity, microbatching, the
AF_UNIX front, and the kill-and-keep-serving refresh drill.

Parity note: per-row scores are row-independent, so padding the batch to a
ladder rung can never change a real row's bits; padding the ELL feature
width CAN regroup the reduction, so the bitwise resident-vs-batch tests pin
max row nnz = 4 = the smallest width rung (serving.engine.LADDER_WIDTH[0]).
"""

from __future__ import annotations

import os
import json
import socket
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu import obs, serving
from photon_ml_tpu.estimators.game_estimator import GameTransformer
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import Coefficients, LogisticRegressionModel
from photon_ml_tpu.serving.engine import _ladder_rows, _ladder_width
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset

D_FIXED = 6
D_RE = 4


def make_model(fe_shift=0.0, seed=0):
    """Small two-coordinate GLMix model with deterministic coefficients."""
    rng = np.random.default_rng(seed)
    fe = FixedEffectModel(
        model=LogisticRegressionModel(
            Coefficients(jnp.asarray(rng.standard_normal(D_FIXED) + fe_shift))
        ),
        feature_shard="globalShard",
    )
    re = RandomEffectModel(
        random_effect_type="userId",
        feature_shard="userShard",
        task="logistic_regression",
        entity_ids=np.asarray(["uA", "uB", "uC"], dtype=object),
        coef_indices=jnp.asarray(
            [[0, 2, -1], [1, 3, -1], [0, 1, 2]], jnp.int32
        ),
        coef_values=jnp.asarray(rng.standard_normal((3, 3))),
    )
    return GameModel(models={"global": fe, "per-user": re}, task="logistic_regression")


def make_request(rng, uid):
    """Random request with nnz=4 on the global shard, nnz=2 on the user
    shard (both <= the smallest width rung, for bitwise parity)."""
    gidx = np.sort(rng.choice(D_FIXED, size=4, replace=False))
    uidx = np.sort(rng.choice(D_RE, size=2, replace=False))
    return serving.ScoreRequest(
        features={
            "globalShard": (tuple(int(i) for i in gidx),
                            tuple(rng.standard_normal(4).tolist())),
            "userShard": (tuple(int(i) for i in uidx),
                          tuple(rng.standard_normal(2).tolist())),
        },
        ids={"userId": uid},
        offset=float(rng.standard_normal()),
    )


def oracle_score(model, req):
    """Hand-assembled numpy oracle: offset + FE dot + (RE dot | 0 if unseen)."""
    total = req.offset
    fe = model.models["global"]
    w = np.asarray(fe.model.coefficients.means)
    gi, gv = req.features["globalShard"]
    total += float(np.dot(w[np.asarray(gi)], np.asarray(gv)))
    re = model.models["per-user"]
    uid = req.ids.get("userId")
    ids = list(re.entity_ids)
    if uid in ids:
        row = ids.index(uid)
        coef = {
            int(c): float(v)
            for c, v in zip(
                np.asarray(re.coef_indices)[row], np.asarray(re.coef_values)[row]
            )
            if int(c) >= 0
        }
        ui, uv = req.features["userShard"]
        total += sum(coef.get(int(c), 0.0) * float(v) for c, v in zip(ui, uv))
    return total


@pytest.fixture
def run_telemetry():
    run = obs.RunTelemetry()
    with obs.use_run(run):
        yield run


# -- store ------------------------------------------------------------------


def test_store_roundtrip_bitwise(tmp_path):
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    store = serving.ModelStore.open(store_dir)
    assert store.task == "logistic_regression"
    by_name = {c.name: c for c in store.coords}
    fe, re = by_name["global"], by_name["per-user"]
    np.testing.assert_array_equal(
        np.asarray(fe.weights), np.asarray(model.models["global"].model.coefficients.means)
    )
    np.testing.assert_array_equal(
        np.asarray(re.coef_indices), np.asarray(model.models["per-user"].coef_indices)
    )
    np.testing.assert_array_equal(
        np.asarray(re.coef_values), np.asarray(model.models["per-user"].coef_values)
    )
    # dtype preserved exactly (f64 under the test harness)
    assert np.asarray(re.coef_values).dtype == np.asarray(
        model.models["per-user"].coef_values
    ).dtype
    np.testing.assert_array_equal(
        re.rows_for(["uA", "uC", "nobody", None]), [0, 2, -1, -1]
    )


def test_store_meta_written_last_certifies(tmp_path):
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    os.unlink(os.path.join(store_dir, "store-meta.json"))
    with pytest.raises(Exception):
        serving.ModelStore.open(store_dir)


def test_store_version_refused(tmp_path):
    d = tmp_path / "store"
    d.mkdir()
    (d / "store-meta.json").write_text(
        json.dumps({"version": 99, "task": "x", "coordinates": []})
    )
    with pytest.raises(ValueError, match="unsupported serving store version"):
        serving.ModelStore.open(str(d))


# -- engine: cold start + oracle --------------------------------------------


def test_cold_start_fallback_and_oracle(run_telemetry):
    model = make_model()
    engine = serving.ScoreEngine.from_model(model, dtype=jnp.float64)
    rng = np.random.default_rng(7)
    # mixed batch: seen, unseen, seen, missing-id
    reqs = [
        make_request(rng, "uA"),
        make_request(rng, "stranger"),
        make_request(rng, "uC"),
        serving.ScoreRequest(
            features={"globalShard": ((0, 1), (1.0, 2.0)), "userShard": ((0,), (5.0,))}
        ),
    ]
    scores = engine.score_requests(reqs)
    expected = [oracle_score(model, r) for r in reqs]
    np.testing.assert_allclose(scores, expected, rtol=0, atol=1e-12)
    # unseen entities scored fixed-effect-only: the RE term contributed 0
    # (oracle_score already models that); the counter saw exactly the two
    # cold rows
    snap = run_telemetry.registry.snapshot()
    cold = [
        m for m in snap if m["name"] == "photon_serving_cold_start_total"
    ]
    assert len(cold) == 1
    assert cold[0]["labels"] == {"coordinate": "per-user"}
    assert cold[0]["value"] == 2


def test_warmup_does_not_count_cold_starts(run_telemetry):
    engine = serving.ScoreEngine.from_model(make_model(), dtype=jnp.float64)
    engine.warm()
    snap = run_telemetry.registry.snapshot()
    assert not [m for m in snap if m["name"] == "photon_serving_cold_start_total"]


def test_ladder_shapes():
    assert _ladder_rows(1) == 1
    assert _ladder_rows(9) == 64
    assert _ladder_rows(10**9) == serving.LADDER_ROWS[-1]
    assert _ladder_width(3) == 4
    assert _ladder_width(65) == 256
    with pytest.raises(ValueError, match="padded feature-width ladder"):
        _ladder_width(serving.LADDER_WIDTH[-1] + 1)


# -- parity ------------------------------------------------------------------


@pytest.fixture(scope="module")
def raw_dataset():
    data = generate_mixed_effect_data(
        n=60, d_fixed=D_FIXED, re_specs={"userId": (3, D_RE)}, seed=5
    )
    return mixed_data_to_raw_dataset(data)


def test_transform_and_engine_bitwise_parity(raw_dataset):
    """GameTransformer.transform and the engine's batch path produce
    bitwise-identical scores (transform delegates to the engine)."""
    model = make_model()
    engine = serving.ScoreEngine.from_model(model, dtype=jnp.float64)
    t = GameTransformer(model=model, dtype=jnp.float64)
    raw = _rename_shards(raw_dataset)
    s_t, _ = t.transform(raw)
    s_e = engine.score_dataset(raw)
    np.testing.assert_array_equal(s_t, s_e)


def _rename_shards(raw):
    """The generator emits shards named 'global'/'userId'; the test model
    uses 'globalShard'/'userShard'. Re-key the dataset's shard maps."""
    mapping = {"global": "globalShard", "userId": "userShard"}
    raw.shard_coo = {mapping.get(k, k): v for k, v in raw.shard_coo.items()}
    if getattr(raw, "shard_dims", None):
        raw.shard_dims = {mapping.get(k, k): v for k, v in raw.shard_dims.items()}
    return raw


def test_resident_vs_batch_bitwise_parity(run_telemetry):
    """The same rows scored through the resident ladder-padded path and the
    batch dataset path are bitwise-equal when the ELL width matches (max
    nnz = 4 = the smallest width rung); row padding never changes bits."""
    model = make_model()
    engine = serving.ScoreEngine.from_model(model, dtype=jnp.float64)
    rng = np.random.default_rng(11)
    reqs = [
        make_request(rng, uid)
        for uid in ["uA", "uB", "nobody", "uC", "uA", None, "uB"]
    ]
    resident = engine.score_requests(reqs)

    # hand-assemble the same rows as a batch 'dataset' at natural width 4/2
    n = len(reqs)
    offsets = np.array([r.offset for r in reqs])
    shard_ell = {}
    for shard, width in (("globalShard", 4), ("userShard", 2)):
        idx = np.zeros((n, width), dtype=np.int32)
        val = np.zeros((n, width), dtype=np.float64)
        for i, r in enumerate(reqs):
            fi, fv = r.features[shard]
            idx[i, : len(fi)] = fi
            val[i, : len(fv)] = fv
        shard_ell[shard] = (idx, val)
    # natural widths differ from the rung only for userShard (2 vs 4): pad
    # the batch side to the rung too — trailing (idx=0, val=0) pairs add
    # exact zeros, but regrouping the sum would not be bitwise-safe
    idx, val = shard_ell["userShard"]
    shard_ell["userShard"] = (
        np.pad(idx, ((0, 0), (0, 2))),
        np.pad(val, ((0, 0), (0, 2))),
    )
    re = model.models["per-user"]
    erow = re.rows_for([r.ids.get("userId") for r in reqs]).astype(np.int32)
    batch = engine.score_ell(offsets, shard_ell, {"per-user": erow})
    np.testing.assert_array_equal(resident, batch)


# -- microbatcher ------------------------------------------------------------


def test_batcher_batches_and_scores(run_telemetry):
    model = make_model()
    engine = serving.ScoreEngine.from_model(model, dtype=jnp.float64)
    engine.warm()
    b = serving.MicroBatcher(lambda: engine, max_batch=64, max_latency_ms=20.0)
    rng = np.random.default_rng(3)
    reqs = [make_request(rng, "uA") for _ in range(16)]
    futs = [b.submit(r) for r in reqs]
    got = [f.result(timeout=30.0) for f in futs]
    np.testing.assert_allclose(
        got, [oracle_score(model, r) for r in reqs], rtol=0, atol=1e-12
    )
    b.close()
    snap = run_telemetry.registry.snapshot()
    by_name = {m["name"]: m for m in snap if "count" in m or "value" in m}
    assert by_name["photon_serving_requests_total"]["value"] == 16
    assert by_name["photon_serving_request_latency_seconds"]["count"] == 16
    # at least one multi-request microbatch formed under the 20ms budget
    assert by_name["photon_serving_batch_size"]["sum"] == 16
    assert by_name["photon_serving_batch_size"]["count"] < 16


def test_batcher_error_propagates_and_counts(run_telemetry):
    def broken_engine():
        raise RuntimeError("engine exploded")

    class _Broken:
        def score_requests(self, reqs, count_cold=True):
            raise RuntimeError("engine exploded")

    b = serving.MicroBatcher(lambda: _Broken(), max_batch=4, max_latency_ms=1.0)
    fut = b.submit(serving.ScoreRequest(features={}))
    with pytest.raises(RuntimeError, match="engine exploded"):
        fut.result(timeout=30.0)
    b.close()
    snap = run_telemetry.registry.snapshot()
    errs = [m for m in snap if m["name"] == "photon_serving_request_errors_total"]
    assert errs and errs[0]["value"] == 1


def test_batcher_rejects_after_close():
    engine = serving.ScoreEngine.from_model(make_model(), dtype=jnp.float64)
    b = serving.MicroBatcher(lambda: engine)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(serving.ScoreRequest(features={}))


# -- refresh + server: the kill-and-keep-serving drill -----------------------


def test_publish_and_open_current(tmp_path, run_telemetry):
    root = str(tmp_path / "root")
    assert serving.current_snapshot(root) is None
    serving.publish_snapshot(root, "v1", game_model=make_model())
    name, store = serving.open_current(root)
    assert name == "v1"
    assert store.task == "logistic_regression"
    with pytest.raises(FileExistsError):
        serving.publish_snapshot(root, "v1", game_model=make_model())
    with pytest.raises(ValueError, match="exactly one"):
        serving.publish_snapshot(root, "v2")


def test_refresh_survives_torn_publish(tmp_path, run_telemetry):
    root = tmp_path / "root"
    serving.publish_snapshot(str(root), "v1", game_model=make_model())
    flips = []
    w = serving.RefreshWatcher(
        str(root), lambda n, s: flips.append(n), poll_seconds=60.0, live="v1"
    )
    try:
        # CURRENT points at a snapshot that never finished publishing
        (root / "CURRENT").write_text("v2\n")
        w.poke()
        assert flips == []
        snap = run_telemetry.registry.snapshot()
        swallowed = [
            m
            for m in snap
            if m["name"] == "photon_swallowed_errors_total"
            and m["labels"].get("site") == "serving.refresh"
        ]
        assert swallowed and swallowed[0]["value"] >= 1
    finally:
        w.stop()


def test_kill_and_keep_serving_drill(tmp_path, run_telemetry):
    """Publish a new snapshot mid-stream: no request errors, every response
    comes from exactly one snapshot (v1 before the flip, v2 after — no
    stale-mixed batches), and post-flip scores bitwise-match a fresh load
    of the new model."""
    root = str(tmp_path / "root")
    m1, m2 = make_model(fe_shift=0.0), make_model(fe_shift=100.0)
    serving.publish_snapshot(root, "v1", game_model=m1)
    server = serving.ScoringServer(
        serving_root=root, max_batch=8, max_latency_ms=1.0,
        poll_seconds=3600.0, dtype=jnp.float64,
    )
    rng = np.random.default_rng(23)
    reqs = [make_request(rng, ["uA", "uB", "uC"][i % 3]) for i in range(60)]
    exp1 = np.array([oracle_score(m1, r) for r in reqs])
    exp2 = np.array([oracle_score(m2, r) for r in reqs])
    assert np.min(np.abs(exp1 - exp2)) > 1.0  # the two models are distinguishable

    try:
        futs = []
        for i, r in enumerate(reqs):
            futs.append(server.submit(r))
            if i == 20:
                serving.publish_snapshot(root, "v2", game_model=m2)
                server.poke_refresh()
            time.sleep(0.001)
        got = np.array([f.result(timeout=30.0) for f in futs])  # no errors
        from_v1 = np.isclose(got, exp1, rtol=0, atol=1e-9)
        from_v2 = np.isclose(got, exp2, rtol=0, atol=1e-9)
        # every response from exactly one model, and the stream is monotone:
        # once a response comes from v2, nothing later comes from v1
        assert np.all(from_v1 ^ from_v2)
        if from_v2.any():
            first_v2 = int(np.argmax(from_v2))
            assert np.all(from_v2[first_v2:])
        assert server.snapshot_name == "v2"
        assert from_v2.any()

        # post-flip scores bitwise-match a fresh load of the new snapshot
        fresh = serving.ScoreEngine.from_store(
            serving.ModelStore.open(serving.snapshot_path(root, "v2")),
            dtype=jnp.float64,
        )
        tail = [r for r, v2 in zip(reqs, from_v2) if v2]
        np.testing.assert_array_equal(got[from_v2], fresh.score_requests(tail))

        snap = run_telemetry.registry.snapshot()
        refreshes = [
            m for m in snap if m["name"] == "photon_serving_refresh_total"
        ]
        assert refreshes and refreshes[0]["value"] == 1
        errs = [
            m for m in snap if m["name"] == "photon_serving_request_errors_total"
        ]
        assert not errs
    finally:
        server.close()


# -- the AF_UNIX front -------------------------------------------------------


def _connect_when_listening(client, sock_path, deadline):
    """``serve_socket`` binds its path, then listens: a client that saw the
    path appear may still be refused for a moment."""
    while True:
        try:
            return client.connect(sock_path)
        except ConnectionRefusedError:
            if time.time() >= deadline:
                raise
            time.sleep(0.01)


def test_socket_server_roundtrip(tmp_path, run_telemetry):
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    server = serving.ScoringServer(
        store=serving.ModelStore.open(store_dir),
        max_latency_ms=1.0,
        dtype=jnp.float64,
    )
    sock_path = str(tmp_path / "serve.sock")
    stop = threading.Event()
    t = threading.Thread(
        target=serving.serve_socket, args=(server, sock_path, stop), daemon=True
    )
    t.start()
    try:
        deadline = time.time() + 10
        while not os.path.exists(sock_path) and time.time() < deadline:
            time.sleep(0.01)
        rng = np.random.default_rng(9)
        req = make_request(rng, "uB")
        payload = {
            "features": {k: [list(v[0]), list(v[1])] for k, v in req.features.items()},
            "ids": dict(req.ids),
            "offset": req.offset,
        }
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
            _connect_when_listening(c, sock_path, deadline)
            f = c.makefile("rwb")
            f.write((json.dumps(payload) + "\n").encode())
            f.flush()
            resp = json.loads(f.readline())
            assert abs(resp["score"] - oracle_score(model, req)) < 1e-12
            # every response carries the request-scoped trace id
            assert resp["trace_id"]
            # malformed request -> error response, connection stays up
            f.write(b'{"features": "nonsense"}\n')
            f.flush()
            resp2 = json.loads(f.readline())
            assert "error" in resp2
            assert resp2["trace_id"]
            assert resp2["trace_id"] != resp["trace_id"]
            # client-supplied trace_id is echoed, not replaced
            payload["trace_id"] = "client-abc-123"
            f.write((json.dumps(payload) + "\n").encode())
            f.flush()
            resp3 = json.loads(f.readline())
            assert resp3["trace_id"] == "client-abc-123"
            assert abs(resp3["score"] - oracle_score(model, req)) < 1e-12
    finally:
        stop.set()
        t.join(timeout=10)
        server.close()


def test_shed_response_carries_trace_id(tmp_path, run_telemetry):
    """Sheds are responses too: the AF_UNIX front echoes the trace_id on a
    shed so the client can tie the refusal back to its request."""
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    server = serving.ScoringServer(
        store=serving.ModelStore.open(store_dir),
        max_latency_ms=1.0,
        dtype=jnp.float64,
        default_deadline_ms=1e-6,  # expires before admission: always sheds
    )
    sock_path = str(tmp_path / "serve.sock")
    stop = threading.Event()
    t = threading.Thread(
        target=serving.serve_socket, args=(server, sock_path, stop), daemon=True
    )
    t.start()
    try:
        deadline = time.time() + 10
        while not os.path.exists(sock_path) and time.time() < deadline:
            time.sleep(0.01)
        rng = np.random.default_rng(11)
        req = make_request(rng, "uA")
        payload = {
            "features": {k: [list(v[0]), list(v[1])] for k, v in req.features.items()},
            "trace_id": "shed-trace-9",
        }
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
            _connect_when_listening(c, sock_path, deadline)
            f = c.makefile("rwb")
            f.write((json.dumps(payload) + "\n").encode())
            f.flush()
            resp = json.loads(f.readline())
        assert resp["error_type"] == "shed"
        assert resp["trace_id"] == "shed-trace-9"
        assert resp["reason"]
    finally:
        stop.set()
        t.join(timeout=10)
        server.close()
    sheds = [
        m for m in run_telemetry.registry.snapshot()
        if m["name"] == "photon_serving_shed_total"
    ]
    assert sheds and sum(m["value"] for m in sheds) >= 1


def test_request_stage_spans_and_slow_counter(tmp_path, run_telemetry):
    """A traced request lands per-stage spans (admit/batch/score) parented
    under its serving.request root and all stamped with the trace_id; with a
    sub-millisecond slow threshold every request also trips the
    slow-request counter."""
    from photon_ml_tpu.serving.batcher import RequestTrace

    spans = []

    class _SpanTap:
        def handle(self, event):
            if isinstance(event, obs.SpanEvent):
                spans.append(event.span)

    run_telemetry.register_listener(_SpanTap())
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    server = serving.ScoringServer(
        store=serving.ModelStore.open(store_dir),
        max_latency_ms=1.0,
        dtype=jnp.float64,
        slow_request_ms=1e-4,  # everything is "slow": the counter must fire
    )
    try:
        rng = np.random.default_rng(13)
        req = make_request(rng, "uB")
        with obs.span("serving.request", trace_id="t-42") as root:
            trace = RequestTrace(trace_id="t-42", parent=root)
            score = server.score(req, trace=trace)
        assert abs(score - oracle_score(model, req)) < 1e-12
    finally:
        server.close()

    by_name = {s.name: s for s in spans}
    for stage in ("serving.admit", "serving.batch", "serving.score"):
        assert stage in by_name, f"missing stage span {stage}"
        assert by_name[stage].parent_id == root.span_id
        assert by_name[stage].attrs["trace_id"] == "t-42"
    assert by_name["serving.request"].attrs["trace_id"] == "t-42"
    slow = [
        m for m in run_telemetry.registry.snapshot()
        if m["name"] == "photon_serving_slow_requests_total"
    ]
    assert slow and slow[0]["value"] >= 1


def test_shed_storm_one_flight_dump_zero_requests_lost(tmp_path, run_telemetry):
    """The acceptance drill: a shed storm triggers exactly ONE flight-recorder
    dump (the cooldown latch holds for the rest of the storm) and every
    submitted request still resolves — scored or cleanly shed, none lost."""
    rec = obs.FlightRecorder(
        str(tmp_path / "flight"),
        run=run_telemetry,
        shed_rate_threshold=5.0,
        poll_interval_s=0.0,
        cooldown_s=60.0,
    )
    run_telemetry.register_listener(rec)
    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    server = serving.ScoringServer(
        store=serving.ModelStore.open(store_dir),
        max_latency_ms=20.0,
        dtype=jnp.float64,
        max_pending=2,  # tiny queue: the flood sheds on queue_full
    )
    try:
        rng = np.random.default_rng(17)
        # warm-up request establishes the recorder's rate baseline
        server.score(make_request(rng, "uA"))
        assert rec.poll(force=True) is None
        time.sleep(0.05)

        futs = []
        for i in range(200):
            try:
                futs.append(server.submit(make_request(rng, "uC")))
            except serving.ShedError:
                futs.append(None)  # shed at admission: still a clean outcome
        scored = shed = 0
        for fut in futs:
            if fut is None:
                shed += 1
                continue
            try:
                fut.result(timeout=30.0)
                scored += 1
            except serving.ShedError:
                shed += 1
        assert scored + shed == 200  # zero requests lost
        assert shed >= 1  # the storm actually shed

        first = rec.poll(force=True)
        assert first is not None
        # storm keeps raging; the latch holds — still exactly one dump
        for _ in range(5):
            try:
                server.submit(make_request(rng, "uC")).result(timeout=30.0)
            except serving.ShedError:
                pass
            assert rec.poll(force=True) is None
        assert len(rec.dump_paths) == 1
        doc = json.load(open(first))
        assert doc["trigger"]["kind"] == "shed_spike"
    finally:
        server.close()


def test_cli_serve_store_dir_socket(tmp_path):
    """The cli.serve driver end to end: serve a store over the socket,
    score one request, stop, and find the Prometheus exposition (with the
    serving quantile gauges) in --metrics-out."""
    from photon_ml_tpu.cli import serve as cli_serve

    model = make_model()
    store_dir = serving.build_store_from_model(model, str(tmp_path / "store"))
    sock_path = str(tmp_path / "serve.sock")
    metrics_dir = str(tmp_path / "metrics")
    stop = threading.Event()
    t = threading.Thread(
        target=cli_serve.run,
        args=(
            [
                "--store-dir", store_dir,
                "--socket", sock_path,
                "--max-latency-ms", "1.0",
                "--metrics-out", metrics_dir,
                "--replica-id", "r3",
            ],
            stop,
        ),
        daemon=True,
    )
    t.start()
    try:
        deadline = time.time() + 30
        while not os.path.exists(sock_path) and time.time() < deadline:
            time.sleep(0.01)
        assert os.path.exists(sock_path), "cli.serve never bound its socket"
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
            _connect_when_listening(c, sock_path, deadline)
            f = c.makefile("rwb")
            f.write(b'{"features": {"globalShard": [[0], [1.0]]}}\n')
            f.flush()
            resp = json.loads(f.readline())
        w = np.asarray(model.models["global"].model.coefficients.means)
        assert abs(resp["score"] - float(w[0])) < 1e-6
        assert resp["trace_id"]
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()
    prom = os.path.join(metrics_dir, "metrics.prom")
    assert os.path.exists(prom)
    text = open(prom).read()
    assert "photon_serving_request_latency_seconds_p99" in text
    assert "photon_serving_requests_total" in text
    # --replica-id stamps the obs identity into the build-info gauge
    assert "photon_build_info{" in text
    assert 'replica="r3"' in text
    # the serve driver arms a flight recorder beside the metric sinks
    assert os.path.isdir(os.path.join(metrics_dir, "flight"))


# -- prometheus quantiles ----------------------------------------------------


def test_serving_quantiles_in_prometheus(run_telemetry):
    reg = run_telemetry.registry
    h = reg.histogram(
        "photon_serving_request_latency_seconds",
        "t",
        buckets=serving.SERVING_LATENCY_BUCKETS,
    )
    for v in [0.001] * 50 + [0.004] * 45 + [0.2] * 5:
        h.observe(v)
    text = obs.render_prometheus(reg.snapshot())
    assert "photon_serving_request_latency_seconds_p50" in text
    assert "photon_serving_request_latency_seconds_p95" in text
    assert "photon_serving_request_latency_seconds_p99" in text
    # quantile gauges render for every histogram family, serving or not
    reg.histogram("photon_other", "t").observe(1.0)
    text = obs.render_prometheus(reg.snapshot())
    assert "# TYPE photon_other_p50 gauge" in text


def test_histogram_quantile_interpolation():
    # 100 obs: 50 in (0, 1], 45 in (1, 5], 5 in (5, +Inf)
    buckets = [(1.0, 50), (5.0, 95)]
    assert obs.histogram_quantile(buckets, 100, 0.5) == 1.0
    # p90 -> rank 90 inside (1, 5]: 1 + 4 * (90-50)/45
    assert abs(obs.histogram_quantile(buckets, 100, 0.9) - (1 + 4 * 40 / 45)) < 1e-12
    # target beyond the last finite bucket clamps to its upper bound
    assert obs.histogram_quantile(buckets, 100, 0.99) == 5.0
    assert obs.histogram_quantile(buckets, 0, 0.5) == 0.0
    assert obs.histogram_quantile([], 10, 0.5) == 0.0


def test_retrain_publish_three_daily_flips_drill(tmp_path, run_telemetry):
    """Continuous-training drill: three consecutive daily publishes through
    the chain's real publish path (incremental._ensure_published) flip a
    live server mid-stream. Zero lost requests, every response from exactly
    one published model, and the flip sequence is monotone day over day."""
    from photon_ml_tpu.game import incremental

    root = str(tmp_path / "root")
    models = [make_model(fe_shift=100.0 * k, seed=5) for k in range(4)]
    serving.publish_snapshot(root, "retrain-20260101", game_model=models[0])
    server = serving.ScoringServer(
        serving_root=root, max_batch=8, max_latency_ms=1.0,
        poll_seconds=3600.0, dtype=jnp.float64,
    )
    rng = np.random.default_rng(41)
    reqs = [make_request(rng, ["uA", "uB", "uC"][i % 3]) for i in range(80)]
    exp = np.stack([[oracle_score(m, r) for r in reqs] for m in models])
    # any two of the four dailies are distinguishable on every request
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.min(np.abs(exp[a] - exp[b])) > 1.0

    def _publish(day_index):
        day = f"2026010{day_index + 1}"
        rec = incremental.DayRecord(
            day=day, index=day_index, accepted=True, reason="accepted",
            rows=0, touched_entities={}, snapshot=f"retrain-{day}",
        )
        assert incremental._ensure_published(root, rec, models[day_index])

    try:
        futs = []
        for i, r in enumerate(reqs):
            futs.append(server.submit(r))
            if i in (20, 40, 60):  # three consecutive daily flips mid-stream
                _publish(i // 20)
                server.poke_refresh()
            time.sleep(0.001)
        got = np.array([f.result(timeout=30.0) for f in futs])  # zero lost
        source = np.full(len(reqs), -1)
        for k in range(4):
            hit = np.isclose(got, exp[k], rtol=0, atol=1e-9)
            assert np.all(source[hit] == -1)  # exactly one model per response
            source[hit] = k
        assert np.all(source >= 0)
        # day-over-day monotone: the served model index never goes backwards
        assert np.all(np.diff(source) >= 0)
        assert source[-1] == 3 and server.snapshot_name == "retrain-20260104"

        snap = run_telemetry.registry.snapshot()
        refreshes = [
            m for m in snap if m["name"] == "photon_serving_refresh_total"
        ]
        assert refreshes and refreshes[0]["value"] == 3
        published = [
            m for m in snap if m["name"] == "photon_retrain_published_total"
        ]
        assert published and published[0]["value"] == 3
        errs = [
            m for m in snap if m["name"] == "photon_serving_request_errors_total"
        ]
        assert not errs
    finally:
        server.close()
