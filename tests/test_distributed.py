"""Distributed fault tolerance: heartbeats, bounded-time collectives,
two-phase topology-aware checkpoints, and the kill-a-worker recovery drill.

The tier-1 tests here exercise the whole liveness surface in-process (fake
coordination clients, injected exchanges, the standard fault grammar); the
``slow``-marked drill at the bottom runs the REAL thing: two OS processes,
one killed mid-sweep by ``dist.collective:kill``, the survivor failing with
a typed timeout + a ``peer_lost`` flight dump within the budget, then both
relaunched with ``--resume`` to finish from the last committed two-phase
checkpoint and match an uninterrupted reference run."""

import glob
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.plan import PlanError, planner
from photon_ml_tpu.robust import distributed as rd
from photon_ml_tpu.robust import faults
from photon_ml_tpu.robust.checkpoint import (
    CheckpointIncompatibleError,
    CheckpointManager,
)
from photon_ml_tpu.robust.faults import InjectedIOError, SimulatedKill

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_state():
    yield
    faults.clear()
    rd.clear_collectives()


@pytest.fixture
def run():
    """Fresh telemetry scope so counter assertions see only this test."""
    r = obs.RunTelemetry()
    with obs.use_run(r):
        yield r


def counter_value(run, name, **labels):
    return run.registry.counter(name, "").labels(**labels).value


def _wait_until(predicate, timeout_s=10.0, interval_s=0.01):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


# ---------------------------------------------------------------- heartbeats


def test_heartbeat_write_read_roundtrip(tmp_path):
    d = str(tmp_path)
    rd.write_heartbeat(d, 0, 1)
    rd.write_heartbeat(d, 1, 7)
    recs = rd.read_heartbeats(d)
    assert set(recs) == {0, 1}
    assert recs[1]["seq"] == 7 and recs[1]["pid"] == os.getpid()
    # a torn record reads as a missing peer, not a crash
    with open(rd.heartbeat_path(d, 2), "w") as f:
        f.write('{"process": 2, "se')
    assert set(rd.read_heartbeats(d)) == {0, 1}


def test_heartbeat_ages_and_gauge(tmp_path, run):
    d = str(tmp_path)
    rd.write_heartbeat(d, 0, 1)
    ages = rd.heartbeat_ages(d, now=time.time() + 5.0)
    assert ages[0] == pytest.approx(5.0, abs=1.0)
    gauge = run.registry.gauge(
        "photon_dist_heartbeat_age_seconds", ""
    ).labels(process="0")
    assert gauge.value == pytest.approx(ages[0])


def test_stale_and_missing_peers_raise_typed_error(tmp_path):
    d = str(tmp_path)
    now = time.time()
    rd.write_heartbeat(d, 0, 1)
    rd.write_heartbeat(d, 1, 1)
    # fresh: no stale peers (self excluded either way)
    rd.check_peers(d, 2, stale_after_s=30.0, self_process=0, now=now)
    # peer 1's record ages past the budget; peer 2 never beat at all
    with pytest.raises(rd.PeerLostError, match=r"presumed lost"):
        rd.check_peers(d, 3, 5.0, self_process=0, now=now + 60.0)
    try:
        rd.check_peers(d, 3, 5.0, self_process=0, now=now + 60.0)
    except rd.PeerLostError as e:
        assert "p2=never" in str(e)
        assert "[1, 2]" in str(e)


def test_heartbeat_fault_site_fires(tmp_path, run):
    faults.configure("dist.heartbeat:io:1")
    with pytest.raises(InjectedIOError):
        rd.write_heartbeat(str(tmp_path), 0, 1)
    assert counter_value(
        run, "photon_faults_injected_total", site="dist.heartbeat", kind="io"
    ) == 1


def test_heartbeat_writer_beats_and_swallows_transient_io(tmp_path, run):
    d = str(tmp_path)
    w = rd.HeartbeatWriter(d, 0, interval_s=0.02).start()
    try:
        assert _wait_until(lambda: rd.read_heartbeats(d).get(0, {}).get("seq", 0) >= 3)
        # two transient write failures: swallowed + counted, then the next
        # beat repairs the record and seq keeps advancing
        faults.configure("dist.heartbeat:io:1x2")
        assert _wait_until(
            lambda: counter_value(
                run, "photon_swallowed_errors_total", site="dist.heartbeat"
            ) >= 2
        )
        seq_after_fault = rd.read_heartbeats(d)[0]["seq"]
        assert _wait_until(
            lambda: rd.read_heartbeats(d)[0]["seq"] > seq_after_fault
        )
    finally:
        w.stop()
    assert not w._thread.is_alive()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_heartbeat_kill_takes_down_the_writer_thread(tmp_path):
    """dist.heartbeat:kill is the starved-liveness-plane drill: the process
    keeps running but its beats stop, so peers see a growing age."""
    d = str(tmp_path)
    w = rd.HeartbeatWriter(d, 1, interval_s=0.02)
    w.start()  # beat 1 lands synchronously
    faults.configure("dist.heartbeat:kill:1")
    assert _wait_until(lambda: not w._thread.is_alive())
    seq_frozen = rd.read_heartbeats(d)[1]["seq"]
    time.sleep(0.1)
    assert rd.read_heartbeats(d)[1]["seq"] == seq_frozen


def test_heartbeat_writer_rejects_bad_interval(tmp_path):
    with pytest.raises(ValueError, match="interval must be > 0"):
        rd.HeartbeatWriter(str(tmp_path), 0, interval_s=0.0)


# ------------------------------------------------- bounded-time collectives


def test_sweep_barrier_fires_fault_site_once_per_sweep(run):
    faults.configure("dist.collective:kill:2")
    rd.sweep_barrier(0)  # sweep 1 survives
    with pytest.raises(SimulatedKill):
        rd.sweep_barrier(1)
    assert counter_value(
        run, "photon_faults_injected_total", site="dist.collective", kind="kill"
    ) == 1


def test_barrier_delay_fault_holds_the_process(run):
    faults.configure("dist.collective:delay80:1")
    t0 = time.perf_counter()
    rd.sweep_barrier(0)
    assert time.perf_counter() - t0 >= 0.06


def test_configure_collectives_arm_and_disarm():
    assert rd.collective_timeout() is None
    rd.configure_collectives(12.5, run_dir="/tmp/x", stale_after_s=3.0)
    assert rd.collective_timeout() == 12.5
    rd.configure_collectives(0)  # <= 0 disarms
    assert rd.collective_timeout() is None


class _FakeClient:
    """Stands in for jax's coordination-service client."""

    def __init__(self, error=None):
        self.error = error
        self.calls = []

    def wait_at_barrier(self, barrier_id, timeout_in_ms, process_ids=None):
        self.calls.append((barrier_id, timeout_in_ms))
        if self.error is not None:
            raise self.error


def _fake_two_process(monkeypatch, client):
    monkeypatch.setattr(rd, "_process_count", lambda: 2)
    monkeypatch.setattr(rd, "_coordination_client", lambda: client)


def test_barrier_ids_are_spmd_ordered_per_name(monkeypatch):
    client = _FakeClient()
    _fake_two_process(monkeypatch, client)
    rd.configure_collectives(5.0)
    rd.sweep_barrier(0)
    rd.sweep_barrier(1)
    rd.guard_collective("allgather_object")
    rd.sweep_barrier(1)  # same name again -> next sequence number
    assert [c[0] for c in client.calls] == [
        "photon:cd.sweep.0:1",
        "photon:cd.sweep.1:1",
        "photon:pre:allgather_object:1",
        "photon:cd.sweep.1:2",
    ]
    assert all(ms == 5000 for _, ms in client.calls)


def test_guard_collective_is_noop_unarmed(monkeypatch):
    client = _FakeClient()
    _fake_two_process(monkeypatch, client)
    rd.guard_collective("allgather_object")  # unarmed: no barrier issued
    assert client.calls == []


def test_barrier_deadline_translates_to_typed_timeout(
    monkeypatch, tmp_path, run
):
    d = str(tmp_path)
    rd.write_heartbeat(d, 0, 1)  # peer 1 never beats -> named in the error
    client = _FakeClient(
        error=RuntimeError("DEADLINE_EXCEEDED: barrier timed out")
    )
    _fake_two_process(monkeypatch, client)
    rd.configure_collectives(0.25, run_dir=d, stale_after_s=5.0)
    with pytest.raises(rd.DistributedTimeoutError) as ei:
        rd.sweep_barrier(3)
    msg = str(ei.value)
    assert "a peer process never arrived" in msg
    assert "budget 0.2s" in msg or "budget 0.3s" in msg
    assert "heartbeat-stale peers: [1]" in msg
    assert counter_value(
        run, "photon_dist_collective_timeouts_total", barrier="cd.sweep.3"
    ) == 1
    # and it is a DistributedError -> one except clause catches the family
    assert isinstance(ei.value, rd.DistributedError)


def test_barrier_peer_abort_also_translates(monkeypatch):
    """The coordination service can notice the dead peer BEFORE the deadline
    (missed service heartbeats) and abort the barrier — same typed error."""
    client = _FakeClient(
        error=RuntimeError("UNAVAILABLE: connection to peer task closed")
    )
    _fake_two_process(monkeypatch, client)
    rd.configure_collectives(5.0)
    with pytest.raises(rd.DistributedTimeoutError):
        rd.sweep_barrier(0)


def test_barrier_unrelated_error_is_not_translated(monkeypatch):
    client = _FakeClient(error=RuntimeError("PERMISSION_DENIED: bad token"))
    _fake_two_process(monkeypatch, client)
    rd.configure_collectives(5.0)
    with pytest.raises(RuntimeError, match="PERMISSION_DENIED"):
        rd.sweep_barrier(0)


def test_barrier_unarmed_multiprocess_is_blocking_shape(monkeypatch):
    # no budget armed: the barrier must NOT issue a client wait (collectives
    # keep their historical blocking behavior)
    client = _FakeClient()
    _fake_two_process(monkeypatch, client)
    rd.sweep_barrier(0)
    assert client.calls == []


# ------------------------------------- two-phase topology-aware checkpoints


class _State:
    """Minimal CDBoundaryState stand-in (mirrors tests/test_robust.py)."""

    def __init__(self, iteration=0, summed_scores=None):
        self.iteration = iteration
        self.coordinate_index = 0
        self.coordinate = "global"
        self.coordinate_order = ("global",)
        self.n_iterations = 3
        self.models = {"global": np.arange(3.0)}
        self.summed_scores = (
            np.ones(4) if summed_scores is None else summed_scores
        )
        self.best_eval = None
        self.best_models = {}
        self.evaluations = []
        self.trackers = {}
        self.train_losses = {}


class _FakeExchange:
    """Sequential stand-in for the allgather confirm exchange: the LAST
    caller (the coordinator, in these tests) sees every confirm."""

    def __init__(self):
        self.confirms = []

    def __call__(self, confirm):
        self.confirms.append(confirm)
        return list(self.confirms)

    def reset(self):
        self.confirms = []


_TOPOLOGY = {
    "mesh_axes": {"data": 8, "model": 1},
    "plan_fingerprint": "fp-aaaa",
}


def _pair(tmp_path, exchange):
    """Two managers simulating 2 processes over one shared directory."""
    mgrs = [
        CheckpointManager(
            str(tmp_path),
            fsync=False,
            process=i,
            n_processes=2,
            topology=dict(_TOPOLOGY),
            exchange=exchange,
        )
        for i in range(2)
    ]
    return mgrs[0], mgrs[1]


def _save_step(mgr0, mgr1, exchange, iteration):
    exchange.reset()
    # local row shards: p0 owns [it*10 .. it*10+4), p1 the next 4 rows
    base = float(iteration * 10)
    s1 = mgr1.save(_State(iteration, np.arange(4.0) + base + 4.0))
    s0 = mgr0.save(_State(iteration, np.arange(4.0) + base))
    assert s0 == s1  # sequence numbers agree across processes
    return s0


def test_two_phase_save_commits_shards_and_topology(tmp_path, run):
    ex = _FakeExchange()
    mgr0, mgr1 = _pair(tmp_path, ex)
    ckpt = _save_step(mgr0, mgr1, ex, iteration=0)
    names = sorted(os.listdir(ckpt))
    assert names == ["MANIFEST.json", "shard-p0.pkl", "shard-p1.pkl", "state.pkl"]
    with open(os.path.join(ckpt, "MANIFEST.json")) as f:
        manifest = json.load(f)
    assert [s["process"] for s in manifest["shards"]] == [0, 1]
    assert manifest["topology"] == {
        **_TOPOLOGY,
        "n_processes": 2,
        "global_rows": 8,
    }
    # restore re-concatenates the row shards in process order
    snap = CheckpointManager(str(tmp_path)).latest_valid()
    np.testing.assert_array_equal(snap.summed_scores, np.arange(8.0))
    assert counter_value(run, "photon_checkpoint_saves_total") == 1


def test_two_phase_torn_before_coordinator_phase_falls_back(tmp_path, run):
    """dist.commit tears process 0's phase-1 entry: peer 1's shard is on
    disk but no manifest ever lands — restore falls back one step."""
    ex = _FakeExchange()
    mgr0, mgr1 = _pair(tmp_path, ex)
    _save_step(mgr0, mgr1, ex, iteration=0)  # the consistent step
    ex.reset()
    faults.configure("dist.commit:io:2")
    mgr1.save(_State(1, np.arange(4.0) + 14.0))  # call 1: p1's shard lands
    with pytest.raises(InjectedIOError):
        mgr0.save(_State(1, np.arange(4.0) + 10.0))  # call 2: p0 dies
    torn = os.path.join(str(tmp_path), "ckpt-000001")
    assert os.path.exists(os.path.join(torn, "shard-p1.pkl"))
    assert not os.path.exists(os.path.join(torn, "MANIFEST.json"))
    snap = CheckpointManager(str(tmp_path)).latest_valid()
    assert snap.iteration == 0
    np.testing.assert_array_equal(snap.summed_scores, np.arange(8.0))
    assert counter_value(
        run, "photon_checkpoint_skipped_total", reason="corrupt"
    ) == 1


def test_two_phase_killed_at_commit_point_falls_back(tmp_path, run):
    """dist.commit kills the coordinator AFTER shards + payload are durable
    but before the manifest — the torn save must read as 'no checkpoint',
    exactly like a corrupt single-process one."""
    ex = _FakeExchange()
    mgr0, mgr1 = _pair(tmp_path, ex)
    _save_step(mgr0, mgr1, ex, iteration=0)
    ex.reset()
    faults.configure("dist.commit:kill:3")  # p1 phase-1, p0 phase-1, COMMIT
    mgr1.save(_State(1, np.arange(4.0) + 14.0))
    with pytest.raises(SimulatedKill):
        mgr0.save(_State(1, np.arange(4.0) + 10.0))
    torn = os.path.join(str(tmp_path), "ckpt-000001")
    assert os.path.exists(os.path.join(torn, "state.pkl"))
    assert os.path.exists(os.path.join(torn, "shard-p0.pkl"))
    assert not os.path.exists(os.path.join(torn, "MANIFEST.json"))
    snap = CheckpointManager(str(tmp_path)).latest_valid()
    assert snap.iteration == 0


def test_two_phase_corrupt_shard_digest_falls_back(tmp_path, run):
    ex = _FakeExchange()
    mgr0, mgr1 = _pair(tmp_path, ex)
    _save_step(mgr0, mgr1, ex, iteration=0)
    ckpt1 = _save_step(mgr0, mgr1, ex, iteration=1)
    # flip bytes inside the newest step's p1 shard: the manifest exists and
    # the payload digest passes, but the SHARD digest must catch it
    shard = os.path.join(ckpt1, "shard-p1.pkl")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(bytes(blob))
    snap = CheckpointManager(str(tmp_path)).latest_valid()
    assert snap.iteration == 0
    assert counter_value(
        run, "photon_checkpoint_skipped_total", reason="corrupt"
    ) == 1


def test_restore_topology_same_reshape_and_refusals(tmp_path, run):
    ex = _FakeExchange()
    mgr0, mgr1 = _pair(tmp_path, ex)
    _save_step(mgr0, mgr1, ex, iteration=0)
    reader = CheckpointManager(str(tmp_path))
    same = {**_TOPOLOGY, "n_processes": 2, "global_rows": 8}
    assert reader.latest_valid(expect_topology=same).iteration == 0
    # LEGAL reshape: process count changed, padded row totals agree — the
    # shards re-concatenate into the same global row order
    reshaped = {**_TOPOLOGY, "n_processes": 1, "global_rows": 8}
    snap = reader.latest_valid(expect_topology=reshaped)
    np.testing.assert_array_equal(snap.summed_scores, np.arange(8.0))
    # UNSOUND reshape: row totals disagree -> ledger-pinned refusal
    with pytest.raises(
        CheckpointIncompatibleError,
        match="the process count changed and no legal reshape exists",
    ):
        reader.latest_valid(
            expect_topology={**_TOPOLOGY, "n_processes": 4, "global_rows": 12}
        )
    # model-axis reshape -> refusal
    with pytest.raises(
        CheckpointIncompatibleError,
        match="mesh reshape across the model axis is not supported",
    ):
        reader.latest_valid(
            expect_topology={
                "mesh_axes": {"data": 4, "model": 2},
                "plan_fingerprint": "fp-aaaa",
                "n_processes": 2,
                "global_rows": 8,
            }
        )
    # changed execution plan -> refusal
    with pytest.raises(
        CheckpointIncompatibleError,
        match="changed execution plan is not supported",
    ):
        reader.latest_valid(
            expect_topology={**_TOPOLOGY,
                             "plan_fingerprint": "fp-bbbb",
                             "n_processes": 2, "global_rows": 8}
        )


def test_manager_validates_process_arguments(tmp_path):
    with pytest.raises(ValueError, match="n_processes must be >= 1"):
        CheckpointManager(str(tmp_path), n_processes=0)
    with pytest.raises(ValueError, match="process must be in"):
        CheckpointManager(str(tmp_path), process=2, n_processes=2)


# --------------------------------------------- planner topology unit checks


def test_check_checkpoint_topology_missing_keys_skip():
    # manifests that predate the protocol restore as before
    planner.check_checkpoint_topology({}, {"n_processes": 4, "global_rows": 9})
    planner.check_checkpoint_topology({"n_processes": 2}, {})
    # same process count: row totals are not even consulted
    planner.check_checkpoint_topology(
        {"n_processes": 2, "global_rows": 8},
        {"n_processes": 2, "global_rows": 10},
    )


def test_check_checkpoint_topology_legal_reshape():
    planner.check_checkpoint_topology(
        {"n_processes": 2, "global_rows": 8, "mesh_axes": {"data": 8}},
        {"n_processes": 4, "global_rows": 8, "mesh_axes": {"data": 8}},
    )


def test_check_checkpoint_topology_refuses_row_mismatch():
    with pytest.raises(
        PlanError,
        match="the process count changed and no legal reshape exists",
    ):
        planner.check_checkpoint_topology(
            {"n_processes": 2, "global_rows": 8},
            {"n_processes": 3, "global_rows": 9},
        )


def test_plan_fingerprint_is_topology_independent():
    """The fingerprint pins WHAT the model is (coordinates, layouts,
    dtypes, residency), never WHERE it runs — a legal mesh/process reshape
    keeps it, a changed coordinate configuration does not."""
    import dataclasses as dc

    @dc.dataclass
    class _Reg:
        reg_type: str = "L2"

    @dc.dataclass
    class _Cfg:
        variance_type: str = "NONE"
        down_sampling_rate: float = 1.0
        regularization: _Reg = dc.field(default_factory=_Reg)

    @dc.dataclass
    class _CC:
        name: str = "c0"
        feature_shard: str = "global"
        layout: str = "auto"
        feature_dtype: object = None
        hbm_budget_mb: object = None
        is_random_effect: bool = False
        config: _Cfg = dc.field(default_factory=_Cfg)
        normalization: object = None
        regularize_by_prior: bool = False

    def _fp(layout="auto", mesh=None, n_processes=1):
        plan = planner.resolve(
            [_CC(layout=layout)],
            mesh=mesh,
            n_processes=n_processes,
            distributed=n_processes > 1,
        )
        return planner.plan_fingerprint(plan)

    fp = _fp()
    assert fp == _fp() and len(fp) == 16  # stable digest
    # topology-independent: mesh and process count do not move it
    assert _fp(mesh={"data": 8, "model": 1}, n_processes=2) == fp
    # model-identity-dependent: a changed layout does
    assert _fp(layout="dense") != fp


# ------------------------------------------------------ CLI resume refusal


def _write_logistic_avro(tmp_path, n=64, d=4, seed=5):
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(x @ w)))).astype(int)
    recs = [
        {
            "label": float(y[i]),
            "features": [
                {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                for j in range(d)
            ],
        }
        for i in range(n)
    ]
    p = str(tmp_path / "train.avro")
    write_avro_file(p, TRAINING_EXAMPLE_AVRO, recs)
    return p


def test_cli_resume_refuses_unsound_process_count_change(tmp_path):
    """Satellite: ``train --resume`` against a checkpoint stamped with a
    different process count and disagreeing padded row totals must refuse
    with the typed topology error, not crash mid-sweep."""
    from photon_ml_tpu.cli import train

    data = _write_logistic_avro(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    common = [
        "--input-data", data,
        "--task", "logistic_regression",
        "--feature-shard", "name=global,bags=features",
        "--coordinate",
        "name=global,shard=global,optimizer=LBFGS,tolerance=1e-8,"
        "max.iter=30,reg.type=L2,reg.weights=1",
        "--coordinate-descent-iterations", "2",
        "--checkpoint-dir", ckpt,
        "--checkpoint-every", "1",
    ]
    train.run(common + ["--output-dir", str(tmp_path / "out1")])
    manifests = sorted(
        glob.glob(os.path.join(ckpt, "cd-boundaries", "ckpt-*", "MANIFEST.json"))
    )
    assert manifests, "checkpointed run left no boundary manifests"
    # forge the newest manifest's topology: written by a 2-process run whose
    # padded global row total disagrees with this (single-process) resume
    with open(manifests[-1]) as f:
        manifest = json.load(f)
    assert manifest["topology"]["n_processes"] == 1
    manifest["topology"]["n_processes"] = 2
    manifest["topology"]["global_rows"] = 999_999
    with open(manifests[-1], "w") as f:
        json.dump(manifest, f)
    with pytest.raises(
        CheckpointIncompatibleError,
        match="the process count changed and no legal reshape exists",
    ):
        train.run(
            common + ["--resume", "--output-dir", str(tmp_path / "out2")]
        )


def test_cli_distributed_flags_parse():
    from photon_ml_tpu.cli.train import build_parser

    args = build_parser().parse_args(
        [
            "--input-data", "in", "--output-dir", "out",
            "--collective-timeout", "30",
            "--heartbeat-interval", "0.5",
            "--heartbeat-timeout", "7",
        ]
    )
    assert args.collective_timeout == 30.0
    assert args.heartbeat_interval == 0.5
    assert args.heartbeat_timeout == 7.0
    defaults = build_parser().parse_args(["--input-data", "i", "--output-dir", "o"])
    assert defaults.collective_timeout == 60.0
    assert defaults.heartbeat_interval == 1.0
    assert defaults.heartbeat_timeout == 10.0


# ------------------------------------------------- the kill-a-worker drill


_DRILL_WORKER = """
import os
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.cli import train

try:
    train.run(sys.argv[1:])
    print("WORKER_OK", jax.process_index())
    sys.stdout.flush()
except BaseException as e:  # noqa: BLE001 - drill: report + hard-exit
    import traceback
    traceback.print_exc()
    print("WORKER_DIED %s: %s" % (type(e).__name__, e), file=sys.stderr)
    sys.stderr.flush()
    # hard exit: with a dead peer the graceful jax shutdown barrier would
    # block for its own timeout — the drill wants bounded-time death
    os._exit(70)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _drill_round(tmp_path, data, index_dir, ckpt, out, metrics_prefix,
                 extra=(), env_by_proc=None, timeout=420):
    env_base = {**os.environ, "PYTHONPATH": REPO}
    # 4 virtual CPU devices per process
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env_base.pop("PHOTON_FAULTS", None)
    port = _free_port()
    procs = []
    for i in range(2):
        env = dict(env_base)
        env.update((env_by_proc or {}).get(i, {}))
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-c", _DRILL_WORKER,
                    "--input-data", data,
                    "--feature-shard", "name=global,bags=features",
                    "--task", "logistic_regression",
                    "--coordinate",
                    "name=global,shard=global,optimizer=LBFGS,tolerance=1e-13,"
                    "max.iter=400,reg.type=L2,reg.weights=1",
                    "--coordinate-descent-iterations", "3",
                    "--feature-index-dir", index_dir,
                    "--checkpoint-dir", ckpt,
                    "--checkpoint-every", "1",
                    "--collective-timeout", "20",
                    "--heartbeat-interval", "0.5",
                    "--heartbeat-timeout", "6",
                    "--metrics-out", str(tmp_path / f"{metrics_prefix}-p{i}"),
                    "--output-dir", out,
                    "--mesh-shape", "data=8",
                    "--distributed",
                    f"coordinator=localhost:{port},process={i},n=2",
                    *extra,
                ],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out_s, err_s = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"drill round ({metrics_prefix}) timed out — "
                        "the liveness layer failed to bound the hang")
        outs.append((p.returncode, out_s, err_s))
    return outs


@pytest.mark.slow
def test_kill_a_worker_drill(tmp_path):
    """THE recovery drill (tentpole acceptance): worker 1 dies at its second
    sweep boundary; worker 0 fails with a typed DistributedTimeoutError
    within the collective budget and dumps a peer_lost postmortem; both
    relaunch with --resume from the last committed two-phase checkpoint and
    finish with the same model as an uninterrupted run."""
    from photon_ml_tpu.cli import index as index_cli
    from photon_ml_tpu.io.index_map import load_partitioned
    from photon_ml_tpu.io.model_io import load_game_model

    data = _write_logistic_avro(tmp_path, n=320, d=6, seed=7)
    index_dir = str(tmp_path / "index")
    index_cli.run(
        ["--input-data", data, "--feature-shard", "name=global,bags=features",
         "--output-dir", index_dir]
    )

    # uninterrupted 3-sweep reference
    out_ref = str(tmp_path / "out-ref")
    outs = _drill_round(
        tmp_path, data, index_dir, str(tmp_path / "ckpt-ref"), out_ref, "ref"
    )
    for rc, out_s, err_s in outs:
        assert rc == 0, f"reference worker failed:\n{out_s}\n{err_s}"
        assert "WORKER_OK" in out_s

    # faulted round: worker 1 killed at sweep boundary 2
    ckpt = str(tmp_path / "ckpt-drill")
    out_drill = str(tmp_path / "out-drill")
    t0 = time.monotonic()
    outs = _drill_round(
        tmp_path, data, index_dir, ckpt, out_drill, "drill",
        env_by_proc={1: {"PHOTON_FAULTS": "dist.collective:kill:2"}},
        timeout=300,
    )
    wall = time.monotonic() - t0
    (rc0, out0, err0), (rc1, out1, err1) = outs
    assert rc1 == 70 and "WORKER_DIED SimulatedKill" in err1, (out1, err1)
    # the survivor's failure is TYPED and BOUNDED, not a hang
    assert rc0 == 70, (out0, err0)
    assert "WORKER_DIED DistributedTimeoutError" in err0, err0
    assert "a peer process never arrived" in err0, err0
    assert wall < 240, f"detection not bounded: {wall:.0f}s"
    # the survivor (coordinator) dumped the peer_lost postmortem
    dumps = glob.glob(
        os.path.join(str(tmp_path / "drill-p0"), "flight", "flight-peer_lost-*.json")
    )
    assert dumps, "no peer_lost flight-recorder dump on the survivor"
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["trigger"]["kind"] == "peer_lost"
    # a committed two-phase boundary checkpoint exists to resume from
    manifests = glob.glob(
        os.path.join(ckpt, "cd-boundaries", "ckpt-*", "MANIFEST.json")
    )
    assert manifests, "no committed checkpoint before the kill"
    with open(sorted(manifests)[-1]) as f:
        manifest = json.load(f)
    assert manifest["topology"]["n_processes"] == 2
    assert len(manifest["shards"]) == 2

    # recovery: relaunch BOTH processes with --resume; the run finishes the
    # remaining sweep from the committed checkpoint
    outs = _drill_round(
        tmp_path, data, index_dir, ckpt, out_drill, "resume",
        extra=("--resume",),
    )
    for rc, out_s, err_s in outs:
        assert rc == 0, f"resume worker failed:\n{out_s}\n{err_s}"
        assert "WORKER_OK" in out_s
    assert any(
        "resuming from checkpoint" in err_s for _, _, err_s in outs
    ), "resume round did not actually restore a checkpoint"

    # parity: resumed final model vs the uninterrupted reference (x64,
    # tightly converged LBFGS: agreement is at solver-noise scale)
    imaps = {"global": load_partitioned(index_dir, "global")}
    w_resumed = np.asarray(
        load_game_model(
            os.path.join(out_drill, "models", "best"), imaps,
            task="logistic_regression",
        ).models["global"].model.coefficients.means
    )
    w_ref = np.asarray(
        load_game_model(
            os.path.join(out_ref, "models", "best"), imaps,
            task="logistic_regression",
        ).models["global"].model.coefficients.means
    )
    np.testing.assert_allclose(w_resumed, w_ref, rtol=1e-9, atol=1e-9)
