"""Sweep pipelining (game/pipeline.py + utils/futures.PrefetchQueue): depth
>= 2 must be a pure latency optimization. Accepted models, the accept/reject
ledger, the evaluation ledger, and checkpoint boundary states are pinned
BIT-identical to the serial depth-1 loop — including under an injected NaN
storm and a kill-and-resume across a pipelined boundary."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.evaluation import build_suite
from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectCoordinate,
    GLMOptimizationConfig,
    RandomEffectCoordinate,
    ValidationContext,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
    pipeline,
)
from photon_ml_tpu.obs import interval_overlap_seconds, overlap_ratio
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.robust import CheckpointManager, SimulatedKill, faults
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset
from photon_ml_tpu.utils.futures import PrefetchQueue, WorkerPool


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.clear()


@pytest.fixture
def run():
    r = obs.RunTelemetry()
    with obs.use_run(r):
        yield r


# ------------------------------------------------------------ PrefetchQueue


def test_prefetch_queue_orders_and_exhausts():
    q = PrefetchQueue(lambda i: i * i, count=5, depth=2)
    assert [q.get() for _ in range(5)] == [(i, i * i) for i in range(5)]
    with pytest.raises(RuntimeError, match="exhausted"):
        q.get()
    q.close()
    with pytest.raises(RuntimeError, match="closed"):
        q.get()


def test_prefetch_queue_budget_bounds_inflight():
    """Byte-budgeted staging never exceeds the serial double buffer's
    2-resident worst case (held item + one staged), even at depth 4."""
    q = PrefetchQueue(
        lambda i: i, count=8, depth=4, cost=lambda i: 10, budget=15
    )
    assert [q.get()[0] for _ in range(8)] == list(range(8))
    # queue-empty always admits one item (progress guarantee), so the peak
    # is held + one staged = 20 — never depth * cost = 40
    assert q.peak_inflight <= 20
    q.close()


def test_prefetch_queue_deep_when_budget_allows():
    q = PrefetchQueue(
        lambda i: i, count=6, depth=3, cost=lambda i: 10, budget=1000
    )
    time.sleep(0.05)  # let the worker run ahead
    assert q.qsize() == 3  # bounded by depth, not budget
    assert [q.get()[0] for _ in range(6)] == list(range(6))
    q.close()


def test_prefetch_queue_cyclic_wraps():
    q = PrefetchQueue(lambda i: i, count=3, depth=2, cyclic=True)
    assert [q.get()[0] for _ in range(7)] == [0, 1, 2, 0, 1, 2, 0]
    q.close()


def test_prefetch_queue_reraises_producer_error_in_order():
    def produce(i):
        if i == 2:
            raise ValueError("boom at 2")
        return i

    q = PrefetchQueue(produce, count=5, depth=2)
    assert q.get() == (0, 0)
    assert q.get() == (1, 1)
    with pytest.raises(ValueError, match="boom at 2"):
        q.get()
    with pytest.raises(RuntimeError, match="closed"):
        q.get()


def test_prefetch_queue_validates_args():
    with pytest.raises(ValueError, match="depth"):
        PrefetchQueue(lambda i: i, count=1, depth=0)
    with pytest.raises(ValueError, match="count"):
        PrefetchQueue(lambda i: i, count=0)
    with pytest.raises(ValueError, match="workers"):
        PrefetchQueue(lambda i: i, count=1, workers=0)


# ------------------------------------------------- WorkerPool / pooled queue


def test_worker_pool_futures_and_drain_on_close():
    pool = WorkerPool(2, name="t-pool")
    futs = [pool.submit(lambda k=k: k * k) for k in range(5)]
    pool.close()  # stop accepting; already-queued tasks still drain
    assert [f.result(timeout=5) for f in futs] == [0, 1, 4, 9, 16]
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit(lambda: None)

    def _boom():
        raise ValueError("pool boom")

    err_pool = WorkerPool(1)
    f = err_pool.submit(_boom)
    assert f.done() or not f.done()  # done() never raises
    with pytest.raises(ValueError, match="pool boom"):
        f.result(timeout=5)
    err_pool.close()
    with pytest.raises(ValueError, match="pool size"):
        WorkerPool(0)


def test_prefetch_queue_pooled_emits_in_order():
    """N workers decode concurrently; the sequencer re-emits results in
    production order — identical output to the single-worker queue even
    when later items finish first."""

    def produce(i):
        time.sleep(0.002 * ((7 - i) % 4))  # later items often finish first
        return i * 7

    q = PrefetchQueue(produce, count=12, depth=6, workers=4)
    assert [q.get() for _ in range(12)] == [(i, i * 7) for i in range(12)]
    with pytest.raises(RuntimeError, match="exhausted"):
        q.get()
    q.close()


def test_prefetch_queue_pooled_error_reraises_in_order():
    """A mid-sequence producer error re-raises at ITS turn: earlier items
    still emit, later items (possibly already decoded on other workers)
    are discarded, never emitted past the error."""

    def produce(i):
        if i == 3:
            raise ValueError("boom at 3")
        time.sleep(0.002 * (8 - i))
        return i

    q = PrefetchQueue(produce, count=8, depth=8, workers=4)
    assert [q.get()[1] for _ in range(3)] == [0, 1, 2]
    with pytest.raises(ValueError, match="boom at 3"):
        q.get()
    with pytest.raises(RuntimeError, match="closed"):
        q.get()


def test_prefetch_queue_budget_counts_producing_items():
    """Satellite pin: the budget charges an item when its index is CLAIMED
    (before produce starts), not when it lands in the queue — so N workers
    cannot collectively overshoot a bounded-RSS cap by starting N decodes
    at once. With cost=10 and budget=15, at most ONE produce may ever be in
    flight (the empty-pipeline progress admission), whatever the worker
    count, and the peak stays at the 2-resident worst case."""
    lock = threading.Lock()
    active = 0
    peak_active = 0

    def produce(i):
        nonlocal active, peak_active
        with lock:
            active += 1
            peak_active = max(peak_active, active)
        time.sleep(0.01)
        with lock:
            active -= 1
        return i

    q = PrefetchQueue(
        produce, count=6, depth=4, cost=lambda i: 10, budget=15, workers=3
    )
    assert [q.get()[0] for _ in range(6)] == list(range(6))
    assert peak_active == 1  # producing items count toward the budget
    assert q.peak_inflight <= 20  # held + the one in flight
    assert q.budget_stalls > 0  # the deferred admissions were observed
    q.close()


def test_prefetch_queue_shared_pool_not_closed():
    """A queue built on an externally-owned pool must not close it."""
    pool = WorkerPool(2, name="t-shared")
    q = PrefetchQueue(lambda i: i, count=4, depth=2, pool=pool)
    assert [q.get()[0] for _ in range(4)] == list(range(4))
    q.close()
    f = pool.submit(lambda: 11)  # still accepting: close() was the queue's
    assert f.result(timeout=5) == 11
    pool.close()


# ----------------------------------------------------------------- EvalLane


def test_eval_lane_drains_in_submit_order():
    def fn(snapshot):
        # later submissions finish their "work" faster — order must hold
        time.sleep(0.02 / (snapshot["k"] + 1))
        return snapshot["k"] * 10

    lane = pipeline.EvalLane(fn, capacity=3)
    for k in range(3):
        lane.submit(0, f"c{k}", {"k": k})
    out = lane.drain_all()
    assert out == [(0, "c0", 0), (0, "c1", 10), (0, "c2", 20)]
    lane.close()


def test_eval_lane_reraises_worker_error_at_drain():
    def fn(snapshot):
        if snapshot["k"] == 1:
            raise RuntimeError("eval exploded")
        return snapshot["k"]

    lane = pipeline.EvalLane(fn, capacity=2)
    lane.submit(0, "a", {"k": 0})
    lane.submit(0, "b", {"k": 1})
    with pytest.raises(RuntimeError, match="eval exploded"):
        lane.drain_all()
    lane.close()


def test_eval_lane_validates_capacity():
    with pytest.raises(ValueError, match="capacity"):
        pipeline.EvalLane(lambda s: s, capacity=0)


# ------------------------------------------------------- context plumbing


def test_pipelined_context_scopes_depth():
    assert pipeline.active_depth() == 1
    assert pipeline.stage_anchor() is None
    with pipeline.pipelined(3):
        assert pipeline.active_depth() == 3
        with pipeline.pipelined(2):
            assert pipeline.active_depth() == 2
        assert pipeline.active_depth() == 3
    assert pipeline.active_depth() == 1
    with pytest.raises(ValueError, match="depth"):
        with pipeline.pipelined(0):
            pass


def test_pipelined_context_carries_anchor():
    with obs.span("cd.sweep", iteration=0) as sweep:
        with pipeline.pipelined(2, anchor=sweep):
            assert pipeline.stage_anchor() is sweep


# -------------------------------------------------------- overlap helpers


def test_interval_overlap_seconds():
    assert interval_overlap_seconds([(0.0, 1.0)], [(2.0, 3.0)]) == 0.0
    assert interval_overlap_seconds([(0.0, 2.0)], [(1.0, 3.0)]) == pytest.approx(1.0)
    # touching endpoints merge in the union -> zero genuine overlap
    assert interval_overlap_seconds([(0.0, 1.0)], [(1.0, 2.0)]) == 0.0
    assert interval_overlap_seconds([], [(0.0, 1.0)]) == 0.0


def test_overlap_ratio():
    assert overlap_ratio([], [(0.0, 1.0)]) == 0.0
    assert overlap_ratio([(0.0, 2.0)], [(1.0, 3.0)]) == pytest.approx(0.5)
    assert overlap_ratio([(0.0, 1.0)], [(0.0, 1.0)]) == pytest.approx(1.0)
    # serial double buffer: stage strictly precedes collect
    assert overlap_ratio([(0.0, 1.0)], [(1.0, 2.0)]) == 0.0


# ----------------------------------------------- CD depth-2 bit identity


def _cfg(l2=1.0):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType("LBFGS"), tolerance=1e-9, max_iterations=100
        ),
        regularization=RegularizationContext("L2"),
        reg_weight=l2,
    )


@pytest.fixture(scope="module")
def cd_factory():
    data = generate_mixed_effect_data(
        n=400, d_fixed=5, re_specs={"userId": (12, 3)}, seed=3
    )
    raw = mixed_data_to_raw_dataset(data)

    def make():
        fe_ds = build_fixed_effect_dataset(raw, "global", "global", dtype=jnp.float64)
        re_ds = build_random_effect_dataset(
            raw, "per-user", "userShard", "userId", dtype=jnp.float64
        )
        coords = {
            "global": FixedEffectCoordinate(
                dataset=fe_ds, task="logistic_regression", config=_cfg()
            ),
            "per-user": RandomEffectCoordinate(
                dataset=re_ds, task="logistic_regression", config=_cfg()
            ),
        }
        validation = ValidationContext(
            suite=build_suite(["LOGISTIC_LOSS"], raw.labels),
            score_fns={n: coords[n].score for n in coords},
            offsets=raw.offsets,
        )
        return coords, validation

    return make


def _final_score_bits(coords, result):
    return {
        name: np.asarray(coords[name].score(result.model[name]))
        for name in coords
    }


def _assert_bit_identical(coords, ref, other):
    bits_ref = _final_score_bits(coords, ref)
    bits_other = _final_score_bits(coords, other)
    for name in coords:
        np.testing.assert_array_equal(bits_ref[name], bits_other[name])
    assert [n for n, _ in ref.evaluations] == [n for n, _ in other.evaluations]
    for (_, r1), (_, r2) in zip(ref.evaluations, other.evaluations):
        assert r1.primary_metric == r2.primary_metric


def test_depth2_bit_identical_models_and_ledger(cd_factory):
    """The tentpole guarantee: depth 2 produces the exact bits of depth 1 —
    final per-coordinate scores, the evaluation ledger, and the best
    evaluation — while evals ran on a background lane."""
    coords1, val1 = cd_factory()
    ref = CoordinateDescent(coords1, n_iterations=2, validation=val1).run()
    coords2, val2 = cd_factory()
    piped = CoordinateDescent(
        coords2, n_iterations=2, validation=val2, pipeline_depth=2
    ).run()
    _assert_bit_identical(coords1, ref, piped)
    assert ref.best_evaluation.primary_metric == piped.best_evaluation.primary_metric


def test_depth2_eval_lane_runs_off_main_thread(cd_factory, run):
    """The overlap is real, not cosmetic: at depth 2 the cd.eval spans run on
    the eval-lane worker thread, parented on the sweep span (so the timeline
    attributes them as outermost phase spans)."""
    from photon_ml_tpu.obs.timeline import TimelineRecorder

    rec = TimelineRecorder()
    run.register_listener(rec)
    coords, val = cd_factory()
    CoordinateDescent(
        coords, n_iterations=2, validation=val, pipeline_depth=2
    ).run()
    evals = [s for s in rec.spans() if s.name == "cd.eval"]
    assert evals, "no cd.eval spans recorded"
    assert all(s.thread_name.startswith("photon-eval") for s in evals)
    main = threading.main_thread().ident
    assert all(s.thread_id != main for s in evals)
    # parented on the sweep span -> outermost phase spans for attribution
    sweeps = {s.span_id for s in rec.spans() if s.name == "cd.sweep"}
    assert all(s.parent_id in sweeps for s in evals)


def test_depth_validation():
    with pytest.raises(ValueError, match="pipeline_depth"):
        CoordinateDescent({"x": object()}, pipeline_depth=0)


def test_depth2_nan_storm_ledger_matches(cd_factory):
    """Injected NaN storm (2 consecutive corrupted score updates): the
    rejection counters and final bits match depth 1 exactly — the
    speculative summed-score dispatch never leaks a rejected candidate."""
    results = {}
    rejections = {}
    for depth in (1, 2):
        coords, val = cd_factory()
        r = obs.RunTelemetry()
        faults.configure("coordinate.scores:nan:1x2")
        with obs.use_run(r):
            results[depth] = CoordinateDescent(
                coords, n_iterations=2, validation=val, pipeline_depth=depth
            ).run()
        faults.clear()
        rejections[depth] = {
            name: r.registry.counter(
                "photon_coordinate_rejections_total", ""
            ).labels(coordinate=name).value
            for name in coords
        }
        results[f"coords{depth}"] = coords
    assert rejections[1] == rejections[2]
    assert sum(rejections[1].values()) == 2
    _assert_bit_identical(results["coords1"], results[1], results[2])


def test_depth2_boundary_states_match_serial(cd_factory, tmp_path):
    """Checkpoint manifests across a pipelined sweep: the boundary states a
    depth-2 run hands to the checkpointer carry the same models, summed
    scores, evaluation ledger, and train losses as depth 1 (the eval lane
    drains before every boundary)."""
    snaps = {}
    for depth in (1, 2):
        coords, val = cd_factory()
        mgr = CheckpointManager(str(tmp_path / f"d{depth}"), keep_last=10, fsync=False)
        CoordinateDescent(
            coords, n_iterations=2, validation=val,
            boundary_fn=mgr.on_boundary, pipeline_depth=depth,
        ).run()
        assert len(mgr.checkpoints()) == 4  # 2 sweeps x 2 coordinates
        snaps[depth] = mgr.latest_valid(
            expect_coordinate_order=list(coords), expect_n_iterations=2
        )
    s1, s2 = snaps[1], snaps[2]
    assert (s1.iteration, s1.coordinate_index) == (s2.iteration, s2.coordinate_index)
    np.testing.assert_array_equal(
        np.asarray(s1.summed_scores), np.asarray(s2.summed_scores)
    )
    assert [n for n, _ in s1.evaluations] == [n for n, _ in s2.evaluations]
    for (_, r1), (_, r2) in zip(s1.evaluations, s2.evaluations):
        assert r1.primary_metric == r2.primary_metric
    assert s1.train_losses == s2.train_losses


def test_depth2_kill_and_resume_across_pipelined_boundary(cd_factory, tmp_path):
    """Kill the process right after the 2nd boundary save of a DEPTH-2 run
    (mid-sweep, with an eval potentially in flight), resume at depth 2, and
    the result matches the uninterrupted depth-1 run bit-for-bit."""
    coords, val = cd_factory()
    ref = CoordinateDescent(coords, n_iterations=2, validation=val).run()

    ckpt_dir = str(tmp_path / "ck")
    coords2, val2 = cd_factory()
    mgr = CheckpointManager(ckpt_dir, fsync=False)
    faults.configure("cd.boundary_saved:kill:2")
    with pytest.raises(SimulatedKill):
        CoordinateDescent(
            coords2, n_iterations=2, validation=val2,
            boundary_fn=mgr.on_boundary, pipeline_depth=2,
        ).run()
    faults.clear()

    snap = CheckpointManager(ckpt_dir, fsync=False).latest_valid(
        expect_coordinate_order=list(coords2), expect_n_iterations=2
    )
    assert snap is not None
    assert (snap.iteration, snap.coordinate_index) == (0, 1)
    coords3, val3 = cd_factory()
    resumed = CoordinateDescent(
        coords3, n_iterations=2, validation=val3,
        resume_state=snap, pipeline_depth=2,
    ).run()
    _assert_bit_identical(coords, ref, resumed)
