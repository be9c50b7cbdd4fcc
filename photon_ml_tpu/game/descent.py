"""Coordinate descent over GAME coordinates with residual score exchange.

Reference: photon-lib .../algorithm/CoordinateDescent.scala:43-670 — the outer
loop trains each coordinate against the residual of all others, maintains the
summed scores incrementally (summedScores - oldScores + newScores, :441-446),
evaluates on validation data after every coordinate update, and tracks the
best model seen by the primary validation metric (:607-622). Locked
coordinates (partial retraining) are fetched, never trained (:280-300), and
the invariant checks of checkInvariants:71-92 are enforced up front.

Scores here are plain device arrays in fixed sample order, so the reference's
fullOuterJoin RDD arithmetic is elementwise adds (SURVEY.md §2.1 P7).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.transfer import allow_transfers, logged_fetch, transfer_guard
from ..robust import distributed as robust_dist
from ..robust import faults
from ..evaluation.suite import EvaluationResults, EvaluationSuite
from ..models.game import GameModel
from ..optimize.trackers import build_tracker, record_tracker_metrics
from . import pipeline
from .coordinate import Coordinate, ModelCoordinate

logger = logging.getLogger("photon_ml_tpu")


def _process_count() -> int:
    """Process count without requiring an initialized backend (host-only
    callers — planner dry runs, unit tests with jax stubbed out — see 1)."""
    try:
        import jax

        return jax.process_count()
    except Exception:  # photon: ignore[R4] - no-jax fallback, single process
        return 1


def _local_devices():
    """Device handles for memory sampling; empty when the backend is not up
    (sampling then covers host RSS only)."""
    try:
        import jax

        return jax.local_devices()
    except Exception:  # photon: ignore[R4] - no-jax fallback, host-only sample
        return ()


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    evaluations: List[Tuple[str, EvaluationResults]]  # (coordinate, results) per update
    best_evaluation: Optional[EvaluationResults]
    # coordinate -> Fixed/RandomEffectOptimizationTracker (raw SolverResult on
    # tracker.result)
    trackers: Dict[str, object]


@dataclasses.dataclass
class CDBoundaryState:
    """Everything the outer loop knows at a coordinate-update boundary — the
    unit a crash-safe checkpoint persists (robust.checkpoint) and a resumed
    run restores. Between coordinate updates the entire algorithm state is
    these few values; mid-update there is no consistent host-visible state,
    which is why boundaries are the only snapshot points."""

    iteration: int  # sweep index of the update just finished
    coordinate_index: int  # position in ``coordinate_order`` just finished
    coordinate: str
    coordinate_order: List[str]
    n_iterations: int
    models: Dict[str, object]
    summed_scores: jnp.ndarray
    best_eval: Optional[EvaluationResults]
    best_models: Dict[str, object]
    evaluations: List[Tuple[str, EvaluationResults]]
    trackers: Dict[str, object]
    # last ACCEPTED total train loss per coordinate — the divergence guard's
    # regression baseline; persisted so a resumed run rejects exactly the
    # updates the uninterrupted run would have rejected
    train_losses: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ValidationContext:
    """Validation-side scoring: per-coordinate score fn over the validation set."""

    suite: EvaluationSuite
    score_fns: Mapping[str, object]  # coordinate -> (model -> scores f[n_val])
    offsets: np.ndarray  # base offsets of validation rows


class CoordinateDescent:
    """Train GAME coordinates by block coordinate descent."""

    def __init__(
        self,
        coordinates: Mapping[str, Coordinate],  # ordered
        n_iterations: int = 1,
        validation: Optional[ValidationContext] = None,
        checkpoint_fn: Optional[object] = None,
        validation_frequency: str = "COORDINATE",
        boundary_fn: Optional[object] = None,
        resume_state: Optional[object] = None,
        divergence_guard: bool = True,
        rejection_tolerance: Optional[float] = None,
        pipeline_depth: int = 1,
    ):
        """``checkpoint_fn(iteration, models)`` runs after each completed
        sweep (crash recovery for long runs: resume = warm-start from the
        checkpointed models with the remaining iterations; the score state
        reconstructs exactly from the models).

        ``boundary_fn(state: CDBoundaryState)`` runs after EVERY coordinate
        update — finer-grained crash recovery than ``checkpoint_fn``
        (robust.CheckpointManager.on_boundary is the intended callee). It is
        invoked inside :func:`allow_transfers`, so serializers may fetch
        device arrays freely; the surrounding sweep stays transfer-guarded.

        ``resume_state``: a restored boundary state (duck type:
        robust.CheckpointSnapshot — iteration / coordinate_index / models /
        summed_scores / best_eval / best_models / evaluations). ``run``
        then continues from the update AFTER the snapshot: per-coordinate
        scores re-derive from the restored models (deterministic re-score),
        the summed scores restore exactly from the snapshot, and best-model
        tracking continues rather than restarting. ``initial_models`` passed
        to :meth:`run` are ignored on resume — the snapshot already embeds
        the warm-start lineage. Trackers restart empty (their summaries are
        checkpointed as strings, not as resumable solver state).

        ``validation_frequency``: 'COORDINATE' evaluates after every
        coordinate update (reference semantics, CoordinateDescent.scala:
        312-333); 'SWEEP' evaluates once per full sweep — same best-model
        tracking at 1/n_coordinates of the metric cost (round-4 verdict
        item 5: per-update host metrics dominate large sweeps).

        ``divergence_guard``: reject a coordinate update whose new scores or
        total train loss are non-finite — the previous (model, scores) stand,
        ``summed`` is never poisoned, and the sweep continues (counted in
        ``photon_coordinate_rejections_total{coordinate=}``). Costs one
        scalar :func:`logged_fetch` per update; False restores the strictly
        zero-fetch sweep. ``rejection_tolerance``: additionally reject when
        the update's train loss regresses more than this above the
        coordinate's last accepted loss (None — the default — disables the
        regression check; divergence rejection is purely about finiteness).

        ``pipeline_depth``: async-dispatch lookahead across the three sweep
        lanes (host staging, device solve, device score/eval). Depth 1 (the
        default) is exactly the serial loop. Depth >= 2 dispatches the
        accepted-score sum before the divergence guard's fetch, runs
        validation evaluations on a background lane (up to ``depth - 1`` in
        flight), and lets the streaming layers prefetch their next slice
        while a solve is in flight — all drained back in submit order, so
        accepted bits, the accept/reject ledger, and every boundary state
        handed to ``boundary_fn`` are identical to depth 1."""
        if not coordinates:
            raise ValueError("CoordinateDescent needs at least one coordinate")
        if n_iterations < 1:
            raise ValueError(f"n_iterations must be >= 1: {n_iterations}")
        # checkInvariants (CoordinateDescent.scala:71-92): locked coordinates
        # must not be retrained; with a single coordinate multiple iterations
        # are pointless (reference logs a warning).
        if validation_frequency not in ("COORDINATE", "SWEEP"):
            raise ValueError(
                f"validation_frequency must be COORDINATE or SWEEP: "
                f"{validation_frequency!r}"
            )
        if rejection_tolerance is not None and rejection_tolerance < 0:
            raise ValueError(
                f"rejection_tolerance must be >= 0: {rejection_tolerance}"
            )
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1: {pipeline_depth}")
        self.coordinates = dict(coordinates)
        self.order = list(coordinates)
        self.n_iterations = n_iterations
        self.validation = validation
        self.checkpoint_fn = checkpoint_fn
        self.validation_frequency = validation_frequency
        self.boundary_fn = boundary_fn
        self.resume_state = resume_state
        self.divergence_guard = divergence_guard
        self.rejection_tolerance = rejection_tolerance
        self.pipeline_depth = int(pipeline_depth)
        n_trainable = sum(
            0 if isinstance(c, ModelCoordinate) else 1 for c in self.coordinates.values()
        )
        if n_trainable == 0:
            raise ValueError("all coordinates are locked; nothing to train")
        if len(self.order) == 1 and n_iterations > 1:
            logger.warning(
                "single-coordinate descent with %d iterations is wasteful", n_iterations
            )

    def run(
        self, initial_models: Optional[Mapping[str, object]] = None
    ) -> CoordinateDescentResult:
        initial_models = dict(initial_models or {})
        coords = self.coordinates
        n = next(iter(coords.values())).n_rows
        for c in coords.values():
            if c.n_rows != n:
                raise ValueError(
                    f"coordinate {c.coordinate_id} has {c.n_rows} rows, expected {n}"
                )

        models: Dict[str, object] = {}
        trackers: Dict[str, object] = {}
        scores: Dict[str, jnp.ndarray] = {}
        train_losses: Dict[str, float] = {}
        start_it = 0
        start_idx = 0
        resume = self.resume_state
        # entry to the first sweep: initial (or restored) scores and their sum
        with obs.span("cd.init"):
            if resume is not None:
                # restore the boundary state exactly: models come back verbatim,
                # per-coordinate scores re-derive from them (deterministic XLA →
                # bit-identical to what the dead process held), and the summed
                # scores restore from the snapshot so the incremental arithmetic
                # (summed - own + new) continues on the same values it would have
                # had uninterrupted
                models = dict(resume.models)
                for name in self.order:
                    if name in models:
                        scores[name] = coords[name].score(models[name])
                summed = jnp.asarray(resume.summed_scores)
                evaluations = list(resume.evaluations)
                best_eval = resume.best_eval
                best_models = dict(resume.best_models)
                # older snapshots predate the divergence guard's regression
                # ledger — resume with an empty one (first accepted update of
                # each coordinate re-seeds it)
                train_losses = dict(getattr(resume, "train_losses", None) or {})
                start_it = int(resume.iteration)
                start_idx = int(resume.coordinate_index) + 1
                if start_idx >= len(self.order):
                    start_it += 1
                    start_idx = 0
            else:
                # initialize scores from warm-start models where available
                for name in self.order:
                    if name in initial_models:
                        models[name] = initial_models[name]
                        scores[name] = coords[name].score(initial_models[name])
                zero = jnp.zeros((n,), jnp.float32)
                summed = sum(scores.values(), zero)

                evaluations = []
                best_eval = None
                best_models = dict(models)

        for it in range(start_it, self.n_iterations):
            first = start_idx if it == start_it else 0
            with obs.span(
                "cd.sweep", iteration=it, pipeline_depth=self.pipeline_depth
            ) as sweep_span:
                # background eval lane (depth >= 2, per-coordinate
                # validation): coordinate k's eval overlaps coordinate k+1's
                # solve; results drain in submit order, so the evaluation
                # ledger and best-model choices are the serial loop's
                lane = None
                lane_snaps: collections.deque = collections.deque()
                if (
                    self.pipeline_depth > 1
                    and self.validation is not None
                    and self.validation_frequency == "COORDINATE"
                    and _process_count() == 1
                ):
                    # multi-process runs keep validation eval on the main
                    # thread: every process must enqueue device computations
                    # (and any collectives hiding in sharded score fns) in
                    # the SAME order, and a background eval thread interleaves
                    # its dispatches nondeterministically against the solve
                    # stream — a cross-host ordering mismatch is a deadlock.
                    # Depth >= 2 still pipelines the score-sum dispatch ahead
                    # of the guard fetch and the streaming slice prefetch.
                    lane = pipeline.EvalLane(
                        self._evaluate,
                        capacity=self.pipeline_depth - 1,
                        anchor=sweep_span,
                    )

                def _absorb(drained):
                    nonlocal best_eval, best_models
                    for eit, ename, res in drained:
                        best_eval, best_models = self._absorb_eval(
                            eit,
                            ename,
                            res,
                            lane_snaps.popleft(),
                            evaluations,
                            best_eval,
                            best_models,
                        )

                # zero-fetch invariant, runtime-enforced: inside the sweep
                # every device->host transfer must be an explicit
                # jax.device_get (logged_fetch) — an implicit fetch
                # (float(arr), np.asarray(arr), arr.item()) raises instead of
                # silently stalling the device pipeline. The static half of
                # this contract is photon_ml_tpu.analysis rule R1.
                with pipeline.pipelined(
                    self.pipeline_depth, anchor=sweep_span
                ), pipeline.closing(lane), transfer_guard():
                    for idx in range(first, len(self.order)):
                        name = self.order[idx]
                        coordinate = coords[name]
                        own = scores.get(name)
                        residual = summed - own if own is not None else summed

                        # current-position board for /statusz scrapes: cheap
                        # host dict writes, live even with no sink registered
                        obs.current_run().status.update(
                            sweep=it,
                            n_sweeps=self.n_iterations,
                            coordinate=name,
                            coordinate_index=idx,
                        )
                        with obs.span("cd.coordinate", iteration=it, coordinate=name):
                            with obs.span(
                                "cd.train",
                                iteration=it,
                                coordinate=name,
                                phase="solve",
                            ) as train_span:
                                model, solver_result = coordinate.train(
                                    residual, initial_model=models.get(name)
                                )
                            logger.debug(
                                "cd iter %d coordinate %s: train took %.3fs",
                                it,
                                name,
                                train_span.duration_s,
                            )
                            tracker = build_tracker(coordinate, solver_result)
                            if tracker is not None:
                                trackers[name] = tracker
                                # logOptimizationSummary (CoordinateDescent.scala:
                                # 230-248): per-coordinate convergence histogram /
                                # iteration stats. Gated: both the summary string
                                # and the metrics recording FETCH device arrays (a
                                # pipeline stall per fetch); with INFO disabled
                                # and no telemetry sink the sweep stays fetch-free
                                if logger.isEnabledFor(logging.INFO):
                                    logger.info(
                                        "cd iter %d coordinate %s optimization "
                                        "summary:\n%s",
                                        it,
                                        name,
                                        tracker.to_summary_string(),
                                    )
                                if obs.active():
                                    # exists only with a sink: the metrics'
                                    # own cost (their fetches), made visible
                                    with obs.span("cd.tracker", coordinate=name):
                                        record_tracker_metrics(
                                            obs.current_run().registry, name, tracker
                                        )

                            with obs.span(
                                "cd.score",
                                iteration=it,
                                coordinate=name,
                                phase="score",
                            ) as score_span:
                                new_scores = coordinate.score(model)
                            logger.debug(
                                "cd iter %d coordinate %s: score took %.3fs",
                                it,
                                name,
                                score_span.duration_s,
                            )
                            if faults.active():
                                # fault site coordinate.scores: the schedule
                                # decision is host-side (eager, never traced)
                                # and the planting is a pure device scatter —
                                # legal under the sweep's transfer guard
                                new_scores = faults.corrupt(
                                    "coordinate.scores", new_scores
                                )
                            # depth >= 2: dispatch the accepted-score sum
                            # BEFORE the guard's blocking fetch — async
                            # dispatch queues the add behind the scores, the
                            # fetch overlaps it, and a rejection simply drops
                            # the candidate (models/scores/summed untouched,
                            # same op and operands as the serial add →
                            # bit-identical on accept)
                            candidate = (
                                residual + new_scores
                                if self.pipeline_depth > 1
                                else None
                            )
                            accepted, train_loss = True, None
                            if self.divergence_guard:
                                # the one blocking fetch of an update: where
                                # the host waits for the device when no sink
                                # is attached
                                with obs.span("cd.guard", coordinate=name):
                                    accepted, train_loss = self._guard(
                                        name, new_scores, solver_result, train_losses
                                    )
                            if accepted:
                                models[name] = model
                                # summedScores - oldScores + newScores (:441-446)
                                summed = (
                                    candidate
                                    if candidate is not None
                                    else residual + new_scores
                                )
                                scores[name] = new_scores
                                if train_loss is not None:
                                    train_losses[name] = train_loss
                                    # cheap host registry write (the loss
                                    # already traveled in the guard's fetch):
                                    # per-sweep JSONL flushes turn this gauge
                                    # into the accepted-loss trajectory the
                                    # post-hoc report plots
                                    obs.current_run().registry.gauge(
                                        "photon_cd_accepted_loss",
                                        "last accepted total train loss per "
                                        "coordinate",
                                    ).labels(coordinate=name).set(train_loss)
                                    obs.current_run().status.update(
                                        accepted_losses={
                                            k: float(v)
                                            for k, v in train_losses.items()
                                        }
                                    )

                                if (
                                    self.validation is not None
                                    and self.validation_frequency == "COORDINATE"
                                ):
                                    if lane is not None:
                                        snapshot = dict(models)
                                        lane_snaps.append(snapshot)
                                        lane.submit(it, name, snapshot)
                                        _absorb(lane.drain_ready())
                                    else:
                                        best_eval, best_models = self._track_best(
                                            models, evaluations, best_eval, best_models, it, name
                                        )
                            else:
                                # quarantine the update: models / scores /
                                # summed were never touched, so the sweep
                                # continues exactly as if this train had not
                                # happened (a never-yet-trained coordinate
                                # simply stays untrained until its next turn);
                                # no re-evaluation either — the GAME model is
                                # unchanged
                                self._reject(it, name)
                        if self.boundary_fn is not None:
                            # coordinate-update boundary: the only point where
                            # the outer-loop state is consistent and host-
                            # reachable. Serialization fetches device arrays,
                            # so lift the transfer guard for exactly this call
                            # — a checkpoint is a deliberate sync point.
                            # In-flight evals drain first: the boundary state
                            # must embed the same evaluations/best ledger the
                            # serial loop would have at this exact update.
                            if lane is not None:
                                _absorb(lane.drain_all())
                            with allow_transfers(), obs.span(
                                "cd.checkpoint", phase="checkpoint", coordinate=name
                            ):
                                self.boundary_fn(
                                    CDBoundaryState(
                                        iteration=it,
                                        coordinate_index=idx,
                                        coordinate=name,
                                        coordinate_order=list(self.order),
                                        n_iterations=self.n_iterations,
                                        models=dict(models),
                                        summed_scores=summed,
                                        best_eval=best_eval,
                                        best_models=dict(best_models),
                                        evaluations=list(evaluations),
                                        trackers=dict(trackers),
                                        train_losses=dict(train_losses),
                                    )
                                )
                    if lane is not None:
                        # sweep end is a serial point: everything submitted
                        # this sweep lands in the ledger before the sweep
                        # span closes (and before any sweep checkpoint)
                        _absorb(lane.drain_all())
                    if self.validation is not None and self.validation_frequency == "SWEEP":
                        best_eval, best_models = self._track_best(
                            models, evaluations, best_eval, best_models, it, self.order[-1]
                        )
                # checkpointing runs OUTSIDE the guard: serializers fetch
                # model arrays however they like (np.asarray included), and a
                # checkpoint is a deliberate pipeline sync point anyway
                if self.checkpoint_fn is not None:
                    with obs.span("cd.checkpoint", phase="checkpoint"):
                        self.checkpoint_fn(it, dict(models))
            # sweep-boundary liveness rendezvous: in a distributed run every
            # process must reach the end of the sweep within the collective
            # budget — a dead peer surfaces here as a typed timeout instead
            # of a hang inside next sweep's collectives. Also the once-per-
            # sweep `dist.collective` fault site (the kill-a-worker drill).
            robust_dist.sweep_barrier(it)
            # memory watermarks at the sweep boundary (host RSS via /proc,
            # device HBM via memory_stats when the backend has it): cheap
            # host-only reads, recorded with or without a sink so the peaks
            # land in run_summary.json for every run
            obs.sample_memory(
                obs.current_run().registry, devices=_local_devices()
            )
            if obs.active():
                # one metrics line per sweep in the JSONL stream
                obs.current_run().flush_metrics()

        final_models = best_models if best_eval is not None else models
        task = self._infer_task()
        return CoordinateDescentResult(
            model=GameModel(models=final_models, task=task),
            evaluations=evaluations,
            best_evaluation=best_eval,
            trackers=trackers,
        )

    def _guard(self, name, new_scores, solver_result, train_losses):
        """Decide whether a freshly trained coordinate update is numerically
        sound: one scalar :func:`logged_fetch` per update (the finiteness
        flag and total train loss travel in the same fetch).

        Accepts unless (a) any new score is non-finite, (b) the solver's
        total loss is non-finite (a born-corrupt solve: divergence at
        initialization leaves no good iterate to roll back to), or (c)
        ``rejection_tolerance`` is set and the loss regressed beyond it.
        Returns ``(accepted, train_loss)``; ``train_loss`` is None for
        locked coordinates (no solver result), which keeps the regression
        ledger scoped to real solves."""
        finite_dev = jnp.all(jnp.isfinite(new_scores))
        if solver_result is None:
            ok = bool(logged_fetch("cd.update_guard", finite_dev))
            return ok, None
        finite_h, loss_h = logged_fetch(
            "cd.update_guard", (finite_dev, jnp.sum(solver_result.loss))
        )
        if not bool(finite_h):
            return False, None
        loss = float(loss_h)
        if not np.isfinite(loss):
            return False, None
        prev = train_losses.get(name)
        tol = self.rejection_tolerance
        if tol is not None and prev is not None and loss > prev + tol:
            return False, None
        return True, loss

    def _reject(self, it: int, name: str) -> None:
        # cheap host-only registry work, recorded with or without a sink
        # (same contract as obs.swallowed_error) — rejections must be visible
        # in run_summary.json even for runs that never attach a listener
        obs.current_run().registry.counter(
            "photon_coordinate_rejections_total",
            "coordinate updates rejected by the divergence guard",
        ).labels(coordinate=name).inc()
        logger.warning(
            "cd iter %d coordinate %s: update REJECTED (non-finite scores/"
            "loss or objective regression); previous model stands",
            it,
            name,
        )

    def _track_best(self, models, evaluations, best_eval, best_models, it, name):
        with obs.span("cd.eval", phase="eval", iteration=it, coordinate=name):
            res = self._evaluate(models)
        return self._absorb_eval(
            it, name, res, models, evaluations, best_eval, best_models
        )

    def _absorb_eval(self, it, name, res, snapshot, evaluations, best_eval, best_models):
        """Fold one evaluation result into the ledger: the serial loop calls
        this right after evaluating; the pipelined loop calls it when the
        eval lane drains (same submit order → same ledger). ``snapshot`` is
        the models dict AS OF the evaluated update."""
        evaluations.append((name, res))
        primary = self.validation.suite.primary
        # only snapshots with every coordinate trained are candidates for
        # "best model" — a mid-first-sweep partial model is not a valid GAME
        # model
        complete = len(snapshot) == len(self.order)
        if complete and (
            best_eval is None
            or primary.better(res.primary_metric, best_eval.primary_metric)
        ):
            best_eval = res
            best_models = dict(snapshot)
        if obs.active():
            # res.metrics values are already host floats — no extra fetch
            gauge = obs.current_run().registry.gauge(
                "photon_validation_metric", "validation metric after an update"
            )
            for metric, value in res.metrics.items():
                gauge.labels(metric=metric, coordinate=name).set(float(value))
        logger.info("cd iter %d coordinate %s: %s", it, name, res.metrics)
        return best_eval, best_models

    def _infer_task(self) -> str:
        """Task from the coordinate definitions (every trainable coordinate
        carries it; locked ModelCoordinates delegate to their inner)."""
        for c in self.coordinates.values():
            inner = c.inner if isinstance(c, ModelCoordinate) else c
            task = getattr(inner, "task", None)
            if task:
                return task
        return "linear_regression"

    def _evaluate(self, models: Mapping[str, object]) -> EvaluationResults:
        """Accumulate per-coordinate validation scores on device and, when
        every metric has a device implementation, evaluate there too — one
        scalar fetch per update instead of a score-vector transfer plus host
        sorts (evaluation/device.py). Grouped/ranking metrics fall back to
        the host path."""
        v = self.validation
        acc = None
        for name, model in models.items():
            fn = v.score_fns.get(name)
            if fn is not None:
                s = fn(model)
                acc = s if acc is None else acc + s
        if acc is not None:
            total_dev = acc + jnp.asarray(v.offsets, acc.dtype)
            res = v.suite.evaluate_device(total_dev)
            if res is not None:
                return res
        total = np.asarray(v.offsets, dtype=np.float64)
        if acc is not None:
            total = total + np.asarray(
                logged_fetch("cd.validation_scores", acc), dtype=np.float64
            )
        return v.suite.evaluate(total)
