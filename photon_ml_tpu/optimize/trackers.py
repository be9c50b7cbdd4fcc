"""Optimization trackers: per-coordinate solve summaries for logging.

Reference: photon-api .../optimization/FixedEffectOptimizationTracker.scala:31
(wraps one solve's state history), RandomEffectOptimizationTracker.scala
(aggregates the per-entity solves: convergence-reason histogram + iteration
StatCounter; time-per-entity stats do not exist here because all entities
advance in LOCKSTEP through one vmapped solver — wall-clock is a property of
the whole block, which the Timed sections already record), and
CoordinateDescent.logOptimizationSummary (photon-lib
.../algorithm/CoordinateDescent.scala:230-248).

The reason histogram is enum-driven (``ConvergenceReason(int(u)).name``), so
lanes frozen by the divergence defense show up as NUMERICAL_DIVERGENCE rows
here with no tracker-side changes; ``obs.record_solver_metrics`` additionally
routes that reason into ``photon_solver_diverged_lanes_total``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from ..utils.transfer import logged_fetch
from .common import ConvergenceReason, SolverResult


@dataclasses.dataclass(frozen=True)
class StatCounter:
    """Spark StatCounter equivalent: count/mean/stdev/max/min of a sample."""

    count: int
    mean: float
    stdev: float
    max: float
    min: float

    @classmethod
    def of(cls, a: np.ndarray) -> "StatCounter":
        a = np.asarray(a, dtype=np.float64).ravel()
        if a.size == 0:
            return cls(0, 0.0, 0.0, 0.0, 0.0)
        return cls(
            count=int(a.size),
            mean=float(a.mean()),
            stdev=float(a.std()),
            max=float(a.max()),
            min=float(a.min()),
        )

    def __str__(self) -> str:
        return (
            f"(count: {self.count}, mean: {self.mean:.6g}, "
            f"stdev: {self.stdev:.6g}, max: {self.max:.6g}, min: {self.min:.6g})"
        )


@dataclasses.dataclass(frozen=True)
class FixedEffectOptimizationTracker:
    """One whole-dataset solve (FixedEffectOptimizationTracker.scala:31)."""

    result: SolverResult

    def to_summary_string(self) -> str:
        r = self.result
        reason_v, iters_v, loss_v, history = logged_fetch(
            "tracker_summary", (r.reason, r.iterations, r.loss, r.loss_history)
        )
        reason = ConvergenceReason(int(reason_v)).name
        losses = np.asarray(history, dtype=np.float64)
        losses = losses[np.isfinite(losses)]
        return (
            f"Convergence reason: {reason}\n"
            f"Iterations: {int(iters_v)}\n"
            f"Loss: {float(loss_v):.6g}"
            + (f" (initial {losses[0]:.6g})" if losses.size else "")
        )


@dataclasses.dataclass(frozen=True)
class RandomEffectOptimizationTracker:
    """Aggregate of the vmapped per-entity solves
    (RandomEffectOptimizationTracker.scala: convergence-reason counts +
    iteration stats over entities).

    The aggregates are LAZY: constructing a tracker must not fetch device
    arrays — trackers are built inside the coordinate-descent hot loop every
    sweep, and a host fetch there stalls the device pipeline for a full
    round trip. The [E]-sized fetches happen on first access, typically when
    logs are enabled or the caller inspects the finished result."""

    result: SolverResult
    entity_mask: Optional[np.ndarray] = None

    def _aggregates(self):
        cached = self.__dict__.get("_agg")
        if cached is None:
            reasons, iters = logged_fetch(
                "tracker_aggregates", (self.result.reason, self.result.iterations)
            )
            reasons = np.ravel(reasons)
            iters = np.ravel(iters)
            if self.entity_mask is not None:
                mask = np.asarray(self.entity_mask, dtype=bool).ravel()
                reasons, iters = reasons[mask], iters[mask]
            uniq, counts = np.unique(reasons, return_counts=True)
            hist = {
                ConvergenceReason(int(u)).name: int(c)
                for u, c in zip(uniq, counts)
            }
            cached = (hist, StatCounter.of(iters))
            object.__setattr__(self, "_agg", cached)
        return cached

    @property
    def convergence_reasons(self) -> Dict[str, int]:
        return self._aggregates()[0]

    @property
    def iterations_stats(self) -> StatCounter:
        return self._aggregates()[1]

    @classmethod
    def from_result(
        cls, result: SolverResult, entity_mask: Optional[np.ndarray] = None
    ) -> "RandomEffectOptimizationTracker":
        return cls(result=result, entity_mask=entity_mask)

    def to_summary_string(self) -> str:
        return (
            f"Convergence reasons stats: {self.convergence_reasons}\n"
            f"Number of iterations stats: {self.iterations_stats}"
        )


def build_tracker(coordinate, result: Optional[SolverResult]):
    """SolverResult -> the right tracker for a coordinate (None for locked
    ModelCoordinates, which never train). No device fetch happens here —
    the reason array's NDIM distinguishes fixed (scalar) from per-entity
    results, and shape metadata is host-known."""
    if result is None:
        return None
    if getattr(result.reason, "ndim", 0) == 0:
        return FixedEffectOptimizationTracker(result=result)
    dataset = getattr(coordinate, "dataset", None)
    counts = getattr(dataset, "entity_counts", None)
    mask = None if counts is None else np.asarray(counts)[: result.reason.shape[0]] > 0
    return RandomEffectOptimizationTracker.from_result(result, entity_mask=mask)


def record_tracker_metrics(registry, coordinate_name: str, tracker) -> None:
    """Fold one coordinate update's tracker into the metrics registry:
    ``photon_cd_iterations`` (StatCounter-compatible summary),
    ``photon_cd_cg_iterations`` (fixed effects) and
    ``photon_cd_convergence_reason_total`` per coordinate. Forces the
    tracker's lazy aggregates, so callers in the CD hot loop must gate this
    on ``obs.active()``."""
    if tracker is None:
        return
    iters = registry.summary(
        "photon_cd_iterations", "solver iterations per coordinate update"
    ).labels(coordinate=coordinate_name)
    reasons = registry.counter(
        "photon_cd_convergence_reason_total",
        "coordinate-update solves by termination reason",
    )
    # latest-update iterations as a gauge: the cumulative summary above
    # cannot be read back per sweep, but this gauge lands in every per-sweep
    # metrics.jsonl flush — the report's solver-iterations trajectory
    latest = registry.gauge(
        "photon_cd_update_iterations",
        "solver iterations of the latest coordinate update (entity mean "
        "for random effects)",
    ).labels(coordinate=coordinate_name)
    if isinstance(tracker, RandomEffectOptimizationTracker):
        st = tracker.iterations_stats
        iters.merge_stat(st.count, st.mean, st.stdev, st.max, st.min)
        latest.set(st.mean)
        for reason, n in tracker.convergence_reasons.items():
            reasons.labels(coordinate=coordinate_name, reason=reason).inc(n)
    else:
        r = tracker.result
        # the CG count rides in the one fetch this update already makes
        iters_v, reason_v, loss_v, cg_v = logged_fetch(
            "tracker_metrics", (r.iterations, r.reason, r.loss, r.cg_iterations)
        )
        iters.observe(int(iters_v))
        registry.summary(
            "photon_cd_cg_iterations",
            "TRON inner CG iterations (Hessian-vector products) per "
            "fixed-effect coordinate update; 0 for L-BFGS/OWL-QN",
        ).labels(coordinate=coordinate_name).observe(int(cg_v))
        latest.set(int(iters_v))
        reasons.labels(
            coordinate=coordinate_name,
            reason=ConvergenceReason(int(reason_v)).name,
        ).inc()
        registry.gauge(
            "photon_cd_final_loss", "final training loss of the latest update"
        ).labels(coordinate=coordinate_name).set(float(loss_v))
