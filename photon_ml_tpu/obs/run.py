"""Run-scoped telemetry: one `RunTelemetry` per training run, owning the
metrics registry and the sink listeners.

A module-global "current run" gives instrumentation sites (descent loop,
solvers, streaming) something to record into without threading a handle
through every call. The default current run is PASSIVE — it has a registry
but no listeners — so instrumented code can always record cheap host-known
numbers, while anything requiring a device fetch must gate on ``active()``.
That is what preserves the lazy-aggregate invariant of
``optimize/trackers.py``: with no sink registered, the CD hot loop performs
zero additional device fetches.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional

from ..utils.events import Event, EventEmitter, EventListener
from .metrics import MetricsRegistry


class StatusBoard:
    """Thread-safe key/value board holding the run's *current position*
    (sweep, coordinate, accepted losses, ...) for the ``/statusz`` endpoint.

    Updates are cheap host-only dict writes, so instrumentation sites update
    it unconditionally — it works on passive runs too, and a scrape thread
    can snapshot it while the training thread is mid-sweep."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._state: Dict[str, object] = {}

    def update(self, **kv) -> None:
        with self._lock:
            self._state.update(kv)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return dict(self._state)


@dataclasses.dataclass(frozen=True)
class MetricsSnapshotEvent(Event):
    """A point-in-time registry snapshot (list of JSON-ready series dicts),
    emitted on every ``flush_metrics`` (per CD sweep and at close)."""

    metrics: List[dict]


class RunTelemetry(EventEmitter):
    """EventEmitter + MetricsRegistry for one training run. Sinks register
    as listeners; ``send_event`` inherits EventEmitter's error swallowing,
    so a raising sink can never fail training."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        super().__init__()
        self.registry = registry if registry is not None else MetricsRegistry()
        self.status = StatusBoard()

    def register_listener(self, listener: EventListener) -> None:
        super().register_listener(listener)
        # a run that is listened to wants its spans to carry re-trace and
        # compile attribution: the jax monitoring hook goes in with the
        # first listener (idempotent, best effort — without a usable jax it
        # says no and spans simply carry no such attributes)
        from ..utils.compile_cache import install_compile_metrics_hook

        install_compile_metrics_hook()

    def flush_metrics(self) -> List[dict]:
        snap = self.registry.snapshot()
        self.send_event(MetricsSnapshotEvent(metrics=snap))
        return snap

    def close(self) -> None:
        if self.has_listeners():
            self.flush_metrics()
        self.clear_listeners()


# guards _current: instrumentation sites read it from worker threads (the
# refresh watcher, batcher workers, HTTP scrape handlers) while use_run
# swaps it on the training thread — get/set hold the lock, and the
# RunTelemetry object itself is internally thread-safe past the handoff
_current_lock = threading.Lock()
_current = RunTelemetry()


def current_run() -> RunTelemetry:
    with _current_lock:
        return _current


def set_current_run(run: Optional[RunTelemetry]) -> RunTelemetry:
    """Install ``run`` as the current telemetry scope (None installs a fresh
    passive one) and return the previous scope so callers can restore it."""
    global _current
    with _current_lock:
        prev = _current
        _current = run if run is not None else RunTelemetry()
    return prev


@contextlib.contextmanager
def use_run(run: RunTelemetry):
    prev = set_current_run(run)
    try:
        yield run
    finally:
        set_current_run(prev)


def active() -> bool:
    """True when some sink is listening — i.e. when it is worth paying for
    device fetches to feed the telemetry."""
    return current_run().has_listeners()


def swallowed_error(site: str) -> None:
    """Count a deliberately swallowed exception so degraded-mode operation
    is visible in metrics.jsonl (``photon_swallowed_errors_total{site=}``).

    This is the instrumentation half of lint rule R4: a broad ``except``
    that neither re-raises nor calls this is flagged as an invisible
    swallow. Cheap host-only registry work — safe in any handler, including
    inside event-dispatch error paths."""
    current_run().registry.counter(
        "photon_swallowed_errors_total",
        "exceptions swallowed by degrade-and-continue handlers",
    ).labels(site=site).inc()


def record_solver_metrics(solver: str, result) -> None:
    """Record iterations / convergence reasons / line-search failures /
    final gradient norms for a host-level solve.

    No-ops when (a) no sink is registered — the fetches below would stall
    the device pipeline for nothing — or (b) the result leaves are tracers:
    ``solve_lbfgs``/``solve_tron`` are also called inside the jitted
    random-effect train functions, where there is nothing concrete to read
    (those solves are covered by the trackers instead).
    """
    run = current_run()
    if not run.has_listeners():
        return
    import jax

    if any(
        isinstance(x, jax.core.Tracer)
        for x in (result.iterations, result.reason, result.gradient)
    ):
        return

    import numpy as np

    from ..optimize.common import ConvergenceReason
    from .tracing import record_device_fetch

    # explicit fetch: host-level solves run inside the CD sweep's transfer
    # guard, which rejects a bare np.asarray on a device array. The evaluations
    # a solve counted (OWL-QN always, plain L-BFGS at host level), the feature
    # passes of one that walked margins and OWL-QN's two other counters ride
    # the same fetch.
    #
    # The final gradient's norm is the solver's own: the row of
    # ``grad_norm_history`` its last iteration wrote, taken on the device
    # inside the solve (``_norm`` of the same gradient, every lane). The vector
    # itself stays where it is: [d] floats a solve is 219 MB at d = 54.7M, and
    # a norm taken here by a fresh device program would compile inside a
    # traced window. A host solver's gradient is a host array already.
    on_device = isinstance(result.gradient, jax.Array)
    counts = {
        name: getattr(result, name)
        for name in ("line_search_evals", "orthant_zeroed", "nonzeros", "matvecs", "rmatvecs")
        if getattr(result, name, None) is not None
    }
    norm_source = result.grad_norm_history if on_device else result.gradient
    fetch_start = time.perf_counter()
    iters, reasons, norm_source, *extras = map(
        np.asarray,
        jax.device_get((result.iterations, result.reason, norm_source, *counts.values())),
    )
    fetch_end = time.perf_counter()
    # this fetch, not the enclosing fe.solve span's fence, is where a traced
    # run waits for the solve: the fence finds the device drained
    record_device_fetch(
        f"solver.{solver}",
        iters.nbytes + reasons.nbytes + norm_source.nbytes + sum(x.nbytes for x in extras),
        fetch_start,
        fetch_end,
    )
    if on_device:
        # [max_iter + 1, *lanes]: each lane's row is its own iteration count
        rows = norm_source.reshape(norm_source.shape[0], -1)
        gn = rows[iters.ravel(), np.arange(rows.shape[1])].astype(np.float64)
    else:
        # [d] for a scalar solve, [d, lanes] for batched ones
        grad = norm_source.astype(np.float64)
        gn = np.sqrt((grad * grad).sum(axis=0)).ravel()

    reg = run.registry
    if result.line_search_evals is not None:
        _record_fe_path(
            reg, iterations=int(iters.sum()),
            **{name: int(x.sum()) for name, x in zip(counts, extras)},
        )
    reg.summary(
        "photon_solver_iterations", "iterations per host-level solve"
    ).labels(solver=solver).observe_many(iters.ravel().tolist())
    reason_counter = reg.counter(
        "photon_solver_convergence_reason_total",
        "host-level solves by termination reason",
    )
    uniq, counts = np.unique(reasons.ravel(), return_counts=True)
    for u, c in zip(uniq.tolist(), counts.tolist()):
        reason_counter.labels(solver=solver, reason=ConvergenceReason(int(u)).name).inc(c)
        if int(u) == int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING):
            # the only way the objective stops improving is the line search /
            # trust-region step failing to find descent
            reg.counter(
                "photon_solver_line_search_failures_total",
                "solves terminated because no improving step was found",
            ).labels(solver=solver).inc(c)
        elif int(u) == int(ConvergenceReason.NUMERICAL_DIVERGENCE):
            reg.counter(
                "photon_solver_diverged_lanes_total",
                "solver lanes frozen at their last good iterate after a "
                "non-finite loss/gradient",
            ).labels(solver=solver).inc(c)
    reg.summary(
        "photon_solver_final_grad_norm", "final gradient norm per host-level solve"
    ).labels(solver=solver).observe_many(gn.tolist())


def _record_fe_path(reg, line_search_evals: int, orthant_zeroed: Optional[int] = None,
                    nonzeros: Optional[int] = None, matvecs: Optional[int] = None,
                    rmatvecs: Optional[int] = None, iterations: Optional[int] = None) -> None:
    """What a counting L-BFGS adds to a fixed-effect solve, on the enclosing
    ``fe.solve`` span (``game/problem.py``) and as counters by its coordinate:
    the search it ran (``line_search``: ``margins`` where the solve counted
    its own passes over the features, else ``points``), the trials its
    searches judged (and the first evaluation), a margin walk's passes and,
    under OWL-QN, the support and the orthant's work. A solve with no such
    span around it (a bare ``solve_lbfgs`` call) records nothing."""
    from .tracing import current_span

    solve_span = current_span()
    if solve_span is None or solve_span.name != "fe.solve":
        return
    solve_span.attrs["line_search"] = "points" if matvecs is None else "margins"
    solve_span.attrs["line_search_evals"] = line_search_evals
    if iterations is not None:
        # this solve's own count (``photon_cd_iterations`` is the mean over
        # every solve of a fit: a warm solve inside coordinate descent and
        # the cold first one are told apart by the span's ``warm``)
        solve_span.attrs["iterations"] = iterations
    coordinate = str(solve_span.attrs.get("coordinate"))
    reg.counter(
        "photon_fe_line_search_evals_total",
        "objective evaluations of fixed-effect L-BFGS and OWL-QN solves: the first and every trial judged",
    ).labels(coordinate=coordinate).inc(line_search_evals)
    if matvecs is not None:
        # a search over points is not counted here: its evaluations are the
        # counter above, and what one reads of X is its objective's to say
        passes = reg.counter(
            "photon_fe_feature_passes_total",
            "passes over the feature matrix made by fixed-effect L-BFGS solves that walk margins",
        )
        passes.labels(coordinate=coordinate, kind="matvec").inc(matvecs)
        passes.labels(coordinate=coordinate, kind="rmatvec").inc(rmatvecs)
        per_pass = solve_span.attrs.get("collective_bytes")
        if per_pass:
            # a state split over the chips (game/problem.py state_sharding):
            # every gather reads the vector all-gathered, every scatter-add's
            # sum is reduce-scattered, each moving ``collective_bytes`` a chip
            moved = reg.counter(
                "photon_fe_collective_bytes_total",
                "bytes a chip moves in the all-gathers and reduce-scatters of "
                "fixed-effect solves whose coefficient-length state is sharded",
            )
            moved.labels(coordinate=coordinate, kind="all_gather").inc(matvecs * per_pass)
            moved.labels(coordinate=coordinate, kind="reduce_scatter").inc(rmatvecs * per_pass)
    if nonzeros is None:
        return
    solve_span.attrs["nonzeros"] = nonzeros
    reg.counter(
        "photon_fe_orthant_zeroed_total",
        "coefficients set to zero by OWL-QN's orthant projection, over accepted steps",
    ).labels(coordinate=coordinate).inc(orthant_zeroed)
    reg.gauge(
        "photon_fe_nonzero_coefficients",
        "non-zero coefficients of the last fixed-effect OWL-QN solve, in the solver's space",
    ).labels(coordinate=coordinate).set(nonzeros)


def collect_build_info() -> Dict[str, str]:
    """Build/runtime identity of this process: package version, jax version
    and backend (when a usable jax is present — obs stays importable without
    one), plus process/replica labels. The values every fleet-merged metric
    stream must stay attributable to."""
    from .tracing import get_process_index, get_replica_id

    try:
        from .. import __version__ as version
    # photon: ignore[R4] — a version probe must never fail telemetry setup;
    # the placeholder value IS the degraded-mode signal
    except Exception:  # pragma: no cover
        version = "unknown"
    info = {"version": str(version), "jax": "none", "backend": "none"}
    try:
        import jax

        info["jax"] = str(jax.__version__)
        info["backend"] = str(jax.default_backend())
    # photon: ignore[R4] — build info is best-effort by design: a jax-free
    # process (report rebuilds, fleet aggregation) reports backend "none"
    except Exception:
        pass
    info["process"] = str(get_process_index())
    info["replica"] = get_replica_id() or ""
    return info


def record_build_info(registry: Optional[MetricsRegistry] = None) -> Dict[str, str]:
    """Stamp the ``photon_build_info`` gauge (value 1, identity in labels)
    into ``registry`` (default: the current run's), so every Prometheus
    exposition carries it and merged fleet streams stay attributable."""
    reg = registry if registry is not None else current_run().registry
    info = collect_build_info()
    reg.gauge(
        "photon_build_info",
        "build/runtime identity of this process; value is always 1",
    ).labels(
        version=info["version"],
        jax=info["jax"],
        backend=info["backend"],
        process=info["process"],
        replica=info["replica"],
    ).set(1)
    return info


def build_run_summary(registry: MetricsRegistry, total_wall_seconds: float) -> dict:
    """The ``run_summary.json`` document: total wall time, per-coordinate
    iteration StatCounters and convergence-reason histograms, memory
    watermarks (when the run sampled any), and the full final metrics
    snapshot."""
    from .memory import memory_block

    snap = registry.snapshot()
    coordinates: dict = {}
    for m in snap:
        coord = m.get("labels", {}).get("coordinate")
        if not coord:
            continue
        if m["name"] == "photon_cd_iterations":
            coordinates.setdefault(coord, {})["iterations"] = m["stat"]
        elif m["name"] == "photon_cd_convergence_reason_total":
            coordinates.setdefault(coord, {}).setdefault("convergence_reasons", {})[
                m["labels"].get("reason", "?")
            ] = int(m["value"])
        elif m["name"] == "photon_coordinate_rejections_total":
            coordinates.setdefault(coord, {})["rejections"] = int(m["value"])
    doc = {
        "total_wall_seconds": float(total_wall_seconds),
        "build": collect_build_info(),
        "coordinates": coordinates,
        "metrics": snap,
    }
    mem = memory_block(snap)
    if mem:
        doc["memory"] = mem
    return doc
