"""Host-driven single-lane solvers for out-of-core objectives.

The device solvers (lbfgs.py / tron.py) run their entire loop inside
``lax.while_loop``, which requires the objective to be traceable — fine when
the batch is HBM-resident, impossible when each evaluation must stage host
row slices through the chip with Python-driven double buffering
(game/fe_streaming.py). These ports move the *driver* loop to the host while
the objective math stays on device, which is exactly the reference's
architecture for the fixed effect: Breeze optimizers iterate on the Spark
driver and every evaluation is a ``treeAggregate`` over disk-persisted
partitions (photon-lib .../optimization/LBFGS.scala:38-154,
DistributedObjectiveFunction + AvroDataReader.scala:165-209).

Parity contract with the device twins, single lane (scalar f, ``[d]`` g); the
device L-BFGS has two line searches since PR 37 and the twin here is its
``points`` search (a streamed objective is an opaque callable: it brings no
margin functions):

- same constants (c1=1e-4, c2=0.9; TRON eta/sigma), same bracket updates,
  same correction-pair guard ``s.y > 1e-10 ||y||^2``, same steepest-descent
  fallback, same OWL-QN pseudo-gradient / orthant projection, same L-BFGS-B
  projected gradient, same TRON trust-region schedule and truncated CG with
  boundary crossing;
- same convergence precedence (common.check_convergence) with relative ->
  absolute tolerances from the zero state;
- same numerical-divergence defense: a non-finite trial is never committed
  (the last good iterate survives), its (s, y) pair never enters history, a
  non-finite TRON ratio never resizes the radius, and an already-corrupt
  start freezes at w0 with 0 iterations.

Results are host-materialized ``SolverResult``s (numpy leaves) — directly
compatible with the divergence guard in game/descent and with
``obs.record_solver_metrics``.
"""

from __future__ import annotations

import math
from typing import Callable, List, Optional, Tuple

import numpy as np

from .. import obs
from .common import ConvergenceReason, OptimizerConfig, OptimizerType, SolverResult

_C1 = 1e-4  # Armijo (sufficient decrease)
_C2 = 0.9  # curvature
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0

# Callable w[np d] -> (float, np[d]); the streamed objective fetches its
# accumulated totals once per evaluation, so these are host-concrete.
HostValueAndGradFn = Callable[[np.ndarray], Tuple[float, np.ndarray]]
HostHvpFn = Callable[[np.ndarray, np.ndarray], np.ndarray]


def _norm(v: np.ndarray) -> float:
    return float(np.sqrt(np.dot(v, v)))


def _finite(f: float, g: np.ndarray) -> bool:
    return bool(np.isfinite(f)) and bool(np.all(np.isfinite(g)))


def host_check_convergence(
    it: int,
    max_iterations: int,
    loss: float,
    prev_loss: float,
    grad_norm: float,
    loss_abs_tol: float,
    grad_abs_tol: float,
    objective_not_improving: bool,
    diverged: bool = False,
) -> int:
    """Host port of common.check_convergence: identical precedence chain
    (later conditions override earlier ones; divergence overrides all)."""
    reason = 0
    if grad_norm <= grad_abs_tol:
        reason = int(ConvergenceReason.GRADIENT_CONVERGED)
    if abs(loss - prev_loss) <= loss_abs_tol:
        reason = int(ConvergenceReason.FUNCTION_VALUES_CONVERGED)
    if objective_not_improving:
        reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
    if it >= max_iterations:
        reason = int(ConvergenceReason.MAX_ITERATIONS)
    if diverged:
        reason = int(ConvergenceReason.NUMERICAL_DIVERGENCE)
    return reason


def host_abs_tolerances(
    value_and_grad: HostValueAndGradFn, zero_like: np.ndarray, tolerance: float
) -> Tuple[float, float]:
    """Relative -> absolute tolerances from the zero state (the host twin of
    common.abs_tolerances; costs one extra streamed pass, exactly like the
    device path's extra evaluation)."""
    f0, g0 = value_and_grad(np.zeros_like(zero_like))
    return abs(float(f0)) * tolerance, _norm(np.asarray(g0)) * tolerance


def _pseudo_gradient(w: np.ndarray, g: np.ndarray, l1: float) -> np.ndarray:
    """OWL-QN pseudo-gradient of f(w) + l1*||w||_1 (lbfgs._pseudo_gradient)."""
    gp = g + l1
    gm = g - l1
    pg = np.where(w > 0, gp, np.where(w < 0, gm, 0.0))
    at_zero = np.where(gm > 0, gm, np.where(gp < 0, gp, 0.0))
    return np.where(w == 0, at_zero, pg).astype(g.dtype)


def _two_loop(pairs: List[Tuple[np.ndarray, np.ndarray, float]], g: np.ndarray) -> np.ndarray:
    """Two-loop recursion over the (s, y, rho) history, oldest..newest —
    identical visit order to the device circular buffer (newest-first pass 1,
    oldest-first pass 2, gamma from the newest pair with the yy > 0 guard)."""
    q = g.copy()
    alphas = []
    for s, y, rho in reversed(pairs):
        a = rho * float(np.dot(s, q))
        alphas.append(a)
        q = q - a * y
    if pairs:
        s_n, y_n, _ = pairs[-1]
        yy = float(np.dot(y_n, y_n))
        gamma = float(np.dot(s_n, y_n)) / yy if yy > 0 else 1.0
    else:
        gamma = 1.0
    r = gamma * q
    for (s, y, rho), a in zip(pairs, reversed(alphas)):
        b = rho * float(np.dot(y, r))
        r = r + (a - b) * s
    return r.astype(g.dtype)


def _line_search(
    value_and_grad: HostValueAndGradFn,
    w: np.ndarray,
    f: float,
    direction: np.ndarray,
    dg: float,
    l1: float,
    orthant: Optional[np.ndarray],
    max_iters: int,
    box: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    g_plain: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, float, np.ndarray, bool]:
    """Weak-Wolfe bisection/expansion line search (lbfgs._line_search, one
    lane): OWL-QN projects trials onto the orthant and checks Armijo only;
    L-BFGS-B projects onto the box and measures Armijo on the actual
    displacement."""
    dtype = w.dtype

    def trial(t: float):
        w_t = (w + t * direction).astype(dtype)
        if orthant is not None:
            w_t = np.where(w_t * orthant < 0, 0.0, w_t).astype(dtype)
        if box is not None:
            w_t = np.clip(w_t, box[0], box[1])
        f_t, g_t = value_and_grad(w_t)
        f_t = float(f_t)
        if l1 > 0.0:
            f_t = f_t + l1 * float(np.sum(np.abs(w_t)))
        return w_t, f_t, np.asarray(g_t)

    t, lo, hi = 1.0, 0.0, math.inf
    w_t, f_t, g_t = trial(t)
    for n in range(max_iters):
        finite = bool(np.isfinite(f_t))
        if box is not None:
            armijo_ok = f_t <= f + _C1 * float(np.dot(g_plain, w_t - w))
        else:
            armijo_ok = f_t <= f + _C1 * t * dg
        if orthant is None and box is None:
            curv_ok = float(np.dot(g_t, direction)) >= _C2 * dg
        else:
            curv_ok = True
        if armijo_ok and curv_ok and finite:
            return w_t, f_t, g_t, True
        if n + 1 >= max_iters:
            break
        if armijo_ok and finite:
            # Armijo held but curvature failed: raise the lower bracket
            lo = t
            t = 2.0 * lo + 1.0 if math.isinf(hi) else 0.5 * (lo + hi)
        else:
            # Armijo failed (or non-finite): bisect downward
            hi = t
            t = 0.5 * (lo + t)
        w_t, f_t, g_t = trial(t)
    return w_t, f_t, g_t, False


def solve_lbfgs_host(
    value_and_grad: HostValueAndGradFn,
    w0: np.ndarray,
    loss_abs_tol: float,
    grad_abs_tol: float,
    max_iterations: int = 100,
    num_corrections: int = 10,
    l1_weight: float = 0.0,
    box_constraints: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    max_line_search_iterations: int = 25,
    initial_eval: Optional[Tuple[float, np.ndarray]] = None,
) -> SolverResult:
    """Host port of lbfgs._solve for one lane; numpy-leaved SolverResult.

    ``initial_eval``: a pre-dispatched raw ``value_and_grad(w0)`` result
    (pipelined tolerance overlap, host_optimize); the L1 term is applied
    here with the same arithmetic as ``full_objective``, so the iterate
    stream is bit-identical to evaluating in place. Only valid without box
    constraints (the initial clip would move the evaluation point)."""
    dtype = w0.dtype
    l1 = float(l1_weight)
    box = None
    if box_constraints is not None:
        box = (
            np.asarray(box_constraints[0], dtype),
            np.asarray(box_constraints[1], dtype),
        )

    def full_objective(w: np.ndarray) -> Tuple[float, np.ndarray]:
        f, g = value_and_grad(w)
        f = float(f)
        if l1 > 0.0:
            f = f + l1 * float(np.sum(np.abs(w)))
        return f, np.asarray(g)

    def effective_grad(w: np.ndarray, g: np.ndarray) -> np.ndarray:
        if l1 > 0.0:
            return _pseudo_gradient(w, g, l1)
        if box is not None:
            return (w - np.clip(w - g, box[0], box[1])).astype(g.dtype)
        return g

    w = np.array(w0, dtype, copy=True)
    if box is not None:
        w = np.clip(w, box[0], box[1])
    if initial_eval is not None and box is None:
        f, g = initial_eval
        f = float(f)
        if l1 > 0.0:
            f = f + l1 * float(np.sum(np.abs(w)))
        g = np.asarray(g)
    else:
        f, g = full_objective(w)

    T = max_iterations + 1
    lh = np.full(T, np.nan, dtype)
    gh = np.full(T, np.nan, dtype)
    lh[0] = f
    gh[0] = _norm(effective_grad(w, g))

    def result(it: int, reason: int) -> SolverResult:
        return SolverResult(
            coefficients=w,
            loss=np.asarray(f, dtype),
            gradient=effective_grad(w, g),
            iterations=np.int32(it),
            reason=np.int32(reason),
            loss_history=lh,
            grad_norm_history=gh,
            cg_iterations=np.int32(0),
        )

    if not _finite(f, g):
        # corrupt at start: no good iterate to roll back to — freeze at w0
        return result(0, int(ConvergenceReason.NUMERICAL_DIVERGENCE))

    pairs: List[Tuple[np.ndarray, np.ndarray, float]] = []
    it = 0
    while True:
        pg = effective_grad(w, g)
        direction = -_two_loop(pairs, pg)
        if l1 > 0.0:
            direction = np.where(direction * pg >= 0, 0.0, direction).astype(dtype)
        dg = float(np.dot(direction, pg))
        if dg >= 0:
            # not a descent direction: steepest-descent fallback
            direction = -pg
            dg = -float(np.dot(pg, pg))
        orthant = None
        if l1 > 0.0:
            orthant = np.where(w != 0, np.sign(w), -np.sign(pg)).astype(dtype)

        w_new, f_new, g_new, ls_ok = _line_search(
            value_and_grad, w, f, direction, dg, l1, orthant,
            max_line_search_iterations, box=box, g_plain=g,
        )

        finite_new = _finite(f_new, g_new)
        improved = ls_ok and (f_new < f) and finite_new

        s_vec = w_new - w
        y_vec = g_new - g
        sy = float(np.dot(s_vec, y_vec))
        if improved and sy > 1e-10 * _norm(y_vec) ** 2:
            pairs.append((s_vec, y_vec, 1.0 / sy))
            if len(pairs) > num_corrections:
                pairs.pop(0)

        it += 1
        pg_new = effective_grad(w_new, g_new)
        reason = host_check_convergence(
            it, max_iterations, f_new, f, _norm(pg_new), loss_abs_tol,
            grad_abs_tol, objective_not_improving=not improved,
            diverged=not finite_new,
        )
        if improved:
            w, f, g = w_new, f_new, g_new
        lh[it] = f
        gh[it] = _norm(effective_grad(w, g))
        if reason != 0:
            return result(it, reason)


def _truncated_cg(
    hvp: HostHvpFn,
    w: np.ndarray,
    gradient: np.ndarray,
    delta: float,
    max_cg_iterations: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host port of tron._truncated_cg: solve H step = -g within the radius,
    with the boundary-crossing back-off. Returns (step, residual, iters)."""
    tol = 0.1 * _norm(gradient)
    step = np.zeros_like(gradient)
    r = -gradient
    d = r.copy()
    rtr = float(np.dot(r, r))
    if _norm(r) <= tol:
        return step, r, 0
    it = 0
    while it < max_cg_iterations:
        hd = np.asarray(hvp(w, d))
        dhd = float(np.dot(d, hd))
        alpha = rtr / (dhd if dhd != 0 else 1.0)
        step_try = step + alpha * d
        if _norm(step_try) > delta:
            # hit the trust-region boundary: back off to the crossing
            std = float(np.dot(step, d))
            sts = float(np.dot(step, step))
            dtd = float(np.dot(d, d))
            dsq = delta * delta
            rad = math.sqrt(max(std * std + dtd * (dsq - sts), 0.0))
            if std >= 0:
                denom = std + rad
                alpha_b = (dsq - sts) / (denom if denom != 0 else 1.0)
            else:
                alpha_b = (rad - std) / (dtd if dtd != 0 else 1.0)
            return step + alpha_b * d, r - alpha_b * hd, it + 1
        step = step_try
        r = r - alpha * hd
        rtr_new = float(np.dot(r, r))
        beta = rtr_new / (rtr if rtr != 0 else 1.0)
        d = r + beta * d
        rtr = rtr_new
        it += 1
        if _norm(r) <= tol:
            break
    return step, r, it


def solve_tron_host(
    value_and_grad: HostValueAndGradFn,
    hvp: HostHvpFn,
    w0: np.ndarray,
    loss_abs_tol: float,
    grad_abs_tol: float,
    max_iterations: int = 15,
    max_cg_iterations: int = 20,
    max_improvement_failures: int = 5,
    box_constraints: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    initial_eval: Optional[Tuple[float, np.ndarray]] = None,
) -> SolverResult:
    """Host port of tron._solve for one lane; numpy-leaved SolverResult.

    ``initial_eval``: pre-dispatched ``value_and_grad(w0)`` (pipelined
    tolerance overlap, host_optimize) — TRON starts from unclipped w0, so
    the substitution is exact."""
    dtype = w0.dtype
    box = None
    if box_constraints is not None:
        box = (
            np.asarray(box_constraints[0], dtype),
            np.asarray(box_constraints[1], dtype),
        )

    w = np.array(w0, dtype, copy=True)
    fg = initial_eval if initial_eval is not None else value_and_grad(w)
    f, g = float(fg[0]), np.asarray(fg[1])

    T = max_iterations + 1
    lh = np.full(T, np.nan, dtype)
    gh = np.full(T, np.nan, dtype)
    lh[0] = f
    gh[0] = _norm(g)
    cg_total = 0  # inner CG iterations over the whole solve

    def result(it: int, reason: int) -> SolverResult:
        return SolverResult(
            coefficients=w,
            loss=np.asarray(f, dtype),
            gradient=g,
            iterations=np.int32(it),
            reason=np.int32(reason),
            loss_history=lh,
            grad_norm_history=gh,
            cg_iterations=np.int32(cg_total),
        )

    if not _finite(f, g):
        return result(0, int(ConvergenceReason.NUMERICAL_DIVERGENCE))

    delta = _norm(g)
    it = 0
    failures = 0
    while True:
        step, residual, cg_it = _truncated_cg(hvp, w, g, delta, max_cg_iterations)
        cg_total += cg_it
        w_try = w + step
        gs = float(np.dot(g, step))
        predicted = -0.5 * (gs - float(np.dot(step, residual)))
        fg_try = value_and_grad(w_try)
        f_try, g_try = float(fg_try[0]), np.asarray(fg_try[1])
        actual = f - f_try
        step_norm = _norm(step)

        # first-ever trial shrinks the initial bound (TRON.scala:190-193)
        delta0 = min(delta, step_norm) if (it == 0 and failures == 0) else delta

        denom = f_try - f - gs
        if denom <= 0:
            alpha = _SIGMA3
        else:
            alpha = max(_SIGMA1, -0.5 * gs / (denom if denom != 0 else 1.0))

        a, p = actual, predicted
        if a < _ETA0 * p:
            delta_new = min(max(alpha, _SIGMA1) * step_norm, _SIGMA2 * delta0)
        elif a < _ETA1 * p:
            delta_new = max(_SIGMA1 * delta0, min(alpha * step_norm, _SIGMA2 * delta0))
        elif a < _ETA2 * p:
            delta_new = max(_SIGMA1 * delta0, min(alpha * step_norm, _SIGMA3 * delta0))
        else:
            delta_new = max(delta0, min(alpha * step_norm, _SIGMA3 * delta0))

        # a non-finite trial is numerical divergence: never accept it and
        # keep the NaN out of the trust-region radius
        finite_try = _finite(f_try, g_try)
        accepted = (actual > _ETA0 * predicted) and finite_try
        delta = delta_new if finite_try else delta

        prev_f = f
        if accepted:
            w = np.clip(w_try, box[0], box[1]) if box is not None else w_try
            f, g = f_try, g_try
            it += 1
            lh[it] = f
            gh[it] = _norm(g)
        else:
            failures += 1

        too_many = failures >= max_improvement_failures
        reason = host_check_convergence(
            it, max_iterations, f, prev_f, _norm(g), loss_abs_tol,
            grad_abs_tol, objective_not_improving=too_many,
            diverged=not finite_try,
        )
        # a rejected trial alone isn't convergence; only repeated failure
        # (or divergence, which freezes the rolled-back lane) is
        if not (accepted or too_many or not finite_try):
            reason = 0
        if reason != 0:
            return result(it, reason)


def host_optimize(
    value_and_grad: HostValueAndGradFn,
    w0: np.ndarray,
    config: OptimizerConfig,
    hvp: Optional[HostHvpFn] = None,
    value_and_grad_deferred: Optional[Callable] = None,
) -> SolverResult:
    """Host twin of driver.optimize: tolerance conversion from the zero
    state, then dispatch on the normalized optimizer type. Records the same
    per-solver obs metrics as the device drivers (solver labels ``lbfgs`` /
    ``tron``; numpy results are fetch-free to record).

    ``value_and_grad_deferred``: dispatch-only form of ``value_and_grad``
    (returns a fetch closure — StreamedFEObjective.value_and_grad_deferred).
    When provided, the tolerance pass at zeros and the first real evaluation
    at w0 are BOTH dispatched before either is fetched, so the driver's two
    mandatory serial passes overlap on device. Same kernels on the same
    operands → same bits; skipped under box constraints, where the solver's
    initial clip moves the evaluation point."""
    w0 = np.asarray(w0)
    initial_eval = None
    if value_and_grad_deferred is not None and config.box_constraints is None:
        fetch_zero = value_and_grad_deferred(np.zeros_like(w0))
        fetch_w0 = value_and_grad_deferred(w0)
        f0, g0 = fetch_zero()
        loss_tol = abs(float(f0)) * config.tolerance
        grad_tol = _norm(np.asarray(g0)) * config.tolerance
        initial_eval = fetch_w0()
    else:
        loss_tol, grad_tol = host_abs_tolerances(
            value_and_grad, w0, config.tolerance
        )
    kind = config.normalized_type()

    if kind in (OptimizerType.LBFGS, OptimizerType.LBFGSB, OptimizerType.OWLQN):
        result = solve_lbfgs_host(
            value_and_grad,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=config.max_iterations,
            num_corrections=config.num_corrections,
            l1_weight=config.l1_weight if kind == OptimizerType.OWLQN else 0.0,
            box_constraints=config.box_constraints,
            max_line_search_iterations=config.max_line_search_iterations,
            initial_eval=initial_eval,
        )
        obs.record_solver_metrics("lbfgs", result)
        return result
    if kind == OptimizerType.TRON:
        if hvp is None:
            raise ValueError("TRON requires a Hessian-vector-product function")
        result = solve_tron_host(
            value_and_grad,
            hvp,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=config.max_iterations,
            max_cg_iterations=config.max_cg_iterations,
            max_improvement_failures=config.max_improvement_failures,
            box_constraints=config.box_constraints,
            initial_eval=initial_eval,
        )
        obs.record_solver_metrics("tron", result)
        return result
    raise ValueError(f"Unknown optimizer type: {config.optimizer_type!r}")
