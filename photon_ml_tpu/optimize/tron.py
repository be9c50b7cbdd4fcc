"""TRON: trust-region Newton with truncated conjugate-gradient inner solves.

Functional re-implementation of the trust-region Newton method of Lin & Moré
(the algorithm in Lin, Weng, Keerthi, "Trust region Newton method for
large-scale logistic regression", JMLR 2008) that the reference adapted from
LIBLINEAR (photon-lib .../optimization/TRON.scala:78-335). Constants are
parity-matched: eta = (1e-4, 0.25, 0.75), sigma = (0.25, 0.5, 4.0)
(TRON.scala:93-94), defaults tol 1e-5 / 15 iterations / 20 CG iterations /
5 improvement failures (TRON.scala:252-258), CG stops at
||r|| <= 0.1 * ||g||, and the first accepted step shrinks delta to
min(delta, ||step||).

The Hessian never materializes: CG consumes Hessian-vector products, which on
TPU are one extra fused matvec pair per CG step
(GLMObjective.hessian_vector — the reference's HessianVectorAggregator
treeAggregate, here an XLA all-reduce when the batch is sharded).

Masked state updates make the same code valid under vmap for batched
per-entity TRON solves. The lane shape is generic (``lanes = jnp.shape(f0)``,
reductions over axis 0), so the same solve also drives lambda-lane stacks for
lane-batched hyperparameter sweeps (game/lanes.py): ``w`` is ``[d, L]`` (one
reg candidate per lane) or ``[S, E, L]`` (entity x lambda), and masked
commits freeze converged/diverged lanes at their last committed iterate —
per-lane ``ConvergenceReason`` — without stalling or perturbing neighbors.
The one lockstep artifact: every lane runs until ALL lanes finish, so a
fast-converging lambda can accumulate a few extra (accepted, tiny) Newton
steps vs its sequential solve — parity is ~1e-3, not bitwise
(tests/test_sweep_lanes.py documents the tolerance).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from .common import (
    ConvergenceReason,
    HvpFn,
    SolverResult,
    ValueAndGradFn,
    _norm,
    _vdot,
    as_partial,
    check_convergence,
    finite_state,
    project_box,
)

Array = jax.Array

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0




class _CGState(NamedTuple):
    step: Array
    residual: Array
    direction: Array
    rtr: Array
    it: Array
    done: Array


def _truncated_cg(
    hvp: HvpFn,
    w: Array,
    gradient: Array,
    delta: Array,
    max_cg_iterations: int,
) -> Tuple[Array, Array, Array]:
    """Approximately solve H step = -gradient within ||step|| <= delta.

    Returns (step, residual, cg_iterations). Residual r = -g - H.step is used
    by the caller for the predicted-reduction formula.
    """
    tol = 0.1 * _norm(gradient)
    r0 = -gradient
    init = _CGState(
        step=jnp.zeros_like(gradient),
        residual=r0,
        direction=r0,
        rtr=_vdot(r0, r0),
        it=jnp.zeros(jnp.shape(tol), jnp.int32),
        done=_norm(r0) <= tol,
    )

    def cond(s: _CGState):
        return jnp.logical_not(jnp.all(s.done)) & jnp.any(s.it < max_cg_iterations)

    def body(s: _CGState):
        hd = hvp(w, s.direction)
        dhd = _vdot(s.direction, hd)
        alpha = s.rtr / jnp.where(dhd != 0, dhd, 1.0)
        step_try = s.step + alpha * s.direction

        # Hits the trust-region boundary: back off to the boundary crossing.
        over = _norm(step_try) > delta
        std = _vdot(s.step, s.direction)
        sts = _vdot(s.step, s.step)
        dtd = _vdot(s.direction, s.direction)
        dsq = delta * delta
        rad = jnp.sqrt(jnp.maximum(std * std + dtd * (dsq - sts), 0.0))
        alpha_b = jnp.where(
            std >= 0,
            (dsq - sts) / jnp.where(std + rad != 0, std + rad, 1.0),
            (rad - std) / jnp.where(dtd != 0, dtd, 1.0),
        )
        alpha_eff = jnp.where(over, alpha_b, alpha)
        step_new = s.step + alpha_eff * s.direction
        residual_new = s.residual - alpha_eff * hd

        rtr_new = _vdot(residual_new, residual_new)
        beta = rtr_new / jnp.where(s.rtr != 0, s.rtr, 1.0)
        direction_new = residual_new + beta * s.direction

        converged = _norm(residual_new) <= tol
        done_new = over | converged
        it_new = s.it + 1
        hit_max = it_new >= max_cg_iterations

        keep = s.done
        return _CGState(
            step=jnp.where(keep, s.step, step_new),
            residual=jnp.where(keep, s.residual, residual_new),
            direction=jnp.where(keep, s.direction, direction_new),
            rtr=jnp.where(keep, s.rtr, rtr_new),
            it=jnp.where(keep, s.it, it_new),
            done=keep | done_new | hit_max,
        )

    final = jax.lax.while_loop(cond, body, init)
    return final.step, final.residual, final.it


class _TronState(NamedTuple):
    w: Array
    f: Array
    g: Array
    delta: Array
    it: Array
    failures: Array
    done: Array
    reason: Array
    loss_history: Array
    grad_norm_history: Array
    cg_it: Array  # inner CG iterations so far, per lane


@partial(
    jax.jit,
    static_argnames=(
        "max_iterations",
        "max_cg_iterations",
        "max_improvement_failures",
        "has_box",
    ),
)
def _solve(
    value_and_grad: ValueAndGradFn,
    hvp: HvpFn,
    w0: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    max_iterations: int,
    max_cg_iterations: int,
    max_improvement_failures: int,
    has_box: bool,
    box_lower: Array,
    box_upper: Array,
) -> SolverResult:
    dtype = w0.dtype
    box = (box_lower, box_upper) if has_box else None

    f0, g0 = value_and_grad(w0)
    lanes = jnp.shape(f0)  # () single problem / [E] entity-minor batch
    hist = jnp.full((max_iterations + 1,) + lanes, jnp.nan, dtype)

    # corrupt-at-start lane: no good iterate exists, freeze at w0 (same
    # defense as lbfgs._solve — NaN comparisons are all False, so nothing
    # below would ever terminate the lane for the right reason)
    bad0 = ~finite_state(f0, g0) & jnp.ones(lanes, bool)

    init = _TronState(
        w=w0,
        f=f0,
        g=g0,
        delta=_norm(g0),
        it=jnp.zeros(lanes, jnp.int32),
        failures=jnp.zeros(lanes, jnp.int32),
        done=bad0,
        reason=jnp.where(
            bad0, int(ConvergenceReason.NUMERICAL_DIVERGENCE), 0
        ).astype(jnp.int32),
        loss_history=hist.at[0].set(f0),
        grad_norm_history=hist.at[0].set(_norm(g0)),
        cg_it=jnp.zeros(lanes, jnp.int32),
    )

    def cond(s: _TronState):
        return jnp.logical_not(jnp.all(s.done))

    def body(s: _TronState):
        step, residual, cg_it = _truncated_cg(
            hvp, s.w, s.g, s.delta, max_cg_iterations
        )
        w_try = s.w + step
        gs = _vdot(s.g, step)
        predicted = -0.5 * (gs - _vdot(step, residual))
        f_try, g_try = value_and_grad(w_try)
        actual = s.f - f_try
        step_norm = _norm(step)

        # First-ever trial shrinks the initial bound (TRON.scala:190-193).
        delta0 = jnp.where(
            (s.it == 0) & (s.failures == 0), jnp.minimum(s.delta, step_norm), s.delta
        )

        denom = f_try - s.f - gs
        alpha = jnp.where(
            denom <= 0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * gs / jnp.where(denom != 0, denom, 1.0))
        )

        a, p = actual, predicted
        delta_new = jnp.where(
            a < _ETA0 * p,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * step_norm, _SIGMA2 * delta0),
            jnp.where(
                a < _ETA1 * p,
                jnp.maximum(_SIGMA1 * delta0, jnp.minimum(alpha * step_norm, _SIGMA2 * delta0)),
                jnp.where(
                    a < _ETA2 * p,
                    jnp.maximum(_SIGMA1 * delta0, jnp.minimum(alpha * step_norm, _SIGMA3 * delta0)),
                    jnp.maximum(delta0, jnp.minimum(alpha * step_norm, _SIGMA3 * delta0)),
                ),
            ),
        )

        # a non-finite trial is numerical divergence: never accept it (the
        # masked commit keeps the last good iterate), and keep the NaN out of
        # delta — alpha above is computed from f_try, so without this guard a
        # single NaN trial poisons the trust-region radius of the lane forever
        finite_try = finite_state(f_try, g_try)
        accepted = (actual > _ETA0 * predicted) & finite_try
        delta_new = jnp.where(finite_try, delta_new, s.delta)
        w_acc = project_box(w_try, box) if box is not None else w_try
        w_new = jnp.where(accepted, w_acc, s.w)
        f_new = jnp.where(accepted, f_try, s.f)
        g_new = jnp.where(accepted, g_try, s.g)
        it_new = jnp.where(accepted, s.it + 1, s.it)
        failures_new = jnp.where(accepted, s.failures, s.failures + 1)

        too_many_failures = failures_new >= max_improvement_failures
        reason = check_convergence(
            it_new,
            max_iterations,
            f_new,
            s.f,
            _norm(g_new),
            loss_abs_tol,
            grad_abs_tol,
            objective_not_improving=too_many_failures,
            diverged=~finite_try,
        )
        # a rejected trial alone isn't convergence; only repeated failure
        # (or divergence, which freezes the rolled-back lane) is
        reason = jnp.where(
            accepted | too_many_failures | ~finite_try, reason, 0
        ).astype(jnp.int32)
        newly_done = reason != 0

        keep = s.done
        # accepted-iteration counters diverge across lanes (rejected trials
        # don't advance it), so history writes use a row-mask select instead
        # of per-lane scatter indices
        row = (
            jnp.arange(max_iterations + 1).reshape(
                (max_iterations + 1,) + (1,) * len(lanes)
            )
            == it_new
        )
        write = row & accepted & ~keep
        lh = jnp.where(write, f_new, s.loss_history)
        gh = jnp.where(write, _norm(g_new), s.grad_norm_history)
        return _TronState(
            w=jnp.where(keep, s.w, w_new),
            f=jnp.where(keep, s.f, f_new),
            g=jnp.where(keep, s.g, g_new),
            delta=jnp.where(keep, s.delta, delta_new),
            it=jnp.where(keep, s.it, it_new),
            failures=jnp.where(keep, s.failures, failures_new),
            done=keep | newly_done,
            reason=jnp.where(keep, s.reason, reason).astype(jnp.int32),
            loss_history=lh,
            grad_norm_history=gh,
            cg_it=jnp.where(keep, s.cg_it, s.cg_it + cg_it),
        )

    final = jax.lax.while_loop(cond, body, init)
    return SolverResult(
        coefficients=final.w,
        loss=final.f,
        gradient=final.g,
        iterations=final.it,
        reason=final.reason,
        loss_history=final.loss_history,
        grad_norm_history=final.grad_norm_history,
        cg_iterations=final.cg_it,
    )


def solve_tron(
    value_and_grad: ValueAndGradFn,
    hvp: HvpFn,
    w0: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    max_iterations: int = 15,
    max_cg_iterations: int = 20,
    max_improvement_failures: int = 5,
    box_constraints: Optional[Tuple[Array, Array]] = None,
) -> SolverResult:
    has_box = box_constraints is not None
    zero = jnp.zeros_like(w0)
    lower, upper = box_constraints if has_box else (zero, zero)
    result = _solve(
        as_partial(value_and_grad),
        as_partial(hvp),
        w0,
        jnp.asarray(loss_abs_tol, w0.dtype),
        jnp.asarray(grad_abs_tol, w0.dtype),
        max_iterations,
        max_cg_iterations,
        max_improvement_failures,
        has_box,
        lower,
        upper,
    )
    obs.record_solver_metrics("tron", result)
    return result
