"""Shared optimizer contracts: convergence reasons, configs, results.

TPU re-design of the reference's Optimizer base
(photon-lib .../optimization/Optimizer.scala:35-238): instead of a mutable
iterate-until-converged driver object, each solver is a pure function running
its whole loop inside ``lax.while_loop`` with *masked* state updates — the
same compiled code therefore serves the reference's two execution modes:

- scalar: one (possibly device-sharded) problem — the fixed-effect solve;
- vmapped: thousands of per-entity problems advancing in lockstep with
  per-lane ``done`` freezing — the random-effect solve (SURVEY.md §7.3).

Convergence semantics are parity-matched to Optimizer.scala:126-139:
tolerances are *relative*, converted to absolute using the state at zero
coefficients (loss(0) * tol, ||grad(0)|| * tol; Optimizer.scala:65-69,171),
and the reasons are checked in the reference's order.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Callable w -> (value, gradient)
ValueAndGradFn = Callable[[Array], Tuple[Array, Array]]
# Callable (w, v) -> H(w) v
HvpFn = Callable[[Array, Array], Array]


class MarginFns(NamedTuple):
    """An objective F(w) = sum_i l(z_i) + reg(w) whose margins z are AFFINE in
    w, as its steps: what a line search needs to try step lengths along a
    direction p without a pass over the features (lbfgs.py, the ``margins``
    search). ``value_and_grad(w) == grad_from_margins(margins(w), w)`` and
    ``margins(w + t p) == margins(w) + t * direction_margins(p)``."""

    margins: Callable[[Array], Array]  # w -> z (one matvec)
    direction_margins: Callable[[Array], Array]  # p -> u (one matvec)
    # (z, u, t, w, p) -> F(w + t p), dF/dt: row-length sums, no features
    value_and_slope: Callable[[Array, Array, Array, Array, Array], Tuple[Array, Array]]
    grad_from_margins: Callable[[Array, Array], Tuple[Array, Array]]  # (z, w) -> F, grad (one rmatvec)
    zero_margins: Callable[[], Array]  # () -> margins(0), with no matvec (a GLM's offsets)


class SolveStart(NamedTuple):
    """Where a solve that walks margins starts, handed to it as operands: the
    objective, its gradient and the margins at w0, and the passes over the
    features taking them made (int32; the solve counts its own on top): one
    of each at w0, a scatter-add alone where w0 is zero."""

    f: Array
    g: Array
    z: Array
    matvecs: Array
    rmatvecs: Array


class ConvergenceReason(enum.IntEnum):
    """Reference: photon-lib .../optimization/ConvergenceReason.scala."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    OBJECTIVE_NOT_IMPROVING = 2
    FUNCTION_VALUES_CONVERGED = 3
    GRADIENT_CONVERGED = 4
    # Not in the reference enum: the lane's objective or gradient went
    # non-finite. The solvers roll the lane back to its last good iterate and
    # freeze it — without this, NaN comparisons (all False) sail straight
    # through every tolerance test below and the lane exits with a spurious
    # OBJECTIVE_NOT_IMPROVING after burning its whole line-search budget.
    NUMERICAL_DIVERGENCE = 5


class OptimizerType(str, enum.Enum):
    LBFGS = "LBFGS"
    OWLQN = "OWLQN"
    LBFGSB = "LBFGSB"
    TRON = "TRON"


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Mirrors the reference's OptimizerConfig + regularization plumbing.

    Defaults are the reference's (LBFGS.scala:149-154, TRON.scala:252-258).
    ``l1_weight`` routes LBFGS -> OWL-QN (reference: OptimizerFactory.scala:30-74).
    ``box_constraints`` = (lower[d], upper[d]): LBFGS/LBFGSB run the
    gradient-projection L-BFGS-B scheme (projected gradient + projected
    line-search trials, lbfgs.py; reference LBFGSB.scala:39-92); TRON projects
    after each accepted step (OptimizationUtils.projectCoefficientsToSubspace).
    """

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    tolerance: float = 1e-7
    max_iterations: int = 100
    num_corrections: int = 10
    l1_weight: float = 0.0
    box_constraints: Optional[Tuple[Array, Array]] = None
    max_line_search_iterations: int = 25
    # TRON-specific
    max_improvement_failures: int = 5
    max_cg_iterations: int = 20

    def normalized_type(self) -> OptimizerType:
        t = OptimizerType(self.optimizer_type)
        if t == OptimizerType.LBFGS and self.l1_weight > 0.0:
            return OptimizerType.OWLQN
        return t


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SolverResult:
    """Final solver state plus fixed-size per-iteration history
    (the functional OptimizationStatesTracker, Optimizer.scala /
    OptimizationStatesTracker.scala:32-121)."""

    coefficients: Array
    loss: Array
    gradient: Array
    iterations: Array  # i32 scalar
    reason: Array  # i32 scalar, ConvergenceReason code
    loss_history: Array  # f[max_iter + 1], NaN-padded
    grad_norm_history: Array  # f[max_iter + 1], NaN-padded
    # i32, shaped like ``iterations``: TRON's inner CG iterations summed
    # over the solve (one Hessian-vector product each); 0 for L-BFGS/OWL-QN
    cg_iterations: Array
    # OWL-QN solves alone set these (i32, shaped like ``iterations``); None
    # elsewhere, so the other solvers' programs have no such output:
    # objective (value-and-gradient) evaluations the solve issued, the first
    # one and every line-search trial; coefficients the orthant projection
    # set to zero, summed over the accepted steps; non-zero coefficients of
    # the final iterate (in the space the solver ran in)
    line_search_evals: Optional[Array] = None
    orthant_zeroed: Optional[Array] = None
    nonzeros: Optional[Array] = None
    # a counting L-BFGS solve whose search walked margins alone (i32): the
    # passes over the features its start and its iterations made
    # (``SolveStart``: a start from the state at zero is that state's one
    # rmatvec, no matvec). None where every evaluation is a pass of each kind
    # (``line_search_evals`` then counts them)
    matvecs: Optional[Array] = None
    rmatvecs: Optional[Array] = None

    @property
    def converged(self) -> Array:
        return self.reason != ConvergenceReason.NOT_CONVERGED


def project_box(w: Array, box: Optional[Tuple[Array, Array]]) -> Array:
    """Clamp coefficients into [lower, upper] (OptimizationUtils.scala:34-66)."""
    if box is None:
        return w
    lower, upper = box
    return jnp.clip(w, lower, upper)


def check_convergence(
    it: Array,
    max_iterations: int,
    loss: Array,
    prev_loss: Array,
    grad_norm: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    objective_not_improving: Array,
    diverged: Optional[Array] = None,
) -> Array:
    """Reason code in the reference's precedence order (Optimizer.scala:126-139).

    ``diverged`` (per-lane bool) takes precedence over every other reason:
    a non-finite loss/gradient fails the tolerance comparisons silently (NaN
    compares are all False), so without the explicit flag a diverged lane
    would fall through to OBJECTIVE_NOT_IMPROVING or MAX_ITERATIONS.
    """
    reason = jnp.where(
        grad_norm <= grad_abs_tol, ConvergenceReason.GRADIENT_CONVERGED, 0
    )
    reason = jnp.where(
        jnp.abs(loss - prev_loss) <= loss_abs_tol,
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        reason,
    )
    reason = jnp.where(
        objective_not_improving, ConvergenceReason.OBJECTIVE_NOT_IMPROVING, reason
    )
    reason = jnp.where(it >= max_iterations, ConvergenceReason.MAX_ITERATIONS, reason)
    if diverged is not None:
        reason = jnp.where(
            diverged, ConvergenceReason.NUMERICAL_DIVERGENCE, reason
        )
    return reason.astype(jnp.int32)


def finite_state(f: Array, g: Array) -> Array:
    """Per-lane "this (loss, gradient) pair is numerically sound": scalar for
    1-D gradients, [E] for entity-minor stacks [d, E] (axis-0 reduction like
    :func:`_norm`)."""
    return jnp.isfinite(f) & jnp.all(jnp.isfinite(g), axis=0)


def as_partial(fn):
    """Wrap a callable as a jax.tree_util.Partial so it can flow through jit
    as a DYNAMIC argument: the jit cache keys on the underlying function
    identity + pytree structure, so fresh objective objects of the same
    structure reuse compiled solvers instead of recompiling."""
    if isinstance(fn, jax.tree_util.Partial):
        return fn
    return jax.tree_util.Partial(fn)


def as_margin_fns(margins) -> MarginFns:
    """``margins`` (any sequence in ``MarginFns`` order) with each function a
    jit-cache-stable ``Partial`` (``as_partial``)."""
    return MarginFns(*map(as_partial, margins))


def _start(f0: Array, g0: Array, z0: Array, matvecs: int, rmatvecs: int) -> SolveStart:
    return SolveStart(f0, g0, z0, jnp.asarray(matvecs, jnp.int32), jnp.asarray(rmatvecs, jnp.int32))


def margin_start(margins: MarginFns, w0: Array) -> SolveStart:
    """The start of a walking solve at ``w0``: one matvec, one rmatvec."""
    z0 = margins.margins(w0)
    f0, g0 = margins.grad_from_margins(z0, w0)
    return _start(f0, g0, z0, 1, 1)


@jax.jit
def _abs_tolerances_impl(
    value_and_grad, zero_like: Array, tolerance: Array,
    margins: Optional[MarginFns] = None, start_at: Optional[Array] = None,
):
    if margins is None:
        f0, g0 = value_and_grad(jnp.zeros_like(zero_like))
        return jnp.abs(f0) * tolerance, _norm(g0) * tolerance
    # a GLM's margins are X eff(w) + shift(w) + offsets, and at w = 0 both
    # eff and shift are 0: the state at zero is one scatter-add, no gather
    zeros = jnp.zeros_like(zero_like)
    z0 = margins.zero_margins()
    f0, g0 = margins.grad_from_margins(z0, zeros)
    # a solve from zero starts from this very state
    start = _start(f0, g0, z0, 0, 1) if start_at is None else margin_start(margins, start_at)
    return jnp.abs(f0) * tolerance, _norm(g0) * tolerance, start


def abs_tolerances(
    value_and_grad: ValueAndGradFn, zero_like: Array, tolerance: float
) -> Tuple[Array, Array]:
    """Absolute tolerances from the state at zero coefficients
    (Optimizer.scala:65-69 + :171)."""
    return _abs_tolerances_impl(
        as_partial(value_and_grad), zero_like, jnp.asarray(tolerance, zero_like.dtype)
    )


def walking_tolerances(
    margins: MarginFns, w0: Array, tolerance: float, from_zero: bool
) -> Tuple[Array, Array, SolveStart]:
    """``abs_tolerances`` of a solve that walks margins, and its start, in
    one program: the state at zero from ``zero_margins`` (no matvec); a solve
    ``from_zero`` (w0 is zeros, as the caller knows) starts from that state,
    any other from margins(w0) and its gradient."""
    return _abs_tolerances_impl(
        None, w0, jnp.asarray(tolerance, w0.dtype), as_margin_fns(margins),
        None if from_zero else w0,
    )


def _norm(v: Array) -> Array:
    # axis-0 reduction: identical to the full norm for 1-D coefficient
    # vectors, and per-problem norms for entity-minor batched stacks [d, E]
    return jnp.sqrt(jnp.sum(v * v, axis=0))


def _vdot(a: Array, b: Array) -> Array:
    """Coefficient-axis dot: scalar for 1-D operands, per-lane [E] for
    entity-minor stacks [d, E]. 1-D keeps ``jnp.dot`` — bit-identical to the
    historical solver path (a fused multiply+reduce associates differently,
    which would break the vmapped path's bucket-shape exactness)."""
    if a.ndim == 1:
        return jnp.dot(a, b)
    return jnp.sum(a * b, axis=0)
