"""Optimizer dispatch: config -> solver run.

The functional analogue of the reference's OptimizerFactory + Optimizer.optimize
(photon-api .../optimization/OptimizerFactory.scala:30-74,
photon-lib .../optimization/Optimizer.scala:161-185): computes the relative ->
absolute tolerance conversion from the zero state, dispatches on optimizer
type (LBFGS / OWLQN / LBFGSB / TRON), and runs the whole solve on device.

``value_and_grad`` (and ``hvp`` for TRON) close over their data; whether that
data is a device-sharded global batch (fixed effect) or one lane of a vmapped
per-entity block (random effect) is invisible here — the reference needed a
Distributed/SingleNode class pair for this (SURVEY.md §2.2), we need one
function.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import obs
from .common import (
    HvpFn,
    MarginFns,
    OptimizerConfig,
    OptimizerType,
    SolverResult,
    ValueAndGradFn,
    abs_tolerances,
    walking_tolerances,
)
from .lbfgs import solve_lbfgs, walks_margins
from .tron import solve_tron

Array = jnp.ndarray


def optimize(
    value_and_grad: ValueAndGradFn,
    w0: Array,
    config: OptimizerConfig,
    hvp: Optional[HvpFn] = None,
    margins: Optional[MarginFns] = None,
    from_zero: bool = False,
) -> SolverResult:
    """``hvp`` is TRON's; ``margins`` are the objective as its steps, for an
    L-BFGS whose search can walk them (``lbfgs.walks_margins``). ``from_zero``
    says w0 is zeros, as the caller knows (no model to start from): a walking
    solve then starts from the state at zero that sets its tolerances."""
    host_level = not isinstance(w0, jax.core.Tracer)
    kind = config.normalized_type()
    l1_weight = config.l1_weight if kind == OptimizerType.OWLQN else 0.0
    # a walking solve's tolerance pass takes the margins at zero as the
    # offsets (no matvec) and hands the solve its start; every other solve
    # runs the pass at zero over ``value_and_grad`` and evaluates its own start
    walk = kind != OptimizerType.TRON and walks_margins(
        margins, l1_weight, config.box_constraints, batched=False
    )

    def tolerances():
        if walk:
            return walking_tolerances(margins, w0, config.tolerance, from_zero)
        return (*abs_tolerances(value_and_grad, w0, config.tolerance), None)

    if not host_level:
        # traced inside a jitted train function: nothing host-level to time
        # (the rule obs.record_solver_metrics follows)
        loss_tol, grad_tol, start = tolerances()
    else:
        # one more objective pass at zero coefficients, before every solve
        # (the enclosing fe.solve span says whose solve this is): a scatter-add
        # alone where the solve walks margins (``matvec`` False)
        solve_span = obs.current_span()
        coordinate = solve_span.attrs.get("coordinate") if solve_span else None
        if solve_span is not None and solve_span.name == "fe.solve":
            # host-known: whether the solve starts from the state at zero or
            # from a start evaluated at w0 (its own, or the tolerance pass's)
            solve_span.attrs["start"] = "zero_state" if walk and from_zero else "evaluated"
        with obs.span("fe.tolerances", coordinate=coordinate, matvec=not walk) as sp:
            loss_tol, grad_tol, start = tolerances()
            sp.sync(loss_tol, grad_tol)

    if kind in (OptimizerType.LBFGS, OptimizerType.LBFGSB, OptimizerType.OWLQN):
        box = config.box_constraints
        return solve_lbfgs(
            value_and_grad,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=config.max_iterations,
            num_corrections=config.num_corrections,
            l1_weight=l1_weight,
            box_constraints=box,
            max_line_search_iterations=config.max_line_search_iterations,
            # a host-level solve counts its passes, sink or no sink (one
            # program either way); obs.record_solver_metrics reads the count
            count_evals=host_level,
            margins=margins,
            start=start,
        )
    if kind == OptimizerType.TRON:
        if hvp is None:
            raise ValueError("TRON requires a Hessian-vector-product function")
        return solve_tron(
            value_and_grad,
            hvp,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=config.max_iterations,
            max_cg_iterations=config.max_cg_iterations,
            max_improvement_failures=config.max_improvement_failures,
            box_constraints=config.box_constraints,
        )
    raise ValueError(f"Unknown optimizer type: {config.optimizer_type!r}")
