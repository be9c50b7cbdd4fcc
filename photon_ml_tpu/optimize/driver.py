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
)
from .lbfgs import solve_lbfgs
from .tron import solve_tron

Array = jnp.ndarray


def optimize(
    value_and_grad: ValueAndGradFn,
    w0: Array,
    config: OptimizerConfig,
    hvp: Optional[HvpFn] = None,
    margins: Optional[MarginFns] = None,
) -> SolverResult:
    """``hvp`` is TRON's; ``margins`` are the objective as its steps, for an
    L-BFGS whose search can walk them (``lbfgs.walks_margins``)."""
    host_level = not isinstance(w0, jax.core.Tracer)
    if not host_level:
        # traced inside a jitted train function: nothing host-level to time
        # (the rule obs.record_solver_metrics follows)
        loss_tol, grad_tol = abs_tolerances(value_and_grad, w0, config.tolerance)
    else:
        # one more objective pass at zero coefficients, before every solve
        # (the enclosing fe.solve span says whose solve this is)
        solve_span = obs.current_span()
        coordinate = solve_span.attrs.get("coordinate") if solve_span else None
        with obs.span("fe.tolerances", coordinate=coordinate) as sp:
            loss_tol, grad_tol = abs_tolerances(value_and_grad, w0, config.tolerance)
            sp.sync(loss_tol, grad_tol)
    kind = config.normalized_type()

    if kind in (OptimizerType.LBFGS, OptimizerType.LBFGSB, OptimizerType.OWLQN):
        box = config.box_constraints
        return solve_lbfgs(
            value_and_grad,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=config.max_iterations,
            num_corrections=config.num_corrections,
            l1_weight=config.l1_weight if kind == OptimizerType.OWLQN else 0.0,
            box_constraints=box,
            max_line_search_iterations=config.max_line_search_iterations,
            # a host-level solve counts its passes, sink or no sink (one
            # program either way); obs.record_solver_metrics reads the count
            count_evals=host_level,
            margins=margins,
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
