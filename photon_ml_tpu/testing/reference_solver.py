"""The tests' reference for the random-effect block solve.

``train_blocks_vmapped`` has the contract of ``game/coordinate.py``
``_train_blocks_packed`` and none of its layout: one masked solve per entity
block under ``jax.vmap``, entity axis leading, so a lane's arithmetic depends
on its own block alone and the result is bit-exact across bucket shapes.
Nothing on the training path calls it. A test puts it in the packed solver's
place (the ``use_re_solver`` fixture of ``tests/conftest.py``) to tell a
difference in the data layout from one in the solver's reduction order.
"""

from __future__ import annotations

from functools import partial

import jax

from ..ops.features import FeatureMatrix, LabeledBatch
from ..ops.glm import GLMObjective
from ..ops.losses import get_loss
from ..optimize import SolverResult, solve_lbfgs, solve_tron
from ..optimize.common import abs_tolerances

Array = jax.Array


@partial(
    jax.jit,
    static_argnames=(
        "task",
        "l2",
        "l1",
        "optimizer_type",
        "tolerance",
        "max_iterations",
        "num_corrections",
        "max_cg_iterations",
        "max_improvement_failures",
    ),
)
def train_blocks_vmapped(
    features: Array,  # [E, K, S]
    labels: Array,
    offsets: Array,
    weights: Array,
    w0: Array,  # [E, S]
    prior_mean: Array,  # [E, S]; zeros = plain L2
    prior_prec: Array,  # [E, S]; ones = plain L2
    *,
    task: str,
    l2: float,
    l1: float,
    optimizer_type: str,
    tolerance: float,
    max_iterations: int,
    num_corrections: int,
    max_cg_iterations: int,
    max_improvement_failures: int,
) -> SolverResult:
    """One vmapped masked solve over all entity blocks."""
    loss = get_loss(task)
    S = features.shape[-1]

    def solve_one(feat, y, off, wt, w0_e, pm_e, pp_e):
        batch = LabeledBatch(
            features=FeatureMatrix(dim=S, dense=feat),
            labels=y,
            offsets=off,
            weights=wt,
        )
        obj = GLMObjective(
            loss=loss, batch=batch, l2=l2, prior_mean=pm_e, prior_precision=pp_e
        )
        loss_tol, grad_tol = abs_tolerances(obj.value_and_grad, w0_e, tolerance)
        if optimizer_type == "TRON":
            return solve_tron(
                obj.value_and_grad,
                obj.hessian_vector,
                w0_e,
                loss_tol,
                grad_tol,
                max_iterations=max_iterations,
                max_cg_iterations=max_cg_iterations,
                max_improvement_failures=max_improvement_failures,
            )
        return solve_lbfgs(
            obj.value_and_grad,
            w0_e,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            num_corrections=num_corrections,
            l1_weight=l1,
        )

    return jax.vmap(solve_one)(
        features, labels, offsets, weights, w0, prior_mean, prior_prec
    )
