"""Entity-minor packed RE solver parity vs the vmapped per-entity solver.

The packed path (game/coordinate._train_blocks_packed + the batched modes of
optimize/lbfgs.py and optimize/tron.py) solves the same per-entity problems
with the entity axis minor (TPU lane dimension). Same convex objectives, so
both paths must land on the same optimum; the iterate paths may differ
slightly (reduction-order f32 noise, shared-cursor history in batched LBFGS),
hence optimization-level tolerances.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.game.coordinate import _train_blocks_packed
from photon_ml_tpu.testing.reference_solver import train_blocks_vmapped


def _problem(seed=0, E=37, K=12, S=9, active_k=10):
    rng = np.random.default_rng(seed)
    F = rng.normal(size=(E, K, S)).astype(np.float32)
    w_true = rng.normal(size=(E, S)).astype(np.float32)
    logits = np.einsum("eks,es->ek", F, w_true)
    y = (rng.uniform(size=(E, K)) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    off = rng.normal(size=(E, K)).astype(np.float32) * 0.1
    wt = np.ones((E, K), np.float32)
    wt[:, active_k:] = 0.0  # padded rows carry zero weight
    y[:, active_k:] = 0.0
    w0 = np.zeros((E, S), np.float32)
    pm = np.zeros((E, S), np.float32)
    pp = np.ones((E, S), np.float32)
    return F, y, off, wt, w0, pm, pp


@pytest.mark.parametrize(
    "opt,l1",
    [("LBFGS", 0.0), ("TRON", 0.0), ("LBFGS", 0.05)],
    ids=["lbfgs", "tron", "owlqn"],
)
def test_packed_matches_vmapped(opt, l1):
    args = _problem()
    kwargs = dict(
        task="logistic",
        l2=0.1,
        l1=l1,
        optimizer_type=opt,
        tolerance=1e-7,
        max_iterations=80,
        num_corrections=10,
        max_cg_iterations=20,
        max_improvement_failures=5,
    )
    rv = train_blocks_vmapped(*args, **kwargs)
    rp = _train_blocks_packed(*args, **kwargs)
    np.testing.assert_allclose(
        np.asarray(rp.coefficients), np.asarray(rv.coefficients), atol=5e-3
    )
    np.testing.assert_allclose(np.asarray(rp.loss), np.asarray(rv.loss), atol=1e-4)
    # per-lane result structure matches
    assert rp.coefficients.shape == rv.coefficients.shape
    assert rp.loss_history.shape == rv.loss_history.shape
    assert rp.iterations.shape == rv.iterations.shape


def test_packed_prior_and_warm_start():
    """Prior-centered L2 (incremental training) and a warm start w0 follow
    the same algebra on both paths."""
    F, y, off, wt, w0, pm, pp = _problem(seed=3)
    rng = np.random.default_rng(7)
    w0 = rng.normal(size=w0.shape).astype(np.float32) * 0.1
    pm = rng.normal(size=pm.shape).astype(np.float32) * 0.2
    pp = (0.5 + rng.uniform(size=pp.shape)).astype(np.float32)
    kwargs = dict(
        task="logistic",
        l2=0.7,
        l1=0.0,
        optimizer_type="LBFGS",
        tolerance=1e-8,
        max_iterations=80,
        num_corrections=10,
        max_cg_iterations=20,
        max_improvement_failures=5,
    )
    rv = train_blocks_vmapped(F, y, off, wt, w0, pm, pp, **kwargs)
    rp = _train_blocks_packed(F, y, off, wt, w0, pm, pp, **kwargs)
    np.testing.assert_allclose(
        np.asarray(rp.coefficients), np.asarray(rv.coefficients), atol=5e-3
    )
    np.testing.assert_allclose(np.asarray(rp.loss), np.asarray(rv.loss), atol=1e-4)


def test_batched_lbfgs_gradient_at_optimum():
    """The packed solve's final per-lane gradient norms are small (true
    stationary points, not an artifact of matching a mis-converged twin)."""
    args = _problem(seed=5)
    kwargs = dict(
        task="logistic",
        l2=0.3,
        l1=0.0,
        optimizer_type="LBFGS",
        tolerance=1e-9,
        max_iterations=120,
        num_corrections=10,
        max_cg_iterations=20,
        max_improvement_failures=5,
    )
    rp = _train_blocks_packed(*args, **kwargs)
    gn = np.linalg.norm(np.asarray(rp.gradient), axis=1)
    assert np.all(gn < 1e-2)
    assert np.all(np.asarray(rp.reason) != 0)  # every lane converged
