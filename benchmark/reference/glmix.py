"""Plain reference for logistic GLMix: objective, gradients and a coordinate-
descent sweep in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Independent of the code under test: no buckets, packing, kernels, trust
regions or L-BFGS. The fixed effect is solved by exact (damped) Newton with a
Cholesky solve of the d x d Hessian; the random effect by per-entity (damped)
Newton on Hessians built with ``segment_sum``. Both blocks are strictly convex
under L2, so their minimisers are unique and a correct solver of any kind must
land on them.

Objective (the program's convention, ``ops/glm.py``): labels in {0, 1},
l(z, y) = log(1 + e^z) - y z, and every block adds (l2 / 2) ||w||^2 over ALL
its coefficients, the intercept included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def loss(z, y):
    return jnp.logaddexp(0.0, z) - y * z


def fixed_value_grad(w, x, y, offsets, weights, l2):
    """Value and gradient of the fixed-effect block at w."""
    with HIGHEST():
        z = x @ w + offsets
        value = jnp.sum(weights * loss(z, y)) + 0.5 * l2 * jnp.dot(w, w)
        grad = x.T @ (weights * (jax.nn.sigmoid(z) - y)) + l2 * w
    return value, grad


def fixed_hessian_vector(w, v, x, y, offsets, weights, l2):
    """Hessian of the fixed-effect block at w, times v."""
    with HIGHEST():
        p = jax.nn.sigmoid(x @ w + offsets)
        return x.T @ (weights * p * (1.0 - p) * (x @ v)) + l2 * v


@jax.jit
def _fixed_newton_step(w, x, y, offsets, weights, l2):
    with HIGHEST():
        z = x @ w + offsets
        p = jax.nn.sigmoid(z)
        grad = x.T @ (weights * (p - y)) + l2 * w
        h = (x * (weights * p * (1.0 - p))[:, None]).T @ x + l2 * jnp.eye(x.shape[1], dtype=x.dtype)
        step = jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(h), grad)

        def value(wt):
            zt = x @ wt + offsets
            return jnp.sum(weights * loss(zt, y)) + 0.5 * l2 * jnp.dot(wt, wt)

        # damped: the largest of 1, 1/2, ... 1/128 that does not raise the value
        f0 = value(w)
        scales = 0.5 ** jnp.arange(8, dtype=x.dtype)
        vals = jax.vmap(lambda s: value(w - s * step))(scales)
        ok = vals <= f0
        s = jnp.where(jnp.any(ok), scales[jnp.argmax(ok)], 0.0)
    return w - s * step, jnp.linalg.norm(grad)


def solve_fixed(x, y, offsets, weights, l2, w0=None, iterations=30, rel_tol=1e-7):
    """Minimiser of the fixed-effect block by exact Newton from ``w0``."""
    w = jnp.zeros(x.shape[1], x.dtype) if w0 is None else w0
    g0 = None
    for _ in range(iterations):
        w_new, gnorm = _fixed_newton_step(w, x, y, offsets, weights, jnp.asarray(l2, x.dtype))
        gnorm = float(gnorm)
        g0 = gnorm if g0 is None else g0
        if gnorm <= rel_tol * max(g0, 1e-30):
            break
        w = w_new
    return w


def entity_value_grad(table, ex, y, entity, offsets, weights, l2):
    """Per-entity values [U] and gradients [U, S] of the random-effect block at
    the dense per-entity table [U, S]."""
    u = table.shape[0]
    with HIGHEST():
        z = jnp.einsum("ns,ns->n", ex, table[entity]) + offsets
        value = jax.ops.segment_sum(weights * loss(z, y), entity, u)
        value = value + 0.5 * l2 * jnp.sum(table * table, axis=1)
        r = weights * (jax.nn.sigmoid(z) - y)
        grad = jax.ops.segment_sum(r[:, None] * ex, entity, u) + l2 * table
    return value, grad


@jax.jit
def _entity_newton_step(table, ex, y, entity, offsets, weights, l2):
    u, s = table.shape
    with HIGHEST():
        z = jnp.einsum("ns,ns->n", ex, table[entity]) + offsets
        p = jax.nn.sigmoid(z)
        r = weights * (p - y)
        grad = jax.ops.segment_sum(r[:, None] * ex, entity, u) + l2 * table
        c = weights * p * (1.0 - p)
        h = jax.ops.segment_sum(c[:, None, None] * ex[:, :, None] * ex[:, None, :], entity, u)
        h = h + l2 * jnp.eye(s, dtype=table.dtype)
        step = jnp.linalg.solve(h, grad[..., None])[..., 0]

        def value(t):
            zt = jnp.einsum("ns,ns->n", ex, t[entity]) + offsets
            v = jax.ops.segment_sum(weights * loss(zt, y), entity, u)
            return v + 0.5 * l2 * jnp.sum(t * t, axis=1)

        f0 = value(table)
        scales = 0.5 ** jnp.arange(8, dtype=table.dtype)
        vals = jax.vmap(lambda sc: value(table - sc * step))(scales)  # [8, U]
        ok = vals <= f0[None, :]
        first = jnp.argmax(ok, axis=0)
        sc = jnp.where(jnp.any(ok, axis=0), scales[first], 0.0)
    return table - sc[:, None] * step, jnp.linalg.norm(grad)


def solve_entities(ex, y, entity, n_entities, offsets, weights, l2, table0=None,
                   iterations=30, rel_tol=1e-7):
    """Per-entity minimisers [U, S] by per-entity Newton from ``table0``."""
    table = jnp.zeros((n_entities, ex.shape[1]), ex.dtype) if table0 is None else table0
    g0 = None
    for _ in range(iterations):
        new, gnorm = _entity_newton_step(
            table, ex, y, entity, offsets, weights, jnp.asarray(l2, ex.dtype)
        )
        gnorm = float(gnorm)
        g0 = gnorm if g0 is None else g0
        if gnorm <= rel_tol * max(g0, 1e-30):
            break
        table = new
    return table


def glmix_objective(w, table, x, ex, y, entity, l2_fixed, l2_entity):
    """The whole GLMix objective at (w, table); ``table`` may be None."""
    with HIGHEST():
        z = x @ w
        value = 0.5 * l2_fixed * jnp.dot(w, w)
        if table is not None:
            z = z + jnp.einsum("ns,ns->n", ex, table[entity])
            value = value + 0.5 * l2_entity * jnp.sum(table * table)
        return value + jnp.sum(loss(z, y))


def coordinate_descent(x, ex, y, entity, n_entities, l2_fixed, l2_entity, sweeps):
    """``sweeps`` sweeps of (fixed effect, then random effect) from zero, each
    block solved to its minimiser given the other's scores. Returns (w, table)."""
    n = x.shape[0]
    ones = jnp.ones(n, x.dtype)
    w, table = None, None
    score_entity = jnp.zeros(n, x.dtype)
    for _ in range(sweeps):
        w = solve_fixed(x, y, score_entity, ones, l2_fixed, w0=w)
        with HIGHEST():
            score_fixed = x @ w
        table = solve_entities(ex, y, entity, n_entities, score_fixed, ones, l2_entity, table0=table)
        with HIGHEST():
            score_entity = jnp.einsum("ns,ns->n", ex, table[entity])
    return w, table
