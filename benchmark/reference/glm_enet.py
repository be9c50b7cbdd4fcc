"""Plain reference for a count-response GLM under elastic net: Poisson value,
gradient and Hessian-vector product with weights, offsets, factors and shifts,
the elastic-net objective, the KKT residual of a point, and an independent
solver of the same objective.

Two halves, neither sharing code with ``photon_ml_tpu/optimize`` or ``ops``:

- the *pass* functions are straightforward float32 ``jax.numpy`` under
  ``jax.default_matmul_precision("highest")``, computed in row blocks so that
  one pass fits beside a 6.4 GB matrix (a block's temporaries are block-sized);
- :func:`solve_path` is float64 NumPy on a sample: proximal Newton outer steps
  over an active set, each quadratic model minimised by cyclic coordinate
  descent on the active columns' Gram matrix (glmnet's scheme: Friedman,
  Hastie, Tibshirani 2010, section 3), run to a KKT residual of
  1e-9 * ||g(0)||_inf. No quasi-Newton history, no orthant, no line search on
  a pseudo-gradient.

Objective (the program's convention, ``ops/losses.py`` and ``ops/glm.py``):
l(z, y) = exp(z) - y z (the log y! term, constant in w, is left out);
x' = (x - shift) * factor is never materialised by the pass functions;

    F(w) = sum_i weight_i l(w . x'_i + offset_i, y_i) + (l2 / 2) ||w||^2 + l1 ||w||_1

Departures from glmnet, all upstream's (photon-ml penalises every coefficient
it optimises): the INTERCEPT is penalised like any coefficient, by l2 and by
l1; the loss is a sum over rows, not a mean, so a weight lambda here is
n * lambda there; the elastic-net split is l1 = alpha * lambda,
l2 = (1 - alpha) * lambda with no factor 1/2 on the ridge part beyond the one
in F.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
BLOCK_ROWS = 65_536


def _effective(w, factors, shifts):
    """(coefficients on raw x, constant added to every margin)."""
    eff = w * factors
    return eff, -jnp.dot(eff, shifts)


@functools.partial(jax.jit, static_argnames=("block",))
def _smooth_pass(w, v, x, y, offsets, weights, factors, shifts, block):
    """(sum of weighted losses, gradient, Hessian times v) of the smooth part
    WITHOUT the ridge term, in transformed space, by a scan over row blocks."""
    n, d = x.shape
    with HIGHEST():
        eff, c = _effective(w, factors, shifts)
        eff_v, c_v = _effective(v, factors, shifts)

        def rows(start, size):
            xb = jax.lax.dynamic_slice(x, (start, 0), (size, d))
            cut = lambda a: jax.lax.dynamic_slice(a, (start,), (size,))
            yb, ob, wb = cut(y), cut(offsets), cut(weights)
            z = xb @ eff + c + ob
            mu = jnp.exp(z)
            r = wb * (mu - yb)
            h = wb * mu * (xb @ eff_v + c_v)
            return (jnp.sum(wb * (mu - yb * z)), xb.T @ r, jnp.sum(r), xb.T @ h, jnp.sum(h))

        def step(carry, i):
            return jax.tree.map(jnp.add, carry, rows(i * block, block)), None

        zero = (jnp.zeros((), x.dtype), jnp.zeros(d, x.dtype), jnp.zeros((), x.dtype),
                jnp.zeros(d, x.dtype), jnp.zeros((), x.dtype))
        whole = n // block
        total, _ = jax.lax.scan(step, zero, jnp.arange(whole))
        if whole * block < n:
            total = jax.tree.map(jnp.add, total, rows(whole * block, n - whole * block))
        value, xr, sr, xh, sh = total
        # d/dw' of w'.((x - shift) * factor): factor * (x^T r - shift * sum r)
        grad = factors * (xr - shifts * sr)
        hv = factors * (xh - shifts * sh)
    return value, grad, hv


def _norm_vectors(d, dtype, factors, shifts):
    factors = jnp.ones(d, dtype) if factors is None else jnp.asarray(factors, dtype)
    shifts = jnp.zeros(d, dtype) if shifts is None else jnp.asarray(shifts, dtype)
    return factors, shifts


def value_grad(w, x, y, offsets, weights, l2, factors=None, shifts=None, block=BLOCK_ROWS):
    """Value and gradient of the SMOOTH part (losses + ridge) at w."""
    factors, shifts = _norm_vectors(x.shape[1], x.dtype, factors, shifts)
    value, grad, _ = _smooth_pass(w, jnp.zeros_like(w), x, y, offsets, weights, factors, shifts,
                                  min(block, x.shape[0]))
    return value + 0.5 * l2 * jnp.dot(w, w), grad + l2 * w


def hessian_vector(w, v, x, y, offsets, weights, l2, factors=None, shifts=None, block=BLOCK_ROWS):
    """Hessian of the smooth part at w, times v."""
    factors, shifts = _norm_vectors(x.shape[1], x.dtype, factors, shifts)
    _, _, hv = _smooth_pass(w, v, x, y, offsets, weights, factors, shifts, min(block, x.shape[0]))
    return hv + l2 * v


def enet_objective(w, smooth_value, l1):
    """F(w) from the smooth part's value at w."""
    return smooth_value + l1 * jnp.sum(jnp.abs(w))


def kkt_residual(w, smooth_grad, l1) -> jnp.ndarray:
    """Per-coefficient distance from stationarity of F at w, ``smooth_grad``
    the gradient of the smooth part there (ridge included): for w_j != 0
    |g_j + l1 sign(w_j)|, for w_j = 0 max(0, |g_j| - l1)."""
    at_zero = jnp.maximum(jnp.abs(smooth_grad) - l1, 0.0)
    return jnp.where(w != 0, jnp.abs(smooth_grad + l1 * jnp.sign(w)), at_zero)


# -- the independent solver: float64 NumPy ---------------------------------------


def transformed(x, factors=None, shifts=None) -> np.ndarray:
    """x' = (x - shift) * factor as a float64 host matrix (a SAMPLE's)."""
    xt = np.asarray(x, np.float64)
    if shifts is not None:
        xt = xt - np.asarray(shifts, np.float64)
    if factors is not None:
        xt = xt * np.asarray(factors, np.float64)
    return xt


def _smooth64(w, xt, y, offsets, weights, l2):
    z = xt @ w + offsets
    mu = np.exp(z)
    value = float(np.sum(weights * (mu - y * z)) + 0.5 * l2 * (w @ w))
    return value, xt.T @ (weights * (mu - y)) + l2 * w, mu


def kkt_residual64(w, grad, l1) -> np.ndarray:
    at_zero = np.maximum(np.abs(grad) - l1, 0.0)
    return np.where(w != 0, np.abs(grad + l1 * np.sign(w)), at_zero)


def _lasso_qp(h, g, w, l1, tol=1e-12, passes=200):
    """argmin_u  g.(u - w) + (u - w)^T h (u - w) / 2 + l1 ||u||_1 by cyclic
    coordinate descent (soft threshold), from u = w: a pass over every
    coordinate, then passes over the current support until it is still
    (glmnet's active-set cycling). ``h`` holds the ridge. Without an l1 term
    the model is a plain quadratic: one exact Newton step."""
    if l1 == 0.0:
        return w - np.linalg.solve(h, g)
    u = w.copy()
    q = g.copy()  # gradient of the quadratic model at u
    diag = np.diag(h).copy()
    limit = tol * max(float(np.max(np.abs(g))), 1e-300)

    def cycle(coordinates) -> float:
        moved = 0.0
        for j in coordinates:
            b = q[j] - diag[j] * u[j]
            new = -np.sign(b) * max(abs(b) - l1, 0.0) / diag[j]
            delta = new - u[j]
            if delta != 0.0:
                q[:] += h[:, j] * delta
                u[j] = new
                moved = max(moved, abs(delta) * diag[j])
        return moved

    for _ in range(passes):
        if cycle(range(len(u))) <= limit:
            break
        for _ in range(10 * passes):
            if cycle(np.flatnonzero(u)) <= limit:
                break
    return u


def solve_enet(xt, y, offsets, weights, l1, l2, w0=None, kkt_tol=1e-9, g0_inf=None,
               outer=100) -> Tuple[np.ndarray, float]:
    """Minimiser of F over the rows of ``xt`` (already transformed, float64):
    proximal Newton on the active set {w_j != 0} + {|g_j| > l1}. Returns
    (w, its KKT residual / ||g(0)||_inf)."""
    d = xt.shape[1]
    w = np.zeros(d) if w0 is None else np.asarray(w0, np.float64).copy()
    if g0_inf is None:
        g0_inf = float(np.max(np.abs(_smooth64(np.zeros(d), xt, y, offsets, weights, l2)[1])))
    res = np.inf
    for _ in range(outer):
        value, grad, mu = _smooth64(w, xt, y, offsets, weights, l2)
        res = float(np.max(kkt_residual64(w, grad, l1))) / g0_inf
        if res <= kkt_tol:
            break
        active = np.flatnonzero((w != 0) | (np.abs(grad) > l1))
        xa = xt[:, active]
        h = xa.T @ (xa * (weights * mu)[:, None]) + l2 * np.eye(len(active))
        u = _lasso_qp(h, grad[active], w[active], l1)
        step = np.zeros(d)
        step[active] = u - w[active]
        f_now = value + l1 * np.sum(np.abs(w))
        t = 1.0
        while t > 1e-10:  # damped: never raise F
            trial = w + t * step
            f_trial = _smooth64(trial, xt, y, offsets, weights, l2)[0] + l1 * np.sum(np.abs(trial))
            if np.isfinite(f_trial) and f_trial <= f_now:
                break
            t *= 0.5
        w = w + t * step
    return w, res


def solve_path(xt, y, offsets, weights, lambdas: List[float], alpha: float,
               kkt_tol=1e-9) -> List[Tuple[np.ndarray, float]]:
    """The warm-started path over ``lambdas`` (as given: descending), each
    total weight split l1 = alpha * lambda, l2 = (1 - alpha) * lambda."""
    y, offsets, weights = (np.asarray(a, np.float64) for a in (y, offsets, weights))
    out, w = [], None
    for lam in lambdas:
        l1, l2 = alpha * lam, (1.0 - alpha) * lam
        g0 = _smooth64(np.zeros(xt.shape[1]), xt, y, offsets, weights, l2)[1]
        w, res = solve_enet(xt, y, offsets, weights, l1, l2, w0=w, kkt_tol=kkt_tol,
                            g0_inf=float(np.max(np.abs(g0))))
        out.append((w.copy(), res))
    return out


def lambda_max(grad_at_zero, alpha: float, intercept: Optional[int]) -> float:
    """glmnet's start of a path: max_j |g_j(0)| / alpha over the FEATURE
    columns (the intercept's gradient at zero, n |1 - mean y|, is left out: it
    would set the whole path, and upstream penalises it as any coefficient)."""
    g = np.abs(np.asarray(grad_at_zero, np.float64))
    if intercept is not None:
        g = np.delete(g, intercept)
    return float(np.max(g)) / alpha
