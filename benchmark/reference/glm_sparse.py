"""Plain reference for logistic regression with L2 over a SPARSE feature space:
value, gradient and Hessian-vector product straight from the raw
``(rows, cols, vals)`` triplets, and an independent solver of the same
objective.

Two halves, neither sharing code with ``photon_ml_tpu/`` (no ``FeatureMatrix``,
no ELL or sorted-COO layout, no L-BFGS):

- the *pass* functions are float32 ``jax.numpy`` under
  ``jax.default_matmul_precision("highest")``: a margin is
  ``segment_sum(vals * w[cols], rows)``, a gradient
  ``segment_sum(vals * r[rows], cols)``, the triplets taken in blocks so that a
  pass over 28M of them fits beside a 4.4 GB solver state (a block's
  temporaries are block-sized; the accumulators are the [n] margins and the
  [d] gradient);
- :func:`solve` is float64 NumPy on a sample (``np.bincount`` for both sums):
  damped Newton steps, each system solved by conjugate gradients on the
  Hessian-vector product, run to a gradient of 1e-9 * ||g(0)||. It works on the
  columns the sample TOUCHES only: under L2 from a zero start the coefficient
  of a column no row holds has gradient l2 * w = 0 and never moves, so the
  minimiser is 0 there exactly.

Objective (the program's convention, ``ops/losses.py`` and ``ops/glm.py``):
labels in {0, 1}, l(z, y) = log(1 + e^z) - y z,

    F(w) = sum_i weight_i l(sum_j x_ij w_j + offset_i, y_i) + (l2 / 2) ||w||^2

over ALL coefficients, the intercept included (upstream penalises every
coefficient it optimises); the loss is a sum over rows, not a mean.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
BLOCK_TRIPLETS = 1 << 20


def loss(z, y):
    return jnp.logaddexp(0.0, z) - y * z


# -- float32 passes over triplets, in blocks -----------------------------------------


def _blocked(a, block: int):
    """[m] -> [ceil(m / block), block], the tail padded with zeros (a triplet
    with value 0 adds nothing to row 0 or column 0)."""
    pad = -len(a) % block
    if pad:
        a = jnp.concatenate([a, jnp.zeros(pad, a.dtype)])
    return a.reshape(-1, block)


@functools.partial(jax.jit, static_argnames=("n_rows", "block", "gather_dtype"))
def margins(w, rows, cols, vals, n_rows: int, block: int = BLOCK_TRIPLETS, gather_dtype=None):
    """f32[n]: sum_j x_ij w_j of every row, a scan over blocks of triplets."""
    block = min(block, max(len(rows), 1))

    def step(z, rcv):
        r, c, v = rcv
        picked = w[c]
        if gather_dtype is not None:  # what a lower-precision gather would read
            picked = picked.astype(gather_dtype).astype(w.dtype)
        return z + jax.ops.segment_sum(v * picked, r, num_segments=n_rows), None

    with HIGHEST():
        z, _ = jax.lax.scan(step, jnp.zeros(n_rows, w.dtype),
                            tuple(_blocked(a, block) for a in (rows, cols, vals)))
    return z


@functools.partial(jax.jit, static_argnames=("dim", "block"))
def rmatvec(r, rows, cols, vals, dim: int, block: int = BLOCK_TRIPLETS):
    """f32[d]: sum_i x_ij r_i of every column, a scan over blocks of triplets."""
    block = min(block, max(len(rows), 1))

    def step(g, rcv):
        i, c, v = rcv
        return g + jax.ops.segment_sum(v * r[i], c, num_segments=dim), None

    with HIGHEST():
        g, _ = jax.lax.scan(step, jnp.zeros(dim, r.dtype),
                            tuple(_blocked(a, block) for a in (rows, cols, vals)))
    return g


def value_grad(w, rows, cols, vals, y, offsets, weights, l2, block: int = BLOCK_TRIPLETS,
               gather_dtype=None):
    """(F(w), its gradient) in float32."""
    z = margins(w, rows, cols, vals, n_rows=len(y), block=block, gather_dtype=gather_dtype) + offsets
    with HIGHEST():
        value = jnp.sum(weights * loss(z, y)) + 0.5 * l2 * jnp.dot(w, w)
        r = weights * (jax.nn.sigmoid(z) - y)
    return value, rmatvec(r, rows, cols, vals, dim=w.shape[0], block=block) + l2 * w


def hessian_vector(w, v, rows, cols, vals, y, offsets, weights, l2, block: int = BLOCK_TRIPLETS):
    """The Hessian of F at w, times v, in float32."""
    n = len(y)
    p = jax.nn.sigmoid(margins(w, rows, cols, vals, n_rows=n, block=block) + offsets)
    c = weights * p * (1.0 - p) * margins(v, rows, cols, vals, n_rows=n, block=block)
    return rmatvec(c, rows, cols, vals, dim=w.shape[0], block=block) + l2 * v


# -- the independent solver: float64 NumPy, Newton-CG over the touched columns ---------


def compact(cols: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(the distinct columns, ascending; each triplet's index into them)."""
    touched, local = np.unique(np.asarray(cols), return_inverse=True)
    return touched, local.reshape(-1)


def _margins64(w, rows, local, vals, n):
    return np.bincount(rows, weights=vals * w[local], minlength=n)


def _rmatvec64(r, rows, local, vals, k):
    return np.bincount(local, weights=vals * r[rows], minlength=k)


def _value64(z, y, weights, w, l2):
    return float(np.sum(weights * (np.logaddexp(0.0, z) - y * z)) + 0.5 * l2 * np.dot(w, w))


def solve(rows, cols, vals, y, offsets, weights, l2: float, rel_tol: float = 1e-9,
          max_newton: int = 50, max_cg: int = 500) -> Tuple[np.ndarray, np.ndarray, dict]:
    """(touched columns, their coefficients at the minimiser, {value,
    residual, newton steps, Hv products}) in float64. ``residual`` is
    ||g|| / ||g(0)||; every other column's coefficient is 0."""
    rows = np.asarray(rows, np.int64)
    vals = np.asarray(vals, np.float64)
    y, offsets, weights = (np.asarray(a, np.float64) for a in (y, offsets, weights))
    n = len(y)
    touched, local = compact(cols)
    k = len(touched)
    w = np.zeros(k)

    def grad_at(w):
        z = _margins64(w, rows, local, vals, n) + offsets
        p = 1.0 / (1.0 + np.exp(-z))
        return z, p, _rmatvec64(weights * (p - y), rows, local, vals, k) + l2 * w

    z, p, g = grad_at(w)
    g0 = float(np.linalg.norm(g))
    f = _value64(z, y, weights, w, l2)
    steps = products = 0
    while np.linalg.norm(g) > rel_tol * g0 and steps < max_newton:
        c = weights * p * (1.0 - p)

        def hv(v):
            return _rmatvec64(c * _margins64(v, rows, local, vals, n), rows, local, vals, k) + l2 * v

        # conjugate gradients on H s = -g: to a thousandth of this step's
        # gradient, never past a tenth of the outer target
        s, r = np.zeros(k), -g.copy()
        q, rr = r.copy(), float(np.dot(r, r))
        target = max(0.1 * rel_tol * g0, 1e-3 * float(np.linalg.norm(g))) ** 2
        for _ in range(max_cg):
            if rr <= target:
                break
            hq = hv(q)
            products += 1
            alpha = rr / float(np.dot(q, hq))
            s += alpha * q
            r -= alpha * hq
            rr_new = float(np.dot(r, r))
            q = r + (rr_new / rr) * q
            rr = rr_new
        # damping: halve until the objective falls (convex: a full step nearly always does)
        t = 1.0
        for _ in range(30):
            z_t, p_t, g_t = grad_at(w + t * s)
            f_t = _value64(z_t, y, weights, w + t * s, l2)
            if f_t <= f:
                break
            t *= 0.5
        w, z, p, g, f = w + t * s, z_t, p_t, g_t, f_t
        steps += 1
    return touched, w, {
        "value": f, "residual": float(np.linalg.norm(g)) / g0, "newton_steps": steps,
        "hv_products": products, "touched": int(k),
    }


def objective64(w_touched, touched_local, rows, vals, y, offsets, weights, l2: float) -> float:
    """F in float64 at a point given on the touched columns (``touched_local``
    from :func:`compact`)."""
    rows = np.asarray(rows, np.int64)
    z = _margins64(np.asarray(w_touched, np.float64), rows, touched_local,
                   np.asarray(vals, np.float64), len(y)) + offsets
    return _value64(z, np.asarray(y, np.float64), np.asarray(weights, np.float64),
                    np.asarray(w_touched, np.float64), l2)
