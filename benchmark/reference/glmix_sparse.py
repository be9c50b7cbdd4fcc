"""Plain reference for GLMix over SPARSE id features: one fixed effect over a
sparse shard and one per-user random effect over another sparse shard, by
coordinate descent over residuals, straight from raw ``(rows, cols, vals)``
triplets.

Independent of the code under test (nothing of ``photon_ml_tpu`` is imported):
no ELL layout, no entity blocks, no size buckets, no subspace projection, no
packed lanes, no L-BFGS. The fixed effect is ``reference/glm_sparse.py`` as it
stands (float64 Newton-CG over the touched columns; float32 ``highest`` passes
for the full-size checks). The random effect is this file's: float64 NumPy, a
user's model living on the (user, column) PAIRS its active rows hold, each user
solved by exact damped Newton on its own small dense system.

The published description (photon-ml ``RandomEffectDataset``,
``LinearSubspaceProjector``; KDD'16 GLMix, section 4): a user trains on at
most ``cap`` of its rows, the ``cap`` rows of smallest priority, each weighted
count / cap; its other rows are PASSIVE: never trained on, but scored, so they
reach the fixed effect's residual. A user's coefficients live on the columns
its ACTIVE rows hold; a passive row's feature outside them meets coefficient 0.
The program's priority (a splitmix64 mix of the row index, seed 0) is its
documented rule (``game/data.py`` ``_hash64``): :func:`row_priority` is a copy,
pinned to the program's by a test.

Objective convention (the program's, as in ``reference/glm_sparse.py``):
labels in {0, 1}, l(z, y) = log(1 + e^z) - y z, every block's L2 over all its
coefficients, intercepts included; losses are sums over rows.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from . import glm_sparse as fixed


def row_priority(n_rows: int, seed: int = 0) -> np.ndarray:
    """The program's reservoir priority of rows 0..n-1."""
    x = np.arange(n_rows, dtype=np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def active_weights(user: np.ndarray, priority: np.ndarray, cap: Optional[int], n_users: int) -> np.ndarray:
    """f64[n] training weight of each row: 1 where the row's user has at most
    ``cap`` rows; count / cap on the ``cap`` rows of smallest ``priority`` of a
    user over the cap, 0 on its other rows."""
    user = np.asarray(user, np.int64)
    if cap is None:
        return np.ones(len(user))
    counts = np.bincount(user, minlength=n_users)
    order = np.lexsort((np.asarray(priority), user))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.empty(len(user), np.int64)
    rank[order] = np.arange(len(user)) - starts[user[order]]
    scale = np.where(counts > cap, counts / float(cap), 1.0)
    return np.where(rank < cap, scale[user], 0.0)


@dataclasses.dataclass
class UserBlock:
    """The random effect over the rows given: raw slots, and the support."""

    user: np.ndarray  # i64[n] in [0, n_users)
    cols: np.ndarray  # i64[n, F] columns of the user shard
    vals: np.ndarray  # f64[n, F]
    dim: int  # the user shard's width
    n_users: int
    l2: float
    weights: np.ndarray  # f64[n] from active_weights: 0 on passive rows
    pairs: np.ndarray  # i64[P] user * dim + col of the support, ascending
    slot_pair: np.ndarray  # i64[n, F] index into pairs, -1 = outside the support

    @property
    def pair_user(self) -> np.ndarray:
        return self.pairs // self.dim


def user_block(user, cols, vals, dim: int, n_users: int, l2: float, weights) -> UserBlock:
    """The support is the (user, column) pairs the ACTIVE rows hold with a
    non-zero value; every slot of every row, passive ones included, is then
    looked up in it."""
    user = np.asarray(user, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float64)
    weights = np.asarray(weights, np.float64)
    keys = user[:, None] * dim + cols
    held = (weights > 0)[:, None] & (vals != 0)
    pairs = np.unique(keys[held])
    pos = np.minimum(np.searchsorted(pairs, keys), max(len(pairs) - 1, 0))
    found = (pairs[pos] == keys) if len(pairs) else np.zeros_like(keys, bool)
    return UserBlock(user=user, cols=cols, vals=vals, dim=dim, n_users=n_users, l2=float(l2),
                     weights=weights, pairs=pairs, slot_pair=np.where(found, pos, -1))


def user_scores(block: UserBlock, table: np.ndarray) -> np.ndarray:
    """f64[n]: the random effect's score of EVERY row, passive ones included;
    a slot outside its user's support meets coefficient 0."""
    picked = np.where(block.slot_pair >= 0, table[np.maximum(block.slot_pair, 0)], 0.0)
    return np.sum(block.vals * picked, axis=1)


def _loss(z, y):
    return np.logaddexp(0.0, z) - y * z


def user_value_grad(block: UserBlock, table, y, offsets) -> Tuple[float, np.ndarray]:
    """(the random effect's OWN objective summed over users, its gradient on
    the support [P]): active rows at their weights, given the other
    coordinate's scores as offsets."""
    z = user_scores(block, table) + offsets
    r = block.weights * (1.0 / (1.0 + np.exp(-z)) - y)
    inside = block.slot_pair >= 0
    grad = np.bincount(block.slot_pair[inside], weights=(r[:, None] * block.vals)[inside],
                       minlength=len(block.pairs))
    value = float(np.sum(block.weights * _loss(z, y)) + 0.5 * block.l2 * np.dot(table, table))
    return value, grad + block.l2 * table


def solve_users(block: UserBlock, y, offsets, table0: Optional[np.ndarray] = None,
                rel_tol: float = 1e-10, max_newton: int = 50) -> np.ndarray:
    """f64[P]: every user's exact minimiser given ``offsets``, by damped
    Newton on its own dense system (its active rows x its support columns),
    run to a gradient of ``rel_tol`` times its norm at the start."""
    table = np.zeros(len(block.pairs)) if table0 is None else np.array(table0, np.float64)
    y = np.asarray(y, np.float64)
    offsets = np.asarray(offsets, np.float64)
    active = np.flatnonzero(block.weights > 0)
    order = active[np.argsort(block.user[active], kind="stable")]
    bounds = np.searchsorted(block.user[order], np.arange(block.n_users + 1))
    pair_bounds = np.searchsorted(block.pair_user, np.arange(block.n_users + 1))
    for u in range(block.n_users):
        rows = order[bounds[u]:bounds[u + 1]]
        p0, p1 = pair_bounds[u], pair_bounds[u + 1]
        if len(rows) == 0 or p1 == p0:
            continue
        x = np.zeros((len(rows), p1 - p0))
        local = block.slot_pair[rows] - p0
        inside = block.slot_pair[rows] >= 0
        rr, ff = np.nonzero(inside)
        np.add.at(x, (rr, local[rr, ff]), block.vals[rows][rr, ff])
        wt, yy, off = block.weights[rows], y[rows], offsets[rows]
        w = table[p0:p1].copy()

        def value_grad(w):
            z = x @ w + off
            p = 1.0 / (1.0 + np.exp(-z))
            g = x.T @ (wt * (p - yy)) + block.l2 * w
            return float(np.sum(wt * _loss(z, yy)) + 0.5 * block.l2 * np.dot(w, w)), g, p

        f, g, p = value_grad(w)
        g0 = max(float(np.linalg.norm(g)), 1e-300)
        for _ in range(max_newton):
            if np.linalg.norm(g) <= rel_tol * max(g0, 1.0):
                break
            h = x.T @ (x * (wt * p * (1.0 - p))[:, None]) + block.l2 * np.eye(p1 - p0)
            step = np.linalg.solve(h, -g)
            t = 1.0
            for _ in range(40):
                f_t, g_t, p_t = value_grad(w + t * step)
                if f_t <= f:
                    break
                t *= 0.5
            w, f, g, p = w + t * step, f_t, g_t, p_t
        table[p0:p1] = w
    return table


# -- the whole model --------------------------------------------------------------------


def fixed_margins(touched, w_touched, rows, cols, vals, n_rows: int) -> np.ndarray:
    """f64[n]: the fixed effect's score of every row from its coefficients on
    the touched columns (every other column's is 0)."""
    cols = np.asarray(cols, np.int64)
    pos = np.minimum(np.searchsorted(touched, cols), max(len(touched) - 1, 0))
    picked = np.where(touched[pos] == cols, w_touched[pos], 0.0)
    return np.bincount(np.asarray(rows, np.int64), weights=np.asarray(vals, np.float64) * picked,
                       minlength=n_rows)


def model_objective(z_fixed, w_fixed, l2_fixed: float, block: UserBlock, table, y) -> float:
    """The whole model's objective: the loss of ALL rows at weight 1 under the
    summed scores, plus both blocks' L2."""
    z = np.asarray(z_fixed, np.float64) + user_scores(block, table)
    return float(np.sum(_loss(z, np.asarray(y, np.float64)))
                 + 0.5 * l2_fixed * np.dot(w_fixed, w_fixed) + 0.5 * block.l2 * np.dot(table, table))


def coordinate_descent(rows, cols, vals, y, l2_fixed: float, block: UserBlock, sweeps: int):
    """``sweeps`` sweeps of (fixed effect, random effect) from the zero model,
    each block solved to its minimiser given the other's scores. Returns
    (touched columns, their fixed-effect coefficients, the user table [P],
    {the fixed solves' residuals})."""
    y = np.asarray(y, np.float64)
    n = len(y)
    ones = np.ones(n)
    table = np.zeros(len(block.pairs))
    scores_user = np.zeros(n)
    touched = w = None
    residuals = []
    for _ in range(sweeps):
        touched, w, info = fixed.solve(rows, cols, vals, y, scores_user, ones, l2_fixed)
        residuals.append(info["residual"])
        scores_fixed = fixed_margins(touched, w, rows, cols, vals, n)
        table = solve_users(block, y, scores_fixed, table0=table)
        scores_user = user_scores(block, table)
    return touched, w, table, {"fixed_residuals": residuals}
