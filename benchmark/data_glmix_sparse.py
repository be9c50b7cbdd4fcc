"""Seeded click data for the GLMix-over-sparse-ids cell: the one-hot law of
benchmark/data_sparse.py (every function of it used as it stands) with a USER
read off each row and a per-user term added to the truth.

What is added to the sparse law (the configuration's ``assumed``):

- a row's user is its value in field ``user_field`` (the 22M-value field):
  the id is that slot's global column, so the users' law is the field's own
  Zipf 1.1 over the shard's rows, by fixed quota, seed-free;
- the per-user feature shard holds the fields ``user_shard_fields`` (the four
  smallest) re-indexed to contiguous local ranges in the order given, and an
  intercept LAST: ``len(fields) + 1`` slots a row, every value 1;
- the truth adds to a row's margin a per-user term: a coefficient
  N(0, ``user_feature_var`` / F_u) for every (user, user-shard feature column)
  pair and a per-user intercept N(0, ``user_intercept_var``), each drawn from a
  hash of the pair (:func:`pair_normal`: no table of users x columns exists);
  the global intercept is then re-set by bisection for the click rate, and
  labels are Bernoulli from ONE uniform a row as before.

The run's seed MIRRORS the data: a sign for every feature column of BOTH
shards (the intercepts keep +1), drawn independently; values become +1 or -1,
the truth's coefficients flip with their columns, margins and labels stay what
they were, so every seed does the same arithmetic bit for bit.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

from . import data_sparse as gen


@dataclasses.dataclass
class UserShard:
    """The per-user feature shard's geometry (host arrays)."""

    fields: Tuple[int, ...]  # indices into the law's fields
    starts: np.ndarray  # i64[F_u] first LOCAL column of each field
    dim: int  # sum of the fields' cardinalities + 1 (the intercept, last)


@dataclasses.dataclass
class Rows:
    cols: np.ndarray  # i32[n, F + 1] global-shard columns, intercept last
    user_cols: np.ndarray  # i32[n, F_u + 1] user-shard columns, intercept last
    user: np.ndarray  # i64[n] the row's user id (its column in the user field)
    labels: np.ndarray  # f32[n] in {0, 1}
    margin: np.ndarray  # f64[n] at the truth (both terms)


def user_shard(law: gen.Law, fields: Sequence[int]) -> UserShard:
    fields = tuple(int(f) for f in fields)
    cards = [law.cardinalities[f] for f in fields]
    starts = np.concatenate([[0], np.cumsum(cards)[:-1]]).astype(np.int64)
    return UserShard(fields=fields, starts=starts, dim=int(sum(cards)) + 1)


def user_columns(law: gen.Law, shard: UserShard, cols: np.ndarray) -> np.ndarray:
    """i32[n, F_u + 1]: the rows' values in the shard's fields, re-indexed to
    the shard's local ranges; the shard's intercept (dim - 1) last."""
    out = np.empty((len(cols), len(shard.fields) + 1), np.int32)
    for j, f in enumerate(shard.fields):
        out[:, j] = cols[:, f].astype(np.int64) - law.starts[f] + shard.starts[j]
    out[:, -1] = shard.dim - 1
    return out


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser (vectorised; wraps like the C one)."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def pair_normal(data_seed: int, user: np.ndarray, col: np.ndarray, dim: int) -> np.ndarray:
    """f64, standard normal, a pure function of (data_seed, user, col): two
    hashes of the pair, Box-Muller. The truth's per-user coefficients are
    read off it wherever a (user, column) pair is met: rows, validation rows
    and the reference all see the same value."""
    with np.errstate(over="ignore"):
        key = user.astype(np.uint64) * np.uint64(dim) + col.astype(np.uint64)
        key = key + np.uint64((int(data_seed) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
        a = _mix64(key)
        b = _mix64(a + np.uint64(0x9E3779B97F4A7C15))
    u1 = ((a >> np.uint64(11)).astype(np.float64) + 0.5) / float(1 << 53)
    u2 = ((b >> np.uint64(11)).astype(np.float64) + 0.5) / float(1 << 53)
    return np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)


def user_margins(data_seed: int, shard: UserShard, user: np.ndarray, user_cols: np.ndarray,
                 feature_var: float, intercept_var: float) -> np.ndarray:
    """f64[n]: the truth's per-user term of every row (values are all 1)."""
    n_feature = user_cols.shape[1] - 1
    z = np.zeros(len(user), np.float64)
    scale = np.sqrt(feature_var / n_feature)
    for j in range(n_feature):
        z += scale * pair_normal(data_seed, user, user_cols[:, j], shard.dim)
    return z + np.sqrt(intercept_var) * pair_normal(data_seed, user, user_cols[:, -1], shard.dim)


def set_intercept(law: gen.Law, margin_without: np.ndarray, click_rate: float) -> None:
    """law.beta[-1] such that the mean of sigmoid(margin) over the training
    rows is ``click_rate``, ``margin_without`` being every other term of it:
    data_sparse.set_intercept's bisection, in float64, no seed."""
    lo, hi = -20.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(margin_without + mid)))) > click_rate:
            hi = mid
        else:
            lo = mid
    law.beta[-1] = np.float32(0.5 * (lo + hi))


def draw_rows(data_seed: int, law: gen.Law, shard: UserShard, scale: dict,
              n_sample: int = 0, stream: int = 0) -> Rows:
    """The training rows (``n_sample`` 0; the law's intercept must be set) or
    ``n_sample`` validation rows, with their users, user-shard columns and
    labels under the whole truth."""
    cols = gen.draw_columns(data_seed, law, n_sample, stream)
    user = cols[:, scale["user_field"]].astype(np.int64)
    ucols = user_columns(law, shard, cols)
    z = gen.margins(law, cols) + user_margins(
        data_seed, shard, user, ucols, scale["user_feature_var"], scale["user_intercept_var"]
    )
    return Rows(cols=cols, user_cols=ucols, user=user,
                labels=gen.draw_labels(data_seed, z, stream), margin=z)


def draw_user_signs(seed: int, dim: int) -> np.ndarray:
    """The user shard's mirror: +1 or -1 for every feature column, +1 for the
    intercept; a stream of its own beside ``data_sparse.draw_signs``'s."""
    rng = np.random.default_rng([int(seed), 1])
    s = (2 * rng.integers(0, 2, size=dim, dtype=np.int8) - 1).astype(np.float32)
    s[-1] = 1.0
    return s
