"""Seeded one-hot click data for the sparse fixed-effect cells.

ONE data set per configuration (``scale.data_seed`` draws every value),
MIRRORED by the run's seed as benchmark/data.py mirrors the GLMix cells': a
sign for every feature column but the intercept. A row's values become +1 or
-1, the truth's coefficients flip with their columns, every margin and label
stays what it was, and IEEE arithmetic is symmetric under negation: every seed
does the same gathers, sums, scatter-adds, line-search trials and stopping
decisions, bit for bit.

The law (the configuration's ``assumed``). A row holds one column from each of
the ``fields`` and the intercept, LAST, every value 1: ``len(fields) + 1`` slots
a row, none padded. Field f owns the contiguous column range
[start_f, start_f + C_f), the ranges laid out in the order given. Inside a
field the value of popularity rank r is drawn by ``field_quotas(n, C_f)`` rows,
a FIXED quota under Zipf 1.1 (floor(n p_r), the remainder one row each down
the ranks that follow): the shapes, the number of columns seen and every
column's count depend on no seed. Rank r sits at column
start_f + (r * STRIDE) mod C_f (STRIDE is a prime larger than any field, so the
map is a bijection and the popular ids are scattered over the range, as hashed
ids are); which rows hold which value is a permutation drawn from ``data_seed``.

The truth: a coefficient for every feature column, N(0, 1 / F) for F fields, so
a row's margin has standard deviation 1; the intercept is set by bisection so
that the mean click probability over the training rows is ``click_rate``.
Labels are Bernoulli from ONE uniform a row, y = [u < sigmoid(margin)], the
margin summed in float64 on the host BEFORE the mirror: the same labels under
every seed and on every backend.

No dense matrix exists anywhere: the generator's product is the ``[n, F + 1]``
column index array, built field by field.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

STRIDE = 2_654_435_761  # prime, past every field's cardinality


@dataclasses.dataclass
class Law:
    """What the generator needs besides the permutations (host arrays)."""

    cardinalities: Tuple[int, ...]
    starts: np.ndarray  # i64[F] first column of each field
    dim: int  # sum of the cardinalities + 1 (the intercept, last)
    beta: np.ndarray  # f32[dim] truth; beta[-1] the intercept (``set_intercept``)
    n_rows: int  # the training rows the quotas add up to
    quotas: List[np.ndarray]  # per field: rows owned by the ranks that own any


@dataclasses.dataclass
class Rows:
    cols: np.ndarray  # i32[n, F + 1], last column = dim - 1 (the intercept)
    labels: np.ndarray  # f32[n] in {0, 1}
    margin: np.ndarray  # f64[n] at the truth


def field_quotas(n_rows: int, cardinality: int, zipf: float) -> np.ndarray:
    """Rows owned by the values of rank 0 .. k-1: floor(n p_r) under
    p_r ~ (r + 1)^-zipf over ALL ``cardinality`` ranks, the rows the floors
    leave over handed out one each to the ranks after the last whole one. Only
    the k ranks that own a row are returned; sums to ``n_rows``; no seed."""
    ranks = np.arange(1, cardinality + 1, dtype=np.float64)
    p = ranks ** -zipf
    p /= p.sum()
    whole = np.floor(n_rows * p).astype(np.int64)
    left = int(n_rows - whole.sum())
    first_empty = int(np.searchsorted(-whole, 0, side="left"))  # whole is non-increasing
    if first_empty + left <= cardinality:
        whole[first_empty:first_empty + left] += 1
    else:  # a field smaller than its left-over: round-robin from the top
        whole += left // cardinality
        whole[: left % cardinality] += 1
    k = int(np.count_nonzero(whole))
    assert whole[:k].all() and int(whole.sum()) == n_rows
    return whole[:k]


def draw_law(data_seed: int, cardinalities: Sequence[int], n_rows: int, zipf: float) -> Law:
    cards = tuple(int(c) for c in cardinalities)
    starts = np.concatenate([[0], np.cumsum(cards)[:-1]]).astype(np.int64)
    dim = int(sum(cards)) + 1
    rng = np.random.default_rng([int(data_seed), 1])
    beta = rng.standard_normal(dim, dtype=np.float32) / np.float32(np.sqrt(len(cards)))
    beta[-1] = 0.0
    quotas = [field_quotas(n_rows, c, zipf) for c in cards]
    return Law(cardinalities=cards, starts=starts, dim=dim, beta=beta, n_rows=n_rows, quotas=quotas)


def _field_columns(law: Law, f: int, ranks: np.ndarray) -> np.ndarray:
    c = law.cardinalities[f]
    return law.starts[f] + (ranks.astype(np.int64) * STRIDE) % c


def draw_columns(data_seed: int, law: Law, n_sample: int = 0, stream: int = 0) -> np.ndarray:
    """i32[n, F + 1]: the training rows (``n_sample`` 0: every quota spent
    exactly) or ``n_sample`` rows whose field values are drawn from the
    training rows' own (validation: ``stream`` 1), field by field."""
    n_rows = law.n_rows
    n_out = n_sample or n_rows
    cols = np.empty((n_out, len(law.cardinalities) + 1), np.int32)
    cols[:, -1] = law.dim - 1
    for f, quotas in enumerate(law.quotas):
        rng = np.random.default_rng([int(data_seed), 2, int(stream), f])
        if n_sample:
            # a uniform position in the expanded quota sequence = a training row's value
            ranks = np.searchsorted(np.cumsum(quotas), rng.integers(0, n_rows, n_sample), side="right")
        else:
            ranks = np.repeat(np.arange(len(quotas), dtype=np.int64), quotas)[rng.permutation(n_rows)]
        cols[:, f] = _field_columns(law, f, ranks)
    return cols


def margins(law: Law, cols: np.ndarray) -> np.ndarray:
    """f64[n]: the truth's margin of every row (values are all 1)."""
    z = np.zeros(len(cols), np.float64)
    for f in range(cols.shape[1]):
        z += law.beta[cols[:, f]]
    return z


def set_intercept(law: Law, cols: np.ndarray, click_rate: float) -> None:
    """beta[-1] such that the mean of sigmoid(margin) over ``cols`` is
    ``click_rate``: bisection in float64, no seed."""
    law.beta[-1] = 0.0
    z = margins(law, cols)
    lo, hi = -20.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(z + mid)))) > click_rate:
            hi = mid
        else:
            lo = mid
    law.beta[-1] = np.float32(0.5 * (lo + hi))


def draw_labels(data_seed: int, margin: np.ndarray, stream: int = 0) -> np.ndarray:
    """y = [u < sigmoid(margin)], ONE uniform a row, a generator per stream."""
    rng = np.random.default_rng([int(data_seed), 3, int(stream)])
    u = rng.random(len(margin))
    return (u < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)


def draw_rows(data_seed: int, law: Law, n_sample: int = 0, stream: int = 0) -> Rows:
    cols = draw_columns(data_seed, law, n_sample, stream)
    z = margins(law, cols)
    return Rows(cols=cols, labels=draw_labels(data_seed, z, stream), margin=z)


def draw_signs(seed: int, dim: int) -> np.ndarray:
    """The run's seed as data: +1 or -1 for every feature column, +1 for the
    intercept (benchmark/data.py ``draw_mirror``'s rule at one width)."""
    rng = np.random.default_rng(seed)  # takes any whole number, past 2**31 too
    s = (2 * rng.integers(0, 2, size=dim, dtype=np.int8) - 1).astype(np.float32)
    s[-1] = 1.0
    return s


def triplets(cols: np.ndarray, signs: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The rows as the (rows, cols, vals) COO a ``RawDataset`` shard holds:
    row-major, a row's slots in field order, the intercept last; values are
    the mirror's signs of their columns."""
    n, k = cols.shape
    flat = cols.reshape(-1).astype(np.int64)
    return np.repeat(np.arange(n, dtype=np.int64), k), flat, signs[flat].astype(np.float64)


def columns_seen(cols: np.ndarray, dim: int) -> np.ndarray:
    """bool[dim]: the columns some row holds."""
    seen = np.zeros(dim, bool)
    seen[cols.reshape(-1)] = True
    return seen
