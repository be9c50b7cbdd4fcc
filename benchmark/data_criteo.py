"""Seeded click rows at the Criteo 1TB set's shape for ``logistic-criteo-4chip``.

The law of benchmark/data_sparse.py (ONE data set per configuration, drawn
from ``scale.data_seed``; fixed Zipf quotas inside a field; ranks scattered by
the multiplicative hash; the truth N(0, 1 / F) a column; the intercept set by
bisection for the click rate; labels from ONE uniform a row; the run's seed a
sign for every feature column but the intercept), with what that file's
one-hot rows do not have: the set's 13 integer count features, present in
every row as real values.

A row holds 40 slots, in column order: the 13 count columns [0, 13), one id
from each of the 26 categorical fields (field f owns [13 + start_f, 13 + start_f
+ C_f), the fields in published order), and the intercept, LAST (column d - 1).
A count column's value is log(1 + x), as DLRM's preprocessing feeds it, with
x = floor(exp(mu_j + N(0, 1))) and mu_j = j / 4 for the j-th count column: a
column's median count runs from 0 (j = 0) to 20 (j = 12), and its value is 0
where x is (those slots are stored all the same: 40 a row, none padded). The
seed's mirror turns a value v into -v; the truth's coefficient flips with its
column, so every margin, label, pass and stopping decision is the same under
every seed, bit for bit.

No dense matrix exists anywhere: the products are ``[n, 40]`` column indices
and values.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

from . import data_sparse as base

NUMERIC = 13  # the set's integer features, in front of the fields


@dataclasses.dataclass
class Rows:
    cols: np.ndarray  # i32[n, 40]: 13 count columns, 26 field ids, the intercept
    vals: np.ndarray  # f32[n, 40]: log(1 + x) of the counts, then 1 (before the mirror)
    labels: np.ndarray  # f32[n] in {0, 1}
    margin: np.ndarray  # f64[n] at the truth


def draw_law(data_seed: int, cardinalities: Sequence[int], n_rows: int, zipf: float) -> base.Law:
    """``data_sparse.draw_law`` with the fields moved past the count columns:
    d = 13 + sum of the cardinalities + 1, the truth N(0, 1 / 39) for every
    column (count columns included), the intercept 0 until ``set_intercept``."""
    law = base.draw_law(data_seed, cardinalities, n_rows, zipf)
    dim = NUMERIC + law.dim
    rng = np.random.default_rng([int(data_seed), 4])
    beta = rng.standard_normal(dim, dtype=np.float32) / np.float32(np.sqrt(NUMERIC + len(law.cardinalities)))
    beta[-1] = 0.0
    return dataclasses.replace(law, starts=law.starts + NUMERIC, dim=dim, beta=beta)


def count_values(data_seed: int, n: int, stream: int = 0) -> np.ndarray:
    """f32[n, 13]: log(1 + x) with x = floor(exp(j / 4 + N(0, 1))), one
    generator a stream."""
    rng = np.random.default_rng([int(data_seed), 5, int(stream)])
    mu = np.arange(NUMERIC, dtype=np.float64) / 4.0
    x = np.floor(np.exp(mu + rng.standard_normal((n, NUMERIC))))
    return np.log1p(x).astype(np.float32)


def draw_features(data_seed: int, law: base.Law, n_sample: int = 0, stream: int = 0):
    """(i32[n, 40] columns, f32[n, 40] values) of the training rows
    (``n_sample`` 0: every field's quota spent exactly) or of ``n_sample``
    rows whose ids are drawn from the training rows' own (validation:
    ``stream`` 1)."""
    fields = base.draw_columns(data_seed, law, n_sample, stream)  # [n, 26 + 1]
    n = len(fields)
    cols = np.empty((n, NUMERIC + fields.shape[1]), np.int32)
    cols[:, :NUMERIC] = np.arange(NUMERIC, dtype=np.int32)
    cols[:, NUMERIC:] = fields
    vals = np.ones(cols.shape, np.float32)
    vals[:, :NUMERIC] = count_values(data_seed, n, stream)
    return cols, vals


def margins(law: base.Law, cols: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """f64[n]: the truth's margin of every row."""
    z = np.zeros(len(cols), np.float64)
    for f in range(cols.shape[1]):
        z += law.beta[cols[:, f]].astype(np.float64) * vals[:, f]
    return z


def set_intercept(law: base.Law, cols: np.ndarray, vals: np.ndarray, click_rate: float) -> None:
    """beta[-1] such that the mean of sigmoid(margin) over these rows is
    ``click_rate``: ``data_sparse.set_intercept``'s bisection over real-valued
    rows, no seed."""
    law.beta[-1] = 0.0
    z = margins(law, cols, vals)
    lo, hi = -20.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(z + mid)))) > click_rate:
            hi = mid
        else:
            lo = mid
    law.beta[-1] = np.float32(0.5 * (lo + hi))


def rows(data_seed: int, law: base.Law, cols: np.ndarray, vals: np.ndarray, stream: int = 0) -> Rows:
    """The rows with their margins at the truth and their labels."""
    z = margins(law, cols, vals)
    return Rows(cols=cols, vals=vals, labels=base.draw_labels(data_seed, z, stream), margin=z)


def triplets(cols: np.ndarray, vals: np.ndarray, signs: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The rows as a ``RawDataset`` shard's (rows, cols, vals): row-major, a
    row's slots in column order, each value times its column's sign."""
    n, k = cols.shape
    flat = cols.reshape(-1).astype(np.int64)
    return (np.repeat(np.arange(n, dtype=np.int64), k), flat,
            (vals.reshape(-1) * signs[flat]).astype(np.float64))
