"""Plain reference for ``logistic-criteo-4chip``: logistic regression with L2
over REAL-valued sparse rows (13 count columns and 26 ids a row), float64 on
the host, from the cell's own ``[n, k]`` column and value arrays and the
seed's signs, over ALL of its rows: no mesh, no device, no code of
``photon_ml_tpu/``.

The semantics are benchmark/reference/glm_sparse.py's: labels in {0, 1},
l(z, y) = log(1 + e^z) - y z summed over rows, (l2 / 2) ||w||^2 over every
coefficient, the intercept included. The rows are laid out once
(:func:`slots`: the columns some row holds, each slot's index among them, the
signed values), and :func:`passes` gives the value and the gradient at a
point over them, each gradient entry with the sum of its terms' magnitudes
(what its float32 rounding is proportional to). Off the columns a row holds
the gradient is l2 w exactly, so only its norm is kept.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass
class Slots:
    seen: np.ndarray  # bool[d]: the columns some row holds
    touched: np.ndarray  # i64[t]: their indices
    local: np.ndarray  # i32[n k]: each slot's index into ``touched``
    x: np.ndarray  # f64[n, k]: the values times their columns' signs
    y: np.ndarray  # f64[n]


def slots(cols: np.ndarray, vals: np.ndarray, signs: np.ndarray, y: np.ndarray) -> Slots:
    d = len(signs)
    flat = cols.reshape(-1)
    seen = np.zeros(d, bool)
    seen[flat] = True
    touched = np.flatnonzero(seen)
    remap = np.zeros(d, np.int32)
    remap[touched] = np.arange(len(touched), dtype=np.int32)
    return Slots(seen=seen, touched=touched, local=remap[flat], x=vals.astype(np.float64) * signs[cols],
                 y=np.asarray(y, np.float64))


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def passes(w, s: Slots, l2: float, gather_dtype=None) -> Dict[str, object]:
    """F(w) over every row; its gradient on the touched columns (``grad``,
    ``grad_scale``: each entry's sum of term magnitudes) and the squared norm
    of the rest of it (``off_sq``: l2^2 times that of w off them), in float64.
    ``gather_dtype`` rounds the gathered coefficients first: what a gather in
    that precision reads (the lower-precision control of the comparison)."""
    wt = np.asarray(w)[s.touched]
    picked = wt[s.local]
    if gather_dtype is not None:
        picked = picked.astype(gather_dtype)
    z = np.sum(s.x * picked.astype(np.float64).reshape(s.x.shape), axis=1)
    terms = (s.x * (_sigmoid(z) - s.y)[:, None]).reshape(-1)
    w64, wt64 = np.asarray(w, np.float64), wt.astype(np.float64)
    norm_sq = float(np.dot(w64, w64))
    t = len(wt)
    return {
        "value": float(np.sum(np.logaddexp(0.0, z) - s.y * z)) + 0.5 * l2 * norm_sq,
        "grad": np.bincount(s.local, weights=terms, minlength=t) + l2 * wt64,
        "grad_scale": np.bincount(s.local, weights=np.abs(terms), minlength=t) + l2 * np.abs(wt64),
        "off_sq": l2 * l2 * max(norm_sq - float(np.dot(wt64, wt64)), 0.0),
    }
