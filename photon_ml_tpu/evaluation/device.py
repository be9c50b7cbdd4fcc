"""Device-side validation metrics: per-coordinate evaluation without a host
round trip per update.

The reference evaluates validation data after EVERY coordinate update
(CoordinateDescent.scala:312-333). Keeping that default semantics cheap on
TPU means the metric math must run where the scores already are: one jitted
call computes every requested metric and a single scalar-dict fetch crosses
the host boundary (round-4 verdict item 5 — the host sort-based AUC per
update would otherwise dominate large sweeps).

Parity: `auc` mirrors evaluators.area_under_roc_curve (weighted trapezoidal
tie handling, AreaUnderROCCurveLocalEvaluator.scala:33-72) — the dynamic
tie-group bincount becomes a fixed-size ``segment_sum`` keyed by the cumsum
of tie boundaries (num_segments = n, an upper bound). NaN is returned for
single-class batches exactly like the host version.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

POSITIVE_THRESHOLD = 0.5


def auc(s, y, w):
    order = jnp.argsort(-s, stable=True)
    s, y, w = s[order], y[order], w[order]
    pos = jnp.where(y > POSITIVE_THRESHOLD, w, 0.0)
    neg = jnp.where(y > POSITIVE_THRESHOLD, 0.0, w)
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]]
    )
    gid = jnp.cumsum(boundary) - 1
    n = s.shape[0]
    gp = jax.ops.segment_sum(pos, gid, num_segments=n)
    gn = jax.ops.segment_sum(neg, gid, num_segments=n)
    cum_before = jnp.concatenate([jnp.zeros((1,), gp.dtype), jnp.cumsum(gp)[:-1]])
    raw = jnp.sum(cum_before * gn + gp * gn / 2.0)
    tp, tn = gp.sum(), gn.sum()
    return jnp.where((tp == 0.0) | (tn == 0.0), jnp.nan, raw / (tp * tn))


def rmse(s, y, w):
    return jnp.sqrt(jnp.sum(w * (s - y) ** 2) / jnp.sum(w))


def _mean(loss, s, y, w):
    return jnp.sum(w * loss) / jnp.sum(w)


def logistic_loss(s, y, w):
    yb = jnp.where(y > POSITIVE_THRESHOLD, 1.0, 0.0)
    loss = jnp.log1p(jnp.exp(-jnp.abs(s))) + jnp.maximum(s, 0.0) - yb * s
    return _mean(loss, s, y, w)


def poisson_loss(s, y, w):
    return _mean(jnp.exp(s) - y * s, s, y, w)


def squared_loss(s, y, w):
    # host parity: the squared loss carries the GLM 1/2 factor
    return _mean(0.5 * (s - y) ** 2, s, y, w)


def smoothed_hinge_loss(s, y, w):
    """Parity with evaluators._smoothed_hinge_np: margin in {-1, 1} space,
    quadratically smoothed hinge (Rennie's), gamma=1."""
    yy = jnp.where(y > POSITIVE_THRESHOLD, 1.0, -1.0)
    z = yy * s
    loss = jnp.where(
        z >= 1.0, 0.0, jnp.where(z <= 0.0, 0.5 - z, 0.5 * (1.0 - z) ** 2)
    )
    return _mean(loss, s, y, w)


DEVICE_METRICS = {
    "AUC": auc,
    "RMSE": rmse,
    "LOGISTIC_LOSS": logistic_loss,
    "POISSON_LOSS": poisson_loss,
    "SQUARED_LOSS": squared_loss,
    "SMOOTHED_HINGE_LOSS": smoothed_hinge_loss,
}


def build_device_evaluator(evaluators, labels: np.ndarray, weights):
    """One jitted function computing every (ungrouped, device-supported)
    metric of ``evaluators`` at once, or None when any metric needs the host
    path (grouped/ranking metrics). The caller fetches the stacked scalar
    vector in a single transfer."""
    names = []
    for e in evaluators:
        if e.group_by is not None or e.name not in DEVICE_METRICS:
            return None
        names.append(e.name)

    fns = [DEVICE_METRICS[n] for n in names]

    @jax.jit
    def compute(scores, y, w):
        return jnp.stack([f(scores, y, w) for f in fns])

    y_dev = jnp.asarray(labels, jnp.float32)
    w_dev = (
        jnp.ones_like(y_dev)
        if weights is None
        else jnp.asarray(weights, jnp.float32)
    )

    def evaluate(scores) -> Dict[str, float]:
        from ..utils.transfer import logged_fetch

        vals = logged_fetch(
            "evaluation.device_metrics",
            compute(jnp.asarray(scores, y_dev.dtype), y_dev, w_dev),
        )
        return {n: float(v) for n, v in zip(names, vals)}

    return evaluate
