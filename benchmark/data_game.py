"""Seeded data for a GLMix with ANY number of random effects (job ``fit_game``).

The rules are ``benchmark/data.py``'s, whose pieces this file reuses: ONE data
set per configuration (``scale.data_seed`` draws every value), MIRRORED by the
run's seed (one sign vector per feature bag, the intercepts left alone), every
entity's row count a FIXED quota (``data.user_quotas``: seed-free, Zipf, at
least one row). What is new is the second, third, ... random effect: each has a
quota law, a bag of iid N(0,1) columns with the intercept last, and a sign
vector of its own, and the entity of a row is drawn for each effect
independently of the others (the quotas shuffled), so users and items cross as
they do in a recommender's log.

Labels are Bernoulli draws of sigmoid(fixed margin + the sum of the effects'
margins) on the data as it is; the signs then mirror the features, so two seeds
train on reflections of the same rows with the same labels.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass
class GameMirror:
    """The run's seed as data: one sign vector per feature bag."""

    fixed: np.ndarray  # f32[d]
    effects: Dict[str, np.ndarray]  # random effect name -> f32[d_re]


def draw_mirror(seed: int, d: int, effect_dims: Dict[str, int]) -> GameMirror:
    """Signs for the fixed bag, then for each effect's bag in the order given,
    from ONE generator (the first two vectors are ``data.draw_mirror``'s)."""
    rng = np.random.default_rng(seed)  # takes any whole number, past 2**31 too

    def signs(width: int) -> np.ndarray:
        s = (2 * rng.integers(0, 2, size=width) - 1).astype(np.float32)
        s[-1] = 1.0
        return s

    fixed = signs(d)
    return GameMirror(fixed=fixed, effects={name: signs(w) for name, w in effect_dims.items()})


@dataclasses.dataclass
class GameRows:
    """The host side of one data set: per effect, the entity of every row and
    the row's features in that effect's bag."""

    labels: np.ndarray  # f32[n]
    entity_of_row: Dict[str, np.ndarray]  # name -> i64[n], rank by activity
    features: Dict[str, np.ndarray]  # name -> f32[n, d_re], last column = 1

    def subset(self, take: np.ndarray, relabel: Optional[Dict[str, np.ndarray]] = None) -> "GameRows":
        """Rows ``take``; ``relabel[name]`` maps old entity ranks to new ids."""
        entity = {k: v[take] for k, v in self.entity_of_row.items()}
        if relabel is not None:
            entity = {k: relabel[k][v] for k, v in entity.items()}
        return GameRows(
            labels=self.labels[take], entity_of_row=entity,
            features={k: v[take] for k, v in self.features.items()},
        )


@dataclasses.dataclass
class GameTruth:
    """The generating model (only the generator and the tests look at it)."""

    w_fixed: np.ndarray  # f32[d]
    tables: Dict[str, np.ndarray]  # name -> f32[n_entities, d_re]


def draw_truth(rng: np.random.Generator, d: int, effect_shapes: Dict[str, tuple]) -> GameTruth:
    """``effect_shapes``: name -> (entities, d_re), drawn in the order given."""
    w_fixed = (rng.standard_normal(d) / np.sqrt(d)).astype(np.float32)
    tables = {
        name: (rng.standard_normal((entities, d_re)) / np.sqrt(d_re)).astype(np.float32)
        for name, (entities, d_re) in effect_shapes.items()
    }
    return GameTruth(w_fixed=w_fixed, tables=tables)


def host_rows(
    rng: np.random.Generator,
    entity_of_row: Dict[str, np.ndarray],
    fixed_margin: np.ndarray,
    truth: GameTruth,
    mirror: Optional[GameMirror] = None,
) -> GameRows:
    """Every effect's features, and labels from the whole model's margin, for
    rows whose fixed-effect margin is already known (it comes back from the
    device). The labels are drawn before ``mirror`` flips the features."""
    n = len(fixed_margin)
    z = fixed_margin.astype(np.float32)
    features = {}
    for name, entity in entity_of_row.items():
        table = truth.tables[name]
        ex = rng.standard_normal((n, table.shape[1]), dtype=np.float32)
        ex[:, -1] = 1.0
        z = z + np.einsum("nd,nd->n", ex, table[entity])
        features[name] = ex
    labels = (rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    if mirror is not None:
        for name, ex in features.items():
            ex *= mirror.effects[name]
    return GameRows(labels=labels, entity_of_row=dict(entity_of_row), features=features)
