"""Plain reference for a logistic GAME model with one fixed effect and ANY
number of random effects: the whole model's objective, the active / passive
rule, and coordinate descent over an update sequence, in straightforward
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

Independent of the code under test (it imports nothing of ``photon_ml_tpu``):
no buckets, packing, kernels, trust regions or L-BFGS. Every block is solved by
``reference/glmix.py``'s exact damped Newton, so each update lands on the
block's unique minimiser given the other blocks' scores.

The published description (photon-ml ``RandomEffectDataset``; KDD'16 GLMix,
section 4): a random effect trains each entity on at most ``cap`` of its rows
(``numActiveDataPointsUpperBound``). An entity over the cap keeps the ``cap``
rows of smallest priority as its ACTIVE rows, each weighted count / cap so that
the entity's loss keeps its scale against the regulariser; its other rows are
PASSIVE: the block never trains on them, but scores them, so they reach every
other block's residual. The fixed effect trains on all rows at weight 1.

Departures, each passed in as data or noted here:
- upstream's priority is ``byteswap64(hash(row) ^ uniqueId)`` drawn into a
  reservoir; the program's is a splitmix64 mix of the row index. The reference
  takes ``priority`` as an argument and is right for either.
- upstream solves a block with L-BFGS / TRON to a tolerance; the reference to
  the minimiser (Newton to a relative gradient norm of 1e-7).
- the objective's convention is the program's (``reference/glmix.py``): labels
  in {0, 1}, every block's L2 over all its coefficients, the intercept included.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from . import glmix as ref

FIXED = "fixed"


@dataclasses.dataclass
class Block:
    """One random effect over the rows of the data set."""

    name: str
    features: jnp.ndarray  # f32[n, S]
    entity: jnp.ndarray  # i32[n], in [0, n_entities)
    n_entities: int
    l2: float
    weights: jnp.ndarray  # f32[n] from active_weights: 0 on passive rows

    def scores(self, table) -> jnp.ndarray:
        """The block's score of EVERY row, passive ones included."""
        with ref.HIGHEST():
            return jnp.einsum("ns,ns->n", self.features, table[self.entity])


def active_weights(entity: np.ndarray, priority: np.ndarray, cap: Optional[int],
                   n_entities: int) -> np.ndarray:
    """f32[n] training weight of each row for one random effect: 1 where the
    row's entity has at most ``cap`` rows; count / cap on the ``cap`` rows of
    smallest ``priority`` of an entity over the cap, 0 on its other rows."""
    entity = np.asarray(entity, np.int64)
    counts = np.bincount(entity, minlength=n_entities)
    if cap is None:
        return np.ones(len(entity), np.float32)
    order = np.lexsort((np.asarray(priority), entity))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.empty(len(entity), np.int64)
    rank[order] = np.arange(len(entity)) - starts[entity[order]]
    scale = np.where(counts > cap, counts / float(cap), 1.0)
    return np.where(rank < cap, scale[entity], 0.0).astype(np.float32)


def model_objective(w, tables: Dict[str, jnp.ndarray], x, y, l2_fixed: float,
                    blocks: Sequence[Block]):
    """The whole model's objective at (w, tables): the loss of ALL rows at
    weight 1 under the summed scores, plus every block's L2."""
    with ref.HIGHEST():
        z = x @ w
        value = 0.5 * l2_fixed * jnp.dot(w, w)
    for b in blocks:
        table = tables[b.name]
        z = z + b.scores(table)
        value = value + 0.5 * b.l2 * jnp.sum(table * table)
    return value + jnp.sum(ref.loss(z, y))


def block_value_grad(block: Block, table, y, offsets):
    """Per-entity values [U] and gradients [U, S] of one random effect's OWN
    objective (its active rows at their weights) given the others' scores."""
    return ref.entity_value_grad(
        table, block.features, y, block.entity, offsets, block.weights, block.l2
    )


def coordinate_descent(x, y, l2_fixed: float, blocks: Sequence[Block], sweeps: int,
                       sequence: Sequence[str]) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``sweeps`` sweeps over ``sequence`` (block names; ``FIXED`` is the
    fixed effect) from the zero model, each block solved to its minimiser
    given the summed scores of all the others. Returns (w, {name: table})."""
    n = x.shape[0]
    by_name = {b.name: b for b in blocks}
    if sorted(sequence) != sorted([FIXED] + list(by_name)):
        raise ValueError(f"update sequence {list(sequence)} does not name every block once")
    ones = jnp.ones(n, x.dtype)
    w = None
    tables: Dict[str, Optional[jnp.ndarray]] = {b.name: None for b in blocks}
    scores = {name: jnp.zeros(n, x.dtype) for name in sequence}
    for _ in range(sweeps):
        for name in sequence:
            others = sum((s for k, s in scores.items() if k != name), jnp.zeros(n, x.dtype))
            if name == FIXED:
                w = ref.solve_fixed(x, y, others, ones, l2_fixed, w0=w)
                with ref.HIGHEST():
                    scores[name] = x @ w
            else:
                b = by_name[name]
                tables[name] = ref.solve_entities(
                    b.features, y, b.entity, b.n_entities, others, b.weights, b.l2,
                    table0=tables[name],
                )
                scores[name] = b.scores(tables[name])
    return w, tables
