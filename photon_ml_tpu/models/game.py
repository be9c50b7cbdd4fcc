"""GAME model classes: fixed-effect, random-effect, and composite GAME models.

Reference: photon-lib/.../model/ — GameModel (map coordinateId -> model,
scores summed across coordinates, GameModel.scala:99-104), FixedEffectModel
(broadcast coefficients + dot products, FixedEffectModel.scala:55),
RandomEffectModel (per-entity coefficient lookup joined by entity id, score 0
for unseen entities, RandomEffectModel.scala:70,254+).

TPU re-design: a random-effect model is a *padded per-entity sparse matrix*
(entity-major ``coef_indices i32[E, S]`` / ``coef_values f32[E, S]``, indices
into the shard's global feature space, padded with -1) — the device-friendly
form of the reference's RDD[(entityId, GLM)]. Host keeps the entityId -> row
dict. Scoring gathers the entity row then dot-products in the entity's
subspace; unseen entities contribute 0.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.features import LabeledBatch
from .coefficients import Coefficients
from .glm import GeneralizedLinearModel, model_for_task

Array = jax.Array


def score_entity_ell(
    coef_indices: Array,  # i32[E, S] sorted ascending per row, -1 padded
    coef_values: Array,  # f[E, S]
    entity_rows: Array,  # i32[n], -1 = unseen entity
    feat_idx: Array,  # i32[n, F]
    feat_val: Array,  # f[n, F]
) -> Array:
    """Pure scoring kernel: per-row dot product against per-entity sparse
    coefficient vectors (RandomEffectModel.score semantics; jit/vmap/shard-safe).

    Per row i: score = sum_k feat_val[i,k] * w_e[feat_idx[i,k]] with w_e the
    sparse vector of entity entity_rows[i]; the lookup is a searchsorted into
    the entity's sorted support (-1 padding replaced by a +inf sentinel keeps
    the row sorted)."""
    pos, hit = ell_support_positions(coef_indices, entity_rows, feat_idx)
    return score_entity_ell_at(coef_values, entity_rows, pos, hit, feat_val)


@jax.jit
def ell_support_positions(
    coef_indices: Array,  # i32[E, S] sorted ascending per row, -1 padded
    entity_rows: Array,  # i32[n], -1 = unseen entity
    feat_idx: Array,  # i32[n, F]
):
    """Precompute (pos, hit) mapping each row's ELL features into its entity's
    sorted coefficient support.

    The support LAYOUT (coef_indices) is fixed per dataset while coefficient
    VALUES change every coordinate-descent sweep — so the vmapped
    searchsorted (the expensive part of scoring: a log(S) gather chain per
    feature on TPU) runs ONCE per dataset, and every subsequent score is one
    (row, pos) gather (score_entity_ell_at). Measured at bench shapes
    (n=500k) this takes RE scoring from ~1.7s to ~0.25s. The -1 padding is
    replaced by a +inf sentinel so each support row stays sorted.
    """
    safe_rows = jnp.maximum(entity_rows, 0)
    ent_idx = jnp.take(coef_indices, safe_rows, axis=0)  # [n, S]
    big = jnp.iinfo(jnp.int32).max
    ent_idx = jnp.where(ent_idx < 0, big, ent_idx)

    def one(ei, fi):
        pos = jnp.clip(jnp.searchsorted(ei, fi), 0, ei.shape[0] - 1)
        return pos.astype(jnp.int32), jnp.take(ei, pos) == fi

    return jax.vmap(one)(ent_idx, feat_idx)


@jax.jit
def ell_slot_positions(
    coef_indices: Array,  # i32[E, S] sorted ascending per row, -1 padded
    entity_rows: Array,  # i32[n], -1 = unseen entity
    feat_idx: Array,  # i32[n, F]
):
    """:func:`ell_support_positions` (the same ``(pos, hit)``, integer for
    integer) with no ``[n, S]`` array: a bisection over each slot's own
    entity row of ``coef_indices``, ceil(log2(S + 1)) rounds of one gather a
    slot each, so the memory follows the rows' slots and not the widest
    entity's subspace. For data sets whose subspaces are ragged (a few
    entities with hundreds of columns, rows with a handful of slots): there
    the gathered ``[n, S]`` supports of the vmapped searchsorted are S / F
    times the data. The rounds run over ``[F, n]`` views (the row axis minor,
    as ``[n, F]`` arrays lie on the TPU): over ``[n, F]`` the compiler pads
    every intermediate's F to 128 lanes."""
    S = coef_indices.shape[1]
    rows = jnp.maximum(entity_rows, 0)[None, :]
    want = feat_idx.T  # [F, n]
    big = jnp.iinfo(jnp.int32).max

    def support_at(pos):  # the -1 padding reads as a +inf sentinel: sorted
        v = coef_indices[rows, jnp.minimum(pos, S - 1)]
        return jnp.where(v < 0, big, v)

    def halve(_, bounds):  # leftmost pos with support[pos] >= feat_idx
        lo, hi = bounds
        mid = (lo + hi) // 2
        right = (lo < hi) & (support_at(mid) < want)
        return jnp.where(right, mid + 1, lo), jnp.where((lo < hi) & ~right, mid, hi)

    lo = jnp.zeros(want.shape, jnp.int32)
    lo, _ = jax.lax.fori_loop(
        0, max(int(S).bit_length(), 1), halve, (lo, jnp.full_like(lo, S))
    )
    pos = jnp.clip(lo, 0, S - 1)
    return pos.T, (support_at(pos) == want).T


@jax.jit
def ell_row_subspace(
    coef_indices: Array,  # i32[E, S] sorted ascending per row, -1 padded
    entity_rows: Array,  # i32[n], -1 = unseen entity
    feat_idx: Array,  # i32[n, F]
    feat_val: Array,  # f[n, F]
) -> Array:
    """Densify each row's ELL features into its entity's subspace layout:
    x_sub[i, s] = sum over the row's features that land at support position s.

    Like :func:`ell_support_positions`, this depends only on the support
    LAYOUT and the feature VALUES — both fixed per dataset — so it runs once
    and is cached; every subsequent score is then a contiguous row gather of
    the [E, S] coefficient table plus an elementwise dot
    (:func:`score_entity_rows_dense`), instead of an n*F random 2-D gather
    per sweep (measured ~10x at n=500k bench shapes)."""
    pos, hit = ell_support_positions(coef_indices, entity_rows, feat_idx)
    n = feat_idx.shape[0]
    S = coef_indices.shape[1]
    x_sub = jnp.zeros((n, S), feat_val.dtype)
    return x_sub.at[jnp.arange(n)[:, None], pos].add(
        jnp.where(hit, feat_val, 0.0)
    )


@jax.jit
def score_entity_rows_dense(
    coef_values: Array,  # f[E, S]
    entity_rows: Array,  # i32[n], -1 = unseen entity
    x_sub: Array,  # f[n, S] from ell_row_subspace
) -> Array:
    """Score with per-row subspace features already densified: one row gather
    + masked elementwise dot."""
    safe_rows = jnp.maximum(entity_rows, 0)
    w = jnp.take(coef_values, safe_rows, axis=0)  # [n, S]
    scores = jnp.sum(w * x_sub, axis=1)
    return jnp.where(entity_rows >= 0, scores, 0.0)


@jax.jit
def score_entity_rows_dense_lanes(
    coef_values: Array,  # f[E, S, L] lane-stacked per-entity coefficients
    entity_rows: Array,  # i32[n], -1 = unseen entity
    x_sub: Array,  # f[n, S] from ell_row_subspace
) -> Array:
    """Lane-stacked :func:`score_entity_rows_dense`: [n, L] scores for L
    lambda lanes sharing one densified-subspace cache — the sweep executor's
    RE scoring kernel (game/lanes.py)."""
    safe_rows = jnp.maximum(entity_rows, 0)
    w = jnp.take(coef_values, safe_rows, axis=0)  # [n, S, L]
    scores = jnp.sum(w * x_sub[:, :, None], axis=1)  # [n, L]
    return jnp.where(entity_rows[:, None] >= 0, scores, 0.0)


@jax.jit
def score_entity_ell_at(
    coef_values: Array,  # f[E, S]
    entity_rows: Array,  # i32[n], -1 = unseen entity
    pos: Array,  # i32[n, F] from ell_support_positions / ell_slot_positions
    hit: Array,  # bool[n, F]
    feat_val: Array,  # f[n, F]
) -> Array:
    """Scoring with the searchsorted already resolved: one 2-D gather of
    coef_values at (entity_row, pos) index pairs plus a masked dot. The
    gather keeps (row, col) pairs instead of a flattened row*S+col index so
    E*S beyond int32 range cannot overflow; it and the dot run over ``[F, n]``
    views (the row axis minor: an ``[n, F]`` intermediate is padded to 128
    lanes on the TPU, 600 MB each at 1.18M rows of 5 slots)."""
    safe_rows = jnp.maximum(entity_rows, 0)
    w = coef_values[safe_rows[None, :], pos.T]  # [F, n]
    scores = jnp.sum(jnp.where(hit.T, w * feat_val.T, 0.0), axis=0)
    return jnp.where(entity_rows >= 0, scores, 0.0)


@jax.jit
def score_entity_ell_at_lanes(
    coef_values: Array,  # f[E, S, L] lane-stacked per-entity coefficients
    entity_rows: Array,  # i32[n], -1 = unseen entity
    pos: Array,  # i32[n, F]
    hit: Array,  # bool[n, F]
    feat_val: Array,  # f[n, F]
) -> Array:
    """Lane-stacked :func:`score_entity_ell_at`: [n, L] scores."""
    safe_rows = jnp.maximum(entity_rows, 0)
    w = coef_values[safe_rows[:, None], pos]  # [n, F, L]
    scores = jnp.sum(jnp.where(hit[:, :, None], w * feat_val[:, :, None], 0.0), axis=1)
    return jnp.where(entity_rows[:, None] >= 0, scores, 0.0)


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """One GLM applied to every sample's features from one feature shard."""

    model: GeneralizedLinearModel
    feature_shard: str

    @property
    def coefficients(self) -> Coefficients:
        return self.model.coefficients

    def score(self, batch: LabeledBatch) -> Array:
        """Margins WITHOUT the batch offset: coordinate scores compose by
        summation, offsets are added once by the consumer."""
        return batch.features.matvec(self.model.coefficients.means)


@dataclasses.dataclass
class RandomEffectModel:
    """Per-entity GLMs for one random-effect type over one feature shard."""

    random_effect_type: str  # id-tag column, e.g. "userId"
    feature_shard: str
    task: str
    entity_ids: np.ndarray  # object[E] host-side ids (row order of the arrays)
    coef_indices: Array  # i32[E, S] global feature indices, -1 padded
    coef_values: Array  # f[E, S]
    variances: Optional[Array] = None  # f[E, S] if computed
    _id_to_row: Optional[Dict[str, int]] = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if self._id_to_row is None:
            self._id_to_row = {str(e): i for i, e in enumerate(self.entity_ids)}

    def __getstate__(self):
        # the coordinate-descent hot path tags trained models with a weakref
        # provenance mark (_support_layout_of, game/coordinate.py) — weakrefs
        # are unpicklable, so drop it; unpickled models fall back to the
        # memoized array-comparison layout check
        state = dict(self.__dict__)
        state.pop("_support_layout_of", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)

    def entity_row(self, entity_id: str) -> int:
        """Row index for an entity, -1 if unseen."""
        return self._id_to_row.get(str(entity_id), -1)

    def rows_for(self, entity_ids: Sequence) -> np.ndarray:
        return np.asarray([self.entity_row(e) for e in entity_ids], dtype=np.int64)

    def dense_coefficients(self, dim: int) -> np.ndarray:
        """Materialize [E, dim] dense coefficients (small models / tests)."""
        out = np.zeros((self.num_entities, dim))
        idx = np.asarray(self.coef_indices)
        val = np.asarray(self.coef_values)
        for e in range(self.num_entities):
            m = idx[e] >= 0
            out[e, idx[e][m]] = val[e][m]
        return out

    def score_ell_rows(
        self, entity_rows: Array, feat_idx: Array, feat_val: Array
    ) -> Array:
        """Score rows in ELL layout: row i gets features (feat_idx[i], feat_val[i])
        and entity row entity_rows[i] (-1 => unseen => score 0).

        Delegates to :func:`score_entity_ell`."""
        return score_entity_ell(
            self.coef_indices, self.coef_values, entity_rows, feat_idx, feat_val
        )


@dataclasses.dataclass
class GameModel:
    """coordinateId -> model; total score = sum of coordinate scores
    (GameModel.scala:99-104)."""

    models: Dict[str, object]  # FixedEffectModel | RandomEffectModel
    task: str = "logistic_regression"

    def __getitem__(self, name: str):
        return self.models[name]

    def __contains__(self, name: str) -> bool:
        return name in self.models

    def coordinates(self) -> List[str]:
        return list(self.models)

    def updated(self, name: str, model) -> "GameModel":
        new = dict(self.models)
        new[name] = model
        return GameModel(models=new, task=self.task)
