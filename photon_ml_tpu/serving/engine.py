"""The one GLMix score assembly: compiled fixed+random-effect kernels shared
by batch scoring (``cli.score`` / ``GameTransformer.transform``) and the
resident request path (``serving.server``), so batch/resident parity is
structural rather than asserted.

Scoring semantics are the reference's (GameTransformer.scala:39-318): total
score = offsets + sum of per-coordinate margins, fixed effects as a dot
against one coefficient vector, random effects as a per-entity sparse dot
with unseen entities contributing 0 (the cold-start fallback — the request
path counts those in ``photon_serving_cold_start_total{coordinate=}``).

Kernel warmth: the jitted kernels take the coefficient tables as
*arguments*, not closures, so a refreshed snapshot with the same table
shapes re-uses the already-compiled executables (no recompile mid-flip),
and the persistent compile cache (``utils/compile_cache``) carries them
across server restarts. The resident path pads every request batch to a
small ladder of (rows, feature-width) shapes, so no request shape can
trigger a fresh compile once the ladder is warm.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.transfer import logged_fetch
from ..models.game import score_entity_ell

# Padding ladders for the resident request path. Rows round up to the next
# rung (bigger batches chunk at the top rung); the per-shard ELL feature
# width rounds up likewise. Small ladders keep the warm-kernel set small:
# at most len(LADDER_ROWS) * len(LADDER_WIDTH) compiled shapes per shard.
LADDER_ROWS: Tuple[int, ...] = (1, 8, 64, 256, 1024, 4096, 16384)
LADDER_WIDTH: Tuple[int, ...] = (4, 16, 64, 256, 512)


def _ladder_rows(n: int) -> int:
    for rung in LADDER_ROWS:
        if n <= rung:
            return rung
    return LADDER_ROWS[-1]


def _ladder_width(f: int) -> int:
    for rung in LADDER_WIDTH:
        if f <= rung:
            return rung
    raise ValueError(
        f"request feature width {f} exceeds the serving engine's padded "
        f"feature-width ladder (max {LADDER_WIDTH[-1]} features per row per "
        "shard); score such rows through the batch path (cli.score)"
    )


@jax.jit
def _fe_score_ell(weights, feat_idx, feat_val):
    """Fixed-effect margin for ELL-layout rows: one gather + masked-free dot
    (idx=0/val=0 padding contributes exact zeros)."""
    return jnp.sum(feat_val * jnp.take(weights, feat_idx, axis=0), axis=1)


@dataclasses.dataclass(frozen=True)
class ScoreRequest:
    """One scoring request: per-shard sparse features (already through the
    feature index map) plus the entity id per random-effect type.

    ``model`` routes the request in a multi-model fleet (``serving.fleet``):
    the name of the resident model to score against, or None for the
    server's default model. The engine itself ignores it — routing happens
    one layer up, in the per-model bulkhead lookup."""

    features: Mapping[str, Tuple[Sequence[int], Sequence[float]]]
    ids: Mapping[str, object] = dataclasses.field(default_factory=dict)
    offset: float = 0.0
    model: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class _FixedCoord:
    name: str
    feature_shard: str
    weights: object  # device f[d]


@dataclasses.dataclass(frozen=True)
class _RandomCoord:
    name: str
    feature_shard: str
    random_effect_type: str
    coef_indices: object  # device i32[E, S]
    coef_values: object  # device f[E, S]
    rows_for: object  # callable ids -> np.int64[n], -1 unseen


class ScoreEngine:
    """Compiled score assembly over one model's coordinate tables."""

    def __init__(self, coords: List[object], task: str, dtype=jnp.float32):
        self._coords = coords
        self.task = task
        self.dtype = dtype

    # -- construction --------------------------------------------------------

    @classmethod
    def from_model(cls, game_model, dtype=jnp.float32) -> "ScoreEngine":
        """Engine over an in-memory GameModel (the batch-scoring entry)."""
        from ..models.game import FixedEffectModel, RandomEffectModel

        coords: List[object] = []
        for name, sub in game_model.models.items():
            if isinstance(sub, FixedEffectModel):
                coords.append(
                    _FixedCoord(
                        name=name,
                        feature_shard=sub.feature_shard,
                        weights=sub.model.coefficients.means,
                    )
                )
            elif isinstance(sub, RandomEffectModel):
                coords.append(
                    _RandomCoord(
                        name=name,
                        feature_shard=sub.feature_shard,
                        random_effect_type=sub.random_effect_type,
                        coef_indices=sub.coef_indices,
                        coef_values=sub.coef_values,
                        rows_for=sub.rows_for,
                    )
                )
            else:
                raise TypeError(f"unknown model type for {name}: {type(sub)}")
        return cls(coords, game_model.task, dtype=dtype)

    @classmethod
    def from_store(cls, store, dtype=jnp.float32) -> "ScoreEngine":
        """Engine over an opened mmap ModelStore (the resident entry). The
        coefficient tables are staged to the device once here; entity-row
        lookups stay on the store's zero-heap mmap index."""
        from .store import FixedStoreCoord, RandomStoreCoord

        coords: List[object] = []
        for c in store.coords:
            if isinstance(c, FixedStoreCoord):
                coords.append(
                    _FixedCoord(
                        name=c.name,
                        feature_shard=c.feature_shard,
                        weights=jnp.asarray(np.asarray(c.weights)),
                    )
                )
            elif isinstance(c, RandomStoreCoord):
                coords.append(
                    _RandomCoord(
                        name=c.name,
                        feature_shard=c.feature_shard,
                        random_effect_type=c.random_effect_type,
                        coef_indices=jnp.asarray(np.asarray(c.coef_indices)),
                        coef_values=jnp.asarray(np.asarray(c.coef_values)),
                        rows_for=c.rows_for,
                    )
                )
            else:
                raise TypeError(f"unknown store coordinate type: {type(c)}")
        return cls(coords, store.task, dtype=dtype)

    # -- introspection -------------------------------------------------------

    @property
    def random_effect_types(self) -> List[str]:
        return [
            c.random_effect_type
            for c in self._coords
            if isinstance(c, _RandomCoord)
        ]

    @property
    def feature_shards(self) -> List[str]:
        seen: Dict[str, None] = {}
        for c in self._coords:
            seen.setdefault(c.feature_shard, None)
        return list(seen)

    # -- the shared assembly -------------------------------------------------

    def score_ell(
        self,
        offsets: np.ndarray,
        shard_ell: Mapping[str, Tuple[np.ndarray, np.ndarray]],
        entity_rows: Mapping[str, np.ndarray],
    ) -> np.ndarray:
        """Sum per-coordinate margins over rows already in ELL layout.

        ``shard_ell`` maps feature shard -> (idx i32[n, F], val f[n, F]) with
        idx=0/val=0 padding; ``entity_rows`` maps random-effect coordinate
        name -> i32[n] entity rows (-1 = unseen -> contributes 0). Scores
        accumulate in float64 on the host, one counted fetch per coordinate.
        """
        total = np.array(offsets, dtype=np.float64)
        for c in self._coords:
            idx, val = shard_ell[c.feature_shard]
            fidx = jnp.asarray(idx)
            fval = jnp.asarray(val, self.dtype)
            if isinstance(c, _FixedCoord):
                margin = _fe_score_ell(c.weights, fidx, fval)
            else:
                margin = score_entity_ell(
                    c.coef_indices,
                    c.coef_values,
                    jnp.asarray(entity_rows[c.name]),
                    fidx,
                    fval,
                )
            total += np.array(
                logged_fetch(f"serving.score.{c.name}", margin), dtype=np.float64
            )
        return total

    # -- batch path (cli.score / GameTransformer) ----------------------------

    def score_dataset(self, raw) -> np.ndarray:
        """Score a RawDataset: the batch-mode entry (GameScoringDriver role).
        Shapes follow the dataset (one compile per dataset shape — batch jobs
        are one-shot); the kernels are the same ones the request path keeps
        warm."""
        from ..game.data import _rows_to_ell

        shard_ell: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        for shard in self.feature_shards:
            rows, cols, vals = raw.shard_coo[shard]
            shard_ell[shard] = _rows_to_ell(rows, cols, vals, raw.n_rows)
        entity_rows: Dict[str, np.ndarray] = {}
        for c in self._coords:
            if isinstance(c, _RandomCoord):
                ids = raw.id_tags[c.random_effect_type]
                entity_rows[c.name] = c.rows_for(ids).astype(np.int32)
        return self.score_ell(raw.offsets, shard_ell, entity_rows)

    # -- resident request path ----------------------------------------------

    def score_requests(
        self, requests: Sequence[ScoreRequest], count_cold: bool = True
    ) -> np.ndarray:
        """Score a microbatch of requests through the warm ladder-padded
        kernels; unseen entities fall back to the fixed effect and count in
        ``photon_serving_cold_start_total{coordinate=}`` (``count_cold=False``
        for synthetic warmup traffic that must not pollute the metric)."""
        n = len(requests)
        if n == 0:
            return np.zeros(0, dtype=np.float64)
        top = LADDER_ROWS[-1]
        if n > top:
            return np.concatenate(
                [
                    self.score_requests(requests[i : i + top], count_cold)
                    for i in range(0, n, top)
                ]
            )
        pad_n = _ladder_rows(n)

        shard_ell: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        for shard in self.feature_shards:
            feats = [r.features.get(shard, ((), ())) for r in requests]
            width = _ladder_width(max((len(f[0]) for f in feats), default=1))
            idx = np.zeros((pad_n, width), dtype=np.int32)
            val = np.zeros((pad_n, width), dtype=np.float64)
            for i, (fi, fv) in enumerate(feats):
                k = len(fi)
                if k > width:  # defense in depth; _ladder_width refused above
                    raise ValueError(
                        f"request feature width {k} exceeds the serving "
                        "engine's padded feature-width ladder"
                    )
                idx[i, :k] = fi
                val[i, :k] = fv
            shard_ell[shard] = (idx, val)

        entity_rows: Dict[str, np.ndarray] = {}
        cold = obs.current_run().registry.counter(
            "photon_serving_cold_start_total",
            "requests scored fixed-effect-only because the entity was unseen",
        )
        for c in self._coords:
            if not isinstance(c, _RandomCoord):
                continue
            ids = [r.ids.get(c.random_effect_type) for r in requests]
            rows = c.rows_for(ids)
            n_cold = int(np.count_nonzero(rows < 0))
            if n_cold and count_cold:
                cold.labels(coordinate=c.name).inc(n_cold)
            erow = np.full(pad_n, -1, dtype=np.int32)
            erow[:n] = rows.astype(np.int32)
            entity_rows[c.name] = erow

        offsets = np.zeros(pad_n, dtype=np.float64)
        offsets[:n] = [r.offset for r in requests]
        return self.score_ell(offsets, shard_ell, entity_rows)[:n]

    def warm(self) -> None:
        """Compile the ladder's smallest shapes ahead of traffic (the rest
        fill in from the persistent compile cache or on first use)."""
        req = ScoreRequest(
            features={s: ((0,), (0.0,)) for s in self.feature_shards}
        )
        self.score_requests([req], count_cold=False)
