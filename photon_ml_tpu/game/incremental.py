"""Day-chained incremental retraining with no-degrade promotion gates.

The reference treats warm-start / partial retrain as a first-class production
scenario ("Regularize by Previous Model During Warm-Start Training",
reference README.md:102-103, and the warm-start integration battery in
GameTrainingDriverIntegTest.scala:60-474). This module closes the
train->serve loop around that machinery: walk a time-partitioned feed one
day at a time, warm-start day k+1 from day k's accepted model with
prior-centered L2 (``CoordinateConfig.regularize_by_prior``), re-solve ONLY
what the new rows touch, gate the candidate behind a per-metric no-degrade
check against the live model, and publish accepted models into a running
``cli serve`` via ``serving.refresh.publish_snapshot``.

Partial re-solve falls out of the data layout rather than bookkeeping: a
day's ``RawDataset`` contains exactly the entities its rows touch, so the
day's coordinate descent trains per-entity models for those entities only.
:func:`merge_models` then grows the accepted prior in place —

- entities untouched by the day carry forward **bitwise** (their coefficient
  rows are copied, never recomputed);
- touched entities take the day's re-solved rows (support remapped into the
  merged padded width);
- entities appearing mid-stream are appended, growing the model (their
  warm-start came from the zero-mean prior ``_project_model_values``
  assigns to unseen entities).

Promotion is refused, not assumed: :func:`no_degrade_gate` scores candidate
and live on the SAME held-out validation set and rejects the candidate if
any requested metric (e.g. ``AUC`` and the per-group ``AUC:groupId``)
degrades beyond ``margin``. A rejection is typed and counted
(``photon_retrain_rejected_total{reason=}``) and the previous snapshot keeps
serving — a poisoned day (NaN storm, quarantined rows) can cost a day's
update but never the chain or the live store.

Failure drill points (``PHOTON_FAULTS``):

- ``retrain.day`` — checked once per chain day before any of its work; a
  ``kill`` there is the crash-between-days drill (the ledger resumes).
- ``retrain.publish`` — checked immediately before snapshot publication; an
  ``io`` error there is the torn-publish drill (the decision is already in
  the ledger, the next cycle's :func:`_ensure_published` repairs the store).

Mid-day kills resume through the ordinary boundary-checkpoint path: each
day's CD runs under a ``robust.CheckpointManager`` whose manifests carry the
chain position and the accepted/rejected ledger so far (``base_meta``), and
the chain state file marks the day in progress.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from .. import obs
from ..utils.transfer import logged_fetch
from ..evaluation import build_suite
from ..models.game import GameModel, RandomEffectModel
from ..robust import faults
from ..robust.atomic import atomic_write_json
from ..robust.checkpoint import CheckpointManager
from ..robust.retry import io_call

logger = logging.getLogger(__name__)

CHAIN_STATE_NAME = "chain-state.json"
_CHAIN_STATE_VERSION = 1


# -- random-effect growth ----------------------------------------------------


def grow_random_effect(
    prior: RandomEffectModel, update: RandomEffectModel
) -> RandomEffectModel:
    """Merge a day's re-solved entities into ``prior``, growing it in place.

    Entities present in ``update`` take their re-solved rows; entities only
    in ``prior`` carry forward bitwise (row copies, no recompute); entities
    new to ``update`` are appended after the prior rows (model growth). The
    padded support width widens to fit both sides; widening pads with the
    ``-1`` sentinel, so untouched rows score identically.

    Posterior variances merge only when BOTH sides carry them (a means-only
    day update invalidates the prior's stale variances for touched rows, so
    the merged model drops them rather than serving a mix).
    """
    import jax.numpy as jnp

    if prior.random_effect_type != update.random_effect_type:
        raise ValueError(
            "cannot merge random-effect models of different types: "
            f"{prior.random_effect_type!r} vs {update.random_effect_type!r}"
        )
    if prior.feature_shard != update.feature_shard:
        raise ValueError(
            "cannot merge random-effect models of different feature shards: "
            f"{prior.feature_shard!r} vs {update.feature_shard!r}"
        )

    p_idx = np.asarray(logged_fetch("retrain.merge", prior.coef_indices))
    p_val = np.asarray(logged_fetch("retrain.merge", prior.coef_values))
    u_idx = np.asarray(logged_fetch("retrain.merge", update.coef_indices))
    u_val = np.asarray(logged_fetch("retrain.merge", update.coef_values))

    S = max(p_idx.shape[1], u_idx.shape[1])
    val_dt = np.result_type(p_val.dtype, u_val.dtype)

    def _widen_idx(a):
        if a.shape[1] == S:
            return a
        return np.pad(a, ((0, 0), (0, S - a.shape[1])), constant_values=-1)

    def _widen_val(a):
        if a.shape[1] == S:
            return a
        return np.pad(a, ((0, 0), (0, S - a.shape[1])))

    # destination row for every update entity: the prior's row when it exists
    # (re-solve in place), else a fresh appended row (model growth)
    dest = np.empty(update.num_entities, dtype=np.int64)
    ids = list(map(str, prior.entity_ids))
    for e, ent in enumerate(update.entity_ids):
        r = prior.entity_row(ent)
        if r < 0:
            r = len(ids)
            ids.append(str(ent))
        dest[e] = r
    E_out = len(ids)

    out_idx = np.full((E_out, S), -1, dtype=np.int32)
    out_val = np.zeros((E_out, S), dtype=val_dt)
    out_idx[: prior.num_entities] = _widen_idx(p_idx)
    out_val[: prior.num_entities] = _widen_val(p_val).astype(val_dt, copy=False)
    out_idx[dest] = _widen_idx(u_idx)
    out_val[dest] = _widen_val(u_val).astype(val_dt, copy=False)

    variances = None
    if prior.variances is not None and update.variances is not None:
        p_var = np.asarray(logged_fetch("retrain.merge", prior.variances))
        u_var = np.asarray(logged_fetch("retrain.merge", update.variances))
        out_var = np.zeros((E_out, S), dtype=val_dt)
        out_var[: prior.num_entities] = _widen_val(p_var).astype(val_dt, copy=False)
        out_var[dest] = _widen_val(u_var).astype(val_dt, copy=False)
        variances = jnp.asarray(out_var)

    return RandomEffectModel(
        random_effect_type=prior.random_effect_type,
        feature_shard=prior.feature_shard,
        task=update.task,
        entity_ids=np.asarray(ids, dtype=object),
        coef_indices=jnp.asarray(out_idx),
        coef_values=jnp.asarray(out_val),
        variances=variances,
    )


def merge_models(
    prior: Optional[GameModel], update: GameModel
) -> Tuple[GameModel, Dict[str, int]]:
    """Fold a day's trained model into the accepted prior.

    Fixed effects are whole-model replacements (every row carries the global
    features, so the day re-solves them entirely). Random effects grow via
    :func:`grow_random_effect`. Coordinates absent from the update carry
    forward untouched. Returns ``(merged, touched)`` where ``touched`` maps
    each random-effect coordinate to the number of entities the day
    re-solved or added."""
    if prior is None:
        touched = {
            name: m.num_entities
            for name, m in update.models.items()
            if isinstance(m, RandomEffectModel)
        }
        return update, touched

    merged = dict(prior.models)
    touched: Dict[str, int] = {}
    for name, m in update.models.items():
        old = merged.get(name)
        if isinstance(m, RandomEffectModel) and isinstance(old, RandomEffectModel):
            merged[name] = grow_random_effect(old, m)
            touched[name] = m.num_entities
        else:
            merged[name] = m
            if isinstance(m, RandomEffectModel):
                touched[name] = m.num_entities
    return GameModel(models=merged, task=update.task), touched


# -- the no-degrade promotion gate -------------------------------------------


@dataclasses.dataclass(frozen=True)
class GateDecision:
    """Outcome of one candidate-vs-live promotion check."""

    accepted: bool
    reason: str  # "accepted" | "first-publish" | "non-finite" | "degraded:<metric>"
    candidate_metrics: Dict[str, float]
    live_metrics: Optional[Dict[str, float]] = None


def no_degrade_gate(
    candidate: GameModel,
    live: Optional[GameModel],
    validation,
    evaluator_specs: Sequence[str],
    margin: float = 0.0,
    dtype=None,
) -> GateDecision:
    """Score candidate and live on the SAME held-out validation set; refuse
    the candidate if any requested metric degrades beyond ``margin`` in that
    metric's own direction (per-group ``AUC:groupId`` specs degrade when the
    unweighted mean of per-group AUCs drops). A candidate with non-finite
    scores or a NaN metric is refused outright — a NaN-poisoned day must
    never reach the live store. With no live model the first candidate is
    accepted (``first-publish``)."""
    import jax.numpy as jnp

    from ..estimators.game_estimator import GameTransformer

    dtype = jnp.float32 if dtype is None else dtype
    with obs.span("retrain.gate"):
        scores, evaluation = GameTransformer(
            model=candidate, dtype=dtype
        ).transform(validation, evaluator_specs)
        cand_metrics = dict(evaluation.metrics)
        host_scores = np.asarray(scores)
        if not np.all(np.isfinite(host_scores)) or any(
            not np.isfinite(v) for v in cand_metrics.values()
        ):
            return GateDecision(False, "non-finite", cand_metrics, None)
        if live is None:
            return GateDecision(True, "first-publish", cand_metrics, None)
        _, live_eval = GameTransformer(model=live, dtype=dtype).transform(
            validation, evaluator_specs
        )
        live_metrics = dict(live_eval.metrics)
        suite = build_suite(
            evaluator_specs, validation.labels, validation.weights,
            id_tags=validation.id_tags,
        )
        for ev in suite.evaluators:
            cand_v = cand_metrics[ev.name]
            live_v = live_metrics[ev.name]
            if not np.isfinite(live_v):
                continue  # a broken live metric cannot veto an improvement
            degraded = (
                live_v - cand_v > margin
                if ev.higher_is_better
                else cand_v - live_v > margin
            )
            if degraded:
                return GateDecision(
                    False, f"degraded:{ev.name}", cand_metrics, live_metrics
                )
        return GateDecision(True, "accepted", cand_metrics, live_metrics)


# -- the day chain -----------------------------------------------------------


@dataclasses.dataclass
class DayRecord:
    """One ledger row: the chain's decision for one day."""

    day: str
    index: int
    accepted: bool
    reason: str
    rows: int
    touched_entities: Dict[str, int]
    snapshot: Optional[str] = None
    published: bool = False
    metrics: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class ChainResult:
    """Final state of one :func:`run_chain` invocation."""

    model: Optional[GameModel]  # the live (last accepted) model
    ledger: List[DayRecord]
    rows_touched: int  # rows the incremental chain actually trained on
    rows_cumulative: int  # rows a daily from-scratch retrain would have touched

    @property
    def rows_touched_fraction(self) -> float:
        return self.rows_touched / max(self.rows_cumulative, 1)


def _record_decision(decision: GateDecision, day_index: int) -> None:
    outcome = "accepted" if decision.accepted else "rejected"
    registry = obs.current_run().registry
    registry.counter(
        "photon_retrain_days_total",
        "chain days processed, by promotion outcome",
    ).labels(outcome=outcome).inc()
    if not decision.accepted:
        registry.counter(
            "photon_retrain_rejected_total",
            "candidate models refused by the no-degrade promotion gate",
        ).labels(reason=decision.reason).inc()
    obs.current_run().registry.gauge(
        "photon_retrain_day_index", "index of the chain day last processed"
    ).set(float(day_index))


def _load_chain_state(chain_dir: Optional[str]) -> dict:
    if not chain_dir:
        return {"version": _CHAIN_STATE_VERSION, "days": [], "in_progress": None}
    path = os.path.join(chain_dir, CHAIN_STATE_NAME)
    if not os.path.exists(path):
        return {"version": _CHAIN_STATE_VERSION, "days": [], "in_progress": None}

    def _read():
        with open(path) as f:
            return json.load(f)

    state = io_call(_read, site="io.chain_state")
    if state.get("version") != _CHAIN_STATE_VERSION:
        raise ValueError(
            f"{path}: unsupported chain-state version {state.get('version')!r}"
        )
    return state


def _save_chain_state(chain_dir: Optional[str], state: dict) -> None:
    if not chain_dir:
        return
    os.makedirs(chain_dir, exist_ok=True)
    io_call(
        atomic_write_json,
        os.path.join(chain_dir, CHAIN_STATE_NAME),
        state, indent=2,
        site="io.chain_state",
    )


def _ledger_meta(ledger: Sequence[DayRecord]) -> List[dict]:
    return [dataclasses.asdict(r) for r in ledger]


def _ensure_published(serving_root: str, record: DayRecord, model: GameModel) -> bool:
    """Repair path: make the last accepted decision visible in the serving
    store. Called at the top of every cycle — a torn publish (crash or IO
    error between the gate decision and the store flip) leaves the old
    snapshot serving until this makes the accepted one live. Idempotent:
    an already-live snapshot is a no-op."""
    from ..serving import refresh

    if record.snapshot is None:
        return False
    if (
        refresh.current_snapshot(serving_root) == record.snapshot
        and os.path.isdir(refresh.snapshot_path(serving_root, record.snapshot))
    ):
        return True
    try:
        faults.check("retrain.publish")
        refresh.publish_snapshot(
            serving_root, record.snapshot, game_model=model, replace=True
        )
    except OSError:
        obs.swallowed_error("retrain.publish")
        return False
    obs.current_run().registry.counter(
        "photon_retrain_published_total",
        "accepted snapshots published into the serving store",
    ).inc()
    return True


DayData = Union["RawDataset", Callable[[], "RawDataset"]]  # noqa: F821


def run_chain(
    estimator,
    days: Sequence[Tuple[str, DayData]],
    validation,
    *,
    initial_model: Optional[GameModel] = None,
    chain_dir: Optional[str] = None,
    serving_root: Optional[str] = None,
    snapshot_prefix: str = "retrain",
    evaluator_specs: Optional[Sequence[str]] = None,
    gate_margin: float = 0.0,
    checkpoint_every: int = 0,
    checkpoint_keep: int = 3,
    index_maps: Optional[Mapping[str, object]] = None,
    dtype=None,
) -> ChainResult:
    """Walk ``days`` (ordered ``(label, dataset-or-thunk)`` pairs), training
    each day warm-started from the last ACCEPTED model with prior-centered
    L2, gating every candidate through :func:`no_degrade_gate`, and
    publishing accepted models into ``serving_root``.

    ``chain_dir`` makes the chain durable: the day ledger persists in
    ``chain-state.json``, accepted models are saved under ``models/`` (when
    ``index_maps`` are given), and each day's CD checkpoints (enabled via
    ``checkpoint_every``) carry the chain position in their manifests. A
    re-invocation over the same ``days`` resumes: decided days are skipped
    (their thunks never load), a day killed mid-CD resumes from its newest
    valid boundary checkpoint, and a torn publish is repaired before any new
    work. Day thunks are only called for undecided days, so resume cost is
    proportional to the remaining work."""
    import jax.numpy as jnp

    from ..io.model_io import load_game_model, save_game_model

    dtype = jnp.float32 if dtype is None else dtype
    specs = list(evaluator_specs or estimator.evaluator_specs or ["RMSE"])

    state = _load_chain_state(chain_dir)
    ledger = [DayRecord(**d) for d in state["days"]]
    rows_touched = int(state.get("rows_touched", 0))
    rows_cumulative = int(state.get("rows_cumulative", 0))
    rows_seen = int(state.get("rows_seen", 0))

    live = initial_model
    if ledger and state.get("live_model_dir") and index_maps is not None:
        # resume: the last accepted model reloads from the chain's own store
        live = load_game_model(
            state["live_model_dir"], index_maps, task=estimator.task
        )

    last_accepted = next((r for r in reversed(ledger) if r.accepted), None)
    if serving_root and last_accepted is not None and live is not None:
        if _ensure_published(serving_root, last_accepted, live):
            if not last_accepted.published:
                last_accepted.published = True
                state["days"] = _ledger_meta(ledger)
                _save_chain_state(chain_dir, state)

    for day_index, (label, data) in enumerate(days):
        if day_index < len(ledger):
            continue  # decided on a previous invocation; ledger is durable
        faults.check("retrain.day")
        raw = data() if callable(data) else data
        resume_snap = None
        mgr = None
        if chain_dir and checkpoint_every:
            mgr = CheckpointManager(
                os.path.join(chain_dir, "checkpoints", f"day-{day_index:04d}"),
                keep_last=checkpoint_keep,
                every=checkpoint_every,
                base_meta={
                    "chain_day": label,
                    "chain_day_index": day_index,
                    "chain_ledger": _ledger_meta(ledger),
                },
            )
            if state.get("in_progress") == label:
                resume_snap = mgr.latest_valid()
                if resume_snap is not None:
                    logger.info(
                        "day %s: resuming mid-day from boundary step %s",
                        label, resume_snap.manifest.get("step"),
                    )
        state["in_progress"] = label
        _save_chain_state(chain_dir, state)

        for cc in estimator.coordinate_configs:
            # prior-centered L2 only once a prior exists; day 0 is plain L2
            cc.regularize_by_prior = live is not None

        with obs.span("retrain.day", day=label):
            boundary_fn = None
            if mgr is not None:
                boundary_fn = lambda _w, st, _m=mgr: _m.on_boundary(st)
            results = estimator.fit(
                raw,
                validation=validation,
                initial_model=live,
                boundary_fn=boundary_fn,
                resume_state=resume_snap,
            )
            day_model = estimator.select_best(results).model
            candidate, touched = merge_models(live, day_model)
            decision = no_degrade_gate(
                candidate, live, validation, specs,
                margin=gate_margin, dtype=dtype,
            )

        _record_decision(decision, day_index)
        rows_seen += int(raw.n_rows)
        rows_touched += int(raw.n_rows)
        rows_cumulative += rows_seen  # a from-scratch daily retrain refits the union

        record = DayRecord(
            day=label,
            index=day_index,
            accepted=decision.accepted,
            reason=decision.reason,
            rows=int(raw.n_rows),
            touched_entities=touched,
            metrics=decision.candidate_metrics,
        )
        if decision.accepted:
            live = candidate
            record.snapshot = f"{snapshot_prefix}-{label}"
            if chain_dir and index_maps is not None:
                model_dir = os.path.join(chain_dir, "models", f"day-{label}")
                save_game_model(model_dir, live, index_maps)
                state["live_model_dir"] = model_dir
            if serving_root:
                record.published = _ensure_published(serving_root, record, live)
        else:
            logger.warning(
                "day %s: candidate refused by the promotion gate (%s); "
                "the previous model keeps serving", label, decision.reason,
            )

        ledger.append(record)
        state["days"] = _ledger_meta(ledger)
        state["in_progress"] = None
        state["rows_touched"] = rows_touched
        state["rows_cumulative"] = rows_cumulative
        state["rows_seen"] = rows_seen
        _save_chain_state(chain_dir, state)

    return ChainResult(
        model=live,
        ledger=ledger,
        rows_touched=rows_touched,
        rows_cumulative=rows_cumulative,
    )
