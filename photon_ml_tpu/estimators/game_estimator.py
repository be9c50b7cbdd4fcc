"""GameEstimator: the fit() API over GAME coordinate configurations.

Reference: photon-api .../estimators/GameEstimator.scala:53-705 —
fit(data, validationData, optimizationConfigurations) prepares per-coordinate
datasets once, builds the validation evaluation suite, then runs coordinate
descent once per optimization configuration, warm-starting each run from the
previous configuration's model (:356-374), returning one GameResult per
configuration. Regularization-weight grids expand as a cartesian product over
coordinates (GameTrainingDriver.prepareGameOptConfigs:623-632).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import weakref
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..evaluation.suite import EvaluationResults, build_suite
from ..game.coordinate import (
    Coordinate,
    FixedEffectCoordinate,
    ModelCoordinate,
    RandomEffectCoordinate,
)
from ..game.data import (
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from ..game.descent import CoordinateDescent, ValidationContext
from ..game.problem import GLMOptimizationConfig
from ..io.data import RawDataset
from ..models.game import GameModel
from ..ops.normalization import NormalizationContext
from .. import obs
from .. import plan as execution_plan
from ..utils.events import (
    EventEmitter,
    OptimizationLogEvent,
    TrainingFinishEvent,
    TrainingStartEvent,
)

logger = logging.getLogger("photon_ml_tpu")

# Validation contexts already built, shared by every estimator of the process:
# (id(validation set), signature of the build) -> (weak reference to the set,
# the arrays the build read from it, its ValidationContext). The set is held
# WEAKLY and its entries leave with it, so a context (and its device arrays)
# lives exactly as long as the caller's RawDataset; the arrays are held, so
# that `is` decides a hit and a recycled `id` never can.
_VALIDATION_CONTEXTS: Dict[tuple, tuple] = {}


@dataclasses.dataclass
class CoordinateConfig:
    """One coordinate's dataset + optimization definition (the reference's
    CoordinateConfiguration: dataset config + optimization config + reg grid)."""

    name: str
    feature_shard: str
    config: GLMOptimizationConfig
    random_effect_type: Optional[str] = None  # None => fixed effect
    reg_weights: Sequence[float] = ()  # grid; empty -> [config.reg_weight]
    active_cap: Optional[int] = None
    active_lower_bound: int = 1
    # Pearson feature selection: keep ceil(ratio * n_rows) features per entity
    # (numFeaturesToSamplesRatioUpperBound, RandomEffectDataset.scala:553-565)
    features_to_samples_ratio: Optional[float] = None
    # fixed-effect batch layout: auto|dense|ell|sparse|tiled ('tiled' shards the
    # coefficient dim over the estimator mesh's model axis — the huge-d path)
    layout: str = "auto"
    # optional narrower storage type for the dense feature matrix only (e.g.
    # jnp.bfloat16: halves the HBM traffic of the bandwidth-bound objective
    # sweeps; labels/offsets/weights/solver state stay in estimator dtype)
    feature_dtype: Optional[object] = None
    normalization: Optional[NormalizationContext] = None
    # incremental training: L2-regularize toward the warm-start model
    # ("Regularize by Previous Model During Warm-Start Training")
    regularize_by_prior: bool = False
    # out-of-core coordinates: when the coordinate's device data would exceed
    # this device-memory budget, keep it host-resident and stream
    # double-buffered slices through the chip (the reference's DISK_ONLY
    # spill scale path). Random effects stream entity slices
    # (game/streaming.py); fixed effects stream row slices
    # (game/fe_streaming.py — layouts auto|dense|ell, variance NONE, no
    # down-sampling). Composes with a mesh / multi-process: each host
    # streams its own shard under the per-host budget (plan/planner.py).
    hbm_budget_mb: Optional[int] = None

    @property
    def is_random_effect(self) -> bool:
        return self.random_effect_type is not None

    def grid(self) -> Sequence[float]:
        return tuple(self.reg_weights) or (self.config.reg_weight,)


@dataclasses.dataclass
class GameResult:
    model: GameModel
    config: Dict[str, float]  # coordinate -> reg weight
    evaluation: Optional[EvaluationResults]
    trackers: Dict[str, object]


class GameEstimator(EventEmitter):
    """Emits TrainingStart/OptimizationLog/TrainingFinish events to registered
    listeners (EventEmitter.scala semantics; the reference's telemetry hook)."""

    def __init__(
        self,
        task: str,
        coordinate_configs: Sequence[CoordinateConfig],
        n_cd_iterations: int = 1,
        evaluator_specs: Sequence[str] = (),
        dtype=jnp.float32,
        partial_retrain_locked: Sequence[str] = (),
        entity_pad_multiple: int = 1,
        mesh=None,
        validation_frequency: str = "COORDINATE",
        divergence_guard: bool = True,
        rejection_tolerance: Optional[float] = None,
        pipeline_depth: int = 1,
    ):
        super().__init__()
        if not coordinate_configs:
            raise ValueError("need at least one coordinate configuration")
        names = [c.name for c in coordinate_configs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate coordinate names: {names}")
        self.task = task
        self.coordinate_configs = list(coordinate_configs)
        self.n_cd_iterations = n_cd_iterations
        self.evaluator_specs = list(evaluator_specs)
        self.dtype = dtype
        self.partial_retrain_locked = set(partial_retrain_locked)
        self.mesh = mesh
        self.validation_frequency = validation_frequency
        # numerical-divergence defense knobs, passed straight through to
        # CoordinateDescent (see game/descent.py for semantics)
        self.divergence_guard = divergence_guard
        self.rejection_tolerance = rejection_tolerance
        # sweep pipelining depth (game/pipeline.py): 1 = serial; >= 2 runs
        # eval on a background lane and lets the streamed paths prefetch
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1: {pipeline_depth}")
        self.pipeline_depth = int(pipeline_depth)
        if mesh is not None and entity_pad_multiple == 1:
            # entity blocks shard over the data axis: pad to its size
            from ..parallel.mesh import DATA_AXIS

            entity_pad_multiple = mesh.shape[DATA_AXIS]
        self.entity_pad_multiple = entity_pad_multiple
        unknown = self.partial_retrain_locked - set(names)
        if unknown:
            raise ValueError(f"locked coordinates not in configs: {sorted(unknown)}")
        # ALL composition legality (layout x dtype x mesh x streaming x
        # pipelining) is the execution planner's: one resolve up front
        # replaces the per-knob checks that used to live here, and the
        # resolved plan stays introspectable for --explain-plan /
        # run_summary.json (plan/planner.py). Refusals raise PlanError (a
        # ValueError) with the ledger-pinned messages.
        # Notes the planner's routing table encodes:
        # - normalization works on tiled: GLMProblem pads the stats vectors
        #   to the mesh-padded dim with identity entries (the reference
        #   algebra is layout-agnostic, ValueAndGradientAggregator.scala)
        # - variance=FULL is supported on tiled via the chunked sharded
        #   X^T diag(c) X path (parallel/sparse.py xtcx) up to
        #   ops.glm.MAX_FULL_VARIANCE_DIM; the dim ceiling is checked at
        #   train time when d is known
        import jax

        self.execution_plan = execution_plan.resolve(
            self.coordinate_configs,
            mesh=mesh,
            n_processes=jax.process_count(),
            pipeline_depth=self.pipeline_depth,
            partial_retrain_locked=tuple(self.partial_retrain_locked),
        )

    # -- dataset preparation -------------------------------------------------

    def _prepare_datasets(self, raw: RawDataset):
        import jax

        multiprocess = jax.process_count() > 1
        # re-checked here (not just at __init__) because process topology can
        # be initialized between estimator construction and the first fit
        execution_plan.check_multiprocess_mesh(jax.process_count(), self.mesh)
        datasets = {}
        for cc in self.coordinate_configs:
            with obs.span("fit.prepare_dataset", coordinate=cc.name) as sp:
                datasets[cc.name] = self._prepare_dataset(raw, cc, multiprocess)
            logger.debug("prepare dataset %s took %.3fs", cc.name, sp.duration_s)
        return datasets

    def _prepare_dataset(self, raw: RawDataset, cc: CoordinateConfig, multiprocess: bool):
        budget = cc.hbm_budget_mb * (1 << 20) if cc.hbm_budget_mb is not None else None
        if cc.is_random_effect:
            re_kwargs = dict(
                active_cap=cc.active_cap,
                active_lower_bound=cc.active_lower_bound,
                dtype=self.dtype,
                pad_entities_to_multiple=self.entity_pad_multiple,
                features_to_samples_ratio=cc.features_to_samples_ratio,
                feature_dtype=cc.feature_dtype,
                hbm_budget_bytes=budget,
            )
            if multiprocess:
                # entity planning across hosts + device-side shuffle
                # (game/data_mp.py; the reference's partitioner+
                # partitionBy pipeline)
                from ..game.data_mp import build_random_effect_dataset_global

                return build_random_effect_dataset_global(
                    raw, cc.name, cc.feature_shard, cc.random_effect_type,
                    mesh=self.mesh, **re_kwargs,
                )
            ds = build_random_effect_dataset(
                raw, cc.name, cc.feature_shard, cc.random_effect_type, **re_kwargs
            )
            if self.mesh is not None and not ds.streamed:
                # streamed blocks are host-resident by design: they
                # stream through the chip in slices, so there is
                # nothing to place on the mesh
                from ..parallel.mesh import shard_entity_blocks

                ds = dataclasses.replace(
                    ds, blocks=shard_entity_blocks(ds.blocks, self.mesh)
                )
            return ds
        ds = build_fixed_effect_dataset(
            raw,
            cc.name,
            cc.feature_shard,
            dtype=self.dtype,
            layout=cc.layout,
            mesh=self.mesh,
            feature_dtype=cc.feature_dtype,
            hbm_budget_bytes=budget,
        )
        if ds.streamed:
            return ds
        if self.mesh is not None and cc.layout != "tiled":
            from ..parallel.mesh import shard_batch

            ds = dataclasses.replace(ds, batch=shard_batch(ds.batch, self.mesh))
        if multiprocess:
            # multi-process sample space is the padded GLOBAL row
            # space: scores/residuals stay [N_global], no trimming
            ds = dataclasses.replace(ds, true_n_rows=ds.batch.n_rows)
        return ds

    def _validation_context(
        self, val_raw: RawDataset
    ) -> Tuple[ValidationContext, Dict[str, object]]:
        """The validation context of ``val_raw``: built by the first call that
        presents this very data set under this signature, re-used by every
        later one, of this or any other estimator (``fit`` has the contract).
        The key is what the build reads and nothing else."""
        key = (
            id(val_raw),
            int(val_raw.n_rows),
            tuple(self.evaluator_specs or ["RMSE"]),
            jnp.dtype(self.dtype),
            # a mesh's models are placed on the mesh: its batches are built
            # for no one device (no local column map, ops/features.py)
            self.mesh is None,
            tuple(
                (
                    cc.name, cc.feature_shard, cc.random_effect_type,
                    # the width of a fixed effect's dense batch
                    None if cc.is_random_effect else int(val_raw.shard_dims[cc.feature_shard]),
                )
                for cc in self.coordinate_configs
            ),
        )
        # the arrays the build reads: a re-assigned field is a miss (writing
        # into one in place is not seen)
        read = (
            val_raw.labels, val_raw.weights, val_raw.offsets,
            *val_raw.id_tags.values(),
            *(x for cc in self.coordinate_configs for x in val_raw.shard_coo[cc.feature_shard]),
        )
        cached = _VALIDATION_CONTEXTS.get(key)
        reused = (
            cached is not None
            and cached[0]() is val_raw
            and len(cached[1]) == len(read)
            and all(a is b for a, b in zip(cached[1], read))
        )
        obs.current_run().registry.counter(
            "photon_validation_context_total",
            "validation contexts a fit asked for: built (suite, device batches, "
            "uploads) against reused (this very validation set, prepared before)",
        ).labels(kind="reused" if reused else "built").inc()
        with obs.span(
            "fit.validation_context", rows=int(val_raw.n_rows), reused=reused
        ):
            if reused:
                # the series exists in every run's registry, hit or miss
                obs.add_device_put_bytes("fit.validation_context", 0)
                context = cached[2]
            else:
                context = self._build_validation_context(val_raw)
                # the entry leaves when the data set dies, before its id can
                # be handed out again (the memo is bound: no global at exit)
                gone = lambda _, memo=_VALIDATION_CONTEXTS: memo.pop(key, None)  # noqa: E731
                _VALIDATION_CONTEXTS[key] = (weakref.ref(val_raw, gone), read, context)
        return context, context.score_fns

    def _build_validation_context(self, val_raw: RawDataset) -> ValidationContext:
        import jax

        suite = build_suite(
            self.evaluator_specs or ["RMSE"],
            val_raw.labels,
            val_raw.weights,
            id_tags=val_raw.id_tags,
        )
        # per-coordinate validation scoring closures
        from ..game.data import _rows_to_ell  # host helper

        score_fns = {}
        for cc in self.coordinate_configs:
            rows, cols, vals = val_raw.shard_coo[cc.feature_shard]
            if cc.is_random_effect:
                idx, val = _rows_to_ell(rows, cols, vals, val_raw.n_rows)
                ids = val_raw.id_tags[cc.random_effect_type]
                idx_j = jnp.asarray(idx)
                val_j = jnp.asarray(val, self.dtype)
                uploaded = (idx_j, val_j)

                def fn(model, _ids=ids, _idx=idx_j, _val=val_j):
                    erow = jnp.asarray(model.rows_for(_ids).astype(np.int32))
                    return model.score_ell_rows(erow, _idx, _val)

            else:
                batch = val_raw.to_batch(cc.feature_shard, dtype=self.dtype, mesh=self.mesh)
                uploaded = batch

                def fn(model, _batch=batch):
                    return _batch.features.matvec(model.model.coefficients.means)

            # host-known sizes of what this build put on the device
            obs.add_device_put_bytes(
                "fit.validation_context",
                sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(uploaded)),
            )
            score_fns[cc.name] = fn
        return ValidationContext(suite=suite, score_fns=score_fns, offsets=val_raw.offsets)

    def _make_coordinates(
        self,
        datasets,
        reg_weights: Mapping[str, float],
        initial_models: Mapping[str, object],
    ) -> Dict[str, Coordinate]:
        coords: Dict[str, Coordinate] = {}
        for cc in self.coordinate_configs:
            cfg = cc.config.with_reg_weight(reg_weights[cc.name])
            prior = initial_models.get(cc.name) if cc.regularize_by_prior else None
            if cc.is_random_effect:
                inner: Coordinate = RandomEffectCoordinate(
                    dataset=datasets[cc.name],
                    task=self.task,
                    config=cfg,
                    prior_model=prior,
                )
            else:
                inner = FixedEffectCoordinate(
                    dataset=datasets[cc.name],
                    task=self.task,
                    config=cfg,
                    normalization=cc.normalization,
                    prior_model=prior,
                )
            if cc.name in self.partial_retrain_locked:
                locked = initial_models.get(cc.name)
                if locked is None:
                    raise ValueError(
                        f"locked coordinate {cc.name} needs a pretrained model"
                    )
                coords[cc.name] = ModelCoordinate(inner=inner, locked_model=locked)
            else:
                coords[cc.name] = inner
        return coords

    # -- fit -------------------------------------------------------------------

    def prepare_datasets(self, raw: RawDataset):
        """Build per-coordinate datasets once; pass the result to ``fit`` via
        ``datasets=`` to train several configurations (checkpointed grids,
        tuning trials) without rebuilding."""
        return self._prepare_datasets(raw)

    def fit(
        self,
        raw: RawDataset,
        validation: Optional[RawDataset] = None,
        initial_model: Optional[GameModel] = None,
        checkpoint_fn: Optional[object] = None,
        datasets: Optional[Dict[str, object]] = None,
        combos: Optional[Sequence[Mapping[str, float]]] = None,
        n_cd_iterations: Optional[int] = None,
        boundary_fn: Optional[object] = None,
        resume_state: Optional[object] = None,
    ) -> List[GameResult]:
        """``checkpoint_fn(reg_weights, iteration, game_model)`` runs after
        each completed coordinate-descent sweep of each configuration.

        ``boundary_fn(reg_weights, state)`` runs after EVERY coordinate
        update of every configuration (``state`` is descent's
        CDBoundaryState) — the fine-grained crash-safety hook
        (robust.CheckpointManager). ``resume_state`` (a
        robust.CheckpointSnapshot) resumes the FIRST combo in ``combos``
        mid-run; callers resuming a grid pass the remaining combos
        explicitly, snapshot matching the first.

        ``datasets``: pre-built datasets from :meth:`prepare_datasets`.
        ``combos``: explicit list of per-coordinate reg-weight dicts to train
        instead of the full cartesian grid (checkpoint resume trains the
        remaining combos one at a time). ``n_cd_iterations`` overrides the
        estimator's sweep count for THIS call (resuming a partly-trained
        configuration).

        ``validation`` may be a RawDataset, or a deferred one — a
        ``concurrent.futures.Future`` or zero-arg callable resolving to a
        RawDataset. A deferred validation is resolved only AFTER the training
        datasets are built, so a background decode thread (the CLI's ingest
        overlap; the native Avro decoder releases the GIL) runs concurrently
        with dataset preparation and device uploads.

        A validation RawDataset is READ ONCE: its context (evaluation suite,
        per-coordinate device batches, the evaluator's compiled program) is
        built by the first fit that presents it and re-used by every later
        fit, of any estimator with the same evaluators, dtype and coordinate
        shards, that presents the same object. Writing into its arrays in
        place afterwards is not seen: re-assign the field or pass a new data
        set. While the caller keeps the data set, its context keeps
        N_val * (d_fixed + 2 * sum of the random effects' ELL widths) * 4
        bytes on the device; both go when the data set goes. Fits that share
        a validation set run one at a time (the pipelined eval lane evaluates
        on the fit's own suite)."""
        with obs.span("fit") as fit_span:
            if datasets is None:
                datasets = self._prepare_datasets(raw)
            if validation is not None:
                if hasattr(validation, "result"):
                    validation = validation.result()
                elif callable(validation):
                    validation = validation()
            validation_ctx = None
            if validation is not None:
                # evaluator_specs default to RMSE inside _validation_context
                validation_ctx, _ = self._validation_context(validation)

            # cartesian product of per-coordinate reg-weight grids
            grids = [cc.grid() for cc in self.coordinate_configs]
            names = [cc.name for cc in self.coordinate_configs]
            if combos is None:
                combos = [
                    dict(zip(names, combo)) for combo in itertools.product(*grids)
                ]
            n_iterations = (
                self.n_cd_iterations if n_cd_iterations is None else n_cd_iterations
            )
            results: List[GameResult] = []
            prev_models: Dict[str, object] = dict(
                (initial_model.models if initial_model else {})
            )
            import time as _time

            fit_span.attrs["n_combos"] = len(combos)
            self.send_event(TrainingStartEvent(time=_time.time()))
            for combo_index, reg_weights in enumerate(combos):
                reg_weights = dict(reg_weights)
                with obs.span(
                    "fit.combo", index=combo_index, reg_weights=reg_weights
                ) as combo_span:
                    with obs.span("fit.make_coordinates", index=combo_index):
                        coords = self._make_coordinates(
                            datasets, reg_weights, prev_models
                        )
                    cd_ckpt = None
                    if checkpoint_fn is not None:
                        task = self.task
                        cd_ckpt = lambda it, models, _w=reg_weights: checkpoint_fn(
                            _w, it, GameModel(models=models, task=task)
                        )
                    cd_boundary = None
                    if boundary_fn is not None:
                        cd_boundary = lambda st, _w=reg_weights: boundary_fn(_w, st)
                    cd = CoordinateDescent(
                        coords, n_iterations=n_iterations,
                        validation=validation_ctx, checkpoint_fn=cd_ckpt,
                        validation_frequency=self.validation_frequency,
                        boundary_fn=cd_boundary,
                        # a snapshot describes one in-flight configuration — the
                        # first combo of a resumed call; later combos start fresh
                        resume_state=resume_state if combo_index == 0 else None,
                        divergence_guard=self.divergence_guard,
                        rejection_tolerance=self.rejection_tolerance,
                        pipeline_depth=self.pipeline_depth,
                    )
                    out = cd.run(initial_models=prev_models)
                    results.append(
                        GameResult(
                            model=out.model,
                            config=reg_weights,
                            evaluation=out.best_evaluation,
                            trackers=out.trackers,
                        )
                    )
                    self.send_event(
                        OptimizationLogEvent(
                            reg_weights=reg_weights,
                            trackers=out.trackers,
                            metrics=(
                                None
                                if out.best_evaluation is None
                                else dict(out.best_evaluation.metrics)
                            ),
                        )
                    )
                    # warm start next config from this one (GameEstimator.scala:356-374)
                    prev_models = dict(out.model.models)
                logger.info(
                    "train config %s took %.3fs", reg_weights, combo_span.duration_s
                )
            self.send_event(TrainingFinishEvent(time=_time.time()))
        return results

    def fit_lanes(
        self,
        raw: RawDataset,
        combos: Sequence[Mapping[str, float]],
        validation: Optional[RawDataset] = None,
        datasets: Optional[Dict[str, object]] = None,
        n_cd_iterations: Optional[int] = None,
    ) -> List[GameResult]:
        """Train ``len(combos)`` reg-weight configurations as lambda LANES of
        one batched coordinate-descent run (game/lanes.py): every lane shares
        each coordinate's data residency and compiled solver, the per-lane
        reg weight rides as a vector operand. Returns one GameResult per
        combo, in order — the batched counterpart of calling :meth:`fit`
        once per combo. See game.lanes.check_lane_composition for the
        compositions this path refuses."""
        from ..game.lanes import fit_lanes as _fit_lanes

        return _fit_lanes(
            self,
            raw,
            combos,
            validation=validation,
            datasets=datasets,
            n_cd_iterations=n_cd_iterations,
        )

    def select_best(self, results: Sequence[GameResult]) -> GameResult:
        """Best result by primary validation metric (falls back to the last)."""
        with_eval = [r for r in results if r.evaluation is not None]
        if not with_eval:
            return results[-1]
        suite_primary = build_suite(
            self.evaluator_specs or ["RMSE"], np.zeros(1)
        ).primary
        best = with_eval[0]
        for r in with_eval[1:]:
            if suite_primary.better(
                r.evaluation.primary_metric, best.evaluation.primary_metric
            ):
                best = r
        return best


@dataclasses.dataclass
class GameTransformer:
    """Scoring twin of the estimator (GameTransformer.scala:39-318):
    model + dataset -> summed per-coordinate scores (+offsets), optional eval."""

    model: GameModel
    dtype: object = jnp.float32

    def transform(
        self, raw: RawDataset, evaluator_specs: Sequence[str] = ()
    ) -> Tuple[np.ndarray, Optional[EvaluationResults]]:
        # one score assembly for the whole repo: the serving engine's compiled
        # kernels (serving/engine.py), so batch and resident scoring cannot
        # drift (tests/test_serving.py pins bitwise parity)
        from ..serving.engine import ScoreEngine

        total = ScoreEngine.from_model(self.model, dtype=self.dtype).score_dataset(raw)

        evaluation = None
        if evaluator_specs:
            suite = build_suite(
                evaluator_specs, raw.labels, raw.weights, id_tags=raw.id_tags
            )
            evaluation = suite.evaluate(total)
        return total, evaluation
