"""GAME coordinates: the training/scoring unit of coordinate descent.

Reference: photon-lib .../algorithm/Coordinate.scala:28-84 (trainModel with
optional initial model + residual scores, score), FixedEffectCoordinate.scala
(whole-dataset GLM solve with broadcast model — here: jit over the, possibly
mesh-sharded, global batch), RandomEffectCoordinate.scala:42-375 (per-entity
solves — here: one vmapped masked solver over entity blocks), and the locked
Fixed/RandomEffectModelCoordinate stubs that only score (partial retraining).

Scores returned by coordinates NEVER include base offsets: the coordinate-
descent loop owns residual composition (CoordinateDataScores semantics, P7).
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from functools import partial
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import obs
from ..utils.transfer import logged_fetch
from ..models.coefficients import Coefficients
from ..models.game import FixedEffectModel, RandomEffectModel
from ..models.glm import GeneralizedLinearModel, model_for_task
from ..ops.losses import get_loss
from ..ops.normalization import NormalizationContext
from ..optimize import OptimizerType, SolverResult, solve_lbfgs, solve_tron
from ..optimize.common import abs_tolerances
from ..robust import faults
from .data import (
    EntityBlocks,
    FixedEffectDataset,
    RandomEffectDataset,
    _chunk_rows,
    bucket_blocks,
    record_block_store,
    size_buckets,
)
from .problem import GLMOptimizationConfig, GLMProblem
from .sampling import down_sample

Array = jax.Array


class Coordinate:
    """Base coordinate API (Coordinate.scala:28-84)."""

    coordinate_id: str

    @property
    def n_rows(self) -> int:
        raise NotImplementedError

    def train(self, residual_scores: Optional[Array], initial_model):
        """-> (model, SolverResult-or-None). residual_scores f[n] are OTHER
        coordinates' summed scores, added to base offsets for this solve."""
        raise NotImplementedError

    def score(self, model) -> Array:
        """Per-sample scores of this coordinate's model, excluding offsets."""
        raise NotImplementedError


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    """Whole-dataset GLM solve (FixedEffectCoordinate.scala:33-154)."""

    dataset: FixedEffectDataset
    task: str
    config: GLMOptimizationConfig
    normalization: Optional[NormalizationContext] = None
    down_sampling_seed: int = 0
    # incremental training: regularize toward this model instead of zero
    prior_model: Optional[FixedEffectModel] = None

    def __post_init__(self):
        self.coordinate_id = self.dataset.coordinate_id

    @property
    def n_rows(self) -> int:
        return self.dataset.n_rows

    def train(
        self,
        residual_scores: Optional[Array],
        initial_model: Optional[FixedEffectModel] = None,
    ) -> Tuple[FixedEffectModel, SolverResult]:
        if self.dataset.streamed:
            return self._train_streamed(residual_scores, initial_model)
        batch = self.dataset.batch
        residuals = residual_scores is not None
        if residuals:
            # residual scores live in true sample space; padded batch rows
            # (mesh row multiples) carry zero residual
            n_pad = batch.n_rows - residual_scores.shape[0]
            if n_pad > 0:
                residual_scores = jnp.concatenate(
                    [residual_scores, jnp.zeros((n_pad,), residual_scores.dtype)]
                )
            batch = batch.with_offsets(batch.offsets + residual_scores)
        if self.config.down_sampling_rate < 1.0:
            # runWithSampling (DistributedOptimizationProblem.scala:155-170)
            batch = down_sample(
                batch, self.task, self.config.down_sampling_rate, self.down_sampling_seed
            )
        if faults.active():
            # fault site solver.value_and_grad: corrupt the effective offsets
            # feeding this solve. train() runs eagerly at host level, so the
            # schedule decision never bakes into a compiled function.
            batch = batch.with_offsets(
                faults.corrupt("solver.value_and_grad", batch.offsets)
            )
        problem = GLMProblem(
            task=self.task,
            config=self.config,
            normalization=self.normalization,
            prior=self.prior_model.model.coefficients if self.prior_model else None,
        )
        nnz = self.dataset.nnz
        slots = getattr(batch.features, "slots", None)
        if nnz is not None and slots is not None:
            # host-known from the build: the entries every pass of this solve
            # touches, the shard's own against the layout's padding
            slot_counter = obs.current_run().registry.counter(
                "photon_fe_slots_total",
                "feature slots handed to the fixed-effect solver, "
                "stored entries against the layout's padding",
            )
            slot_counter.labels(coordinate=self.coordinate_id, kind="real").inc(nnz)
            slot_counter.labels(coordinate=self.coordinate_id, kind="padded").inc(
                max(slots - nnz, 0)
            )
        glm, result = problem.run(
            batch,
            initial_model=initial_model.model if initial_model else None,
            coordinate=self.coordinate_id,
            nnz=nnz,
            residuals=residuals,
        )
        if jax.process_count() > 1:
            # tiled solves leave coefficients model-axis-sharded across
            # processes; replicate so every host can read/save the model
            from ..parallel import multihost

            mesh = getattr(batch.features, "mesh", None)
            if mesh is not None:
                glm = dataclasses.replace(
                    glm,
                    coefficients=multihost.fully_replicate(glm.coefficients, mesh),
                )
                result = multihost.fully_replicate(result, mesh)
        # models live in the shard's TRUE feature space: trim any mesh padding
        d_true = self.dataset.dim
        if glm.coefficients.means.shape[0] > d_true:
            glm = dataclasses.replace(
                glm,
                coefficients=Coefficients(
                    means=glm.coefficients.means[:d_true],
                    variances=None
                    if glm.coefficients.variances is None
                    else glm.coefficients.variances[:d_true],
                ),
            )
        return (
            FixedEffectModel(model=glm, feature_shard=self.dataset.feature_shard),
            result,
        )

    def train_lanes(
        self,
        residual_lanes: Array,  # f[n, L] per-lane residual scores
        l2_lanes: Array,  # f[L] per-lane L2 weights
        w0_lanes: Optional[Array] = None,  # f[d_true, L] warm start
    ) -> Tuple[Array, SolverResult]:
        """Lane-stacked train: L lambda candidates share this batch's data
        residency and one compiled solve (game/lanes.py sweep executor).
        Returns (coefficients f[d_true, L], per-lane SolverResult). The fault
        site mirrors :meth:`train`: flat index 0 of the [n, L] offsets is row
        0 / lane 0, so an injected NaN poisons exactly one lane."""
        if self.dataset.streamed:
            raise ValueError(
                "trial-lanes sweeps require HBM-resident coordinates"
                f" (coordinate {self.coordinate_id} is streamed)"
            )
        if self.config.down_sampling_rate < 1.0:
            raise ValueError(
                "down-sampling is not supported with trial-lanes"
            )
        batch = self.dataset.batch
        L = residual_lanes.shape[1]
        n_pad = batch.n_rows - residual_lanes.shape[0]
        if n_pad > 0:
            residual_lanes = jnp.concatenate(
                [residual_lanes, jnp.zeros((n_pad, L), residual_lanes.dtype)]
            )
        offsets_lanes = batch.offsets[:, None] + residual_lanes
        if faults.active():
            offsets_lanes = faults.corrupt(
                "solver.value_and_grad", offsets_lanes
            )
        if w0_lanes is not None and w0_lanes.shape[0] < batch.dim:
            w0_lanes = jnp.concatenate(
                [
                    w0_lanes,
                    jnp.zeros(
                        (batch.dim - w0_lanes.shape[0], L), w0_lanes.dtype
                    ),
                ]
            )
        problem = GLMProblem(
            task=self.task,
            config=self.config,
            normalization=self.normalization,
            prior=self.prior_model.model.coefficients if self.prior_model else None,
        )
        W, result = problem.run_lanes(
            batch, offsets_lanes, l2_lanes, w0=w0_lanes
        )
        d_true = self.dataset.dim
        if W.shape[0] > d_true:
            W = W[:d_true]
        return W, result

    def score_lanes(self, W: Array) -> Array:
        """Per-sample scores [n, L] of lane-stacked coefficients W[d, L] —
        one fused matmat instead of L matvec dispatches."""
        feats = self.dataset.batch.features
        dtype = self.dataset.batch.labels.dtype
        W = jnp.asarray(W, dtype)
        d_pad = feats.dim - W.shape[0]
        if d_pad > 0:
            W = jnp.concatenate(
                [W, jnp.zeros((d_pad, W.shape[1]), W.dtype)]
            )
        scores = feats.matmat(W)
        n_true = self.dataset.n_rows
        return scores[:n_true] if scores.shape[0] > n_true else scores

    def _train_streamed(
        self,
        residual_scores: Optional[Array],
        initial_model: Optional[FixedEffectModel] = None,
    ) -> Tuple[FixedEffectModel, SolverResult]:
        """Out-of-core FE solve: host-resident rows streamed through the chip
        in double-buffered row slices (game/fe_streaming.py; the reference's
        DISK_ONLY spill + treeAggregate scale path for the fixed effect,
        AvroDataReader.scala:165-209)."""
        ds = self.dataset
        hb = ds.host_batch
        if self.config.down_sampling_rate < 1.0:
            raise ValueError(
                f"coordinate {self.coordinate_id}: down_sampling_rate < 1 is"
                " not supported on the streamed fixed-effect path; raise"
                " hbm.budget.mb so the batch is HBM-resident, or disable"
                " down-sampling"
            )
        if faults.active():
            # same fault site as the resident path: corrupt the host offsets
            # feeding this solve (faults.corrupt copies numpy leaves)
            hb = dataclasses.replace(
                hb, offsets=faults.corrupt("solver.value_and_grad", hb.offsets)
            )
        if (
            residual_scores is not None
            and ds.mesh is not None
            and jax.process_count() > 1
        ):
            # the residual is the global row-sharded [N] vector; this host
            # streams only ITS row slice, so hand the objective the local
            # block (trimmed of the per-host mesh padding rows). A fully
            # replicated residual (e.g. the zeros vector of the first sweep)
            # comes back global from host_local_rows — slice this process's
            # padded block out of it first.
            from ..parallel import multihost

            local = multihost.host_local_rows(residual_scores)
            n_loc_pad = self.n_rows // jax.process_count()
            if local.shape[0] > n_loc_pad:
                start = jax.process_index() * n_loc_pad
                local = local[start : start + n_loc_pad]
            residual_scores = local[: hb.n_rows]
        problem = GLMProblem(
            task=self.task,
            config=self.config,
            normalization=self.normalization,
            prior=self.prior_model.model.coefficients if self.prior_model else None,
        )
        glm, result = problem.run_streamed(
            hb,
            ds.hbm_budget_bytes,
            residual_scores=residual_scores,
            initial_model=initial_model.model if initial_model else None,
        )
        return (
            FixedEffectModel(model=glm, feature_shard=ds.feature_shard),
            result,
        )

    def score(self, model: FixedEffectModel) -> Array:
        if self.dataset.streamed:
            from .fe_streaming import score_streamed_fe

            ds = self.dataset
            hb = ds.host_batch
            dtype = hb.labels.dtype
            means = jnp.asarray(model.model.coefficients.means, dtype)
            d_pad = hb.dim - means.shape[0]
            if d_pad > 0:
                means = jnp.concatenate([means, jnp.zeros((d_pad,), means.dtype)])
            scores = score_streamed_fe(hb, means, ds.hbm_budget_bytes, dtype)
            if ds.mesh is not None and jax.process_count() > 1:
                # local row scores -> global row-sharded vector: pad this
                # host's slice to the per-host mesh chunk (zero-score pad
                # rows, like pad_rows_for_mesh) and put_global
                from jax.sharding import PartitionSpec
                from ..parallel import multihost
                from ..parallel.mesh import DATA_AXIS

                local = np.asarray(
                    logged_fetch("coordinate.fe_stream_score", scores)
                )
                chunk = max(
                    ds.mesh.shape[DATA_AXIS] // jax.process_count(), 1
                )
                n_pad = -(-local.shape[0] // chunk) * chunk
                if n_pad > local.shape[0]:
                    local = np.concatenate(
                        [local, np.zeros(n_pad - local.shape[0], local.dtype)]
                    )
                return multihost.put_global(
                    local, ds.mesh, PartitionSpec(DATA_AXIS)
                )
            return scores
        feats = self.dataset.batch.features
        with obs.span(
            "fe.score", coordinate=self.coordinate_id,
            gather=getattr(feats, "gather", "global"),
            gather_columns=getattr(feats, "gather_columns", feats.dim),
        ) as sp:
            scores = self._score_resident(model)
            sp.sync(scores)
        return scores

    def _score_resident(self, model: FixedEffectModel) -> Array:
        feats = self.dataset.batch.features
        # compute in the dataset's dtype: a warm-start model loaded under an
        # x64 config is f64 and must not promote the f32 score/residual stream
        dtype = self.dataset.batch.labels.dtype
        means = jnp.asarray(model.model.coefficients.means, dtype)
        d_pad = feats.dim - means.shape[0]
        if d_pad > 0:
            means = jnp.concatenate([means, jnp.zeros((d_pad,), means.dtype)])
        mesh = getattr(feats, "mesh", None)
        if mesh is not None and jax.process_count() > 1:
            # tiled matvec shard_maps over the model axis: reshard the vector
            # on device (no host round trip — the d-sized fetch would cost
            # seconds at huge d)
            from jax.sharding import PartitionSpec
            from ..parallel import multihost
            from ..parallel.sparse import MODEL_AXIS

            means = multihost.reshard(means, mesh, PartitionSpec(MODEL_AXIS))
        scores = feats.matvec(means)
        n_true = self.dataset.n_rows
        return scores[:n_true] if scores.shape[0] > n_true else scores


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Entity-blocked batched solves (RandomEffectCoordinate.scala:42-375).

    The reference joined per-entity datasets with per-entity problems and ran
    thousands of small sequential L-BFGS solves inside each partition (P8).
    Here all entities advance in lockstep through ONE vmapped masked solver —
    each lane converges and freezes independently — and entity blocks shard
    over the mesh on dim 0.
    """

    dataset: RandomEffectDataset
    task: str
    config: GLMOptimizationConfig
    # incremental training: per-entity prior means/precisions
    prior_model: Optional[RandomEffectModel] = None

    def __post_init__(self):
        self.coordinate_id = self.dataset.coordinate_id

    @property
    def n_rows(self) -> int:
        ds = self.dataset
        if ds.entity_shard_range is not None:
            # streamed + sharded: the row arrays hold this host's equal-share
            # slice of the padded global row space
            return ds.row_entity.shape[0] * jax.process_count()
        return ds.row_entity.shape[0]

    def train(
        self,
        residual_scores: Optional[Array],
        initial_model: Optional[RandomEffectModel] = None,
    ) -> Tuple[RandomEffectModel, SolverResult]:
        if self.dataset.streamed:
            return self._train_streamed(residual_scores, initial_model)
        # Size-bucketed solves: each of the dataset's chunks is sorted by
        # descending row count and dealt the same size profile, so a (K, S)-
        # rounded bucket is the SAME local row range of every chunk; solving
        # per bucket avoids every small entity paying the padding of the
        # largest (RandomEffectDatasetPartitioner's size-awareness, re-purposed
        # for vmap lane economy), and under a mesh every chip holds an equal
        # share of every bucket. The blocks are STORED at these shapes
        # (game/data.py): a bucket's arrays go to the solver whole. No
        # buckets: one whole-extent bucket.
        blocks, buckets = _bucketed_blocks(self.dataset)
        E, K, S = blocks.features.shape
        # solver state stays in the WIDE dtype: features may be stored bf16
        # (feature_dtype), labels/weights/offsets carry the solve precision
        dtype = blocks.labels.dtype
        chunks = self.dataset.entity_chunks
        sharded = _chunk_axis(blocks.features, chunks)
        exchange = partial(
            _bucket_offsets, blocks.active_rows.parts, blocks.offsets.parts,
            chunks=chunks, sharded=sharded,
        )
        if residual_scores is not None:
            # the residual exchange: every slot a bucket will solve gathers
            # its row's residual (the other coordinates' summed scores);
            # block_slots is the logical [E, K] extent the buckets lie in
            with obs.span(
                "re.exchange", coordinate=self.coordinate_id, entities=E,
                slots=sum(chunks * (end - start) * kb for start, end, kb, _ in buckets),
                block_slots=E * K,
            ) as sp:
                bucket_offsets = exchange(residual_scores)
                sp.sync(bucket_offsets)
        else:
            bucket_offsets = exchange(None)
        if faults.active():
            # same fault site as the fixed-effect path; flat index 0 of the
            # FIRST bucket's offsets is entity 0's first row, so the
            # corruption deterministically poisons exactly one entity lane
            # (handed every bucket's array it would poison one lane in each).
            # (The streamed path carries no injection site — its offsets
            # never materialize whole.)
            bucket_offsets = (
                faults.corrupt("solver.value_and_grad", bucket_offsets[0]),
                *bucket_offsets[1:],
            )

        # w0/priors: multi-process passes host numpy (every process holds the
        # full array; jit treats numpy inputs as replicated contributions).
        # Single-process on an ACCELERATOR creates the default zeros/ones ON
        # DEVICE — host [E, S] uploads per train call would otherwise ride
        # the host->device link every sweep. On
        # the CPU backend host numpy is kept: the transfer is a memcpy, and
        # device-created inputs to the sharded-blocks pjit tickled an XLA:CPU
        # compiler segfault under long test sessions (observed at
        # test_scale_paths with 8 virtual devices).
        multiproc = jax.process_count() > 1
        if multiproc or jax.default_backend() == "cpu":
            xp, xdt = np, np.dtype(dtype)
            # explicit logged fetch: warm-start/prior projections may land on
            # device; the CD sweep runs under transfer_guard, which rejects
            # a bare np.asarray on device arrays
            to_host = lambda a: logged_fetch("coordinate.host_state", a)  # noqa: E731
        else:
            xp, xdt = jnp, dtype
            to_host = lambda a: a  # noqa: E731 — single decision point
        # the solver's state: a warm start and a prior come as [E, S] tables
        # through the model projection, whose layout fetches block (the span
        # is their parent); the defaults (zeros, ones) are made at each
        # bucket's own shape below and never as tables
        w0 = prior_mean = prior_prec = None
        with obs.span(
            "re.warm_start", coordinate=self.coordinate_id,
            warm=initial_model is not None, priors=self.prior_model is not None,
        ) as sp:
            if initial_model is not None:
                w0 = to_host(
                    _initial_subspace_coefficients(self.dataset, initial_model, dtype)
                )
            if self.prior_model is not None:
                prior_mean = to_host(
                    _project_model_values(
                        self.dataset, self.prior_model, self.prior_model.coef_values, dtype
                    )
                )
                if self.prior_model.variances is not None:
                    var = _project_model_values(
                        self.dataset, self.prior_model, self.prior_model.variances, dtype
                    )
                    prior_prec = to_host(1.0 / jnp.maximum(var, 1e-12))
            sp.sync(w0, prior_mean, prior_prec)

        solver_kwargs = self._solver_kwargs()
        defaults = _default_state(self.dataset, blocks, xp, xdt)
        # a bucket's shape and its real rows and cells are the data set's,
        # reckoned once: a train call makes no pass over the statistics
        accounts = _bucket_accounts(self.dataset, blocks)
        parts = []
        for b, ((start, end, kb, sb), offsets) in enumerate(zip(buckets, bucket_offsets)):
            shape = accounts.buckets[b]
            with obs.span("re.bucket", coordinate=self.coordinate_id, **shape) as sp:
                # with a sink the bucket says how much of its enqueue is the
                # cut of the [E, S] state tables a warm start or a prior
                # brought (the blocks come cut: they are stored so): the rest
                # of enqueue_s is the solve's dispatch
                # photon: ignore[R7] — an attribute of the bucket's own
                # span (a child span a bucket would be one more event)
                cut_start = time.perf_counter() if obs.active() else None
                zeros, ones = defaults[b]
                state = tuple(
                    default if table is None
                    else _state_rows(table, chunks, sharded, start, end, sb)
                    for table, default in (
                        (w0, zeros), (prior_mean, zeros), (prior_prec, ones)
                    )
                )
                if cut_start is not None:
                    # photon: ignore[R7] — closes the stamp above
                    sp.attrs["cut_s"] = time.perf_counter() - cut_start
                part = _train_blocks_packed(
                    blocks.features.parts[b], blocks.labels.parts[b], offsets,
                    blocks.weights.parts[b], *state, **solver_kwargs,
                )
                sp.sync(part)
            parts.append(part)
        if obs.active() and not multiproc:
            # (across processes a bucket's lanes are not all addressable
            # from here; the trackers count them after the collect)
            self._record_lane_iterations(parts)
        _record_accounts(self.coordinate_id, accounts)
        with obs.span("re.collect", coordinate=self.coordinate_id) as sp:
            results = (
                parts[0]
                if len(parts) == 1 and parts[0].coefficients.shape == (E, S)
                else _concat_results(parts, S, chunks, sharded)
            )
            if multiproc:
                # entity-sharded outputs span processes; replicate so every
                # host can read the model (saving, validation scoring,
                # trackers) — the reference's collect-model-to-driver step
                from ..parallel import multihost

                mesh = blocks.features.sharding.mesh
                results = multihost.fully_replicate(results, mesh)
                coef_indices = jnp.asarray(self.dataset.host_proj_cols)
            else:
                coef_indices = blocks.proj_cols
            w_sub = results.coefficients  # [E, S]
            valid = coef_indices >= 0
            model = RandomEffectModel(
                random_effect_type=self.dataset.random_effect_type,
                feature_shard=self.dataset.feature_shard,
                task=self.task,
                entity_ids=self.dataset.entity_ids,
                coef_indices=coef_indices,
                coef_values=jnp.where(valid, w_sub, 0.0),
            )
            sp.sync(model.coef_values)
        # provenance mark (weakref: must not pin the dataset's device arrays
        # to the model's lifetime): this model's support layout IS this
        # dataset's block layout, so score() can take the cached-positions
        # fast path without fetching/comparing the [E, S] index arrays
        object.__setattr__(model, "_support_layout_of", weakref.ref(self.dataset))
        return model, results

    def _record_lane_iterations(self, parts: list) -> None:
        """A lockstep bucket runs until its slowest lane stops: ``useful`` is
        the iterations its lanes needed, ``issued`` what the bucket ran for
        all of them. Called only with a sink attached, after the buckets'
        fences: ONE fetch of every bucket's iterations array as it stands (a
        reduction on the device would be one more program, in traced runs
        alone; a fetch a bucket was most of a small call's own host time)."""
        fetched = logged_fetch("re.bucket_iterations", [p.iterations for p in parts])
        buckets = [np.asarray(iters) for iters in fetched if np.size(iters)]
        if not buckets:
            return
        counter = obs.current_run().registry.counter(
            "photon_re_lane_iterations_total",
            "random-effect solver iterations per bucket: useful (summed over "
            "lanes) against issued (lanes x the bucket's slowest lane)",
        )
        counter.labels(coordinate=self.coordinate_id, kind="useful").inc(
            sum(int(iters.sum()) for iters in buckets)
        )
        counter.labels(coordinate=self.coordinate_id, kind="issued").inc(
            sum(int(iters.size) * int(iters.max()) for iters in buckets)
        )

    def _solver_kwargs(self) -> dict:
        """Shared static solver arguments — ONE construction site so the
        in-memory and streamed paths cannot drift."""
        cfg = self.config
        solver_cfg = cfg.solver_config()
        return dict(
            task=self.task,
            l2=cfg.regularization.l2_weight(cfg.reg_weight),
            l1=solver_cfg.l1_weight,
            optimizer_type=OptimizerType(solver_cfg.normalized_type()).value,
            tolerance=solver_cfg.tolerance,
            max_iterations=solver_cfg.max_iterations,
            num_corrections=solver_cfg.num_corrections,
            max_cg_iterations=solver_cfg.max_cg_iterations,
            max_improvement_failures=solver_cfg.max_improvement_failures,
        )

    def train_lanes(
        self,
        residual_lanes: Array,  # f[n, L] per-lane residual scores
        l2_lanes: Array,  # f[L] per-lane L2 weights
        w0_lanes: Optional[Array] = None,  # f[E, S, L] warm start
    ) -> Tuple[Array, SolverResult]:
        """Lane-stacked train: every (entity, lambda) pair is one lockstep
        solver lane (game/lanes.py sweep executor). Returns (coef_values
        f[E, S, L] zeroed outside each entity's support, per-lane
        SolverResult with loss/reason [E, L]).

        No size-bucketing here: bucketed stitching pads the trailing axis
        (_concat_results.pad_cols), which on this path is the LANE axis — one
        full-shape solve keeps the layout unambiguous, and the sweep already
        amortizes the padding over L lambdas. The fault site mirrors
        :meth:`train`: flat index 0 of the [E, K, L] offsets is entity 0 /
        row 0 / lane 0."""
        if self.dataset.streamed:
            raise ValueError(
                "trial-lanes sweeps require HBM-resident coordinates"
                f" (coordinate {self.coordinate_id} is streamed)"
            )
        if self.prior_model is not None:
            raise ValueError(
                "regularize-by-prior is not supported with trial-lanes"
            )
        # one full-shape solve over the logical planes, assembled from the
        # store once a dataset (ROADMAP.md Design: the lane-stacked solve has
        # no bucketed form yet)
        blocks = _plane_blocks(self.dataset)
        E, K, S = blocks.features.shape
        dtype = blocks.labels.dtype
        L = residual_lanes.shape[1]
        res = jnp.take(
            residual_lanes, jnp.maximum(blocks.active_rows, 0), axis=0
        ) * (blocks.active_rows >= 0)[:, :, None]
        offsets_lanes = blocks.offsets[:, :, None] + res.astype(dtype)
        if faults.active():
            offsets_lanes = faults.corrupt(
                "solver.value_and_grad", offsets_lanes
            )
        # same host-numpy zeros policy as train(): CPU backend keeps w0 on
        # host (device-created pjit inputs tickled an XLA:CPU segfault)
        if jax.process_count() > 1 or jax.default_backend() == "cpu":
            if w0_lanes is None:
                w0 = np.zeros((E, S, L), np.dtype(jnp.zeros((), dtype).dtype))
            else:
                w0 = np.asarray(
                    logged_fetch("coordinate.host_state", w0_lanes)
                )
        else:
            w0 = (
                jnp.zeros((E, S, L), dtype)
                if w0_lanes is None
                else jnp.asarray(w0_lanes, dtype)
            )
        solver_kwargs = self._solver_kwargs()
        if solver_kwargs.pop("l1") > 0.0:
            raise ValueError(
                "trial-lanes sweeps support L2 regularization only (the "
                "OWL-QN l1 weight is one operand of a solve, not a per-lane "
                "vector)"
            )
        del solver_kwargs["l2"]  # replaced by the dynamic l2_lanes operand
        results = _train_blocks_packed_lanes(
            blocks.features,
            blocks.labels,
            offsets_lanes,
            blocks.weights,
            w0,
            jnp.asarray(l2_lanes, dtype),
            **solver_kwargs,
        )
        valid = blocks.proj_cols >= 0
        W = jnp.where(valid[:, :, None], results.coefficients, 0.0)
        return W, results

    def score_lanes(self, coef_values: Array) -> Array:
        """Per-sample scores [n, L] of lane-stacked per-entity coefficients
        [E, S, L], reusing the score cache of the sequential scoring hot path
        (``_score_cache``: in either form one gather + a dot for all L lanes)."""
        from ..models.game import (
            score_entity_ell_at_lanes,
            score_entity_rows_dense_lanes,
        )

        ds = self.dataset
        form, cache = _score_cache(ds)
        score_dt = jnp.promote_types(ds.ell_val.dtype, ds.blocks.labels.dtype)
        vals = jnp.asarray(coef_values, score_dt)
        if form == "subspace":
            return score_entity_rows_dense_lanes(vals, ds.row_entity, cache)
        return score_entity_ell_at_lanes(vals, ds.row_entity, *cache, ds.ell_val)

    def _train_streamed(
        self,
        residual_scores: Optional[Array],
        initial_model: Optional[RandomEffectModel] = None,
    ) -> Tuple[RandomEffectModel, SolverResult]:
        """Out-of-core solve: host-resident blocks streamed through the chip
        in double-buffered entity slices (game/streaming.py; the reference's
        DISK_ONLY spill scale path, CoordinateDescent.scala:262,404)."""
        from .streaming import solve_streamed

        ds = self.dataset
        blocks = ds.blocks  # host numpy (streamed+sharded: the local range)
        E, K, S = blocks.features.shape
        sdt = blocks.labels.dtype  # solve dtype (features may be narrower)
        shard = ds.entity_shard_range  # set only when streamed + sharded
        E_g = ds.num_entities  # global entity count (== E when unsharded)

        # warm start / priors are projected in the GLOBAL entity layout
        # (_project_model_values keys off host_proj_cols), then sliced to
        # this host's block-row range for the local solve
        if initial_model is not None:
            w0 = _project_model_values(
                ds, initial_model, initial_model.coef_values, sdt, to_device=False
            )
        else:
            w0 = np.zeros((E_g, S), sdt)
        prior_mean = np.zeros((E_g, S), sdt)
        prior_prec = np.ones((E_g, S), sdt)
        if self.prior_model is not None:
            prior_mean = _project_model_values(
                ds, self.prior_model, self.prior_model.coef_values, sdt,
                to_device=False,
            )
            if self.prior_model.variances is not None:
                var = _project_model_values(
                    ds, self.prior_model, self.prior_model.variances, sdt,
                    to_device=False,
                )
                prior_prec = (1.0 / np.maximum(var, 1e-12)).astype(sdt)

        if shard is not None:
            from ..parallel import multihost

            lo, hi = shard
            w0 = w0[lo:hi]
            prior_mean = prior_mean[lo:hi]
            prior_prec = prior_prec[lo:hi]
            if residual_scores is not None:
                # local active_rows index the PADDED GLOBAL row space, so
                # the solve needs the FULL residual addressable on this
                # host: replicate, fetch, re-place as a plain local array
                residual_scores = jnp.asarray(
                    logged_fetch(
                        "coordinate.stream_residual",
                        multihost.fully_replicate(residual_scores, ds.mesh),
                    )
                )

        solver_kwargs = self._solver_kwargs()
        segments = _contiguous_segments(ds, entity_range=shard) or [(0, E, K, S)]
        results = solve_streamed(
            blocks,
            segments,
            residual_scores,
            w0,
            prior_mean,
            prior_prec,
            ds.hbm_budget_bytes,
            _train_blocks_packed,
            solver_kwargs,
        )
        if shard is not None:
            # every host solved ITS contiguous block-row range; process order
            # IS entity order, so a host-side allgather + concat rebuilds the
            # global result table on every host (the reference's
            # collect-model-to-driver step, host-side because the tables are
            # host numpy by streamed design)
            parts = multihost.allgather_object(results)
            results = _concat_results_np(parts)
            coef_indices = np.asarray(ds.host_proj_cols)
        else:
            coef_indices = blocks.proj_cols
        valid = coef_indices >= 0
        model = RandomEffectModel(
            random_effect_type=ds.random_effect_type,
            feature_shard=ds.feature_shard,
            task=self.task,
            entity_ids=ds.entity_ids,
            coef_indices=coef_indices,
            coef_values=np.where(valid, results.coefficients, 0.0),
        )
        object.__setattr__(model, "_support_layout_of", weakref.ref(ds))
        return model, results

    def _support_layout_matches(self, model: RandomEffectModel) -> bool:
        """True when model.coef_indices is this dataset's own block layout
        (the coordinate-descent case). Checks provenance/identity first;
        falls back to a memoized array comparison (bounded FIFO memo holding
        strong refs, so a GC'd array's id cannot alias a stale entry; the
        host proj_cols fetch is cached on the dataset)."""
        ds = self.dataset
        prov = getattr(model, "_support_layout_of", None)
        if prov is not None and prov() is ds:
            return True
        ci = model.coef_indices
        if ci is ds.blocks.proj_cols:
            return True
        memo = getattr(ds, "_layout_match_memo", None)
        if memo is None:
            memo = {}
            object.__setattr__(ds, "_layout_match_memo", memo)
        hit = memo.get(id(ci))
        if hit is not None and hit[0] is ci:
            return hit[1]
        pc_host = getattr(ds, "_host_proj_cols_cache", None)
        if pc_host is None:
            pc_host = ds.host_proj_cols
            if pc_host is None:
                pc_host = logged_fetch(
                    "coordinate.layout_check", ds.blocks.proj_cols
                )
            object.__setattr__(ds, "_host_proj_cols_cache", pc_host)
        ok = tuple(ci.shape) == tuple(np.shape(pc_host)) and np.array_equal(
            logged_fetch("coordinate.layout_check", ci), pc_host
        )
        while len(memo) >= 8:  # bounded: drop oldest entries
            memo.pop(next(iter(memo)))
        memo[id(ci)] = (ci, ok)
        return ok

    def score(self, model: RandomEffectModel) -> Array:
        if self.dataset.streamed:
            from .streaming import score_streamed

            ds = self.dataset
            # identity short-circuit: CD-trained models carry the dataset's
            # own entity_ids array — avoid two O(E) str() list builds per
            # sweep at streamed (big-E) scale
            same_ids = model.entity_ids is ds.entity_ids or list(
                map(str, ds.entity_ids)
            ) == list(map(str, model.entity_ids))
            same_layout = same_ids and self._support_layout_matches(model)
            sdt = np.dtype(ds.blocks.labels.dtype)  # solve/residual dtype
            if same_layout:
                vals = np.asarray(
                    logged_fetch("coordinate.stream_score_model", model.coef_values),
                    sdt,
                )
            else:
                # re-project a differently laid-out model into this dataset's
                # entity/subspace layout on host (no device round trip)
                vals = _project_model_values(
                    ds, model, model.coef_values, sdt, to_device=False
                )
            cache = getattr(ds, "_stream_xsub_cache", None)
            # streamed + sharded: row_entity holds GLOBAL block-row indices,
            # so the coefficient table and support layout must be the GLOBAL
            # ones (blocks.proj_cols covers only this host's range)
            proj = (
                np.asarray(ds.host_proj_cols)
                if ds.entity_shard_range is not None
                else np.asarray(ds.blocks.proj_cols)
            )
            scores, cache = score_streamed(
                vals,
                proj,
                ds.row_entity,
                ds.ell_idx,
                ds.ell_val,
                ds.hbm_budget_bytes,
                cache,
                score_dtype=jnp.promote_types(ds.ell_val.dtype, sdt),
            )
            object.__setattr__(ds, "_stream_xsub_cache", cache)
            if ds.entity_shard_range is not None:
                # local row scores -> global row-sharded vector (each host
                # contributed exactly its padded row slice)
                from jax.sharding import PartitionSpec
                from ..parallel import multihost
                from ..parallel.mesh import DATA_AXIS

                local = np.asarray(
                    logged_fetch("coordinate.stream_score", scores)
                )
                scores = multihost.put_global(
                    local, ds.mesh, PartitionSpec(DATA_AXIS)
                )
            return scores
        with obs.span("re.score", coordinate=self.coordinate_id) as sp:
            scores = self._score_resident(model, sp)
            sp.sync(scores)
        return scores

    def _score_resident(self, model: RandomEffectModel, sp=None) -> Array:
        row_entity = self.dataset.row_entity
        # The model's entity-row order may differ from this dataset's block
        # order (warm start from a loaded model, locked partial-retrain
        # models): remap dataset block rows -> model rows by entity id.
        # Device-side gather: works when row_entity is sharded across
        # processes (multi-process) as well as single-host.
        # (identity first: a model this coordinate trained carries the
        # dataset's own id array, and two str() lists of every entity a score
        # are host time that grows with E)
        same_ids = model.entity_ids is self.dataset.entity_ids
        if not same_ids:
            ds_ids = list(map(str, self.dataset.entity_ids))
            m_ids = list(map(str, model.entity_ids))
            same_ids = ds_ids == m_ids
        if same_ids and self._support_layout_matches(model):
            # coordinate-descent hot path: the support LAYOUT is this
            # dataset's own block layout, so where each row's features land
            # in its entity's subspace is resolved once and cached
            # (``_score_cache``); each sweep's score is then one gather and
            # a dot
            from ..models.game import score_entity_ell_at, score_entity_rows_dense

            form, cache = _score_cache(self.dataset)
            if sp is not None:
                sp.attrs["form"] = form
            # scores compute in the WIDE dtype: bf16 feature storage must not
            # truncate the coefficients or the residual stream
            score_dt = jnp.promote_types(
                self.dataset.ell_val.dtype, self.dataset.blocks.labels.dtype
            )
            vals = jnp.asarray(model.coef_values, score_dt)
            if form == "subspace":
                return score_entity_rows_dense(vals, row_entity, cache)
            return score_entity_ell_at(
                vals, row_entity, *cache, self.dataset.ell_val
            )
        if sp is not None:
            sp.attrs["form"] = "searched"
        if not same_ids:
            block_to_model = model.rows_for(self.dataset.entity_ids).astype(np.int32)
            row_entity = jnp.where(
                row_entity >= 0,
                jnp.take(jnp.asarray(block_to_model), jnp.maximum(row_entity, 0)),
                -1,
            ).astype(jnp.int32)
        ds_dtype = jnp.promote_types(
            self.dataset.ell_val.dtype, self.dataset.blocks.labels.dtype
        )
        if model.coef_values.dtype != ds_dtype:
            model = dataclasses.replace(
                model, coef_values=jnp.asarray(model.coef_values, ds_dtype)
            )
        return model.score_ell_rows(row_entity, self.dataset.ell_idx, self.dataset.ell_val)


def _size_buckets(
    dataset: RandomEffectDataset,
    min_dim: int = 8,
    align: int = 1,
):
    """The dataset's size buckets ``[(start, end, K_b, S_b)]`` (game/data.py
    ``size_buckets``, from its per-entity stats), or None when per-entity
    stats are unavailable or bucketing cannot shrink anything: the shapes the
    entity blocks are stored at and each bucket's solve runs at. The stats
    cover ALL block rows (streamed + sharded blocks hold one host's range of
    them). ``align`` (the per-device entity chunk) is accepted for the
    benchmark's re_pad_share reader, which passes it, and changes nothing: a
    bucket takes the same rows of every chunk, so no part splits a device
    shard."""
    del align
    counts = dataset.entity_counts
    svec = dataset.entity_subspace_dims
    if counts is None or svec is None:
        return None
    _, K, S = dataset.blocks.features.shape
    return size_buckets(counts, svec, K, S, dataset.entity_chunks, min_dim)


def _bucketed_blocks(dataset: RandomEffectDataset):
    """(the resident dataset's entity blocks stored by bucket, the buckets).
    The single-process build stores them so. Blocks that arrive as planes
    (hand-assembled; the multi-process build, game/data_mp.py) are cut once,
    here, each bucket's five arrays in one program (per device under a mesh),
    and kept on the dataset beside the plane (ROADMAP.md Design)."""
    blocks = dataset.blocks
    if blocks.bucketed:
        return blocks, blocks.features.segments
    cached = getattr(dataset, "_bucketed_blocks_cache", None)
    if cached is None:
        E, K, S = blocks.features.shape
        chunks = dataset.entity_chunks
        segments = tuple(_size_buckets(dataset) or [(0, E // chunks, K, S)])
        cut = None
        if chunks > 1 and not isinstance(blocks.features, np.ndarray):
            sharded = _chunk_axis(blocks.features, chunks)
            cut = lambda planes, start, end, dims: _chunk_rows_of(  # noqa: E731
                planes, chunks=chunks, start=start, end=end, dims=dims, sharded=sharded
            )
        bucketed = bucket_blocks(blocks, segments, chunks, cut)
        cached = (bucketed, segments)
        object.__setattr__(dataset, "_bucketed_blocks_cache", cached)
    return cached


def _score_cache(dataset: RandomEffectDataset):
    """(form, cached operand) of the random-effect score under the dataset's
    own support layout, resolved once a dataset. The two forms compute the
    same sums; which one is what the shapes say, nothing else:

    - ``subspace``: every row's features densified into its entity's
      subspace, ``x_sub f[n, S]`` (models/game.py ``ell_row_subspace``), the
      score one contiguous row gather of the ``[E, S]`` table and a dot.
      Taken when S <= F (a dense shard: every entity's subspace is the
      shard, and x_sub is no larger than the rows' own slots).
    - ``slots``: where each of the row's F slots lands, ``(pos, hit)
      [n, F]`` (``ell_slot_positions``: no ``[n, S]`` array on the way
      either), the score a gather of the table at (entity, pos) pairs. Taken
      when S > F (ragged subspaces over a sparse shard: x_sub would be S / F
      times the data, most of it zeros under the widest entity's width)."""
    cache = getattr(dataset, "_score_form_cache", None)
    if cache is None:
        from ..models.game import ell_row_subspace, ell_slot_positions

        proj_cols = dataset.blocks.proj_cols
        if proj_cols.shape[1] <= dataset.ell_idx.shape[1]:
            cache = ("subspace", ell_row_subspace(
                proj_cols, dataset.row_entity, dataset.ell_idx, dataset.ell_val
            ))
        else:
            cache = ("slots", ell_slot_positions(
                proj_cols, dataset.row_entity, dataset.ell_idx
            ))
        object.__setattr__(dataset, "_score_form_cache", cache)
    return cache


def _plane_blocks(dataset: RandomEffectDataset) -> EntityBlocks:
    """The dataset's entity blocks as logical ``[E, K(, S)]`` planes,
    assembled from the store on demand and kept on the dataset: for the
    trial-lanes solve alone, which runs one full-shape program."""
    blocks = dataset.blocks
    if not blocks.bucketed:
        return blocks
    cached = getattr(dataset, "_plane_blocks_cache", None)
    if cached is None:
        cached = EntityBlocks(
            features=blocks.features.plane(), labels=blocks.labels.plane(),
            offsets=blocks.offsets.plane(), weights=blocks.weights.plane(),
            proj_cols=blocks.proj_cols, active_rows=blocks.active_rows.plane(),
        )
        object.__setattr__(dataset, "_plane_blocks_cache", cached)
    return cached


class _BucketAccounts(NamedTuple):
    """What a train call reports of its buckets, host-known from the data
    set: each bucket's ``re.bucket`` attributes, the call's totals for the
    slot, row and cell counters (None with no per-entity statistics) and the
    store's bytes for its gauge."""

    buckets: Tuple[dict, ...]
    totals: Optional[dict]
    store_bytes: int


def _bucket_accounts(dataset: RandomEffectDataset, blocks: EntityBlocks) -> _BucketAccounts:
    """``_BucketAccounts`` of the dataset's stored buckets, made once a
    dataset (the statistics are immutable)."""
    cached = getattr(dataset, "_bucket_accounts_cache", None)
    if cached is not None:
        return cached
    chunks = dataset.entity_chunks
    counts, sdims = dataset.entity_counts, dataset.entity_subspace_dims
    S = blocks.features.shape[2]
    if counts is not None:
        chunk_counts = np.asarray(counts).reshape(chunks, -1)
        # an entity's real feature cells: its rows times its own subspace
        chunk_cells = chunk_counts * (
            np.asarray(sdims).reshape(chunks, -1) if sdims is not None else S
        )
    shapes = []
    for start, end, kb, sb in blocks.features.segments:
        entities = chunks * (end - start)
        shape = dict(
            k=kb, s=sb, entities=entities, slots=entities * kb, chunks=chunks,
            cells=entities * kb * sb,
        )
        if counts is not None:
            chunk_real = chunk_counts[:, start:end].sum(axis=1)
            shape["real_rows"] = int(chunk_real.sum())
            # the chips' balance: real_rows over chunks * this
            shape["max_chunk_real_rows"] = int(chunk_real.max())
            shape["real_cells"] = int(chunk_cells[:, start:end].sum())
        shapes.append(shape)
    totals = None
    if counts is not None:
        totals = {
            key: sum(shape[key] for shape in shapes)
            for key in ("slots", "cells", "real_rows", "real_cells")
        }
        totals["passive_rows"] = len(dataset.passive_rows)
    cached = _BucketAccounts(tuple(shapes), totals, blocks.store_bytes)
    object.__setattr__(dataset, "_bucket_accounts_cache", cached)
    return cached


def _record_accounts(coordinate_id: str, accounts: _BucketAccounts) -> None:
    """A resident train call's counters, and the store's gauge again (the
    build set it: a registry attached after the build reads it too)."""
    record_block_store(coordinate_id, accounts.store_bytes)
    totals = accounts.totals
    if totals is None:
        return
    registry = obs.current_run().registry
    slot_counter = registry.counter(
        "photon_re_block_slots_total",
        "entity-block row slots handed to the random-effect solver, "
        "real rows against bucket padding",
    )
    slot_counter.labels(coordinate=coordinate_id, kind="real").inc(totals["real_rows"])
    slot_counter.labels(coordinate=coordinate_id, kind="padded").inc(
        totals["slots"] - totals["real_rows"]
    )
    # host-known from the dataset: the rows this coordinate trains on (the
    # buckets' real slots) against the rows over the active cap, which it
    # only scores
    row_counter = registry.counter(
        "photon_re_rows_total",
        "rows of a random-effect coordinate per train call: active "
        "(in an entity block) against passive (scored, never trained)",
    )
    row_counter.labels(coordinate=coordinate_id, kind="active").inc(totals["real_rows"])
    row_counter.labels(coordinate=coordinate_id, kind="passive").inc(totals["passive_rows"])
    # the feature cells the buckets hold against the cells the entities' own
    # rows x subspaces fill: what the S rounding and the widest entity of a
    # bucket cost, beside the row padding
    cell_counter = registry.counter(
        "photon_re_subspace_cells_total",
        "entity-block feature cells handed to the random-effect solver, "
        "real (an entity's rows x its own subspace) against bucket padding",
    )
    cell_counter.labels(coordinate=coordinate_id, kind="real").inc(totals["real_cells"])
    cell_counter.labels(coordinate=coordinate_id, kind="padded").inc(
        totals["cells"] - totals["real_cells"]
    )


def _default_state(dataset: RandomEffectDataset, blocks: EntityBlocks, xp, xdt):
    """Per bucket, the solver's default state at the bucket's own shape:
    (zeros, ones) ``[chunks * (end - start), S_b]`` for a cold start and a
    plain-L2 prior. Made once a dataset (immutable: the solve donates
    nothing) and placed as the bucket's blocks are, so a sharded solve takes
    them on the chips that hold its rows; no ``[E, S]`` table of defaults
    exists."""
    key = (xp.__name__, str(xdt))
    cache = getattr(dataset, "_default_state_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(dataset, "_default_state_cache", cache)
    if key not in cache:
        sharded = _chunk_axis(blocks.features, dataset.entity_chunks)
        place = {}
        if xp is jnp and sharded is not None:
            mesh, axis = sharded
            place = dict(device=NamedSharding(mesh, PartitionSpec(axis)))
        cache[key] = tuple(
            (
                xp.zeros((part.shape[0], sb), xdt, **place),
                xp.ones((part.shape[0], sb), xdt, **place),
            )
            for part, (_, _, _, sb) in zip(blocks.features.parts, blocks.features.segments)
        )
    return cache[key]


def _state_rows(table, chunks: int, sharded, start: int, end: int, sb: int):
    """A bucket's rows of an ``[E, S]`` state table (a warm start, a prior),
    cut to its S_b: host tables (the CPU backend, across processes) and one
    sorted run by plain slices, several chunks of a device table in ONE
    program, per device under ``sharded``."""
    if chunks == 1 or isinstance(table, np.ndarray):
        return _chunk_rows(table, chunks, start, end, sb)
    return _chunk_rows_of(
        (table,), chunks=chunks, start=start, end=end, dims=((sb,),), sharded=sharded
    )[0]


def _contiguous_segments(
    dataset: RandomEffectDataset,
    entity_range: Optional[Tuple[int, int]] = None,
):
    """``_size_buckets`` as contiguous block-row segments, for the streamed
    solve (game/streaming.py walks host blocks slice by slice): every chunk's
    copy of every bucket, in block-row order — ``entity_chunks`` times as many
    segments of the same shapes. ``entity_range`` (streamed + sharded: the
    blocks hold only this host's [lo, hi) of the block rows) keeps the parts
    inside the range, relative to ``lo``."""
    local = _size_buckets(dataset)
    if local is None:
        return None
    chunk_rows = dataset.num_entities // dataset.entity_chunks
    lo, hi = entity_range if entity_range is not None else (0, dataset.num_entities)
    segments = []
    for c in range(dataset.entity_chunks):
        for start, end, kb, sb in local:
            s = max(c * chunk_rows + start, lo)
            e = min(c * chunk_rows + end, hi)
            if s < e:
                segments.append((s - lo, e - lo, kb, sb))
    return segments or None


def _entity_shard_align(blocks) -> int:
    """Per-device chunk size of mesh-sharded entity blocks (1 = unsharded).
    Read by the benchmark's re_pad_share reader alone: the solve's buckets
    are chunk-local (``_size_buckets``)."""
    try:
        sh = blocks.features.sharding
        if len(sh.device_set) > 1:
            chunk = sh.shard_shape(blocks.features.shape)[0]
            if chunk < blocks.features.shape[0]:
                return int(chunk)
    except AttributeError:
        # host-numpy blocks (streamed datasets) carry no .sharding: unsharded
        pass
    return 1


def _chunk_axis(a, chunks: int):
    """(mesh, axis name) when ``a``'s leading axis is sharded over one mesh
    axis that deals whole chunks to every device (what ``shard_entity_blocks``
    places), else None."""
    sharding = getattr(a, "sharding", None)
    if not isinstance(sharding, NamedSharding) or len(sharding.device_set) == 1:
        return None
    axis, *rest = tuple(sharding.spec) or (None,)
    if not isinstance(axis, str) or any(r is not None for r in rest):
        return None
    return (sharding.mesh, axis) if chunks % sharding.mesh.shape[axis] == 0 else None


def _per_device(fn, chunks: int, sharded, n_whole: int = 0):
    """``fn(chunks_here, arrays, *whole)`` over the whole arrays, or under
    ``sharded`` = (mesh, axis) per device over that device's own chunks of
    ``arrays`` (shard_map: no row leaves its chip, and the per-device program is
    the one-chip one); the ``n_whole`` operands after them reach every device
    entire."""
    if sharded is None:
        return partial(fn, chunks)
    mesh, axis = sharded
    spec = PartitionSpec(axis)
    return jax.shard_map(
        partial(fn, chunks // mesh.shape[axis]), mesh=mesh,
        in_specs=(spec,) + (PartitionSpec(),) * n_whole, out_specs=spec,
    )


@partial(jax.jit, static_argnames=("chunks", "start", "end", "dims", "sharded"))
def _chunk_rows_of(arrays, *, chunks, start, end, dims, sharded):
    """``_chunk_rows`` of several device arrays as ONE program."""

    def cut(chunks_here, arrays):
        return tuple(
            _chunk_rows(a, chunks_here, start, end, *d) for a, d in zip(arrays, dims)
        )

    return _per_device(cut, chunks, sharded)(arrays)


def _bucket_offsets(active_parts, offset_parts, residual_scores, *, chunks, sharded):
    """The residual exchange as ONE program: every bucket's solver offsets,
    one ``[chunks * (end - start), K_b]`` array a bucket, rows as the store
    orders them. Only the slots a bucket solves are gathered: ``active_parts``
    and ``offset_parts`` are the store's own per-bucket arrays, the residual
    is gathered at those rows, padding slots (-1) masked, and added. Under
    ``sharded`` every chip gathers for its own chunks from the whole [N]
    residual (all-gathered once on entry when it is row-sharded).
    ``residual_scores`` None: the blocks' own offsets, as stored."""
    if residual_scores is None:
        return tuple(offset_parts)
    return _gather_bucket_offsets(
        tuple(active_parts), tuple(offset_parts), residual_scores,
        chunks=chunks, sharded=sharded,
    )


@partial(jax.jit, static_argnames=("chunks", "sharded"))
def _gather_bucket_offsets(active_parts, offset_parts, residual_scores, *, chunks, sharded):
    def gather(_chunks_here, blocks, residual):
        def one(rows, offsets):
            # on the transposed view: the TPU holds a narrow [n_b, K_b] part
            # entity-minor (K_b < 128 lanes), where the transpose is free and
            # the gather's indices are lane-dense; gathered as stored, it
            # re-laid every such part out row-major, padded to 128 lanes
            rows = rows.T
            res = jnp.take(residual, jnp.maximum(rows, 0), axis=0) * (rows >= 0)
            return (offsets.T + res.astype(offsets.dtype)).T

        return tuple(one(rows, offsets) for rows, offsets in zip(*blocks))

    return _per_device(gather, chunks, sharded, 1)(
        (active_parts, offset_parts), residual_scores
    )


def _concat_results(parts, S: int, chunks: int = 1, sharded=None) -> SolverResult:
    """Stitch per-bucket SolverResults back into block-row order, zero-padding
    coefficients/gradients to the global subspace dim. A part holds its
    bucket's rows of every chunk, chunk-major (``_chunk_rows``); several
    chunks are put back in ONE program, per device under ``sharded``."""
    if chunks == 1:
        return _stitch_results(S, 1, parts)
    return _stitch_chunked_results(parts, S=S, chunks=chunks, sharded=sharded)


def _stitch_results(S: int, chunks: int, parts) -> SolverResult:
    def field(name):
        columns = [getattr(p, name) for p in parts]
        if columns[0] is None:  # OWL-QN's counters: the packed solve sets none
            return None
        if name in ("coefficients", "gradient"):
            columns = [
                a if a.shape[-1] == S else jnp.pad(a, ((0, 0), (0, S - a.shape[-1])))
                for a in columns
            ]
        if chunks == 1:
            return jnp.concatenate(columns)
        # chunk by chunk, every bucket's rows of it
        return jnp.concatenate(
            [
                a[i * (a.shape[0] // chunks) : (i + 1) * (a.shape[0] // chunks)]
                for i in range(chunks)
                for a in columns
            ]
        )

    return SolverResult(**{f.name: field(f.name) for f in dataclasses.fields(SolverResult)})


@partial(jax.jit, static_argnames=("S", "chunks", "sharded"))
def _stitch_chunked_results(parts, *, S: int, chunks: int, sharded) -> SolverResult:
    return _per_device(partial(_stitch_results, S), chunks, sharded)(parts)


def _concat_results_np(parts) -> SolverResult:
    """Stitch per-host streamed SolverResults (host numpy) into the global
    entity order — process order == entity order because the streamed entity
    shard ranges are contiguous and ascending by process."""
    if len(parts) == 1:
        return parts[0]
    return SolverResult(
        **{
            f.name: np.concatenate([np.asarray(getattr(p, f.name)) for p in parts])
            for f in dataclasses.fields(SolverResult)
            if getattr(parts[0], f.name) is not None
        }
    )


def _project_model_values(
    dataset: RandomEffectDataset, model: RandomEffectModel, values, dtype,
    to_device: bool = True,
) -> Array:
    """Project per-entity values stored in ``model``'s (entity, support)
    layout into this dataset's entity/subspace block layout (model projection,
    reference ModelProjection.scala:30-85). ``to_device=False`` keeps the
    result in host numpy (streamed datasets must not materialize [E, S] on
    device)."""
    blocks = dataset.blocks
    # multi-process: blocks.proj_cols is entity-sharded (not host-addressable)
    # or, streamed+sharded, holds only the local block-row range; the dataset
    # carries a GLOBAL host copy for layout checks and projection — shapes
    # derive from it so the projection is always in the global entity layout
    pc_host = dataset.host_proj_cols
    if pc_host is None:
        pc_host = logged_fetch("coordinate.project_layout", blocks.proj_cols)
    E, S = np.shape(pc_host)
    idx = np.asarray(
        logged_fetch("coordinate.project_layout", model.coef_indices)
    )
    if (
        idx.shape == (E, S)
        and model.num_entities == E
        and np.array_equal(idx, pc_host)
        and list(map(str, model.entity_ids)) == list(map(str, dataset.entity_ids))
    ):
        # same layout: reuse directly
        if not to_device:
            return np.asarray(
                logged_fetch("coordinate.project_values", values), dtype
            )
        return jnp.asarray(values, dtype)
    # general path: one vectorized sorted-key lookup over all (entity, column)
    # support pairs — no per-entity Python loop and no dense [E, global_dim]
    # intermediate, so re-projecting a large RE model from a differently
    # laid-out checkpoint stays O(nnz log nnz) host time.
    dim = int(max(int(pc_host.max(initial=0)), int(idx.max(initial=0))) + 1)
    vals = np.asarray(logged_fetch("coordinate.project_values", values))
    me, ms = np.nonzero(idx >= 0)
    mkeys = me.astype(np.int64) * dim + idx[me, ms]
    order = np.argsort(mkeys, kind="stable")
    mkeys_s = mkeys[order]
    mvals_s = vals[me, ms][order]

    rows = np.asarray(
        jax.device_get(model.rows_for(dataset.entity_ids))
    )  # [E] model row or -1
    pc = pc_host
    de, dsl = np.nonzero((pc >= 0) & (rows[:, None] >= 0))
    dkeys = rows[de].astype(np.int64) * dim + pc[de, dsl]
    w0 = np.zeros((E, S))
    if len(mkeys_s) and len(dkeys):
        # side='right' - 1: among duplicate support columns the LAST stored
        # value wins, matching numpy fancy-assignment (the prior dense path)
        pos = np.clip(np.searchsorted(mkeys_s, dkeys, side="right") - 1, 0, None)
        hit = mkeys_s[pos] == dkeys
        w0[de[hit], dsl[hit]] = mvals_s[pos[hit]]
    return np.asarray(w0, dtype) if not to_device else jnp.asarray(w0, dtype)


def _initial_subspace_coefficients(
    dataset: RandomEffectDataset, model: RandomEffectModel, dtype
) -> Array:
    """Warm-start coefficients in this dataset's block layout."""
    return _project_model_values(dataset, model, model.coef_values, dtype)


@partial(
    jax.jit,
    static_argnames=(
        "task",
        "l2",
        "l1",
        "optimizer_type",
        "tolerance",
        "max_iterations",
        "num_corrections",
        "max_cg_iterations",
        "max_improvement_failures",
    ),
)
def _train_blocks_packed(
    features: Array,  # [E, K, S]
    labels: Array,
    offsets: Array,
    weights: Array,
    w0: Array,  # [E, S]
    prior_mean: Array,  # [E, S]; zeros = plain L2
    prior_prec: Array,  # [E, S]; ones = plain L2
    *,
    task: str,
    l2: float,
    l1: float,
    optimizer_type: str,
    tolerance: float,
    max_iterations: int,
    num_corrections: int,
    max_cg_iterations: int,
    max_improvement_failures: int,
) -> SolverResult:
    """Entity-minor lockstep solve over all entity blocks.

    The tests' reference (``testing/reference_solver.py``) solves the same
    contract by vmapping with the entity axis leading; [E, K, S] puts S in the
    TPU's 128-wide lane dimension, and at S=32 that wastes 3/4 of every
    vector op. Here the data is transposed so
    the ENTITY axis is minor: features [K, S, E], coefficients [S, E]. Every
    solver op is then elementwise over a fully packed lane dimension whatever
    S is, and the per-entity reductions are axis-0 sums. This is the
    lane-packing redesign of the reference's per-partition sequential solves
    (RandomEffectCoordinate.scala:273-329). The transpose happens inside jit
    so GSPMD sharding propagates (entity-sharded blocks stay entity-sharded
    on the trailing axis).
    """
    loss = get_loss(task)
    # features may be stored narrower (bf16); products below promote to the
    # labels' (solve) dtype on the fly, halving the F sweep traffic
    F = jnp.transpose(features, (1, 2, 0))  # [K, S, E]
    y = labels.T  # [K, E]
    off = offsets.T.astype(labels.dtype)
    wt = weights.T
    w0t = w0.T  # [S, E]
    pm = prior_mean.T
    pp = prior_prec.T

    def value_and_grad(w):  # [S, E] -> ([E], [S, E])
        z = jnp.sum(F * w[None, :, :], axis=1) + off  # [K, E]
        lvals, dz = loss.loss_and_dz(z, y)
        wdz = wt * dz
        value = jnp.sum(wt * lvals, axis=0)  # [E]
        grad = jnp.sum(F * wdz[:, None, :], axis=0)  # [S, E]
        delta = w - pm
        value = value + 0.5 * l2 * jnp.sum(pp * delta * delta, axis=0)
        grad = grad + l2 * pp * delta
        return value, grad

    def hessian_vector(w, v):
        z = jnp.sum(F * w[None, :, :], axis=1) + off
        c = wt * loss.d2z(z, y) * jnp.sum(F * v[None, :, :], axis=1)  # [K, E]
        return jnp.sum(F * c[:, None, :], axis=0) + l2 * pp * v

    loss_tol, grad_tol = abs_tolerances(value_and_grad, w0t, tolerance)
    if optimizer_type == "TRON":
        res = solve_tron(
            value_and_grad,
            hessian_vector,
            w0t,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            max_cg_iterations=max_cg_iterations,
            max_improvement_failures=max_improvement_failures,
        )
    else:
        res = solve_lbfgs(
            value_and_grad,
            w0t,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            num_corrections=num_corrections,
            l1_weight=l1,
            batched=True,
        )
    return SolverResult(
        coefficients=res.coefficients.T,
        loss=res.loss,
        gradient=res.gradient.T,
        iterations=res.iterations,
        reason=res.reason,
        loss_history=res.loss_history.T,
        grad_norm_history=res.grad_norm_history.T,
        cg_iterations=res.cg_iterations,
    )


@partial(
    jax.jit,
    static_argnames=(
        "task",
        "optimizer_type",
        "tolerance",
        "max_iterations",
        "num_corrections",
        "max_cg_iterations",
        "max_improvement_failures",
    ),
)
def _train_blocks_packed_lanes(
    features: Array,  # [E, K, S]
    labels: Array,  # [E, K]
    offsets_lanes: Array,  # [E, K, L] residual-composed per-lane offsets
    weights: Array,  # [E, K]
    w0: Array,  # [E, S, L]
    l2_lanes: Array,  # f[L] — dynamic operand, NOT static: candidate
    # refreshes must reuse the executable
    *,
    task: str,
    optimizer_type: str,
    tolerance: float,
    max_iterations: int,
    num_corrections: int,
    max_cg_iterations: int,
    max_improvement_failures: int,
) -> SolverResult:
    """Entity-minor lockstep solve widened by the lambda-lane axis.

    Same contract as :func:`_train_blocks_packed`, with the solver lane set
    the (entity, lambda) product: coefficients run as ``[S, E, L]`` so every
    per-problem reduction stays axis-0 and the L2 weight vector broadcasts
    from the trailing lane axis. One executable covers every candidate batch
    of the same L (the lambdas are data, not shape)."""
    loss = get_loss(task)
    F = jnp.transpose(features, (1, 2, 0))  # [K, S, E]
    y = labels.T[:, :, None]  # [K, E, 1]
    off = jnp.transpose(offsets_lanes, (1, 0, 2)).astype(labels.dtype)  # [K, E, L]
    wt = weights.T[:, :, None]
    w0t = jnp.transpose(w0, (1, 0, 2)).astype(labels.dtype)  # [S, E, L]

    def value_and_grad(w):  # [S, E, L] -> ([E, L], [S, E, L])
        z = jnp.einsum("kse,sel->kel", F, w) + off  # [K, E, L]
        lvals, dz = loss.loss_and_dz(z, y)
        wdz = wt * dz
        value = jnp.sum(wt * lvals, axis=0)  # [E, L]
        grad = jnp.einsum("kse,kel->sel", F, wdz)  # [S, E, L]
        value = value + 0.5 * l2_lanes * jnp.sum(w * w, axis=0)
        grad = grad + l2_lanes * w
        return value, grad

    def hessian_vector(w, v):
        z = jnp.einsum("kse,sel->kel", F, w) + off
        c = wt * loss.d2z(z, y) * jnp.einsum("kse,sel->kel", F, v)
        return jnp.einsum("kse,kel->sel", F, c) + l2_lanes * v

    loss_tol, grad_tol = abs_tolerances(value_and_grad, w0t, tolerance)
    if optimizer_type == "TRON":
        res = solve_tron(
            value_and_grad,
            hessian_vector,
            w0t,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            max_cg_iterations=max_cg_iterations,
            max_improvement_failures=max_improvement_failures,
        )
    else:
        res = solve_lbfgs(
            value_and_grad,
            w0t,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            num_corrections=num_corrections,
            batched=True,
        )
    back = lambda a: jnp.transpose(a, (1, 0, 2))  # noqa: E731 — [S,E,L]->[E,S,L]
    return SolverResult(
        coefficients=back(res.coefficients),
        loss=res.loss,  # [E, L]
        gradient=back(res.gradient),
        iterations=res.iterations,
        reason=res.reason,  # [E, L]
        loss_history=jnp.moveaxis(res.loss_history, 0, -1),  # [E, L, T]
        grad_norm_history=jnp.moveaxis(res.grad_norm_history, 0, -1),
        cg_iterations=res.cg_iterations,
    )


@dataclasses.dataclass
class ModelCoordinate(Coordinate):
    """Locked coordinate: scores a pretrained model, never retrains
    (ModelCoordinate.scala / Fixed-/RandomEffectModelCoordinate — partial
    retraining, CoordinateDescent.scala:280-300)."""

    inner: Coordinate
    locked_model: Union[FixedEffectModel, RandomEffectModel]

    def __post_init__(self):
        self.coordinate_id = self.inner.coordinate_id

    @property
    def n_rows(self) -> int:
        return self.inner.n_rows

    def train(self, residual_scores, initial_model=None):
        return self.locked_model, None

    def score(self, model=None) -> Array:
        return self.inner.score(self.locked_model)
