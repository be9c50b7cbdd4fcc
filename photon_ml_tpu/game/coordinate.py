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
from typing import Optional, Tuple, Union

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
from .data import FixedEffectDataset, RandomEffectDataset
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
        if residual_scores is not None:
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
        with obs.span("fe.score", coordinate=self.coordinate_id) as sp:
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
        blocks = self.dataset.blocks
        E, K, S = blocks.features.shape
        # solver state stays in the WIDE dtype: features may be stored bf16
        # (feature_dtype), labels/weights/offsets carry the solve precision
        dtype = blocks.labels.dtype

        # Size-bucketed solves: each of the dataset's chunks is sorted by
        # descending row count and dealt the same size profile, so a (K, S)-
        # rounded bucket is the SAME local row range of every chunk; solving
        # per bucket avoids every small entity paying the padding of the
        # largest (RandomEffectDatasetPartitioner's size-awareness, re-purposed
        # for vmap lane economy), and under a mesh every chip holds an equal
        # share of every bucket. No buckets: one whole-block solve, on the
        # arrays as they stand (a full-range slice would copy).
        segments = _size_buckets(self.dataset)
        chunks = self.dataset.entity_chunks
        sharded = _chunk_axis(blocks.features, chunks)
        buckets = tuple(segments or [(0, E // chunks, K, S)])
        exchange = partial(
            _bucket_offsets, blocks.active_rows, blocks.offsets,
            segments=buckets, chunks=chunks, sharded=sharded,
        )
        if residual_scores is not None:
            # the residual exchange: every slot a bucket will solve gathers
            # its row's residual (the other coordinates' summed scores);
            # block_slots is the [E, K] plane the buckets are cut from
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
        # DEVICE — three host [E, S] uploads per train call (~7 MB at bench
        # shapes) would otherwise ride the host->device link every sweep. On
        # the CPU backend host numpy is kept: the transfer is a memcpy, and
        # device-created inputs to the sharded-blocks pjit tickled an XLA:CPU
        # compiler segfault under long test sessions (observed at
        # test_scale_paths with 8 virtual devices).
        multiproc = jax.process_count() > 1
        if multiproc or jax.default_backend() == "cpu":
            xp, xdt = np, np.dtype(jnp.zeros((), dtype).dtype)
            # explicit logged fetch: warm-start/prior projections may land on
            # device; the CD sweep runs under transfer_guard, which rejects
            # a bare np.asarray on device arrays
            to_host = lambda a: logged_fetch("coordinate.host_state", a)  # noqa: E731
        else:
            xp, xdt = jnp, dtype
            to_host = lambda a: a  # noqa: E731 — single decision point
        # the solver's [E, S] state: a warm start and a prior come through
        # the model projection, whose layout fetches block (the span is
        # their parent)
        with obs.span(
            "re.warm_start", coordinate=self.coordinate_id,
            warm=initial_model is not None, priors=self.prior_model is not None,
        ) as sp:
            if initial_model is not None:
                w0 = to_host(
                    _initial_subspace_coefficients(self.dataset, initial_model, dtype)
                )
            else:
                w0 = xp.zeros((E, S), xdt)

            prior_mean = xp.zeros((E, S), xdt)
            prior_prec = xp.ones((E, S), xdt)
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
        counts = self.dataset.entity_counts
        if counts is not None:
            chunk_counts = np.asarray(counts).reshape(chunks, -1)
        real_slots = padded_slots = 0
        parts = []
        for (start, end, kb, sb), offsets in zip(buckets, bucket_offsets):
            entities = chunks * (end - start)
            slots = entities * kb
            shape = dict(
                coordinate=self.coordinate_id,
                k=kb, s=sb, entities=entities, slots=slots, chunks=chunks,
            )
            if counts is not None:
                chunk_real = chunk_counts[:, start:end].sum(axis=1)
                shape["real_rows"] = int(chunk_real.sum())
                # the chips' balance: real_rows over chunks * this
                shape["max_chunk_real_rows"] = int(chunk_real.max())
                real_slots += shape["real_rows"]
                padded_slots += slots - shape["real_rows"]
            with obs.span("re.bucket", **shape) as sp:
                if segments is None:
                    part = _train_blocks_packed(
                        blocks.features, blocks.labels, offsets, blocks.weights,
                        w0, prior_mean, prior_prec, **solver_kwargs,
                    )
                else:
                    # with a sink the bucket says how much of its enqueue is
                    # the cut: the rest of enqueue_s is the solve's dispatch
                    # photon: ignore[R7] — an attribute of the bucket's own
                    # span (a child span a bucket would be one more event)
                    cut_start = time.perf_counter() if obs.active() else None
                    operands = _bucket_operands(
                        (blocks.features, blocks.labels, blocks.weights),
                        offsets, (w0, prior_mean, prior_prec),
                        chunks, sharded, start, end, kb, sb,
                    )
                    if cut_start is not None:
                        # photon: ignore[R7] — closes the stamp above
                        sp.attrs["cut_s"] = time.perf_counter() - cut_start
                    part = _train_blocks_packed(*operands, **solver_kwargs)
                sp.sync(part)
            if obs.active() and not multiproc:
                # (across processes a bucket's lanes are not all addressable
                # from here; the trackers count them after the collect)
                self._record_lane_iterations(part)
            parts.append(part)
        if counts is not None:
            slot_counter = obs.current_run().registry.counter(
                "photon_re_block_slots_total",
                "entity-block row slots handed to the random-effect solver, "
                "real rows against bucket padding",
            )
            slot_counter.labels(coordinate=self.coordinate_id, kind="real").inc(real_slots)
            slot_counter.labels(coordinate=self.coordinate_id, kind="padded").inc(
                padded_slots
            )
            # host-known from the dataset: the rows this coordinate trains on
            # (the buckets' real slots) against the rows over the active cap,
            # which it only scores
            row_counter = obs.current_run().registry.counter(
                "photon_re_rows_total",
                "rows of a random-effect coordinate per train call: active "
                "(in an entity block) against passive (scored, never trained)",
            )
            row_counter.labels(coordinate=self.coordinate_id, kind="active").inc(
                real_slots
            )
            row_counter.labels(coordinate=self.coordinate_id, kind="passive").inc(
                len(self.dataset.passive_rows)
            )
        with obs.span("re.collect", coordinate=self.coordinate_id) as sp:
            results = (
                parts[0]
                if segments is None
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

    def _record_lane_iterations(self, part: SolverResult) -> None:
        """A lockstep bucket runs until its slowest lane stops: ``useful`` is
        the iterations its lanes needed, ``issued`` what the bucket ran for
        all of them. Called only with a sink attached, after the bucket's
        fence: the fetch is the iterations array as it stands (a reduction
        on the device would be one more program, in traced runs alone)."""
        iters = np.asarray(logged_fetch("re.bucket_iterations", part.iterations))
        if iters.size == 0:
            return
        counter = obs.current_run().registry.counter(
            "photon_re_lane_iterations_total",
            "random-effect solver iterations per bucket: useful (summed over "
            "lanes) against issued (lanes x the bucket's slowest lane)",
        )
        counter.labels(coordinate=self.coordinate_id, kind="useful").inc(
            int(iters.sum())
        )
        counter.labels(coordinate=self.coordinate_id, kind="issued").inc(
            int(iters.size) * int(iters.max())
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
        blocks = self.dataset.blocks
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
        [E, S, L], reusing the densified-subspace cache of the sequential
        scoring hot path (one row gather + fused dot for all L lanes)."""
        from ..models.game import ell_row_subspace, score_entity_rows_dense_lanes

        ds = self.dataset
        row_entity = ds.row_entity
        cache = getattr(ds, "_score_xsub_cache", None)
        if cache is None:
            cache = ell_row_subspace(
                ds.blocks.proj_cols, row_entity, ds.ell_idx, ds.ell_val
            )
            object.__setattr__(ds, "_score_xsub_cache", cache)
        score_dt = jnp.promote_types(ds.ell_val.dtype, ds.blocks.labels.dtype)
        vals = jnp.asarray(coef_values, score_dt)
        return score_entity_rows_dense_lanes(vals, row_entity, cache)

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
            scores = self._score_resident(model)
            sp.sync(scores)
        return scores

    def _score_resident(self, model: RandomEffectModel) -> Array:
        row_entity = self.dataset.row_entity
        # The model's entity-row order may differ from this dataset's block
        # order (warm start from a loaded model, locked partial-retrain
        # models): remap dataset block rows -> model rows by entity id.
        # Device-side gather: works when row_entity is sharded across
        # processes (multi-process) as well as single-host.
        ds_ids = list(map(str, self.dataset.entity_ids))
        m_ids = list(map(str, model.entity_ids))
        if ds_ids == m_ids and self._support_layout_matches(model):
            # coordinate-descent hot path: the support LAYOUT is this
            # dataset's own block layout, so the row features are densified
            # into entity-subspace layout once and cached; each sweep's score
            # is then one contiguous row gather + elementwise dot
            # (models/game.py score_entity_rows_dense)
            from ..models.game import ell_row_subspace, score_entity_rows_dense

            cache = getattr(self.dataset, "_score_xsub_cache", None)
            if cache is None:
                cache = ell_row_subspace(
                    model.coef_indices, row_entity,
                    self.dataset.ell_idx, self.dataset.ell_val,
                )
                object.__setattr__(self.dataset, "_score_xsub_cache", cache)
            # scores compute in the WIDE dtype: bf16 feature storage must not
            # truncate the coefficients or the residual stream
            score_dt = jnp.promote_types(
                self.dataset.ell_val.dtype, self.dataset.blocks.labels.dtype
            )
            vals = jnp.asarray(model.coef_values, score_dt)
            return score_entity_rows_dense(vals, row_entity, cache)
        if ds_ids != m_ids:
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


def _pow2_ceil(x: np.ndarray) -> np.ndarray:
    """Exact elementwise 2**ceil(log2(max(x, 1))) for int64 inputs < 2^53
    (frexp exponents of exactly-represented ints are bit_lengths)."""
    v = np.maximum(np.asarray(x, dtype=np.int64), 1) - 1
    return np.int64(1) << np.frexp(v.astype(np.float64))[1].astype(np.int64)


def _size_buckets(
    dataset: RandomEffectDataset,
    min_dim: int = 8,
    align: int = 1,
):
    """Chunk-local entity segments with power-of-2-rounded (K, S) block shapes.

    Returns [(start, end, K_b, S_b)], or None when per-entity stats are
    unavailable or bucketing cannot shrink anything. ``start``/``end`` are
    rows of ONE chunk: the dataset's block rows are ``entity_chunks`` equal
    chunks, each size-sorted descending and dealt the same size profile
    (``_entity_plan``), and a bucket is rows [start, end) of EVERY chunk. One
    set of bounds serves all chunks: the row count at a local position is
    taken as the largest over the chunks, so an entity a chunk reaches one
    position early fits the larger K of the bucket before it. With one chunk
    the segments are plain block-row ranges. Rounding to powers of two (floored
    at ``min_dim``) bounds the number of distinct compiled solver shapes at
    O(log^2) while removing the bulk of the padding FLOPs.

    Fully vectorized (no per-entity Python work — this runs on every train()
    call, potentially over millions of entities). ``align`` (the per-device
    entity chunk) is accepted for the benchmark's re_pad_share reader, which
    passes it, and changes nothing: a bucket takes the same rows of every
    chunk, so no slice splits a device shard.
    """
    del align
    counts = dataset.entity_counts
    svec = dataset.entity_subspace_dims
    if counts is None or svec is None or len(counts) == 0:
        return None
    _, K, S = dataset.blocks.features.shape
    chunks = dataset.entity_chunks
    # the stats cover ALL block rows (streamed + sharded blocks hold one
    # host's range of them); per local position, the largest over the chunks
    counts = np.asarray(counts, dtype=np.int64).reshape(chunks, -1).max(axis=0)
    sv = np.asarray(svec, dtype=np.int64).reshape(chunks, -1).max(axis=0)
    chunk_rows = len(counts)

    kb_of = np.minimum(np.maximum(_pow2_ceil(counts), min_dim), K)
    bounds = np.flatnonzero(np.diff(kb_of)) + 1  # starts of new equal-K runs
    starts = np.concatenate([[0], bounds])
    ends = np.concatenate([bounds, [chunk_rows]])

    sb_of = np.minimum(
        np.maximum(_pow2_ceil(np.maximum.reduceat(sv, starts)), min_dim), S
    )
    segments = [
        (
            int(s),
            int(e),
            int(kb_of[s]),  # counts non-increasing => max K of the segment
            int(sb),
        )
        for s, e, sb in zip(starts, ends, sb_of)
    ]
    if len(segments) == 1 and segments[0][2] >= K and segments[0][3] >= S:
        return None
    return segments


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


def _chunk_rows(a, chunks: int, start: int, end: int, *dims: int):
    """Rows [start, end) of every one of the ``chunks`` equal chunks of
    ``a``'s leading axis, chunk-major, the trailing axes cut to ``dims``.
    One chunk: the plain slice. (Slices joined, not a reshape sliced: behind a
    reshape the TPU compiler re-lays the whole array out before it cuts.)"""
    cut = tuple(slice(None, d) for d in dims)
    rows = a.shape[0] // chunks
    parts = [
        a[(slice(c * rows + start, c * rows + end),) + cut] for c in range(chunks)
    ]
    if chunks == 1:
        return parts[0]
    return (np if isinstance(a, np.ndarray) else jnp).concatenate(parts)


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


@partial(jax.jit, static_argnames=("segments", "chunks", "sharded"))
def _bucket_offsets(active_rows, offsets, residual_scores, *, segments, chunks, sharded):
    """The residual exchange as ONE program: every bucket's solver offsets,
    one ``[chunks * (end - start), K_b]`` array a segment, rows as
    ``_chunk_rows`` orders them. Only the slots a bucket solves are gathered:
    ``active_rows`` and ``offsets`` ([E, K]) are cut to the bucket BEFORE the
    residual is gathered at those rows, padding slots (-1) masked, and added.
    Under ``sharded`` every chip gathers for its own chunks from the whole [N]
    residual (all-gathered once on entry when it is row-sharded).
    ``residual_scores`` None: the blocks' own offsets, cut."""

    def gather(chunks_here, blocks, *residual):
        active_rows, offsets = blocks
        chunk_rows = offsets.shape[0] // chunks_here

        def piece(c, start, end, kb):
            cut = (slice(c * chunk_rows + start, c * chunk_rows + end), slice(None, kb))
            if not residual:
                return offsets[cut]
            rows = active_rows[cut]
            res = jnp.take(residual[0], jnp.maximum(rows, 0), axis=0) * (rows >= 0)
            return offsets[cut] + res.astype(offsets.dtype)

        # slices gathered chunk by chunk and joined LAST: joined first, the
        # TPU compiler re-lays a narrow bucket's index array out row-major
        # (lane-padded 128 / K_b times) to flatten it for the gather
        return tuple(
            jnp.concatenate([piece(c, start, end, kb) for c in range(chunks_here)])
            for start, end, kb, _ in segments
        )

    whole = () if residual_scores is None else (residual_scores,)
    return _per_device(gather, chunks, sharded, len(whole))(
        (active_rows, offsets), *whole
    )


def _bucket_operands(
    block_arrays, offsets, state_arrays, chunks, sharded, start, end, kb, sb
):
    """A bucket's solver operands: rows [start, end) of every chunk, cut to
    the bucket's (K_b, S_b). ``block_arrays`` are the [E, K(, S)] features,
    labels and weights, ``offsets`` the bucket's own from the exchange
    (``_bucket_offsets``: cut already), ``state_arrays`` the [E, S] w0 and
    priors (host numpy on the CPU backend and across processes: cut on the
    host); ``sharded`` is the blocks' ``_chunk_axis``."""
    dims = ((kb, sb), (kb,), (kb,)) + ((sb,),) * len(state_arrays)
    arrays = tuple(block_arrays) + tuple(state_arrays)
    if chunks == 1:
        # one sorted run: plain eager slices (a chunked cut is one program)
        cut = (_chunk_rows(a, 1, start, end, *d) for a, d in zip(arrays, dims))
    else:
        on_host = [isinstance(a, np.ndarray) for a in arrays]
        on_device = iter(
            _chunk_rows_of(
                tuple(a for a, h in zip(arrays, on_host) if not h),
                chunks=chunks, start=start, end=end,
                dims=tuple(d for d, h in zip(dims, on_host) if not h), sharded=sharded,
            )
        )
        cut = (
            _chunk_rows(a, chunks, start, end, *d) if h else next(on_device)
            for a, d, h in zip(arrays, dims, on_host)
        )
    features, labels, weights, *state = cut
    return (features, labels, offsets, weights, *state)


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
