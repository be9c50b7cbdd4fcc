"""GLM optimization problems: objective + optimizer + regularization in one unit.

Reference: photon-api .../optimization/ —
GeneralizedLinearOptimizationProblem.scala:45-162 (run / initializeZeroModel /
de-normalization back to original space), DistributedOptimizationProblem
(fixed effect: down-sampling hook, mutable reg weight for lambda sweeps,
variance computation) and SingleNodeOptimizationProblem (per-entity local
problems). On TPU both are this one class: "distributed" = the batch is
sharded over the mesh, "single node" = the problem is one vmap lane.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..models.coefficients import Coefficients
from ..models.glm import GeneralizedLinearModel, model_for_task
from ..ops.features import FeatureMatrix, LabeledBatch
from ..ops.glm import GLMObjective, compute_variances
from ..ops.losses import get_loss
from ..ops.normalization import NormalizationContext
from ..ops.regularization import NO_REGULARIZATION, RegularizationContext
from ..optimize import (
    OptimizerConfig,
    OptimizerType,
    SolverResult,
    optimize,
    solve_lbfgs,
    solve_tron,
)
from ..optimize.common import abs_tolerances
from ..optimize.lbfgs import history_account, history_row_width, state_partition, state_shards

Array = jax.Array

logger = logging.getLogger("photon_ml_tpu")


def _fusion_mode(batch: LabeledBatch):
    """Decide whether this batch takes the single-sweep Pallas kernels
    (ops/pallas_glm.py). Returns (mode, mesh): mode None = jnp two-pass path;
    mesh is set when the batch is DATA-axis-sharded over >1 device, in which
    case the kernels run per-shard under shard_map + psum (a bare pallas_call
    has no GSPMD partitioning rule — without the explicit shard_map XLA would
    all-gather the sharded X around it). Model-axis-sharded dense batches
    keep the jnp path."""
    from ..ops import pallas_glm

    none = (None, None)
    mode = pallas_glm.mode()
    if mode == "off":
        return none
    f = batch.features
    if not f.is_dense:
        return none
    x = f.dense
    if isinstance(x, jax.core.Tracer):
        return none
    n, d = x.shape
    if not pallas_glm.eligible(n, d, x.dtype):
        if mode == "auto" and jax.default_backend() == "tpu":
            # a default intercept turns a 1024-wide feature bag into d=1025
            logger.info(
                "dense %s batch n=%d d=%d is outside the fused-kernel gate "
                "(d a multiple of %d, n >= %d): jnp two-pass path",
                x.dtype, n, d, pallas_glm.LANE, pallas_glm.MIN_FUSED_ROWS,
            )
        return none
    mesh = None
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(getattr(sharding, "device_set", ())) > 1:
        from jax.sharding import NamedSharding
        from ..parallel.mesh import DATA_AXIS

        if not isinstance(sharding, NamedSharding):
            return none
        spec = tuple(sharding.spec)
        # rows on the data axis, feature dim unsharded
        if len(spec) == 0 or spec[0] != DATA_AXIS:
            return none
        if any(s is not None for s in spec[1:]):
            return none
        mesh = sharding.mesh
    if mode == "interpret":
        return "interpret", mesh
    return ("compiled", mesh) if jax.default_backend() == "tpu" else none


def splits_state(layout: str, kind: OptimizerType, dim: int, data_shards: int) -> bool:
    """THE rule by which a fixed effect's coefficient-length solver state is
    split over the chips, from what the host knows: an ELL batch whose rows
    are sharded over a data axis of more than one device, a solver that keeps
    a history (L-BFGS, OWL-QN), and coefficients wide enough for it to keep
    that history by rows (``lbfgs.history_row_width``: d >= 2^20). At d =
    187.8M the history alone is 15 GB whole: split, each chip holds its
    quarter and gathers the vector for a pass (PERF.md, PR 40). The planner
    names the split by this rule; ``state_sharding`` applies it to a batch."""
    return (
        layout == "ell"
        and kind != OptimizerType.TRON
        and data_shards > 1
        and history_row_width((int(dim),), False) is not None
    )


def state_sharding(batch: LabeledBatch, solver_config: OptimizerConfig):
    """The data axis's ``NamedSharding`` of the coefficients where
    ``splits_state`` holds for ``batch`` (an ELL batch whose rows are
    sharded over the data axis); None otherwise (every one-chip solve, a
    narrow or TRON solve on a mesh, a tiled batch, whose own rule splits w
    over the model axis)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import DATA_AXIS

    f = batch.features
    idx = getattr(f, "idx", None)  # the ELL layout's; a tiled batch has none
    rows = getattr(idx, "sharding", None)
    if isinstance(idx, jax.core.Tracer) or not isinstance(rows, NamedSharding):
        return None
    spec = tuple(rows.spec)
    shards = rows.mesh.shape.get(DATA_AXIS, 1) if spec and spec[0] == DATA_AXIS else 1
    if not splits_state(f.layout, solver_config.normalized_type(), batch.dim, shards):
        return None
    return NamedSharding(rows.mesh, PartitionSpec(DATA_AXIS))


def _pad_dim(v: Array, dim: int, fill: float) -> Array:
    """Zero/one-pad a [d] vector up to a mesh-padded feature dim."""
    if v.shape[0] >= dim:
        return v
    return jnp.concatenate(
        [v, jnp.full((dim - v.shape[0],), fill, dtype=v.dtype)]
    )


@dataclasses.dataclass(frozen=True)
class GLMOptimizationConfig:
    """Per-coordinate optimization settings (reference:
    CoordinateOptimizationConfiguration + OptimizerConfig)."""

    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    regularization: RegularizationContext = NO_REGULARIZATION
    reg_weight: float = 0.0
    down_sampling_rate: float = 1.0
    variance_type: str = "NONE"  # NONE | SIMPLE | FULL

    def with_reg_weight(self, w: float) -> "GLMOptimizationConfig":
        return dataclasses.replace(self, reg_weight=w)

    def solver_config(self) -> OptimizerConfig:
        """OptimizerConfig with the regularization split applied
        (OptimizerFactory.scala:30-74: L1/elastic-net -> OWLQN l1 weight)."""
        return dataclasses.replace(
            self.optimizer,
            l1_weight=self.regularization.l1_weight(self.reg_weight),
        )


@dataclasses.dataclass(frozen=True)
class GLMProblem:
    """A ready-to-run training problem over one batch."""

    task: str
    config: GLMOptimizationConfig
    normalization: Optional[NormalizationContext] = None
    # incremental training: L2 centered on a prior model's means, weighted by
    # its precisions (README.md:102-103 "Regularize by Previous Model")
    prior: Optional[Coefficients] = None

    def _norm_for(self, batch: LabeledBatch) -> Optional[NormalizationContext]:
        """Normalization stats padded to the batch's (possibly mesh-padded)
        feature dim — identity entries on structural padding dims."""
        if self.normalization is None:
            return None
        return self.normalization.padded(batch.dim)

    def objective(
        self,
        batch: LabeledBatch,
        fused: Optional[str] = None,
        fused_mesh=None,
        state=None,
    ) -> GLMObjective:
        norm = self._norm_for(batch)
        prior_mean = prior_precision = None
        if self.prior is not None:
            dtype = batch.labels.dtype
            prior_mean = jnp.asarray(self.prior.means, dtype)
            if self.normalization is not None:
                prior_mean = self.normalization.model_to_transformed_space(prior_mean)
            if self.prior.variances is not None:
                var = jnp.asarray(self.prior.variances, dtype)
                prior_precision = 1.0 / jnp.maximum(var, 1e-12)
            else:
                prior_precision = jnp.ones_like(prior_mean)
            # mesh-tiled batches pad the feature dim; padded coords have no
            # data — prior mean 0 / precision 1 pins them at zero
            prior_mean = _pad_dim(prior_mean, batch.dim, 0.0)
            if prior_precision is not None:
                prior_precision = _pad_dim(prior_precision, batch.dim, 1.0)
        return GLMObjective(
            loss=get_loss(self.task),
            batch=batch,
            l2=self.config.regularization.l2_weight(self.config.reg_weight),
            norm=norm,
            prior_mean=prior_mean,
            prior_precision=prior_precision,
            fused=fused,
            fused_mesh=fused_mesh,
            state_sharding=state,
        )

    def solve_objective(
        self, batch: LabeledBatch, solver_config: Optional[OptimizerConfig] = None
    ) -> Tuple[GLMObjective, object]:
        """The objective ``run`` hands the solver for ``batch``, and the
        sharding of the solver's coefficient-length state (``state_sharding``;
        None: not split). A split state runs over d_pad columns
        (``lbfgs.history_row_width``: zeros past d, no row holds them and the
        ridge keeps them 0), so that every chip's part of every
        coefficient-length array is whole rows of its history."""
        state = state_sharding(batch, solver_config or self.config.solver_config())
        if state is not None:
            from ..parallel.mesh import DATA_AXIS

            wide = history_row_width((int(batch.dim),), False, state.mesh.shape[DATA_AXIS])
            batch = dataclasses.replace(
                batch, features=dataclasses.replace(batch.features, dim=wide)
            )
        fused, fused_mesh = _fusion_mode(batch)
        return self.objective(batch, fused=fused, fused_mesh=fused_mesh, state=state), state

    def run(
        self,
        batch: LabeledBatch,
        initial_model: Optional[GeneralizedLinearModel] = None,
        coordinate: Optional[str] = None,
        nnz: Optional[int] = None,
        residuals: bool = False,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """Train; returns (model in ORIGINAL space, solver result).
        ``coordinate`` names the caller's coordinate on the ``fe.solve`` span,
        ``nnz`` the entries its dataset's build stored (host-known),
        ``residuals`` whether the batch's offsets carry other coordinates'
        scores (the span's ``offsets``).

        Normalization semantics parity (Optimizer.scala:161-185 +
        GeneralizedLinearOptimizationProblem): warm-start coefficients are
        mapped to the transformed space, optimization runs there, the final
        coefficients map back.
        """
        if self.config.variance_type.upper() == "FULL":
            # fail BEFORE the (possibly hours-long) solve, not after it —
            # same check (and exception) as the post-solve entry points
            from ..ops.glm import check_full_variance_dim

            check_full_variance_dim(batch.dim)
        solver_config = self.config.solver_config()
        dim = int(batch.dim)
        obj, state = self.solve_objective(batch, solver_config)
        batch, fused = obj.batch, obj.fused
        dtype = batch.labels.dtype
        if initial_model is not None:
            w0 = jnp.asarray(initial_model.coefficients.means, dtype)
            if self.normalization is not None:
                with obs.span("fe.normalization", coordinate=coordinate, direction="in") as sp:
                    w0 = self.normalization.model_to_transformed_space(w0)
                    sp.sync(w0)
            w0 = _pad_dim(w0, batch.dim, 0.0)
        else:
            w0 = jnp.zeros(batch.dim, dtype)
        mesh = getattr(batch.features, "mesh", None)
        if mesh is not None:
            # tiled batch: shard the coefficient vector over the model axis so
            # every solver state array ([m, d] L-BFGS history included)
            # inherits the partition instead of replicating d on one device
            # (multi-process safe, no host round trip: every process built the
            # same w0, the jitted reshard places it)
            from jax.sharding import PartitionSpec
            from ..parallel.multihost import reshard
            from ..parallel.sparse import MODEL_AXIS

            w0 = reshard(jnp.asarray(w0, dtype), mesh, PartitionSpec(MODEL_AXIS))
        elif state is not None:
            # the same for a row-sharded batch over the data axis: the solver
            # keeps its state as w0 is split (lbfgs.state_partition)
            from ..parallel.multihost import reshard

            w0 = reshard(jnp.asarray(w0, dtype), state.mesh, state.spec)

        from ..ops.glm import hvp_fn, margin_fns, vg_fn

        # the two-pass objective comes as its steps too, whatever the layout:
        # a plain L-BFGS walks them (one matvec and one rmatvec an iteration)
        margins = margin_fns(obj) if fused is None else None
        history = {}
        if solver_config.normalized_type() != OptimizerType.TRON:
            # how L-BFGS / OWL-QN keeps its correction pairs at this width, and
            # what they hold on EACH device: from shapes, as the solver decides
            # (the solver splits its state as w0 is split: over the data axis
            # by the rule above, over the model axis for a tiled batch)
            placed = state_partition(w0)
            shards = state_shards(placed)
            itemsize = jnp.dtype(dtype).itemsize
            history["history"], history["history_bytes"] = history_account(
                dim, solver_config.num_corrections, itemsize, shards
            )
            history["state_sharding"] = "replicated" if placed is None else str(placed.spec[0])
            history["state_shards"] = shards
            if state is not None:
                # what one gather's all-gather and one scatter-add's
                # reduce-scatter move into / out of each chip: the chip's
                # missing (shards - 1) / shards of a d_pad vector
                # (obs.record_solver_metrics counts them by the solve's passes)
                history["collective_bytes"] = (shards - 1) * (int(batch.dim) // shards) * itemsize
        with obs.span(
            "fe.solve",
            coordinate=coordinate,
            optimizer=solver_config.normalized_type().value,
            reg_weight=float(self.config.reg_weight),
            l1_weight=float(solver_config.l1_weight),
            l2_weight=float(obj.l2),
            # what one pass touches, from shapes and the build: no fetch
            layout=getattr(batch.features, "layout", None),
            dim=dim,
            slots=getattr(batch.features, "slots", None),
            nnz=nnz,
            # a solve inside coordinate descent: warm-started from the last
            # sweep's model, under the other coordinates' scores as offsets
            warm=initial_model is not None,
            offsets=bool(residuals),
            **history,
        ) as sp:
            # with a sink, an L-BFGS or OWL-QN solve adds ``line_search`` (the
            # search it ran: ``margins`` | ``points``) and ``line_search_evals``
            # here, OWL-QN ``nonzeros`` too (obs.record_solver_metrics); every
            # host-level solve adds ``start`` (``optimize``: a walking solve
            # from no model starts from the state at zero)
            result = optimize(
                vg_fn(obj), w0, solver_config, hvp=hvp_fn(obj), margins=margins,
                from_zero=initial_model is None,
            )
            sp.sync(result)

        variances = compute_variances(obj, result.coefficients, self.config.variance_type)

        means = result.coefficients
        if self.normalization is not None:
            # padded to batch.dim: tiled coefficients live in the mesh-padded
            # space until the coordinate trims them back to d_true
            with obs.span("fe.normalization", coordinate=coordinate, direction="out") as sp:
                means = self._norm_for(batch).model_to_original_space(means)
                sp.sync(means)
            # variances stay in transformed space in the reference as well
        if state is not None:
            # back to the batch's own d (the d_pad tail is exact zeros), whole
            # on every chip as the model a driver holds: a score then gathers
            # from it for its own rows with no collective
            from jax.sharding import PartitionSpec
            from ..parallel.multihost import reshard

            means = reshard(means[:dim], state.mesh, PartitionSpec())
            variances = None if variances is None else variances[:dim]

        model = model_for_task(
            self.task, Coefficients(means=means, variances=variances)
        )
        return model, result

    def run_streamed(
        self,
        host_batch,  # game.data.HostRowBatch
        budget_bytes: int,
        residual_scores: Optional[Array] = None,  # device f[n] or None
        initial_model: Optional[GeneralizedLinearModel] = None,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """Train out-of-core: row slices of the host batch stream through the
        chip double-buffered (game/fe_streaming.py) while the optimizer runs
        on the host (optimize/host_driver.py) — the reference's
        Breeze-on-the-driver + treeAggregate-per-evaluation split. Same
        normalization / warm-start / prior semantics as ``run``; returns a
        host-materialized SolverResult."""
        from ..optimize import host_optimize
        from .fe_streaming import StreamedFEObjective

        vt = self.config.variance_type.upper()
        if vt != "NONE":
            raise ValueError(
                f"variance={vt} is not supported on the streamed fixed-effect"
                " path (out-of-core row slices never materialize the Hessian);"
                " use variance=NONE or raise hbm.budget.mb so the batch is"
                " HBM-resident"
            )
        dim = host_batch.dim
        dtype = host_batch.labels.dtype
        norm = None
        if self.normalization is not None:
            norm = self.normalization.padded(dim)
        prior_mean = prior_precision = None
        if self.prior is not None:
            prior_mean = jnp.asarray(self.prior.means, dtype)
            if self.normalization is not None:
                prior_mean = self.normalization.model_to_transformed_space(prior_mean)
            if self.prior.variances is not None:
                var = jnp.asarray(self.prior.variances, dtype)
                prior_precision = 1.0 / jnp.maximum(var, 1e-12)
            else:
                prior_precision = jnp.ones_like(prior_mean)
        if initial_model is not None:
            w0 = jnp.asarray(initial_model.coefficients.means, dtype)
            if self.normalization is not None:
                w0 = self.normalization.model_to_transformed_space(w0)
            w0 = np.asarray(jax.device_get(w0))
        else:
            w0 = np.zeros(dim, dtype)

        obj = StreamedFEObjective(
            get_loss(self.task),
            host_batch,
            budget_bytes,
            norm=norm,
            l2_weight=self.config.regularization.l2_weight(self.config.reg_weight),
            prior_mean=prior_mean,
            prior_precision=prior_precision,
            residual_scores=residual_scores,
        )
        try:
            with obs.span(
                "fe_stream.solve",
                phase="solve",
                n_slices=obj.n_slices,
                budget_bytes=int(budget_bytes),
            ) as solve_span:
                # at pipeline depth >= 2 the driver gets the deferred form
                # too, so the tolerance pass and the first real evaluation
                # are both in flight before either is fetched
                deferred = (
                    obj.value_and_grad_deferred if obj.pipeline_depth > 1 else None
                )
                result = host_optimize(
                    obj.value_and_grad,
                    w0,
                    self.config.solver_config(),
                    hvp=obj.hessian_vector,
                    value_and_grad_deferred=deferred,
                )
            obj.record_metrics("fe.train", solve_span.duration_s)
        finally:
            obj.close()

        means = jnp.asarray(result.coefficients, dtype)
        if self.normalization is not None:
            means = norm.model_to_original_space(means)
        model = model_for_task(
            self.task, Coefficients(means=means, variances=None)
        )
        return model, result

    def run_lanes(
        self,
        batch: LabeledBatch,
        offsets_lanes: Array,  # f[n, L] effective offsets per lambda lane
        l2_lanes: Array,  # f[L] per-lane L2 weights (DYNAMIC operand)
        w0: Optional[Array] = None,  # f[d, L] warm start; None = zeros
    ) -> Tuple[Array, SolverResult]:
        """Lane-stacked solve: L regularization candidates share one data
        residency and ONE compiled kernel. The per-lane reg weight enters as a
        vector operand (never a static argument), so a refreshed candidate set
        from the tuner reuses the executable instead of recompiling.

        Returns (coefficients f[d, L], per-lane SolverResult — loss/reason/
        iterations all [L]). A lane that is born corrupt or diverges freezes
        at its warm start with ``ConvergenceReason.NUMERICAL_DIVERGENCE``
        without stalling its neighbors (PR 4's masked-commit machinery; see
        optimize/lbfgs.py).

        Composition limits (checked here because this is the deep entry
        point; game/lanes.py pins the user-facing refusals): L2-only
        regularization (the OWL-QN l1 weight is one operand of a solve, not a
        per-lane vector), variance=NONE, no normalization, no prior."""
        solver_cfg = self.config.solver_config()
        if solver_cfg.l1_weight > 0.0:
            raise ValueError(
                "trial-lanes sweeps support L2 regularization only (the "
                "OWL-QN l1 weight is one operand of a solve, not a per-lane "
                "vector)"
            )
        if self.config.variance_type.upper() != "NONE":
            raise ValueError(
                "trial-lanes sweeps require variance=NONE (per-lane "
                "Hessian inversion is not lane-stacked)"
            )
        if self.normalization is not None:
            raise ValueError(
                "feature normalization is not supported with trial-lanes"
            )
        if self.prior is not None:
            raise ValueError(
                "regularize-by-prior is not supported with trial-lanes"
            )
        dtype = batch.labels.dtype
        L = offsets_lanes.shape[1]
        if w0 is None:
            w0 = jnp.zeros((batch.dim, L), dtype)
        result = _train_fe_lanes(
            batch.features,
            batch.labels,
            offsets_lanes,
            batch.weights,
            jnp.asarray(w0, dtype),
            jnp.asarray(l2_lanes, dtype),
            task=self.task,
            optimizer_type=OptimizerType(solver_cfg.normalized_type()).value,
            tolerance=solver_cfg.tolerance,
            max_iterations=solver_cfg.max_iterations,
            num_corrections=solver_cfg.num_corrections,
            max_cg_iterations=solver_cfg.max_cg_iterations,
            max_improvement_failures=solver_cfg.max_improvement_failures,
        )
        return result.coefficients, result

    def zero_model(self, dim: int, dtype=jnp.float32) -> GeneralizedLinearModel:
        return model_for_task(self.task, Coefficients.zeros(dim, dtype))


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
def _train_fe_lanes(
    features: FeatureMatrix,
    labels: Array,  # f[n]
    offsets_lanes: Array,  # f[n, L]
    weights: Array,  # f[n]
    w0: Array,  # f[d, L]
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
    """Batched fixed-effect objective over the lambda-lane axis.

    Same algebra as GLMObjective, with the coefficient vector widened to
    ``[d, L]``: margins are one ``matmat`` ([n, L]), the gradient one
    ``rmatmat`` ([d, L]), and the L2 term broadcasts the per-lane weight
    vector. Every solver reduction is axis-0 (optimize/common._norm), so the
    trailing lane axis rides through L-BFGS/TRON untouched — exactly the
    entity-minor batched-solve contract of PR 4, with lambdas instead of
    entities as the lane dimension."""
    loss = get_loss(task)
    y = labels[:, None]
    wt = weights[:, None]

    def value_and_grad(w):  # [d, L] -> ([L], [d, L])
        z = features.matmat(w) + offsets_lanes  # [n, L]
        lvals, dz = loss.loss_and_dz(z, y)
        value = jnp.sum(wt * lvals, axis=0)  # [L]
        grad = features.rmatmat(wt * dz)  # [d, L]
        value = value + 0.5 * l2_lanes * jnp.sum(w * w, axis=0)
        grad = grad + l2_lanes[None, :] * w
        return value, grad

    def hessian_vector(w, v):
        z = features.matmat(w) + offsets_lanes
        c = wt * loss.d2z(z, y) * features.matmat(v)  # [n, L]
        return features.rmatmat(c) + l2_lanes[None, :] * v

    loss_tol, grad_tol = abs_tolerances(value_and_grad, w0, tolerance)  # [L]
    if optimizer_type == "TRON":
        return solve_tron(
            value_and_grad,
            hessian_vector,
            w0,
            loss_tol,
            grad_tol,
            max_iterations=max_iterations,
            max_cg_iterations=max_cg_iterations,
            max_improvement_failures=max_improvement_failures,
        )
    return solve_lbfgs(
        value_and_grad,
        w0,
        loss_tol,
        grad_tol,
        max_iterations=max_iterations,
        num_corrections=num_corrections,
        batched=True,
    )
