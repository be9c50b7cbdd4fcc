"""Fused GLM objective kernels: value, gradient, Hessian-vector / diagonal / matrix.

This is the TPU re-design of the reference's aggregator quartet
(ValueAndGradientAggregator / HessianVectorAggregator / HessianDiagonalAggregator /
HessianMatrixAggregator, photon-lib .../function/glm/): the per-partition
``seqOp`` hot loop becomes one batched XLA computation, and the Spark
``treeAggregate`` all-reduce becomes the implicit collective XLA inserts when the
batch is sharded over a device mesh (SURVEY.md §2.1 P1-P3). No explicit psum is
needed: under ``jit`` with a batch sharded on the "data" mesh axis and
replicated coefficients, the ``jnp.sum``/``rmatvec`` reductions lower to
all-reduces over ICI.

Objective (sum, not mean — parity with the reference):

    F(w') = sum_i weight_i * l(margin_i, y_i) + (l2/2) * ||w'||^2
    margin_i = effective_coef . x_i + margin_shift + offset_i

with effective_coef = w' .* factor, margin_shift = -effective_coef.shift from
the NormalizationContext (normalized features are never materialized;
derivation at ValueAndGradientAggregator.scala:36-80).

L1 is NOT part of the objective — it lives in the OWL-QN solver
(reference: DistributedOptimizationProblem.scala:64-75).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .features import LabeledBatch
from .losses import PointwiseLoss
from .normalization import NormalizationContext, identity_normalization

Array = jax.Array

# FULL variance builds a [d, d] Hessian and Cholesky-solves it. The tiled
# layout accumulates it model-axis-sharded (parallel/sparse.py xtcx), but the
# factorization gathers to one device: the ceiling is that device's memory.
# Measured on a 16 GB v5e chip: d = 16384 (1 GB f32 matrix) compiles and runs
# (131s first-call incl. compile); d = 32768 OOMs — XLA's blocked
# cholesky/triangular-solve temps peak near 10x the matrix even with the
# chunked-RHS formulation below (40 GB needed). Beyond the cap, SIMPLE is the
# answer (the reference densifies the same way,
# HessianMatrixAggregator.scala:92-128).
MAX_FULL_VARIANCE_DIM = 16384


def check_full_variance_dim(dim: int) -> None:
    """Single source of truth for the FULL-variance dim ceiling: every entry
    point (pre-solve config check and direct hessian_matrix/compute_variances
    callers) raises the same ValueError, and raises it EARLY."""
    if dim > MAX_FULL_VARIANCE_DIM:
        raise ValueError(
            f"variance=FULL needs a [d, d] Hessian factorization; d={dim} "
            f"exceeds the supported ceiling {MAX_FULL_VARIANCE_DIM} — use "
            "variance=SIMPLE"
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """A pure-functional GLM objective over a fixed batch.

    The same object serves both of the reference's execution modes
    (DistributedObjectiveFunction / SingleNodeObjectiveFunction,
    photon-api .../function/): "distributed" is just this objective jitted
    with a device-sharded batch; "local" is the same code vmapped over
    per-entity blocks. The reference achieved this with abstract
    ``type Data`` polymorphism (ObjectiveFunction.scala:25-74); here it falls
    out of JAX's transforms.

    On the two-pass ``jnp`` path (``fused is None``) ``value_and_grad`` is two
    steps with the row-length margins between them, ``grad_from_margins(
    margins(w), w)``, and the steps are offered apart (:func:`margin_fns`): the
    margins are AFFINE in the coefficients, so along a search direction p
    ``margins(w + t p) = margins(w) + t * direction_margins(p)``, and
    ``value_and_slope`` gives the objective and its derivative along p at any
    step length from row-length sums, with no pass over the features. The
    L-BFGS line search walks them (optimize/lbfgs.py): one ``matvec`` and one
    ``rmatvec`` an iteration, however many step lengths it tries. The fused
    kernels read X once for value AND gradient and keep ``value_and_grad``.
    """

    loss: PointwiseLoss
    batch: LabeledBatch
    # dynamic leaf (not static): lambda sweeps must NOT trigger recompiles —
    # the reference kept a mutable reg weight for exactly this reason
    # (DistributedOptimizationProblem.updateRegularizationWeight:64-75)
    l2: float = 0.0
    norm: Optional[NormalizationContext] = None
    # Incremental training ("Regularize by Previous Model During Warm-Start
    # Training", reference README.md:102-103): the L2 penalty centers on a
    # prior model's means and weights per-coefficient by the prior precision
    # (1/variance). With prior_mean=0 / prior_precision=1 this is plain L2.
    prior_mean: Optional[Array] = None
    prior_precision: Optional[Array] = None
    # Pallas fusion mode (static): None = two-pass jnp path; "compiled" =
    # single-HBM-sweep TPU kernels (ops/pallas_glm.py); "interpret" = the same
    # kernels on the Pallas interpreter (non-TPU test parity). Set by
    # GLMProblem.run after its concrete eligibility checks — never default-on.
    fused: Optional[str] = dataclasses.field(default=None, metadata=dict(static=True))
    # When the batch is sharded over a mesh's DATA axis, the fused kernels run
    # per-shard under shard_map with an explicit psum (pallas_call has no
    # GSPMD partitioning rule; without this a sharded batch must keep the jnp
    # path). None = single-device placement.
    fused_mesh: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )
    # The coefficients' sharding where the batch's rows AND the solver's
    # coefficient-length state are split over the data axis (GLMProblem.run's
    # rule, ``problem.state_sharding``): a gather reads the vector all-gathered,
    # once a pass, and the scatter-add's local [d] sum is reduce-scattered onto
    # the part each device owns. None = the vector is whole wherever it is read.
    state_sharding: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    def _norm(self) -> NormalizationContext:
        return self.norm if self.norm is not None else identity_normalization()

    def _matvec(self, coef: Array) -> Array:
        if self.state_sharding is None:
            return self.batch.features.matvec(coef)
        return self.batch.features.matvec_gathered(coef, self.state_sharding)

    def _rmatvec(self, c: Array) -> Array:
        if self.state_sharding is None:
            return self.batch.features.rmatvec(c)
        return self.batch.features.rmatvec_scattered(c, self.state_sharding)

    def _reg_delta(self, coef: Array) -> Array:
        return coef if self.prior_mean is None else coef - self.prior_mean

    def _precision(self, like: Array) -> Array:
        return (
            jnp.ones_like(like) if self.prior_precision is None else self.prior_precision
        )

    def value(self, coef: Array) -> Array:
        return self.value_and_grad(coef)[0]

    def gradient(self, coef: Array) -> Array:
        return self.value_and_grad(coef)[1]

    def value_and_grad(self, coef: Array) -> Tuple[Array, Array]:
        b = self.batch
        if self.fused is None or not b.features.is_dense:
            return self.grad_from_margins(self.margins(coef), coef)
        # single-sweep Pallas kernel returns the raw aggregates; the
        # normalization/L2 algebra is the jnp path's
        from .pallas_glm import sharded_value_grad

        norm = self._norm()
        eff, mshift = norm.effective_coefficients(coef)
        value, raw_grad, wdz_sum = sharded_value_grad(
            self.fused_mesh, b.features.dense, eff, b.labels,
            b.offsets + mshift, b.weights, self.loss,
            interpret=(self.fused == "interpret"),
        )
        return self._finish_value_grad(coef, value, raw_grad, wdz_sum)

    def _finish_value_grad(
        self, coef: Array, value: Array, raw_grad: Array, wdz_sum: Array
    ) -> Tuple[Array, Array]:
        """The row sums' way into the solver's space: shifts, factors, and the
        L2 / prior term at ``coef`` (the streamed objective's finalize step)."""
        return finalize_value_grad(
            coef, value, raw_grad, wdz_sum, self._norm(), self.l2,
            self.prior_mean, self.prior_precision,
        )

    # -- the two-pass path's steps, apart (see the class docstring) ------------

    def margins(self, coef: Array) -> Array:
        """z = X eff(coef) + shift(coef) + offsets: the gather."""
        eff, mshift = self._norm().effective_coefficients(coef)
        return self._matvec(eff) + mshift + self.batch.offsets

    def direction_margins(self, direction: Array) -> Array:
        """u with ``margins(w + t p) = margins(w) + t u``: ``margins(p)``
        without the offsets."""
        eff, mshift = self._norm().effective_coefficients(direction)
        return self._matvec(eff) + mshift

    def value_and_slope(
        self, z: Array, u: Array, t: Array, coef: Array, direction: Array
    ) -> Tuple[Array, Array]:
        """phi(t) = F(coef + t direction) and phi'(t), given z = margins(coef)
        and u = direction_margins(direction): row-length sums, plus the L2 /
        prior term streamed at coef + t direction. No feature is touched."""
        b = self.batch
        loss, dz = self.loss.loss_and_dz(z + t * u, b.labels)
        value = jnp.sum(b.weights * loss)
        slope = jnp.sum(b.weights * dz * u)
        delta = self._reg_delta(coef + t * direction)
        scaled = self._precision(coef) * delta
        value = value + 0.5 * self.l2 * jnp.dot(delta, scaled)
        slope = slope + self.l2 * jnp.dot(scaled, direction)
        return value, slope

    def grad_from_margins(self, z: Array, coef: Array) -> Tuple[Array, Array]:
        """Value and full gradient at ``coef`` given z = margins(coef): the
        scatter-add ``rmatvec(w l'(z))``, shifts, factors, L2 / prior."""
        b = self.batch
        loss, dz = self.loss.loss_and_dz(z, b.labels)
        wdz = b.weights * dz
        value = jnp.sum(b.weights * loss)
        raw_grad = self._rmatvec(wdz)
        wdz_sum = jnp.sum(wdz) if self._norm().shifts is not None else None
        return self._finish_value_grad(coef, value, raw_grad, wdz_sum)

    def _d2z_weights(self, coef: Array) -> Array:
        b = self.batch
        return b.weights * self.loss.d2z(self.margins(coef), b.labels)

    def hessian_vector(self, coef: Array, v: Array) -> Array:
        """H(w') v — the TRON inner-CG kernel
        (reference: HessianVectorAggregator.scala:38-173).

        hv_j = factor_j * (sum_i x_ji * w_i l''_i u_i - shift_j * sum_i w_i l''_i u_i)
        with u_i = (x_i - shift) .* factor . v  (a margin of v with zero offset).
        """
        b = self.batch
        norm = self._norm()
        if self.fused is not None and b.features.is_dense:
            # one X sweep instead of three: z, u and the accumulation are all
            # row-local, so the Pallas kernel computes them per tile in VMEM
            from .pallas_glm import sharded_hessian_vector

            eff, mshift = norm.effective_coefficients(coef)
            eff_v, vshift = norm.effective_coefficients(v)
            hv, csum = sharded_hessian_vector(
                self.fused_mesh, b.features.dense, eff, eff_v, b.labels,
                b.offsets + mshift, b.weights, vshift, self.loss,
                interpret=(self.fused == "interpret"),
            )
            if norm.shifts is not None:
                hv = hv - norm.shifts * csum
        else:
            c = self._d2z_weights(coef) * self.direction_margins(v)
            hv = self._rmatvec(c)
            if norm.shifts is not None:
                hv = hv - norm.shifts * jnp.sum(c)
        if norm.factors is not None:
            hv = hv * norm.factors
        hv = hv + self.l2 * self._precision(v) * v
        return hv

    def hessian_diagonal(self, coef: Array) -> Array:
        """diag H = sum_i w_i l''_i x'_ji^2 (+ l2), expanded for normalization:
        f_j^2 [S2_j - 2 s_j S1_j + s_j^2 S0] with S2=sum c x^2, S1=sum c x, S0=sum c.
        (reference: HessianDiagonalAggregator.scala:33-128; used for SIMPLE
        variance = 1/diag, DistributedOptimizationProblem.scala:84-108)."""
        b = self.batch
        norm = self._norm()
        need_shifts = norm.shifts is not None
        if self.fused is not None and b.features.is_dense:
            # one X sweep for (s2[, s1, s0]) instead of up to three
            from .pallas_glm import sharded_hessian_stats

            eff, mshift = norm.effective_coefficients(coef)
            s2, s1, s0 = sharded_hessian_stats(
                self.fused_mesh, b.features.dense, eff, b.labels,
                b.offsets + mshift, b.weights, self.loss,
                interpret=(self.fused == "interpret"),
                need_shifts=need_shifts,
            )
        else:
            c = self._d2z_weights(coef)
            s2 = b.features.sq_rmatvec(c)
            s1 = b.features.rmatvec(c) if need_shifts else None
            s0 = jnp.sum(c) if need_shifts else None
        diag = s2
        if need_shifts:
            diag = s2 - 2.0 * norm.shifts * s1 + norm.shifts**2 * s0
        if norm.factors is not None:
            diag = diag * norm.factors**2
        diag = diag + self.l2 * self._precision(diag)
        return diag

    def hessian_matrix(self, coef: Array) -> Array:
        """Dense d x d Hessian = X'^T diag(w l'') X' (+ l2 I). Used for FULL
        variance (diag of inverse); densifies features, so only for small d
        (reference: HessianMatrixAggregator.scala:33-129). On the mesh-tiled
        layout the chunked sharded xtcx path runs instead — no global
        densification, result sharded over the model axis — with zero-activity
        (mesh-padded) diagonal entries pinned to 1 so the matrix stays
        invertible (same convention SIMPLE variance uses for zero diagonals)."""
        b = self.batch
        norm = self._norm()
        c = self._d2z_weights(coef)
        if getattr(b.features, "layout", None) == "tiled":
            check_full_variance_dim(b.dim)
            h = b.features.xtcx(c)
            if not norm.is_identity:
                # transformed-space Hessian without densifying X:
                #   H' = F (H - s S1^T - S1 s^T + S0 s s^T) F
                # with F = diag(factors), s = shifts, S1 = X^T c, S0 = sum c
                # (expand (x - s) f terms of HessianMatrixAggregator.scala:92-128)
                if norm.shifts is not None:
                    s1 = b.features.rmatvec(c)
                    s0 = jnp.sum(c)
                    sh = norm.shifts
                    h = h - sh[:, None] * s1[None, :] - s1[:, None] * sh[None, :]
                    h = h + s0 * sh[:, None] * sh[None, :]
                if norm.factors is not None:
                    h = h * norm.factors[:, None] * norm.factors[None, :]
            # pin only STRUCTURAL mesh-padding dims (>= dim_true) to unit
            # diagonal; real-but-inactive features keep the dense path's
            # behavior (their variance is governed by l2, as in the reference)
            d_true = getattr(b.features, "dim_true", 0) or b.dim
            zeros_d = jnp.zeros(b.dim, h.dtype)
            pad_pin = (jnp.arange(b.dim) >= d_true).astype(h.dtype)
            h = h + jnp.diag(self.l2 * self._precision(zeros_d) + pad_pin)
            return _pin_zero_diagonal(h)
        x = b.features.to_dense()
        if norm.shifts is not None:
            x = x - norm.shifts[None, :]
        if norm.factors is not None:
            x = x * norm.factors[None, :]
        h = x.T @ (c[:, None] * x)
        h = h + self.l2 * jnp.diag(self._precision(jnp.diagonal(h)))
        return _pin_zero_diagonal(h)


def _pin_zero_diagonal(h: Array) -> Array:
    """Pin exact-zero Hessian diagonal entries to 1 so FULL variance with
    l2=0 and a zero-activity feature column stays invertible instead of
    poisoning every variance with inf/nan — the same convention SIMPLE
    variance applies to zero diagonals (compute_variances). A zero-activity
    column has a zero row AND column, so pinning its diagonal makes it an
    isolated unit basis vector: its own variance reads 1, others unaffected."""
    d = h.shape[0]
    i = jnp.arange(d)
    dg = jnp.diagonal(h)
    return h.at[i, i].set(jnp.where(dg == 0, jnp.ones((), h.dtype), dg))


# ---------------------------------------------------------------------------
# Sliced aggregators: the out-of-core fixed-effect objective
# (game/fe_streaming.py) streams row slices through the chip and needs the
# objective split into per-slice partial sums plus one finalize step. The
# decomposition is exact, not approximate: value, X^T(w dz), sum(w dz),
# X^T c and sum(c) are all plain row sums, while the normalization
# shift/factor algebra, the prior delta and the L2 term depend only on the
# coefficient vector — so they apply ONCE to the accumulated totals and the
# streamed objective equals the resident one up to float summation order.
# (Reference: the same split between the per-partition seqOp and the driver-
# side combOp of ValueAndGradientAggregator.scala:36-161.)


def slice_value_grad_partials(
    loss: PointwiseLoss,
    batch_slice: LabeledBatch,
    eff: Array,
    mshift: Array,
) -> Tuple[Array, Array, Array]:
    """Per-row-slice partial sums of the GLM objective: (sum_i w_i l_i,
    X_slice^T (w dz), sum_i w_i dz_i). ``eff``/``mshift`` are the
    normalization-effective coefficients (norm.effective_coefficients),
    computed once per evaluation, not per slice."""
    b = batch_slice
    z = b.features.matvec(eff) + mshift + b.offsets
    l, dz = loss.loss_and_dz(z, b.labels)
    wdz = b.weights * dz
    return jnp.sum(b.weights * l), b.features.rmatvec(wdz), jnp.sum(wdz)


def slice_hessian_vector_partials(
    loss: PointwiseLoss,
    batch_slice: LabeledBatch,
    eff: Array,
    mshift: Array,
    eff_v: Array,
    vshift: Array,
) -> Tuple[Array, Array]:
    """Per-row-slice partial sums of H v: (X_slice^T c, sum_i c_i) with
    c = w l''(z) u and u = x.eff_v + vshift (hessian_vector's row terms)."""
    b = batch_slice
    z = b.features.matvec(eff) + mshift + b.offsets
    c = b.weights * loss.d2z(z, b.labels) * (b.features.matvec(eff_v) + vshift)
    return b.features.rmatvec(c), jnp.sum(c)


def finalize_value_grad(
    coef: Array,
    value_sum: Array,
    raw_grad_sum: Array,
    wdz_sum: Array,
    norm: NormalizationContext,
    l2: Array,
    prior_mean: Optional[Array],
    prior_precision: Optional[Array],
) -> Tuple[Array, Array]:
    """Apply the per-evaluation (not per-slice) algebra of
    GLMObjective.value_and_grad to accumulated slice partials."""
    grad = raw_grad_sum
    if norm.shifts is not None:
        grad = grad - norm.shifts * wdz_sum
    if norm.factors is not None:
        grad = grad * norm.factors
    delta = coef if prior_mean is None else coef - prior_mean
    prec = jnp.ones_like(coef) if prior_precision is None else prior_precision
    value = value_sum + 0.5 * l2 * jnp.dot(delta, prec * delta)
    grad = grad + l2 * prec * delta
    return value, grad


def finalize_hessian_vector(
    v: Array,
    hv_sum: Array,
    csum: Array,
    norm: NormalizationContext,
    l2: Array,
    prior_precision: Optional[Array],
) -> Array:
    """Apply GLMObjective.hessian_vector's post-accumulation algebra to
    accumulated slice partials."""
    hv = hv_sum
    if norm.shifts is not None:
        hv = hv - norm.shifts * csum
    if norm.factors is not None:
        hv = hv * norm.factors
    prec = jnp.ones_like(v) if prior_precision is None else prior_precision
    return hv + l2 * prec * v


def _vg(obj: "GLMObjective", coef: Array):
    return obj.value_and_grad(coef)


def _hvp(obj: "GLMObjective", coef: Array, v: Array) -> Array:
    return obj.hessian_vector(coef, v)


def vg_fn(obj: GLMObjective):
    """value_and_grad as a jit-cache-stable pytree callable: the function
    identity is the module-level _vg, the objective rides along as a pytree
    argument — repeated solver calls with fresh GLMObjective instances of the
    same structure REUSE the compiled solver instead of recompiling."""
    return jax.tree_util.Partial(_vg, obj)


def hvp_fn(obj: GLMObjective):
    return jax.tree_util.Partial(_hvp, obj)


def _margins(obj: "GLMObjective", coef: Array) -> Array:
    return obj.margins(coef)


def _direction_margins(obj: "GLMObjective", direction: Array) -> Array:
    return obj.direction_margins(direction)


def _value_and_slope(obj: "GLMObjective", z, u, t, coef, direction):
    return obj.value_and_slope(z, u, t, coef, direction)


def _grad_from_margins(obj: "GLMObjective", z: Array, coef: Array):
    return obj.grad_from_margins(z, coef)


def margin_fns(obj: GLMObjective):
    """The two-pass objective's steps for a search that walks margins, in the
    order of ``optimize.common.MarginFns`` (margins, direction_margins,
    value_and_slope, grad_from_margins), each jit-cache-stable as ``vg_fn``
    is. For the two-pass path: the fused kernels read X once for value and
    gradient, and a solve over them keeps ``vg_fn``."""
    return tuple(
        jax.tree_util.Partial(fn, obj)
        for fn in (_margins, _direction_margins, _value_and_slope, _grad_from_margins)
    )


@jax.jit
def _diag_of_inverse(m: Array) -> Array:
    """diag(m^-1) for SPD m via Cholesky (the reference Cholesky-solves too,
    Linalg.scala): with m = L L^T, diag(m^-1)_j = ||column j of L^-1||^2.

    The columns of L^-1 are computed in CHUNKED triangular solves
    (L X = I[:, j0:j1]) instead of one full-eye cho_solve: XLA's
    triangular_solve with a [d, d] RHS materializes a d x d temp per block
    step (measured 509 GB of HLO temps at d = 32768); a [d, chunk] RHS keeps
    the peak at L + one chunk."""
    d = m.shape[0]
    L = jnp.linalg.cholesky(m)
    chunk = min(d, 2048)
    n_chunks = -(-d // chunk)

    def body(i, diag):
        cols = i * chunk + jnp.arange(chunk)
        rhs = (jnp.arange(d)[:, None] == cols[None, :]).astype(m.dtype)
        x = jax.scipy.linalg.solve_triangular(L, rhs, lower=True)  # [d, chunk]
        return jax.lax.dynamic_update_slice(diag, jnp.sum(x * x, axis=0), (i * chunk,))

    diag = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros(n_chunks * chunk, m.dtype)
    )
    return diag[:d]


def compute_variances(
    objective: GLMObjective, coef: Array, variance_type: str
) -> Optional[Array]:
    """Coefficient variances (reference: DistributedOptimizationProblem.computeVariances,
    photon-api .../optimization/DistributedOptimizationProblem.scala:84-108).

    SIMPLE -> 1 / diag(H); FULL -> diag(H^-1) via Cholesky; NONE -> None.
    """
    vt = variance_type.upper()
    if vt == "NONE":
        return None
    if vt == "SIMPLE":
        d = objective.hessian_diagonal(coef)
        return 1.0 / jnp.where(d == 0, 1.0, d)
    if vt == "FULL":
        h = objective.hessian_matrix(coef)
        # jitted module-level helper (stable cache key) so a model-axis-
        # sharded h (tiled layout, possibly multi-process) gathers for the
        # one-device inversion without recompiling per call
        return _diag_of_inverse(h)
    raise ValueError(f"Unknown variance computation type: {variance_type!r}")
