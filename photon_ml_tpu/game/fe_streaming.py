"""Out-of-core FIXED-effect training: row slices streamed through HBM.

The missing twin of ``game/streaming.py`` (which streams entity blocks for
the random effects). The reference trains its fixed effect at any n by
streaming disk-persisted partitions through ``treeAggregate``
(photon-lib .../data/avro/AvroDataReader.scala:165-209, DISK_ONLY persists at
CoordinateDescent.scala:262,404): each partition computes the partial sums of
the GLM objective (seqOp) and the driver combines them (combOp) before Breeze
takes an optimizer step on the driver. The TPU re-design mirrors that split
exactly:

- the FE batch lives in HOST memory (``HostRowBatch``: row-major numpy), and
  only budget-sized ROW SLICES of the feature planes are resident on device
  at a time — slice size comes from the same ``hbm.budget.mb`` contract as
  the RE stream, halved for double buffering;
- slice k+1's ``jax.device_put`` is dispatched before slice k's partial sums
  are consumed, so H2D staging overlaps compute;
- per-slice partials (``ops/glm.py: slice_value_grad_partials`` /
  ``slice_hessian_vector_partials``) are accumulated SEQUENTIALLY in slice
  order on device — a fixed left-to-right reduction, so results are bitwise
  stable run-to-run — and the per-evaluation algebra (normalization shifts /
  factors, prior delta, L2) applies once to the totals
  (``finalize_value_grad`` / ``finalize_hessian_vector``), making the
  streamed objective equal to the resident one up to float summation order;
- the optimizer itself runs on the HOST (``optimize/host_driver.py``), one
  evaluation per full pass over the slices — the Breeze-on-the-driver shape
  of the reference, where device state is bounded by ~2 slices of features
  plus O(d) vectors regardless of n.

The [n]-sized scalar planes (labels / offsets / weights, plus the residual
score vector the coordinate composes in) stay device-resident: they are the
same order of footprint as the RE stream's row-sized ELL arrays, which are
device-resident by the same assumption — the budget governs the n*d feature
mass, which is what actually scales.

All slices share ONE step size (the tail slice is zero-padded host-side at
construction, pad rows carry weight 0 and are invisible to the objective),
so each kernel compiles once per (layout, step, d) — no per-remainder
recompiles.

Streaming composes with the mesh / multi-process topology (the execution
planner's streamed+sharded routing, plan/planner.py): each host streams ITS
OWN row slice under the per-host budget — the seqOp stays local — and the
combOp grows one cross-host rung: the accumulated per-pass partial sums
(O(d), not O(n*d)) are exchanged host-side in process order before the
finalize kernels, exactly where the reference's treeAggregate combined
executor partials on the driver. Single-process, that rung is a no-op and
the math is bit-identical to the resident path up to float summation order.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.transfer import logged_fetch
from ..utils.futures import PrefetchQueue
from . import pipeline
from ..ops.features import FeatureMatrix, LabeledBatch, feature_bytes
from ..ops.glm import (
    finalize_hessian_vector,
    finalize_value_grad,
    slice_hessian_vector_partials,
    slice_value_grad_partials,
)
from ..ops.losses import PointwiseLoss
from ..ops.normalization import NormalizationContext, identity_normalization

Array = jax.Array


def estimate_fe_batch_bytes(
    n_rows: int,
    dim: int,
    layout: str,
    ell_width: int = 0,
    feature_itemsize: int = 4,
    scalar_itemsize: int = 4,
    one_device: bool = False,
) -> int:
    """Device bytes of an in-HBM fixed-effect LabeledBatch of this shape
    (features + labels/offsets/weights). The streamed-vs-resident decision in
    ``build_fixed_effect_dataset`` compares this against ``hbm_budget_bytes``.

    ``scalar_itemsize`` is the labels/offsets/weights itemsize (8 for an
    x64-configured dataset); callers derive both itemsizes from the actual
    dtypes, like the RE estimator. ``one_device``: the batch is built for one
    device, where a wide ELL batch also holds its local column map: the
    feature bytes are ``ops.features.feature_bytes``."""
    feat = feature_bytes(
        n_rows, dim, layout, width=ell_width, itemsize=feature_itemsize,
        one_device=one_device,
    )
    return int(feat + 3 * n_rows * scalar_itemsize)


# slice row counts are rounded to this lane multiple (not a byte itemsize)
_ROW_MULTIPLE = 8


def rows_per_slice(
    budget_bytes: int, feature_row_nbytes: int, multiple: int = _ROW_MULTIPLE
) -> int:
    """Rows per streamed slice under ``budget_bytes``: double-buffered (2
    slices of feature planes resident at once). Only the feature planes are
    staged per evaluation — the [n] scalar planes are device-resident by
    assumption (see module docstring) — so the slice size is governed by the
    per-row feature bytes alone, rounded down to a lane multiple."""
    r = max(budget_bytes // max(2 * feature_row_nbytes, 1), multiple)
    return int(r // multiple * multiple)


# --- per-slice kernels -------------------------------------------------------
#
# Module-level jits shared by every StreamedFEObjective: the loss is a
# register_static pytree and FeatureMatrix carries its dim statically, so one
# compilation covers every evaluation of a given (layout, step, d) — and the
# L2 weight rides through the finalize kernels as a DYNAMIC scalar, so a
# regularization sweep re-uses the same executables.


@jax.jit
def _vg_slice_kernel(
    loss: PointwiseLoss,
    feats: FeatureMatrix,
    labels: Array,
    offsets: Array,
    weights: Array,
    eff: Array,
    mshift: Array,
):
    batch = LabeledBatch(features=feats, labels=labels, offsets=offsets, weights=weights)
    return slice_value_grad_partials(loss, batch, eff, mshift)


@jax.jit
def _hvp_slice_kernel(
    loss: PointwiseLoss,
    feats: FeatureMatrix,
    labels: Array,
    offsets: Array,
    weights: Array,
    eff: Array,
    mshift: Array,
    eff_v: Array,
    vshift: Array,
):
    batch = LabeledBatch(features=feats, labels=labels, offsets=offsets, weights=weights)
    return slice_hessian_vector_partials(loss, batch, eff, mshift, eff_v, vshift)


@jax.jit
def _finalize_vg_kernel(coef, value_sum, raw_grad_sum, wdz_sum, norm, l2, pm, pp):
    return finalize_value_grad(coef, value_sum, raw_grad_sum, wdz_sum, norm, l2, pm, pp)


@jax.jit
def _finalize_hvp_kernel(v, hv_sum, csum, norm, l2, pp):
    return finalize_hessian_vector(v, hv_sum, csum, norm, l2, pp)


class StreamedFEObjective:
    """Row-sliced, double-buffered fixed-effect GLM objective for the host
    solver driver: ``value_and_grad(w)`` / ``hessian_vector(w, v)`` take and
    return host numpy, and each call is one full streamed pass over the
    batch (the reference's treeAggregate per Breeze evaluation)."""

    def __init__(
        self,
        loss: PointwiseLoss,
        host_batch,  # game.data.HostRowBatch
        budget_bytes: int,
        norm: Optional[NormalizationContext] = None,
        l2_weight: float = 0.0,
        prior_mean: Optional[Array] = None,
        prior_precision: Optional[Array] = None,
        residual_scores: Optional[Array] = None,  # device f[n] or None
        pipeline_depth: Optional[int] = None,  # None -> pipeline.active_depth()
    ):
        self.loss = loss
        self.hb = host_batch
        self.budget_bytes = int(budget_bytes)
        self.dim = int(host_batch.dim)
        n = host_batch.n_rows
        self.n_rows = n
        sdt = np.dtype(host_batch.labels.dtype)
        self.sdt = sdt
        self.norm = identity_normalization() if norm is None else norm
        self._l2 = jnp.asarray(l2_weight, sdt)
        self._pm = None if prior_mean is None else jnp.asarray(prior_mean)
        self._pp = None if prior_precision is None else jnp.asarray(prior_precision)

        row_bytes = host_batch.feature_row_nbytes()
        # never slice wider than the batch itself (lane-multiple rounding up)
        n_up = -(-n // _ROW_MULTIPLE) * _ROW_MULTIPLE
        step = min(rows_per_slice(self.budget_bytes, row_bytes), n_up)
        self.step = step
        self.n_slices = -(-n // step)
        n_padded = self.step * self.n_slices
        pad = n_padded - n

        # the tail slice is padded ONCE, host-side, to the common step size:
        # a private copy of just that slice (never of the whole batch), so
        # every slice shares one compiled kernel shape
        self._tail = None
        if pad:
            s0 = (self.n_slices - 1) * step
            if host_batch.dense is not None:
                t = np.zeros((step, self.dim), host_batch.dense.dtype)
                t[: n - s0] = host_batch.dense[s0:]
                self._tail = (t,)
            else:
                ti = np.zeros((step, host_batch.ell_idx.shape[1]), host_batch.ell_idx.dtype)
                tv = np.zeros((step, host_batch.ell_val.shape[1]), host_batch.ell_val.dtype)
                ti[: n - s0] = host_batch.ell_idx[s0:]
                tv[: n - s0] = host_batch.ell_val[s0:]
                self._tail = (ti, tv)

        # device-resident scalar planes, padded with weight-0 rows
        def _padded(a: np.ndarray) -> np.ndarray:
            a = np.ascontiguousarray(a, sdt)
            if pad:
                a = np.concatenate([a, np.zeros(pad, sdt)])
            return a

        labels = _padded(host_batch.labels)
        offsets = _padded(host_batch.offsets)
        weights = _padded(host_batch.weights)
        obs.add_device_put_bytes(
            "fe_streaming.resident", labels.nbytes + offsets.nbytes + weights.nbytes
        )
        dl = jax.device_put(labels)
        do = jax.device_put(offsets)
        dw = jax.device_put(weights)
        if residual_scores is not None:
            res = residual_scores.astype(dl.dtype)
            if pad:
                res = jnp.concatenate([res, jnp.zeros(pad, res.dtype)])
            do = do + res
        self._scalar_slices = [
            (
                dl[k * step : (k + 1) * step],
                do[k * step : (k + 1) * step],
                dw[k * step : (k + 1) * step],
            )
            for k in range(self.n_slices)
        ]

        self.stats = {
            "vg_passes": 0,
            "hvp_passes": 0,
            "slices": 0,
            "staged_bytes": 0,
            "max_slice_bytes": 0,
            "stage_seconds": 0.0,
        }

        # sweep pipelining (game/pipeline.py): depth >= 2 moves staging onto
        # a background thread whose queue is bounded by the SAME byte budget
        # (queued + held slice bytes <= budget_bytes, queue-empty admits one
        # — the inline double buffer's 2-resident worst case, so slice
        # geometry and the left-to-right accumulation bits never change).
        # The stager cycles 0..n_slices-1 forever: every pass (vg and hvp)
        # consumes slices in that exact order, so the NEXT pass's slice 0 is
        # already staged while this pass's finalize fetch is in flight.
        self.pipeline_depth = (
            pipeline.active_depth() if pipeline_depth is None else int(pipeline_depth)
        )
        # multi-process: each host streams its OWN row slice; the per-pass
        # O(d) partial sums are combined across hosts before finalize (the
        # treeAggregate combOp rung — see module docstring)
        self._cross_host = jax.process_count() > 1
        self._anchor = pipeline.stage_anchor()
        self._slice_cost = self.step * row_bytes
        self._prefetch: Optional[PrefetchQueue] = None
        # (start, end) host wall intervals behind photon_stream_overlap_ratio:
        # "pass" covers each dispatch loop (kernels for earlier slices are in
        # flight the whole time under async dispatch), "collect" the blocking
        # result fetch — together the host-observable compute shadow
        self._intervals = {"stage": [], "collect": [], "pass": []}

    # -- staging --------------------------------------------------------------

    def _acquire(self, k: int) -> FeatureMatrix:
        """Slice k's staged features: inline at depth 1, popped from the
        background stager at depth >= 2 (started lazily on first use)."""
        if self.pipeline_depth <= 1 or self.n_slices <= 1:
            return self._stage_features(k)
        if self._prefetch is None:
            self._prefetch = PrefetchQueue(
                lambda i: self._stage_features(i, parent=self._anchor),
                self.n_slices,
                depth=self.pipeline_depth,
                cyclic=True,
                cost=lambda i: self._slice_cost,
                budget=self.budget_bytes,
                name="photon-fe-stage",
            )
        idx, staged = self._prefetch.get()
        if idx != k:
            raise RuntimeError(
                f"fe_streaming prefetch out of order: staged slice {idx}, "
                f"consumer wants {k}"
            )
        return staged

    def _stage_features(self, k: int, parent: Optional[obs.Span] = None) -> FeatureMatrix:
        """H2D-stage slice k's feature planes (dispatched before the previous
        slice's partials are consumed, so the copy overlaps compute). On the
        stager thread ``parent`` anchors the span under the sweep — the
        contextvar ancestry does not cross threads."""
        with obs.span("fe_stream.stage", parent=parent, phase="stage", slice=k) as sp:
            s0 = k * self.step
            s1 = s0 + self.step
            if self._tail is not None and k == self.n_slices - 1:
                host = self._tail
            elif self.hb.dense is not None:
                host = (self.hb.dense[s0:s1],)
            else:
                host = (self.hb.ell_idx[s0:s1], self.hb.ell_val[s0:s1])
            nbytes = int(sum(a.nbytes for a in host))
            self.stats["slices"] += 1
            self.stats["staged_bytes"] += nbytes
            self.stats["max_slice_bytes"] = max(self.stats["max_slice_bytes"], nbytes)
            obs.add_device_put_bytes("fe_streaming.stage", nbytes)
            dev = [jax.device_put(np.ascontiguousarray(a)) for a in host]
        # duration_s is set when the span closes; route all slice timing
        # through the span so the timeline stays complete (lint rule R7)
        self.stats["stage_seconds"] += sp.duration_s
        self._intervals["stage"].append((sp.start_perf, sp.start_perf + sp.duration_s))
        obs.current_run().registry.histogram(
            "photon_stream_slice_stage_seconds",
            "host wall per H2D slice-staging dispatch",
        ).observe(sp.duration_s)
        if len(dev) == 1:
            return FeatureMatrix(dim=self.dim, dense=dev[0])
        return FeatureMatrix(dim=self.dim, idx=dev[0], val=dev[1])

    # -- objective ------------------------------------------------------------

    def _combine_partials(self, acc):
        """Sum this pass's accumulated partials across processes (multi-host
        combOp). Each host's acc covers only its own rows; the exchange is
        host-side (allgather of O(d) arrays) and summed in process order, so
        every host computes the identical totals deterministically.
        Single-process: identity."""
        if not self._cross_host:
            return acc
        from ..parallel import multihost

        local = tuple(logged_fetch("fe_streaming.cross_host", a) for a in acc)
        parts = multihost.allgather_object(local)
        totals = list(parts[0])
        for p in parts[1:]:
            totals = [t + q for t, q in zip(totals, p)]
        return tuple(jnp.asarray(t) for t in totals)

    def _collect(self, kind: str, out):
        """The pass's single blocking fetch, wrapped in a phase="collect"
        span so the overlap ratio can measure staging hidden under it."""
        with obs.span("fe_stream.collect", phase="collect", kind=kind) as cp:
            out = logged_fetch("fe_streaming.collect", out)
        self._intervals["collect"].append((cp.start_perf, cp.start_perf + cp.duration_s))
        return out

    def value_and_grad_deferred(self, w: np.ndarray):
        """Dispatch one streamed (value, grad) pass WITHOUT fetching; returns
        a zero-arg closure that fetches the result. Async dispatch means the
        device is already chewing on this pass while the caller dispatches
        the next one (host_driver overlaps the tolerance pass with the first
        real evaluation this way) — and at depth >= 2 the background stager
        is meanwhile staging the next pass's slices."""
        coef = jnp.asarray(w, self.sdt)
        eff, mshift = self.norm.effective_coefficients(coef)
        self.stats["vg_passes"] += 1
        with obs.span("fe_stream.pass", kind="vg", n_slices=self.n_slices) as pp:
            acc = None
            staged = self._acquire(0)
            for k in range(self.n_slices):
                labels, offsets, weights = self._scalar_slices[k]
                part = _vg_slice_kernel(
                    self.loss, staged, labels, offsets, weights, eff, mshift
                )
                if k + 1 < self.n_slices:
                    staged = self._acquire(k + 1)  # overlaps slice k
                # fixed left-to-right accumulation: bitwise-stable run-to-run
                acc = part if acc is None else tuple(a + p for a, p in zip(acc, part))
            acc = self._combine_partials(acc)
            value, grad = _finalize_vg_kernel(
                coef, acc[0], acc[1], acc[2], self.norm, self._l2, self._pm, self._pp
            )
        self._intervals["pass"].append((pp.start_perf, pp.start_perf + pp.duration_s))
        return lambda: self._collect("vg", (value, grad))

    def value_and_grad(self, w: np.ndarray):
        """One streamed pass: (objective value, gradient) as host numpy."""
        return self.value_and_grad_deferred(w)()

    def hessian_vector(self, w: np.ndarray, v: np.ndarray) -> np.ndarray:
        """One streamed pass of H(w) v (the TRON inner-CG kernel)."""
        coef = jnp.asarray(w, self.sdt)
        vv = jnp.asarray(v, self.sdt)
        eff, mshift = self.norm.effective_coefficients(coef)
        eff_v, vshift = self.norm.effective_coefficients(vv)
        self.stats["hvp_passes"] += 1
        with obs.span("fe_stream.pass", kind="hvp", n_slices=self.n_slices) as pp:
            acc = None
            staged = self._acquire(0)
            for k in range(self.n_slices):
                labels, offsets, weights = self._scalar_slices[k]
                part = _hvp_slice_kernel(
                    self.loss, staged, labels, offsets, weights,
                    eff, mshift, eff_v, vshift,
                )
                if k + 1 < self.n_slices:
                    staged = self._acquire(k + 1)
                acc = part if acc is None else tuple(a + p for a, p in zip(acc, part))
            acc = self._combine_partials(acc)
            hv = _finalize_hvp_kernel(vv, acc[0], acc[1], self.norm, self._l2, self._pp)
        self._intervals["pass"].append((pp.start_perf, pp.start_perf + pp.duration_s))
        (hv,) = self._collect("hvp", (hv,))
        return hv

    def close(self) -> None:
        """Stop the background stager (idempotent; depth-1 objectives have
        nothing to stop). An in-flight device_put completes harmlessly."""
        if self._prefetch is not None:
            self._prefetch.close()
            self._prefetch = None

    # -- metrics --------------------------------------------------------------

    def record_metrics(self, site: str, solve_seconds: float) -> None:
        """Emit the stream counters for one completed solve; ``site``
        distinguishes the FE stream ("fe.train") from the RE stream
        ("re.train") in the shared metric families. stage_seconds vs
        solve_seconds is the measured overlap claim: staging wall that the
        double buffer failed to hide shows up as their ratio."""
        reg = obs.current_run().registry
        st = self.stats
        reg.counter(
            "photon_stream_slices_total", "streamed slices staged through the chip"
        ).labels(site=site).inc(st["slices"])
        reg.counter(
            "photon_stream_staged_bytes_total", "host bytes staged to device"
        ).labels(site=site).inc(st["staged_bytes"])
        reg.counter(
            "photon_stream_passes_total", "full streamed passes over the batch"
        ).labels(site=site, kind="vg").inc(st["vg_passes"])
        reg.counter(
            "photon_stream_passes_total", "full streamed passes over the batch"
        ).labels(site=site, kind="hvp").inc(st["hvp_passes"])
        reg.gauge(
            "photon_stream_budget_bytes", "configured HBM budget"
        ).labels(site=site).set(self.budget_bytes)
        reg.gauge(
            "photon_stream_actual_slice_bytes", "largest slice actually staged"
        ).labels(site=site).set(st["max_slice_bytes"])
        reg.gauge(
            "photon_stream_budget_headroom_bytes",
            "budget minus double-buffered peak (negative = over budget)",
        ).labels(site=site).set(self.budget_bytes - 2 * st["max_slice_bytes"])
        reg.gauge(
            "photon_stream_stage_seconds",
            "host wall spent dispatching H2D stages (overlapped under compute)",
        ).labels(site=site).set(st["stage_seconds"])
        reg.gauge(
            "photon_stream_solve_seconds", "wall of the whole streamed solve"
        ).labels(site=site).set(solve_seconds)
        # measured (not inferred) overlap: fraction of staging wall that ran
        # concurrently with the compute shadow (dispatch-loop pass windows,
        # where async-dispatched slice kernels are in flight, plus the
        # blocking collect fetch). One source of truth, shared with the
        # timeline's phase math (obs.timeline.overlap_ratio). Inline staging
        # (depth 1) executes ON the solve thread inside those same windows —
        # serial with the compute it sits between, so the serial double
        # buffer scores exactly 0 rather than a self-overlap 1.0.
        if self.pipeline_depth <= 1 or self._prefetch is None:
            measured_overlap = 0.0
        else:
            measured_overlap = obs.overlap_ratio(
                self._intervals["stage"],
                self._intervals["pass"] + self._intervals["collect"],
            )
        reg.gauge(
            "photon_stream_overlap_ratio",
            "fraction of staging wall overlapped with in-flight compute",
        ).labels(site=site).set(measured_overlap)
        if self._prefetch is not None:
            reg.gauge(
                "photon_stream_inflight_peak_bytes",
                "peak staged bytes in flight (queued + held), bounded by the budget",
            ).labels(site=site).set(self._prefetch.peak_inflight)


def score_streamed_fe(
    host_batch,  # game.data.HostRowBatch
    means: Array,  # device f[d] model coefficients (original space)
    budget_bytes: int,
    score_dtype,
) -> Array:
    """Score all rows against device-resident coefficients by streaming
    budget-sized row slices of the host feature planes through the chip
    (double-buffered, like training). Returns device scores ``[n]`` in
    ``score_dtype`` — row-sized, device-resident by assumption."""
    n, d = host_batch.n_rows, host_batch.dim
    step = min(
        rows_per_slice(budget_bytes, host_batch.feature_row_nbytes()),
        -(-n // _ROW_MULTIPLE) * _ROW_MULTIPLE,
    )
    w = means.astype(score_dtype)

    def stage(s0: int):
        s1 = min(s0 + step, n)
        if host_batch.dense is not None:
            host = (host_batch.dense[s0:s1],)
        else:
            host = (host_batch.ell_idx[s0:s1], host_batch.ell_val[s0:s1])
        obs.add_device_put_bytes(
            "fe_streaming.score_stage", int(sum(a.nbytes for a in host))
        )
        dev = [jax.device_put(np.ascontiguousarray(a)) for a in host]
        if len(dev) == 1:
            return FeatureMatrix(dim=d, dense=dev[0])
        return FeatureMatrix(dim=d, idx=dev[0], val=dev[1])

    parts = []
    starts = list(range(0, n, step))
    staged = stage(starts[0])
    for i, s0 in enumerate(starts):
        parts.append(staged.matvec(w).astype(score_dtype))
        if i + 1 < len(starts):
            staged = stage(starts[i + 1])
    reg = obs.current_run().registry
    reg.counter(
        "photon_stream_slices_total", "streamed slices staged through the chip"
    ).labels(site="fe.score").inc(len(starts))
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts)
