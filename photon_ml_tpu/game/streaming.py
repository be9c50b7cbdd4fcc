"""Out-of-core random-effect training: entity-block slices streamed through HBM.

The reference reaches "hundreds of billions of coefficients"
(/root/reference/README.md:56) because Spark spills: RandomEffectDataset RDDs
persist DISK_ONLY and stream through executors
(photon-lib .../algorithm/CoordinateDescent.scala:262,404;
RandomEffectDataset.scala:51-66). The TPU re-design keeps entity blocks in
HOST memory (numpy) and pipelines fixed-size entity slices through the chip:

- the slice size is chosen from an explicit HBM budget (bytes), halved for
  double buffering;
- slice i+1's ``jax.device_put`` is dispatched BEFORE slice i's solve is
  awaited, so the H2D transfer can overlap compute (whether it hides under
  the solve on a real host link has no chip number yet: ROADMAP.md S4);
- per-slice results are fetched to host numpy as soon as the NEXT slice's
  solve is dispatched, so device residency stays bounded by ~2 slices of
  data + solver state regardless of total model size.

Slices respect the size-bucket segmentation (``_contiguous_segments``: every
chunk's copy of every ``_size_buckets`` bucket, as block-row ranges), so each
solve call keeps the bucket's (K, S)-rounded shapes and the packed solver's
lane economy. Scoring streams the per-entity coefficient table through the
chip the same way (the model itself is bigger than the budget by
assumption).

Composes with multi-process sharding (the execution planner's
streamed+sharded routing, plan/planner.py): multi-process GLMix shards
entities ACROSS hosts (game/data_mp.py), and when the per-host entity shard
still exceeds ``hbm_budget_bytes`` each host keeps ITS contiguous block-row
range host-resident and streams it through this module under the PER-HOST
budget. Per-host results are exchanged host-side in process order
(coordinate._train_streamed), so streaming scales UP each host's share while
sharding scales OUT across hosts — total coefficient capacity is
P hosts x (host RAM), beyond any single-host resident configuration.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.transfer import logged_fetch
from ..optimize import SolverResult
from ..utils.futures import PrefetchQueue
from . import pipeline

Array = jax.Array

# proj_cols / active_rows are int32 index planes (io/data.py builds them
# that way); derived here so a future widening to int64 keeps the HBM
# estimates honest instead of silently under-counting
_INDEX_ITEMSIZE = int(np.dtype(np.int32).itemsize)


def estimate_block_bytes(
    E: int, K: int, S: int, feature_itemsize: int = 4, scalar_itemsize: int = 4
) -> int:
    """Device bytes of an in-HBM EntityBlocks of this shape (features +
    labels/offsets/weights + proj_cols/active_rows).

    ``scalar_itemsize`` is the labels/offsets/weights itemsize — 8 for an
    x64-configured dataset; callers derive it from
    ``blocks.labels.dtype.itemsize`` (the old hardcoded 4 under-counted f64
    datasets by up to a third)."""
    return (
        E * K * S * feature_itemsize
        + 3 * E * K * scalar_itemsize
        + E * (S + K) * _INDEX_ITEMSIZE
    )


def entities_per_slice(
    budget_bytes: int,
    K: int,
    S: int,
    feature_itemsize: int = 4,
    multiple: int = 8,
    scalar_itemsize: int = 4,
) -> int:
    """Entities per streamed slice under ``budget_bytes``: double-buffered
    (2 slices resident) plus ~4 [E_s, S] solver-state arrays per entity
    lane (w0/prior/coef/grad; the L-BFGS history is bounded separately by the
    solve itself). Solver state follows the label dtype (``scalar_itemsize``)."""
    state_planes = 4  # w0 / prior-mean / coefficient / gradient per entity
    per_entity = (
        2 * (K * S * feature_itemsize + 3 * K * scalar_itemsize
             + (S + K) * _INDEX_ITEMSIZE)
        + state_planes * S * scalar_itemsize
    )
    e = max(budget_bytes // max(per_entity, 1), multiple)
    return int(e // multiple * multiple)


def solve_streamed(
    blocks_np,  # EntityBlocks holding HOST numpy arrays
    segments,  # [(start, end, K_b, S_b)] from _contiguous_segments (or one segment)
    residual_scores: Optional[Array],  # device f[n] or None
    w0_np: np.ndarray,  # [E, S] host
    prior_mean_np: np.ndarray,
    prior_prec_np: np.ndarray,
    budget_bytes: int,
    train_fn,  # coordinate._train_blocks_packed
    solver_kwargs: dict,
    pipeline_depth: Optional[int] = None,  # None -> pipeline.active_depth()
) -> SolverResult:
    """Double-buffered streamed solve over all entity slices; returns a
    host-materialized SolverResult in entity order (numpy arrays).

    At ``pipeline_depth`` >= 2 staging moves to a background thread bounded
    by the same byte budget (queued + held slice bytes <= ``budget_bytes``,
    queue-empty admits one — the inline double buffer's worst case). Slice
    geometry, dispatch order, and collect order are unchanged, so the
    outputs are bit-identical to the serial loop."""
    depth = pipeline.active_depth() if pipeline_depth is None else int(pipeline_depth)
    anchor = pipeline.stage_anchor()
    E, K, S = blocks_np.features.shape
    feat_itemsize = blocks_np.features.dtype.itemsize
    # solve dtype follows the dataset's labels (features may be narrower):
    # a f64-configured streamed dataset keeps f64 results, like the in-HBM path
    sdt = np.dtype(blocks_np.labels.dtype)

    # build the flat slice list: buckets split into budget-sized windows
    slices = []
    for start, end, kb, sb in segments:
        step = max(
            min(
                entities_per_slice(
                    budget_bytes, kb, sb, feat_itemsize, scalar_itemsize=sdt.itemsize
                ),
                end - start,
            ),
            8,
        )
        for s0 in range(start, end, step):
            s1 = min(s0 + step, end)
            slices.append((s0, s1, kb, sb))

    staged_stats = {"total_bytes": 0, "max_slice_bytes": 0}
    # (start, end) host wall intervals behind photon_stream_overlap_ratio
    intervals = {"stage": [], "collect": []}

    def stage(sl, parent=None):
        with obs.span(
            "re_stream.stage", parent=parent, phase="stage", slice=sl[0]
        ) as sp:
            s0, s1, kb, sb = sl
            host = (
                blocks_np.features[s0:s1, :kb, :sb],
                blocks_np.labels[s0:s1, :kb],
                blocks_np.offsets[s0:s1, :kb],
                blocks_np.weights[s0:s1, :kb],
                blocks_np.active_rows[s0:s1, :kb],
                w0_np[s0:s1, :sb],
                prior_mean_np[s0:s1, :sb],
                prior_prec_np[s0:s1, :sb],
            )
            nbytes = int(sum(a.nbytes for a in host))
            staged_stats["total_bytes"] += nbytes
            staged_stats["max_slice_bytes"] = max(
                staged_stats["max_slice_bytes"], nbytes
            )
            obs.add_device_put_bytes("streaming.stage", nbytes)
            dev = [jax.device_put(np.ascontiguousarray(a)) for a in host]
        intervals["stage"].append((sp.start_perf, sp.start_perf + sp.duration_s))
        return dev

    def dispatch(staged):
        feats, labels, offsets, weights, active_rows, w0, pm, pp = staged
        if residual_scores is not None:
            res = jnp.take(
                residual_scores, jnp.maximum(active_rows, 0), axis=0
            ) * (active_rows >= 0)
            offsets = offsets + res.astype(offsets.dtype)
        return train_fn(feats, labels, offsets, weights, w0, pm, pp, **solver_kwargs)

    out_coef = np.zeros((E, S), sdt)
    out_grad = np.zeros((E, S), sdt)
    out_loss = np.zeros(E, sdt)
    out_it = np.zeros(E, np.int32)
    out_reason = np.zeros(E, np.int32)
    out_cg = np.zeros(E, np.int32)
    T = solver_kwargs["max_iterations"] + 1
    out_lh = np.full((E, T), np.nan, sdt)
    out_gh = np.full((E, T), np.nan, sdt)
    empty_result = SolverResult(
        coefficients=out_coef,
        loss=out_loss,
        gradient=out_grad,
        iterations=out_it,
        reason=out_reason,
        loss_history=out_lh,
        grad_norm_history=out_gh,
        cg_iterations=out_cg,
    )
    if not slices:
        # every segment was empty (e.g. all entities filtered out): nothing
        # to solve — zero coefficients, NOT_CONVERGED reasons, NaN histories
        return empty_result

    def collect(sl, res):
        s0, s1, _, sb = sl
        with obs.span("re_stream.collect", phase="collect", slice=s0) as cp:
            coef, grad, loss, iters, reason, lh, gh, cg = logged_fetch(
                "streaming.collect",
                (
                    res.coefficients, res.gradient, res.loss, res.iterations,
                    res.reason, res.loss_history, res.grad_norm_history,
                    res.cg_iterations,
                ),
            )
        intervals["collect"].append((cp.start_perf, cp.start_perf + cp.duration_s))
        out_coef[s0:s1, :sb] = coef
        out_grad[s0:s1, :sb] = grad
        out_loss[s0:s1] = loss
        out_it[s0:s1] = iters
        out_reason[s0:s1] = reason
        out_cg[s0:s1] = cg
        out_lh[s0:s1] = lh
        out_gh[s0:s1] = gh

    def _staged_slice_bytes(e: int, kb: int, sb: int) -> int:
        # what stage() actually transfers: features + labels/offsets/weights
        # + active_rows + the w0/prior-mean/prior-precision planes (proj_cols
        # is not staged — projection happens on the host side)
        return (
            e * kb * sb * feat_itemsize
            + 3 * e * kb * sdt.itemsize
            + e * kb * blocks_np.active_rows.dtype.itemsize
            + 3 * e * sb * sdt.itemsize
        )

    est_max_slice = max(
        _staged_slice_bytes(s1 - s0, kb, sb) for s0, s1, kb, sb in slices
    )

    prefetch = None
    if depth > 1 and len(slices) > 1:
        prefetch = PrefetchQueue(
            lambda i: stage(slices[i], parent=anchor),
            len(slices),
            depth=depth,
            cost=lambda i: _staged_slice_bytes(
                slices[i][1] - slices[i][0], slices[i][2], slices[i][3]
            ),
            budget=budget_bytes,
            name="photon-re-stage",
        )

    def acquire(i):
        if prefetch is None:
            return stage(slices[i])
        idx, staged = prefetch.get()
        if idx != i:
            raise RuntimeError(
                f"re streaming prefetch out of order: staged slice {idx}, "
                f"consumer wants {i}"
            )
        return staged

    try:
        with obs.span(
            "stream.solve", n_slices=len(slices), budget_bytes=int(budget_bytes)
        ):
            staged = acquire(0)
            pending = None  # (slice, dispatched result)
            for i, sl in enumerate(slices):
                res = dispatch(staged)  # async dispatch on the staged slice
                if i + 1 < len(slices):
                    staged = acquire(i + 1)  # H2D overlaps the running solve
                if pending is not None:
                    collect(*pending)  # fetch of slice i-1 syncs AFTER i is queued
                pending = (sl, res)
            collect(*pending)
    finally:
        if prefetch is not None:
            prefetch.close()

    reg = obs.current_run().registry
    # site label distinguishes this (entity-sliced RE) path from the
    # row-sliced fixed-effect path (fe_streaming.py, site="fe.train")
    reg.counter(
        "photon_stream_slices_total", "streamed slices staged through the chip"
    ).labels(site="re.train").inc(len(slices))
    reg.counter(
        "photon_stream_staged_bytes_total", "host bytes staged to device"
    ).labels(site="re.train").inc(staged_stats["total_bytes"])
    reg.gauge(
        "photon_stream_budget_bytes", "configured HBM budget"
    ).labels(site="re.train").set(budget_bytes)
    reg.gauge(
        "photon_stream_estimated_slice_bytes",
        "largest slice footprint by the block-byte estimator",
    ).labels(site="re.train").set(est_max_slice)
    reg.gauge(
        "photon_stream_actual_slice_bytes", "largest slice actually staged"
    ).labels(site="re.train").set(staged_stats["max_slice_bytes"])
    reg.gauge(
        "photon_stream_budget_headroom_bytes",
        "budget minus double-buffered peak (negative = over budget)",
    ).labels(site="re.train").set(budget_bytes - 2 * staged_stats["max_slice_bytes"])
    reg.gauge(
        "photon_stream_overlap_ratio",
        "fraction of staging wall overlapped with in-flight compute",
    ).labels(site="re.train").set(
        obs.overlap_ratio(intervals["stage"], intervals["collect"])
    )
    if prefetch is not None:
        reg.gauge(
            "photon_stream_inflight_peak_bytes",
            "peak staged bytes in flight (queued + held), bounded by the budget",
        ).labels(site="re.train").set(prefetch.peak_inflight)

    return SolverResult(
        coefficients=out_coef,
        loss=out_loss,
        gradient=out_grad,
        iterations=out_it,
        reason=out_reason,
        loss_history=out_lh,
        grad_norm_history=out_gh,
        cg_iterations=out_cg,
    )


class StreamedScoreCache:
    """One-time host-side regroup of rows by entity slice (plus the x_sub
    densification) reused across score sweeps.

    ``slice_rows[k]`` holds the row indices whose entity falls in slice k,
    padded with the out-of-range sentinel ``n`` up to a power-of-two bucket
    so repeated sweeps reuse O(log n) compiled shapes. ``device_rows`` is the
    total padded row count gathered per sweep — the device work counter the
    flat-wall assertion checks (<= 2n regardless of slice count)."""

    def __init__(self, x_sub, step, slice_rows, device_rows):
        self.x_sub = x_sub  # [n, S] device
        self.step = step
        self.slice_rows = slice_rows  # per-slice device i32[m_k], pad = n
        self.device_rows = device_rows


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def score_streamed(
    coef_values_np: np.ndarray,  # [E, S] host model table
    proj_cols_np: np.ndarray,  # [E, S] host support layout
    row_entity: Array,  # device i32[n]
    ell_idx: Array,  # device i32[n, F]
    ell_val: Array,  # device f[n, F]
    budget_bytes: int,
    cache: Optional[StreamedScoreCache] = None,
    score_dtype=None,
) -> tuple:
    """Score all rows against a host-resident per-entity coefficient table by
    streaming entity slices of the table through the device.

    Returns (scores [n], cache to reuse across sweeps). The cache holds the
    x_sub densification (row features in entity-subspace layout — row-sized
    [n, S], device-resident by assumption like the ELL arrays) plus a
    one-time host regroup of rows by entity slice.

    Cost shape: rows are regrouped by slice once (stable argsort of
    row_entity on host), so each sweep's slice k touches ONLY its own rows —
    a gather + dot over m_k padded rows with sum(m_k) <= 2n. A sweep is O(n)
    total regardless of slice count (previously each slice did masked O(n)
    work, making sweeps O(n * n_slices))."""
    from ..models.game import ell_support_positions

    E, S = coef_values_np.shape
    n = row_entity.shape[0]
    itemsize = np.dtype(coef_values_np.dtype).itemsize
    # photon: ignore[R3] — the //8*8 below rounds to the 8-entity lane
    # multiple (matches entities_per_slice), not an itemsize
    step = max(int(budget_bytes // max(S * itemsize * 2, 1)) // 8 * 8, 8)
    if score_dtype is None:
        score_dtype = jnp.promote_types(ell_val.dtype, jnp.float32)

    if cache is not None and not isinstance(cache, StreamedScoreCache):
        # pre-regroup callers cached the bare x_sub array
        cache = StreamedScoreCache(cache, -1, None, 0)

    if cache is None or cache.x_sub is None:
        x_sub = jnp.zeros((n, S), ell_val.dtype)
        for s0 in range(0, E, step):
            s1 = min(s0 + step, E)
            pc = jax.device_put(np.ascontiguousarray(proj_cols_np[s0:s1]))
            in_sl = (row_entity >= s0) & (row_entity < s1)
            # reuse the canonical support lookup (models/game.py): rows
            # outside the slice resolve against entity 0's layout but their
            # contribution is masked to zero below
            loc = jnp.where(in_sl, row_entity - s0, 0)
            pos, hit = ell_support_positions(pc, loc, ell_idx)
            contrib = jnp.where(hit & in_sl[:, None], ell_val, 0.0)
            x_sub = x_sub.at[jnp.arange(n)[:, None], pos].add(contrib)
        cache = StreamedScoreCache(x_sub, -1, None, 0)

    if cache.step != step or cache.slice_rows is None:
        # one-time regroup: rows sorted by entity are contiguous by slice;
        # per-slice groups pad to power-of-two buckets (sentinel n) so sweeps
        # reuse O(log n) compiled shapes and total padded work stays <= 2n
        re_np = np.asarray(
            logged_fetch("streaming.score_regroup", row_entity)
        ).astype(np.int64)
        order = np.argsort(re_np, kind="stable")
        edges = np.arange(0, E + step, step)[: (E + step - 1) // step + 1]
        bounds = np.searchsorted(re_np[order], edges)
        slice_rows = []
        device_rows = 0
        for k in range(len(edges) - 1):
            rows = order[bounds[k] : bounds[k + 1]]
            if len(rows) == 0:
                slice_rows.append(None)
                continue
            m = _pow2_ceil(len(rows))
            padded = np.full(m, n, dtype=np.int32)
            padded[: len(rows)] = rows
            slice_rows.append(jax.device_put(padded))
            device_rows += m
        cache = StreamedScoreCache(cache.x_sub, step, slice_rows, device_rows)
        reg = obs.current_run().registry
        reg.gauge(
            "photon_stream_score_device_rows",
            "padded rows gathered per streamed score sweep "
            "(O(n), flat in slice count)",
        ).labels(site="re.score").set(device_rows)

    xsub_wide = cache.x_sub.astype(score_dtype)  # hoisted: cast once per sweep
    scores = jnp.zeros(n, score_dtype)
    n_slices = (E + step - 1) // step
    for k in range(n_slices):
        idx = cache.slice_rows[k]
        if idx is None:
            continue
        s0 = k * step
        e_k = min(s0 + step, E) - s0
        # pad the table slice to `step` entities so every slice shares one
        # compiled shape (the tail would otherwise compile separately)
        w_np = np.zeros((step, S), coef_values_np.dtype)
        w_np[:e_k] = coef_values_np[s0 : s0 + e_k]
        w = jax.device_put(w_np)
        # sentinel rows (idx == n) read entity s0's coefficients against a
        # zero-filled feature row and are dropped by the scatter below
        loc = jnp.take(row_entity, idx, mode="fill", fill_value=s0) - s0
        wr = jnp.take(w, loc, axis=0).astype(score_dtype)  # [m, S]
        xr = jnp.take(xsub_wide, idx, axis=0, mode="fill", fill_value=0)
        part = jnp.sum(wr * xr.astype(score_dtype), axis=1)
        scores = scores.at[idx].add(part, mode="drop")
    reg = obs.current_run().registry
    reg.counter(
        "photon_stream_slices_total", "streamed slices staged through the chip"
    ).labels(site="re.score").inc(n_slices)
    return scores, cache
