"""Multi-process random-effect dataset build: entity planning across hosts.

Reference: the reference's cluster-side RE pipeline — entities placed by a
size-aware partitioner that collects (entityId -> count) to the driver
(photon-api .../data/RandomEffectDatasetPartitioner.scala:117-180), followed
by a ``partitionBy`` shuffle of every entity's rows to its owning executor and
per-partition local dataset builds (RandomEffectDataset.scala:255-360).

TPU re-design: the sample axis is already sharded across processes (each host
read its own row range), so the build splits into

1. **Planning metadata exchange** (host, small): each process allgathers its
   local (entity id, count) table (`multihost.allgather_object`); every
   process merges them identically and derives the same `_EntityPlan`
   (size-sorted entity order dealt over the mesh's chunks, block capacity K,
   weight rescales) — the
   analogue of the reference's driver-side partitioner state.
2. **Device-side shuffle** (bulk, zero host networking): per-row planning
   columns (entity index, splitmix64 reservoir priority) and the row data
   (labels/weights/offsets + ELL features at a globally-agreed width) are
   assembled into globally row-sharded arrays (`multihost.put_global`). The
   active-set selection is ONE multi-key stable device sort
   (``lax.sort(num_keys=3)`` — exactly ``np.lexsort((priority64, entity))``
   via the (hi32, lo32) key split), and the "shuffle" into entity-sharded
   blocks is a device gather: GSPMD lowers the row-sharded -> entity-sharded
   data movement to cross-device collectives over ICI/DCN, which is where the
   reference's Spark shuffle traffic belongs on a TPU pod.
3. **Per-entity subspace projection on device**: each entity's active feature
   column union (LinearSubspaceProjector.scala:37-90) is a vmapped
   sort-and-compact over its gathered ELL columns; block features are
   remapped into subspace slots by a vmapped searchsorted.

Single-process, this degrades to plain device_puts and produces bit-identical
planning to `build_random_effect_dataset` (same `_EntityPlan`, same reservoir
order) — asserted by tests/test_re_build.py's parity tests. Pearson feature
selection included: scores are computed in wide precision and quantized to a
1e-12 grid before ranking, so the ~1e-13 reduction-order differences between
host numpy and XLA collapse onto the same sort key and the stable
column-order tie-break keeps the SAME column on both paths (exact ties are
common for tiny entities, e.g. four columns all scoring sqrt(6)/4). This is
a mitigation with a vanishing — not zero — failure window: a true score
within ~1 ulp of a grid midpoint can still round apart on the two paths.
Tied-column parity therefore NEEDS f64: the 1e-12 grid is below f32
resolution, so the wide scoring path requires jax_enable_x64 and refuses to
run without it (``_require_wide_dtype``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..io.data import RawDataset
from ..parallel import multihost
from ..parallel.mesh import DATA_AXIS
from .data import (
    EntityBlocks,
    RandomEffectDataset,
    _entity_plan,
    _hash64,
    _rows_to_ell,
)


def build_random_effect_dataset_global(
    raw: RawDataset,
    coordinate_id: str,
    feature_shard: str,
    random_effect_type: str,
    mesh,
    active_cap: Optional[int] = None,
    active_lower_bound: int = 1,
    seed: int = 0,
    dtype=jnp.float32,
    pad_entities_to_multiple: int = 1,
    features_to_samples_ratio: Optional[float] = None,
    feature_dtype=None,
    hbm_budget_bytes: Optional[int] = None,
) -> RandomEffectDataset:
    """Build a RandomEffectDataset whose row axis spans ALL processes' rows.

    ``raw`` is this process's local (equal-share padded) row slice; the
    resulting dataset's sample space is the padded GLOBAL row space
    [P * raw.n_rows], row-sharded over the mesh data axis, and the entity
    blocks are entity-sharded over the same axis.

    ``hbm_budget_bytes``: when set and this host's entity shard would exceed
    the budget, the dataset is built STREAMED — each process keeps only ITS
    contiguous block-row range as HOST numpy (``entity_shard_range`` marks
    the range) and training/scoring stream entity slices under the PER-HOST
    budget (game/streaming.py; the execution planner's streamed+sharded
    routing). Caveat: the build itself still stages the full blocks through
    device memory — the budget bounds steady-state training residency, not
    peak build residency.
    """
    if jax.process_count() > 1 and raw.global_row_start is None:
        raise ValueError(
            "multi-process RE build requires raw.global_row_start (this "
            "process's first global row): without it every host would hash "
            "reservoir priorities from row 0 and the active-set selection "
            "silently diverges; set it from multihost.host_row_range"
        )
    np_dtype = np.dtype(jnp.zeros((), dtype).dtype)
    # Pearson selection scores must see pre-cast values (parity with the
    # single-process host build, which selects in f64 and casts after):
    # stage the build in the widest available float, downcast at the end
    build_dtype = (
        np.dtype(jnp.zeros((), jnp.float64).dtype)
        if features_to_samples_ratio is not None
        else np_dtype
    )
    true_local = raw.true_rows if raw.true_rows is not None else raw.n_rows
    g_start = raw.global_row_start or 0
    n_proc = jax.process_count()
    # pad the local row slice exactly like pad_rows_for_mesh pads the
    # fixed-effect batch, so the padded GLOBAL row space (and hence residual
    # score vector positions) is identical across all coordinates
    chunk = max(mesh.shape[DATA_AXIS] // n_proc, 1)
    n_local = ((raw.n_rows + chunk - 1) // chunk) * chunk
    N = n_local * n_proc
    d_shard = raw.shard_dims[feature_shard]
    rows, cols, vals = raw.shard_coo[feature_shard]

    # --- 1. planning metadata exchange (host, small) -------------------------
    ids_arr = np.asarray(raw.id_tags[random_effect_type][:true_local]).astype(str)
    uniq_l, inv_l = np.unique(ids_arr, return_inverse=True)
    counts_l = np.bincount(inv_l, minlength=len(uniq_l)).astype(np.int64)
    nnz_rows = np.bincount(rows, minlength=n_local) if len(rows) else np.zeros(1)
    f_local = max(int(nnz_rows.max()) if n_local else 1, 1)
    tables = multihost.allgather_object((uniq_l, counts_l, f_local))

    all_ids = np.concatenate([t[0] for t in tables])
    all_cnt = np.concatenate([t[1] for t in tables])
    F = max(t[2] for t in tables)
    uniq, inv_m = np.unique(all_ids, return_inverse=True)
    counts = np.zeros(len(uniq), np.int64)
    np.add.at(counts, inv_m, all_cnt)

    plan = _entity_plan(counts, active_lower_bound, active_cap, pad_entities_to_multiple)
    E_real, E, K = plan.E_real, plan.E, plan.K

    # per-host build shape telemetry (host-known numbers; no device fetch)
    reg = obs.current_run().registry
    proc = str(multihost.process_index())
    reg.gauge(
        "photon_re_build_rows", "true (unpadded) local rows per process"
    ).labels(coordinate=coordinate_id, process=proc).set(true_local)
    reg.gauge(
        "photon_re_build_local_entities", "distinct local entities per process"
    ).labels(coordinate=coordinate_id, process=proc).set(len(uniq_l))
    reg.gauge(
        "photon_re_build_global_entities", "kept entities in the merged plan"
    ).labels(coordinate=coordinate_id).set(E_real)

    # --- 2. local per-row planning columns -> global row-sharded arrays ------
    local_block = plan.old_to_block[np.searchsorted(uniq, ids_arr)]
    ent_local = np.full(n_local, -1, np.int32)
    ent_local[:true_local] = local_block
    # reservoir priorities hash the TRUE global row id (parity with the
    # single-process path); active_rows index the PADDED global row space
    pr = _hash64(g_start + np.arange(true_local, dtype=np.int64), seed)
    phi = np.zeros(n_local, np.uint32)
    plo = np.zeros(n_local, np.uint32)
    phi[:true_local] = (pr >> np.uint64(32)).astype(np.uint32)
    plo[:true_local] = (pr & np.uint64(0xFFFFFFFF)).astype(np.uint32)

    def _pad1(a):
        out = np.zeros(n_local, np.float64)
        out[: len(a)] = a
        return out

    wt_local = _pad1(raw.weights)
    safe_block = np.maximum(local_block, 0)
    wt_local[:true_local] *= plan.weight_scale[safe_block]
    lab_local = _pad1(raw.labels)
    off_local = _pad1(raw.offsets)

    ell_idx_l, ell_val_l = _rows_to_ell(rows, cols, vals, n_local, width=F)

    row_spec = P(DATA_AXIS)
    put_row = lambda a: multihost.put_global(a, mesh, row_spec)
    put_ell = lambda a: multihost.put_global(a, mesh, P(DATA_AXIS, None))
    ent_g = put_row(ent_local)
    phi_g = put_row(phi)
    plo_g = put_row(plo)
    lab_g = put_row(lab_local.astype(build_dtype))
    off_g = put_row(off_local.astype(np_dtype))
    wt_g = put_row(wt_local.astype(np_dtype))
    eli_g = put_ell(ell_idx_l)
    elv_g = put_ell(ell_val_l.astype(build_dtype))

    ent_shard = NamedSharding(mesh, P(DATA_AXIS, None))
    ent_shard3 = NamedSharding(mesh, P(DATA_AXIS, None, None))

    # --- 3. device-side active selection (the reservoir, P9) -----------------
    if E_real == 0:
        active_rows = multihost.put_global_from_full(
            np.full((E, K), -1, np.int32), mesh, P(DATA_AXIS, None)
        )
    else:

        def _select(ent, hi, lo):
            n = ent.shape[0]
            idx = jnp.arange(n, dtype=jnp.int32)
            # stable 3-key sort == np.lexsort((priority64, entity)): primary
            # entity, then priority hi32, then lo32, then original position
            s_ent, _, _, s_rows = lax.sort((ent, hi, lo, idx), num_keys=3, is_stable=True)
            starts = jnp.searchsorted(s_ent, jnp.arange(E_real, dtype=s_ent.dtype))
            rank = jnp.arange(n, dtype=jnp.int32) - starts[
                jnp.clip(s_ent, 0, E_real - 1)
            ].astype(jnp.int32)
            active = (s_ent >= 0) & (rank < K)
            te = jnp.where(active, s_ent, E)  # out-of-bounds rows drop
            tk = jnp.where(active, rank, 0)
            return (
                jnp.full((E, K), -1, jnp.int32).at[te, tk].set(s_rows, mode="drop")
            )

        active_rows = jax.jit(_select, out_shardings=ent_shard)(ent_g, phi_g, plo_g)

    # --- 4. device-side shuffle: gather row data into entity blocks ----------
    def _gather(act, lab, off, wt, eli, elv):
        valid = (act >= 0).astype(lab.dtype)
        safe = jnp.maximum(act, 0)
        lb = jnp.take(lab, safe, axis=0) * valid
        ob = jnp.take(off, safe, axis=0) * valid
        wb = jnp.take(wt, safe, axis=0) * valid
        bc = jnp.take(eli, safe, axis=0)  # [E, K, F] global columns
        bv = jnp.take(elv, safe, axis=0) * valid[..., None]
        return lb, ob, wb, bc, bv

    lb, ob, wb, bc, bv = jax.jit(
        _gather,
        out_shardings=(ent_shard, ent_shard, ent_shard, ent_shard3, ent_shard3),
    )(active_rows, lab_g, off_g, wt_g, eli_g, elv_g)

    # --- 5. per-entity subspace projection on device -------------------------
    def _unions(bc, bv):
        keyc = jnp.where(bv != 0, bc, d_shard).reshape(E, K * F)
        sk = jnp.sort(keyc, axis=1)
        prev = jnp.concatenate([jnp.full((E, 1), -1, sk.dtype), sk[:, :-1]], axis=1)
        new = (sk != prev) & (sk < d_shard)
        return sk, new, new.sum(axis=1)

    sk, newm, sizes = jax.jit(
        _unions, out_shardings=(ent_shard, ent_shard, NamedSharding(mesh, P(DATA_AXIS)))
    )(bc, bv)
    sizes_host = np.asarray(multihost.fully_replicate(sizes, mesh)).astype(np.int64)
    S = max(int(sizes_host.max()) if E_real else 1, 1)

    def _project(sk, newm, bc, bv):
        pos = jnp.cumsum(newm, axis=1) - 1
        te = jnp.broadcast_to(jnp.arange(E)[:, None], sk.shape)
        pc = (
            jnp.full((E, S), -1, jnp.int32)
            .at[te, jnp.where(newm, pos, S)]
            .set(sk.astype(jnp.int32), mode="drop")
        )
        pc_search = jnp.where(pc >= 0, pc, d_shard)
        loc = jax.vmap(jnp.searchsorted)(pc_search, bc.reshape(E, K * F))
        loc = loc.reshape(E, K, F)
        nz = bv != 0
        e3 = jnp.broadcast_to(jnp.arange(E)[:, None, None], loc.shape)
        k3 = jnp.broadcast_to(jnp.arange(K)[None, :, None], loc.shape)
        feats = (
            jnp.zeros((E, K, S), bv.dtype)
            .at[e3, k3, jnp.where(nz, loc, S)]
            .set(bv, mode="drop")
        )
        return pc, feats

    pc, feats = jax.jit(_project, out_shardings=(ent_shard, ent_shard3))(
        sk, newm, bc, bv
    )

    if features_to_samples_ratio is not None:
        pc, feats, sizes_host, S = _pearson_select_device(
            mesh, ent_shard, ent_shard3, pc, feats, lb,
            (active_rows >= 0), features_to_samples_ratio, E_real,
        )

    host_pc = np.asarray(multihost.fully_replicate(pc, mesh))

    # --- 6. assemble (downcast wide staging to the block dtype; features and
    # ELL values optionally narrower via feature_dtype) -----------------------
    fdt = feature_dtype or dtype
    fdt_np = np.dtype(jnp.zeros((), fdt).dtype)
    streamed = False
    if hbm_budget_bytes is not None:
        from .streaming import estimate_block_bytes

        # per-HOST budget against this host's entity shard (same estimator
        # as the single-process build, scaled to the local share of E)
        streamed = (
            estimate_block_bytes(-(-E // n_proc), K, int(pc.shape[1]), fdt_np.itemsize)
            > hbm_budget_bytes
        )
    entity_shard_range = None
    if streamed:
        # streamed + sharded: pull THIS host's contiguous block-row range to
        # host numpy; train/score stream it in budget-sized slices
        # (game/streaming.py) and exchange results host-side in process order
        shard_keys = sorted(
            {
                (s.index[0].start or 0, s.index[0].stop)
                for s in active_rows.addressable_shards
            }
        )
        lo = int(shard_keys[0][0])
        hi = int(shard_keys[-1][1]) if shard_keys[-1][1] is not None else E
        entity_shard_range = (lo, hi)
        pull = multihost.host_local_rows
        blocks = EntityBlocks(
            features=pull(feats).astype(fdt_np),
            labels=pull(lb).astype(np_dtype),
            offsets=pull(ob).astype(np_dtype),
            weights=pull(wb).astype(np_dtype),
            proj_cols=pull(pc).astype(np.int32),
            active_rows=pull(active_rows).astype(np.int32),
        )
        # scoring arrays stay LOCAL (this host's padded row slice, plain
        # single-device arrays): the streamed score computes local scores
        # and put_globals them into the global row space
        row_entity_out = jnp.asarray(ent_local)
        ell_idx_out = jnp.asarray(ell_idx_l)
        ell_val_out = jnp.asarray(ell_val_l.astype(fdt_np))
    else:
        if build_dtype != np_dtype or feature_dtype is not None:
            feats = feats.astype(fdt)
            lb = lb.astype(dtype)
            elv_g = elv_g.astype(fdt)
        blocks = EntityBlocks(
            features=feats,
            labels=lb,
            offsets=ob.astype(dtype),
            weights=wb.astype(dtype),
            proj_cols=pc,
            active_rows=active_rows,
        )
        row_entity_out = ent_g
        ell_idx_out = eli_g
        ell_val_out = elv_g
    kept_ids = uniq[plan.kept_entities].astype(str)
    entity_ids = (
        np.concatenate(
            [kept_ids, np.asarray([f"__pad{i}" for i in range(E - E_real)], dtype=object)]
        )
        if E > E_real
        else kept_ids
    )
    entity_counts = np.zeros(E, np.int64)
    entity_counts[:E_real] = np.minimum(counts[plan.kept_entities], K)

    return RandomEffectDataset(
        coordinate_id=coordinate_id,
        feature_shard=feature_shard,
        random_effect_type=random_effect_type,
        entity_ids=entity_ids.astype(object),
        blocks=blocks,
        row_entity=row_entity_out,
        ell_idx=ell_idx_out,
        ell_val=ell_val_out,
        # per-entity passive/active accounting (RandomEffectDataset.scala:
        # 590-599): global rows that belong to a kept entity but were
        # reservoir-dropped from its active block. Derived from the
        # replicated plan arrays — same O(E*K + n) host cost the
        # single-process build pays
        passive_rows=_derive_passive_rows(mesh, ent_local, n_local, active_rows),
        entity_counts=entity_counts,
        entity_subspace_dims=sizes_host,
        entity_chunks=plan.chunks,
        host_proj_cols=host_pc,
        streamed=streamed,
        hbm_budget_bytes=hbm_budget_bytes if streamed else None,
        entity_shard_range=entity_shard_range,
        mesh=mesh if streamed else None,
    )


def _derive_passive_rows(mesh, ent_local, n_local, active_rows) -> np.ndarray:
    """PADDED-global row ids that belong to a kept entity but are not in any
    active block (the reference's passive set, RandomEffectDataset.scala:
    590-599).

    ``active_rows`` indexes the padded global row space (local row i on
    process p lives at ``p * n_local + i``), so the local candidates must be
    computed in that same space. Using the TRUE global row start here is
    wrong whenever ``n_rows`` is not divisible by the per-process chunk:
    the pad shifts every later process's rows, active rows get misclassified
    as passive and the returned ids don't address the dataset's row space.

    Scalability: the [n] entity map is NOT replicated — each host tests only
    its own local row slice (host numpy, O(n/p)) against the [E, K] active
    table (replicated once, the same scale as the host_proj_cols table this
    build already replicates), then the per-host PASSIVE candidates — usually
    a small reservoir-dropped subset — are exchanged and concatenated."""
    ar_host = np.asarray(multihost.fully_replicate(active_rows, mesh)).ravel()
    active_ids = np.sort(ar_host[ar_host >= 0].astype(np.int64))
    local_in_entity = (
        multihost.process_index() * n_local
        + np.flatnonzero(np.asarray(ent_local) >= 0)
    ).astype(np.int64)
    pos = np.searchsorted(active_ids, local_in_entity)
    pos = np.minimum(pos, max(len(active_ids) - 1, 0))
    is_active = (
        active_ids[pos] == local_in_entity if len(active_ids) else
        np.zeros(len(local_in_entity), bool)
    )
    local_passive = local_in_entity[~is_active]
    parts = multihost.allgather_object(local_passive)
    return np.sort(np.concatenate(parts)) if parts else local_passive


def _require_wide_dtype():
    """The dtype the device-side Pearson scoring runs in — must be f64.

    The tied-column parity scheme quantizes |score| to a 1e-12 grid
    (``jnp.round(|score|, 12)``) so host/device reduction-order noise
    collapses onto one sort key. f32 resolves ~7 decimal digits, so under
    f32 the rounding is a silent no-op, near-ties rank by raw f32 noise, and
    tied-column selection can diverge from the single-process host build.
    Hence: wide scoring requires jax_enable_x64."""
    wide = jnp.zeros((), jnp.float64).dtype
    if wide != np.dtype(np.float64):
        raise ValueError(
            "features_to_samples_ratio on the multi-process build requires "
            "jax_enable_x64: without f64 the 1e-12 tie-break quantization "
            "(jnp.round(|score|, 12)) is below f32 resolution — a silent "
            "no-op — and tied-column selection can diverge from the "
            "single-process host path. Enable x64 or drop the ratio."
        )
    return wide


def _pearson_select_device(
    mesh, ent_shard, ent_shard3, pc, feats, labels, row_mask, ratio, E_real
):
    """Device-side port of data._pearson_keep_mask + column compaction
    (LocalDataset.filterFeaturesByPearsonCorrelationScore,
    LocalDataset.scala:103-130): keep per entity the ceil(ratio * n_rows)
    columns with the largest |Pearson(feature, label)|, compact kept columns
    to the front, shrink the block subspace dim."""
    E, K, S = feats.shape

    wide = _require_wide_dtype()

    def _keep(feats, labels, row_mask, pc):
        fw = feats.astype(wide)
        lw = labels.astype(wide)
        rm = row_mask.astype(wide)
        eps = jnp.finfo(jnp.float64).eps
        n_e = rm.sum(axis=1)
        n_safe = jnp.maximum(n_e, 1.0)
        mean_y = (lw * rm).sum(axis=1) / n_safe
        dy = (lw - mean_y[:, None]) * rm
        std_y = jnp.sqrt((dy * dy).sum(axis=1))
        mean_x = (fw * rm[:, :, None]).sum(axis=1) / n_safe[:, None]
        dx = (fw - mean_x[:, None, :]) * rm[:, :, None]
        cov = jnp.einsum("eks,ek->es", dx, dy)
        std_x = jnp.sqrt((dx * dx).sum(axis=1))
        score = cov / (std_y[:, None] * std_x + eps)

        const = std_x < jnp.sqrt(n_safe)[:, None] * eps
        cand = const & (jnp.abs(mean_x - 1.0) < 1e-12) & (pc >= 0)
        has = cand.any(axis=1)
        first = jnp.argmax(cand, axis=1)
        first_one = (
            jnp.zeros_like(cand)
            .at[jnp.arange(E), first]
            .set(has)
        )
        score = jnp.where(const, jnp.where(first_one, 1.0, 0.0), score)

        n_active = (pc >= 0).sum(axis=1)
        k_keep = jnp.ceil(ratio * n_e).astype(jnp.int64)
        k_keep = jnp.minimum(k_keep, n_active)
        # quantize to the same 1e-12 grid as the host path: ulp-level
        # reduction-order differences collapse onto one key, so the stable
        # column-order tie-break picks the SAME column on both paths
        absc = jnp.where(pc >= 0, jnp.round(jnp.abs(score), 12), -1.0)
        order = jnp.argsort(-absc, axis=1, stable=True)
        rank = (
            jnp.zeros((E, S), jnp.int64)
            .at[jnp.broadcast_to(jnp.arange(E)[:, None], (E, S)), order]
            .set(jnp.broadcast_to(jnp.arange(S, dtype=jnp.int64), (E, S)))
        )
        keep = (rank < k_keep[:, None]) & (pc >= 0)
        # compact kept columns to the front (stable)
        corder = jnp.argsort(~keep, axis=1, stable=True)
        pc2 = jnp.take_along_axis(jnp.where(keep, pc, -1), corder, axis=1)
        f2 = jnp.take_along_axis(
            jnp.where(keep[:, None, :], feats, 0.0), corder[:, None, :], axis=2
        )
        return pc2, f2, keep.sum(axis=1)

    pc2, f2, sizes = jax.jit(
        _keep,
        out_shardings=(ent_shard, ent_shard3, NamedSharding(mesh, P(DATA_AXIS))),
    )(feats, labels, row_mask, pc)
    sizes_host = np.asarray(multihost.fully_replicate(sizes, mesh)).astype(np.int64)
    S2 = max(int(sizes_host.max()) if E_real else 1, 1)
    return pc2[:, :S2], f2[:, :, :S2], sizes_host, S2
