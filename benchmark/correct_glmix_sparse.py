"""The comparison that decides ``correct`` for the ``fit_glmix_sparse`` job, on
the pattern of benchmark/correct_sparse.py and benchmark/correct_game.py,
against ``benchmark/reference/glmix_sparse.py``.

(a) *Sample parity*, in set-up:
    - the fixed effect's objective on the cell's first 65,536 rows, built into
      a batch by the program's own path, against the plain reference at a
      seeded DENSE point UNDER NON-ZERO OFFSETS (what a solve inside coordinate
      descent is handed): value, gradient, Hv, each gradient entry against the
      sum of its terms' magnitudes (``correct_sparse``'s measure);
    - the WHOLE 3-sweep coordinate descent, both coordinates from ONE raw data
      set through ``prepare_datasets``, on ALL rows of a user sample that holds
      ``USERS_PER_BUCKET`` users of every K bucket of the cell, capped ones
      among them (seed-free: ranks inside each bucket), against the reference's
      float64 block minimisers: the fixed effect's coefficients on the touched
      columns, every user's coefficients on its support, the supports
      themselves (set for set), and the whole model's objective.
(b) *Full size*, after the window, by plain passes over ALL rows, at EVERY
    sweep's model (one more fit keeps them; the window's fits hand back only
    their best by validation AUC, so two of three sweeps' timed work ends in
    models a fit discards, and they are checked all the same): for sweep k the
    fixed effect's gradient under the per-user scores of sweep k - 1 as
    offsets (none for k = 1: what its solve was handed) and every user's
    gradient over its active rows under sweep k's fixed-effect scores, each
    against its norm at zero. At the model handed back: the whole objective
    below its value at zero; every fixed-effect column no row holds, and every
    per-user slot outside its user's support, exactly 0; the program's
    supports the reference's, set for set.
(c) Fit-to-fit sameness (both coordinates' iterations, trials judged, feature
    passes, validation AUC bit for bit) and no new program inside the window or
    the second warm-up fit are counted by the job and folded in there.

Tolerances: every limit lies between two readings (PERF.md section 6, PR 38,
gives both): what the change reads on the chip (a seed only mirrors the data,
so the solves' readings repeat to the last digit; the kernel check's point is
not mirrored and does move) and what a lower precision, or a wrong model,
reads. "Wrong model" readings are the reference computed wrongly against the
reference itself, float64 on the CPU, on a 6,256-row sample of 48 users drawn
by this file's rule from a 131,072-row toy at the cell's cap and ridges.

- ``KERNEL_TOL`` 5e-5 (``correct_sparse.KERNEL_TOL``, its measure and its
  reason): the v5e reads 5.8e-6 here under offsets of standard deviation 0.7;
  the reference with its gathered coefficients rounded to bfloat16 reads
  7.1e-3 (``kernel_err_bf16``, printed beside it in every run): THIS is the
  check a lower precision fails.
- ``FIXED_COEF_TOL`` 1e-2 of ||w||_inf on the touched columns
  (``correct_sparse.COEF_TOL`` and its reason: the program stops when an
  iteration gains under 1e-6 of the loss, the reference at a gradient of
  1e-9): the v5e reads 2.2e-3 on the 6,392-row sample; a ridge weight off by a
  tenth reads 2.5e-2, one sweep short 1.0e-1.
- ``USER_COEF_TOL`` 1e-2 of the table's largest coefficient (a third of the
  L-BFGS lanes stop OBJECTIVE_NOT_IMPROVING at 1e-6 in f32, and each user is
  solved against a residual that carries the fixed effect's slack): the v5e
  reads 2.0e-3; a per-user ridge off by a tenth reads 4.8e-2, one sweep short
  6.1e-2, weights left at 1 on the capped users 7.6e-1, no cap at all 1.0.
- ``OBJECTIVE_TOL`` 3e-4 relative (``correct_game.OBJECTIVE_TOL`` and its
  reason: the model is a sequence of block minimisers, not a minimum of this
  objective, so the solvers' slack enters at first order): the v5e reads
  7.5e-5; a ridge off by a tenth reads 2.7e-3 (fixed) or 1.0e-2 (users), one
  sweep short 5.5e-3, weights left at 1 on the capped users 1.1e-1.
- ``GRADIENT_TOL`` 5e-3, each block's OWN gradient against its norm at zero,
  under the offsets the block was SOLVED under, at every sweep k: the fixed
  effect under the per-user scores of sweep k - 1 (none for k = 1), every user
  under sweep k's fixed-effect scores (the random effect is the sweep's last
  update). The sweeps' models come from one more fit after the window that
  keeps each through the program's ``checkpoint_fn`` hook (the same fit, bit
  for bit: ``model_of_sweep``, the sweep whose model the window's last fit
  handed back as its best by validation AUC, is found by equality of the
  coefficients). Sweeps 2 and 3 are the warm solves from the 219 MB vector
  under the slot-form score's offsets: a wrong score, a warm start from the
  wrong model or a solve cut short reads there and nowhere in sweep 1. A
  solve cut short, rows trained that should be passive, weights left at 1 on
  a capped user or a wrong ridge read 1e-2 or more.
  ``fixed_gradients_after`` (sweep k's fixed effect under the SAME sweep's
  per-user scores, no limit) is printed beside them: where sweep k + 1's warm
  solve starts, which is how far the coordinate descent is from its fixed
  point after k sweeps, not a solver's slack.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from . import correct_sparse
from . import data_sparse as gen
from .jobs import fit as fitjob
from .jobs import fit_glmix_sparse as job_mod
from .jobs import fit_sparse as sparsejob
from .reference import glm_sparse as ref_fixed
from .reference import glmix_sparse as ref

KERNEL_TOL = correct_sparse.KERNEL_TOL
FIXED_COEF_TOL = 1e-2
USER_COEF_TOL = 1e-2
OBJECTIVE_TOL = 3e-4
GRADIENT_TOL = 5e-3
PARITY_L2 = correct_sparse.PARITY_L2
OFFSET_STD = 0.7  # the truth's per-user term has this spread

SAMPLE_ROWS = correct_sparse.SAMPLE_ROWS
USERS_PER_BUCKET = 8
MIN_K = 8  # the program's smallest bucket (``size_buckets`` min_dim)


def kernel_parity(job, batch, trip, offsets) -> Dict[str, float]:
    """``correct_sparse.kernel_parity`` with the rows' offsets non-zero on
    both sides: the program's objective on ``batch`` against the reference on
    the same rows' triplets at a seeded dense point."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import GLMProblem
    from photon_ml_tpu.ops.glm import GLMObjective

    cfg = job.config
    d, k = cfg["fixed_effect"]["d"], cfg["fixed_effect"]["slots_per_row"]
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(d, dtype=np.float32) / np.float32(np.sqrt(k)))
    v = jnp.asarray(rng.standard_normal(d, dtype=np.float32) / np.float32(np.sqrt(k)))
    problem = GLMProblem(task=cfg["task"], config=sparsejob._opt_config(cfg["fixed_effect"], PARITY_L2))
    objective = problem.objective(batch.with_offsets(batch.offsets + offsets))
    value, grad = jax.jit(GLMObjective.value_and_grad)(objective, w)
    hv = jax.jit(GLMObjective.hessian_vector)(objective, w, v)
    rows, cols, vals = trip
    y = batch.labels
    ones = jnp.ones_like(y)
    want_value, want_grad = ref_fixed.value_grad(w, rows, cols, vals, y, offsets, ones, PARITY_L2)
    want_hv = ref_fixed.hessian_vector(w, v, rows, cols, vals, y, offsets, ones, PARITY_L2)
    low_value, low_grad = ref_fixed.value_grad(w, rows, cols, vals, y, offsets, ones, PARITY_L2,
                                               gather_dtype=jnp.bfloat16)
    z = ref_fixed.margins(w, rows, cols, vals, n_rows=len(y)) + offsets
    p = jax.nn.sigmoid(z)
    absvals = jnp.abs(vals)
    g_scale = ref_fixed.rmatvec(jnp.abs(p - y), rows, cols, absvals, dim=d) + PARITY_L2 * jnp.abs(w)
    u_abs = ref_fixed.margins(jnp.abs(v), rows, cols, absvals, n_rows=len(y))
    h_scale = ref_fixed.rmatvec(p * (1.0 - p) * u_abs, rows, cols, absvals, dim=d) + PARITY_L2 * jnp.abs(v)
    errs = {
        "value": abs(float(value) - float(want_value)) / abs(float(want_value)),
        "gradient": correct_sparse._scaled_err(grad, want_grad, g_scale),
        "hv": correct_sparse._scaled_err(hv, want_hv, h_scale),
    }
    low = max(abs(float(low_value) - float(want_value)) / abs(float(want_value)),
              correct_sparse._scaled_err(low_grad, want_grad, g_scale))
    return {"kernel_err": max(errs.values()), "kernel_errs": errs, "kernel_err_bf16": low}


def sample_users(user: np.ndarray, cap: int) -> np.ndarray:
    """The ids of the parity sample's users, seed-free: of every K bucket of
    the cell (a user's row count rounded up to a power of two, floored at
    ``MIN_K``, capped), the users at ``USERS_PER_BUCKET`` evenly spaced ranks
    of the bucket's users sorted by descending count (half a stride in, so the
    head user of 135,000 rows stays out and users just over the cap come in)."""
    ids, counts = np.unique(user, return_counts=True)
    order = np.lexsort((ids, -counts))
    ids, counts = ids[order], counts[order]
    kb = np.minimum(np.maximum(1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64), MIN_K), cap)
    picked = []
    for k in np.unique(kb):
        members = np.flatnonzero(kb == k)
        take = min(USERS_PER_BUCKET, len(members))
        ranks = ((np.arange(take) + 0.5) * len(members) / take).astype(np.int64)
        picked.append(ids[members[ranks]])
    return np.sort(np.concatenate(picked))


def reference_block(job, rows, user_index: np.ndarray, n_users: int, l2: float) -> ref.UserBlock:
    """The reference's view of the rows' random effect: raw user-shard slots
    under the run's mirror, active weights by the published rule under the
    program's priority (of the row's index IN THE DATA SET GIVEN)."""
    re = job.config["random_effect"]
    weights = ref.active_weights(user_index, ref.row_priority(len(user_index)), re["active_cap"], n_users)
    return ref.user_block(
        user_index, rows.user_cols, job.user_mirror[rows.user_cols].astype(np.float64),
        re["d_re"], n_users, l2, weights,
    )


def model_table(model, block: ref.UserBlock, user_ids: np.ndarray, values=None) -> Dict[str, object]:
    """The program's random-effect model on the reference's support: the
    table [P], whether the two supports are the same set, and how many slots
    outside a user's support hold a non-zero. ``values`` (host f[E, S])
    stands in for the model's own coefficients (another sweep's, in the same
    layout)."""
    import jax

    idx = np.asarray(jax.device_get(model.coef_indices))
    val = np.asarray(jax.device_get(model.coef_values)) if values is None else values
    real = np.asarray([not str(e).startswith("__pad") for e in model.entity_ids])
    entity_user = np.full(len(real), -1, np.int64)
    ids = np.asarray([int(e) for e in np.asarray(model.entity_ids)[real]], np.int64)
    entity_user[real] = np.searchsorted(user_ids, ids)
    assert np.array_equal(user_ids[entity_user[real]], ids), "a model entity the data does not hold"
    e, s = np.nonzero(idx >= 0)
    keys = entity_user[e] * block.dim + idx[e, s].astype(np.int64)
    order = np.argsort(keys)
    table = np.zeros(len(block.pairs))
    same = len(keys) == len(block.pairs) and np.array_equal(keys[order], block.pairs)
    if same:
        table = val[e, s][order].astype(np.float64)
    return {"table": table, "same_support": bool(same),
            "outside_nonzero": int(np.count_nonzero(val[idx < 0]))}


def sweep_models(job) -> list:
    """One more fit, after the window, that keeps every sweep's model on the
    host through the program's own ``checkpoint_fn`` hook: [(fixed-effect
    coefficients f[d], per-user coefficients f[E, S])] by sweep. A fit hands
    back its BEST model by the validation metric, which may be an earlier
    sweep's; the checks below need to know which, and what came before it."""
    import jax

    fe, re = job.config["fixed_effect"]["name"], job.config["random_effect"]["name"]
    kept = []

    def keep(_reg_weights, _iteration, model):
        kept.append(tuple(np.asarray(a) for a in jax.device_get(
            (fitjob.coefficients(model[fe]), fitjob.coefficients(model[re]))
        )))

    job.estimator.fit(None, validation=job.validation_raw, datasets=job.datasets, checkpoint_fn=keep)
    return kept


def _rows_subset(rows, take: np.ndarray):
    from . import data_glmix_sparse as gen_user

    return gen_user.Rows(cols=rows.cols[take], user_cols=rows.user_cols[take], user=rows.user[take],
                         labels=rows.labels[take], margin=rows.margin[take])


def sample_parity(job) -> Dict[str, object]:
    """(a). Returns the observed errors and ``ok``."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = job.config, job.traffic
    fe, re = cfg["fixed_effect"], cfg["random_effect"]
    d, n = fe["d"], len(job.host.labels)
    out: Dict[str, object] = {}

    # -- the fixed effect's passes under non-zero offsets ----------------------
    n_k = min(SAMPLE_ROWS, n)
    cols, labels = job.host.cols[:n_k], job.host.labels[:n_k]
    fixed_only = dict(traffic, coordinates=[fe["name"]], reg_weights={fe["name"]: [PARITY_L2]})
    _, datasets = sparsejob.assemble(
        cfg, fixed_only, sparsejob.raw_dataset(d, cols, labels, job.mirror), validate=False
    )
    batch = datasets[fe["name"]].batch
    out["sample_layout"] = batch.features.layout
    offsets = jnp.asarray(
        OFFSET_STD * np.random.default_rng(2).standard_normal(n_k, dtype=np.float32)
    )
    t = time.perf_counter()
    out.update(kernel_parity(job, batch, correct_sparse.device_triplets(cols, job.mirror), offsets))
    out["kernel_parity_s"] = time.perf_counter() - t
    ok = out["kernel_err"] <= KERNEL_TOL and out["sample_layout"] == job.features.layout
    del datasets, batch

    # -- the whole coordinate descent on the user sample ------------------------
    users = sample_users(job.host.user, re["active_cap"])
    take = np.flatnonzero(np.isin(job.host.user, users))
    rows = _rows_subset(job.host, take)
    n_s = len(take)
    lam = traffic["reg_weights"][fe["name"]][0] * n_s / n  # the loss is a SUM over rows
    reg = {fe["name"]: [lam], re["name"]: traffic["reg_weights"][re["name"]]}
    raw = job_mod.raw_dataset(cfg, rows, job.mirror, job.user_mirror)
    est, datasets = job_mod.assemble(cfg, traffic, raw, reg_weights=reg, validate=False)
    t = time.perf_counter()
    result, = fitjob.run_fit(est, datasets, None, traffic["update_sequence"])
    out["sample_fit_s"] = time.perf_counter() - t
    w_sys = np.asarray(jax.device_get(fitjob.coefficients(result.model[fe["name"]])), np.float64)

    t = time.perf_counter()
    user_ids, user_index = np.unique(rows.user, return_inverse=True)
    block = reference_block(job, rows, user_index, len(user_ids), reg[re["name"]])
    rows64, cols64, vals64 = raw.shard_coo[job_mod.GLOBAL_SHARD]
    y64 = rows.labels.astype(np.float64)
    touched, w_ref, t_ref, info = ref.coordinate_descent(
        rows64, cols64, vals64, y64, lam, block, traffic["cd_sweeps"]
    )
    out["reference_s"] = time.perf_counter() - t  # host float64: part of setup_s
    sys_table = model_table(result.model[re["name"]], block, user_ids)
    t_sys = sys_table["table"]
    z_sys = ref.fixed_margins(touched, w_sys[touched], rows64, cols64, vals64, n_s)
    z_ref = ref.fixed_margins(touched, w_ref, rows64, cols64, vals64, n_s)
    f_sys = ref.model_objective(z_sys, w_sys[touched], lam, block, t_sys, y64)
    f_ref = ref.model_objective(z_ref, w_ref, lam, block, t_ref, y64)
    counts = np.bincount(user_index, minlength=len(user_ids))
    out.update(
        sample_rows=int(n_s), sample_users=int(len(user_ids)),
        sample_capped=int((counts > re["active_cap"]).sum()),
        sample_passive_rows=int((block.weights == 0).sum()),
        sample_buckets=job_mod.store_shape_of(datasets[re["name"]]).get("buckets"),
        sample_support=int(len(block.pairs)), same_support=sys_table["same_support"],
        outside_nonzero=sys_table["outside_nonzero"],
        fixed_coef_err=float(np.max(np.abs(w_sys[touched] - w_ref)) / max(np.max(np.abs(w_ref)), 1e-30)),
        fixed_untouched_nonzero=int(np.count_nonzero(w_sys) - np.count_nonzero(w_sys[touched])),
        user_coef_err=float(np.max(np.abs(t_sys - t_ref)) / max(np.max(np.abs(t_ref)), 1e-30)),
        objective_err=abs(f_sys - f_ref) / abs(f_ref),
        reference=info,
    )
    full_capped = bool(np.unique(job.host.user, return_counts=True)[1].max() > re["active_cap"])
    ok &= (
        out["same_support"] and out["outside_nonzero"] == 0 and out["fixed_untouched_nonzero"] == 0
        and out["fixed_coef_err"] <= FIXED_COEF_TOL and out["user_coef_err"] <= USER_COEF_TOL
        and out["objective_err"] <= OBJECTIVE_TOL and max(info["fixed_residuals"]) <= 1e-8
        # the cap must be part of what is compared wherever the cell has one
        and (out["sample_capped"] > 0 or not full_capped)
    )
    out["ok"] = bool(ok)
    return out


def full_size(job, results) -> Dict[str, object]:
    """(b): plain passes over ALL of the cell's rows at every sweep's model."""
    import jax
    import jax.numpy as jnp

    cfg = job.config
    fe, re = cfg["fixed_effect"], cfg["random_effect"]
    d, n = fe["d"], len(job.host.labels)
    result, = results
    lam = result.config[fe["name"]]
    rows, cols, vals = correct_sparse.device_triplets(job.host.cols, job.mirror)
    y = jnp.asarray(job.host.labels, jnp.float32)
    y64 = job.host.labels.astype(np.float64)
    ones = jnp.ones_like(y)
    w = jnp.asarray(fitjob.coefficients(result.model[fe["name"]]), jnp.float32)

    user_ids, user_index = np.unique(job.host.user, return_inverse=True)
    block = reference_block(job, job.host, user_index, len(user_ids), result.config[re["name"]])
    re_model = result.model[re["name"]]
    sys_table = model_table(re_model, block, user_ids)
    table = sys_table["table"]
    z_fixed = np.asarray(jax.device_get(ref_fixed.margins(w, rows, cols, vals, n_rows=n)), np.float64)

    def fixed_gradient(w_k, scores) -> float:
        offsets = jnp.asarray(scores, jnp.float32)
        _, g_zero = ref_fixed.value_grad(jnp.zeros(d, jnp.float32), rows, cols, vals, y, offsets, ones, lam)
        _, grad = ref_fixed.value_grad(w_k, rows, cols, vals, y, offsets, ones, lam)
        return float(jnp.linalg.norm(grad) / jnp.linalg.norm(g_zero))

    def user_gradient(table_k, scores) -> float:
        _, gu_zero = ref.user_value_grad(block, np.zeros_like(table_k), y64, scores)
        _, gu = ref.user_value_grad(block, table_k, y64, scores)
        return float(np.linalg.norm(gu) / np.linalg.norm(gu_zero))

    # every sweep's model, and which of them the fit handed back (its best by
    # validation AUC)
    by_sweep = sweep_models(job)
    w_host = np.asarray(jax.device_get(w))
    best = [k for k, (w_k, _) in enumerate(by_sweep) if np.array_equal(w_k, w_host)]
    fixed, users, after, outside = [], [], [], []
    z_before = np.zeros(n)  # the per-user scores sweep k's fixed-effect solve was handed
    while by_sweep:
        w_k, values_k = by_sweep.pop(0)  # (released sweep by sweep: 0.7 GB each)
        w_k = jnp.asarray(w_k)
        swept = model_table(re_model, block, user_ids, values=values_k)
        z_after = ref.user_scores(block, swept["table"])
        z_k = np.asarray(jax.device_get(ref_fixed.margins(w_k, rows, cols, vals, n_rows=n)), np.float64)
        fixed.append(fixed_gradient(w_k, z_before))
        users.append(user_gradient(swept["table"], z_k))
        after.append(fixed_gradient(w_k, z_after))
        outside.append(swept["outside_nonzero"] if swept["same_support"] else -1)
        z_before = z_after
    f_model = ref.model_objective(z_fixed, np.zeros(0), 0.0, block, table, y64) + 0.5 * lam * float(jnp.dot(w, w))
    seen = jnp.asarray(gen.columns_seen(job.host.cols, d))
    out: Dict[str, object] = {
        "fixed_gradients": fixed, "user_gradients": users, "fixed_gradients_after": after,
        "fixed_gradient": max(fixed), "user_gradient": max(users),
        "sweeps": len(fixed), "sweeps_outside_nonzero": outside,
        "objective_drop": f_model / (n * float(np.log(2.0))),
        "columns_seen": int(jnp.sum(seen)),
        "unseen_nonzero": int(jnp.sum((w != 0) & ~seen)),
        "users": int(len(user_ids)), "support": int(len(block.pairs)),
        "model_of_sweep": best[-1] + 1 if best else None,
        "same_support": sys_table["same_support"], "outside_nonzero": sys_table["outside_nonzero"],
    }
    out["ok"] = bool(
        out["sweeps"] == job.traffic["cd_sweeps"]
        and out["fixed_gradient"] <= GRADIENT_TOL and out["user_gradient"] <= GRADIENT_TOL
        and all(count == 0 for count in outside)
        and out["objective_drop"] < 1.0 and out["unseen_nonzero"] == 0
        and out["same_support"] and out["outside_nonzero"] == 0 and out["model_of_sweep"] is not None
    )
    return out
