"""The comparison that decides ``correct`` for the ``fit`` job.

(a) *Sample parity*, in set-up: the system's fit and the plain reference's
    solve agree on seeded samples of the cell's own rows at full widths.
(b) *Full size*, after the window: the plain objective at the final model is
    below its value at the zero model, and the plain gradient of the
    last-updated coordinate is small against its norm at zero.
(c) Fit-to-fit sameness and "no new program compiled inside the window" are
    counted by the harness (``run.py``) and folded in there.

Tolerances (all as max|a - b| / max|b| unless said otherwise). "CPU" numbers
are from this PR's rehearsals at tiny sizes (16,384 rows, 600 users, Pallas in
interpret mode); where no chip number is given, none was measured (PERF.md).

- ``KERNEL_TOL`` 2e-5: the program's objective (value, gradient, Hv through
  the fused kernels) against the reference at one seeded point of the 65,536-row
  sample. Both sides keep f32 (HIGHEST dots), so only the summation order
  differs: a few f32 ulps times sqrt(rows). chip_smoke.py saw 8.5e-7 between
  the fused kernels and the jnp path on the v5e (PR 21); a single bf16 pass
  would show ~2e-3. THIS is the check that a lower precision fails: bf16
  storage of X moves the reference's own coefficients by only 4.5e-4 at
  n = 65,536, lambda = 1 (PR 24, CPU, f32 arithmetic on bf16-rounded X), which
  is inside any solver's stopping slack.
- ``FIXED_COEF_TOL`` 2e-3, fixed effect on 65,536 rows over the cell's lambda
  grid: TRON stops on a relative gradient norm of 1e-6 and the reference on
  1e-7, so they differ by the stopping slack. CPU: 1.1e-4 to 6.6e-4.
- ``GLMIX_FIXED_COEF_TOL`` 1e-2, the fixed effect inside the whole CD on the
  users' sample: a few thousand rows for 1024 coefficients, so the Hessian is
  ~10x smaller than above and the same gradient slack moves the coefficients
  ~10x further. CPU: 7.8e-4 and 1.4e-3 (four virtual devices).
- ``ENTITY_COEF_TOL`` 2e-2: the per-user L-BFGS solves stop
  OBJECTIVE_NOT_IMPROVING for a third of the lanes at tol 1e-6 in f32 (PERF.md
  section 6, PR 21), and four chips differed from one by 2.0e-3 there. CPU:
  5.5e-3 to 6.4e-3.
- ``OBJECTIVE_TOL`` 1e-5 relative: both sides sit at the same minimum, where
  the value is flat to second order. CPU: 4.3e-6.
- ``STATIONARITY_TOL`` 5e-3: ||grad at the final model|| / ||grad at zero|| of
  the last-updated block, by the plain f32 pass. A block solved to a relative
  tolerance of 1e-6 on the solver's own gradient reads 1e-5..2e-3 here (CPU:
  1e-5..2e-4 fixed, 1.3e-3 per-user; PR 22's ledger line printed 9.1e-4 for the
  fixed effect on the v5e as fe_grad_ratio); a solve cut an iteration short
  reads 1e-2 or more.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from . import data as gen
from .jobs import fit as fitjob
from .reference import glmix as ref

KERNEL_TOL = 2e-5
FIXED_COEF_TOL = 2e-3
GLMIX_FIXED_COEF_TOL = 1e-2
ENTITY_COEF_TOL = 2e-2
OBJECTIVE_TOL = 1e-5
STATIONARITY_TOL = 5e-3

FIXED_SAMPLE_ROWS = 65_536
SAMPLE_USERS = 400
MIN_FUSED_ROWS = 4_096  # below this the program leaves the fused kernels


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def sample_users(quotas: np.ndarray, active_cap: int, n_users: int) -> np.ndarray:
    """Ranks of the sample's users: drawn by activity among the users whose
    rows all train (quota <= cap), by a FIXED generator: the same users, hence
    the same rows, programs and set-up work, for every seed of a run."""
    eligible = np.flatnonzero(quotas <= active_cap)
    p = quotas[eligible] / quotas[eligible].sum()
    rng = np.random.default_rng(0)
    return np.sort(rng.choice(eligible, size=min(n_users, len(eligible)), replace=False, p=p))


def entity_table(model, n_entities: int, d_re: int) -> np.ndarray:
    """A RandomEffectModel as a dense [users, d_re] host table (integer ids)."""
    import jax

    idx, val = jax.device_get((model.coef_indices, model.coef_values))
    ids = np.array(
        [-1 if str(e).startswith("__pad") else int(e) for e in model.entity_ids], np.int64
    )
    e_at, s_at = np.nonzero((idx >= 0) & (ids >= 0)[:, None])
    table = np.zeros((n_entities, d_re), np.float32)
    table[ids[e_at], idx[e_at, s_at]] = val[e_at, s_at]
    return table


def _subset(rows: gen.HostData, take: np.ndarray, relabel=None) -> gen.HostData:
    users = rows.user_of_row[take]
    if relabel is not None:
        users = relabel[users]
    return gen.HostData(
        user_of_row=users, user_features=rows.user_features[take], labels=rows.labels[take]
    )


def kernel_parity(cfg: dict, batch, x, y) -> float:
    """The program's objective (the fused kernels, as ``GLMProblem.run`` builds
    it) against the reference at a fixed seeded point: value, gradient and
    Hessian-vector product, worst max|a - b| / max|b|. The objective is a
    pytree ARGUMENT of the jitted call: closed over, its X would be folded
    into the program as a constant (PERF.md section 6, PR 21)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import GLMProblem, _fusion_mode
    from photon_ml_tpu.ops.glm import GLMObjective

    d = x.shape[1]
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    v = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    fused, fused_mesh = _fusion_mode(batch)
    problem = GLMProblem(task=cfg["task"], config=fitjob._opt_config(cfg["fixed_effect"], 1.0))
    objective = problem.objective(batch, fused=fused, fused_mesh=fused_mesh)
    value, grad = jax.jit(GLMObjective.value_and_grad)(objective, w)
    hv = jax.jit(GLMObjective.hessian_vector)(objective, w, v)
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    value_ref, grad_ref = ref.fixed_value_grad(w, x, y, zeros, ones, 1.0)
    hv_ref = ref.fixed_hessian_vector(w, v, x, y, zeros, ones, 1.0)
    got, want = jax.device_get(((value, grad, hv), (value_ref, grad_ref, hv_ref)))
    return max(rel_err(a, b) for a, b in zip(got, want))


def sample_parity(job, required_fusion: str = "compiled") -> Dict[str, object]:
    """(a): two system fits on samples against the reference. Returns the
    observed errors and ``ok``."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = job.config, job.traffic
    fe, re = cfg["fixed_effect"], cfg["random_effect"]
    x_full = job.datasets[fe["name"]].batch.features.dense
    out: Dict[str, object] = {}
    ok = True

    # -- the fixed effect alone, over the cell's lambda grid ------------------
    n_fixed = min(FIXED_SAMPLE_ROWS, x_full.shape[0])
    take = np.arange(n_fixed)
    x_s = jnp.asarray(jax.device_get(x_full[:n_fixed]))  # onto device 0, unsharded
    rows = _subset(job.host, take)
    fixed_only = dict(traffic, coordinates=[fe["name"]], cd_sweeps=1)
    est, datasets = fitjob.assemble(cfg, fixed_only, job.mesh, x_s, rows, validate=False)
    from photon_ml_tpu.game.problem import _fusion_mode

    out["sample_fusion"] = _fusion_mode(datasets[fe["name"]].batch)[0]
    results = fitjob.run_fit(est, datasets, None, [fe["name"]])
    y = jnp.asarray(rows.labels)
    zeros, ones = jnp.zeros(n_fixed, jnp.float32), jnp.ones(n_fixed, jnp.float32)
    out["kernel_err"] = kernel_parity(cfg, datasets[fe["name"]].batch, x_s, y)
    ok &= out["kernel_err"] <= KERNEL_TOL
    w_ref, errs = None, []
    for r in results:
        lam = r.config[fe["name"]]
        w_ref = ref.solve_fixed(x_s, y, zeros, ones, lam, w0=w_ref)
        errs.append(rel_err(jax.device_get(fitjob.coefficients(r.model[fe["name"]])), w_ref))
    out["fixed_coef_err"] = max(errs)
    ok &= out["fixed_coef_err"] <= FIXED_COEF_TOL and out["sample_fusion"] == required_fusion

    # -- the whole GLMix CD on all rows of a few hundred users ----------------
    if re["name"] in traffic["coordinates"]:
        users = sample_users(job.quotas, re["active_cap"], SAMPLE_USERS)
        relabel = np.full(len(job.quotas), -1, np.int64)
        relabel[users] = np.arange(len(users))
        take = np.flatnonzero(relabel[job.host.user_of_row] >= 0)
        if len(take) < MIN_FUSED_ROWS:
            raise ValueError(f"parity sample has {len(take)} rows, under {MIN_FUSED_ROWS}")
        rows = _subset(job.host, take, relabel)
        x_s = jnp.asarray(jax.device_get(jnp.take(x_full, jnp.asarray(take), axis=0)))
        est, datasets = fitjob.assemble(cfg, traffic, job.mesh, x_s, rows, validate=False)
        results = fitjob.run_fit(est, datasets, None, traffic["coordinates"])
        model = results[-1].model
        w_sys = jax.device_get(fitjob.coefficients(model[fe["name"]]))
        t_sys = entity_table(model[re["name"]], len(users), re["d_re"])
        ex, y = jnp.asarray(rows.user_features), jnp.asarray(rows.labels)
        entity = jnp.asarray(rows.user_of_row)
        l2_fixed = traffic["reg_weights"][fe["name"]][-1]
        w_ref, t_ref = ref.coordinate_descent(
            x_s, ex, y, entity, len(users), l2_fixed, re["reg_weight"], traffic["cd_sweeps"]
        )
        f_sys = float(ref.glmix_objective(
            jnp.asarray(w_sys), jnp.asarray(t_sys), x_s, ex, y, entity, l2_fixed, re["reg_weight"]))
        f_ref = float(ref.glmix_objective(w_ref, t_ref, x_s, ex, y, entity, l2_fixed, re["reg_weight"]))
        out["glmix_rows"] = int(len(take))
        out["glmix_fixed_coef_err"] = rel_err(w_sys, w_ref)
        out["glmix_entity_coef_err"] = rel_err(t_sys, t_ref)
        out["glmix_objective_err"] = abs(f_sys - f_ref) / abs(f_ref)
        ok &= (
            out["glmix_fixed_coef_err"] <= GLMIX_FIXED_COEF_TOL
            and out["glmix_entity_coef_err"] <= ENTITY_COEF_TOL
            and out["glmix_objective_err"] <= OBJECTIVE_TOL
        )
    out["ok"] = bool(ok)
    return out


def full_size(job, results) -> Dict[str, object]:
    """(b): one plain pass over the cell's own data at the final model(s)."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = job.config, job.traffic
    fe, re = cfg["fixed_effect"], cfg["random_effect"]
    batch = job.datasets[fe["name"]].batch
    x, y = batch.features.dense, batch.labels
    n = x.shape[0]
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    with_re = re["name"] in traffic["coordinates"]
    out: Dict[str, object] = {"stationarity": [], "objective_drop": []}
    ok = True
    if with_re:
        ex = jnp.asarray(job.host.user_features)
        entity = jnp.asarray(job.host.user_of_row)
        y0 = jnp.asarray(job.host.labels)
        trains_whole = jnp.asarray(job.quotas <= re["active_cap"])
    for r in results:
        w = fitjob.coefficients(r.model[fe["name"]])
        l2_fixed = r.config[fe["name"]]
        if not with_re:
            f_model, g_model = ref.fixed_value_grad(w, x, y, zeros, ones, l2_fixed)
            f_zero, g_zero = ref.fixed_value_grad(jnp.zeros_like(w), x, y, zeros, ones, l2_fixed)
            ratio = float(jnp.linalg.norm(g_model) / jnp.linalg.norm(g_zero))
        else:
            # the random effect was updated last: its block's gradient, given
            # the fixed effect's final scores, over the users whose rows all
            # train (capped users train on a reweighted reservoir)
            with ref.HIGHEST():
                score_fixed = jnp.asarray(jax.device_get(x @ w))
            table = jnp.asarray(entity_table(r.model[re["name"]], len(job.quotas), re["d_re"]))
            args = (ex, y0, entity, score_fixed, jnp.ones(n, jnp.float32), re["reg_weight"])
            v_m, g_m = ref.entity_value_grad(table, *args)
            _, g_z = ref.entity_value_grad(jnp.zeros_like(table), *args)
            pen = 0.5 * l2_fixed * jnp.dot(w, w)
            f_model = jnp.sum(v_m) + pen
            f_zero = jnp.sum(ref.loss(jnp.zeros_like(y0), y0))
            ratio = float(
                jnp.linalg.norm(g_m * trains_whole[:, None]) / jnp.linalg.norm(g_z * trains_whole[:, None])
            )
        out["stationarity"].append(ratio)
        out["objective_drop"].append(float(f_model) / float(f_zero))
        ok &= ratio <= STATIONARITY_TOL and float(f_model) < float(f_zero)
    out["ok"] = bool(ok)
    return out

