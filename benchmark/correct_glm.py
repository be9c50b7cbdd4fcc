"""The comparison that decides ``correct`` for the ``fit_glm`` job, on the
pattern of benchmark/correct.py.

(a) *Sample parity*, in set-up, on the cell's first 65,536 rows under the
    cell's own factors and shifts: the program's objective against the plain
    reference at a fixed non-zero point, and the program's whole lambda path
    (the cell's weights scaled by the sample's share of the rows) against the
    independent float64 solver.
(b) *Full size*, after the window, by the plain reference over ALL rows at each
    lambda's final model: the KKT residual of the elastic-net objective, the
    objective against its value at zero, the support growing as lambda falls.
(c) Fit-to-fit sameness (iteration counts, support sizes, objective
    evaluations and validation losses bit for bit), no new program inside the
    window, and ONE compiled solver for the five weights are counted by the
    harness (``jobs/fit_glm.py``) and folded in there.

Tolerances. Every limit of this file is set between two readings (PERF.md
section 6, PR 32, gives both): what the change reads on the chip, the same on
every seed because a seed only mirrors the data, and what a pass in bfloat16
reads.

- ``KERNEL_TOL`` 2e-5, max|a - b| / max|b| over value, gradient and Hv: both
  sides keep f32 with HIGHEST dots, so only the summation order differs. The
  v5e reads 1.2e-6..1.5e-6 (2.4e-6 with the Hv kernel under TRON). THIS is the
  check a lower precision fails: ``kernel_err_bf16`` (the reference fed X
  rounded to bfloat16, against itself in f32) is printed beside it in every
  run and reads 5.9e-4..6.4e-4 there, thirty times the limit.
- ``COEF_TOL`` 4e-3 of ||w||_inf, the path's coefficients against the
  independent solver's at every lambda: the program stops when one iteration
  gains under 1e-6 of the loss at zero, the reference at a KKT residual of
  1e-9, so they differ by the stopping slack. The v5e reads 1.4e-4..1.41e-3
  over the five weights (the largest at the first). ISSUE 32 asked for 2e-3:
  1.4 times the reading is no room for the next change of a summation order,
  which moves the point a solve stops at.
- ``SUPPORT_TOL`` = ``COEF_TOL``: the supports are compared but for
  coefficients under that size on either side (a coefficient that small is
  one the two stopping rules may or may not have let in yet). Reads 0.
- ``OBJECTIVE_TOL`` 1e-5 relative, the elastic-net objective of the program's
  point against the reference's minimum (float64, same sample): flat to
  second order at the minimum, so it reads the coefficients' error squared:
  1.2e-8..4.1e-7 on the v5e.
- ``KKT_TOL`` 5e-3 of ||g(0)||_inf at full size, by the plain f32 pass: what
  the 1e-6 stopping rule leaves reads 9.9e-5..1.28e-3 on the v5e; a solve cut
  an iteration short, or a wrong factor or shift, reads 1e-2 or more.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .jobs import fit as fitjob
from .jobs import fit_glm as glmjob
from .reference import glm_enet as ref

KERNEL_TOL = 2e-5
COEF_TOL = 4e-3
SUPPORT_TOL = COEF_TOL
OBJECTIVE_TOL = 1e-5
KKT_TOL = 5e-3

SAMPLE_ROWS = 65_536


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def to_transformed(job, model) -> np.ndarray:
    """A fitted model's coefficients (original space) in the standardised
    space the solver and the references work in, float64 on the host."""
    import jax

    w = np.asarray(jax.device_get(fitjob.coefficients(model)), np.float64)
    factors, shifts = _norm64(job)
    w = w.copy()
    w[job.normalization.intercept_index] += w @ shifts
    return w / factors


def _norm64(job):
    import jax

    f, s = jax.device_get((job.normalization.factors, job.normalization.shifts))
    return np.asarray(f, np.float64), np.asarray(s, np.float64)


def kernel_parity(job, batch, x, y) -> Dict[str, float]:
    """The program's objective (the fused kernels with the cell's factors and
    shifts, as ``GLMProblem.run`` builds it) against the reference at a fixed
    seeded point: value, gradient, Hv, worst max|a - b| / max|b|; and the
    reference against itself fed X rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import GLMProblem, _fusion_mode
    from photon_ml_tpu.ops.glm import GLMObjective

    cfg = job.config
    d = x.shape[1]
    rng = np.random.default_rng(1)
    # small margins: the point is a test of the arithmetic, not of exp's range
    w = jnp.asarray(0.5 * rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    v = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
    fused, fused_mesh = _fusion_mode(batch)
    problem = GLMProblem(
        task=cfg["task"], config=glmjob._opt_config(dict(cfg["fixed_effect"], regularization="L2"), 1.0),
        normalization=job.normalization,
    )
    objective = problem.objective(batch, fused=fused, fused_mesh=fused_mesh)
    value, grad = jax.jit(GLMObjective.value_and_grad)(objective, w)
    hv = jax.jit(GLMObjective.hessian_vector)(objective, w, v)
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    norm = (job.normalization.factors, job.normalization.shifts)
    want = (*ref.value_grad(w, x, y, zeros, ones, 1.0, *norm),
            ref.hessian_vector(w, v, x, y, zeros, ones, 1.0, *norm))
    x16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    low = (*ref.value_grad(w, x16, y, zeros, ones, 1.0, *norm),
           ref.hessian_vector(w, v, x16, y, zeros, ones, 1.0, *norm))
    got, want, low = jax.device_get(((value, grad, hv), want, low))
    return {
        "kernel_err": max(rel_err(a, b) for a, b in zip(got, want)),
        "kernel_err_bf16": max(rel_err(a, b) for a, b in zip(low, want)),
    }


def sample_parity(job, required_fusion: str = "compiled") -> Dict[str, object]:
    """(a). Returns the observed errors and ``ok``."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import _fusion_mode

    cfg, traffic = job.config, job.traffic
    fe = cfg["fixed_effect"]
    name = fe["name"]
    x_full = job.datasets[name].batch.features.dense
    n = x_full.shape[0]
    n_s = min(SAMPLE_ROWS, n)
    x_s = x_full[:n_s]
    labels = job.host.labels[:n_s]
    batch = glmjob.labeled_batch(x_s, labels)
    out: Dict[str, object] = {"sample_fusion": _fusion_mode(batch)[0]}
    out.update(kernel_parity(job, batch, x_s, batch.labels))
    ok = out["kernel_err"] <= KERNEL_TOL and out["sample_fusion"] == required_fusion

    # the cell's path at the sample's scale: the loss is a SUM over rows
    lambdas = [lam * n_s / n for lam in traffic["reg_weights"][name]]
    est, datasets = glmjob.assemble(cfg, traffic, batch, job.normalization, reg_weights=lambdas,
                                    validate=False)
    results = fitjob.run_fit(est, datasets, None, [name])
    alpha = fe.get("elastic_net_alpha", 1.0)
    t_ref = time.perf_counter()
    xt = ref.transformed(jax.device_get(x_s), *_norm64(job))
    y64 = labels.astype(np.float64)
    zeros, ones = np.zeros(n_s), np.ones(n_s)
    path = ref.solve_path(xt, y64, zeros, ones, lambdas, alpha)
    out["reference_path_s"] = time.perf_counter() - t_ref  # host float64: part of setup_s
    coef_err, objective_err, support_diff, ref_kkt = [], [], [], []
    for r, lam, (w_ref, res) in zip(results, lambdas, path):
        l1, l2 = alpha * lam, (1.0 - alpha) * lam
        w_sys = to_transformed(job, r.model[name])
        scale = max(float(np.max(np.abs(w_ref))), 1e-30)
        coef_err.append(float(np.max(np.abs(w_sys - w_ref))) / scale)
        differ = (w_sys != 0) != (w_ref != 0)
        support_diff.append(int(np.sum(differ & (np.maximum(np.abs(w_sys), np.abs(w_ref)) > SUPPORT_TOL * scale))))
        f_sys = ref._smooth64(w_sys, xt, y64, zeros, ones, l2)[0] + l1 * np.sum(np.abs(w_sys))
        f_ref = ref._smooth64(w_ref, xt, y64, zeros, ones, l2)[0] + l1 * np.sum(np.abs(w_ref))
        objective_err.append(abs(f_sys - f_ref) / abs(f_ref))
        ref_kkt.append(res)
    out.update(path_coef_err=coef_err, path_objective_err=objective_err,
               path_support_diff=support_diff, reference_kkt=ref_kkt)
    ok &= (
        max(coef_err) <= COEF_TOL and max(objective_err) <= OBJECTIVE_TOL
        and max(support_diff) == 0 and max(ref_kkt) <= 1e-8
    )
    out["ok"] = bool(ok)
    return out


def full_size(job, results, base) -> Dict[str, object]:
    """(b): plain passes over the cell's own data at each lambda's final
    model. ``base`` is the warm-up fit's outcome (its support sizes)."""
    import jax
    import jax.numpy as jnp

    fe = job.config["fixed_effect"]
    name = fe["name"]
    alpha = fe.get("elastic_net_alpha", 1.0)
    batch = job.datasets[name].batch
    x, y = batch.features.dense, batch.labels
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    norm = (job.normalization.factors, job.normalization.shifts)
    d = x.shape[1]
    f_zero, g_zero = ref.value_grad(jnp.zeros(d, x.dtype), x, y, zeros, ones, 0.0, *norm)
    g0_inf = float(jnp.max(jnp.abs(g_zero)))
    out: Dict[str, object] = {
        "kkt": [], "objective_drop": [], "nonzeros": [],
    }
    if alpha > 0:  # a path has a start only where there is an l1 term
        out["lambda_max_observed"] = ref.lambda_max(jax.device_get(g_zero), alpha, fe["intercept_column"])
    ok = True
    for r in results:
        lam = r.config[name]
        l1, l2 = alpha * lam, (1.0 - alpha) * lam
        w = jnp.asarray(to_transformed(job, r.model[name]), x.dtype)
        value, grad = ref.value_grad(w, x, y, zeros, ones, l2, *norm)
        kkt = float(jnp.max(ref.kkt_residual(w, grad, l1))) / g0_inf
        drop = float(ref.enet_objective(w, value, l1)) / float(f_zero)
        out["kkt"].append(kkt)
        out["objective_drop"].append(drop)
        out["nonzeros"].append(int(jnp.sum(w != 0)))
        ok &= kkt <= KKT_TOL and drop < 1.0
    # lambda falls along the path: the support may only grow (the solver's own
    # count, OWL-QN's: other solvers report none and hold no coefficient at zero)
    solver_nonzeros = [row[0] for row in base.fingerprint[2] if row[0] is not None]
    ok &= all(a <= b for a, b in zip(solver_nonzeros, solver_nonzeros[1:]))
    out["ok"] = bool(ok)
    return out
