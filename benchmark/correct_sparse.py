"""The comparison that decides ``correct`` for the ``fit_sparse`` job, on the
pattern of benchmark/correct.py and benchmark/correct_glm.py.

(a) *Sample parity*, in set-up, on the cell's first 65,536 rows, built into a
    batch by the program's own path (the same layout choice as the cell): the
    program's objective against the plain reference at a seeded DENSE point
    (a coefficient on every one of the d columns), and the program's whole
    solve on the sample (the cell's weight scaled by the sample's share of the
    rows) against the independent float64 optimum.
(b) *Full size*, after the window, by the plain reference over ALL rows at the
    final model: the gradient against its norm at zero, the objective against
    its value at zero, and the coefficient of every column no row holds, which
    must be exactly 0.
(c) Fit-to-fit sameness (iterations, value-and-gradient passes and validation
    AUC bit for bit), no new program inside the window, and ONE compiled solver
    are counted by the harness (``jobs/fit_sparse.py``) and folded in there.

How a pass is compared. A gradient entry is a sum of signed terms, one a row
that holds the column: from 1 term (most columns) to all 65,536 (the
intercept). An entry's error is measured against the sum of its terms'
MAGNITUDES, ``scale_j = sum_i |x_ij r_i| + l2 |w_j|``, the quantity rounding
errors of a sum are proportional to: max_j |a_j - b_j| / scale_j. (Against
max_j |b_j|, the intercept's entry, four orders of magnitude above a
one-row column's, would hide everything else.) The value compares relatively.
The point's ridge weight is ``PARITY_L2`` = 1e-3: at 1 the term
(l2 / 2) ||w||^2 over 54.7M columns is forty times the loss of 65,536 rows and
the value would compare nothing else.

Tolerances: every limit lies between two readings (PERF.md section 6, PR 34,
gives both): what the change reads on the chip and what a lower precision, or
a wrong model, reads.

- ``KERNEL_TOL`` 5e-5: both sides keep f32; a margin sums twelve terms, a
  column's gradient its rows' terms in whatever order the scatter takes them.
  The v5e reads 4.9e-6..6.5e-6 over eight seeds (the gradient's entries; Hv
  7.5e-7..1.4e-6, the value 0..9e-8, the intercept's 65,536-term entry
  7e-8..3.9e-6): the seeded point is not mirrored, so this reading does move
  with the seed, and the limit leaves it eight times of room (ISSUE 34 named
  2e-5, three times the largest reading: a max over 155,000 entries' rounding
  is steady, but a fresh seed's refusal costs the PR). THIS is the check a
  lower precision fails: ``kernel_err_bf16`` (the reference with its gathered
  coefficients rounded to bfloat16, against itself in f32) is printed beside
  it in every run and reads 5.9e-3..7.3e-3, a hundred times the limit.
- ``COEF_TOL`` 1e-2 of ||w||_inf, the sample solve's coefficients on the
  touched columns against the float64 optimum: the program stops when one
  iteration gains under 1e-6 of the loss at zero, the reference at a gradient
  of 1e-9, so they differ by the stopping slack. The v5e reads 1.75e-3 (every
  seed: the solve is mirrored; 1.5e-3..1.6e-3 at the 2,359,296-row share, 1.5e-3
  on the CPU); a ridge weight off by a tenth reads 5e-2.
- ``OBJECTIVE_TOL`` 1e-4, relative, the objective of the program's point
  against the reference's minimum (float64, same sample). One iteration at the
  stopping rule is worth 3.7e-6 of the minimum here, and the v5e reads 8.75e-6
  (1.9e-6 at the larger share): ISSUE 34's 1e-5 would have sat inside what one
  iteration more or fewer moves. A wrong ridge or a dropped field reads 1e-3 or
  more.
- ``GRADIENT_TOL`` 5e-3 of ||g(0)|| at full size, by the plain f32 pass over
  all rows: what the 1e-6 stopping rule leaves reads 4.2e-4 on the v5e (9.3e-4
  at the larger share); a solve cut short, a scatter that drops updates or a
  wrong ridge term reads 1e-2 or more.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from . import data_sparse as gen
from .jobs import fit as fitjob
from .jobs import fit_sparse as sparsejob
from .reference import glm_sparse as ref

KERNEL_TOL = 5e-5
COEF_TOL = 1e-2
OBJECTIVE_TOL = 1e-4
GRADIENT_TOL = 5e-3
PARITY_L2 = 1e-3

SAMPLE_ROWS = 65_536


def device_triplets(cols: np.ndarray, signs: np.ndarray):
    """The generator's ``triplets`` of the rows on the device, as the plain
    reference takes them: rows and cols i32, vals f32."""
    import jax.numpy as jnp

    rows, flat, vals = gen.triplets(cols, signs)
    return jnp.asarray(rows, jnp.int32), jnp.asarray(flat, jnp.int32), jnp.asarray(vals, jnp.float32)


def _scaled_err(got, want, scale) -> float:
    """max_j |got_j - want_j| / scale_j over the entries with a scale."""
    import jax.numpy as jnp

    return float(jnp.max(jnp.where(scale > 0, jnp.abs(got - want) / jnp.where(scale > 0, scale, 1.0), 0.0)))


def kernel_parity(job, batch, trip) -> Dict[str, float]:
    """The program's objective on ``batch`` (whatever layout the program built)
    against the reference on the same rows' triplets at a seeded dense point:
    value, gradient, Hv; and the reference against itself with its gathered
    coefficients rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import GLMProblem
    from photon_ml_tpu.ops.glm import GLMObjective

    cfg = job.config
    d, k = cfg["fixed_effect"]["d"], cfg["fixed_effect"]["slots_per_row"]
    rng = np.random.default_rng(1)
    # margins of standard deviation about 1: a test of the arithmetic at the
    # sizes the solve meets
    w = jnp.asarray(rng.standard_normal(d, dtype=np.float32) / np.float32(np.sqrt(k)))
    v = jnp.asarray(rng.standard_normal(d, dtype=np.float32) / np.float32(np.sqrt(k)))
    problem = GLMProblem(task=cfg["task"], config=sparsejob._opt_config(cfg["fixed_effect"], PARITY_L2))
    objective = problem.objective(batch)
    value, grad = jax.jit(GLMObjective.value_and_grad)(objective, w)
    hv = jax.jit(GLMObjective.hessian_vector)(objective, w, v)
    rows, cols, vals = trip
    y = batch.labels
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    want_value, want_grad = ref.value_grad(w, rows, cols, vals, y, zeros, ones, PARITY_L2)
    want_hv = ref.hessian_vector(w, v, rows, cols, vals, y, zeros, ones, PARITY_L2)
    low_value, low_grad = ref.value_grad(w, rows, cols, vals, y, zeros, ones, PARITY_L2,
                                         gather_dtype=jnp.bfloat16)
    # what each entry's rounding is proportional to: its terms' magnitudes
    z = ref.margins(w, rows, cols, vals, n_rows=len(y))
    p = jax.nn.sigmoid(z)
    absvals = jnp.abs(vals)
    g_scale = ref.rmatvec(jnp.abs(p - y), rows, cols, absvals, dim=d) + PARITY_L2 * jnp.abs(w)
    u_abs = ref.margins(jnp.abs(v), rows, cols, absvals, n_rows=len(y))
    h_scale = ref.rmatvec(p * (1.0 - p) * u_abs, rows, cols, absvals, dim=d) + PARITY_L2 * jnp.abs(v)
    errs = {
        "value": abs(float(value) - float(want_value)) / abs(float(want_value)),
        "gradient": _scaled_err(grad, want_grad, g_scale),
        "hv": _scaled_err(hv, want_hv, h_scale),
    }
    low = max(abs(float(low_value) - float(want_value)) / abs(float(want_value)),
              _scaled_err(low_grad, want_grad, g_scale))
    # the intercept's entry alone: 65,536 terms in the scatter's order
    last = float(jnp.abs(grad[-1] - want_grad[-1]) / g_scale[-1])
    return {"kernel_err": max(errs.values()), "kernel_errs": errs, "kernel_err_intercept": last,
            "kernel_err_bf16": low}


def sample_parity(job) -> Dict[str, object]:
    """(a). Returns the observed errors and ``ok``."""
    import jax

    cfg, traffic = job.config, job.traffic
    fe = cfg["fixed_effect"]
    name, d = fe["name"], fe["d"]
    n = len(job.host.labels)
    n_s = min(SAMPLE_ROWS, n)
    cols, labels = job.host.cols[:n_s], job.host.labels[:n_s]
    lam = traffic["reg_weights"][name][0] * n_s / n  # the loss is a SUM over rows
    raw = sparsejob.raw_dataset(d, cols, labels, job.mirror)
    est, datasets = sparsejob.assemble(cfg, traffic, raw, reg_weights=[lam], validate=False)
    batch = datasets[name].batch
    out: Dict[str, object] = {"sample_layout": batch.features.layout}
    trip = device_triplets(cols, job.mirror)
    t_kernel = time.perf_counter()
    out.update(kernel_parity(job, batch, trip))
    out["kernel_parity_s"] = time.perf_counter() - t_kernel
    del trip
    ok = out["kernel_err"] <= KERNEL_TOL and out["sample_layout"] == job.features.layout

    # the program's whole solve on the sample against the float64 optimum
    t_fit = time.perf_counter()
    result, = fitjob.run_fit(est, datasets, None, [name])
    out["sample_fit_s"] = time.perf_counter() - t_fit
    w_sys = np.asarray(jax.device_get(fitjob.coefficients(result.model[name])), np.float64)
    t_ref = time.perf_counter()
    rows64, cols64, vals64 = raw.shard_coo[sparsejob.GLOBAL_SHARD]
    y64 = labels.astype(np.float64)
    zeros, ones = np.zeros(n_s), np.ones(n_s)
    touched, w_ref, info = ref.solve(rows64, cols64, vals64, y64, zeros, ones, lam)
    out["reference_solve_s"] = time.perf_counter() - t_ref  # host float64: part of setup_s
    _, local = ref.compact(cols64)
    scale = max(float(np.max(np.abs(w_ref))), 1e-30)
    f_sys = ref.objective64(w_sys[touched], local, rows64, vals64, y64, zeros, ones, lam)
    out.update(
        sample_coef_err=float(np.max(np.abs(w_sys[touched] - w_ref))) / scale,
        sample_objective_err=abs(f_sys - info["value"]) / abs(info["value"]),
        sample_untouched_nonzero=int(np.count_nonzero(w_sys) - np.count_nonzero(w_sys[touched])),
        sample_iterations=int(np.sum(jax.device_get(result.trackers[name].result.iterations))),
        reference=info,
    )
    ok &= (
        out["sample_coef_err"] <= COEF_TOL and out["sample_objective_err"] <= OBJECTIVE_TOL
        and out["sample_untouched_nonzero"] == 0 and info["residual"] <= 1e-8
    )
    out["ok"] = bool(ok)
    return out


def full_size(job, results) -> Dict[str, object]:
    """(b): plain passes over ALL of the cell's rows at the final model."""
    import jax.numpy as jnp

    fe = job.config["fixed_effect"]
    name, d = fe["name"], fe["d"]
    result, = results
    lam = result.config[name]
    rows, cols, vals = device_triplets(job.host.cols, job.mirror)
    y = jnp.asarray(job.host.labels, jnp.float32)
    zeros, ones = jnp.zeros_like(y), jnp.ones_like(y)
    w = jnp.asarray(fitjob.coefficients(result.model[name]), jnp.float32)
    f_zero, g_zero = ref.value_grad(jnp.zeros(d, jnp.float32), rows, cols, vals, y, zeros, ones, lam)
    value, grad = ref.value_grad(w, rows, cols, vals, y, zeros, ones, lam)
    seen = jnp.asarray(gen.columns_seen(job.host.cols, d))
    out: Dict[str, object] = {
        "gradient": float(jnp.linalg.norm(grad) / jnp.linalg.norm(g_zero)),
        "objective_drop": float(value) / float(f_zero),
        "columns_seen": int(jnp.sum(seen)),
        "nonzeros": int(jnp.sum(w != 0)),
        "unseen_nonzero": int(jnp.sum((w != 0) & ~seen)),
    }
    out["ok"] = bool(
        out["gradient"] <= GRADIENT_TOL and out["objective_drop"] < 1.0 and out["unseen_nonzero"] == 0
    )
    return out
