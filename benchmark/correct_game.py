"""The comparison that decides ``correct`` for the ``fit_game`` job: a GAME
model with one fixed effect and any number of random effects, against
``benchmark/reference/game.py``.

(a) *Sample parity*, in set-up, on the cell's own rows at full widths:
    the fused kernels at a fixed point, the fixed effect alone against exact
    Newton (both as in ``correct.py``, whose functions and limits are used as
    they stand), and the WHOLE coordinate descent over the update sequence on
    the crossed sample below: every block's coefficients and the whole model's
    objective.
(b) *Full size*, after the window: the plain objective of the whole model at
    the final coefficients is below its value at zero, and the plain gradient
    of the last-updated block, over its ACTIVE rows at their weights, is small
    against its norm at zero.
(c) Fit-to-fit sameness and "no new program compiled inside the window" are
    counted by the job (``jobs/fit_game.py``) and folded in there.

The crossed sample: an entity of rank r (0 = most active) of an effect is in
the sample when r = offset (mod stride) (``SAMPLE_STRIDES``, by the effect's
scale key: seed-free, so every seed builds the same rows, shapes and programs);
a row is in it when ALL its entities are. At the cell's size that is about
48,000 rows of 6,553 users and 1,366 items, of which about 19 users and 3 items
are over their caps INSIDE the sample, so the program's active / passive split
and its count / cap weights are part of what is compared; ``sample_parity``
fails if a capped effect has no capped entity there. The program's reservoir
priority (a splitmix64 mix of the row index, seed 0) is handed to the reference
as data: ``row_priority`` is a copy, pinned to the program's by a test.

Tolerances (all as max|a - b| / max|b| unless said otherwise). "CPU" numbers
are from this PR's rehearsals (PR 28; 32,768 rows, 1,200 users, 96 items, d
128 / 8, Pallas in interpret mode); chip numbers are in PERF.md section 6.

- ``correct.KERNEL_TOL`` 2e-5 and ``correct.FIXED_COEF_TOL`` 2e-3: as there;
  the kernel check is the one a bf16 pass fails (it would read ~2e-3).
- ``GAME_FIXED_COEF_TOL`` 1e-2, the fixed effect inside the whole CD: the
  reason of ``correct.GLMIX_FIXED_COEF_TOL`` (few rows per coefficient, so the
  stopping slack of TRON moves the coefficients further) carries over to
  48,208 rows for 1024 coefficients. Chip: 8.5e-4; CPU: 1.7e-4 to 4.9e-4.
- ``ENTITY_COEF_TOL`` 2e-2, each random effect's table, relative to the
  table's largest coefficient: a third of the L-BFGS lanes stop
  OBJECTIVE_NOT_IMPROVING at tol 1e-6 in f32 (PERF.md section 6, PR 21); the
  second effect is solved against a residual that already carries the first
  one's slack, so it reads no lower than the first. Chip: 3.7e-3 per-user,
  4.5e-3 per-item; CPU: 5e-4 to 1.3e-3.
- ``OBJECTIVE_TOL`` 3e-4 relative, the whole model's objective (all rows at
  weight 1). System and reference follow the same sequence of block
  minimisers, but the model is NOT at a minimum of this objective: a capped
  entity was fitted to its reweighted active rows, and the blocks updated
  before the last one to residuals that have since moved. So the solvers' slack
  enters at FIRST order, not squared as in ``correct.OBJECTIVE_TOL``'s one-
  effect, uncapped sample. Chip: 6.3e-5 (the same for every seed: seeds mirror
  one data set); CPU: 3.5e-6 to 2.1e-5. What the limit is there to catch reads
  30 to 400 times above it (CPU, the reference itself computed wrongly, against
  the reference): a sweep short 9.6e-3; active rows left at weight 1 instead of
  count / cap 3.5e-2; no cap at all 1.2e-1 (and 9e-2 to 0.5 on the tables).
- ``STATIONARITY_TOL`` 5e-3: ||grad at the final model|| / ||grad at zero|| of
  the last-updated block's OWN objective (active rows, count / cap weights), by
  the plain f32 pass. A block solved to 1e-6 on the solver's own gradient reads
  1e-5..2e-3 here; a solve cut an iteration short, rows trained that should be
  passive, or weights left at 1 on a capped entity read 1e-2 or more.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from . import correct
from .jobs import fit as fitjob
from .jobs import fit_game
from .reference import game as ref_game
from .reference import glmix as ref

GAME_FIXED_COEF_TOL = 1e-2
ENTITY_COEF_TOL = 2e-2
OBJECTIVE_TOL = 3e-4
STATIONARITY_TOL = 5e-3

# effect's scale key -> (stride, offset) over its entities' ranks
SAMPLE_STRIDES = {"users": (8, 4), "items": (3, 0)}


def row_priority(n_rows: int, seed: int = 0) -> np.ndarray:
    """The program's reservoir priority of rows 0..n-1 (``game/data.py``
    ``_hash64``, copied: a splitmix64-style mix of the row index)."""
    x = np.arange(n_rows, dtype=np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def sample_entities(n_entities: int, scale_key: str) -> np.ndarray:
    """Ranks of one effect's sampled entities: seed-free."""
    stride, offset = SAMPLE_STRIDES.get(scale_key, (1, 0))
    return np.arange(offset, n_entities, stride)


def reference_blocks(effects, rows, reg_weights) -> list:
    """The reference's view of ``rows``: one Block per effect, its active
    weights by the published rule under the program's priority."""
    import jax.numpy as jnp

    n = len(rows.labels)
    priority = row_priority(n)
    blocks = []
    for e in effects:
        entity = rows.entity_of_row[e["name"]]
        n_entities = int(e["n_entities"])
        blocks.append(ref_game.Block(
            name=e["name"],
            features=jnp.asarray(rows.features[e["name"]]),
            entity=jnp.asarray(entity, jnp.int32),
            n_entities=n_entities,
            l2=float(reg_weights[e["name"]]),
            weights=jnp.asarray(ref_game.active_weights(entity, priority, e["active_cap"], n_entities)),
        ))
    return blocks


def model_tables(model, effects) -> Dict[str, np.ndarray]:
    return {
        e["name"]: correct.entity_table(model[e["name"]], int(e["n_entities"]), e["d_re"])
        for e in effects
    }


def sample_parity(job, required_fusion: str = "compiled") -> Dict[str, object]:
    """(a): the system's fits on samples against the reference. Returns the
    observed errors and ``ok``."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.problem import _fusion_mode

    cfg, traffic = job.config, job.traffic
    fe = cfg["fixed_effect"]
    x_full = job.datasets[fe["name"]].batch.features.dense
    out: Dict[str, object] = {}
    ok = True

    # -- the fixed effect alone, over the cell's lambda grid ------------------
    n_fixed = min(correct.FIXED_SAMPLE_ROWS, x_full.shape[0])
    x_s = jnp.asarray(jax.device_get(x_full[:n_fixed]))  # onto device 0, unsharded
    rows = job.host.subset(np.arange(n_fixed))
    fixed_only = dict(traffic, coordinates=[fe["name"]], update_sequence=[fe["name"]], cd_sweeps=1)
    est, datasets = fit_game.assemble(cfg, fixed_only, job.mesh, x_s, rows, validate=False)
    out["sample_fusion"] = _fusion_mode(datasets[fe["name"]].batch)[0]
    results = fitjob.run_fit(est, datasets, None, [fe["name"]])
    y = jnp.asarray(rows.labels)
    zeros, ones = jnp.zeros(n_fixed, jnp.float32), jnp.ones(n_fixed, jnp.float32)
    out["kernel_err"] = correct.kernel_parity(cfg, datasets[fe["name"]].batch, x_s, y)
    ok &= out["kernel_err"] <= correct.KERNEL_TOL
    w_ref, errs = None, []
    for r in results:
        w_ref = ref.solve_fixed(x_s, y, zeros, ones, r.config[fe["name"]], w0=w_ref)
        errs.append(correct.rel_err(jax.device_get(fitjob.coefficients(r.model[fe["name"]])), w_ref))
    out["fixed_coef_err"] = max(errs)
    ok &= out["fixed_coef_err"] <= correct.FIXED_COEF_TOL and out["sample_fusion"] == required_fusion

    # -- the whole CD over the update sequence on the crossed sample ----------
    effects = [e for e in job.effects if e["name"] in traffic["coordinates"]]
    if effects:
        in_sample = np.ones(len(job.host.labels), bool)
        relabel, sampled = {}, []
        for e in effects:
            ranks = sample_entities(int(e["n_entities"]), e["entities"])
            relabel[e["name"]] = np.full(int(e["n_entities"]), -1, np.int64)
            relabel[e["name"]][ranks] = np.arange(len(ranks))
            in_sample &= relabel[e["name"]][job.host.entity_of_row[e["name"]]] >= 0
            sampled.append(dict(e, n_entities=len(ranks)))
        take = np.flatnonzero(in_sample)
        if len(take) < correct.MIN_FUSED_ROWS:
            raise ValueError(f"parity sample has {len(take)} rows, under {correct.MIN_FUSED_ROWS}")
        rows = job.host.subset(take, relabel)
        x_s = jnp.asarray(jax.device_get(jnp.take(x_full, jnp.asarray(take), axis=0)))
        est, datasets = fit_game.assemble(cfg, traffic, job.mesh, x_s, rows, validate=False)
        results = fitjob.run_fit(est, datasets, None, traffic["update_sequence"])
        model = results[-1].model
        w_sys = jax.device_get(fitjob.coefficients(model[fe["name"]]))
        t_sys = model_tables(model, sampled)

        reg = traffic["reg_weights"]
        l2_fixed = reg[fe["name"]][-1]
        blocks = reference_blocks(sampled, rows, reg)
        y = jnp.asarray(rows.labels)
        sequence = [ref_game.FIXED if name == fe["name"] else name for name in traffic["update_sequence"]]
        w_ref, t_ref = ref_game.coordinate_descent(
            x_s, y, l2_fixed, blocks, traffic["cd_sweeps"], sequence
        )
        f_sys = float(ref_game.model_objective(
            jnp.asarray(w_sys), {k: jnp.asarray(v) for k, v in t_sys.items()}, x_s, y, l2_fixed, blocks))
        f_ref = float(ref_game.model_objective(w_ref, t_ref, x_s, y, l2_fixed, blocks))
        out["game_rows"] = int(len(take))
        out["game_fixed_coef_err"] = correct.rel_err(w_sys, w_ref)
        out["game_objective_err"] = abs(f_sys - f_ref) / abs(f_ref)
        ok &= (
            out["game_fixed_coef_err"] <= GAME_FIXED_COEF_TOL
            and out["game_objective_err"] <= OBJECTIVE_TOL
        )
        for e, b in zip(sampled, blocks):
            counts = np.bincount(rows.entity_of_row[e["name"]], minlength=e["n_entities"])
            capped = int((counts > e["active_cap"]).sum())
            err = correct.rel_err(t_sys[e["name"]], t_ref[e["name"]])
            out[e["name"]] = {
                "entities": e["n_entities"], "capped": capped,
                "passive_rows": int((np.asarray(b.weights) == 0).sum()), "coef_err": err,
            }
            # the cap must be part of what is compared wherever the cell has one
            full_capped = bool((job.quotas[e["name"]] > e["active_cap"]).any())
            ok &= err <= ENTITY_COEF_TOL and (capped > 0 or not full_capped)
    out["ok"] = bool(ok)
    return out


def full_size(job, results) -> Dict[str, object]:
    """(b): one plain pass over the cell's own data at the final model(s)."""
    import jax.numpy as jnp

    cfg, traffic = job.config, job.traffic
    fe = cfg["fixed_effect"]
    batch = job.datasets[fe["name"]].batch
    x, y = batch.features.dense, batch.labels
    effects = [e for e in job.effects if e["name"] in traffic["coordinates"]]
    blocks = reference_blocks(effects, job.host, traffic["reg_weights"])
    by_name = {b.name: b for b in blocks}
    last = traffic["update_sequence"][-1]
    ones = jnp.ones_like(y)
    out: Dict[str, object] = {"stationarity": [], "objective_drop": [], "last_updated": last}
    ok = True
    for r in results:
        w = fitjob.coefficients(r.model[fe["name"]])
        l2_fixed = r.config[fe["name"]]
        tables = {k: jnp.asarray(v) for k, v in model_tables(r.model, effects).items()}
        f_model = ref_game.model_objective(w, tables, x, y, l2_fixed, blocks)
        f_zero = jnp.sum(ref.loss(jnp.zeros_like(y), y))
        with ref.HIGHEST():
            scores = {fe["name"]: x @ w}
        scores.update({b.name: b.scores(tables[b.name]) for b in blocks})
        others = sum((s for k, s in scores.items() if k != last), jnp.zeros_like(y))
        if last == fe["name"]:
            _, g_m = ref.fixed_value_grad(w, x, y, others, ones, l2_fixed)
            _, g_z = ref.fixed_value_grad(jnp.zeros_like(w), x, y, others, ones, l2_fixed)
        else:
            _, g_m = ref_game.block_value_grad(by_name[last], tables[last], y, others)
            _, g_z = ref_game.block_value_grad(by_name[last], jnp.zeros_like(tables[last]), y, others)
        ratio = float(jnp.linalg.norm(g_m) / jnp.linalg.norm(g_z))
        out["stationarity"].append(ratio)
        out["objective_drop"].append(float(f_model) / float(f_zero))
        ok &= ratio <= STATIONARITY_TOL and float(f_model) < float(f_zero)
    out["ok"] = bool(ok)
    return out
