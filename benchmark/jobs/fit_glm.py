"""Job kind ``fit_glm``: whole ``GameEstimator.fit`` calls from a zero model for
ONE generalized linear model (a fixed effect and nothing else) under a task,
a regularisation and a normalisation of the configuration's choosing.

The protocol is job ``fit``'s, step for step (set-up, two warm-up fits, sample
parity, a window of whole fits each closed by one scalar fetch that depends on
the coefficients, full-size checks; the same result line, ``breakdown`` and
``notes``), and so are the pieces imported from it. What is this file's own: a
configuration with NO ``random_effect``, count labels and columns with means
and scales of their own (benchmark/data_glm.py), the normalisation built by
the program's own path from statistics taken ON THE DEVICE
(``utils/stats.py`` -> ``ops/normalization.py``), the elastic-net split and
L-BFGS history of the configuration, a fingerprint that holds the support's
size at every lambda, and the checks of benchmark/correct_glm.py.
``jobs/fit.py``, ``correct.py`` and ``data.py`` are wired to Bernoulli labels
and one ``random_effect`` and are not this PR's to edit.

The statistics are asked for FIRST, straight after the matrix is drawn: a
program without the device path (any commit before PR 32) raises there, at
once, and never falls back to a host COO of 1.6G triplets.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

from .. import data as gen
from .. import data_glm as gen_glm
from . import fit as fitjob

GLOBAL_SHARD = fitjob.GLOBAL_SHARD

# The readers this job brings (benchmark/layer_metrics/<name>.py). BENCHMARK.json
# cannot list them yet (PERF.md, Open questions: the pin on ``per_layer``'s
# tail), so a traced run prints what they read under ``notes["glm_path"]``.
GLM_PATH_READERS = (
    "fe_line_search_evals", "fe_evals_per_iter", "fe_nonzeros_last", "fe_orthant_zeroed",
    "fe_normalization_s",
)


def _opt_config(spec: dict, reg_weight: float):
    from photon_ml_tpu.game.problem import GLMOptimizationConfig
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType

    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType[spec["optimizer"]],
            tolerance=spec["tolerance"],
            max_iterations=spec["max_iterations"],
            num_corrections=spec.get("num_corrections", 10),
        ),
        regularization=RegularizationContext(
            spec["regularization"], spec.get("elastic_net_alpha", 1.0)
        ),
        reg_weight=reg_weight,
    )


@dataclasses.dataclass
class GlmRows:
    labels: np.ndarray  # f32[n] counts


@dataclasses.dataclass
class GlmFitJob(fitjob.FitJob):
    """``FitJob`` (its ``fit`` as it stands): ``host`` is a ``GlmRows``,
    ``quotas`` None; the law and the normalisation built from the batch."""

    law: Optional[gen_glm.Law] = None
    normalization: Optional[object] = None

    def outcome(self, results) -> fitjob.FitOutcome:
        """``FitJob.outcome`` plus, in the fingerprint, what an OWL-QN solve
        adds: the support's size and the objective evaluations per lambda
        (None where the solver reports none, as TRON and plain L-BFGS do)."""
        import jax

        base = super().outcome(results)
        name = self.config["fixed_effect"]["name"]
        path = []
        for r in results:
            res = r.trackers[name].result
            path.append((res.nonzeros, res.line_search_evals, res.orthant_zeroed))
        path = tuple(
            tuple(None if v is None else int(v) for v in row) for row in jax.device_get(path)
        )
        return dataclasses.replace(base, fingerprint=base.fingerprint + (path,))


def validation_raw(d: int, x_val: np.ndarray, labels: np.ndarray):
    """The validation rows as the f64 COO ``GameEstimator._validation_context``
    reads (8,192 x d: small)."""
    from photon_ml_tpu.io.data import RawDataset

    n = len(labels)
    return RawDataset(
        n_rows=n, labels=labels.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        shard_coo={GLOBAL_SHARD: gen.dense_coo(x_val)}, shard_dims={GLOBAL_SHARD: d}, id_tags={},
    )


def labeled_batch(x, labels):
    import jax.numpy as jnp

    from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch

    n, d = x.shape
    return LabeledBatch(
        features=FeatureMatrix(dim=d, dense=x),
        labels=jnp.asarray(labels, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
    )


def standardization(config: dict, batch):
    """The NormalizationContext by the program's own path, what ``cli train
    --normalization`` runs, from statistics of the batch on the device."""
    from photon_ml_tpu.ops.normalization import build_normalization
    from photon_ml_tpu.utils.stats import compute_feature_statistics

    fe = config["fixed_effect"]
    stats = compute_feature_statistics(batch)
    return build_normalization(
        fe["normalization"], stats["mean"], stats["variance"], stats["max_magnitude"],
        intercept_index=fe["intercept_column"],
    )


def assemble(config: dict, traffic: dict, batch, normalization, reg_weights=None, validate=True):
    """(estimator, datasets) for ``traffic`` on the batch given, under the
    normalisation given; ``reg_weights`` replaces the mix's grid (the parity
    sample scales it by its share of the rows)."""
    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
    from photon_ml_tpu.game.data import FixedEffectDataset

    fe = config["fixed_effect"]
    n, d = batch.features.dense.shape
    datasets = {
        fe["name"]: FixedEffectDataset(
            coordinate_id=fe["name"], feature_shard=GLOBAL_SHARD, batch=batch,
            true_dim=d, true_n_rows=n,
        )
    }
    grid = tuple(traffic["reg_weights"][fe["name"]] if reg_weights is None else reg_weights)
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=[
            CoordinateConfig(
                name=fe["name"], feature_shard=GLOBAL_SHARD, config=_opt_config(fe, grid[0]),
                reg_weights=grid, normalization=normalization,
            )
        ],
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]] if validate else (),
        mesh=None,
        validation_frequency=traffic["validation"]["frequency"],
    )
    return estimator, datasets


def build(config: dict, traffic: dict, chips: int, seed: int) -> GlmFitJob:
    """A cell's set-up up to the first fit."""
    import jax

    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, scale = config["fixed_effect"], config["scale"]
    n, n_val, d = scale["rows"], scale["validation_rows"], fe["d"]
    if fe["intercept_column"] != d - 1:
        raise ValueError("the intercept must be the last fixed-effect column")
    if fitjob.make_mesh(config, chips) is not None:
        raise ValueError("job fit_glm runs on one chip")
    data_seed, chunk = scale["data_seed"], scale["generation_chunk_rows"]
    law = gen_glm.draw_law(data_seed, d, scale["support"], scale["margin_std"], scale["mean_count"])
    mirror = gen.draw_mirror(seed, d, 1)
    x, margin = gen_glm.device_features(data_seed, n, chunk, law, mirror.fixed)
    labels = gen_glm.draw_counts(data_seed, np.asarray(jax.device_get(margin)))
    del margin
    batch = labeled_batch(x, labels)
    norm = standardization(config, batch)  # FIRST: see the module's docstring

    x_val, margin_val = gen_glm.device_features(
        data_seed, n_val, min(chunk, n_val), law, mirror.fixed, stream=1
    )
    x_val_h, margin_val_h = jax.device_get((x_val, margin_val))
    del x_val, margin_val
    val = validation_raw(d, x_val_h, gen_glm.draw_counts(data_seed, margin_val_h, stream=1))
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, batch, norm)
    spans["dataset_build"] = time.perf_counter() - t_build
    return GlmFitJob(
        config=config, traffic=traffic, mesh=None, estimator=estimator, datasets=datasets,
        validation_raw=val, host=GlmRows(labels=labels), mirror=mirror, quotas=None,
        setup_spans=spans, law=law, normalization=norm,
    )


def solver_programs() -> int:
    """Compiled L-BFGS / OWL-QN solvers held by the program's jit cache."""
    from photon_ml_tpu.optimize import lbfgs

    return lbfgs._solve._cache_size()


def lambda_path(job: GlmFitJob, results, outcome: fitjob.FitOutcome) -> List[dict]:
    """One line a lambda for ``notes``: what the fingerprint holds, by name."""
    name = job.config["fixed_effect"]["name"]
    iters, metrics, path = outcome.fingerprint
    return [
        {"reg_weight": r.config[name], "iterations": it, "nonzeros": nz, "line_search_evals": ev,
         "orthant_zeroed": oz, **dict(m)}
        for r, it, m, (nz, ev, oz) in zip(results, iters, metrics, path)
    ]


# -- the run protocol: jobs/fit.py ``run``, with this job's build and checks ---


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float,
        required_fusion: str = "compiled") -> str:
    """Set-up, window, correctness; returns the result line. ``required_fusion``
    is what ``_fusion_mode`` must say (tests on the CPU pass "interpret")."""
    import logging

    import jax

    from photon_ml_tpu.game.problem import _fusion_mode
    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct_glm, observe, trace as trace_mod
    from ..compile_listener import CompileListener
    from ..run import HERE, NoResult, load_json, load_reader, report_metrics, result_line
    from ..window import run_window

    # -- set-up --------------------------------------------------------------------
    listener = CompileListener().install()
    enable_persistent_compilation_cache()
    logging.getLogger("photon_ml_tpu").setLevel(logging.WARNING)  # no logging in a fit
    job = build(cell.config, cell.traffic, cell.chips, seed)
    fe_name = cell.config["fixed_effect"]["name"]
    fusion = _fusion_mode(job.datasets[fe_name].batch)[0]
    if fusion != required_fusion:
        raise NoResult(f"_fusion_mode is {fusion!r}, not {required_fusion!r}: the cell would measure the jnp path")

    programs = solver_programs()
    t = time.perf_counter()
    first = job.fit()  # compiles, or loads from the cache
    base = job.outcome(first)
    warm1 = time.perf_counter() - t
    programs = solver_programs() - programs
    listener.phase = "warm"
    t = time.perf_counter()
    second = job.outcome(job.fit())  # must find every program in memory
    warm2 = time.perf_counter() - t
    listener.phase = "setup"
    notes = {
        "warmup_fit_s": [warm1, warm2],
        "warmup_incomplete": listener.compiles("warm") > 0,
        # ONE solver for the whole lambda path: the l1 weight is an operand
        "solver_programs_first_fit": programs,
        "lambda_path": lambda_path(job, first, base),
    }
    del first
    parity = correct_glm.sample_parity(job, required_fusion)
    notes["sample_parity"] = parity
    setup_s = time.perf_counter() - t_process_start

    # -- window ----------------------------------------------------------------------
    state = {"rejections": second.rejections, "same": second.fingerprint == base.fingerprint,
             "last": None}

    def check(results) -> bool:
        out = job.outcome(results)
        rejected = out.rejections - state["rejections"]
        state["rejections"] = out.rejections
        state["same"] &= out.fingerprint == base.fingerprint
        state["last"] = results
        return out.finite and rejected == 0

    listener.phase = "window"
    if not traced:
        window = run_window(job.fit, check, seconds)
    else:
        state["rejections"] = 0  # counted in the traced part's fresh registry
        window, collector, counters, device_trace = fitjob._traced_window(job, check, seconds, listener)
    listener.phase = "after"

    # -- after -----------------------------------------------------------------------
    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[: cell.chips]
    )
    device = dict(device, memory_peak_bytes=memory_peak)
    full = (
        correct_glm.full_size(job, state["last"], base) if state["last"] is not None else {"ok": False}
    )
    notes["full_size"] = full
    notes["window_compiles"] = listener.compiles("window")
    notes["window_retraces"] = listener.retraces("window")
    notes["fits_same_as_warmup"] = state["same"]
    notes["fit_walls_s"] = window.walls
    is_correct = (
        parity["ok"] and full["ok"] and state["same"] and window.failed == 0
        and listener.compiles("window") == 0 and not notes["warmup_incomplete"]
        and programs == 1 and len(window.walls) > 0
    )

    if not traced:
        values = {"fit_s": window.median_s, "setup_s": setup_s}
        metrics = report_metrics(cell.end_to_end, values)
        return result_line(is_correct, window.attempted, window.failed, metrics, device, notes=notes)

    complete = len(window.walls) == len(window.starts)  # spans of a failed fit mean nothing
    observations = observe.Observations(
        fit_windows=[(s, s + w) for s, w in zip(window.starts, window.walls)] if complete else [],
        spans=collector.spans, counters=counters, listener=listener,
        setup_spans=job.setup_spans, job=job,
        peak=load_json(os.path.join(HERE, "peaks.json"))[device["kind"]],
        chips=cell.chips, memory_peak_bytes=memory_peak, trace=device_trace,
    )
    values = {}
    for m in cell.per_layer:
        values[m["name"]] = load_reader(m["name"]).read(observations)
    metrics = report_metrics(cell.per_layer, values)
    listed = {m["name"] for m in cell.per_layer}
    glm_path = {
        name: load_reader(name).read(observations) for name in GLM_PATH_READERS if name not in listed
    }
    notes["glm_path"] = {name: value for name, value in glm_path.items() if value is not None}
    breakdown = None
    if observations.trace is not None and observations.trace.chips and observations.fit_windows:
        span_window = observations.traced_window
        device["busy_s"] = trace_mod.mean_busy_seconds(observations.trace, span_window)
        device["window_s"] = span_window[1] - span_window[0]
        host_spans = [
            (s.name + (":" + str(s.attrs["coordinate"]) if s.name == "cd.coordinate" else ""),
             s.start, s.end)
            for s in observations.spans if s.name in ("cd.coordinate", "cd.eval")
        ]
        breakdown = {
            "device_ops": trace_mod.top_ops(observations.trace, span_window),
            "idle_gaps": trace_mod.idle_gaps_by_span(observations.trace, span_window, host_spans),
        }
    return result_line(is_correct, window.attempted, window.failed, metrics, device,
                       breakdown=breakdown, notes=notes)
