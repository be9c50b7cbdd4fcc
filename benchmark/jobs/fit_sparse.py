"""Job kind ``fit_sparse``: whole ``GameEstimator.fit`` calls from a zero model
for ONE generalized linear model over a SPARSE fixed-effect shard: a
``RawDataset`` of (rows, cols, vals) triplets handed to the program's own
dataset build under the configuration's ``layout`` (``auto``: what a user who
passes a wide sparse shard gets), so the cell measures whatever the program
chooses there, through ``ops/features.py``'s gather and scatter-add and
``ops/glm.py``'s ``jnp`` objective. No dense ``x`` exists at any point.

The protocol is job ``fit``'s, step for step (set-up, two warm-up fits, sample
parity, a window of whole fits each closed by one scalar fetch that depends on
the coefficients, full-size checks; the same result line, ``breakdown`` and
``notes``), and so are the pieces imported from it. What is this file's own: the
one-hot law of benchmark/data_sparse.py, the datasets built by
``GameEstimator.prepare_datasets`` from the raw triplets (the other jobs wrap a
ready device matrix), a fingerprint that holds the solve's value-and-gradient
passes beside its iterations, the checks of benchmark/correct_sparse.py, and
the readers of ``SPARSE_READERS``. ``jobs/fit.py``, ``fit_glm.py`` and their
comparisons read ``features.dense`` and are not this PR's to edit.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional

import numpy as np

from .. import data_sparse as gen
from . import fit as fitjob

GLOBAL_SHARD = fitjob.GLOBAL_SHARD

# The readers this job brings (benchmark/layer_metrics/<name>.py). BENCHMARK.json
# cannot list them yet (PERF.md, Open questions: the pin on ``per_layer``'s
# tail), so a traced run prints what they read under ``notes["sparse"]``.
SPARSE_READERS = (
    "fe_sparse_vg_roofline", "fe_sparse_pass_s", "fe_sparse_gather_s", "fe_sparse_scatter_s",
    "fe_sparse_slot_pad_share", "fe_line_search_evals", "fe_evals_per_iter",
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
            num_corrections=spec["num_corrections"],
        ),
        regularization=RegularizationContext(spec["regularization"]),
        reg_weight=reg_weight,
    )


@dataclasses.dataclass
class SparseFitJob(fitjob.FitJob):
    """``FitJob`` (its ``fit`` as it stands): ``host`` is the generator's
    ``Rows`` (column indices, labels), ``mirror`` the seed's signs f32[d],
    ``quotas`` None."""

    law: Optional[gen.Law] = None
    # a traced run's device operations with their whole HLO lines, on the
    # perf_counter clock (benchmark/sparse_ops.py: ``trace.py`` keeps names only)
    device_ops: Optional[list] = None

    @property
    def pass_shape(self) -> dict:
        """What ``auto`` chose and what one pass touches, from shapes (not
        from ``FeatureMatrix.slots``: the job also runs over a program that
        has no such property, any commit before PR 34)."""
        f = self.features
        n = self.datasets[self.config["fixed_effect"]["name"]].n_rows
        if f.layout == "ell":
            slots, width = f.idx.shape[0] * f.idx.shape[1], int(f.idx.shape[1])
        elif f.layout == "coo":
            slots, width = int(f.coo_cols.shape[0]), None
        else:
            slots, width = n * f.dim, int(f.dim)
        return {"layout": f.layout, "dim": int(f.dim), "rows": int(n), "slots": int(slots), "width": width}

    @property
    def features(self):
        """The fixed effect's feature matrix, in the layout the program chose."""
        return self.datasets[self.config["fixed_effect"]["name"]].batch.features

    def outcome(self, results) -> fitjob.FitOutcome:
        """``FitJob.outcome`` plus, in the fingerprint, the value-and-gradient
        passes of each solve (None on a program whose plain L-BFGS reports
        none: any commit before PR 34)."""
        import jax

        base = super().outcome(results)
        name = self.config["fixed_effect"]["name"]
        evals = jax.device_get(
            [getattr(r.trackers[name].result, "line_search_evals", None) for r in results]
        )
        evals = tuple(None if e is None else int(np.sum(e)) for e in evals)
        return dataclasses.replace(base, fingerprint=base.fingerprint + (evals,))


def raw_dataset(d: int, cols: np.ndarray, labels: np.ndarray, signs: np.ndarray):
    """The rows as the ``RawDataset`` ``cli train`` would hand the estimator:
    one sparse shard of int64 / float64 triplets."""
    from photon_ml_tpu.io.data import RawDataset

    n = len(labels)
    return RawDataset(
        n_rows=n, labels=labels.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        shard_coo={GLOBAL_SHARD: gen.triplets(cols, signs)}, shard_dims={GLOBAL_SHARD: d}, id_tags={},
    )


def assemble(config: dict, traffic: dict, raw, reg_weights=None, validate=True):
    """(estimator, datasets) for ``traffic`` on the raw rows given, the
    datasets by the program's own build (``prepare_datasets`` ->
    ``build_fixed_effect_dataset`` -> ``RawDataset.to_batch`` under the
    configuration's layout); ``reg_weights`` replaces the mix's grid (the
    parity sample scales it by its share of the rows)."""
    import jax.numpy as jnp

    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator

    fe = config["fixed_effect"]
    grid = tuple(traffic["reg_weights"][fe["name"]] if reg_weights is None else reg_weights)
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=[
            CoordinateConfig(
                name=fe["name"], feature_shard=GLOBAL_SHARD, config=_opt_config(fe, grid[0]),
                reg_weights=grid, layout=fe["layout"],
            )
        ],
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]] if validate else (),
        mesh=None,
        validation_frequency=traffic["validation"]["frequency"],
        dtype=getattr(jnp, config["dtype"]),
    )
    return estimator, estimator.prepare_datasets(raw)


def build(config: dict, traffic: dict, chips: int, seed: int) -> SparseFitJob:
    """A cell's set-up up to the first fit."""
    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, scale = config["fixed_effect"], config["scale"]
    n, n_val, d = scale["rows"], scale["validation_rows"], fe["d"]
    if fe["intercept_column"] != d - 1:
        raise ValueError("the intercept must be the last fixed-effect column")
    if fitjob.make_mesh(config, chips) is not None:
        raise ValueError("job fit_sparse runs on one chip")
    data_seed = scale["data_seed"]
    law = gen.draw_law(data_seed, scale["fields"], n, scale["zipf_exponent"])
    if law.dim != d or len(law.cardinalities) + 1 != fe["slots_per_row"]:
        raise ValueError("the fields do not add up to the configuration's d and slots a row")
    cols = gen.draw_columns(data_seed, law)
    gen.set_intercept(law, cols, scale["click_rate"])
    margin = gen.margins(law, cols)
    rows = gen.Rows(cols=cols, labels=gen.draw_labels(data_seed, margin), margin=margin)
    val = gen.draw_rows(data_seed, law, n_sample=n_val, stream=1)
    signs = gen.draw_signs(seed, d)
    raw = raw_dataset(d, rows.cols, rows.labels, signs)
    validation = raw_dataset(d, val.cols, val.labels, signs)
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, raw)
    spans["dataset_build"] = time.perf_counter() - t_build
    return SparseFitJob(
        config=config, traffic=traffic, mesh=None, estimator=estimator, datasets=datasets,
        validation_raw=validation, host=rows, mirror=signs, quotas=None, setup_spans=spans, law=law,
    )


def solver_programs() -> int:
    """Compiled L-BFGS solvers held by the program's jit cache."""
    from photon_ml_tpu.optimize import lbfgs

    return lbfgs._solve._cache_size()


def traced_window(job: SparseFitJob, check, seconds: float, listener):
    """``jobs/fit.py`` ``_traced_window`` as it stands, with the trace file
    read a second time while it exists: the operations' whole lines go to
    ``job.device_ops``, moved onto the clock the first reading was moved to."""
    from .. import sparse_ops, trace as trace_mod

    kept = {}
    load = trace_mod.load

    def load_and_keep(path, *args, **kwargs):
        kept["ops"] = sparse_ops.load(path)
        kept["raw"] = load(path, *args, **kwargs)
        return kept["raw"]

    trace_mod.load = load_and_keep
    try:
        out = fitjob._traced_window(job, check, seconds, listener)
    finally:
        trace_mod.load = load
    device_trace = out[3]
    if device_trace is not None and kept.get("ops") and kept["raw"].chips:
        first = lambda t: next(iter(t.chips.values()))[0][1]  # noqa: E731
        job.device_ops = sparse_ops.shifted(kept["ops"], first(device_trace) - first(kept["raw"]))
    return out


# -- the run protocol: jobs/fit.py ``run``, with this job's build and checks ---


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float) -> str:
    """Set-up, window, correctness; returns the result line."""
    import logging

    import jax

    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct_sparse, observe, trace as trace_mod
    from ..compile_listener import CompileListener
    from ..run import HERE, load_json, load_reader, report_metrics, result_line
    from ..window import run_window

    # -- set-up --------------------------------------------------------------------
    listener = CompileListener().install()
    enable_persistent_compilation_cache()
    logging.getLogger("photon_ml_tpu").setLevel(logging.WARNING)  # no logging in a fit
    job = build(cell.config, cell.traffic, cell.chips, seed)

    programs = solver_programs()
    t = time.perf_counter()
    base = job.outcome(job.fit())  # compiles, or loads from the cache
    warm1 = time.perf_counter() - t
    programs = solver_programs() - programs
    listener.phase = "warm"
    t = time.perf_counter()
    second = job.outcome(job.fit())  # must find every program in memory
    warm2 = time.perf_counter() - t
    listener.phase = "setup"
    iters, metrics, evals = base.fingerprint
    notes = {
        "warmup_fit_s": [warm1, warm2],
        "warmup_incomplete": listener.compiles("warm") > 0,
        "solver_programs_first_fit": programs,
        "shape": job.pass_shape,
        "fingerprint": {"iterations": list(iters), "line_search_evals": list(evals),
                        "validation": [dict(m) for m in metrics]},
    }
    parity = correct_sparse.sample_parity(job)
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
        window, collector, counters, device_trace = traced_window(job, check, seconds, listener)
    listener.phase = "after"

    # -- after -----------------------------------------------------------------------
    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[: cell.chips]
    )
    device = dict(device, memory_peak_bytes=memory_peak)
    full = (
        correct_sparse.full_size(job, state["last"]) if state["last"] is not None else {"ok": False}
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
    sparse = {
        name: load_reader(name).read(observations) for name in SPARSE_READERS if name not in listed
    }
    notes["sparse"] = {name: value for name, value in sparse.items() if value is not None}
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
            "device_ops": trace_mod.top_ops(observations.trace, span_window, k=16),
            "idle_gaps": trace_mod.idle_gaps_by_span(observations.trace, span_window, host_spans),
        }
    return result_line(is_correct, window.attempted, window.failed, metrics, device,
                       breakdown=breakdown, notes=notes)
