"""Job kind ``fit``: whole ``GameEstimator.fit`` calls from a zero model.

One call of :func:`build` is a cell's set-up (the configuration's data
mirrored by the seed, datasets through the program's builders, the estimator);
:meth:`FitJob.fit` is the unit the window repeats: the same entry ``cli train`` reaches, closed by one scalar
``jax.device_get`` that depends on every coordinate's coefficients
(``bench.py`` ``bench_tpu``'s sync), under the program's own transfer guard.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

from .. import data as gen

USER_SHARD = "userShard"
GLOBAL_SHARD = "globalShard"


def _opt_config(spec: dict, reg_weight: float):
    from photon_ml_tpu.game.problem import GLMOptimizationConfig
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType

    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType[spec["optimizer"]],
            tolerance=spec["tolerance"],
            max_iterations=spec["max_iterations"],
        ),
        regularization=RegularizationContext(spec["regularization"]),
        reg_weight=reg_weight,
    )


@dataclasses.dataclass
class FitOutcome:
    """What one fit leaves for the checks between fits (host values only)."""

    finite: bool
    rejections: int
    fingerprint: tuple  # solver iteration counts and validation metrics


@dataclasses.dataclass
class FitJob:
    config: dict
    traffic: dict
    mesh: Optional[object]
    estimator: object
    datasets: Dict[str, object]
    validation_raw: object
    host: gen.HostData
    mirror: gen.Mirror
    quotas: np.ndarray
    setup_spans: Dict[str, float]

    @property
    def coordinates(self) -> List[str]:
        return list(self.traffic["coordinates"])

    def fit(self):
        """One whole fit, synchronised. Returns the GameResults."""
        return run_fit(self.estimator, self.datasets, self.validation_raw, self.coordinates)

    def outcome(self, results) -> FitOutcome:
        """Fetched AFTER the timed fit: finiteness of every coefficient, the
        divergence guard's rejections so far, and the fit's fingerprint."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu import obs

        finite = jnp.asarray(True)
        iters = []
        for r in results:
            for name in self.coordinates:
                finite = finite & jnp.all(jnp.isfinite(coefficients(r.model[name])))
                iters.append(jnp.sum(r.trackers[name].result.iterations))
        finite_h, iters_h = jax.device_get((finite, iters))
        metrics = tuple(
            tuple(sorted((k, float(v)) for k, v in r.evaluation.metrics.items()))
            for r in results
            if r.evaluation is not None
        )
        rejections = sum(
            int(m["value"])
            for m in obs.current_run().registry.snapshot()
            if m["name"] == "photon_coordinate_rejections_total"
        )
        return FitOutcome(
            finite=bool(finite_h),
            rejections=rejections,
            fingerprint=(tuple(int(i) for i in iters_h), metrics),
        )


def run_fit(estimator, datasets, validation_raw, coordinates):
    """``GameEstimator.fit`` from a zero model, closed by ONE scalar fetch that
    depends on every coordinate's coefficients, under the transfer guard. With
    prebuilt datasets ``fit`` never reads its ``raw`` argument, so none is kept
    (the per-user COO of the build is 0.8 KB a row on the host)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.analysis import transfer_guard

    with transfer_guard():
        results = estimator.fit(None, validation=validation_raw, datasets=datasets)
        total = jnp.zeros((), jnp.float32)
        for r in results:
            for name in coordinates:
                total = total + jnp.sum(coefficients(r.model[name]))
        jax.device_get(total)
    return results


def coefficients(model):
    """The coefficient array of a fixed- or random-effect model."""
    if hasattr(model, "coef_values"):
        return model.coef_values
    return model.model.coefficients.means


def make_mesh(config: dict, chips: int):
    """None for one chip (device 0 alone); ``data=<chips>`` otherwise."""
    import jax

    n_data = int(config["mesh"]["data"])
    if n_data != chips:
        raise ValueError(f"config mesh data={n_data} but the cell asks for {chips} chips")
    if n_data == 1:
        return None
    from photon_ml_tpu.parallel.mesh import make_mesh as program_mesh

    return program_mesh(n_data=n_data, n_model=1, devices=jax.devices()[:n_data])


def raw_dataset(config: dict, rows: gen.HostData, global_x=None):
    """RawDataset of the per-user shard (and, for validation, the dense global
    shard as the f64 COO ``GameEstimator._validation_context`` reads)."""
    from photon_ml_tpu.io.data import RawDataset

    n_rows = len(rows.labels)
    coo = {USER_SHARD: gen.dense_coo(rows.user_features)}
    dims = {USER_SHARD: config["random_effect"]["d_re"]}
    if global_x is not None:
        coo[GLOBAL_SHARD] = gen.dense_coo(global_x)
        dims[GLOBAL_SHARD] = config["fixed_effect"]["d"]
    return RawDataset(
        n_rows=n_rows,
        labels=rows.labels.astype(np.float64),
        offsets=np.zeros(n_rows),
        weights=np.ones(n_rows),
        shard_coo=coo,
        shard_dims=dims,
        # integer ids: build_random_effect_dataset groups them in their
        # native dtype (strings cost more than the rest of the build)
        id_tags={config["random_effect"]["id"]: rows.user_of_row},
    )


def assemble(config: dict, traffic: dict, mesh, x, rows: gen.HostData, validate: bool = True):
    """(estimator, datasets) for ``traffic`` on the rows given: the
    fixed-effect dataset around the device matrix ``x`` as it stands (as
    ``bench.py`` ``_glmix_datasets`` does: a dense f64 COO of it would not fit
    the host), the random-effect dataset through the program's builder, both
    placed on ``mesh`` the way ``GameEstimator._prepare_datasets`` places them.
    The full-size build and the sample-parity fits share this."""
    import jax.numpy as jnp

    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
    from photon_ml_tpu.game.data import FixedEffectDataset, build_random_effect_dataset
    from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch

    fe, re = config["fixed_effect"], config["random_effect"]
    n, d = x.shape
    batch = LabeledBatch(
        features=FeatureMatrix(dim=d, dense=x),
        labels=jnp.asarray(rows.labels, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
    )
    if mesh is not None:
        from photon_ml_tpu.parallel.mesh import shard_batch

        batch = shard_batch(batch, mesh)
    datasets: Dict[str, object] = {
        fe["name"]: FixedEffectDataset(
            coordinate_id=fe["name"], feature_shard=GLOBAL_SHARD, batch=batch,
            true_dim=d, true_n_rows=n,
        )
    }
    grid = tuple(traffic["reg_weights"][fe["name"]])
    configs = [
        CoordinateConfig(
            name=fe["name"], feature_shard=GLOBAL_SHARD,
            config=_opt_config(fe, grid[0]), reg_weights=grid,
        )
    ]
    if re["name"] in traffic["coordinates"]:
        n_data = 1 if mesh is None else mesh.shape["data"]
        # the training RawDataset carries the per-user shard only
        ds = build_random_effect_dataset(
            raw_dataset(config, rows), re["name"], USER_SHARD, re["id"],
            active_cap=re["active_cap"], pad_entities_to_multiple=n_data,
        )
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import shard_entity_blocks

            ds = dataclasses.replace(ds, blocks=shard_entity_blocks(ds.blocks, mesh))
        datasets[re["name"]] = ds
        configs.append(
            CoordinateConfig(
                name=re["name"], feature_shard=USER_SHARD,
                config=_opt_config(re, re["reg_weight"]),
                random_effect_type=re["id"], active_cap=re["active_cap"],
            )
        )
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=configs,
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]] if validate else (),
        mesh=mesh,
        validation_frequency=traffic["validation"]["frequency"],
    )
    return estimator, datasets


def build(config: dict, traffic: dict, chips: int, seed: int) -> FitJob:
    """A cell's set-up up to the first fit: the configuration's data set
    (``scale.data_seed`` draws every value), mirrored by the run's seed so that
    every seed does the same work (benchmark/data.py), then datasets."""
    import jax

    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, re, scale = config["fixed_effect"], config["random_effect"], config["scale"]
    n, n_users, n_val = scale["rows"], scale["users"], scale["validation_rows"]
    d = fe["d"]
    if fe["intercept_column"] != d - 1:
        raise ValueError("the intercept must be the last fixed-effect column")
    mesh = make_mesh(config, chips)
    data_seed = scale["data_seed"]
    rng = np.random.default_rng(data_seed)
    mirror = gen.draw_mirror(seed, d, re["d_re"])
    truth = gen.draw_truth(rng, d, n_users, re["d_re"])
    quotas = gen.user_quotas(n, n_users, scale["zipf_exponent"])

    chunk = scale["generation_chunk_rows"]
    x, margin = gen.device_features(
        data_seed, n, d, chunk, truth.w_fixed, mesh=mesh, signs=mirror.fixed
    )
    x_val, margin_val = gen.device_features(
        data_seed, n_val, d, min(chunk, n_val), truth.w_fixed, stream=1, signs=mirror.fixed
    )
    margin_h, x_val_h, margin_val_h = jax.device_get((margin, x_val, margin_val))
    del margin, x_val, margin_val
    host = gen.host_rows(rng, gen.train_users(rng, quotas), margin_h, truth, mirror.user)
    val = gen.host_rows(
        rng, gen.validation_users(rng, quotas, n_val), margin_val_h, truth, mirror.user
    )
    validation_raw = raw_dataset(config, val, x_val_h)
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, mesh, x, host)
    spans["dataset_build"] = time.perf_counter() - t_build
    return FitJob(
        config=config, traffic=traffic, mesh=mesh, estimator=estimator,
        datasets=datasets, validation_raw=validation_raw,
        host=host, mirror=mirror, quotas=quotas, setup_spans=spans,
    )


# -- the run protocol ------------------------------------------------------------


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float,
        required_fusion: str = "compiled") -> str:
    """Set-up, window, correctness; returns the result line. ``required_fusion``
    is what ``_fusion_mode`` must say (tests on the CPU pass "interpret")."""
    import logging

    import jax

    from photon_ml_tpu.game.problem import _fusion_mode
    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct, observe, trace as trace_mod
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

    t = time.perf_counter()
    base = job.outcome(job.fit())  # compiles, or loads from the cache
    warm1 = time.perf_counter() - t
    listener.phase = "warm"
    t = time.perf_counter()
    second = job.outcome(job.fit())  # must find every program in memory
    warm2 = time.perf_counter() - t
    listener.phase = "setup"
    notes = {
        "warmup_fit_s": [warm1, warm2],
        "warmup_incomplete": listener.compiles("warm") > 0,
        "fingerprint": [list(base.fingerprint[0]), [dict(m) for m in base.fingerprint[1]]],
    }
    parity = correct.sample_parity(job, required_fusion)
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
        window, collector, counters, device_trace = _traced_window(job, check, seconds, listener)
    listener.phase = "after"

    # -- after -----------------------------------------------------------------------
    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[: cell.chips]
    )
    device = dict(device, memory_peak_bytes=memory_peak)
    full = (
        correct.full_size(job, state["last"]) if state["last"] is not None else {"ok": False}
    )
    notes["full_size"] = full
    notes["window_compiles"] = listener.compiles("window")
    notes["window_retraces"] = listener.retraces("window")
    notes["fits_same_as_warmup"] = state["same"]
    notes["fit_walls_s"] = window.walls
    is_correct = (
        parity["ok"] and full["ok"] and state["same"] and window.failed == 0
        and listener.compiles("window") == 0 and not notes["warmup_incomplete"]
        and len(window.walls) > 0
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


def _traced_window(job: FitJob, check, seconds: float, listener):
    """``trace_fits`` fits under ``jax.profiler`` with the program's span and
    metrics collection attached (with a sink it fetches after every solve,
    ``obs/run.py``: one more reason these timings are per-layer numbers and
    never ``fit_s``). Returns (window, span collector, registry snapshot, the
    device trace on the perf_counter clock or None)."""
    import glob
    import shutil
    import tempfile

    import jax

    from photon_ml_tpu import obs

    from .. import observe, trace as trace_mod
    from ..window import run_window

    run_telemetry = obs.RunTelemetry()
    collector = observe.SpanCollector()
    run_telemetry.register_listener(collector)
    marks: List[float] = []

    def marked_fit():
        marks.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.fit"):
            return job.fit()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
    try:
        with obs.use_run(run_telemetry):
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                window = run_window(marked_fit, check, seconds, max_fits=job.traffic["trace_fits"])
            finally:
                jax.profiler.stop_trace()
        listener.phase = "after"
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        device_trace = trace_mod.load(paths[0]) if paths else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if device_trace is not None:
        offset = trace_mod.clock_offset(device_trace, "bench.fit", marks)
        device_trace = device_trace.shifted(offset) if offset is not None else None
    return window, collector, run_telemetry.registry.snapshot(), device_trace
