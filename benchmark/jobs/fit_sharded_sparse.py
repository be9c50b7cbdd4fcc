"""Job kind ``fit_sharded_sparse``: whole ``GameEstimator.fit`` calls from a
zero model for ONE logistic GLM over a sparse fixed effect too wide for one
chip's solver state, on a ``data`` mesh of the cell's chips: the rows sharded
over the chips, and the coefficient-length state (coefficients, gradient,
direction, the L-BFGS history) split over them by the program's own rule
(``game/problem.py`` ``state_sharding``), never by this job.

The protocol is ``jobs/fit_sparse.py``'s, step for step (set-up, two warm-up
fits, parity, a window of whole fits each closed by one scalar fetch,
full-size checks; the same result line, ``breakdown`` and ``notes``), and so
are the pieces imported from it. What is this file's own: the mesh, the
Criteo law of benchmark/data_criteo.py (13 real-valued count columns and 26 ids
a row), the checks of benchmark/correct_sharded_sparse.py (both on the timed
objects, at full size), a refusal in set-up of a state no chip can hold
(below), the trace's operations of EVERY chip (``job.device_ops_by_chip``,
benchmark/sharded_ops.py), and the plan entry the program's planner gives the
coordinate at this width.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional

import numpy as np

from .. import data_criteo as gen
from .. import data_sparse
from . import fit as fitjob
from . import fit_sparse as sparsejob

GLOBAL_SHARD = fitjob.GLOBAL_SHARD

# readers whose numbers a traced run prints under ``notes["sharded"]``: this
# cell's four (benchmark/layer_metrics/; BENCHMARK.json cannot list them while
# test_benchmark_fit_span_metrics.py pins the tail of ``per_layer``, PERF.md
# section 7) and three that read here as they read in ``fit-sparse``
NOTE_READERS = (
    "fe_shard_pass_s", "fe_shard_pass_roofline", "fe_state_collective_s", "fe_state_collective_roofline",
    "fe_line_search_evals", "fe_evals_per_iter", "fe_sparse_slot_pad_share",
)

# what each chip may fill with the solve's state: a v5e holds 16 GB; the
# features, labels and the runtime's own take the rest
CHIP_STATE_BYTES = 13e9


def chip_state_bytes(config: dict, chips: int) -> int:
    """The solver state one chip must hold at the configuration's width, as
    the program under test lays it out over ``chips`` shards: its L-BFGS
    history (the program's ``history_account``), the whole-width vectors a
    pass needs (the gathered vector and the scatter-add's target), and eight
    coefficient-length vectors of the solve (coefficients, gradient,
    direction, the trial's pair, the tolerances' and the line search's), each
    a shard's share. A program whose ``history_account`` takes no shard count
    (any commit before PR 40, which holds the whole state on every chip)
    raises ``TypeError`` here: the run exits non-zero at once."""
    from photon_ml_tpu.optimize import lbfgs

    fe = config["fixed_effect"]
    d, m = int(fe["d"]), int(fe["num_corrections"])
    _, history = lbfgs.history_account(d, m, 4, chips)
    d_pad = lbfgs.history_row_width((d,), False, chips) or d
    return history + 2 * d_pad * 4 + 8 * (d_pad // chips) * 4


def refuse_a_state_no_chip_can_hold(config: dict, chips: int) -> None:
    """Raise ``NoResult`` (the run exits non-zero at once) where the state
    would not fit a chip: BEFORE any data is drawn or any buffer placed, so a
    configuration too wide for its chips fails cleanly and soon instead of
    exhausting the chips, or the host, later (PERF.md section 7)."""
    from ..run import NoResult

    need = chip_state_bytes(config, chips)
    if need > CHIP_STATE_BYTES:
        raise NoResult(
            f"the solver state of d={config['fixed_effect']['d']} needs {need / 1e9:.2f} GB a chip "
            f"on {chips} chips, over the {CHIP_STATE_BYTES / 1e9:.0f} GB a chip can give it"
        )


@dataclasses.dataclass
class ShardedFitJob(sparsejob.SparseFitJob):
    """``SparseFitJob`` on a mesh: ``host`` is the generator's ``Rows``
    (columns, values, labels), ``mirror`` the seed's signs f32[d]."""

    # a traced run's device operations with their whole HLO lines, of every
    # chip, on the perf_counter clock (benchmark/sharded_ops.py)
    device_ops_by_chip: Optional[dict] = None
    plan: Optional[dict] = None
    # the rows as the float64 reference reads them, laid out once by the first
    # check that needs them (benchmark/correct_sharded_sparse.py)
    reference_slots: Optional[object] = None


def raw_dataset(d: int, cols: np.ndarray, vals: np.ndarray, labels: np.ndarray, signs: np.ndarray):
    """The rows as the ``RawDataset`` ``cli train`` would hand the estimator:
    one sparse shard of int64 / float64 triplets, real values."""
    from photon_ml_tpu.io.data import RawDataset

    n = len(labels)
    return RawDataset(
        n_rows=n, labels=labels.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        shard_coo={GLOBAL_SHARD: gen.triplets(cols, vals, signs)}, shard_dims={GLOBAL_SHARD: d}, id_tags={},
    )


def assemble(config: dict, traffic: dict, raw, mesh):
    """``fit_sparse.assemble`` on ``mesh``: the estimator the mesh is handed
    to, the datasets by its own build (rows sharded over the data axis by
    ``GameEstimator.prepare_datasets``)."""
    import jax.numpy as jnp

    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator

    fe = config["fixed_effect"]
    grid = tuple(traffic["reg_weights"][fe["name"]])
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=[
            CoordinateConfig(
                name=fe["name"], feature_shard=GLOBAL_SHARD, config=sparsejob._opt_config(fe, grid[0]),
                reg_weights=grid, layout=fe["layout"],
            )
        ],
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]],
        mesh=mesh,
        validation_frequency=traffic["validation"]["frequency"],
        dtype=getattr(jnp, config["dtype"]),
    )
    return estimator, estimator.prepare_datasets(raw)


def plan_entry(estimator, config: dict) -> Optional[dict]:
    """The program's plan for the coordinate at the configuration's width
    (``plan/planner.py`` ``resolve`` with the shard's dimension)."""
    from photon_ml_tpu.plan import planner

    plan = planner.resolve(estimator.coordinate_configs, mesh=estimator.mesh,
                           dims={GLOBAL_SHARD: config["fixed_effect"]["d"]})
    c = plan.coordinates[0]
    return {"sharding": c.sharding, "geometry": dict(c.geometry)}


def build(config: dict, traffic: dict, chips: int, seed: int) -> ShardedFitJob:
    """A cell's set-up up to the first fit."""
    refuse_a_state_no_chip_can_hold(config, chips)
    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, scale = config["fixed_effect"], config["scale"]
    n, n_val, d = scale["rows"], scale["validation_rows"], fe["d"]
    if fe["intercept_column"] != d - 1:
        raise ValueError("the intercept must be the last fixed-effect column")
    mesh = fitjob.make_mesh(config, chips)
    data_seed = scale["data_seed"]
    law = gen.draw_law(data_seed, scale["fields"], n, scale["zipf_exponent"])
    if law.dim != d or gen.NUMERIC + len(law.cardinalities) + 1 != fe["slots_per_row"]:
        raise ValueError("the fields do not add up to the configuration's d and slots a row")
    cols, vals = gen.draw_features(data_seed, law)
    gen.set_intercept(law, cols, vals, scale["click_rate"])
    rows = gen.rows(data_seed, law, cols, vals)
    val = gen.rows(data_seed, law, *gen.draw_features(data_seed, law, n_sample=n_val, stream=1), stream=1)
    signs = data_sparse.draw_signs(seed, d)
    raw = raw_dataset(d, rows.cols, rows.vals, rows.labels, signs)
    validation = raw_dataset(d, val.cols, val.vals, val.labels, signs)
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, raw, mesh)
    del raw  # the build holds what it needs; the host keeps ``rows``
    spans["dataset_build"] = time.perf_counter() - t_build
    return ShardedFitJob(
        config=config, traffic=traffic, mesh=mesh, estimator=estimator, datasets=datasets,
        validation_raw=validation, host=rows, mirror=signs, quotas=None, setup_spans=spans, law=law,
        plan=plan_entry(estimator, config),
    )


def traced_window(job: ShardedFitJob, check, seconds: float, listener):
    """``jobs/fit.py`` ``_traced_window`` as it stands, its one reading of the
    trace file made by ``sharded_ops.load``: the ``DeviceTrace`` the harness
    reads, and beside it every chip's operations with their whole lines
    (``job.device_ops_by_chip``), moved onto the clock the harness moved the
    trace to. The file lives only inside ``_traced_window`` and ``trace.load``
    keeps names alone (both the harness's files, as ``fit_sparse.py``'s
    ``traced_window`` found), hence the loader handed in for the call."""
    from .. import sharded_ops, trace as trace_mod

    kept = {}

    def load(path, *args, **kwargs):
        kept["trace"], kept["ops"] = sharded_ops.load(path)
        return kept["trace"]

    real, trace_mod.load = trace_mod.load, load
    try:
        out = fitjob._traced_window(job, check, seconds, listener)
    finally:
        trace_mod.load = real
    device_trace = out[3]
    if device_trace is not None and kept.get("ops"):
        first = lambda t: next(iter(t.chips.values()))[0][1]  # noqa: E731
        offset = first(device_trace) - first(kept["trace"])
        job.device_ops_by_chip = {chip: sharded_ops.shifted(ops, offset) for chip, ops in kept["ops"].items()}
    return out


# -- the run protocol: jobs/fit_sparse.py ``run``, with this job's build and checks --


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float) -> str:
    """Set-up, window, correctness; returns the result line."""
    import logging

    import jax

    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct_sharded_sparse, observe, sharded_ops, trace as trace_mod
    from ..compile_listener import CompileListener
    from ..run import HERE, load_json, load_reader, report_metrics, result_line
    from ..window import run_window

    # -- set-up --------------------------------------------------------------------
    listener = CompileListener().install()
    enable_persistent_compilation_cache()
    logging.getLogger("photon_ml_tpu").setLevel(logging.WARNING)  # no logging in a fit
    job = build(cell.config, cell.traffic, cell.chips, seed)

    programs = sparsejob.solver_programs()
    t = time.perf_counter()
    base = job.outcome(job.fit())  # compiles, or loads from the cache
    warm1 = time.perf_counter() - t
    programs = sparsejob.solver_programs() - programs
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
        "plan": job.plan,
        "fingerprint": {"iterations": list(iters), "line_search_evals": list(evals),
                        "validation": [dict(m) for m in metrics]},
    }
    parity = correct_sharded_sparse.parity(job)
    notes["parity"] = parity
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
        correct_sharded_sparse.full_size(job, state["last"])
        if state["last"] is not None else {"ok": False}
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
    extra = {name: load_reader(name).read(observations) for name in NOTE_READERS if name not in listed}
    notes["sharded"] = {name: value for name, value in extra.items() if value is not None}
    notes["sharded"]["solve_spans"] = [
        {k: s.attrs.get(k) for k in ("state_sharding", "state_shards", "history", "history_bytes",
                                      "collective_bytes", "iterations", "line_search_evals", "dim")}
        for s in observations.spans_named("fe.solve")
    ]
    if observations.fit_windows and job.device_ops_by_chip:
        notes["sharded"]["ops"] = sharded_ops.summary(observations)
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
