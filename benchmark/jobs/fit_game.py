"""Job kind ``fit_game``: whole ``GameEstimator.fit`` calls from a zero model
for a GAME model with one fixed effect and ANY number of random effects.

The protocol is job ``fit``'s, step for step (set-up, two warm-up fits, sample
parity, a window of whole fits each closed by one scalar fetch that depends on
every coordinate's coefficients, full-size checks; the same result line,
``breakdown`` and ``notes``), and so are the pieces imported from it. What is
this file's own: a configuration with a LIST ``random_effects``, one
``build_random_effect_dataset`` per effect, the traffic's update sequence, a
sign vector per feature bag (benchmark/data_game.py), and the checks of
benchmark/correct_game.py. ``jobs/fit.py``, ``correct.py`` and ``data.py`` are
wired to one ``random_effect`` and are not this PR's to edit.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List

import numpy as np

from .. import data as gen
from .. import data_game as gen_game
from . import fit as fitjob


def effects_of(config: dict) -> List[dict]:
    """The configuration's random effects with their scale resolved: each
    entry's ``entities`` names the key of ``scale`` that counts its entities
    and selects its Zipf exponent."""
    scale = config["scale"]
    return [
        dict(e, n_entities=scale[e["entities"]], zipf=scale["zipf_exponent"][e["entities"]])
        for e in config["random_effects"]
    ]


# The per-coordinate readers this job brings (benchmark/layer_metrics/<name>.py).
# BENCHMARK.json cannot list them yet: a PR that changes the program may only
# append to ``per_layer``, and tests/benchmark_yardstick/
# test_benchmark_fit_span_metrics.py pins that list from ``fit_validation_ctx_s``
# to its end. Until a ``benchmark`` PR lists them, a traced run prints what they
# read under ``notes["per_coordinate"]``, which the driver does not read.
PER_COORDINATE_READERS = (
    "re_user_update_s", "re_item_update_s", "re_item_exchange_s", "re_item_solve_s", "re_item_score_s",
    "re_item_slot_pad_share", "re_item_lockstep_share", "re_item_passive_share", "re_user_solve_s",
)


@dataclasses.dataclass
class GameFitJob(fitjob.FitJob):
    """``FitJob`` (its ``fit`` and ``outcome`` as they stand) with the host
    rows of every effect: ``host`` is a ``GameRows``, ``mirror`` a
    ``GameMirror``, ``quotas`` a dict by effect name."""

    effects: List[dict] = dataclasses.field(default_factory=list)

    @property
    def coordinates(self) -> List[str]:
        return list(self.traffic["update_sequence"])


def raw_dataset(effects: List[dict], rows: gen_game.GameRows, fixed=None):
    """RawDataset of the given effects' shards (and, for validation, the dense
    global shard ``fixed`` = (shard, x, d) as the f64 COO
    ``GameEstimator._validation_context`` reads)."""
    from photon_ml_tpu.io.data import RawDataset

    n_rows = len(rows.labels)
    coo = {e["shard"]: gen.dense_coo(rows.features[e["name"]]) for e in effects}
    dims = {e["shard"]: e["d_re"] for e in effects}
    if fixed is not None:
        shard, x, d = fixed
        coo[shard], dims[shard] = gen.dense_coo(x), d
    return RawDataset(
        n_rows=n_rows,
        labels=rows.labels.astype(np.float64),
        offsets=np.zeros(n_rows),
        weights=np.ones(n_rows),
        shard_coo=coo,
        shard_dims=dims,
        # integer ids, as in job fit: the builder groups them in their dtype
        id_tags={e["id"]: rows.entity_of_row[e["name"]] for e in effects},
    )


def assemble(config: dict, traffic: dict, mesh, x, rows: gen_game.GameRows, validate: bool = True):
    """(estimator, datasets) for ``traffic`` on the rows given, coordinates in
    the order of the update sequence: the fixed-effect dataset around the
    device matrix ``x`` as it stands, each random-effect dataset through the
    program's builder from a RawDataset of that effect's shard alone (one f64
    COO at a time on the host). The full-size build and the sample-parity fits
    share this."""
    import jax.numpy as jnp

    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
    from photon_ml_tpu.game.data import FixedEffectDataset, build_random_effect_dataset
    from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch

    fe = config["fixed_effect"]
    effects = {e["name"]: e for e in effects_of(config)}
    n_data = 1 if mesh is None else mesh.shape["data"]
    datasets: Dict[str, object] = {}
    configs = []
    for name in traffic["update_sequence"]:
        if name == fe["name"]:
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
            datasets[name] = FixedEffectDataset(
                coordinate_id=name, feature_shard=fitjob.GLOBAL_SHARD, batch=batch,
                true_dim=d, true_n_rows=n,
            )
            grid = tuple(traffic["reg_weights"][name])
            configs.append(CoordinateConfig(
                name=name, feature_shard=fitjob.GLOBAL_SHARD,
                config=fitjob._opt_config(fe, grid[0]), reg_weights=grid,
            ))
            continue
        e = effects[name]
        ds = build_random_effect_dataset(
            raw_dataset([e], rows), name, e["shard"], e["id"],
            active_cap=e["active_cap"], pad_entities_to_multiple=n_data,
        )
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import shard_entity_blocks

            ds = dataclasses.replace(ds, blocks=shard_entity_blocks(ds.blocks, mesh))
        datasets[name] = ds
        configs.append(CoordinateConfig(
            name=name, feature_shard=e["shard"],
            config=fitjob._opt_config(e, traffic["reg_weights"][name]),
            random_effect_type=e["id"], active_cap=e["active_cap"],
        ))
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=configs,
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]] if validate else (),
        mesh=mesh,
        validation_frequency=traffic["validation"]["frequency"],
    )
    return estimator, datasets


def build(config: dict, traffic: dict, chips: int, seed: int) -> GameFitJob:
    """A cell's set-up up to the first fit: the configuration's data set
    (``scale.data_seed`` draws every value), mirrored by the run's seed so that
    every seed does the same work, then datasets."""
    import jax

    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, scale = config["fixed_effect"], config["scale"]
    effects = effects_of(config)
    if sorted(traffic["update_sequence"]) != sorted(traffic["coordinates"]):
        raise ValueError("the update sequence must name every coordinate of the mix once")
    n, n_val, d = scale["rows"], scale["validation_rows"], fe["d"]
    if fe["intercept_column"] != d - 1:
        raise ValueError("the intercept must be the last fixed-effect column")
    mesh = fitjob.make_mesh(config, chips)
    data_seed = scale["data_seed"]
    rng = np.random.default_rng(data_seed)
    mirror = gen_game.draw_mirror(seed, d, {e["name"]: e["d_re"] for e in effects})
    truth = gen_game.draw_truth(
        rng, d, {e["name"]: (e["n_entities"], e["d_re"]) for e in effects}
    )
    quotas = {e["name"]: gen.user_quotas(n, e["n_entities"], e["zipf"]) for e in effects}

    chunk = scale["generation_chunk_rows"]
    x, margin = gen.device_features(
        data_seed, n, d, chunk, truth.w_fixed, mesh=mesh, signs=mirror.fixed
    )
    x_val, margin_val = gen.device_features(
        data_seed, n_val, d, min(chunk, n_val), truth.w_fixed, stream=1, signs=mirror.fixed
    )
    margin_h, x_val_h, margin_val_h = jax.device_get((margin, x_val, margin_val))
    del margin, x_val, margin_val
    # the entity of a row, one effect after the other: independent shuffles
    host = gen_game.host_rows(
        rng, {name: gen.train_users(rng, q) for name, q in quotas.items()},
        margin_h, truth, mirror,
    )
    val = gen_game.host_rows(
        rng, {name: gen.validation_users(rng, q, n_val) for name, q in quotas.items()},
        margin_val_h, truth, mirror,
    )
    validation_raw = raw_dataset(effects, val, (fitjob.GLOBAL_SHARD, x_val_h, d))
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, mesh, x, host)
    spans["dataset_build"] = time.perf_counter() - t_build
    return GameFitJob(
        config=config, traffic=traffic, mesh=mesh, estimator=estimator,
        datasets=datasets, validation_raw=validation_raw,
        host=host, mirror=mirror, quotas=quotas, setup_spans=spans, effects=effects,
    )


# -- the run protocol: jobs/fit.py ``run``, with this job's build and checks ---


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float,
        required_fusion: str = "compiled") -> str:
    """Set-up, window, correctness; returns the result line. ``required_fusion``
    is what ``_fusion_mode`` must say (tests on the CPU pass "interpret")."""
    import logging

    import jax

    from photon_ml_tpu.game.problem import _fusion_mode
    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct_game, observe, trace as trace_mod
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
    parity = correct_game.sample_parity(job, required_fusion)
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
        correct_game.full_size(job, state["last"]) if state["last"] is not None else {"ok": False}
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
    listed = {m["name"] for m in cell.per_layer}
    per_coordinate = {
        name: load_reader(name).read(observations) for name in PER_COORDINATE_READERS if name not in listed
    }
    notes["per_coordinate"] = {name: value for name, value in per_coordinate.items() if value is not None}
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
