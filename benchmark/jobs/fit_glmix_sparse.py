"""Job kind ``fit_glmix_sparse``: whole ``GameEstimator.fit`` calls from a zero
model for GLMix over sparse id features: ONE ``RawDataset`` holding two sparse
shards (the global one-hot shard of job ``fit_sparse`` and a per-user shard of
the smallest fields) and the ``userId`` tag, both coordinates' datasets built
by ``GameEstimator.prepare_datasets``, the normal path of ``cli train``. The
fixed effect runs ``ops/features.py``'s gather and scatter-add under plain
L-BFGS inside coordinate descent (residual offsets, warm starts); the random
effect runs the bucketed packed L-BFGS on the entity-block store the program
builds for per-user subspaces that differ (2 to 439 columns).

The protocol is job ``fit``'s, step for step (set-up, two warm-up fits, sample
parity, a window of whole fits each closed by one scalar fetch that depends on
both coordinates' coefficients, full-size checks; the same result line,
``breakdown`` and ``notes``), and so are the pieces imported from it and from
``fit_sparse`` (whose job class, traced window and seven readers this job
keeps). What is this file's own: the law of benchmark/data_glmix_sparse.py, two
coordinates from one raw data set, a fingerprint that holds the fixed effect's
passes and the per-user iteration sum, the checks of
benchmark/correct_glmix_sparse.py, and the readers of ``GLMIX_SPARSE_READERS``.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

from .. import data_glmix_sparse as gen_user
from .. import data_sparse as gen
from . import fit as fitjob
from . import fit_sparse as sparsejob

GLOBAL_SHARD = fitjob.GLOBAL_SHARD

# The readers this job brings (benchmark/layer_metrics/<name>.py), then those
# of earlier PRs that read this cell's spans and counters. BENCHMARK.json
# cannot list any of them (PERF.md, Open questions: the pin on ``per_layer``'s
# tail), so a traced run prints what they read under ``notes["glmix_sparse"]``.
GLMIX_SPARSE_READERS = (
    "re_subspace_pad_share", "re_block_store_gb", "fe_warm_solver_iters", "re_score_form",
)
BORROWED_READERS = sparsejob.SPARSE_READERS + (
    "re_bucket_enqueue_s", "re_bucket_cut_s", "re_bucket_wait_s", "re_bucket_device_s",
    "re_warm_start_s", "re_update_s", "re_exchange_s", "re_solve_s", "re_score_s",
    "re_pad_share", "re_slot_pad_share", "re_lockstep_share", "re_solver_iters",
)


@dataclasses.dataclass
class GlmixSparseFitJob(sparsejob.SparseFitJob):
    """``SparseFitJob`` (its ``fit``, ``pass_shape`` and ``features`` as they
    stand): ``host`` is the generator's ``Rows`` with users and user-shard
    columns, ``mirror`` the global shard's signs f32[d], ``user_mirror`` the
    user shard's f32[d_re]."""

    shard: Optional[gen_user.UserShard] = None
    user_mirror: Optional[np.ndarray] = None

    @property
    def coordinates(self) -> List[str]:
        return list(self.traffic["update_sequence"])

    def outcome(self, results) -> fitjob.FitOutcome:
        """``SparseFitJob.outcome`` (iterations of both coordinates, the
        per-user one a sum over users; validation metrics; trials judged) plus
        the feature passes the last fixed-effect solve counted."""
        import jax

        base = super().outcome(results)
        name = self.config["fixed_effect"]["name"]
        passes = jax.device_get([
            (getattr(r.trackers[name].result, "matvecs", None),
             getattr(r.trackers[name].result, "rmatvecs", None)) for r in results
        ])
        passes = tuple(tuple(None if p is None else int(np.sum(p)) for p in pair) for pair in passes)
        return dataclasses.replace(base, fingerprint=base.fingerprint + (passes,))


def raw_dataset(config: dict, rows: gen_user.Rows, signs: np.ndarray, user_signs: np.ndarray):
    """The rows as the ``RawDataset`` ``cli train`` would hand the estimator:
    two sparse shards of int64 / float64 triplets and the user tag (integer
    ids: the builder groups them in their dtype)."""
    from photon_ml_tpu.io.data import RawDataset

    fe, re = config["fixed_effect"], config["random_effect"]
    n = len(rows.labels)
    return RawDataset(
        n_rows=n, labels=rows.labels.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        shard_coo={
            GLOBAL_SHARD: gen.triplets(rows.cols, signs),
            re["shard"]: gen.triplets(rows.user_cols, user_signs),
        },
        shard_dims={GLOBAL_SHARD: fe["d"], re["shard"]: re["d_re"]},
        id_tags={re["id"]: rows.user},
    )


def assemble(config: dict, traffic: dict, raw, reg_weights=None, validate=True):
    """(estimator, datasets) for ``traffic`` on the raw rows given, both
    coordinates' datasets by the program's own build from the ONE raw data set;
    ``reg_weights`` replaces the mix's (the parity sample scales the fixed
    effect's by its share of the rows)."""
    import jax.numpy as jnp

    from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator

    fe, re = config["fixed_effect"], config["random_effect"]
    reg = dict(traffic["reg_weights"]) if reg_weights is None else dict(reg_weights)
    by_name = {
        fe["name"]: CoordinateConfig(
            name=fe["name"], feature_shard=GLOBAL_SHARD,
            config=sparsejob._opt_config(fe, reg[fe["name"]][0]),
            reg_weights=tuple(reg[fe["name"]]), layout=fe["layout"],
        ),
        re["name"]: CoordinateConfig(
            name=re["name"], feature_shard=re["shard"],
            config=sparsejob._opt_config(re, reg[re["name"]]),
            random_effect_type=re["id"], active_cap=re["active_cap"],
            active_lower_bound=re["active_lower_bound"],
            features_to_samples_ratio=re["features_to_samples_ratio"],
        ),
    }
    estimator = GameEstimator(
        task=config["task"],
        coordinate_configs=[by_name[name] for name in traffic["update_sequence"]],
        n_cd_iterations=traffic["cd_sweeps"],
        evaluator_specs=[traffic["validation"]["evaluator"]] if validate else (),
        mesh=None,
        validation_frequency=traffic["validation"]["frequency"],
        dtype=getattr(jnp, config["dtype"]),
    )
    return estimator, estimator.prepare_datasets(raw)


def stores_a_plane() -> bool:
    """Whether the program under this job keeps a random effect's entity
    blocks as ONE ``[E, K, S]`` array: observed on a two-user data set built
    by its own builder, not read off its names."""
    from photon_ml_tpu.game.data import build_random_effect_dataset
    from photon_ml_tpu.io.data import RawDataset

    raw = RawDataset(
        n_rows=3, labels=np.zeros(3), offsets=np.zeros(3), weights=np.ones(3),
        shard_coo={"probe": (np.array([0, 0, 1, 2]), np.array([0, 1, 0, 1]), np.ones(4))},
        shard_dims={"probe": 2}, id_tags={"user": np.array([0, 0, 1])},
    )
    features = build_random_effect_dataset(raw, "probe", "probe", "user").blocks.features
    return not hasattr(features, "parts")


def refuse_a_plane_the_host_cannot_hold(config: dict, rows: gen_user.Rows) -> None:
    """A clean ``MemoryError`` in set-up, BEFORE the program's build, where
    the program would stage an ``[E, K, S]`` plane larger than the machine's
    memory (125 GB in float32, 250 GB staged, at this configuration: every
    commit before PR 38). Left to itself such a build does not fail cleanly
    on a machine that overcommits: ``np.zeros`` is handed its 250 GB, the
    conversion to the device then touches all of it, and the kernel kills the
    process or the machine (my chip runs, PR 38: three machines lost). A
    program that stores the blocks by bucket is not asked anything. The
    plane is bounded from below: E users, K the cap (or the largest user), S
    the distinct columns of the widest user UNDER the cap (all of whose rows
    are active, whatever the reservoir takes of the others), float64."""
    if not stores_a_plane():
        return
    re = config["random_effect"]
    users, index, counts = np.unique(rows.user, return_inverse=True, return_counts=True)
    pairs = np.unique(index[:, None] * re["d_re"] + rows.user_cols)
    widths = np.bincount(pairs // re["d_re"], minlength=len(users))
    widest = int(widths[counts <= re["active_cap"]].max())
    needed = len(users) * int(min(counts.max(), re["active_cap"])) * widest * 8
    held = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    if needed > held:
        raise MemoryError(
            f"the program would stage a {needed / 1e9:.0f} GB [E, K, S] entity-block plane on a "
            f"host of {held / 1e9:.0f} GB: it cannot build this configuration's random effect"
        )


def build(config: dict, traffic: dict, chips: int, seed: int) -> GlmixSparseFitJob:
    """A cell's set-up up to the first fit."""
    spans: Dict[str, float] = {}
    t_data = time.perf_counter()
    fe, re, scale = config["fixed_effect"], config["random_effect"], config["scale"]
    n, n_val, d = scale["rows"], scale["validation_rows"], fe["d"]
    if fe["intercept_column"] != d - 1 or re["intercept_column"] != re["d_re"] - 1:
        raise ValueError("an intercept must be its shard's last column")
    if sorted(traffic["update_sequence"]) != sorted(traffic["coordinates"]):
        raise ValueError("the update sequence must name every coordinate of the mix once")
    if fitjob.make_mesh(config, chips) is not None:
        raise ValueError("job fit_glmix_sparse runs on one chip")
    data_seed = scale["data_seed"]
    law = gen.draw_law(data_seed, scale["fields"], n, scale["zipf_exponent"])
    shard = gen_user.user_shard(law, scale["user_shard_fields"])
    if law.dim != d or len(law.cardinalities) + 1 != fe["slots_per_row"]:
        raise ValueError("the fields do not add up to the configuration's d and slots a row")
    if shard.dim != re["d_re"] or len(shard.fields) + 1 != re["slots_per_row"]:
        raise ValueError("the user shard's fields do not add up to d_re and its slots a row")
    cols = gen.draw_columns(data_seed, law)
    user = cols[:, scale["user_field"]].astype(np.int64)
    user_cols = gen_user.user_columns(law, shard, cols)
    margin = gen.margins(law, cols) + gen_user.user_margins(
        data_seed, shard, user, user_cols, scale["user_feature_var"], scale["user_intercept_var"]
    )  # the law's intercept is still 0 here
    gen_user.set_intercept(law, margin, scale["click_rate"])
    margin = margin + float(law.beta[-1])
    rows = gen_user.Rows(cols=cols, user_cols=user_cols, user=user,
                         labels=gen.draw_labels(data_seed, margin), margin=margin)
    refuse_a_plane_the_host_cannot_hold(config, rows)
    val = gen_user.draw_rows(data_seed, law, shard, scale, n_sample=n_val, stream=1)
    signs = gen.draw_signs(seed, d)
    user_signs = gen_user.draw_user_signs(seed, shard.dim)
    raw = raw_dataset(config, rows, signs, user_signs)
    validation = raw_dataset(config, val, signs, user_signs)
    spans["data"] = time.perf_counter() - t_data

    t_build = time.perf_counter()
    estimator, datasets = assemble(config, traffic, raw)
    spans["dataset_build"] = time.perf_counter() - t_build
    return GlmixSparseFitJob(
        config=config, traffic=traffic, mesh=None, estimator=estimator, datasets=datasets,
        validation_raw=validation, host=rows, mirror=signs, quotas=None, setup_spans=spans,
        law=law, shard=shard, user_mirror=user_signs,
    )


def store_shape_of(ds) -> dict:
    """What the program built for a random effect, from shapes and the
    dataset's host statistics alone (a program before the ragged store has no
    ``parts``: the logical plane is then what it holds)."""
    features = ds.blocks.features
    e, k, s = (int(v) for v in features.shape)
    parts = getattr(features, "parts", None)
    out = {
        "entities": e, "k_max": k, "s_max": s,
        "plane_gb": e * k * s * features.dtype.itemsize / 1e9,
        "passive_rows": int(len(ds.passive_rows)),
        "over_cap": int(np.sum(np.asarray(ds.entity_counts) >= k)),
    }
    if parts is not None:
        out["buckets"] = [[int(v) for v in p.shape] for p in parts]
        out["store_gb"] = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in parts) / 1e9
    return out


def store_shape(job: GlmixSparseFitJob) -> dict:
    return store_shape_of(job.datasets[job.config["random_effect"]["name"]])


# -- the run protocol: jobs/fit.py ``run``, with this job's build and checks ---


def run(cell, seed: int, seconds: float, traced: bool, device: dict, t_process_start: float) -> str:
    """Set-up, window, correctness; returns the result line."""
    import logging

    import jax

    from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

    from .. import correct_glmix_sparse as correct, observe, trace as trace_mod
    from ..compile_listener import CompileListener
    from ..run import HERE, load_json, load_reader, report_metrics, result_line
    from ..window import run_window

    # -- set-up --------------------------------------------------------------------
    listener = CompileListener().install()
    enable_persistent_compilation_cache()
    logging.getLogger("photon_ml_tpu").setLevel(logging.WARNING)  # no logging in a fit
    job = build(cell.config, cell.traffic, cell.chips, seed)

    t = time.perf_counter()
    base = job.outcome(job.fit())  # compiles, or loads from the cache
    warm1 = time.perf_counter() - t
    listener.phase = "warm"
    t = time.perf_counter()
    second = job.outcome(job.fit())  # must find every program in memory
    warm2 = time.perf_counter() - t
    listener.phase = "setup"
    iters, metrics, evals, passes = base.fingerprint
    notes = {
        "warmup_fit_s": [warm1, warm2],
        "warmup_incomplete": listener.compiles("warm") > 0,
        "shape": job.pass_shape,
        "store": store_shape(job),
        "fingerprint": {"iterations": list(iters), "line_search_evals": list(evals),
                        "feature_passes": [list(p) for p in passes],
                        "validation": [dict(m) for m in metrics]},
    }
    parity = correct.sample_parity(job)
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
        window, collector, counters, device_trace = sparsejob.traced_window(job, check, seconds, listener)
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
    listed = {m["name"] for m in cell.per_layer}
    unlisted = {
        name: load_reader(name).read(observations)
        for name in GLMIX_SPARSE_READERS + BORROWED_READERS if name not in listed
    }
    notes["glmix_sparse"] = {name: value for name, value in unlisted.items() if value is not None}
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
