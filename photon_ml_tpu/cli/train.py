"""GAME training driver.

Reference: photon-client .../cli/game/training/GameTrainingDriver.scala:54-854
(§3.1 call stack): read+index data -> validate -> normalization -> expand
optimization configs -> GameEstimator.fit -> model selection (output mode
ALL/BEST/TUNED) -> optional GP hyperparameter tuning -> save models.

Usage:
  python -m photon_ml_tpu.cli.train \\
    --input-data train.avro --validation-data val.avro \\
    --task logistic_regression \\
    --feature-shard name=globalShard,bags=features \\
    --feature-shard name=userShard,bags=userFeatures \\
    --coordinate name=global,shard=globalShard,optimizer=TRON,reg.type=L2,reg.weights=1|10 \\
    --coordinate name=per-user,shard=userShard,re.type=userId,reg.type=L2,reg.weights=1 \\
    --evaluators AUC,LOGISTIC_LOSS --output-dir out/
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from .. import obs
from ..estimators.game_estimator import GameEstimator, GameResult, GameTransformer
from ..io import (
    read_avro_dataset,
    read_avro_dataset_chunked,
    resolve_ingest_workers,
    save_game_model,
)
from ..io.index_map import IndexMap
from ..io.model_io import load_game_model
from ..parallel import multihost
from ..robust import CheckpointManager, atomic_write, atomic_write_json, faults
from ..robust import distributed as robust_dist
from ..ops.normalization import build_normalization
from ..tuning.rescaling import HyperparameterConfig, ParamRange
from ..tuning.tuner import get_tuner
from ..utils.futures import DaemonFuture, WorkerPool
from ..utils.logging import setup_logging
from ..utils.stats import compute_feature_statistics, save_feature_statistics
from .params import (
    add_common_io_args,
    build_shard_configs,
    parse_coordinate,
    parse_input_columns,
    parse_mesh_shape,
    parse_pipeline_depth,
    plan_host_row_split,
    resolve_input_paths,
)

logger = logging.getLogger("photon_ml_tpu")

OUTPUT_MODE_ALL = "ALL"
OUTPUT_MODE_BEST = "BEST"
OUTPUT_MODE_TUNED = "TUNED"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("photon-ml-tpu game training driver")
    add_common_io_args(p)
    p.add_argument("--validation-data", default=None)
    p.add_argument("--task", default="logistic_regression")
    p.add_argument(
        "--coordinate",
        action="append",
        default=[],
        required=False,
        help="coordinate configuration spec (repeatable, ordered)",
    )
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument(
        "--validation-frequency",
        default="COORDINATE",
        choices=["COORDINATE", "SWEEP"],
        help="evaluate validation metrics after every coordinate update "
        "(reference semantics) or once per sweep (1/n_coordinates of the "
        "metric cost on long sweeps)",
    )
    p.add_argument("--evaluators", default="", help="comma-separated evaluator specs")
    p.add_argument(
        "--validate-data",
        default="disabled",
        choices=["full", "sample", "quarantine", "disabled"],
        help="input data validation (DataValidators semantics): 'full' checks "
        "every row and fails on problems, 'sample' checks ~1%% of rows "
        "(seeded by --seed), 'quarantine' zero-weights offending rows and "
        "keeps training (counted in photon_rows_quarantined_total), "
        "'disabled' skips validation",
    )
    p.add_argument(
        "--seed",
        type=int,
        default=0,
        help="run seed for seeded subsampling (e.g. SAMPLE-mode data "
        "validation draws the same rows across reruns)",
    )
    p.add_argument(
        "--no-divergence-guard",
        action="store_true",
        help="disable the coordinate-descent divergence guard (rejection of "
        "updates with non-finite scores/loss); restores the strictly "
        "zero-fetch sweep",
    )
    p.add_argument(
        "--coordinate-rejection-tolerance",
        type=float,
        default=None,
        help="additionally reject a coordinate update whose training loss "
        "regresses more than this above the coordinate's last accepted "
        "loss (default: finiteness-only rejection)",
    )
    p.add_argument(
        "--pipeline-depth",
        type=parse_pipeline_depth,
        default=1,
        help="sweep pipelining depth (pipeline.depth): 1 = serial loop "
        "(default); >= 2 overlaps host staging, device solves and "
        "validation eval across coordinates with bit-identical accepted "
        "models, ledger and checkpoints (game/pipeline.py). Composes with "
        "--distributed; the execution planner (plan/planner.py) resolves "
        "the full routing",
    )
    p.add_argument(
        "--explain-plan",
        action="store_true",
        help="dry run: resolve the execution plan (per-coordinate routing: "
        "resident vs streamed, sharded vs replicated, pipelined vs serial, "
        "slice/shard geometry) from the flags alone, pretty-print it and "
        "exit 0 WITHOUT reading data or touching a device; a refused "
        "configuration prints its PlanError and exits 1",
    )
    p.add_argument("--output-dir", required=True)
    p.add_argument(
        "--output-mode",
        default=OUTPUT_MODE_BEST,
        choices=[OUTPUT_MODE_ALL, OUTPUT_MODE_BEST, OUTPUT_MODE_TUNED],
    )
    p.add_argument("--model-input-dir", default=None, help="warm-start GAME model")
    p.add_argument(
        "--incremental-training",
        action="store_true",
        help="L2-regularize toward the warm-start model's means weighted by its "
        "precisions (requires --model-input-dir)",
    )
    p.add_argument(
        "--partial-retrain-locked",
        default="",
        help="comma-separated coordinate names to lock (requires --model-input-dir)",
    )
    p.add_argument(
        "--normalization",
        default="NONE",
        choices=["NONE", "STANDARDIZATION", "SCALE_WITH_STANDARD_DEVIATION", "SCALE_WITH_MAX_MAGNITUDE"],
    )
    p.add_argument("--model-sparsity-threshold", type=float, default=0.0)
    p.add_argument("--compute-feature-stats", action="store_true")
    p.add_argument(
        "--hyper-parameter-tuning",
        default="NONE",
        choices=["NONE", "RANDOM", "BAYESIAN"],
    )
    p.add_argument("--hyper-parameter-tuning-iter", type=int, default=10)
    p.add_argument(
        "--trial-lanes",
        type=int,
        default=1,
        help="tuning trials trained concurrently as lambda lanes of one "
        "batched solve (game/lanes.py): K candidates share each "
        "coordinate's data residency and compiled kernel. 1 = the "
        "sequential trial loop; the reference's cluster-of-trials "
        "concurrency mapped onto one chip",
    )
    p.add_argument(
        "--hyper-parameter-config",
        default=None,
        help="JSON tuning config (HyperparameterSerialization.configFromJson "
        "shape: tuning_mode + variables map); overrides the default "
        "per-coordinate log-reg-weight ranges",
    )
    p.add_argument(
        "--hyper-parameter-prior",
        default=None,
        help="JSON prior observations ({'records': [...]}) used to shrink the "
        "search range around the GP-predicted best prior candidate "
        "(ShrinkSearchRange.getBounds)",
    )
    p.add_argument(
        "--hyper-parameter-shrink-radius",
        type=float,
        default=0.25,
        help="unit-cube radius of the shrunk search range around the best "
        "prior candidate",
    )
    p.add_argument(
        "--mesh-shape",
        default="",
        help="device mesh, e.g. data=4,model=2: data axis shards rows/entities, "
        "model axis shards the coefficient dim of layout=tiled coordinates",
    )
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        help="save the model after every coordinate-descent sweep (and each "
        "finished grid config / tuning trial); rerunning the same command "
        "resumes from the last completed unit (crash recovery for long runs)",
    )
    p.add_argument(
        "--checkpoint-every",
        type=int,
        default=0,
        help="additionally snapshot the full coordinate-descent outer-loop "
        "state every N coordinate-update boundaries under "
        "<checkpoint-dir>/cd-boundaries (crash-safe: temp+fsync+rename, "
        "digest-bearing manifest); 0 disables. Requires --checkpoint-dir",
    )
    p.add_argument(
        "--checkpoint-keep",
        type=int,
        default=3,
        help="boundary checkpoints retained (keep-last-K rotation)",
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="resume from the newest VALID boundary checkpoint under "
        "<checkpoint-dir>/cd-boundaries (corrupt ones are skipped with a "
        "warning); training continues from the coordinate update after the "
        "snapshot, bit-identical to the uninterrupted run. Requires "
        "--checkpoint-dir",
    )
    p.add_argument(
        "--distributed",
        default=None,
        help="multi-host: 'coordinator=HOST:PORT,process=I,n=P' (or 'auto' "
        "for env/cluster auto-detection); each process reads its own row "
        "range and only process 0 writes outputs",
    )
    p.add_argument(
        "--collective-timeout",
        type=float,
        default=60.0,
        help="multi-process: budget in seconds for guarded collectives and "
        "the per-sweep liveness barrier; a dead peer raises a typed "
        "DistributedTimeoutError within this budget (plus a peer_lost "
        "flight-recorder dump) instead of hanging forever. 0 disables",
    )
    p.add_argument(
        "--heartbeat-interval",
        type=float,
        default=1.0,
        help="multi-process: seconds between liveness records each process "
        "writes under <checkpoint-dir|metrics-out>/heartbeats (read back as "
        "the photon_dist_heartbeat_age_seconds{process=} gauge and to name "
        "the stale peer in timeout errors). 0 disables the heartbeat plane",
    )
    p.add_argument(
        "--heartbeat-timeout",
        type=float,
        default=10.0,
        help="multi-process: a peer whose newest heartbeat is older than "
        "this many seconds is reported as presumed lost",
    )
    p.add_argument("--log-file", default=None)
    p.add_argument("--log-level", default="INFO")
    p.add_argument(
        "--metrics-out",
        default=None,
        help="directory for machine-readable run telemetry: metrics.jsonl "
        "(one line per span / per-sweep metrics flush; non-coordinator "
        "processes write metrics.p<i>.jsonl beside it — merge with cli "
        "fleetz), metrics.prom (Prometheus text exposition), flight/ "
        "(anomaly-triggered postmortems), and run_summary.json "
        "(coordinator only: total wall time, per-coordinate iteration "
        "stats, convergence-reason histogram)",
    )
    p.add_argument(
        "--trace-out",
        default=None,
        help="write a Chrome-trace / Perfetto JSON timeline of the run here "
        "(coordinator only); per-sweep phase attribution (stage/solve/"
        "score/eval/checkpoint + overlap factor) lands in run_summary.json",
    )
    p.add_argument(
        "--status-port",
        type=int,
        default=None,
        help="serve live /metrics (Prometheus text), /healthz and /statusz "
        "(JSON: current sweep/coordinate, accepted losses, rejection "
        "counters) on this port while training (0 = ephemeral port)",
    )
    p.add_argument(
        "--report-out",
        default=None,
        help="directory for the post-hoc training report (coordinator only): "
        "report.json (machine-readable model/convergence/performance "
        "diagnostics) and report.html (self-contained, stdlib-rendered). "
        "Implies --metrics-out into the same directory when that flag is "
        "absent, so the report directory is a complete artifact set that "
        "`cli report` can rebuild from",
    )
    return p


def run(argv: Optional[List[str]] = None) -> Dict:
    args = build_parser().parse_args(argv)
    setup_logging(args.log_level, args.log_file)
    if args.explain_plan:
        # dry run: resolve and print the execution plan from the flags
        # alone — no data read, no device touched, no jax import
        return _explain_plan(args)
    # PHOTON_FAULTS / PHOTON_FAULTS_SEED: deterministic fault injection at IO
    # and checkpoint boundaries (robust.faults); absent env clears any
    # injector a previous in-process run installed
    faults.install_from_env()

    from ..utils.compile_cache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    if args.distributed:
        if args.distributed == "auto":
            multihost.initialize()
        else:
            multihost.initialize_from_spec(args.distributed)
        import jax  # only safe to touch after jax.distributed.initialize

        if not args.mesh_shape:
            raise SystemExit(
                "--distributed requires --mesh-shape spanning all global "
                f"devices (e.g. data={jax.device_count()}); without a mesh "
                "each process would silently train on only its own row slice"
            )
        logger.info(
            "distributed: process %d/%d, %d local / %d global devices",
            multihost.process_index(), multihost.process_count(),
            jax.local_device_count(), jax.device_count(),
        )
        # stamp span/JSONL lane identity so merged multi-process telemetry
        # stays attributable (obs cannot import jax to ask for itself)
        obs.set_process_index(multihost.process_index())

    t_run0 = time.perf_counter()
    run_t = None
    prev_run = None
    metric_sinks = []
    recorder = None
    status_server = None
    if args.report_out and not args.metrics_out:
        # the report is rebuilt from on-disk artifacts; without a metrics dir
        # the trajectories would have nothing to read, so the report dir
        # doubles as the metrics dir
        args.metrics_out = args.report_out
    telemetry_on = bool(
        args.metrics_out or args.trace_out or args.status_port is not None
    )
    flight = None
    if telemetry_on:
        coordinator = multihost.is_coordinator()
        # every process streams its own telemetry so cli fleetz can merge
        # the fleet view; the coordinator keeps the bare filenames (all
        # single-process tooling reads those), peers suffix their lane
        suffix = "" if coordinator else f".p{multihost.process_index()}"
        run_t = obs.RunTelemetry()
        obs.record_build_info(run_t.registry)
        if args.metrics_out:
            os.makedirs(args.metrics_out, exist_ok=True)
            metric_sinks = [
                obs.JsonlSink(
                    os.path.join(args.metrics_out, f"metrics{suffix}.jsonl")
                ),
                obs.PrometheusSink(
                    os.path.join(args.metrics_out, f"metrics{suffix}.prom")
                ),
            ]
            # anomaly postmortems (solver divergence, coordinate rejection,
            # crash): last window of spans/metrics, one dump per incident
            flight = obs.FlightRecorder(
                os.path.join(args.metrics_out, f"flight{suffix}"),
                run=run_t,
            )
            metric_sinks = metric_sinks + [flight]
        if args.trace_out and coordinator:
            recorder = obs.TimelineRecorder()
            metric_sinks = metric_sinks + [recorder]
        for sink in metric_sinks:
            run_t.register_listener(sink)
        prev_run = obs.set_current_run(run_t)
        if args.status_port is not None and coordinator:
            status_server = obs.IntrospectionServer(run_t, port=args.status_port)
            logger.info(
                "introspection endpoints -> http://127.0.0.1:%d/{metrics,"
                "healthz,statusz}", status_server.port,
            )
        if args.metrics_out and coordinator:
            logger.info("run telemetry -> %s", args.metrics_out)
    # distributed liveness plane (robust.distributed): heartbeat records in
    # a shared directory + a process-wide collective budget, so a dead peer
    # is a bounded-time typed failure instead of a silent hang
    hb_writer = None
    if args.distributed and multihost.process_count() > 1:
        hb_root = args.checkpoint_dir or args.metrics_out
        hb_dir = os.path.join(hb_root, "heartbeats") if hb_root else None
        if hb_dir and args.heartbeat_interval > 0:
            hb_writer = robust_dist.HeartbeatWriter(
                hb_dir,
                multihost.process_index(),
                interval_s=args.heartbeat_interval,
            ).start()
        robust_dist.configure_collectives(
            args.collective_timeout,
            run_dir=hb_dir,
            stale_after_s=args.heartbeat_timeout,
        )
    try:
        summary = _run_training(args, run_t, metric_sinks, t_run0, recorder)
    except BaseException as exc:
        # crash-flush: a mid-sweep abort (including an injected
        # SimulatedKill) still leaves run_summary.json on disk with the
        # partial timeline / phase attribution collected so far, marked
        # "aborted" — the report and post-mortems read it
        if flight is not None:
            # a collective timeout / stale peer is the survivor's view of a
            # PEER's death: dump it under its own trigger kind so the fleet
            # postmortem separates "I crashed" from "my peer vanished"
            kind = (
                "peer_lost"
                if isinstance(
                    exc,
                    (
                        robust_dist.DistributedTimeoutError,
                        robust_dist.PeerLostError,
                    ),
                )
                else "crash"
            )
            try:
                flight.trigger(
                    kind, detail=f"{type(exc).__name__}: {exc}"
                )
            except Exception:
                obs.swallowed_error("cli.flightrec_crash_dump")
        if run_t is not None and multihost.is_coordinator():
            try:
                _write_run_summary(args, run_t, recorder, t_run0, aborted=True)
            except Exception:
                obs.swallowed_error("cli.run_summary_flush")
                logger.exception("could not flush partial run summary")
        raise
    finally:
        if hb_writer is not None:
            hb_writer.stop()
        robust_dist.clear_collectives()
        if status_server is not None:
            status_server.stop()
        if run_t is not None:
            # final flush: last metrics.jsonl line + the final metrics.prom
            run_t.close()
            obs.set_current_run(prev_run)
    if args.report_out and multihost.is_coordinator():
        _emit_report(args)
    return summary


def _explain_plan(args) -> Dict:
    """``--explain-plan``: resolve the ExecutionPlan from the parsed flags and
    pretty-print it, reading no data and touching no device (the planner is
    jax-free, so this works on a host with no accelerator runtime). A refused
    configuration prints its PlanError and exits 1; a resolved plan prints
    and the process exits 0 (in-process callers get the plan document)."""
    from ..plan import PlanError, resolve as resolve_plan
    from .params import parse_kv

    coord_specs = args.coordinate or [
        "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1"
    ]
    try:
        coords = [parse_coordinate(s) for s in coord_specs]
        if args.incremental_training:
            for cc in coords:
                cc.regularize_by_prior = True
        mesh = None
        if args.mesh_shape:
            kv = parse_kv(args.mesh_shape)
            mesh = {"data": int(kv.pop("data", 1)),
                    "model": int(kv.pop("model", 1))}
            if kv:
                raise SystemExit(f"unknown mesh keys: {sorted(kv)}")
        n_processes = 1
        if args.distributed and args.distributed != "auto":
            for part in args.distributed.split(","):
                k, _, v = part.partition("=")
                if k.strip() == "n":
                    n_processes = int(v)
        dims = None
        if args.feature_index_dir:
            # index maps are metadata, not training data: load them so the
            # plan carries concrete slice geometry; advisory, never fatal
            try:
                from ..io.index_map import load_partitioned

                dims = {
                    s: load_partitioned(args.feature_index_dir, s).size
                    for s in build_shard_configs(args)
                }
            except Exception:  # photon: ignore[R4] - dims only enrich the
                dims = None  # printed geometry; a dry run must never fail here
        plan = resolve_plan(
            coords,
            mesh=mesh,
            n_processes=n_processes,
            pipeline_depth=args.pipeline_depth,
            trial_lanes=int(getattr(args, "trial_lanes", 1) or 1),
            distributed=bool(args.distributed),
            partial_retrain_locked=tuple(
                c for c in args.partial_retrain_locked.split(",") if c
            ),
            normalization=args.normalization,
            dims=dims,
        )
    except PlanError as e:
        print(f"plan refused: {e}", file=sys.stderr)
        raise SystemExit(1)
    print(plan.pretty())
    return {"plan": plan.to_dict()}


def _run_training(args, run_t, metric_sinks, t_run0, recorder=None) -> Dict:
    shards = build_shard_configs(args)
    id_tags = [t for t in args.id_tags.split(",") if t]
    coord_specs = args.coordinate or [
        "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1"
    ]
    coords = [parse_coordinate(s) for s in coord_specs]
    for cc in coords:
        if cc.is_random_effect and cc.random_effect_type not in id_tags:
            id_tags.append(cc.random_effect_type)

    input_paths = resolve_input_paths(args)
    input_columns = parse_input_columns(args)
    logger.info("reading training data from %s", input_paths)
    index_maps = None
    if args.feature_index_dir:
        from ..io.index_map import load_partitioned

        index_maps = {s: load_partitioned(args.feature_index_dir, s) for s in shards}

    row_range = None
    equal_share = None
    part_counts = None
    if multihost.process_count() > 1:
        if index_maps is None:
            raise SystemExit(
                "multi-process training requires --feature-index-dir "
                "(host-local index maps would disagree across hosts)"
            )
        row_range, part_counts = plan_host_row_split(input_paths)
        total_rows = sum(part_counts.values())
        # all hosts pad their slice to a common size so every process
        # contributes equal local shapes to the global arrays
        equal_share = multihost.equal_host_share(total_rows)
        logger.info(
            "process %d reads rows [%d, %d) of %d (padded to %d)",
            multihost.process_index(), row_range[0], row_range[1], total_rows,
            equal_share,
        )
    ingest_pool = None
    if multihost.process_count() == 1:
        # pipelined pooled ingest (io/data.read_avro_dataset_chunked):
        # --ingest-workers parts decode concurrently on the shared pool
        # (sequenced back to file order, bit-identical at any count) while
        # the consumer converts each part to columnar arrays and frees it —
        # decode overlaps dataset build, peak record RSS stays bounded by
        # the queue depth, and the SAME pool later runs the background
        # validation decode instead of oversubscribing cores with a second
        # thread fleet
        n_ingest_workers = resolve_ingest_workers(args.ingest_workers)
        ingest_pool = WorkerPool(n_ingest_workers, name="photon-ingest")
        try:
            raw, index_maps = read_avro_dataset_chunked(
                input_paths,
                shards,
                index_maps=index_maps,
                id_tag_columns=id_tags,
                response_column=args.response_column,
                columns=input_columns,
                workers=n_ingest_workers,
                pool=ingest_pool,
            )
        except BaseException:
            # a failed read leaves no future behind — release the workers
            # instead of leaking idle daemon threads across in-process runs
            ingest_pool.close()
            raise
    else:
        # multi-process: row-windowed read on the main thread (collective
        # ordering across hosts must stay deterministic)
        raw, index_maps = read_avro_dataset(
            input_paths,
            shards,
            index_maps=index_maps,
            id_tag_columns=id_tags,
            response_column=args.response_column,
            columns=input_columns,
            row_range=row_range,
            part_counts=part_counts,
        )
    try:
        if row_range is not None:
            raw.global_row_start = row_range[0]
        if args.validate_data != "disabled":
            # validate BEFORE multi-process padding: pad rows are synthetic
            # zero-weight rows that would dilute the sample and trip nothing
            from ..io import validators

            mode = {
                "full": validators.VALIDATE_FULL,
                "sample": validators.VALIDATE_SAMPLE,
                "quarantine": validators.VALIDATE_QUARANTINE,
            }[args.validate_data]
            validators.validate_dataset(raw, args.task, mode, rng_seed=args.seed)
        if equal_share is not None:
            raw = raw.pad_rows(equal_share)
        logger.info(
            "training rows: %d; shard dims: %s", raw.n_rows, raw.shard_dims
        )

        validation = None
        if args.validation_data:
            def _read_validation():
                v, _ = read_avro_dataset(
                    args.validation_data,
                    shards,
                    index_maps=index_maps,
                    id_tag_columns=id_tags,
                    response_column=args.response_column,
                    columns=input_columns,
                )
                return v

            if multihost.process_count() == 1:
                # ingest overlap: decode validation on the SAME worker pool
                # the training ingest used (the native Avro decoder releases
                # the GIL) while the training datasets build and upload; the
                # estimator resolves the future only when the validation
                # context is first needed (executor-parallel decode,
                # AvroDataReader.scala:165-209). Pool workers are daemon
                # threads (vs ThreadPoolExecutor): a crash elsewhere exits
                # bounded instead of blocking on concurrent.futures' atexit
                # join of a decode that nobody will consume
                validation = ingest_pool.submit(_read_validation)
            else:
                # multi-process: keep the read on the main thread (collective
                # ordering across hosts must stay deterministic)
                validation = _read_validation()
    finally:
        if ingest_pool is not None:
            # stop accepting work; the already-queued validation decode
            # still drains. Repeated in-process train_run calls then never
            # accumulate idle worker threads
            ingest_pool.close()

    # normalization from feature statistics (GameTrainingDriver:555-571)
    if args.normalization != "NONE":
        for cc in coords:
            if not cc.is_random_effect:
                stats = compute_feature_statistics(raw, cc.feature_shard)
                cc.normalization = build_normalization(
                    args.normalization,
                    stats["mean"],
                    stats["variance"],
                    stats["max_magnitude"],
                    intercept_index=index_maps[cc.feature_shard].intercept_index,
                )

    if args.compute_feature_stats:
        for shard in shards:
            # the statistics reduce is a COLLECTIVE (cross-host allgather of
            # moment sums): every process must participate; only the
            # coordinator writes
            stats = compute_feature_statistics(raw, shard)
            if multihost.is_coordinator():
                os.makedirs(args.output_dir, exist_ok=True)
                save_feature_statistics(
                    os.path.join(args.output_dir, f"feature-stats-{shard}.avro"),
                    stats,
                    index_maps[shard],
                )

    initial_model = None
    if args.model_input_dir:
        if args.incremental_training:
            # prior-compatibility check BEFORE the load: load_game_model keys
            # coefficients off (name, term) and silently drops features the
            # current index cannot host — acceptable for plain warm-start
            # initialization, fatal for priors (a dropped feature re-centers
            # its prior at zero without saying so). Indices that merely
            # permuted remap losslessly; missing features are refused.
            from ..io.model_io import check_prior_compatibility

            compat = check_prior_compatibility(args.model_input_dir, index_maps)
            logger.info(
                "incremental prior feature-index compatibility: %s",
                ", ".join(f"{s}={v}" for s, v in sorted(compat.items())),
            )
        initial_model = load_game_model(args.model_input_dir, index_maps, task=args.task)
    if args.incremental_training:
        if initial_model is None:
            raise SystemExit("--incremental-training requires --model-input-dir")
        for cc in coords:
            cc.regularize_by_prior = True

    evaluators = [e for e in args.evaluators.split(",") if e]
    mesh = parse_mesh_shape(args.mesh_shape)

    estimator = GameEstimator(
        task=args.task,
        coordinate_configs=coords,
        n_cd_iterations=args.coordinate_descent_iterations,
        evaluator_specs=evaluators,
        partial_retrain_locked=[
            c for c in args.partial_retrain_locked.split(",") if c
        ],
        mesh=mesh,
        validation_frequency=args.validation_frequency,
        divergence_guard=not args.no_divergence_guard,
        rejection_tolerance=args.coordinate_rejection_tolerance,
        pipeline_depth=args.pipeline_depth,
    )
    if int(getattr(args, "trial_lanes", 1) or 1) > 1:
        from ..game.lanes import check_lane_composition

        # pre-empt lane-composition refusals at plan time — BEFORE any
        # dataset build or grid-config training, the same check the lane
        # path re-runs at fit_lanes time (and --explain-plan dry-runs)
        check_lane_composition(
            estimator,
            int(args.trial_lanes),
            distributed=multihost.process_count() > 1,
        )
    for sink in metric_sinks:
        # estimator lifecycle events (TrainingStart/OptimizationLog/Finish)
        # land in the same JSONL stream as spans and metric flushes
        estimator.register_listener(sink)
    if run_t is not None:
        # attach the resolved execution plan so run_summary.json and the
        # live /statusz endpoint both surface the per-coordinate routing
        run_t.execution_plan = estimator.execution_plan.to_dict()
    ckpt = None
    # datasets are reg-weight-independent: build once, lazily (an idempotent
    # rerun of a completed checkpoint must not pay the device build), and
    # share across grid configs and tuning trials
    datasets_cache: Dict[str, object] = {}

    def get_datasets():
        if "d" not in datasets_cache:
            datasets_cache["d"] = estimator.prepare_datasets(raw)
        return datasets_cache["d"]

    # fine-grained crash safety (robust.checkpoint): snapshot the CD
    # outer-loop state at coordinate-update boundaries, resume bit-exact
    cd_manager = None
    resume_snap = None
    ckpt_topology = None
    if args.checkpoint_every or args.resume:
        from ..plan import plan_fingerprint

        # the topology contract both sides of a checkpoint speak: saves
        # stamp it into the manifest, resumes judge the saved stamp through
        # plan.check_checkpoint_topology. global_rows is the PADDED total
        # (equal_host_share rows per process), so the number itself encodes
        # whether per-host shard boundaries agree across process counts
        ckpt_topology = {
            "n_processes": multihost.process_count(),
            "mesh_axes": estimator.execution_plan.mesh_axes,
            "plan_fingerprint": plan_fingerprint(estimator.execution_plan),
            "global_rows": int(raw.n_rows) * multihost.process_count(),
        }
    if args.checkpoint_every:
        if not args.checkpoint_dir:
            raise SystemExit("--checkpoint-every requires --checkpoint-dir")
        cd_manager = CheckpointManager(
            os.path.join(args.checkpoint_dir, "cd-boundaries"),
            keep_last=args.checkpoint_keep,
            every=args.checkpoint_every,
            process=multihost.process_index(),
            n_processes=multihost.process_count(),
            topology={
                "mesh_axes": ckpt_topology["mesh_axes"],
                "plan_fingerprint": ckpt_topology["plan_fingerprint"],
            },
        )
    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        mgr = cd_manager or CheckpointManager(
            os.path.join(args.checkpoint_dir, "cd-boundaries"),
            keep_last=args.checkpoint_keep,
        )
        # boundary checkpoints are coordinator-written; load there and
        # broadcast so non-shared filesystems resume consistently
        if multihost.is_coordinator():
            resume_snap = mgr.latest_valid(
                expect_coordinate_order=[cc.name for cc in coords],
                expect_topology=ckpt_topology,
            )
        if multihost.process_count() > 1:
            resume_snap = multihost.broadcast_object(resume_snap)
        if resume_snap is None:
            logger.info("--resume: no valid boundary checkpoint; starting fresh")

    with obs.span("train"):
        if args.checkpoint_dir:
            ckpt = _Checkpoint.open(args, coords, index_maps)
            results = ckpt.fit_grid(
                estimator, raw, validation, get_datasets, initial_model,
                cd_manager=cd_manager, resume_snapshot=resume_snap,
            )
        else:
            results = estimator.fit(
                raw, validation=validation, initial_model=initial_model,
                datasets=get_datasets(),
            )

        # optional hyperparameter auto-tuning (GameTrainingDriver:642-673)
        tuned_results: List[GameResult] = []
        if args.hyper_parameter_tuning != "NONE" and validation is not None:
            tuned_results = _run_tuning(
                args, estimator, raw, _resolve_validation(validation), coords,
                results, ckpt=ckpt, datasets_fn=get_datasets,
                resume_snap=resume_snap,
            )

    all_results = list(results) + tuned_results
    best = estimator.select_best(all_results)

    summary = {
        "task": args.task,
        "configs": [
            {
                "reg_weights": r.config,
                "metrics": None if r.evaluation is None else r.evaluation.metrics,
            }
            for r in all_results
        ],
        "best": {
            "reg_weights": best.config,
            "metrics": None if best.evaluation is None else best.evaluation.metrics,
        },
    }
    if run_t is not None and multihost.is_coordinator():
        # run_summary.json is a fleet-level document (one per run, not per
        # process); peers contribute via their metrics.p*.jsonl streams
        _write_run_summary(args, run_t, recorder, t_run0, summary=summary)
    if not multihost.is_coordinator():
        # only process 0 writes outputs (the reference's driver-to-HDFS role)
        return summary

    os.makedirs(args.output_dir, exist_ok=True)
    atomic_write_json(
        os.path.join(args.output_dir, "training-summary.json"),
        summary, indent=2, default=float,
    )

    to_save = all_results if args.output_mode == OUTPUT_MODE_ALL else [best]
    for i, r in enumerate(to_save):
        name = "best" if r is best and args.output_mode != OUTPUT_MODE_ALL else f"model-{i}"
        save_game_model(
            os.path.join(args.output_dir, "models", name),
            r.model,
            index_maps,
            metadata={"regWeights": r.config},
            sparsity_threshold=args.model_sparsity_threshold,
        )
    logger.info("saved %d model(s) to %s", len(to_save), args.output_dir)
    return summary


def _write_run_summary(args, run_t, recorder, t_run0, summary=None,
                       aborted=False) -> None:
    """Write run_summary.json (+ the Chrome trace) from the run's registry.

    Shared between the end-of-run path and the crash-flush in ``run()``: on
    a mid-sweep abort ``summary`` is None, the document carries
    ``"aborted": true``, and the timeline holds every span that closed
    before the abort."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # photon: ignore[R4] - no-jax fallback, host-only sample
        devices = ()
    # final sample so host/device watermarks are present even for runs that
    # never reached a sweep boundary
    obs.sample_memory(run_t.registry, devices=devices)
    doc = obs.build_run_summary(
        run_t.registry, total_wall_seconds=time.perf_counter() - t_run0
    )
    doc["task"] = getattr(args, "task", None) if summary is None else summary["task"]
    plan = getattr(run_t, "execution_plan", None)
    if plan is not None:
        doc["plan"] = plan
    if summary is not None:
        doc["best"] = summary["best"]
    if aborted:
        doc["aborted"] = True
    if recorder is not None:
        # drain the listener queue: on the normal path the "train" span has
        # closed by here, so the timeline holds the whole run
        doc["timeline"] = recorder.phase_attribution()
        recorder.write_chrome_trace(args.trace_out)
        logger.info("chrome trace -> %s (load at ui.perfetto.dev)",
                    args.trace_out)
    # --trace-out without --metrics-out still gets a run_summary.json
    # (the phase attribution belongs with the trace): next to the trace
    summary_dir = args.metrics_out or os.path.dirname(
        os.path.abspath(args.trace_out or "")
    )
    if args.metrics_out or args.trace_out:
        atomic_write_json(
            os.path.join(summary_dir, "run_summary.json"),
            doc, indent=2, default=float,
        )


def _emit_report(args) -> None:
    """Build report.json + report.html under --report-out.

    Reads back the artifacts just written to disk (run_summary.json,
    metrics.jsonl, training-summary.json, saved models) rather than any
    in-memory state, so a later ``cli report`` over the same directory
    reproduces report.json byte-identically."""
    from ..obs import report as report_mod

    try:
        inputs = report_mod.collect_training_inputs(
            summary_dir=args.metrics_out or (
                os.path.dirname(os.path.abspath(args.trace_out))
                if args.trace_out else None
            ),
            output_dir=args.output_dir,
            checkpoint_dir=args.checkpoint_dir,
            feature_index_dir=args.feature_index_dir,
        )
        paths = report_mod.write_report(
            report_mod.build_report(inputs), args.report_out
        )
    except Exception:
        # the report is a post-hoc convenience; a rendering bug must not
        # turn a finished (and saved) training run into a CLI failure
        obs.swallowed_error("cli.report_out")
        logger.exception("training report generation failed")
        return
    logger.info("training report -> %s", paths["html"])


# shared with io/data's chunked training-data reader (utils/futures.py);
# the old name stays as an alias for anything importing it from here
_DaemonFuture = DaemonFuture


def _resolve_validation(validation):
    """Unwrap a deferred validation dataset (Future from the background
    decode thread); already-resolved datasets pass through."""
    return validation.result() if hasattr(validation, "result") else validation


def _run_tuning(args, estimator, raw, validation, coords, prior_results,
                ckpt=None, datasets_fn=None, resume_snap=None):
    """GP/random tuning over per-coordinate log10 reg weights
    (GameEstimatorEvaluationFunction semantics: candidate <-> (log lambda,...)).

    The explicit grid results seed the tuner as observations
    (GameTrainingDriver.scala:666 `convertObservations(models)`), so the GP
    starts warm instead of re-exploring the grid. An optional JSON tuning
    config overrides the search ranges; optional prior observations shrink
    the range around the GP-predicted best (ShrinkSearchRange.getBounds).

    With ``ckpt``, each finished trial is recorded (model + metrics + unit
    vector); a resumed run replays recorded trials as observations and only
    runs the remainder. Trials always train the FULL
    --coordinate-descent-iterations (the estimator's sweep count is never
    mutated by checkpoint resume — round-3 advisor finding).
    """
    from ..tuning import Observation, prior_to_json

    tunable = [cc.name for cc in coords if cc.name not in estimator.partial_retrain_locked]
    hp = _build_tuning_config(args, tunable)
    names = [p.name for p in hp.params]
    higher_better = _higher_is_better(args.evaluators)
    sign = -1.0 if higher_better else 1.0
    results: List[GameResult] = []

    def evaluate(unit_vec):
        faults.check("tuning.trial")
        native = hp.scale_up(unit_vec)
        weights = {
            n.removesuffix(".reg_weight"): float(v) for n, v in zip(names, native)
        }
        import dataclasses as dc

        cfgs = []
        for cc in coords:
            w = weights.get(cc.name, cc.config.reg_weight)
            cfgs.append(dc.replace(cc, reg_weights=(w,)))
        est = GameEstimator(
            task=args.task,
            coordinate_configs=cfgs,
            n_cd_iterations=args.coordinate_descent_iterations,
            evaluator_specs=[e for e in args.evaluators.split(",") if e],
            partial_retrain_locked=list(estimator.partial_retrain_locked),
            mesh=estimator.mesh,
            validation_frequency=estimator.validation_frequency,
            divergence_guard=estimator.divergence_guard,
            rejection_tolerance=estimator.rejection_tolerance,
            pipeline_depth=estimator.pipeline_depth,
        )
        r = est.fit(
            raw, validation=validation,
            datasets=datasets_fn() if datasets_fn is not None else None,
        )[0]
        results.append(r)
        metric = r.evaluation.primary_metric
        # the tuner minimizes; negate higher-is-better metrics
        value = sign * metric
        if ckpt is not None:
            ckpt.record_trial(unit_vec, value, r)
        obs.current_run().registry.counter(
            "photon_tuning_trials_total", "tuning trials completed"
        ).inc()
        return value, r

    def evaluate_batch(cands):
        """Train a whole candidate batch as lambda lanes of ONE solve
        (game/lanes.py): every lane shares each coordinate's data residency
        and compiled executable, so K trials cost roughly one K-lane-wide
        solve instead of K sequential fits."""
        registry = obs.current_run().registry
        combos = []
        for unit_vec in cands:
            native = hp.scale_up(unit_vec)
            weights = {
                n.removesuffix(".reg_weight"): float(v)
                for n, v in zip(names, native)
            }
            combos.append(
                {cc.name: weights.get(cc.name, cc.config.reg_weight) for cc in coords}
            )
        est = GameEstimator(
            task=args.task,
            coordinate_configs=list(coords),
            n_cd_iterations=args.coordinate_descent_iterations,
            evaluator_specs=[e for e in args.evaluators.split(",") if e],
            partial_retrain_locked=list(estimator.partial_retrain_locked),
            mesh=estimator.mesh,
            validation_frequency=estimator.validation_frequency,
            divergence_guard=estimator.divergence_guard,
            rejection_tolerance=estimator.rejection_tolerance,
            pipeline_depth=estimator.pipeline_depth,
        )
        with obs.span("tuning.batch", phase="tuning", lanes=len(cands)) as span:
            lane_results = est.fit_lanes(
                raw, combos, validation=validation,
                datasets=datasets_fn() if datasets_fn is not None else None,
            )
        registry.histogram(
            "photon_tuning_batch_wall_seconds",
            "wall time of one lane-batched tuning trial batch",
        ).observe(span.duration_s)
        out = []
        for unit_vec, r in zip(cands, lane_results):
            # record lanes IN LANE ORDER: a mid-batch fault leaves a recorded
            # prefix whose count alone realigns the (chunking-invariant)
            # tuner candidate sequence on resume
            faults.check("tuning.trial")
            results.append(r)
            value = sign * r.evaluation.primary_metric
            if ckpt is not None:
                ckpt.record_trial(
                    unit_vec, value, r, lane=r.trackers.get("lane")
                )
            registry.counter(
                "photon_tuning_trials_total", "tuning trials completed"
            ).inc()
            out.append((value, r))
        return out

    # seed the tuner with the explicit-grid results (convertObservations);
    # skip grid points outside the search range — scale_down would clip them
    # to the cube edge and attach a far-away point's metric to it
    observations = []
    for r in prior_results or []:
        if r.evaluation is None:
            continue
        native = _native_vec(r, names)
        if any(not (p.min <= v <= p.max) for p, v in zip(hp.params, native)):
            continue
        observations.append(
            Observation(
                candidate=hp.scale_down(native),
                value=sign * r.evaluation.primary_metric,
                artifact=r,
            )
        )

    # replay checkpointed trials: reconstruct their results and re-seed the
    # tuner so only the remaining trial budget runs
    n_iter = args.hyper_parameter_tuning_iter
    if ckpt is not None:
        for rec in ckpt.completed_trials():
            r = ckpt._reconstruct(rec)
            results.append(r)
            observations.append(
                Observation(
                    candidate=np.asarray(rec["unit"]),
                    value=float(rec["value"]),
                    artifact=r,
                )
            )
        n_done = len(ckpt.completed_trials())
        if resume_snap is not None:
            # boundary manifests record the trial count at write time; a
            # lost/older checkpoint-state.json must not replay candidates the
            # manifest proves were already drawn — burn those candidates
            # (their observations are gone, but a deterministic tuner's
            # sequence stays aligned via skip=)
            from_manifest = int(resume_snap.manifest.get("tuner_trials", 0))
            if from_manifest > n_done:
                logger.warning(
                    "checkpoint manifest records %d tuning trials but state "
                    "has %d; skipping the %d lost candidates",
                    from_manifest, n_done, from_manifest - n_done,
                )
                n_done = from_manifest
        if n_done:
            logger.info("checkpoint: %d/%d tuning trials already run", n_done, n_iter)
        n_iter = max(n_iter - n_done, 0)

    trial_lanes = int(getattr(args, "trial_lanes", 1) or 1)
    if n_iter > 0:
        tuner = get_tuner(args.hyper_parameter_tuning)
        if trial_lanes > 1:
            from ..game.lanes import check_lane_composition

            check_lane_composition(
                estimator, trial_lanes,
                distributed=multihost.process_count() > 1,
            )
            tuner.search_batched(
                n_iter,
                hp.dim,
                evaluate_batch,
                trial_lanes,
                observations=observations,
                discrete_params=hp.discrete_dims(),
                seed=0,
                # resumed deterministic (Sobol) searches must continue the
                # original candidate sequence, not repeat its prefix — the
                # Sobol stream is chunking-invariant, so the trial COUNT
                # alone realigns it even across a mid-batch kill
                skip=args.hyper_parameter_tuning_iter - n_iter,
            )
        else:
            tuner.search(
                n_iter,
                hp.dim,
                evaluate,
                observations=observations,
                discrete_params=hp.discrete_dims(),
                seed=0,
                # resumed deterministic (Sobol) searches must continue the
                # original candidate sequence, not repeat its prefix
                skip=args.hyper_parameter_tuning_iter - n_iter,
            )

    # record every (grid + tuned) observation as a reusable prior file
    priors = [
        (_native_vec(r, names), r.evaluation.primary_metric)
        for r in list(prior_results or []) + results
        if r.evaluation is not None
    ]

    if multihost.is_coordinator():
        os.makedirs(args.output_dir, exist_ok=True)
        with atomic_write(
            os.path.join(args.output_dir, "hyperparameter-prior.json"), "w"
        ) as f:
            f.write(prior_to_json(names, priors))
    return results


class _Checkpoint:
    """Per-sweep crash-recovery checkpointing across reg-weight grids AND
    tuning trials (beyond the reference, which only has model-granularity
    warm start; round-3 verdict item 9).

    State (``checkpoint-state.json``, version 2, atomically replaced):
      grid           expanded combo list this run must train, in order
      completed      per finished combo: model dir + validation metrics
      current        mid-combo progress: index, completed sweeps, model dir
      tuning_trials  per finished tuning trial: unit vector, value, model dir

    Resume = rerun the same command: finished combos/trials reconstruct from
    their saved models + recorded metrics, the in-flight combo warm-starts
    from its last completed sweep, and tuning resumes with the recorded
    trials re-seeded as GP observations.

    Multi-process: only process 0 writes, and its state is AUTHORITATIVE —
    every process allgathers the state views and adopts the coordinator's
    (warned when they differ), and checkpointed models load on the
    coordinator and one-to-all broadcast. A shared filesystem is therefore
    NOT required; collective schedules stay aligned because all processes
    run the coordinator's state (round-3 advisor finding: divergent
    `remaining` counts => mismatched collective schedules, hang).

    With --validation-data, best-model tracking within the in-flight combo
    restarts at the resume point: pre-crash sweeps are no longer best-model
    candidates (the checkpoint stores last-sweep models, not the tracked
    best)."""

    def __init__(self, args, coords, index_maps, state, state_path):
        self.args = args
        self.coords = coords
        self.index_maps = index_maps
        self.state = state
        self.state_path = state_path
        self.dir = args.checkpoint_dir

    @classmethod
    def open(cls, args, coords, index_maps):

        names = [cc.name for cc in coords]
        import itertools

        combos = [
            dict(zip(names, map(float, c)))
            for c in itertools.product(*[cc.grid() for cc in coords])
        ]
        state_path = os.path.join(args.checkpoint_dir, "checkpoint-state.json")
        state = None
        if os.path.exists(state_path):
            with open(state_path) as f:
                state = json.load(f)
        if multihost.process_count() > 1:
            # the COORDINATOR's state is authoritative: it is the only writer
            # (process-0-only writes), so a non-shared filesystem leaves the
            # other processes stale or empty — broadcast process 0's view
            # instead of refusing (r3 advisor suggestion; model files are
            # broadcast the same way in _load_model). The collective schedule
            # stays aligned because every process now runs the same state.
            views = multihost.allgather_object(json.dumps(state, sort_keys=True))
            if len(set(views)) != 1:
                logger.warning(
                    "checkpoint states differ across processes (non-shared "
                    "filesystem); adopting the coordinator's state"
                )
            state = json.loads(views[0])
        if state is None:
            state = {
                "version": 2,
                "grid": combos,
                "n_cd_iterations": args.coordinate_descent_iterations,
                "completed": [],
                "current": None,
                "tuning_trials": [],
            }
        elif state.get("version") != 2:
            raise SystemExit(
                f"checkpoint at {args.checkpoint_dir} uses state version "
                f"{state.get('version')}; this build writes version 2 — pass "
                "a fresh --checkpoint-dir"
            )
        elif state.get("grid") != combos:
            raise SystemExit(
                f"checkpoint at {args.checkpoint_dir} was written for grid "
                f"{state.get('grid')}, not {combos}; pass a fresh "
                "--checkpoint-dir"
            )
        elif state.get("n_cd_iterations") != args.coordinate_descent_iterations:
            raise SystemExit(
                f"checkpoint at {args.checkpoint_dir} was written for "
                f"{state.get('n_cd_iterations')} coordinate-descent "
                "iterations; resume with the same "
                "--coordinate-descent-iterations (completed configurations "
                "trained that many sweeps), or warm-start a fresh run from "
                "the final model via --model-input-dir"
            )
        if args.validation_data:
            logger.warning(
                "--checkpoint-dir with --validation-data: on resume, "
                "best-model tracking only sees post-resume sweeps of the "
                "in-flight configuration"
            )
        os.makedirs(args.checkpoint_dir, exist_ok=True)
        return cls(args, coords, index_maps, state, state_path)

    def _write(self):

        if not multihost.is_coordinator():
            return
        atomic_write_json(self.state_path, self.state)

    def _load_model(self, model_dir):
        # model files exist only where the coordinator wrote them
        # (process-0-only writes): load there, one-to-all broadcast to the
        # others — checkpoint resume no longer requires a shared filesystem,
        # and the payload crosses the fabric exactly once
        if multihost.process_count() > 1:
            model = None
            if multihost.is_coordinator():
                model = load_game_model(
                    os.path.join(self.dir, model_dir),
                    self.index_maps,
                    task=self.args.task,
                )
            return multihost.broadcast_object(model)
        return load_game_model(
            os.path.join(self.dir, model_dir), self.index_maps, task=self.args.task
        )

    def _save_model(self, model_dir, game_model, reg_weights):

        if multihost.is_coordinator():
            save_game_model(
                os.path.join(self.dir, model_dir), game_model, self.index_maps,
                metadata={"regWeights": reg_weights},
            )

    def _reconstruct(self, rec):
        ev = None
        if rec.get("metrics"):
            from ..evaluation.suite import EvaluationResults

            ev = EvaluationResults(
                primary_name=rec["primary_name"], metrics=rec["metrics"]
            )
        return GameResult(
            model=self._load_model(rec["model_dir"]),
            config=rec["reg_weights"],
            evaluation=ev,
            trackers={},
        )

    def fit_grid(self, estimator, raw, validation, datasets_fn, initial_model,
                 cd_manager=None, resume_snapshot=None):
        """``cd_manager`` (robust.CheckpointManager) adds coordinate-update-
        boundary snapshots on top of the per-sweep model saves;
        ``resume_snapshot`` (robust.CheckpointSnapshot) resumes its combo
        mid-sweep, bit-identical. The two granularities compose: whichever
        record is further along wins, and boundary manifests carry
        ``combo_index`` / ``sweep_offset`` so a snapshot written during a
        sweep-level-resumed run still maps back to global sweep numbering."""
        import shutil

        # checkpointed grids read validation directly (recovered-metric
        # scoring): resolve any deferred decode up front
        validation = _resolve_validation(validation)

        combos = self.state["grid"]
        n_iter = self.args.coordinate_descent_iterations
        results: List[GameResult] = []
        prev = initial_model
        for rec in self.state["completed"]:
            r = self._reconstruct(rec)
            results.append(r)
            prev = r.model
        if self.state["completed"]:
            logger.info(
                "checkpoint: %d/%d configurations already trained",
                len(self.state["completed"]), len(combos),
            )

        for k in range(len(results), len(combos)):
            done = 0
            cur = self.state.get("current")
            if cur and cur.get("index") == k and cur.get("completed_sweeps", 0) > 0:
                done = int(cur["completed_sweeps"])
            snap = None
            if (
                resume_snapshot is not None
                and int(resume_snapshot.manifest.get("combo_index", -1)) == k
            ):
                snap = resume_snapshot
                # global sweep the snapshot sits in = offset of the run that
                # wrote it + its local iteration; an older sweep-level record
                # must not win over it (and vice versa)
                snap_global = int(snap.manifest.get("sweep_offset", 0)) + int(
                    snap.iteration
                )
                if snap_global < done:
                    logger.info(
                        "config %d: per-sweep record (sweep %d) is ahead of "
                        "the boundary snapshot (sweep %d); using the former",
                        k, done, snap_global,
                    )
                    snap = None
            if snap is not None:
                done = int(snap.manifest.get("sweep_offset", 0))
                logger.info(
                    "resuming config %d from boundary snapshot %s "
                    "(iter %d after coordinate %s)",
                    k, snap.path, snap.iteration, snap.coordinate,
                )
            elif done > 0:
                prev = self._load_model(cur["model_dir"])
                logger.info(
                    "resuming config %d from sweep %d/%d", k, done, n_iter
                )

            def sweep_fn(reg_weights, iteration, game_model, _k=k, _done=done):
                j = _done + iteration + 1
                model_dir = f"config-{_k:03d}-sweep-{j:04d}"
                self._save_model(model_dir, game_model, reg_weights)
                self.state["current"] = {
                    "index": _k, "completed_sweeps": j, "model_dir": model_dir,
                }
                self._write()
                prev_dir = os.path.join(
                    self.dir, f"config-{_k:03d}-sweep-{j - 1:04d}"
                )

                if multihost.is_coordinator() and os.path.isdir(prev_dir):
                    shutil.rmtree(prev_dir, ignore_errors=True)

            boundary = None
            if cd_manager is not None:
                n_trials = len(self.state.get("tuning_trials", []))

                def boundary(reg_weights, st, _k=k, _done=done, _n=n_trials):
                    # single-process: coordinator-only like _save_model
                    # (boundary snapshots live on the coordinator's
                    # filesystem and broadcast on resume). A distributed
                    # manager instead needs EVERY process at the boundary:
                    # phase one writes each process's score shard and the
                    # confirm exchange is itself a collective
                    if cd_manager.n_processes > 1 or multihost.is_coordinator():
                        cd_manager.on_boundary(
                            st,
                            meta={
                                "reg_weights": reg_weights,
                                "combo_index": _k,
                                "sweep_offset": _done,
                                "tuner_trials": _n,
                            },
                        )

            if snap is not None:
                # fine-grained resume: descent continues mid-sweep from the
                # snapshot (full per-call iteration count of the run that
                # wrote it; resume_state overrides initial models)
                r = estimator.fit(
                    raw, validation=validation, initial_model=prev,
                    checkpoint_fn=sweep_fn, datasets=datasets_fn(),
                    combos=[combos[k]],
                    n_cd_iterations=int(snap.manifest["n_iterations"]),
                    boundary_fn=boundary, resume_state=snap,
                )[0]
                self._finish_combo(k, combos, r, n_iter)
                results.append(r)
                prev = r.model
                continue

            remaining = n_iter - done
            if remaining <= 0:
                # crashed between the last sweep save and the completion
                # record: the model is fully trained, only metrics are lost —
                # recover them by scoring the validation set (same default
                # evaluator as _validation_context, so the recovered config
                # stays comparable in select_best)
                model = prev
                ev = None
                if validation is not None:
                    ev = GameTransformer(model=model, dtype=estimator.dtype).transform(
                        validation,
                        evaluator_specs=estimator.evaluator_specs or ["RMSE"],
                    )[1]
                r = GameResult(
                    model=model, config=combos[k], evaluation=ev, trackers={}
                )
            else:
                r = estimator.fit(
                    raw, validation=validation, initial_model=prev,
                    checkpoint_fn=sweep_fn, datasets=datasets_fn(),
                    combos=[combos[k]], n_cd_iterations=remaining,
                    boundary_fn=boundary,
                )[0]
            self._finish_combo(k, combos, r, n_iter)
            results.append(r)
            prev = r.model
        return results

    def _finish_combo(self, k, combos, r: GameResult, n_iter):
        """Record config ``k`` as completed: final model, metrics, state
        flip, per-sweep model cleanup."""
        import shutil

        final_dir = f"config-{k:03d}-final"
        self._save_model(final_dir, r.model, combos[k])
        self.state["completed"].append(
            {
                "reg_weights": combos[k],
                "model_dir": final_dir,
                "metrics": None if r.evaluation is None else r.evaluation.metrics,
                "primary_name": None
                if r.evaluation is None
                else r.evaluation.primary_name,
            }
        )
        self.state["current"] = None
        self._write()

        if multihost.is_coordinator():
            last = os.path.join(self.dir, f"config-{k:03d}-sweep-{n_iter:04d}")
            if os.path.isdir(last):
                shutil.rmtree(last, ignore_errors=True)

    # -- tuning trials --------------------------------------------------------

    def completed_trials(self):
        return list(self.state.get("tuning_trials", []))

    def record_trial(self, unit_vec, value, result: GameResult, lane=None):
        """``lane``: lane-batched sweeps (--trial-lanes) pass the trial's
        lane tracker so a resumed run can tell how far through a batch the
        interrupted run got — lanes record IN LANE ORDER, so the trial count
        alone realigns the Sobol/GP sequence (chunking-invariant)."""
        i = len(self.state["tuning_trials"])
        model_dir = f"tuning-{i:03d}"
        self._save_model(model_dir, result.model, result.config)
        rec = {
            "unit": [float(x) for x in np.asarray(unit_vec).ravel()],
            "value": float(value),
            "reg_weights": result.config,
            "model_dir": model_dir,
            "metrics": None
            if result.evaluation is None
            else result.evaluation.metrics,
            "primary_name": None
            if result.evaluation is None
            else result.evaluation.primary_name,
        }
        if lane is not None:
            rec["lane"] = {
                "index": int(lane.get("index", 0)),
                "n_lanes": int(lane.get("n_lanes", 1)),
            }
        self.state["tuning_trials"].append(rec)
        self._write()


def _native_vec(result: GameResult, names: List[str]) -> np.ndarray:
    """GameResult -> native hyperparameter vector ordered by `names`
    (vectorizeParams semantics; names are '<coordinate>.reg_weight')."""
    return np.asarray(
        [result.config.get(n.removesuffix(".reg_weight"), 1.0) for n in names]
    )


def _build_tuning_config(args, tunable: List[str]) -> HyperparameterConfig:
    """Default per-coordinate log-λ ranges, optionally overridden by a JSON
    tuning config and shrunk around prior observations."""
    from ..tuning import config_from_json, get_bounds

    if args.hyper_parameter_config:
        with open(args.hyper_parameter_config) as f:
            _, hp = config_from_json(f.read())
        tunable_names = {f"{n}.reg_weight" for n in tunable}
        bad = [p.name for p in hp.params if p.name not in tunable_names]
        if bad:
            raise SystemExit(
                f"--hyper-parameter-config variables {bad} do not name tunable "
                f"coordinates; expected names among {sorted(tunable_names)}"
            )
    else:
        hp = HyperparameterConfig(
            params=[
                ParamRange(name=f"{n}.reg_weight", min=1e-4, max=1e4, transform="LOG")
                for n in tunable
            ]
        )
    if args.hyper_parameter_prior:
        import dataclasses as dc

        with open(args.hyper_parameter_prior) as f:
            lower, upper = get_bounds(
                hp,
                f.read(),
                radius=args.hyper_parameter_shrink_radius,
                higher_is_better=_higher_is_better(args.evaluators),
            )
        hp = HyperparameterConfig(
            params=[
                dc.replace(p, min=float(lo), max=float(hi))
                for p, lo, hi in zip(hp.params, lower, upper)
            ]
        )
    return hp


def _higher_is_better(evaluators: str) -> bool:
    from ..evaluation.evaluators import build_evaluator

    specs = [e for e in evaluators.split(",") if e]
    if not specs:
        return False
    return build_evaluator(specs[0]).higher_is_better


def main():
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
