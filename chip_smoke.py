"""Chip smoke: train a GLMix model on the TPU, then score with it.

Run as ``python chip_smoke.py`` from the repo root, one process per chip. It
drives the main path once through the entry points a user would call (the
index, train and serve CLIs) at the full width of the repo's oldest model —
logistic GLMix, fixed effect d = 1024 (1023 named features + intercept)
solved with TRON, plus a per-user random effect over 20,000 users, d_re = 32,
solved with L-BFGS, f32 — on data generated from a seed, and exits non-zero at
the first thing that is not right. It has no CPU mode: ``JAX_PLATFORMS`` is
pinned to ``tpu`` before jax is imported, so a missing or busy chip is an
error from jax. The last line of stdout is the result JSON.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "out-chip-smoke")

SEED = 2026
N_TRAIN = 65_536  # rows cut from the 500k of the round-1 shape; widths are not
N_VAL = 8_192
D_FIXED_NAMED = 1023  # + intercept = 1024: a multiple of 128, Pallas-eligible
D_RE_NAMED = 31  # + intercept = 32
N_USERS = 20_000
ZIPF = 1.1
NNZ_FIXED = 24  # named features present per row (the device matrix is dense)
NNZ_RE = 8
ACTIVE_CAP = 256

# Validation AUC of exactly this train command on the CPU backend at f32
# (jax 0.9.0, PR 21, same seed). The solvers stop on relative tolerances, so
# backends part ways in the last iterations; the v5e landed 7e-6 away (PR 21).
# One coordinate update fewer costs 7e-3 (the CPU run's validation AUC went
# 0.7027, 0.7104, 0.7175 over its first three updates), so 5e-4 sits between.
CPU_F32_AUC = 0.717495
AUC_TOL = 5e-4

# Fused kernel vs the jnp two-pass path, as max|a-b| / max|b|. f32: the kernel
# runs Precision.HIGHEST dots and XLA's f32 GEMV keeps f32 too, so only the
# summation order differs (row tiles of 128-2048 vs one pass): a few f32 ulps
# times sqrt(rows); the v5e showed 8.5e-7, a bf16-pass dot would show ~2e-3.
# bf16: X and the per-row factor enter the MXU as bf16 on the fused path while
# the jnp path promotes to f32: bounded by bf16's 2^-8; the v5e showed 3.0e-3.
KERNEL_TOL = {"float32": 2e-5, "bfloat16": 1e-2}

# Served score vs the GameModel's own arrays dotted in numpy f64: the same f32
# tables times the same values over <= 64 terms, summed in f32 on the device.
# The v5e showed 3.1e-7 on scores of order 1.
SCORE_TOL = 1e-5
# Four-chip coefficients vs the one-chip run, as max|a-b| / max|b|: the same
# solves stopped by the same relative tolerance (1e-6 of the starting loss),
# with psum'd partial sums and other (K, S) bucket boundaries. Four v5e chips
# showed 5.8e-4 on the fixed effect and 2.0e-3 on the per-user table (PR 21).
MESH_COEF_TOL = 5e-3


class SmokeFailure(Exception):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


# ---------------------------------------------------------------------------
# device


def phase_device() -> dict:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU for this process: {e}") from e
    dev = devices[0]
    check(dev.platform == "tpu", f"backend is {dev.platform!r}, not 'tpu'")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }

    from photon_ml_tpu.utils.transfer import transfer_guard

    # the CD sweep's transfer guard is a no-op on the CPU backend; here an
    # implicit fetch inside it must raise
    x = jnp.full((4096, 4096), 1e-3, jnp.float32)
    with transfer_guard():
        try:
            float(x[0, 0])
            guard_live = False
        except RuntimeError:
            guard_live = True
    check(guard_live, "an implicit fetch inside transfer_guard() did not raise")

    # does block_until_ready wait for the device? Time a warm ~5.5 TFLOP
    # chain: the call returning, block_until_ready returning, and a scalar
    # fetch after that. It synchronises when the block holds the compute time
    # and the fetch after it finds nothing left to wait for.
    @jax.jit
    def chain(a):
        return jax.lax.fori_loop(0, 40, lambda _, b: jnp.tanh(b @ b), a)

    jax.device_get(chain(x)[0, 0])
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    jax.device_get(y[0, 0])
    t3 = time.perf_counter()
    log(
        "device",
        **device,
        jax=jax.__version__,
        jaxlib=md.version("jaxlib"),
        libtpu=md.version("libtpu"),
        transfer_guard_live=guard_live,
        block_until_ready={
            "dispatch_ms": (t1 - t0) * 1e3,
            "block_ms": (t2 - t1) * 1e3,
            "fetch_after_block_ms": (t3 - t2) * 1e3,
            "synchronises": (t3 - t2) < 0.2 * (t2 - t1),
        },
    )
    return device


class CompileWatch:
    """Compile seconds, program count and persistent-cache hits for the
    process, from jax's own monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.durations: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.BACKEND:
            self.durations.append(duration)

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# data


def _rows(rng, n: int, n_named: int, nnz: int):
    """[n, nnz] distinct named-feature columns and their N(0,1) values."""
    cols = np.argpartition(rng.random((n, n_named)), nnz, axis=1)[:, :nnz]
    return np.sort(cols, axis=1), rng.standard_normal((n, nnz))


def generate_data(workdir: str) -> dict:
    """Seeded GLMix rows -> train.avro / val.avro. Every user owns one train
    row, the rest of the rows are drawn Zipf 1.1 over the 20,000 users, so the
    random-effect table has its full 20,000 entities at a cut row count."""
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO

    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    n = N_TRAIN + N_VAL
    gcols, gvals = _rows(rng, n, D_FIXED_NAMED, NNZ_FIXED)
    ucols, uvals = _rows(rng, n, D_RE_NAMED, NNZ_RE)
    probs = 1.0 / np.arange(1, N_USERS + 1) ** ZIPF
    probs /= probs.sum()
    users = rng.choice(N_USERS, size=n, p=probs)
    users[:N_USERS] = rng.permutation(N_USERS)
    w_fixed = rng.standard_normal(D_FIXED_NAMED) / np.sqrt(NNZ_FIXED)
    w_user = rng.standard_normal((N_USERS, D_RE_NAMED)) / np.sqrt(NNZ_RE)
    z = np.sum(gvals * w_fixed[gcols], axis=1)
    z += np.sum(uvals * w_user[users[:, None], ucols], axis=1)
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(float)

    schema = {
        **TRAINING_EXAMPLE_AVRO,
        "fields": TRAINING_EXAMPLE_AVRO["fields"]
        + [
            {
                "name": "userFeatures",
                "type": {"type": "array", "items": "FeatureAvro"},
                "default": [],
            }
        ],
    }

    def records(lo: int, hi: int):
        for i in range(lo, hi):
            yield {
                "uid": str(i),
                "label": labels[i],
                "features": [
                    {"name": f"g{c}", "term": "", "value": v}
                    for c, v in zip(gcols[i].tolist(), gvals[i].tolist())
                ],
                "userFeatures": [
                    {"name": f"u{c}", "term": "", "value": v}
                    for c, v in zip(ucols[i].tolist(), uvals[i].tolist())
                ],
                "metadataMap": {"userId": f"u{users[i]}"},
                "weight": 1.0,
                "offset": 0.0,
            }

    paths = {
        "train": os.path.join(workdir, "train.avro"),
        "val": os.path.join(workdir, "val.avro"),
    }
    write_avro_file(paths["train"], schema, records(0, N_TRAIN))
    write_avro_file(paths["val"], schema, records(N_TRAIN, n))
    log("data", rows_train=N_TRAIN, rows_val=N_VAL, users=N_USERS,
        wall_s=time.perf_counter() - t0)
    return paths


SHARDS = [
    "--feature-shard", "name=globalShard,bags=features",
    "--feature-shard", "name=userShard,bags=userFeatures",
]


def build_index(paths: dict, workdir: str) -> str:
    from photon_ml_tpu.cli import index as index_cli

    t0 = time.perf_counter()
    index_dir = os.path.join(workdir, "index")
    maps = index_cli.run(
        ["--input-data", paths["train"], *SHARDS, "--output-dir", index_dir]
    )
    dims = {s: len(m) for s, m in maps.items()}
    check(
        dims == {"globalShard": D_FIXED_NAMED + 1, "userShard": D_RE_NAMED + 1},
        f"index dims {dims}",
    )
    log("index", dims=dims, wall_s=time.perf_counter() - t0)
    return index_dir


# ---------------------------------------------------------------------------
# train


def _series_total(snapshot: list, name: str) -> float:
    """Sum over the label sets of one series in a registry snapshot (the
    ``metrics`` list of run_summary.json); 0 when the series is absent."""
    return sum(m["value"] for m in snapshot if m["name"] == name)


def train(paths: dict, index_dir: str, outdir: str, extra=(), global_extra=""):
    """cli.train.run on the generated Avro; returns (summary, datasets,
    GameModel) with the estimator's datasets and result captured on the way
    out of ``GameEstimator.fit`` (the CLI returns only the summary dict)."""
    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    captured = {}
    fit = GameEstimator.fit

    def capturing_fit(self, raw, **kw):
        results = fit(self, raw, **kw)
        captured["datasets"] = kw["datasets"]
        captured["model"] = results[-1].model
        return results

    argv = [
        "--input-data", paths["train"],
        "--validation-data", paths["val"],
        "--feature-index-dir", index_dir,
        *SHARDS,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=globalShard,optimizer=TRON,tolerance=1e-6,"
        "max.iter=10,reg.type=L2,reg.weights=1" + global_extra,
        "--coordinate",
        "name=per-user,shard=userShard,re.type=userId,optimizer=LBFGS,"
        f"tolerance=1e-6,max.iter=30,reg.type=L2,reg.weights=1,active.cap={ACTIVE_CAP}",
        "--coordinate-descent-iterations", "2",
        "--evaluators", "AUC",
        "--output-dir", os.path.join(outdir, "model"),
        "--metrics-out", os.path.join(outdir, "metrics"),
        "--checkpoint-dir", os.path.join(outdir, "ckpt"),
        "--checkpoint-every", "1",
        "--log-level", "WARNING",
        *extra,
    ]
    with mock.patch.object(GameEstimator, "fit", capturing_fit):
        summary = train_cli.run(argv)
    return summary, captured["datasets"], captured["model"]


def check_train_outputs(summary: dict, outdir: str) -> dict:
    """What every train run of the smoke must show, from the returned summary
    and run_summary.json."""
    with open(os.path.join(outdir, "metrics", "run_summary.json")) as f:
        run_summary = json.load(f)
    check(
        run_summary["build"]["backend"] == "tpu",
        f"run_summary build: {run_summary['build']}",
    )
    for series in (
        "photon_coordinate_rejections_total",
        "photon_solver_diverged_lanes_total",
        "photon_swallowed_errors_total",
    ):
        total = _series_total(run_summary["metrics"], series)
        check(total == 0, f"{series} = {total}")
    reasons = {}
    for name in ("global", "per-user"):
        solved = run_summary["coordinates"][name]
        check(solved["iterations"]["max"] >= 1, f"coordinate {name}: {solved['iterations']}")
        reasons[name] = solved["convergence_reasons"]
        illegal = set(reasons[name]) & {"NOT_CONVERGED", "NUMERICAL_DIVERGENCE"}
        check(reasons[name] and not illegal, f"{name} reasons: {reasons[name]}")
    ckpts = os.listdir(os.path.join(outdir, "ckpt", "cd-boundaries"))
    check(ckpts, "no boundary checkpoint written")
    auc = summary["best"]["metrics"]["AUC"]
    check(np.isfinite(auc), f"AUC = {auc}")
    return {"auc": auc, "reasons": reasons}


def model_arrays(game_model) -> dict:
    fe, re = game_model["global"], game_model["per-user"]
    return {
        "global.means": fe.model.coefficients.means,
        "per-user.coef_indices": re.coef_indices,
        "per-user.coef_values": re.coef_values,
    }


def phase_train(paths: dict, index_dir: str, workdir: str) -> dict:
    import jax

    from photon_ml_tpu import native
    from photon_ml_tpu.game.problem import _fusion_mode

    t0 = time.perf_counter()
    outdir = os.path.join(workdir, "train")
    summary, datasets, game_model = train(paths, index_dir, outdir)
    seen = check_train_outputs(summary, outdir)

    batch = datasets["global"].batch
    check(batch.features.dense.shape == (N_TRAIN, D_FIXED_NAMED + 1),
          f"fixed-effect batch {batch.features.dense.shape}")
    fusion = _fusion_mode(batch)
    check(fusion == ("compiled", None), f"_fusion_mode = {fusion}")
    blocks = datasets["per-user"].blocks
    check(blocks.features.shape[0] == N_USERS and blocks.features.shape[2] == D_RE_NAMED + 1,
          f"entity blocks {blocks.features.shape}")
    # the entity blocks are stored one array a size bucket (BucketedArray)
    placed = {"batch": batch.features.dense,
              **{f"blocks[{b}]": part for b, part in enumerate(blocks.features.parts)},
              **model_arrays(game_model)}
    for name, arr in placed.items():
        check(isinstance(arr, jax.Array), f"{name} is {type(arr).__name__}, not on device")
        platforms = {d.platform for d in arr.devices()}
        check(platforms == {"tpu"}, f"{name} lives on {platforms}")
        check(bool(jax.device_get(jax.numpy.all(jax.numpy.isfinite(arr)))),
              f"{name} has non-finite values")
    check(abs(seen["auc"] - CPU_F32_AUC) <= AUC_TOL,
          f"AUC {seen['auc']:.6f} vs CPU f32 {CPU_F32_AUC} (tol {AUC_TOL})")
    log("train", auc=seen["auc"], cpu_f32_auc=CPU_F32_AUC, reasons=seen["reasons"],
        fusion=list(fusion), decode="native" if native.available() else "python",
        entity_blocks=list(blocks.features.shape), wall_s=time.perf_counter() - t0)
    return {"model": game_model, "outdir": outdir}


# ---------------------------------------------------------------------------
# kernels


def phase_kernels() -> None:
    """Each Pallas kernel, compiled, against the jnp two-pass path of
    ops/glm.py on the same inputs: the train phase's shape, then the edges
    ``pallas_glm.eligible`` admits."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops import pallas_glm
    from photon_ml_tpu.ops.features import batch_from_dense
    from photon_ml_tpu.ops.glm import GLMObjective
    from photon_ml_tpu.ops.losses import get_loss

    value_grad = jax.jit(GLMObjective.value_and_grad)
    hessian_vector = jax.jit(GLMObjective.hessian_vector)
    hessian_diagonal = jax.jit(GLMObjective.hessian_diagonal)

    t0 = time.perf_counter()
    tasks = ("logistic_regression", "linear_regression", "poisson_regression",
             "smoothed_hinge_loss_linear_svm")
    # (rows, d, X dtype, tasks); the odd row counts leave a masked last tile
    cases = [
        (N_TRAIN, D_FIXED_NAMED + 1, jnp.float32, tasks),
        (5_000, 128, jnp.float32, tasks[:1]),
        (16_421, pallas_glm.MAX_FUSED_DIM_F32, jnp.float32, tasks[:1]),
        (16_421, pallas_glm.MAX_FUSED_DIM_BF16, jnp.bfloat16, tasks[:1]),
    ]
    rng = np.random.default_rng(SEED + 1)
    worst = {}
    for n, d, dtype, case_tasks in cases:
        check(pallas_glm.eligible(n, d, dtype), f"gate refuses n={n} d={d} {dtype}")
        x = rng.standard_normal((n, d), dtype=np.float32)
        w = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
        v = jnp.asarray(rng.standard_normal(d) / np.sqrt(d), jnp.float32)
        for task in case_tasks:
            y = rng.random(n) < 0.5 if task != "poisson_regression" else rng.poisson(1.0, n)
            batch = batch_from_dense(
                x, y.astype(x.dtype), weights=rng.random(n).astype(x.dtype) + 0.5,
                feature_dtype=dtype,
            )
            objs = [
                GLMObjective(loss=get_loss(task), batch=batch, l2=1.0, fused=fused)
                for fused in ("compiled", None)
            ]
            outs = []
            for obj in objs:
                # the objective is a pytree ARGUMENT: closed over, its X
                # would be folded into the program as a 268 MB constant
                ops = {"value_grad": value_grad(obj, w)}
                if task == "logistic_regression":
                    ops["hessian_vector"] = hessian_vector(obj, w, v)
                    ops["hessian_stats"] = hessian_diagonal(obj, w)
                outs.append(jax.device_get(ops))
            for op in outs[0]:
                fused_leaves = jax.tree_util.tree_leaves(outs[0][op])
                plain_leaves = jax.tree_util.tree_leaves(outs[1][op])
                for a, b in zip(fused_leaves, plain_leaves):
                    check(np.all(np.isfinite(a)), f"{op} n={n} d={d}: non-finite")
                    err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                    key = f"{op}/{jnp.dtype(dtype).name}"
                    worst[key] = max(worst.get(key, 0.0), err)
                    tol = KERNEL_TOL[jnp.dtype(dtype).name]
                    check(err <= tol,
                          f"{op} {task} n={n} d={d} {jnp.dtype(dtype).name}: "
                          f"rel err {err:.3g} > {tol}")
    log("kernels", cases=[(n, d, jnp.dtype(t).name, len(k)) for n, d, t, k in cases],
        worst_rel_err=worst, wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# score


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _connect(port: int, deadline_s: float = 60.0) -> socket.socket:
    end = time.monotonic() + deadline_s
    while True:
        try:
            return socket.create_connection(("127.0.0.1", port), timeout=120)
        except OSError:
            if time.monotonic() > end:
                raise
            time.sleep(0.1)


def phase_score(trained: dict, index_dir: str, workdir: str) -> None:
    """Publish the trained model, serve it resident over TCP in this process,
    and compare every served score with the GameModel's own arrays."""
    import jax

    from photon_ml_tpu.cli import serve as serve_cli
    from photon_ml_tpu.io.index_map import feature_key, load_partitioned
    from photon_ml_tpu.obs.fleet import parse_prometheus

    t0 = time.perf_counter()
    root = os.path.join(workdir, "serving")
    serve_cli.run([
        "--serving-root", root,
        "--publish-model", os.path.join(trained["outdir"], "model", "models", "best"),
        "--feature-index-dir", index_dir, "--publish-only", "--log-level", "WARNING",
    ])

    arrays = jax.device_get(model_arrays(trained["model"]))
    re_model = trained["model"]["per-user"]
    gmap = load_partitioned(index_dir, "globalShard")
    umap = load_partitioned(index_dir, "userShard")

    # 100 requests: 3 of 4 name a trained user, the rest an unseen one; the
    # global shard's width alternates between two rungs of the padding ladder
    rng = np.random.default_rng(SEED + 2)
    requests, expected = [], []
    for i in range(100):
        n_g = 12 if i % 2 else 40  # +intercept: rungs 16 and 64
        gi = [gmap.get_index(feature_key(f"g{c}")) for c in
              rng.choice(D_FIXED_NAMED, n_g, replace=False)] + [gmap.intercept_index]
        ui = [umap.get_index(feature_key(f"u{c}")) for c in
              rng.choice(D_RE_NAMED, NNZ_RE, replace=False)] + [umap.intercept_index]
        gv = rng.standard_normal(n_g).tolist() + [1.0]
        uv = rng.standard_normal(NNZ_RE).tolist() + [1.0]
        user = f"u{rng.integers(N_USERS)}" if i % 4 else f"unseen{i}"
        requests.append({"features": {"globalShard": [gi, gv], "userShard": [ui, uv]},
                         "ids": {"userId": user}, "offset": 0.25})
        score = 0.25 + float(np.dot(arrays["global.means"][gi].astype(np.float64), gv))
        row = re_model.entity_row(user)
        if row >= 0:
            dense = np.zeros(D_RE_NAMED + 1)
            support = arrays["per-user.coef_indices"][row]
            dense[support[support >= 0]] = arrays["per-user.coef_values"][row][support >= 0]
            score += float(np.dot(dense[ui], uv))
        expected.append(score)

    port = _free_port()
    stop = threading.Event()
    serve_argv = ["--serving-root", root, "--listen", f"127.0.0.1:{port}",
                  "--metrics-out", os.path.join(workdir, "serve-metrics"),
                  "--log-level", "WARNING"]
    responses = [None] * len(requests)

    def client(k: int, n_clients: int):
        with _connect(port) as conn, conn.makefile("rw") as f:
            for i in range(k, len(requests), n_clients):
                f.write(json.dumps(requests[i]) + "\n")
                f.flush()
                responses[i] = json.loads(f.readline())

    with ThreadPoolExecutor(max_workers=5) as pool:
        server = pool.submit(serve_cli.run, serve_argv, stop)
        try:
            for c in [pool.submit(client, k, 4) for k in range(4)]:
                c.result(timeout=300)
        finally:
            stop.set()
            server.result(timeout=60)  # a server failure outranks a client's
    worst = 0.0
    for i, (resp, want) in enumerate(zip(responses, expected)):
        check(resp is not None and "score" in resp, f"request {i}: {resp}")
        worst = max(worst, abs(resp["score"] - want))
    check(worst <= SCORE_TOL, f"served score off by {worst:.3g} (tol {SCORE_TOL})")
    with open(os.path.join(workdir, "serve-metrics", "metrics.prom")) as f:
        served = parse_prometheus(f.read())
    for series in ("photon_serving_shed_total", "photon_swallowed_errors_total"):
        total = _series_total(served, series)
        check(total == 0, f"{series} = {total}")
    cold = _series_total(served, "photon_serving_cold_start_total")
    check(cold == 25, f"cold starts counted: {cold}, sent 25 unseen users")
    log("score", requests=len(requests), unseen=25, max_abs_err=worst,
        wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# mesh


# (--mesh-shape, extra keys of the global coordinate): rows and entity blocks
# over four chips; then dryrun_multichip's configuration at real size, the
# fixed effect tiled over (data x model)
MESH_RUNS = (("data=4", ""), ("data=2,model=2", ",layout=tiled"))


def phase_mesh(device: dict, paths: dict, index_dir: str, workdir: str, trained: dict) -> None:
    """The train phase again over four chips, checked against the one-chip
    run. No virtual-CPU stand-in: fewer than four chips skips the phase."""
    import jax

    from photon_ml_tpu.game.problem import _fusion_mode

    if device["count"] < 4:
        log("mesh", skipped=f"{device['count']} device(s)")
        return
    one_chip = jax.device_get(model_arrays(trained["model"]))
    for shape, global_extra in MESH_RUNS:
        t0 = time.perf_counter()
        outdir = os.path.join(workdir, "mesh-" + shape.replace("=", "").replace(",", "-"))
        summary, datasets, game_model = train(
            paths, index_dir, outdir, extra=("--mesh-shape", shape),
            global_extra=global_extra,
        )
        seen = check_train_outputs(summary, outdir)
        batch = datasets["global"].batch
        spread = {"blocks": datasets["per-user"].blocks.features.sharding.device_set}
        if global_extra:
            spread["tiles"] = batch.features.lval.sharding.device_set
        else:
            spread["batch"] = batch.features.dense.sharding.device_set
            mode, mesh = _fusion_mode(batch)
            check(mode == "compiled" and mesh is not None,
                  f"{shape}: sharded _fusion_mode = {(mode, mesh)}")
        spread = {name: len(devs) for name, devs in spread.items()}
        check(set(spread.values()) == {4}, f"{shape}: arrays spread over {spread} devices")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()[:4]]
        check(all(b > 0 for b in in_use), f"{shape}: bytes_in_use {in_use}")
        meshed = jax.device_get(model_arrays(game_model))
        errs = {}
        for name in ("global.means", "per-user.coef_values"):
            a, b = meshed[name], one_chip[name]
            check(a.shape == b.shape, f"{shape} {name}: {a.shape} vs {b.shape}")
            errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            check(errs[name] <= MESH_COEF_TOL,
                  f"{shape} {name}: rel err {errs[name]:.3g} > {MESH_COEF_TOL}")
        check(abs(seen["auc"] - CPU_F32_AUC) <= AUC_TOL, f"{shape}: AUC {seen['auc']}")
        log("mesh", shape=shape, auc=seen["auc"], spread=spread, bytes_in_use=in_use,
            coef_rel_err=errs, reasons=seen["reasons"], wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "tpu"
    t_start = time.perf_counter()
    try:
        device = phase_device()
        from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

        cache_dir = enable_persistent_compilation_cache()
        watch = CompileWatch()
        shutil.rmtree(OUT, ignore_errors=True)  # the smoke's own output
        os.makedirs(OUT)
        paths = generate_data(OUT)
        index_dir = build_index(paths, OUT)
        trained = phase_train(paths, index_dir, OUT)
        phase_kernels()
        phase_score(trained, index_dir, OUT)
        phase_mesh(device, paths, index_dir, OUT, trained)
        d = np.asarray(watch.durations)
        log("cache", dir=cache_dir, compile_s=float(d.sum()), programs=int(d.size),
            persistent_cache_hits=watch.cache_hits,
            programs_under_1s=int((d < 1.0).sum()),
            compile_s_under_1s=float(d[d < 1.0].sum()))
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log("done", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
