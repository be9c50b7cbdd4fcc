"""Two-process CPU smoke test of the multi-host runtime.

Plays the role of the reference's cluster integration tests (SURVEY.md §4):
two OS processes, each with 4 virtual CPU devices, connect through
``jax.distributed.initialize`` into one 8-device mesh and run the REAL
training CLI with ``--distributed``: per-host row-range reads, data-parallel
gradient all-reduce across processes, process-0-only writes. The resulting
model must match a single-process run on the same data.

Run directly: ``python -m pytest tests/test_multihost.py -q``.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.cli import train

args = sys.argv[1:]
summary = train.run(args)
print("WORKER_OK", jax.process_index(), summary["best"]["metrics"]["AUC"])

# exact-math parity of the cross-host all-reduce: distributed value+grad at a
# fixed point must equal the single-process computation to float64 precision
import numpy as np
import jax.numpy as jnp
from photon_ml_tpu.io import FeatureShardConfig, read_avro_dataset
from photon_ml_tpu.io.avro import count_avro_rows
from photon_ml_tpu.io.index_map import load_partitioned
from photon_ml_tpu.ops.glm import GLMObjective
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.parallel import make_mesh, multihost, replicate, shard_batch

a = dict(zip(args, args[1:]))
imaps = {"global": load_partitioned(a["--feature-index-dir"], "global")}
rr = multihost.host_row_range(count_avro_rows(a["--input-data"]))
ds, _ = read_avro_dataset(
    a["--input-data"], {"global": FeatureShardConfig(("features",))},
    index_maps=imaps, row_range=rr)
mesh = make_mesh(n_data=8, n_model=1)
batch = shard_batch(ds.to_batch("global", dtype=jnp.float64), mesh)
d = batch.features.dim
w = replicate(jnp.asarray(np.linspace(-1.0, 1.0, d)), mesh)

# the global batch must be a jit ARGUMENT (closing over an array that spans
# other processes' devices is not allowed)
def _vg(b, w):
    return GLMObjective(loss=LOGISTIC, batch=b, l2=1.0).value_and_grad(w)

v, g = jax.jit(_vg)(batch, w)
print("GRADCHECK", repr(float(v)), " ".join(repr(float(x)) for x in np.asarray(g)))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _write_data(tmp_path, n=320, d=6, seed=7):
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(x @ w)))).astype(int)
    recs = []
    for i in range(n):
        recs.append(
            {
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(d)
                ],
            }
        )
    p = str(tmp_path / "train.avro")
    write_avro_file(p, TRAINING_EXAMPLE_AVRO, recs)
    return p


@pytest.mark.slow
def test_two_process_training_matches_single_process(tmp_path):
    data = _write_data(tmp_path)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")
    out_single = str(tmp_path / "single")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=global,bags=features",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=global,optimizer=LBFGS,tolerance=1e-13,max.iter=400,"
        "reg.type=L2,reg.weights=1",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--feature-index-dir", index_dir,
    ]

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    procs = []
    for i in range(2):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-c", _WORKER,
                    *train_common,
                    "--output-dir", out_multi,
                    "--mesh-shape", "data=8",
                    "--distributed", f"coordinator=localhost:{port},process={i},n=2",
                ],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process training timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out
    # per-host row ranges were actually used
    assert any("reads rows [0, 160)" in err for _, _, err in outs)
    assert any("reads rows [160, 320)" in err for _, _, err in outs)

    # single-process reference on the same data (in-process: conftest already
    # pinned CPU + 8 virtual devices)
    from photon_ml_tpu.cli import train as train_cli

    train_cli.run(train_common + ["--output-dir", out_single])

    with open(os.path.join(out_multi, "training-summary.json")) as f:
        multi = json.load(f)
    with open(os.path.join(out_single, "training-summary.json")) as f:
        single = json.load(f)
    # AUC is a step function of score order; sharded-vs-single reduction
    # order can flip near-ties, so parity is loose here and exact on the
    # fixed-point gradient below
    assert multi["best"]["metrics"]["AUC"] == pytest.approx(
        single["best"]["metrics"]["AUC"], abs=1e-3
    )
    assert multi["best"]["metrics"]["LOGISTIC_LOSS"] == pytest.approx(
        single["best"]["metrics"]["LOGISTIC_LOSS"], rel=1e-4
    )

    from photon_ml_tpu.io.index_map import load_partitioned

    imaps = {"global": load_partitioned(index_dir, "global")}

    # exact-math all-reduce parity: both workers' distributed value+grad at
    # the fixed w equals the single-process computation to ~f64 precision
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.io import FeatureShardConfig, read_avro_dataset
    from photon_ml_tpu.ops.glm import GLMObjective
    from photon_ml_tpu.ops.losses import LOGISTIC

    ds, _ = read_avro_dataset(
        data, {"global": FeatureShardConfig(("features",))}, index_maps=imaps
    )
    batch = ds.to_batch("global", dtype=jnp.float64)
    obj = GLMObjective(loss=LOGISTIC, batch=batch, l2=1.0)
    d = batch.features.dim
    w_fixed = jnp.asarray(np.linspace(-1.0, 1.0, d))
    v_ref, g_ref = obj.value_and_grad(w_fixed)
    for _, out, _ in outs:
        line = next(l for l in out.splitlines() if l.startswith("GRADCHECK"))
        vals = [float(t) for t in line.split()[1:]]
        np.testing.assert_allclose(vals[0], float(v_ref), rtol=1e-12)
        np.testing.assert_allclose(vals[1:], np.asarray(g_ref), rtol=1e-11)

    # process-0-only writes: exactly one model dir, written once
    from photon_ml_tpu.io.model_io import load_game_model

    m_multi = load_game_model(
        os.path.join(out_multi, "models", "best"), imaps, task="logistic_regression"
    )
    m_single = load_game_model(
        os.path.join(out_single, "models", "best"), imaps, task="logistic_regression"
    )
    w_multi = np.asarray(m_multi.models["global"].coefficients.means)
    w_single = np.asarray(m_single.models["global"].coefficients.means)
    # optimizer iterate paths diverge chaotically at float noise; the basin
    # is shared (losses match above), so this bound is deliberately loose
    np.testing.assert_allclose(w_multi, w_single, rtol=1e-2, atol=1e-3)


def test_host_row_range_balanced():
    from photon_ml_tpu.parallel.multihost import host_row_range

    for n, p in [(10, 3), (8, 8), (7, 2), (0, 4), (5, 1)]:
        spans = [host_row_range(n, i, p) for i in range(p)]
        assert spans[0][0] == 0 and spans[-1][1] == n
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert a1 == b0
        sizes = [b - a for a, b in spans]
        assert max(sizes) - min(sizes) <= 1


def test_initialize_spec_validation():
    from photon_ml_tpu.parallel.multihost import initialize_from_spec

    with pytest.raises(ValueError, match="unknown --distributed keys"):
        initialize_from_spec("coordinator=x:1,bogus=2")


@pytest.mark.slow
def test_two_process_uneven_rows(tmp_path):
    """321 rows across 2 hosts (161/160): equal-share padding must keep the
    processes' local shapes consistent for the global array assembly."""
    data = _write_data(tmp_path, n=321)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")

    from photon_ml_tpu.cli import index as index_cli

    common = ["--input-data", data, "--feature-shard", "name=global,bags=features"]
    index_cli.run(common + ["--output-dir", index_dir])

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _WORKER.split("# exact-math parity")[0],
                *common,
                "--validation-data", data,
                "--task", "logistic_regression",
                "--coordinate",
                "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1",
                "--evaluators", "AUC",
                "--feature-index-dir", index_dir,
                "--output-dir", out_multi,
                "--mesh-shape", "data=8",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("uneven-rows multi-process training timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out
    assert any("reads rows [0, 161) of 321 (padded to 161)" in err for _, _, err in outs)
    assert any("reads rows [161, 321) of 321 (padded to 161)" in err for _, _, err in outs)
    assert os.path.exists(os.path.join(out_multi, "training-summary.json"))


def _write_glmix_data(tmp_path, n=640, seed=21):
    """Avro records with global + per-user feature bags and userId ids."""
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing import (
        generate_game_records,
        generate_mixed_effect_data,
    )

    data = generate_mixed_effect_data(
        n=n, d_fixed=5, re_specs={"userId": (12, 3)}, seed=seed
    )
    recs = generate_game_records(data)
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
    p = str(tmp_path / "glmix.avro")
    write_avro_file(p, schema, recs)
    return p


@pytest.mark.slow
def test_two_process_glmix_matches_single_process(tmp_path):
    """THE cluster test: GLMix (fixed + per-user random effect) trained across
    2 processes — per-host row reads, cross-host entity planning, device-side
    shuffle, entity-sharded solves — must match the single-process model.
    (Reference: RandomEffectCoordinate.scala:273-329 trains entities across
    executors; this is the TPU-native equivalent.)"""
    data = _write_glmix_data(tmp_path)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")
    out_single = str(tmp_path / "single")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=globalShard,bags=features",
        "--feature-shard", "name=userShard,bags=userFeatures",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=globalShard,optimizer=LBFGS,tolerance=1e-12,"
        "max.iter=300,reg.type=L2,reg.weights=1",
        "--coordinate",
        "name=per-user,shard=userShard,re.type=userId,optimizer=LBFGS,"
        "tolerance=1e-12,max.iter=300,reg.type=L2,reg.weights=1",
        "--coordinate-descent-iterations", "2",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--feature-index-dir", index_dir,
    ]

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _WORKER.split("# exact-math parity")[0],
                *train_common,
                "--output-dir", out_multi,
                "--mesh-shape", "data=8",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process GLMix training timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out

    from photon_ml_tpu.cli import train as train_cli

    train_cli.run(train_common + ["--output-dir", out_single, "--mesh-shape", "data=8"])

    with open(os.path.join(out_multi, "training-summary.json")) as f:
        multi = json.load(f)
    with open(os.path.join(out_single, "training-summary.json")) as f:
        single = json.load(f)
    assert multi["best"]["metrics"]["AUC"] == pytest.approx(
        single["best"]["metrics"]["AUC"], abs=2e-3
    )
    assert multi["best"]["metrics"]["LOGISTIC_LOSS"] == pytest.approx(
        single["best"]["metrics"]["LOGISTIC_LOSS"], rel=1e-3
    )

    from photon_ml_tpu.io.index_map import load_partitioned
    from photon_ml_tpu.io.model_io import load_game_model

    imaps = {s: load_partitioned(index_dir, s) for s in ("globalShard", "userShard")}
    m_multi = load_game_model(
        os.path.join(out_multi, "models", "best"), imaps, task="logistic_regression"
    )
    m_single = load_game_model(
        os.path.join(out_single, "models", "best"), imaps, task="logistic_regression"
    )
    w_multi = np.asarray(m_multi.models["global"].coefficients.means)
    w_single = np.asarray(m_single.models["global"].coefficients.means)
    np.testing.assert_allclose(w_multi, w_single, rtol=1e-2, atol=1e-3)

    re_m, re_s = m_multi.models["per-user"], m_single.models["per-user"]
    # compare per-entity coefficient vectors keyed by entity id (block order
    # may legally differ between the two builds)
    dim = max(
        int(np.asarray(re_m.coef_indices).max()), int(np.asarray(re_s.coef_indices).max())
    ) + 1
    dense_m = re_m.dense_coefficients(dim)
    dense_s = re_s.dense_coefficients(dim)
    ids_s = [str(e) for e in re_s.entity_ids if not str(e).startswith("__pad")]
    rows_m = re_m.rows_for(ids_s)
    rows_s = re_s.rows_for(ids_s)
    assert np.all(rows_m >= 0), "multi-process model is missing entities"
    np.testing.assert_allclose(
        dense_m[rows_m], dense_s[rows_s], rtol=1e-2, atol=2e-3
    )


_STREAM_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.cli import train

summary = train.run(sys.argv[1:])
print("WORKER_OK", jax.process_index(), summary["best"]["metrics"]["AUC"])
"""


@pytest.mark.slow
def test_two_process_streamed_pipelined_glmix_matches_single_process(tmp_path):
    """The execution-planner tentpole: GLMix across 2 processes with BOTH
    coordinates forced out-of-core (hbm.budget.mb=0) AND --pipeline-depth 2 —
    streamed FE row slices per host, streamed RE entity shards per host, the
    sweep pipeline overlapping staging with solves — must match the
    single-process fully-resident reference. Not bit-exact by construction:
    per-host streamed partial sums reduce in a different order than the
    single-device resident contraction, so parity is pinned at the same
    tolerances as the resident multi-process GLMix test above. The planner's
    resolved routing must land in run_summary.json, and the stream-slice
    counters prove the run actually streamed (budget 0 admits nothing)."""
    data = _write_glmix_data(tmp_path)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")
    out_single = str(tmp_path / "single")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=globalShard,bags=features",
        "--feature-shard", "name=userShard,bags=userFeatures",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    base = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate-descent-iterations", "2",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--feature-index-dir", index_dir,
    ]
    fe = (
        "name=global,shard=globalShard,optimizer=LBFGS,tolerance=1e-12,"
        "max.iter=300,reg.type=L2,reg.weights=1"
    )
    re_ = (
        "name=per-user,shard=userShard,re.type=userId,optimizer=LBFGS,"
        "tolerance=1e-12,max.iter=300,reg.type=L2,reg.weights=1"
    )

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _STREAM_WORKER,
                *base,
                # budget 0: every block/batch estimate exceeds it -> streams
                "--coordinate", fe + ",hbm.budget.mb=0",
                "--coordinate", re_ + ",hbm.budget.mb=0",
                "--pipeline-depth", "2",
                "--output-dir", out_multi,
                # non-shared metrics dir per process (no shared fs assumed)
                "--metrics-out", str(tmp_path / f"metrics-p{i}"),
                "--mesh-shape", "data=8",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("streamed+pipelined multi-process GLMix timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out

    # single-process fully-resident reference: no budgets, no mesh
    from photon_ml_tpu.cli import train as train_cli

    train_cli.run(
        base + ["--coordinate", fe, "--coordinate", re_,
                "--output-dir", out_single]
    )

    # the resolved plan rode into run_summary.json (satellite: observability)
    with open(os.path.join(str(tmp_path / "metrics-p0"), "run_summary.json")) as f:
        run_summary = json.load(f)
    plan = run_summary["plan"]
    assert plan["n_processes"] == 2
    assert plan["pipeline_depth"] == 2
    assert plan["mesh_axes"] == {"data": 8, "model": 1}
    routing = {c["name"]: c for c in plan["coordinates"]}
    assert routing["global"]["residency"] == "streamed"
    assert routing["global"]["sharding"] == "host-sharded rows (streamed slices)"
    assert routing["per-user"]["residency"] == "streamed"
    assert routing["per-user"]["sharding"] == "entity-sharded (host-resident blocks)"
    assert routing["global"]["pipelined"] and routing["per-user"]["pipelined"]
    # the run actually streamed: slice counters are live in the summary's
    # metrics snapshot (budget 0 admits no resident batch)
    slices = sum(
        m["value"]
        for m in run_summary["metrics"]
        if m["name"] == "photon_stream_slices_total" and m["kind"] == "counter"
    )
    assert slices > 0, "streamed run staged no slices"

    with open(os.path.join(out_multi, "training-summary.json")) as f:
        multi = json.load(f)
    with open(os.path.join(out_single, "training-summary.json")) as f:
        single = json.load(f)
    assert multi["best"]["metrics"]["AUC"] == pytest.approx(
        single["best"]["metrics"]["AUC"], abs=2e-3
    )
    assert multi["best"]["metrics"]["LOGISTIC_LOSS"] == pytest.approx(
        single["best"]["metrics"]["LOGISTIC_LOSS"], rel=1e-3
    )

    from photon_ml_tpu.io.index_map import load_partitioned
    from photon_ml_tpu.io.model_io import load_game_model

    imaps = {s: load_partitioned(index_dir, s) for s in ("globalShard", "userShard")}
    m_multi = load_game_model(
        os.path.join(out_multi, "models", "best"), imaps, task="logistic_regression"
    )
    m_single = load_game_model(
        os.path.join(out_single, "models", "best"), imaps, task="logistic_regression"
    )
    w_multi = np.asarray(m_multi.models["global"].coefficients.means)
    w_single = np.asarray(m_single.models["global"].coefficients.means)
    np.testing.assert_allclose(w_multi, w_single, rtol=1e-2, atol=1e-3)

    re_m, re_s = m_multi.models["per-user"], m_single.models["per-user"]
    dim = max(
        int(np.asarray(re_m.coef_indices).max()), int(np.asarray(re_s.coef_indices).max())
    ) + 1
    dense_m = re_m.dense_coefficients(dim)
    dense_s = re_s.dense_coefficients(dim)
    ids_s = [str(e) for e in re_s.entity_ids if not str(e).startswith("__pad")]
    rows_m = re_m.rows_for(ids_s)
    rows_s = re_s.rows_for(ids_s)
    assert np.all(rows_m >= 0), "streamed multi-process model is missing entities"
    np.testing.assert_allclose(
        dense_m[rows_m], dense_s[rows_s], rtol=1e-2, atol=2e-3
    )


_SCORE_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.cli import score

score.run(sys.argv[1:])
print("SCORE_OK", jax.process_index())
"""


@pytest.mark.slow
def test_two_process_normalization_stats_and_scoring(tmp_path):
    """Round-4 verdict item 6: multi-process normalization (global moment
    sums), --compute-feature-stats (global summaries, process-0 writes), and
    a distributed scoring driver (per-host row ranges, part files, global
    metrics) must all match their single-process runs."""
    data = _write_data(tmp_path, n=320)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")
    out_single = str(tmp_path / "single")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=global,bags=features",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=global,optimizer=LBFGS,tolerance=1e-12,max.iter=300,"
        "reg.type=L2,reg.weights=1",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--feature-index-dir", index_dir,
        "--normalization", "STANDARDIZATION",
        "--compute-feature-stats",
    ]

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _WORKER.split("# exact-math parity")[0],
                *train_common,
                "--output-dir", out_multi,
                "--mesh-shape", "data=8",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process normalized training timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out

    from photon_ml_tpu.cli import train as train_cli

    train_cli.run(train_common + ["--output-dir", out_single])

    # normalized training matches single-process
    with open(os.path.join(out_multi, "training-summary.json")) as f:
        multi = json.load(f)
    with open(os.path.join(out_single, "training-summary.json")) as f:
        single = json.load(f)
    assert multi["best"]["metrics"]["LOGISTIC_LOSS"] == pytest.approx(
        single["best"]["metrics"]["LOGISTIC_LOSS"], rel=1e-4
    )

    # feature statistics written by process 0 are the GLOBAL statistics
    from photon_ml_tpu.io import read_avro_file

    _, recs_m = read_avro_file(os.path.join(out_multi, "feature-stats-global.avro"))
    _, recs_s = read_avro_file(os.path.join(out_single, "feature-stats-global.avro"))
    sm = {(r["featureName"], r["featureTerm"]): r["metrics"] for r in recs_m}
    ss = {(r["featureName"], r["featureTerm"]): r["metrics"] for r in recs_s}
    assert sm.keys() == ss.keys() and len(sm) > 0
    for k in sm:
        for metric in ("mean", "variance", "numNonzeros"):
            assert sm[k][metric] == pytest.approx(ss[k][metric], rel=1e-12), (k, metric)

    # distributed scoring: per-host part files + global metrics
    score_multi = str(tmp_path / "score-multi")
    score_single = str(tmp_path / "score-single")
    score_common = common + [
        "--feature-index-dir", index_dir,
        "--model-input-dir", os.path.join(out_multi, "models", "best"),
        "--task", "logistic_regression",
        "--evaluators", "AUC",
    ]
    port2 = _free_port()
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _SCORE_WORKER,
                *score_common,
                "--output-dir", score_multi,
                "--distributed", f"coordinator=localhost:{port2},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process scoring timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"score worker failed:\n{out}\n{err}"
        assert "SCORE_OK" in out

    from photon_ml_tpu.cli import score as score_cli

    score_cli.run(score_common + ["--output-dir", score_single])

    _, single_recs = read_avro_file(os.path.join(score_single, "scores.avro"))
    multi_recs = []
    for i in range(2):
        _, part = read_avro_file(
            os.path.join(score_multi, f"scores-part-{i:04d}.avro")
        )
        multi_recs.extend(part)
    assert len(multi_recs) == len(single_recs) == 320
    s_single = np.asarray([r["predictionScore"] for r in single_recs])
    s_multi = np.asarray([r["predictionScore"] for r in multi_recs])
    np.testing.assert_allclose(s_multi, s_single, rtol=1e-6)

    with open(os.path.join(score_multi, "evaluation.json")) as f:
        ev_m = json.load(f)
    with open(os.path.join(score_single, "evaluation.json")) as f:
        ev_s = json.load(f)
    assert ev_m["AUC"] == pytest.approx(ev_s["AUC"], abs=1e-12)


@pytest.mark.slow
def test_two_process_tiled_matches_single_process(tmp_path):
    """Round-4 verdict item 8: layout=tiled (model-axis coefficient sharding)
    across 2 processes — each host builds tiles for its own data-axis rows;
    only the tile-size agreement crosses hosts — must match single-process."""
    data = _write_data(tmp_path, n=320, d=10)
    index_dir = str(tmp_path / "index")
    out_multi = str(tmp_path / "multi")
    out_single = str(tmp_path / "single")

    from photon_ml_tpu.cli import index as index_cli

    common = ["--input-data", data, "--feature-shard", "name=global,bags=features"]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=global,layout=tiled,optimizer=LBFGS,tolerance=1e-12,"
        "max.iter=300,reg.type=L2,reg.weights=1",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--feature-index-dir", index_dir,
    ]

    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _WORKER.split("# exact-math parity")[0],
                *train_common,
                "--output-dir", out_multi,
                "--mesh-shape", "data=4,model=2",
                "--distributed", f"coordinator=localhost:{port},process={i},n=2",
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process tiled training timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "WORKER_OK" in out

    from photon_ml_tpu.cli import train as train_cli

    train_cli.run(
        train_common + ["--output-dir", out_single, "--mesh-shape", "data=4,model=2"]
    )

    with open(os.path.join(out_multi, "training-summary.json")) as f:
        multi = json.load(f)
    with open(os.path.join(out_single, "training-summary.json")) as f:
        single = json.load(f)
    assert multi["best"]["metrics"]["LOGISTIC_LOSS"] == pytest.approx(
        single["best"]["metrics"]["LOGISTIC_LOSS"], rel=1e-4
    )

    from photon_ml_tpu.io.index_map import load_partitioned
    from photon_ml_tpu.io.model_io import load_game_model

    imaps = {"global": load_partitioned(index_dir, "global")}
    w_m = np.asarray(
        load_game_model(
            os.path.join(out_multi, "models", "best"), imaps,
            task="logistic_regression",
        ).models["global"].coefficients.means
    )
    w_s = np.asarray(
        load_game_model(
            os.path.join(out_single, "models", "best"), imaps,
            task="logistic_regression",
        ).models["global"].coefficients.means
    )
    np.testing.assert_allclose(w_m, w_s, rtol=1e-2, atol=1e-3)


_WORKER_F32 = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
# NO x64: the fused Pallas kernels require f32 batches

from photon_ml_tpu.cli import train

summary = train.run(sys.argv[1:])
print("WORKER_OK", jax.process_index(), summary["best"]["metrics"]["AUC"])

# prove which objective path the trainer took (guards against the test
# passing vacuously if gating ever stops admitting multi-process batches)
import jax.numpy as jnp
from photon_ml_tpu.io import FeatureShardConfig, read_avro_dataset
from photon_ml_tpu.io.avro import count_avro_rows
from photon_ml_tpu.io.index_map import load_partitioned
from photon_ml_tpu.game.problem import _fusion_mode
from photon_ml_tpu.parallel import make_mesh, multihost, shard_batch

a = dict(zip(sys.argv[1:], sys.argv[2:]))
imaps = {"global": load_partitioned(a["--feature-index-dir"], "global")}
rr = multihost.host_row_range(count_avro_rows(a["--input-data"]))
ds, _ = read_avro_dataset(
    a["--input-data"], {"global": FeatureShardConfig(("features",))},
    index_maps=imaps, row_range=rr)
mesh = make_mesh(n_data=8, n_model=1)
batch = shard_batch(ds.to_batch("global", dtype=jnp.float32), mesh)
mode, fmesh = _fusion_mode(batch)
print("FUSIONMODE", mode, "mesh" if fmesh is not None else "nomesh")
"""


@pytest.mark.slow
def test_two_process_fused_pallas_matches_unfused(tmp_path):
    """The fused Pallas shard_map path across PROCESSES: a 2-process run at
    fused-eligible shapes (n >= 4096, d = 128) with PHOTON_PALLAS=interpret
    must train to the same model as the same 2-process run with fusion off —
    the per-shard kernel + cross-host psum against the GSPMD jnp path.
    127 raw features + the shard intercept = d 128 (the fused path needs a
    lane-width multiple; the FUSIONMODE assertions below guard against this
    test passing vacuously on the jnp path)."""
    data = _write_data(tmp_path, n=4608, d=127, seed=11)
    index_dir = str(tmp_path / "index")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=global,bags=features",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--validation-data", data,
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=global,optimizer=LBFGS,tolerance=1e-9,max.iter=60,"
        "reg.type=L2,reg.weights=1",
        "--evaluators", "AUC",
        "--feature-index-dir", index_dir,
    ]

    models = {}
    for mode in ("off", "interpret"):
        out_dir = str(tmp_path / f"out-{mode}")
        port = _free_port()
        env = {**os.environ, "PYTHONPATH": REPO, "PHOTON_PALLAS": mode}
        env.pop("XLA_FLAGS", None)
        procs = [
            subprocess.Popen(
                [
                    sys.executable, "-c", _WORKER_F32,
                    *train_common,
                    "--output-dir", out_dir,
                    "--mesh-shape", "data=8",
                    "--distributed",
                    f"coordinator=localhost:{port},process={i},n=2",
                ],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail(f"fused-pallas 2-process run ({mode}) timed out")
            outs.append((p.returncode, out, err))
        for rc, out, err in outs:
            assert rc == 0, f"worker failed ({mode}):\n{out}\n{err}"
            assert "WORKER_OK" in out
            # NOT vacuous: the interpret run must have actually fused (with
            # the cross-host mesh), the off run must not have
            expected = "FUSIONMODE interpret mesh" if mode == "interpret" else "FUSIONMODE None"
            assert expected in out, f"({mode}) fusion gating changed:\n{out}"

        from photon_ml_tpu.io.index_map import load_partitioned
        from photon_ml_tpu.io.model_io import load_game_model

        imaps = {"global": load_partitioned(index_dir, "global")}
        model = load_game_model(
            os.path.join(out_dir, "models", "best"), imaps,
            task="logistic_regression",
        )
        models[mode] = np.asarray(model.models["global"].coefficients.means)

    # f32 solves with different reduction orders: agree at the optimum to
    # f32-accumulation scale
    scale = max(np.max(np.abs(models["off"])), 1.0)
    assert np.max(np.abs(models["interpret"] - models["off"])) <= 5e-3 * scale


_CKPT_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.cli import train

summary = train.run(sys.argv[1:])
print("WORKER_OK", jax.process_index(), summary["best"]["reg_weights"])
"""


def test_two_process_checkpoint_resume_without_shared_fs(tmp_path):
    """Checkpoint + --distributed WITHOUT a shared filesystem (VERDICT r4
    weak item 6): each process gets its own checkpoint dir; only the
    coordinator's is ever populated (process-0-only writes). On resume the
    coordinator's state AND its model files broadcast to the other process
    instead of refusing — the run completes idempotently."""
    data = _write_data(tmp_path)
    index_dir = str(tmp_path / "index")

    from photon_ml_tpu.cli import index as index_cli

    common = [
        "--input-data", data,
        "--feature-shard", "name=global,bags=features",
    ]
    index_cli.run(common + ["--output-dir", index_dir])

    train_common = common + [
        "--task", "logistic_regression",
        "--coordinate",
        "name=global,shard=global,optimizer=LBFGS,tolerance=1e-10,max.iter=60,"
        "reg.type=L2,reg.weights=1|10",
        "--feature-index-dir", index_dir,
    ]
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)

    def run_round():
        port = _free_port()
        procs = []
        for i in range(2):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-c", _CKPT_WORKER,
                        *train_common,
                        # NON-shared: a different checkpoint/output dir per process
                        "--checkpoint-dir", str(tmp_path / f"ckpt-p{i}"),
                        "--output-dir", str(tmp_path / f"out-p{i}"),
                        "--mesh-shape", "data=8",
                        "--distributed",
                        f"coordinator=localhost:{port},process={i},n=2",
                    ],
                    env=env,
                    cwd=REPO,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("2-process checkpoint round timed out")
            outs.append((p.returncode, out, err))
        for rc, out, err in outs:
            assert rc == 0, f"worker failed:\n{out}\n{err}"
            assert "WORKER_OK" in out
        return outs

    run_round()  # fresh: trains the 2-config grid, coordinator writes state
    # coordinator's checkpoint exists; the other process's dir is empty/state-less
    assert os.path.exists(tmp_path / "ckpt-p0" / "checkpoint-state.json")
    assert not os.path.exists(tmp_path / "ckpt-p1" / "checkpoint-state.json")

    outs = run_round()  # resume: states DIVERGE across processes -> broadcast
    assert any(
        "2/2 configurations already trained" in err for _, _, err in outs
    ), "resume did not recognize the completed grid from the coordinator state"


_PASSIVE_WORKER = """
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.parallel import make_mesh, multihost

spec, n_total = sys.argv[1], int(sys.argv[2])
multihost.initialize_from_spec(spec)

from photon_ml_tpu.game.data_mp import build_random_effect_dataset_global
from photon_ml_tpu.io.data import RawDataset

r0, r1 = multihost.host_row_range(n_total)
n_loc = r1 - r0
g = np.arange(r0, r1)
d = 2
raw = RawDataset(
    n_rows=n_loc,
    labels=np.asarray(g % 2, np.float64),
    offsets=np.zeros(n_loc),
    weights=np.ones(n_loc),
    shard_coo={
        "userShard": (
            np.repeat(np.arange(n_loc), d),
            np.tile(np.arange(d), n_loc),
            np.linspace(0.1, 1.0, n_loc * d),
        )
    },
    shard_dims={"userShard": d},
    id_tags={"userId": np.array(["u%d" % (x % 3) for x in g], dtype=object)},
    global_row_start=r0,
)
raw = raw.pad_rows(multihost.equal_host_share(n_total))
mesh = make_mesh(n_data=8, n_model=1)

# the regression needs the PADDED local row space to differ from the true
# one: chunk = 8 devices / 2 procs = 4, so 11 local rows pad to 12
chunk = max(8 // jax.process_count(), 1)
n_local = ((raw.n_rows + chunk - 1) // chunk) * chunk
assert n_local != raw.n_rows, (n_local, raw.n_rows)

ds = build_random_effect_dataset_global(
    raw, "re", "userShard", "userId", mesh=mesh, active_cap=2,
    pad_entities_to_multiple=8,
)

# ground truth from the padded-global entity map: every row that belongs to
# a kept entity is either in an active block or passive — exactly once
ent_g = np.asarray(multihost.fully_replicate(ds.row_entity, mesh))
in_entity = np.flatnonzero(ent_g >= 0).astype(np.int64)
ar = np.asarray(multihost.fully_replicate(ds.blocks.active_rows, mesh)).ravel()
active = np.sort(ar[ar >= 0].astype(np.int64))
union = np.sort(np.concatenate([active, ds.passive_rows]))
assert np.array_equal(union, in_entity), (union.tolist(), in_entity.tolist())
assert len(np.intersect1d(active, ds.passive_rows)) == 0
print("PASSIVE_OK", jax.process_index(), len(ds.passive_rows))
"""


@pytest.mark.slow
def test_two_process_passive_rows_padded_space(tmp_path):
    """Satellite regression: _derive_passive_rows used to compare TRUE-global
    row ids against the PADDED-space active_rows table. With 21 rows on 2
    processes (host shares 11/10, padded to 11, chunk 4 -> n_local 12) every
    host-1 row id was off by the pad shift, so active rows were misclassified
    as passive. 3 users x 7 rows with active_cap=2 must yield exactly
    3 * (7 - 2) = 15 passive rows, disjoint from the active set, and the
    active/passive union must be exactly the rows mapped to a kept entity."""
    n_total = 21  # not divisible by chunk=4: host 1's padded ids shift by 1
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    # 4 virtual CPU devices per process
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", _PASSIVE_WORKER,
                f"coordinator=localhost:{port},process={i},n=2",
                str(n_total),
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process passive-rows build timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
        assert "PASSIVE_OK" in out
    counts = {
        int(l.split()[2])
        for _, out, _ in outs
        for l in out.splitlines()
        if l.startswith("PASSIVE_OK")
    }
    assert counts == {15}, counts


def test_single_process_passive_rows_partition():
    """Fast single-process counterpart of the padded-space regression: with 8
    virtual devices chunk=8, so 21 rows pad to 24 — active_rows and passive
    rows must still partition exactly the rows mapped to a kept entity."""
    from photon_ml_tpu.game.data_mp import build_random_effect_dataset_global
    from photon_ml_tpu.io.data import RawDataset
    from photon_ml_tpu.parallel import make_mesh

    n = 21
    g = np.arange(n)
    d = 2
    raw = RawDataset(
        n_rows=n,
        labels=np.asarray(g % 2, np.float64),
        offsets=np.zeros(n),
        weights=np.ones(n),
        shard_coo={
            "userShard": (
                np.repeat(np.arange(n), d),
                np.tile(np.arange(d), n),
                np.linspace(0.1, 1.0, n * d),
            )
        },
        shard_dims={"userShard": d},
        id_tags={"userId": np.array([f"u{x % 3}" for x in g], dtype=object)},
        global_row_start=0,
    )
    ds = build_random_effect_dataset_global(
        raw, "re", "userShard", "userId", mesh=make_mesh(n_data=8, n_model=1),
        active_cap=2, pad_entities_to_multiple=8,
    )
    ent_g = np.asarray(ds.row_entity)
    in_entity = np.flatnonzero(ent_g >= 0).astype(np.int64)
    ar = np.asarray(ds.blocks.active_rows).ravel()
    active = np.sort(ar[ar >= 0].astype(np.int64))
    union = np.sort(np.concatenate([active, ds.passive_rows]))
    np.testing.assert_array_equal(union, in_entity)
    assert len(ds.passive_rows) == 3 * (7 - 2)
