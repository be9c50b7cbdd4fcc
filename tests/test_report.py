"""Training-report subsystem tests: diagnostics oracles, the golden
report.json schema, jax-free rendering (subprocess with a poisoned jax on
sys.path), and the slow `cli train --report-out` -> `cli report`
rebuild-identity end-to-end."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu.cli import report as report_cli
from photon_ml_tpu.obs import diagnostics
from photon_ml_tpu.obs import report as report_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- oracles


def test_coefficient_summary_oracle():
    values = [0.0, 1.0, -2.0, 0.5]
    names = ["a", "b", "c", "d"]
    s = diagnostics.coefficient_summary(values, names, n_features_total=10, top_k=2)
    assert s["n_recorded"] == 4 and s["n_nonzero"] == 3
    assert s["n_features_total"] == 10
    assert s["sparsity"] == pytest.approx(0.7)
    assert s["l1_norm"] == pytest.approx(3.5)
    assert s["l2_norm"] == pytest.approx(math.sqrt(1 + 4 + 0.25))
    assert s["max_abs"] == pytest.approx(2.0)
    assert s["quantiles"]["p0"] == pytest.approx(-2.0)
    assert s["quantiles"]["p100"] == pytest.approx(1.0)
    assert s["quantiles"]["p50"] == pytest.approx(0.25)
    # top-k by |weight|, stable order, truncated to k
    assert s["top_features"] == [
        {"feature": "c", "weight": -2.0},
        {"feature": "b", "weight": 1.0},
    ]


def test_coefficient_summary_empty_and_nameless():
    s = diagnostics.coefficient_summary([])
    assert s["n_recorded"] == 0 and s["l2_norm"] == 0.0
    assert s["top_features"] == []
    # no names -> no top_features even with values
    assert diagnostics.coefficient_summary([1.0])["top_features"] == []


def test_shrinkage_summary_oracle():
    """Hand-computed log2 binning: bin = floor(log2(count)), count 0 has
    its own bin; mean/min/max per bin."""
    norms = [1.0, 2.0, 3.0, 4.0, 5.0]
    counts = [1, 2, 3, 5, 0]
    s = diagnostics.shrinkage_summary(norms, counts)
    assert s["n_entities"] == 5
    assert s["norm_quantiles"]["p50"] == pytest.approx(3.0)
    assert [h["support"] for h in s["histogram"]] == ["0", "[1,2)", "[2,4)", "[4,8)"]
    by_bin = {h["support"]: h for h in s["histogram"]}
    assert by_bin["0"]["n_entities"] == 1
    assert by_bin["0"]["mean_norm"] == pytest.approx(5.0)
    assert by_bin["[2,4)"]["n_entities"] == 2
    assert by_bin["[2,4)"]["mean_norm"] == pytest.approx(2.5)
    assert by_bin["[2,4)"]["min_norm"] == pytest.approx(2.0)
    assert by_bin["[2,4)"]["max_norm"] == pytest.approx(3.0)
    with pytest.raises(ValueError):
        diagnostics.shrinkage_summary([1.0], [1, 2])


def test_gauge_trajectories_align_with_none_gaps():
    def g(name, value, **labels):
        return {"name": name, "kind": "gauge", "labels": labels, "value": value}

    snaps = [
        [g("photon_cd_accepted_loss", 5.0, coordinate="global")],
        [
            g("photon_cd_accepted_loss", 4.0, coordinate="global"),
            g("photon_cd_accepted_loss", 9.0, coordinate="per-user"),
        ],
    ]
    t = diagnostics.gauge_trajectories(snaps, "photon_cd_accepted_loss", "coordinate")
    assert t == {"global": [5.0, 4.0], "per-user": [None, 9.0]}


def test_iter_metric_snapshots_tolerates_torn_lines():
    lines = [
        json.dumps({"type": "metrics", "metrics": [{"name": "x"}]}),
        '{"type": "span", "name": "cd.sweep"}',
        '{"type": "metrics", "metr',  # torn trailing line from a crash
    ]
    snaps = list(diagnostics.iter_metric_snapshots(lines))
    assert snaps == [[{"name": "x"}]]


def test_sparkline_svg():
    svg = report_mod.sparkline_svg([1.0, None, 3.0, 2.0])
    assert svg.startswith("<svg") and "polyline" in svg
    # fewer than 2 finite points: placeholder box, no polyline
    placeholder = report_mod.sparkline_svg([1.0])
    assert "n/a" in placeholder and "polyline" not in placeholder


# ---------------------------------------------------------------- fixtures


def _gauge(name, value, **labels):
    return {"name": name, "kind": "gauge", "help": "", "labels": labels,
            "value": value}


def _compile_series(event, total):
    return {"name": "photon_jax_compile_seconds", "kind": "summary", "help": "",
            "labels": {"event": event}, "sum": total,
            "stat": {"count": 2, "mean": total / 2, "stdev": 0.1,
                     "max": total, "min": 0.0}}


def _final_snapshot():
    return [
        _gauge("photon_cd_accepted_loss", 4.0, coordinate="global"),
        _gauge("photon_cd_final_loss", 4.0, coordinate="global"),
        _gauge("photon_cd_update_iterations", 7.0, coordinate="global"),
        _gauge("photon_validation_metric", 0.71, metric="AUC", coordinate="global"),
        # the family as the hook of utils/compile_cache.py fills it: one series
        # per jax event whose name contains "compile"
        _compile_series("/jax/core/compile/backend_compile_duration", 1.25),
        _compile_series("/jax/core/compile/jaxpr_trace_duration", 0.5),
        _compile_series("/jax/compilation_cache/compile_time_saved_sec", 40.0),
        _gauge("photon_stream_budget_bytes", 1024.0, site="fe.train"),
        _gauge("photon_stream_actual_slice_bytes", 256.0, site="fe.train"),
        _gauge("photon_stream_budget_headroom_bytes", 512.0, site="fe.train"),
        _gauge("photon_mem_host_rss_bytes", 1000.0),
        _gauge("photon_mem_host_peak_rss_bytes", 2000.0),
        _gauge("photon_mem_device_bytes_in_use", 300.0, device="0"),
        _gauge("photon_mem_device_peak_bytes_in_use", 400.0, device="0"),
        _gauge("photon_mem_device_bytes_limit", 4096.0, device="0"),
    ]


def _write_model_fixture(model_dir):
    """A saved-model layout written without jax: one fixed effect, one
    random effect with three entities."""
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_AVRO

    def rec(model_id, triples):
        return {
            "modelId": model_id,
            "modelClass": None,
            "means": [{"name": n, "term": t, "value": v} for n, t, v in triples],
            "variances": None,
            "lossFunction": None,
        }

    os.makedirs(model_dir, exist_ok=True)
    with open(os.path.join(model_dir, "model-metadata.json"), "w") as f:
        json.dump({"modelType": "LOGISTIC_REGRESSION"}, f)

    fe = os.path.join(model_dir, "fixed-effect", "global")
    os.makedirs(os.path.join(fe, "coefficients"), exist_ok=True)
    with open(os.path.join(fe, "id-info"), "w") as f:
        f.write("globalShard\n")
    write_avro_file(
        os.path.join(fe, "coefficients", "part-00000.avro"),
        BAYESIAN_LINEAR_MODEL_AVRO,
        [rec("global", [("f0", "", 2.0), ("f1", "t", -1.0), ("f2", "", 0.5)])],
    )

    re_ = os.path.join(model_dir, "random-effect", "per-user")
    os.makedirs(os.path.join(re_, "coefficients"), exist_ok=True)
    with open(os.path.join(re_, "id-info"), "w") as f:
        f.write("userId\nuserShard\n")
    write_avro_file(
        os.path.join(re_, "coefficients", "part-00000.avro"),
        BAYESIAN_LINEAR_MODEL_AVRO,
        [
            rec("u1", [("f0", "", 3.0), ("f1", "", 4.0)]),
            rec("u2", [("f0", "", 1.0)]),
            rec("u3", []),
        ],
    )


def _make_artifacts(root):
    """A complete synthetic artifacts tree `cli report` can discover."""
    os.makedirs(root, exist_ok=True)
    final = _final_snapshot()
    with open(os.path.join(root, "run_summary.json"), "w") as f:
        json.dump(
            {
                "total_wall_seconds": 12.5,
                "task": "logistic_regression",
                "best": {"reg_weights": {"global": 1.0}, "metrics": {"AUC": 0.71}},
                "coordinates": {
                    "global": {
                        "iterations": {"count": 2, "mean": 7.0, "stdev": 0.0,
                                       "max": 7.0, "min": 7.0},
                        "convergence_reasons": {"GRADIENT_CONVERGED": 2},
                        "rejections": 1,
                    }
                },
                "metrics": final,
                "plan": {
                    "coordinates": [
                        {"name": "global", "kind": "fixed-effect",
                         "layout": "auto", "feature_dtype": None,
                         "residency": "streamed",
                         "sharding": "host-sharded rows (streamed slices)",
                         "pipelined": True, "hbm_budget_mb": 0,
                         "geometry": {}, "notes": []},
                    ],
                    "mesh_axes": {"data": 8, "model": 1},
                    "n_processes": 2,
                    "pipeline_depth": 2,
                    "trial_lanes": 1,
                    "normalization": "NONE",
                    "distributed": True,
                },
                "memory": {"host": {"rss_bytes": 1000, "peak_rss_bytes": 2000}},
                "timeline": {
                    "n_sweeps": 2,
                    "sweeps": [{"overlap_factor": 0.0}, {"overlap_factor": 0.25}],
                    "total": {"wall_seconds": 10.0, "phases": {"solve": 8.0},
                              "critical_path_seconds": 9.0, "other_seconds": 1.0,
                              "sum_of_phases_seconds": 12.0,
                              "overlap_factor": 0.25},
                },
            },
            f,
        )
    with open(os.path.join(root, "metrics.jsonl"), "w") as f:
        first = [
            _gauge("photon_cd_accepted_loss", 5.0, coordinate="global"),
            _gauge("photon_validation_metric", 0.65, metric="AUC",
                   coordinate="global"),
        ]
        f.write(json.dumps({"type": "metrics", "metrics": first}) + "\n")
        f.write(json.dumps({"type": "metrics", "metrics": final}) + "\n")
    with open(os.path.join(root, "training-summary.json"), "w") as f:
        json.dump({"task": "logistic_regression",
                   "best": {"reg_weights": {"global": 1.0}}}, f)
    _write_model_fixture(os.path.join(root, "models", "best"))
    idx = os.path.join(root, "index")
    os.makedirs(idx, exist_ok=True)
    with open(os.path.join(idx, "_index-globalShard-meta.json"), "w") as f:
        json.dump({"shard": "globalShard", "numPartitions": 1, "size": 6}, f)
    ck = os.path.join(root, "ckpt", "boundary-000001")
    os.makedirs(ck, exist_ok=True)
    with open(os.path.join(ck, "MANIFEST.json"), "w") as f:
        json.dump({"step": 1, "iteration": 0, "coordinate": "global",
                   "bytes": 123, "sha256": "0" * 64}, f)
    return root


# ---------------------------------------------------------------- golden schema


def test_report_json_golden_schema(tmp_path):
    """Pin the report.json schema: key sets at every level a consumer would
    bind to. Additions require a deliberate schema_version discussion."""
    root = _make_artifacts(str(tmp_path / "artifacts"))
    doc = report_cli.run([root, "--out", str(tmp_path / "rep")])

    assert doc["schema_version"] == 4
    assert set(doc) == {
        "schema_version", "task", "best", "models", "convergence",
        "performance", "plan", "memory", "checkpoints", "flight",
    }
    assert doc["task"] == "logistic_regression"

    # v4: the "bench" key went with the script that wrote its inputs
    # v3: flight-recorder postmortems ride along (none in these artifacts)
    assert doc["flight"] == []

    # v2: the resolved execution plan rides along verbatim from
    # run_summary.json (None when the run predates the planner)
    plan = doc["plan"]
    assert plan["n_processes"] == 2 and plan["mesh_axes"] == {"data": 8,
                                                             "model": 1}
    (cp,) = plan["coordinates"]
    assert cp["residency"] == "streamed"
    assert cp["sharding"] == "host-sharded rows (streamed slices)"

    assert set(doc["models"]) == {"best"}
    model = doc["models"]["best"]
    assert set(model) == {"metadata", "coordinates"}
    assert set(model["coordinates"]) == {"global", "per-user"}
    fe = model["coordinates"]["global"]
    assert set(fe) == {"type", "feature_shard", "coefficients"}
    assert fe["type"] == "fixed" and fe["feature_shard"] == "globalShard"
    assert set(fe["coefficients"]) == {
        "n_nonzero", "n_recorded", "n_features_total", "sparsity", "l1_norm",
        "l2_norm", "max_abs", "quantiles", "top_features",
    }
    assert set(fe["coefficients"]["quantiles"]) == {"p0", "p25", "p50", "p75",
                                                    "p100"}
    # sparsity uses the feature-index size: 3 recorded of 6 total
    assert fe["coefficients"]["sparsity"] == pytest.approx(0.5)
    assert fe["coefficients"]["top_features"][0] == {"feature": "f0",
                                                     "weight": 2.0}
    re_ = model["coordinates"]["per-user"]
    assert set(re_) == {"type", "feature_shard", "random_effect_type",
                        "n_entities", "coefficients", "shrinkage"}
    assert re_["type"] == "random" and re_["n_entities"] == 3
    assert set(re_["shrinkage"]) == {"n_entities", "norm_quantiles",
                                     "histogram"}
    assert [h["support"] for h in re_["shrinkage"]["histogram"]] == \
        ["0", "[1,2)", "[2,4)"]
    assert set(re_["shrinkage"]["histogram"][0]) == {
        "support", "n_entities", "mean_norm", "min_norm", "max_norm",
    }

    conv = doc["convergence"]
    assert set(conv) == {"coordinates", "validation_trajectories",
                         "n_metric_flushes"}
    assert conv["n_metric_flushes"] == 2
    g = conv["coordinates"]["global"]
    assert g["accepted_loss_trajectory"] == [5.0, 4.0]
    assert g["iterations_trajectory"] == [None, 7.0]
    assert g["final_loss"] == pytest.approx(4.0)
    assert g["rejections"] == 1
    assert conv["validation_trajectories"]["AUC"] == [0.65, 0.71]

    perf = doc["performance"]
    assert set(perf) == {"total_wall_seconds", "aborted", "compile_seconds",
                         "timeline", "streaming"}
    assert perf["aborted"] is False
    # 1.25 and not the family's 41.75: a re-trace is not a compilation, and
    # compile_time_saved_sec is what the persistent cache SAVED (before PR 30
    # the report summed every series, so a warm run reported the most)
    assert perf["compile_seconds"] == pytest.approx(1.25)
    assert set(perf["timeline"]) == {"n_sweeps", "total",
                                     "overlap_factor_per_sweep"}
    assert perf["timeline"]["overlap_factor_per_sweep"] == [0.0, 0.25]
    assert perf["streaming"]["fe.train"]["budget_utilization"] == \
        pytest.approx(0.5)

    assert doc["memory"]["host"]["rss_bytes"] == 1000
    assert doc["checkpoints"] == [{"step": 1, "iteration": 0,
                                   "coordinate": "global", "bytes": 123}]

    # files landed and report.json round-trips to the returned doc
    out = str(tmp_path / "rep")
    with open(os.path.join(out, "report.json")) as f:
        assert json.load(f) == json.loads(json.dumps(doc, default=float))
    with open(os.path.join(out, "report.html")) as f:
        html = f.read()
    assert html.lower().startswith("<!doctype html>") and "<svg" in html


def test_report_discovers_flight_dumps(tmp_path):
    """A flight-recorder postmortem in the artifacts tree lands as a
    doc["flight"] row (and an HTML section), ordered by trigger time."""
    root = _make_artifacts(str(tmp_path / "artifacts"))
    flight_dir = os.path.join(root, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    for seq, (kind, t) in enumerate(
        [("shed_spike", 200.0), ("crash", 100.0)]
    ):
        with open(
            os.path.join(flight_dir, f"flight-{kind}-{seq:04d}.json"), "w"
        ) as f:
            json.dump({
                "trigger": {"kind": kind, "detail": "drill", "unix_time": t},
                "window_seconds": 30.0,
                "identity": {"process_index": 0, "replica": None, "host": "h"},
                "events": [{"type": "span", "name": "x"}],
                "metrics": [],
            }, f)
    doc = report_cli.run([root, "--out", str(tmp_path / "rep")])
    assert [row["trigger"] for row in doc["flight"]] == ["crash", "shed_spike"]
    row = doc["flight"][0]
    assert row["detail"] == "drill" and row["n_events"] == 1
    assert row["path"].startswith("flight/")
    html = open(os.path.join(str(tmp_path / "rep"), "report.html")).read()
    assert "Flight recorder" in html


def test_report_cli_rejects_empty_dir(tmp_path):
    with pytest.raises(SystemExit):
        report_cli.run([str(tmp_path / "empty")])


# ---------------------------------------------------------------- jax-free


def test_report_cli_runs_with_poisoned_jax(tmp_path):
    """`cli report` must work in a process where importing jax raises — the
    acceptance criterion for the jax-free report path. The rebuilt
    report.json must equal the one built with jax importable."""
    root = _make_artifacts(str(tmp_path / "artifacts"))
    ref = report_cli.run([root, "--out", str(tmp_path / "ref")])

    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('jax is poisoned for this test')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(poison), REPO_ROOT, env.get("PYTHONPATH", "")]
    )
    out = str(tmp_path / "rebuilt")
    proc = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.cli.report", root, "--out", out],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    # the poison actually poisons: a control subprocess importing jax fails
    control = subprocess.run(
        [sys.executable, "-c", "import jax"], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert control.returncode != 0

    with open(os.path.join(out, "report.json")) as f:
        rebuilt = json.load(f)
    assert rebuilt == json.loads(json.dumps(ref, default=float))
    with open(os.path.join(out, "report.html")) as f:
        assert "<svg" in f.read()


def test_obs_and_io_import_without_jax(tmp_path):
    """The report-path modules import with jax poisoned (lint rule R8's
    runtime counterpart)."""
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text("raise ImportError('poisoned')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(poison), REPO_ROOT, env.get("PYTHONPATH", "")]
    )
    src = (
        "import photon_ml_tpu.obs as obs\n"
        "import photon_ml_tpu.obs.report, photon_ml_tpu.obs.diagnostics\n"
        "import photon_ml_tpu.obs.memory, photon_ml_tpu.cli.report\n"
        "from photon_ml_tpu.io import read_avro_file, IndexMap\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


# ---------------------------------------------------------------- memory block


def test_memory_block_from_snapshot():
    from photon_ml_tpu.obs.memory import memory_block

    block = memory_block(_final_snapshot())
    assert block["host"] == {"rss_bytes": 1000, "peak_rss_bytes": 2000}
    assert block["devices"]["0"] == {
        "bytes_in_use": 300, "peak_bytes_in_use": 400, "bytes_limit": 4096,
    }
    assert block["streaming"]["fe.train"]["hbm_budget_bytes"] == 1024
    assert memory_block([]) == {}


def test_sample_memory_host_and_peak_monotone():
    from photon_ml_tpu.obs.memory import sample_memory
    from photon_ml_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    sample_memory(reg)
    snap = {m["name"]: m for m in reg.snapshot()}
    assert snap["photon_mem_host_rss_bytes"]["value"] > 0
    peak1 = snap["photon_mem_host_peak_rss_bytes"]["value"]
    assert peak1 >= snap["photon_mem_host_rss_bytes"]["value"] * 0  # present
    # a second sample can only raise the peak
    sample_memory(reg)
    snap2 = {m["name"]: m for m in reg.snapshot()}
    assert snap2["photon_mem_host_peak_rss_bytes"]["value"] >= peak1


# ---------------------------------------------------------------- slow e2e


@pytest.mark.slow
def test_train_report_rebuild_identity(tmp_path):
    """Acceptance criterion: `cli train --report-out` writes report.json +
    report.html, and `cli report <artifacts-root>` rebuilds a byte-identical
    report.json from the artifacts alone."""
    from photon_ml_tpu.cli import train
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing import (
        generate_game_records, generate_mixed_effect_data,
    )

    data = generate_mixed_effect_data(
        n=300, d_fixed=4, re_specs={"userId": (8, 2)}, seed=7
    )
    recs = generate_game_records(data)
    schema = {
        **TRAINING_EXAMPLE_AVRO,
        "fields": TRAINING_EXAMPLE_AVRO["fields"] + [
            {"name": "userFeatures",
             "type": {"type": "array", "items": "FeatureAvro"}, "default": []}
        ],
    }
    train_p = str(tmp_path / "train.avro")
    write_avro_file(train_p, schema, recs)

    root = tmp_path / "run"
    train.run([
        "--input-data", train_p,
        "--task", "logistic_regression",
        "--feature-shard", "name=globalShard,bags=features",
        "--feature-shard", "name=userShard,bags=userFeatures",
        "--coordinate",
        "name=global,shard=globalShard,optimizer=LBFGS,reg.type=L2,"
        "reg.weights=1",
        "--coordinate",
        "name=per-user,shard=userShard,re.type=userId,reg.type=L2,"
        "reg.weights=1",
        "--coordinate-descent-iterations", "2",
        "--output-dir", str(root / "out"),
        "--metrics-out", str(root / "metrics"),
        "--report-out", str(root / "report"),
    ])

    rep = root / "report"
    assert (rep / "report.html").exists()
    with open(rep / "report.json", "rb") as f:
        trained_bytes = f.read()
    trained = json.loads(trained_bytes)
    assert trained["task"] == "logistic_regression"
    assert set(trained["models"]) == {"best"}
    assert set(trained["models"]["best"]["coordinates"]) == \
        {"global", "per-user"}
    g = trained["convergence"]["coordinates"]["global"]
    assert len(g["accepted_loss_trajectory"]) == \
        trained["convergence"]["n_metric_flushes"]
    assert any(v is not None for v in g["accepted_loss_trajectory"])
    assert trained["memory"]["host"]["rss_bytes"] > 0
    assert trained["performance"]["aborted"] is False

    report_cli.run([str(root), "--out", str(tmp_path / "rebuilt")])
    with open(tmp_path / "rebuilt" / "report.json", "rb") as f:
        rebuilt_bytes = f.read()
    assert rebuilt_bytes == trained_bytes
