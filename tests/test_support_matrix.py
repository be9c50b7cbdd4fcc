"""The README "Support matrix" is load-bearing documentation: every refused
combination in its ledger is asserted here against the actual refusal site,
so the table cannot drift from the code (and vice versa — removing a refusal
without updating the docs fails too).

Each case pins (a) the quoted message fragment appears verbatim in the
README ledger, and (b) triggering the combination raises with a message
containing that exact fragment. The matrix itself must be present in both
README.md and MIGRATION.md.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem
from photon_ml_tpu.ops.glm import MAX_FULL_VARIANCE_DIM, check_full_variance_dim
from photon_ml_tpu.ops.normalization import build_normalization
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.parallel import mesh as mesh_mod
from photon_ml_tpu.plan import PlanError
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def readme_text():
    return (ROOT / "README.md").read_text()


@pytest.fixture(scope="module")
def migration_text():
    return (ROOT / "MIGRATION.md").read_text()


@pytest.fixture(scope="module")
def raw():
    data = generate_mixed_effect_data(
        n=80, d_fixed=5, re_specs={"userId": (6, 3)}, seed=3
    )
    return mixed_data_to_raw_dataset(data)


def _cfg(**kw):
    return GLMOptimizationConfig(
        regularization=RegularizationContext("L2"), reg_weight=1.0, **kw
    )


def _estimator(ccs, mesh=None):
    return GameEstimator(
        task="logistic_regression", coordinate_configs=ccs, mesh=mesh
    )


def _fe(name="global", **kw):
    return CoordinateConfig(
        name=name, feature_shard="global", config=kw.pop("config", _cfg()), **kw
    )


# -- the refusal triggers (one per ledger row) -------------------------------


def _trigger_feature_dtype_tiled(raw):
    _estimator([_fe(layout="tiled", feature_dtype=jnp.bfloat16)])


def _trigger_feature_dtype_tiled_batch(raw):
    raw.to_batch("global", layout="tiled", feature_dtype=jnp.bfloat16)


def _trigger_tiled_no_mesh(raw):
    _estimator([_fe(layout="tiled")])


def _trigger_tiled_batch_no_mesh(raw):
    raw.to_batch("global", layout="tiled")


def _trigger_streamed_fe_bad_layout(raw):
    _estimator([_fe(layout="coo", hbm_budget_mb=1)])


def _trigger_streamed_fe_variance(raw):
    _estimator([_fe(config=_cfg(variance_type="SIMPLE"), hbm_budget_mb=1)])


def _trigger_streamed_fe_down_sampling(raw):
    _estimator([_fe(config=_cfg(down_sampling_rate=0.5), hbm_budget_mb=1)])


def _trigger_streamed_fe_deep_variance(raw):
    # the train-time re-check behind the estimator gate: direct GLMProblem use
    GLMProblem(
        task="logistic_regression", config=_cfg(variance_type="FULL")
    ).run_streamed(None, 1 << 20)


def _trigger_full_variance_ceiling(raw):
    check_full_variance_dim(MAX_FULL_VARIANCE_DIM + 1)


def _trigger_standardization_no_intercept(raw):
    d = 4
    build_normalization(
        "STANDARDIZATION", np.ones(d), np.ones(d), np.ones(d), intercept_index=None
    )


def _trigger_coo_on_mesh(raw):
    batch = raw.to_batch("global", layout="coo")
    mesh_mod.shard_batch(batch, mesh_mod.make_mesh(n_data=len(jax.devices())))


def _trigger_multiprocess_ell(raw, monkeypatch):
    batch = raw.to_batch("global", layout="ell")
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    mesh_mod.shard_batch(batch, mesh_mod.make_mesh(n_data=len(jax.devices())))


def _trigger_multiprocess_no_mesh(raw):
    from photon_ml_tpu.plan import check_multiprocess_mesh

    check_multiprocess_mesh(2, None)


def _trigger_multiprocess_model_axis(raw, monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    mesh_mod.shard_coefficients(
        jnp.zeros(8), mesh_mod.make_mesh(n_data=len(jax.devices()))
    )


def _trigger_serving_width_ladder(raw):
    from photon_ml_tpu.serving.engine import LADDER_WIDTH, _ladder_width

    _ladder_width(LADDER_WIDTH[-1] + 1)


def _trigger_disk_slice_bad_layout(raw, tmp_path):
    from photon_ml_tpu.game.data import build_fixed_effect_dataset_from_disk
    from photon_ml_tpu.io import FeatureShardConfig, write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(n=8, d_fixed=3, re_specs={}, seed=11)
    write_avro_file(
        str(tmp_path / "part-00000.avro"),
        TRAINING_EXAMPLE_AVRO,
        generate_game_records(data),
    )
    build_fixed_effect_dataset_from_disk(
        str(tmp_path),
        {"global": FeatureShardConfig(feature_bags=("features",))},
        "global",
        "global",
        1 << 20,
        layout="coo",
    )


def _trigger_socket_and_listen(raw):
    from photon_ml_tpu.cli.serve import check_socket_front

    check_socket_front("/tmp/serve.sock", "127.0.0.1:8473")


def _trigger_fleet_duplicate_model(raw):
    from photon_ml_tpu.plan import check_fleet_composition

    check_fleet_composition(["jobs-us", "jobs-emea", "jobs-us"])


def _trigger_fleet_front_af_unix(raw):
    from photon_ml_tpu.plan import check_fleet_composition

    check_fleet_composition((), front_replicas=["/tmp/photon-serve.sock"])


def _trigger_serving_store_version(raw, tmp_path):
    import json as _json

    from photon_ml_tpu.serving.store import ModelStore

    d = tmp_path / "store"
    d.mkdir()
    (d / "store-meta.json").write_text(
        _json.dumps({"version": 99, "task": "x", "coordinates": []})
    )
    ModelStore.open(str(d))


def _lane_check(ccs, mesh=None, distributed=False, **est_kw):
    from photon_ml_tpu.game.lanes import check_lane_composition

    est = GameEstimator(
        task="logistic_regression", coordinate_configs=ccs, mesh=mesh, **est_kw
    )
    check_lane_composition(est, 4, distributed=distributed)


def _trigger_lanes_mesh(raw):
    _lane_check([_fe()], mesh=mesh_mod.make_mesh(n_data=len(jax.devices())))


def _trigger_lanes_multiprocess(raw):
    _lane_check([_fe()], distributed=True)


def _trigger_lanes_pipeline(raw):
    _lane_check([_fe()], pipeline_depth=2)


def _trigger_lanes_partial_retrain(raw):
    _lane_check([_fe()], partial_retrain_locked=["global"])


def _trigger_lanes_streamed(raw):
    _lane_check([_fe(hbm_budget_mb=1)])


def _trigger_lanes_l1(raw):
    _lane_check(
        [
            _fe(
                config=GLMOptimizationConfig(
                    regularization=RegularizationContext("L1"), reg_weight=1.0
                )
            )
        ]
    )


def _trigger_lanes_variance(raw):
    _lane_check([_fe(config=_cfg(variance_type="SIMPLE"))])


def _trigger_lanes_down_sampling(raw):
    _lane_check([_fe(config=_cfg(down_sampling_rate=0.5))])


def _trigger_lanes_normalization(raw):
    d = 4
    norm = build_normalization(
        "STANDARDIZATION", np.ones(d), np.ones(d), np.ones(d), intercept_index=0
    )
    _lane_check([_fe(normalization=norm)])


def _trigger_lanes_regularize_by_prior(raw):
    _lane_check([_fe(regularize_by_prior=True)])


def _trigger_retrain_distributed(raw):
    from photon_ml_tpu.cli.params import check_retrain_composition

    check_retrain_composition(True, 1)


def _trigger_retrain_trial_lanes(raw):
    from photon_ml_tpu.cli.params import check_retrain_composition

    check_retrain_composition(False, 4)


def _trigger_retrain_streamed(raw):
    from photon_ml_tpu.cli.params import check_retrain_composition

    check_retrain_composition(False, 1, ["global"])


def _trigger_prior_index_mismatch(raw, tmp_path):
    from photon_ml_tpu.io.index_map import IndexMap
    from photon_ml_tpu.io.model_io import (
        check_prior_compatibility,
        save_game_model,
    )
    from photon_ml_tpu.models.game import FixedEffectModel, GameModel
    from photon_ml_tpu.models.glm import Coefficients, LogisticRegressionModel

    imaps = {
        "global": IndexMap.from_name_terms(
            [("f0", ""), ("f1", "")], add_intercept=False
        )
    }
    model = GameModel(
        models={
            "global": FixedEffectModel(
                model=LogisticRegressionModel(
                    Coefficients(jnp.asarray([1.0, 2.0]))
                ),
                feature_shard="global",
            )
        },
        task="logistic_regression",
    )
    model_dir = str(tmp_path / "prior")
    save_game_model(model_dir, model, imaps)
    shrunk = {
        "global": IndexMap.from_name_terms([("f0", "")], add_intercept=False)
    }
    check_prior_compatibility(model_dir, shrunk)


def _trigger_ckpt_model_axis_reshape(raw):
    from photon_ml_tpu.plan import planner

    planner.check_checkpoint_topology(
        {"mesh_axes": {"data": 8, "model": 1}},
        {"mesh_axes": {"data": 4, "model": 2}},
    )


def _trigger_ckpt_process_count_reshape(raw):
    from photon_ml_tpu.plan import planner

    planner.check_checkpoint_topology(
        {"n_processes": 2, "global_rows": 8},
        {"n_processes": 3, "global_rows": 9},
    )


def _trigger_ckpt_plan_fingerprint(raw):
    from photon_ml_tpu.plan import planner

    planner.check_checkpoint_topology(
        {"plan_fingerprint": "fp-aaaa"}, {"plan_fingerprint": "fp-bbbb"}
    )


def _trigger_chain_state_version(raw, tmp_path):
    import json

    from photon_ml_tpu.game import incremental

    chain_dir = tmp_path / "chain"
    chain_dir.mkdir()
    (chain_dir / incremental.CHAIN_STATE_NAME).write_text(
        json.dumps({"version": 99, "days": []})
    )
    incremental._load_chain_state(str(chain_dir))


CASES = [
    # (id, documented message fragment, exception type, trigger)
    (
        "ckpt-model-axis-reshape",
        "checkpoint mesh reshape across the model axis is not supported",
        PlanError,
        _trigger_ckpt_model_axis_reshape,
    ),
    (
        "ckpt-process-count-reshape",
        "the process count changed and no legal reshape exists",
        PlanError,
        _trigger_ckpt_process_count_reshape,
    ),
    (
        "ckpt-plan-fingerprint",
        "resuming across a changed execution plan is not supported",
        PlanError,
        _trigger_ckpt_plan_fingerprint,
    ),
    (
        "chain-state-version",
        "unsupported chain-state version",
        ValueError,
        _trigger_chain_state_version,
    ),
    (
        "retrain-distributed",
        "incremental retrain is single-process: not composable with "
        "--distributed",
        PlanError,
        _trigger_retrain_distributed,
    ),
    (
        "retrain-trial-lanes",
        "incremental retrain warm-starts with regularize-by-prior: not "
        "composable with --trial-lanes",
        PlanError,
        _trigger_retrain_trial_lanes,
    ),
    (
        "retrain-streamed",
        "incremental retrain requires HBM-resident coordinates: not "
        "composable with hbm.budget.mb streaming",
        PlanError,
        _trigger_retrain_streamed,
    ),
    (
        "prior-index-mismatch",
        "prior model features absent from the current feature index",
        ValueError,
        _trigger_prior_index_mismatch,
    ),
    (
        "lanes-mesh",
        "trial-lanes sweeps are single-chip: not composable with a device "
        "mesh",
        PlanError,
        _trigger_lanes_mesh,
    ),
    (
        "lanes-multiprocess",
        "trial-lanes sweeps are single-process: not composable with "
        "multi-process training",
        PlanError,
        _trigger_lanes_multiprocess,
    ),
    (
        "lanes-pipeline",
        "trial-lanes sweeps drive their own lane schedule: not composable "
        "with pipeline_depth > 1",
        PlanError,
        _trigger_lanes_pipeline,
    ),
    (
        "lanes-partial-retrain",
        "partial retraining (locked coordinates) is not supported with "
        "trial-lanes",
        PlanError,
        _trigger_lanes_partial_retrain,
    ),
    (
        "lanes-streamed",
        "trial-lanes sweeps require HBM-resident coordinates",
        PlanError,
        _trigger_lanes_streamed,
    ),
    (
        "lanes-l1",
        "trial-lanes sweeps support L2 regularization only (the OWL-QN l1 "
        "weight is one operand of a solve, not a per-lane vector)",
        ValueError,
        _trigger_lanes_l1,
    ),
    (
        "lanes-variance",
        "trial-lanes sweeps require variance=NONE",
        ValueError,
        _trigger_lanes_variance,
    ),
    (
        "lanes-down-sampling",
        "down-sampling is not supported with trial-lanes",
        ValueError,
        _trigger_lanes_down_sampling,
    ),
    (
        "lanes-normalization",
        "feature normalization is not supported with trial-lanes",
        ValueError,
        _trigger_lanes_normalization,
    ),
    (
        "lanes-regularize-by-prior",
        "regularize-by-prior is not supported with trial-lanes",
        ValueError,
        _trigger_lanes_regularize_by_prior,
    ),
    (
        "feature-dtype-tiled-estimator",
        "feature_dtype is not supported with layout='tiled'",
        PlanError,
        _trigger_feature_dtype_tiled,
    ),
    (
        "feature-dtype-tiled-batch",
        "feature_dtype is not supported on the tiled layout",
        ValueError,
        _trigger_feature_dtype_tiled_batch,
    ),
    (
        "tiled-no-mesh-estimator",
        "layout='tiled' requires the estimator to be built with a device mesh",
        ValueError,
        _trigger_tiled_no_mesh,
    ),
    (
        "tiled-no-mesh-batch",
        "layout='tiled' requires a device mesh",
        ValueError,
        _trigger_tiled_batch_no_mesh,
    ),
    (
        "streamed-fe-bad-layout",
        "hbm_budget_mb on a fixed effect requires a row-sliceable layout",
        ValueError,
        _trigger_streamed_fe_bad_layout,
    ),
    (
        "streamed-fe-variance",
        "is not supported with hbm_budget_mb on a fixed effect "
        "(out-of-core row slices never materialize the Hessian)",
        PlanError,
        _trigger_streamed_fe_variance,
    ),
    (
        "streamed-fe-down-sampling",
        "down_sampling_rate < 1 is not supported with hbm_budget_mb on a "
        "fixed effect",
        PlanError,
        _trigger_streamed_fe_down_sampling,
    ),
    (
        "streamed-fe-deep-check",
        "not supported on the streamed fixed-effect path",
        ValueError,
        _trigger_streamed_fe_deep_variance,
    ),
    (
        "full-variance-ceiling",
        "exceeds the supported ceiling",
        ValueError,
        _trigger_full_variance_ceiling,
    ),
    (
        "standardization-no-intercept",
        "STANDARDIZATION requires an intercept term",
        ValueError,
        _trigger_standardization_no_intercept,
    ),
    (
        "coo-on-mesh",
        "shard_batch does not support the column-sorted COO layout",
        NotImplementedError,
        _trigger_coo_on_mesh,
    ),
    (
        "multiprocess-ell",
        "multi-process ELL sharding is not supported",
        NotImplementedError,
        _trigger_multiprocess_ell,
    ),
    (
        "multiprocess-no-mesh",
        "multi-process training requires a device mesh spanning all global "
        "devices",
        PlanError,
        _trigger_multiprocess_no_mesh,
    ),
    (
        "multiprocess-model-axis",
        "model-axis sharding across processes is not supported yet",
        NotImplementedError,
        _trigger_multiprocess_model_axis,
    ),
    (
        "serving-width-ladder",
        "exceeds the serving engine's padded feature-width ladder",
        ValueError,
        _trigger_serving_width_ladder,
    ),
    (
        "serving-store-version",
        "unsupported serving store version",
        ValueError,
        _trigger_serving_store_version,
    ),
    (
        "socket-and-listen",
        "pass at most one of --socket / --listen (one socket front per "
        "server process)",
        ValueError,
        _trigger_socket_and_listen,
    ),
    (
        "fleet-duplicate-model",
        "duplicate model name in the serving fleet",
        PlanError,
        _trigger_fleet_duplicate_model,
    ),
    (
        "fleet-front-af-unix",
        "the replica front routes over TCP replicas: not composable with "
        "AF_UNIX socket paths",
        PlanError,
        _trigger_fleet_front_af_unix,
    ),
    (
        "disk-slice-bad-layout",
        "the disk-to-slice ingest path requires a row-sliceable layout",
        ValueError,
        _trigger_disk_slice_bad_layout,
    ),
]


@pytest.mark.parametrize(
    "fragment,exc,trigger", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_refusal_message_agrees_with_table(
    fragment, exc, trigger, raw, readme_text, monkeypatch, tmp_path
):
    assert fragment in readme_text, (
        "refusal message fragment missing from the README support-matrix "
        f"ledger: {fragment!r}"
    )
    available = {"monkeypatch": monkeypatch, "tmp_path": tmp_path}
    kwargs = {
        k: v
        for k, v in available.items()
        if k in trigger.__code__.co_varnames
    }
    with pytest.raises(exc, match=re.escape(fragment)):
        trigger(raw, **kwargs)


def test_pins_are_exactly_the_refusal_inventory():
    """The machine-readable contract (refusals.json, regenerated by
    ``python -m photon_ml_tpu.analysis --write-refusal-inventory``) and the
    CASES pins above must describe the same refusal set, both directions:
    every pin backs an inventory entry with a matching exception type, and
    every inventory entry is exercised by some pin."""
    import json

    inv = json.loads((ROOT / "refusals.json").read_text())
    entries = inv["refusals"]
    assert len(entries) == len(CASES)
    for _id, fragment, exc, _trigger in CASES:
        matching = [e for e in entries if fragment in e["fragment"]]
        assert matching, f"pin not in refusals.json: {fragment!r}"
        assert any(exc.__name__ in e["exceptions"] for e in matching), fragment
        assert all(e["modules"] for e in matching), fragment
    for entry in entries:
        assert any(
            c[1] in entry["fragment"] for c in CASES
        ), f"inventory entry pinned by no case: {entry['fragment']!r}"


def test_matrix_present_in_both_docs(readme_text, migration_text):
    for text, doc in ((readme_text, "README.md"), (migration_text, "MIGRATION.md")):
        assert "## Support matrix" in text, doc
        # the two rows this PR added must be in the matrix, in both docs
        assert "streamed FE row slices" in text, doc
        assert "streamed RE entity slices" in text, doc


def test_documented_ceiling_matches_code(readme_text):
    # the README quotes the FULL-variance dim ceiling as a number; keep it
    # equal to the single source of truth in ops/glm.py
    assert f"d={MAX_FULL_VARIANCE_DIM}" in readme_text
