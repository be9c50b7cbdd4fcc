"""Out-of-core (streamed) FIXED-effect training parity.

The row-sliced streamed path (game/fe_streaming.py + optimize/host_driver.py)
must reproduce the resident device path: same objective decomposed into
per-slice partials, same solver semantics on the host, just pipelined through
the chip in budget-sized double-buffered row slices. Accumulation order is
fixed (left-to-right over slices), so repeated evaluations — and whole
repeated solves — are bitwise identical run-to-run.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectCoordinate,
    GLMOptimizationConfig,
    RandomEffectCoordinate,
    ValidationContext,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.fe_streaming import (
    StreamedFEObjective,
    estimate_fe_batch_bytes,
    rows_per_slice,
    score_streamed_fe,
)
from photon_ml_tpu.evaluation import build_suite
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.robust import faults
from photon_ml_tpu.testing import generate_mixed_effect_data
from photon_ml_tpu.testing.generators import mixed_data_to_raw_dataset


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.clear()


def _cfg(l2=0.8, optimizer="LBFGS", reg="L2", **opt_kw):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType(optimizer),
            tolerance=1e-9,
            max_iterations=80,
            **opt_kw,
        ),
        regularization=RegularizationContext(reg),
        reg_weight=l2,
    )


@pytest.fixture(scope="module")
def raw():
    return mixed_data_to_raw_dataset(
        generate_mixed_effect_data(
            n=700, d_fixed=6, re_specs={"userId": (20, 4)}, seed=11
        )
    )


def _pair(raw, budget_bytes=16 << 10, layout="auto", dtype=jnp.float64):
    mem = build_fixed_effect_dataset(raw, "global", "global", dtype=dtype, layout=layout)
    streamed = build_fixed_effect_dataset(
        raw, "global", "global", dtype=dtype, layout=layout,
        hbm_budget_bytes=budget_bytes,
    )
    assert streamed.streamed, "budget should force the streamed build"
    assert streamed.batch is None and streamed.host_batch is not None
    return mem, streamed


# ---------------------------------------------------------------- sizing


def test_estimate_and_rows_per_slice():
    assert estimate_fe_batch_bytes(100, 10, "dense") == 100 * 10 * 4 + 3 * 100 * 4
    # ELL: idx (i32) + val planes per row slot
    assert (
        estimate_fe_batch_bytes(100, 10, "ell", ell_width=3)
        == 100 * 3 * (4 + 4) + 3 * 100 * 4
    )
    with pytest.raises(ValueError, match="dense|ell"):
        estimate_fe_batch_bytes(1, 1, "coo")
    # budget halves for double buffering, rounds down to the row multiple
    assert rows_per_slice(8000, 100) == 40
    # floor: at least one row multiple even under an absurd budget
    assert rows_per_slice(0, 100) == 8


def test_budget_decision(raw):
    big = build_fixed_effect_dataset(
        raw, "global", "global", hbm_budget_bytes=1 << 30
    )
    assert not big.streamed and big.batch is not None
    _, streamed = _pair(raw)
    assert streamed.hbm_budget_bytes == 16 << 10
    with pytest.raises(ValueError, match="row-sliceable layout"):
        build_fixed_effect_dataset(
            raw, "global", "global", layout="tiled", hbm_budget_bytes=0
        )


def test_streamed_ell_build(raw):
    _, streamed = _pair(raw, layout="ell")
    hb = streamed.host_batch
    assert hb.layout == "ell"
    assert hb.ell_idx is not None and hb.ell_val.shape == hb.ell_idx.shape


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("layout", ["dense", "ell"])
@pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
def test_streamed_train_matches_resident(raw, layout, optimizer):
    mem, streamed = _pair(raw, layout=layout)
    cfg = _cfg(optimizer=optimizer)
    cm = FixedEffectCoordinate(dataset=mem, task="logistic_regression", config=cfg)
    cs = FixedEffectCoordinate(dataset=streamed, task="logistic_regression", config=cfg)
    m_mem, r_mem = cm.train(None)
    m_str, r_str = cs.train(None)
    np.testing.assert_allclose(
        np.asarray(m_str.model.coefficients.means),
        np.asarray(m_mem.model.coefficients.means),
        atol=1e-6,
    )
    assert abs(float(r_str.loss) - float(r_mem.loss)) < 1e-8 * max(
        1.0, abs(float(r_mem.loss))
    )


def test_streamed_owlqn_l1_parity(raw):
    mem, streamed = _pair(raw)
    cfg = _cfg(l2=0.5, reg="ELASTIC_NET")
    cm = FixedEffectCoordinate(dataset=mem, task="logistic_regression", config=cfg)
    cs = FixedEffectCoordinate(dataset=streamed, task="logistic_regression", config=cfg)
    m_mem, _ = cm.train(None)
    m_str, _ = cs.train(None)
    np.testing.assert_allclose(
        np.asarray(m_str.model.coefficients.means),
        np.asarray(m_mem.model.coefficients.means),
        atol=1e-6,
    )


def test_streamed_residual_and_warm_start_parity(raw):
    mem, streamed = _pair(raw)
    cfg = _cfg()
    cm = FixedEffectCoordinate(dataset=mem, task="logistic_regression", config=cfg)
    cs = FixedEffectCoordinate(dataset=streamed, task="logistic_regression", config=cfg)
    n = raw.n_rows
    residual = jnp.asarray(np.linspace(-0.5, 0.5, n), jnp.float64)
    m0, _ = cm.train(None)
    m_mem, r_mem = cm.train(residual, initial_model=m0)
    m_str, r_str = cs.train(residual, initial_model=m0)
    np.testing.assert_allclose(
        np.asarray(m_str.model.coefficients.means),
        np.asarray(m_mem.model.coefficients.means),
        atol=1e-6,
    )


def test_streamed_normalization_parity(raw):
    from photon_ml_tpu.ops.normalization import build_normalization
    from photon_ml_tpu.utils.stats import compute_feature_statistics

    stats = compute_feature_statistics(raw, "global")
    norm = build_normalization(
        "SCALE_WITH_STANDARD_DEVIATION", stats["mean"], stats["variance"],
        stats["max_magnitude"], intercept_index=None, dtype=jnp.float64,
    )
    mem, streamed = _pair(raw)
    cfg = _cfg()
    cm = FixedEffectCoordinate(
        dataset=mem, task="logistic_regression", config=cfg, normalization=norm
    )
    cs = FixedEffectCoordinate(
        dataset=streamed, task="logistic_regression", config=cfg, normalization=norm
    )
    m_mem, _ = cm.train(None)
    m_str, _ = cs.train(None)
    np.testing.assert_allclose(
        np.asarray(m_str.model.coefficients.means),
        np.asarray(m_mem.model.coefficients.means),
        atol=1e-6,
    )


def test_streamed_variance_refused(raw):
    _, streamed = _pair(raw)
    cs = FixedEffectCoordinate(
        dataset=streamed,
        task="logistic_regression",
        config=dataclasses.replace(_cfg(), variance_type="SIMPLE"),
    )
    with pytest.raises(ValueError, match="variance"):
        cs.train(None)


def test_streamed_down_sampling_refused(raw):
    _, streamed = _pair(raw)
    cs = FixedEffectCoordinate(
        dataset=streamed,
        task="logistic_regression",
        config=dataclasses.replace(_cfg(), down_sampling_rate=0.5),
    )
    with pytest.raises(ValueError, match="down_sampling_rate"):
        cs.train(None)


def test_streamed_score_matches_resident(raw):
    mem, streamed = _pair(raw)
    cfg = _cfg()
    cm = FixedEffectCoordinate(dataset=mem, task="logistic_regression", config=cfg)
    cs = FixedEffectCoordinate(dataset=streamed, task="logistic_regression", config=cfg)
    model, _ = cm.train(None)
    s_mem = np.asarray(cm.score(model))
    s_str = np.asarray(cs.score(model))
    np.testing.assert_allclose(s_str, s_mem, atol=1e-10)


# ---------------------------------------------------------------- stability


def test_streamed_objective_bitwise_stable(raw):
    """Fixed left-to-right slice accumulation: repeated evaluations and a
    repeated whole solve are BITWISE identical, not merely close."""
    from photon_ml_tpu.ops.losses import get_loss

    _, streamed = _pair(raw)
    hb = streamed.host_batch
    obj = StreamedFEObjective(
        get_loss("logistic_regression"), hb, 16 << 10, l2_weight=0.3
    )
    assert obj.n_slices > 1
    w = np.linspace(-0.2, 0.2, hb.dim).astype(np.float64)
    v1, g1 = obj.value_and_grad(w)
    v2, g2 = obj.value_and_grad(w)
    assert float(v1) == float(v2)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    h1 = obj.hessian_vector(w, w)
    h2 = obj.hessian_vector(w, w)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))

    cfg = _cfg()
    cs = FixedEffectCoordinate(
        dataset=streamed, task="logistic_regression", config=cfg
    )
    ma, _ = cs.train(None)
    mb, _ = cs.train(None)
    np.testing.assert_array_equal(
        np.asarray(ma.model.coefficients.means),
        np.asarray(mb.model.coefficients.means),
    )


# ---------------------------------------------------------------- telemetry


def test_streamed_metrics_recorded(raw):
    _, streamed = _pair(raw)
    run = obs.RunTelemetry()
    with obs.use_run(run):
        cs = FixedEffectCoordinate(
            dataset=streamed, task="logistic_regression", config=_cfg()
        )
        _, result = cs.train(None)
    snap = {
        (m["name"], m["labels"].get("site"), m["labels"].get("kind")): m
        for m in run.registry.snapshot()
    }
    staged = snap[("photon_stream_staged_bytes_total", "fe.train", None)]["value"]
    assert staged > 0
    n_slices = snap[("photon_stream_slices_total", "fe.train", None)]["value"]
    assert n_slices >= 2
    vg = snap[("photon_stream_passes_total", "fe.train", "vg")]["value"]
    assert vg >= result.iterations
    # every staged slice stayed within the double-buffered budget
    actual = snap[("photon_stream_actual_slice_bytes", "fe.train", None)]["value"]
    assert 0 < 2 * actual <= 16 << 10
    # the overlap claim is measured: stage wall and solve wall are both
    # recorded, and staging (H2D) must not dominate the solve
    stage_s = snap[("photon_stream_stage_seconds", "fe.train", None)]["value"]
    solve_s = snap[("photon_stream_solve_seconds", "fe.train", None)]["value"]
    assert 0 <= stage_s <= solve_s


# ---------------------------------------------------- satellite: RE scoring


def test_re_streamed_score_work_flat_in_slice_count(raw):
    """Satellite: streamed RE scoring is O(n) per sweep — the regrouped
    device work (padded gathered rows) stays <= 2n no matter how many slices
    the budget forces, where the old masked sweep did n * n_slices work."""
    kw = dict(active_cap=64, dtype=jnp.float64)
    mem = build_random_effect_dataset(raw, "re", "userShard", "userId", **kw)
    cm = RandomEffectCoordinate(dataset=mem, task="logistic_regression", config=_cfg())
    model, _ = cm.train(None)
    ref = np.asarray(cm.score(model))

    n = raw.n_rows
    work = []
    for budget in (16 << 10, 1 << 10):  # few slices vs many slices
        ds = build_random_effect_dataset(
            raw, "re", "userShard", "userId", hbm_budget_bytes=budget, **kw
        )
        assert ds.streamed
        cs = RandomEffectCoordinate(
            dataset=ds, task="logistic_regression", config=_cfg()
        )
        scores = np.asarray(cs.score(model))
        np.testing.assert_allclose(scores, ref, atol=1e-8)
        cache = getattr(ds, "_stream_xsub_cache", None)
        assert cache is not None and cache.device_rows > 0
        work.append(cache.device_rows)
        assert cache.device_rows <= 2 * n
    # more slices must NOT mean more per-sweep device work
    assert work[1] <= 2 * n and work[0] <= 2 * n


# ---------------------------------------------------------------- defense


def test_streamed_fe_divergence_rejection(raw):
    """The NaN fault + divergence guard keep working on the streamed path:
    corrupting the streamed FE solver's input freezes the solve at w0 with
    NUMERICAL_DIVERGENCE and the coordinate update is rejected."""
    _, streamed = _pair(raw)
    re_ds = build_random_effect_dataset(
        raw, "per-user", "userShard", "userId", dtype=jnp.float64
    )
    run = obs.RunTelemetry()
    with obs.use_run(run):
        coords = {
            "global": FixedEffectCoordinate(
                dataset=streamed, task="logistic_regression", config=_cfg()
            ),
            "per-user": RandomEffectCoordinate(
                dataset=re_ds, task="logistic_regression", config=_cfg()
            ),
        }
        validation = ValidationContext(
            suite=build_suite(["LOGISTIC_LOSS"], raw.labels),
            score_fns={name: coords[name].score for name in coords},
            offsets=raw.offsets,
        )
        faults.configure("solver.value_and_grad:nan:1")
        result = CoordinateDescent(coords, n_iterations=2, validation=validation).run()
        rejected = (
            run.registry.counter("photon_coordinate_rejections_total", "")
            .labels(coordinate="global")
            .value
        )
    assert rejected == 1
    for name in coords:
        assert np.isfinite(np.asarray(coords[name].score(result.model[name]))).all()


def test_streamed_fe_checkpoint_resume(raw, tmp_path):
    """Boundary checkpoints keep working with a streamed FE coordinate: a
    fresh descent resumed from the first boundary reproduces the
    uninterrupted run."""
    from photon_ml_tpu.robust import CheckpointManager

    def make():
        _, streamed = _pair(raw)
        re_ds = build_random_effect_dataset(
            raw, "per-user", "userShard", "userId", dtype=jnp.float64
        )
        coords = {
            "global": FixedEffectCoordinate(
                dataset=streamed, task="logistic_regression", config=_cfg()
            ),
            "per-user": RandomEffectCoordinate(
                dataset=re_ds, task="logistic_regression", config=_cfg()
            ),
        }
        return coords

    coords = make()
    ref = CoordinateDescent(coords, n_iterations=2, validation=None).run()

    ckpt = str(tmp_path / "ck")
    mgr = CheckpointManager(ckpt, fsync=False)
    coords2 = make()
    CoordinateDescent(
        coords2, n_iterations=2, validation=None, boundary_fn=mgr.on_boundary
    ).run()
    snap = CheckpointManager(ckpt, fsync=False).latest_valid(
        expect_coordinate_order=list(coords2), expect_n_iterations=2
    )
    assert snap is not None
    coords3 = make()
    resumed = CoordinateDescent(
        coords3, n_iterations=2, validation=None, resume_state=snap
    ).run()
    for name in coords:
        np.testing.assert_allclose(
            np.asarray(coords[name].score(ref.model[name])),
            np.asarray(coords[name].score(resumed.model[name])),
            atol=1e-8,
        )


# ---------------------------------------------------------------- e2e CLI


def test_cli_trains_streamed_fe_with_parity(tmp_path):
    """Acceptance e2e: an FE coordinate whose feature matrix exceeds an
    artificially shrunk hbm.budget.mb trains STREAMED through cli.train —
    multi-part input exercises the chunked pipelined ingest — and reproduces
    the in-memory run's quality within dtype tolerances."""
    from photon_ml_tpu.cli.train import run as train_run
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(
        n=600, d_fixed=6, re_specs={"userId": (24, 5)}, seed=4, entity_skew=1.4
    )
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
    recs = generate_game_records(data)
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    for i in range(3):  # multi-part: the chunked reader pipelines over parts
        write_avro_file(
            str(train_dir / f"part-{i:05d}.avro"), schema, recs[i * 200 : (i + 1) * 200]
        )

    args = [
        "--input-data", str(train_dir),
        "--validation-data", str(train_dir),
        "--task", "logistic_regression",
        "--feature-shard", "name=global,bags=features",
        "--feature-shard", "name=userShard,bags=userFeatures",
        "--coordinate",
        "name=per-user,shard=userShard,re.type=userId,reg.type=L2,reg.weights=1",
        "--evaluators", "AUC",
    ]
    fe_coord = "name=global,shard=global,optimizer=LBFGS,reg.type=L2,reg.weights=1"

    out_mem = str(tmp_path / "out-mem")
    s_mem = train_run(args + ["--coordinate", fe_coord, "--output-dir", out_mem])
    out_str = str(tmp_path / "out-streamed")
    # zero budget: far below the feature matrix footprint => streamed FE
    s_str = train_run(
        args
        + ["--coordinate", fe_coord + ",hbm.budget.mb=0", "--output-dir", out_str]
    )
    assert abs(s_str["best"]["metrics"]["AUC"] - s_mem["best"]["metrics"]["AUC"]) < 1e-3


def test_score_streamed_fe_direct(raw):
    """score_streamed_fe agrees with a resident matvec bit-for-bit at f64."""
    mem, streamed = _pair(raw)
    hb = streamed.host_batch
    w = jnp.asarray(np.linspace(-0.3, 0.3, hb.dim), jnp.float64)
    ref = np.asarray(mem.batch.features.matvec(w))
    out = np.asarray(score_streamed_fe(hb, w, 16 << 10, jnp.float64))
    np.testing.assert_allclose(out, ref, atol=1e-12)


def test_disk_to_slice_streamed_fit_matches_memory_path(tmp_path):
    """Acceptance: a streamed-FE fit fed by the disk→slice path (part files
    decoded across the ingest worker pool straight into preallocated
    HostRowBatch planes — no RawDataset ever materializes) is BIT-identical
    to one fed by the in-memory builder, host planes and trained
    coefficients alike, at any worker count."""
    from photon_ml_tpu.game.data import build_fixed_effect_dataset_from_disk
    from photon_ml_tpu.io import (
        FeatureShardConfig,
        read_avro_dataset_chunked,
        write_avro_file,
    )
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(n=240, d_fixed=6, re_specs={}, seed=7)
    recs = generate_game_records(data)
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    for i in range(4):
        write_avro_file(
            str(train_dir / f"part-{i:05d}.avro"),
            TRAINING_EXAMPLE_AVRO,
            recs[i * 60 : (i + 1) * 60],
        )
    shards = {"global": FeatureShardConfig(feature_bags=("features",))}
    raw, maps = read_avro_dataset_chunked(str(train_dir), shards, engine="python")
    budget = 4 << 10  # far below the matrix footprint => streamed row slices
    mem = build_fixed_effect_dataset(
        raw, "global", "global", dtype=jnp.float64, hbm_budget_bytes=budget
    )
    assert mem.streamed
    disk, _ = build_fixed_effect_dataset_from_disk(
        str(train_dir), shards, "global", "global", budget,
        index_maps=maps, dtype=jnp.float64, workers=3,
    )
    assert disk.streamed
    assert mem.host_batch.dense.tobytes() == disk.host_batch.dense.tobytes()
    np.testing.assert_array_equal(mem.host_batch.labels, disk.host_batch.labels)

    cfg = _cfg()
    m_mem, _ = FixedEffectCoordinate(
        dataset=mem, task="logistic_regression", config=cfg
    ).train(None)
    m_disk, _ = FixedEffectCoordinate(
        dataset=disk, task="logistic_regression", config=cfg
    ).train(None)
    assert (
        np.asarray(m_mem.model.coefficients.means).tobytes()
        == np.asarray(m_disk.model.coefficients.means).tobytes()
    )


def test_disk_to_slice_refuses_non_row_sliceable_layout(tmp_path):
    from photon_ml_tpu.game.data import build_fixed_effect_dataset_from_disk
    from photon_ml_tpu.io import FeatureShardConfig, write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing.generators import generate_game_records

    data = generate_mixed_effect_data(n=40, d_fixed=4, re_specs={}, seed=9)
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    write_avro_file(
        str(train_dir / "part-00000.avro"),
        TRAINING_EXAMPLE_AVRO,
        generate_game_records(data),
    )
    shards = {"global": FeatureShardConfig(feature_bags=("features",))}
    with pytest.raises(ValueError, match="requires a row-sliceable layout"):
        build_fixed_effect_dataset_from_disk(
            str(train_dir), shards, "global", "global", 1 << 20, layout="tiled"
        )
