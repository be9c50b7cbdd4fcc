"""The cell ``poisson-enet-1chip.fit-enet-grid`` (PR 32) as a yardstick: its
files resolve and hold what the manifest tests ask of every cell, the
generator's law is pinned from ``data_seed``, the five readers the job brings
read a synthetic ``Observations``, every reader the manifest lists for the cell
takes the new job's ``Observations`` without raising, and the whole job runs
end to end on the CPU at a small size (Pallas in interpret mode)."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import correct_glm, data as gen, data_glm
from benchmark import run as brun
from benchmark.jobs import fit_glm
from benchmark.observe import Observations, SpanRecord
from benchmark.reference import glm_enet as ref

CELL = "poisson-enet-1chip.fit-enet-grid"
CONFIG = "poisson-enet-1chip"


@pytest.fixture(scope="module")
def cell():
    return brun.resolve_cell(brun.load_manifest(), CELL)


def test_the_cell_resolves_with_every_key_the_manifest_tests_ask_for(cell):
    manifest = brun.load_manifest()
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    workload = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert workload["chips"] == 1 and cell.chips == 1 and cell.config["mesh"]["data"] == 1
    assert cell.config["reduced"] == entry["reduced"] == ["rows"]
    assert cell.config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cell.traffic["job"] == "fit_glm"
    assert os.path.isfile(os.path.join(brun.ROOT, "benchmark", "jobs", "fit_glm.py"))
    assert not CELL.endswith(".fit")  # the listed re_* readers belong to the cells that do
    assert [m["name"] for m in cell.end_to_end] == ["fit_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"fe_vg_roofline", "device_idle_share", "peak_hbm_gb", "window_compiles", "fe_solve_s",
            "fe_solver_iters", "eval_fit_s", "fe_cg_iters"} <= reported
    # nothing in this cell calls the Hv kernel or trains a random effect
    assert not reported & {"fe_hvp_roofline", "re_update_s", "re_solve_s", "re_solver_iters",
                           "collective_exposed_s"}
    assert "random_effect" not in cell.config and "random_effects" not in cell.config


def test_the_configuration_keeps_every_published_setting(cell):
    fe = cell.config["fixed_effect"]
    assert cell.config["task"] == "poisson_regression" and cell.config["dtype"] == "float32"
    assert (fe["d"], fe["intercept_column"], fe["layout"]) == (1024, 1023, "dense")
    assert (fe["optimizer"], fe["max_iterations"], fe["num_corrections"]) == ("LBFGS", 100, 10)
    assert (fe["regularization"], fe["elastic_net_alpha"], fe["normalization"]) == (
        "ELASTIC_NET", 0.5, "STANDARDIZATION")
    # the same rows and width as glmix-user-1chip.fit-fixed: equal bytes under fe_vg_roofline
    fixed = brun.resolve_cell(brun.load_manifest(), "glmix-user-1chip.fit-fixed").config
    assert cell.config["scale"]["rows"] == fixed["scale"]["rows"] == 12 * 2**17
    assert fe["d"] == fixed["fixed_effect"]["d"]
    for key in ("rows", "optimizer", "tolerance", "elastic_net_alpha", "columns", "truth", "lambda_path",
                "validation_rows", "data_seed"):
        assert cell.config["assumed"][key], key
    config = fit_glm._opt_config(fe, 254000.0)
    solver = config.solver_config()
    assert solver.normalized_type().value == "OWLQN" and solver.num_corrections == 10
    assert solver.l1_weight == 127000.0 and config.regularization.l2_weight(254000.0) == 127000.0


def test_the_five_weights_are_numbers_down_from_lambda_max(cell):
    grid = cell.traffic["reg_weights"]["global"]
    lmax = cell.traffic["lambda_max"]
    assert grid == [float(f"{lmax * 10 ** (-k / 2):.3g}") for k in range(1, 6)]
    assert grid == [254000.0, 80400.0, 25400.0, 8040.0, 2540.0]
    assert cell.traffic["coordinates"] == ["global"] and cell.traffic["cd_sweeps"] == 1
    assert cell.traffic["validation"] == {"evaluator": "POISSON_LOSS", "frequency": "SWEEP"}


# -- the generator's law, from data_seed ---------------------------------------------


@pytest.fixture(scope="module")
def sample(cell):
    """The configuration's first 65,536 rows at full width, unmirrored."""
    import jax

    scale, d = cell.config["scale"], cell.config["fixed_effect"]["d"]
    law = data_glm.draw_law(scale["data_seed"], d, scale["support"], scale["margin_std"], scale["mean_count"])
    n = scale["generation_chunk_rows"]
    x, margin = jax.device_get(data_glm.device_features(scale["data_seed"], n, n, law, np.ones(d, np.float32)))
    return law, np.asarray(x), np.asarray(margin), data_glm.draw_counts(scale["data_seed"], np.asarray(margin))


def test_the_law_is_pinned_from_the_data_seed(cell, sample):
    law, x, margin, labels = sample
    d = len(law.mu)
    assert law.sigma[-1] == 1.0 and law.mu[-1] == 0.0 and np.all(x[:, -1] == 1.0)
    assert 0.1 <= law.sigma[:-1].min() < 0.12 and 9.0 < law.sigma[:-1].max() <= 10.0
    assert int(np.sum(law.beta[:-1] != 0)) == 102
    assert np.linalg.norm(law.beta[:-1]) == pytest.approx(0.7, rel=1e-6)
    assert float(law.beta[-1]) == pytest.approx(np.log(1.5) - 0.245, rel=1e-6)
    # the draws themselves: a change of generator or of seed moves these
    assert float(law.sigma[0]) == pytest.approx(0.209163, rel=1e-5)
    assert float(law.mu[0]) == pytest.approx(0.312855, rel=1e-5)
    assert int(np.flatnonzero(law.beta)[0]) == 3
    # column moments follow the law
    n = len(x)
    assert np.max(np.abs(x[:, :-1].mean(0) - law.mu[:-1]) / law.sigma[:-1]) < 5.0 / np.sqrt(n)
    assert np.max(np.abs(x[:, :-1].std(0) / law.sigma[:-1] - 1.0)) < 5.0 / np.sqrt(2 * n)
    assert margin.std() == pytest.approx(0.7, rel=0.02)
    assert labels.mean() == pytest.approx(1.5, rel=0.03)
    assert 3.0 < margin.max() < 4.5 and labels.max() < 100  # exp stays finite at the truth
    assert np.all(labels == np.round(labels)) and labels.min() == 0.0


def test_lambda_max_of_the_sample_scales_to_the_recorded_one(cell, sample):
    law, x, margin, labels = sample
    x64 = x.astype(np.float64)
    std = x64.std(0)
    std[-1] = 1.0
    mean = x64.mean(0)
    mean[-1] = 0.0
    xt = ref.transformed(x64, 1.0 / std, mean)
    g0 = ref._smooth64(np.zeros(x.shape[1]), xt, labels.astype(np.float64), 0.0, 1.0, 0.0)[1]
    scaled = ref.lambda_max(g0, 0.5, x.shape[1] - 1) * cell.config["scale"]["rows"] / len(x)
    assert scaled == pytest.approx(cell.traffic["lambda_max"], rel=0.1)  # 65,536 rows: a 3% draw


def test_a_seed_mirrors_the_columns_and_nothing_else(cell, sample):
    import jax

    law, x, margin, labels = sample
    scale, d = cell.config["scale"], len(law.mu)
    signs = gen.draw_mirror(2**31 + 11, d, 1).fixed
    assert signs[-1] == 1.0 and set(np.unique(signs)) == {-1.0, 1.0}
    n = scale["generation_chunk_rows"]
    x_m, margin_m = jax.device_get(data_glm.device_features(scale["data_seed"], n, n, law, signs))
    np.testing.assert_array_equal(np.asarray(x_m), x * signs)
    np.testing.assert_array_equal(np.asarray(margin_m), margin)


# -- the readers the job brings, on a synthetic Observations ----------------------------

FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]


def _span(name, start, end, root, **attrs):
    return SpanRecord(name, start, end, dict(attrs, root_id=root))


def _fit_tree(root, t0, s, owlqn=True):
    spans = [_span("fit", t0, t0 + 10 * s, root, n_combos=2)]
    for combo in range(2):
        t = t0 + 1 * s + 4 * combo * s
        extra = dict(l1_weight=5.0, l2_weight=5.0, nonzeros=7 + combo, line_search_evals=30) if owlqn else {}
        spans += [
            _span("cd.coordinate", t, t + 3.5 * s, root, coordinate="global"),
            _span("fe.solve", t + 0.25 * s, t + 3 * s, root, coordinate="global", optimizer="OWLQN", **extra),
        ]
        if owlqn:
            if combo:
                spans.append(_span("fe.normalization", t, t + 0.125 * s, root, coordinate="global", direction="in"))
            spans.append(_span("fe.normalization", t + 3 * s, t + 3.25 * s, root, coordinate="global", direction="out"))
    return spans


def _observations(owlqn=True):
    spans = _fit_tree("s10", 0.0, 1.0, owlqn) + _fit_tree("s90", 20.0, 1.125, owlqn)
    spans += _fit_tree("s1", -15.0, 1.0, owlqn)  # a warm-up fit outside every traced window
    counters = [{"name": "photon_cd_iterations", "kind": "summary", "labels": {"coordinate": "global"},
                 "sum": 24.0, "stat": {"count": 4, "mean": 6.0}}]
    if owlqn:
        counters += [
            {"name": "photon_fe_line_search_evals_total", "kind": "counter", "labels": {"coordinate": "global"}, "value": 120.0},
            {"name": "photon_fe_orthant_zeroed_total", "kind": "counter", "labels": {"coordinate": "global"}, "value": 6.0},
            {"name": "photon_fe_nonzero_coefficients", "kind": "gauge", "labels": {"coordinate": "global"}, "value": 8.0},
        ]
    job = types.SimpleNamespace(config={"fixed_effect": {"name": "global"}})
    return Observations(fit_windows=list(FIT_WINDOWS), spans=spans, counters=counters, listener=None,
                        setup_spans={}, job=job, peak={}, chips=1, memory_peak_bytes=0)


EXPECTED = {
    "fe_line_search_evals": 60.0,  # 120 over two traced fits
    "fe_evals_per_iter": 5.0,  # 120 evaluations over 24 iterations
    "fe_nonzeros_last": 8.0,
    "fe_orthant_zeroed": 3.0,
    "fe_normalization_s": (0.125 + 2 * 0.25) * 1.0625,  # per fit, median of scales 1 and 1.125
}


def test_the_job_brings_exactly_these_readers():
    assert list(fit_glm.GLM_PATH_READERS) == list(EXPECTED)
    for name in EXPECTED:
        reader = brun.load_reader(name)
        assert (reader.MOVES, reader.BETTER, reader.LAYER) == ("fit_s", "lower", "fixed-effect solve")
        assert reader.SOURCE in ("program_counter", "program_span") and reader.UNIT in ("count", "ratio", "s")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_reads_the_owlqn_path(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_returns_nothing_on_a_program_without_the_path(name):
    """The driver lays these readers over the parent's checkout too, and a
    TRON or plain L-BFGS solve reports none of it."""
    assert brun.load_reader(name).read(_observations(owlqn=False)) is None


# -- the whole job on the CPU at a small size ----------------------------------------------


@pytest.fixture(scope="module")
def rehearsal(cell):
    """``fit_glm.run`` traced, Pallas in interpret mode: d 128, 16,384 rows."""
    import jax.numpy as jnp

    small = brun.resolve_cell(brun.load_manifest(), CELL)
    small.config["fixed_effect"].update(d=128, intercept_column=127)
    small.config["scale"].update(rows=16384, validation_rows=512, generation_chunk_rows=4096, support=12)
    job = fit_glm.build(small.config, small.traffic, 1, 3)
    batch = job.datasets["global"].batch
    zeros = jnp.zeros_like(batch.labels)
    g0 = ref.value_grad(jnp.zeros(128, jnp.float32), batch.features.dense, batch.labels, zeros, zeros + 1.0,
                        0.0, job.normalization.factors, job.normalization.shifts)[1]
    lmax = ref.lambda_max(np.asarray(g0), 0.5, 127)
    small.traffic["reg_weights"]["global"] = [float(f"{lmax * 10 ** (-k / 2):.3g}") for k in range(1, 6)]
    old = os.environ.get("PHOTON_PALLAS"), correct_glm.SAMPLE_ROWS
    os.environ["PHOTON_PALLAS"], correct_glm.SAMPLE_ROWS = "interpret", 4096
    captured = {}
    real = Observations.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        captured["observations"] = self

    Observations.__init__ = spy
    try:
        line = fit_glm.run(small, 2**31 + 77, 0.5, True, {"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
                           time.perf_counter(), required_fusion="interpret")
    finally:
        Observations.__init__ = real
        correct_glm.SAMPLE_ROWS = old[1]
        if old[0] is None:
            del os.environ["PHOTON_PALLAS"]
        else:
            os.environ["PHOTON_PALLAS"] = old[0]
    return json.loads(line), captured["observations"]


def test_the_job_runs_end_to_end_and_is_correct(rehearsal):
    line, _ = rehearsal
    notes = line["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert notes["solver_programs_first_fit"] == 1  # ONE OWL-QN program for five weights
    assert notes["window_compiles"] == 0 and notes["warmup_incomplete"] is False
    assert notes["fits_same_as_warmup"] is True
    parity, full = notes["sample_parity"], notes["full_size"]
    assert parity["kernel_err"] <= correct_glm.KERNEL_TOL < parity["kernel_err_bf16"]
    assert max(parity["path_support_diff"]) == 0 and max(full["kkt"]) <= correct_glm.KKT_TOL
    path = notes["lambda_path"]
    assert len(path) == 5 and all(p["iterations"] > 0 and p["line_search_evals"] > p["iterations"] for p in path)
    supports = [p["nonzeros"] for p in path]
    assert supports == sorted(supports) and supports == full["nonzeros"]
    assert all(np.isfinite(p["POISSON_LOSS"]) for p in path)
    assert set(notes["glm_path"]) == set(EXPECTED) and all(
        isinstance(v, float) for v in notes["glm_path"].values())
    assert notes["glm_path"]["fe_nonzeros_last"] == supports[-1]
    assert notes["glm_path"]["fe_line_search_evals"] == sum(p["line_search_evals"] for p in path)
    assert line["metrics"]["fe_cg_iters"]["value"] == 0.0 and "fe_hvp_roofline" not in line["metrics"]


def test_every_listed_reader_takes_the_new_jobs_observations(cell, rehearsal):
    """A number or None from each, and none raises; ``kernel_roofline`` finds
    the dense batch where it looks for it."""
    _, observations = rehearsal
    name = observations.job.config["fixed_effect"]["name"]
    assert observations.job.datasets[name].batch.features.dense.shape == (16384, 128)
    for m in cell.per_layer:
        value = brun.load_reader(m["name"]).read(observations)
        assert value is None or isinstance(float(value), float), m["name"]
