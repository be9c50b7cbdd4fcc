"""The cell ``logistic-sparse-1chip.fit-sparse`` (PR 34) as a yardstick: its
files resolve and hold what the manifest tests ask of every cell, the
generator's law is pinned (fixed quotas, field ranges, one-hot rows, a mirror
that moves nothing but signs), the byte function's arithmetic, the trace's
gather and scatter told apart by their lines, the readers the job brings on a
synthetic ``Observations`` (a number, or None on a program without the counter
or a trace without the lines), and the whole job end to end on the CPU at a toy
size."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import correct_sparse, data_sparse as gen, shapes_sparse, sparse_ops
from benchmark import run as brun
from benchmark.jobs import fit_sparse
from benchmark.observe import Observations, SpanRecord

CELL = "logistic-sparse-1chip.fit-sparse"
CONFIG = "logistic-sparse-1chip"
FIELDS = [22000000, 20000000, 5000000, 4000000, 2000000, 1000000, 600000, 70000, 16000, 440, 12]
TOY_FIELDS = [40000, 36000, 9000, 7000, 4000, 2000, 1200, 140, 32, 8, 4]


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
    assert len(workload["why"]) <= 200 and cell.traffic["job"] == "fit_sparse"
    assert os.path.isfile(os.path.join(brun.ROOT, "benchmark", "jobs", "fit_sparse.py"))
    assert not CELL.endswith(".fit")  # the listed re_* readers belong to the cells that do
    assert [m["name"] for m in cell.end_to_end] == ["fit_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"device_idle_share", "peak_hbm_gb", "window_compiles", "fe_solve_s", "fe_solver_iters",
            "eval_fit_s", "fe_cg_iters", "fe_tolerances_s", "fe_score_s"} <= reported
    # nothing in this cell calls a Pallas kernel or trains a random effect
    assert not reported & {"fe_vg_roofline", "fe_hvp_roofline", "re_update_s", "re_solve_s",
                           "re_solver_iters", "collective_exposed_s"}
    assert "random_effect" not in cell.config and "random_effects" not in cell.config


def test_the_dense_rooflines_list_the_cells_that_were_there():
    manifest = brun.load_manifest()
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    before = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    assert metrics["fe_vg_roofline"]["workloads"] == before
    assert CELL not in metrics["fe_hvp_roofline"]["workloads"]
    for name in fit_sparse.SPARSE_READERS:  # the pin on per_layer's tail: printed under notes.sparse
        assert name not in metrics or "workloads" not in metrics[name]


def test_the_configuration_keeps_every_published_setting(cell):
    fe, scale = cell.config["fixed_effect"], cell.config["scale"]
    assert cell.config["task"] == "logistic_regression" and cell.config["dtype"] == "float32"
    assert (fe["d"], fe["intercept_column"], fe["slots_per_row"], fe["layout"]) == (54686453, 54686452, 12, "auto")
    assert (fe["optimizer"], fe["max_iterations"], fe["num_corrections"], fe["tolerance"]) == ("LBFGS", 100, 10, 1e-6)
    assert (fe["regularization"], fe["normalization"]) == ("L2", "NONE")
    assert scale["fields"] == FIELDS and sum(FIELDS) == 54686452 == fe["d"] - 1
    assert scale["rows"] in (18 * 2**17, 9 * 2**17) and scale["published_rows"] == 149639105
    assert abs(scale["published_rows"] / 64 - scale["rows"]) < 0.01 * scale["rows"] or scale["rows"] == 9 * 2**17
    assert (scale["validation_rows"], scale["data_seed"], scale["zipf_exponent"]) == (8192, 34, 1.1)
    assert "64" in cell.config["deployment"] and "149,639,105" in cell.config["deployment"]
    for key in ("rows", "slots_per_row", "layout", "optimizer", "tolerance", "normalization", "fields",
                "zipf_exponent", "truth", "validation_rows", "data_seed", "published_rows"):
        assert cell.config["assumed"][key], key
    config = fit_sparse._opt_config(fe, 1000.0)
    solver = config.solver_config()
    assert solver.normalized_type().value == "LBFGS" and solver.num_corrections == 10
    assert solver.l1_weight == 0.0 and config.regularization.l2_weight(1000.0) == 1000.0


def test_the_mix_is_one_weight_one_sweep_and_auc(cell):
    assert cell.traffic["reg_weights"] == {"global": [1000.0]} and cell.traffic["coordinates"] == ["global"]
    assert cell.traffic["cd_sweeps"] == 1 and cell.traffic["trace_fits"] == 2
    assert cell.traffic["validation"] == {"evaluator": "AUC", "frequency": "SWEEP"}


# -- the generator's law ---------------------------------------------------------------------


@pytest.mark.parametrize("n,cardinality", [(2359296, 22000000), (2359296, 70000), (2359296, 12), (1000, 440),
                                          (32768, 4), (100, 1000000)])
def test_quotas_are_fixed_whole_and_non_increasing(n, cardinality):
    quotas = gen.field_quotas(n, cardinality, 1.1)
    k = len(quotas)
    assert int(quotas.sum()) == n and k <= min(n, cardinality) and quotas.min() >= 1
    assert np.all(np.diff(quotas) <= 0)
    again = gen.field_quotas(n, cardinality, 1.1)
    np.testing.assert_array_equal(quotas, again)  # no seed anywhere
    p = np.arange(1, cardinality + 1, dtype=np.float64) ** -1.1
    whole = np.floor(n * p / p.sum())
    assert np.all(quotas >= whole[:k]) and np.all(whole[k:] == 0)  # the floors, and the left-over on top


def test_the_full_size_law_sees_the_columns_its_quotas_say(cell):
    """At the cell's own rows, from quotas alone (no rows drawn): how many
    columns of each field some row holds."""
    scale = cell.config["scale"]
    seen = [len(gen.field_quotas(scale["rows"], c, scale["zipf_exponent"])) for c in scale["fields"][5:]]
    assert seen[-3:] == [16000, 440, 12]  # small fields are seen whole
    assert all(a >= b for a, b in zip(seen, seen[1:]))


@pytest.fixture(scope="module")
def toy():
    n = 32768
    law = gen.draw_law(34, TOY_FIELDS, n, 1.1)
    cols = gen.draw_columns(34, law)
    gen.set_intercept(law, cols, 0.05)
    return law, gen.draw_rows(34, law)


def test_rows_are_one_hot_by_field_with_the_intercept_last(toy):
    law, rows = toy
    n, k = rows.cols.shape
    assert k == len(TOY_FIELDS) + 1 and law.dim == sum(TOY_FIELDS) + 1
    assert np.all(rows.cols[:, -1] == law.dim - 1)
    ends = law.starts + np.asarray(TOY_FIELDS)
    for f in range(len(TOY_FIELDS)):
        assert np.all((rows.cols[:, f] >= law.starts[f]) & (rows.cols[:, f] < ends[f]))
        # every quota is spent exactly: the column counts ARE the quotas
        counts = np.sort(np.bincount(rows.cols[:, f] - law.starts[f], minlength=TOY_FIELDS[f]))[::-1]
        np.testing.assert_array_equal(counts[: len(law.quotas[f])], law.quotas[f])
        assert counts[len(law.quotas[f]):].sum() == 0
    assert np.all(np.diff(law.starts) == np.asarray(TOY_FIELDS[:-1]))
    # the rank-to-column map is a bijection inside a field
    ranks = np.arange(TOY_FIELDS[0])
    assert len(np.unique(gen._field_columns(law, 0, ranks))) == TOY_FIELDS[0]


def test_the_truth_has_unit_margins_and_the_click_rate(toy):
    law, rows = toy
    assert rows.margin.std() == pytest.approx(1.0, rel=0.1)
    assert float(np.mean(1.0 / (1.0 + np.exp(-rows.margin)))) == pytest.approx(0.05, rel=1e-4)
    assert rows.labels.mean() == pytest.approx(0.05, rel=0.1) and set(np.unique(rows.labels)) == {0.0, 1.0}
    assert law.beta[:-1].std() == pytest.approx(1 / np.sqrt(11), rel=0.02)
    # the draws themselves: a change of generator or of seed moves these
    again = gen.draw_rows(34, law)
    np.testing.assert_array_equal(again.cols, rows.cols)
    np.testing.assert_array_equal(again.labels, rows.labels)
    assert not np.array_equal(gen.draw_columns(35, law)[:, 0], rows.cols[:, 0])


def test_validation_rows_draw_their_values_from_the_training_rows(toy):
    law, rows = toy
    val = gen.draw_rows(34, law, n_sample=4096, stream=1)
    assert val.cols.shape == (4096, 12) and np.all(val.cols[:, -1] == law.dim - 1)
    seen = gen.columns_seen(rows.cols, law.dim)
    assert seen[val.cols.reshape(-1)].all()
    assert 0.02 < val.labels.mean() < 0.09


def test_a_seed_mirrors_the_values_and_nothing_else(toy):
    law, rows = toy
    signs = gen.draw_signs(2**31 + 11, law.dim)
    assert signs[-1] == 1.0 and set(np.unique(signs)) == {-1.0, 1.0} and signs.dtype == np.float32
    r, c, v = gen.triplets(rows.cols, signs)
    r1, c1, v1 = gen.triplets(rows.cols, np.ones(law.dim, np.float32))
    np.testing.assert_array_equal(r, r1)
    np.testing.assert_array_equal(c, c1)
    np.testing.assert_array_equal(v, signs[c] * v1)
    assert r.dtype == c.dtype == np.int64 and v.dtype == np.float64 and np.all(v1 == 1.0)
    np.testing.assert_array_equal(r[:13], [0] * 12 + [1])
    assert not np.array_equal(signs, gen.draw_signs(2**31 + 12, law.dim))


# -- the byte function ----------------------------------------------------------------------------


def test_the_pass_bytes_count_every_slot_twice_and_the_columns_four_times():
    n, k, d = 2359296, 12, 54686453
    slots = n * k
    assert shapes_sparse.slot_bytes(n, k) == 2 * slots * 12
    assert shapes_sparse.ell_value_grad_bytes(n, k, d) == 2 * slots * 12 + 6 * n * 4 + 4 * d * 4
    assert shapes_sparse.ell_value_grad_bytes(n, k, d) == 1_611_083_600
    assert shapes_sparse.ell_value_grad_flops(n, k, d) == 4 * slots + 12 * n + 2 * d
    # at the HBM peak: 1.97 ms a pass; the flops are nothing beside it
    from benchmark import shapes

    peak = brun.load_json(os.path.join(brun.HERE, "peaks.json"))["TPU v5 lite"]
    share = shapes.roofline_share(shapes_sparse.ell_value_grad_bytes(n, k, d),
                                  shapes_sparse.ell_value_grad_flops(n, k, d), 0.1, peak)
    assert share["bound"] == "memory" and share["share"] == pytest.approx(1.967, rel=1e-3)


# -- gather and scatter, by their lines -------------------------------------------------------------

SLOTS, DIM = 28311552, 54686453
LINES = {
    "gather_fusion": "%fusion.68 = f32[28311552]{0:T(1024)} fusion(%get-tuple-element.2239, %broadcast_clamp_fusion.8), kind=kCustom, calls=%fused_computation.clone",
    "scatter_fusion": "%fusion.72 = f32[54686453]{0:T(1024)} fusion(%get-tuple-element.2242, %copy-done.1, %get-tuple-element.2246), kind=kCustom, calls=%fused_computation.3",
    "gather_op": "%gather.9 = f32[2359296,12]{1,0:T(8,128)} gather(%param_0.8, %transpose.29), offset_dims={}",
    "scatter_op": "%scatter-add.18 = f32[54686453]{0:T(1024)} scatter(%param_0.11, %transpose.46, %transpose.47), update_window_dims={}",
    "scatter_sort": "%sort.6 = (s32[28311552]{0:T(1024)S(1)}, f32[28311552]{0:T(1024)}) sort(s32[28311552]{0:T(1024)} %bitcast.24, f32[28311552]{0:T(1024)} %get-tuple-element.16), dimensions={0}, to_apply=%compare",
    "loop_fusion": "%multiply_reduce_fusion = f32[54686453]{0:T(1024)} fusion(%a), kind=kLoop, calls=%fused_computation.7",
    "kernel": "%fused_value_grad.8 = (f32[1,1]{1,0}, f32[1024]{0}) custom-call(%a), custom_call_target=\"tpu_custom_call\"",
    "name_only": "fusion.68",
}


@pytest.mark.parametrize("line,want", [("gather_fusion", "gather"), ("scatter_fusion", "scatter"),
                                       ("gather_op", "gather"), ("scatter_op", "scatter"), ("scatter_sort", "scatter"),
                                       ("loop_fusion", None), ("kernel", None), ("name_only", None)])
def test_an_operation_is_told_by_its_line(line, want):
    assert sparse_ops.kind(LINES[line], SLOTS, DIM) == want


def _device_ops():
    """Two fits of three passes each (one in the tolerance pass's module), a
    gather of 40 ms and a scatter of 60 ms a pass (10 ms of it the sort of its
    indices), and a scoring gather outside the solve that must not count."""
    ops = []
    for t0 in (0.0, 20.0):
        for i, module in enumerate(("jit__abs_tolerances_impl", "jit__solve", "jit__solve")):
            t = t0 + 1.0 + i
            ops.append((LINES["gather_fusion"], t, t + 0.04, module))
            ops.append((LINES["loop_fusion"], t + 0.04, t + 0.05, module))
            ops.append((LINES["scatter_sort"], t + 0.05, t + 0.06, module))
            ops.append((LINES["scatter_fusion"], t + 0.06, t + 0.11, module))
        ops.append((LINES["gather_fusion"], t0 + 5.0, t0 + 5.04, "jit_matvec"))
    ops.append((LINES["scatter_fusion"], 50.0, 50.06, "jit__solve"))  # after the traced window
    return ops


def test_passes_are_counted_by_their_scatters_inside_the_window():
    found = sparse_ops.pass_seconds(_device_ops(), (0.0, 32.0), SLOTS, DIM)
    assert found["passes"] == 6
    assert found["gather_s"] == pytest.approx(6 * 0.04) and found["scatter_s"] == pytest.approx(6 * 0.06)
    assert sparse_ops.pass_seconds(_device_ops(), (0.0, 0.5), SLOTS, DIM) is None
    assert sparse_ops.pass_seconds([(LINES["name_only"], 1.0, 2.0, "jit__solve")], (0.0, 32.0), SLOTS, DIM) is None


# -- the readers the job brings, on a synthetic Observations ----------------------------------------

FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]


def _observations(counters=True, lines=True):
    series = [{"name": "photon_cd_iterations", "kind": "summary", "labels": {"coordinate": "global"},
               "sum": 48.0, "stat": {"count": 2, "mean": 24.0}}]
    if counters:
        series += [
            {"name": "photon_fe_line_search_evals_total", "kind": "counter", "labels": {"coordinate": "global"}, "value": 132.0},
            {"name": "photon_fe_slots_total", "kind": "counter", "labels": {"coordinate": "global", "kind": "real"}, "value": 300.0},
            {"name": "photon_fe_slots_total", "kind": "counter", "labels": {"coordinate": "global", "kind": "padded"}, "value": 100.0},
        ]
    job = types.SimpleNamespace(
        config={"fixed_effect": {"name": "global"}},
        device_ops=_device_ops() if lines else None,
        pass_shape={"layout": "ell", "dim": DIM, "rows": 2359296, "slots": SLOTS, "width": 12},
    )
    peak = brun.load_json(os.path.join(brun.HERE, "peaks.json"))["TPU v5 lite"]
    return Observations(fit_windows=list(FIT_WINDOWS), spans=[SpanRecord("fit", 0.0, 10.0, {"root_id": "a"})],
                        counters=series, listener=None, setup_spans={}, job=job, peak=peak, chips=1,
                        memory_peak_bytes=0)


EXPECTED = {
    "fe_sparse_vg_roofline": 100.0 * (1_611_083_600 / 819e9) / 0.1,
    "fe_sparse_pass_s": 0.1,
    "fe_sparse_gather_s": 0.04,
    "fe_sparse_scatter_s": 0.06,
    "fe_sparse_slot_pad_share": 25.0,
    "fe_line_search_evals": 66.0,  # 132 over two traced fits
    "fe_evals_per_iter": 2.75,  # 132 evaluations over 48 iterations
}


def test_the_job_brings_exactly_these_readers():
    assert list(fit_sparse.SPARSE_READERS) == list(EXPECTED)
    for name in EXPECTED:
        reader = brun.load_reader(name)
        assert reader.MOVES == "fit_s" and reader.LAYER in ("GLM kernels", "fixed-effect solve")
        assert reader.SOURCE in ("device_trace", "program_counter") and reader.UNIT in ("%", "s", "count", "ratio")
        assert reader.BETTER == ("higher" if name.endswith("_roofline") else "lower")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_reads_the_sparse_pass(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_returns_nothing_on_a_program_without_the_counters_or_a_trace_without_the_lines(name):
    """The driver lays these readers over the parent's checkout too: its plain
    L-BFGS counts no passes and its coordinate no slots."""
    value = brun.load_reader(name).read(_observations(counters=False, lines=False))
    assert value is None
    if brun.load_reader(name).SOURCE == "device_trace":
        assert brun.load_reader(name).read(_observations(lines=False)) is None
        assert brun.load_reader(name).read(_observations(counters=False)) == pytest.approx(EXPECTED[name])


def test_the_roofline_reader_has_no_byte_function_for_another_layout():
    obs = _observations()
    obs.job.pass_shape = dict(obs.job.pass_shape, layout="coo", width=None)
    assert brun.load_reader("fe_sparse_vg_roofline").read(obs) is None
    assert brun.load_reader("fe_sparse_pass_s").read(obs) == pytest.approx(0.1)


# -- the whole job on the CPU at a toy size ----------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    """``fit_sparse.run`` traced: d 99,385, 32,768 rows, the parity sample 8,192."""
    small = brun.resolve_cell(brun.load_manifest(), CELL)
    d = sum(TOY_FIELDS) + 1
    small.config["fixed_effect"].update(d=d, intercept_column=d - 1)
    small.config["scale"].update(rows=32768, validation_rows=1024, fields=TOY_FIELDS)
    small.traffic["reg_weights"]["global"] = [1000.0 * 32768 / 2359296]
    old = correct_sparse.SAMPLE_ROWS, correct_sparse.COEF_TOL, correct_sparse.OBJECTIVE_TOL
    correct_sparse.SAMPLE_ROWS = 8192
    # 8,192 rows under a ridge of 3.5: the stopping slack of a 1e-6 tolerance
    # is a larger share of the minimiser than at the cell's size
    correct_sparse.COEF_TOL, correct_sparse.OBJECTIVE_TOL = 2e-2, 2e-4
    captured = {}
    real = Observations.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        captured["observations"] = self

    Observations.__init__ = spy
    try:
        line = fit_sparse.run(small, 2**31 + 77, 0.5, True, {"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
                              time.perf_counter())
    finally:
        Observations.__init__ = real
        correct_sparse.SAMPLE_ROWS, correct_sparse.COEF_TOL, correct_sparse.OBJECTIVE_TOL = old
    return json.loads(line), captured["observations"]


def test_the_job_runs_end_to_end_and_is_correct(rehearsal):
    line, _ = rehearsal
    notes = line["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert notes["solver_programs_first_fit"] == 1
    assert notes["window_compiles"] == 0 and notes["warmup_incomplete"] is False
    assert notes["fits_same_as_warmup"] is True
    assert notes["shape"] == {"layout": "ell", "dim": sum(TOY_FIELDS) + 1, "rows": 32768, "slots": 32768 * 12, "width": 12}
    parity, full = notes["sample_parity"], notes["full_size"]
    assert parity["sample_layout"] == "ell"
    assert parity["kernel_err"] <= correct_sparse.KERNEL_TOL < 1e-3 < parity["kernel_err_bf16"]
    assert parity["sample_untouched_nonzero"] == 0 and parity["reference"]["residual"] <= 1e-8
    assert full["gradient"] <= correct_sparse.GRADIENT_TOL and full["objective_drop"] < 1.0
    assert full["unseen_nonzero"] == 0 and full["nonzeros"] == full["columns_seen"] < sum(TOY_FIELDS)
    fp = notes["fingerprint"]
    assert fp["iterations"][0] > 5 and fp["line_search_evals"][0] > fp["iterations"][0]
    assert 0.5 < fp["validation"][0]["AUC"] < 1.0
    # a CPU run has no device trace: the counters' readers read, the trace's do not
    assert set(notes["sparse"]) == {"fe_sparse_slot_pad_share", "fe_line_search_evals", "fe_evals_per_iter"}
    assert notes["sparse"]["fe_sparse_slot_pad_share"] == 0.0
    assert notes["sparse"]["fe_line_search_evals"] == fp["line_search_evals"][0]
    assert notes["sparse"]["fe_evals_per_iter"] == pytest.approx(fp["line_search_evals"][0] / fp["iterations"][0])
    assert line["metrics"]["fe_cg_iters"]["value"] == 0.0 and line["metrics"]["fe_solver_iters"]["value"] == fp["iterations"][0]
    assert "fe_vg_roofline" not in line["metrics"] and "fe_hvp_roofline" not in line["metrics"]
    # the gradient is no longer fetched: a sweep fetches under a kilobyte
    assert line["metrics"]["cd_fetch_bytes_per_sweep"]["value"] < 1024


def test_every_listed_reader_takes_the_new_jobs_observations(cell, rehearsal):
    """A number or None from each, and none raises (``kernel_roofline`` finds no
    kernel's events before it would look for a dense matrix)."""
    _, observations = rehearsal
    assert observations.job.features.layout == "ell" and observations.job.device_ops is None
    for m in cell.per_layer:
        value = brun.load_reader(m["name"]).read(observations)
        assert value is None or isinstance(float(value), float), m["name"]
    needs_a_device = {"device_idle_share", "peak_hbm_gb"}
    for m in cell.per_layer:
        if m["name"] not in needs_a_device:
            assert brun.load_reader(m["name"]).read(observations) is not None, m["name"]
