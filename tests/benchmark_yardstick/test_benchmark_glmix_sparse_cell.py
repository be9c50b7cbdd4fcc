"""The cell ``glmix-sparse-user-1chip.fit-glmix-sparse`` (PR 38) as a yardstick:
its files resolve and hold what the manifest tests ask of every cell, the
generator's additions to the sparse law are pinned (users read off a field, a
re-indexed user shard, a per-user truth that is a pure function of the pair, a
mirror that moves nothing but signs), the plain reference's rules (the
program's priority, the active / passive weights, scores that meet 0 outside a
user's support, exact per-user minimisers), the four readers the job brings on
a synthetic ``Observations``, and the whole job end to end on the CPU at a toy
size: the system against the reference on ragged subspaces, both coordinates,
three sweeps."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import correct_glmix_sparse as correct
from benchmark import data_glmix_sparse as gen_user
from benchmark import data_sparse as gen
from benchmark import run as brun
from benchmark.jobs import fit_glmix_sparse as job_mod
from benchmark.observe import Observations, SpanRecord
from benchmark.reference import glmix_sparse as ref

CELL = "glmix-sparse-user-1chip.fit-glmix-sparse"
CONFIG = "glmix-sparse-user-1chip"
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
    assert len(workload["why"]) <= 200 and len(entry["why"]) <= 200
    assert cell.traffic["job"] == "fit_glmix_sparse"
    assert os.path.isfile(os.path.join(brun.ROOT, "benchmark", "jobs", "fit_glmix_sparse.py"))
    # appended after every cell and configuration that was there (by membership and
    # order, not by "last": the next PR appends after this one)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("logistic-sparse-1chip.fit-sparse")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("logistic-sparse-1chip")
    sources = [c["source"] for c in manifest["configs"]]
    assert len(set(sources)) == len(sources)  # two deployments from one public set: sources differ
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(manifest["workloads"]) // 4
    assert [m["name"] for m in cell.end_to_end] == ["fit_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"device_idle_share", "peak_hbm_gb", "window_compiles", "fe_solve_s", "fe_solver_iters",
            "eval_fit_s", "fe_tolerances_s", "fe_score_s", "sweep_s"} <= reported
    # the listed re_* readers name the cells they were listed for; no Pallas kernel runs here
    assert not reported & {"fe_vg_roofline", "fe_hvp_roofline", "re_update_s", "re_solve_s",
                           "re_pad_share", "collective_exposed_s"}
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in job_mod.GLMIX_SPARSE_READERS:  # the pin on per_layer's tail: printed under notes
        assert name not in metrics


def test_the_configuration_keeps_every_published_setting(cell):
    fe, re, scale = cell.config["fixed_effect"], cell.config["random_effect"], cell.config["scale"]
    sparse = brun.load_json(os.path.join(brun.ROOT, "benchmark", "configs", "logistic-sparse-1chip.json"))
    assert fe == sparse["fixed_effect"]  # the sparse cell's fixed effect, unchanged
    assert cell.config["task"] == "logistic_regression" and cell.config["dtype"] == "float32"
    assert scale["fields"] == FIELDS == sparse["scale"]["fields"] and sum(FIELDS) == fe["d"] - 1
    assert (scale["rows"], scale["published_rows"]) == (9 * 2**17, 149639105)
    assert (scale["validation_rows"], scale["data_seed"], scale["zipf_exponent"]) == (8192, 38, 1.1)
    assert (scale["user_field"], scale["user_shard_fields"]) == (0, [7, 8, 9, 10])
    assert (scale["user_feature_var"], scale["user_intercept_var"], scale["click_rate"]) == (0.25, 0.25, 0.05)
    assert re["d_re"] == sum(FIELDS[f] for f in scale["user_shard_fields"]) + 1 == 86453
    assert (re["intercept_column"], re["slots_per_row"], re["shard"], re["id"]) == (86452, 5, "userShard", "userId")
    assert (re["optimizer"], re["max_iterations"], re["num_corrections"], re["tolerance"]) == ("LBFGS", 30, 10, 1e-6)
    assert (re["regularization"], re["reg_weight"], re["active_cap"], re["active_lower_bound"]) == ("L2", 1.0, 256, 1)
    assert re["features_to_samples_ratio"] is None
    assert "64" in cell.config["deployment"] and "by user" in cell.config["deployment"].lower()
    assert "exactly 0" in cell.config["guarantees"] and "float32" in cell.config["guarantees"]
    for key in ("rows", "fields", "user_field", "user_shard_fields", "user_truth", "random_effect",
                "data_seed", "validation_rows", "truth", "zipf_exponent"):
        assert cell.config["assumed"][key], key


def test_the_mix_is_two_coordinates_three_sweeps_and_auc(cell):
    t = cell.traffic
    assert t["coordinates"] == t["update_sequence"] == ["global", "per-user"]
    assert t["reg_weights"] == {"global": [1000.0], "per-user": 1.0}
    assert t["cd_sweeps"] == 3 and t["validation"] == {"evaluator": "AUC", "frequency": "SWEEP"}


def test_the_full_size_user_law_is_the_fields_own(cell):
    """From quotas alone (no rows drawn): the users of the shard, those over
    the cap and the rows they leave passive, as ISSUE 38 reckoned them."""
    scale, re = cell.config["scale"], cell.config["random_effect"]
    quotas = gen.field_quotas(scale["rows"], scale["fields"][scale["user_field"]], scale["zipf_exponent"])
    cap = re["active_cap"]
    assert len(quotas) == 278177 and int((quotas > cap).sum()) == 297
    assert int((quotas <= 8).sum()) == 271921
    assert np.maximum(quotas - cap, 0).sum() / scale["rows"] == pytest.approx(0.499, abs=1e-3)


# -- the generator's additions ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    n = 16384
    scale = dict(user_field=0, user_feature_var=0.25, user_intercept_var=0.25)
    law = gen.draw_law(38, TOY_FIELDS, n, 1.1)
    shard = gen_user.user_shard(law, [7, 8, 9, 10])
    cols = gen.draw_columns(38, law)
    user = cols[:, 0].astype(np.int64)
    ucols = gen_user.user_columns(law, shard, cols)
    without = gen.margins(law, cols) + gen_user.user_margins(38, shard, user, ucols, 0.25, 0.25)
    gen_user.set_intercept(law, without, 0.05)
    margin = without + float(law.beta[-1])
    rows = gen_user.Rows(cols=cols, user_cols=ucols, user=user, labels=gen.draw_labels(38, margin), margin=margin)
    return law, shard, scale, rows


def test_the_user_shard_is_the_smallest_fields_reindexed_with_an_intercept(toy):
    law, shard, _, rows = toy
    assert shard.dim == sum(TOY_FIELDS[7:]) + 1 and list(shard.starts) == [0, 140, 172, 180]
    assert rows.user_cols.shape == (len(rows.labels), 5) and np.all(rows.user_cols[:, -1] == shard.dim - 1)
    for j, f in enumerate(shard.fields):
        local = rows.user_cols[:, j] - shard.starts[j]
        assert np.all((local >= 0) & (local < TOY_FIELDS[f]))
        np.testing.assert_array_equal(local, rows.cols[:, f] - law.starts[f])  # the same values, re-indexed
    np.testing.assert_array_equal(rows.user, rows.cols[:, 0])
    counts = np.sort(np.bincount(rows.user - law.starts[0]))[::-1]
    np.testing.assert_array_equal(counts[: len(law.quotas[0])], law.quotas[0])  # the field's own quotas


def test_the_per_user_truth_is_a_pure_function_of_the_pair(toy):
    _, shard, _, rows = toy
    z = gen_user.pair_normal(38, rows.user, rows.user_cols[:, 0], shard.dim)
    np.testing.assert_array_equal(z, gen_user.pair_normal(38, rows.user.copy(), rows.user_cols[:, 0].copy(), shard.dim))
    key = rows.user * shard.dim + rows.user_cols[:, 0]
    first = {}
    for k, v in zip(key.tolist(), z.tolist()):
        assert first.setdefault(k, v) == v  # one value a pair, wherever the pair is met
    draws = gen_user.pair_normal(38, np.arange(200000), np.arange(200000) % 97, 97)
    assert abs(draws.mean()) < 0.01 and draws.std() == pytest.approx(1.0, abs=0.01)
    assert not np.array_equal(z, gen_user.pair_normal(39, rows.user, rows.user_cols[:, 0], shard.dim))
    term = gen_user.user_margins(38, shard, rows.user, rows.user_cols, 0.25, 0.25)
    assert term.std() == pytest.approx(np.sqrt(0.5), rel=0.15)
    assert float(np.mean(1.0 / (1.0 + np.exp(-rows.margin)))) == pytest.approx(0.05, rel=1e-4)
    assert rows.labels.mean() == pytest.approx(0.05, rel=0.15)


def test_validation_rows_draw_their_users_from_the_training_rows(toy):
    law, shard, scale, rows = toy
    val = gen_user.draw_rows(38, law, shard, scale, n_sample=2048, stream=1)
    assert val.cols.shape == (2048, 12) and val.user_cols.shape == (2048, 5)
    assert np.isin(val.user, rows.user).all() and 0.02 < val.labels.mean() < 0.09
    again = gen_user.draw_rows(38, law, shard, scale, n_sample=2048, stream=1)
    np.testing.assert_array_equal(again.labels, val.labels)


def test_a_seed_mirrors_both_shards_and_nothing_else(toy):
    _, shard, _, _ = toy
    signs = gen_user.draw_user_signs(2**31 + 11, shard.dim)
    assert signs[-1] == 1.0 and set(np.unique(signs)) == {-1.0, 1.0} and signs.dtype == np.float32
    assert not np.array_equal(signs, gen_user.draw_user_signs(2**31 + 12, shard.dim))
    # a stream of its own: not the global shard's first d_re signs
    assert not np.array_equal(signs[:-1], gen.draw_signs(2**31 + 11, shard.dim)[:-1])


# -- the plain reference's rules -------------------------------------------------------------


def test_the_references_priority_is_the_programs():
    from photon_ml_tpu.game.data import _hash64

    for seed in (0, 7):
        np.testing.assert_array_equal(ref.row_priority(5000, seed), _hash64(np.arange(5000, dtype=np.int64), seed))


def test_active_weights_follow_the_published_rule():
    user = np.asarray([0, 0, 0, 0, 0, 1, 1, 2])
    priority = np.asarray([5, 1, 4, 2, 3, 9, 8, 7], np.uint64)
    w = ref.active_weights(user, priority, 2, 3)
    np.testing.assert_array_equal(w, [0, 2.5, 0, 2.5, 0, 1, 1, 1])  # count / cap on the 2 smallest
    np.testing.assert_array_equal(ref.active_weights(user, priority, None, 3), np.ones(8))


def test_a_slot_outside_its_users_support_meets_zero_and_users_land_on_their_minimisers():
    rng = np.random.default_rng(4)
    n, users, dim = 400, 12, 30
    user = rng.integers(0, users, n)
    cols = np.concatenate([rng.integers(0, dim - 1, (n, 2)), np.full((n, 1), dim - 1)], axis=1)
    vals = rng.choice([-1.0, 1.0], (n, 3))
    y = (rng.random(n) < 0.4).astype(np.float64)
    offsets = rng.normal(size=n) * 0.5
    weights = ref.active_weights(user, ref.row_priority(n), 20, users)
    assert (weights == 0).any() and (weights > 1).any()
    block = ref.user_block(user, cols, vals, dim, users, 1.0, weights)
    passive_outside = (block.slot_pair < 0) & (weights == 0)[:, None]
    assert passive_outside.any() and not ((block.slot_pair < 0) & (weights > 0)[:, None]).any()
    table = ref.solve_users(block, y, offsets)
    _, grad = ref.user_value_grad(block, table, y, offsets)
    _, grad0 = ref.user_value_grad(block, np.zeros_like(table), y, offsets)
    assert np.linalg.norm(grad) < 1e-9 * np.linalg.norm(grad0)
    scores = ref.user_scores(block, np.ones_like(table))
    want = np.sum(vals * (block.slot_pair >= 0), axis=1)
    np.testing.assert_allclose(scores, want)
    # a warm start from the minimiser stays there
    np.testing.assert_allclose(ref.solve_users(block, y, offsets, table0=table), table, atol=1e-12)


def test_the_parity_sample_holds_users_of_every_bucket_capped_ones_among_them(toy):
    _, _, _, rows = toy
    users = correct.sample_users(rows.user, cap=64)
    ids, counts = np.unique(rows.user, return_counts=True)
    picked = counts[np.isin(ids, users)]
    kb = np.minimum(np.maximum(1 << np.ceil(np.log2(picked)).astype(int), 8), 64)
    assert set(np.unique(kb)) == {8, 16, 32, 64}
    assert all((kb == k).sum() == min(correct.USERS_PER_BUCKET, (np.minimum(np.maximum(
        1 << np.ceil(np.log2(counts)).astype(int), 8), 64) == k).sum()) for k in (8, 16, 32, 64))
    assert (picked > 64).sum() > 0 and counts.max() not in picked  # capped users, not the head one
    np.testing.assert_array_equal(users, correct.sample_users(rows.user, cap=64))  # seed-free


# -- the readers the job brings, on a synthetic Observations ----------------------------------

FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]


def _observations(program=True):
    series = [{"name": "photon_cd_iterations", "kind": "summary", "labels": {"coordinate": "global"},
               "sum": 90.0, "stat": {"count": 6, "mean": 15.0}}]
    spans = [SpanRecord("fit", 0.0, 10.0, {"root_id": "a"}), SpanRecord("fit", 20.0, 32.0, {"root_id": "b"})]
    for root, t0 in (("a", 0.0), ("b", 20.0)):
        for i, (warm, iters) in enumerate(((False, 16), (True, 14), (True, 12))):
            attrs = {"root_id": root, "coordinate": "global"}
            if program:
                attrs.update(warm=warm, offsets=i > 0, iterations=iters)
            spans.append(SpanRecord("fe.solve", t0 + 1 + i, t0 + 2 + i, attrs))
            score = {"root_id": root, "coordinate": "per-user"}
            if program:
                score["form"] = "slots"
            spans.append(SpanRecord("re.score", t0 + 2 + i, t0 + 2.1 + i, score))
    if program:
        series += [
            {"name": "photon_re_subspace_cells_total", "kind": "counter", "labels": {"coordinate": "per-user", "kind": "real"}, "value": 300.0},
            {"name": "photon_re_subspace_cells_total", "kind": "counter", "labels": {"coordinate": "per-user", "kind": "padded"}, "value": 900.0},
            {"name": "photon_re_block_store_bytes", "kind": "gauge", "labels": {"coordinate": "per-user"}, "value": 0.79e9},
        ]
    job = types.SimpleNamespace(config={"fixed_effect": {"name": "global"}, "random_effect": {"name": "per-user"}})
    return Observations(fit_windows=list(FIT_WINDOWS), spans=spans, counters=series, listener=None,
                        setup_spans={}, job=job, peak={}, chips=1, memory_peak_bytes=0)


EXPECTED = {
    "re_subspace_pad_share": 75.0,
    "re_block_store_gb": 0.79,
    "fe_warm_solver_iters": 13.0,  # the four warm solves: 14, 12, 14, 12
    "re_score_form": 100.0,
}


def test_the_job_brings_exactly_these_readers():
    assert list(job_mod.GLMIX_SPARSE_READERS) == list(EXPECTED)
    for name in EXPECTED:
        reader = brun.load_reader(name)
        assert reader.MOVES in ("fit_s", "setup_s") and reader.LAYER in ("random-effect solve", "fixed-effect solve")
        assert reader.SOURCE in ("program_counter", "program_span") and reader.UNIT in ("%", "GB", "count")
        assert reader.BETTER in ("lower", "higher")
    for name in job_mod.BORROWED_READERS:  # the earlier PRs' readers this cell prints beside them
        assert os.path.isfile(os.path.join(brun.ROOT, "benchmark", "layer_metrics", name + ".py")), name


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_reads_the_store_and_the_warm_solves(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_returns_nothing_on_a_program_without_the_store(name):
    """The driver lays these readers over the parent's checkout too: its spans
    carry no ``warm``, ``iterations`` or ``form`` and it has neither series."""
    assert brun.load_reader(name).read(_observations(program=False)) is None


def test_the_score_form_reader_tells_the_forms_apart():
    obs = _observations()
    for s in obs.spans:
        if s.name == "re.score" and s.attrs["root_id"] == "b":
            s.attrs["form"] = "subspace"
    assert brun.load_reader("re_score_form").read(obs) == pytest.approx(50.0)


# -- the whole job on the CPU at a toy size ----------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    """``fit_glmix_sparse.run`` traced: d 99,385 / d_re 185, 32,768 rows,
    8,483 users under a cap of 64 (48 over it), subspaces of 2 to 72 columns."""
    small = brun.resolve_cell(brun.load_manifest(), CELL)
    d, d_re, n = sum(TOY_FIELDS) + 1, sum(TOY_FIELDS[7:]) + 1, 32768
    small.config["fixed_effect"].update(d=d, intercept_column=d - 1)
    small.config["random_effect"].update(d_re=d_re, intercept_column=d_re - 1, active_cap=64)
    small.config["scale"].update(rows=n, validation_rows=1024, fields=TOY_FIELDS)
    small.traffic["reg_weights"]["global"] = [1000.0 * n / 1179648]
    old = correct.SAMPLE_ROWS, correct.OBJECTIVE_TOL
    correct.SAMPLE_ROWS = 8192
    # the toy's parity sample is 1,513 rows of 32 users under a ridge of 1.3:
    # the solvers' stopping slack is a larger share of so small an objective
    # than at the cell's size (2.5e-4 here, 7.5e-5 on the chip)
    correct.OBJECTIVE_TOL = 1e-3
    captured = {}
    real = Observations.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        captured["observations"] = self

    Observations.__init__ = spy
    try:
        line = job_mod.run(small, 2**31 + 77, 0.5, True, {"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
                           time.perf_counter())
    finally:
        Observations.__init__ = real
        correct.SAMPLE_ROWS, correct.OBJECTIVE_TOL = old
    return json.loads(line), captured["observations"]


def test_the_job_runs_end_to_end_and_is_correct(rehearsal):
    line, _ = rehearsal
    notes = line["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert notes["window_compiles"] == 0 and notes["warmup_incomplete"] is False
    assert notes["fits_same_as_warmup"] is True
    assert notes["shape"] == {"layout": "ell", "dim": sum(TOY_FIELDS) + 1, "rows": 32768, "slots": 32768 * 12, "width": 12}
    store = notes["store"]
    assert store["entities"] == 8483 and store["k_max"] == 64 and store["over_cap"] == 48
    assert [b[1] for b in store["buckets"]] == [64, 32, 16, 8] and store["store_gb"] < 0.1 * store["plane_gb"]
    assert len({b[2] for b in store["buckets"]}) > 1  # the S extents differ: the subspaces are ragged
    fp = notes["fingerprint"]
    assert fp["iterations"][0] > 5 and fp["iterations"][1] > 8483  # the per-user sum over users
    assert fp["feature_passes"] == [[fp["iterations"][0] + 1] * 2]  # the last solve walked margins
    assert 0.5 < fp["validation"][0]["AUC"] < 1.0


def test_the_system_lands_on_the_references_block_minimisers(rehearsal):
    """Both coordinates, three sweeps, ragged subspaces, capped users."""
    parity = rehearsal[0]["notes"]["sample_parity"]
    assert parity["ok"] is True and parity["sample_layout"] == "ell"
    assert parity["kernel_err"] <= correct.KERNEL_TOL < 1e-3 < parity["kernel_err_bf16"]
    assert parity["sample_users"] == 4 * correct.USERS_PER_BUCKET and parity["sample_capped"] > 0
    assert parity["sample_passive_rows"] > 0 and [b[1] for b in parity["sample_buckets"]] == [64, 32, 16, 8]
    assert parity["same_support"] is True and parity["outside_nonzero"] == 0
    assert parity["fixed_untouched_nonzero"] == 0
    assert parity["fixed_coef_err"] <= correct.FIXED_COEF_TOL and parity["user_coef_err"] <= correct.USER_COEF_TOL
    assert parity["objective_err"] <= correct.OBJECTIVE_TOL
    assert max(parity["reference"]["fixed_residuals"]) <= 1e-8


def test_the_full_size_checks_hold_at_every_sweeps_model(rehearsal):
    """Sweeps 2 and 3 (the warm solves under per-user offsets) are held to the
    limit sweep 1 is, though the fit hands back one sweep's model only."""
    full = rehearsal[0]["notes"]["full_size"]
    assert full["ok"] is True and full["objective_drop"] < 1.0
    assert full["sweeps"] == 3 and full["model_of_sweep"] in (1, 2, 3)
    assert len(full["fixed_gradients"]) == len(full["user_gradients"]) == 3
    assert max(full["fixed_gradients"]) == full["fixed_gradient"] <= correct.GRADIENT_TOL
    assert max(full["user_gradients"]) == full["user_gradient"] <= correct.GRADIENT_TOL
    assert full["sweeps_outside_nonzero"] == [0, 0, 0]
    # the coordinate descent itself is not at its fixed point: under its own
    # sweep's per-user scores a fixed effect is further from a minimiser than
    # under those it was solved for, and less so sweep by sweep
    after = full["fixed_gradients_after"]
    assert all(a > g for a, g in zip(after, full["fixed_gradients"])) and after[-1] < after[0]
    assert full["unseen_nonzero"] == 0 and full["outside_nonzero"] == 0 and full["same_support"] is True
    assert full["users"] == 8483 and full["support"] > full["users"]


def test_the_new_readers_print_under_the_cells_notes(rehearsal):
    line, _ = rehearsal
    printed = line["notes"]["glmix_sparse"]
    assert set(job_mod.GLMIX_SPARSE_READERS) <= set(printed)
    assert printed["re_score_form"] == 100.0 and 0.0 < printed["re_subspace_pad_share"] < 100.0
    assert printed["re_block_store_gb"] == pytest.approx(line["notes"]["store"]["store_gb"], rel=0.2)
    assert printed["fe_warm_solver_iters"] >= 1.0
    # a CPU run has no device trace: the counters' and spans' readers read, the trace's do not
    assert {"re_exchange_s", "re_solve_s", "re_score_s", "re_slot_pad_share", "re_pad_share",
            "re_bucket_cut_s", "fe_line_search_evals"} <= set(printed)
    assert "re_bucket_device_s" not in printed and "fe_sparse_gather_s" not in printed
    assert printed["re_pad_share"] == pytest.approx(printed["re_slot_pad_share"])


def test_every_listed_reader_takes_the_new_jobs_observations(cell, rehearsal):
    """A number or None from each, and none raises."""
    _, observations = rehearsal
    for m in cell.per_layer:
        value = brun.load_reader(m["name"]).read(observations)
        assert value is None or isinstance(float(value), float), m["name"]
    needs_a_device = {"device_idle_share", "peak_hbm_gb"}
    for m in cell.per_layer:
        if m["name"] not in needs_a_device:
            assert brun.load_reader(m["name"]).read(observations) is not None, m["name"]


# -- the parent must fail cleanly in this cell ---------------------------------------------------


def test_a_plane_the_host_cannot_hold_is_refused_in_set_up_not_by_the_kernel(toy, rehearsal, monkeypatch):
    """The driver tries the cell on the parent, whose build stages a 250 GB
    ``[E, K, S]`` plane here: on a machine that overcommits that is no
    ``MemoryError`` but a killed process or a lost machine. The job asks the
    program what it stores and refuses such a plane itself, before the build."""
    _, _, _, rows = toy
    config = {"random_effect": {"active_cap": 64, "d_re": sum(TOY_FIELDS[7:]) + 1}}
    assert job_mod.stores_a_plane() is False  # this program stores by bucket: nothing is asked
    monkeypatch.setattr(job_mod.os, "sysconf", lambda name: 1)  # a host of one byte
    job_mod.refuse_a_plane_the_host_cannot_hold(config, rows)
    monkeypatch.undo()
    monkeypatch.setattr(job_mod, "stores_a_plane", lambda: True)
    job_mod.refuse_a_plane_the_host_cannot_hold(config, rows)  # the toy's plane fits this host: not refused
    # the bound is from below, and close: the toy job's own store gives the
    # plane (its float32 one is half the staged float64)
    store = rehearsal[0]["notes"]["store"]
    plane = store["entities"] * store["k_max"] * store["s_max"] * 8
    assert store["plane_gb"] * 1e9 * 2 == pytest.approx(plane)
    pages = -(-plane // 4096)
    monkeypatch.setattr(job_mod.os, "sysconf", lambda name: {"SC_PAGE_SIZE": 4096, "SC_PHYS_PAGES": pages}[name])
    job_mod.refuse_a_plane_the_host_cannot_hold(config, rows)  # a host the whole plane fits
    monkeypatch.setattr(job_mod.os, "sysconf", lambda name: {"SC_PAGE_SIZE": 4096, "SC_PHYS_PAGES": pages // 2}[name])
    with pytest.raises(MemoryError, match=r"\[E, K, S\] entity-block plane"):
        job_mod.refuse_a_plane_the_host_cannot_hold(config, rows)


def test_the_cells_own_plane_is_past_any_host_the_cell_runs_on(cell):
    """From quotas alone: 278,177 users x 256 rows x at least 256 columns x 8
    bytes is 146 GB before the widest user's 439 columns are counted."""
    scale, re = cell.config["scale"], cell.config["random_effect"]
    quotas = gen.field_quotas(scale["rows"], scale["fields"][scale["user_field"]], scale["zipf_exponent"])
    assert len(quotas) * min(int(quotas.max()), re["active_cap"]) * 256 * 8 > 140e9
