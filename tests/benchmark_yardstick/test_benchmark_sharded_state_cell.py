"""The cell ``logistic-criteo-4chip.fit-sharded-state`` (PR 40) as a yardstick:
its files resolve with what the manifest tests ask of every cell, the Criteo
law is pinned (fields that add up to d, 40 slots a row with the 13 count
columns in every row, a mirror that moves nothing but signs), the job refuses
a program or a width whose state no chip can hold, the trace read once for
the harness's view and every chip's lines, the pass and collectives told
apart by their lines on every chip, the four readers on a synthetic
``Observations``, and the whole job end to end on four virtual CPU devices at
a toy width just past 2^20: correct as it is, and NOT correct with a bfloat16
gather planted in the program."""

import json
import time

import numpy as np
import pytest

from benchmark import correct_sharded_sparse, data_criteo as gen, data_sparse, shapes_sharded, sharded_ops
from benchmark import run as brun
from benchmark.jobs import fit_sharded_sparse
from benchmark.observe import Observations

CELL = "logistic-criteo-4chip.fit-sharded-state"
CONFIG = "logistic-criteo-4chip"
READERS = ("fe_shard_pass_s", "fe_shard_pass_roofline", "fe_state_collective_s", "fe_state_collective_roofline")
FIELDS = [39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951, 2953546, 403346, 10, 2208, 11938,
          155, 4, 976, 14, 39979771, 25641295, 39664984, 585935, 12972, 108, 36]


def _toy_fields(width):
    """The 26 fields scaled down so that 13 + their sum + 1 = ``width``."""
    target = width - 14
    toy = [max(int(c * target / sum(FIELDS)), 2) for c in FIELDS]
    toy[0] += target - sum(toy)
    return toy


@pytest.fixture(scope="module")
def cell():
    return brun.resolve_cell(brun.load_manifest(), CELL)


def test_the_cell_takes_four_chips_and_prints_its_four_readers_under_notes(cell):
    manifest = brun.load_manifest()
    w = {x["name"]: x for x in manifest["workloads"]}[CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "fit-sharded-state", 4)
    assert CONFIG in {c["name"] for c in manifest["configs"]}
    # the tail of ``per_layer`` is pinned (test_benchmark_fit_span_metrics.py):
    # the four read under notes["sharded"], the exposed collectives are listed
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert not set(READERS) & set(listed) and set(READERS) <= set(fit_sharded_sparse.NOTE_READERS)
    assert listed["collective_exposed_s"]["workloads"] == ["glmix-user-4chip.fit", CELL]
    assert "collective_exposed_s" in {m["name"] for m in cell.per_layer}
    assert sum(x["chips"] == 4 for x in manifest["workloads"]) <= len(manifest["workloads"]) // 4


def test_the_configuration_keeps_every_published_setting(cell):
    c = cell.config
    fe, scale = c["fixed_effect"], c["scale"]
    assert scale["fields"] == FIELDS and len(FIELDS) == 26
    assert fe["d"] == 13 + sum(FIELDS) + 1 == 187_767_413 and fe["intercept_column"] == fe["d"] - 1
    assert (fe["numeric_columns"], fe["slots_per_row"], fe["layout"]) == (13, 40, "auto")
    assert (fe["optimizer"], fe["num_corrections"], fe["max_iterations"], fe["tolerance"]) == ("LBFGS", 10, 100, 1e-6)
    assert (fe["regularization"], fe["normalization"]) == ("L2", "NONE")
    assert c["mesh"] == {"data": 4} and c["chips"] == 4 and c["reduced"] == ["rows"]
    assert scale["rows"] == 2 ** 21 and scale["data_seed"] == 40 and scale["validation_rows"] == 8192
    assert len(c["source"]) <= 200


def test_the_mix_is_one_weight_one_sweep_and_auc(cell):
    t = cell.traffic
    assert t["job"] == "fit_sharded_sparse" and t["coordinates"] == ["global"] and t["cd_sweeps"] == 1
    assert t["reg_weights"]["global"] == [1783.0] and t["trace_fits"] == 2
    assert t["reg_weights"]["global"][0] / cell.config["scale"]["rows"] == pytest.approx(8.5e-4, rel=1e-3)
    assert t["validation"] == {"evaluator": "AUC", "frequency": "SWEEP"}


# -- the law --------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    fields = _toy_fields(4000)
    law = gen.draw_law(40, fields, 2048, 1.1)
    cols, vals = gen.draw_features(40, law)
    gen.set_intercept(law, cols, vals, 0.034)
    return law, gen.rows(40, law, cols, vals), fields


def test_the_fields_add_up_to_d_with_forty_slots_and_the_counts_in_every_row(toy):
    law, rows, fields = toy
    assert law.dim == 13 + sum(fields) + 1 == 4000
    assert rows.cols.shape == rows.vals.shape == (2048, 40)
    assert np.all(rows.cols[:, :13] == np.arange(13)) and np.all(rows.cols[:, -1] == law.dim - 1)
    for f, (start, card) in enumerate(zip(law.starts, law.cardinalities)):
        ids = rows.cols[:, 13 + f]
        assert start >= 13 and np.all((ids >= start) & (ids < start + card))
    assert np.all(rows.vals[:, 13:] == 1.0) and np.all(rows.vals[:, :13] >= 0.0)
    # log(1 + x) of counts whose median grows down the columns
    medians = np.median(np.expm1(rows.vals[:, :13]), axis=0)
    assert medians[0] <= 1.0 and 15.0 <= medians[-1] <= 25.0
    assert rows.labels.mean() == pytest.approx(0.034, abs=0.02)


def test_a_seed_mirrors_the_values_and_nothing_else(toy):
    law, rows, _ = toy
    a, b = data_sparse.draw_signs(2**31 + 5, law.dim), data_sparse.draw_signs(7, law.dim)
    assert a[-1] == b[-1] == 1.0 and np.any(a != b)
    for signs in (a, b):
        r, c, v = gen.triplets(rows.cols, rows.vals, signs)
        assert len(r) == 2048 * 40 and np.array_equal(c, rows.cols.reshape(-1).astype(np.int64))
        np.testing.assert_array_equal(np.abs(v), rows.vals.reshape(-1).astype(np.float64))
        # the mirrored truth gives every row the margin it had
        beta = law.beta.astype(np.float64) * signs
        z = np.bincount(r, weights=v * beta[c], minlength=2048)
        np.testing.assert_allclose(z, rows.margin, rtol=1e-12, atol=1e-12)


def test_the_state_a_chip_holds_is_the_programs_over_the_four_shards(cell):
    from photon_ml_tpu.optimize import lbfgs

    need = fit_sharded_sparse.chip_state_bytes(cell.config, 4)
    _, history = lbfgs.history_account(187_767_413, 10, 4, 4)
    assert history == 3_755_356_160 and 6e9 < need < fit_sharded_sparse.CHIP_STATE_BYTES
    fit_sharded_sparse.refuse_a_state_no_chip_can_hold(cell.config, 4)


@pytest.mark.parametrize("case", ["no-shards", "too-wide"])
def test_the_job_refuses_a_state_no_chip_holds_before_any_data(cell, monkeypatch, case):
    from photon_ml_tpu.optimize import lbfgs

    monkeypatch.setattr(fit_sharded_sparse.gen, "draw_law", lambda *a, **k: pytest.fail("data drawn"))
    if case == "no-shards":  # a program whose history knows no shards: any commit before PR 40
        monkeypatch.setattr(lbfgs, "history_account", lambda dim, m, itemsize: ("rows", 0))
        with pytest.raises(TypeError):
            fit_sharded_sparse.build(cell.config, cell.traffic, 4, 2**31 + 5)
    else:
        wide = dict(cell.config, fixed_effect=dict(cell.config["fixed_effect"], d=4 * 187_767_413))
        with pytest.raises(brun.NoResult, match="GB a chip"):
            fit_sharded_sparse.build(wide, cell.traffic, 4, 2**31 + 5)


def test_the_trace_is_read_once_for_the_harness_and_for_every_chips_lines(tmp_path):
    """On the CPU the profile holds the host's marks and no device plane: the
    one reading gives what ``trace.load`` gives."""
    import glob
    import os

    import jax
    import jax.numpy as jnp

    from benchmark import trace as trace_mod

    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    device_trace, by_chip = sharded_ops.load(path)
    assert device_trace == trace_mod.load(path) and len(device_trace.marks) == 2
    assert set(by_chip) == set(device_trace.chips)


# -- the trace's lines and the readers ---------------------------------------------------------

D, CHIP_SLOTS = 1_052_576, 4096 * 40
LINES = [
    (f"%fusion.9 = f32[40,4096]{{1,0:T(8,128)}} fusion(%a, %b), kind=kCustom, calls=%g", "gather"),
    (f"%fusion.12 = f32[{D + 96}]{{0:T(1024)}} fusion(%c, %d), kind=kCustom, calls=%s", "scatter"),
    ("%sort.3 = (s32[163840]{0}, f32[163840]{0}) sort(%e, %f), dimensions={0}", "scatter"),
    ("%all-gather.13 = f32[4,1,263168]{2,1,0:T(1,128)} all-gather(%g), channel_id=1, dimensions={0}", "all_gather"),
    ("%all-gather-start.2 = (f32[263168], f32[4,263168]) all-gather-start(%g), dimensions={0}", "all_gather"),
    ("%fusion.84 = f32[2088,128]{1,0:T(8,128)} fusion(%h), kind=kCustom, calls=%all-reduce-scatter.clone", "reduce_scatter"),
    ("%collective-permute-start = (f32[6,128], f32[6,128]) collective-permute-start(%i), channel_id=30", "reduce_scatter"),
    ("%all-reduce.46 = f32[]{:T(128)} all-reduce(%j), channel_id=2", None),
    (f"%fusion.70 = f32[{D + 96}]{{0:T(1024)}} fusion(%k), kind=kLoop, calls=%z", None),
]


@pytest.mark.parametrize("line, want", LINES)
def test_an_operation_is_told_by_its_line(line, want):
    assert sharded_ops.kind(line, CHIP_SLOTS, D) == want


def _events(offset=0.0):
    """Two fits on one chip: two passes each, their collectives, a tolerance
    pass outside ``jit__solve`` and an operation outside the window."""
    out = []
    for fit in range(2):
        t = offset + 10.0 * fit
        for p in range(2):
            a = t + 1.0 + p
            out += [(LINES[0][0], a, a + 0.3, "jit__solve"), (LINES[1][0], a + 0.3, a + 0.45, "jit__solve"),
                    (LINES[2][0], a + 0.45, a + 0.5, "jit__solve"), (LINES[3][0], a + 0.5, a + 0.52, "jit__solve"),
                    (LINES[5][0], a + 0.52, a + 0.55, "jit__solve"), (LINES[6][0], a + 0.55, a + 0.56, "jit__solve")]
        out.append((LINES[0][0], t + 0.1, t + 0.4, "jit__abs_tolerances_impl"))
    out.append((LINES[0][0], 50.0, 51.0, "jit__solve"))
    return out


def _observations(ops=True, counters=True, partial=False):
    job = type("Job", (), {})()
    job.config = {"fixed_effect": {"name": "global"}}
    job.pass_shape = {"layout": "ell", "dim": D, "rows": 16384, "slots": 16384 * 40, "width": 40}
    # the v5e's first chip named its operations ``region.<n>``, in no module
    # (my chip run, PR 40): where it shows no pass it is left out of every
    # mean; where it names half of them (``partial``: the first fit's, as the
    # second chip run read) its seconds a pass count, its passes do not
    unnamed = [("%region.237 = f32[40,4096] fusion(%a), kind=kCustom", 1.0, 9.0, "")]
    first = unnamed + [e for e in _events() if e[1] < 10.0] if partial else unnamed
    job.device_ops_by_chip = {"/device:TPU:0": first, "/device:TPU:1": _events(),
                              "/device:TPU:2": _events()} if ops else None
    moved = [{"name": "photon_fe_collective_bytes_total", "labels": {"coordinate": "global", "kind": k}, "value": v}
             for k, v in (("all_gather", 2e9), ("reduce_scatter", 2e9))] if counters else []
    return Observations(fit_windows=[(0.0, 9.0), (10.0, 19.0)], spans=[], counters=moved, listener=None,
                        setup_spans={}, job=job, peak={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
                        chips=4, memory_peak_bytes=0, trace=None)


def test_each_chips_passes_are_counted_by_their_scatters_inside_the_window():
    obs = _observations()
    chips = sharded_ops.per_chip(obs)
    assert len(chips) == 2 and sharded_ops.summary(obs)["chips_read"] == 2
    assert sharded_ops.summary(obs)["collective_lines"]
    for c in chips:
        assert c["passes"] == 4
        assert c["gather"] == pytest.approx(4 * 0.3) and c["scatter"] == pytest.approx(4 * 0.2)
        assert c["all_gather"] == pytest.approx(4 * 0.02) and c["reduce_scatter"] == pytest.approx(4 * 0.04)


@pytest.mark.parametrize("partial", [False, True])
def test_the_readers_read_the_sharded_pass_and_its_collectives(partial):
    obs = _observations(partial=partial)
    assert len(sharded_ops.per_chip(obs)) == 2 + partial
    read = {name: brun.load_reader(name).read(obs) for name in READERS}
    assert read["fe_shard_pass_s"] == pytest.approx(0.5)
    assert read["fe_state_collective_s"] == pytest.approx(0.12)  # 2 passes x 0.06 s a fit
    assert read["fe_state_collective_roofline"] == pytest.approx(100 * 2e9 / 200e9 / 0.12)
    width = shapes_sharded.solve_width(D, 4)
    want = 100 * shapes_sharded.chip_pass_bytes(4096, 40, width) / 819e9 / 0.5
    assert read["fe_shard_pass_roofline"] == pytest.approx(want) and 0 < want < 100


@pytest.mark.parametrize("ops, counters", [(False, True), (True, False), (False, False)])
def test_the_readers_read_nothing_without_the_lines_or_the_counter(ops, counters):
    obs = _observations(ops=ops, counters=counters)
    read = {name: brun.load_reader(name).read(obs) for name in READERS}
    if not ops:
        assert all(v is None for v in read.values())
    else:
        assert read["fe_state_collective_roofline"] is None and read["fe_shard_pass_s"] is not None


@pytest.mark.parametrize("chips", [1, 2, 4, 8, 16])
def test_the_yardsticks_width_is_the_programs(chips):
    from photon_ml_tpu.optimize import lbfgs

    for d in (D, 187_767_413, 54_686_453):
        assert shapes_sharded.solve_width(d, chips) == lbfgs.history_row_width((d,), False, chips)


def test_the_pass_bytes_and_the_links_peak():
    n, k, width = 524_288, 40, 187_767_808
    slots = n * k
    assert shapes_sharded.chip_pass_bytes(n, k, width) == 2 * slots * 12 + 6 * n * 4 + width * 4
    assert shapes_sharded.ICI_BYTES_PER_S == 200e9
    assert shapes_sharded.ici_share(0.56e9, 0.01) == pytest.approx(28.0)


# -- the whole job on four CPU devices at a toy width -------------------------------------------


def _rehearse(traced: bool, seed: int):
    """``fit_sharded_sparse.run`` on four virtual devices: d 1,052,576 (just
    past 2^20, so the program splits the state), 16,384 rows. Returns the
    result line and the ``Observations`` a traced run hands its readers."""
    small = brun.resolve_cell(brun.load_manifest(), CELL)
    fields = _toy_fields(D)
    small.config["fixed_effect"].update(d=D, intercept_column=D - 1)
    small.config["scale"].update(rows=16384, validation_rows=1024, fields=fields)
    small.traffic["reg_weights"]["global"] = [1783.0 * 16384 / 2 ** 21]
    captured = {}
    real = Observations.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        captured["observations"] = self

    Observations.__init__ = spy
    try:
        line = fit_sharded_sparse.run(small, seed, 0.5, traced,
                                      {"platform": "cpu", "kind": "TPU v5 lite", "count": 4}, time.perf_counter())
    finally:
        Observations.__init__ = real
    return json.loads(line), captured.get("observations")


@pytest.fixture(scope="module")
def rehearsal():
    return _rehearse(True, 2**31 + 77)


def test_the_job_runs_end_to_end_with_its_state_split_and_is_correct(rehearsal):
    line, _ = rehearsal
    notes = line["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert notes["solver_programs_first_fit"] == 1 and notes["window_compiles"] == 0
    assert notes["fits_same_as_warmup"] is True and notes["warmup_incomplete"] is False
    assert notes["shape"] == {"layout": "ell", "dim": D, "rows": 16384, "slots": 16384 * 40, "width": 40}
    width = shapes_sharded.solve_width(D, 4)
    assert notes["plan"] == {"sharding": "row-sharded, state-sharded", "geometry": {
        "state_shards": 4, "state_columns": width, "state_columns_per_chip": width // 4,
        "history_bytes_per_chip": 2 * 10 * (width // 4) * 4}}
    parity, full = notes["parity"], notes["full_size"]
    assert parity["state_sharded"] is True and parity["solve_columns"] == width and parity["tail_nonzero"] == 0
    # the limit lies between the program's reading and the bfloat16 control's
    assert parity["kernel_err"] <= correct_sharded_sparse.KERNEL_TOL < 1e-3 < parity["kernel_err_bf16"]
    assert full["gradient"] <= correct_sharded_sparse.GRADIENT_TOL and full["objective_drop"] < 1.0
    assert full["unseen_nonzero"] == 0 and full["nonzeros"] == full["columns_seen"] < D
    span, = notes["sharded"]["solve_spans"][:1]
    assert (span["state_sharding"], span["state_shards"], span["history"]) == ("data", 4, "rows")
    assert span["collective_bytes"] == 3 * (width // 4) * 4 and span["dim"] == D
    fp = notes["fingerprint"]
    assert fp["iterations"][0] > 5 and 0.5 < fp["validation"][0]["AUC"] < 1.0
    # a CPU run has no device trace: the trace's readers read nothing and are left out
    assert not set(READERS) & set(notes["sharded"]) and "collective_exposed_s" not in line["metrics"]


def test_a_program_that_gathers_in_bfloat16_is_not_correct(monkeypatch):
    """The lower-precision control through the harness's own comparison: the
    program's sharded gather reads its coefficients rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.features import FeatureMatrix

    gathered = FeatureMatrix.matvec_gathered

    def in_bfloat16(self, w, sharding):
        return gathered(self, w.astype(jnp.bfloat16).astype(w.dtype), sharding)

    monkeypatch.setattr(FeatureMatrix, "matvec_gathered", in_bfloat16)
    jax.clear_caches()  # no program traced with the f32 gather may answer
    try:
        line, _ = _rehearse(False, 2**31 + 78)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    parity = line["notes"]["parity"]
    assert line["correct"] is False and parity["ok"] is False
    assert parity["kernel_err"] > 1e-3 > correct_sharded_sparse.KERNEL_TOL


def test_every_listed_reader_takes_the_new_jobs_observations(cell, rehearsal):
    _, observations = rehearsal
    assert observations.job.device_ops_by_chip is None
    for m in cell.per_layer:
        value = brun.load_reader(m["name"]).read(observations)
        assert value is None or isinstance(float(value), float), m["name"]
