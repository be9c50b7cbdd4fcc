"""The per-coordinate readers of PR 28 on a synthetic ``Observations`` whose
fits have TWO random effects: each reads its own coordinate's spans and
counters, summed per fit, median over the traced fits; None where the
coordinate is absent or the program's spans do not say whose they are (any
commit before PR 28). And the new cell as the manifest lists it: the readers
themselves are the job's own table until a benchmark PR lists them."""

import types

import pytest

from benchmark import run as brun
from benchmark.observe import Observations, SpanRecord

CELL = "glmix-user-item-1chip.fit-3coord"
FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]
USER, ITEM = "per-user", "per-item"


def _span(name, start, end, root, **attrs):
    return SpanRecord(name, start, end, dict(attrs, root_id=root))


def _update(root, t0, s, coordinate, exchange, buckets, score):
    """One random-effect update from ``t0``: its phase spans back to back
    under one cd.coordinate span that is 0.25 s longer than their sum."""
    spans, t = [], t0
    for name, lengths in (("re.exchange", [exchange]), ("re.bucket", buckets), ("re.collect", [0.125]),
                          ("re.score", [score])):
        for length in lengths:
            spans.append(_span(name, t, t + length * s, root, coordinate=coordinate, device=True))
            t += length * s
    spans.append(_span("cd.guard", t, t + 0.125 * s, root, coordinate=coordinate))
    spans.append(_span("cd.coordinate", t0, t + 0.25 * s, root, coordinate=coordinate))
    return spans


def _fit_tree(root, t0, s, effects, labelled=True):
    spans = [
        _span("fit", t0, t0 + 10 * s, root, n_combos=1),
        _span("cd.sweep", t0 + 1 * s, t0 + 9 * s, root, iteration=0),
        _span("cd.coordinate", t0 + 1 * s, t0 + 3 * s, root, coordinate="global"),
        _span("fe.solve", t0 + 1 * s, t0 + 2.5 * s, root, coordinate="global", device=True),
    ]
    if USER in effects:  # thin lanes: two sweeps' worth of updates in one fit
        spans += _update(root, t0 + 3 * s, s, USER, 0.5, [0.25, 0.5], 0.25)
        spans += _update(root, t0 + 5 * s, s, USER, 0.5, [0.25, 0.25], 0.25)
    if ITEM in effects:
        spans += _update(root, t0 + 7 * s, s, ITEM, 0.125, [0.5, 0.25, 0.125], 0.375)
    if not labelled:  # a program before PR 28: only the cd.* spans name their coordinate
        spans = [
            SpanRecord(x.name, x.start, x.end,
                       {k: v for k, v in x.attrs.items() if k != "coordinate" or x.name.startswith("cd.")})
            for x in spans
        ]
    return spans


def _counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels, "value": float(value)}


def _observations(effects=(USER, ITEM), labelled=True):
    spans = _fit_tree("s10", 0.0, 1.0, effects, labelled) + _fit_tree("s90", 20.0, 1.125, effects, labelled)
    spans += _fit_tree("s1", -15.0, 1.0, effects, labelled)  # a warm-up fit outside every traced window
    counters = []
    shares = {USER: (578, 422, 600, 800, 372, 628), ITEM: (745, 255, 700, 1000, 698, 302)}
    for name in effects:
        real, padded, useful, issued, active, passive = shares[name]
        counters += [
            _counter("photon_re_block_slots_total", real, coordinate=name, kind="real"),
            _counter("photon_re_block_slots_total", padded, coordinate=name, kind="padded"),
            _counter("photon_re_lane_iterations_total", useful, coordinate=name, kind="useful"),
            _counter("photon_re_lane_iterations_total", issued, coordinate=name, kind="issued"),
        ]
        if labelled:  # the rows counter is PR 28's
            counters += [
                _counter("photon_re_rows_total", active, coordinate=name, kind="active"),
                _counter("photon_re_rows_total", passive, coordinate=name, kind="passive"),
            ]
    job = types.SimpleNamespace(config={"fixed_effect": {"name": "global"}})
    return Observations(
        fit_windows=list(FIT_WINDOWS), spans=spans, counters=counters, listener=None,
        setup_spans={}, job=job, peak={}, chips=1, memory_peak_bytes=0,
    )


MID = 1.0625  # the median of two fits of scales 1 and 1.125
EXPECTED = {
    "re_user_update_s": (2 * (0.5 + 0.125 + 0.25 + 0.25) + 0.75 + 0.5) * MID,
    "re_item_update_s": (0.125 + 0.875 + 0.125 + 0.375 + 0.25) * MID,
    "re_item_exchange_s": 0.125 * MID,
    "re_item_solve_s": 0.875 * MID,
    "re_item_score_s": 0.375 * MID,
    "re_item_slot_pad_share": 25.5,
    "re_item_lockstep_share": 30.0,
    "re_item_passive_share": 30.2,
    "re_user_solve_s": 1.25 * MID,
}
USER_ONLY = {name: (value if "_user_" in name else None) for name, value in EXPECTED.items()}
# a program before PR 28 names the coordinate on cd.* spans and on the slot and lane counters only
BEFORE_PR_28 = {name: (value if name.endswith(("_update_s", "_slot_pad_share", "_lockstep_share")) else None)
                for name, value in EXPECTED.items()}


def test_the_job_brings_the_table_and_the_manifest_lists_the_cell():
    """BENCHMARK.json cannot list these readers yet (PERF.md, Open questions):
    the job names them itself and prints what they read under ``notes``."""
    from benchmark.jobs import fit_game

    assert list(fit_game.PER_COORDINATE_READERS) == list(EXPECTED)
    for name in EXPECTED:
        reader = brun.load_reader(name)
        assert (reader.MOVES, reader.BETTER, reader.LAYER) == ("fit_s", "lower", "random-effect solve")
    manifest = brun.load_manifest()
    assert manifest["workloads"][-1]["name"] == CELL and manifest["configs"][-1]["name"] == CELL.split(".")[0]
    cell = brun.resolve_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic["job"] == "fit_game"
    reported = {m["name"] for m in cell.per_layer}
    assert {"fe_vg_roofline", "fe_hvp_roofline", "device_idle_share", "peak_hbm_gb",
            "window_compiles", "fe_solve_s", "eval_fit_s"} <= reported
    # the readers wired to ONE random_effect stay with the cells that have one
    assert not reported & {"re_update_s", "re_pad_share", "re_solve_s", "re_solver_iters", "collective_exposed_s"}


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_reads_its_own_coordinate(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_without_the_item_coordinate(name):
    value = brun.load_reader(name).read(_observations(effects=(USER,)))
    if USER_ONLY[name] is None:
        assert value is None
    else:
        assert value == pytest.approx(USER_ONLY[name])
    assert brun.load_reader(name).read(_observations(effects=())) is None


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_returns_nothing_where_the_program_does_not_say_whose_span_it_is(name):
    """The driver lays these readers over the parent's checkout too."""
    value = brun.load_reader(name).read(_observations(labelled=False))
    if BEFORE_PR_28[name] is None:
        assert value is None
    else:
        assert value == pytest.approx(BEFORE_PR_28[name])


@pytest.mark.parametrize("name", [n for n in EXPECTED if n.endswith("_s")])
def test_span_reader_returns_nothing_when_a_traced_fit_failed(name):
    obs = _observations()
    obs.fit_windows = []
    assert brun.load_reader(name).read(obs) is None


def test_the_old_sums_still_sum_over_both_random_effects():
    """Names stayed and an attribute was added: PR 26's readers read a cell
    with two random effects as the sum of both."""
    obs = _observations()
    assert brun.load_reader("re_solve_s").read(obs) == pytest.approx((1.25 + 0.875) * MID)
    assert brun.load_reader("re_exchange_s").read(obs) == pytest.approx((1.0 + 0.125) * MID)
    assert brun.load_reader("re_slot_pad_share").read(obs) == pytest.approx(100 * (422 + 255) / 2000)
