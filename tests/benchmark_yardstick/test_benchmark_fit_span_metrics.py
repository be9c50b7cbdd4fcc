"""The per-fit readers of PR 26 on a synthetic ``Observations``: sums per fit
over the spans that share the ``fit`` root's id, median over the traced fits;
counters per fit; None wherever the program gives nothing to read (a commit
without the span tree, a cell without a random effect)."""

import types

import pytest

from benchmark import fit_spans
from benchmark import run as brun
from benchmark.observe import Observations, SpanRecord

FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]


def _span(name, start, end, root, **attrs):
    return SpanRecord(name, start, end, dict(attrs, root_id=root))


def _fit_tree(root, t0, scale, random_effect):
    """One fit's spans, every duration a multiple of ``scale`` so the two
    fits differ and a median over fits is not a sum over all spans."""
    s = scale
    spans = [
        _span("fit", t0, t0 + 10 * s, root, n_combos=1, retraces=34, retrace_s=0.04 * s),
        _span("fit.validation_context", t0, t0 + 1 * s, root, rows=8192),
        _span("fit.combo", t0 + 1 * s, t0 + 9.5 * s, root, index=0),
        _span("fit.make_coordinates", t0 + 1 * s, t0 + 1.25 * s, root),
        _span("cd.init", t0 + 1.25 * s, t0 + 1.5 * s, root),
        _span("cd.sweep", t0 + 1.5 * s, t0 + 9 * s, root, iteration=0),
        _span("fe.solve", t0 + 1.5 * s, t0 + 3.5 * s, root, device=True),
        _span("fe.tolerances", t0 + 1.5 * s, t0 + 2 * s, root, device=True),
        _span("cd.tracker", t0 + 3.5 * s, t0 + 3.625 * s, root),
        _span("fe.score", t0 + 3.625 * s, t0 + 4 * s, root, device=True),
        _span("cd.guard", t0 + 4 * s, t0 + 4.125 * s, root),
        # two eval spans of very different length: a median per span would
        # read the short one, a sum per fit reads both
        _span("cd.eval", t0 + 8 * s, t0 + 8.0625 * s, root),
        _span("cd.eval", t0 + 8.0625 * s, t0 + 9 * s, root),
    ]
    if random_effect:
        spans += [
            _span("re.exchange", t0 + 4.25 * s, t0 + 5.25 * s, root, device=True),
            _span("re.bucket", t0 + 5.25 * s, t0 + 5.75 * s, root, k=256, s=32, device=True),
            _span("re.bucket", t0 + 5.75 * s, t0 + 6.5 * s, root, k=8, s=32, device=True),
            _span("re.score", t0 + 7 * s, t0 + 7.5 * s, root, device=True),
            _span("cd.guard", t0 + 7.5 * s, t0 + 7.625 * s, root),
        ]
    return spans


def _counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels, "value": float(value)}


def _summary(name, values, **labels):
    return {
        "name": name, "kind": "summary", "labels": labels, "sum": float(sum(values)),
        "stat": {"count": len(values), "mean": sum(values) / len(values)},
    }


def _observations(random_effect=True, with_tree=True):
    first = _fit_tree("s10", 0.0, 1.0, random_effect)
    second = _fit_tree("s90", 20.0, 1.125, random_effect)
    # the two fits' spans interleaved, as a collector shared by threads gives them
    spans = [s for pair in zip(first, second) for s in pair] + first[len(second):] + second[len(first):]
    spans += [
        # a warm-up fit outside every traced window, and a span of another tree
        # lying inside one: neither belongs to a traced fit
        *_fit_tree("s1", -15.0, 1.0, random_effect),
        _span("serving.request", 2.0, 6.0, "s55"),
        _span("cd.eval", 2.0, 6.0, "s56"),
    ]
    if not with_tree:  # what a commit before PR 26 emits: no root, no root_id
        spans = [
            SpanRecord(s.name, s.start, s.end, {k: v for k, v in s.attrs.items() if k != "root_id"})
            for s in spans if s.name.startswith("cd.")
        ]
    counters = [
        _counter("photon_device_fetch_bytes_total", 4096, site="cd.update_guard"),
        _summary("photon_cd_iterations", [3, 1, 2, 0], coordinate="global"),
    ]
    if with_tree:
        counters += [
            _counter("photon_device_put_bytes_total", 2 * 33_554_432, site="fit.validation_context"),
            _counter("photon_device_put_bytes_total", 2 * 65_536, site="somewhere.else"),
            _summary("photon_cd_cg_iterations", [7, 3, 4, 0], coordinate="global"),
        ]
    if random_effect:
        counters.append(_summary("photon_cd_iterations", [12.5, 13.5], coordinate="per-user"))
        if with_tree:
            counters += [
                _counter("photon_re_block_slots_total", 578, coordinate="per-user", kind="real"),
                _counter("photon_re_block_slots_total", 422, coordinate="per-user", kind="padded"),
                _counter("photon_re_lane_iterations_total", 600, coordinate="per-user", kind="useful"),
                _counter("photon_re_lane_iterations_total", 800, coordinate="per-user", kind="issued"),
            ]
    job = types.SimpleNamespace(
        config={"fixed_effect": {"name": "global"}, "random_effect": {"name": "per-user"}}
    )
    return Observations(
        fit_windows=list(FIT_WINDOWS), spans=spans, counters=counters, listener=None,
        setup_spans={}, job=job, peak={}, chips=1, memory_peak_bytes=0,
    )


# metric -> its value on the synthetic GLMix cell: the median of the two fits
# (scales 1 and 1.125), which for two values is their mean, so x * 1.0625
MID = 1.0625
EXPECTED = {
    "fit_validation_ctx_s": 1.0 * MID,
    "fit_combo_setup_s": 0.5 * MID,
    "fit_retrace_s": 0.04 * MID,
    "fit_self_s": 0.5 * MID,  # 10 - (1 + 8.5): validation context and combo, nothing double
    "fit_put_bytes": 33_554_432 + 65_536,
    "cd_guard_s": 0.25 * MID,
    "cd_tracker_s": 0.125 * MID,
    "eval_fit_s": 1.0 * MID,
    "fe_tolerances_s": 0.5 * MID,
    "fe_solve_s": 2.0 * MID,
    "fe_score_s": 0.375 * MID,
    "fe_cg_iters": 7.0,
    "re_exchange_s": 1.0 * MID,
    "re_solve_s": 1.25 * MID,
    "re_score_s": 0.5 * MID,
    "re_slot_pad_share": 42.2,
    "re_lockstep_share": 25.0,
    "re_solver_iters": 13.0,
}
# without a random effect: one guard a fit, and nothing of the re_* kind
FIXED_ONLY = dict(EXPECTED, cd_guard_s=0.125 * MID, **{k: None for k in EXPECTED if k.startswith("re_")})


def test_the_table_covers_the_manifest():
    manifest = brun.load_manifest()
    first_new = [m["name"] for m in manifest["per_layer"]].index("fit_validation_ctx_s")
    added = [m for m in manifest["per_layer"][first_new:] if m["name"] != "collective_exposed_s"]
    assert [m["name"] for m in added] == list(EXPECTED)
    cells = {w["name"] for w in manifest["workloads"]}
    for m in added:
        assert m["moves"] == "fit_s" and m["better"] == "lower"
        if m["name"].startswith("re_"):  # the cells whose mix trains the random effect
            assert set(m["workloads"]) == {c for c in cells if c.endswith(".fit")}
        else:
            assert "workloads" not in m


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_sums_per_fit(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_without_a_random_effect(name):
    value = brun.load_reader(name).read(_observations(random_effect=False))
    if FIXED_ONLY[name] is None:
        assert value is None
    else:
        assert value == pytest.approx(FIXED_ONLY[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_returns_nothing_on_a_program_without_the_tree(name):
    """The driver lays these readers over the parent's checkout too."""
    obs = _observations(with_tree=False)
    if name == "re_solver_iters":  # reads a counter the parent already has
        assert brun.load_reader(name).read(obs) == pytest.approx(13.0)
    else:
        assert brun.load_reader(name).read(obs) is None
    if name not in ("re_slot_pad_share", "re_lockstep_share"):  # ratios need no fit window
        assert brun.load_reader(name).read(_no_fits()) is None


def _no_fits():
    obs = _observations()
    obs.fit_windows = []  # a traced fit failed: its spans mean nothing
    return obs


def test_fits_are_the_trees_whose_root_lies_in_a_traced_window():
    trees = fit_spans.fits(_observations())
    assert [root.attrs["root_id"] for root, _ in trees] == ["s10", "s90"]
    for root, tree in trees:
        assert {s.attrs["root_id"] for s in tree} == {root.attrs["root_id"]}
        assert root not in tree and "serving.request" not in {s.name for s in tree}


def test_self_time_on_overlapping_and_overhanging_children():
    """Children that overlap count once; one that overhangs its root (a span
    closed by a worker thread after the root) is clipped to it."""
    spans = [
        _span("fit", 0.0, 10.0, "s1"),
        _span("fit.combo", 1.0, 6.0, "s1"),
        _span("cd.eval", 4.0, 8.0, "s1"),  # overlaps the combo by 2
        _span("cd.eval", 9.5, 12.0, "s1"),  # overhangs the root by 2
        _span("cd.sweep", 2.0, 3.0, "s1"),  # inside the combo
    ]
    obs = _observations()
    obs.spans, obs.fit_windows = spans, [(0.0, 12.5)]
    assert fit_spans.self_s(obs) == pytest.approx(10.0 - (7.0 + 0.5))
    assert brun.load_reader("eval_fit_s").read(obs) == pytest.approx(6.5)
