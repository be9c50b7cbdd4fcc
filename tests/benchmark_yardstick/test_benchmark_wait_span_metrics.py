"""The readers of PR 36 on a synthetic ``Observations``: what a fenced span
says of its two halves (``enqueue_s``, ``wait_s``; a bucket's ``cut_s``), the
``fetch`` leaves and their seconds counter, and the device's busy seconds
inside the buckets, each summed per fit, median over the traced fits; None
wherever the program gives nothing to read (any commit before PR 36, a run
whose traced fit failed). The readers are files beside the others and are not
in ``BENCHMARK.json`` yet (PERF.md section 7)."""

import pytest

from benchmark import run as brun
from benchmark import wait_spans
from benchmark.observe import Observations, SpanRecord
from benchmark.trace import DeviceTrace

FIT_WINDOWS = [(0.0, 10.0), (20.0, 32.0)]
USER, ITEM = "per-user", "per-item"
NEW_ATTRS = ("enqueue_s", "wait_s", "cut_s")


def _span(name, start, end, root, **attrs):
    return SpanRecord(name, start, end, dict(attrs, root_id=root))


def _fit_tree(root, t0, s):
    """One fit's spans, every time a multiple of ``s``. A fenced span gives
    (enqueue, wait) and closes with its fence; a bucket its cut besides."""

    def fenced(name, a, enqueue, wait, coordinate, **attrs):
        return _span(
            name, t0 + a * s, t0 + (a + enqueue + wait) * s, root, coordinate=coordinate,
            device=True, enqueue_s=enqueue * s, wait_s=wait * s,
            **{k: v * s if k == "cut_s" else v for k, v in attrs.items()},
        )

    def fetch(a, b, site):
        return _span("fetch", t0 + a * s, t0 + b * s, root, site=site, bytes=64)

    return [
        _span("fit", t0, t0 + 10 * s, root, n_combos=1),
        _span("cd.sweep", t0 + 1 * s, t0 + 9 * s, root, iteration=0),
        # the solve's own metrics fetch waits for the solve BEFORE its fence
        fenced("fe.solve", 1.0, 1.5, 0.5, "global"),
        fenced("fe.tolerances", 1.0, 0.25, 0.25, "global"),
        fetch(2.0, 2.5, "solver.tron"),
        fenced("re.exchange", 3.0, 0.25, 0.25, USER),
        fenced("re.warm_start", 3.5, 0.375, 0.125, USER, warm=True, priors=False),
        fetch(3.5, 3.75, "coordinate.project_layout"),
        fenced("re.bucket", 4.0, 0.5, 0.5, USER, k=256, s=32, cut_s=0.25),
        fenced("re.bucket", 5.0, 0.25, 0.25, USER, k=8, s=32, cut_s=0.125),
        fetch(5.5, 5.625, "re.bucket_iterations"),
        fenced("re.warm_start", 5.75, 0.125, 0.125, ITEM, warm=True, priors=False),
        fenced("re.bucket", 6.0, 0.375, 0.625, ITEM, k=1024, s=32, cut_s=0.125),
        _span("cd.guard", t0 + 7 * s, t0 + 7.25 * s, root, coordinate=ITEM),
        fetch(7.0, 7.25, "cd.update_guard"),
    ]


def _chip_events(t0, s):
    """Two chips' operations of one fit: (chip 0, chip 1)."""
    at = lambda a, b: ("jit__train_blocks_packed/fusion.1", t0 + a * s, t0 + b * s)  # noqa: E731
    return (
        # 0.75 of the first user bucket, 0.25 of the second, 0.5 of the items'
        [at(4.25, 5.25), at(6.5, 7.5), at(8.0, 9.0)],
        # 0.5 of the first user bucket, in two operations that touch
        [at(4.0, 4.25), at(4.25, 4.5)],
    )


def _counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels, "value": float(value)}


def _observations(split=True, with_trace=True):
    fits = [("s10", 0.0, 1.0), ("s90", 20.0, 1.125)]
    spans = [x for root, t0, s in fits for x in _fit_tree(root, t0, s)]
    spans += _fit_tree("s1", -15.0, 1.0)  # a warm-up fit outside every traced window
    counters = [_counter("photon_device_fetch_bytes_total", 4096, site="cd.update_guard")]
    if split:
        counters += [
            _counter("photon_device_fetch_seconds_total", 1.5, site="coordinate.project_layout"),
            _counter("photon_device_fetch_seconds_total", 0.75, site="cd.update_guard"),
        ]
    else:  # what a commit before PR 36 emits: the same phases, unsplit, no fetch span, no warm start
        spans = [
            SpanRecord(x.name, x.start, x.end, {k: v for k, v in x.attrs.items() if k not in NEW_ATTRS})
            for x in spans if x.name not in ("fetch", "re.warm_start")
        ]
    trace = None
    if with_trace:
        chips = [_chip_events(t0, s) for _, t0, s in fits]
        trace = DeviceTrace(
            chips={
                "/device:TPU:0": [e for first, _ in chips for e in first],
                "/device:TPU:1": [e for _, second in chips for e in second],
            },
            marks=[],
        )
    return Observations(
        fit_windows=list(FIT_WINDOWS), spans=spans, counters=counters, listener=None,
        setup_spans={}, job=None, peak={}, chips=2, memory_peak_bytes=0, trace=trace,
    )


# reader -> (its value on the synthetic cell, SOURCE, LAYER). The median of two
# fits of scales 1 and 1.125 is their mean: x * 1.0625; a counter is per fit
MID = 1.0625
FENCE_WAIT = 0.5 + 0.25 + 0.25 + (0.125 + 0.125) + (0.5 + 0.25 + 0.625)
FETCHES = 0.5 + 0.25 + 0.125 + 0.25  # none of them inside a fence's wait
READERS = {
    "re_bucket_enqueue_s": ((0.5 + 0.25 + 0.375) * MID, "program_span", "random-effect solve"),
    "re_bucket_cut_s": ((0.25 + 0.125 + 0.125) * MID, "program_span", "random-effect solve"),
    "re_bucket_wait_s": ((0.5 + 0.25 + 0.625) * MID, "program_span", "random-effect solve"),
    "re_bucket_device_s": (((0.75 + 0.25 + 0.5) + 0.5) / 2 * MID, "device_trace", "random-effect solve"),
    "re_warm_start_s": ((0.5 + 0.25) * MID, "program_span", "random-effect solve"),
    "fit_fetch_wait_s": ((1.5 + 0.75) / 2, "program_counter", "CD loop"),
    "fit_fence_wait_s": (FENCE_WAIT * MID, "program_span", "entry point"),
    "fit_enqueue_s": ((10.0 - FENCE_WAIT - FETCHES) * MID, "program_span", "entry point"),
}


@pytest.mark.parametrize("name", list(READERS))
def test_reader_sums_per_fit(name):
    assert brun.load_reader(name).read(_observations()) == pytest.approx(READERS[name][0])


@pytest.mark.parametrize("name", list(READERS))
def test_reader_says_what_it_is_as_the_listed_ones_do(name):
    """Unit, direction, source, layer and the end-to-end metric sit in the
    reader file until a benchmark PR lists it; the layer is one the manifest
    names already."""
    reader, manifest = brun.load_reader(name), brun.load_manifest()
    assert (reader.UNIT, reader.BETTER, reader.MOVES) == ("s", "lower", "fit_s")
    assert (reader.SOURCE, reader.LAYER) == READERS[name][1:]
    assert reader.LAYER in {m["layer"] for m in manifest["per_layer"]}
    assert name not in {m["name"] for m in manifest["per_layer"]}


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_nothing_on_a_program_that_does_not_split_its_spans(name):
    """The driver lays these readers over the parent's checkout too."""
    value = brun.load_reader(name).read(_observations(split=False))
    if name == "re_bucket_device_s":  # reads spans and a trace the parent already has
        assert value == pytest.approx(READERS[name][0])
    else:
        assert value is None


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_nothing_on_a_run_without_fits(name):
    obs = _observations()
    obs.fit_windows = []  # a traced fit failed: its spans mean nothing
    assert brun.load_reader(name).read(obs) is None


def test_the_device_reader_needs_a_trace_with_chips():
    assert brun.load_reader("re_bucket_device_s").read(_observations(with_trace=False)) is None
    obs = _observations()
    obs.trace = DeviceTrace(chips={}, marks=[])
    assert brun.load_reader("re_bucket_device_s").read(obs) is None


def test_a_wait_inside_another_counts_once():
    """The fence wait is a UNION: a span whose wait lies inside its parent's
    (or a fence that the root's end cuts) adds only what is not yet counted."""
    spans = [
        _span("fit", 0.0, 10.0, "s1"),
        _span("fe.solve", 1.0, 3.0, "s1", enqueue_s=1.0, wait_s=1.0),  # waits over [2, 3]
        _span("fe.tolerances", 2.25, 2.75, "s1", enqueue_s=0.125, wait_s=0.25),  # [2.375, 2.625]
        _span("re.score", 8.5, 10.5, "s1", enqueue_s=0.5, wait_s=1.5),  # [9, 10.5]: 1 inside the root
        _span("fetch", 2.5, 3.5, "s1", site="a", bytes=8),  # half of it inside fe.solve's wait
        _span("fetch", 5.0, 5.25, "s1", site="b", bytes=8),
    ]
    obs = _observations()
    obs.spans, obs.fit_windows = spans, [(0.0, 11.0)]
    assert wait_spans.fence_wait_s(obs) == pytest.approx(1.0 + 1.0)
    assert brun.load_reader("fit_fence_wait_s").read(obs) == pytest.approx(2.0)
    assert brun.load_reader("fit_enqueue_s").read(obs) == pytest.approx(10.0 - 2.0 - (0.5 + 0.25))


def test_one_coordinate_can_be_read_apart():
    obs = _observations()
    for attr, user, item in (("enqueue_s", 0.75, 0.375), ("cut_s", 0.375, 0.125), ("wait_s", 0.75, 0.625)):
        assert wait_spans.per_fit_attr_sum_s(obs, "re.bucket", attr, USER) == pytest.approx(user * MID)
        assert wait_spans.per_fit_attr_sum_s(obs, "re.bucket", attr, ITEM) == pytest.approx(item * MID)
    assert wait_spans.device_s(obs, "re.bucket", USER) == pytest.approx((0.75 + 0.25 + 0.5) / 2 * MID)
    assert wait_spans.device_s(obs, "re.bucket", ITEM) == pytest.approx(0.5 / 2 * MID)
    assert wait_spans.fence_wait_s(obs, ITEM) == pytest.approx((0.125 + 0.625) * MID)
    assert wait_spans.fence_wait_s(obs, "global") == pytest.approx((0.5 + 0.25) * MID)
    # a coordinate the cell does not have
    assert wait_spans.per_fit_attr_sum_s(obs, "re.bucket", "wait_s", "per-query") is None
    assert wait_spans.device_s(obs, "re.bucket", "per-query") is None
    assert wait_spans.fence_wait_s(obs, "per-query") is None
