"""The trace reduction: on plain intervals with hand-made answers, and on a
small trace recorded on a v5e (one fit of the fit-fixed job at n = 1,966,080,
PR 24's exploration call; 550 KB)."""

import os

import pytest

from benchmark import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "fit-fixed-one-fit.xplane.pb")


def test_merge_clip_subtract():
    merged = trace.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert trace.total(merged) == 5
    assert trace.clip(merged, (2, 5.5)) == [(2, 3), (5, 5.5)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 1), (2, 3)], [(0, 5)]) == []


def synthetic():
    # one chip: a while [1, 5] around two kernels, an all-reduce alone, a matmul
    ops = [
        ("jit_f/while.1", 1.0, 5.0),
        ("jit_f/fused_value_grad.8", 1.0, 2.0),
        ("jit_f/fused_value_grad.8", 3.0, 4.5),
        ("jit_g/all-reduce.1", 6.0, 7.0),
        ("jit_g/fusion.2", 6.5, 6.75),
        ("jit_h/dot.3", 8.0, 9.0),
    ]
    return trace.DeviceTrace(chips={"/device:TPU:0": ops}, marks=[("bench.fit", 0.0, 10.0)])


def test_busy_idle_kernel_time_on_intervals():
    tr = synthetic()
    window = (0.0, 10.0)
    assert trace.mean_busy_seconds(tr, window) == pytest.approx(4.0 + 1.0 + 1.0)
    assert trace.idle_share(tr, window) == pytest.approx(0.4)
    assert trace.kernel_seconds(tr, "fused_value_grad", window) == (pytest.approx(2.5), 2)
    # a window that cuts an event leaves it out of the kernel's calls
    assert trace.kernel_seconds(tr, "fused_value_grad", (0.0, 4.0))[1] == 1
    top = trace.top_ops(tr, window, k=2)
    assert top[0][0] == "jit_f/fused_value_grad.8" and top[0][1] == pytest.approx(2.5)
    assert all("while" not in name for name, _ in trace.top_ops(tr, window))


def test_gap_attribution_goes_to_the_innermost_span():
    tr = synthetic()
    spans = [("cd.coordinate:global", 0.5, 7.5), ("cd.eval", 7.2, 7.9)]
    gaps = dict(trace.idle_gaps_by_span(tr, (0.0, 10.0), spans))
    # idle: [0,1] [5,6] [7,8] [9,10]
    assert gaps["cd.coordinate:global"] == pytest.approx(0.5 + 1.0 + 0.2)
    assert gaps["cd.eval"] == pytest.approx(0.7)
    assert gaps["fit_host"] == pytest.approx(0.5 + 0.1 + 1.0)
    assert sum(gaps.values()) == pytest.approx(4.0)


def test_collective_exposed_is_the_part_nothing_else_covers():
    assert trace.collective_exposed_seconds(synthetic(), (0.0, 10.0)) == pytest.approx(0.75)


def test_names_and_modules():
    text = "%fused_value_grad.8 = (f32[1,1]{1,0:T(1,128)}) custom-call(f32[8,8] %x), custom_call_target=\"tpu_custom_call\""
    assert trace.op_name(text) == "fused_value_grad.8"
    assert trace.module_name("jit__solve(3517158820482080362)") == "jit__solve"
    labelled = trace.label_ops([(text, 2.0, 3.0), ("%add.1 = f32[] add()", 9.0, 9.5)], [("jit__solve(1)", 1.0, 4.0)])
    assert [n for n, _, _ in labelled] == ["jit__solve/fused_value_grad.8", "add.1"]
    assert trace.is_container("jit__solve/while.27") and not trace.is_container("jit_f/while_fusion.1")
    assert trace.is_collective("jit_f/all-reduce.3") and not trace.is_collective("jit_f/fusion.1")


def test_clock_offset_from_marks():
    tr = trace.DeviceTrace(chips={}, marks=[("bench.fit", 1.0, 2.0), ("bench.fit", 3.0, 4.0)])
    assert trace.clock_offset(tr, "bench.fit", [101.0, 103.0]) == pytest.approx(100.0)
    assert trace.clock_offset(tr, "bench.fit", [101.0]) is None
    moved = tr.shifted(100.0)
    assert moved.marks[0][1:] == (101.0, 102.0)


def test_recorded_v5e_trace():
    tr = trace.load(FIXTURE)
    assert list(tr.chips) == ["/device:TPU:0"]
    assert [m[0] for m in tr.marks] == ["bench.fit"]
    window = tr.marks[0][1:]
    assert window[1] - window[0] == pytest.approx(1.6057, abs=1e-3)
    busy = trace.mean_busy_seconds(tr, window)
    assert busy == pytest.approx(1.3272, abs=1e-3)
    assert trace.idle_share(tr, window) == pytest.approx(0.1735, abs=1e-3)
    vg_s, vg_calls = trace.kernel_seconds(tr, "fused_value_grad", window)
    hv_s, hv_calls = trace.kernel_seconds(tr, "fused_hessian_vector", window)
    assert (vg_calls, hv_calls) == (42, 32)
    assert vg_s == pytest.approx(0.54336, abs=1e-4) and hv_s == pytest.approx(0.66274, abs=1e-4)
    top = trace.top_ops(tr, window)
    assert top[0][0] == "jit__solve/fused_hessian_vector.4"
    # busy is the union: a while around the kernels adds only its loop control
    leaf = sum(b - a for n, a, b in tr.chips["/device:TPU:0"] if not trace.is_container(n))
    assert busy == pytest.approx(leaf, abs=1e-3)
    gaps = trace.idle_gaps_by_span(tr, window, [])
    assert gaps[0][0] == "fit_host" and gaps[0][1] == pytest.approx((window[1] - window[0]) - busy, abs=1e-6)
