"""Bytes functions against hand counts; the window's arithmetic on a fake
clock; the compile listener on exact event names."""

import pytest

from benchmark import shapes
from benchmark.compile_listener import BACKEND, CACHE_HIT, RETRIEVAL, TRACE, CompileListener
from benchmark.window import run_window


def test_value_grad_bytes_by_hand():
    # n=8 rows, d=4 columns, f32: X 8*4*4=128, coef 16, three [n] vectors 96,
    # out: grad 16 + two scalars 8
    assert shapes.value_grad_bytes(8, 4) == 128 + 16 + 96 + 24
    # bf16 X and coef, f32 per-row vectors and outputs
    assert shapes.value_grad_bytes(8, 4, x_itemsize=2) == 64 + 8 + 96 + 24
    assert shapes.value_grad_flops(8, 4) == 128


def test_hessian_vector_bytes_by_hand():
    # X 128, coef + v 32, three [n] vectors 96 + vshift 4, out: hv 16 + scalar 4
    assert shapes.hessian_vector_bytes(8, 4) == 128 + 32 + 100 + 20
    assert shapes.hessian_vector_flops(8, 4) == 192


def test_roofline_share_names_its_bound():
    peak = {"hbm_bytes_per_s": 800.0, "bf16_flops_per_s": 1e6}
    r = shapes.roofline_share(bytes_=400, flops=1000, seconds=1.0, peak=peak)
    assert r == {"share": pytest.approx(50.0), "bound": "memory"}
    r = shapes.roofline_share(bytes_=8, flops=2_000_000, seconds=4.0, peak=peak)
    assert r == {"share": pytest.approx(50.0), "bound": "compute"}
    # the real kernel at the cell's size is memory-bound by a wide margin
    v5e = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    n, d = 1_572_864, 1024
    r = shapes.roofline_share(shapes.value_grad_bytes(n, d), shapes.value_grad_flops(n, d), 1.0, v5e)
    assert r["bound"] == "memory"


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_median_attempted_failed_on_a_fake_clock():
    clock = FakeClock()
    walls = iter([2.0, 4.0, 3.0, 50.0, 1.0])
    outcomes = iter(["ok", "ok", "raise", "reject", "ok"])

    def fit():
        clock.t += next(walls)
        what = next(outcomes)
        if what == "raise":
            raise RuntimeError("a fit that raises")
        return what

    def check(result):
        clock.t += 0.5  # time between fits is in no wall
        return result == "ok"

    w = run_window(fit, check, seconds=12.0, clock=clock)
    # fits start at 0, 2.5, 7.0 (raises at 10.0), 10.0 -> 60.0: the fit in flight is finished
    assert w.attempted == 4 and w.failed == 2
    assert w.walls == [2.0, 4.0] and w.median_s == 3.0
    assert w.starts == [100.0, 102.5, 107.0, 110.0]


def test_window_max_fits_and_empty():
    clock = FakeClock()

    def fit():
        clock.t += 1.0

    w = run_window(fit, lambda r: True, seconds=1e9, max_fits=3, clock=clock)
    assert w.attempted == 3 and w.failed == 0 and w.median_s == 1.0
    w = run_window(fit, lambda r: False, seconds=0.5, clock=clock)
    assert w.attempted == 1 and w.failed == 1 and w.median_s is None


def test_cache_hit_is_a_retrace_not_a_compile():
    ls = CompileListener()
    ls.phase = "window"
    # a cold compile: trace, backend, no hit
    ls.on_duration(TRACE, 0.1)
    ls.on_duration(BACKEND, 2.0)
    # a re-trace answered by the persistent cache: jax fires the backend event
    # around the cache lookup, plus the hit and the retrieval time
    ls.on_duration(TRACE, 0.1)
    ls.on_event(CACHE_HIT)
    ls.on_duration(RETRIEVAL, 0.01)
    ls.on_duration(BACKEND, 0.02)
    # events the program's own hook would add to its sum are not counted at all
    ls.on_duration("/jax/compilation_cache/compile_time_saved_sec", 1.9)
    ls.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.3)
    assert ls.retraces("window") == 2
    assert ls.compiles("window") == 1
    assert ls.backend_seconds("window") == pytest.approx(2.02)
    assert ls.retraces("setup") == 0 and ls.compiles("setup") == 0


def test_listener_on_real_jax_events(tmp_path):
    """A jitted function compiled, dropped from memory and called again with
    the persistent cache on: the second call re-traces and hits."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    ls = CompileListener().install()
    # a throw-away cache for this test alone (set through a dict: only
    # utils/compile_cache.py may name the cache directory in a config call)
    settings = {
        "jax_enable_compilation_cache": True,
        "jax_compilation_cache_dir": str(tmp_path),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
    old = {k: getattr(jax.config, k) for k in settings}
    try:
        for k, v in settings.items():
            jax.config.update(k, v)
        cc.reset_cache()

        def f(x):
            return jnp.tanh(x * 3.0 + 1.0).sum()

        ls.phase = "cold"
        jax.jit(f)(jnp.arange(7.0)).block_until_ready()
        jax.clear_caches()
        ls.phase = "again"
        jax.jit(f)(jnp.arange(7.0)).block_until_ready()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
        ls.phase = "done"
    assert ls.compiles("cold") >= 1
    assert ls.retraces("again") >= 1
    assert ls.compiles("again") == 0
