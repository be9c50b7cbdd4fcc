"""Parity tests for the fused Pallas GLM kernels (ops/pallas_glm.py).

Strategy: the kernels must be bit-for-bit interchangeable (to f32 tolerance)
with the two-pass jnp path on the SAME padded batch — every loss, with and
without normalization, weights/offsets, L2 and prior-centered regularization.
On CPU they run under interpret=True; the compiled TPU path shares every line
except the Mosaic lowering.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem, _fusion_mode
from photon_ml_tpu.ops import pallas_glm
from photon_ml_tpu.ops.features import batch_from_dense, pad_batch
from photon_ml_tpu.ops.glm import GLMObjective, compute_variances
from photon_ml_tpu.ops.losses import LOSSES
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig


D = 256
TN = pallas_glm.tile_rows(D)


def _make_batch(rng, n, d=D, dtype=np.float32):
    x = rng.standard_normal((n, d)).astype(dtype)
    y = (rng.random(n) > 0.5).astype(dtype)
    off = (rng.standard_normal(n) * 0.1).astype(dtype)
    wt = (rng.random(n) + 0.5).astype(dtype)
    return batch_from_dense(x, y, offsets=off, weights=wt, dtype=jnp.dtype(dtype))


def _norm_ctx(rng, d=D, dtype=np.float32):
    return NormalizationContext(
        factors=jnp.asarray((rng.random(d) + 0.5).astype(dtype)),
        shifts=jnp.asarray(((rng.random(d) - 0.5) * 0.2).astype(dtype)),
        intercept_index=0,
    )


@pytest.mark.parametrize("loss_name", sorted(LOSSES))
@pytest.mark.parametrize("with_norm", [False, True])
def test_fused_value_grad_and_hv_parity(rng, loss_name, with_norm):
    loss = LOSSES[loss_name]
    batch = _make_batch(rng, 2 * TN)
    if loss_name in ("poisson",):
        batch = dataclasses.replace(batch, labels=jnp.abs(batch.labels) * 2)
    norm = _norm_ctx(rng) if with_norm else None
    pm = jnp.asarray((rng.standard_normal(D) * 0.01).astype(np.float32))
    pp = jnp.asarray((rng.random(D) + 0.5).astype(np.float32))
    base = GLMObjective(
        loss=loss, batch=batch, l2=0.3, norm=norm, prior_mean=pm, prior_precision=pp
    )
    fused = dataclasses.replace(base, fused="interpret")
    w = jnp.asarray((rng.standard_normal(D) * 0.1).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))

    v0, g0 = base.value_and_grad(w)
    v1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    # f32 accumulation order differs (per-tile partial sums vs one reduce), so
    # compare against the result's own magnitude, not element-wise rtol
    g0, g1 = np.asarray(g0), np.asarray(g1)
    assert np.max(np.abs(g1 - g0)) <= 3e-5 * max(np.max(np.abs(g0)), 1.0)

    h0 = np.asarray(base.hessian_vector(w, v))
    h1 = np.asarray(fused.hessian_vector(w, v))
    assert np.max(np.abs(h1 - h0)) <= 3e-5 * max(np.max(np.abs(h0)), 1.0)

    d0 = np.asarray(base.hessian_diagonal(w))
    d1 = np.asarray(fused.hessian_diagonal(w))
    assert np.max(np.abs(d1 - d0)) <= 3e-5 * max(np.max(np.abs(d0)), 1.0)


def test_fused_under_jit_and_partial_tile(rng):
    """The fused objective must jit (solvers trace it), handle a row count
    that is NOT a tile multiple (in-kernel masking of the last tile), and
    ignore explicit weight-0 padding rows exactly like the jnp path does."""
    batch = _make_batch(rng, TN + 7)  # deliberately not a tile multiple
    base = GLMObjective(loss=LOSSES["logistic"], batch=batch, l2=0.1)
    fused = dataclasses.replace(base, fused="interpret")
    fused_padded = GLMObjective(
        loss=LOSSES["logistic"],
        batch=pad_batch(batch, 2 * TN),
        l2=0.1,
        fused="interpret",
    )
    w = jnp.asarray((rng.standard_normal(D) * 0.1).astype(np.float32))

    from photon_ml_tpu.ops.glm import vg_fn

    @jax.jit
    def run(f, w):
        return f(w)

    v0, g0 = run(vg_fn(base), w)
    for obj in (fused, fused_padded):
        v1, g1 = run(vg_fn(obj), w)
        np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-4, atol=1e-4)


def test_fusion_mode_gating(rng, monkeypatch):
    """_fusion_mode: off by default on CPU (auto), on under interpret, and
    never for sparse layouts, tiny batches, or misaligned feature dims."""
    ok = _make_batch(rng, pallas_glm.MIN_FUSED_ROWS)
    monkeypatch.setenv("PHOTON_PALLAS", "auto")
    assert _fusion_mode(ok) == (None, None)  # CPU backend
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    assert _fusion_mode(ok) == ("interpret", None)
    # too few rows
    assert _fusion_mode(_make_batch(rng, 512)) == (None, None)
    # misaligned feature dim
    assert _fusion_mode(_make_batch(rng, pallas_glm.MIN_FUSED_ROWS, d=200)) == (None, None)
    # f64 batch (x64 test mode)
    assert _fusion_mode(
        _make_batch(rng, pallas_glm.MIN_FUSED_ROWS, dtype=np.float64)
    ) == (None, None)
    monkeypatch.setenv("PHOTON_PALLAS", "off")
    assert _fusion_mode(ok) == (None, None)
    monkeypatch.setenv("PHOTON_PALLAS", "bogus")
    with pytest.raises(ValueError):
        _fusion_mode(ok)


def test_fusion_mode_sharded_batches(rng, monkeypatch):
    """A DATA-axis-sharded dense batch fuses via shard_map (mesh returned);
    model-axis feature sharding falls back to the jnp path."""
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.parallel.mesh import shard_batch

    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    batch = _make_batch(rng, pallas_glm.MIN_FUSED_ROWS)
    mesh = make_mesh(n_data=4, n_model=2)
    sharded = shard_batch(batch, mesh)
    mode, fmesh = _fusion_mode(sharded)
    assert mode == "interpret" and fmesh is mesh

    sharded_model = shard_batch(batch, mesh, shard_features_dim=True)
    assert _fusion_mode(sharded_model) == (None, None)


def test_sharded_fused_matches_unsharded(rng, monkeypatch):
    """shard_map'd fused kernels on an 8-device data-parallel mesh produce
    the same objective value/grad/Hv as the single-device jnp path."""
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.parallel.mesh import shard_batch

    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    n = pallas_glm.MIN_FUSED_ROWS + 13  # force partial tiles per shard
    batch = _make_batch(rng, n)
    mesh = make_mesh(n_data=8, n_model=1)
    sharded = shard_batch(batch, mesh)  # zero-weight-pads rows to the mesh
    mode, fmesh = _fusion_mode(sharded)
    assert mode == "interpret" and fmesh is mesh

    base = GLMObjective(loss=LOSSES["logistic"], batch=batch, l2=0.2)
    fused = GLMObjective(
        loss=LOSSES["logistic"], batch=sharded, l2=0.2,
        fused="interpret", fused_mesh=mesh,
    )
    w = jnp.asarray((rng.standard_normal(D) * 0.1).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))

    v0, g0 = base.value_and_grad(w)
    v1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    g0, g1 = np.asarray(g0), np.asarray(g1)
    assert np.max(np.abs(g1 - g0)) <= 3e-5 * max(np.max(np.abs(g0)), 1.0)

    h0 = np.asarray(base.hessian_vector(w, v))
    h1 = np.asarray(fused.hessian_vector(w, v))
    assert np.max(np.abs(h1 - h0)) <= 3e-5 * max(np.max(np.abs(h0)), 1.0)

    d0 = np.asarray(base.hessian_diagonal(w))
    d1 = np.asarray(fused.hessian_diagonal(w))
    assert np.max(np.abs(d1 - d0)) <= 3e-5 * max(np.max(np.abs(d0)), 1.0)

    # the shifts path of the sharded stats kernel (s1/s0 psums)
    norm = _norm_ctx(rng)
    base_n = dataclasses.replace(base, norm=norm)
    fused_n = dataclasses.replace(fused, norm=norm)
    dn0 = np.asarray(base_n.hessian_diagonal(w))
    dn1 = np.asarray(fused_n.hessian_diagonal(w))
    assert np.max(np.abs(dn1 - dn0)) <= 3e-5 * max(np.max(np.abs(dn0)), 1.0)


@pytest.mark.parametrize("d", [128, 384, 1024])
@pytest.mark.parametrize("n_off", [0, 1, 127])
def test_kernel_shape_sweep(rng, d, n_off):
    """Property sweep over feature dims and row remainders (full tiles,
    off-by-one, near-full partial tile): fused value+grad must match the jnp
    path at every shape the gating can admit."""
    n = pallas_glm.tile_rows(d) * 2 + n_off
    x = (rng.standard_normal((n, d)) * 0.4).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    batch = batch_from_dense(x, y)
    base = GLMObjective(loss=LOSSES["logistic"], batch=batch, l2=0.1)
    fused = dataclasses.replace(base, fused="interpret")
    w = jnp.asarray((rng.standard_normal(d) * 0.1).astype(np.float32))
    v0, g0 = base.value_and_grad(w)
    v1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    g0, g1 = np.asarray(g0), np.asarray(g1)
    assert np.max(np.abs(g1 - g0)) <= 3e-5 * max(np.max(np.abs(g0)), 1.0)


# relative to the result's own magnitude: f32 as everywhere in this file,
# bf16 as tests/test_bf16_features.py compares the kernel with the jnp path
# on the same bf16 X (the kernel rounds coef, v and c*u to bf16 at the dots)
HV_TOL = {"float32": 3e-5, "bfloat16": 1e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [128, 384, 1024])
@pytest.mark.parametrize("n_off", [0, 1, 127])
@pytest.mark.parametrize("with_norm", [False, True])
@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_hv_kernel_shape_sweep(rng, loss_name, with_norm, n_off, d, dtype):
    """The stacked Hv kernel (ONE dot for the margins of coef and of v)
    against the three-sweep jnp composition of GLMObjective.hessian_vector:
    every loss, with and without normalization shifts (a non-zero vshift),
    full / off-by-one / near-full last tile, both X dtypes."""
    itemsize = jnp.dtype(dtype).itemsize
    n = pallas_glm.tile_rows(d, itemsize) * 2 + n_off
    x = (rng.standard_normal((n, d)) * 0.4).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    if loss_name == "poisson":
        y = y * 2
    batch = batch_from_dense(
        x, y, offsets=(rng.standard_normal(n) * 0.1).astype(np.float32),
        weights=(rng.random(n) + 0.5).astype(np.float32),
        feature_dtype=jnp.dtype(dtype),
    )
    base = GLMObjective(
        loss=LOSSES[loss_name], batch=batch, l2=0.1,
        norm=_norm_ctx(rng, d) if with_norm else None,
    )
    fused = dataclasses.replace(base, fused="interpret")
    w = jnp.asarray((rng.standard_normal(d) * 0.1).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    if dtype == "bfloat16":
        # the kernel rounds the effective coefficients to X's dtype at the
        # dot: give it ones that are bf16 already, so that both paths see the
        # same margins and the band where smoothed hinge's l'' is 1 holds the
        # same rows (v's rounding stays: u is linear in it)
        eff = w if base.norm is None else w * base.norm.factors
        eff = eff.astype(jnp.bfloat16).astype(jnp.float32)
        w = eff if base.norm is None else eff / base.norm.factors
    h0 = np.asarray(base.hessian_vector(w, v))
    h1 = np.asarray(fused.hessian_vector(w, v))
    assert h1.dtype == np.float32 and np.all(np.isfinite(h1))
    assert np.max(np.abs(h1 - h0)) <= HV_TOL[dtype] * max(np.max(np.abs(h0)), 1.0)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _dots_in_kernel_body(fn, *args, **kwargs):
    """Where the dot_generals of ``fn``'s ONE pallas_call body sit: (the count
    at the body's top level, [the count inside each top-level equation that
    holds any, i.e. each ``pl.when`` branch with work in it])."""
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args).jaxpr
    (call,) = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    (body,) = jax.core.jaxprs_in_params(call.params)
    nested = [
        sum(
            e.primitive.name == "dot_general"
            for sub in jax.core.jaxprs_in_params(eqn.params)
            for e in _eqns(sub)
        )
        for eqn in body.eqns
    ]
    top = sum(eqn.primitive.name == "dot_general" for eqn in body.eqns)
    return top, [n for n in nested if n]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_off", [0, 7])
def test_kernel_bodies_pass_the_tile_through_two_dots(n_off, dtype):
    """Structural pin of what the chip's time goes with (ops/pallas_glm.py's
    header): each X tile enters the MXU twice a call in the Hv kernel, as in
    the value-and-gradient kernel, in the full-tile branch and in the masked
    one. A third dot_general over the tile costs 6.2 ms a call at the cells'
    shape whatever else the kernel does. The expected structure is spelled
    out (two dots at the top level when n is a multiple of the tile, else none
    there and two in each of the two branches), so a change in how the body
    is traced fails here and is not counted as something else."""
    d = 256
    n = 2 * pallas_glm.tile_rows(d, jnp.dtype(dtype).itemsize) + n_off
    x = jax.ShapeDtypeStruct((n, d), jnp.dtype(dtype))
    vec_d = jax.ShapeDtypeStruct((d,), jnp.float32)
    vec_n = jax.ShapeDtypeStruct((n,), jnp.float32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    loss = LOSSES["logistic"]
    expected = (0, [2, 2]) if n_off else (2, [])
    assert _dots_in_kernel_body(
        pallas_glm.fused_hessian_vector, x, vec_d, vec_d, vec_n, vec_n, vec_n, scalar,
        loss=loss, interpret=True,
    ) == expected
    assert _dots_in_kernel_body(
        pallas_glm.fused_value_grad, x, vec_d, vec_n, vec_n, vec_n, loss=loss, interpret=True
    ) == expected


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sharded_hessian_vector_equals_unsharded(rng, dtype):
    """sharded_hessian_vector on an 8-device CPU mesh (the kernel per row
    shard with a masked last tile each, then the psum) against the one-device
    call on the same arrays, the replicated [2, d] operand and a non-zero
    vshift included."""
    from photon_ml_tpu.parallel import make_mesh

    d = D
    n = 8 * (pallas_glm.tile_rows(d, jnp.dtype(dtype).itemsize) + 13)
    x = jnp.asarray((rng.standard_normal((n, d)) * 0.4).astype(np.float32), jnp.dtype(dtype))
    y = jnp.asarray((rng.random(n) > 0.5).astype(np.float32))
    off = jnp.asarray((rng.standard_normal(n) * 0.1).astype(np.float32))
    wt = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    w = jnp.asarray((rng.standard_normal(d) * 0.1).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    args = (x, w, v, y, off, wt, jnp.float32(0.37), LOSSES["logistic"])
    hv0, c0 = pallas_glm.sharded_hessian_vector(None, *args, interpret=True)
    hv1, c1 = pallas_glm.sharded_hessian_vector(
        make_mesh(n_data=8, n_model=1), *args, interpret=True
    )
    hv0, hv1 = np.asarray(hv0), np.asarray(hv1)
    # only the order of the sums over tiles differs (eight partial sums, psum)
    assert np.max(np.abs(hv1 - hv0)) <= 3e-5 * max(np.max(np.abs(hv0)), 1.0)
    np.testing.assert_allclose(float(c1), float(c0), rtol=3e-5, atol=3e-5)


def test_end_to_end_sharded_solve(rng, monkeypatch):
    """GLMProblem.run on a mesh-sharded batch picks the shard_map fused path
    and converges to the same model as the unsharded unfused solve."""
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.parallel.mesh import shard_batch

    n = pallas_glm.MIN_FUSED_ROWS
    batch = _make_batch(rng, n)
    problem = GLMProblem(
        task="logistic_regression",
        config=GLMOptimizationConfig(
            optimizer=OptimizerConfig(tolerance=1e-9, max_iterations=60),
            regularization=RegularizationContext("L2"),
            reg_weight=1.0,
        ),
    )
    monkeypatch.setenv("PHOTON_PALLAS", "off")
    m0, r0 = problem.run(batch)
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    mesh = make_mesh(n_data=8, n_model=1)
    m1, r1 = problem.run(shard_batch(batch, mesh))
    # two f32 solvers with different reduction orders walk different
    # trajectories at an unreachably tight tolerance; assert they reach the
    # same optimum: objective values agree tightly, coefficients to scale
    np.testing.assert_allclose(float(r1.loss), float(r0.loss), rtol=1e-5)
    w0_, w1_ = np.asarray(m0.coefficients.means), np.asarray(m1.coefficients.means)
    assert np.max(np.abs(w1_ - w0_)) <= 5e-3 * max(np.max(np.abs(w0_)), 1.0)


def _l2_logistic_problem(optimizer, max_iterations, tolerance=1e-9):
    return GLMProblem(
        task="logistic_regression",
        config=GLMOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=optimizer, tolerance=tolerance, max_iterations=max_iterations
            ),
            regularization=RegularizationContext("L2"),
            reg_weight=1.0,
            variance_type="SIMPLE",
        ),
    )


# TRON's 4th iteration on this problem is at f32's noise floor: whether it is
# taken at all, and where it lands, is decided by rounding, on the jnp path as
# on the fused one (data seeds 0-2: the two stop after 5/4, 3/5 and 4/4
# iterations, 3.6e-5 to 5.2e-5 apart, each up to 5.2e-5 from the float64
# optimum). Over the three iterations both always take they agree to 7e-8,
# so the two paths are compared there; that each also converges is
# test_tron_converges_to_the_float64_optimum's to say.
# L-BFGS likewise since PR 37: the jnp path's search walks margins, the fused
# path's evaluates points. Over the six iterations both take they agree to
# 4e-8; the seventh is at the floor (the points search's full step no longer
# lowers the value and it stops where it stood, the margins search takes two
# more steps). Where each lands when run to its end is
# test_lbfgs_converges_to_the_float64_optimum's to say.
E2E_ITERATIONS = {"LBFGS": 6, "TRON": 3}


@pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
def test_end_to_end_solve_matches_unfused(rng, monkeypatch, optimizer):
    """GLMProblem.run with PHOTON_PALLAS=interpret walks to the same model as
    the jnp path, iteration for iteration — the full solver loop (L-BFGS line
    search / TRON CG) driving the fused kernels."""
    n = pallas_glm.MIN_FUSED_ROWS
    batch = _make_batch(rng, n)
    problem = _l2_logistic_problem(optimizer, E2E_ITERATIONS[optimizer])
    monkeypatch.setenv("PHOTON_PALLAS", "off")
    m0, r0 = problem.run(batch)
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    m1, r1 = problem.run(batch)
    np.testing.assert_allclose(
        np.asarray(m1.coefficients.means),
        np.asarray(m0.coefficients.means),
        rtol=1e-3,
        atol=1e-5,
    )
    # variances come from the (unfused) hessian_diagonal on the padded batch;
    # weight-0 padding rows must not change them
    np.testing.assert_allclose(
        np.asarray(m1.coefficients.variances),
        np.asarray(m0.coefficients.variances),
        rtol=1e-3,
        atol=1e-6,
    )


def _float64_optimum(b32, monkeypatch):
    """The optimum of ``b32``'s problem: a float64 TRON solve of the same data."""
    b64 = batch_from_dense(
        np.asarray(b32.features.dense, np.float64), np.asarray(b32.labels, np.float64),
        offsets=np.asarray(b32.offsets, np.float64),
        weights=np.asarray(b32.weights, np.float64), dtype=jnp.float64,
    )
    monkeypatch.setenv("PHOTON_PALLAS", "off")
    m64, _ = _l2_logistic_problem("TRON", 60, tolerance=1e-12).run(b64)
    assert m64.coefficients.means.dtype == jnp.float64
    return np.asarray(m64.coefficients.means)


@pytest.mark.parametrize("pallas", ["off", "interpret"])
def test_tron_converges_to_the_float64_optimum(rng, monkeypatch, pallas):
    """TRON run to its end in f32, over the jnp path and over the fused
    kernels: each stops within f32's noise floor of the optimum that a
    float64 solve of the same data finds (which of its last iterations a
    path takes does not matter here)."""
    b32 = _make_batch(rng, pallas_glm.MIN_FUSED_ROWS)
    w64 = _float64_optimum(b32, monkeypatch)
    monkeypatch.setenv("PHOTON_PALLAS", pallas)
    m32, r32 = _l2_logistic_problem("TRON", 60).run(b32)
    assert int(r32.iterations) < 60
    assert np.max(np.abs(np.asarray(m32.coefficients.means) - w64)) <= 1e-4


@pytest.mark.parametrize("pallas", ["off", "interpret"])
def test_lbfgs_converges_to_the_float64_optimum(rng, monkeypatch, pallas):
    """L-BFGS run to its end in f32 (cap 60, as the end-to-end test ran it
    before PR 37): the jnp path, whose search walks margins, and the fused
    path, whose search evaluates points, each stop within f32's floor of the
    float64 optimum. Measured (PR 37, max |w32 - w64|; data seeds 0, 1, 2):
    margins 5.5e-6 after 9 iterations, 1.14e-4 after 7, 1.5e-5 after 8;
    points 1.92e-4 after 7 (its 7th moves nothing), 1.14e-4 after 7, 1.5e-5
    after 8. One ulp of the objective (2652.46, 2.4e-4) is worth that much in
    a coefficient. A solve one iteration short of the points search's last
    step reads 3.8e-4 (seed 0, five iterations), two short 1.2e-3."""
    b32 = _make_batch(rng, pallas_glm.MIN_FUSED_ROWS)
    w64 = _float64_optimum(b32, monkeypatch)
    monkeypatch.setenv("PHOTON_PALLAS", pallas)
    m32, r32 = _l2_logistic_problem("LBFGS", 60).run(b32)
    assert int(r32.iterations) < 60
    # the search each path ran: only a margin walk counts its own passes
    assert (r32.matvecs is not None) == (pallas == "off")
    assert np.max(np.abs(np.asarray(m32.coefficients.means) - w64)) <= 2.7e-4


def test_tile_rows_and_eligibility_constants():
    """Guards the VMEM-derived tiling rules: budgets per dtype, the parts
    divisor for multi-temporary kernels, the [128, 2048] clamp, and the
    lane-dim multiple-of-128 invariant (Mosaic requirement on the [1, tn]
    blocks — see the measured OOM notes in ops/pallas_glm.py)."""
    # f32 budget 2MB: d=1024 -> 512 rows; bf16 budget 4MB: d=1024 -> 2048
    assert pallas_glm.tile_rows(1024, 4) == 512
    assert pallas_glm.tile_rows(1024, 2) == 2048
    # parts=2 halves the budget (the Hessian-stats kernel's x*x temporary)
    assert pallas_glm.tile_rows(1024, 4, parts=2) == 256
    # clamps: tiny d caps at 2048 rows; the max fused dims keep >= 128 rows
    assert pallas_glm.tile_rows(128, 4) == 2048
    assert pallas_glm.tile_rows(pallas_glm.MAX_FUSED_DIM_F32, 4) == 128
    assert pallas_glm.tile_rows(pallas_glm.MAX_FUSED_DIM_BF16, 2) == 256
    for d in (128, 384, 1024, 4096, 8192):
        for itemsize in (2, 4):
            for parts in (1, 2):
                tn = pallas_glm.tile_rows(d, itemsize, parts)
                assert tn % 128 == 0 and 128 <= tn <= 2048

    import jax.numpy as jnp

    n = pallas_glm.MIN_FUSED_ROWS
    # dtype-specific dim ceilings
    assert pallas_glm.eligible(n, pallas_glm.MAX_FUSED_DIM_F32, jnp.float32)
    assert not pallas_glm.eligible(n, pallas_glm.MAX_FUSED_DIM_F32 + 128, jnp.float32)
    assert pallas_glm.eligible(n, pallas_glm.MAX_FUSED_DIM_BF16, jnp.bfloat16)
    assert not pallas_glm.eligible(n, pallas_glm.MAX_FUSED_DIM_BF16 + 128, jnp.bfloat16)
    assert not pallas_glm.eligible(n, 1024, jnp.float64)
