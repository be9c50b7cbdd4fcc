"""The generator (two seeds: one data set, mirrored), and the plain
reference against the system's fit, at a tiny size on the CPU."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import correct, data as gen
from benchmark.jobs import fit as fitjob

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tiny(config_name="glmix-user-1chip", traffic_name="fit"):
    with open(os.path.join(ROOT, "benchmark", "configs", config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    config = copy.deepcopy(config)
    config["fixed_effect"].update(d=128, intercept_column=127)
    config["random_effect"].update(d_re=8, active_cap=64)
    config["scale"].update(rows=8192, users=300, validation_rows=256, generation_chunk_rows=2048)
    return config, traffic


def test_quotas_are_seed_free_and_whole():
    q = gen.user_quotas(2_000_000, 80_000, 1.1)
    assert q.sum() == 2_000_000 and q.min() >= 1
    assert np.all(np.diff(q) <= 0)  # by rank
    assert q[0] > 200_000  # Zipf 1.1 has a heavy head
    # the floor of one row can overshoot: rows are taken back from the largest
    q = gen.user_quotas(1000, 900, 1.1)
    assert q.sum() == 1000 and q.min() == 1
    with pytest.raises(ValueError):
        gen.user_quotas(10, 11, 1.1)


def test_device_matrix_is_seeded_and_chunk_keyed():
    w = np.ones(16, np.float32)
    x1, z1 = gen.device_features(7, 64, 16, 16, w)
    x2, _ = gen.device_features(7, 64, 16, 16, w)
    x3, _ = gen.device_features(2**31 + 7, 64, 16, 16, w)
    x4, _ = gen.device_features(7, 64, 16, 16, w, stream=1)
    x1, x2, x3, x4, z1 = map(np.asarray, (x1, x2, x3, x4, z1))
    assert x1.dtype == np.float32 and np.array_equal(x1, x2)
    assert not np.array_equal(x1, x3) and not np.array_equal(x1, x4)
    assert np.all(x1[:, -1] == 1.0)  # the intercept is the last column
    np.testing.assert_allclose(z1, x1.sum(axis=1), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        gen.device_features(7, 60, 16, 16, w)
    # a mirror flips columns and nothing else: the margin is the same bits
    signs = gen.draw_mirror(2**31 + 5, 16, 4).fixed
    assert set(signs.tolist()) == {-1.0, 1.0} and signs[-1] == 1.0
    x5, z5 = map(np.asarray, gen.device_features(7, 64, 16, 16, w, signs=signs))
    assert np.array_equal(x5, x1 * signs) and np.array_equal(z5, z1)


@pytest.fixture(scope="module")
def two_jobs():
    config, traffic = tiny()
    return [fitjob.build(config, traffic, chips=1, seed=s) for s in (11, 2**31 + 12)]


def test_two_seeds_mirror_one_data_set(two_jobs):
    from photon_ml_tpu.game.coordinate import _size_buckets

    a, b = two_jobs
    for name in ("global", "per-user"):
        assert name in a.datasets
    xa, xb = (np.asarray(j.datasets["global"].batch.features.dense) for j in (a, b))
    assert xa.shape == xb.shape == (8192, 128) and xa.dtype == np.float32
    assert not np.array_equal(xa, xb)
    assert not np.array_equal(a.mirror.fixed, b.mirror.fixed)
    assert not np.array_equal(a.mirror.user, b.mirror.user)
    # undo each run's mirror: the same data, bit for bit
    assert np.array_equal(xa * a.mirror.fixed, xb * b.mirror.fixed)
    assert np.all(xa[:, -1] == 1.0) and np.all(a.host.user_features[:, -1] == 1.0)
    assert not np.array_equal(a.host.user_features, b.host.user_features)
    assert np.array_equal(a.host.user_features * a.mirror.user, b.host.user_features * b.mirror.user)
    assert np.array_equal(a.host.user_of_row, b.host.user_of_row)
    assert np.array_equal(a.host.labels, b.host.labels) and 0.2 < a.host.labels.mean() < 0.8
    ra, rb = (j.datasets["per-user"] for j in (a, b))
    assert ra.blocks.features.shape == rb.blocks.features.shape
    assert np.array_equal(ra.entity_counts, rb.entity_counts)
    assert _size_buckets(ra) == _size_buckets(rb)
    assert ra.ell_idx.shape == rb.ell_idx.shape
    assert np.array_equal(np.asarray(ra.blocks.labels), np.asarray(rb.blocks.labels))
    # the validation set too, with the shapes fit() will rebuild its context from
    va, vb = (j.validation_raw for j in (a, b))
    assert va.n_rows == 256
    assert va.shard_coo["globalShard"][0].shape == (256 * 128,)
    assert np.array_equal(va.labels, vb.labels)
    assert not np.array_equal(va.shard_coo["globalShard"][2], vb.shard_coo["globalShard"][2])
    assert np.array_equal(
        np.abs(va.shard_coo["globalShard"][2]), np.abs(vb.shard_coo["globalShard"][2])
    )


def test_two_seeds_do_the_same_work(two_jobs):
    """What the first check of PR 24 refused: work that moved with the seed.
    Arithmetic is symmetric under negation, so two mirrors of one data set give
    the same solver iteration counts and validation metrics EXACTLY, and
    coefficients that are each other's reflection bit for bit."""
    import jax

    a, b = two_jobs
    ra, rb = a.fit(), b.fit()
    assert a.outcome(ra).fingerprint == b.outcome(rb).fingerprint
    wa, wb = (
        np.asarray(jax.device_get(fitjob.coefficients(r[-1].model["global"]))) for r in (ra, rb)
    )
    assert np.any(wa != wb)
    assert np.array_equal(wa * a.mirror.fixed, wb * b.mirror.fixed)
    ta, tb = (correct.entity_table(r[-1].model["per-user"], 300, 8) for r in (ra, rb))
    assert np.any(ta != tb)
    assert np.array_equal(ta * a.mirror.user, tb * b.mirror.user)


def test_reference_agrees_with_the_system_fit(two_jobs, monkeypatch):
    """Sample parity (a) and the full-size checks (b) at a tiny size; on the
    CPU the program takes its jnp path, so the fusion it must report is None."""
    job = two_jobs[0]
    monkeypatch.setattr(correct, "FIXED_SAMPLE_ROWS", 4096)
    monkeypatch.setattr(correct, "SAMPLE_USERS", 150)
    monkeypatch.setattr(correct, "MIN_FUSED_ROWS", 1)
    parity = correct.sample_parity(job, required_fusion=None)
    assert parity["ok"], parity
    assert parity["glmix_objective_err"] <= correct.OBJECTIVE_TOL
    results = job.fit()
    out = job.outcome(results)
    assert out.finite and out.rejections == 0
    assert out.fingerprint == job.outcome(job.fit()).fingerprint
    full = correct.full_size(job, results)
    assert full["ok"], full
    assert full["objective_drop"][0] < 1.0


def test_parity_fails_when_the_system_is_wrong(two_jobs, monkeypatch):
    """The comparison is not vacuous: the reference's solution for OTHER labels
    is far outside the tolerance."""
    import jax.numpy as jnp

    from benchmark.reference import glmix as ref

    job = two_jobs[0]
    x = job.datasets["global"].batch.features.dense[:2048]
    y = jnp.asarray(job.host.labels[:2048])
    zeros, ones = jnp.zeros(2048, jnp.float32), jnp.ones(2048, jnp.float32)
    w = ref.solve_fixed(x, y, zeros, ones, 1.0)
    w_other = ref.solve_fixed(x, 1.0 - y, zeros, ones, 1.0)
    assert correct.rel_err(w_other, w) > 100 * correct.FIXED_COEF_TOL
    # and the stationarity certificate reads ~1 away from the minimiser
    _, g = ref.fixed_value_grad(w, x, y, zeros, ones, 1.0)
    _, g_other = ref.fixed_value_grad(w_other, x, y, zeros, ones, 1.0)
    _, g0 = ref.fixed_value_grad(jnp.zeros_like(w), x, y, zeros, ones, 1.0)
    assert float(jnp.linalg.norm(g) / jnp.linalg.norm(g0)) < 1e-4
    assert float(jnp.linalg.norm(g_other) / jnp.linalg.norm(g0)) > 0.5


def test_sample_users_are_seed_free_and_uncapped():
    q = gen.user_quotas(100_000, 4_000, 1.1)
    users = correct.sample_users(q, 256, 400)
    assert len(users) == 400 and len(set(users.tolist())) == 400
    assert np.all(q[users] <= 256)
    assert np.array_equal(users, correct.sample_users(q, 256, 400))
