"""Job ``fit_game``'s generator (item quotas seed-free and whole; two seeds:
one data set, mirrored by THREE sign vectors), and the plain reference
``reference/game.py`` against the system's three-coordinate fit, at a tiny size
on the CPU."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import correct, correct_game, data as gen, data_game
from benchmark.jobs import fit as fitjob
from benchmark.jobs import fit_game

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("global", "per-user", "per-item")


def load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def tiny():
    config = copy.deepcopy(load("configs", "glmix-user-item-1chip"))
    config["fixed_effect"].update(d=128, intercept_column=127)
    user, item = config["random_effects"]
    user.update(d_re=8, active_cap=64)
    item.update(d_re=8, active_cap=256)
    config["scale"].update(rows=16384, users=600, items=48, validation_rows=256, generation_chunk_rows=2048)
    return config, load("traffic", "fit-3coord")


def test_the_configuration_is_the_issues():
    config, traffic = load("configs", "glmix-user-item-1chip"), load("traffic", "fit-3coord")
    one_chip = load("configs", "glmix-user-1chip")
    assert config["fixed_effect"] == one_chip["fixed_effect"]  # the un-listed readers read this key
    user, item = fit_game.effects_of(config)
    assert (user["name"], user["id"], user["shard"], user["active_cap"]) == ("per-user", "userId", "userShard", 256)
    assert (item["name"], item["id"], item["shard"], item["active_cap"]) == ("per-item", "itemId", "itemShard", 1024)
    solver = {k: one_chip["random_effect"][k] for k in
              ("d_re", "optimizer", "tolerance", "max_iterations", "regularization", "reg_weight")}
    assert {k: user[k] for k in solver} == solver == {k: item[k] for k in solver}
    assert (user["n_entities"], user["zipf"], item["n_entities"], item["zipf"]) == (52428, 1.1, 4096, 0.8)
    assert config["scale"]["rows"] % (1 << 17) == 0 and config["scale"]["validation_rows"] == 8192
    assert set(config) >= {"source", "reduced", "assumed", "deployment", "notes"}
    assert traffic["update_sequence"] == list(NAMES) == traffic["coordinates"]
    assert traffic["reg_weights"] == {"global": [1.0], "per-user": 1.0, "per-item": 1.0}
    assert (traffic["job"], traffic["cd_sweeps"], traffic["trace_fits"]) == ("fit_game", 3, 3)


def test_item_quotas_are_seed_free_and_whole():
    """The configuration's bucket and passive-row table, from the quotas alone."""
    config = load("configs", "glmix-user-item-1chip")
    n = config["scale"]["rows"]
    user, item = fit_game.effects_of(config)
    q = gen.user_quotas(n, item["n_entities"], item["zipf"])
    assert q.sum() == n and q.min() >= 1 and np.all(np.diff(q) <= 0)
    assert np.array_equal(q, gen.user_quotas(n, item["n_entities"], item["zipf"]))
    at_scale = n == 1_310_720  # the numbers below are the issue's size's
    if at_scale:
        assert (q.max(), q.min()) == (59_706, 76)
        active = np.minimum(q, item["active_cap"])
        k = 2 ** np.ceil(np.log2(active)).astype(int)
        assert dict(zip(*np.unique(k, return_counts=True))) == {128: 1950, 256: 1235, 512: 528, 1024: 383}
        assert int((q > item["active_cap"]).sum()) == 161
        assert int((q - active).sum()) == 395_580  # 30.2% of the rows are passive for the items
        uq = gen.user_quotas(n, user["n_entities"], user["zipf"])
        assert int((uq > user["active_cap"]).sum()) == 390


@pytest.fixture(scope="module")
def two_jobs():
    config, traffic = tiny()
    return [fit_game.build(config, traffic, chips=1, seed=s) for s in (11, 2**31 + 12)]


def test_two_seeds_mirror_one_data_set_with_three_sign_vectors(two_jobs):
    from photon_ml_tpu.game.coordinate import _size_buckets

    a, b = two_jobs
    assert list(a.datasets) == list(NAMES) == a.coordinates
    # the first two sign vectors are job fit's; the third is the item bag's
    old = gen.draw_mirror(11, 128, 8)
    assert np.array_equal(a.mirror.fixed, old.fixed) and np.array_equal(a.mirror.effects["per-user"], old.user)
    xa, xb = (np.asarray(j.datasets["global"].batch.features.dense) for j in (a, b))
    assert xa.shape == (16384, 128) and not np.array_equal(xa, xb)
    assert np.array_equal(xa * a.mirror.fixed, xb * b.mirror.fixed)
    assert np.array_equal(a.host.labels, b.host.labels) and 0.2 < a.host.labels.mean() < 0.8
    for name in NAMES[1:]:
        sa, sb = a.mirror.effects[name], b.mirror.effects[name]
        assert set(sa.tolist()) == {-1.0, 1.0} and sa[-1] == 1.0 and not np.array_equal(sa, sb)
        fa, fb = a.host.features[name], b.host.features[name]
        assert np.all(fa[:, -1] == 1.0) and not np.array_equal(fa, fb)
        assert np.array_equal(fa * sa, fb * sb)  # undo each mirror: the same data, bit for bit
        assert np.array_equal(a.host.entity_of_row[name], b.host.entity_of_row[name])
        # the fixed quotas, shuffled
        assert np.array_equal(np.bincount(a.host.entity_of_row[name]), a.quotas[name])
        ra, rb = a.datasets[name], b.datasets[name]
        assert ra.blocks.features.shape == rb.blocks.features.shape
        assert np.array_equal(ra.entity_counts, rb.entity_counts)
        assert _size_buckets(ra) == _size_buckets(rb)
        assert len(ra.passive_rows) == int(np.maximum(a.quotas[name] - ra.blocks.features.shape[1], 0).sum()) > 0
    assert not np.array_equal(a.mirror.effects["per-user"], a.mirror.effects["per-item"])
    # the item of a row is drawn independently of its user
    users, items = a.host.entity_of_row["per-user"], a.host.entity_of_row["per-item"]
    assert abs(np.corrcoef(users, items)[0, 1]) < 0.05
    va, vb = (j.validation_raw for j in (a, b))
    assert va.n_rows == 256 and set(va.shard_coo) == {"globalShard", "userShard", "itemShard"}
    assert set(va.id_tags) == {"userId", "itemId"} and np.array_equal(va.labels, vb.labels)
    assert np.array_equal(np.abs(va.shard_coo["itemShard"][2]), np.abs(vb.shard_coo["itemShard"][2]))
    assert not np.array_equal(va.shard_coo["itemShard"][2], vb.shard_coo["itemShard"][2])


def test_two_seeds_do_the_same_work(two_jobs):
    """Two mirrors of one data set: the same solver iteration counts of all
    three coordinates and the same validation metric EXACTLY, coefficients
    that are each other's reflection bit for bit."""
    import jax

    a, b = two_jobs
    ra, rb = a.fit(), b.fit()
    fa, fb = a.outcome(ra).fingerprint, b.outcome(rb).fingerprint
    assert fa == fb and len(fa[0]) == 3
    wa, wb = (np.asarray(jax.device_get(fitjob.coefficients(r[-1].model["global"]))) for r in (ra, rb))
    assert np.any(wa != wb) and np.array_equal(wa * a.mirror.fixed, wb * b.mirror.fixed)
    for e in a.effects:
        ta, tb = (correct.entity_table(r[-1].model[e["name"]], e["n_entities"], e["d_re"]) for r in (ra, rb))
        assert np.any(ta != tb)
        assert np.array_equal(ta * a.mirror.effects[e["name"]], tb * b.mirror.effects[e["name"]])


def test_row_priority_is_the_programs():
    from photon_ml_tpu.game.data import _hash64

    assert np.array_equal(correct_game.row_priority(5000), _hash64(np.arange(5000, dtype=np.int64), 0))


@pytest.fixture()
def small_samples(monkeypatch):
    monkeypatch.setattr(correct, "FIXED_SAMPLE_ROWS", 4096)
    monkeypatch.setattr(correct, "MIN_FUSED_ROWS", 1)
    monkeypatch.setattr(correct_game, "SAMPLE_STRIDES", {"users": (2, 1), "items": (2, 0)})


def test_reference_agrees_with_the_system_fit(two_jobs, small_samples):
    """Sample parity (a) and the full-size checks (b) at a tiny size; on the
    CPU the program takes its jnp path, so the fusion it must report is None."""
    job = two_jobs[0]
    parity = correct_game.sample_parity(job, required_fusion=None)
    assert parity["ok"], parity
    for name in NAMES[1:]:  # the cap is part of what was compared, in both effects
        assert 0 < parity[name]["capped"] < parity[name]["entities"] and parity[name]["passive_rows"] > 0
    results = job.fit()
    out = job.outcome(results)
    assert out.finite and out.rejections == 0
    full = correct_game.full_size(job, results)
    assert full["ok"] and full["last_updated"] == "per-item", full
    assert full["objective_drop"][0] < 1.0


@pytest.mark.parametrize("block", NAMES)
def test_parity_fails_when_one_block_of_the_systems_model_is_perturbed(two_jobs, small_samples, monkeypatch, block):
    """The comparison is not vacuous: 5% on one block's coefficients, the
    other two as the system left them, is outside that block's limit (and the
    full-size gradient certificate, when the block is the last updated)."""
    import dataclasses

    job = two_jobs[0]
    real_coefficients, real_run_fit = fitjob.coefficients, fitjob.run_fit
    target = []

    def scaled(model):
        values = real_coefficients(model)
        return values * 1.05 if any(model is t for t in target) else values

    def run_fit(est, datasets, validation_raw, coordinates):
        results = real_run_fit(est, datasets, validation_raw, coordinates)
        if len(coordinates) == len(NAMES):  # the whole CD, not the fixed-alone fit
            model = results[-1].model[block]
            if hasattr(model, "coef_values"):  # entity_table reads the arrays themselves
                results[-1].model.models[block] = dataclasses.replace(model, coef_values=model.coef_values * 1.05)
            else:
                target.append(model)
        return results

    monkeypatch.setattr(correct_game.fitjob, "run_fit", run_fit)
    monkeypatch.setattr(correct_game.fitjob, "coefficients", scaled)
    parity = correct_game.sample_parity(job, required_fusion=None)
    assert not parity["ok"]
    key = "game_fixed_coef_err" if block == "global" else block
    err = parity[key] if block == "global" else parity[key]["coef_err"]
    limit = correct_game.GAME_FIXED_COEF_TOL if block == "global" else correct_game.ENTITY_COEF_TOL
    assert err > limit
    others = [n for n in NAMES[1:] if n != block]
    assert all(parity[n]["coef_err"] <= correct_game.ENTITY_COEF_TOL for n in others)
    assert parity["fixed_coef_err"] <= correct.FIXED_COEF_TOL  # the fixed-alone fit was left alone


def test_full_size_fails_when_the_last_block_is_not_at_its_minimiser(two_jobs):
    import dataclasses

    job = two_jobs[0]
    results = job.fit()
    model = results[-1].model["per-item"]
    results[-1].model.models["per-item"] = dataclasses.replace(model, coef_values=model.coef_values * 1.05)
    full = correct_game.full_size(job, results)
    assert not full["ok"] and full["stationarity"][0] > correct_game.STATIONARITY_TOL
