"""The local column map of a wide one-device ELL batch (``ops/features.py``):
its margins gather from ``w[cols]``, the table of the columns its rows hold,
and are the whole vector's gather bit for bit; which batches carry one; what
the ``fe.solve`` / ``fe.score`` spans say of it."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.estimators.game_estimator import CoordinateConfig, GameEstimator
from photon_ml_tpu.game.problem import GLMOptimizationConfig, GLMProblem
from photon_ml_tpu.io.data import RawDataset
from photon_ml_tpu.ops.features import LOCAL_MAP_MIN_DIM, FeatureMatrix, pad_batch
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig, OptimizerType
from photon_ml_tpu.utils.events import EventListener

SHARD = "globalShard"
WIDE = LOCAL_MAP_MIN_DIM + 37


def _raw(n, d, seed=5, held=None, max_slots=6):
    """Rows of 1 to ``max_slots`` entries (so some slots are padding) over
    ``held`` columns of a ``d``-wide shard (all of them when None)."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, max_slots + 1, n)
    r = np.repeat(np.arange(n), counts)
    pool = np.arange(d) if held is None else rng.choice(d, held, replace=False)
    c = pool[rng.integers(0, len(pool), len(r))]
    return RawDataset(n_rows=n, labels=(rng.random(n) < 0.3).astype(np.float64), offsets=np.zeros(n),
                      weights=np.ones(n), shard_coo={SHARD: (r, c, rng.standard_normal(len(r)))},
                      shard_dims={SHARD: d}, id_tags={})


def _without_map(f: FeatureMatrix) -> FeatureMatrix:
    """The same matrix as the parent built it: the global gather."""
    return dataclasses.replace(f, cols=None, idx_local=None)


def _bits(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


_matvec = jax.jit(lambda f, w: f.matvec(w))


# -- the margins, bit for bit ---------------------------------------------------------------


@pytest.mark.parametrize("case", ["random", "padded-slots", "pad_batch", "validation"])
def test_margins_from_the_local_table_are_the_whole_vectors_bit_for_bit(case):
    n = 8192 if case == "validation" else 1000
    batch = _raw(n, WIDE, seed=len(case), held=3000).to_batch(SHARD, dtype=jnp.float32)
    f = batch.features
    assert f.gather == "local"
    # the held columns, padding's column 0 among them
    idx = np.asarray(f.idx)
    assert np.array_equal(np.asarray(f.cols), np.unique(idx))
    assert np.array_equal(np.asarray(f.cols)[np.asarray(f.idx_local)], idx)
    if case == "padded-slots":
        assert (np.asarray(f.val) == 0).any()
    if case == "pad_batch":
        f = pad_batch(batch, 1536).features
        assert f.idx_local.shape[0] == 1536
        assert not np.asarray(f.idx_local)[n:].any() and not np.asarray(f.val)[n:].any()
    for seed in range(3):
        w = jax.random.normal(jax.random.PRNGKey(seed), (WIDE,), jnp.float32)
        local = _matvec(f, w)
        assert _bits(local) == _bits(_matvec(_without_map(f), w))
        # and eagerly, as the fixed effect's score runs it
        assert _bits(f.matvec(w)) == _bits(_without_map(f).matvec(w))


def test_every_pass_but_the_margins_reads_the_global_indices():
    """The scatter-adds and the dense view take no notice of the map."""
    f = _raw(700, WIDE, held=900).to_batch(SHARD, dtype=jnp.float32).features
    g = _without_map(f)
    c = jax.random.normal(jax.random.PRNGKey(3), (700,), jnp.float32)
    for op in ("rmatvec", "sq_rmatvec"):
        assert _bits(getattr(f, op)(c)) == _bits(getattr(g, op)(c))
    lanes = jax.random.normal(jax.random.PRNGKey(4), (WIDE, 2), jnp.float32)
    assert _bits(f.matmat(lanes)) == _bits(g.matmat(lanes))


def test_a_map_needs_both_halves_and_an_ell_matrix():
    idx = jnp.zeros((4, 2), jnp.int32)
    with pytest.raises(ValueError, match="both"):
        FeatureMatrix(dim=8, idx=idx, val=jnp.zeros((4, 2)), cols=jnp.zeros(1, jnp.int32))
    with pytest.raises(ValueError, match="ELL"):
        FeatureMatrix(dim=8, dense=jnp.zeros((4, 8)), cols=jnp.zeros(1, jnp.int32), idx_local=idx)


# -- the Pallas table gather (interpreted here; compiled for the v5e in test_tpu_compile.py) --


@pytest.mark.parametrize("k,n,table_len", [(1, 300, 5000), (3, 1100, 20_000), (2, 1024, 1)])
def test_the_table_gather_reads_every_slots_word(k, n, table_len):
    """Every slot's word, whatever the rows (a tile is 1,024 of them: 300 and
    1,100 leave a ragged last tile) and however few words the table has."""
    from photon_ml_tpu.ops import pallas_gather

    rng = np.random.default_rng(k)
    table = rng.standard_normal(table_len).astype(np.float32)
    idx = rng.integers(0, table_len, (n, k)).astype(np.int32)
    words = pallas_gather.gather(jnp.asarray(table), jnp.asarray(idx), interpret=True)
    assert words.shape == (k, n) and np.array_equal(np.asarray(words), table[idx.T])


def test_the_margins_through_the_kernel_are_the_whole_vectors_bit_for_bit(monkeypatch):
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    f = _raw(700, WIDE, seed=21, held=800, max_slots=2).to_batch(SHARD, dtype=jnp.float32).features
    assert f.idx.shape[1] == 2
    for seed in range(2):
        w = jax.random.normal(jax.random.PRNGKey(seed), (WIDE,), jnp.float32)
        through = jax.jit(lambda f, w: f.matvec(w))(f, w)
        assert _bits(through) == _bits(_matvec(_without_map(f), w))


@pytest.mark.parametrize("mode,backend,table_len,slots,route", [
    ("auto", "tpu", 1_712_040, 12, "compiled"), ("auto", "cpu", 1_712_040, 12, None),
    ("off", "tpu", 1_712_040, 12, None), ("interpret", "cpu", 1_712_040, 12, "interpret"),
    ("auto", "tpu", (64 << 20) // 4, 64, "compiled"), ("auto", "tpu", (64 << 20) // 4 + 1, 12, None),
    ("interpret", "cpu", (64 << 20) // 4 + 1, 12, None), ("auto", "tpu", 1_712_040, 65, None)])
def test_the_kernel_runs_where_pallas_may_and_its_operands_fit(monkeypatch, mode, backend, table_len, slots, route):
    """``PHOTON_PALLAS`` as for the GLM kernels; a table past
    ``MAX_TABLE_BYTES`` (64 MiB of VMEM: 16.7M columns), or rows of more than
    ``MAX_SLOTS`` (a step's indices in SMEM), take XLA's take wherever."""
    from photon_ml_tpu.ops import pallas_gather

    assert (pallas_gather.MAX_TABLE_BYTES, pallas_gather.MAX_SLOTS) == (64 << 20, 64)
    monkeypatch.setenv("PHOTON_PALLAS", mode)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_gather.route(table_len, slots, jnp.float32) == route


@pytest.mark.parametrize("mode,backend", [("auto", "tpu"), ("interpret", "cpu")])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.bfloat16])
def test_a_table_of_any_dtype_but_float32_takes_xlas_take(monkeypatch, mode, backend, dtype):
    """Mosaic has no 64-bit vectors: a float64 solve's table (``jax_enable_x64``
    with no dtype asked for) takes XLA's take, as ``pallas_glm.eligible``
    refuses every dtype but its own; bfloat16 is not one the kernel was
    measured in."""
    from photon_ml_tpu.ops import pallas_gather

    monkeypatch.setenv("PHOTON_PALLAS", mode)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_gather.route(1_712_040, 12, dtype) is None


def test_a_float64_batchs_margins_gather_through_xla_from_its_table(monkeypatch):
    """Where the kernel may run (interpreted here), a float64 batch's map still
    serves its margins, through XLA's take from the table: no Pallas call in
    the program, and the whole vector's gather bit for bit."""
    monkeypatch.setenv("PHOTON_PALLAS", "interpret")
    f = _raw(300, WIDE, seed=23, held=400, max_slots=3).to_batch(SHARD, dtype=jnp.float64).features
    assert f.gather == "local" and f.val.dtype == jnp.float64
    w = jax.random.normal(jax.random.PRNGKey(5), (WIDE,), jnp.float64)
    margins = jax.jit(lambda f, w: f.matvec(w))
    assert "pallas_call" not in str(jax.make_jaxpr(margins)(f, w))
    assert _bits(margins(f, w)) == _bits(_matvec(_without_map(f), w))


# -- a wide solve, with and without the map ------------------------------------------------


def _config(max_iterations=30):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.LBFGS, tolerance=1e-7, max_iterations=max_iterations),
        regularization=RegularizationContext("L2"), reg_weight=0.3)


def test_a_wide_lbfgs_solve_is_the_same_with_and_without_the_map():
    """``GLMProblem.run`` over a batch wider than ``LOCAL_MAP_MIN_DIM`` whose
    rows hold 2,000 columns: the same iterations, passes and coefficients bit
    for bit, whichever vector the margins gather from."""
    batch = _raw(1200, WIDE, seed=9, held=2000).to_batch(SHARD, dtype=jnp.float32)
    assert batch.features.gather_columns <= 2000 + 1
    parent = dataclasses.replace(batch, features=_without_map(batch.features))
    results = [GLMProblem(task="logistic_regression", config=_config()).run(b, coordinate="global")
               for b in (batch, parent)]
    (m_local, r_local), (m_global, r_global) = results
    assert int(r_local.iterations) == int(r_global.iterations) > 3
    assert int(r_local.matvecs) == int(r_global.matvecs) and int(r_local.rmatvecs) == int(r_global.rmatvecs)
    assert _bits(m_local.coefficients.means) == _bits(m_global.coefficients.means)
    assert _bits(r_local.loss_history) == _bits(r_global.loss_history)


# -- which batches carry one ------------------------------------------------------------------


@pytest.mark.parametrize("case", ["wide-ell", "narrow-ell", "dense", "tiled", "sharded", "streamed"])
def test_only_a_wide_ell_batch_for_one_device_carries_a_map(case):
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch

    d = LOCAL_MAP_MIN_DIM - 1 if case == "narrow-ell" else (64 if case == "dense" else WIDE)
    raw = _raw(512, d, held=None if case == "dense" else 400)
    if case == "streamed":
        from photon_ml_tpu.game.data import build_fixed_effect_dataset
        from photon_ml_tpu.game.fe_streaming import score_streamed_fe

        ds = build_fixed_effect_dataset(raw, "global", SHARD, dtype=jnp.float32, layout="ell",
                                        hbm_budget_bytes=4096)
        assert ds.streamed and ds.batch is None
        seen = []
        matvec = FeatureMatrix.matvec

        def spy(self, w):
            seen.append(self.gather)
            return matvec(self, w)

        try:
            FeatureMatrix.matvec = spy
            score_streamed_fe(ds.host_batch, jnp.zeros(d, jnp.float32), 4096, jnp.float32)
        finally:
            FeatureMatrix.matvec = matvec
        assert seen and set(seen) == {"global"}
        return
    if case in ("tiled", "sharded"):
        mesh = make_mesh(2, 2) if case == "tiled" else make_mesh(4)
        if case == "tiled":
            f = raw.to_batch(SHARD, dtype=jnp.float32, layout="tiled", mesh=mesh).features
        else:
            f = shard_batch(raw.to_batch(SHARD, dtype=jnp.float32), mesh).features
            assert raw.to_batch(SHARD, dtype=jnp.float32, mesh=mesh).features.gather == "global"
        assert getattr(f, "cols", None) is None and getattr(f, "gather", "global") == "global"
        return
    layout = "dense" if case == "dense" else "ell"
    f = raw.to_batch(SHARD, dtype=jnp.float32, layout=layout).features
    assert f.layout == layout
    assert (f.gather, f.gather_columns) == (("local", f.cols.shape[0]) if case == "wide-ell" else ("global", d))
    if case == "wide-ell":
        assert f.cols.shape[0] <= 401 and f.idx_local.shape == f.idx.shape


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "mesh"])
def test_the_hbm_budget_prices_the_map_where_the_batch_would_hold_one(on_mesh):
    """A fixed effect stays resident only if its batch fits the budget: a
    one-device wide ELL batch holds ``idx_local`` and ``cols`` beside ``idx``
    and ``val``, so a budget between the estimate without them and with them
    streams it; over a mesh the batch holds no map and stays resident."""
    from photon_ml_tpu.game.data import build_fixed_effect_dataset
    from photon_ml_tpu.game.fe_streaming import estimate_fe_batch_bytes
    from photon_ml_tpu.parallel.mesh import make_mesh

    raw = _raw(512, WIDE, seed=29, held=400)
    resident = raw.to_batch(SHARD, dtype=jnp.float32)
    n, k = resident.features.idx.shape
    without, with_map = (estimate_fe_batch_bytes(n, WIDE, "ell", ell_width=k, one_device=one)
                         for one in (False, True))
    assert with_map == without + (n * k + min(n * k, WIDE)) * 4
    # what the one-device batch holds lies within the estimate that counts its map
    assert without < sum(a.nbytes for a in jax.tree_util.tree_leaves(resident)) <= with_map
    mesh = make_mesh(4) if on_mesh else None
    ds = build_fixed_effect_dataset(raw, "global", SHARD, dtype=jnp.float32, layout="ell", mesh=mesh,
                                    hbm_budget_bytes=(without + with_map) // 2)
    assert ds.streamed is not on_mesh
    if on_mesh:
        assert ds.batch.features.gather == "global"


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "mesh"])
def test_a_validation_batch_has_a_map_only_where_its_models_are_on_one_device(on_mesh):
    """A mesh's models are placed on the mesh, so its validation batch is
    scored on the mesh too: built without a map (a Mosaic kernel has no
    partitioning rule), and its context is not the one-device estimator's."""
    from types import SimpleNamespace

    from photon_ml_tpu.parallel.mesh import make_mesh

    val_raw = _raw(256, WIDE, seed=17, held=300)
    est = GameEstimator(
        task="logistic_regression",
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(5), layout="ell")],
        n_cd_iterations=1, dtype=jnp.float32, mesh=make_mesh(4) if on_mesh else None)
    if on_mesh:  # a one-device estimator's context over the same data set is not handed to it
        GameEstimator(task="logistic_regression", coordinate_configs=est.coordinate_configs,
                      n_cd_iterations=1, dtype=jnp.float32)._validation_context(val_raw)
    _, score_fns = est._validation_context(val_raw)
    seen = []
    matvec = FeatureMatrix.matvec

    def spy(self, w):
        seen.append(self.gather)
        return matvec(self, w)

    model = SimpleNamespace(model=SimpleNamespace(coefficients=SimpleNamespace(means=jnp.zeros(WIDE, jnp.float32))))
    try:
        FeatureMatrix.matvec = spy
        score_fns["global"](model)
    finally:
        FeatureMatrix.matvec = matvec
    assert seen == ["global" if on_mesh else "local"]


# -- what the spans say -----------------------------------------------------------------------


class _Spans(EventListener):
    def __init__(self):
        self.spans = []

    def handle(self, event) -> None:
        if isinstance(event, obs.SpanEvent):
            self.spans.append(event.span)


@pytest.mark.parametrize("case", ["wide-ell", "narrow-ell", "dense"])
def test_the_solve_and_score_spans_say_what_the_margins_gather_from(case):
    d = {"wide-ell": WIDE, "narrow-ell": 5003, "dense": 64}[case]
    raw = _raw(600, d, seed=13, held=None if case == "dense" else 500)
    est = GameEstimator(
        task="logistic_regression",
        coordinate_configs=[CoordinateConfig(name="global", feature_shard=SHARD, config=_config(5),
                                             layout="dense" if case == "dense" else "ell")],
        n_cd_iterations=1, dtype=jnp.float32)
    datasets = est.prepare_datasets(raw)
    run, sink = obs.RunTelemetry(), _Spans()
    run.register_listener(sink)
    with obs.use_run(run):
        est.fit(None, datasets=datasets)
    solve, = [s for s in sink.spans if s.name == "fe.solve"]
    scores = [s for s in sink.spans if s.name == "fe.score"]
    assert scores
    held = datasets["global"].batch.features.gather_columns
    want = ("local", held) if case == "wide-ell" else ("global", d)
    if case == "wide-ell":
        assert held <= 501
    for span in [solve, *scores]:
        assert (span.attrs["gather"], span.attrs["gather_columns"]) == want
