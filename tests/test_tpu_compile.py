"""Programs of the mesh path compiled for a described v5e 2x2 host (no chip is
attached and nothing runs): what the TPU compiler does with them, which the
CPU backend's tests cannot show. Keep every such compile in THIS file, behind
the ``topo`` fixture: one process at a time may load the TPU's library."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# the four-chip cell's blocks (benchmark/configs/glmix-user-4chip-mesh.json)
E, K, S, CHIPS = 104_856, 256, 32, 4
COLLECTIVE = re.compile(r"all-gather|all-to-all|collective-permute|all-reduce")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(CHIPS), ("data",))


def _sharded(mesh, shape, dtype=jnp.float32):
    spec = PartitionSpec("data", *([None] * (len(shape) - 1)))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


@pytest.mark.parametrize("chunks", [4, 8])
@pytest.mark.parametrize("bucket", [(0, 1500, 256, 32), (6000, 13107, 8, 8)])
def test_bucket_operands_are_cut_on_their_own_chip(mesh, chunks, bucket):
    """A bucket's operands from the dealt, P(data)-sharded blocks: no
    collective, the operands sharded as the blocks are, and no temporary the
    size of a chip's whole feature block (a reshape sliced, where this joins
    slices, had the compiler re-lay all 859 MB out before it cut 3 MB)."""
    from photon_ml_tpu.game.coordinate import _chunk_rows_of

    start, end, kb, sb = bucket
    arrays = (
        _sharded(mesh, (E, K, S)), _sharded(mesh, (E, K)), _sharded(mesh, (E, K)),
        _sharded(mesh, (E, K)), _sharded(mesh, (E, S)),
    )
    dims = ((kb, sb), (kb,), (kb,), (kb,), (sb,))
    compiled = _chunk_rows_of.lower(
        arrays, chunks=chunks, start=start, end=end, dims=dims, sharded=(mesh, "data")
    ).compile()
    assert not COLLECTIVE.findall(compiled.as_text())
    for out, a in zip(compiled.output_shardings, arrays):
        assert out.is_equivalent_to(a.sharding, len(a.shape))
    features, labels, *_ = jax.tree_util.tree_leaves(compiled.out_info)
    rows = chunks * (end - start)
    assert features.shape == (rows, kb, sb) and labels.shape == (rows, kb)
    chip_features_bytes = E // CHIPS * K * S * 4
    assert compiled.memory_analysis().temp_size_in_bytes < chip_features_bytes // 8


def test_bucket_results_go_back_on_their_own_chip(mesh):
    from photon_ml_tpu.game.coordinate import _stitch_chunked_results
    from photon_ml_tpu.optimize import SolverResult

    segments = [(0, 1500, 32), (1500, 8000, 32), (8000, 18000, 16), (18000, 26214, 8)]
    parts = []
    for start, end, sb in segments:
        n = CHIPS * (end - start)
        lane = _sharded(mesh, (n,), jnp.int32)
        parts.append(
            SolverResult(
                coefficients=_sharded(mesh, (n, sb)), loss=_sharded(mesh, (n,)),
                gradient=_sharded(mesh, (n, sb)), iterations=lane, reason=lane,
                loss_history=_sharded(mesh, (n, 31)),
                grad_norm_history=_sharded(mesh, (n, 31)), cg_iterations=lane,
            )
        )
    compiled = _stitch_chunked_results.lower(
        parts, S=S, chunks=CHIPS, sharded=(mesh, "data")
    ).compile()
    assert not COLLECTIVE.findall(compiled.as_text())
    out = compiled.out_info
    assert out.coefficients.shape == (E, S) and out.iterations.shape == (E,)
    want = NamedSharding(mesh, PartitionSpec("data"))
    assert compiled.output_shardings.coefficients.is_equivalent_to(want, 2)


# the four-chip cell's buckets, rows of ONE chunk: _size_buckets of its quotas
# (benchmark.data.user_quotas(N_ROWS, E, 1.1), capped at K, size-sorted, dealt)
N_ROWS = 2_621_440
SEGMENTS = {
    4: ((0, 335, 256, 32), (335, 629, 128, 32), (629, 1182, 64, 32),
        (1182, 2219, 32, 32), (2219, 4167, 16, 32), (4167, 26214, 8, 32)),
    8: ((0, 168, 256, 32), (168, 315, 128, 32), (315, 591, 64, 32),
        (591, 1110, 32, 32), (1110, 2084, 16, 32), (2084, 13107, 8, 32)),
}


@pytest.mark.parametrize("chunks", [4, 8])
def test_the_exchange_gathers_a_buckets_slots_on_its_own_chip(mesh, chunks):
    """The residual exchange over the dealt, P(data)-sharded blocks and the
    row-sharded residual: the only thing that crosses chips is the [N]
    residual, all-gathered once; every bucket's offsets come out sharded as
    the blocks are; and only the buckets' own slots are gathered (6% of the
    logical [E/4, K] extent of a chip, which is stored nowhere)."""
    from photon_ml_tpu.game.coordinate import _gather_bucket_offsets

    segments = SEGMENTS[chunks]
    assert all(end * chunks <= E for _, end, _, _ in segments)
    # the store's own per-bucket arrays (game/data.py BucketedArray.parts)
    shapes = [(chunks * (end - start), kb) for start, end, kb, _ in segments]
    active_parts = tuple(_sharded(mesh, shape, jnp.int32) for shape in shapes)
    offset_parts = tuple(_sharded(mesh, shape) for shape in shapes)
    offsets = offset_parts[0]
    residual = _sharded(mesh, (N_ROWS,))
    compiled = _gather_bucket_offsets.lower(
        active_parts, offset_parts, residual, chunks=chunks, sharded=(mesh, "data"),
    ).compile()
    crossing = [
        line.strip() for line in compiled.as_text().splitlines()
        if re.search(r"= \S+ (%s)(-start|-done)?\(" % COLLECTIVE.pattern, line)
    ]
    assert crossing and all(
        re.search(rf"= f32\[{N_ROWS}\]\S* all-gather", line) for line in crossing
    ), crossing
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.shape for o in outs] == [
        (chunks * (end - start), kb) for start, end, kb, _ in segments
    ]
    for sharding in compiled.output_shardings:
        assert sharding.is_equivalent_to(offsets.sharding, 2)
    # nothing the size of a chip's logical [E/4, K] extent, nor a narrow part
    # re-laid row-major (a [n_b, 8] part padded to 128 lanes is 16 times
    # itself; the gather runs on the transposed view, which the TPU holds
    # already)
    chip_plane_bytes = E // CHIPS * K * 4
    assert compiled.memory_analysis().temp_size_in_bytes < chip_plane_bytes // 8 + N_ROWS * 4


# -- the sparse cell's solver on ONE chip (benchmark/configs/logistic-sparse-1chip.json) --------


def history_row_copies(text, m, d):
    """The operations of a compiled solve that read a correction history
    (``[m, ...]`` of at least d columns a pair) and write a row-length array
    without reading one: copies of a pair out of the history. The recursion's
    own work reads q or r beside the pair (the dot, the axpy) or writes the
    history (the update). On PR 39's parent this names the four that ran forty
    times an iteration: two ``dynamic-slice_reduce_fusion`` ("reduced" over the
    axis of length one) and two ``multiply_reduce_fusion`` (the same, scaled)."""

    def f32_shapes(types):
        return [tuple(int(x) for x in dims.split(",") if x)
                for kind, dims in re.findall(r"\b(\w+)\[([\d,]*)\]", types) if kind == "f32"]

    def is_history(shape):
        return len(shape) >= 2 and shape[0] == m and int(np.prod(shape[1:])) >= d

    def is_row(shape):
        return bool(shape) and not is_history(shape) and d <= int(np.prod(shape)) < 2 * d

    fusions = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, shapes, in_fusion = [], {}, False
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY )?%([\w.\-]+) \((.*)\) -> (.*) \{$", line)
        if header:
            name, params, result = header.groups()
            in_fusion, shapes = name in fusions, {}
            if in_fusion:
                read = [s for p in re.findall(r"[\w.\-]+: (\w+\[[\d,]*\])", params) for s in f32_shapes(p)]
                if any(map(is_history, read)) and any(map(is_row, f32_shapes(result))) and not any(map(is_row, read)):
                    found.append(name)
            continue
        op = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\((.*)", line)
        if not op or in_fusion:
            continue
        # an unfused slice, copy or unit-axis reduce of a row, in a loop's body
        name, result, kind, rest = op.groups()
        shapes[name] = f32_shapes(result)
        operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0])
        if kind in ("dynamic-slice", "slice", "copy", "reduce") and any(map(is_row, shapes[name])) and any(
                is_history(s) for o in operands for s in shapes.get(o, [])):
            found.append(name)
    return found


@pytest.mark.parametrize("layout,limit_gb", [("ell", 8.2), ("coo", 8.3)])
def test_the_sparse_solve_fits_one_chip_beside_its_history(topo, layout, limit_gb, monkeypatch):
    """``jit__solve`` at one chip's whole share of the sparse deployment
    (2,359,296 rows x 12 slots into 54,686,453 columns, plain L-BFGS, m = 10;
    the cell runs half those rows). The TPU tiles a ``[10, d]`` history as
    (8, 128): 16 rows, 3.5 GB each, and every ``S[j]`` of the two-loop recursion
    was a strided copy of a row out of it (four 219 MB copies a step, forty an
    iteration: ``history_row_copies``). Since PR 39 a solve this wide keeps a
    pair as one row of ``[10, 427240, 128]`` (``lbfgs.history_row_width``): no
    padded row (2.19 GB each, 4.37 GB the two), a step of either loop the dot
    and the axpy with the row's slice fused into them, and 6.16 GB of
    temporaries where the tiled history held 8.78. What this also pins is the
    rest: with the ELL sums written over ``[n, k]`` intermediates the compiler
    padded each to 128 lanes (1.2 GB, three alive at once: 14.2 GB of
    temporaries, no room on a 16 GB chip); over ``[k, n]`` (the row axis minor,
    as the arrays lie on the device) nothing is padded, as over sorted COO.
    Since PR 37 the solve walks margins (what ``GLMProblem.run`` hands a two-pass
    objective's L-BFGS), the search's loop carrying scalars; the objective comes
    as an argument once for every margin step that reads it (the same buffers,
    counted each time: 7.77 / 7.88 GB as counted here, 10.39 / 10.50 over the
    tiled history). It is handed its start (f, g, z at w0) as operands. The ELL
    batch brings its local column map (2,899,743 held columns at these rows)
    and its margins gather through the Pallas table gather: 7.69 GB, the
    table's 11.6 MB more arguments than with the global gather."""
    from photon_ml_tpu.optimize import lbfgs

    n, k, d = 2_359_296, SPARSE_SLOTS, SPARSE_DIM
    compiled = _sparse_solve(topo, n, layout, monkeypatch)
    memory = compiled.memory_analysis()
    total = memory.temp_size_in_bytes + memory.argument_size_in_bytes + memory.output_size_in_bytes
    assert memory.temp_size_in_bytes < 6.5e9, memory.temp_size_in_bytes
    assert total < limit_gb * 1e9, total
    text = compiled.as_text()
    # no [n, k] temporary padded to 128 lanes: n * 128 * 4 bytes each
    assert f"[{n},{k}]{{1,0:T(8,128)}}" not in text
    # the history lies by rows, nowhere tiled over (pair, column), and nothing copies a row out of it
    rows = lbfgs.history_row_width((d,), False) // 128
    assert f"f32[10,{d}]" not in text and f"f32[10,{rows},128]{{2,1,0:T(8,128)}}" in text
    assert history_row_copies(text, 10, d) == []


SPARSE_SLOTS, SPARSE_DIM = 12, 54_686_453
# the columns the sparse configuration's rows hold (its generator's fixed
# quotas, benchmark/data_sparse.py), by rows: the cell's and the whole share's
SPARSE_HELD = {1_179_648: 1_712_040, 2_359_296: 2_899_743}


def _sparse_solve(topo, n, layout="ell", monkeypatch=None):
    """The walking ``jit__solve`` of the sparse configuration over ``n`` rows on
    one v5e, compiled, handed its start as ``GLMProblem.run`` hands it. An ELL
    batch carries its local column map, as a one-device build gives it, and
    (the backend asked while tracing is the described chip's) its margins
    gather through the Pallas table gather."""
    from jax.sharding import SingleDeviceSharding

    from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch
    from photon_ml_tpu.ops.glm import GLMObjective, margin_fns, vg_fn
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.optimize import lbfgs
    from photon_ml_tpu.optimize.common import MarginFns, as_partial

    k, d = SPARSE_SLOTS, SPARSE_DIM
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    if layout == "ell":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        features = FeatureMatrix(dim=d, idx=s((n, k), jnp.int32), val=s((n, k)),
                                 cols=s((SPARSE_HELD[n],), jnp.int32), idx_local=s((n, k), jnp.int32))
    else:
        features = FeatureMatrix(dim=d, coo_cols=s((n * k,), jnp.int32), coo_rows=s((n * k,), jnp.int32),
                                 coo_vals=s((n * k,)), coo_n_rows=n)
    batch = LabeledBatch(features=features, labels=s((n,)), offsets=s((n,)), weights=s((n,)))
    objective = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=1000.0)
    return lbfgs._solve.lower(
        as_partial(vg_fn(objective)), s((d,)), s(()), s(()), 100, 10, None, 25, False, s((d,)), s((d,)),
        False, True, MarginFns(*margin_fns(objective)), None, _start(s, d, n),
    ).compile()


def _start(s, d, n, vec=None, scalar=None):
    """A walking solve's start as operands (``common.SolveStart``): f, g at
    w0 split as w0 is, z as the rows are."""
    from photon_ml_tpu.optimize.common import SolveStart

    if vec is None:
        return SolveStart(s(()), s((d,)), s((n,)), s((), jnp.int32), s((), jnp.int32))
    return SolveStart(s((), scalar), s((d,), vec), s((n,), vec), s((), scalar, jnp.int32), s((), scalar, jnp.int32))


# ``jit__solve`` at the sparse cell's own shape as it was when it evaluated its own start,
# compiled for the v5e (``memory_analysis()``): temporaries and arguments, bytes
SPARSE_CELL_SOLVE_TEMP, SPARSE_CELL_SOLVE_ARGS = 6_142_588_928, 695_326_720


def test_the_sparse_cells_solve_is_handed_its_start_for_no_more_memory(topo, monkeypatch):
    """``jit__solve`` at ``fit-sparse``'s 1,179,648 rows: handed its start
    (``common.SolveStart``: g0 219 MB, z0 4.7 MB among its arguments) instead
    of evaluating it, it holds no more temporaries than when it took its own
    first margins and gradient (6.14 GB), and one gather and one
    scatter-add of the features, the loop's. That gather is the table's
    (``w[cols]``, its 1,712,040 held columns), beside the Pallas kernel
    that reads the slots' words from it in VMEM: the table's 6.85 MB come in
    beside the start, and ``idx_local`` takes the place of ``idx`` in the
    margins' copy of the batch (769.9 MB of arguments against 763.1 with the
    global gather)."""
    n, k, d = 1_179_648, SPARSE_SLOTS, SPARSE_DIM
    held = SPARSE_HELD[n]
    compiled = _sparse_solve(topo, n, monkeypatch=monkeypatch)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= SPARSE_CELL_SOLVE_TEMP, memory.temp_size_in_bytes
    # the start's g0 and z0 and the map's table come in; the first margins' copy of the batch no longer does
    assert (d + n) * 4 < memory.argument_size_in_bytes < SPARSE_CELL_SOLVE_ARGS + (d + n + held) * 4
    text = compiled.as_text()
    # one gather of the table, one kernel over the [k, n] slots and one
    # scatter-add into [d], in the loop's body (a solve that evaluated its
    # start had a second of each); XLA gathers no slot
    assert len(re.findall(rf"= f32\[{held}\]\S* gather\(", text)) == 1
    assert not re.findall(rf"= f32\[{k},{n}\]\S* gather\(", text)
    assert text.count("tpu_custom_call") == 1 and "ell_table_gather" in text
    assert len(re.findall(rf"= f32\[{d}\]\S* scatter\(", text)) == 1


@pytest.mark.parametrize("table_len,rows,slots", [
    (1_712_040, 8192, 12), (2_899_743, 2_359_296, 12), ((64 << 20) // 4, 2048, 1), (200_000, 8192, 64)])
def test_the_table_gather_fits_vmem_and_smem_at_its_gates_edges(topo, table_len, rows, slots):
    """The Pallas table gather (``ops/pallas_gather.py``) compiles for the v5e
    at the sparse cell's table (its validation rows and the whole share's), at
    ``MAX_TABLE_BYTES`` of table in VMEM and at ``MAX_SLOTS`` slots a row, whose
    indices fill half of the 1 MiB of SMEM a step (128 slots ran out of it)."""
    from jax.sharding import SingleDeviceSharding

    from photon_ml_tpu.ops import pallas_gather

    assert table_len * 4 <= pallas_gather.MAX_TABLE_BYTES and slots <= pallas_gather.MAX_SLOTS
    one = SingleDeviceSharding(topo.devices[0])
    compiled = pallas_gather.gather.lower(
        jax.ShapeDtypeStruct((table_len,), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((rows, slots), jnp.int32, sharding=one)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# -- the GLMix-over-sparse-ids cell on ONE chip (benchmark/configs/glmix-sparse-user-1chip.json) --

# its store's buckets (K_b, entities, S_b), from the user field's fixed quotas
GLMIX_SPARSE_BUCKETS = [(256, 556, 439), (128, 480, 256), (64, 884, 256), (32, 1589, 128),
                        (16, 2747, 64), (8, 271_921, 32)]
GLMIX_SPARSE = dict(rows=1_179_648, users=278_177, slots=5, s_max=439)


@pytest.mark.parametrize("kb,entities,sb", GLMIX_SPARSE_BUCKETS)
def test_every_bucket_of_the_ragged_store_solves_on_one_chip(topo, kb, entities, sb):
    """The packed solver at each of the cell's stored bucket shapes: it
    compiles for the v5e, its arguments are the bucket's own arrays unpadded
    (the TPU lays ``[E_b, K_b, S_b]`` out entity-minor), and the widest bucket
    (556 x 256 x 439) holds under 3 GB with its L-BFGS history: the plane all
    six would have been cut from is 278,177 x 256 x 439 x 4 = 125 GB."""
    from jax.sharding import SingleDeviceSharding

    from photon_ml_tpu.game.coordinate import _train_blocks_packed

    one = SingleDeviceSharding(topo.devices[0])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)  # noqa: E731
    compiled = _train_blocks_packed.lower(
        s(entities, kb, sb), s(entities, kb), s(entities, kb), s(entities, kb),
        s(entities, sb), s(entities, sb), s(entities, sb),
        task="logistic_regression", l2=1.0, l1=0.0, optimizer_type="LBFGS", tolerance=1e-6,
        max_iterations=30, num_corrections=10, max_cg_iterations=20, max_improvement_failures=5,
    ).compile()
    memory = compiled.memory_analysis()
    cells = entities * kb * sb * 4
    assert memory.argument_size_in_bytes < 1.05 * (cells + 3 * entities * (kb + sb) * 4) + (1 << 20)
    total = memory.temp_size_in_bytes + memory.argument_size_in_bytes + memory.output_size_in_bytes
    assert total < 3e9, total


def test_the_slot_score_holds_no_row_by_subspace_array_on_one_chip(topo):
    """The random-effect score of the cell's 1,179,648 rows (5 slots each)
    under 278,177 users' subspaces of up to 439 columns: positions by
    bisection and the score by a gather at (entity, position) pairs, neither
    holding an [n, 439] array (2.07 GB each; the densified form held two)."""
    from jax.sharding import SingleDeviceSharding

    from photon_ml_tpu.models.game import ell_slot_positions, score_entity_ell_at

    n, e, f, s_max = (GLMIX_SPARSE[k] for k in ("rows", "users", "slots", "s_max"))
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    positions = ell_slot_positions.lower(
        s((e, s_max), jnp.int32), s((n,), jnp.int32), s((n, f), jnp.int32)
    ).compile()
    score = score_entity_ell_at.lower(
        s((e, s_max)), s((n,), jnp.int32), s((n, f), jnp.int32), s((n, f), jnp.bool_), s((n, f))
    ).compile()
    row_by_subspace = n * s_max * 4
    for compiled in (positions, score):
        assert compiled.memory_analysis().temp_size_in_bytes < row_by_subspace // 8
        assert f"[{n},{s_max}]" not in compiled.as_text()
        # nor an [n, F] intermediate padded to 128 lanes (604 MB each)
        assert f"[{n},{f}]{{1,0:T(8,128)" not in compiled.as_text()


def test_the_exchange_re_lays_no_narrow_bucket_on_one_chip(topo):
    """The residual exchange at the cell's stored bucket shapes: the K = 8
    bucket's 271,921 x 8 rows re-laid row-major would pad to 128 lanes (139 MB
    a copy, 0.45 GB of temporaries in all); gathered on the transposed view
    the program holds no copy of any part."""
    from jax.sharding import SingleDeviceSharding

    from photon_ml_tpu.game.coordinate import _gather_bucket_offsets

    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    shapes = [(entities, kb) for kb, entities, _ in GLMIX_SPARSE_BUCKETS]
    compiled = _gather_bucket_offsets.lower(
        tuple(s(shape, jnp.int32) for shape in shapes), tuple(s(shape) for shape in shapes),
        s((GLMIX_SPARSE["rows"],)), chunks=1, sharded=None,
    ).compile()
    narrow = max(entities * 128 * 4 for kb, entities, _ in GLMIX_SPARSE_BUCKETS if kb < 128)
    assert compiled.memory_analysis().temp_size_in_bytes < narrow // 8


# -- the Criteo cell on one 2x2 host (benchmark/configs/logistic-criteo-4chip.json) -------------


def test_the_criteo_solve_splits_its_state_over_the_four_chips(topo, mesh):
    """``jit__solve`` at the cell's shapes (2^21 rows x 40 slots into
    187,767,413 columns, plain L-BFGS, m = 10), rows and state split over the
    2x2 (``game/problem.py`` ``state_sharding``). Its history alone is 15.02 GB
    whole; here each chip holds ``[10, 366734, 128]``, its quarter, and 6.19 GB
    of temporaries in all. Per pass the vector is all-gathered once for the
    gather and the scatter-add's local [d_pad] sum leaves the pass through the
    TPU's reduce-scatter fusion (``all-reduce-scatter``; a ``[d]`` psum_scatter
    would be an all-reduce of all d and a slice): no d-length all-reduce."""
    from photon_ml_tpu.ops.features import FeatureMatrix, LabeledBatch
    from photon_ml_tpu.ops.glm import GLMObjective, margin_fns, vg_fn
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.optimize import lbfgs
    from photon_ml_tpu.optimize.common import MarginFns, as_partial

    n, k, d = 1 << 21, 40, 187_767_413
    d_pad = lbfgs.history_row_width((d,), False, CHIPS)
    vec, rep = NamedSharding(mesh, PartitionSpec("data")), NamedSharding(mesh, PartitionSpec())
    s = lambda shape, sh, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    batch = LabeledBatch(features=FeatureMatrix(dim=d_pad, idx=s((n, k), rows, jnp.int32), val=s((n, k), rows)),
                         labels=s((n,), vec), offsets=s((n,), vec), weights=s((n,), vec))
    objective = GLMObjective(loss=get_loss("logistic_regression"), batch=batch, l2=1783.0, state_sharding=vec)
    compiled = lbfgs._solve.lower(
        as_partial(vg_fn(objective)), s((d_pad,), vec), s((), rep), s((), rep), 100, 10, None, 25, False,
        s((d_pad,), vec), s((d_pad,), vec), False, True, MarginFns(*margin_fns(objective)), vec,
        _start(s, d_pad, n, vec, rep),
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 6.5e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert f"f32[10,{d_pad // CHIPS // 128},128]" in text and f"f32[10,{d_pad // 128},128]" not in text
    gathers = re.findall(r"= f32\[4,1,(\d+)\]\S* all-gather\(", text)
    assert gathers == [str(d_pad // CHIPS)]  # the loop's pass: the solve is handed its start
    assert len(re.findall(r"calls=%all-reduce-scatter", text)) == 1
    assert not re.search(rf"f32\[{d_pad}\]\S* all-reduce\(", text)
    assert not re.search(r"all-gather\(%\S+\), channel_id=\d+, replica_groups=\S+, dimensions=\{1\}", text)
