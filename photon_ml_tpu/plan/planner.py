"""The execution planner: resolve the full scale configuration up front.

``resolve`` is the port's analogue of Spark's physical plan: it takes every
per-coordinate knob (layout, feature dtype, HBM budget) plus the run-level
topology (mesh axes, process count, pipeline depth, trial lanes) and decides,
before any data is read or any device memory committed, which routing every
coordinate takes — resident vs streamed, sharded vs replicated, pipelined vs
serial — together with the derived slice/shard geometry. Configurations the
runtime genuinely cannot execute raise :class:`PlanError` with the exact
message pinned in the README support-matrix ledger and
tests/test_support_matrix.py; those messages are the single source of truth
and moved here from ``estimators/game_estimator.py``, ``parallel/mesh.py``,
``game/lanes.py`` and ``cli/params.py``. The deep runtime raises that remain
in ``mesh.py``/``data.py`` are backstops for direct API callers; every
driver-level entry point consults this planner first.

The module is deliberately jax-free: a plan can be resolved (and printed via
``cli train --explain-plan``) on a host with no accelerator runtime at all.
Geometry that needs the streaming helpers imports them lazily.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Dict, Mapping, Optional, Sequence, Tuple


class PlanError(ValueError):
    """A configuration the execution planner refuses.

    Subclasses ``ValueError`` so existing callers (and the support-matrix
    pins) that catch the historical exception type keep working; the message
    is always one of the ledger-pinned refusal strings."""


# -- resolved plan types -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CoordinatePlan:
    """The routing one coordinate takes under the resolved plan."""

    name: str
    kind: str  # "fixed-effect" | "random-effect"
    layout: str
    feature_dtype: str
    residency: str  # "resident" | "streamed"
    sharding: str
    pipelined: bool
    hbm_budget_mb: Optional[int] = None
    geometry: Dict[str, object] = dataclasses.field(default_factory=dict)
    notes: Tuple[str, ...] = ()

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["notes"] = list(self.notes)
        return d


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The full resolved execution configuration for one training run."""

    coordinates: Tuple[CoordinatePlan, ...]
    mesh_axes: Optional[Dict[str, int]]
    n_processes: int
    pipeline_depth: int
    trial_lanes: int
    normalization: str
    distributed: bool

    def to_dict(self) -> Dict[str, object]:
        return {
            "coordinates": [c.to_dict() for c in self.coordinates],
            "mesh_axes": dict(self.mesh_axes) if self.mesh_axes else None,
            "n_processes": self.n_processes,
            "pipeline_depth": self.pipeline_depth,
            "trial_lanes": self.trial_lanes,
            "normalization": self.normalization,
            "distributed": self.distributed,
        }

    def pretty(self) -> str:
        mesh = (
            " ".join(f"{k}={v}" for k, v in self.mesh_axes.items())
            if self.mesh_axes
            else "none (single device)"
        )
        lines = [
            "execution plan",
            f"  topology: {self.n_processes} process(es), mesh {mesh}",
            f"  pipeline depth: {self.pipeline_depth}"
            + (" (staging/solve/eval overlap)" if self.pipeline_depth > 1 else " (serial)"),
            f"  trial lanes: {self.trial_lanes}",
            f"  normalization: {self.normalization}",
            "  coordinates:",
        ]
        for c in self.coordinates:
            head = (
                f"    {c.name}: {c.kind}, layout={c.layout}, "
                f"feature_dtype={c.feature_dtype}, {c.residency}, {c.sharding}"
            )
            if c.pipelined:
                head += ", pipelined"
            lines.append(head)
            for k in sorted(c.geometry):
                lines.append(f"      {k}: {c.geometry[k]}")
            for n in c.notes:
                lines.append(f"      note: {n}")
        return "\n".join(lines)


# -- mesh introspection (duck-typed: jax Mesh, dict, tuple or None) ----------

DATA_AXIS = "data"
MODEL_AXIS = "model"


def _mesh_axes(mesh) -> Optional[Dict[str, int]]:
    """Normalize a mesh spec to {"data": n, "model": n} (None -> no mesh).

    Accepts a ``jax.sharding.Mesh`` (its ``.shape`` mapping), a dict, or a
    ``(n_data, n_model)`` tuple — the planner itself never imports jax."""
    if mesh is None:
        return None
    if isinstance(mesh, dict):
        return {DATA_AXIS: int(mesh.get(DATA_AXIS, 1)),
                MODEL_AXIS: int(mesh.get(MODEL_AXIS, 1))}
    if isinstance(mesh, (tuple, list)):
        n_data = int(mesh[0])
        n_model = int(mesh[1]) if len(mesh) > 1 else 1
        return {DATA_AXIS: n_data, MODEL_AXIS: n_model}
    shape = getattr(mesh, "shape", None)  # jax Mesh: OrderedDict axis->size
    if shape is not None:
        return {DATA_AXIS: int(shape.get(DATA_AXIS, 1)),
                MODEL_AXIS: int(shape.get(MODEL_AXIS, 1))}
    raise TypeError(f"cannot interpret mesh spec {mesh!r}")


def _dtype_name(feature_dtype) -> str:
    if feature_dtype is None:
        return "float32"
    return str(getattr(feature_dtype, "__name__", None) or
               getattr(feature_dtype, "name", None) or feature_dtype)


# -- legality checks (the refusal ledger, in one module) ---------------------


def _check_coordinate(cc, axes, n_processes) -> Tuple[str, ...]:
    """Per-coordinate legality; returns planner notes for the legal cases."""
    notes = []
    if cc.feature_dtype is not None and cc.layout == "tiled":
        # dense/ell/coo fixed effects and RE entity blocks all accept narrow
        # feature storage (solver state stays wide); the tiled shard_map path
        # keeps its value arrays in the solve dtype
        raise PlanError(
            f"coordinate {cc.name}: feature_dtype is not supported "
            "with layout='tiled'"
        )
    if cc.hbm_budget_mb is not None and not cc.is_random_effect:
        # the streamed FE path slices on the row axis: only row-major
        # layouts stream; the Hessian-free out-of-core objective never
        # materializes variances; down-sampling is a resident-batch op
        if cc.layout not in ("auto", "dense", "ell"):
            raise PlanError(
                f"coordinate {cc.name}: hbm_budget_mb on a fixed "
                "effect requires a row-sliceable layout "
                f"(auto|dense|ell), got layout={cc.layout!r}"
            )
        if cc.config.variance_type.upper() != "NONE":
            raise PlanError(
                f"coordinate {cc.name}: variance="
                f"{cc.config.variance_type.upper()} is not supported "
                "with hbm_budget_mb on a fixed effect (out-of-core "
                "row slices never materialize the Hessian); use "
                "variance=NONE"
            )
        if cc.config.down_sampling_rate < 1.0:
            raise PlanError(
                f"coordinate {cc.name}: down_sampling_rate < 1 is not "
                "supported with hbm_budget_mb on a fixed effect"
            )
    if cc.layout == "tiled" and axes is None:
        raise PlanError(
            f"coordinate {cc.name}: layout='tiled' requires the "
            "estimator to be built with a device mesh"
        )
    if (
        axes is not None
        and not cc.is_random_effect
        and cc.layout in ("coo", "sparse")
        and cc.hbm_budget_mb is None
    ):
        # pre-empt parallel.mesh.shard_batch's runtime refusal at plan time
        raise PlanError(
            "shard_batch does not support the column-sorted COO layout (its "
            "nnz axis is column-major, not row-partitionable); for a "
            "mesh-sharded huge-d batch build layout='tiled' "
            "(parallel.sparse.tiled_sparse_batch)"
        )
    if (
        n_processes > 1
        and not cc.is_random_effect
        and cc.layout == "ell"
        and cc.hbm_budget_mb is None
    ):
        # pre-empt parallel.mesh.shard_batch's runtime refusal at plan time;
        # the STREAMED ell path is legal multi-process (host row slices never
        # cross a process boundary, so per-host ELL widths are private)
        raise PlanError(
            "multi-process ELL sharding is not supported: the ELL width "
            "is the max nnz of the LOCAL rows, so per-host shapes (and "
            "the compiled programs) would disagree; use a dense layout "
            "(d <= 4096) for multi-process runs"
        )
    if cc.hbm_budget_mb is not None and axes is not None:
        notes.append(
            "streamed under a mesh: each host streams its own shard "
            "(FE: local row slices; RE: local entity blocks) under the "
            "per-host budget"
        )
    return tuple(notes)


def check_multiprocess_mesh(n_processes: int, mesh) -> None:
    """Multi-process training without a mesh cannot place global arrays."""
    if n_processes > 1 and mesh is None:
        raise PlanError(
            "multi-process training requires a device mesh spanning all "
            "global devices (pass mesh= to GameEstimator)"
        )


def _check_topology(axes, n_processes) -> None:
    check_multiprocess_mesh(n_processes, axes)
    if n_processes > 1 and axes is not None and axes[MODEL_AXIS] > 1:
        # pre-empt parallel.mesh._reject_multiprocess_model_axis at plan time
        raise PlanError(
            "model-axis sharding across processes is not supported yet: "
            "callers pass full arrays, but each process may only contribute "
            "its own model-axis slice; multi-process runs shard the data "
            "axis only"
        )


def check_lane_composition(
    coordinate_configs: Sequence,
    n_lanes: int,
    *,
    mesh=None,
    n_processes: int = 1,
    distributed: bool = False,
    pipeline_depth: int = 1,
    partial_retrain_locked: Sequence[str] = (),
) -> None:
    """Refuse compositions the trial-lane path does not support. Every
    message is pinned verbatim in the README support matrix and
    tests/test_support_matrix.py — keep them stable."""
    if n_lanes < 1:
        raise PlanError(f"trial-lanes must be >= 1: {n_lanes}")
    if _mesh_axes(mesh) is not None:
        raise PlanError(
            "trial-lanes sweeps are single-chip: not composable with a "
            "device mesh (the lane axis already fills the chip; shard "
            "trials across hosts instead)"
        )
    if distributed or n_processes > 1:
        raise PlanError(
            "trial-lanes sweeps are single-process: not composable with "
            "multi-process training"
        )
    if pipeline_depth > 1:
        raise PlanError(
            "trial-lanes sweeps drive their own lane schedule: not "
            "composable with pipeline_depth > 1"
        )
    if partial_retrain_locked:
        raise PlanError(
            "partial retraining (locked coordinates) is not supported "
            "with trial-lanes"
        )
    for cc in coordinate_configs:
        where = f"coordinate {cc.name}"
        if cc.hbm_budget_mb is not None:
            raise PlanError(
                f"{where}: trial-lanes sweeps require HBM-resident "
                "coordinates (hbm_budget_mb streams the data; the lane "
                "axis multiplies its residency)"
            )
        if cc.config.regularization.reg_type in ("L1", "ELASTIC_NET"):
            raise PlanError(
                f"{where}: trial-lanes sweeps support L2 regularization "
                "only (the OWL-QN l1 weight is one operand of a solve, not "
                "a per-lane vector)"
            )
        if cc.config.variance_type.upper() != "NONE":
            raise PlanError(
                f"{where}: trial-lanes sweeps require variance=NONE"
            )
        if cc.config.down_sampling_rate < 1.0:
            raise PlanError(
                f"{where}: down-sampling is not supported with trial-lanes"
            )
        if cc.normalization is not None:
            raise PlanError(
                f"{where}: feature normalization is not supported with "
                "trial-lanes"
            )
        if cc.regularize_by_prior:
            raise PlanError(
                f"{where}: regularize-by-prior is not supported with "
                "trial-lanes"
            )


def check_retrain_composition(
    distributed: bool, trial_lanes: int, streamed_coordinates=()
) -> None:
    """Refuse the illegal incremental-retrain compositions up front, in one
    place (support-matrix ledger). The day chain is a local control loop: it
    loads/merges host-resident models, appends a durable ledger, and flips a
    local serving store — none of which is collective-aware; trial lanes are
    already refused with regularize-by-prior (the warm-start mechanism the
    chain is built on); streamed coordinates never materialize the
    host-resident models the per-day entity merge carries forward."""
    if distributed:
        raise PlanError(
            "incremental retrain is single-process: not composable with "
            "--distributed (the day chain's ledger, model merge and serving "
            "publish are host-local; shard the feed by day across hosts "
            "instead)"
        )
    if trial_lanes and trial_lanes > 1:
        raise PlanError(
            "incremental retrain warm-starts with regularize-by-prior: not "
            "composable with --trial-lanes (the lane solver has no per-lane "
            "prior operand)"
        )
    streamed = [str(c) for c in streamed_coordinates if c]
    if streamed:
        raise PlanError(
            "incremental retrain requires HBM-resident coordinates: not "
            "composable with hbm.budget.mb streaming (the per-day entity "
            f"merge carries host-resident models forward) — remove "
            f"hbm.budget.mb from {sorted(streamed)}"
        )


# -- checkpoint topology (resume legality across topology changes) ----------


def plan_fingerprint(plan: ExecutionPlan) -> str:
    """A stable digest of the plan facts that must MATCH for a checkpoint
    to be resumable: the coordinate set and each coordinate's layout,
    feature dtype, kind and residency, plus the normalization mode.
    Deliberately topology-INDEPENDENT — mesh axes, process count, sharding
    and pipelining are excluded, so a legal reshape (same model, different
    process count) keeps its fingerprint while a changed coordinate
    configuration (which would silently train a different model) does not."""
    facts = {
        "coordinates": [
            {
                "name": c.name,
                "kind": c.kind,
                "layout": c.layout,
                "feature_dtype": c.feature_dtype,
                "residency": c.residency,
            }
            for c in plan.coordinates
        ],
        "normalization": plan.normalization,
    }
    blob = json.dumps(facts, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]


def check_fleet_composition(
    model_names: Sequence[str],
    front_replicas: Optional[Sequence[str]] = None,
) -> None:
    """Refuse the illegal serving-fleet compositions up front, in one place
    (support-matrix ledger): the multi-model ``ModelSet`` and the replica
    front (``serving/fleet.py`` / ``serving/front.py``) both route by name,
    so ambiguous names and unroutable replica addresses are plan errors,
    not runtime surprises.

    ``model_names`` is the fleet's model list *as given* (ordered, possibly
    repeated — ``--models`` flags, ModelSet pairs); ``front_replicas`` is
    the replica address list handed to the least-loaded front."""
    seen = set()
    for name in model_names:
        if name in seen:
            raise PlanError(
                f"duplicate model name in the serving fleet: {name!r} — "
                "request-protocol model= routing needs one bulkhead per "
                "name; give each resident snapshot a distinct --models name"
            )
        seen.add(name)
    for addr in front_replicas or ():
        host, sep, port = str(addr).rpartition(":")
        if not sep or not host or not port.isdigit():
            raise PlanError(
                "the replica front routes over TCP replicas: not composable "
                f"with AF_UNIX socket paths (got {addr!r}; give each "
                "replica a host:port --listen address)"
            )


def check_checkpoint_topology(
    saved: Mapping, current: Mapping
) -> None:
    """Judge whether a checkpoint written under ``saved`` topology may be
    restored by a run under ``current`` topology. Keys (each optional — a
    missing key skips its check, so manifests that predate this protocol
    restore as before): ``n_processes``, ``mesh_axes``, ``global_rows``
    (the PADDED global row total — ``equal_host_share`` padding means the
    total itself encodes whether per-host boundaries agree), and
    ``plan_fingerprint`` (:func:`plan_fingerprint`).

    Legal: identical topology (bit-exact resume), and a data-axis process
    count change whose padded global row totals agree (the restore path
    re-concatenates row shards in process order). Everything else raises a
    ledger-pinned :class:`PlanError`."""

    def _axes(t: Mapping) -> Optional[Dict[str, int]]:
        try:
            return _mesh_axes(t.get("mesh_axes"))
        except TypeError:
            return None

    saved_model = (_axes(saved) or {}).get(MODEL_AXIS, 1)
    current_model = (_axes(current) or {}).get(MODEL_AXIS, 1)
    if saved_model != current_model:
        # model-axis shards are per-program solver state, not row blocks:
        # there is no host-side re-concatenation that reassembles them
        raise PlanError(
            "checkpoint mesh reshape across the model axis is not "
            f"supported: the checkpoint was saved with model={saved_model}, "
            f"this run uses model={current_model}; resume on a mesh with "
            "the same model axis (data-axis reshapes are the legal ones)"
        )
    saved_p, current_p = saved.get("n_processes"), current.get("n_processes")
    saved_rows = saved.get("global_rows")
    current_rows = current.get("global_rows")
    if (
        saved_p is not None
        and current_p is not None
        and int(saved_p) != int(current_p)
        and saved_rows is not None
        and current_rows is not None
        and int(saved_rows) != int(current_rows)
    ):
        raise PlanError(
            "cannot resume: the process count changed and no legal reshape "
            f"exists — the padded global row totals disagree ({saved_rows} "
            f"rows saved under {saved_p} process(es), {current_rows} under "
            f"{current_p}: per-host padding rows would land inside the "
            "data); rerun with the original process count, or a row count "
            "whose per-host padding agrees"
        )
    saved_fp = saved.get("plan_fingerprint")
    current_fp = current.get("plan_fingerprint")
    if saved_fp and current_fp and saved_fp != current_fp:
        raise PlanError(
            "resuming across a changed execution plan is not supported: "
            f"the checkpoint's plan fingerprint {saved_fp} != this run's "
            f"{current_fp} (the coordinate set, a layout, a feature dtype "
            "or a residency changed — the snapshot would silently train a "
            "different model); rerun the original configuration or start a "
            "fresh checkpoint directory"
        )


# -- geometry ----------------------------------------------------------------


def _fe_geometry(cc, axes, n_processes, dim) -> Dict[str, object]:
    """Derived slice geometry for a budgeted fixed effect (dim known)."""
    geom: Dict[str, object] = {}
    if cc.hbm_budget_mb is None:
        return geom
    budget = cc.hbm_budget_mb * (1 << 20)
    geom["budget_bytes"] = budget
    if dim is None:
        return geom
    itemsize = 2 if _dtype_name(cc.feature_dtype) == "bfloat16" else 4
    try:
        from ..game.fe_streaming import rows_per_slice

        geom["rows_per_slice"] = rows_per_slice(budget, dim * itemsize)
        geom["slice_row_bytes"] = dim * itemsize
    except Exception:  # photon: ignore[R4] - geometry is advisory; the plan
        pass  # stays valid without it (dry runs resolve with no game modules)
    if axes is not None and n_processes > 1:
        geom["hosts_streaming"] = n_processes
    return geom


def _re_geometry(cc, axes, n_processes) -> Dict[str, object]:
    geom: Dict[str, object] = {}
    if cc.hbm_budget_mb is not None:
        geom["budget_bytes"] = cc.hbm_budget_mb * (1 << 20)
        if n_processes > 1:
            geom["hosts_streaming"] = n_processes
    if axes is not None:
        geom["entity_shards"] = axes[DATA_AXIS]
    return geom


def _state_geometry(cc, axes, dim) -> Dict[str, object]:
    """Where a row-sharded fixed effect's solver state is split over the data
    axis as well (``game/problem.py`` ``splits_state``; ``auto`` is ELL past
    4,096 columns): the shard count, the columns the solve runs over and each
    chip's share of them, and each chip's L-BFGS history. Empty where the rule
    does not fire or ``dim`` is unknown."""
    if dim is None or cc.layout not in ("auto", "ell"):
        return {}
    from ..game.problem import splits_state
    from ..optimize.lbfgs import history_account, history_row_width

    solver = cc.config.solver_config()
    shards = axes[DATA_AXIS]
    if not splits_state("ell", solver.normalized_type(), dim, shards):
        return {}
    d_pad = history_row_width((int(dim),), False, shards)
    _, held = history_account(int(dim), solver.num_corrections, 4, shards)
    return {
        "state_shards": shards,
        "state_columns": d_pad,
        "state_columns_per_chip": d_pad // shards,
        "history_bytes_per_chip": held,
    }


# -- the planner -------------------------------------------------------------


def resolve(
    coordinate_configs: Sequence,
    *,
    mesh=None,
    n_processes: int = 1,
    pipeline_depth: int = 1,
    trial_lanes: int = 1,
    distributed: bool = False,
    partial_retrain_locked: Sequence[str] = (),
    normalization: str = "NONE",
    dims: Optional[Dict[str, int]] = None,
) -> ExecutionPlan:
    """Resolve the execution configuration, or raise one typed PlanError.

    ``coordinate_configs`` are ``CoordinateConfig``-shaped objects (the
    planner duck-types: name, layout, feature_dtype, hbm_budget_mb,
    is_random_effect, config.variance_type/down_sampling_rate/regularization,
    normalization, regularize_by_prior). ``mesh`` may be a jax Mesh, a
    ``{"data": n, "model": n}`` dict, an ``(n_data, n_model)`` tuple or
    None. ``dims`` optionally maps feature-shard name -> dimension so the
    plan can carry concrete slice geometry (``--explain-plan`` passes the
    index-map dims when available)."""
    axes = _mesh_axes(mesh)
    if pipeline_depth < 1:
        raise PlanError(f"pipeline depth must be >= 1: {pipeline_depth}")
    _check_topology(axes, n_processes)
    if trial_lanes > 1:
        check_lane_composition(
            coordinate_configs,
            trial_lanes,
            mesh=axes,
            n_processes=n_processes,
            distributed=distributed,
            pipeline_depth=pipeline_depth,
            partial_retrain_locked=partial_retrain_locked,
        )

    plans = []
    for cc in coordinate_configs:
        notes = _check_coordinate(cc, axes, n_processes)
        streamed = cc.hbm_budget_mb is not None
        if cc.is_random_effect:
            kind = "random-effect"
            if axes is None:
                sharding = "single-device"
            elif streamed:
                sharding = "entity-sharded (host-resident blocks)"
            else:
                sharding = "entity-sharded"
            geometry = _re_geometry(cc, axes, n_processes)
        else:
            kind = "fixed-effect"
            if axes is None:
                sharding = "single-device"
            elif streamed:
                sharding = "host-sharded rows (streamed slices)"
            elif cc.layout == "tiled" or axes[MODEL_AXIS] > 1:
                sharding = "row+model-sharded"
            else:
                sharding = "row-sharded"
            dim = (dims or {}).get(cc.feature_shard)
            geometry = _fe_geometry(cc, axes, n_processes, dim)
            state = _state_geometry(cc, axes, dim) if sharding == "row-sharded" else None
            if state:
                sharding = "row-sharded, state-sharded"
                geometry.update(state)
        residency = "streamed" if streamed else "resident"
        if streamed:
            notes = notes + (
                "streams only when the build estimate exceeds the budget; "
                "a batch that fits stays resident",
            )
        plans.append(
            CoordinatePlan(
                name=cc.name,
                kind=kind,
                layout=cc.layout,
                feature_dtype=_dtype_name(cc.feature_dtype),
                residency=residency,
                sharding=sharding,
                pipelined=pipeline_depth > 1,
                hbm_budget_mb=cc.hbm_budget_mb,
                geometry=geometry,
                notes=notes,
            )
        )

    return ExecutionPlan(
        coordinates=tuple(plans),
        mesh_axes=axes,
        n_processes=n_processes,
        pipeline_depth=pipeline_depth,
        trial_lanes=trial_lanes,
        normalization=normalization,
        distributed=bool(distributed),
    )
