"""Exhaustive combination sweep over the execution planner.

Every point in (kind x layout x dtype x mesh x processes x streaming x
pipelining x variance x regularization x lanes) must either resolve to a
typed ExecutionPlan OR raise exactly one PlanError whose message carries a
ledger-pinned fragment (refusals.json, the machine-readable mirror of the
README support matrix) — never a deep-stack NotImplementedError out of
parallel/mesh.py or game/data.py, and never a second error flavor for the
same combination.

Deliberately imports no jax: the planner must stay resolvable with no
accelerator runtime (cli train --explain-plan dry-runs on any host).
"""

from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path
from typing import Optional

import pytest

from photon_ml_tpu.plan import (
    ExecutionPlan,
    PlanError,
    check_multiprocess_mesh,
    resolve,
)

ROOT = Path(__file__).resolve().parents[1]

FRAGMENTS = [
    e["fragment"]
    for e in json.loads((ROOT / "refusals.json").read_text())["refusals"]
]


@dataclasses.dataclass
class _Reg:
    reg_type: str = "L2"


@dataclasses.dataclass
class _Cfg:
    variance_type: str = "NONE"
    down_sampling_rate: float = 1.0
    regularization: _Reg = dataclasses.field(default_factory=_Reg)


@dataclasses.dataclass
class _CC:
    """CoordinateConfig-shaped duck object — exactly the attribute surface
    the planner documents; using it (instead of the real CoordinateConfig)
    proves the planner needs no estimator/jax import."""

    name: str = "c0"
    feature_shard: str = "global"
    layout: str = "auto"
    feature_dtype: Optional[str] = None
    hbm_budget_mb: Optional[int] = None
    is_random_effect: bool = False
    config: _Cfg = dataclasses.field(default_factory=_Cfg)
    normalization: Optional[object] = None
    regularize_by_prior: bool = False


KINDS = (False, True)  # is_random_effect
LAYOUTS = ("auto", "dense", "ell", "sparse", "tiled")
DTYPES = (None, "bfloat16")
MESHES = (None, {"data": 8, "model": 1}, {"data": 4, "model": 2})
PROCESSES = (1, 2)
BUDGETS = (None, 0, 64)
DEPTHS = (1, 2)
VARIANCES = ("NONE", "SIMPLE")
REGS = ("L2", "L1")
LANES = (1, 4)


def _combos():
    return itertools.product(
        KINDS, LAYOUTS, DTYPES, MESHES, PROCESSES, BUDGETS, DEPTHS,
        VARIANCES, REGS, LANES,
    )


def test_every_combination_plans_or_refuses_with_ledger_message():
    n_plans = n_refusals = 0
    for (is_re, layout, dtype, mesh, n_proc, budget, depth, variance,
         reg, lanes) in _combos():
        cc = _CC(
            layout=layout,
            feature_dtype=dtype,
            hbm_budget_mb=budget,
            is_random_effect=is_re,
            config=_Cfg(variance_type=variance, regularization=_Reg(reg)),
        )
        label = (
            f"kind={'re' if is_re else 'fe'} layout={layout} dtype={dtype} "
            f"mesh={mesh} procs={n_proc} budget={budget} depth={depth} "
            f"variance={variance} reg={reg} lanes={lanes}"
        )
        try:
            # any exception other than PlanError (a NotImplementedError
            # leaking up from a deep layer, a TypeError from a missing
            # attribute) propagates and fails the sweep outright
            plan = resolve(
                [cc],
                mesh=mesh,
                n_processes=n_proc,
                pipeline_depth=depth,
                trial_lanes=lanes,
                distributed=n_proc > 1,
            )
        except PlanError as e:
            n_refusals += 1
            assert isinstance(e, ValueError), label
            assert any(f in str(e) for f in FRAGMENTS), (
                f"refusal message not pinned in refusals.json ({label}): {e}"
            )
            continue
        n_plans += 1
        assert isinstance(plan, ExecutionPlan), label
        (cp,) = plan.coordinates
        assert cp.residency == ("streamed" if budget is not None else "resident"), label
        assert cp.pipelined == (depth > 1), label
        assert plan.n_processes == n_proc and plan.pipeline_depth == depth, label
        assert plan.trial_lanes == lanes, label
        if mesh is None:
            assert cp.sharding == "single-device", label
        elif budget is not None:
            assert cp.sharding == (
                "entity-sharded (host-resident blocks)"
                if is_re
                else "host-sharded rows (streamed slices)"
            ), label
        # the plan document must be JSON-serializable and printable as-is
        json.dumps(plan.to_dict())
        assert plan.pretty().startswith("execution plan"), label
    # the sweep covers both outcomes at scale, not a vacuous pass
    assert n_plans > 100 and n_refusals > 100, (n_plans, n_refusals)


def test_planner_preempts_deep_stack_runtime_refusals():
    """The combinations parallel/mesh.py would reject mid-build (with a
    NotImplementedError deep inside shard_batch/shard_coefficients) must be
    refused by the planner up front, with the same ledger message."""
    mesh = {"data": 8, "model": 1}
    for layout in ("ell", "sparse"):
        with pytest.raises(PlanError, match="multi-process ELL sharding"):
            resolve([_CC(layout=layout)], mesh=mesh, n_processes=2, distributed=True)
    with pytest.raises(
        PlanError, match="model-axis sharding across processes"
    ):
        resolve(
            [_CC()], mesh={"data": 4, "model": 2}, n_processes=2,
            distributed=True,
        )
    with pytest.raises(
        PlanError, match="multi-process training requires a device mesh"
    ):
        check_multiprocess_mesh(2, None)


@pytest.mark.parametrize("mesh", [{"data": 8, "model": 1}, {"data": 4, "model": 2}])
def test_sparse_on_a_mesh_plans_as_ell_does(mesh):
    """``sparse`` is the ELL layout by another name: on a mesh it plans, with
    the routing ``ell`` gets."""
    ell, sparse = (resolve([_CC(layout=layout)], mesh=mesh, n_processes=1) for layout in ("ell", "sparse"))
    (want,), (got,) = ell.coordinates, sparse.coordinates
    assert got.layout == "sparse" and dataclasses.replace(got, layout="ell") == want


def test_newly_legal_compositions_resolve():
    """The three compositions this planner legalized (formerly ledger
    refusals): streamed FE x mesh/multi-process, streamed RE x mesh
    sharding, pipeline depth >= 2 x distributed."""
    mesh = {"data": 8, "model": 1}
    plan = resolve(
        [
            _CC(name="global", hbm_budget_mb=0),
            _CC(name="per-user", hbm_budget_mb=0, is_random_effect=True),
        ],
        mesh=mesh,
        n_processes=2,
        pipeline_depth=2,
        distributed=True,
    )
    fe, re_ = plan.coordinates
    assert fe.residency == re_.residency == "streamed"
    assert fe.sharding == "host-sharded rows (streamed slices)"
    assert re_.sharding == "entity-sharded (host-resident blocks)"
    assert fe.pipelined and re_.pipelined and plan.distributed
    # streamed ELL is legal multi-process (per-host widths are private)
    plan = resolve(
        [_CC(layout="ell", hbm_budget_mb=64)], mesh=mesh, n_processes=2,
        distributed=True,
    )
    assert plan.coordinates[0].residency == "streamed"


def test_streamed_geometry_carries_slice_shape():
    """With a known feature dim the FE plan carries concrete slice geometry
    (the numbers --explain-plan prints)."""
    plan = resolve(
        [_CC(name="g", hbm_budget_mb=64)],
        mesh={"data": 8, "model": 1},
        n_processes=2,
        dims={"global": 1000},
    )
    g = plan.coordinates[0].geometry
    assert g["budget_bytes"] == 64 << 20
    assert g["slice_row_bytes"] == 1000 * 4
    assert g["rows_per_slice"] >= 1
    assert g["hosts_streaming"] == 2
    # bfloat16 halves the slice row bytes
    plan16 = resolve(
        [_CC(name="g", hbm_budget_mb=64, feature_dtype="bfloat16")],
        mesh={"data": 8, "model": 1},
        n_processes=2,
        dims={"global": 1000},
    )
    assert plan16.coordinates[0].geometry["slice_row_bytes"] == 1000 * 2


def test_plan_error_is_a_value_error():
    # call sites that predate the planner catch ValueError; PlanError must
    # keep flowing through them
    assert issubclass(PlanError, ValueError)
