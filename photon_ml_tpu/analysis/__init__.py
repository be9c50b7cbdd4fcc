"""JAX-aware static analysis for photon-ml-tpu.

The package's two recurring defect classes — silent host<->device syncs and
dtype-discipline bugs — are mechanical, not creative: a ``float()`` on a jax
array in the coordinate-descent hot loop, a hardcoded ``* 4`` itemsize that
under-counts an x64 dataset, an ``except Exception`` that eats a real error.
The reference Photon ML leaned on scalac's type discipline for this class of
invariant; a dynamically typed JAX port has to build its own. This package is
that discipline, in two halves:

- **static**: an AST linter (stdlib ``ast`` only) in two tiers. Per-file
  rules R1-R8 — implicit device transfer in hot-loop modules, recompile
  hazards inside ``@jit``, dtype discipline, swallow-and-continue handlers,
  non-atomic writes, NaN mishandling, unattributed wall-clock timing,
  module-level jax imports on the jax-free report path. Whole-program
  passes R9-R16 (``analysis/project.py`` + ``analysis/dataflow.py``) — a
  package-wide symbol table and call graph feeding a thread-context race
  detector (R9), refusal-ledger consistency against
  README/tests/``refusals.json`` (R10), the ``photon_*`` metric-name
  contract (R11), unused-suppression detection (R12), and the
  interprocedural dataflow rules: lock-order deadlock cycles (R13),
  resources not released on every CFG path including exception edges
  (R14), jit tracer hazards by call-graph reachability (R15), and
  fault-site inventory drift against ``faults.json``/README/tests (R16).
  Run it with ``python -m photon_ml_tpu.analysis`` (``--cache`` for the
  incremental mtime+size-keyed fast path); configure it from
  ``[tool.photon-lint]`` in pyproject.toml; suppress individual lines
  with ``# photon: ignore[RULE]``; declare intent the analyses cannot see
  with ``# photon: guarded-by[lock_attr]`` / ``# photon: thread-confined``
  / ``# photon: lock-order[LockA < LockB]`` / ``# photon:
  static-arg[name]``; grandfather findings in a checked-in baseline.

- **runtime**: :func:`transfer_guard`, a context manager the CD sweep
  enters, which makes JAX hard-error on any *implicit* device->host
  fetch. Legitimate fetches go through :func:`logged_fetch` (explicit
  ``jax.device_get`` + an obs byte counter), so "zero unlogged fetches in
  the hot loop" is enforced by the runtime, not just asserted by a test.
  That half lives in ``photon_ml_tpu/utils/transfer.py`` (the measured path
  imports it from there and never imports this package); the four names
  are re-exported here.
"""

from .config import LintConfig, find_repo_root, load_config
from .engine import (
    Finding,
    LintResult,
    analyze_paths,
    analyze_source,
    load_baseline,
    write_baseline,
    write_fault_inventory,
    write_refusal_inventory,
)
from .project import analyze_project
from .rules import RULES, explain_rule
from ..utils.transfer import allow_transfers, guard_level, logged_fetch, transfer_guard

__all__ = [
    "Finding",
    "LintConfig",
    "LintResult",
    "RULES",
    "allow_transfers",
    "analyze_paths",
    "analyze_project",
    "analyze_source",
    "explain_rule",
    "find_repo_root",
    "guard_level",
    "load_baseline",
    "load_config",
    "logged_fetch",
    "transfer_guard",
    "write_baseline",
    "write_fault_inventory",
    "write_refusal_inventory",
]
