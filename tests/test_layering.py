"""The arrows of the module graph point one way: the training and serving
packages import no development tooling.

``photon_ml_tpu.analysis`` (the AST linter: ``engine``, ``rules``,
``project``, ``dataflow``) is a development tool. The transfer guard and
``logged_fetch``, which every fetch of the measured path goes through, live
in ``photon_ml_tpu/utils/transfer.py``; ``analysis`` re-exports them and
nothing imports them from there but the benchmark's job file.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGES = (
    "estimators", "game", "optimize", "ops", "evaluation", "parallel",
    "models", "serving",
)
LINTER = tuple(
    f"photon_ml_tpu.analysis.{m}" for m in ("engine", "rules", "project", "dataflow")
)

# One interpreter imports the eight packages and records who imported whom
# (``builtins.__import__`` sees every import statement as its module's body
# runs, once). A package is charged with a linter module when the module is
# reachable from it over those edges, importing a submodule runs its parents'
# ``__init__`` too, so the verdict of one package does not depend on which
# was imported before it.
_PROBE = r"""
import builtins, importlib, importlib.util, json, sys

packages, linter = json.loads(sys.argv[1]), set(json.loads(sys.argv[2]))
edges = {}
real_import = builtins.__import__


def spy(name, globals=None, locals=None, fromlist=(), level=0):
    module = real_import(name, globals, locals, fromlist, level)
    importer = (globals or {}).get("__name__", "")
    if importer.startswith("photon_ml_tpu"):
        full = name
        if level:
            full = importlib.util.resolve_name(
                "." * level + name, globals.get("__package__") or importer
            )
        seen = [full] + [f"{full}.{item}" for item in fromlist or ()]
        edges.setdefault(importer, set()).update(
            m for m in seen if m.startswith("photon_ml_tpu") and m in sys.modules
        )
    return module


builtins.__import__ = spy
for package in packages:
    importlib.import_module("photon_ml_tpu." + package)
builtins.__import__ = real_import


def reach(root):
    seen, todo = set(), [root]
    while todo:
        m = todo.pop()
        if m in seen:
            continue
        seen.add(m)
        parts = m.split(".")
        todo.extend(".".join(parts[:i]) for i in range(1, len(parts)))
        todo.extend(edges.get(m, ()))
    return seen


charged = {p: sorted(reach("photon_ml_tpu." + p) & linter) for p in packages}
loaded = sorted(m for m in sys.modules if m in linter)
if loaded and not any(charged.values()):
    # an import the spy cannot see (importlib at module level): charge all
    charged = {p: loaded for p in packages}
print(json.dumps(charged))
"""


@pytest.fixture(scope="module")
def linter_modules_by_package():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, json.dumps(PACKAGES), json.dumps(LINTER)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _analysis_imports(path: pathlib.Path) -> list:
    """Lines of ``path`` that import ``photon_ml_tpu.analysis`` or anything
    under it, at module level or inside a function."""
    package = ".".join(path.relative_to(REPO).parts[:-1])  # its directory
    hits = []
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if isinstance(node, ast.Import):
            targets = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = importlib.util.resolve_name(
                "." * node.level + (node.module or ""), package
            ) if node.level else node.module
            targets = [base] + [f"{base}.{alias.name}" for alias in node.names]
        else:
            continue
        if any((t + ".").startswith("photon_ml_tpu.analysis.") for t in targets):
            hits.append(f"{path.relative_to(REPO)}:{node.lineno}")
    return hits


def _files_outside_analysis(root: pathlib.Path) -> list:
    analysis = REPO / "photon_ml_tpu" / "analysis"
    return [p for p in sorted(root.rglob("*.py")) if analysis not in p.parents]


@pytest.mark.parametrize("package", PACKAGES)
def test_package_imports_no_linter(linter_modules_by_package, package):
    """Neither when the package is imported (the probe) nor later, from an
    import inside a function or in a submodule its ``__init__`` leaves out
    (the source scan)."""
    assert linter_modules_by_package[package] == [], (
        f"import photon_ml_tpu.{package} loads the development linter: "
        "import the transfer guard from photon_ml_tpu.utils.transfer"
    )
    files = _files_outside_analysis(REPO / "photon_ml_tpu" / package)
    assert files
    assert [hit for f in files for hit in _analysis_imports(f)] == []


def test_nothing_outside_analysis_imports_it():
    """analysis -> utils, never the reverse: the rest of the package (cli, io,
    obs, robust, utils, ... too) and the chip smoke do without the linter."""
    files = _files_outside_analysis(REPO / "photon_ml_tpu") + [REPO / "chip_smoke.py"]
    assert [hit for f in files for hit in _analysis_imports(f)] == []


def test_analysis_reexports_the_transfer_names():
    """benchmark/jobs/fit.py does ``from photon_ml_tpu.analysis import
    transfer_guard``: the names stay importable there, and are the objects of
    utils/transfer.py, not copies with a guard stack of their own."""
    from photon_ml_tpu import analysis
    from photon_ml_tpu.utils import transfer

    for name in ("transfer_guard", "logged_fetch", "allow_transfers", "guard_level"):
        assert getattr(analysis, name) is getattr(transfer, name), name
    assert not (REPO / "photon_ml_tpu" / "analysis" / "runtime.py").exists()


def test_no_solver_switch_in_the_package():
    """The packed solver is the solver: no file of the package reads the
    PHOTON_RE_SOLVER environment switch, and the coordinate has no method
    that picks a solver. The vmapped solve is the tests' reference."""
    readers = [
        str(path.relative_to(REPO))
        for path in sorted((REPO / "photon_ml_tpu").rglob("*.py"))
        if "PHOTON_RE_SOLVER" in path.read_text(encoding="utf-8")
    ]
    assert readers == []
    from photon_ml_tpu.game import coordinate
    from photon_ml_tpu.testing.reference_solver import train_blocks_vmapped

    assert not hasattr(coordinate.RandomEffectCoordinate, "_train_fn")
    assert not hasattr(coordinate, "_train_blocks")
    assert callable(train_blocks_vmapped)
