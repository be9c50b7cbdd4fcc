"""Tier-1 self-check: the package passes its own static analysis.

This is the CI gate in test form — the same configuration, baseline, and
rule set as ``python -m photon_ml_tpu.analysis``. A new unsuppressed finding
anywhere in the configured paths (photon_ml_tpu/ and chip_smoke.py) fails this
test with the finding list in the assertion message; fix it, suppress it
with a reasoned ``# photon: ignore[Rn]``, or (for a deliberate
grandfathering) add it to lint_baseline.json via --write-baseline."""

import os

import pytest

from photon_ml_tpu.analysis import analyze_paths, load_baseline, load_config
from photon_ml_tpu.analysis.engine import iter_python_files
from photon_ml_tpu.analysis.project import (
    analyze_project,
    render_refusal_inventory,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    return load_config(pyproject=os.path.join(REPO_ROOT, "pyproject.toml"))


@pytest.fixture(scope="module")
def package_sources(config):
    root = os.path.abspath(config.root)
    sources = {}
    for path in iter_python_files(config.paths, config):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            sources[rel] = f.read()
    return sources


def test_package_is_lint_clean(config):
    # analyze_paths with default paths is a FULL configured run, so this
    # gate covers the whole-program passes (R9-R12) too, not just R1-R8
    baseline = load_baseline(config.baseline_path)
    result = analyze_paths(config=config, baseline=baseline)
    assert not result.parse_errors, result.parse_errors
    assert not result.active, "\n" + "\n".join(
        f"{f.file}:{f.line}:{f.col}: {f.rule} {f.message}\n    {f.code}"
        for f in result.active
    )
    assert result.files_scanned > 50  # the walk really covered the package


def test_race_annotations_are_consulted(config, package_sources):
    """The R9 pass really runs and really validates: no annotation errors,
    and the package's guarded-by/thread-confined annotations are consumed
    by actual race findings (an unused one would be R12 upstream)."""
    res = analyze_project(package_sources, config, rules=("R9",))
    assert res.errors == []
    assert res.annotations, "expected race annotations in the package"
    assert res.used_annotations, "annotations exist but excuse no race"


def test_refusal_inventory_is_fresh(config, package_sources):
    """refusals.json must be byte-identical to a fresh regeneration, and
    every documented refusal must be enforced by at least one raise site."""
    res = analyze_project(package_sources, config, rules=("R10",))
    assert res.refusal_inventory is not None, "README ledger not found"
    want = render_refusal_inventory(res.refusal_inventory)
    inv_path = os.path.join(config.root, config.refusal_inventory)
    with open(inv_path, encoding="utf-8") as f:
        assert f.read() == want, "stale: run --write-refusal-inventory"
    for entry in res.refusal_inventory["refusals"]:
        assert entry["modules"], f"unenforced refusal: {entry['fragment']!r}"
        assert entry["exceptions"], entry["fragment"]


def test_dataflow_rules_are_zero_active(config, package_sources):
    """R13-R16 hold on the package itself with NO baseline net: no lock-order
    cycle, no resource leaked on any CFG path, no tracer hazard reachable
    from a @jit root, no fault-site drift."""
    res = analyze_project(
        package_sources, config, rules=("R13", "R14", "R15", "R16")
    )
    assert res.errors == []
    assert res.findings == [], "\n" + "\n".join(
        f"{f.file}:{f.line}: {f.rule} {f.message}" for f in res.findings
    )


def test_fault_inventory_is_fresh(config, package_sources):
    """faults.json must be byte-identical to a fresh regeneration (both
    directions: a new site or a deleted one is equally stale), and every
    inventoried site must name at least one declaring module."""
    from photon_ml_tpu.analysis.dataflow import (
        build_fault_inventory,
        extract_fault_sites,
        render_fault_inventory,
    )

    want = render_fault_inventory(
        build_fault_inventory(extract_fault_sites(package_sources))
    )
    inv_path = os.path.join(config.root, config.fault_inventory)
    with open(inv_path, encoding="utf-8") as f:
        assert f.read() == want, "stale: run --write-fault-inventory"
    doc = build_fault_inventory(extract_fault_sites(package_sources))
    assert doc["sites"], "expected fault sites in the package"
    for entry in doc["sites"]:
        assert entry["modules"], f"siteless entry: {entry['site']!r}"


def test_cached_lint_matches_uncached(config, tmp_path, monkeypatch):
    """--cache is a pure speedup: byte-identical findings, and the second
    run really is served from the run-level cache entry."""
    import dataclasses as _dc

    from photon_ml_tpu.analysis.engine import CACHE_DIR_NAME

    cfg = _dc.replace(config, root=config.root)
    plain = analyze_paths(config=cfg)
    monkeypatch.setattr(
        "photon_ml_tpu.analysis.engine.CACHE_DIR_NAME",
        str(tmp_path / CACHE_DIR_NAME),
    )
    cold = analyze_paths(config=cfg, cache=True)
    warm = analyze_paths(config=cfg, cache=True)
    for result in (cold, warm):
        assert [f.to_dict() for f in result.findings] == [
            f.to_dict() for f in plain.findings
        ]
        assert result.files_scanned == plain.files_scanned
