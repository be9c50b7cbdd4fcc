"""The documents name programs that exist, and the benchmark's sources cite
records that exist.

There is one benchmark (``BENCHMARK.json`` + ``benchmark/``) and one record of
it (``PERF_LEDGER.jsonl``, read through ``PERF.md``). A document that tells a
reader to run a script or a module the tree no longer has sends them to
measure with a tool nothing can be compared with.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")


def _tier1_block(text: str) -> str:
    (line,) = [l for l in text.splitlines() if l.startswith("**Tier-1 verify:**")]
    return line


DOCUMENTS = {
    "README.md": None,
    "MIGRATION.md": None,
    "examples/README.md": None,
    "PERF.md": None,
    "ROADMAP.md": _tier1_block,
}


def _module_exists(name: str) -> bool:
    path = REPO.joinpath(*name.split("."))
    if path.with_suffix(".py").is_file() or (path / "__init__.py").is_file():
        return True
    top = name.split(".")[0]
    if (REPO / top).exists() or (REPO / f"{top}.py").exists():
        return False  # a module of this repo that is not there
    return importlib.util.find_spec(top) is not None  # pytest and the like


@pytest.mark.parametrize("document", sorted(DOCUMENTS))
def test_document_names_programs_that_exist(document):
    path = REPO / document
    text = path.read_text(encoding="utf-8")
    if DOCUMENTS[document] is not None:
        text = DOCUMENTS[document](text)
    scripts = sorted(set(_SCRIPT.findall(text)))
    modules = sorted(set(_MODULE.findall(text)))
    assert scripts or modules, f"{document} names no program: the patterns rotted"
    missing = [
        s for s in scripts
        if not (REPO / s).is_file() and not (path.parent / s).is_file()
    ]
    missing += [m for m in modules if not _module_exists(m)]
    assert missing == [], f"{document} tells the reader to run {missing}"


def _benchmark_configs():
    return json.loads((REPO / "BENCHMARK.json").read_text(encoding="utf-8"))["configs"]


@pytest.mark.parametrize("config", [c["name"] for c in _benchmark_configs()])
def test_benchmark_source_cites_a_baseline_config_that_exists(config):
    """Each configuration's file names "BASELINE.json config N" as the origin
    of its widths: N is an entry ``BASELINE.json`` ``configs`` still has, and
    one of the configuration's own kind (a GLMix entry for a ``glmix-*``
    configuration, the Poisson one for ``poisson-*``, the fixed-effect logistic
    one for ``logistic-*``)."""
    (entry,) = [c for c in _benchmark_configs() if c["name"] == config]
    source = json.loads((REPO / entry["file"]).read_text(encoding="utf-8"))["source"]
    cited = [int(n) for n in re.findall(r"BASELINE\.json config (\d+)", source)]
    assert cited, f"{entry['file']} cites no BASELINE.json config: {source!r}"
    baseline = json.loads((REPO / "BASELINE.json").read_text(encoding="utf-8"))
    assert "measured_baselines" not in baseline
    for n in cited:
        assert 1 <= n <= len(baseline["configs"]), (n, len(baseline["configs"]))
        kind = {"glmix": "GLMix", "poisson": "Poisson", "logistic": "logistic regression"}[config.split("-")[0]]
        assert kind in baseline["configs"][n - 1]
