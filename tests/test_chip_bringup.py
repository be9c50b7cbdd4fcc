"""Bring-up rules that a CPU run can pin (PR 21): where the compile cache goes,
that chip_smoke.py has no CPU mode, the intercept trap at the fused-kernel
gate, and the content-keyed native library."""

import hashlib
import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from photon_ml_tpu import native
from photon_ml_tpu.cli.params import parse_feature_shard
from photon_ml_tpu.io.data import build_index_maps
from photon_ml_tpu.ops import pallas_glm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = (
    "import jax\n"
    "from photon_ml_tpu.utils.compile_cache import "
    "enable_persistent_compilation_cache as enable\n"
    "print(enable())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _run(argv, env_overrides, drop=()):
    env = {**os.environ, "PYTHONPATH": REPO, **env_overrides}
    for name in drop:
        env.pop(name, None)
    return subprocess.run(
        argv, env=env, cwd=REPO, capture_output=True, text=True, timeout=120
    )


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, placed):
    if placed:
        want = str(tmp_path / "cache")
        proc = _run(
            [sys.executable, "-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": want}
        )
    else:
        want = os.path.join(REPO, ".xla_cache")
        proc = _run(
            [sys.executable, "-c", _CACHE_PROBE], {}, drop=["JAX_COMPILATION_CACHE_DIR"]
        )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]


def _tracked_text_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".") and d != "__pycache__" and not d.startswith("out-")
        ]
        for name in files:
            if name.endswith((".py", ".md", ".toml", ".json", ".jsonl")):
                yield os.path.join(root, name)


def test_no_entry_point_places_the_cache_itself():
    removed_switch = "PHOTON_" + "COMPILE_CACHE"
    sets_dir = re.compile(r"""config\.update\(\s*["']jax_compilation_cache_dir""")
    offenders = []
    for path in _tracked_text_files():
        rel = os.path.relpath(path, REPO)
        if rel == "ISSUE.md":
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if removed_switch in text:
            offenders.append((rel, removed_switch))
        if (
            rel.endswith(".py")
            and rel != os.path.join("photon_ml_tpu", "utils", "compile_cache.py")
            and sets_dir.search(text)
        ):
            offenders.append((rel, "sets jax_compilation_cache_dir"))
    assert not offenders


def test_chip_smoke_has_no_cpu_mode():
    proc = _run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], {"JAX_PLATFORMS": "cpu"}
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("n_named, fused", [(1023, True), (1024, False)])
def test_default_intercept_decides_the_fused_gate(n_named, fused):
    """Every CLI shard gets an intercept by default, so a 1024-wide feature
    bag trains at d = 1025 and never reaches a Pallas kernel."""
    shards = parse_feature_shard("name=globalShard,bags=features")
    record = {
        "features": [
            {"name": f"g{j}", "term": "", "value": 1.0} for j in range(n_named)
        ]
    }
    d = len(build_index_maps([record], shards)["globalShard"])
    assert d == n_named + 1
    assert pallas_glm.eligible(16_384, d, jnp.float32) is fused


def test_native_library_is_keyed_by_source_content(tmp_path):
    src = tmp_path / "decoder.cpp"
    shutil.copy(os.path.join(os.path.dirname(native.__file__), "decoder.cpp"), src)
    before = native.lib_path(str(src))
    assert before == native.lib_path()
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert os.path.basename(before) == f"_photon_native.{digest}.so"
    with open(src, "ab") as f:
        f.write(b"\n// changed\n")
    assert native.lib_path(str(src)) != before
