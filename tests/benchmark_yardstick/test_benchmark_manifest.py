"""BENCHMARK.json against the contract's rules that a file can be checked for,
the files each cell needs, and the last line's keys. A configuration, a mix
and a per-layer metric added as FILES ONLY (plus entries) are found."""

import importlib.util
import json
import os
import re
import shutil

import pytest

from benchmark import run as brun

ROOT = brun.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return brun.load_manifest()


def cells_of(metric, manifest):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        names.append(w["name"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert [m["name"] for m in manifest["end_to_end"]] == ["fit_s", "setup_s"]


def test_command_and_paths(manifest):
    assert 1 <= len(manifest["paths"]) <= 16
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p
        for dirpath, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        cell = brun.resolve_cell(manifest, w["name"])
        used.add(w["config"])
        assert configs[w["config"]]["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert cell.config["mesh"]["data"] == w["chips"]
        assert sorted(cell.config["reduced"]) == sorted(configs[w["config"]]["reduced"])
        assert cell.config["source"] == configs[w["config"]]["source"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "jobs", cell.traffic["job"] + ".py"))
        assert [m["name"] for m in cell.end_to_end] == ["fit_s", "setup_s"]
        assert cell.per_layer, w["name"]
    assert used == set(configs)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    # widths are never cut
    for c in manifest["configs"]:
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")) and key not in ("d", "d_re")


def test_per_layer_metrics_have_readers_and_move_a_reported_metric(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cell_names = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        reader = brun.load_reader(m["name"])
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            m["unit"], m["better"], m["source"], m["layer"], m["moves"]), m["name"]
        assert callable(reader.read)
        assert m["moves"] in e2e
        for cell in cells_of(m, manifest):
            assert cell in cell_names
            assert cell in cells_of(e2e[m["moves"]], manifest)
    # every listed metric has its reader file (a reader may wait for its cell)
    listed = {m["name"] + ".py" for m in manifest["per_layer"]}
    present = {f for f in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert listed <= present


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_added_files_are_found_without_editing_any(tmp_path, manifest):
    """A later PR adds a configuration, a mix and a metric as new files plus
    entries of BENCHMARK.json; no file that is there changes."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), root): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(root) for f in fs
    }
    config = brun.load_json(os.path.join(ROOT, "benchmark", "configs", "glmix-user-1chip.json"))
    config["name"] = "throwaway-1chip"
    config["scale"]["rows"] = 131072
    with open(os.path.join(root, "benchmark", "configs", "throwaway-1chip.json"), "w") as f:
        json.dump(config, f)
    mix = {"job": "fit", "coordinates": ["global"], "reg_weights": {"global": [3.0]},
           "cd_sweeps": 2, "validation": {"evaluator": "AUC", "frequency": "SWEEP"}, "trace_fits": 2}
    with open(os.path.join(root, "benchmark", "traffic", "fit-one-lambda.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "layer_metrics", "sweeps.per_fit.py"), "w") as f:
        f.write('UNIT = "count"\nBETTER = "lower"\nSOURCE = "program_span"\nLAYER = "CD loop"\n'
                'MOVES = "fit_s"\n\n\ndef read(obs):\n    return len(obs.spans_named("cd.sweep")) / obs.n_fits\n')
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({"name": "throwaway-1chip", "source": "a test",
                             "file": "benchmark/configs/throwaway-1chip.json", "reduced": ["rows"],
                             "why": "a test"})
    grown["workloads"].append({"name": "throwaway-1chip.fit-one-lambda", "config": "throwaway-1chip",
                               "traffic": "fit-one-lambda", "chips": 1, "why": "a test"})
    grown["per_layer"].append({"name": "sweeps.per_fit", "unit": "count", "better": "lower",
                               "source": "program_span", "layer": "CD loop", "moves": "fit_s",
                               "workloads": ["throwaway-1chip.fit-one-lambda"]})
    cell = brun.resolve_cell(grown, "throwaway-1chip.fit-one-lambda", root=root)
    assert cell.config["scale"]["rows"] == 131072 and cell.traffic["cd_sweeps"] == 2
    assert "sweeps.per_fit" in [m["name"] for m in cell.per_layer]
    assert "re_update_s" not in [m["name"] for m in cell.per_layer]
    reader = brun.load_reader("sweeps.per_fit", root=root)

    class Obs:
        n_fits = 2

        def spans_named(self, name):
            return [1, 2, 3, 4]

    assert reader.read(Obs()) == 2.0
    # the old cells resolve as before, and no file that was there changed
    assert brun.resolve_cell(grown, "glmix-user-1chip.fit-fixed", root=root).config["scale"]["rows"] == 1572864
    after = {p: os.path.getmtime(os.path.join(root, p)) for p in before}
    assert after == before
    with pytest.raises(brun.NoResult):
        brun.resolve_cell(grown, "no-such-cell", root=root)


def test_result_line_has_the_contract_keys_and_no_surprise():
    metrics = brun.report_metrics(
        [{"name": "fit_s", "unit": "s"}, {"name": "setup_s", "unit": "s"}, {"name": "absent", "unit": "s"}],
        {"fit_s": 1.25, "setup_s": 30, "absent": None},
    )
    assert metrics == {"fit_s": {"value": 1.25, "unit": "s"}, "setup_s": {"value": 30.0, "unit": "s"}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
    line = json.loads(brun.result_line(True, 12, 0, metrics, device))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    line = json.loads(brun.result_line(False, 3, 1, metrics, device,
                                       breakdown={"device_ops": [], "idle_gaps": []}, notes={"x": 1}))
    assert set(line) - {"notes"} == set(brun.RESULT_KEYS)
    assert line["correct"] is False and line["attempted"] == 3 and line["failed"] == 1


def test_no_tpu_no_result(capsys):
    """On this machine jax has no TPU: the run exits non-zero and prints no line."""
    rc = brun.main(["--workload", "glmix-user-1chip.fit-fixed", "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no CPU mode" in out.err


def test_peaks_table_names_its_source():
    peaks = brun.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in v5e["source"]
