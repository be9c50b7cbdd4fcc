"""One cell, one process, one run.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one job kind or
one per-layer metric sits in a file of its own, found by the name in
``BENCHMARK.json``; this file knows none of them. The last line of stdout is
the result JSON. There is no CPU mode: without a TPU, with fewer chips than the
cell asks for, or on a device that ``peaks.json`` does not list, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device", "breakdown")


class NoResult(Exception):
    """The run cannot produce a result at all: exit non-zero, print none."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(manifest: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell's files, found by name: nothing here names a configuration, a
    mix or a metric."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=load_json(os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if _in_cell(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if _in_cell(m, workload)],
    )


def load_reader(name: str, root: str = ROOT):
    """benchmark/layer_metrics/<name>.py, by path (a name may hold a dot)."""
    path = os.path.join(root, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("benchmark_layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_job(kind: str):
    return importlib.import_module(f"benchmark.jobs.{kind}")


def check_device(chips: int) -> dict:
    """The device line, or NoResult when this is not the machine for the cell."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoResult(f"jax found no device: {e}") from e
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoResult(f"backend is {dev.platform!r}, not 'tpu': the benchmark has no CPU mode")
    if len(devices) < chips:
        raise NoResult(f"the cell asks for {chips} chips, jax sees {len(devices)}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if dev.device_kind not in peaks:
        raise NoResult(f"device kind {dev.device_kind!r} is not in benchmark/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def result_line(correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
                device: dict, breakdown: Optional[dict] = None, notes: Optional[dict] = None) -> str:
    """The one JSON object the driver reads (``notes`` is for humans: the
    driver ignores any other key)."""
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    return json.dumps(line)


def report_metrics(wanted: List[dict], values: Dict[str, Optional[float]]) -> Dict[str, dict]:
    """Name -> {value, unit} for the metrics that have a value, units from
    BENCHMARK.json. A metric with nothing to read is left out of the line."""
    return {
        m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
        for m in wanted
        if values.get(m["name"]) is not None
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    try:
        cell = resolve_cell(load_manifest(), args.workload)
        device = check_device(cell.chips)
        job = load_job(cell.traffic["job"])
        line = job.run(cell, args.seed, args.seconds, bool(args.trace), device, T_PROCESS_START)
    except NoResult as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
