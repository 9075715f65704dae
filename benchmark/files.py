"""Where the benchmark's files are, found by the names in BENCHMARK.json.

Nothing here imports jax: the parent process (run.py) uses it too. A cell
names a configuration and a traffic mix; every other file is found from
those two names and from the metric names. There is no registry: a later
PR adds a cell by adding files and one ``workloads`` entry.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The ``workloads`` entry called ``name``."""
    cells = {w["name"]: w for w in benchmark_json()["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    return cells[name]


def config_path(config: str) -> str:
    """The configuration's file as BENCHMARK.json names it."""
    for c in benchmark_json()["configs"]:
        if c["name"] == config:
            return os.path.join(ROOT, c["file"])
    raise SystemExit(f"no config {config!r} in BENCHMARK.json")


def traffic_path(mix: str) -> str:
    return os.path.join(HERE, "traffic", f"{mix}.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. An unknown device is an error, never a
    default: a utilization against another chip's peak is not a number."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"benchmark/peaks.json; known: "
                         f"{sorted(table['devices'])}")
    return table["devices"][device_kind]


def load_module(path: str, name: str):
    """Import a python file by path (file names carry dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config_module(config: str):
    """``configs/<config>.py``: builds the job for a cell (see worker.py)."""
    path = os.path.splitext(config_path(config))[0] + ".py"
    return load_module(path, "bench_config")


def reference_module(config: str):
    """``reference/<config>.py``: the plain float32 forward and loss."""
    return load_module(os.path.join(HERE, "reference", f"{config}.py"),
                       "bench_reference")


def layer_metric(name: str):
    """(spec, read) of one per-layer metric: ``layer_metrics/<name>.json``
    names a reader kind in ``readers/``, unless ``layer_metrics/<name>.py``
    brings a reader of its own."""
    base = os.path.join(HERE, "layer_metrics", name)
    spec = load_json(base + ".json")
    own = base + ".py"
    path = own if os.path.exists(own) else os.path.join(
        HERE, "readers", f"{spec['reader']}.py")
    return spec, load_module(path, f"bench_reader_{name}").read
