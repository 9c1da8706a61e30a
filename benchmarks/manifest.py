"""``BENCHMARK.json`` and the files it names, found by name.

Nothing here knows a cell, a configuration, a mix or a metric by name:
a new one is a new file under ``benchmarks/`` and one entry in the
manifest.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
LAYER_METRICS_DIR = os.path.join(BENCH_DIR, "layer_metrics")

#: what a layer-metric file declares, and the manifest repeats
METRIC_FIELDS = ("name", "unit", "better", "source", "layer", "moves")


def subdir(bench_root: str, name: str) -> str:
    """``<bench_root>/benchmarks/<name>`` where another manifest's tree
    brings its own (a test's copy), else this checkout's."""
    own = os.path.join(bench_root, "benchmarks", name)
    return own if os.path.isdir(own) else os.path.join(BENCH_DIR, name)


def load(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            return entry
    raise KeyError("BENCHMARK.json has no workload %r; it has %s"
                   % (name, [w["name"] for w in manifest["workloads"]]))


def config_entry(manifest: dict, name: str) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return entry
    raise KeyError("BENCHMARK.json has no config %r" % name)


def load_config_file(manifest: dict, name: str, repo: str = REPO) -> dict:
    with open(os.path.join(repo, config_entry(manifest, name)["file"])) as f:
        return json.load(f)


def metrics_for(manifest: dict, group: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_layer_metric(name: str, directory: str = LAYER_METRICS_DIR):
    """The module ``layer_metrics/<name>.py``: its description
    (``NAME``, ``UNIT``, ``BETTER``, ``SOURCE``, ``LAYER``, ``MOVES``;
    the cells that report it are the manifest's to say) and its reader
    ``read(facts)``, which returns the value, or None when there is
    nothing to read."""
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "__")
        .replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError("per-layer metric %r has no reader %s"
                                % (name, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def describe(module) -> dict:
    return {field: getattr(module, field.upper())
            for field in METRIC_FIELDS}
