"""``BENCHMARK.json`` and the files it names, found by name.

Nothing here knows a cell, a configuration, a mix, a metric or a model
family by name: a new one is a new file under ``benchmarks/`` and, but
for a family, one entry in the manifest. A configuration's file names
its family (``"family": "<name>"``), which is
``benchmarks/families/<name>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
LAYER_METRICS_DIR = os.path.join(BENCH_DIR, "layer_metrics")
FAMILIES_DIR = os.path.join(BENCH_DIR, "families")

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
    path = os.path.join(repo, config_entry(manifest, name)["file"])
    with open(path) as f:
        config = json.load(f)
    if not isinstance(config.get("family"), str):
        raise KeyError("%s names no \"family\": a configuration says which "
                       "benchmarks/families/<name>.py prepares and checks "
                       "it; there is no default" % path)
    return config


def metrics_for(manifest: dict, group: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def _load_file(path: str, kind: str, name: str):
    """The module at ``path``, as ``benchmarks.<kind>.<name>``."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks.%s.%s" % (kind, name.replace(".", "__")
                              .replace("-", "_")), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError("%s %r has no file %s" % (kind, name, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_layer_metric(name: str, directory: str = LAYER_METRICS_DIR):
    """The module ``layer_metrics/<name>.py``: its description
    (``NAME``, ``UNIT``, ``BETTER``, ``SOURCE``, ``LAYER``, ``MOVES``;
    the cells that report it are the manifest's to say) and its reader
    ``read(facts)``, which returns the value, or None when there is
    nothing to read."""
    return _load_file(os.path.join(directory, name + ".py"),
                      "layer_metrics", name)


#: what a family file provides, and benchmarks/run.py calls
FAMILY_CONTRACT = ("build", "prepare_inputs", "make_weights",
                   "check_outputs", "flops_per_row", "wire_bytes_per_row")


def load_family(name: str, directory: str = FAMILIES_DIR):
    """The module ``families/<name>.py``: what the harness has to know
    of one family of models, so that ``benchmarks/run.py`` knows none.
    Importing it imports neither JAX nor the program (``build`` runs
    before JAX starts). It provides:

    ``build(repo)``
        children that never touch JAX, before it is imported;
    ``prepare_inputs(config, data_base) -> dict``
        ``short_files`` and ``long_files`` (request files, which the
        traffic generator cycles), ``rows_of`` (rows of each),
        ``data_root`` (where the program is pointed) and ``sample``
        (whatever ``check_outputs`` wants of them);
    ``make_weights(config, seed, ckpt_base) -> (ckpt_path, weights)``
        what the program loads, written under ``ckpt_base`` in the
        family's own form, and what the reference reads;
    ``check_outputs(config, pipeline, weights, ckpt_path, seed, inputs,
    devices, result) -> dict``
        what :func:`benchmarks.references.compare` returns, from the
        family's own plain reference (``references/<name>.py``);
    ``flops_per_row(config)``, ``wire_bytes_per_row(config, pipeline)``
        the operations one row needs and the bytes it ships.

    The harness's own tests ask a family whose configuration is in
    ``BENCHMARK.json`` for two more, so that no test names a model:
    ``check_config(config) -> [problems]`` (what has to hold between
    the parts of the configuration's file) and ``project_memory(config,
    sharding) -> {rows, temporaries, arguments, waiting}`` (bytes of
    the largest bucket's stage program compiled for a described chip,
    held against the file's ``size_record``).

    A tree that brings its own ``families`` directory (a test's copy)
    need not repeat this checkout's files: a name it lacks is looked
    for here."""
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        path = os.path.join(FAMILIES_DIR, name + ".py")
    module = _load_file(path, "families", name)
    missing = [f for f in FAMILY_CONTRACT
               if not callable(getattr(module, f, None))]
    if missing:
        raise AttributeError("%s lacks %s" % (path, ", ".join(missing)))
    return module


def describe(module) -> dict:
    return {field: getattr(module, field.upper())
            for field in METRIC_FIELDS}
