"""A second family, as new files only: token sequences in, logits out.
What ``benchmarks/manifest.py``'s ``load_family`` asks of a family
file, at a toy size; a family the harness has never heard of."""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def build(repo):
    # no child to run; the stages the configuration names live beside
    # this file, so this directory goes on the path
    if HERE not in sys.path:
        sys.path.insert(0, HERE)


def prepare_inputs(config, data_base):
    """Token files, written once: three short (one row) and two long
    (``long_rows`` rows), ids drawn from a fixed seed."""
    import numpy as np
    spec, model = config["dataset"], config["model"]
    root = os.path.join(data_base, "toytok-%dx%d-s%d" % (
        spec["long_rows"], model["length"], spec["seed"]))
    os.makedirs(os.path.join(root, "requests"), exist_ok=True)
    rng = np.random.default_rng(spec["seed"])
    files = {"short": [], "long": []}
    rows_of = {}
    for kind, count, rows in (("short", 3, 1),
                              ("long", 2, spec["long_rows"])):
        for i in range(count):
            path = os.path.join(root, "requests", "%s-%d.npy" % (kind, i))
            tokens = rng.integers(0, model["vocab_size"],
                                  (rows, model["length"]), dtype=np.int32)
            if not os.path.exists(path):
                np.save(path, tokens)
            files[kind].append(path)
            rows_of[path] = rows
    return {"short_files": files["short"], "long_files": files["long"],
            "rows_of": rows_of, "data_root": root,
            "sample": files["long"][0]}


def make_weights(config, seed, ckpt_base):
    """-> (the .npz the runner loads, the float32 arrays the reference
    reads)."""
    import numpy as np
    model = config["model"]
    rng = np.random.default_rng([seed % 2 ** 63, 11])
    weights = {
        "embed": rng.standard_normal(
            (model["vocab_size"], model["hidden_size"])).astype(np.float32),
        "head": rng.standard_normal(
            (model["hidden_size"], model["num_classes"])).astype(np.float32)
        / np.sqrt(model["hidden_size"])}
    os.makedirs(os.path.dirname(ckpt_base), exist_ok=True)
    ckpt_path = ckpt_base + ".npz"
    np.savez(ckpt_path, **weights)
    return ckpt_path, weights


def _reference():
    path = os.path.join(os.path.dirname(HERE), "references", "toytok.py")
    spec = importlib.util.spec_from_file_location("toytok_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_outputs(config, pipeline, weights, ckpt_path, seed, inputs,
                  devices, result):
    """The runner's own jitted function on the weights it loads, over a
    real request padded as the loader pads it, against the reference."""
    import jax
    import numpy as np

    import toytok_stages as stage
    from benchmarks.references import compare
    step = pipeline["pipeline"][config["weights_steps"][0]]
    tokens = np.load(inputs["sample"])
    padded = np.zeros((step["max_rows"], step["length"]), np.int32)
    padded[:len(tokens)] = tokens
    got = np.asarray(jax.jit(stage.apply)(
        stage.load_params(ckpt_path, devices[0]), padded))[:len(tokens)]
    return compare(got, np.asarray(_reference().forward(weights, tokens)))


def flops_per_row(config):
    model = config["model"]
    return 2 * model["hidden_size"] * model["num_classes"] \
        + model["length"] * model["hidden_size"]


def wire_bytes_per_row(config, pipeline):
    return 4 * config["model"]["length"]
