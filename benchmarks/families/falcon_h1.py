"""The Falcon-H1 family (a Mamba-2 scan and grouped-query attention side
by side in every block, a dense gated MLP, muP multipliers; served as
prefill over packed token rows), behind the contract
``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "falcon_h1"``. The plain reference is
``benchmarks/references/falcon_h1.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
bytes a row ships, the mix's mean context) is ``families/nemotron_h.py``'s
and is called from there, so that the token families' cells draw prompts
through one code.

**The weights.** A recipe (seed, sizes; the family holds no experts),
not a file of values: the program makes each tensor on its device from
the seed and the tensor's name, and :func:`check_outputs` hands the
reference the same values through ``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens; each is recomputed
by the reference on the chip, one tensor group's float32 weights at a
time, and both go to :func:`benchmarks.references.compare`. Nothing is
chosen in this family (no router, no selection), so nothing is handed
over but the tokens.

**Tolerance.** Two limits on the same samples, each between two
readings on the v5e (PR 53, my chip runs; PERF.md section 2 and the
configuration's ``precision_readings``): bfloat16 weights and
activations as the configuration states them, and the same comparison
with every stored matrix of the layers rounded through float8 (e4m3),
which is not correct by either. ``SHARE_OF_SPREAD`` = 5% of the
reference logits' spread for the worst logit, the limit of every token
family here; it is the largest of two million differences and moves by
a tenth from run to run. ``RMS_SHARE_OF_SPREAD`` holds their root mean
square, which moves by a few hundredths. The scan's states carried in
bfloat16 are recorded beside (``CONTROL_MAY_PASS``): neither number
sees them, on an 8,100-token prompt either.
"""

from __future__ import annotations

import glob
import os
from typing import List

import numpy as np

from benchmarks import manifest

_tokens = manifest.load_family("nemotron_h")

#: the comparison's limit, as a share of the reference logits' spread
SHARE_OF_SPREAD = 0.05
#: the second limit: the root mean square of the differences over every
#: compared logit, as a share of the same spread
RMS_SHARE_OF_SPREAD = 0.009
#: the reference runs prompts padded to a multiple of this many tokens
REF_PAD = 512
#: the control arms that are recorded whether they pass or not
#: (``scripts/prefill_control.py``): every other arm must fail
CONTROL_MAY_PASS = ("state_bfloat16",)

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
wire_bytes_per_row = _tokens.wire_bytes_per_row
mean_context = _tokens.mean_context


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "falcon_h1")):
        raise SystemExit("benchmarks/families/falcon_h1.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "falcon_h1: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in ("published", "ssm_multipliers", "mlp_multipliers")
            or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.falcon_h1 import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed)
    return path, None


def compare_logits(config: dict, got, ref) -> dict:
    """``references.compare`` under ``SHARE_OF_SPREAD``, unless the
    configuration's file states its own ``share_of_spread`` (a
    toy-width copy in the tests does: the largest difference of narrow
    sums averages less rounding away), and the root mean square under
    ``RMS_SHARE_OF_SPREAD``, which the toy widths keep."""
    from benchmarks.references import compare
    limit = float(config.get("share_of_spread", SHARE_OF_SPREAD))
    verdict = compare(got, ref, limit)
    verdict.update(limit=limit, rms_limit=RMS_SHARE_OF_SPREAD)
    if verdict.get("ref_spread"):
        delta = np.asarray(got, np.float64) - np.asarray(ref, np.float64)
        rms = float(np.sqrt((delta ** 2).mean()) / verdict["ref_spread"])
        verdict.update(rms_share_of_spread=rms, ok=bool(
            verdict["ok"] and rms <= RMS_SHARE_OF_SPREAD))
    return verdict


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference, under
    :func:`compare_logits`'s two limits."""
    import jax

    from benchmarks.references import falcon_h1 as reference
    from rnb_tpu.models.falcon_h1 import checkpoint
    samples = sorted(glob.glob(os.path.join(result.log_dir,
                                            "prefill-sample-*.npz")))
    if not samples:
        return {"ok": False, "why": "the final stage kept no sample under "
                + result.log_dir}
    by_tokens = {}
    for path in inputs["short_files"] + inputs["long_files"]:
        by_tokens[np.load(path).tobytes()] = path
    cfg, _, _ = checkpoint.load_recipe(ckpt_path)
    read = checkpoint.reference_reader(cfg, seed, devices[0])
    ref_model = reference.Reference(published_keys(config))
    got, ref, files, rows = [], [], [], []
    with jax.default_matmul_precision("highest"):
        for path in samples:
            with np.load(path) as sample:
                tokens, logits = sample["tokens"], sample["logits"]
                bucket = int(sample["rows"])
            name = by_tokens.get(tokens.tobytes())
            if name is None:
                return {"ok": False, "why": "%s holds tokens of no request "
                        "file" % path}
            # padded behind its last token to a multiple of REF_PAD, so
            # that the reference compiles a few lengths and not one a
            # prompt; causal mixers: the last real position is the same
            count = len(tokens)
            out = ref_model.forward(
                read, np.pad(tokens, (0, -count % REF_PAD)),
                position=count - 1)
            got.append(logits)
            ref.append(np.asarray(out["logits"]))
            files.append(os.path.basename(name))
            rows.append(bucket)
    verdict = compare_logits(config, np.stack(got), np.stack(ref))
    verdict.update(samples=len(got), files=files, dispatch_rows=rows)
    return verdict


#: a cut keeps at least so many of the 72 blocks (all alike: the
#: pattern's period is one)
LAYER_FLOOR = 4


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.falcon_h1 import checkpoint, network
    problems = []
    cfg = network.FalconH1Config.from_published(published_keys(config))
    layers = config["num_hidden_layers"]
    if config["model"]["layers"] != layers or layers < LAYER_FLOOR:
        problems.append("layers held: the model's %r, num_hidden_layers "
                        "%d (floor: %d)" % (config["model"]["layers"],
                                            layers, LAYER_FLOOR))
    held = layers * checkpoint.params_per_layer(cfg) \
        + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    if abs(held / 1e9 - config["model"]["params_billions_held"]) > 5e-4:
        problems.append("the tensors held are %.3f billion parameters, "
                        "the file says %r" % (
                            held / 1e9,
                            config["model"]["params_billions_held"]))
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
    if config["chunk_size"] != config["mamba_chunk_size"]:
        problems.append("a row is the model's own mamba_chunk_size")
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    if not loader["max_rows"] == batcher["batch"] == prefill["max_rows"] \
            == max(prefill["row_buckets"]):
        problems.append("the three stages disagree on the row cap")
    if batcher["row_buckets"] != prefill["row_buckets"]:
        problems.append("the batcher packs buckets the final stage has "
                        "not compiled")
    if not loader["chunk"] == prefill["chunk"] == config["chunk_size"]:
        problems.append("a row is chunk_size tokens in every stage")
    if prefill.get("family") != config["family"]:
        problems.append("the final stage's pipeline names another family")
    longest = max(prompt_lengths(config).values())
    if rows_of_tokens(longest, config["chunk_size"]) > loader["max_rows"]:
        problems.append("a prompt of %d tokens is more than one call of "
                        "%d rows" % (longest, loader["max_rows"]))
    return problems


def project_memory(config: dict, sharding) -> dict:
    """Bytes the largest row bucket takes on the device of ``sharding``
    (a described chip: the real stage program is compiled and nothing
    runs): the program's ``temporaries`` and ``arguments`` (the weights
    held and one packed batch) and the batches that may be ``waiting``
    on the device, one a slot of the ring in front of the stage."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.falcon_h1 import checkpoint, network
    cfg = network.FalconH1Config.from_published(published_keys(config))
    batcher, step = config["pipeline_config"]["pipeline"][-2:]
    rows = max(step["row_buckets"])
    params = {}
    for group, tensors in checkpoint.tensor_specs(cfg).items():
        made = {name: jax.ShapeDtypeStruct(
            spec.shape, getattr(jnp, spec.dtype), sharding=sharding)
            for name, spec in tensors.items()}
        params.update(made if group == "top" else {group: made})

    def of(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    memory = jax.jit(lambda p, t, m: network.forward(
        cfg, p, None, t, m[0], m[1], m[2])).lower(
        params, of((rows, cfg.chunk_size)),
        of((3, rows))).compile().memory_analysis()
    return {"rows": rows,
            "temporaries": memory.temp_size_in_bytes,
            "arguments": memory.argument_size_in_bytes,
            "waiting": batcher["num_shared_tensors"]
            * wire_bytes_per_row(config, config["pipeline_config"]) * rows}


# -- operations and bytes -------------------------------------------------


def _ssm_widths(config: dict):
    """(the scan's columns, the convolution's channels, in_proj's
    columns)."""
    d_ssm = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = d_ssm + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return d_ssm, conv_dim, d_ssm + conv_dim + config["mamba_n_heads"]


def ssm_flops_per_token(config: dict) -> int:
    d, q = config["hidden_size"], config["chunk_size"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    g, n = config["mamba_n_groups"], config["mamba_d_state"]
    d_ssm, conv_dim, into = _ssm_widths(config)
    proj = 2 * d * into + 2 * d_ssm * d
    conv = 2 * config["mamba_d_conv"] * conv_dim
    # blocked scan at chunk q: C.B scores, scores.x, the row's end state
    # and the incoming state's part
    scan = 2 * g * q * n + 2 * heads * q * p + 4 * heads * p * n
    return proj + conv + scan


def _attention_widths(config: dict):
    return (config["num_attention_heads"] * config["head_dim"],
            config["num_key_value_heads"] * config["head_dim"])


def attention_flops_per_token(config: dict, context: float) -> int:
    d = config["hidden_size"]
    hq, hk = _attention_widths(config)
    return int(2 * d * (hq + 2 * hk) + 2 * hq * d + 4 * context * hq)


def mlp_flops(config: dict) -> int:
    return 6 * config["hidden_size"] * config["intermediate_size"]


def flops_per_token(config: dict, context: float) -> int:
    return config["num_hidden_layers"] * (
        ssm_flops_per_token(config)
        + attention_flops_per_token(config, context) + mlp_flops(config))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    context."""
    return config["chunk_size"] * flops_per_token(config,
                                                  mean_context(config))


def mechanism_work(config: dict, mechanism: str, tokens: float,
                   dispatches: float):
    """(operations, bytes) one mechanism of every layer held needs for
    ``tokens`` valid tokens served in ``dispatches`` dispatches; a
    branch's bytes are its weights once a dispatch plus its input and
    output activations in bfloat16.

    ``ssm``: the state-space branch whole (two projections, convolution,
    scan, gate and norm). ``scan``: the recurrence alone, what the kernel
    ``ssd_scan`` is there for: a token's decay of a head's ``P x N``
    state, its update and its read-out (5 P N) and the skip term; x, z
    and y in bfloat16, B and C, the steps in float32, each once — less
    than any blocked form computes and less than the kernel reads (it
    takes z in float32), so the share cannot pass 100. ``flash``: the
    scores and values of every valid query against its own request's
    keys at or before it, at the mix's mean context; queries, keys,
    values in and the result out in bfloat16. ``mlp``: the gated MLP."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    act = 2 * 2 * d * tokens
    if mechanism == "ssm":
        d_ssm, _, into = _ssm_widths(config)
        weights = 2 * (d * into + d_ssm * d)
        return (layers * tokens * ssm_flops_per_token(config),
                layers * (weights * dispatches + act))
    if mechanism == "scan":
        heads, p = config["mamba_n_heads"], config["mamba_d_head"]
        wide = config["mamba_n_groups"] * config["mamba_d_state"]
        return (layers * tokens * heads * (5 * p * config["mamba_d_state"]
                                           + 2 * p),
                layers * tokens * (2 * 3 * heads * p + 2 * 2 * wide
                                   + 4 * heads))
    if mechanism == "flash":
        hq, hk = _attention_widths(config)
        return (layers * tokens * 4 * mean_context(config) * hq,
                layers * tokens * 2 * (2 * hq + 2 * hk))
    if mechanism == "mlp":
        return (layers * tokens * mlp_flops(config),
                layers * (2 * 3 * d * config["intermediate_size"]
                          * dispatches + act))
    raise ValueError("mechanism %r" % (mechanism,))
