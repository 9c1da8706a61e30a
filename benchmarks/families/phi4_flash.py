"""The Phi-4-mini-flash family (Mamba-1 layers alternating with
differential attention under a window, one full differential attention
layer, then a cross-decoder of Gated Memory Units and cross-attention
that reads that layer's keys and values and the last Mamba layer's scan
output; served as prefill over packed token rows *with the prefill
exit*: the cross-decoder runs on one line a request), behind the
contract ``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "phi4_flash"``. The plain reference is
``benchmarks/references/phi4_flash.py``, which runs every layer over
every position.

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
by the reference on the chip, one layer's float32 weights at a time, and
both go to :func:`benchmarks.references.compare`. Nothing is chosen in
this family, so nothing is handed over but the tokens. Since the
reference takes no exit, the comparison is also the test of the
program's.

**Tolerance.** Two limits on the same samples, each between two readings
on the v5e (PR 59, my chip runs; PERF.md section 2 and the
configuration's ``precision_readings``): bfloat16 weights and
activations as the configuration states them, and the same comparison
with every stored matrix of the layers rounded through float8 (e4m3),
which is not correct. ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread for the worst logit, the limit of every token family here;
``RMS_SHARE_OF_SPREAD`` holds the differences' root mean square. The
scans' states carried between rows in bfloat16 are recorded beside
(``CONTROL_MAY_PASS``).

**Operations** are counted on the exit's path: layers 0 .. n/2 + 1 a
token, the cross-decoder a request (:func:`flops_per_row` spreads a
request's part over the mix's mean rows a request). A count of every
layer a token would read the utilisation near 170% of the peak.
"""

from __future__ import annotations

import glob
import os
from typing import List

import numpy as np

from benchmarks import manifest

_tokens = manifest.load_family("nemotron_h")
_windows = manifest.load_family("exaone_moe")

#: the comparison's limit, as a share of the reference logits' spread
SHARE_OF_SPREAD = 0.05
#: the second limit: the root mean square of the differences over every
#: compared logit, as a share of the same spread
RMS_SHARE_OF_SPREAD = 0.012
#: the reference runs prompts padded to a multiple of this many tokens:
#: four lengths for prompts of 4k to 16k, a set of six programs each
#: (the other families' 512 would compile one set a sample)
REF_PAD = 4096
#: the control arms that are recorded whether they pass or not
#: (``scripts/prefill_control.py``): every other arm must fail
CONTROL_MAY_PASS = ("state_bfloat16",)
#: the control's prompts: the longest past two windows, so that
#: ``window_off`` reads keys the window hides
CONTROL_LENGTHS = (300, 1190, 700, 2400)

#: the Mamba mixer's sizes where a configuration's file has no such key
#: (``assumed``), and the operations of a (channel, state) a token
MAMBA = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}
SCAN_OPS_PER_STATE = 7

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
wire_bytes_per_row = _tokens.wire_bytes_per_row
mean_context = _tokens.mean_context
#: keys a query of a sliding layer reads, over the mix: ``min(t + 1,
#: sliding_window)`` (K-EXAONE's family file counts it, by the same key)
mean_window_keys = _windows.mean_window_keys


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "phi4_flash")):
        raise SystemExit("benchmarks/families/phi4_flash.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "phi4_flash: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k == "published" or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.phi4_flash import checkpoint
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

    from benchmarks.references import phi4_flash as reference
    from rnb_tpu.models.phi4_flash import checkpoint
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


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.phi4_flash import checkpoint, network
    problems = []
    layers = config["num_hidden_layers"]
    if layers % 4:
        problems.append("num_hidden_layers %d: the layers' law wants a "
                        "multiple of 4 (two halves of pairs)" % layers)
        return problems
    cfg = network.Phi4FlashConfig.from_published(published_keys(config))
    if config["model"]["layers"] != layers:
        problems.append("layers held: the model's %r, num_hidden_layers "
                        "%d" % (config["model"]["layers"], layers))
    held = checkpoint.total_params(cfg)
    if abs(held / 1e9 - config["model"]["params_billions_held"]) > 5e-3:
        problems.append("the tensors held are %.3f billion parameters, "
                        "the file says %r" % (
                            held / 1e9,
                            config["model"]["params_billions_held"]))
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
    for key, value in MAMBA.items():
        if config.get(key, value) != value:
            problems.append("%s %r: the family's files count %d"
                            % (key, config[key], value))
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

    from rnb_tpu.models.phi4_flash import checkpoint, network
    cfg = network.Phi4FlashConfig.from_published(published_keys(config))
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


def _sizes(config: dict):
    """(hidden, d_inner, states, taps, dt_rank, head size, Q's columns,
    K's (= V's) columns)."""
    d = config["hidden_size"]
    mamba = {key: config.get(key, value) for key, value in MAMBA.items()}
    head = d // config["num_attention_heads"]
    return (d, mamba["mamba_expand"] * d, mamba["mamba_d_state"],
            mamba["mamba_d_conv"], -(-d // 16), head,
            config["num_attention_heads"] * head,
            config["num_key_value_heads"] * head)


def layer_counts(config: dict):
    """(Mamba layers, window layers, (GMU, cross) pairs): with the one
    full layer, all of them."""
    half = config["num_hidden_layers"] // 2
    return half // 2 + 1, half // 2, (half - 2) // 2


def mean_request(config: dict):
    """(tokens, rows) of the mix's mean request."""
    lengths = prompt_lengths(config)
    chunk = config["chunk_size"]
    every = float(config["dataset"].get("long_every", 11))

    def mean(of):
        short = np.mean([of(n) for k, n in lengths.items() if k[0] == "s"])
        long = np.mean([of(n) for k, n in lengths.items() if k[0] == "l"])
        return float(((every - 1) * short + long) / every)
    return mean(float), mean(lambda n: rows_of_tokens(n, chunk))


def mlp_flops(config: dict) -> int:
    return 6 * config["hidden_size"] * config["intermediate_size"]


def scan_flops_per_token(config: dict) -> int:
    _, di, n, *_ = _sizes(config)
    return di * (SCAN_OPS_PER_STATE * n + 2)


def mamba_flops_per_token(config: dict) -> int:
    d, di, n, taps, rank, *_ = _sizes(config)
    proj = 2 * d * 2 * di + 2 * di * (rank + 2 * n) + 2 * rank * di \
        + 2 * di * d
    return proj + 2 * taps * di + scan_flops_per_token(config) + 4 * di


def pair_flops(config: dict) -> int:
    """Operations a (query, key) over all heads: 384 a head at 64."""
    head = _sizes(config)[5]
    return config["num_attention_heads"] * 6 * head


def attention_flops_per_token(config: dict, keys: float) -> int:
    d, *_, hq, hk = _sizes(config)
    return int(2 * d * (hq + 2 * hk) + 2 * hq * d
               + keys * pair_flops(config))


def flops_per_token(config: dict, context: float,
                    window_keys: float) -> int:
    """Layers 0 .. n/2 + 1: what runs over every token."""
    mambas, windows, _ = layer_counts(config)
    return mambas * mamba_flops_per_token(config) \
        + windows * attention_flops_per_token(config, window_keys) \
        + attention_flops_per_token(config, context) \
        + (mambas + windows + 1) * mlp_flops(config)


def flops_per_request(config: dict, keys: float) -> int:
    """The cross-decoder on a request's one line."""
    d, di, *_, hq, _ = _sizes(config)
    pairs = layer_counts(config)[2]
    gmu = 2 * d * di + di + 2 * di * d
    cross = 2 * 2 * d * hq + keys * pair_flops(config)
    return int(pairs * (gmu + cross + 2 * mlp_flops(config)))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers that run a token, at the
    mix's mean contexts, and a row's share of its request's line through
    the cross-decoder."""
    tokens, rows = mean_request(config)
    return int(config["chunk_size"] * flops_per_token(
        config, mean_context(config), mean_window_keys(config))
        + flops_per_request(config, tokens) / rows)


def mechanism_work(config: dict, mechanism: str, tokens: float,
                   dispatches: float):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens served in ``dispatches`` dispatches, over the layers that run
    a token.

    ``selective_scan``: the recurrence alone, what the kernel
    ``selective_scan`` is there for: a token's decay, update and
    read-out of every (channel, state) and the skip term; x, z and y in
    bfloat16 and dt in float32, B and C in float32, each once — the same
    work whatever the kernel does inside. ``window_attn``: the sliding
    layers' two softmaxes, every valid query against the at most
    ``sliding_window`` keys of its request it may read; ``diff_attn``:
    the full layer's, against the whole context at or before it; both
    with queries, keys, values in and the result out once in bfloat16.
    ``mlp``: the gated MLPs, each layer's weights once a dispatch plus
    its input and output activations. ``conv``: the convolutions' taps,
    bias and SiLU; the channels in and out in bfloat16."""
    d, di, n, taps, _, _, hq, hk = _sizes(config)
    mambas, windows, _ = layer_counts(config)
    moved = tokens * 2 * (2 * hq + 2 * hk)
    if mechanism == "selective_scan":
        return (mambas * tokens * scan_flops_per_token(config),
                mambas * tokens * (di * (2 + 4 + 2 + 2) + 2 * 4 * n))
    if mechanism == "window_attn":
        return (windows * tokens * mean_window_keys(config)
                * pair_flops(config), windows * moved)
    if mechanism == "diff_attn":
        return (tokens * mean_context(config) * pair_flops(config), moved)
    if mechanism == "mlp":
        layers = mambas + windows + 1
        return (layers * tokens * mlp_flops(config),
                layers * (2 * 3 * d * config["intermediate_size"]
                          * dispatches + 2 * 2 * d * tokens))
    if mechanism == "conv":
        return (mambas * tokens * di * (2 * taps + 5),
                mambas * tokens * di * 2 * 2)
    raise ValueError("mechanism %r" % (mechanism,))
