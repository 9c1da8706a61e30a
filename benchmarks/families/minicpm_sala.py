"""The MiniCPM-SALA family (block-selected sparse attention in one layer
of four, lightning linear attention in the other three, a dense gated
MLP in each; served as prefill over packed token rows), behind the
contract ``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "minicpm_sala"``. The plain reference is
``benchmarks/references/minicpm_sala.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
bytes a row ships) is ``families/nemotron_h.py``'s and is called from
there, so that the token families' cells draw prompts through one code.

**The weights.** A recipe (seed, sizes; the family holds no experts),
not a file of values: the program makes each tensor on its device from
the seed and the tensor's name, and :func:`check_outputs` hands the
reference the same values through ``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and, in the place
of router choices, the key blocks every query of the request chose in
each sparse layer; each is recomputed by the reference on the chip, one
layer's float32 weights at a time, and both go to
:func:`benchmarks.references.compare`. The reference is given the
program's chosen blocks (its own free choice is checked beside:
wherever the two differ, the program's weakest chosen block must lie
within ``BLOCK_SLACK`` of the reference's ``topk``-th best block score,
and a block a query may not choose, or a set of another size, fails),
so that the tolerance measures arithmetic and not which of two nearly
tied blocks a rounding difference picked.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (PR 35, my chip runs; PERF.md
section 2): bfloat16 weights and activations as the configuration
states them land at 2.5-3.0% through 4 layers (22 runs of the cell x 8
requests of 4k-16k tokens x 73,448 logits; 2.7% in
``scripts/prefill_control.py``); the same comparison with every stored
matrix rounded through float8 (e4m3) lands at 15.4% and is not
correct. The lightning layers' states carried in bfloat16 read 2.6%:
that arm does not discriminate at this depth, the float8 one does.
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
#: the reference runs prompts padded to a multiple of this many tokens
REF_PAD = 2048
#: how far below the reference's topk-th best block score (a sum of 16
#: heads' softmax shares over up to 1,022 windows: 0.01 to 0.05 for a
#: block near the cut) the program's weakest chosen block may lie where
#: the choices differ. Between two readings on the v5e (PR 35, my chip
#: runs): as stated 0.00008 to 0.00013 over 22 runs of the cell; the
#: float8 control 0.00165
BLOCK_SLACK = 0.0005

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
wire_bytes_per_row = _tokens.wire_bytes_per_row


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "minicpm_sala")):
        raise SystemExit("benchmarks/families/minicpm_sala.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "minicpm_sala: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in ("published", "sparse_config", "mixer_types")
            or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.minicpm_sala import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed)
    return path, None


#: the lower-precision control's prompts (``scripts/prefill_control.py``):
#: one under ``dense_len`` and one over it, in one dispatch of 128 rows
CONTROL_LENGTHS = (4500, 9800)


def unpack_choices(config: dict, packed, count: int, padded: int = None):
    """What a sample keeps of a request's choices (the program's
    ``network.request_choices``: (sparse layers, tokens, Hk, bytes), the
    block axis packed to bits) -> bool (sparse layers, ``padded`` tokens,
    Hk, blocks of ``padded`` tokens), false behind the request's
    ``count`` tokens; ``padded`` defaults to ``count``."""
    block_size = config["sparse_config"]["block_size"]
    padded = count if padded is None else padded
    blocks = -(-count // block_size)
    own = np.unpackbits(packed, axis=-1, bitorder="little")[..., :blocks]
    return np.pad(own.astype(bool), (
        (0, 0), (0, padded - count), (0, 0),
        (0, -(-padded // block_size) - blocks)))


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    import jax

    from benchmarks.references import compare, minicpm_sala as reference
    from rnb_tpu.models.minicpm_sala import checkpoint
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
    got, ref, files, rows, shortfall, differ = [], [], [], [], 0.0, 0
    with jax.default_matmul_precision("highest"):
        for path in samples:
            with np.load(path) as sample:
                tokens, logits = sample["tokens"], sample["logits"]
                chosen, bucket = sample["chosen"], int(sample["rows"])
            name = by_tokens.get(tokens.tobytes())
            if name is None:
                return {"ok": False, "why": "%s holds tokens of no request "
                        "file" % path}
            # padded behind its last token to a multiple of REF_PAD, so
            # that the reference compiles a few lengths and not one a
            # prompt; causal mixers: the last real position is the same
            count = len(tokens)
            padded = count + -count % REF_PAD
            out = ref_model.forward(
                read, np.pad(tokens, (0, padded - count)),
                forced=unpack_choices(config, chosen, count, padded),
                position=count - 1, length=count)
            got.append(logits)
            ref.append(np.asarray(out["logits"]))
            files.append(os.path.basename(name))
            rows.append(bucket)
            short = np.asarray(out["shortfall"])[:, :count]
            shortfall = max(shortfall, float(short.max()))
            differ += int((short > 0).sum())
    verdict = compare(np.stack(got), np.stack(ref), share_of_spread)
    verdict.update(samples=len(got), files=files, dispatch_rows=rows,
                   block_shortfall_max=shortfall, block_differ=differ,
                   limit=share_of_spread)
    if shortfall > BLOCK_SLACK:
        verdict["ok"] = False
        verdict["why"] = ("a chosen key block %.5f under the reference's "
                          "topk-th best block score, over %.5f"
                          % (shortfall, BLOCK_SLACK))
    return verdict


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.minicpm_sala import network
    problems = []
    cfg = network.MinicpmSalaConfig.from_published(published_keys(config))
    published = config["published"]
    layers = config["num_hidden_layers"]
    if config["model"]["layers"] != layers \
            or config["mixer_types"] != published["mixer_types"][:layers] \
            or layers < 4:
        problems.append("layers held: the model's %r, num_hidden_layers "
                        "%d (floor: one whole period of 4), mixer_types "
                        "%r" % (config["model"]["layers"], layers,
                                config["mixer_types"]))
    held = len(cfg.layers_of(network.SPARSE)) / layers
    whole = published["mixer_types"].count(network.SPARSE) \
        / published["num_hidden_layers"]
    if held != whole:
        problems.append("sparse layers: %.3f of those held, %.3f of the "
                        "published ones" % (held, whole))
    for key in config["reduced"]:
        if published.get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
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

    from rnb_tpu.models.minicpm_sala import checkpoint, network
    cfg = network.MinicpmSalaConfig.from_published(published_keys(config))
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


def request_reads(config: dict, length: int):
    """-> (keys, windows): over the queries of one request of ``length``
    tokens, the keys they attend to — all causal ones under ``dense_len``
    or with ``topk`` blocks or fewer, else ``topk`` blocks, the query's
    own up to itself: the *chosen* keys, never the dense triangle — and
    the compressed keys they score (none where nothing is selected)."""
    sparse = config["sparse_config"]
    at = np.arange(int(length), dtype=np.int64)
    if length < sparse["dense_len"]:
        return int((at + 1).sum()), 0
    size, topk = sparse["block_size"], sparse["topk"]
    keys = np.where(at // size + 1 <= topk, at + 1,
                    (topk - 1) * size + at % size + 1)
    windows = np.maximum((at + 1 - sparse["kernel_size"])
                         // sparse["kernel_stride"] + 1, 0)
    return int(keys.sum()), int(windows.sum())


def mean_reads(config: dict):
    """(keys, windows) a query reads, averaged over the tokens of the
    mix (``long_every``: one long prompt in eleven)."""
    lengths = prompt_lengths(config)
    every = float(config["dataset"].get("long_every", 11))
    sums = {"s": np.zeros(3), "l": np.zeros(3)}
    for name, count in lengths.items():
        sums[name[0]] += (count,) + request_reads(config, count)
    short = sums["s"] / sum(n[0] == "s" for n in lengths)
    long = sums["l"] / sum(n[0] == "l" for n in lengths)
    total = (every - 1) * short + long
    return float(total[1] / total[0]), float(total[2] / total[0])


def mean_context(config: dict) -> float:
    """Keys a query of a sparse layer attends to, averaged over the
    tokens of the mix."""
    return mean_reads(config)[0]


def _wide(config: dict) -> int:
    return config["num_attention_heads"] * config["head_dim"]


def mlp_flops(config: dict) -> int:
    return 6 * config["hidden_size"] * config["intermediate_size"]


def sparse_proj_flops(config: dict) -> int:
    narrow = config["num_key_value_heads"] * config["head_dim"]
    return 2 * config["hidden_size"] * (3 * _wide(config) + 2 * narrow)


def sparse_read_flops(config: dict, keys: float, windows: float) -> float:
    return 4.0 * keys * _wide(config) + 2.0 * windows * _wide(config)


def lightning_flops(config: dict) -> int:
    heads, dim = config["lightning_nh"], config["lightning_head_dim"]
    q = config["chunk_size"]
    return 2 * config["hidden_size"] * 5 * heads * dim \
        + heads * (4 * q * dim + 4 * dim * dim)


def _layers(config: dict):
    """(sparse layers, lightning layers) held here."""
    kinds = config["mixer_types"][:config["num_hidden_layers"]]
    return kinds.count("minicpm4"), kinds.count("lightning-attn")


def flops_per_token(config: dict, keys: float, windows: float) -> int:
    sparse, lightning = _layers(config)
    return int(
        sparse * (sparse_proj_flops(config)
                  + sparse_read_flops(config, keys, windows))
        + lightning * lightning_flops(config)
        + (sparse + lightning) * mlp_flops(config))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    chosen keys and seen windows a query."""
    return config["chunk_size"] * flops_per_token(config,
                                                  *mean_reads(config))


def mechanism_work(config: dict, mechanism: str, tokens: float,
                   dispatches: float, chosen_keys: float = None):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens served in ``dispatches`` dispatches. ``sparse_attn``: the
    scores and values of every query over the keys of the blocks it
    chose (``chosen_keys`` a (query, key-value head) pair where the run
    counted them, else the mix's mean), what the kernel
    ``block_sparse_attention`` computes; queries, keys, values in and
    the result out in bfloat16, and the block mask. ``ssd``: the
    lightning layers' mixers whole (five projections and the blocked
    scan); bytes are each layer's weights once a dispatch plus its input
    and output activations in bfloat16."""
    d = config["hidden_size"]
    sparse, lightning = _layers(config)
    if mechanism == "sparse_attn":
        keys = mean_context(config) if chosen_keys is None else chosen_keys
        narrow = config["num_key_value_heads"] * config["head_dim"]
        blocks = tokens / dispatches / config["sparse_config"]["block_size"]
        return (sparse * tokens * 4.0 * keys * _wide(config),
                sparse * tokens * (2 * (2 * _wide(config) + 2 * narrow)
                                   + 2 * config["num_key_value_heads"]
                                   * blocks))
    if mechanism == "ssd":
        wide = config["lightning_nh"] * config["lightning_head_dim"]
        return (lightning * tokens * lightning_flops(config),
                lightning * (2 * 5 * d * wide * dispatches
                             + 2 * 2 * d * tokens))
    raise ValueError("mechanism %r" % (mechanism,))
