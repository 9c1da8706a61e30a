"""The Nemotron-H family (Mamba-2 + sparse experts + grouped-query
attention, served as prefill over packed token rows), behind the
contract ``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "nemotron_h"``. The plain reference is
``benchmarks/references/nemotron_h.py``.

**The requests.** A request is a prompt: a ``.npy`` of int32 token ids,
uniform over the vocabulary. ``dataset`` says how many and how long:
short prompts with log-normal lengths and long ones uniform in a range,
all from ``dataset.seed``; the files are written once per checkout. A
*row* is ``chunk_size`` tokens, and a prompt of L tokens is
ceil(L / chunk_size) rows.

**The weights.** A recipe (seed, sizes, experts held), not a file of
values: the program makes each tensor on its device from the seed and
the tensor's name, and :func:`check_outputs` hands the reference the
same values through ``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and the router's
choices; each is recomputed by the reference on the chip, one block's
float32 weights at a time, and both go to
:func:`benchmarks.references.compare`. The reference is given the
program's router choices for those tokens (its own free choice is
checked beside: wherever the two differ, the program's weakest choice
must lie within ``ROUTE_SLACK`` of the reference's k-th best score), so
that the tolerance measures arithmetic and not which of two nearly
tied experts a rounding difference picked.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (PR 28, my chip runs; PERF.md
section 2): bfloat16 weights and activations through 14 blocks land at
3.2-3.9% (22 runs of the cell x 8 requests x 131,072 logits: the worst
of a million; half of it is the bfloat16 residual stream's rounding,
fourteen times); the same comparison with the experts' matrices
rounded through float8 (e4m3) lands at 6.2-6.8% and is not correct.
The scan's states carried in bfloat16 read 3.3-3.4%: that control
does not discriminate at this depth, the float8 one does.
"""

from __future__ import annotations

import glob
import math
import os
from typing import Dict, List

import numpy as np

#: bump when the prompt synthesis changes: it is part of the key
GENERATOR_VERSION = 1
#: the comparison's limit, as a share of the reference logits' spread
SHARE_OF_SPREAD = 0.05
#: the reference runs prompts padded to a multiple of this many tokens
REF_PAD = 512
#: how far below the reference's k-th best score (sigmoid + bias) the
#: program's weakest chosen expert may lie where the choices differ
ROUTE_SLACK = 0.02


# -- the prompts ----------------------------------------------------------


def prompt_lengths(config: dict) -> Dict[str, int]:
    """{file name: tokens}, from ``dataset`` alone."""
    spec = config["dataset"]
    rng = np.random.default_rng([int(spec["seed"]), GENERATOR_VERSION])
    short = spec["short"]
    lengths = np.exp(rng.normal(math.log(short["median"]), short["sigma"],
                                int(short["count"])))
    lengths = np.clip(np.rint(lengths), short["min"], short["max"])
    out = {"short-%03d.npy" % i: int(n) for i, n in enumerate(lengths)}
    long = spec["long"]
    for i, n in enumerate(rng.integers(long["min"], long["max"] + 1,
                                       int(long["count"]))):
        out["long-%03d.npy" % i] = int(n)
    return out


def dataset_key(config: dict) -> str:
    spec = config["dataset"]
    return "prompts-v%d-s%d-%dx%d-%dx%d-g%d" % (
        config["vocab_size"], spec["seed"], spec["short"]["count"],
        spec["short"]["median"], spec["long"]["count"],
        spec["long"]["max"], GENERATOR_VERSION)


def rows_of_tokens(tokens: int, chunk: int) -> int:
    return -(-int(tokens) // int(chunk))


def build(repo: str) -> None:
    """No child to run: the family builds nothing."""


def prepare_inputs(config: dict, data_base: str) -> dict:
    root = os.path.join(data_base, dataset_key(config))
    marker = os.path.join(root, "COMPLETE")
    lengths = prompt_lengths(config)
    if not os.path.exists(marker):
        os.makedirs(os.path.join(root, "requests"), exist_ok=True)
        for name, count in lengths.items():
            rng = np.random.default_rng(
                [int(config["dataset"]["seed"]), count,
                 int(name.split("-")[1].split(".")[0])])
            np.save(os.path.join(root, "requests", name),
                    rng.integers(0, config["vocab_size"], count,
                                 dtype=np.int32))
        with open(marker, "w") as f:
            f.write("ok\n")
    paths = {name: os.path.join(root, "requests", name) for name in lengths}
    chunk = int(config["chunk_size"])
    return {"short_files": [p for n, p in paths.items()
                            if n.startswith("short")],
            "long_files": [p for n, p in paths.items()
                           if n.startswith("long")],
            "rows_of": {paths[n]: rows_of_tokens(c, chunk)
                        for n, c in lengths.items()},
            "data_root": root, "sample": None}


def held_experts(config: dict) -> List[int]:
    share = config["experts_held"]
    return list(range(int(share["first"]),
                      int(share["first"]) + int(share["count"])))


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.nemotron_h import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k == "published" or not isinstance(v, (dict, list))}


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    import jax

    from benchmarks.references import compare, nemotron_h as reference
    from rnb_tpu.models.nemotron_h import checkpoint
    samples = sorted(glob.glob(os.path.join(result.log_dir,
                                            "prefill-sample-*.npz")))
    if not samples:
        return {"ok": False, "why": "the final stage kept no sample under "
                + result.log_dir}
    by_tokens = {}
    for path in inputs["short_files"] + inputs["long_files"]:
        by_tokens[np.load(path).tobytes()] = path
    cfg, _, held = checkpoint.load_recipe(ckpt_path)
    read = checkpoint.reference_reader(cfg, seed, devices[0])
    ref_model = reference.Reference(published_keys(config))
    got, ref, files, shortfall, differ, rows = [], [], [], 0.0, 0, []
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
            pad = -count % REF_PAD
            out = ref_model.forward(
                read, np.pad(tokens, (0, pad)), held=held,
                forced=np.pad(chosen, ((0, 0), (0, pad), (0, 0))),
                position=count - 1)
            got.append(logits)
            ref.append(np.asarray(out["logits"]))
            files.append(os.path.basename(name))
            rows.append(bucket)
            short = np.asarray(out["shortfall"])[:, :count]
            shortfall = max(shortfall, float(short.max()))
            differ += int((short > 0).sum())
    verdict = compare(np.stack(got), np.stack(ref), share_of_spread)
    verdict.update(samples=len(got), files=files, dispatch_rows=rows,
                   route_shortfall_max=shortfall, route_differ=differ,
                   limit=share_of_spread)
    if shortfall > ROUTE_SLACK:
        verdict["ok"] = False
        verdict["why"] = ("a router choice %.4f under the reference's k-th "
                          "best score, over %.4f" % (shortfall, ROUTE_SLACK))
    return verdict


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.nemotron_h import network
    problems = []
    cfg = network.NemotronHConfig.from_published(published_keys(config))
    if cfg.pattern != config["model"]["blocks"] \
            or len(cfg.pattern) != config["num_hidden_layers"]:
        problems.append("blocks held: the model's %r, the pattern's first "
                        "%d %r" % (config["model"]["blocks"],
                                   config["num_hidden_layers"], cfg.pattern))
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
    if config["experts_held"]["count"] != config["n_routed_experts"]:
        problems.append("experts_held.count is not n_routed_experts")
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    if not loader["max_rows"] == batcher["batch"] == prefill["max_rows"] \
            == max(prefill["row_buckets"]):
        problems.append("the three stages disagree on the row cap")
    if batcher["row_buckets"] != prefill["row_buckets"]:
        problems.append("the batcher packs buckets the final stage has "
                        "not compiled")
    if not loader["chunk"] == prefill["chunk"] == config["chunk_size"]:
        problems.append("a row is chunk_size tokens in every stage")
    return problems


def project_memory(config: dict, sharding) -> dict:
    """Bytes the largest row bucket takes on the device of ``sharding``
    (a described chip: the real stage program is compiled and nothing
    runs): the program's ``temporaries`` and ``arguments`` (the weights
    held and one packed batch) and the batches that may be ``waiting``
    on the device, one a slot of the ring in front of the stage."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import checkpoint, network
    cfg = network.NemotronHConfig.from_published(published_keys(config))
    batcher, step = config["pipeline_config"]["pipeline"][-2:]
    rows = max(step["row_buckets"])
    params = {}
    for group, tensors in checkpoint.tensor_specs(
            cfg, config["experts_held"]["count"]).items():
        made = {name: jax.ShapeDtypeStruct(
            spec.shape, getattr(jnp, spec.dtype), sharding=sharding)
            for name, spec in tensors.items()}
        params.update(made if group == "top" else {group: made})

    def of(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    memory = jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2])).lower(
        params, of((cfg.router_experts,)), of((rows, cfg.chunk_size)),
        of((3, rows))).compile().memory_analysis()
    return {"rows": rows,
            "temporaries": memory.temp_size_in_bytes,
            "arguments": memory.argument_size_in_bytes,
            "waiting": batcher["num_shared_tensors"]
            * wire_bytes_per_row(config, config["pipeline_config"]) * rows}


# -- operations and bytes -------------------------------------------------


def mean_context(config: dict) -> float:
    """Keys a query attends to, averaged over the tokens of the mix
    (``long_every`` from the bulk and open-loop mixes: one long prompt
    in eleven)."""
    lengths = prompt_lengths(config)
    short = np.array([n for k, n in lengths.items() if k[0] == "s"], float)
    long = np.array([n for k, n in lengths.items() if k[0] == "l"], float)
    every = float(config["dataset"].get("long_every", 11))
    pairs = ((every - 1) * (short * (short + 1) / 2).mean()
             + (long * (long + 1) / 2).mean())
    return float(pairs / ((every - 1) * short.mean() + long.mean()))


def mamba_flops_per_token(config: dict) -> int:
    d, q = config["hidden_size"], config["chunk_size"]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    inner = heads * p
    conv_dim = inner + 2 * g * n
    proj = 2 * d * (inner + conv_dim + heads) + 2 * inner * d
    conv = 2 * config["conv_kernel"] * conv_dim
    # blocked scan at chunk q: C.B scores, scores.x, the row's end
    # state and the incoming state's part
    scan = 2 * g * q * n + 2 * heads * q * p + 4 * heads * p * n
    return proj + conv + scan


def attention_flops_per_token(config: dict, context: float) -> int:
    d = config["hidden_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hk = config["num_key_value_heads"] * config["head_dim"]
    return int(2 * d * (hq + 2 * hk) + 2 * hq * d + 4 * context * hq)


def expert_flops(config: dict) -> int:
    return 4 * config["hidden_size"] * config["moe_intermediate_size"]


def experts_flops_per_token(config: dict, held_per_token: float) -> int:
    d = config["hidden_size"]
    return int(2 * d * config["published"]["n_routed_experts"]
               + 4 * d * config["moe_shared_expert_intermediate_size"]
               + held_per_token * expert_flops(config))


def _blocks(config: dict, kind: str) -> int:
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return pattern.count(kind)


def flops_per_token(config: dict, context: float,
                    held_per_token: float) -> int:
    return (_blocks(config, "M") * mamba_flops_per_token(config)
            + _blocks(config, "*") * attention_flops_per_token(config,
                                                               context)
            + _blocks(config, "E") * experts_flops_per_token(
                config, held_per_token))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the blocks held, at the mix's mean
    context and the mean share of a token's experts that is held."""
    held_per_token = config["num_experts_per_tok"] \
        * config["experts_held"]["count"] \
        / config["published"]["n_routed_experts"]
    return config["chunk_size"] * flops_per_token(
        config, mean_context(config), held_per_token)


def wire_bytes_per_row(config: dict, pipeline: dict) -> int:
    """A row's token ids and its valid-token count, int32."""
    return 4 * int(config["chunk_size"]) + 4


def mechanism_work(config: dict, mechanism: str, tokens: float,
                   held_assignments: float, dispatches: float):
    """(operations, bytes) the blocks of one mechanism (``ssd``: the M
    blocks; ``experts``: the E blocks; ``gmm``: the grouped-product
    kernel inside them) need for ``tokens`` valid tokens
    of which ``held_assignments`` (token, expert) pairs fell to held
    experts (over all E blocks), served in ``dispatches`` dispatches:
    bytes are each block's weights once a dispatch plus the block's
    input and output activations in bfloat16."""
    d = config["hidden_size"]
    act = 2 * 2 * d * tokens
    if mechanism == "ssd":
        blocks = _blocks(config, "M")
        inner = config["mamba_num_heads"] * config["mamba_head_dim"]
        conv_dim = inner + 2 * config["n_groups"] * config["ssm_state_size"]
        weights = 2 * (d * (inner + conv_dim + config["mamba_num_heads"])
                       + inner * d)
        return (blocks * tokens * mamba_flops_per_token(config),
                blocks * (weights * dispatches + act))
    if mechanism == "experts":
        blocks = _blocks(config, "E")
        held = config["experts_held"]["count"]
        weights = 2 * (2 * d * config["moe_intermediate_size"] * held
                       + 2 * d * config["moe_shared_expert_intermediate_size"]
                       + d * config["published"]["n_routed_experts"])
        ops = blocks * tokens * experts_flops_per_token(config, 0.0) \
            + held_assignments * expert_flops(config)
        return ops, blocks * (weights * dispatches + act)
    if mechanism == "gmm":
        # the grouped product alone: both projections of every held
        # (token, expert) pair; the held experts' two matrices once a
        # dispatch, each pair's rows in and out in bfloat16
        blocks = _blocks(config, "E")
        inner = config["moe_intermediate_size"]
        weights = 2 * 2 * d * inner * config["experts_held"]["count"]
        return (held_assignments * expert_flops(config),
                blocks * weights * dispatches
                + held_assignments * 2 * 2 * (d + inner))
    if mechanism == "flash":
        # scores and values of every query against its request's keys
        # at or before it (the mix's mean context); queries, keys,
        # values in and the result out in bfloat16
        blocks = _blocks(config, "*")
        hq = config["num_attention_heads"] * config["head_dim"]
        hk = config["num_key_value_heads"] * config["head_dim"]
        return (blocks * tokens * 4 * mean_context(config) * hq,
                blocks * tokens * 2 * (2 * hq + 2 * hk))
    raise ValueError("mechanism %r" % (mechanism,))
