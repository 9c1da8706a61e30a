"""The DeepSeek-V2 family (latent attention with decoupled YaRN rotary
keys; a leading dense layer, then softmax-routed, group-limited sparse
experts with shared ones; served as prefill over packed token rows),
behind the contract ``benchmarks/run.py`` calls. A configuration's file
names it: ``"family": "deepseek_v2"``. The plain reference is
``benchmarks/references/deepseek_v2.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
mix's mean context, the bytes a row ships) is ``families/nemotron_h.py``'s
and is called from there, so that the two families' cells draw the same
prompts through the same code.

**The weights.** A recipe (seed, sizes, experts held), not a file of
values: the program makes each tensor on its device from the seed and
the tensor's name, and :func:`check_outputs` hands the reference the
same values, in the published form, through
``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and the router's
choices; each is recomputed by the reference on the chip, one layer's
float32 weights at a time, and both go to
:func:`benchmarks.references.compare`. The reference is given the
program's router choices for those tokens (its own free choice is
checked beside: wherever the two differ, the program's weakest group
must lie within ``GROUP_SLACK`` of the reference's ``topk_group``-th
best group score, and its weakest chosen expert within ``ROUTE_SLACK``
of the reference's k-th best score among the groups used), so that the
tolerance measures arithmetic and not which of two nearly tied experts
a rounding difference picked.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (PR 33, my chip runs; PERF.md
section 2): bfloat16 weights and activations as the configuration
states them land at 2.6-3.0% through 1 + 4 layers (12 runs of the cell
x 8 requests x 102,400 logits) and at 3.1-3.5% through 1 + 6 (9 runs);
the same comparison with the feed-forwards' matrices rounded through
float8 (e4m3) lands at 26.4%, with every layer's matrices at 32.8%
(``scripts/prefill_control.py``; 31.6% and 35.7% through 1 + 6), and
is not correct.
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
REF_PAD = 512
#: how far below the reference's k-th best score (a softmax over 160:
#: a chosen expert's is about 0.02 to 0.1) the program's weakest chosen
#: expert may lie where the choices differ, and how far below its
#: ``topk_group``-th best group score the weakest group used. Between
#: two readings on the v5e (PR 33, my chip runs): as stated 0.0003 to
#: 0.0006 and 0.0004 to 0.0010; the float8 control 0.0074 to 0.0107
ROUTE_SLACK = 0.004
GROUP_SLACK = 0.004

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
build = _tokens.build
prepare_inputs = _tokens.prepare_inputs
held_experts = _tokens.held_experts
mean_context = _tokens.mean_context
wire_bytes_per_row = _tokens.wire_bytes_per_row


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in ("published", "rope_scaling")
            or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.deepseek_v2 import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    import jax

    from benchmarks.references import compare, deepseek_v2 as reference
    from rnb_tpu.models.deepseek_v2 import checkpoint
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
    got, ref, files, rows = [], [], [], []
    short = {"shortfall": 0.0, "group_shortfall": 0.0}
    differ = 0
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
            # prompt; causal attention: the last real position is the same
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
            for key in short:
                part = np.asarray(out[key])[:, :count]
                short[key] = max(short[key], float(part.max()))
            differ += int((np.asarray(out["shortfall"])[:, :count]
                           > 0).sum())
    verdict = compare(np.stack(got), np.stack(ref), share_of_spread)
    verdict.update(samples=len(got), files=files, dispatch_rows=rows,
                   route_shortfall_max=short["shortfall"],
                   group_shortfall_max=short["group_shortfall"],
                   route_differ=differ, limit=share_of_spread)
    if short["shortfall"] > ROUTE_SLACK \
            or short["group_shortfall"] > GROUP_SLACK:
        verdict["ok"] = False
        verdict["why"] = (
            "a router choice %.5f under the reference's k-th best score "
            "(limit %.5f) or a group %.5f under its topk_group-th best "
            "(limit %.5f)" % (short["shortfall"], ROUTE_SLACK,
                              short["group_shortfall"], GROUP_SLACK))
    return verdict


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.deepseek_v2 import network
    problems = []
    cfg = network.DeepseekV2Config.from_published(published_keys(config))
    if config["model"]["layers"] != config["num_hidden_layers"] \
            or cfg.num_expert_layers < 4:
        problems.append("layers held: the model's %r, num_hidden_layers "
                        "%d, of which %d expert layers (floor: 4)"
                        % (config["model"]["layers"],
                           config["num_hidden_layers"],
                           cfg.num_expert_layers))
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
    share = config["experts_held"]
    per_group = cfg.router_experts // cfg.n_group
    if share["count"] != config["n_routed_experts"]:
        problems.append("experts_held.count is not n_routed_experts")
    if share["count"] % per_group or share["first"] % per_group:
        problems.append("the share is not whole routing groups of %d"
                        % per_group)
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
    return problems


def project_memory(config: dict, sharding) -> dict:
    """Bytes the largest row bucket takes on the device of ``sharding``
    (a described chip: the real stage program is compiled and nothing
    runs): the program's ``temporaries`` and ``arguments`` (the weights
    held and one packed batch) and the batches that may be ``waiting``
    on the device, one a slot of the ring in front of the stage."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    cfg = network.DeepseekV2Config.from_published(published_keys(config))
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


def _layers(config: dict):
    """(dense layers, expert layers) held here."""
    dense = min(config["first_k_dense_replace"], config["num_hidden_layers"])
    return dense, config["num_hidden_layers"] - dense


def _attention_widths(config: dict):
    """(query-key columns, value columns) of all heads together."""
    heads = config["num_attention_heads"]
    return (heads * (config["qk_nope_head_dim"]
                     + config["qk_rope_head_dim"]),
            heads * config["v_head_dim"])


def attention_params(config: dict) -> int:
    """The five projections of one layer's latent attention."""
    d = config["hidden_size"]
    qk, v = _attention_widths(config)
    rank = config["kv_lora_rank"]
    return (d * config["q_lora_rank"] + config["q_lora_rank"] * qk
            + d * (rank + config["qk_rope_head_dim"])
            + rank * (config["num_attention_heads"]
                      * config["qk_nope_head_dim"] + v)
            + v * d)


def attention_score_flops_per_token(config: dict, context: float) -> float:
    """Scores and values of one query against ``context`` keys, in the
    expanded form (192 + 128 columns a head)."""
    qk, v = _attention_widths(config)
    return 2.0 * context * (qk + v)


def mlp_flops(config: dict, inner: int) -> int:
    return 6 * config["hidden_size"] * inner


def expert_flops(config: dict) -> int:
    return mlp_flops(config, config["moe_intermediate_size"])


def shared_width(config: dict) -> int:
    return config["n_shared_experts"] * config["moe_intermediate_size"]


def experts_flops_per_token(config: dict, held_per_token: float) -> float:
    return 2 * config["hidden_size"] \
        * config["published"]["n_routed_experts"] \
        + mlp_flops(config, shared_width(config)) \
        + held_per_token * expert_flops(config)


def flops_per_token(config: dict, context: float,
                    held_per_token: float) -> int:
    dense, sparse = _layers(config)
    return int(
        (dense + sparse) * (2 * attention_params(config)
                            + attention_score_flops_per_token(config,
                                                              context))
        + dense * mlp_flops(config, config["intermediate_size"])
        + sparse * experts_flops_per_token(config, held_per_token))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    context and the mean share of a token's experts that is held."""
    held_per_token = config["num_experts_per_tok"] \
        * config["experts_held"]["count"] \
        / config["published"]["n_routed_experts"]
    return config["chunk_size"] * flops_per_token(
        config, mean_context(config), held_per_token)


def mechanism_work(config: dict, mechanism: str, tokens: float,
                   held_assignments: float, dispatches: float):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens of which ``held_assignments`` (token, expert) pairs fell to
    held experts (over all expert layers), served in ``dispatches``
    dispatches. ``attn``: every layer's latent attention (projections,
    scores and values); ``flash``: the scores and values alone, what
    the flash kernel computes; ``experts``: every layer's feed-forward
    (the dense layer's MLP, the routers, the shared and the held routed
    experts); ``gmm``: the grouped products inside them. Bytes are each
    layer's weights once a dispatch plus its input and output
    activations in bfloat16."""
    d = config["hidden_size"]
    dense, sparse = _layers(config)
    layers = dense + sparse
    act = 2 * 2 * d * tokens
    qk, v = _attention_widths(config)
    scores = tokens * attention_score_flops_per_token(
        config, mean_context(config))
    if mechanism == "attn":
        return (layers * (tokens * 2 * attention_params(config) + scores),
                layers * (2 * attention_params(config) * dispatches + act))
    if mechanism == "flash":
        # every query against its request's keys at or before it (the
        # mix's mean context); queries, keys, values in and the result
        # out in bfloat16, keys and values expanded to every head
        return layers * scores, layers * tokens * 2 * (2 * qk + 2 * v)
    inner = config["moe_intermediate_size"]
    held = config["experts_held"]["count"]
    if mechanism == "experts":
        weights = 2 * (
            dense * 3 * d * config["intermediate_size"]
            + sparse * (3 * d * inner * held + 3 * d * shared_width(config)
                        + d * config["published"]["n_routed_experts"]))
        ops = tokens * (dense * mlp_flops(config,
                                          config["intermediate_size"])
                        + sparse * experts_flops_per_token(config, 0.0)) \
            + held_assignments * expert_flops(config)
        return ops, weights * dispatches + layers * act
    if mechanism == "gmm":
        # the grouped products alone: the three projections of every
        # held (token, expert) pair; the held experts' matrices once a
        # dispatch, each pair's rows in and out in bfloat16
        return (held_assignments * expert_flops(config),
                sparse * 2 * 3 * d * inner * held * dispatches
                + held_assignments * 2 * 2 * (d + inner))
    raise ValueError("mechanism %r" % (mechanism,))
