"""The Kimi-Linear family (Kimi Delta Attention, a delta rule whose gate
is a vector, one decay a key channel, in three layers of four; latent
attention without positions in the fourth; a dense MLP in the first
layer and sigmoid-routed gated experts with one shared expert behind
it; served as prefill over packed token rows), behind the contract
``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "kimi_linear"``. The plain reference is
``benchmarks/references/kimi_linear.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
mix's mean context, the bytes a row ships) is ``families/nemotron_h.py``'s
and is called from there, so that the token families' cells draw prompts
through one code.

**The weights.** A recipe (seed, sizes, experts held), not a file of
values: the program makes each tensor on its device from the seed and
the tensor's name, and :func:`check_outputs` hands the reference the
same values, in the published form, through
``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and the router's
choices; each is recomputed by the reference on the chip, one layer's
float32 weights at a time (the routed experts 32 at a time, each
visited once over the tokens that chose it; the delta rule token by
token), and both go to :func:`benchmarks.references.compare`. The
reference is given the program's router choices for those tokens (its
own free choice is checked beside: wherever the two differ, the
program's weakest chosen expert must lie within ``ROUTE_SLACK`` of the
reference's k-th best ``s + b``), so that the tolerance measures
arithmetic and not which of two nearly tied experts a rounding
difference picked.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (PR 49, my chip runs; PERF.md
section 2): bfloat16 weights and activations as the configuration
states them, and the same comparison with every stored matrix rounded
through float8 (e4m3) (``scripts/prefill_control.py``), which is not
correct; nor is a scalar gate in the vector's place, nor a rotated
latent attention.
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
#: how far below the reference's k-th best ``s + b`` (a sigmoid's score
#: plus the correction bias: the chosen ones lie about 0.7 to 0.95) the
#: program's weakest chosen expert may lie where the choices differ.
#: Between two readings on the v5e (PR 49, my chip runs; PERF.md
#: section 2)
ROUTE_SLACK = 0.02
#: the lower-precision control's prompts (``scripts/prefill_control.py``):
#: two requests in one dispatch of 128 rows
CONTROL_LENGTHS = (4500, 9800)
#: the control arms that are recorded whether or not they read over the
#: limit; every other arm has to fail (``scripts/prefill_control.py``)
CONTROL_MAY_PASS = ("state_bfloat16",)

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
held_experts = _tokens.held_experts
mean_context = _tokens.mean_context
wire_bytes_per_row = _tokens.wire_bytes_per_row


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "kimi_linear")):
        raise SystemExit("benchmarks/families/kimi_linear.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "kimi_linear: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in ("published", "linear_attn_config")
            or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.kimi_linear import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def pad_choices(config: dict, chosen, pad: int):
    """``chosen`` (expert layers, tokens, k) with ``pad`` tokens behind: their
    choices go round the router's experts, so that the reference's pad
    tokens load every expert alike (all on expert 0 they would set the
    room it gives every expert)."""
    layers, _, k = chosen.shape
    spread = (np.arange(pad)[:, None] * k + np.arange(k)) \
        % config["published"]["num_experts"]
    return np.concatenate([chosen, np.broadcast_to(
        spread.astype(chosen.dtype), (layers, pad, k))], axis=1)


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    import jax

    from benchmarks.references import compare, kimi_linear as reference
    from rnb_tpu.models.kimi_linear import checkpoint
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
                forced=pad_choices(config, chosen, pad),
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
        verdict["why"] = ("a router choice %.5f under the reference's k-th "
                          "best score, over %.5f" % (shortfall, ROUTE_SLACK))
    return verdict


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.kimi_linear import network
    problems = []
    cfg = network.KimiLinearConfig.from_published(published_keys(config))
    layers = config["num_hidden_layers"]
    kinds = [cfg.is_attention(i) for i in range(layers)]
    if config["model"]["layers"] != layers or cfg.num_expert_layers < 4 \
            or kinds[:4] != [False, False, False, True]:
        problems.append("layers held: the model's %r, num_hidden_layers %d "
                        "of which %d expert layers (floor: 4), the first "
                        "period %r (KDA KDA KDA MLA)"
                        % (config["model"]["layers"], layers,
                           cfg.num_expert_layers, kinds[:4]))
    linear = config["published"].get("linear_attn_config", {})
    for key in ("kda_layers", "full_attn_layers"):
        if [i for i in linear.get(key, ()) if i <= layers] \
                != config["linear_attn_config"][key]:
            problems.append("%s is not the published list's entries up to "
                            "layer %d" % (key, layers))
    for key in ("num_heads", "head_dim", "short_conv_kernel_size"):
        if linear.get(key) != config["linear_attn_config"][key]:
            problems.append("linear_attn_config.%s is a width: as "
                            "published" % key)
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
            problems.append("reduced key %s: \"published\" has to hold "
                            "the source's value, which differs" % key)
    share = config["experts_held"]
    if share["count"] != config["num_experts"]:
        problems.append("experts_held.count is not num_experts")
    if share["first"] + share["count"] > cfg.router_experts \
            or cfg.router_experts % share["count"]:
        problems.append("the share is not one of equal shares of the "
                        "router's %d experts" % cfg.router_experts)
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    if not loader["max_rows"] == batcher["batch"] == prefill["max_rows"] \
            == max(prefill["row_buckets"]):
        problems.append("the three stages disagree on the row cap")
    if batcher["row_buckets"] != prefill["row_buckets"]:
        problems.append("the batcher packs buckets the final stage has "
                        "not compiled")
    if not loader["chunk"] == prefill["chunk"] == config["chunk_size"]:
        problems.append("a row is chunk_size tokens in every stage")
    if config["chunk_size"] & (config["chunk_size"] - 1):
        problems.append("chunk_size is no power of two: the delta rule's "
                        "triangular solve and its pairs halve a row")
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

    from rnb_tpu.models.kimi_linear import checkpoint, network
    cfg = network.KimiLinearConfig.from_published(published_keys(config))
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

#: the inner width of the two low-rank pairs (``assumed.low_rank``)
LOW_RANK = 128


def _layers(config: dict):
    """(KDA layers, attention layers, dense layers, expert layers) held
    here."""
    linear = config["linear_attn_config"]
    dense = config["first_k_dense_replace"]
    return (len(linear["kda_layers"]), len(linear["full_attn_layers"]),
            dense, config["num_hidden_layers"] - dense)


def _kda_width(config: dict) -> int:
    """The columns of all heads' ``q`` (or ``k``, or ``v``)."""
    linear = config["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def kda_params(config: dict) -> int:
    """The products and the convolutions of one KDA mixer: ``q``, ``k``,
    ``v`` and the way back, the step's column a head, the two low-rank
    pairs."""
    d, width = config["hidden_size"], _kda_width(config)
    linear = config["linear_attn_config"]
    return d * 3 * width + linear["short_conv_kernel_size"] * 3 * width \
        + d * linear["num_heads"] \
        + 2 * (d * LOW_RANK + LOW_RANK * width) + width * d


def delta_rule_flops_per_token(config: dict) -> int:
    """The recurrence's own, one layer: a head's state of ``Dk x Dv`` is
    decayed (1), read by the key (2), written by the outer product (2)
    and read by the query (2). Any blocked form computes more; the share
    is of this."""
    linear = config["linear_attn_config"]
    return 7 * linear["num_heads"] * linear["head_dim"] ** 2


def kda_flops_per_token(config: dict) -> int:
    return 2 * kda_params(config) + delta_rule_flops_per_token(config)


def attention_params(config: dict) -> int:
    """The four products of one latent-attention layer as published
    (192 query columns a head, not the 256 the program stores)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, value = config["kv_lora_rank"], config["v_head_dim"]
    return d * heads * (nope + rot) + d * (rank + rot) \
        + rank * heads * (nope + value) + heads * value * d


def attention_score_flops_per_token(config: dict, context: float) -> float:
    """Scores and values of one query against ``context`` keys."""
    return 2.0 * context * config["num_attention_heads"] \
        * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
           + config["v_head_dim"])


def mlp_flops(config: dict, inner: int) -> int:
    return 6 * config["hidden_size"] * inner


def expert_flops(config: dict) -> int:
    return mlp_flops(config, config["moe_intermediate_size"])


def shared_width(config: dict) -> int:
    return config["num_shared_experts"] * config["moe_intermediate_size"]


def experts_flops_per_token(config: dict, held_per_token: float) -> float:
    """One expert layer: router, the shared expert, and
    ``held_per_token`` routed experts of those a token chose."""
    return 2 * config["hidden_size"] * config["published"]["num_experts"] \
        + mlp_flops(config, shared_width(config)) \
        + held_per_token * expert_flops(config)


def flops_per_token(config: dict, context: float,
                    held_per_token: float) -> int:
    kda, attention, dense, sparse = _layers(config)
    return int(
        kda * kda_flops_per_token(config)
        + attention * (2 * attention_params(config)
                       + attention_score_flops_per_token(config, context))
        + dense * mlp_flops(config, config["intermediate_size"])
        + sparse * experts_flops_per_token(config, held_per_token))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    context and the mean share of a token's experts that is held."""
    held_per_token = config["num_experts_per_token"] \
        * config["experts_held"]["count"] \
        / config["published"]["num_experts"]
    return config["chunk_size"] * flops_per_token(
        config, mean_context(config), held_per_token)


def mechanism_work(config: dict, mechanism: str, tokens: float, *served):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens. ``served`` is ``(held_assignments, dispatches)`` from
    ``benchmarks/scopes.py`` (the (token, expert) pairs that fell to
    held experts over all expert layers) or ``(dispatches,)`` from
    ``benchmarks/subscopes.py``. ``experts``: every layer's feed-forward
    (the dense layer's MLP, the routers, the shared and the held routed
    experts); ``gmm``: the grouped products inside them; ``flash``: the
    latent-attention layers' scores and values, what the flash kernel
    computes; ``deltanet``: the KDA layers' mixers whole (products,
    convolutions, low-rank pairs, rule); ``deltarule``: the rule alone,
    by the recurrence's own count. Bytes are each layer's weights once a
    dispatch plus its input and output activations in bfloat16; the
    rule's are its operands read and its result written once."""
    dispatches = served[-1]
    d = config["hidden_size"]
    kda, attention, dense, sparse = _layers(config)
    act = 2 * 2 * d * tokens
    if mechanism == "deltanet":
        return (kda * tokens * kda_flops_per_token(config),
                kda * (2 * kda_params(config) * dispatches + act))
    if mechanism == "deltarule":
        # q, k, v in bfloat16, log alpha a channel and beta a head in
        # float32, the result out in float32
        width = _kda_width(config)
        each = 2 * 3 * width + 4 * width \
            + 4 * config["linear_attn_config"]["num_heads"] + 4 * width
        return (kda * tokens * delta_rule_flops_per_token(config),
                kda * tokens * each)
    if mechanism == "flash":
        # every query against its request's keys at or before it (the
        # mix's mean context); queries, keys, values in and the result
        # out in bfloat16, keys and values expanded to every head
        heads = config["num_attention_heads"]
        qk = heads * (config["qk_nope_head_dim"]
                      + config["qk_rope_head_dim"])
        value = heads * config["v_head_dim"]
        return (attention * tokens * attention_score_flops_per_token(
            config, mean_context(config)),
            attention * tokens * 2 * (2 * qk + 2 * value))
    inner = config["moe_intermediate_size"]
    held = config["experts_held"]["count"]
    if mechanism == "experts":
        held_assignments = served[0]
        weights = 2 * (
            dense * 3 * d * config["intermediate_size"]
            + sparse * (3 * d * inner * held + 3 * d * shared_width(config)
                        + d * config["published"]["num_experts"]))
        ops = tokens * (dense * mlp_flops(config,
                                          config["intermediate_size"])
                        + sparse * experts_flops_per_token(config, 0.0)) \
            + held_assignments * expert_flops(config)
        return ops, weights * dispatches + (dense + sparse) * act
    if mechanism == "gmm":
        # the grouped products alone: the three projections of every
        # held (token, expert) pair; the held experts' matrices once a
        # dispatch, each pair's rows in and out in bfloat16
        held_assignments = served[0]
        return (held_assignments * expert_flops(config),
                sparse * 2 * 3 * d * inner * held * dispatches
                + held_assignments * 2 * 2 * (d + inner))
    raise ValueError("mechanism %r" % (mechanism,))
