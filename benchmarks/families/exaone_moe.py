"""The K-EXAONE family (``exaone_moe``: grouped-query attention with a
window of 128 keys in three layers of four and over the whole request in
the fourth, norms behind the mixer and the feed-forward; a leading dense
layer, then sigmoid-routed sparse experts with a shared one; served as
prefill over packed token rows), behind the contract
``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "exaone_moe"``. The plain reference is
``benchmarks/references/exaone_moe.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
mix's mean context, the bytes a row ships) is ``families/nemotron_h.py``'s
and is called from there, so that the token families' cells draw prompts
through one code. The ids are uniform over the vocabulary *held*
(``vocab_size``: a slice of the published one).

**The weights.** A recipe (seed, sizes, experts held), not a file of
values: the program makes each tensor on its device from the seed and
the tensor's name, and :func:`check_outputs` hands the reference the
same values, in the published form, through
``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and the router's
choices; each is recomputed by the reference on the chip, one layer's
float32 weights at a time (the routed experts 8 at a time, each visited
once over the tokens that chose it; the window as an explicit mask),
and both go to :func:`benchmarks.references.compare`. The reference is
given the program's router choices for those tokens (its own free
choice is checked beside: wherever the two differ, the program's
weakest chosen expert must lie within ``ROUTE_SLACK`` of the
reference's k-th best score), so that the tolerance measures arithmetic
and not which of two nearly tied experts a rounding difference picked.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (PR 42, my chip runs; PERF.md
section 2): bfloat16 weights and activations as the configuration
states them land at 3.0-3.6% through the dense layer and four sparse
ones (eight runs of the cell x 7 requests x 19,200 logits, and the
control's two prompts); the same comparison with the feed-forwards'
matrices rounded through float8 (e4m3) lands at 36.6%, with every
layer's matrices at 61.0% (``scripts/prefill_control.py``), and is not
correct.

**The flash kernel's work.** Both kinds of attention layer call one
Pallas kernel under one name (splash's), so the accepted
``flash_roofline_pct.bulk`` reads the calls of both, and this file's
``"flash"`` work is the sum of both kinds' own: a full layer's queries
against their whole context, a sliding layer's against the at most
``sliding_window`` keys they may read. The tiles have a counter a kind:
``flash_tile_visit_pct.bulk`` reads the full layers' alone,
``window_tile_visit_pct.bulk`` the sliding layers'. ``"window_attn"``
is the sliding layers' own work alone, held against the kernel's calls
under ``attn/window/kernel``: the same yardstick whatever implements
the window.

This file repeats ``check_outputs``, ``project_memory`` and the stage
lines of ``check_config`` a fifth time (PERF.md section 7, debts t, z,
ai): a ``model_config`` PR may not edit the older family files to share
them.
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
#: how far below the reference's k-th best ``sigmoid + bias`` (a chosen
#: expert's is 0.5 to 1) the program's weakest chosen expert may lie
#: where the choices differ. Between two readings on the v5e (PR 42, my
#: chip runs): as stated 0.0031 to 0.0051; the float8 controls 0.055
#: and 0.195
ROUTE_SLACK = 0.02
#: the lower-precision control's prompts (``scripts/prefill_control.py``):
#: two requests in one dispatch of 128 rows
CONTROL_LENGTHS = (4500, 9800)

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
held_experts = _tokens.held_experts
mean_context = _tokens.mean_context
wire_bytes_per_row = _tokens.wire_bytes_per_row

#: the configuration file's lists and groups the model is built from
_GROUPS = ("published", "layer_types", "mlp_layer_types",
           "rope_parameters")


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "exaone_moe")):
        raise SystemExit("benchmarks/families/exaone_moe.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "exaone_moe: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in _GROUPS or not isinstance(v, (dict, list))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.exaone_moe import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def pad_choices(config: dict, chosen, pad: int):
    """``chosen`` (expert layers, tokens, k) with ``pad`` tokens behind:
    their choices go round the router's experts, so that the reference's
    pad tokens load every expert alike (all on expert 0 they would set
    the room it gives every expert)."""
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

    from benchmarks.references import compare, exaone_moe as reference
    from rnb_tpu.models.exaone_moe import checkpoint
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
            # prompt; causal attention: the last real position is the same
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
    from rnb_tpu.models.exaone_moe import network
    problems = []
    cfg = network.ExaoneMoeConfig.from_published(published_keys(config))
    layers = config["num_hidden_layers"]
    period = [network.SLIDING if kind == "L" else network.FULL
              for kind in config["sliding_window_pattern"]]
    behind = layers - config["first_k_dense_replace"]
    if config["model"]["layers"] != layers or behind < 4 \
            or list(cfg.layer_types[:len(period)]) != period:
        problems.append("layers held: the model's %r, num_hidden_layers "
                        "%d of which %d behind the dense ones (floor: 4, "
                        "and one whole period %r)"
                        % (config["model"]["layers"], layers, behind,
                           config["sliding_window_pattern"]))
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
    if config["vocab_size"] * 8 < config["published"]["vocab_size"]:
        problems.append("less than an eighth of the vocabulary is held")
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

    from rnb_tpu.models.exaone_moe import checkpoint, network
    cfg = network.ExaoneMoeConfig.from_published(published_keys(config))
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
    """(sliding layers, full layers, dense layers, expert layers) held
    here."""
    held = config["num_hidden_layers"]
    sliding = config["layer_types"][:held].count("sliding_attention")
    dense = config["mlp_layer_types"][:held].count("dense")
    return sliding, held - sliding, dense, held - dense


def mean_window_keys(config: dict) -> float:
    """Keys a query of a sliding layer attends to, averaged over the
    tokens of the mix as :func:`mean_context` averages a full layer's:
    query t of a prompt reads ``min(t + 1, sliding_window)``."""
    window = config["sliding_window"]
    lengths = prompt_lengths(config)

    def pairs(names):
        count = np.array([n for k, n in lengths.items() if k[0] == names],
                         float)
        inside = np.minimum(count, window)
        return (inside * (inside + 1) / 2
                + (count - inside) * window).mean(), count.mean()
    every = float(config["dataset"].get("long_every", 11))
    (short, short_len), (long, long_len) = pairs("s"), pairs("l")
    return float(((every - 1) * short + long)
                 / ((every - 1) * short_len + long_len))


def attention_params(config: dict) -> int:
    """The four products of one attention layer."""
    d, dim = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return d * (2 * hq + 2 * hk) * dim


def attention_score_flops_per_token(config: dict, keys: float) -> float:
    """Scores and values of one query against ``keys`` keys."""
    return 4.0 * keys * config["num_attention_heads"] * config["head_dim"]


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


def flops_per_token(config: dict, context: float, window_keys: float,
                    held_per_token: float) -> int:
    sliding, full, dense, sparse = _layers(config)
    return int(
        (sliding + full) * 2 * attention_params(config)
        + full * attention_score_flops_per_token(config, context)
        + sliding * attention_score_flops_per_token(config, window_keys)
        + dense * mlp_flops(config, config["intermediate_size"])
        + sparse * experts_flops_per_token(config, held_per_token))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    context (a sliding layer's: its mean keys inside the window) and the
    mean share of a token's experts that is held."""
    held_per_token = config["num_experts_per_tok"] \
        * config["experts_held"]["count"] \
        / config["published"]["num_experts"]
    return config["chunk_size"] * flops_per_token(
        config, mean_context(config), mean_window_keys(config),
        held_per_token)


def mechanism_work(config: dict, mechanism: str, tokens: float, *served):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens. ``served`` is ``(held_assignments, dispatches)`` from
    ``benchmarks/scopes.py`` (the (token, expert) pairs that fell to
    held experts over all expert layers) or ``(dispatches,)`` from
    ``benchmarks/subscopes.py``. ``window_attn``: the sliding layers'
    scores and values, every valid query against the at most
    ``sliding_window`` keys of its request it may read; ``flash``: that
    and the full layers' queries against their whole context, what the
    flash kernel's calls of both kinds compute (the module's text);
    ``experts``: every layer's feed-forward (the dense layer's MLP, the
    routers, the shared and the held routed experts); ``gmm``: the
    grouped products inside them. An attention's bytes are its queries,
    keys and values read and its result written once, in bfloat16; the
    feed-forwards' each layer's weights once a dispatch plus its input
    and output activations."""
    dispatches = served[-1]
    d = config["hidden_size"]
    sliding, full, dense, sparse = _layers(config)
    hq = config["num_attention_heads"] * config["head_dim"]
    hk = config["num_key_value_heads"] * config["head_dim"]
    moved = tokens * 2 * (2 * hq + 2 * hk)
    window = (sliding * tokens * attention_score_flops_per_token(
        config, mean_window_keys(config)), sliding * moved)
    if mechanism == "window_attn":
        return window
    if mechanism == "flash":
        return (window[0] + full * tokens * attention_score_flops_per_token(
            config, mean_context(config)), window[1] + full * moved)
    inner = config["moe_intermediate_size"]
    held = config["experts_held"]["count"]
    held_assignments = served[0]
    if mechanism == "experts":
        weights = 2 * (
            dense * 3 * d * config["intermediate_size"]
            + sparse * (3 * d * inner * held + 3 * d * shared_width(config)
                        + d * config["published"]["num_experts"]))
        ops = tokens * (dense * mlp_flops(config,
                                          config["intermediate_size"])
                        + sparse * experts_flops_per_token(config, 0.0)) \
            + held_assignments * expert_flops(config)
        return ops, weights * dispatches \
            + (dense + sparse) * 2 * 2 * d * tokens
    if mechanism == "gmm":
        # the grouped products alone: the three projections of every
        # held (token, expert) pair; the held experts' matrices once a
        # dispatch, each pair's rows in and out in bfloat16
        return (held_assignments * expert_flops(config),
                sparse * 2 * 3 * d * inner * held * dispatches
                + held_assignments * 2 * 2 * (d + inner))
    raise ValueError("mechanism %r" % (mechanism,))
