"""The Keye-VL-2.0 family (grouped-query attention under a learned
indexer's choice of 2,048 keys a query, 128 softmax-routed gated experts
in every layer, all of them held; served as prefill over packed token
rows), behind the contract ``benchmarks/run.py`` calls. A
configuration's file names it: ``"family": "keye_vl2"``. The plain
reference is ``benchmarks/references/keye_vl2.py``.

**The requests** are the token families' own: prompts as ``.npy`` files
of int32 ids from ``dataset``, rows of ``chunk_size`` tokens. What is
not particular to the model (prompt synthesis, the request files, the
bytes a row ships) is ``families/nemotron_h.py``'s and is called from
there, so that the token families' cells draw prompts through one code.

**The weights.** A recipe (seed, sizes, the ids of the experts held:
all of them), not a file of values: the program makes each tensor on
its device from the seed and the tensor's name, and
:func:`check_outputs` hands the reference the same values through
``checkpoint.reference_reader``.

**What is compared.** The final stage keeps, under the run's log
directory, the last-position logits of 8 requests it served from full
packed dispatches of the timed path, with the tokens and *both* kinds
of choice the stack made for them: the router's eight experts a (layer,
token), and the keys every query read a layer (the pool's bits). Each
request is recomputed by the reference on the chip, one layer's float32
weights at a time, and both go to :func:`benchmarks.references.compare`.
The reference is given the program's choices of both kinds; its own
free choices are checked beside: wherever they differ, the program's
weakest chosen expert must lie within ``ROUTE_SLACK`` of the
reference's eighth-best probability, and the program's weakest chosen
key within ``KEY_SLACK`` of the reference's ``topk``-th best score. A
set of another size than ``min(t + 1, topk)``, a key of the future or
of another request fails outright.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, between two readings on the v5e (my chip runs, PR 46; PERF.md
section 2): bfloat16 weights and activations as the configuration
states them land at 3.4-4.1% through 6 layers (fourteen runs of the cell x
8 requests of 4k-16k tokens x 151,936 logits, and 3.4% over the control
script's two prompts); the same comparison with every stored matrix
rounded through float8 (e4m3) lands at 20.5% and is not correct. Three
further controls (``scripts/prefill_control.py``) hold the *mechanism*
to the check: the indexer's operands through float8 read 3.75% on the
logits — with the program's sets given the logits do not notice — and
fail the key slack, 0.21 against 0.04-0.06 as stated; every causal key in the
place of the sets reads 117% and the latest 2,048 keys 153%, against
the reference on its own sets.

**The draw** (``rnb_tpu/models/keye_vl2/checkpoint.py``) is chosen so
that every control can fail. At the tests' toy widths (hidden 64, 4 / 2
heads of 16, 4 index heads of 16, ``topk`` 48 over prompts of 30, 150
and 230 tokens in one dispatch, 8 experts, 3 layers; CPU counts over two
seeds of weights, PR 46), as shares of the reference logits' spread:
with query-key gains of 1 and the projections back onto the stream at
the published depth's 1 / sqrt(96) the stated precision read 1.5-2.0%
and every matrix through float8 2.3-2.6% — not told apart — with all
causal keys at 12-13% and the latest ``topk`` at 16-25%; with gains of
1.5 and the projections back at 1 / sqrt(2) the same four read
1.9-2.3%, 15-20%, 198-228% and 262-277%.
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
#: how far below the reference's eighth-best probability (a softmax over
#: 128: a chosen expert's is 0.01 to 0.1) the program's weakest chosen
#: expert may lie where the choices differ. Between two readings on the
#: v5e (my chip runs, PR 46; PERF.md section 2): as stated 0.0007 to 0.0010 over fifteen readings;
#: every stored matrix through float8 0.0073
ROUTE_SLACK = 0.003
#: how far below the reference's topk-th best score (the scores of a
#: query's keys spread by about 0.5, the topk-th and the median of
#: 10,000 lie 0.4 apart) the program's weakest chosen key may lie where
#: the sets differ: the largest of some 10^8 (query, key) pairs a
#: request and layer, of which bfloat16 operands move 5 million across
#: the cut. Between two readings on the v5e (my chip runs, PR 46;
#: PERF.md section 2): as stated 0.041 to 0.058 over fifteen readings; the indexer's operands through
#: float8 0.21 (every stored matrix through float8 0.40)
KEY_SLACK = 0.1
#: ``scripts/prefill_control.py`` holds this family's every control to a
#: failure (the others' at least one)
EVERY_CONTROL_FAILS = True
#: the lower-precision control's prompts (``scripts/prefill_control.py``):
#: two requests in one dispatch of 128 rows
CONTROL_LENGTHS = (4500, 9800)

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
mean_context = _tokens.mean_context
wire_bytes_per_row = _tokens.wire_bytes_per_row


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models",
                                      "keye_vl2")):
        raise SystemExit("benchmarks/families/keye_vl2.py: this "
                         "checkout's program has no rnb_tpu/models/"
                         "keye_vl2: it cannot serve the family")


def published_keys(config: dict) -> dict:
    """The configuration file's keys the model is built from."""
    return {k: v for k, v in config.items()
            if k in ("published", "mlp_only_layers", "sa_config",
                     "rope_scaling")
            or not isinstance(v, (dict, list))}


def held_experts(config: dict) -> List[int]:
    """Every expert of the router: the chip holds them all."""
    return list(range(int(config["num_experts"])))


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.keye_vl2 import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def pad_choices(config: dict, chosen, pad: int):
    """``chosen`` (layers, tokens, k) with ``pad`` tokens behind: their
    choices go round the router's experts, so that the reference's pad
    tokens load every expert alike."""
    layers, _, k = chosen.shape
    spread = (np.arange(pad)[:, None] * k + np.arange(k)) \
        % config["num_experts"]
    return np.concatenate([chosen, np.broadcast_to(
        spread.astype(chosen.dtype), (layers, pad, k))], axis=1)


def unpack_choices(config: dict, kept: dict, count: int,
                   padded: int = None):
    """What a sample keeps of a request's choices (the program's
    ``network.request_choices``) -> (the router's (layers, ``padded``,
    k), the pad tokens' going round the experts; the sets as a list, a
    layer each, of bool (``padded``, ``padded``): query t reads key s,
    both counted from the request's first token, nothing behind its
    ``count`` tokens; the bits a sample holds on keys *outside* the
    request, which a sound set has none of). ``padded`` defaults to
    ``count``."""
    from rnb_tpu.ops import indexed
    padded = count if padded is None else padded
    first = int(kept["first"])
    sets, strays = [], 0
    for packed in np.asarray(kept["key_sets"]):
        # a layer at a time: a 16k-token request's bits are 256 MB
        bits = indexed.unpack_sets(packed)
        own = bits[:, first:first + count]
        strays += int(bits.sum()) - int(own.sum())
        sets.append(np.pad(own, ((0, padded - count),) * 2))
    return pad_choices(config, np.asarray(kept["chosen"]),
                       padded - count), sets, strays


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away; its ``key_slack`` and
    ``ref_pad`` likewise)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    key_slack = float(config.get("key_slack", KEY_SLACK))
    import jax

    from benchmarks.references import compare, keye_vl2 as reference
    from rnb_tpu.models.keye_vl2 import checkpoint
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
    ref_pad = int(config.get("ref_pad", REF_PAD))
    got, ref, files, rows = [], [], [], []
    worst = {"route_shortfall_max": 0.0, "route_differ": 0,
             "key_shortfall_max": 0.0, "key_differ": 0, "key_bad": 0}
    with jax.default_matmul_precision("highest"):
        for path in samples:
            with np.load(path) as sample:
                tokens, logits = sample["tokens"], sample["logits"]
                bucket = int(sample["rows"])
                kept = {key: sample[key]
                        for key in ("chosen", "key_sets", "first")}
            name = by_tokens.get(tokens.tobytes())
            if name is None:
                return {"ok": False, "why": "%s holds tokens of no request "
                        "file" % path}
            # padded behind its last token to a multiple of ref_pad, so
            # that the reference compiles a few lengths and not one a
            # prompt; a causal stack: the last real position is the same
            count = len(tokens)
            padded = count + -count % ref_pad
            forced, sets, strays = unpack_choices(config, kept, count,
                                                  padded)
            out = ref_model.forward(
                read, np.pad(tokens, (0, padded - count)), held=held,
                forced=forced, forced_sets=sets, forced_count=count,
                position=count - 1)
            got.append(logits)
            ref.append(np.asarray(out["logits"]))
            files.append(os.path.basename(name))
            rows.append(bucket)
            short = np.asarray(out["shortfall"])[:, :count]
            key_short = np.asarray(out["key_shortfall"])[:, :count]
            bad = np.asarray(out["key_bad"])[:, :count]
            worst["route_shortfall_max"] = max(
                worst["route_shortfall_max"], float(short.max()))
            worst["route_differ"] += int((short > 0).sum())
            worst["key_shortfall_max"] = max(
                worst["key_shortfall_max"],
                float(np.where(bad, 0.0, key_short).max()))
            worst["key_differ"] += int(
                np.asarray(out["key_differ"])[:, :count].sum())
            worst["key_bad"] += int(bad.sum()) + strays
    verdict = compare(np.stack(got), np.stack(ref), share_of_spread)
    verdict.update(samples=len(got), files=files, dispatch_rows=rows,
                   limit=share_of_spread, **worst)
    if worst["key_bad"]:
        verdict["ok"] = False
        verdict["why"] = ("%d set(s) of another size than min(t + 1, "
                          "topk), with a key of the future or with a key "
                          "of another request" % worst["key_bad"])
    elif worst["route_shortfall_max"] > ROUTE_SLACK:
        verdict["ok"] = False
        verdict["why"] = ("a router choice %.5f under the reference's k-th "
                          "best score, over %.5f"
                          % (worst["route_shortfall_max"], ROUTE_SLACK))
    elif worst["key_shortfall_max"] > key_slack:
        verdict["ok"] = False
        verdict["why"] = ("a chosen key %.5f under the reference's topk-th "
                          "best score, over %.5f"
                          % (worst["key_shortfall_max"], key_slack))
    return verdict


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.keye_vl2 import network
    problems = []
    network.KeyeVL2Config.from_published(published_keys(config))
    layers = config["num_hidden_layers"]
    if config["model"]["layers"] != layers or layers < 4:
        problems.append("layers held: the model's %r, num_hidden_layers %d "
                        "(floor: 4; every layer is of one kind)"
                        % (config["model"]["layers"], layers))
    for key in config["reduced"]:
        if config["published"].get(key) in (None, config[key]):
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

    from rnb_tpu.models.keye_vl2 import checkpoint, network
    cfg = network.KeyeVL2Config.from_published(published_keys(config))
    batcher, step = config["pipeline_config"]["pipeline"][-2:]
    rows = max(step["row_buckets"])
    params = {}
    for group, tensors in checkpoint.tensor_specs(
            cfg, config["num_experts"]).items():
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


def request_reads(config: dict, length: int):
    """-> (causal, chosen): over the queries of one request of
    ``length`` tokens, the keys they may read (every one at or before
    them: what the indexer scores) and the keys of their sets (``min(t +
    1, topk)``: what attention reads)."""
    at = np.arange(int(length), dtype=np.int64) + 1
    return int(at.sum()), \
        int(np.minimum(at, config["sa_config"]["topk"]).sum())


def mean_reads(config: dict):
    """(causal, chosen) keys a query, averaged over the tokens of the
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


def _wide(config: dict) -> int:
    return config["num_attention_heads"] * config["head_dim"]


def _narrow(config: dict) -> int:
    return config["num_key_value_heads"] * config["head_dim"]


def _index_width(config: dict) -> int:
    """The columns of the indexer's three products together."""
    sa = config["sa_config"]
    return sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + sa["indexer_head_dim"] + sa["indexer_num_heads"]


def attention_proj_flops(config: dict) -> int:
    d = config["hidden_size"]
    return 2 * d * (_wide(config) + 2 * _narrow(config)) \
        + 2 * _wide(config) * d


def attention_read_flops(config: dict, chosen: float) -> float:
    """Scores and values of one query over ``chosen`` keys."""
    return 4.0 * chosen * _wide(config)


def indexer_flops(config: dict, causal: float) -> float:
    """The indexer's own, one query: its three products and one score a
    head over each of the ``causal`` keys it may read. Choosing the
    ``topk`` among them adds nothing to the yardstick."""
    sa = config["sa_config"]
    return 2 * config["hidden_size"] * _index_width(config) \
        + 2.0 * causal * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def expert_flops(config: dict) -> int:
    return 6 * config["hidden_size"] * config["moe_intermediate_size"]


def experts_flops_per_token(config: dict, held_per_token: float) -> float:
    """One expert layer: the router and ``held_per_token`` routed
    experts of those a token chose."""
    return 2 * config["hidden_size"] * config["num_experts"] \
        + held_per_token * expert_flops(config)


def flops_per_token(config: dict, causal: float, chosen: float,
                    held_per_token: float) -> int:
    return int(config["num_hidden_layers"] * (
        attention_proj_flops(config)
        + attention_read_flops(config, chosen)
        + indexer_flops(config, causal)
        + experts_flops_per_token(config, held_per_token)))


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, each mechanism by
    its own work: the indexer over the mix's mean causal keys a query,
    attention over the mean *chosen* keys, every expert a token chose
    (all are held)."""
    return config["chunk_size"] * flops_per_token(
        config, *mean_reads(config), config["num_experts_per_tok"])


def mechanism_work(config: dict, mechanism: str, tokens: float, *served):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens. ``served`` is ``(held_assignments, dispatches)`` from
    ``benchmarks/scopes.py`` (the (token, expert) pairs the experts
    served over all layers) or ``(dispatches,)`` from
    ``benchmarks/subscopes.py``. ``experts``: every layer's router and
    routed experts; ``gmm``: the grouped products inside them;
    ``select``: the indexer's own work (:func:`indexer_flops` at the
    mix's mean causal keys; its three matrices once a dispatch, the
    normed stream read and its queries, keys and weights written and
    read once) — the same work whether the scores are written and
    sorted, thresholded or fused into the attention kernel;
    ``indexed_attn``: each query against the keys of its set (the mix's
    mean ``min(t + 1, topk)``), queries, keys and values read and the
    result written once in bfloat16."""
    dispatches = served[-1]
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    causal, chosen = mean_reads(config)
    if mechanism == "select":
        width = _index_width(config)
        return (layers * tokens * indexer_flops(config, causal),
                layers * (2 * d * width * dispatches
                          + tokens * (2 * d + 2 * 2 * width)))
    if mechanism == "indexed_attn":
        return (layers * tokens * attention_read_flops(config, chosen),
                layers * tokens * 2 * (2 * _wide(config)
                                       + 2 * _narrow(config)))
    inner = config["moe_intermediate_size"]
    held = config["num_experts"]
    held_assignments = served[0]
    if mechanism == "experts":
        weights = 2 * (3 * d * inner * held + d * config["num_experts"])
        return (layers * tokens * experts_flops_per_token(config, 0.0)
                + held_assignments * expert_flops(config),
                layers * (weights * dispatches + 2 * 2 * d * tokens))
    if mechanism == "gmm":
        # the grouped products alone: the three projections of every
        # (token, expert) pair; the experts' matrices once a dispatch,
        # each pair's rows in and out in bfloat16
        return (held_assignments * expert_flops(config),
                layers * 2 * 3 * d * inner * held * dispatches
                + held_assignments * 2 * 2 * (d + inner))
    raise ValueError("mechanism %r" % (mechanism,))
