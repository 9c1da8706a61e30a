"""The Xing4.0 family (DeepSeek-V3's layer — latent attention under YaRN,
sigmoid-routed experts chosen with a correction bias beside a shared
one — on a residual stream ``hc_mult`` wide that per-token mappings mix
into and out of every sublayer: manifold-constrained hyper-connections;
served as prefill over packed token rows), behind the contract
``benchmarks/run.py`` calls. A configuration's file names it:
``"family": "xing4"``. The plain reference is
``benchmarks/references/xing4.py``.

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
packed dispatches of the timed path, with the tokens, the router's
choices and every (sublayer, token)'s ``H_res`` defect (the largest
distance of a row or column sum from 1); each is recomputed by the
reference on the chip, one layer's float32 weights at a time, and the
logits go to :func:`benchmarks.references.compare`. The reference is
given the program's router choices (its own free choice is checked
beside: wherever the two differ, the program's weakest chosen expert
must lie within ``ROUTE_SLACK`` of the reference's k-th best
``sigmoid + bias``). Every (sublayer, token)'s defect in the program is
held to the reference's own of the same sublayer and token, in the mean
of their relative distances (:func:`defects_apart`, ``DEFECT_APART``):
both sides iterate the same float32 steps on nearly the same logits, so
a token's two defects agree far more closely than any two tokens' do
(by 0.6% at the median token, at the published widths); a program that
stops the iteration early, or iterates in a lower precision, reads
apart on *every* token, whatever the logits do and however slowly the
worst token of the sample converges (under the seeded draw twenty steps
leave a defect of 5e-4 at the median token and 3e-2 at the worst: one
step fewer is a third more at every token). :func:`held_to_the_limits`
is the run's whole verdict, and ``scripts/prefill_control.py``'s on each
of its arms.

**Tolerance.** ``SHARE_OF_SPREAD`` = 5% of the reference logits'
spread, the limit of every token family here, between two readings on
the v5e (PERF.md section 2 gives both: as stated, and the stored
matrices through float8, which is not correct; and ``DEFECT_APART``'s:
as stated, and nineteen Sinkhorn steps for twenty, five, and the
mappings' coefficients in bfloat16, none of which is correct).
"""

from __future__ import annotations

import glob
import os
from typing import List

import numpy as np

from benchmarks import manifest

_tokens = manifest.load_family("nemotron_h")
_layer = manifest.load_family("deepseek_v2")

#: the comparison's limit, as a share of the reference logits' spread
SHARE_OF_SPREAD = 0.05
#: the reference runs prompts padded to a multiple of this many tokens
REF_PAD = 1024
#: how far below the reference's k-th best ``sigmoid + bias`` (a chosen
#: expert's is 0.5 to 1) the program's weakest chosen expert may lie
#: where the choices differ: K-EXAONE's limit, the same rule at the same
#: precision (``families/exaone_moe.py``; PERF.md section 2 has this
#: cell's readings)
ROUTE_SLACK = 0.02
#: the float32 rounding of a sum of ``hc_mult`` shares about 1: a
#: defect under it is noise on both sides
DEFECT_FLOOR = 1e-6
#: how far a (sublayer, token)'s defect in the program may lie from the
#: reference's own, as a share of the reference's + ``DEFECT_FLOOR``, in
#: the mean over the samples' tokens (PERF.md section 2 has the readings
#: on both sides: 0.011 as stated; one Sinkhorn step short, five for
#: twenty and the coefficients in bfloat16 over it)
DEFECT_APART = 0.1
#: every control arm has to come out not correct
#: (``scripts/prefill_control.py``)
EVERY_CONTROL_FAILS = True

prompt_lengths = _tokens.prompt_lengths
dataset_key = _tokens.dataset_key
rows_of_tokens = _tokens.rows_of_tokens
prepare_inputs = _tokens.prepare_inputs
held_experts = _tokens.held_experts
mean_context = _tokens.mean_context
wire_bytes_per_row = _tokens.wire_bytes_per_row
published_keys = _layer.published_keys


def build(repo: str) -> None:
    """No child to run. A checkout whose program lacks the family says
    so here, before JAX starts."""
    if not os.path.isdir(os.path.join(repo, "rnb_tpu", "models", "xing4")):
        raise SystemExit("benchmarks/families/xing4.py: this checkout's "
                         "program has no rnb_tpu/models/xing4: it cannot "
                         "serve the family")


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the recipe the program makes its weights from, None: the
    reference reads the same values through the recipe)."""
    from rnb_tpu.models.xing4 import checkpoint
    path = ckpt_base + ".recipe.json"
    checkpoint.save_recipe(path, published_keys(config), seed,
                           held_experts(config))
    return path, None


def unpack_choices(config: dict, kept: dict, count: int):
    """What the reference is given of a sample's choices
    (``network.request_choices``): the router's experts."""
    return kept["chosen"]


def defects_apart(got, own) -> float:
    """``got``, ``own``: a request's (sublayers, tokens) ``H_res``
    defects each, the program's and the reference's -> the mean over
    every (sublayer, token) of all of them of |program - reference| /
    (reference + ``DEFECT_FLOOR``). The mean, because it is steady where
    the largest is not (PERF.md section 2: the largest read 1.8 to 10.2
    over four sound runs, a token whose own defect happens to be small;
    the mean 0.0105 to 0.0114 a prompt) and still sees a gross fault on
    one token in ten thousand."""
    apart = [np.abs(np.asarray(g, np.float64) - o) / (o + DEFECT_FLOOR)
             for g, o in zip(got, (np.asarray(o, np.float64) for o in own))]
    return float(np.concatenate([a.reshape(-1) for a in apart]).mean())


def held_to_the_limits(config: dict, verdict: dict, worst: dict) -> dict:
    """``references.compare``'s ``verdict`` on the logits with the
    family's other limits on ``worst`` (``route_shortfall_max``,
    ``res_defect_apart`` and whatever else is to be reported): the run's
    check, and ``scripts/prefill_control.py``'s on each of its arms."""
    verdict.update(worst)
    if worst["route_shortfall_max"] > ROUTE_SLACK:
        verdict["ok"] = False
        verdict["why"] = ("a router choice %.5f under the reference's k-th "
                          "best score, over %.5f"
                          % (worst["route_shortfall_max"], ROUTE_SLACK))
    elif worst["res_defect_apart"] > DEFECT_APART:
        verdict["ok"] = False
        verdict["why"] = ("the H_res defects lie %.3g of the reference's "
                          "own (+ %.0e) from them in the mean, over %.3g"
                          % (worst["res_defect_apart"], DEFECT_FLOOR,
                             DEFECT_APART))
    return verdict


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The logits the timed path served, against the reference. The
    limit is ``SHARE_OF_SPREAD`` unless the configuration's file states
    its own ``share_of_spread`` (a toy-width copy in the tests does:
    narrow sums average less rounding away)."""
    share_of_spread = float(config.get("share_of_spread",
                                       SHARE_OF_SPREAD))
    import jax

    from benchmarks.references import compare, xing4 as reference
    from rnb_tpu.models.xing4 import checkpoint
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
    shortfall, differ, defects, ref_defects = 0.0, 0, [], []
    with jax.default_matmul_precision("highest"):
        for path in samples:
            with np.load(path) as sample:
                tokens, logits = sample["tokens"], sample["logits"]
                chosen, bucket = sample["chosen"], int(sample["rows"])
                mixed = sample["res_defect"]
            name = by_tokens.get(tokens.tobytes())
            if name is None:
                return {"ok": False, "why": "%s holds tokens of no request "
                        "file" % path}
            # padded behind its last token to a multiple of REF_PAD, so
            # that the reference compiles a few lengths and not one a
            # prompt; the stack is causal: the last real position is the
            # same
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
            defects.append(mixed)
            ref_defects.append(np.asarray(out["res_defect"])[:, :count])
    verdict = compare(np.stack(got), np.stack(ref), share_of_spread)
    verdict.update(samples=len(got), files=files, dispatch_rows=rows,
                   limit=share_of_spread)
    return held_to_the_limits(config, verdict, {
        "route_shortfall_max": shortfall, "route_differ": differ,
        "res_defect_max": max(float(d.max()) for d in defects),
        "ref_res_defect_max": max(float(d.max()) for d in ref_defects),
        "res_defect_apart": defects_apart(defects, ref_defects)})


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    from rnb_tpu.models.xing4 import network
    problems = []
    cfg = network.Xing4Config.from_published(published_keys(config))
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
    if share["first"] != 0 or share["count"] != config["n_routed_experts"]:
        problems.append("experts_held is not every routed expert "
                        "(ep_size 1)")
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

    from rnb_tpu.models.xing4 import checkpoint, network
    cfg = network.Xing4Config.from_published(published_keys(config))
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


def _as_the_layer(config: dict) -> dict:
    """The configuration as ``families/deepseek_v2.py`` reads one: the
    router's width under ``published`` (no expert is cut here)."""
    return dict(config, published=dict(
        config["published"], n_routed_experts=config["n_routed_experts"]))


def sublayers(config: dict) -> int:
    return 2 * config["num_hidden_layers"]


def mapping_rows(config: dict) -> int:
    """The coefficients a sublayer's mappings give a token: pre, post,
    res."""
    n = config["hc_mult"]
    return 2 * n + n * n


def hyper_flops_per_token(config: dict) -> int:
    """One sublayer's mappings on one token: the projection ``x^ phi``,
    the statistic, ``H_res X`` and ``h_pre X`` with ``h_post^T y``; the
    Sinkhorn steps (n^2 numbers a token) are not counted."""
    n, c = config["hc_mult"], config["hidden_size"]
    return 2 * n * c * mapping_rows(config) + 2 * n * c + 2 * n * n * c \
        + 2 * n * c


def flops_per_token(config: dict, context: float,
                    held_per_token: float) -> int:
    return _layer.flops_per_token(_as_the_layer(config), context,
                                  held_per_token) \
        + sublayers(config) * hyper_flops_per_token(config)


def flops_per_row(config: dict) -> int:
    """``chunk_size`` tokens through the layers held, at the mix's mean
    context; every expert is held, so a token's ``num_experts_per_tok``
    choices are all served here."""
    return config["chunk_size"] * flops_per_token(
        config, mean_context(config), config["num_experts_per_tok"])


def mechanism_work(config: dict, mechanism: str, tokens: float, *served):
    """(operations, bytes) one mechanism needs for ``tokens`` valid
    tokens. ``served`` is ``(held_assignments, dispatches)`` from
    ``benchmarks/scopes.py`` or ``(dispatches,)`` from
    ``benchmarks/subscopes.py``.

    ``attn``, ``flash``, ``experts``, ``gmm``: as
    ``families/deepseek_v2.py`` counts them (the same layer).
    ``hyper``: every sublayer's mappings and mixings. Operations:
    :func:`hyper_flops_per_token`. Bytes: a token's stream read once and
    written once, the sublayer's input written and its output read, in
    bfloat16 — (2n + 2) C 2 a token a sublayer — and ``phi`` once a
    dispatch: *the least any implementation can move*, whatever it fuses
    (one that hands the next sublayer its input while the stream is in
    fast memory moves exactly this), so the share cannot pass 100.
    ``hyper_mix``: the part of it the kernel of that name does, one call
    a sublayer — the first sublayer's way in (the stream read, the
    input written: (n + 1) C 2 bytes a token), then every way out with
    the next way in; the last way out is made on the head's lines and is
    not the kernel's. ``phi`` once a call; the coefficients are not
    counted."""
    dispatches = served[-1]
    n, c = config["hc_mult"], config["hidden_size"]
    phi = dispatches * n * c * mapping_rows(config) * 2
    if mechanism == "hyper":
        return (sublayers(config) * tokens * hyper_flops_per_token(config),
                sublayers(config) * (tokens * (2 * n + 2) * c * 2 + phi))
    if mechanism == "hyper_mix":
        between = sublayers(config) - 1
        return (tokens * (
            between * hyper_flops_per_token(config)
            + 2 * n * c * mapping_rows(config) + 4 * n * c),
            tokens * (between * (2 * n + 2) + n + 1) * c * 2
            + sublayers(config) * phi)
    if len(served) != 2:
        raise ValueError("mechanism %r" % (mechanism,))
    return _layer.mechanism_work(_as_the_layer(config), mechanism, tokens,
                                 *served)
