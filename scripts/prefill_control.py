"""The lower-precision control of a token family's comparison, on the
device it runs on: a few prompts packed into one dispatch at the
configuration's real widths, compared with the family's float32
reference (the router's choices given) once as the configuration states
its precision and once an arm of its family below. The first must lie
inside the family file's tolerance and a control outside it; the last
stdout line is one JSON object with each arm's share of the spread.

    python3 scripts/prefill_control.py [--config <file>] [--seed N]
    chiprun -- python3 scripts/prefill_control.py --config \\
        benchmarks/configs/deepseek-v2-ep8.json

The configuration's family file writes the recipe, and the recipe names
the family whose program, reference and arms are used: one script for
every token family. An arm is keyword arguments of the family's
``network.forward`` or a set of stored matrices rounded
through float8 (e4m3) where they lie, each conversion a program of its
own: inside the dispatch's program the v5e's compiler fuses bf16 ->
float8 -> bf16 in front of the product and keeps the excess precision,
and the arm then read the stated precision's logits bit for bit (PR 29,
my chip run). Rounded weights stay rounded: such arms come last, the
narrower set first. A family whose attention reads a learned indexer's
sets (``keye_vl2``) has two arms more, each of which must read over the
limit against the reference's own sets: every causal key in their place,
and the latest ``topk``; and one that must fail the key slack with the
program's sets given: the indexer's operands through float8. A family
whose delta rule is gated a channel and whose latent attention turns
nothing (``kimi_linear``) has an arm for each: a head's mean ``log alpha``
in the place of its channels', and rotary turned on; both must fail, as
must float8, and the carried states through bfloat16 are recorded
whether they do or not (the family file's ``CONTROL_MAY_PASS``;
``falcon_h1`` records its scan's states through bfloat16 the same way
and holds float8 to a failure). ``dots3_note`` (latent attention under
an indexer's sets in its full layers, under a window in its sliding
ones) has ``keye_vl2``'s lower-precision arm, the indexer's operands
through float8, which must fail the key slack, every stored matrix
through float8, and :func:`dots3_note_faults`: each changes what the
program is *given* — a tensor of its parameter tree scaled, a field of
its configuration, a function of its module replaced — and nothing in
the program, and must fail one of the family's limits: the head-wise
gates' matrices at zero (every gate a half), the latents' norm weights
over the rescale (the rescale left out), the window a key short, the
rotary bases of the two layer types swapped, the indexer's rotary left
out (that one fails the key slack). ``old_draw`` is a witness and no
fault: program *and* reference with the latents' norm weights at one,
the draw the cell's first chip run read 24% of the spread under
(``models/dots3_note/checkpoint.py``); it fails as that run did.
``phi4_flash`` (Mamba-1 scans, differential attention under a window and
in one full layer, a cross-decoder on one line a request) has its scans'
states through bfloat16, recorded either way, every stored matrix
through float8, and :func:`phi4_flash_faults`, functions of its module
replaced, each of which must fail: ``one_softmax`` (``lambda`` at zero:
``lambda P2 V`` dropped in all sixteen attention layers),
``window_off`` (the sliding layers read the whole context) and
``memory_gated`` (the Gated Memory Units read layer 16's *gated*
output). ``own_keys`` is no arm: a cross layer has no keys of its own.
``xing4`` (a residual stream four wide under per-token mappings) has
:func:`xing4_faults` — ``no_dynamic_term`` (every ``alpha`` at zero),
``one_stream_read`` (the head reads one stream, not their sum),
``sinkhorn_5`` and ``sinkhorn_19`` (five and nineteen steps for twenty)
and ``mappings_bfloat16`` (the sigmoids, ``exp`` and Sinkhorn steps in
bfloat16) — and the feed-forwards' and every stored matrix through
float8; every arm goes through the family's whole verdict
(``families/xing4.held_to_the_limits``: the logits, the router's slack,
each (sublayer, token)'s ``H_res`` defect against the reference's own)
and every one must fail.
``--arms`` names the ones to run where a chip's minutes are counted.
"""

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_FFN = ("gate", "up", "down", "shared_gate", "shared_up", "shared_down")


def arms_of(family: str):
    """[(arm, forward's keyword arguments, which (group, tensor) to
    round through float8 or None)], in the order they run."""
    import jax.numpy as jnp
    if family == "nemotron_h":
        return [("as_stated", {}, None),
                ("state_bfloat16", {"state_dtype": jnp.bfloat16}, None),
                ("experts_float8", {}, lambda group, name: name in (
                    "up", "down", "shared_up", "shared_down"))]
    if family in ("deepseek_v2", "exaone_moe"):
        return [("as_stated", {}, None),
                ("experts_float8", {}, lambda group, name: name in _FFN),
                ("layers_float8", {}, lambda group, name: True)]
    if family in ("minicpm_sala", "qwen3_next", "falcon_h1"):
        return [("as_stated", {}, None),
                ("state_bfloat16", {"state_dtype": jnp.bfloat16}, None),
                ("layers_float8", {}, lambda group, name: True)]
    if family == "keye_vl2":
        from rnb_tpu.models.keye_vl2.network import FLOAT8_BITS
        return [("as_stated", {}, None),
                ("index_float8", {"index_bits": FLOAT8_BITS}, None),
                ("all_causal_keys", {"select": "causal"}, None),
                ("recent_keys", {"select": "recent"}, None),
                ("layers_float8", {}, lambda group, name: True)]
    if family == "kimi_linear":
        return [("as_stated", {}, None),
                ("state_bfloat16", {"state_dtype": jnp.bfloat16}, None),
                ("scalar_gate", {"gate": "scalar"}, None),
                ("rotary_on", {"rotary": True}, None),
                ("layers_float8", {}, lambda group, name: True)]
    if family == "dots3_note":
        from rnb_tpu.models.dots3_note.network import FLOAT8_BITS
        return [("as_stated", {}, None),
                ("index_float8", {"index_bits": FLOAT8_BITS}, None)] \
            + [(name, {}, None) for name in (
                "flat_gates", "no_rescale", "window_a_key_short",
                "theta_swapped", "index_no_rotary", "old_draw")] \
            + [("layers_float8", {}, lambda group, name: True)]
    if family == "phi4_flash":
        return [("as_stated", {}, None),
                ("state_bfloat16", {"state_dtype": jnp.bfloat16}, None)] \
            + [(name, {}, None) for name in (
                "one_softmax", "window_off", "memory_gated")] \
            + [("layers_float8", {}, lambda group, name: name.split(
                ".")[-1] in PHI4_FLASH_MATRICES)]
    if family == "xing4":
        return [("as_stated", {}, None)] \
            + [(name, {}, None) for name in (
                "no_dynamic_term", "one_stream_read", "sinkhorn_5",
                "sinkhorn_19", "mappings_bfloat16")] \
            + [("experts_float8", {}, lambda group, name: name in _FFN),
               ("layers_float8", {}, lambda group, name: True)]
    raise ValueError("no control arms for family %r" % (family,))


def xing4_faults(cfg):
    """{arm: fault} for a ``Xing4Config``, planted from outside the
    program as :func:`dots3_note_faults`' are: every mapping's ``alpha``
    at zero (the dynamic term ``alpha (x^ phi)`` dropped: the mappings
    no longer depend on the token), the head reading one stream in the
    place of their sum, five Sinkhorn steps for twenty and nineteen
    (twenty means twenty: one step short is refused), and the
    mappings' coefficients — the sigmoids, ``exp`` and every Sinkhorn
    step, what ``hyper/maps`` spends its time on — in bfloat16. (The
    kernel's share of the float32 arithmetic, the statistic, the
    projection's scaling and both mixings' sums, cannot be lowered on
    this chip: Mosaic refuses ``hyper_mix`` in bfloat16 for a v5e, a
    ``vector.broadcast`` of a float32 scalar to bfloat16 lanes in the
    logistic. ``tests/test_xing4.py`` lowers all of ``ops/hyper.COMPUTE``
    under the interpreter.)"""
    import jax.numpy as jnp
    from jax import lax

    from rnb_tpu.ops import hyper

    def step_by_step(m, iters, hc_eps):
        # under a loop each step's result is rounded where it lies;
        # written out, XLA's CPU compiler also takes eleven seconds a
        # sublayer for them in bfloat16
        return lax.fori_loop(0, iters, lambda _, m: hyper.sinkhorn_step(
            m, hc_eps), m)
    as_given = hyper.coefficients_of

    def in_bfloat16(logits, *sizes):
        # ``coefficients_of`` works in the logits' dtype
        return as_given(logits.astype(jnp.bfloat16), *sizes)
    return {
        "no_dynamic_term": {"scale": {
            "l%d.%s_hc_alpha" % (i, sub): 0.0
            for i in range(cfg.num_hidden_layers)
            for sub in ("attn", "ffn")}},
        "one_stream_read": {"patch": {
            "merge_streams": lambda last, n: last.astype(
                jnp.float32)[:, :last.shape[1] // n]}},
        "sinkhorn_5": {"cfg": dataclasses.replace(
            cfg, hc_sinkhorn_iters=5)},
        "sinkhorn_19": {"cfg": dataclasses.replace(
            cfg, hc_sinkhorn_iters=19)},
        "mappings_bfloat16": {"patch": {"coefficients_of": in_bfloat16,
                                        "sinkhorn": step_by_step},
                              "patched": hyper}}


#: the stored matrices of a Phi-4-mini-flash layer (a stacked group's
#: vectors have two axes too: the rounding goes by name)
PHI4_FLASH_MATRICES = ("gate_up", "down", "in_proj", "x_proj", "dt_proj",
                       "out_proj", "qkv", "q", "o", "g_in", "g_out")


def phi4_flash_faults():
    """{arm: fault} of ``models/phi4_flash/network``: attributes of the
    module replaced while the arm's program is traced
    (:func:`dots3_note_faults`' ``patch``); ``network.forward`` has no
    switch for any of them."""
    from rnb_tpu.models.phi4_flash import network
    return {
        "one_softmax": {"patch": {
            "lambda_of": lambda p, lambda_init: 0.0 * lambda_init}},
        "window_off": {"patch": {
            "window_attention": network.full_attention}},
        "memory_gated": {"patch": {
            "scan_memory": lambda gated, y: gated}}}


def dots3_note_faults(cfg):
    """{arm: fault} for a ``Dots3NoteConfig``. A fault is what the arm's
    program is given in the place of the stated one: ``scale`` {tensor:
    factor} on the parameter tree (and, with ``reference_too``, on what
    the reference reads), ``cfg`` another configuration, ``patch``
    attributes of ``models/dots3_note/network`` replaced while the
    arm's program is traced. ``network.forward`` itself has no switch
    for any of them."""
    from rnb_tpu.models.dots3_note.checkpoint import LATENT_SPREAD

    def scaled(tensor, factor, sliding=None):
        return {"l%d.%s" % (i, tensor): factor(cfg.geometry(i))
                for i in range(cfg.num_hidden_layers)
                if sliding in (None, cfg.is_sliding(i))}
    faults = {}
    for kind, sliding in (("full", False), ("sliding", True)):
        faults["gate_flat_" + kind] = {
            "scale": scaled("attn_gate", lambda geo: 0.0, sliding)}
        for at, (latent, norm) in enumerate((("q", "q_a_norm"),
                                             ("kv", "kv_a_norm"))):
            faults["rho_%s_%s" % (latent, kind)] = {"scale": scaled(
                norm, lambda geo, at=at: 1.0 / cfg.rescales(geo)[at],
                sliding)}

    def together(*names):
        return {"scale": {k: v for n in names
                          for k, v in faults[n]["scale"].items()}}
    faults["flat_gates"] = together("gate_flat_full", "gate_flat_sliding")
    faults["no_rescale"] = together("rho_q_full", "rho_kv_full",
                                    "rho_q_sliding", "rho_kv_sliding")
    faults["window_a_key_short"] = {"cfg": dataclasses.replace(
        cfg, sliding_window_size=cfg.sliding_window_size - 1)}
    faults["theta_swapped"] = {"cfg": dataclasses.replace(
        cfg, full=dataclasses.replace(cfg.full, theta=cfg.sliding.theta),
        sliding=dataclasses.replace(cfg.sliding, theta=cfg.full.theta))}
    faults["index_no_rotary"] = {"patch": {
        "rotate_front": lambda x, positions, inv_freq: x}}
    faults["old_draw"] = {"reference_too": True, "scale": {
        k: v for at, norm in enumerate(("q_a_norm", "kv_a_norm"))
        for k, v in scaled(norm, lambda geo, at=at: cfg.rescales(geo)[at]
                           / LATENT_SPREAD).items()}}
    return faults


def scale_tensor(w, factor):
    """``w`` times ``factor`` in float32, rounded as ``w`` is stored."""
    import jax.numpy as jnp
    return (w.astype(jnp.float32) * factor).astype(w.dtype)


def planted(params, fault):
    """The parameter tree with the fault's ``scale`` on it (the groups
    touched are copies: ``params`` stays as stated)."""
    out = dict(params)
    for name, factor in fault.get("scale", {}).items():
        group, tensor = name.split(".", 1)
        out[group] = dict(out[group])
        out[group][tensor] = scale_tensor(out[group][tensor], factor)
    return out


def reads_planted(read, fault):
    """The reference's reader with the fault's ``scale`` on what it
    reads, rounded as the program stores it, where the fault says
    ``reference_too``; else ``read``."""
    import jax.numpy as jnp
    if not fault.get("reference_too"):
        return read
    scale = fault["scale"]

    def scaled(name, expert_ids=None):
        w = read(name, expert_ids)
        if name not in scale:
            return w
        return scale_tensor(w.astype(jnp.bfloat16), scale[name]) \
            .astype(jnp.float32)
    return scaled


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "nemotron3-nano-l14-ep2.json"))
    parser.add_argument("--seed", type=int, default=2_500_000_017)
    parser.add_argument("--lengths", default=None, help="prompt lengths, "
                        "comma-separated (default: the family file's "
                        "CONTROL_LENGTHS, else 300,1190,700,2400)")
    parser.add_argument("--arms", default=None, help="the arms to run "
                        "behind as_stated, comma-separated (default: all "
                        "of the family's)")
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import manifest
    from benchmarks.references import compare
    from rnb_tpu.models import seeded, token_stages as stages
    with open(args.config) as f:
        config = json.load(f)
    family = manifest.load_family(config["family"])
    recipe, _ = family.make_weights(
        config, args.seed, os.path.join(REPO, "checkpoints", "control"))
    name = seeded.read_recipe(recipe)["family"]
    reference = importlib.import_module("benchmarks.references." + name)
    checkpoint, network = (
        importlib.import_module("rnb_tpu.models.%s.%s" % (name, part))
        for part in ("checkpoint", "network"))
    published = family.published_keys(config)
    cfg, _, held = checkpoint.load_recipe(recipe)
    limit = float(config.get("share_of_spread", family.SHARE_OF_SPREAD))
    device = jax.devices()[0]
    params = checkpoint.make_params(cfg, args.seed, held, device)
    # a family without experts has no slots (models/token_stages.py)
    slots = network.held_slots(cfg, held) \
        if "expert_served" in network.COUNTERS else None
    lengths = args.lengths.split(",") if args.lengths else getattr(
        family, "CONTROL_LENGTHS", (300, 1190, 700, 2400))
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in lengths]
    chunk = cfg.chunk_size
    rows = -(-sum(stages.rows_of_tokens(len(p), chunk)
                  for p in prompts) // 16) * 16
    tokens, meta, offsets = stages.pack_prompts(prompts, rows, chunk)
    read = checkpoint.reference_reader(cfg, args.seed, device)
    ref_model = reference.Reference(published)
    out = {"device": device.device_kind, "family": name, "limit": limit,
           "rows": rows, "lengths": [len(p) for p in prompts]}
    arms, kept = arms_of(name), None
    if args.arms is not None:
        wanted = args.arms.split(",")
        unknown = set(wanted) - {arm for arm, _, _ in arms}
        if unknown:
            parser.error("no such arm of %s: %s" % (name, sorted(unknown)))
        arms = [a for a in arms if a[0] == "as_stated" or a[0] in wanted]
    faults = dots3_note_faults(cfg) if name == "dots3_note" \
        else phi4_flash_faults() if name == "phi4_flash" \
        else xing4_faults(cfg) if name == "xing4" else {}
    programs = {}
    for arm, kwargs, rounded in arms:
        fault = faults.get(arm, {})
        if rounded is not None:
            for group, block in params.items():
                if isinstance(block, dict):
                    for tensor, w in block.items():
                        if w.ndim >= 2 and rounded(group, tensor):
                            block[tensor] = w.astype(
                                jnp.float8_e4m3fn).astype(w.dtype)
        arm_cfg = fault.get("cfg", cfg)
        # arms that differ in the weights alone share one program
        own = arm if kwargs or "cfg" in fault or "patch" in fault else None
        if own not in programs:
            programs[own] = jax.jit(
                lambda p, s, t, m, arm_cfg=arm_cfg, kwargs=kwargs:
                network.forward(
                    arm_cfg, p, s, t, m[0], m[1], m[2],
                    interpret=device.platform != "tpu", **kwargs))
        with mock.patch.multiple(fault.get("patched", network),
                                 **fault["patch"]) \
                if "patch" in fault else contextlib.nullcontext():
            logits, chosen, *_ = programs[own](
                planted(params, fault), slots, tokens, meta)
        arm_read = reads_planted(read, fault)
        chosen = jax.tree.map(np.asarray, chosen)
        # a family that chooses nothing is given nothing: its reference
        # is the same for every arm, and is computed once
        again = kept is None or fault.get("reference_too") or any(
            leaf.size for leaf in jax.tree.leaves(chosen))
        want, short, key_short, defects = [], 0.0, None, ([], [])
        with jax.default_matmul_precision("highest"):
            for prompt, first in zip(prompts, offsets) if again else ():
                # the request's own choices, as a sample keeps them
                # (models/token_stages.py) and the run's check reads them
                keep = getattr(network, "request_choices", None)
                sample = None if keep is None else keep(
                    cfg, chosen, first * chunk, len(prompt))
                forced = chosen[:, first * chunk:
                                first * chunk + len(prompt)] \
                    if keep is None else family.unpack_choices(
                        config, sample, len(prompt))
                given = {}
                if isinstance(forced, tuple):
                    # two kinds of choice: the router's and the keys
                    # every query read; an arm that reads other keys
                    # than the indexer's is held to the reference's own
                    forced, sets, strays = forced
                    if "select" not in kwargs:
                        given = {"forced_sets": sets}
                ref = ref_model.forward(arm_read, prompt, held=held,
                                        forced=forced, **given)
                want.append(np.asarray(ref["logits"]))
                short = max([short] + [
                    float(ref[key].max())
                    for key in ("shortfall", "group_shortfall")
                    if key in ref])
                if "res_defect" in ref:
                    # each (sublayer, token)'s defect, as a sample keeps
                    # them, and the reference's own
                    defects[0].append(sample["res_defect"])
                    defects[1].append(np.asarray(ref["res_defect"]))
                if given:
                    key_short = max(
                        key_short or 0.0,
                        float("inf") if strays else 0.0,
                        float(np.asarray(ref["key_shortfall"]).max()))
        kept = np.stack(want) if again else kept
        got, want = np.asarray(logits)[:len(prompts)], kept
        # a family that holds the logits to more than the one limit
        # brings its comparison (``falcon_h1``'s root mean square)
        verdict = family.compare_logits(config, got, want) \
            if hasattr(family, "compare_logits") \
            else compare(got, want, limit)
        out[arm] = {"share_of_spread": verdict["share_of_spread"],
                    "ok": verdict["ok"], "route_shortfall_max": short}
        # the root mean square beside the worst logit: a limit of
        # ``falcon_h1``'s, a record for the others
        out[arm]["rms_share_of_spread"] = verdict.get(
            "rms_share_of_spread", float(np.sqrt(np.mean(
                (got.astype(np.float64) - want) ** 2)) / want.std()))
        worst = {"route_shortfall_max": short}
        if key_short is not None:
            out[arm]["key_shortfall_max"] = key_short
            out[arm]["ok"] = bool(verdict["ok"]
                                  and key_short <= float(config.get(
                                      "key_slack", family.KEY_SLACK)))
            worst.update(key_bad=int(key_short == float("inf")),
                         key_shortfall_max=key_short)
        if defects[0]:
            out[arm]["res_defect_apart"] = worst["res_defect_apart"] \
                = family.defects_apart(*defects)
        if hasattr(family, "held_to_the_limits"):
            # every limit of the run's own check, the router's too
            out[arm]["ok"] = family.held_to_the_limits(
                config, dict(verdict), worst)["ok"]
        print("[control] %s %s" % (arm, out[arm]), file=sys.stderr,
              flush=True)
    # a family that says so holds every control to a failure (or every
    # one but those it names as recorded either way), the others at
    # least one
    may_pass = getattr(family, "CONTROL_MAY_PASS", None)
    fails = any if may_pass is not None \
        or getattr(family, "EVERY_CONTROL_FAILS", False) else all
    out["ok"] = out["as_stated"]["ok"] and not fails(
        out[arm]["ok"] for arm, _, _ in arms[1:]
        if arm not in (may_pass or ()))
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
