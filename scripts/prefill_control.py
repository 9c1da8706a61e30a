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
and holds float8 to a failure).
"""

import argparse
import importlib
import json
import os
import sys

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
    raise ValueError("no control arms for family %r" % (family,))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "nemotron3-nano-l14-ep2.json"))
    parser.add_argument("--seed", type=int, default=2_500_000_017)
    parser.add_argument("--lengths", default=None, help="prompt lengths, "
                        "comma-separated (default: the family file's "
                        "CONTROL_LENGTHS, else 300,1190,700,2400)")
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
    for arm, kwargs, rounded in arms:
        if rounded is not None:
            for group, block in params.items():
                if isinstance(block, dict):
                    for tensor, w in block.items():
                        if w.ndim >= 2 and rounded(group, tensor):
                            block[tensor] = w.astype(
                                jnp.float8_e4m3fn).astype(w.dtype)
        logits, chosen, *_ = jax.jit(
            lambda p, s, t, m: network.forward(
                cfg, p, s, t, m[0], m[1], m[2],
                interpret=device.platform != "tpu", **kwargs))(
            params, slots, tokens, meta)
        chosen = jax.tree.map(np.asarray, chosen)
        # a family that chooses nothing is given nothing: its reference
        # is the same for every arm, and is computed once
        again = kept is None or any(
            leaf.size for leaf in jax.tree.leaves(chosen))
        want, short, key_short = [], 0.0, None
        with jax.default_matmul_precision("highest"):
            for prompt, first in zip(prompts, offsets) if again else ():
                # the request's own choices, as a sample keeps them
                # (models/token_stages.py) and the run's check reads them
                keep = getattr(network, "request_choices", None)
                forced = chosen[:, first * chunk:
                                first * chunk + len(prompt)] \
                    if keep is None else family.unpack_choices(
                        config, keep(cfg, chosen, first * chunk,
                                     len(prompt)), len(prompt))
                given = {}
                if isinstance(forced, tuple):
                    # two kinds of choice: the router's and the keys
                    # every query read; an arm that reads other keys
                    # than the indexer's is held to the reference's own
                    forced, sets, strays = forced
                    if "select" not in kwargs:
                        given = {"forced_sets": sets}
                ref = ref_model.forward(read, prompt, held=held,
                                        forced=forced, **given)
                want.append(np.asarray(ref["logits"]))
                short = max([short] + [
                    float(ref[key].max())
                    for key in ("shortfall", "group_shortfall")
                    if key in ref])
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
        if "rms_share_of_spread" in verdict:
            out[arm]["rms_share_of_spread"] = verdict["rms_share_of_spread"]
        if key_short is not None:
            out[arm]["key_shortfall_max"] = key_short
            out[arm]["ok"] = bool(verdict["ok"]
                                  and key_short <= float(config.get(
                                      "key_slack", family.KEY_SLACK)))
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
