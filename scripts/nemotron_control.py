"""The lower-precision control of the Nemotron-H comparison, on the
device it runs on: a few prompts packed into one dispatch at the
configuration's real widths, compared with the float32 reference (the
router's choices given) three times — as the configuration states its
precision, with the experts' matrices rounded through float8 (e4m3),
and with the scan's states carried in bfloat16. The first must lie
inside the family file's tolerance and a control outside it; the last
stdout line is one JSON object with the three shares of the spread.

    python3 scripts/nemotron_control.py [--config <file>] [--seed N]
    chiprun -- python3 scripts/nemotron_control.py
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "nemotron3-nano-l14-ep2.json"))
    parser.add_argument("--seed", type=int, default=2_500_000_017)
    parser.add_argument("--lengths", default="300,1190,700,2400")
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import manifest
    from benchmarks.references import compare, nemotron_h as reference
    from rnb_tpu.models.nemotron_h import checkpoint, network, stages
    with open(args.config) as f:
        config = json.load(f)
    family = manifest.load_family(config["family"])
    published = family.published_keys(config)
    cfg = network.NemotronHConfig.from_published(published)
    held = family.held_experts(config)
    device = jax.devices()[0]
    params = checkpoint.make_params(cfg, args.seed, held, device)
    slots = network.held_slots(cfg, held)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in args.lengths.split(",")]
    chunk = cfg.chunk_size
    counts = [stages.rows_of_tokens(len(p), chunk) for p in prompts]
    rows = -(-sum(counts) // 16) * 16
    tokens = np.zeros((rows, chunk), np.int32)
    per_row = np.zeros(rows, np.int32)
    offsets = [0]
    for prompt, n in zip(prompts, counts):
        row = offsets[-1]
        tokens.reshape(-1)[row * chunk:row * chunk + len(prompt)] = prompt
        per_row[row:row + n] = chunk
        per_row[row + n - 1] = len(prompt) - (n - 1) * chunk
        offsets.append(row + n)
    meta = stages.dispatch_meta(offsets, per_row, rows, chunk)
    # the float8 arm comes last: it rounds the experts' matrices where
    # they lie, each conversion a program of its own. Inside the
    # dispatch's program the v5e's compiler fuses bf16 -> float8 -> bf16
    # in front of the grouped product and keeps the excess precision:
    # the arm then read the stated precision's logits bit for bit
    # (PR 29, my chip run)
    arms = {
        "as_stated": {},
        "state_bfloat16": {"state_dtype": jnp.bfloat16},
        "experts_float8": {}}
    read = checkpoint.reference_reader(cfg, args.seed, device)
    ref_model = reference.Reference(published)
    out = {"device": device.device_kind, "limit": family.SHARE_OF_SPREAD,
           "rows": rows, "lengths": [len(p) for p in prompts]}
    for arm, kwargs in arms.items():
        if arm == "experts_float8":
            for index in cfg.blocks_of(network.EXPERTS):
                block = params["b%d" % index]
                for name in ("up", "down", "shared_up", "shared_down"):
                    block[name] = block[name].astype(
                        jnp.float8_e4m3fn).astype(jnp.bfloat16)
        logits, chosen, _ = jax.jit(
            lambda p, s, t, m: network.forward(
                cfg, p, s, t, m[0], m[1], m[2],
                interpret=device.platform != "tpu", **kwargs))(
            params, slots, tokens, meta)
        chosen = np.asarray(chosen)
        want, short = [], 0.0
        with jax.default_matmul_precision("highest"):
            for prompt, first in zip(prompts, offsets):
                ref = ref_model.forward(
                    read, prompt, held=held,
                    forced=chosen[:, first * chunk:
                                  first * chunk + len(prompt)])
                want.append(np.asarray(ref["logits"]))
                short = max(short, float(ref["shortfall"].max()))
        verdict = compare(np.asarray(logits)[:len(prompts)],
                          np.stack(want), family.SHARE_OF_SPREAD)
        out[arm] = {"share_of_spread": verdict["share_of_spread"],
                    "ok": verdict["ok"], "route_shortfall_max": short}
        print("[control] %s %s" % (arm, out[arm]), file=sys.stderr,
              flush=True)
    out["ok"] = out["as_stated"]["ok"] and not (
        out["experts_float8"]["ok"] and out["state_bfloat16"]["ok"])
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
