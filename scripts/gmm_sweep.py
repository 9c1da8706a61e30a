"""The sweep behind ``rnb_tpu.ops.moe.gmm_tiling``'s table (PR 44): the
grouped product's tiles under a real dispatch's group sizes, on the chip.
Phase A: each family's stack at its real widths serves one full dispatch
of the cell's prompts and returns the pairs each held expert served, a
layer. Phase B: the kernel alone over the first and the last expert
layer's counts, by the host's clock around jitted calls, a tiling a line
(``base``: the wide tiles; a tiling that runs out of VMEM gives its
error). Lines go to stdout and to ``chiprun_out/gmm_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/gmm_sweep.py [--only=nemotron ...]

Some 7 minutes of one v5e for the three cells (my chip run, PR 44). A
product added to ``TILINGS`` is timed the same way; off the TPU the
kernel runs in Pallas's interpret mode, which at these widths is of no
use.
"""
import gc
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import manifest  # noqa: E402
from rnb_tpu.models import seeded, token_stages as stages  # noqa: E402
from rnb_tpu.ops import moe  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "gmm_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
ONLY = [a.split("=")[1] for a in sys.argv if a.startswith("--only=")]
SEED = 3_100_044_001

#: (tag, configuration, rows of a full dispatch)
CELLS = [("nemotron", "nemotron3-nano-l14-ep2", 64),
         ("qwen", "qwen3-next-l4-ep2", 128),
         ("deepseek", "deepseek-v2-ep8", 64)]


def say(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sweep.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def real_counts(config, rows):
    """(layers, held): the pairs each held expert served in one full
    dispatch."""
    family = manifest.load_family(config["family"])
    recipe, _ = family.make_weights(
        config, SEED, os.path.join(REPO, "checkpoints", "gmm_sweep"))
    name = seeded.read_recipe(recipe)["family"]
    checkpoint, network = (
        importlib.import_module("rnb_tpu.models.%s.%s" % (name, part))
        for part in ("checkpoint", "network"))
    cfg, _, held = checkpoint.load_recipe(recipe)
    params = checkpoint.make_params(cfg, SEED, held, DEVICE)
    slots = network.held_slots(cfg, held)
    chunk = cfg.chunk_size
    lengths = family.prompt_lengths(config)
    rng = np.random.default_rng(SEED)
    names = sorted(lengths)
    rng.shuffle(names)
    prompts, used = [], 0
    for n in names:
        need = stages.rows_of_tokens(lengths[n], chunk)
        if used + need <= rows:
            prompts.append(rng.integers(0, cfg.vocab_size, lengths[n])
                           .astype(np.int32))
            used += need
    if used < rows:      # the rest: one request cut to what is left
        prompts.append(rng.integers(
            0, cfg.vocab_size, (rows - used) * chunk - int(
                rng.integers(0, chunk))).astype(np.int32))
    tokens, meta, _ = stages.pack_prompts(prompts, rows, chunk)
    t0 = time.time()
    out = jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2], interpret=INTERPRET))(
        params, slots, tokens, meta)
    names = ("logits", "chosen") + tuple(network.COUNTERS)
    served = np.asarray(out[names.index("expert_served")])
    say({"phase": "counts", "family": name, "rows": rows,
         "prompts": [len(p) for p in prompts],
         "forward_s": round(time.time() - t0, 1),
         "served": served.tolist()})
    del params, out
    gc.collect()
    return served


def visits(counts, tm):
    """``moe.gmm_visits`` on the host."""
    ends = np.cumsum(counts)
    starts = ends - counts
    return int(np.where(counts > 0, -(-ends // tm) - starts // tm, 0).sum())


def sweep(tag, m, k, n, transposed, counts_sets, tilings):
    """Times each tiling on each set of counts."""
    held = len(counts_sets[0])
    rows = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
    shape = (held, n, k) if transposed else (held, k, n)
    weights = (jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
               * 0.02).astype(jnp.bfloat16)
    want = None
    for tiling in tilings:
        fn = jax.jit(lambda r, w, c, tiling=tiling: moe.grouped_matmul(
            r, w, c, INTERPRET, transposed=transposed, tiling=tiling))
        line = {"phase": "sweep", "product": tag, "m": m, "k": k, "n": n,
                "tiling": list(tiling), "base": tiling == tilings[0]}
        try:
            t0 = time.time()
            compiled = fn.lower(rows, weights,
                                jnp.asarray(counts_sets[0])).compile()
            line["compile_s"] = round(time.time() - t0, 1)
        except Exception as err:           # out of VMEM, mostly
            text = str(err).strip().splitlines()
            line["error"] = " | ".join(text[:2] + text[-1:])[:300]
            say(line)
            continue
        ms, fills = [], []
        for i, counts in enumerate(counts_sets):
            c = jnp.asarray(counts)
            got = jax.block_until_ready(compiled(rows, weights, c))
            if i == 0:
                kept = np.asarray(got[:int(counts.sum())])
                if want is None:
                    want = kept
                else:
                    line["max_abs_diff"] = float(np.abs(kept - want).max())
                del kept
            best = 1e9
            reps = 2 if INTERPRET else 10
            for _ in range(1 if INTERPRET else 3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    got = compiled(rows, weights, c)
                jax.block_until_ready(got)
                best = min(best, (time.perf_counter() - t0) / reps)
            ms.append(round(best * 1e3, 3))
            v = visits(counts, tiling[0])
            fills.append(round(100.0 * counts.sum() / (v * tiling[0]), 1))
        line.update(ms=ms, mean_ms=round(float(np.mean(ms)), 3),
                    fill_pct=fills, held_pairs=[int(c.sum())
                                                for c in counts_sets])
        say(line)
    del rows, weights
    gc.collect()


TILINGS = {
    # K 2688 -> N 1856 (weights (G, N, K)); K 1856 -> N 2688
    "nemotron": (
        [(512, 896, 1024), (256, 896, 1024), (128, 896, 1024),
         (128, 2688, 1024), (256, 2688, 640), (256, 2688, 512),
         (128, 2688, 640), (256, 1344, 1024), (128, 1344, 1024),
         (256, 896, 1856), (128, 1344, 1856), (384, 896, 1024),
         (128, 2688, 896)],
        [(512, 1856, 896), (256, 1856, 896), (128, 1856, 896),
         (256, 1856, 1024), (128, 1856, 1344), (384, 1856, 896),
         (256, 928, 1344), (128, 1856, 1024)]),
    # K 2048 -> N 512; K 512 -> N 2048
    "qwen": (
        [(512, 2048, 512), (256, 2048, 512), (128, 2048, 512),
         (384, 2048, 512), (320, 2048, 512), (64, 2048, 512)],
        [(512, 512, 1024), (256, 512, 1024), (128, 512, 1024),
         (256, 512, 2048), (128, 512, 2048), (384, 512, 1024),
         (320, 512, 1024), (64, 512, 2048)]),
    # K 5120 -> N 1536; K 1536 -> N 5120
    "deepseek": (
        [(512, 1024, 768), (256, 1024, 768), (128, 1024, 768),
         (128, 5120, 512), (256, 5120, 384), (256, 2560, 768),
         (128, 2560, 768), (256, 1024, 1536), (128, 5120, 384),
         (256, 5120, 256)],
        [(512, 1536, 1024), (256, 1536, 1024), (128, 1536, 1024),
         (256, 1536, 1280), (128, 1536, 1280), (128, 1536, 2560),
         (384, 1536, 1024)]),
}


def main():
    for tag, conf, rows in CELLS:
        if ONLY and tag not in ONLY:
            continue
        with open(os.path.join(REPO, "benchmarks", "configs",
                               conf + ".json")) as f:
            config = json.load(f)
        first, second = TILINGS[tag]
        served = real_counts(config, rows)
        picks = [np.asarray(served[i], np.int32)
                 for i in sorted({0, len(served) - 1})]
        hidden = config["hidden_size"]
        inner = config["moe_intermediate_size"]
        m = rows * config["chunk_size"] * config["num_experts_per_tok"]
        sweep(tag + ".up", m, hidden, inner, True, picks, first)
        sweep(tag + ".down", m, inner, hidden, False, picks, second)


if __name__ == "__main__":
    main()
