"""The kernels of ``rnb_tpu.ops.indexed`` alone, on the chip, at
Keye-VL's widths (16 index heads of 64 on one key head; 32 / 4 heads of
128; ``topk`` 2,048): a check of the sets against ``lax.top_k`` on a
small pool, then the time of each piece, by the host's clock around
jitted calls, over pools of 128 rows packed as one, two and three
requests. Lines go to stdout and to
``chiprun_out/indexed_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/indexed_sweep.py [--rows=128]

Off the TPU the kernels run in Pallas's interpret mode, which at these
sizes is of no use (``--rows=4`` is a dry run of the control flow).
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from rnb_tpu.ops import indexed  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "indexed_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
ROWS = int(([a.split("=")[1] for a in sys.argv
             if a.startswith("--rows=")] or [128])[0])
QLEN, HEADS, DIM, TOPK = 128, 16, 64, 2048
HK, PER, HEAD = 4, 8, 128
REPEATS = 5


def say(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sweep.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def timed(f, *args):
    out = jax.block_until_ready(f(*args))
    took = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        took.append(1e3 * (time.perf_counter() - t0))
    return out, round(float(np.median(took)), 3)


def operands(rng, tokens):
    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    return (draw(tokens, HEADS, DIM), draw(tokens, DIM),
            jnp.asarray(rng.normal(size=(tokens, HEADS)) / 32,
                        jnp.float32))


def check_sets(rng):
    """The kernels' sets against ``lax.top_k`` over the same keys."""
    rows, topk = 16, 256
    tokens = rows * QLEN
    row_start = jnp.asarray([0] * 9 + [9] * 7, jnp.int32)
    start, _ = indexed.token_table(
        row_start, jnp.full((rows,), QLEN, jnp.int32), QLEN)
    q, k, w = operands(rng, tokens)
    k = k.at[5].set(k[3]).at[700].set(k[3])         # equal scores
    keys = indexed.index_keys(q, k, w, start, interpret=INTERPRET)
    position = jnp.arange(tokens, dtype=jnp.int32) - start
    tau, cut = indexed.thresholds(keys, position, topk, INTERPRET)
    mask = np.asarray(indexed.chosen_mask(keys, tau, cut, start))
    qa = jnp.zeros((tokens, 1, 1, 128), jnp.bfloat16)
    _, sets = indexed.masked_attention(qa, qa[:, :, 0], qa[:, :, 0], keys,
                                       tau, cut, start, INTERPRET)
    bits_differ = int((indexed.unpack_sets(sets)[:, :tokens]
                       != mask).sum())
    _, best = jax.lax.top_k(keys, topk)
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(best), True, axis=1)
    at = np.arange(tokens)
    want &= (at[None, :] <= at[:, None]) \
        & (at[None, :] >= np.asarray(start)[:, None])
    say({"check": "sets", "tokens": tokens, "topk": topk,
         "differ": int((want != mask).sum()), "bits_differ": bits_differ,
         "tied_queries": int((np.asarray(cut) < tokens).sum())})


def main():
    rng = np.random.default_rng(46)
    say({"device": DEVICE.device_kind, "rows": ROWS})
    check_sets(rng)
    tokens = ROWS * QLEN
    q, k, w = operands(rng, tokens)
    qa = jnp.asarray(rng.normal(size=(tokens, HK, PER, HEAD)) / 11,
                     jnp.bfloat16)
    ka, va = (jnp.asarray(rng.normal(size=(tokens, HK, HEAD)),
                          jnp.bfloat16) for _ in range(2))
    tile_q, _ = indexed.attention_tiles(tokens)
    for requests in (1, 2, 3):
        firsts = [ROWS * r // requests for r in range(requests)]
        row_start = jnp.asarray(
            [max(f for f in firsts if f <= r) for r in range(ROWS)],
            jnp.int32)
        start, _ = indexed.token_table(
            row_start, jnp.full((ROWS,), QLEN, jnp.int32), QLEN)
        position = jnp.arange(tokens, dtype=jnp.int32) - start
        line = {"requests": requests, "tokens": tokens}
        keys, line["scores_ms"] = timed(jax.jit(
            lambda q, k, w, s: indexed.index_keys(q, k, w, s,
                                                  INTERPRET)),
            q, k, w, start)
        (tau, cut), line["thresholds_ms"] = timed(jax.jit(
            lambda keys, p: indexed.thresholds(keys, p, TOPK,
                                               INTERPRET)),
            keys, position)
        (_, sets), line["attention_ms"] = timed(jax.jit(
            lambda *a: indexed.masked_attention(*a,
                                                interpret=INTERPRET)),
            qa, ka, va, keys, tau, cut, start)
        chose, reached = indexed.count_sets(sets, tile_q)
        line.update(
            tiles_chosen=int(reached),
            tiles_causal=indexed.causal_tiles(tokens),
            chosen_keys=int(chose.sum()),
            tied_queries=int((np.asarray(cut) < tokens).sum()),
            sets_exact=bool((np.asarray(chose) == np.minimum(
                np.asarray(position) + 1, TOPK)).all()))
        say(line)


if __name__ == "__main__":
    main()
