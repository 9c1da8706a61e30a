"""The kernels of ``rnb_tpu.ops.indexed`` alone, on the chip, at
Keye-VL's widths (16 index heads of 64 on one key head; 32 / 4 heads of
128; ``topk`` 2,048): a check of the sets against ``lax.top_k`` on a
small pool and of the attention kernel against the one it replaced
(``tests/keye_parent.py``: PR 46's, a key-value head a grid step, with
the mixer's passes over q in front of it), then the time of each piece,
by the host's clock around jitted calls, over pools of 128 rows packed
as one, two and three requests: the scores, the thresholds, the parent's
attention (its kernel alone, the passes around it as XLA runs them, and
both) and the attention kernel as it stands (the forms PR 54 timed
beside it — queries a tile, the heads that share a product, the mask as
a ``where``, k transposed, the heads' loop rolled — are a record in
``ops/indexed.py``'s text: none was faster, and the kernel keeps one).
Lines go to stdout and to ``chiprun_out/indexed_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/indexed_sweep.py [--rows=128]

Off the TPU the kernels run in Pallas's interpret mode, which at these
sizes is of no use (``--rows=4`` is a dry run of the control flow).
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import keye_parent  # noqa: E402
from rnb_tpu.ops import banded, indexed, rope  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "indexed_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"


def option(name, default):
    return ([a.split("=", 1)[1] for a in sys.argv
             if a.startswith("--%s=" % name)] or [default])[0]


ROWS = int(option("rows", 128))
QLEN, HEADS, DIM, TOPK = 128, 16, 64, 2048
HQ, HK, HEAD, EPS = 32, 4, 128, 1e-6
INV_FREQ = (1e7 ** (-np.arange(0, HEAD, 2, dtype=np.float64) / HEAD)) \
    .astype(np.float32)
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


def attention_operands(rng, tokens):
    """(q as its product writes it, k and v as the mixer hands them, the
    query norm's weight)."""
    return (jnp.asarray(rng.normal(size=(tokens, HQ * HEAD)) * 3,
                        jnp.float32),
            jnp.asarray(rng.normal(size=(tokens, HK * HEAD)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(tokens, HK * HEAD)), jnp.bfloat16),
            jnp.asarray(1 + 0.2 * rng.normal(size=HEAD), jnp.bfloat16))


def pool(rows, requests):
    """``rows`` rows as ``requests`` requests of equal length: (the rows'
    requests, each token's request's first token, its index inside it)."""
    firsts = [rows * r // requests for r in range(requests)]
    row_start = jnp.asarray(
        [max(f for f in firsts if f <= r) for r in range(rows)], jnp.int32)
    start, _ = indexed.token_table(
        row_start, jnp.full((rows,), QLEN, jnp.int32), QLEN)
    return row_start, start, \
        jnp.arange(rows * QLEN, dtype=jnp.int32) - start


def parent_calls(tokens):
    """The parent's attention over a pool of ``tokens``, each jitted and
    each behind the pool's ``positions`` or ``tables``: passes and
    kernel; the passes alone (q to the kernel's layout, a result of it
    back); the kernel alone; q as the kernel read it."""
    tile_q, _ = indexed.attention_tiles(tokens)
    tiled = (tokens // tile_q, tile_q, HK, HQ // HK, HEAD)

    def laid_out(positions, q, weight):
        return keye_parent.replaced_passes(
            q.reshape(positions.shape + (-1,)), weight, positions, INV_FREQ,
            EPS, jnp.bfloat16).reshape(tokens, HK, HQ // HK, HEAD)

    def both(tables, q, k, v, weight, keys, tau, cut):
        return keye_parent.indexed_attention(
            q, k, v, keys, tau, cut, weight, tables, EPS, INTERPRET,
            qlen=QLEN, inv_freq=INV_FREQ)

    def passes(positions, q, weight, out):
        # the copies the kernel's wrapper made on both sides of it
        return laid_out(positions, q, weight).reshape(tiled) \
            .transpose(2, 0, 3, 1, 4), \
            out.transpose(1, 3, 0, 2, 4).reshape(tokens, HQ * HEAD)

    def kernel(tables, qs, k, v, keys, tau, cut):
        return keye_parent.masked_attention(
            qs, k.reshape(tokens, HK, HEAD), v.reshape(tokens, HK, HEAD),
            keys, tau, cut, tables[2][:, 0], INTERPRET)
    return {"both": jax.jit(both), "passes": jax.jit(passes),
            "kernel": jax.jit(kernel), "laid_out": jax.jit(laid_out),
            "tiled": tiled}


def kernel_call():
    return jax.jit(lambda tables, *a: indexed.indexed_attention(
        *a, tables, EPS, INTERPRET))


def check(rng):
    """The kernels' sets against ``lax.top_k`` over the same keys, and
    the attention kernel's result and bits against the parent's."""
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
    qa, ka, va, weight = attention_operands(rng, tokens)
    tables = banded.band_tables(row_start, QLEN, INV_FREQ)
    want, want_sets = parent_calls(tokens)["both"](
        tables, qa, ka, va, weight, keys, tau, cut)
    out, sets = kernel_call()(tables, qa, ka, va, keys, tau, cut, weight)
    say({"check": "attention",
         "bits_differ": int((indexed.unpack_sets(sets)[:, :tokens]
                             != mask).sum()),
         "sets_differ": int((np.asarray(sets)
                             != np.asarray(want_sets)).sum()),
         "max_abs_differ": float(np.abs(
             np.asarray(out, np.float32)
             - np.asarray(want, np.float32)).max()),
         "values_differ": int((np.asarray(out) != np.asarray(want))
                              .sum())})
    _, best = jax.lax.top_k(keys, topk)
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(best), True, axis=1)
    at = np.arange(tokens)
    want &= (at[None, :] <= at[:, None]) \
        & (at[None, :] >= np.asarray(start)[:, None])
    say({"check": "sets", "tokens": tokens, "topk": topk,
         "differ": int((want != mask).sum()),
         "tied_queries": int((np.asarray(cut) < tokens).sum())})


def main():
    rng = np.random.default_rng(46)
    say({"device": DEVICE.device_kind, "rows": ROWS})
    check(rng)
    tokens = ROWS * QLEN
    q, k, w = operands(rng, tokens)
    qa, ka, va, weight = attention_operands(rng, tokens)
    parent, kernel = parent_calls(tokens), kernel_call()
    for requests in (1, 2, 3):
        row_start, start, position = pool(ROWS, requests)
        line = {"requests": requests, "tokens": tokens}
        keys, line["scores_ms"] = timed(jax.jit(
            lambda q, k, w, s: indexed.index_keys(q, k, w, s,
                                                  INTERPRET)),
            q, k, w, start)
        (tau, cut), line["thresholds_ms"] = timed(jax.jit(
            lambda keys, p: indexed.thresholds(keys, p, TOPK,
                                               INTERPRET)),
            keys, position)
        positions = rope.pool_positions(row_start, QLEN)
        tables = banded.band_tables(row_start, QLEN, INV_FREQ)
        (out, _), line["parent_ms"] = timed(
            parent["both"], tables, qa, ka, va, weight, keys, tau, cut)
        _, line["parent_kernel_ms"] = timed(
            parent["kernel"], tables, parent["laid_out"](positions, qa,
                                                         weight),
            ka, va, keys, tau, cut)
        _, line["parent_passes_ms"] = timed(
            parent["passes"], positions, qa, weight,
            out.reshape(parent["tiled"]).transpose(2, 0, 3, 1, 4))
        (_, sets), line["kernel_ms"] = timed(
            kernel, tables, qa, ka, va, keys, tau, cut, weight)
        tile_q, _ = indexed.attention_tiles(tokens)
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
