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
The thresholds' time stands beside the parent's (``tests/keye_parent.py``:
PR 46's, a ``lax.cond`` and a lane reduction a chunk from key 0), with
``tau`` and ``cut`` compared to the bit and the walk's chunk visits;
``--only=thresholds`` is that line alone, at each (queries a step, keys a
chunk) of ``--select=32-2048,128-2048,...`` (the module's constants are
set for each: the kernels take no such argument).
Lines go to stdout and to ``chiprun_out/indexed_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/indexed_sweep.py [--rows=128] [--only=thresholds [--select=...]]

``--shape=latent`` (PR 55) is the same module at dots3-note's shapes,
with ``ops/banded.py``'s latent kernel beside it: the scores at 64 index
heads of 128, the thresholds, the latent kernel under the sets (128
heads of 128 + 64 / 128) at each (queries a tile, keys a tile, heads a
step) of ``--tiles=512-512-8,...``, and the latent kernel under a
window of 513 (64 heads of 192 + 64 / 128) at each count of heads a
step, for one, two and three requests with a pad row behind; beside
each time the kernel's floor (its operations at the bf16 peak, its
bytes at the HBM bandwidth). First a check of both kernels as Mosaic
compiles them against the dense form (an explicit mask, a plain softmax
in float32 at ``highest``) on a pool of 16 rows, of ``ops/mla.queries``
at nope 192, and the witness of the draw (the kernel at a sharp
softmax: :func:`sharp_softmax_witness`). The module's constants are set
for each form tried: the kernels take no tile argument.

    chiprun -- python3 scripts/indexed_sweep.py --shape=latent [--rows=N] [--only=full|band|check|witness] [--tiles=...]

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
from rnb_tpu.ops import banded, indexed, latent, rope  # noqa: E402

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
REPEATS = int(option("repeats", 5))

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



# -- latent attention (dots3-note): --shape=latent ------------------------

WINDOW = 513
FULL = dict(heads=128, nope=128, rotary=64, value=128)
SLIDING = dict(heads=64, nope=192, rotary=64, value=128)
INDEX_HEADS, INDEX_DIM = 64, 128
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
#: the latent kernel's (queries a tile, keys a tile, heads a step) as it
#: stands, and the ones tried beside it: --tiles=a-b-c,...
STANDING = indexed._LATENT_TILES
TILES = [tuple(int(n) for n in t.split("-")) for t in option(
    "tiles", "1024-512-4,512-512-8,1024-512-8,512-512-4,2048-512-2,"
    "1024-512-2,512-512-16,1024-512-16,256-512-16").split(",")]


def latent_timed(f, *args):
    return timed(f, *args)[1]


def latent_pool(rows, requests):
    """row_start of ``rows`` rows shared by ``requests`` requests, the
    last row a pad row where there is room."""
    cuts = [round(i * (rows - 1) / requests) for i in range(requests)]
    start = np.zeros(rows, np.int32)
    for lo, hi in zip(cuts, cuts[1:] + [rows - 1]):
        start[lo:hi] = lo
    start[rows - 1] = rows - 1
    row_tokens = np.full(rows, QLEN, np.int32)
    row_tokens[rows - 1] = 0
    return jnp.asarray(start), jnp.asarray(row_tokens)


def latent_operands(rng, tokens, geo, spread=2.0, rounded=True):
    """(q, kv, k_pe, gate) as the mixer hands them: q with the scores'
    scale in it, scores of a spread of ``spread``; ``rounded`` False:
    the float32 draws the bfloat16 operands are rounded from."""
    heads, nope, rot, value = (geo[k] for k in ("heads", "nope", "rotary",
                                                "value"))
    lanes = -(-(nope + rot) // 128) * 128
    own = latent.key_lanes(nope, lanes)

    def draw(*shape, scale=1.0):
        return rng.standard_normal(shape, np.float32) * scale
    q = np.zeros((heads, tokens, lanes), np.float32)
    q[..., :nope + rot] = draw(heads, tokens, nope + rot,
                               scale=spread * (nope + rot) ** -0.5)
    kv = np.zeros((tokens, heads, own + value), np.float32)
    kv[..., :nope] = draw(tokens, heads, nope)
    kv[..., own:] = draw(tokens, heads, value)
    bf = jnp.bfloat16 if rounded else jnp.float32
    return (jnp.asarray(q, bf), jnp.asarray(kv.reshape(tokens, -1), bf),
            jnp.asarray(draw(tokens, rot), bf),
            jnp.asarray(jax.nn.sigmoid(draw(tokens, heads))))


def dense(q, kv, k_pe, gate, mask, geo):
    """The dense form: every head's softmax under ``mask`` (T, T)."""
    heads, nope, rot, value = (geo[k] for k in ("heads", "nope", "rotary",
                                                "value"))
    tokens = q.shape[1]
    own = kv.shape[1] // heads - value
    kv = kv.astype(jnp.float32).reshape(tokens, heads, own + value)
    q = q.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("htd,shd->hts", q[..., :nope], kv[..., :nope]) \
            + jnp.einsum("htd,sd->hts", q[..., nope:nope + rot],
                         k_pe.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("hts,shd->thd", p, kv[..., own:])
    return (out * gate[:, :, None]).reshape(tokens, heads * value)


def latent_index_operands(rng, tokens):
    def draw(*shape):
        return rng.standard_normal(shape, np.float32)
    return (jnp.asarray(draw(tokens, INDEX_HEADS, INDEX_DIM), jnp.bfloat16),
            jnp.asarray(draw(tokens, INDEX_DIM), jnp.bfloat16),
            jnp.asarray(draw(tokens, INDEX_HEADS)
                        * (INDEX_HEADS * INDEX_DIM) ** -0.5))


def sets_of(rng, rows, requests, topk):
    tokens = rows * QLEN
    row_start, row_tokens = latent_pool(rows, requests)
    start, _ = indexed.token_table(row_start, row_tokens, QLEN)
    at = jnp.arange(tokens, dtype=jnp.int32) - start
    keys = jax.jit(lambda q, k, w, s: indexed.index_keys(
        q, k, w, s, INTERPRET))(*latent_index_operands(rng, tokens), start)
    tau, cut = jax.jit(lambda k, a: indexed.thresholds(
        k, a, topk, INTERPRET))(keys, at)
    return start, at, keys, tau, cut


def latent_check(rng):
    rows, small = 16, dict(FULL, heads=8)
    tokens = rows * QLEN
    start, at, keys, tau, cut = sets_of(rng, rows, 3, 300)
    q, kv, k_pe, gate = latent_operands(rng, tokens, small)
    mask = indexed.chosen_mask(keys, tau, cut, start)
    want = np.asarray(dense(q, kv, k_pe, gate, mask, small))
    for tiles in ((512, 512, 4), (256, 256, 2), (1024, 512, 8)):
        indexed._LATENT_TILES = tiles
        got, sets = indexed.latent_indexed_attention(
            q, kv, k_pe, gate, keys, tau, cut, start[:, None],
            small["nope"], small["value"], interpret=INTERPRET)
        bits = indexed.unpack_sets(np.asarray(sets))[:, :tokens]
        say({"check": "latent_indexed_attention", "tiles": tiles,
             "max_abs_diff": float(np.abs(
                 np.asarray(got, np.float32) - want).max()),
             "spread": float(want.std()),
             "sets_equal": bool((bits == np.asarray(mask)).all())})
    small = dict(SLIDING, heads=8)
    q, kv, k_pe, gate = latent_operands(rng, tokens, small)
    t = np.arange(tokens)
    band = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < WINDOW) \
        & (t[None, :] >= np.asarray(start)[:, None])
    want = np.asarray(dense(q, kv, k_pe, gate, jnp.asarray(band), small))
    indexed._LATENT_TILES = STANDING
    for per in (4, 8):
        banded._LATENT_HEADS = per
        got, _ = banded.latent_banded_attention(
            q, kv, k_pe, gate, start[:, None], WINDOW, small["nope"],
            small["value"], interpret=INTERPRET)
        say({"check": "latent_banded_attention", "per": per,
             "max_abs_diff": float(np.abs(
                 np.asarray(got, np.float32) - want).max()),
             "spread": float(want.std())})


def check_queries(rng):
    """``ops/mla.queries`` at nope 192 (the rotary columns begin inside
    a lane tile, the third lane tile unwritten), as Mosaic compiles it,
    against ``ops/rope.rotate`` on the float32 product."""
    from rnb_tpu.ops import mla, rope
    tokens, rank, heads, nope, rot, scale = 2048, 1024, 4, 192, 64, 0.0625
    w = rng.standard_normal((heads, rank, nope + rot), np.float32) \
        / np.sqrt(rank)
    stored = np.zeros((heads, rank, mla.query_lanes(nope, rot)), np.float32)
    stored[..., :nope + rot] = w
    stored[..., nope + rot:nope + rot + rot // 2] = -w[..., nope + rot // 2:]
    stored[..., nope + rot + rot // 2:nope + 2 * rot] = \
        w[..., nope:nope + rot // 2]
    latent = jnp.asarray(rng.standard_normal((tokens, rank), np.float32),
                         jnp.bfloat16)
    stored = jnp.asarray(stored, jnp.bfloat16)
    at = jnp.asarray(rng.integers(0, 16384, tokens), jnp.int32)
    inv_freq = (5e4 ** (-np.arange(0, rot, 2) / rot)).astype(np.float32)
    got = mla.queries(latent, stored, at, inv_freq, nope, scale,
                      interpret=INTERPRET, out_columns=256,
                      tables=mla.turn_tables(at, inv_freq, nope))
    with jax.default_matmul_precision("highest"):
        product = jnp.einsum("tr,hrc->htc", latent.astype(jnp.float32),
                             stored.astype(jnp.float32)[..., :nope + rot])
    turned = rope.rotate(product[..., nope:].transpose(1, 0, 2)[None],
                         at[None], inv_freq)[0].transpose(1, 0, 2)
    want = np.concatenate([np.asarray(product[..., :nope]),
                           np.asarray(turned)], -1) * scale
    say({"check": "mla_queries nope 192", "shape": list(got.shape),
         "max_abs_diff": float(np.abs(
             np.asarray(got, np.float32) - want).max()),
         "spread": float(want.std())})


def sharp_softmax_witness(rng):
    """Why dots3-note's draw holds the scores to a spread of 2.25
    (``models/dots3_note/checkpoint.py``): the full layers' kernel at the
    published head count and widths, as Mosaic compiles it, over a pool
    of 16 rows as two requests under sets of 512, once at that spread
    and once at 7.1, the spread norm weights of one give under the
    rescale. At each: the kernel against the dense form on the *same*
    bfloat16 operands (the kernel's own error), and that dense form
    against the dense form on the float32 draws the operands were
    rounded from (what rounding q, k and v costs), worst element and
    root mean square over the result's spread."""
    # off the chip a dry run of the control flow: 4 heads, half the pool
    rows, geo = (8, dict(FULL, heads=4)) if INTERPRET else (16, FULL)
    tokens = rows * QLEN
    indexed._LATENT_TILES = STANDING
    start, at, keys, tau, cut = sets_of(rng, rows, 2, tokens // 4)
    mask = indexed.chosen_mask(keys, tau, cut, start)
    for spread in (2.25, 7.1):
        seed = int(rng.integers(2 ** 31))
        exact = latent_operands(np.random.default_rng(seed), tokens, geo,
                                spread, rounded=False)
        bf = latent_operands(np.random.default_rng(seed), tokens, geo,
                             spread)
        got, _ = indexed.latent_indexed_attention(
            *bf, keys, tau, cut, start[:, None], geo["nope"],
            geo["value"], interpret=INTERPRET)
        got = np.asarray(got, np.float32)
        same = np.asarray(dense(*bf, mask, geo))
        want = np.asarray(dense(*exact, mask, geo))

        def off(a, b):
            return {"max": float(np.abs(a - b).max() / want.std()),
                    "rms": float(np.sqrt(((a - b) ** 2).mean())
                                 / want.std())}
        say({"witness": "latent_indexed_attention", "score_spread": spread,
             "tokens": tokens, "heads": geo["heads"],
             "result_spread": float(want.std()),
             "kernel_vs_dense_same_operands": off(got, same),
             "rounded_vs_float32_operands": off(same, want)})


def floor_ms(pairs, geo, tokens):
    """(ms at the bf16 peak of every head's two products over ``pairs``,
    ms at the HBM bandwidth of q, kv and the result once)."""
    lanes = -(-(geo["nope"] + geo["rotary"]) // 128) * 128
    ops = 2.0 * pairs * geo["heads"] * (geo["nope"] + geo["rotary"]
                                        + geo["value"])
    moved = 2 * tokens * geo["heads"] * (lanes + geo["nope"]
                                         + 2 * geo["value"])
    return 1e3 * ops / PEAK_FLOPS, 1e3 * moved / PEAK_BYTES


def latent_main():
    rng = np.random.default_rng(0)
    rows, only = ROWS, option("only", "")
    tokens = rows * QLEN
    say({"device": DEVICE.device_kind, "rows": rows, "shape": "latent"})
    if not only and not INTERPRET or only == "check":
        latent_check(rng)
        check_queries(rng)
    if not only and not INTERPRET or only == "witness":
        sharp_softmax_witness(rng)
    if only in ("check", "witness"):
        return
    for requests in (1, 2, 3):
        start, at, keys, tau, cut = sets_of(rng, rows, requests, TOPK)
        causal = int((np.asarray(at) + 1).sum())
        if only in ("", "full"):
            idx = latent_index_operands(rng, tokens)
            line = {"requests": requests, "causal_pairs": causal}
            line["index_scores_ms"] = latent_timed(jax.jit(
                lambda q, k, w, s: indexed.index_keys(q, k, w, s, INTERPRET)),
                *idx, start)
            line["index_scores_floor_ms"] = 1e3 * max(
                2.0 * causal * INDEX_HEADS * INDEX_DIM / PEAK_FLOPS,
                4.0 * causal / PEAK_BYTES)
            line["thresholds_ms"] = latent_timed(jax.jit(
                lambda k, a: indexed.thresholds(k, a, TOPK, INTERPRET)),
                keys, at)
            say(line)
            q, kv, k_pe, gate = latent_operands(rng, tokens, FULL)
            for tiles in TILES:
                if tokens % tiles[0]:
                    continue
                indexed._LATENT_TILES = tiles
                try:
                    ms = latent_timed(jax.jit(
                        lambda *a: indexed.latent_indexed_attention(
                            *a, FULL["nope"], FULL["value"],
                            interpret=INTERPRET)),
                        q, kv, k_pe, gate, keys, tau, cut, start[:, None])
                except Exception as e:                      # VMEM, mostly
                    say({"requests": requests, "tiles": tiles,
                         "failed": str(e)[:200]})
                    continue
                visited = indexed.latent_causal_tiles(tokens) \
                    * tiles[0] * tiles[1]
                say({"requests": requests, "kernel": indexed.LATENT_KERNEL,
                     "tiles": tiles, "ms": ms,
                     "floor_ms_causal_pairs": floor_ms(causal, FULL, tokens),
                     "floor_ms_pool_tiles": floor_ms(visited, FULL,
                                                     tokens)[0]})
        if only in ("", "band"):
            q, kv, k_pe, gate = latent_operands(rng, tokens, SLIDING)
            kept = int(np.minimum(np.asarray(at) + 1, WINDOW).sum())
            for per in (8, 4, 16, 2):
                banded._LATENT_HEADS = per
                try:
                    ms = latent_timed(jax.jit(
                        lambda *a: banded.latent_banded_attention(
                            *a, WINDOW, SLIDING["nope"], SLIDING["value"],
                            interpret=INTERPRET)),
                        q, kv, k_pe, gate, start[:, None])
                except Exception as e:
                    say({"requests": requests, "per": per,
                         "failed": str(e)[:200]})
                    continue
                block = banded.band_block(tokens, WINDOW)
                say({"requests": requests,
                     "kernel": banded.LATENT_KERNEL_NAME, "per": per,
                     "ms": ms,
                     "floor_ms_kept_pairs": floor_ms(kept, SLIDING, tokens),
                     "floor_ms_band_blocks": floor_ms(
                         tokens * 2 * block, SLIDING, tokens)[0]})


def thresholds_line(keys, position, parent=None):
    """The thresholds as they stand, timed, beside the parent's
    (``parent``: its results and time, computed here where None):
    -> ((tau, cut), the parent's pair, the line's fields)."""
    def call(f):
        return jax.jit(lambda keys, p: f(keys, p, TOPK, INTERPRET))
    (tau, cut), ms = timed(call(indexed.thresholds), keys, position)
    (want_tau, want_cut), parent_ms = parent or timed(
        call(keye_parent.thresholds), keys, position)
    walked, to_diagonal = (int(n) for n in
                           indexed.chunk_visits(position, TOPK))
    return (tau, cut), ((want_tau, want_cut), parent_ms), {
        "thresholds_ms": ms, "parent_thresholds_ms": parent_ms,
        "queries_a_step": indexed._SELECT_TILE_Q,
        "keys_a_chunk": indexed._SELECT_CHUNK,
        "chunks_walked": walked, "chunks_to_diagonal": to_diagonal,
        "tau_differ": int((np.asarray(tau) != np.asarray(want_tau)).sum()),
        "cut_differ": int((np.asarray(cut) != np.asarray(want_cut)).sum())}


def thresholds_main():
    """The thresholds alone over one, two and three requests, at each
    (queries a step, keys a chunk) of ``--select``."""
    rng = np.random.default_rng(46)
    tokens = ROWS * QLEN
    q, k, w = operands(rng, tokens)
    standing = "%d-%d" % (indexed._SELECT_TILE_Q, indexed._SELECT_CHUNK)
    forms = [tuple(int(n) for n in form.split("-"))
             for form in option("select", standing).split(",")]
    for requests in (1, 2, 3):
        _, start, position = pool(ROWS, requests)
        keys = jax.jit(lambda q, k, w, s: indexed.index_keys(
            q, k, w, s, INTERPRET))(q, k, w, start)
        parent = None
        for form in forms:
            indexed._SELECT_TILE_Q, indexed._SELECT_CHUNK = form
            try:
                _, parent, line = thresholds_line(keys, position, parent)
            except Exception as e:                          # VMEM, mostly
                say({"requests": requests, "form": form,
                     "failed": str(e)[:300]})
                continue
            say(dict(line, requests=requests, tokens=tokens))


def main():
    if option("shape", "keye") == "latent":
        return latent_main()
    say({"device": DEVICE.device_kind, "rows": ROWS})
    if option("only", "") == "thresholds":
        return thresholds_main()
    rng = np.random.default_rng(46)
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
        (tau, cut), _, fields = thresholds_line(keys, position)
        line.update(fields)
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
