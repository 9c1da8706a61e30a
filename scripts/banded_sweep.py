"""``rnb_tpu.ops.banded``'s kernel alone, on the chip, at K-EXAONE's
shapes (rows of 128 tokens, 64 / 8 heads of 128, a window of 128), with
the operands the mixer hands it: q and k in float32 as their products
wrote them, v in bfloat16. A check of the kernel as Mosaic compiles it —
its first lines (the head norms, the rotary, q's scale, one rounding),
the band, the softmax — against the plain composition (``rms_norm``,
``ops/rope.rotate``, the cast) under an explicit mask in float64, on a
pool of three requests, one that opens inside a band, and a pad row.
Then the kernel's time at 128 rows at each choice it has (the queries a
step), beside the ``jnp`` passes it replaced in the mixer as XLA runs
them (norms, rotary, scale and cast, the three ``segattn.heads_first``
and the transpose back; splash's own call under its local mask left the
tree with them: 6.2 ms alone, ``ops/banded.py``'s text). The host's clock
around ``REPEATS`` calls: a call is milliseconds, the launch a few
tenths of one. Lines go to stdout and to
``chiprun_out/banded_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/banded_sweep.py [--rows=N]

Off the TPU the kernel runs in Pallas's interpret mode, which at these
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

from rnb_tpu.models.exaone_moe.network import rms_norm  # noqa: E402
from rnb_tpu.ops import banded, rope, segattn  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "banded_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
REPEATS = 10
QLEN, HQ, HK, DIM, WINDOW, EPS = 128, 64, 8, 128, 128, 1e-5
INV_FREQ = (1e6 ** (-np.arange(0, DIM, 2, dtype=np.float64) / DIM)) \
    .astype(np.float32)


def option(name, default):
    given = [a.split("=")[1] for a in sys.argv if a.startswith(name + "=")]
    return given[0] if given else default


def draw(rows, seed=0):
    """(q, k, v, the two norms' weights) as the mixer hands them."""
    rng = np.random.default_rng(seed)

    def n(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    tokens = rows * QLEN
    return (n(tokens, HQ * DIM, scale=3.0), n(tokens, HK * DIM, scale=3.0),
            n(tokens, HK * DIM).astype(jnp.bfloat16),
            (1.0 + 0.1 * n(DIM)).astype(jnp.bfloat16),
            (1.0 + 0.1 * n(DIM)).astype(jnp.bfloat16))


def rounded_operands(q, k, q_weight, k_weight, row_start):
    """The passes the kernel's first lines replaced: -> q (rows, Q, Hq,
    D) and k (rows, Q, Hk, D), normed, turned, q scaled, rounded."""
    rows = row_start.shape[0]
    positions = rope.pool_positions(row_start, QLEN)
    qs = rms_norm(q.reshape(rows, QLEN, HQ, DIM), q_weight, EPS, jnp.float32)
    ks = rms_norm(k.reshape(rows, QLEN, HK, DIM), k_weight, EPS, jnp.float32)
    qs = rope.rotate(qs, positions, INV_FREQ)
    ks = rope.rotate(ks, positions, INV_FREQ)
    return (qs * DIM ** -0.5).astype(jnp.bfloat16), ks.astype(jnp.bfloat16)


def explicit(qs, ks, v, row_start):
    """Softmax attention under the explicit mask, float64, on the host."""
    rows = len(row_start)
    tokens = rows * QLEN
    qf = np.asarray(qs.astype(jnp.float32), np.float64) \
        .reshape(tokens, HQ, DIM)
    kf = np.asarray(ks.astype(jnp.float32), np.float64) \
        .reshape(tokens, HK, DIM)
    vf = np.asarray(v.astype(jnp.float32), np.float64) \
        .reshape(tokens, HK, DIM)
    seg, at = np.repeat(np.asarray(row_start), QLEN), np.arange(tokens)
    ok = (seg[:, None] == seg[None, :]) & (at[None, :] <= at[:, None]) \
        & (at[None, :] > at[:, None] - WINDOW)
    out = np.zeros((tokens, HQ, DIM))
    for h in range(HQ):
        s = np.where(ok, qf[:, h] @ kf[:, h // (HQ // HK)].T, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ vf[:, h // (HQ // HK)]
    return out.reshape(tokens, HQ * DIM)


def kernel(q, k, v, q_weight, k_weight, row_start):
    tables = banded.band_tables(row_start, QLEN, INV_FREQ)
    return banded.banded_attention(q, k, v, q_weight, k_weight, tables,
                                   WINDOW, EPS, INTERPRET)[0]


def replaced_passes(q, k, v, q_weight, k_weight, row_start):
    """What stood around splash's call in the mixer, without the call:
    the operands laid out heads first, and a result laid back."""
    tokens = q.shape[0]
    qs, ks = rounded_operands(q, k, q_weight, k_weight, row_start)
    laid = (segattn.heads_first(qs.reshape(tokens, HK, HQ // HK, DIM)),
            segattn.heads_first(ks.reshape(tokens, HK, DIM)),
            segattn.heads_first(v.reshape(tokens, HK, DIM)))
    # a stand-in for the kernel between them: one pass that keeps the
    # compiler from folding the two transposes into one
    out = laid[0] + laid[1][:, None] + laid[2][:, None]
    return jnp.moveaxis(out, -2, 0)[:tokens].reshape(tokens, HQ * DIM)


def timed(call, *operands):
    jax.block_until_ready(call(*operands))
    start = time.perf_counter()
    for _ in range(REPEATS):
        out = call(*operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / REPEATS * 1e3


def main():
    os.makedirs(OUT, exist_ok=True)
    lines = []

    def say(**line):
        line["device"] = DEVICE.device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    # the check: three requests (the second opens inside a band's first
    # block, the third is one row) and a pad row, 16 rows
    starts = np.asarray([0] * 5 + [5] * 9 + [14, 15], np.int32)
    small = draw(len(starts), seed=1)
    row_start = jnp.asarray(starts)
    got = np.asarray(jax.jit(kernel)(*small, row_start)
                     .astype(jnp.float32), np.float64)
    qs, ks = rounded_operands(small[0], small[1], small[3], small[4],
                              row_start)
    want = explicit(qs, ks, small[2], starts)
    say(phase="check", rows=len(starts),
        max_abs_diff=float(np.abs(got - want).max()),
        ref_spread=float(want.std()))

    rows = int(option("--rows", 128))
    operands = draw(rows) + (jnp.zeros(rows, jnp.int32),)
    say(phase="time", what="replaced_passes", rows=rows,
        ms=timed(jax.jit(replaced_passes), *operands))
    whole = banded.band_block
    for block in (None, 256):
        if block is not None:
            banded.band_block = lambda tokens, window, b=block: b
        jax.clear_caches()
        try:
            say(phase="time", what="kernel", rows=rows,
                block=banded.band_block(rows * QLEN, WINDOW),
                ms=timed(jax.jit(kernel), *operands))
        except Exception as e:   # a choice that does not compile
            say(phase="time", what="kernel", rows=rows, block=block,
                error=str(e)[:300])
        banded.band_block = whole
    with open(os.path.join(OUT, "sweep.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
