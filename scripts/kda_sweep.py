"""``rnb_tpu.ops.deltanet.channel_gated_delta_rule`` alone, on the chip,
at Kimi-Linear's shapes (128 rows of 128 tokens, 32 heads of 128): a
check of the kernel as Mosaic compiles it against the token-by-token
recurrence on a pool of three requests and a pad row, at a mild and at
a harsh draw of the decays (``log alpha`` down to -25 a token), then the
kernel's time at each choice it has — the block inside which pairs are
formed on the vector unit (``_PAIR_BASE``), the heads a grid step
(``_KDA_HEADS``) — beside the scalar rule's kernel at the same shape (32
key heads, 32 value heads). (The levels' score products in float32 at
``highest`` in the place of the activations' bfloat16 read 18.90 ms for
16.12 at 8 and 2 and left the tree: my chip run, PR 49.) The host's clock
around ``REPEATS`` calls: a call is tens of milliseconds, the launch a
few tenths of one. Lines go to stdout and to
``chiprun_out/kda_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/kda_sweep.py [--rows=N]

Off the TPU the kernel runs in Pallas's interpret mode, which at these
sizes is of no use (``--rows=4`` is a dry run of the control flow).
"""
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from rnb_tpu.ops import deltanet  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "kda_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
REPEATS = 5
QLEN, HEADS, DIM = 128, 32, 128


def option(name, default):
    given = [a.split("=")[1] for a in sys.argv if a.startswith(name + "=")]
    return given[0] if given else default


def draw(rows, heads, harsh, seed=0):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = n(rows, QLEN, heads, DIM), n(rows, QLEN, heads, DIM)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DIM ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rate = jnp.asarray(rng.uniform(0.01, 1.0, (heads, DIM)), jnp.float32)
    token = jnp.asarray(rng.uniform(0.03, 1.0, (rows, QLEN, heads, DIM)),
                        jnp.float32)
    bf = jnp.bfloat16
    return (q.astype(bf), k.astype(bf), n(rows, QLEN, heads, DIM).astype(bf),
            -(25.0 if harsh else 0.03) * rate * token,
            jax.nn.sigmoid(n(rows, QLEN, heads)))


@jax.jit
def recurrence(q, k, v, log_alpha, beta):
    """One request, token by token: (L, H, D) operands."""
    def step(state, token):
        qt, kt, vt, at, bt = token
        state = state * jnp.exp(at)[:, :, None]
        read = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - read))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt)
    zero = jnp.zeros((q.shape[1], DIM, DIM), jnp.float32)
    return lax.scan(step, zero, (q, k, v, log_alpha, beta))[1]


def check(emit):
    firsts = (0, 3, 5)
    for harsh in (False, True):
        inputs = draw(6, 4, harsh)
        row_first = np.zeros(6, bool)
        row_first[list(firsts)] = True
        out = np.asarray(deltanet.channel_gated_delta_rule(
            *inputs, jnp.asarray(row_first), interpret=INTERPRET))
        worst = 0.0
        with jax.default_matmul_precision("highest"):
            for lo, hi in zip(firsts, firsts[1:] + (6,)):
                want = np.asarray(recurrence(*(
                    x[lo:hi].reshape((-1,) + x.shape[2:])
                    .astype(jnp.float32) for x in inputs)))
                worst = max(worst, float(np.abs(
                    out[lo:hi].reshape(want.shape) - want).max()
                    / want.std()))
        emit({"check": "harsh" if harsh else "mild",
              "finite": bool(np.isfinite(out).all()),
              "max_error_over_spread": worst})


def timed(call, *operands):
    jax.block_until_ready(call(*operands))
    start = time.perf_counter()
    for _ in range(REPEATS):
        out = call(*operands)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / REPEATS


def main():
    os.makedirs(OUT, exist_ok=True)
    lines = open(os.path.join(OUT, "sweep.jsonl"), "w")

    def emit(record):
        record["device"] = DEVICE.device_kind
        print(json.dumps(record), flush=True)
        lines.write(json.dumps(record) + "\n")
        lines.flush()
    check(emit)
    rows = int(option("--rows", 128))
    inputs = draw(rows, HEADS, False, seed=1)
    row_first = jnp.asarray(np.arange(rows) % 40 == 0)
    scalar = jax.jit(lambda q, k, v, a, b, f: deltanet.gated_delta_rule(
        q, k, v, a[..., 0], b, f, interpret=INTERPRET))
    emit({"kernel": deltanet.KERNEL_NAME, "rows": rows,
          "ms": timed(scalar, *inputs, row_first)})
    for base, heads in itertools.product((8, 16), (1, 2, 4)):
        deltanet._PAIR_BASE, deltanet._KDA_HEADS = base, heads
        deltanet._kda_call.clear_cache()
        began = time.perf_counter()
        call = jax.jit(lambda *x: deltanet.channel_gated_delta_rule(
            *x, interpret=INTERPRET))
        ms = timed(call, *inputs, row_first)
        emit({"kernel": deltanet.KDA_KERNEL_NAME, "rows": rows,
              "pair_base": base, "heads_a_step": heads, "ms": ms,
              "first_call_s": time.perf_counter() - began
              - REPEATS * ms / 1e3})


if __name__ == "__main__":
    main()
