"""``rnb_tpu.ops.deltanet``'s two kernels alone, on the chip, at
Kimi-Linear's shapes (128 rows of 128 tokens, 32 heads of 128), with the
operands the mixers hand them: q with k in float32 as the convolution
wrote them, v in bfloat16, the output gate's pre-activation in float32.
A check of the vector gate's kernel as Mosaic compiles it — its first
lines (the heads' L2 norms, q's scale, one rounding), the rule, its last
lines (the head norm, the gate, one rounding) — against the plain
composition around the token-by-token recurrence on a pool of three
requests and a pad row, at a mild and at a harsh draw of the decays
(``log alpha`` down to -25 a token). Then both kernels' times, and *what
the first and last lines cost*: each kernel again with the lines taken
out of its body (``_unit`` and ``_gated_norm`` replaced by functions that
pass their operand through; the operands still cross HBM), beside the
figures of the kernels that read q and k normalised and rounded and
wrote float32 (``PR49_MS``), and the ``jnp`` passes the lines replaced
in the mixers, as XLA runs them. Then *where each kernel's time is*
(``PROBES``): the kernel again with one part of its body left out at a
time - the solve's merges, its substitution, the levels, the offsets,
the running sums, the score products, the products behind the solve -
each replaced by the cheapest stand-in of its shape, so that the times
say what the part costs and the results say nothing (PERF.md section 6,
PR 58, has the table before and after that PR). Then the vector gate's
kernel at each choice it has — the block inside which pairs are formed
on the vector unit (``_PAIR_BASE``), the heads a grid step
(``_KDA_HEADS``). (The levels' score products in float32 at ``highest`` in the place of the
activations' bfloat16 read 18.90 ms for 16.12 at 8 and 2 and left the
tree: my chip run, PR 49.) The host's clock around ``REPEATS`` calls: a
call is tens of milliseconds, the launch a few tenths of one. Lines go
to stdout and to ``chiprun_out/kda_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/kda_sweep.py [--rows=N] [--only=probes]

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


#: ms a layer at 128 rows of the kernels that read q and k normalised
#: and rounded and wrote float32 (my chip run, PR 49): the timed call
#: took operands of a head axis, so the figures hold the relayouts to
#: the kernel's (rows, Q, heads x 128) and back; with the lines these
#: kernels read 11.84 and 13.56 (my chip run, PR 50)
PR49_MS = {deltanet.KERNEL_NAME: 13.70, deltanet.KDA_KERNEL_NAME: 16.13}
EPS = 1e-5


def draw(rows, heads, harsh, seed=0):
    """(qk, v, log alpha a channel, beta, z, the norm's weight)."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    rate = jnp.asarray(rng.uniform(0.01, 1.0, (heads, DIM)), jnp.float32)
    token = jnp.asarray(rng.uniform(0.03, 1.0, (rows, QLEN, heads, DIM)),
                        jnp.float32)
    log_alpha = -(25.0 if harsh else 0.03) * rate * token
    return (n(rows, QLEN, 2 * heads * DIM),
            n(rows, QLEN, heads * DIM).astype(jnp.bfloat16),
            log_alpha.reshape(rows, QLEN, heads * DIM),
            jax.nn.sigmoid(n(rows, QLEN, heads)), n(rows, QLEN, heads * DIM),
            1.0 + 0.1 * n(DIM))


def front(qk):
    """The passes in front of the rule as the mixers had them: (rows,
    Q, 2 H D) float32 -> q, k (rows, Q, H, D) bfloat16."""
    x = qk.reshape(qk.shape[:2] + (2, -1, DIM))
    x = x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return ((x[:, :, 0] * DIM ** -0.5).astype(jnp.bfloat16),
            x[:, :, 1].astype(jnp.bfloat16))


def behind(out, z, weight):
    """The passes behind it: the rule's float32 (rows, Q, H D) result
    through the head's norm, times ``sigmoid(z)``, rounded."""
    x = out.reshape(out.shape[:2] + (-1, DIM))
    x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * weight
    return (x.reshape(out.shape) * jax.nn.sigmoid(z)).astype(jnp.bfloat16)


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


def kda(*operands):
    return deltanet.channel_gated_delta_rule(
        *operands, eps=EPS, activation="sigmoid", interpret=INTERPRET)


def check(emit):
    firsts = (0, 3, 5)
    f32 = jnp.float32
    for harsh in (False, True):
        qk, v, log_alpha, beta, z, weight = draw(6, 4, harsh)
        row_first = np.zeros(6, bool)
        row_first[list(firsts)] = True
        out = np.asarray(kda(qk, v, log_alpha, beta, z, weight,
                             jnp.asarray(row_first)).astype(f32))
        q, k = front(qk)
        worst = 0.0
        with jax.default_matmul_precision("highest"):
            for lo, hi in zip(firsts, firsts[1:] + (6,)):
                rule = recurrence(*(
                    x[lo:hi].reshape(-1, 4, DIM).astype(f32)
                    for x in (q, k, v, log_alpha)),
                    beta[lo:hi].reshape(-1, 4))
                want = np.asarray(behind(
                    rule.reshape(hi - lo, QLEN, 4 * DIM), z[lo:hi], weight)
                    .astype(f32))
                worst = max(worst, float(
                    np.abs(out[lo:hi] - want).max() / want.std()))
        # the result is rounded to bfloat16 on both sides: a step of it
        # on the largest entries is 3% of the spread
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


def the_lines(emit, rows, operands, row_first):
    """Both kernels with and without their first and last lines, and
    the passes the lines replaced."""
    qk, v, log_alpha, beta, z, weight = operands
    one = log_alpha.reshape(rows, QLEN, HEADS, DIM)[..., 0]
    kernels = {
        deltanet.KERNEL_NAME: (
            deltanet._rule_call, lambda: deltanet.gated_delta_rule(
                qk, v, one, beta, z, weight, row_first, key_heads=HEADS,
                eps=EPS, activation="silu", interpret=INTERPRET)),
        deltanet.KDA_KERNEL_NAME: (
            deltanet._kda_call, lambda: kda(*operands, row_first))}
    lines = deltanet._unit, deltanet._gated_norm
    for name, (jitted, call) in kernels.items():
        with_lines = timed(call)
        # the operands are drawn near unit length, so that the rule
        # without its norms still reads numbers of the rule's size
        deltanet._unit = lambda x: x * DIM ** -0.5
        deltanet._gated_norm = lambda o, z, weight, eps, activation: o
        jitted.clear_cache()
        try:
            without = timed(call)
        finally:
            deltanet._unit, deltanet._gated_norm = lines
            jitted.clear_cache()
        emit({"kernel": name, "rows": rows, "ms": with_lines,
              "ms_without_lines": without,
              "lines_ms": with_lines - without, "pr49_ms": PR49_MS[name]})
    emit({"passes": "front", "rows": rows,
          "ms": timed(jax.jit(front), qk)})
    # an array of the rule's float32 result's shape in its place
    emit({"passes": "behind", "rows": rows,
          "ms": timed(jax.jit(behind), qk[..., :HEADS * DIM] + 0.0, z,
                      weight)})


def stand_in(a, b, dims=deltanet._NN):
    """In a product's place: one addition of its operands' first parts
    (every shape of the sweep is 128 x 128)."""
    return a[0].astype(jnp.float32) + b[0].astype(jnp.float32)


def probes():
    """What to leave out -> the module's functions to replace, and the
    kernels it is a part of."""
    product = deltanet._product
    both = (deltanet.KERNEL_NAME, deltanet.KDA_KERNEL_NAME)

    def with_the_products(function):
        def run(*operands):
            replaced, deltanet._product = deltanet._product, product
            try:
                return function(*operands)
            finally:
                deltanet._product = replaced
        return run

    def products_of(parts):
        """Stand-ins for the products of ``parts`` (both operands one
        part or not) outside the merges and the levels."""
        return {
            "_product": lambda a, b, dims=deltanet._NN: (
                stand_in if (len(a) == len(b) == 1) == parts else product)(
                    a, b, dims),
            "_merged": with_the_products(deltanet._merged),
            "_level": with_the_products(deltanet._level)}

    def zeros(rows):
        # made inside the kernel's body: it may capture no array
        return lambda *_: (jnp.zeros((rows, QLEN), jnp.float32),) * 2
    return {
        "nothing": ({}, both),
        "the merges": ({"_merged": lambda x, xs, lowers, block: (x, xs)},
                       both),
        "the substitution": ({"_substituted": lambda packed: packed}, both),
        "the solve": ({"unit_lower_inverses": lambda lowers: lowers}, both),
        "the four levels": ({"_level": zeros(QLEN // 2)}, both[1:]),
        "the seven offsets": ({"_offsets": zeros(QLEN)}, both[1:]),
        "the running sums": ({"_running_sums": lambda x: x}, both[1:]),
        "the two score products": (products_of(True), both[:1]),
        "the six products behind the solve": (products_of(False), both),
        "the first and last lines": ({
            # the operands are drawn near unit length, so that the rule
            # without its norms still reads numbers of the rule's size
            "_unit": lambda x: x * DIM ** -0.5,
            "_gated_norm": lambda o, z, weight, eps, activation: o}, both),
    }


def the_parts(emit, rows, operands, row_first):
    """Each kernel with one part left out at a time: times only."""
    qk, v, log_alpha, beta, z, weight = operands
    one = log_alpha.reshape(rows, QLEN, HEADS, DIM)[..., 0]
    kernels = {
        deltanet.KERNEL_NAME: (
            deltanet._rule_call, lambda: deltanet.gated_delta_rule(
                qk, v, one, beta, z, weight, row_first, key_heads=HEADS,
                eps=EPS, activation="silu", interpret=INTERPRET)),
        deltanet.KDA_KERNEL_NAME: (
            deltanet._kda_call, lambda: kda(*operands, row_first))}
    for left_out, (replaced, of) in probes().items():
        for name in of:
            jitted, call = kernels[name]
            kept = {attr: getattr(deltanet, attr) for attr in replaced}
            for attr, function in replaced.items():
                setattr(deltanet, attr, function)
            jitted.clear_cache()
            try:
                emit({"kernel": name, "rows": rows, "left_out": left_out,
                      "ms": timed(call)})
            finally:
                for attr, function in kept.items():
                    setattr(deltanet, attr, function)
                jitted.clear_cache()


def main():
    os.makedirs(OUT, exist_ok=True)
    lines = open(os.path.join(OUT, "sweep.jsonl"), "w")

    def emit(record):
        record["device"] = DEVICE.device_kind
        print(json.dumps(record), flush=True)
        lines.write(json.dumps(record) + "\n")
        lines.flush()
    only = option("--only", "")
    if not only:
        check(emit)
    rows = int(option("--rows", 128))
    operands = draw(rows, HEADS, False, seed=1)
    row_first = jnp.asarray(np.arange(rows) % 40 == 0)
    if not only:
        the_lines(emit, rows, operands, row_first)
    the_parts(emit, rows, operands, row_first)
    if only:
        return
    for base, heads in itertools.product((8, 16), (1, 2, 4)):
        deltanet._PAIR_BASE, deltanet._KDA_HEADS = base, heads
        deltanet._kda_call.clear_cache()
        began = time.perf_counter()
        ms = timed(kda, *operands, row_first)
        emit({"kernel": deltanet.KDA_KERNEL_NAME, "rows": rows,
              "pair_base": base, "heads_a_step": heads, "ms": ms,
              "first_call_s": time.perf_counter() - began
              - REPEATS * ms / 1e3})


if __name__ == "__main__":
    main()
