"""``rnb_tpu.ops.hyper`` alone, on the chip, at Xing4.0's published shape
(4 streams of 3,584 channels, rows of 128 tokens) and the cell's row
buckets: a check of the coefficients against a plain ``(tokens, n, n)``
Sinkhorn in float32 (the reference's form, written out here) on a pool
whose logits reach past the clip, and of the kernel's ``X'``, ``u`` and
logits against the module's plain statement composed here
(``logits_of``, ``mix_in``, ``mix_out``: the form XLA's own passes
make, which lost and which nothing in the program calls); then the time
of one sublayer's steps in each of the two (``--forms``) and, for the
kernel, at each count of tokens a step (``--tokens``): the device's own
time from a profiler trace of ``REPEATS`` calls — ``enter_ms`` the first
sublayer's way in (the statistic, the projection, ``u``), ``maps_ms``
``coefficients_from`` (two transposes around the sigmoids, ``exp`` and
the 20 Sinkhorn steps), ``leave_enter_ms`` a sublayer's way out with the
next one's way in, and ``sublayer_ms`` the last two in one jitted call:
what each of a dispatch's 12 sublayers costs in the stack — beside
``floor_ms``: what ``benchmarks/families/xing4.py`` counts for
``hyper``, a token's stream read once and written once with ``u`` and
``y`` at the HBM's rate, and the operations at the matrix unit's bf16
peak. Lines go to stdout and to ``chiprun_out/hyper_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/hyper_sweep.py [--rows=16,32,48,64]
        [--forms=plain,kernel] [--tokens=128,256] [--check-rows=4]

Off the TPU the kernel runs in Pallas's interpret mode, which at these
sizes is of no use (``--rows=1 --check-rows=1`` is a dry run of the
control flow).
"""
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import peaks, xplane  # noqa: E402
from rnb_tpu.ops import hyper  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "hyper_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
REPEATS = 5
QLEN, STREAMS, CHANNELS = 128, 4, 3584
SIZES = dict(n=STREAMS, eps=1e-6)
STEPS = dict(n=STREAMS, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))


def option(name, default):
    given = [a.split("=")[1] for a in sys.argv if a.startswith(name + "=")]
    return given[0] if given else default


ROWS = [int(v) for v in option("--rows", "16,32,48,64").split(",")]
FORMS = option("--forms", "plain,kernel").split(",")
TOKENS = [int(v) for v in option("--tokens", str(hyper._TOKENS)).split(",")]
CHECK_ROWS = int(option("--check-rows", "4"))
#: the v5e's published peaks: a floor is a statement about that chip
V5E = peaks.peak_for("TPU v5 lite")


def say(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sweep.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def device_ms(f, *args):
    """Every device operation of one call of ``f``, from a trace of
    ``REPEATS`` calls; the host's clock off the chip."""
    jax.block_until_ready(f(*args))
    took = []
    trace_dir = tempfile.mkdtemp()
    with jax.profiler.trace(trace_dir):
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            took.append(1e3 * (time.perf_counter() - t0))
    if INTERPRET:
        return round(float(np.median(took)), 3)
    ops = [op for plane in xplane.device_ops(
        xplane.find_xplane(trace_dir)).values() for op in plane]
    return round(sum(end - start for start, end, _ in ops)
                 / REPEATS / 1e6, 4)


def operands(rows, seed, spread=1.0):
    """A pool's stream and one sublayer's weights as the seeded draw
    makes them (``models/xing4/checkpoint.py``); ``spread`` scales the
    bias, so that logits reach past the clip."""
    rng = np.random.default_rng(seed)
    tokens, wide = rows * QLEN, STREAMS * CHANNELS
    x = jnp.asarray(rng.standard_normal((tokens, wide)), jnp.bfloat16)
    phi = jnp.asarray(rng.standard_normal((wide, hyper.rows_of(STREAMS)))
                      / np.sqrt(wide), jnp.bfloat16)
    bias = rng.standard_normal(hyper.rows_of(STREAMS)) * spread
    bias[2 * STREAMS:] += 3.0 * spread * np.eye(STREAMS).reshape(-1)
    return (x, phi, jnp.ones(3, jnp.float32),
            jnp.asarray(bias, jnp.float32))


def plain_coefficients(x, phi, alpha, bias):
    """The mappings a token at a time as the reference writes them:
    ``(tokens, n, n)`` under a literal ``for``."""
    n = STREAMS
    with jax.default_matmul_precision("highest"):
        flat = x.astype(jnp.float32)
        unit = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                               + SIZES["eps"])
        moved = unit @ phi.astype(jnp.float32)
    scale = jnp.repeat(alpha, jnp.array([n, n, n * n]),
                       total_repeat_length=hyper.rows_of(n))
    logits = moved * scale + bias
    res = jnp.exp(jnp.clip(logits[:, 2 * n:], *STEPS["clamp"])) \
        .reshape(-1, n, n)
    for _ in range(STEPS["iters"]):
        res = res / (res.sum(1, keepdims=True) + STEPS["hc_eps"])
        res = res / (res.sum(2, keepdims=True) + STEPS["hc_eps"])
    return jnp.concatenate([
        jax.nn.sigmoid(logits[:, :n]), 2 * jax.nn.sigmoid(logits[:, n:2 * n]),
        res.reshape(-1, n * n)], -1).T, logits


def floor_ms(rows):
    """(by operations, by bytes) of one sublayer."""
    tokens, n, c = rows * QLEN, STREAMS, CHANNELS
    ops = tokens * (2 * n * c * hyper.rows_of(n) + 2 * n * c
                    + 2 * n * n * c + 2 * n * c)
    nbytes = tokens * (2 * n + 2) * c * 2 + n * c * hyper.rows_of(n) * 2
    return (round(1e3 * ops / V5E["bf16_flops_per_s"], 4),
            round(1e3 * nbytes / V5E["hbm_bytes_per_s"], 4))


def plain_enter(x, *weights):
    """``hyper.enter`` from the module's plain statement."""
    n = STREAMS
    logits = hyper.logits_of(x, *weights, n, SIZES["eps"])
    return hyper.mix_in(x, jax.nn.sigmoid(logits[:n]), n, x.dtype), \
        hyper.token_major(logits)


def plain_leave_enter(x, y, coef, *weights):
    """``hyper.leave_enter`` from the module's plain statement: the
    way out over the whole pool, then the way in."""
    new = hyper.leave_lines(x, y, coef, STREAMS)
    return (new,) + plain_enter(new, *weights)


def steps_of(form):
    """(enter, leave_enter) in ``form``: the kernel, or the plain
    statement composed."""
    if form == "plain":
        return plain_enter, plain_leave_enter
    return (lambda x, *w: hyper.enter(x, *w, interpret=INTERPRET, **SIZES),
            lambda x, y, coef, *w: hyper.leave_enter(
                x, y, coef, *w, interpret=INTERPRET, **SIZES))


def maps(logits_tm):
    return hyper.coefficients_from(logits_tm, **STEPS)


def main():
    say({"device": DEVICE.device_kind, "platform": DEVICE.platform})
    for spread in (1.0, 12.0):
        x, *weights = operands(CHECK_ROWS, 62, spread)
        want, logits = jax.jit(plain_coefficients)(x, *weights)
        kept = {}
        for form in FORMS:
            enter, leave_enter = map(jax.jit, steps_of(form))
            u, logits_tm = enter(x, *weights)
            coef, _ = jax.jit(maps)(logits_tm)
            new, u2, logits2 = leave_enter(x, u.astype(jnp.float32), coef,
                                           *weights)
            got = np.asarray(coef)[:, :hyper.rows_of(STREAMS)].T
            worst = float(np.abs(got - np.asarray(want)).max())
            kept[form] = [np.asarray(a, np.float32)
                          for a in (u, new, u2, logits2)]
            say({"check": "coefficients", "form": form, "spread": spread,
                 "rows": CHECK_ROWS, "worst": worst,
                 "logit_max": float(np.abs(np.asarray(logits)).max())})
            assert worst < 1e-4, worst
        if len(kept) == 2:
            # one rounding of the stream's dtype apart, and the logits
            # of streams that far apart
            apart = [float(np.abs(a - b).max() / (1 + np.abs(b).max()))
                     for a, b in zip(*kept.values())]
            say({"check": "forms", "spread": spread, "apart": dict(zip(
                ("u", "new", "u2", "logits2"), apart))})
            assert max(apart[:3]) <= 2.0 ** -7 and apart[3] < 1e-2, apart
    for rows in ROWS:
        x, *weights = operands(rows, rows)
        for form in FORMS:
            for tokens in TOKENS if form == "kernel" else [None]:
                if tokens:
                    hyper._TOKENS = tokens
                enter, leave_enter = steps_of(form)
                u, logits_tm = jax.jit(enter)(x, *weights)
                coef, _ = jax.jit(maps)(logits_tm)
                y = u.astype(jnp.float32)

                def sublayer(x, y, logits_tm, *w, leave_enter=leave_enter):
                    coef, worst = maps(logits_tm)
                    return leave_enter(x, y, coef, *w), worst
                say({"rows": rows, "form": form, "tokens_a_step": tokens,
                     "floor_ms": floor_ms(rows),
                     "enter_ms": device_ms(jax.jit(enter), x, *weights),
                     "maps_ms": device_ms(jax.jit(maps), logits_tm),
                     "leave_enter_ms": device_ms(jax.jit(leave_enter), x, y,
                                                 coef, *weights),
                     "sublayer_ms": device_ms(jax.jit(sublayer), x, y,
                                              logits_tm, *weights)})


if __name__ == "__main__":
    main()
