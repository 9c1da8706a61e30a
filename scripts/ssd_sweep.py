"""``rnb_tpu.ops.ssd.ssd_scan`` alone, on the chip, at its callers'
shapes (Nemotron-H's M block: 64 rows, 64 heads of 64 in 8 groups, N
128, steps and a skip term; MiniCPM-SALA's lightning layer: 128 rows, 32
heads of 128, a group a head, unit steps; Falcon-H1's state-space
branch: 64 rows, 32 heads of 128 in 2 groups, N 256, steps and a skip
term — a group is 2,048 lanes, so a step is one group whatever
``_STEP_LANES`` says up to 2,048, and both at 4,096, which wants
``--vmem-mib``): a check of the kernel against
the blocked ``jax.numpy`` form it replaced (``tests/test_ssd_kernel.py``
keeps it) on a pool of three requests and a pad row, then the time of
that form and of the kernel at each count of lanes a grid step
(``ssd._STEP_LANES``), and of Nemotron-H's form with its gated norm as
the kernel's last lines: the device's own time from a profiler trace of
``REPEATS`` calls (the kernel's custom call, and every operation of the
jitted scan: the running sums and their transposes are XLA's), and the
host's clock around the calls, which at these sizes is mostly the launch.
Every kernel line says the scan's least time by its bytes and by its
operations (``floor_ms``: the recurrence's own 5 P N a token and head, x,
z and y in bfloat16, B, C and the steps once — what
``benchmarks/families/falcon_h1.py`` counts for ``scan``).
Then (``--only=lightning`` for it alone) a lightning layer between its
products and ``o``, from q, k and the gate in float32 and v in bfloat16
to ``o``'s bfloat16 operand: the head norms, ``rope.rotate``, the scale
and the rounding in front of the kernel and the output norm and the gate
behind it as XLA's passes, as MiniCPM-SALA's mixer ran them until PR 60,
against the kernel with those lines inside (``head_norm``,
``out_norm``), with the count of bfloat16 outputs that differ.
Then ``rnb_tpu.ops.ssd.segment_conv1d`` alone at its callers' shapes
(Nemotron-H's M block: 64 rows of 6,144 channels, a bias, xs, B and C
as three bfloat16 arrays; Qwen3-Next's DeltaNet layer: 128 rows of
8,192, no bias, q with k in float32 and v in bfloat16; Falcon-H1's 64
rows of 5,120, a bias, parts of 4,096, 512 and 512; all four taps
and the SiLU): the ``jax.numpy`` passes it replaced
(``tests/test_segment_conv.py`` keeps them) with the caller's SiLU,
slices and rounding behind them, against the kernel at each count of
rows and of lanes a grid step and of lanes the body holds at once (``ssd._CONV_ROWS``, ``ssd._CONV_LANES``, ``ssd._CONV_CHUNK``).
Lines go to stdout and to ``chiprun_out/ssd_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/ssd_sweep.py [--only=scan|lightning|conv] [--rows=N]
        [--callers=falcon_h1] [--lanes=512,1024] [--vmem-mib=64]
        [--conv-rows=4,8] [--conv-lanes=512,1024] [--conv-chunk=128,256]

Off the TPU the kernel runs in Pallas's interpret mode, which at these
sizes is of no use (``--rows=4`` is a dry run of the control flow).
"""
import functools
import itertools
import json
import os
import sys
import tempfile
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import peaks, xplane  # noqa: E402
from rnb_tpu.ops import ssd  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "ssd_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
REPEATS = 10
QLEN = 128


def option(name, default):
    given = [a.split("=")[1] for a in sys.argv if a.startswith(name + "=")]
    return given[0] if given else default


#: caller -> (rows, heads, groups, P, N, steps and a skip term?)
CALLERS = {"nemotron_h": (64, 64, 8, 64, 128, True),
           "lightning": (128, 32, 32, 128, 128, False),
           "falcon_h1": (64, 32, 2, 128, 256, True)}
ONLY_CALLERS = [c for c in option("--callers", "").split(",") if c]
LANES = [int(v) for v in option("--lanes", "512,1024,2048,4096").split(",")]
#: the scoped-VMEM limit of this sweep's kernels, MiB (0: the compiler's
#: own, 16 MiB on the v5e, under which every caller runs): no option of
#: ``ops/ssd.py`` — the sweep wraps the ``CompilerParams`` that module
#: builds, to try a step the default refuses
VMEM_MIB = int(option("--vmem-mib", "0"))
if VMEM_MIB:
    ssd.pltpu = types.SimpleNamespace(**dict(
        vars(ssd.pltpu), CompilerParams=functools.partial(
            ssd.pltpu.CompilerParams,
            vmem_limit_bytes=VMEM_MIB * 2 ** 20)))
#: the v5e's published peaks: a floor is a statement about that chip
V5E = peaks.peak_for("TPU v5 lite")

CONV_ROWS = [int(v) for v in option("--conv-rows", "1,2,4,8,16").split(",")]
CONV_LANES = [int(v) for v in
              option("--conv-lanes", "512,1024,2048").split(",")]
CONV_CHUNKS = [int(v) for v in option("--conv-chunk", "128").split(",")]


def say(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sweep.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def timed(f, *args, kernel=ssd.KERNEL_NAME):
    """-> (the result, {host_ms: median by the host's clock, device_ms:
    every operation's time a call in the device's trace, kernel_ms: the
    custom call named ``kernel`` alone})."""
    out = jax.block_until_ready(f(*args))
    took = []
    trace_dir = tempfile.mkdtemp()
    with jax.profiler.trace(trace_dir):
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            took.append(1e3 * (time.perf_counter() - t0))
    times = {"host_ms": round(float(np.median(took)), 3)}
    if not INTERPRET:
        ops = [op for plane in xplane.device_ops(
            xplane.find_xplane(trace_dir)).values() for op in plane]
        for key, mine in (("device_ms", ops), ("kernel_ms", [
                op for op in ops if kernel in op[2]])):
            times[key] = round(sum(end - start for start, end, _ in mine)
                               / REPEATS / 1e6, 4)
    return out, times


def operands(caller, rows, seed):
    """The heads' lanes side by side, as the callers hold them."""
    _, heads, groups, p, n, full = CALLERS[caller]
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    first = np.zeros(rows, bool)
    first[[0, rows // 3, max(rows - 2, 0), rows - 1]] = True
    a = -jnp.asarray(rng.uniform(0.01, 0.3, heads), jnp.float32)
    dt = d = None
    if full:
        dt = jnp.asarray(rng.uniform(0.005, 0.1, (rows, QLEN, heads)),
                         jnp.float32)
        d = jnp.asarray(rng.standard_normal(heads), jnp.float32)
    return (draw(rows, QLEN, heads * p), dt, a, draw(rows, QLEN, groups * n),
            draw(rows, QLEN, groups * n), d, jnp.asarray(first))


def scan_of(caller, scan, **kwargs):
    _, heads, groups, p, n, _ = CALLERS[caller]

    def run(xs, dt, a, b, c, d, first):
        rows = xs.shape[0]
        return scan(xs.reshape(rows, QLEN, heads, p), dt, a,
                    b.reshape(rows, QLEN, groups, n),
                    c.reshape(rows, QLEN, groups, n), d, first,
                    **kwargs).reshape(rows, QLEN, heads * p)
    return jax.jit(run)


def conv_sweep():
    from test_segment_conv import CALLERS as channels_of, REAL_ROWS, \
        SPLITS, passes
    for caller, rows in REAL_ROWS.items():
        if ONLY_CALLERS and caller not in ONLY_CALLERS:
            continue
        channels, biased, _ = channels_of[caller]
        parts = SPLITS[caller][2]
        rows = int(option("--rows", rows))
        rng = np.random.default_rng(48)
        x = jnp.asarray(rng.standard_normal((rows, QLEN, channels)),
                        jnp.bfloat16)
        weight = jnp.asarray(rng.standard_normal((channels, 4)) * 0.5,
                             jnp.bfloat16)
        bias = jnp.asarray(rng.standard_normal(channels), jnp.bfloat16)
        first = np.zeros(rows, bool)
        first[[0, rows // 3, max(rows - 2, 0), rows - 1]] = True
        args = (x, weight, bias, jnp.asarray(first))

        def as_passes(x, w, b, f):
            out = jax.nn.silu(passes(x, w, b if biased else jnp.zeros_like(b),
                                     f))
            edges = np.cumsum([0] + [count for count, _ in parts])
            return [out[..., lo:hi].astype(dtype)
                    for lo, hi, (_, dtype) in zip(edges, edges[1:], parts)]

        def as_kernel(x, w, b, f):
            return ssd.segment_conv1d(
                x, w, b if biased else None, f, activation="silu",
                out_dtype=tuple(dtype for _, dtype in parts),
                interpret=INTERPRET, split=tuple(c for c, _ in parts))

        def whole(outs):
            return np.concatenate([np.asarray(o.astype(jnp.float32))
                                   for o in outs], axis=-1)
        want, times = timed(jax.jit(as_passes), *args)
        say({"caller": caller, "rows": rows, "form": "conv passes", **times})
        want = whole(want)
        chosen = ssd._CONV_ROWS, ssd._CONV_LANES, ssd._CONV_CHUNK
        for tiles in itertools.product(CONV_ROWS, CONV_LANES, CONV_CHUNKS):
            ssd._CONV_ROWS, ssd._CONV_LANES, ssd._CONV_CHUNK = tiles
            # the tiles are read while the call traces, and jit keeps a
            # trace a function
            ssd._conv_call.clear_cache()
            got, times = timed(jax.jit(lambda *args: as_kernel(*args)),
                               *args, kernel=ssd.CONV_KERNEL_NAME)
            worst = float(np.abs(whole(got) - want).max()
                          / (1.0 + np.abs(want).max()))
            say({"caller": caller, "rows": rows, "form": "conv kernel",
                 "step_rows": ssd._CONV_ROWS,
                 "step_lanes": ssd._CONV_LANES,
                 "chunk_lanes": ssd._CONV_CHUNK, **times,
                 "worst_vs_passes": worst})
            assert worst < 5e-3, worst
        ssd._CONV_ROWS, ssd._CONV_LANES, ssd._CONV_CHUNK = chosen
        ssd._conv_call.clear_cache()


def main():
    say({"device": DEVICE.device_kind, "platform": DEVICE.platform})
    only = option("--only", "")
    if only in ("", "scan"):
        scan_sweep()
    if only in ("", "lightning"):
        lightning_sweep()
    if only in ("", "conv"):
        conv_sweep()


def scan_floor_ms(shape, rows):
    """The least time of one call by the recurrence's own operations and
    bytes: (by operations, by bytes), ms."""
    _, heads, groups, p, n, full = shape
    tokens = rows * QLEN
    ops = tokens * heads * (5 * p * n + 2 * p)
    nbytes = tokens * (2 * (2 + full) * heads * p + 2 * 2 * groups * n
                       + 4 * heads * full)
    return (round(1e3 * ops / V5E["bf16_flops_per_s"], 4),
            round(1e3 * nbytes / V5E["hbm_bytes_per_s"], 4))


def scan_sweep():
    from test_ssd_kernel import blocked
    chosen = ssd._STEP_LANES
    for caller, shape in CALLERS.items():
        if ONLY_CALLERS and caller not in ONLY_CALLERS:
            continue
        rows = int(option("--rows", shape[0]))
        args = operands(caller, rows, 47)
        want, times = timed(scan_of(caller, blocked), *args)
        say({"caller": caller, "rows": rows, "form": "blocked", **times})
        want = np.asarray(want)
        for lanes in LANES:
            ssd._STEP_LANES = lanes
            t0 = time.perf_counter()
            try:
                got, times = timed(
                    scan_of(caller, ssd.ssd_scan, interpret=INTERPRET),
                    *args)
            except Exception as e:   # a step the compiler refuses
                say({"caller": caller, "rows": rows, "form": "kernel",
                     "step_lanes": lanes, "vmem_mib": VMEM_MIB,
                     "refused": str(e)[-300:]})
                continue
            worst = float(np.abs(np.asarray(got) - want).max()
                          / (1.0 + np.abs(want).max()))
            say({"caller": caller, "rows": rows, "form": "kernel",
                 "step_lanes": lanes, "vmem_mib": VMEM_MIB,
                 "groups_a_step": ssd._groups_a_step(shape[2],
                                                     shape[1] // shape[2],
                                                     shape[3]),
                 "first_call_and_trace_s": round(time.perf_counter() - t0,
                                                 1),
                 "floor_ms": scan_floor_ms(shape, rows),
                 **times, "worst_vs_blocked": worst})
            assert worst < 5e-3, worst
        ssd._STEP_LANES = chosen
        if shape[5]:
            rng = np.random.default_rng(48)
            width = shape[1] * shape[3]
            gated_norm = (
                jnp.asarray(rng.standard_normal((rows, QLEN, width)),
                            jnp.float32),
                jnp.asarray(rng.uniform(0.5, 1.5, width), jnp.bfloat16), 1e-5)
            _, times = timed(scan_of(caller, ssd.ssd_scan, interpret=INTERPRET,
                                     gated_norm=gated_norm), *args)
            say({"caller": caller, "rows": rows,
                 "form": "kernel with the gated norm", "step_lanes": chosen,
                 "vmem_mib": VMEM_MIB, "floor_ms": scan_floor_ms(shape, rows),
                 **times})


def lightning_sweep():
    """A lightning layer between its products and ``o``, from q, k and
    the gate in float32 as their products write them and v in bfloat16 to
    ``o``'s bfloat16 operand: the passes around the kernel as the mixer
    ran them until PR 60 (head norms, ``rope.rotate``, scale and
    rounding in front; the output norm and the gate behind a float32
    ``y``) against the kernel with those lines inside. The rotary tables
    of the kernel's form are made outside the timed call, once a dispatch
    in the program; the passes form their angles in every call, as the
    program's did."""
    from rnb_tpu.models.minicpm_sala.network import rms_norm
    from rnb_tpu.ops import banded, rope
    rows, heads, _, p, n, _ = CALLERS["lightning"]
    rows = int(option("--rows", rows))
    rng = np.random.default_rng(60)
    f32, act, eps, width = jnp.float32, jnp.bfloat16, 1e-6, heads * p
    first = np.zeros(rows, bool)
    first[[0, rows // 3, max(rows - 2, 0), rows - 1]] = True
    row_start = jnp.asarray(np.maximum.accumulate(
        np.where(first, np.arange(rows), 0)), jnp.int32)
    inv_freq = (10000.0 ** (-np.arange(0, n, 2) / n)).astype(np.float32)
    log_decay = jnp.asarray(-2.0 ** (-8.0 * np.arange(1, heads + 1) / heads),
                            f32)
    q, k, gate = (jnp.asarray(rng.standard_normal((rows, QLEN, width)), f32)
                  for _ in range(3))
    v = jnp.asarray(rng.standard_normal((rows, QLEN, width)), act)
    qw, kw = (jnp.asarray(rng.uniform(0.5, 1.5, n), act) for _ in range(2))
    ow = jnp.asarray(rng.uniform(0.5, 1.5, width), act)
    tables = [t.reshape(rows, QLEN, n) for t in
              banded.band_tables(row_start, QLEN, inv_freq)[:2]]
    args = (q, k, v, gate, jnp.asarray(first))

    def of_heads(x):
        return x.reshape(rows, QLEN, heads, -1)

    def passes(q, k, v, gate, first):
        positions = rope.pool_positions(row_start, QLEN)
        qs = rope.rotate(rms_norm(of_heads(q), qw, eps, f32), positions,
                         inv_freq)
        ks = rope.rotate(rms_norm(of_heads(k), kw, eps, f32), positions,
                         inv_freq)
        y = ssd.ssd_scan(of_heads(v), None, log_decay, ks.astype(act),
                         (qs * n ** -0.5).astype(act), None, first,
                         interpret=INTERPRET)
        y = rms_norm(y.reshape(rows, QLEN, width), ow, eps, f32)
        return (y * jax.nn.sigmoid(gate)).astype(act)

    def inside(q, k, v, gate, first, cos, sin):
        return ssd.ssd_scan(
            of_heads(v), None, log_decay, of_heads(k), of_heads(q), None,
            first, interpret=INTERPRET,
            head_norm=(kw, qw, eps, n ** -0.5, cos, sin),
            out_norm=(gate, ow, eps)).reshape(rows, QLEN, width)
    want, times = timed(jax.jit(passes), *args)
    say({"caller": "lightning", "rows": rows,
         "form": "passes around the kernel", **times})
    want = np.asarray(want.astype(f32))
    t0 = time.perf_counter()
    got, times = timed(jax.jit(inside), *args, *tables)
    got = np.asarray(got.astype(f32))
    say({"caller": "lightning", "rows": rows,
         "form": "lines inside the kernel",
         "first_call_and_trace_s": round(time.perf_counter() - t0, 1),
         **times, "elements_apart": int((got != want).sum()),
         "of": int(got.size),
         "worst_apart": float(np.abs(got - want).max())})
    assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max()

if __name__ == "__main__":
    main()
