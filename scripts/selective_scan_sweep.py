"""``rnb_tpu.ops.selective_scan.selective_scan`` alone, on the chip, at
Phi-4-mini-flash's published shape (5,120 channels, 16 states, rows of
128 tokens) and the cell's row buckets: a check of the kernel against
the token-by-token recurrence (``selective_scan.recurrence``) and, to
the bit, against the slab form it replaced (``tests/
selective_scan_slabs.py``: PR 59's kernel behind XLA's copies into
``(rows, Q, C / 128, 128)``) on a pool of three requests and a pad row
at the draw's extremes, then its time a call at each count of channels
a grid step and of tokens a turn (``selective_scan._STEP_CHANNELS``,
``_UNROLL``: the token loop's body holds one turn) beside the slab
form's: the device's own time from a profiler trace of ``REPEATS`` calls
— ``kernel_ms`` the custom call alone, ``device_ms`` every operation of
the jitted call (the slab form's relayouts of x, dt and z in front of
the kernel and of y behind it are XLA's; the kernel that reads the
pool's layout has none) — and the host's clock around the calls. Every
line says the scan's least time by its operations at the matrix unit's
bf16 peak and by its bytes at the HBM's rate (``floor_ms``: what
``benchmarks/families/phi4_flash.py`` counts for ``selective_scan`` —
the peaks' table has no vector-unit rate, ROADMAP D10).
Lines go to stdout and to ``chiprun_out/selective_scan_sweep/sweep.jsonl``.

    chiprun -- python3 scripts/selective_scan_sweep.py [--rows=64,128]
        [--channels=1024,5120] [--unroll=8,16] [--check-rows=8]
        [--vmem-mib=64] [--slabs=0]

``--channels`` takes what divides 5,120 in whole registers: 1,024 or all
5,120 (2,048 does not divide them, and ``step_channels`` then takes them
all: what PR 59's table called 2,048 was 5,120 a step); ``--vmem-mib``
puts another limit on scoped VMEM in place of the kernel's own.
Off the TPU the kernel runs in Pallas's interpret mode, which at these
sizes is of no use (``--rows=2 --check-rows=2`` is a dry run of the
control flow).
"""
import itertools
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import selective_scan_slabs as slabs  # noqa: E402
from benchmarks import peaks, xplane  # noqa: E402
from rnb_tpu.ops import selective_scan as ss  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "selective_scan_sweep")
DEVICE = jax.devices()[0]
INTERPRET = DEVICE.platform != "tpu"
REPEATS = 5
QLEN, CHANNELS, STATES = 128, 5120, 16


def option(name, default):
    given = [a.split("=")[1] for a in sys.argv if a.startswith(name + "=")]
    return given[0] if given else default


def ints(name, default):
    return [int(v) for v in option(name, default).split(",")]


ROWS = ints("--rows", "64,80,96,112,128")
STEP_CHANNELS = ints("--channels", "5120")
UNROLL = ints("--unroll", "8")
CHECK_ROWS = int(option("--check-rows", "8"))
VMEM_MIB = int(option("--vmem-mib", "0"))
SLABS = bool(int(option("--slabs", "1")))
#: the v5e's published peaks: a floor is a statement about that chip
V5E = peaks.peak_for("TPU v5 lite")


def say(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sweep.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def timed(f, *args):
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
                # the slab form's name begins with the kernel's
                op for op in ops if ss.KERNEL_NAME in op[2]])):
            times[key] = round(sum(end - start for start, end, _ in mine)
                               / REPEATS / 1e6, 4)
    return out, times


def operands(rows, seed, extreme=None):
    """A pool of ``rows`` rows as a Mamba layer hands it to the scan;
    ``extreme`` (A, dt) pins every decay to one corner of the draw."""
    rng = np.random.default_rng(seed)

    def draw(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    first = np.zeros(rows, bool)
    first[[0, rows // 3, max(rows - 2, 0), rows - 1]] = True
    a = -np.tile(np.arange(1, STATES + 1, dtype=np.float32), (CHANNELS, 1))
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1),
                            (rows, QLEN, CHANNELS))).astype(np.float32)
    if extreme is not None:
        a[:], dt[:] = extreme
    return (draw(rows, QLEN, CHANNELS), jnp.asarray(dt), jnp.asarray(a),
            draw(rows, QLEN, STATES, dtype=jnp.float32),
            draw(rows, QLEN, STATES, dtype=jnp.float32),
            jnp.ones(CHANNELS, jnp.float32), draw(rows, QLEN, CHANNELS),
            jnp.asarray(first))


def floor_ms(rows):
    """(by operations, by bytes): a decay, an update and a read-out a
    state and the skip term; x, z, y in bfloat16, dt in float32, B and C
    once."""
    tokens = rows * QLEN
    ops = tokens * CHANNELS * (7 * STATES + 2)
    nbytes = tokens * (CHANNELS * (2 + 4 + 2 + 2) + 2 * 4 * STATES)
    return (round(1e3 * ops / V5E["bf16_flops_per_s"], 4),
            round(1e3 * nbytes / V5E["hbm_bytes_per_s"], 4))


def run(*args):
    return ss.selective_scan(*args, memory=True, interpret=INTERPRET)


def run_slabs(*args):
    return slabs.selective_scan(*args, memory=True, interpret=INTERPRET)


def main():
    say({"device": DEVICE.device_kind, "platform": DEVICE.platform})
    if VMEM_MIB:
        ss._VMEM_LIMIT = VMEM_MIB << 20
    for extreme in (None, (-16.0, 0.1), (-1.0, 0.001)):
        args = operands(CHECK_ROWS, 59, extreme)
        want = jax.jit(ss.recurrence)(*args)
        got = jax.jit(run)(*args)
        worst = [float(np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
                       / (1.0 + np.abs(np.asarray(w)).max()))
                 for g, w in zip(got, want)]
        equal = [bool(np.array_equal(np.asarray(g, np.float32),
                                     np.asarray(s, np.float32)))
                 for g, s in zip(got, jax.jit(run_slabs)(*args))]
        say({"check": "extreme %s" % (extreme,), "rows": CHECK_ROWS,
             "worst_vs_recurrence": worst, "equal_to_slabs": equal})
        # off the chip XLA's CPU fusions round the two bodies apart
        # (``tests/test_selective_scan.py`` compares them unfused)
        assert max(worst) < 5e-3 and (INTERPRET or all(equal)), (worst, equal)
    chosen = ss._STEP_CHANNELS, ss._UNROLL
    forms = [("pool", run) + form
             for form in itertools.product(STEP_CHANNELS, UNROLL)] \
        + [("slabs", run_slabs) + chosen] * SLABS
    for rows in ROWS:
        args = operands(rows, 60)
        for layout, call, channels, unroll in forms:
            ss._STEP_CHANNELS, ss._UNROLL = channels, unroll
            # read while the call traces, and jit keeps a trace a function
            ss._scan_call.clear_cache()
            line = {"rows": rows, "layout": layout,
                    "step_channels": ss.step_channels(CHANNELS),
                    "unroll": unroll}
            t0 = time.perf_counter()
            try:
                _, times = timed(jax.jit(lambda *a: call(*a)), *args)
            except Exception as e:   # a step the compiler refuses
                say({**line, "refused": str(e)[-300:]})
                continue
            say({**line, "first_call_and_trace_s": round(
                time.perf_counter() - t0, 1), "floor_ms": floor_ms(rows),
                **times})
    ss._STEP_CHANNELS, ss._UNROLL = chosen
    ss._scan_call.clear_cache()


if __name__ == "__main__":
    main()
