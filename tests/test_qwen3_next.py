"""The Qwen3-Next family against its plain reference, at a toy size on
the CPU with weights from a seed: the packed prefill through dispatches
with several requests, a pad row and a request that ends inside a row,
the lower-precision controls, the gated delta rule's kernel
(``ops/deltanet``, interpreted) with its first and last lines against
the plain composition - ``l2_norm``, the token-by-token recurrence,
``rms_norm`` times the gate - with state and convolution history reset
at every request's first row, at toy heads and at the family's 16 / 32,
its states through bfloat16, the triangular solve alone, the shares of
the experts adding up to the uncut layer, partial rotary, the stages and their counters, the operation counts,
the cell through the one benchmark command, the four new readers on a
run without their scope, the real configuration against the catalog's
row, what the rule's kernel keeps out of the lowered program (a row's
``Q x Q`` arrays; at the real widths any float32 array of a head axis),
the kernel compiled at the published widths for a described v5e, and the shared
code's StableHLO for the three older families.
Nothing here needs the native decode library or a chip."""

import functools
import hashlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import qwen3_next as reference  # noqa: E402

REAL = "benchmarks/configs/qwen3-next-l4-ep2.json"
CELL = "qwen3-next.bulk"
SEED = 3_000_000_123

#: one period of the published pattern at toy widths: 2 key / 4 value
#: heads of 16 in the DeltaNet layers, 4 / 2 heads of 32 with rotary on
#: 8 columns in the attention layer, 16 experts top-4 of which 8 held
TOY = {
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "hidden_size": 64, "vocab_size": 256, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 8,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "rms_norm_eps": 1e-6,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True, "rope_scaling": None, "hidden_act": "silu",
    "published": {"num_hidden_layers": 48, "num_experts": 16}}
HELD = tuple(range(8))
OTHER = tuple(range(8, 16))
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths: narrow sums average less
#: rounding away than the real ones (the real limit is the family
#: file's SHARE_OF_SPREAD); the toy reads 2.2 to 2.7% over two seeds
#: of weights and its float8 control 11 to 19%
TOY_LIMIT = 0.04


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.qwen3_next import checkpoint, network
    cfg = network.Qwen3NextConfig.from_published(TOY)
    device = jax.devices()[0]
    return {"cfg": cfg, "device": device, "programs": {},
            "params": checkpoint.make_params(cfg, SEED, HELD, device),
            "slots": network.held_slots(cfg, HELD),
            "read": checkpoint.reference_reader(cfg, SEED, device),
            "reference": reference.Reference(TOY)}


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def pack(prompts, rows):
    from rnb_tpu.models import token_stages
    return token_stages.pack_prompts(prompts, rows, Q)


def program_of(toy, **arm):
    """The toy stack jitted once an arm of ``forward``, kept on the
    module's ``toy``: a test that runs it at rows another has run traces
    and compiles nothing."""
    import jax

    from rnb_tpu.models.qwen3_next import network
    key = tuple(sorted(arm.items()))
    if key not in toy["programs"]:
        toy["programs"][key] = jax.jit(
            lambda p, t, m: network.forward(
                toy["cfg"], p, toy["slots"], t, m[0], m[1], m[2],
                interpret=True, **arm))
    return toy["programs"][key]


def run_program(toy, prompts, rows, params=None, **kwargs):
    """-> (logits a prompt, each prompt's router choices (layers,
    tokens, k), the counters)."""
    tokens, meta, offsets = pack(prompts, rows)
    logits, chosen, *counts = program_of(toy, **kwargs)(
        toy["params"] if params is None else params, tokens, meta)
    chosen = np.asarray(chosen)
    per_prompt = [chosen[:, o * Q:o * Q + len(p)]
                  for o, p in zip(offsets, prompts)]
    return np.asarray(logits)[:len(prompts)], per_prompt, \
        [np.asarray(c) for c in counts]


def run_reference(toy, prompt, forced=None):
    import jax
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(toy["read"], prompt, held=HELD,
                                        forced=forced)


def through_float8(params):
    """The stored matrices of every layer rounded through float8 (e4m3):
    the nearest precision below the one the configuration states."""
    import jax.numpy as jnp
    out = dict(params)
    for group, tensors in params.items():
        if isinstance(tensors, dict):
            out[group] = {
                name: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                       if w.ndim >= 2 else w)
                for name, w in tensors.items()}
    return out


# -- the whole stack ----------------------------------------------------------

#: dispatches of 16 rows: several requests, one that ends inside a row,
#: one that fills its rows, pad rows behind
DISPATCHES = {"three": [120, 37, 70], "whole_rows": [16, 96, 5, 64],
              "one_long": [250]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_matches_the_reference(toy, case):
    family = mm.load_family("qwen3_next")
    prompts = prompts_of(DISPATCHES[case], seed=4)
    logits, chosen, counts = run_program(toy, prompts, 16)
    refs = [run_reference(toy, p, forced=c)
            for p, c in zip(prompts, chosen)]
    want = np.stack([np.asarray(r["logits"]) for r in refs])
    verdict = compare(logits, want, TOY_LIMIT)
    assert verdict["ok"], verdict
    assert max(float(np.asarray(r["shortfall"]).max()) for r in refs) \
        < family.ROUTE_SLACK
    # the reference's own free choice agrees almost everywhere
    for prompt, mine in zip(prompts, chosen):
        free = np.asarray(run_reference(toy, prompt)["chosen"])
        assert (np.sort(free, -1) == np.sort(mine, -1)).all(-1).mean() > 0.9
    # the counters: served pairs and sending tokens of the valid
    # tokens, the rows the first product multiplied for those pairs
    served, sent, tiles, gmm_rows = counts
    assert gmm_rows.shape == (4,) and (served.sum(-1) <= gmm_rows).all()
    valid = sum(DISPATCHES[case])
    assert served.shape == (4, 8) and sent.shape == (4,)
    assert served.sum() == sum(int(np.isin(c, HELD).sum()) for c in chosen)
    assert (sent <= valid).all() and sent.min() > 0
    assert tiles.shape == (1, 2) and tiles[0, 0] <= tiles[0, 1]


def test_the_lower_precision_controls(toy):
    """The stored matrices through float8 lie outside the stated
    tolerance, with the rule's states through bfloat16 and without. The
    states through bfloat16 alone are reported by the control script on
    the chip; here they read like the stated precision."""
    import jax.numpy as jnp
    prompts = prompts_of([120, 37, 70], seed=4)

    def reading(**how):
        logits, chosen, _ = run_program(toy, prompts, 16, **how)
        want = np.stack([np.asarray(run_reference(toy, p, forced=c)
                                    ["logits"])
                         for p, c in zip(prompts, chosen)])
        return compare(logits, want, TOY_LIMIT)
    assert reading()["ok"]
    assert not reading(params=through_float8(toy["params"]))["ok"]
    assert not reading(params=through_float8(toy["params"]),
                       state_dtype=jnp.bfloat16)["ok"]
    assert reading(state_dtype=jnp.bfloat16)["share_of_spread"] < 0.2


def test_packing_is_invisible_and_state_and_positions_restart(toy):
    """A prompt's logits and choices depend neither on what shares its
    dispatch, nor on where in the pool it lies, nor on the bucket."""
    a, b, c, d = prompts_of([100, 5, 70, 20])
    alone, chosen, _ = run_program(toy, [a], 8)
    packed, packed_chosen, _ = run_program(toy, [b, c, a, d], 16)
    other, other_chosen, _ = run_program(toy, [d, a], 16)
    want = run_reference(toy, a, forced=chosen[0])
    spread = float(np.asarray(want["logits"]).std())
    for got in (packed[2], other[1]):
        assert np.abs(got - alone[0]).max() < 0.005 * spread
    assert np.array_equal(packed_chosen[2], chosen[0])
    assert np.array_equal(other_chosen[1], chosen[0])
    assert compare(alone[0], np.asarray(want["logits"]), TOY_LIMIT)["ok"]


# -- the delta rule alone -------------------------------------------------------


EPS = 1e-6


def rule_inputs(rows, qlen, hk=2, hv=4, dk=8, dv=8, seed=1, act=None):
    """The kernel's operands as the mixer hands them over: ``qk`` as a
    convolution wrote it (float32, every key head's q then every key
    head's k, a token's length anything from a tenth to ten), ``v`` (in
    ``act``, else float32), ``log alpha``, ``beta``, the output gate
    before its SiLU and the head norm's weight."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    qk = n(rows, qlen, 2 * hk, dk) \
        * jnp.exp(jnp.log(10.0) * jnp.asarray(
            rng.uniform(-1, 1, (rows, qlen, 2 * hk, 1)), jnp.float32))
    return (qk.reshape(rows, qlen, 2 * hk * dk),
            n(rows, qlen, hv * dv).astype(act or jnp.float32),
            -0.3 * jnp.exp(n(rows, qlen, hv)),
            jax.nn.sigmoid(n(rows, qlen, hv)), n(rows, qlen, hv * dv),
            1.0 + 0.1 * n(dv))


def rule(inputs, row_first, hk, **how):
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    return np.asarray(deltanet.gated_delta_rule(
        *inputs, jnp.asarray(row_first), key_heads=hk, eps=EPS,
        activation="silu", interpret=True, **how).astype(jnp.float32))


def composed(inputs, lo, hi, hk):
    """What the kernel replaces, the plain way, over rows ``lo`` to
    ``hi`` as a request of its own: ``l2_norm`` a head and q's scale,
    one rounding to the activations' dtype, the reference's recurrence
    token by token, the head's ``rms_norm``, times ``silu(z)``, one
    rounding. -> (L, Hv Dv) float32."""
    import jax
    import jax.numpy as jnp
    qk, v, log_alpha, beta, z, weight = (
        x[lo:hi].reshape((-1,) + x.shape[2:]) if x.ndim > 1 else x
        for x in inputs)
    act, hv = v.dtype, beta.shape[-1]
    length, dk = qk.shape[0], qk.shape[1] // (2 * hk)
    qk = qk.reshape(length, 2, hk, dk)
    q = (reference.l2_norm(qk[:, 0]) * dk ** -0.5).astype(act)
    k = reference.l2_norm(qk[:, 1]).astype(act)
    with jax.default_matmul_precision("highest"):
        out = reference.delta_rule(
            *(jnp.repeat(x.astype(jnp.float32), hv // hk, axis=1)
              for x in (q, k)),
            v.reshape(length, hv, -1).astype(jnp.float32),
            jnp.exp(log_alpha), beta)
    out = reference.rms_norm(out, weight, EPS, centred=False) \
        .reshape(z.shape) * jax.nn.silu(z)
    return np.asarray(out.astype(act).astype(jnp.float32))


def firsts_of(rows, firsts):
    row_first = np.zeros(rows, bool)
    row_first[list(firsts)] = True
    return row_first


def first_token_alone(inputs, row, hk, eps, gate):
    """What a request's first token gives, in float64: it reads only its
    own write, beta v (k . q), behind the head's norm and ``gate`` of
    ``z``. ``inputs`` as ``rule_inputs`` gives them, the request's first
    row ``row``. -> (value heads, Dv)."""
    qk, v, _, beta, z, weight = (np.asarray(x, np.float64) for x in inputs)
    dv = weight.size
    unit = qk[row, 0].reshape(2, hk, -1)
    unit = unit / np.sqrt((unit * unit).sum(-1, keepdims=True) + 1e-6)
    dots = (unit[0] * unit[1]).sum(-1) * unit.shape[-1] ** -0.5
    v, z = (x[row, 0].reshape(-1, dv) for x in (v, z))
    alone = (beta[row, 0] * np.repeat(dots, len(v) // hk))[:, None] * v
    return alone / np.sqrt((alone * alone).mean(-1, keepdims=True) + eps) \
        * weight * gate(z)


#: (tokens a row, the rows that open a request, key heads): the first
#: five are the blocked form's own cases; then what only a kernel that
#: walks the rows with its states at hand has to face - every row of two
#: head groups' walks opens a request (the state zeroed each step), one
#: request spans the whole pool, a pad row (a request of its own) lies
#: between two requests
RULE_CASES = [
    (16, (0,), 2), (16, (0, 3, 4), 2), (16, (0, 1, 2, 3, 4, 5), 2),
    (64, (0, 2), 2), (128, (0, 1), 2),
    (64, (0, 1, 2, 3, 4, 5), 4), (128, (0,), 2), (32, (0, 3, 4), 4)]


@pytest.mark.parametrize("qlen,firsts,hk", RULE_CASES)
def test_the_blocked_rule_matches_the_recurrence(qlen, firsts, hk):
    """``gated_delta_rule`` (the kernel, interpreted) over a pool of six
    rows (three at 128) against the plain composition around the
    reference's recurrence, token by token, a request at a time: the
    state starts at zero at every request's first row. Rows of 32, 64
    and 128 tokens go through the solve's merged levels; four key heads
    are two head groups."""
    rows = 3 if qlen == 128 else 6
    inputs = rule_inputs(rows, qlen, hk=hk, hv=2 * hk)
    out = rule(inputs, firsts_of(rows, firsts), hk)
    bounds = list(firsts) + [rows]
    for lo, hi in zip(bounds, bounds[1:]):
        want = composed(inputs, lo, hi, hk)
        got = out[lo:hi].reshape(want.shape)
        assert np.abs(got - want).max() < 2e-5 * max(
            1.0, np.abs(want).max())
    # a state that did not restart would show at the second request's
    # first token
    if len(firsts) > 1:
        lo = firsts[1]
        alone = first_token_alone(inputs, lo, hk, EPS,
                                  lambda z: z / (1.0 + np.exp(-z)))
        assert np.abs(out[lo, 0].reshape(alone.shape) - alone).max() < 2e-5


#: (key heads, value heads, the activations' dtype, the limit as a share
#: of the largest entry): Qwen3-Next's head geometry - 16 key heads, two
#: value heads reading each, eight head groups - at heads of 128, in
#: float32 and in the program's bfloat16, where kernel and composition
#: round at the same two places and a sum that associates otherwise may
#: move a value by one step of bfloat16, 2^-8
GEOMETRY_CASES = [(16, 32, "float32", 2e-5), (16, 32, "bfloat16", 2.0 ** -7)]


@pytest.mark.parametrize("hk,hv,act,limit", GEOMETRY_CASES)
def test_the_kernels_first_and_last_lines_are_the_mixers_norms(
        hk, hv, act, limit):
    """The kernel with its prologue and epilogue against ``l2_norm`` ->
    the sequential rule -> ``rms_norm`` x ``silu(z)``, at the family's
    head counts and head size, over a pool of six rows that holds a
    request of one row, a request of three rows whose first lies
    mid-pool, a pad row (a request of its own) and a request's first
    row at the pool's end."""
    import jax.numpy as jnp
    firsts = (0, 1, 4, 5)
    inputs = rule_inputs(6, 16, hk=hk, hv=hv, dk=128, dv=128,
                         act=jnp.dtype(act))
    # a pad row: the tokens the packer left empty read as zeros
    inputs = tuple(x.at[4].set(0) if x.ndim == 3 else x for x in inputs)
    out = rule(inputs, firsts_of(6, firsts), hk)
    assert np.isfinite(out).all()
    for lo, hi in zip(firsts, firsts[1:] + (6,)):
        want = composed(inputs, lo, hi, hk)
        got = out[lo:hi].reshape(want.shape)
        assert np.abs(got - want).max() < limit * max(
            1.0, np.abs(want).max()), (lo, hi)


def test_bfloat16_states_differ_by_one_rounding_a_row():
    """The control's ``state_dtype``: a request's first row reads no
    carried state, so it is the float32 rule's bit for bit; every later
    row reads a state rounded once more, and differs (a rounding that
    was dropped would read zero) by no more than its roundings, each
    2^-9 of the state, allow."""
    import jax.numpy as jnp
    inputs = rule_inputs(6, 16)

    def states(firsts, **how):
        return rule(inputs, firsts_of(6, firsts), 2, **how)
    exact, rounded = states((0, 4)), states((0, 4), state_dtype=jnp.bfloat16)
    scale = np.abs(exact).max()
    for row in (0, 4):
        assert np.array_equal(rounded[row], exact[row])
    for row, roundings in ((1, 1), (2, 2), (3, 3), (5, 1)):
        off = np.abs(rounded[row] - exact[row]).max()
        assert 0 < off < roundings * 2.0 ** -7 * scale, (row, off)
    # requests of one row each: nothing rounded is ever read
    alone = range(6)
    assert np.array_equal(states(alone, state_dtype=jnp.bfloat16),
                          states(alone))


def solve(lower):
    """``deltanet.unit_lower_inverse`` over a batch, as the kernel's
    body calls it: one matrix at a time."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    return np.asarray(jax.jit(lambda batch: jnp.stack(
        [deltanet.unit_lower_inverse(x) for x in batch]))(lower))


#: 8 to 32: the substitution alone; 64 and 128: one and two levels of
#: merges over the odd blocks' rows
@pytest.mark.parametrize("size", [8, 16, 32, 64, 128])
def test_the_triangular_solve_is_the_inverse(size):
    rng = np.random.default_rng(size)
    lower = np.tril(0.2 * rng.normal(size=(5, size, size)), -1) \
        .astype(np.float32)
    got = solve(lower)
    want = np.linalg.inv(np.eye(size) + lower.astype(np.float64))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert np.array_equal(np.triu(got, 1), np.zeros_like(got))


#: the contractions the kernels take (``a b``, ``a b^T``, ``a^T b``) by
#: the operands' dtypes: both float32 (six passes), one bfloat16 (three)
PRODUCT_CASES = [(dims, left, right)
                 for dims in ("_NN", "_NT", "_TN")
                 for left, right in (("float32", "float32"),
                                     ("float32", "bfloat16"),
                                     ("bfloat16", "float32"))]


def product_operands(rng, dims, left, right, whole):
    """(a, b) float64 for a contraction over 32 terms, a (24, 40)
    result, ``left`` and ``right`` the dtypes they will be given in.
    ``whole``: integers of 12 bits (two bfloat16 parts) in float32 and
    of 6 (one part) in bfloat16, so that every sum of 32 products is a
    float32 to the bit."""
    def draw(shape, dtype):
        if whole:
            top = 4095 if dtype == "float32" else 63
            return rng.integers(-top, top + 1, shape).astype(np.float64)
        return rng.normal(size=shape) \
            * np.exp(rng.uniform(-3, 3, (shape[0], 1)))
    return (draw((32, 24) if dims == "_TN" else (24, 32), left),
            draw((40, 32) if dims == "_NT" else (32, 40), right))


@pytest.mark.parametrize("dims,left,right", PRODUCT_CASES)
def test_a_product_keeps_every_part_its_operands_hold(dims, left, right):
    """``deltanet._product`` over ``_parts``: against ``highest`` on the
    same operands to 2^-21 of the result's scale, whatever the dtypes;
    and where an operand is bfloat16 (one part, three passes) *exact*:
    on integers whose sums float32 holds to the bit, the float64
    product's value, which no dropped part would give."""
    import jax.numpy as jnp
    from jax import lax

    from rnb_tpu.ops import deltanet
    how = getattr(deltanet, dims)
    rng = np.random.default_rng(len(dims + left + right))

    def both(whole):
        a, b = product_operands(rng, dims, left, right, whole)
        a, b = jnp.asarray(a, jnp.dtype(left)), jnp.asarray(b,
                                                            jnp.dtype(right))
        got = deltanet._product(deltanet._parts(a), deltanet._parts(b), how)
        assert got.dtype == jnp.float32
        f32 = jnp.float32
        want = lax.dot_general(a.astype(f32), b.astype(f32), how,
                               precision=lax.Precision.HIGHEST)
        exact = np.einsum(
            {"_NN": "ik,kj->ij", "_NT": "ik,jk->ij", "_TN": "ki,kj->ij"}[
                dims], np.asarray(a, np.float64), np.asarray(b, np.float64))
        return np.asarray(got, np.float64), np.asarray(want), exact
    got, want, exact = both(False)
    assert np.abs(got - want).max() <= 2.0 ** -21 * np.abs(want).max()
    assert np.abs(got - exact).max() <= 2.0 ** -21 * np.abs(exact).max()
    passes = len(deltanet._terms(*(
        len(deltanet._parts(jnp.zeros((), jnp.dtype(x))))
        for x in (left, right))))
    if "bfloat16" not in (left, right):
        assert passes == 6
        return
    assert passes == 3
    # the float32 side's 12 bits lie in two parts: one part alone is off
    got, _, exact = both(True)
    assert np.abs(exact).max() < 2 ** 24
    assert np.array_equal(got, exact)


def test_a_float32_operand_is_its_three_parts_to_the_bit():
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(64, 64)) * np.exp(rng.uniform(-20, 20, (64, 64)))) \
        .astype(np.float32)
    parts = deltanet._parts(jnp.asarray(x))
    assert [p.dtype for p in parts] == [jnp.bfloat16] * 3
    assert np.array_equal(
        sum(np.asarray(p, np.float64) for p in parts), x.astype(np.float64))
    one = deltanet._parts(jnp.asarray(x, jnp.bfloat16))
    assert len(one) == 1 and one[0].dtype == jnp.bfloat16


def test_keys_alike_do_not_break_the_solve():
    """Neighbouring keys that are all but equal with steps near one: the
    Neumann series' terms would grow to 1e30 over a row of 128; the
    substitution stays exact. Once on the matrix alone, and once through
    the kernel on a row whose keys and steps produce it."""
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    lower = np.tril(np.full((1, 128, 128), 0.99, np.float32), -1)
    got = solve(lower)
    want = np.linalg.inv(np.eye(128) + lower[0].astype(np.float64))
    assert np.abs(got[0] - want).max() < 1e-5
    # one key for every token, steps of 0.99, no decay: ``lower`` above.
    # The first row reads no state, so o = tril(q . k) T (beta v), with
    # q and k over their lengths, then the head's norm and the gate
    rng = np.random.default_rng(0)
    key = np.broadcast_to(3.0 * rng.normal(size=8), (1, 128, 8))
    q = rng.normal(size=(1, 128, 8))
    v = rng.normal(size=(1, 128, 8))
    z = rng.normal(size=(1, 128, 8))
    out = np.asarray(deltanet.gated_delta_rule(
        *(jnp.asarray(x, jnp.float32) for x in (
            np.concatenate([q, key], -1), v, np.zeros((1, 128, 1)),
            np.full((1, 128, 1), 0.99), z, np.ones(8))),
        jnp.asarray([True]), key_heads=1, eps=EPS, activation="silu",
        interpret=True))
    unit = key[0] / np.linalg.norm(key[0], axis=-1, keepdims=True)
    scaled = q[0] / np.linalg.norm(q[0], axis=-1, keepdims=True) * 8 ** -0.5
    scores = np.tril(scaled @ unit.T)
    want = scores @ want @ (0.99 * v[0])
    want = want / np.sqrt((want * want).mean(-1, keepdims=True) + EPS) \
        * z[0] / (1.0 + np.exp(-z[0]))
    assert np.abs(out[0] - want).max() < 1e-4 * np.abs(want).max()


def test_the_mixer_restarts_state_and_convolution_history(toy):
    """The DeltaNet mixer on a packed pool against each request alone:
    the convolution's history and the rule's state are zero at every
    request's first row, wherever it lies."""
    import jax.numpy as jnp

    from rnb_tpu.models.qwen3_next import network
    cfg, p = toy["cfg"], toy["params"]["l0"]
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(6, Q, 64)), jnp.bfloat16)
    first = jnp.asarray([True, False, False, True, True, False])
    packed = np.asarray(network.deltanet_mixer(cfg, p, h, first,
                                               interpret=True))
    for lo, hi in ((0, 3), (3, 4), (4, 6)):
        alone = np.asarray(network.deltanet_mixer(
            cfg, p, h[lo:hi], jnp.arange(hi - lo) == 0, interpret=True))
        assert np.abs(packed[lo:hi] - alone).max() \
            < 1e-5 * np.abs(alone).max()
    # and against the plain reference's mixer, token by token
    w = {t: toy["read"]("l0." + t) for t in reference.DELTANET}
    import jax
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.deltanet(
            TOY, w, h[:3].reshape(3 * Q, 64).astype(jnp.float32)))
    assert np.abs(packed[:3].reshape(want.shape) - want).max() \
        < 0.03 * want.std()


# -- the experts' shares ----------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-7 held here and 8-15 on the other chip: the two shares'
    routed parts plus the shared expert, which both chips compute alike,
    once, are the uncut reference's expert layer. In the reference, and
    in the program with the slots of each share."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.qwen3_next import checkpoint, network
    cfg, model, read = toy["cfg"], toy["reference"], toy["read"]
    rng = np.random.default_rng(7)
    hb = jnp.asarray(rng.normal(size=(3, Q, 64)), jnp.bfloat16)
    h = hb.reshape(3 * Q, 64).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, ids, _, _, shared = model.experts(read, 1, h, range(16))
        here = model.experts(read, 1, h, HELD)
        there = model.experts(read, 1, h, OTHER)
    whole, shared = np.asarray(whole), np.asarray(shared)
    summed = np.asarray(here[3]) + np.asarray(there[3]) + shared
    assert np.abs(summed - whole).max() < 1e-5 * np.abs(whole).max()
    assert np.abs(np.asarray(here[0]) + np.asarray(there[0]) - shared
                  - whole).max() < 1e-5 * np.abs(whole).max()
    # the tokens' choices are spread over both shares
    assert np.isin(np.asarray(ids), HELD).any() \
        and np.isin(np.asarray(ids), OTHER).any()
    # the program: each share's layer from its own stacks and slots
    ok = jnp.ones((3, Q), bool)
    outs = []
    for share in (HELD, OTHER):
        p = checkpoint.make_params(cfg, SEED, share, toy["device"],
                                   groups=["l1"])["l1"]
        out, chose, counts, _, _ = network.experts_ffn(
            cfg, p, hb, ok, network.held_slots(cfg, share), interpret=True)
        assert int(counts.sum()) == int(np.isin(np.asarray(chose),
                                                share).sum())
        outs.append(np.asarray(out).reshape(3 * Q, 64))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.experts(
            read, 1, h, range(16), forced=jnp.asarray(chose))[0])
    got = outs[0] + outs[1] - shared
    assert np.abs(got - want).max() < 0.03 * want.std()


# -- partial rotary ---------------------------------------------------------------


def test_partial_rotary_leaves_the_rest_of_a_head_untouched(toy):
    import jax.numpy as jnp

    from rnb_tpu.models.qwen3_next import network
    from rnb_tpu.ops import rope
    cfg = toy["cfg"]
    assert cfg.rotary_dim == 8 and len(cfg.inv_freq()) == 4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, Q, 2, 32)), jnp.float32)
    # rows 0-1 one request, row 2 the next, row 3 a pad row
    positions = rope.pool_positions(jnp.asarray([0, 0, 2, 3]), Q)
    out = np.asarray(network.rotate_front(cfg, x, positions))
    assert np.array_equal(out[..., 8:], np.asarray(x)[..., 8:])
    # position 0 turns nothing; any other turns the first columns
    assert np.allclose(out[0, 0], np.asarray(x)[0, 0], atol=1e-6)
    assert np.allclose(out[2, 0], np.asarray(x)[2, 0], atol=1e-6)
    assert np.abs(out[1, 5, :, :8] - np.asarray(x)[1, 5, :, :8]).max() > 0.1
    # the plain reference's rotary on the first request
    want = np.asarray(reference.rotary(TOY, x[:2].reshape(2 * Q, 2, 32)))
    assert np.abs(out[:2].reshape(want.shape) - want).max() < 1e-5
    # the real sizes: 64 of 256 columns, theta 1e7
    with open(os.path.join(REPO, REAL)) as f:
        real = network.Qwen3NextConfig.from_published(
            mm.load_family("qwen3_next").published_keys(json.load(f)))
    assert real.rotary_dim == 64
    assert np.allclose(real.inv_freq()[[0, -1]], [1.0, 1e7 ** (-62 / 64)])


# -- the recipe and the stages ------------------------------------------------------


def test_recipe_gives_program_and_reference_the_same_values(toy):
    from rnb_tpu.models.qwen3_next import checkpoint
    params, read = toy["params"], toy["read"]
    for name, tensor in (("l0.in_qkvz", params["l0"]["in_qkvz"]),
                         ("top.embed", params["embed"]),
                         ("l3.q", params["l3"]["q"]),
                         ("l2.a_log", params["l2"]["a_log"]),
                         ("l1.shared_w", params["l1"]["shared_w"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name)))
    # a routed expert's first matrices lie [held, inner, hidden]; the
    # reference reads them as published, by global id
    assert params["l0"]["gate"].shape == (8, 32, 64)
    assert np.array_equal(
        np.asarray(params["l0"]["gate"][3], np.float32).T,
        np.asarray(read("l0.gate", [3]))[0])
    assert np.asarray(read("l3.q_norm")).tolist() \
        == [checkpoint.QK_NORM] * 32
    assert np.abs(np.asarray(read("l0.mixer_norm"))).max() <= 0.1
    assert np.asarray(read("l0.o_norm")).tolist() == [1.0] * 16
    assert "q" not in params["l0"] and "in_qkvz" not in params["l3"]
    assert params["l0"]["in_qkvz"].shape == (64, 2 * 32 + 2 * 64)
    assert params["l3"]["q"].shape == (64, 4 * 2 * 32)


def the_stage_counts_experts_and_tiles(served):
    """``family_contract.stage_serves``'s entry for this family: the
    held experts' assignments and the flash kernel's tiles."""
    from rnb_tpu.telemetry import stage_counter_report
    counters, valid = served.stage.stage_counters(), served.valid
    assert counters["experts_per_token"] == 4
    assert counters["expert_served"].shape == (4, 8)
    assert 0 < counters["expert_served"].sum() < 4 * 4 * valid
    assert 0 < counters["group_tokens"] <= 4 * valid
    assert counters["attn_tiles"].tolist() == [1, 1]
    lines, _ = stage_counter_report([counters])
    assert lines[0] == "Tokens: valid=%d shipped=%d" % (valid, 8 * Q)
    assert lines[1].startswith("Experts: ")


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.qwen3_next import flops, network
    family = mm.load_family("qwen3_next")
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.Qwen3NextConfig.from_published(
        family.published_keys(config))
    assert flops.flops_per_token(cfg, 3000.0, 5.0) \
        == family.flops_per_token(config, 3000.0, 5.0)
    assert flops.deltanet_flops_per_token(cfg) \
        == family.deltanet_flops_per_token(config)
    assert flops.delta_rule_flops_per_token(cfg) == 7 * 32 * 128 * 128 \
        == family.delta_rule_flops_per_token(config)
    assert family.flops_per_row(config) == 128 * flops.flops_per_token(
        cfg, family.mean_context(config), 5.0)
    # ISSUE 39's arithmetic: some 0.54 GFLOP a token, of which the one
    # attention layer's scores some 90 MFLOP at the mix's mean context
    per_token = family.flops_per_row(config) / 128
    assert 0.45e9 < per_token < 0.62e9
    assert 70e6 < flops.attention_score_flops_per_token(
        cfg, family.mean_context(config)) < 110e6
    # both callers: scopes.py passes the held assignments, subscopes.py
    # does not
    ops, nbytes = family.mechanism_work(config, "deltarule", 1e6, 80.0)
    assert ops == 3 * 1e6 * 7 * 32 * 128 * 128
    assert nbytes == 3 * 1e6 * (2 * 8192 + 8 * 32 + 4 * 4096)
    assert family.mechanism_work(config, "deltanet", 1e6, 5e6, 80.0) \
        == family.mechanism_work(config, "deltanet", 1e6, 80.0)
    gmm_ops, _ = family.mechanism_work(config, "gmm", 1e6, 5e6, 80.0)
    assert gmm_ops == 5e6 * flops.expert_flops(cfg)
    flash_ops, _ = family.mechanism_work(config, "flash", 1e6, 5e6, 80.0)
    assert flash_ops == 1e6 * 4 * family.mean_context(config) * 4096
    experts_ops, experts_bytes = family.mechanism_work(
        config, "experts", 1e6, 5e6, 80.0)
    assert experts_ops > gmm_ops and experts_bytes > 80 * 4 * 2 * 805e6


# -- through the one benchmark command ------------------------------------------


def toy_config():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    config.update(TOY)
    config["experts_held"] = {"first": 0, "count": 8}
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 60, "sigma": 0.5,
                                   "min": 20, "max": 100},
                         "long": {"count": 2, "min": 100, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 80
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=3, samples=8)
    return config


#: ``tests/test_qwen3_next_cell.py`` runs it
CONTRACT = contract.Family(
    name="qwen3_next", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts:", "Attention:"),
    scopes=("/deltanet/rule/",),
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "held_assignment_pct.bulk": "(0, 100)",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "flash_tile_visit_pct.bulk": "(0, 100]"},
    not_from_a_cpu="roofline|util|deltarule|busy_pct",
    stage=contract.Stage(
        lengths=(80, 9, 30), row_buckets=(4, 8),
        scopes=("/deltanet/", "/deltanet/rule/", "/attn/", "/experts/",
                "/head/", "/embed/"),
        chosen_shape=(4, 80, 4), also=the_stage_counts_experts_and_tiles),
    # as stated inside the limit, the float8 arm outside it, the
    # bfloat16 states reported
    control=contract.Control(
        lengths="37,120,70", outside=("layers_float8",),
        reads={("state_bfloat16", "share_of_spread"): "[0, inf)"}))


# -- the four new readers -------------------------------------------------------------

NEW_READERS = ("deltanet_busy_pct.bulk", "deltanet_roofline_pct.bulk",
               "deltarule_roofline_pct.bulk", "deltarule_ms_per_dispatch.bulk")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_on_a_run_without_its_scope(
        name, tmp_path):
    """No trace, and a trace whose run wrote no scope table (the parent's
    program has none of these scopes): None, not a raise."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    # PR 49's cell, the rule under a vector gate, joined the list
    assert entry and entry[0]["workloads"] == [CELL, "kimi-linear.bulk"]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "gated delta rule"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("qwen3_next")
        config = {}
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    assert module.read(Facts) is None

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    from benchmarks import subscopes
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5}
    Facts.trace = Trace
    try:
        assert subscopes.seconds_under(Facts, "deltanet") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]": "jit(apply)/jit(main)/experts/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet") is None
        assert subscopes.seconds_under(Facts, "deltanet/rule") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]":
             "jit(apply)/jit(main)/deltanet/rule/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet") == 0.5
        assert subscopes.seconds_under(Facts, "deltanet/rule") == 0.5
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


# -- the real configuration -----------------------------------------------------


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Qwen3-Next-80B-A3B-Instruct":
                return row
    return None


#: the catalog's ``config`` of Qwen3-Next-80B-A3B-Instruct
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_real_configuration_keeps_the_published_sizes():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    entry = mm.config_entry(mm.load(), "qwen3-next-l4-ep2")
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "num_experts"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 4 and config["num_experts"] == 256
    row = catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
        assert row["config"] == PUBLISHED
    for key in ("chunk_size", "column_order", "norms", "rotary", "decay",
                "mtp", "weights", "precision", "prompts", "batch"):
        assert config["assumed"][key], key
    assert "two chips share each layer" in config["deployment"]
    assert config["size_record"]["projected_gib"] >= 4
    assert config["capacity_why"] and config["capacity_videos_per_chip_s"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    cell = mm.cell(mm.load(), CELL)
    assert cell["config"] == "qwen3-next-l4-ep2" and cell["chips"] == 1 \
        and cell["traffic"] == "bulk"
    # the weights the file states, from the tensor list: ISSUE 39's
    # 33.72 M and 27.26 M of mixers, 805.3 M of held experts a layer,
    # 622.3 M of embedding and head
    from rnb_tpu.models.qwen3_next import checkpoint, network
    cfg = network.Qwen3NextConfig.from_published(
        family.published_keys(config))
    specs = checkpoint.tensor_specs(cfg, 256)
    sizes = {group: sum(int(np.prod(spec.shape)) for spec in tensors.values())
             for group, tensors in specs.items()}
    assert abs(sizes["top"] / 1e6 - 622.3) < 0.1
    assert abs(sizes["l0"] / 1e6 - 843.2) < 0.1
    assert abs(sizes["l3"] / 1e6 - 836.8) < 0.1
    held = sum(sizes.values())
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 0.01
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01
    # the prompts the issue states, as minicpm-sala-l4 draws them
    with open(os.path.join(
            REPO, "benchmarks/configs/minicpm-sala-l4.json")) as f:
        assert json.load(f)["dataset"] == config["dataset"]
    lengths = family.prompt_lengths(config)
    assert min(lengths.values()) == 4096 and max(lengths.values()) <= 16384
    # a held expert's tokens a full dispatch
    assert 128 * 128 * config["num_experts_per_tok"] \
        // config["published"]["num_experts"] == 320


# -- what the kernel keeps off the chip's memory ---------------------------------


def test_the_rule_leaves_no_row_by_row_arrays_outside_the_kernel():
    """The lowered program of a toy stack (rows of 32 tokens, so that no
    other array has these shapes) holds no float32 array of ``(rows, Hv,
    Q, Q)`` - the decay, the scores' triangle, the solve - nor of
    ``(rows, Hk, per, Q, Dk)`` - ``w``, ``to_end`` - which the plain
    ``jnp`` rule wrote to the chip's memory one after another: inside
    the kernel they are a grid step's, of one row."""
    from rnb_tpu.models.qwen3_next import checkpoint, network
    cfg = network.Qwen3NextConfig.from_published(dict(TOY, chunk_size=32))
    rows, q = 8, cfg.chunk_size
    text = lowered_text(checkpoint, network, cfg, HELD, rows)
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    # the kernel's result, which does leave it, is in the text: the
    # output product's operand
    assert "tensor<%dx%dx%dxbf16>" % (
        rows, q, hv * cfg.linear_value_head_dim) in text
    for shape in ((rows, hv, q, q), (rows * hv, q, q),
                  (rows, hk, hv // hk, q, q),
                  (rows, hk, hv // hk, q, cfg.linear_key_head_dim)):
        assert "tensor<%sxf32>" % "x".join(map(str, shape)) not in text, \
            shape


def real_stack():
    """-> (the real configuration's ``cfg``, its held experts a layer)."""
    from rnb_tpu.models.qwen3_next import network
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    family = mm.load_family(config["family"])
    return network.Qwen3NextConfig.from_published(
        family.published_keys(config)), config["num_experts"]


def head_axis_arrays(text: str, heads, dim: int):
    """The float32 arrays of ``text`` (StableHLO) whose two minor axes
    are one of ``heads`` and ``dim``: a token's heads side by side as an
    axis of their own, which the device tiles (8, 128) with the axis in
    front - a relayout of what a product or a kernel wrote as (tokens,
    heads x dim)."""
    import re
    return sorted(set(re.findall(
        r"tensor<[\dx]*x(?:%s)x%dxf32>" % ("|".join(map(str, heads)), dim),
        text)))


def test_no_array_of_a_head_axis_between_convolution_and_output_product():
    """The real configuration's 128-row program, lowered (nothing is
    compiled or run; the kernels interpreted, their bodies a grid
    step's): from ``segment_conv1d``'s call to ``o``'s product the
    DeltaNet mixer reshapes nothing to (tokens, heads, 128) - the heads'
    L2 norms, the head norm and the gate are the rule's kernel's, on
    slices in VMEM - so the program holds no float32 array of 16 or of
    32 heads of 128 at all (the attention layer's heads are 256 wide).
    PR 49's tree held two of ``128x128x16x128`` (q and k through
    ``l2_norm``) and one of ``128x128x32x128`` (the rule's result
    through ``rms_norm``) a layer."""
    from rnb_tpu.models.qwen3_next import checkpoint, network
    cfg, held = real_stack()
    assert cfg.linear_key_head_dim == cfg.linear_value_head_dim == 128
    text = lowered_text(checkpoint, network, cfg, range(held), rows=128)
    tokens = "128x%d" % cfg.chunk_size
    # what the kernel reads and writes is there, as its neighbours wrote it
    assert "tensor<%sx%dxf32>" % (tokens, 2 * cfg.key_dim) in text
    assert "tensor<%sx%dxbf16>" % (tokens, cfg.value_dim) in text
    assert head_axis_arrays(
        text, (cfg.linear_num_key_heads, cfg.linear_num_value_heads),
        128) == []


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_at_the_published_widths(one_chip):
    """The rule of one layer over the largest row bucket, compiled for a
    described v5e (nothing runs): one custom call, and what the program
    holds beside its operands and result is the running sums and steps
    in the kernel's two orientations, not a ``Q x Q`` array a head."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    rows = max(config["pipeline_config"]["pipeline"][-1]["row_buckets"])
    q, hk, hv = config["chunk_size"], config["linear_num_key_heads"], \
        config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        deltanet.gated_delta_rule, key_heads=hk, eps=config["rms_norm_eps"],
        activation="silu")).lower(
        of((rows, q, 2 * hk * dk), jnp.float32),
        of((rows, q, hv * dv), jnp.bfloat16), of((rows, q, hv), jnp.float32),
        of((rows, q, hv), jnp.float32), of((rows, q, hv * dv), jnp.float32),
        of((dv,), jnp.bfloat16), of((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert deltanet.KERNEL_NAME in text
    assert "f32[%d,%d,%d,%d]" % (rows, hv, q, q) not in text
    # q and k reach the kernel as one array, the result leaves it rounded
    assert "bf16[%d,%d,%d]" % (rows, q, hv * dv) in text
    assert "bf16[%d,%d,%d]" % (rows, q, hk * dk) not in text
    # the call tells the compiler what it costs, so that the scheduler
    # overlaps its own copies with it (without, the stage program's
    # temporaries stood 62 MiB over PR 49's: the stream's prefetch in
    # front of ``o``'s product had nothing to hide behind)
    assert "\"cost_estimate\":{\"flops\":\"" in text


# -- the shared code, for the three older families -------------------------------------


def lowered_text(checkpoint, network, cfg, held, rows=8) -> str:
    """The StableHLO text of a family's ``forward`` over one dispatch of
    ``rows`` rows, its Pallas kernels interpreted; ``held``: the experts
    held here, None for a family without experts."""
    import jax
    import jax.numpy as jnp
    if held is None:
        specs, slots = checkpoint.tensor_specs(cfg), None
    else:
        specs = checkpoint.tensor_specs(cfg, len(held))
        slots = jax.ShapeDtypeStruct((cfg.router_experts,), jnp.int32)
    params = {}
    for group, tensors in specs.items():
        made = {name: jax.ShapeDtypeStruct(spec.shape,
                                           getattr(jnp, spec.dtype))
                for name, spec in tensors.items()}
        params.update(made if group == "top" else {group: made})
    return jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2], interpret=True)).lower(
        params, slots,
        jax.ShapeDtypeStruct((rows, cfg.chunk_size), jnp.int32),
        jax.ShapeDtypeStruct((3, rows), jnp.int32)).as_text()


def stack_text(family: str) -> str:
    """The StableHLO text of a toy stack's ``forward`` over one 8-row
    dispatch, from the family's own test module's toy sizes."""
    import importlib
    toys = importlib.import_module("test_" + family)
    checkpoint, network = (
        importlib.import_module("rnb_tpu.models.%s.%s" % (family, part))
        for part in ("checkpoint", "network"))
    cfg = getattr(network, [n for n in dir(network)
                            if n.endswith("Config")
                            and n != "SparseConfig"][0]) \
        .from_published(toys.TOY)
    return lowered_text(checkpoint, network, cfg,
                        getattr(toys, "HELD", None))


@pytest.mark.parametrize("family", ["nemotron_h", "deepseek_v2",
                                    "minicpm_sala", "falcon_h1"])
def test_the_older_families_lower_to_the_text_the_parent_gave(family):
    """PR 39 left ``ops/moe.py``, ``ops/segattn.py``, ``ops/rope.py`` and
    ``ops/ssd.py`` as they were: each older family's toy stack lowers to
    the StableHLO text PR 38's tree gave (its SHA-256, recorded from a
    ``git archive`` of that commit under ``tests/recorded``). A PR that
    moves one of them on purpose records the new text and shows those
    cells on the chip: PR 44 recorded the two expert families' again
    (``forward`` returns ``gmm_rows``, the rows the first grouped
    product multiplied); PR 47 recorded ``nemotron_h``'s and
    ``minicpm_sala``'s again (``ops/ssd.ssd_scan`` is one Pallas kernel,
    interpreted here; ``deepseek_v2``'s is the text PR 44 recorded); PR
    48 recorded ``nemotron_h``'s again (``ops/ssd.segment_conv1d`` is one
    Pallas kernel with the SiLU and the rounding inside, interpreted
    here; ``minicpm_sala``, which calls ``ssd_scan`` alone, and
    ``deepseek_v2`` lower to the texts they had); PR 60 recorded
    ``minicpm_sala``'s again (its lightning mixer hands ``ssd_scan`` q
    and k as their products wrote them and the gate: the head norms, the
    rotation, the output norm and the gate are the kernel's first and
    last lines, under a (row, step) grid) and holds both Mamba-2 callers
    of that kernel to the parent's program: ``nemotron_h``'s text is the
    one PR 48 recorded, and ``falcon_h1``'s, which had no entry, is
    recorded from a ``git archive`` of the parent ``461cc79``."""
    with open(os.path.join(REPO, "tests", "recorded",
                           "toy_stack_stablehlo.json")) as f:
        recorded = json.load(f)
    import jax
    if recorded["jax"] != jax.__version__:
        pytest.skip("recorded under jax %s" % recorded["jax"])
    assert hashlib.sha256(stack_text(family).encode()).hexdigest() \
        == recorded[family]
