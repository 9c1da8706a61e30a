"""The Kimi-Linear family against its plain reference, at a toy size on
the CPU with weights from a seed: the vector gate's kernel
(``ops/deltanet.channel_gated_delta_rule``, interpreted) with its first
and last lines against the plain composition - ``l2_norm``, the
token-by-token recurrence, ``rms_norm`` times the gate - over packed
pools at a mild and at a harsh draw of the decays, at toy heads and at
the family's 32 of 128, against the scalar rule under a gate that is the
same in every channel, its states through bfloat16; no float32 array of
a head axis in the real 128-row program; the shares of the experts adding
up to the uncut layer; latent attention that rotates nothing and reads
no other request's keys; the recipe, the operation counts, the real
configuration against the catalog's row, and the kernel compiled at the
published widths for a described v5e; the family's record for
``family_contract.py`` and the two new readers. The packed prefill is
``test_kimi_linear_stack.py``'s; the cell through the benchmark command,
the control script and the stage are ``test_kimi_linear_cell.py``'s (one
file is one worker's under ``--dist loadfile``).
Nothing here needs the native decode library or a chip."""

import functools
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
from benchmarks.references import kimi_linear as reference  # noqa: E402
from test_qwen3_next import first_token_alone, firsts_of  # noqa: E402

REAL = "benchmarks/configs/kimi-linear-l5-ep2.json"
CELL = "kimi-linear.bulk"
SEED = 3_000_000_123

#: the published layers 1-5 at toy widths: 4 KDA heads of 16 behind
#: 4-tap convolutions, latent attention of 4 heads (a latent of 32, keys
#: of 16 + 8 shared columns, values of 16), a dense MLP of 128, 16
#: sigmoid-routed experts top-4 of which 8 held
TOY = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "hidden_size": 64, "vocab_size": 256, "chunk_size": 16,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
        "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
    "mla_use_nope": True, "rope_theta": 10000, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "num_experts": 8, "num_experts_per_token": 4,
    "routed_scaling_factor": 2.446, "moe_layer_freq": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "num_expert_group": 1, "topk_group": 1, "hidden_act": "silu",
    "rms_norm_eps": 1e-5,
    "published": {"num_hidden_layers": 27, "num_experts": 16}}
HELD = tuple(range(8))
OTHER = tuple(range(8, 16))
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths: narrow sums average less
#: rounding away than the real ones (the real limit is the family
#: file's SHARE_OF_SPREAD); the toy reads 3.4% and its float8 control
#: 18%
TOY_LIMIT = 0.05


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.kimi_linear import checkpoint, network
    cfg = network.KimiLinearConfig.from_published(TOY)
    device = jax.devices()[0]
    return {"cfg": cfg, "device": device,
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


_PROGRAMS = {}


def run_program(toy, prompts, rows, params=None, cfg=None, **kwargs):
    """-> (logits a prompt, each prompt's router choices (expert layers,
    tokens, k), the counters)."""
    import jax

    from rnb_tpu.models.kimi_linear import network
    cfg = toy["cfg"] if cfg is None else cfg
    tokens, meta, offsets = pack(prompts, rows)
    key = (cfg, rows, tuple(sorted((k, str(v)) for k, v in kwargs.items())))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(
            lambda p, t, m: network.forward(
                cfg, p, toy["slots"], t, m[0], m[1], m[2],
                interpret=True, **kwargs))
    logits, chosen, *counts = _PROGRAMS[key](
        toy["params"] if params is None else params, tokens, meta)
    chosen = np.asarray(chosen)
    per_prompt = [chosen[:, o * Q:o * Q + len(p)]
                  for o, p in zip(offsets, prompts)]
    return np.asarray(logits)[:len(prompts)], per_prompt, \
        [np.asarray(c) for c in counts]


#: the reference runs every prompt padded to this many tokens behind its
#: last (every mixer is causal), so that it compiles one length
REF_LENGTH = 256


def run_reference(toy, prompt, forced=None, model=None, read=None):
    """-> the reference's logits at the prompt's last token, its choices
    and shortfalls over the prompt's own tokens."""
    import jax
    count, pad = len(prompt), REF_LENGTH - len(prompt)
    if forced is not None:
        layers, _, k = forced.shape
        forced = np.concatenate([forced, np.broadcast_to(
            np.arange(k, dtype=forced.dtype), (layers, pad, k))], axis=1)
    with jax.default_matmul_precision("highest"):
        out = (model or toy["reference"]).forward(
            read or toy["read"], np.pad(prompt, (0, pad)), held=HELD,
            forced=forced, position=count - 1)
    return {"logits": out["logits"], "chosen": out["chosen"][:, :count],
            "shortfall": out["shortfall"][:, :count]}


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


# -- the vector gate's kernel alone ---------------------------------------


EPS = 1e-5


def rule_inputs(rows, qlen, heads=2, dk=16, dv=16, harsh=False, seed=1,
                act=None):
    """The kernel's operands as the mixer hands them over: ``qk`` as a
    convolution wrote it (float32, every head's q then every head's k, a
    token's length anything from a tenth to ten), ``v`` (in ``act``,
    else float32), ``log alpha`` a channel, ``beta``, the output gate
    before its sigmoid and the head norm's weight. ``mild``: a
    channel's ``log alpha`` is -0.0003 to -0.03 a token, so that it
    fades over hundreds to thousands of tokens; ``harsh``: down to -25 a
    token, a head's channels two orders apart, so that ``exp(-g)`` alone
    would overflow float32 inside a row of 8 already."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    qk = n(rows, qlen, 2 * heads, dk) \
        * jnp.exp(jnp.log(10.0) * jnp.asarray(
            rng.uniform(-1, 1, (rows, qlen, 2 * heads, 1)), jnp.float32))
    rate = jnp.asarray(rng.uniform(0.01, 1.0, size=(heads, dk)),
                       jnp.float32)
    token = jnp.asarray(rng.uniform(0.03, 1.0, size=(rows, qlen, heads, dk)),
                        jnp.float32)
    log_alpha = -(25.0 if harsh else 0.03) * rate * token
    return (qk.reshape(rows, qlen, 2 * heads * dk),
            n(rows, qlen, heads * dv).astype(act or jnp.float32),
            log_alpha.reshape(rows, qlen, heads * dk),
            jax.nn.sigmoid(n(rows, qlen, heads)), n(rows, qlen, heads * dv),
            1.0 + 0.1 * n(dv))


def rule(inputs, row_first, activation="sigmoid", **how):
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    return np.asarray(deltanet.channel_gated_delta_rule(
        *inputs, jnp.asarray(row_first), eps=EPS, activation=activation,
        interpret=True, **how).astype(jnp.float32))


def composed(inputs, lo, hi):
    """What the kernel replaces, the plain way, over rows ``lo`` to
    ``hi`` as a request of its own: ``l2_norm`` a head and q's scale,
    one rounding to the activations' dtype, the reference's recurrence
    token by token, the head's ``rms_norm``, times ``sigmoid(z)``, one
    rounding. -> (L, H Dv) float32."""
    import jax
    import jax.numpy as jnp
    qk, v, log_alpha, beta, z, weight = (
        x[lo:hi].reshape((-1,) + x.shape[2:]) if x.ndim > 1 else x
        for x in inputs)
    act, heads = v.dtype, beta.shape[-1]
    length, dk = qk.shape[0], qk.shape[1] // (2 * heads)
    qk = qk.reshape(length, 2, heads, dk)
    q = (reference.l2_norm(qk[:, 0]) * dk ** -0.5).astype(act)
    k = reference.l2_norm(qk[:, 1]).astype(act)
    with jax.default_matmul_precision("highest"):
        out = reference.delta_rule(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.reshape(length, heads, -1).astype(jnp.float32),
            jnp.exp(log_alpha.reshape(length, heads, dk)), beta)
    out = reference.rms_norm(out, weight, EPS).reshape(z.shape) \
        * jax.nn.sigmoid(z)
    return np.asarray(out.astype(act).astype(jnp.float32))


#: (tokens a row, rows, the rows that open a request, heads, harsh): a
#: request that starts mid-pool, spans several rows and ends in pad rows
#: (requests of their own); every row a request; one request the whole
#: pool; rows of 8 (pairs on the vector unit alone), of 16 to 64 (one
#: to three levels through the matrix unit) and of 128 (four levels,
#: the solve's merges); four heads are two head groups
RULE_CASES = [
    (16, 6, (0, 2, 5), 2, False), (16, 6, (0, 2, 5), 2, True),
    (8, 6, (0, 3, 4), 2, True), (32, 6, (0, 1, 2, 3, 4, 5), 4, True),
    (64, 6, (0,), 2, False), (64, 6, (0, 2), 4, True),
    (128, 3, (0, 1), 2, False), (128, 3, (0, 2), 2, True)]


@pytest.mark.parametrize("qlen,rows,firsts,heads,harsh", RULE_CASES)
def test_the_kernel_matches_the_recurrence(qlen, rows, firsts, heads, harsh):
    """``channel_gated_delta_rule`` (interpreted) over a packed pool
    against the plain composition around the recurrence token by token,
    a request at a time: finite everywhere and within 2e-5 of the
    result's largest entry, float32 operands. At the harsh draw a
    channel's running sum passes -10 a token: under -160 inside a row
    of 16 and -1,280 inside one of 128, where float32 ends at
    exp(88)."""
    inputs = rule_inputs(rows, qlen, heads, harsh=harsh)
    out = rule(inputs, firsts_of(rows, firsts))
    assert np.isfinite(out).all()
    if harsh:
        assert float(np.asarray(inputs[2]).sum(1).min()) < -10.0 * qlen
    bounds = list(firsts) + [rows]
    for lo, hi in zip(bounds, bounds[1:]):
        want = composed(inputs, lo, hi)
        got = out[lo:hi].reshape(want.shape)
        assert np.abs(got - want).max() < 2e-5 * max(
            1.0, np.abs(want).max())
    # a state that did not restart would show at the second request's
    # first token
    lo = firsts[1] if len(firsts) > 1 else 0
    alone = first_token_alone(inputs, lo, heads, EPS,
                              lambda z: 1.0 / (1.0 + np.exp(-z)))
    assert np.abs(out[lo, 0].reshape(alone.shape) - alone).max() < 2e-5


#: (heads, the activations' dtype, the limit as a share of the largest
#: entry): Kimi-Linear's head geometry - 32 heads of 128, each with its
#: own q and k, sixteen head groups - in float32 and in the program's
#: bfloat16, where kernel and composition round at the same two places:
#: the levels' products then take ``k exp(.)`` rounded once more (below)
GEOMETRY_CASES = [(32, "float32", 2e-5), (32, "bfloat16", 0.02)]


@pytest.mark.parametrize("heads,act,limit", GEOMETRY_CASES)
def test_the_kernels_first_and_last_lines_are_the_mixers_norms(
        heads, act, limit):
    """The kernel with its prologue and epilogue against ``l2_norm`` ->
    the sequential rule -> ``rms_norm`` x ``sigmoid(z)``, at the
    family's head count and head size, over a pool of six rows that
    holds a request of one row, a request of three rows whose first
    lies mid-pool, a pad row (a request of its own) and a request's
    first row at the pool's end."""
    import jax.numpy as jnp
    firsts = (0, 1, 4, 5)
    inputs = rule_inputs(6, 16, heads, dk=128, dv=128, act=jnp.dtype(act))
    # a pad row: the tokens the packer left empty read as zeros
    inputs = tuple(x.at[4].set(0) if x.ndim == 3 else x for x in inputs)
    out = rule(inputs, firsts_of(6, firsts))
    assert np.isfinite(out).all()
    for lo, hi in zip(firsts, firsts[1:] + (6,)):
        want = composed(inputs, lo, hi)
        got = out[lo:hi].reshape(want.shape)
        assert np.abs(got - want).max() < limit * max(
            1.0, np.abs(want).max()), (lo, hi)


@pytest.mark.parametrize("harsh", [False, True])
def test_bfloat16_operands_stay_inside_their_rounding(harsh):
    """What the program hands the kernel: ``v`` in bfloat16, and ``q``
    and ``k`` rounded to it behind their norms. The levels' products
    then take ``k exp(.)`` rounded to bfloat16; against the composition
    that rounds at the kernel's two places the result stays within 2% of
    its spread and the one step of bfloat16 (2^-7 of the entry) that the
    last rounding may fall to the other side by."""
    import jax.numpy as jnp
    inputs = rule_inputs(4, 64, 2, harsh=harsh, act=jnp.bfloat16)
    out = rule(inputs, [True, False, False, True])
    want = np.concatenate([composed(inputs, 0, 3), composed(inputs, 3, 4)])
    assert np.isfinite(out).all()
    assert (np.abs(out.reshape(want.shape) - want)
            < 0.02 * want.std() + 2.0 ** -7 * np.abs(want)).all()


@pytest.mark.parametrize("qlen", [16, 128])
def test_a_gate_alike_in_every_channel_is_the_scalar_rule(qlen):
    """The two rules of ``ops/deltanet.py`` on the same operands: with a
    head's ``log alpha`` the same in all its channels the vector gate's
    kernel gives what ``gated_delta_rule`` gives (one value head a key
    head), the first and last lines alike under either activation."""
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    rows, heads, dk = 3, 2, 16
    qk, v, log_alpha, beta, z, weight = rule_inputs(rows, qlen, heads, dk)
    one = log_alpha.reshape(rows, qlen, heads, dk)[..., :1] * 10.0
    alike = jnp.broadcast_to(one, (rows, qlen, heads, dk)) \
        .reshape(log_alpha.shape)
    row_first = jnp.asarray([True, False, True])
    for activation in ("sigmoid", "silu"):
        vector = rule((qk, v, alike, beta, z, weight), row_first, activation)
        scalar = np.asarray(deltanet.gated_delta_rule(
            qk, v, one[..., 0], beta, z, weight, row_first, key_heads=heads,
            eps=EPS, activation=activation, interpret=True))
        assert np.abs(vector - scalar).max() < 2e-5 * max(
            1.0, np.abs(scalar).max())
    # and another gate in one channel is another result
    other = rule((qk, v, alike.at[..., 0].multiply(3.0), beta, z, weight),
                 row_first, "silu")
    assert np.abs(other - scalar).max() > 1e-3 * np.abs(scalar).max()


@pytest.mark.parametrize("qlen,harsh", [(16, False), (16, True),
                                        (128, False), (128, True)])
def test_the_scores_are_the_sum_pair_by_pair(qlen, harsh):
    """``deltanet.channel_scores`` - the levels over their own rows, the
    offsets inside blocks of 8 - against ``sum_d x_id k_jd exp(g_id -
    g_jd)`` taken pair by pair in float64: every value finite, zero
    where no pair is (``kk`` on and over the diagonal, ``qk`` over it).
    Rows of 16: one level; of 128: four."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    qk, _, log_alpha, _, _, _ = rule_inputs(1, qlen, 1, harsh=harsh)
    dk = log_alpha.shape[-1]
    unit = np.asarray(qk[0], np.float64).reshape(qlen, 2, dk)
    unit = unit / np.linalg.norm(unit, axis=-1, keepdims=True)
    q, k = unit[:, 0] * dk ** -0.5, unit[:, 1]
    g = np.cumsum(np.asarray(log_alpha[0], np.float64), axis=0)
    if harsh:
        assert g.min() < -10.0 * qlen
    kk, qk = (np.asarray(x) for x in jax.jit(
        deltanet.channel_scores, static_argnums=3)(
        *(jnp.asarray(x, jnp.float32) for x in (q, k, g)), jnp.float32))
    assert np.isfinite(kk).all() and np.isfinite(qk).all()
    later = np.tril(np.ones((qlen, qlen), bool))
    # exp of a difference under -745 is zero in float64 too: no overflow
    decay = np.exp(np.where(later[:, :, None], g[:, None] - g[None], -np.inf))
    for got, x, pairs in ((kk, k, np.tril(later, -1)), (qk, q, later)):
        want = np.where(pairs, np.einsum("id,jd,ijd->ij", x, k, decay), 0.0)
        assert np.array_equal(got[~pairs], np.zeros_like(got[~pairs]))
        assert np.abs(got - want).max() < (5e-5 if harsh else 1e-6)


def matrix_unit_work(jaxpr) -> int:
    """The multiply-adds of every ``dot_general`` of a jaxpr and of the
    jaxprs inside it (a kernel's body under ``pallas_call``, a
    ``pl.when``'s branches)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract_a, _), _ = eqn.params["dimension_numbers"]
            a, b = (v.aval for v in eqn.invars)
            # a pass of the matrix unit: one part by one part
            assert a.dtype == b.dtype == np.dtype("bfloat16"), eqn
            total += int(np.prod(a.shape)) * int(np.prod(b.shape)) \
                // int(np.prod([a.shape[i] for i in contract_a]))
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += matrix_unit_work(inner)
    return total


#: (the vector gate, the activations' dtype, value heads a key head,
#: the most passes of 128^3 a value head and row): ISSUE 58's counts -
#: 74 and 61 before it - and with float32 operands (these tests') six
#: passes a product, none dropped
PASS_CASES = [(True, "bfloat16", 1, 52), (False, "bfloat16", 2, 40),
              (True, "float32", 1, None), (False, "float32", 2, None)]


@pytest.mark.parametrize("channel,act,per,most", PASS_CASES)
def test_the_matrix_units_passes_are_counted_from_the_kernels(
        channel, act, per, most):
    """``deltanet._passes``, which ``_cost`` reads, at the cells' sizes
    (Q = Dk = Dv = 128): no more passes than the issue allows with
    bfloat16 activations, six for every product of two float32
    operands - and the same work as the ``dot_general``s of the
    kernel's own traced body, so that the table cannot leave it."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    size = 128
    table = deltanet._passes(size, size, size, jnp.dtype(act),
                             channel=channel, per=per)
    counted = sum(work * passes for work, passes in table.values())
    if most is None:
        assert {passes for _, passes in table.values()} == {6}
    else:
        assert counted <= most * size ** 3
        assert table["solve"] == (2 * size ** 3, 6)     # two levels' halves
    heads = 2
    rows, f32 = 1, jnp.float32
    operands = (
        jnp.zeros((rows, size, 2 * heads * size), f32),
        jnp.zeros((rows, size, heads * per * size), jnp.dtype(act)),
        jnp.zeros((rows, size, heads * (size if channel else per)), f32),
        jnp.zeros((rows, size, heads * per), f32),
        jnp.zeros((rows, size, heads * per * size), f32),
        jnp.ones((size,), f32), jnp.ones((rows,), bool))
    call = functools.partial(
        deltanet.channel_gated_delta_rule, eps=EPS, activation="sigmoid") \
        if channel else functools.partial(
            deltanet.gated_delta_rule, key_heads=heads, eps=EPS,
            activation="silu")
    traced = matrix_unit_work(jax.make_jaxpr(call)(*operands).jaxpr)
    assert traced == counted * heads * per


def test_bfloat16_states_differ_by_one_rounding_a_row():
    """The control's ``state_dtype``: a request's first row reads no
    carried state, so it is the float32 rule's bit for bit; every later
    row reads a state rounded once more, and differs by no more than
    its roundings allow."""
    import jax.numpy as jnp
    inputs = rule_inputs(6, 16)

    def states(firsts, **how):
        return rule(inputs, firsts_of(6, firsts), **how)
    exact, rounded = states((0, 4)), states((0, 4), state_dtype=jnp.bfloat16)
    scale = np.abs(exact).max()
    for row in (0, 4):
        assert np.array_equal(rounded[row], exact[row])
    for row, roundings in ((1, 1), (2, 2), (3, 3), (5, 1)):
        off = np.abs(rounded[row] - exact[row]).max()
        assert 0 < off < roundings * 2.0 ** -7 * scale, (row, off)


# -- latent attention without positions -----------------------------------


def attention_only():
    """A stack of two latent-attention layers (the first with the dense
    feed-forward, the second with the experts) and no KDA layer:
    whatever carries position is gone."""
    return dict(TOY, num_hidden_layers=2, linear_attn_config=dict(
        TOY["linear_attn_config"], kda_layers=[], full_attn_layers=[1, 2]))


def test_latent_attention_rotates_no_column(toy):
    """Two requests of equal tokens at different offsets in the pool
    give equal logits through an attention-only stack, and the layer
    knows no position: with a request's earlier tokens in another order
    its last token reads the same set of keys and values and gives the
    same result. With rotary turned on (the control arm) it does not."""
    import jax.numpy as jnp

    from rnb_tpu.models.kimi_linear import checkpoint, network
    from rnb_tpu.ops import rope
    flat = attention_only()
    cfg = network.KimiLinearConfig.from_published(flat)
    params = checkpoint.make_params(cfg, SEED, HELD, toy["device"])
    a, b = prompts_of([70, 5], seed=9)
    logits, _, _ = run_program(toy, [b, a, a], 16, params=params, cfg=cfg)
    assert np.array_equal(logits[1], logits[2])
    want = np.asarray(run_reference(
        toy, a, model=reference.Reference(flat),
        read=checkpoint.reference_reader(cfg, SEED, toy["device"]))["logits"])
    assert compare(logits[1][None], want[None], TOY_LIMIT)["ok"]
    # the layer alone: a request of three rows, and the same with the
    # tokens before its last in reverse order
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(3 * Q, 64)), jnp.bfloat16)
    turned = jnp.concatenate([h[:-1][::-1], h[-1:]])
    start = jnp.zeros(3, jnp.int32)
    at = rope.pool_positions(start, Q)

    def last(x, **how):
        out, _ = network.latent_attention(
            toy["cfg"], toy["params"]["l3"], x.reshape(3, Q, 64), start, at,
            interpret=True, **how)
        return np.asarray(out)[-1, -1]
    plain = last(h)
    assert np.abs(last(turned) - plain).max() < 0.02 * np.abs(plain).max()
    rotated = last(h, rotary=True)
    assert np.abs(last(turned, rotary=True) - rotated).max() \
        > 0.2 * np.abs(rotated).max()


def test_a_request_reads_no_other_requests_keys(toy):
    """The latent attention over a packed pool against each request
    alone: other requests' tokens, before or behind, change nothing."""
    import jax.numpy as jnp

    from rnb_tpu.models.kimi_linear import network
    from rnb_tpu.ops import rope
    cfg, p = toy["cfg"], toy["params"]["l3"]
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(8, Q, 64)), jnp.bfloat16)
    start = jnp.asarray([0, 0, 0, 3, 4, 4, 6, 7], jnp.int32)
    packed, tiles = network.latent_attention(
        cfg, p, h, start, rope.pool_positions(start, Q), interpret=True)
    packed = np.asarray(packed)
    for lo, hi in ((0, 3), (3, 4), (4, 6)):
        own = jnp.zeros(hi - lo, jnp.int32)
        alone, _ = network.latent_attention(
            cfg, p, h[lo:hi], own, rope.pool_positions(own, Q),
            interpret=True)
        assert np.abs(packed[lo:hi] - np.asarray(alone)).max() \
            < 1e-2 * np.abs(packed[lo:hi]).max()
    assert int(tiles[0]) >= 1


# -- the experts' shares --------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-7 held here and 8-15 on the other chip: the two shares'
    routed parts plus the shared expert, which both chips compute alike,
    once, are the uncut reference's expert layer. In the reference, and
    in the program with the slots of each share."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.kimi_linear import checkpoint, network
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


# -- the recipe, the counts, the real configuration -----------------------


def test_recipe_gives_program_and_reference_the_same_values(toy):
    params, read = toy["params"], toy["read"]
    for name, tensor in (("l0.in_qkv", params["l0"]["in_qkv"]),
                         ("top.embed", params["embed"]),
                         ("l3.kv_b", params["l3"]["kv_b"]),
                         ("l2.a_log", params["l2"]["a_log"]),
                         ("l2.dt_bias", params["l2"]["dt_bias"]),
                         ("l1.b_corr", params["l1"]["b_corr"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name)))
    # a routed expert's first matrices lie [held, inner, hidden]; the
    # reference reads them as published, by global id
    assert params["l1"]["gate"].shape == (8, 32, 64)
    assert np.array_equal(
        np.asarray(params["l1"]["gate"][3], np.float32).T,
        np.asarray(read("l1.gate", [3]))[0])
    # the dense layer's are plain matrices
    assert params["l0"]["gate"].shape == (64, 128)
    # the queries' weight lies heads first with zeros behind a head's
    # 24 columns, up to whole lanes
    stored = np.asarray(params["l3"]["q"], np.float32)
    assert stored.shape == (4, 64, 128) and not stored[..., 24:].any()
    assert np.array_equal(
        stored[..., :24].transpose(1, 0, 2).reshape(64, 96),
        np.asarray(read("l3.q")))
    # A_log a head, dt_bias a channel
    assert params["l0"]["a_log"].shape == (4,)
    assert params["l0"]["dt_bias"].shape == (64,)
    assert "q" not in params["l0"] and "in_qkv" not in params["l3"]
    assert "router" not in params["l0"] and "b_corr" in params["l4"]


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.kimi_linear import checkpoint, flops, network
    family = mm.load_family("kimi_linear")
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.KimiLinearConfig.from_published(
        family.published_keys(config))
    assert family.LOW_RANK == checkpoint.LOW_RANK
    assert flops.flops_per_token(cfg, 3000.0, 4.0) \
        == family.flops_per_token(config, 3000.0, 4.0)
    assert flops.kda_flops_per_token(cfg) \
        == family.kda_flops_per_token(config)
    assert flops.delta_rule_flops_per_token(cfg) == 7 * 32 * 128 * 128 \
        == family.delta_rule_flops_per_token(config)
    assert family.flops_per_row(config) == 128 * flops.flops_per_token(
        cfg, family.mean_context(config), 4.0)
    # ISSUE 49's arithmetic: 0.79 GFLOP a token of products and about
    # 0.1 of attention's scores at the mix's mean context; a KDA mixer
    # 39.52 M parameters, an MLA mixer 29.11 M
    per_token = family.flops_per_row(config) / 128
    assert 0.85e9 < per_token < 1.0e9
    assert abs(family.kda_params(config) / 1e6 - 39.52) < 0.1
    assert abs(family.attention_params(config) / 1e6 - 29.11) < 0.1
    # both callers: scopes.py passes the held assignments, subscopes.py
    # does not
    ops, nbytes = family.mechanism_work(config, "deltarule", 1e6, 80.0)
    assert ops == 4 * 1e6 * 7 * 32 * 128 * 128
    assert nbytes == 4 * 1e6 * (2 * 3 * 4096 + 4 * 4096 + 4 * 32 + 4 * 4096)
    assert family.mechanism_work(config, "deltanet", 1e6, 5e6, 80.0) \
        == family.mechanism_work(config, "deltanet", 1e6, 80.0)
    gmm_ops, _ = family.mechanism_work(config, "gmm", 1e6, 5e6, 80.0)
    assert gmm_ops == 5e6 * flops.expert_flops(cfg)
    flash_ops, _ = family.mechanism_work(config, "flash", 1e6, 5e6, 80.0)
    assert flash_ops == 1e6 * 2 * family.mean_context(config) * 32 * 320
    experts_ops, experts_bytes = family.mechanism_work(
        config, "experts", 1e6, 5e6, 80.0)
    assert experts_ops > gmm_ops and experts_bytes > 80 * 4 * 2 * 900e6


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Kimi-Linear-48B-A3B-Instruct":
                return row
    return None


#: the catalog's ``config`` of Kimi-Linear-48B-A3B-Instruct
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_real_configuration_keeps_the_published_sizes():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    entry = mm.config_entry(mm.load(), "kimi-linear-l5-ep2")
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "linear_attn_config", "num_experts"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 5 and config["num_experts"] == 128
    # the group that changed: the two lists cut, its widths as published
    linear = config["linear_attn_config"]
    assert linear["kda_layers"] == [1, 2, 3, 5] \
        and linear["full_attn_layers"] == [4]
    for key in ("num_heads", "head_dim", "short_conv_kernel_size"):
        assert linear[key] == PUBLISHED["linear_attn_config"][key]
    row = catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
        assert row["config"] == PUBLISHED
    for key, text in config["assumed"].items():
        assert text, key
    for key in ("low_rank", "gate", "convolutions", "norms", "mla",
                "router", "chunk_size"):
        assert "NOT CHECKED against the modelling code" \
            in config["assumed"][key], key
    assert "two chips share each layer" in config["deployment"]
    assert config["experts_held"] == {"first": 0, "count": 128}
    assert 8 <= config["size_record"]["projected_gib"] <= 14
    assert config["capacity_why"] and config["capacity_videos_per_chip_s"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    cell = mm.cell(mm.load(), CELL)
    assert cell["config"] == "kimi-linear-l5-ep2" and cell["chips"] == 1 \
        and cell["traffic"] == "bulk"
    # the weights the file states, from the tensor list: ISSUE 49's
    # 39.52 M and 29.11 M of mixers (the queries' stored pad columns
    # 4.7 M more), 63.70 M of dense MLP, 7.078 M an expert, 754.97 M of
    # embedding and head
    from rnb_tpu.models.kimi_linear import checkpoint, network
    cfg = network.KimiLinearConfig.from_published(
        family.published_keys(config))
    specs = checkpoint.tensor_specs(cfg, 128)
    sizes = {group: sum(int(np.prod(spec.shape)) for spec in tensors.values())
             for group, tensors in specs.items()}
    assert abs(sizes["top"] / 1e6 - 754.98) < 0.1
    assert abs(sizes["l0"] / 1e6 - (39.52 + 63.70)) < 0.1
    assert abs(sizes["l3"] / 1e6 - (29.11 + 4.72 + 129 * 7.078 + 0.59)) < 0.2
    held = sum(sizes.values())
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 0.01
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.02
    # qwen3-next-l4-ep2's dataset block to the letter
    with open(os.path.join(
            REPO, "benchmarks/configs/qwen3-next-l4-ep2.json")) as f:
        sibling = json.load(f)
    assert sibling["dataset"] == config["dataset"]
    assert sibling["runtime_env"] == config["runtime_env"]
    lengths = family.prompt_lengths(config)
    assert min(lengths.values()) == 4096 and max(lengths.values()) <= 16384
    # a held expert's tokens a full dispatch
    assert 128 * 128 * config["num_experts_per_token"] \
        // config["published"]["num_experts"] == 512


# -- the control arms ---------------------------------------------------


def toy_config():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    lists = config["published"]["linear_attn_config"]
    config.update(TOY)
    config["published"] = dict(
        TOY["published"], linear_attn_config=dict(
            lists, **{k: TOY["linear_attn_config"][k]
                      for k in ("num_heads", "head_dim")}))
    config["experts_held"] = {"first": 0, "count": 8}
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 60, "sigma": 0.5,
                                   "min": 20, "max": 100},
                         "long": {"count": 2, "min": 100, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 42
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=3, samples=8)
    return config


def the_stage_counts_experts_and_tiles(served):
    """``family_contract.stage_serves``'s entry for this family: the
    held experts' assignments and the flash kernel's tiles."""
    from rnb_tpu.telemetry import stage_counter_report
    counters, valid = served.stage.stage_counters(), served.valid
    assert counters["experts_per_token"] == 4
    # four expert layers behind the dense one, one attention layer
    assert counters["expert_served"].shape == (4, 8)
    assert 0 < counters["expert_served"].sum() < 4 * 4 * valid
    assert 0 < counters["group_tokens"] <= 4 * valid
    assert counters["attn_tiles"].tolist() == [1, 1]
    lines, _ = stage_counter_report([counters])
    assert lines[0] == "Tokens: valid=%d shipped=%d" % (valid, 8 * Q)
    assert lines[1].startswith("Experts: ")


#: ``tests/test_kimi_linear_cell.py`` runs it
CONTRACT = contract.Family(
    name="kimi_linear", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts:", "Attention:"),
    scopes=("/deltanet/rule/", "/deltanet/gate/", "/deltanet/conv/"),
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "held_assignment_pct.bulk": "(0, 100)",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "flash_tile_visit_pct.bulk": "(0, 100]",
        "gmm_row_fill_pct.bulk": "(0, 100]"},
    not_from_a_cpu="roofline|util|deltarule|busy_pct|^kda_",
    stage=contract.Stage(
        lengths=(80, 9, 30), row_buckets=(8,),
        scopes=("/deltanet/", "/deltanet/conv/", "/deltanet/gate/",
                "/deltanet/rule/", "/attn/", "/experts/", "/head/",
                "/embed/"),
        chosen_shape=(4, 80, 4), also=the_stage_counts_experts_and_tiles),
    # as stated inside the limit; the float8, the scalar-gate and the
    # rotary arm outside it; the bfloat16 states reported, and free to
    # pass
    control=contract.Control(
        lengths="120,120",
        outside=("layers_float8", "scalar_gate", "rotary_on"),
        reads={("state_bfloat16", "share_of_spread"): "[0, 0.2)"},
        may_pass=("state_bfloat16",)))


# -- the kernel for the chip ----------------------------------------------


def test_no_array_of_a_head_axis_between_convolution_and_output_product():
    """The real configuration's 128-row program, lowered (nothing is
    compiled or run; the kernels interpreted, their bodies a grid
    step's): from ``segment_conv1d``'s call to ``o``'s product the KDA
    mixer reshapes nothing to (tokens, heads, 128) - the heads' L2
    norms, the head norm and the gate are the rule's kernel's, on slices
    in VMEM, and ``log alpha`` is a head's rate repeated over its
    channels - so the program holds no float32 array of 32 heads of 128
    at all (latent attention's operands lie heads first). PR 49's tree
    held four of ``128x128x32x128`` a KDA layer: q, k, the gate's steps
    and the rule's result."""
    from test_qwen3_next import head_axis_arrays, lowered_text

    from rnb_tpu.models.kimi_linear import checkpoint, network
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.KimiLinearConfig.from_published(
        mm.load_family(config["family"]).published_keys(config))
    assert cfg.kda_head_dim == 128
    text = lowered_text(checkpoint, network, cfg,
                        range(config["num_experts"]), rows=128)
    tokens = "128x%d" % cfg.chunk_size
    # what the kernel reads and writes is there, as its neighbours wrote it
    assert "tensor<%sx%dxf32>" % (tokens, 2 * cfg.kda_dim) in text
    assert "tensor<%sx%dxbf16>" % (tokens, cfg.kda_dim) in text
    assert head_axis_arrays(text, (cfg.kda_num_heads,), 128) == []


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
    described v5e (nothing runs): one custom call under the kernel's
    name, and no running sums, no ``Q x Q`` and no second ``Q x Dk``
    array a head in the chip's memory beside the operands."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import deltanet
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    rows = max(config["pipeline_config"]["pipeline"][-1]["row_buckets"])
    q = config["chunk_size"]
    heads = config["linear_attn_config"]["num_heads"]
    dim = config["linear_attn_config"]["head_dim"]

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        deltanet.channel_gated_delta_rule, eps=config["rms_norm_eps"],
        activation="sigmoid")).lower(
        of((rows, q, 2 * heads * dim), jnp.float32),
        of((rows, q, heads * dim), jnp.bfloat16),
        of((rows, q, heads * dim), jnp.float32),
        of((rows, q, heads), jnp.float32),
        of((rows, q, heads * dim), jnp.float32), of((dim,), jnp.bfloat16),
        of((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert deltanet.KDA_KERNEL_NAME in text
    assert deltanet.KDA_KERNEL_NAME != deltanet.KERNEL_NAME
    assert "f32[%d,%d,%d,%d]" % (rows, heads, q, q) not in text
    # the result leaves the kernel rounded: ``o``'s operand
    assert "bf16[%d,%d,%d]" % (rows, q, heads * dim) in text
    # and the call tells the compiler's scheduler what it costs
    assert "\"cost_estimate\":{\"flops\":\"" in text


# -- the two new readers --------------------------------------------------

NEW_READERS = ("kda_kernel_roofline_pct.bulk", "kda_gate_ms_per_dispatch.bulk")
#: the accepted readers whose lists gained the cell
LISTED = (
    "deltanet_busy_pct", "deltanet_roofline_pct", "deltarule_roofline_pct",
    "deltarule_ms_per_dispatch", "segment_conv_ms_per_dispatch",
    "attn_busy_pct", "flash_roofline_pct", "flash_tile_visit_pct",
    "mla_proj_ms_per_dispatch", "experts_busy_pct", "experts_roofline_pct",
    "gmm_roofline_pct", "gmm_row_fill_pct", "held_assignment_pct",
    "expert_load_max_over_mean", "net_flops_util_pct", "net_roofline_pct",
    "tokens_per_s", "pad_token_pct", "pad_row_pct", "pad_row_traced_pct",
    "rows_per_dispatch", "host_cores_busy", "device_idle_pct",
    "hbm_peak_gib")


def test_the_accepted_readers_list_the_cell():
    by_name = {m["name"]: m for m in mm.load()["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name + ".bulk"]["workloads"], name
    # (PR 51's set-up metrics list every cell and move `setup_s`)
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", ())
              and m["moves"] == "videos_per_s"}
    assert listed == {n + ".bulk" for n in LISTED} | set(NEW_READERS)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_on_a_run_without_its_scope(
        name, tmp_path):
    """No trace, a trace whose run wrote no scope table, and a family
    whose file counts no ``deltarule`` (the parent's programs have
    neither the scope nor the kernel): None, not a raise."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "gated delta rule"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("kimi_linear")
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
        assert subscopes.seconds_under(Facts, "deltanet/gate") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]": "jit(apply)/jit(main)/deltanet/rule/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet/gate") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]":
             "jit(apply)/jit(main)/deltanet/gate/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet/gate") == 0.5
        assert subscopes.seconds_under(Facts, "deltanet") == 0.5
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


def test_the_kernels_name_is_the_readers():
    from rnb_tpu.ops import deltanet
    reader = mm.load_layer_metric("kda_kernel_roofline_pct.bulk")
    assert reader.KERNEL == deltanet.KDA_KERNEL_NAME
    # an older family's file counts no such mechanism and raises: the
    # reader says None there
    with open(os.path.join(
            REPO, "benchmarks/configs/deepseek-v2-ep8.json")) as f:
        older = json.load(f)
    with pytest.raises(ValueError):
        mm.load_family("deepseek_v2").mechanism_work(
            older, "deltarule", 1.0, 1.0, 1.0)
