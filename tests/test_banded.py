"""``rnb_tpu.ops.banded``, the kernel of K-EXAONE's sliding layers, on
the CPU in Pallas's interpret mode: against an explicit mask (windows
under, at and over a block, a request that begins inside a band, a pad
row, the pool's first block, the toy widths of ``test_exaone_moe.py``);
its first lines (the head norm, the rotary, q's scale, one rounding)
against the passes they replaced, to the bit; the pair it returns
against a count by hand; and the kernel lowered and compiled at the
published widths for a described v5e.
Nothing here needs the native decode library or a chip."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REAL = "benchmarks/configs/k-exaone-l5-ep8.json"
EPS = 1e-5


def inv_freq(dim, theta=1e6):
    return (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)) \
        .astype(np.float32)


def draw(seed, rows, qlen, hq, hk, dim, dtype):
    """(q, k, v, the two norms' weights) as the mixer hands them: q and
    k float32, far from unit norm."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    tokens = rows * qlen

    def n(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)
    return (n(tokens, hq * dim, scale=3.0), n(tokens, hk * dim, scale=0.3),
            n(tokens, hk * dim, dtype=dtype), 1.0 + 0.2 * n(dim),
            1.0 + 0.2 * n(dim))


def replaced_passes(q, k, q_weight, k_weight, row_start, qlen, act):
    """What ``attention_mixer`` did in front of the kernel it called
    until PR 52: -> q (T, Hq, D) and k (T, Hk, D) in ``act``."""
    import jax.numpy as jnp

    from rnb_tpu.models.exaone_moe.network import rms_norm
    from rnb_tpu.ops import rope
    rows, dim = row_start.shape[0], q_weight.shape[0]
    positions = rope.pool_positions(row_start, qlen)
    qs = rms_norm(q.reshape(rows, qlen, -1, dim), q_weight, EPS, jnp.float32)
    ks = rms_norm(k.reshape(rows, qlen, -1, dim), k_weight, EPS, jnp.float32)
    qs = rope.rotate(qs, positions, inv_freq(dim))
    ks = rope.rotate(ks, positions, inv_freq(dim))
    qs = (qs * dim ** -0.5).astype(act)
    return qs.reshape(rows * qlen, -1, dim), \
        ks.astype(act).reshape(rows * qlen, -1, dim)


def explicit(qs, ks, v, row_start, qlen, window):
    """Softmax attention under an explicit (T, T) mask, float64: a key
    of the query's request, at or before it, inside its window."""
    tokens, hq, dim = qs.shape
    hk = ks.shape[1]
    qf, kf = np.asarray(qs, np.float64), np.asarray(ks, np.float64)
    vf = np.asarray(v, np.float64).reshape(tokens, hk, dim)
    seg, at = np.repeat(np.asarray(row_start), qlen), np.arange(tokens)
    ok = (seg[:, None] == seg[None, :]) & (at[None, :] <= at[:, None]) \
        & (at[None, :] > at[:, None] - window)
    out = np.zeros((tokens, hq, dim))
    for h in range(hq):
        s = np.where(ok, qf[:, h] @ kf[:, h // (hq // hk)].T, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ vf[:, h // (hq // hk)]
    return out.reshape(tokens, hq * dim)


def run(operands, starts, qlen, window):
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    q, k, v, q_weight, k_weight = operands
    row_start = jnp.asarray(starts, jnp.int32)
    tables = banded.band_tables(row_start, qlen, inv_freq(len(q_weight)))
    out, tiles = banded.banded_attention(q, k, v, q_weight, k_weight,
                                         tables, window, EPS, True)
    return out, tiles, row_start


#: (rows, tokens a row, each row's request, the window, query heads, key
#: heads, their width): the cases ``test_exaone_moe.py`` held splash's
#: local mask to until PR 52 (3 and 16 rows of 128 tokens; windows under,
#: at and over a block — a window over two blocks' reach takes the next
#: divisor of the pool; requests that open inside a band; a pad row, the
#: pool's last), one key head's eight query heads, and the toy stack's
#: widths (a window of 24 over rows of 16: blocks of 32 tokens, a band
#: across two rows' requests, and a pool that is one block)
WINDOWS = [
    (3, 128, [0, 0, 2], 40, 4, 2, 128),
    (3, 128, [0, 0, 2], 128, 4, 2, 128),
    (3, 128, [0, 0, 0], 200, 4, 2, 128),
    (16, 128, [0] * 9 + [9] * 6 + [15], 100, 4, 2, 128),
    (16, 128, [0] * 9 + [9] * 6 + [15], 512, 4, 2, 128),
    (16, 128, [0] * 16, 600, 4, 2, 128),
    (4, 128, [0, 1, 1, 3], 128, 8, 1, 128),
    (8, 16, [0, 0, 0, 3, 3, 5, 6, 7], 24, 4, 2, 32),
    (3, 16, [0, 0, 2], 24, 4, 2, 32),
]


@pytest.mark.parametrize("rows,qlen,starts,window,hq,hk,dim", WINDOWS)
def test_the_banded_kernel_equals_the_explicit_mask(rows, qlen, starts,
                                                    window, hq, hk, dim):
    """Float32 values, so nothing is rounded on the way: the kernel's
    one softmax over the band against the whole pool's under the mask,
    and the pair it returns inside its bounds."""
    import jax.numpy as jnp
    operands = draw(rows + window, rows, qlen, hq, hk, dim, jnp.float32)
    out, tiles, row_start = run(operands, starts, qlen, window)
    qs, ks = replaced_passes(operands[0], operands[1], operands[3],
                             operands[4], row_start, qlen, jnp.float32)
    want = explicit(qs, ks, operands[2], starts, qlen, window)
    assert out.shape == (rows * qlen, hq * dim) and out.dtype == jnp.float32
    assert np.abs(np.asarray(out) - want).max() < 5e-6
    ran, causal = (int(n) for n in np.asarray(tiles))
    assert 0 < ran <= causal


@pytest.mark.parametrize("starts", [[0] * 6, [0, 0, 2, 2, 2, 5]])
def test_in_the_activations_dtype_the_kernel_rounds_where_the_mixer_did(
        starts):
    """bfloat16 values: q and k are rounded once behind norm, rotary and
    scale (the explicit mask reads the same rounded operands), the
    result on the store; the probabilities go into the values' product
    as float32 (the compiled product rounds them, the interpreted one
    here keeps them: ``ops/banded.py``'s sweep)."""
    import jax.numpy as jnp
    operands = draw(7, len(starts), 128, 4, 2, 128, jnp.bfloat16)
    out, _, row_start = run(operands, starts, 128, 128)
    assert out.dtype == jnp.bfloat16
    qs, ks = replaced_passes(operands[0], operands[1], operands[3],
                             operands[4], row_start, 128, jnp.bfloat16)
    want = explicit(qs.astype(jnp.float32), ks.astype(jnp.float32),
                    operands[2].astype(jnp.float32), starts, 128, 128)
    # a rounding to eight bits of a value of a few tenths, with room
    assert np.abs(np.asarray(out, np.float64) - want).max() \
        < 0.02 * np.abs(want).max()


# -- the first lines -------------------------------------------------------------


@pytest.mark.parametrize("dim,act", [(128, "bfloat16"), (128, "float32"),
                                     (32, "bfloat16"), (256, "bfloat16")])
@pytest.mark.parametrize("scaled", [True, False])
def test_the_first_lines_are_the_passes_they_replaced_to_the_bit(
        dim, act, scaled):
    """``_first_lines`` inside a Pallas call in interpret mode, on a
    head's float32 columns with positions that restart, against
    ``rms_norm`` + ``ops/rope.rotate`` + the scale + the cast: the same
    float32 operations in the same order, so the same bits."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from rnb_tpu.models.exaone_moe.network import rms_norm
    from rnb_tpu.ops import banded, rope
    act = jnp.dtype(act)
    qlen, starts = 16, [0, 0, 0, 3, 3, 5]
    rng = np.random.default_rng(dim + scaled)
    tokens = len(starts) * qlen
    x = jnp.asarray(rng.normal(size=(tokens, dim)) * 2.5, jnp.float32)
    weight = jnp.asarray(1.0 + 0.2 * rng.normal(size=dim), jnp.bfloat16)
    row_start = jnp.asarray(starts, jnp.int32)
    cos, sin, start = banded.band_tables(row_start, qlen, inv_freq(dim))
    assert np.array_equal(np.asarray(start)[:, 0],
                          np.repeat(np.asarray(starts) * qlen, qlen))
    scale = dim ** -0.5 if scaled else None

    def body(x_ref, w_ref, cos_ref, sin_ref, o_ref):
        o_ref[...] = banded._first_lines(
            x_ref[...], w_ref[...], cos_ref[...], sin_ref[...], EPS, act,
            scale)
    got = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((tokens, dim), act),
        interpret=True)(x, weight.astype(jnp.float32)[None, :], cos, sin)

    # under one ``jit`` like the interpreted kernel: op by op the CPU's
    # compiler has no multiply next to an add to contract, in one
    # program it has, on both sides alike
    @jax.jit
    def passes(x):
        x = rms_norm(x.reshape(len(starts), qlen, 1, dim), weight, EPS,
                     jnp.float32)
        x = rope.rotate(x, rope.pool_positions(row_start, qlen),
                        inv_freq(dim))
        return ((x * scale) if scaled else x).astype(act)
    want = passes(x)
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32))
                          .reshape(tokens, dim))


# -- the pair --------------------------------------------------------------------


@pytest.mark.parametrize("tokens,window,block,steps,causal", [
    # the published window over the largest and the smallest row bucket
    (16384, 128, 128, 128, 4160), (2048, 128, 128, 16, 72),
    # 384 tokens in blocks of 48: 1 1 2 2 3 3 4 4
    (384, 40, 48, 8, 20),
    # the toy stack: four blocks of 32, 1 1 2 2; three rows are one block
    (128, 24, 32, 4, 6), (48, 24, 48, 1, 1),
    # a window over half the pool leaves two blocks, over it one
    (2048, 600, 1024, 2, 2), (2048, 1500, 2048, 1, 1)])
def test_the_pair_is_the_count_by_hand(tokens, window, block, steps, causal):
    """The steps a key-value head takes and the (B, 2B) tiles on or
    under the diagonal, by the rule for B and by a count of the tiles
    one by one."""
    from rnb_tpu.ops import banded
    assert banded.band_block(tokens, window) == block
    assert block >= min(window - 1, tokens) and tokens % block == 0
    assert banded.band_tiles(tokens, block) == (steps, causal)
    # tile (i, j) holds queries i B .. i B + B - 1 and keys 2 j B ..: on
    # or under the diagonal where its first key is no later than its
    # last query
    assert causal == sum(2 * j * block <= i * block + block - 1
                         for i in range(steps) for j in range(steps))


def test_the_kernel_returns_the_pair_of_its_pool():
    import jax.numpy as jnp
    operands = draw(0, 8, 16, 4, 2, 32, jnp.float32)
    _, tiles, _ = run(operands, [0] * 8, 16, 24)
    assert tiles.dtype == jnp.int32 and tiles.tolist() == [4, 6]


# -- compiled for the chip -------------------------------------------------------


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
    """A sliding layer's kernel over the largest row bucket, from the
    products' float32 results to ``o``'s operand, compiled for a
    described v5e (nothing runs): one custom call under its own name,
    and no array with a head axis beside it."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    rows = max(config["pipeline_config"]["pipeline"][-1]["row_buckets"])
    q, hq, hk, dim = (config["chunk_size"], config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    tokens, f32 = rows * q, jnp.float32
    assert banded.band_block(tokens, config["sliding_window"]) == 128

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b, c, qw, kw, cos, sin, start: banded.banded_attention(
            a, b, c, qw, kw, (cos, sin, start), config["sliding_window"],
            config["rms_norm_eps"])).lower(
        of((tokens, hq * dim), f32), of((tokens, hk * dim), f32),
        of((tokens, hk * dim)), of((dim,)), of((dim,)),
        of((tokens, dim), f32), of((tokens, dim), f32),
        of((tokens, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert banded.KERNEL_NAME in text
    assert "bf16[%d,%d]" % (tokens, hq * dim) in text
    assert "transpose(" not in text and "pad(" not in text


# -- latent attention under a window (dots3-note's sliding layers) ---------------

#: (rows, tokens a row, the rows that open a request, window, heads,
#: nope, rotary, value, heads a step): an odd window over one block (the
#: whole pool) and over several, every head its own key, keys wider than
#: values, the own key padded and whole lane tiles
LATENT_WINDOWS = [
    (4, 16, [0, 0, 2, 2], 37, 2, 24, 8, 16, 2),
    (16, 32, [0, 0, 0, 3, 3, 3, 3, 3, 8, 8, 8, 8, 8, 8, 14, 15], 37, 4,
     24, 8, 16, 2),
    (16, 32, [0, 0, 0, 3, 3, 3, 3, 3, 8, 8, 8, 8, 8, 8, 14, 15], 65, 2,
     128, 16, 32, 1),
    (8, 32, [0] * 8, 513, 2, 24, 8, 16, 2)]


@pytest.mark.parametrize(
    "rows,qlen,starts,window,heads,nope,rotary,value,per", LATENT_WINDOWS)
def test_the_latent_banded_kernel_equals_the_explicit_mask(
        rows, qlen, starts, window, heads, nope, rotary, value, per,
        monkeypatch):
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import banded, latent
    monkeypatch.setattr(banded, "_LATENT_HEADS", per)
    rng = np.random.default_rng(rows + window)
    tokens = rows * qlen
    lanes = -(-(nope + rotary) // 128) * 128
    own = latent.key_lanes(nope, lanes)
    assert own == (nope if nope % 128 == 0 else lanes)
    q = np.zeros((heads, tokens, lanes), np.float32)
    q[..., :nope + rotary] = rng.normal(
        size=(heads, tokens, nope + rotary)) * 2 * (nope + rotary) ** -0.5
    kv = np.zeros((tokens, heads, own + value), np.float32)
    kv[..., :nope] = rng.normal(size=(tokens, heads, nope))
    kv[..., own:] = rng.normal(size=(tokens, heads, value))
    k_pe = rng.normal(size=(tokens, rotary))
    gate = np.asarray(jax.nn.sigmoid(jnp.asarray(
        rng.normal(size=(tokens, heads)), jnp.float32)))
    bf = jnp.bfloat16
    q, kv, k_pe = (jnp.asarray(x, bf) for x in (q, kv.reshape(tokens, -1),
                                                k_pe))
    start = np.repeat(np.asarray(starts) * qlen, qlen)
    out, tiles = banded.latent_banded_attention(
        q, kv, k_pe, jnp.asarray(gate), jnp.asarray(start, jnp.int32)[:, None],
        window, nope, value, interpret=True)
    assert out.shape == (tokens, heads * value) and out.dtype == bf
    t = np.arange(tokens)
    keep = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window) \
        & (t[None, :] >= start[:, None])
    qf = np.asarray(q, np.float32)
    kvf = np.asarray(kv, np.float32).reshape(tokens, heads, own + value)
    s = np.einsum("htd,shd->hts", qf[..., :nope], kvf[..., :nope]) \
        + np.einsum("htd,sd->hts", qf[..., nope:nope + rotary],
                    np.asarray(k_pe, np.float32))
    s = np.where(keep[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True),
                     kvf[..., own:]) * gate[:, :, None]
    assert np.abs(np.asarray(out, np.float32)
                  - want.reshape(tokens, -1)).max() < 0.03
    block = banded.band_block(tokens, window)
    assert tiles.tolist() == list(banded.band_tiles(tokens, block))
    # one key fewer in the window is another result
    short, _ = banded.latent_banded_attention(
        q, kv, k_pe, jnp.asarray(gate), jnp.asarray(start, jnp.int32)[:, None],
        window - 1, nope, value, interpret=True)
    binds = bool((keep.sum(1) == window).any())
    assert (np.abs(np.asarray(short, np.float32)
                   - np.asarray(out, np.float32)).max() > 0.03) == binds


# -- differential attention under a window (Phi-4-mini-flash's) ------------------


def differential_explicit(qkv, lam, sub, starts, qlen, window, hq, hk, eps):
    """Two softmaxes a head pair over the pair's one value under an
    explicit (T, T) mask, in plain float64: ``RMSNorm(P1 [v1 | v2] -
    lambda P2 [v1 | v2]) sub``; query pair j = heads 2j, 2j + 1 reads
    key-value pair ``j // (hq / hk)``; q comes scaled."""
    x = np.asarray(qkv, np.float64)
    tokens = x.shape[0]
    d = x.shape[1] // (hq + 2 * hk)
    q = x[:, :hq * d].reshape(tokens, hq, d)
    k = x[:, hq * d:(hq + hk) * d].reshape(tokens, hk, d)
    v = x[:, (hq + hk) * d:].reshape(tokens, hk // 2, 2 * d)
    seg, at = np.repeat(np.asarray(starts), qlen), np.arange(tokens)
    ok = (seg[:, None] == seg[None, :]) & (at[None, :] <= at[:, None]) \
        & (at[None, :] > at[:, None] - window)

    def softmax(s):
        s = np.where(ok, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        return p / p.sum(-1, keepdims=True)
    out = np.zeros((tokens, hq // 2, 2 * d))
    for j in range(hq // 2):
        g = j // (hq // hk)
        o = softmax(q[:, 2 * j] @ k[:, 2 * g].T) @ v[:, g] \
            - lam * (softmax(q[:, 2 * j + 1] @ k[:, 2 * g + 1].T) @ v[:, g])
        out[:, j] = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * np.asarray(sub, np.float64)
    return out.reshape(tokens, -1), ok


def differential_draw(seed, tokens, hq, hk, d, dtype):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    qkv = rng.normal(size=(tokens, (hq + 2 * hk) * d))
    qkv[:, :hq * d] *= 3.0 * d ** -0.5          # q: scaled
    return (jnp.asarray(qkv, dtype),
            jnp.asarray(1.0 + 0.2 * rng.normal(size=2 * d), jnp.float32))


#: (rows, tokens a row, each row's request, the window, query heads, key
#: heads, a head's width): the published pairing (two query pairs a
#: key-value pair, heads of 64) under a window under, at and over a
#: block, requests that open inside a band, a pad row; the toy stack's
#: rows of 16
DIFFERENTIAL_WINDOWS = [
    (3, 128, [0, 0, 2], 40, 4, 2, 64),
    (6, 128, [0, 0, 0, 0, 4, 5], 128, 8, 4, 64),
    (6, 128, [0] * 6, 200, 4, 2, 64),
    (8, 16, [0, 0, 0, 3, 3, 5, 6, 7], 24, 4, 2, 64),
    (3, 16, [0, 0, 2], 24, 8, 2, 64),
]


@pytest.mark.parametrize("rows,qlen,starts,window,hq,hk,d",
                         DIFFERENTIAL_WINDOWS)
def test_two_softmaxes_over_one_value_equal_plain_jnp(rows, qlen, starts,
                                                      window, hq, hk, d):
    """Float32 values: the kernel's last lines (``P1 V - lambda P2 V``,
    the norm over a pair's 128 columns, its weight) against the explicit
    mask's."""
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    qkv, sub = differential_draw(rows + window, rows * qlen, hq, hk, d,
                                 jnp.float32)
    out, tiles = banded.differential_banded_attention(
        qkv, jnp.float32(0.37), sub,
        banded.band_start(jnp.asarray(starts, jnp.int32), qlen), window,
        (hq, hk), EPS, True)
    want, _ = differential_explicit(qkv, 0.37, sub, starts, qlen, window,
                                    hq, hk, EPS)
    assert out.shape == (rows * qlen, hq * d) and out.dtype == jnp.float32
    assert np.abs(np.asarray(out) - want).max() < 2e-5
    ran, causal = (int(n) for n in np.asarray(tiles))
    assert 0 < ran <= causal


def test_a_query_reads_key_t_minus_511_and_not_t_minus_512():
    """The published window: 512 keys that end with the query's own. A
    value planted at key ``t - 511`` moves query t's result, one at ``t -
    512`` does not; nor does one across a request boundary inside the
    band."""
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    rows, qlen, hq, hk, d, window = 12, 128, 4, 2, 64, 512
    starts = [0] * 9 + [9] * 3
    assert banded.band_block(rows * qlen, window) == 512
    qkv, sub = differential_draw(9, rows * qlen, hq, hk, d, jnp.float32)
    start = banded.band_start(jnp.asarray(starts, jnp.int32), qlen)

    def run(qkv):
        out, _ = banded.differential_banded_attention(
            qkv, jnp.float32(0.3), sub, start, window, (hq, hk), EPS, True)
        return np.asarray(out)
    base = run(qkv)
    value = (hq + hk) * d               # V's first column
    t = 1100                            # of the first request (rows 0-8)
    for key, moves in ((t - 511, True), (t - 512, False)):
        moved = np.abs(run(qkv.at[key, value:].add(50.0)) - base).max(-1)
        assert (moved[t] > 1e-3) == moves, (key, moved[t])
        # the first query past the window's reach of that key is clean
        assert moved[key + 512] < 1e-6 and moved[key + 511] > 1e-3
    # the second request's first queries lie within 512 of the first
    # request's last keys and read none of them
    boundary = 9 * qlen
    moved = np.abs(run(qkv.at[boundary - 1, value:].add(50.0)) - base).max(-1)
    assert moved[boundary - 1] > 1e-3
    assert moved[boundary:].max() < 1e-6
    want, ok = differential_explicit(qkv, 0.3, sub, starts, qlen, window,
                                     hq, hk, EPS)
    assert ok[t, t - 511] and not ok[t, t - 512] and not ok[boundary,
                                                           boundary - 1]
    assert np.abs(base - want).max() < 2e-5


def test_the_differential_kernel_rounds_once_in_bfloat16():
    """bfloat16 values, as the layer's first product wrote them: the
    explicit mask reads the same rounded operands; the result is rounded
    on the store."""
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    starts = [0, 0, 2, 2, 2, 5]
    qkv, sub = differential_draw(11, 6 * 128, 4, 2, 64, jnp.bfloat16)
    out, _ = banded.differential_banded_attention(
        qkv, jnp.float32(0.6), sub,
        banded.band_start(jnp.asarray(starts, jnp.int32), 128), 128, (4, 2),
        EPS, True)
    want, _ = differential_explicit(qkv, 0.6, sub, starts, 128, 128, 4, 2,
                                    EPS)
    assert out.dtype == jnp.bfloat16
    assert np.abs(np.asarray(out, np.float64) - want).max() \
        < 2 ** -7 * np.abs(want).max()


def test_the_differential_kernel_compiles_at_the_published_widths(one_chip):
    """A sliding layer's kernel over the largest row bucket, from the
    first product's one array to ``o``'s operand, compiled for a
    described v5e (nothing runs): one custom call under its own name,
    and nothing beside it — no slice of Q, K or V, no pad, no
    transpose."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    with open(os.path.join(
            REPO, "benchmarks/configs/phi4-mini-flash.json")) as f:
        config = json.load(f)
    rows = max(config["pipeline_config"]["pipeline"][-1]["row_buckets"])
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // hq
    tokens = rows * config["chunk_size"]
    assert banded.band_block(tokens, config["sliding_window"]) == 512

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda qkv, lam, sub, start: banded.differential_banded_attention(
            qkv, lam, sub, start, config["sliding_window"], (hq, hk),
            config["layer_norm_eps"])).lower(
        of((tokens, (hq + 2 * hk) * d)), of((), jnp.float32),
        of((2 * d,), jnp.float32), of((tokens, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert banded.DIFFERENTIAL_KERNEL_NAME in text
    assert "bf16[%d,%d]" % (tokens, hq * d) in text
    assert "transpose(" not in text and "pad(" not in text \
        and " slice(" not in text
