"""``ops/indexed``'s kernels under latent attention (dots3-note's full
layers), on the CPU: the latent kernel under the sets against
``chosen_mask``'s dense form over packed pools, interpreted, and the
tiles it refuses; then dots3-note's four kernels (the scores, the latent
kernel under the sets, the latent banded kernel and ``ops/mla.queries``)
compiled at the published widths for a described v5e. The pools and the
described chip are ``test_keye_vl2.py``'s, where the same kernels stand
under grouped-query attention (one file is one worker's under ``--dist
loadfile``, and these compiles take three minutes of it)."""

import numpy as np
import pytest

from test_keye_vl2 import a_pool, one_chip  # noqa: F401


# -- the sets under latent attention (dots3-note's full layers) -----------------


def latent_operands(rng, tokens, heads, nope, rotary, value):
    """(q, kv, k_pe, gate) as ``models/dots3_note`` hands them to the
    latent kernels: q heads-first ``[q_nope | q_pe | 0]`` to whole lanes
    with the scale in it, a head's ``[own key | value]`` in ``kv``, the
    one rotary key, the heads' gates."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import latent
    lanes = -(-(nope + rotary) // 128) * 128
    own = latent.key_lanes(nope, lanes)
    q = np.zeros((heads, tokens, lanes), np.float32)
    q[..., :nope + rotary] = rng.normal(
        size=(heads, tokens, nope + rotary)) * 2 * (nope + rotary) ** -0.5
    kv = np.zeros((tokens, heads, own + value), np.float32)
    kv[..., :nope] = rng.normal(size=(tokens, heads, nope))
    kv[..., own:] = rng.normal(size=(tokens, heads, value))
    return (jnp.asarray(q, jnp.bfloat16),
            jnp.asarray(kv.reshape(tokens, -1), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(tokens, rotary)), jnp.bfloat16),
            jax.nn.sigmoid(jnp.asarray(rng.normal(size=(tokens, heads)),
                                       jnp.float32)))


def latent_dense(q, kv, k_pe, gate, mask, nope, rotary, value):
    """Every head's softmax under the explicit ``mask``, in numpy."""
    heads, tokens, _ = q.shape
    q = np.asarray(q, np.float32)
    kv = np.asarray(kv, np.float32).reshape(tokens, heads, -1)
    s = np.einsum("htd,shd->hts", q[..., :nope], kv[..., :nope]) \
        + np.einsum("htd,sd->hts", q[..., nope:nope + rotary],
                    np.asarray(k_pe, np.float32))
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    out = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True),
                    kv[..., -value:])
    return (out * np.asarray(gate)[:, :, None]).reshape(tokens, -1)


#: (rows of 32 tokens, the rows that open a request, heads, nope, rotary,
#: value, (queries, keys, heads) a step): the MLA shape in both forms of
#: the own key — whole lane tiles with the rotary key's product beside
#: it, and padded with the rotary key added under it —, keys wider than
#: values, pools of several tiles with requests that start inside one
LATENT_POOLS = {
    "own_key_whole_lanes": (16, [0, 9, 14, 15], 4, 128, 16, 32,
                            (128, 128, 2)),
    "own_key_padded": (16, [0, 9, 14, 15], 4, 24, 8, 16, (128, 128, 4)),
    "one_tile_one_group": (8, [0, 5, 7], 2, 128, 16, 32, (1024, 512, 4)),
    "queries_over_keys_tiles": (32, [0, 9, 21, 30, 31], 4, 24, 8, 16,
                                (256, 128, 2))}


@pytest.mark.parametrize("pool", sorted(LATENT_POOLS))
def test_the_latent_kernel_reads_the_sets_alone(pool, monkeypatch):
    """``latent_indexed_attention`` against ``chosen_mask``'s dense form:
    every head its own key and value, the one rotary key shared, the
    gate on the result; the sets it writes as bits are the mask."""
    import jax.numpy as jnp

    from rnb_tpu.ops import indexed
    rows, firsts, heads, nope, rotary, value, tiles = LATENT_POOLS[pool]
    monkeypatch.setattr(indexed, "_LATENT_TILES", tiles)
    operands, mask, position = a_pool(rows, firsts, 4, 2, 16)
    keys, tau, cut = operands[3:6]
    start = operands[7][2]
    tokens = rows * 32
    rng = np.random.default_rng(rows + nope)
    q, kv, k_pe, gate = latent_operands(rng, tokens, heads, nope, rotary,
                                        value)
    out, sets = indexed.latent_indexed_attention(
        q, kv, k_pe, gate, keys, tau, cut, start, nope, value,
        interpret=True)
    assert out.shape == (tokens, heads * value) \
        and out.dtype == jnp.bfloat16
    want = latent_dense(q, kv, k_pe, gate, mask, nope, rotary, value)
    assert np.abs(np.asarray(out, np.float32) - want).max() < 0.03
    tile_q, tile_k, _ = indexed.latent_tiles(tokens)
    assert sets.shape == (tokens, tile_k) and sets.dtype == jnp.uint32
    assert (indexed.unpack_sets(sets)[:, :tokens] == mask).all()
    chose, reached = indexed.count_sets(sets, tile_q)
    assert (np.asarray(chose) == np.minimum(position + 1, 40)).all()
    reach = mask.reshape(tokens // tile_q, tile_q, tokens // tile_k,
                         tile_k).any(axis=(1, 3))
    assert int(reached) == int(reach.sum()) \
        <= indexed.latent_causal_tiles(tokens)


def test_more_than_32_key_tiles_are_refused(monkeypatch):
    from rnb_tpu.ops import indexed
    assert indexed.latent_tiles(16384) == indexed._LATENT_TILES
    monkeypatch.setattr(indexed, "_LATENT_TILES", (1024, 256, 4))
    with pytest.raises(ValueError, match="more than 32 key tiles"):
        indexed.latent_tiles(16384)
    monkeypatch.setattr(indexed, "_LATENT_TILES", (1024, 512, 4))
    assert indexed.latent_causal_tiles(16384) \
        == sum(2 * (i + 1) for i in range(16))


@pytest.mark.parametrize("rows", [128, 80])
@pytest.mark.parametrize("piece", ["scores", "full", "window", "queries"])
def test_the_latent_kernels_compile_at_the_published_widths(piece, rows,
                                                            one_chip):
    """dots3-note's shapes for a described v5e (nothing runs): the
    scores at 64 index heads of 128, the latent kernel under the sets
    (128 heads of 128 + 64 / 128), the latent banded kernel (64 heads of
    192 + 64 / 128, window 513) and ``ops/mla.queries`` at nope 192
    from a latent of 1,024; from the products' results to ``o``'s
    operand with no transpose and no pad of an array with a head axis."""
    import re

    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import banded, indexed, mla
    tokens = rows * 128
    bf, f32 = jnp.bfloat16, jnp.float32

    def of(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if piece == "scores":
        lowered = jax.jit(indexed.index_keys).lower(
            of((tokens, 64, 128), bf), of((tokens, 128), bf),
            of((tokens, 64), f32), of((tokens,)))
        name = indexed.SCORES_KERNEL
    elif piece == "full":
        lowered = jax.jit(
            lambda q, kv, k_pe, gate, keys, tau, cut, start:
            indexed.latent_indexed_attention(
                q, kv, k_pe, gate, keys, tau, cut, start, 128, 128)).lower(
            of((128, tokens, 256), bf), of((tokens, 128 * 256), bf),
            of((tokens, 64), bf), of((tokens, 128), f32),
            of((tokens, tokens)), of((tokens,)), of((tokens,)),
            of((tokens, 1)))
        name = indexed.LATENT_KERNEL
    elif piece == "window":
        lowered = jax.jit(
            lambda q, kv, k_pe, gate, start:
            banded.latent_banded_attention(
                q, kv, k_pe, gate, start, 513, 192, 128)).lower(
            of((64, tokens, 256), bf), of((tokens, 64 * 384), bf),
            of((tokens, 64), bf), of((tokens, 64), f32), of((tokens, 1)))
        name = banded.LATENT_KERNEL_NAME
    else:
        inv = np.ones(32, np.float32)
        lowered = jax.jit(lambda c, w, at: mla.queries(
            c, w, at, inv, 192, 0.0625, out_columns=256)).lower(
            of((tokens, 1024), bf), of((64, 1024, 384), bf), of((tokens,)))
        name = mla.KERNEL_NAME
    text = lowered.compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert name in text
    if piece in ("full", "window"):
        heads = 128 if piece == "full" else 64
        assert "bf16[%d,%d]" % (tokens, heads * 128) in text
        # nothing with a head axis is laid out in front of the kernel or
        # behind it: the gates' (T, heads) float32 alone is regrouped
        assert not re.search(r"bf16\[[\d,]*\]\S* transpose\(", text)
        assert not re.search(r"bf16\[\d+,\d+,\d+\]\S* pad\(", text)
    if piece == "queries":
        assert "bf16[64,%d,256]" % tokens in text
