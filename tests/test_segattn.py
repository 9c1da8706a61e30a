"""The packed flash kernel's block table (``rnb_tpu/ops/segattn.py``):
the table against brute force over seeded packings, the kernel under
the table against the same kernel with every causal tile on (bit for
bit) and against one masked softmax, the counters' log-meta line and
the benchmark's reader (a stack's counters against the table's own
sums: ``tests/test_deepseek_v2.py``). Pallas runs in interpret mode; the
tiles are cut to 128 and 256 tokens so that a pool of a thousand holds
several."""

import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import manifest as mm  # noqa: E402

Q = 16


def row_starts(sizes, rows):
    """``row_start`` of requests of ``sizes`` rows packed in order into
    ``rows`` rows, the rest pad rows (requests of their own)."""
    start = np.arange(rows, dtype=np.int32)
    at = 0
    for size in sizes:
        start[at:at + size] = at
        at += size
    assert at <= rows
    return start


def random_sizes(seed, rows):
    rng = np.random.default_rng(seed)
    sizes = []
    while sum(sizes) < rows - 3:
        sizes.append(int(min(rng.integers(1, 24), rows - 3 - sum(sizes))))
    return sizes


#: name -> (rows, sizes of the requests): the pool of 64 rows is 1,024
#: tokens, whole blocks; the pool of 60 is padded up to them
PACKINGS = {
    "one-request-fills-the-pool": (64, [64]),
    "64-one-row-requests": (64, [1] * 64),
    "pad-rows-at-the-end": (64, [20, 3, 17]),
    "padded-up-to-a-block": (60, [7, 30, 1, 22]),
    "random-1": (64, random_sizes(1, 64)),
    "random-2": (64, random_sizes(2, 64)),
    "random-3-padded": (52, random_sizes(3, 52)),
}
BLOCKS = [(128, 128), (128, 256), (256, 128), (512, 128)]


def token_segments(row_start, padded):
    """Brute force: each token's request, as the first token of it; a
    token past the pool is a request of its own."""
    rows = len(row_start)
    seg = np.arange(padded)
    seg[:rows * Q] = np.repeat(row_start * Q, Q)
    return seg


@pytest.mark.parametrize("block_q,block_kv", BLOCKS)
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_the_table_leaves_out_no_tile_that_holds_a_permitted_pair(
        packing, block_q, block_kv):
    import jax.numpy as jnp

    from rnb_tpu.ops import segattn
    rows, sizes = PACKINGS[packing]
    padded = -(-rows * Q // max(block_q, block_kv)) * max(block_q, block_kv)
    seg = token_segments(row_starts(sizes, rows), padded)
    run, fetch, causal = segattn.block_table(
        jnp.asarray(seg[::block_q]), block_q, block_kv)
    run, fetch = np.asarray(run), np.asarray(fetch)
    nq, nk = padded // block_q, padded // block_kv
    assert run.shape == fetch.shape == (nq, nk)
    at = np.arange(padded)
    permitted = (seg[:, None] == seg[None, :]) & (at[None, :] <= at[:, None])
    holds = permitted.reshape(nq, block_q, nk, block_kv).any(axis=(1, 3))
    under = (at[None, :] <= at[:, None]) \
        .reshape(nq, block_q, nk, block_kv).any(axis=(1, 3))
    assert not (holds & (run == 0)).any()
    assert not (run[~under]).any()
    assert causal == under.sum() and run.sum() <= causal
    # a step that does not run asks for no block but one that runs: its
    # own row's first before it, the next row's first (the next head's
    # first row's after the last) past the diagonal
    for i in range(nq):
        ran = np.flatnonzero(run[i])
        assert len(ran) and (fetch[i, ran] == ran).all()
        assert (fetch[i, :ran[0]] == ran[0]).all()
        assert (fetch[i, ran[-1] + 1:]
                == np.flatnonzero(run[(i + 1) % nq])[0]).all()
    if packing == "one-request-fills-the-pool":
        assert (run == under).all()
    if packing == "64-one-row-requests" and block_q == block_kv:
        assert (run == np.eye(nq, dtype=run.dtype)).all()


FORMS = {
    # grouped queries: 16 query heads a key-value head
    "gqa": dict(hq=32, hk=2, dim=32, dim_v=32),
    # latent attention expanded: 192 / 128, one query head a key head
    "mla": dict(hq=2, hk=2, dim=192, dim_v=128),
    # five query heads a key-value head (Falcon-H1's 20 / 4): no power
    # of two
    "gqa_five": dict(hq=10, hk=2, dim=32, dim_v=32),
}


def masked_softmax(q, k, v, seg):
    """float32, one softmax a query over the keys of its own request at
    or before it. q (T, Hq, D) scaled, k (T, Hk, D), v (T, Hk, Dv)."""
    tokens, hq, _ = q.shape
    per = hq // k.shape[1]
    at = np.arange(tokens)
    allowed = (seg[:, None] == seg[None, :]) & (at[None, :] <= at[:, None])
    k, v = np.repeat(k, per, axis=1), np.repeat(v, per, axis=1)
    scores = np.einsum("qhd,khd->hqk", q, k)
    scores = np.where(allowed[None], scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", weights, v)


@pytest.mark.parametrize("block_q,block_kv", [(128, 128), (128, 256),
                                              (256, 128)])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernel_under_the_table_equals_every_causal_tile_bit_for_bit(
        form, block_q, block_kv, monkeypatch):
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import segattn
    monkeypatch.setattr(segattn, "_BLOCK_Q", block_q)
    monkeypatch.setattr(segattn, "_BLOCK_KV", block_kv)
    monkeypatch.setattr(segattn, "_BLOCK_COMPUTE", 128)
    shape = FORMS[form]
    rows, sizes = PACKINGS["padded-up-to-a-block"]
    start = row_starts(sizes, rows)
    rng = np.random.default_rng(len(form) + block_q)
    q, k, v = (jnp.asarray(rng.standard_normal((rows, Q, heads, dim)),
                           jnp.bfloat16)
               for heads, dim in ((shape["hq"], shape["dim"]),
                                  (shape["hk"], shape["dim"]),
                                  (shape["hk"], shape["dim_v"])))
    q = q * shape["dim"] ** -0.5

    def attend():
        return jax.jit(lambda *a: segattn.packed_attention(
            *a, interpret=True))(q, k, v, jnp.asarray(start))
    got, tiles = attend()
    table = segattn.block_table
    monkeypatch.setattr(
        segattn, "block_table",
        lambda first, *sizes: table(jnp.zeros_like(first), *sizes))
    every, all_tiles = attend()
    visited, causal = np.asarray(tiles).tolist()
    assert np.asarray(all_tiles).tolist() == [causal, causal]
    assert 0 < visited < causal
    assert got.shape == (rows, Q, shape["hq"], shape["dim_v"])
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(every, np.float32))
    flat = [np.asarray(x, np.float32).reshape((rows * Q,) + x.shape[2:])
            for x in (q, k, v)]
    want = masked_softmax(*flat, token_segments(start, rows * Q))
    got = np.asarray(got, np.float32).reshape(want.shape)
    # the output is rounded to bfloat16 once: an ulp of each value
    assert (np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + 2.0 ** -9).all()


#: the callers' real head counts and widths, over a pool of two blocks:
#: Nemotron-H's grouped queries, DeepSeek-V2's latent attention,
#: Falcon-H1's five query heads a key-value head
ENTRIES = {
    "gqa-32-over-2-of-128": dict(hq=32, hk=2, dim=128, dim_v=128),
    "mla-128-of-192-and-128": dict(hq=128, hk=128, dim=192, dim_v=128),
    "gqa-20-over-4-of-128": dict(hq=20, hk=4, dim=128, dim_v=128),
}


@pytest.mark.parametrize("form", sorted(ENTRIES))
def test_the_upper_entry_is_the_kernels_own_behind_a_layout(form,
                                                            monkeypatch):
    """``packed_attention`` (tokens first in) against
    ``heads_first_attention`` fed operands laid out by hand in numpy:
    heads first, the pool's 176 tokens padded to two blocks of 128,
    192 columns to 256 with zeros. Bit for bit, the tiles too."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import segattn
    for name in ("_BLOCK_Q", "_BLOCK_KV", "_BLOCK_COMPUTE"):
        monkeypatch.setattr(segattn, name, 128)
    shape = ENTRIES[form]
    rows, tokens, padded = 11, 11 * Q, 256
    assert segattn.pool_tokens(tokens) == padded
    start = jnp.asarray(row_starts([5, 4], rows))
    rng = np.random.default_rng(len(form))
    q, k, v = (jnp.asarray(rng.standard_normal((rows, Q, heads, dim)),
                           jnp.bfloat16)
               for heads, dim in ((shape["hq"], shape["dim"]),
                                  (shape["hk"], shape["dim"]),
                                  (shape["hk"], shape["dim_v"])))
    q = q * shape["dim"] ** -0.5
    got, tiles = jax.jit(lambda *a: segattn.packed_attention(
        *a, interpret=True))(q, k, v, start)

    def by_hand(x, heads):
        x = np.asarray(x, np.float32).reshape((tokens,) + heads + (-1,))
        laid = np.zeros(heads + (padded, -(-x.shape[-1] // 128) * 128),
                        np.float32)
        laid[..., :tokens, :x.shape[-1]] = np.moveaxis(x, 0, -2)
        return jnp.asarray(laid, jnp.bfloat16)
    hk, per = shape["hk"], shape["hq"] // shape["hk"]
    out, own_tiles = jax.jit(lambda *a: segattn.heads_first_attention(
        *a, Q, interpret=True))(
        by_hand(q, (hk, per)), by_hand(k, (hk,)), by_hand(v, (hk,)), start)
    assert out.shape == (hk, per, padded, shape["dim_v"])
    assert np.array_equal(np.asarray(tiles), np.asarray(own_tiles))
    back = np.moveaxis(np.asarray(out, np.float32), -2, 0)[:tokens]
    assert np.array_equal(
        np.asarray(got, np.float32),
        back.reshape(rows, Q, shape["hq"], shape["dim_v"]))
    with pytest.raises(ValueError, match="laid out as"):
        segattn.heads_first_attention(
            by_hand(q, (hk, per))[..., :128, :], by_hand(k, (hk,)),
            by_hand(v, (hk,)), start, Q, interpret=True)


def test_the_attention_line_is_declared_summed_and_parsed(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import parse_utils
    from rnb_tpu import telemetry
    attention = [row for row in telemetry.STAGE_COUNTERS
                 if row.counter == "attn_tiles"]
    assert [(row.line, row.keys) for row in attention] == [
        ("Attention:", ("tiles_visited", "tiles_causal"))]
    assert any(spec.pattern == "Attention:"
               for spec in telemetry.META_LINE_REGISTRY)
    stage = {"tokens_valid": 10, "tokens_shipped": 16,
             "attn_tiles": np.array([23, 36])}
    idle = {"tokens_valid": 3, "tokens_shipped": 16}
    lines, fields = telemetry.stage_counter_report([stage, idle, stage])
    assert lines[-1] == "Attention: tiles_visited=46 tiles_causal=72"
    assert (fields["attention_tiles_visited"],
            fields["attention_tiles_causal"]) == (46, 72)
    lines, fields = telemetry.stage_counter_report([idle])
    assert len(lines) == 1 and "attention_tiles_visited" not in fields
    (tmp_path / "log-meta.txt").write_text(
        "Tokens: valid=10 shipped=16\n"
        "Attention: tiles_visited=46 tiles_causal=72\n")
    meta = parse_utils.parse_meta(str(tmp_path))
    assert [meta[field] for field in attention[0].fields] == [46, 72]


def test_the_reader_is_silent_without_the_counter():
    """The parent's result has no such field, a family without the
    kernel leaves it 0: the reader returns None and does not raise."""
    from rnb_tpu.benchmark import BenchmarkResult
    reader = mm.load_layer_metric("flash_tile_visit_pct.bulk")
    assert reader.read(types.SimpleNamespace(
        result=types.SimpleNamespace())) is None
    fields = BenchmarkResult.__dataclass_fields__
    assert fields["attention_tiles_visited"].default == 0
    assert reader.read(types.SimpleNamespace(result=types.SimpleNamespace(
        attention_tiles_visited=0, attention_tiles_causal=0))) is None
    assert reader.read(types.SimpleNamespace(result=types.SimpleNamespace(
        attention_tiles_visited=23, attention_tiles_causal=36))) \
        == pytest.approx(100 * 23 / 36)
