"""The Keye-VL-2.0 family (``keye_vl2``) against its plain reference, at a
toy size on the CPU with weights from a seed: the packed prefill through
dispatches that mix requests under and over ``topk``, with a pad row; the
sets by themselves (``ops/indexed``: exactly ``min(t + 1, topk)`` keys,
none of the future or of another request, equal scores to the lower key,
against a sort in NumPy) and the attention kernel under them against an
explicit mask, at the toy's heads and at 32 / 4, and against the passes
and the kernel it replaced (``tests/keye_parent.py``) bit for bit, in
every form the sweep times and through the whole toy stack; a query with
``topk`` keys or fewer against plain causal
attention, bit for bit; the reference's multimodal rotary against
``ops/rope.rotate``; every expert held; the counters against a NumPy
count; a sample's two kinds of choice and the check's refusals (a
tampered set, the float8 indexer, both attention controls, every matrix
through float8); the family's record for ``family_contract.py``, by
which ``test_keye_vl2_cell.py`` runs the stage, the control script and
the cell; the operation counts against a count by hand; the five new
readers on a run without their scope; the real configuration against the
catalog's row; the mixer's ``attn/kernel`` scope and the real 128-row
program's arrays between q's product and ``o``'s; the kernels compiled
at the published widths for a described v5e (dots3-note's, under latent
attention, in ``test_indexed_latent.py``); and the shared code's
StableHLO for the five older families and for this one.
Nothing here needs the native decode library or a chip."""

import functools
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import keye_vl2 as reference  # noqa: E402

REAL = "benchmarks/configs/keye-vl2-stage0.json"
CELL = "keye-vl2.bulk"
SEED = 4_600_000_123

#: three layers at toy widths: 4 / 2 heads of 16, 4 index heads of 16 on
#: one key head, a query keeps 48 keys, rows of 16 tokens, 8 experts
#: top-2, all held
TOY = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 2,
    "num_local_experts": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 48},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 512, "chunk_size": 16}
HELD = tuple(range(8))
Q = TOY["chunk_size"]
TOPK = TOY["sa_config"]["topk"]
#: the comparison's limit at the toy widths: narrow sums average less
#: rounding away than the real ones (the real limit is the family
#: file's SHARE_OF_SPREAD); the toy reads 1.6 to 2.4% over the
#: dispatches below. The slacks likewise: a toy score is a sum of 4
#: heads of 16 columns, and bfloat16 operands move it by up to 0.03
TOY_LIMIT = 0.045
TOY_KEY_SLACK = 0.05


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.keye_vl2 import checkpoint, network
    cfg = network.KeyeVL2Config.from_published(TOY)
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


@functools.lru_cache(maxsize=None)
def _program(**arm):
    import jax

    from rnb_tpu.models.keye_vl2 import network
    cfg = network.KeyeVL2Config.from_published(TOY)
    return jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2], interpret=True, **arm))


def run_program(toy, prompts, rows, params=None, **arm):
    """-> (logits a prompt, each prompt's choices as a sample keeps them
    (``network.request_choices``), the counters)."""
    from rnb_tpu.models.keye_vl2 import network
    tokens, meta, offsets = pack(prompts, rows)
    logits, chosen, *counts = _program(**arm)(
        toy["params"] if params is None else params, toy["slots"], tokens,
        meta)
    chosen = tuple(np.asarray(c) for c in chosen)
    kept = [network.request_choices(toy["cfg"], chosen, o * Q, len(p))
            for o, p in zip(offsets, prompts)]
    return np.asarray(logits)[:len(prompts)], kept, \
        [np.asarray(c) for c in counts]


def sets_of(kept, count):
    """A sample's sets -> bool (layers, count, count): query t reads key
    s of its own request."""
    from rnb_tpu.ops import indexed
    first = int(kept["first"])
    return indexed.unpack_sets(kept["key_sets"])[:, :, first:first + count]


def run_reference(toy, prompt, kept=None, **kwargs):
    import jax
    given = {} if kept is None else {
        "forced": kept["chosen"],
        "forced_sets": list(sets_of(kept, len(prompt)))}
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(toy["read"], prompt, held=HELD,
                                        **given, **kwargs)


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

#: dispatches of 32 rows (512 tokens): requests under ``topk`` 48 and
#: over it in one pool, one that ends inside a row, one of exactly
#: ``topk`` tokens, pad rows behind
DISPATCHES = {"under_and_over": [150, 30, 230], "at_topk": [48, 49, 200],
              "one_fills_it": [505]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_matches_the_reference(toy, case):
    """Logits inside the toy's limit with the program's choices given;
    the reference's own sets differ from the program's in near-ties
    alone: the weakest key the program chose lies within the toy's slack
    of the reference's topk-th best, and no set has another size, a key
    of the future or of another request."""
    prompts = prompts_of(DISPATCHES[case], seed=len(case))
    logits, kept, _ = run_program(toy, prompts, 32)
    want, differ, pairs = [], 0, 0
    for prompt, choices in zip(prompts, kept):
        count = len(prompt)
        assert choices["chosen"].shape == (3, count, 2)
        sets = sets_of(choices, count)
        at = np.arange(count)
        assert (sets.sum(-1) == np.minimum(at + 1, TOPK)).all()
        assert not (sets & (at[None, :] > at[:, None])).any()
        # nothing outside the request's own keys
        assert int(np.unpackbits(choices["key_sets"].view(np.uint8)).sum()) \
            == int(sets.sum())
        out = run_reference(toy, prompt, choices, keep_sets=True)
        assert not np.asarray(out["key_bad"]).any()
        assert float(np.asarray(out["key_shortfall"]).max()) < TOY_KEY_SLACK
        assert float(np.asarray(out["shortfall"]).max()) < 0.02
        own = np.asarray(out["key_sets"])
        assert (np.asarray(out["key_differ"])
                == (own != sets).sum(-1)).all()
        differ += int((own != sets).sum())
        pairs += int(sets.sum())
        # a query with topk keys or fewer reads them all, in both
        assert (own[:, :TOPK] == sets[:, :TOPK]).all()
        want.append(np.asarray(out["logits"]))
    verdict = compare(logits, np.stack(want), TOY_LIMIT)
    assert verdict["ok"], verdict
    assert differ < 0.05 * pairs, (differ, pairs)


def test_packing_is_invisible_and_positions_restart(toy):
    """A request's logits and choices do not depend on what it is packed
    beside, nor on where in the pool it lies."""
    a, b = prompts_of([150, 90], seed=5)
    alone, kept_alone, _ = run_program(toy, [a], 32)
    behind, kept_behind, _ = run_program(toy, [b, a], 32)
    assert np.array_equal(kept_alone[0]["chosen"], kept_behind[1]["chosen"])
    assert np.array_equal(sets_of(kept_alone[0], 150),
                          sets_of(kept_behind[1], 150))
    assert int(kept_behind[1]["first"]) == 6 * Q
    assert np.abs(alone[0] - behind[1]).max() < 1e-5


# -- the sets ---------------------------------------------------------------------


def planted(rng, tokens, heads=4, dim=16):
    """Index operands with equal scores planted: keys that repeat."""
    import jax.numpy as jnp
    q = jnp.asarray(rng.normal(size=(tokens, heads, dim)), jnp.bfloat16)
    k = np.asarray(rng.normal(size=(tokens, dim)), np.float32)
    for copy, of in ((70, 65), (66, 65), (90, 65), (200, 130), (131, 130)):
        k[copy] = k[of]
    w = jnp.asarray(rng.normal(size=(tokens, heads)), jnp.float32)
    return q, jnp.asarray(k, jnp.bfloat16), w


@pytest.mark.parametrize("topk", [40, 8, 300])
def test_a_set_is_the_topk_keys_of_a_sort(topk):
    """``ops/indexed``: the scores against NumPy's, the sets against a
    sort by (score descending, position ascending) of each query's own
    keys: exactly ``min(t + 1, topk)``, none of the future, none of
    another request, equal scores to the lower key."""
    import jax.numpy as jnp

    from rnb_tpu.ops import indexed
    rng = np.random.default_rng(topk)
    row_start = jnp.asarray([0, 0, 0, 3, 3, 5, 5, 7], jnp.int32)
    row_tokens = jnp.asarray([32, 32, 20, 32, 10, 32, 32, 0], jnp.int32)
    tokens = 8 * 32
    start, valid = indexed.token_table(row_start, row_tokens, 32)
    q, k, w = planted(rng, tokens)
    keys = np.asarray(indexed.index_keys(q, k, w, start, interpret=True))
    at = np.arange(tokens)
    mine = (at[None, :] <= at[:, None]) \
        & (at[None, :] >= np.asarray(start)[:, None])
    scores = np.einsum("th,ths->ts", np.asarray(w), np.maximum(np.einsum(
        "thd,sd->ths", np.asarray(q, np.float32),
        np.asarray(k, np.float32)), 0))
    # the sort key's order is the float's, what may not be read lowest
    assert (keys[~mine] == indexed.LOWEST).all()
    back = np.where(keys >= 0, keys, keys ^ 0x7FFFFFFF) \
        .astype(np.int32).view(np.float32)
    assert np.abs(np.where(mine, back - scores, 0)).max() < 1e-4
    position = jnp.asarray(at - np.asarray(start), jnp.int32)
    tau, cut = indexed.thresholds(jnp.asarray(keys), position, topk,
                                  interpret=True)
    mask = np.asarray(indexed.chosen_mask(jnp.asarray(keys), tau, cut,
                                          start))
    for t in range(tokens):
        order = sorted(np.nonzero(mine[t])[0],
                       key=lambda s: (-int(keys[t, s]), s))[:topk]
        want = np.zeros(tokens, bool)
        want[order] = True
        assert (want == mask[t]).all(), t
    if topk == 40:
        # the planted repeats put equal scores at some query's cut
        assert (np.asarray(cut) < tokens).any()


#: pools for the thresholds' walk at 8 queries a step and 128 keys a
#: chunk, rows of 4 tokens: (rows, the rows that open a request, pad rows
#: behind, ``topk``, repeated index keys (copy, of)). Requests begin
#: inside a chunk and inside a query step (row 75: token 300; row 161:
#: token 644), pad rows are requests of their own, and the repeats put
#: equal scores at some query's cut in every request
WALKS = {
    "one_request": (128, [0], 0, 40,
                    ((70, 65), (66, 65), (90, 65), (200, 130), (131, 130))),
    "two_requests": (256, [0, 75], 6, 40,
                     ((70, 65), (66, 65), (90, 65), (420, 400), (401, 400),
                      (900, 650), (651, 650))),
    "three_requests": (256, [0, 33, 161], 3, 24,
                       ((70, 65), (66, 65), (300, 210), (211, 210),
                        (800, 700), (701, 700), (760, 700))),
    "over_topk_nowhere": (128, [0, 20, 50, 90], 8, 300, ((70, 65),)),
}


def a_walk(name):
    """-> (sort keys (T, T), each token's request's first token, its
    index inside the request, ``topk``)."""
    import jax.numpy as jnp

    from rnb_tpu.ops import indexed
    rows, firsts, pads, topk, repeats = WALKS[name]
    firsts = firsts + list(range(rows - pads, rows))
    rng = np.random.default_rng(len(name))
    tokens = rows * 4
    row_start = jnp.asarray([max(f for f in firsts if f <= r)
                             for r in range(rows)], jnp.int32)
    row_tokens = jnp.asarray([4] * (rows - pads) + [0] * pads, jnp.int32)
    start, _ = indexed.token_table(row_start, row_tokens, 4)
    q = jnp.asarray(rng.normal(size=(tokens, 4, 16)), jnp.bfloat16)
    k = np.asarray(rng.normal(size=(tokens, 16)), np.float32)
    for copy, of in repeats:
        k[copy] = k[of]
    w = jnp.asarray(rng.normal(size=(tokens, 4)), jnp.float32)
    keys = indexed.index_keys(q, jnp.asarray(k, jnp.bfloat16), w, start,
                              interpret=True)
    return keys, np.asarray(start), np.arange(tokens) - np.asarray(start), \
        topk


def small_steps(monkeypatch):
    """8 queries a step and 128 keys a chunk for the walk, 32 and 256
    for the parent's: pools of a few hundred tokens are many of each."""
    import keye_parent
    from rnb_tpu.ops import indexed
    monkeypatch.setattr(indexed, "_SELECT_TILE_Q", 8)
    monkeypatch.setattr(indexed, "_SELECT_CHUNK", 128)
    monkeypatch.setattr(keye_parent, "SELECT_TILE_Q", 32)
    monkeypatch.setattr(keye_parent, "SELECT_CHUNK", 256)


def visits_by_hand(start, position, topk, tile, chunk):
    """(the chunks a count visits, those from key 0 to the diagonal),
    a query step at a time."""
    walked = whole = 0
    for first in range(0, len(start), tile):
        last = first + tile - 1
        whole += last // chunk + 1
        if (position[first:first + tile] + 1 >= topk).any():
            walked += last // chunk - start[first] // chunk + 1
    return [walked, whole]


@pytest.mark.parametrize("pool", sorted(WALKS))
def test_the_walk_gives_the_parents_thresholds_to_the_bit(pool,
                                                          monkeypatch):
    """One loop from the chunk of the step's first request's first key
    to the diagonal's, sums under the lanes, ``over`` and ``reach`` the
    counts of the last candidate refused and taken, nothing counted in
    a step no query of which has ``topk`` keys: ``tau`` and ``cut``,
    and so which queries are tied, are the parent's (a ``lax.cond`` a
    chunk from key 0, 34 counts: ``tests/keye_parent.py``) bit for
    bit; the sets are a sort's; the walk's chunk visits are a count by
    hand."""
    import jax.numpy as jnp

    import keye_parent
    from rnb_tpu.ops import indexed
    small_steps(monkeypatch)
    keys, start, position, topk = a_walk(pool)
    tokens = len(start)
    at = jnp.asarray(position, jnp.int32)
    tau, cut = (np.asarray(x) for x in indexed.thresholds(
        keys, at, topk, interpret=True))
    want_tau, want_cut = (np.asarray(x) for x in keye_parent.thresholds(
        keys, at, topk, interpret=True))
    assert tau.dtype == want_tau.dtype and cut.dtype == want_cut.dtype
    assert np.array_equal(tau, want_tau) and np.array_equal(cut, want_cut)
    # the sets: a stable sort by descending key keeps the lower position
    # of equal keys first, and what may not be read (LOWEST) last
    order = np.argsort(-np.asarray(keys, np.int64), axis=1, kind="stable")
    want = np.zeros((tokens, tokens), bool)
    for t in range(tokens):
        want[t, order[t, :min(position[t] + 1, topk)]] = True
    mask = np.asarray(indexed.chosen_mask(
        keys, jnp.asarray(tau), jnp.asarray(cut), jnp.asarray(start)))
    assert (mask == want).all()
    lo, hi = (np.asarray(x) for x in indexed.select_walk(at, topk))
    assert np.asarray(indexed.chunk_visits(at, topk)).tolist() \
        == [int((hi + 1 - lo).sum()), int((hi + 1).sum())] \
        == visits_by_hand(start, position, topk, 8, 128)
    tied = cut < tokens
    if pool == "over_topk_nowhere":
        # every query reads everything: no step counts, none is tied
        assert (lo == hi + 1).all() and (tau == indexed.LOWEST).all() \
            and not tied.any()
        return
    # steps that count nothing, steps that begin behind key 0, a step
    # that holds the end of a request and the head of the next, a query
    # with exactly topk keys (its tau is its lowest key's), ties in
    # every request
    assert (lo == hi + 1).any() and (tau[position + 1 < topk]
                                     == indexed.LOWEST).all()
    assert (tau[position + 1 == topk] > indexed.LOWEST).all()
    requests = np.unique(start[position + 1 > topk])
    assert len(requests) == len(WALKS[pool][1])
    assert all(tied[start == first].any() for first in requests)
    if len(requests) > 1:
        assert ((lo > 0) & (lo <= hi)).any()
        straddles = (start[::8] != start[7::8]) & (lo <= hi)
        assert straddles.any()


@pytest.mark.parametrize("case", ["few_values", "all_equal", "sign_only"])
def test_the_walk_under_keys_that_are_mostly_ties(case, monkeypatch):
    """Sort keys made by hand, most of them equal: few values (every
    query past ``topk`` is tied), one value (``tau`` is it, the cut is
    the ``topk``-th position), the values -1 and the largest int32 (a
    ``tau`` with no 0 bit under its sign: ``over`` is then the sign's
    own count, or none's) — over two requests, the second from inside a
    chunk, with a step whose queries all read everything and steps
    whose walk begins behind key 0. ``tau``, ``cut`` and which queries
    are tied are the parent's."""
    import jax.numpy as jnp

    import keye_parent
    from rnb_tpu.ops import indexed
    small_steps(monkeypatch)
    tokens, second, topk = 512, 200, 24
    rng = np.random.default_rng(len(case))
    at = np.arange(tokens)
    start = np.where(at < second, 0, second)
    mine = (at[None, :] <= at[:, None]) & (at[None, :] >= start[:, None])
    values = {"few_values": rng.integers(-3, 4, (tokens, tokens)),
              "all_equal": np.full((tokens, tokens), 7),
              "sign_only": rng.choice([-1, np.iinfo(np.int32).max],
                                      (tokens, tokens))}[case]
    keys = jnp.asarray(np.where(mine, values, indexed.LOWEST), jnp.int32)
    position = jnp.asarray(at - start, jnp.int32)
    tau, cut = (np.asarray(x) for x in indexed.thresholds(
        keys, position, topk, interpret=True))
    want_tau, want_cut = (np.asarray(x) for x in keye_parent.thresholds(
        keys, position, topk, interpret=True))
    assert np.array_equal(tau, want_tau) and np.array_equal(cut, want_cut)
    chooses = at - start + 1 > topk
    assert ((cut < tokens) == (want_cut < tokens)).all() \
        and (cut < tokens)[chooses].mean() > 0.9 \
        and not (cut < tokens)[~chooses].any()
    lo, hi = (np.asarray(x) for x in indexed.select_walk(position, topk))
    assert (lo == hi + 1).sum() == 4 and lo[second // 8 + 3:].min() == 1
    if case == "all_equal":
        assert (tau[chooses] == 7).all() \
            and (cut[chooses] == start[chooses] + topk - 1).all()
    if case == "sign_only":
        assert set(tau[chooses].tolist()) <= {-1, np.iinfo(np.int32).max}


def inv_freq(dim, theta=1e7):
    return (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)) \
        .astype(np.float32)


def a_pool(rows, firsts, hq, hk, dim, topk=40, qlen=32):
    """A seeded pool of ``rows`` rows of ``qlen`` tokens, a request from
    each of ``firsts``, with equal index scores planted: -> (what
    ``indexed.indexed_attention`` takes, in its order; the explicit mask
    of the sets; each token's index inside its request)."""
    import jax.numpy as jnp

    from rnb_tpu.ops import banded, indexed
    rng = np.random.default_rng(rows + hq)
    tokens = rows * qlen
    row_start = jnp.asarray([max(f for f in firsts if f <= r)
                             for r in range(rows)], jnp.int32)
    start, _ = indexed.token_table(
        row_start, jnp.full((rows,), qlen, jnp.int32), qlen)
    keys = indexed.index_keys(*planted(rng, tokens), start, interpret=True)
    position = jnp.arange(tokens, dtype=jnp.int32) - start
    tau, cut = indexed.thresholds(keys, position, topk, interpret=True)
    mask = np.asarray(indexed.chosen_mask(keys, tau, cut, start))
    # q as its product writes it: float32, far from unit norm
    q = jnp.asarray(rng.normal(size=(tokens, hq * dim)) * 3, jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(tokens, hk * dim)), jnp.bfloat16)
            for _ in range(2))
    weight = jnp.asarray(1 + 0.2 * rng.normal(size=dim), jnp.bfloat16)
    tables = banded.band_tables(row_start, qlen, inv_freq(dim))
    return (q, k, v, keys, tau, cut, weight, tables, 1e-6, True), mask, \
        np.asarray(position)


#: (rows of 32 tokens, the rows that open a request, query heads,
#: key-value heads, their width): PR 46's two pools at the toy's heads (a
#: pool of one tile; one of 8 x 4 tiles of 256 x 512, with requests that
#: start inside a key tile), and 32 / 4 heads over one, two and three
#: requests with pad rows behind (a pad row is a request of its own) and
#: at the published head width
POOLS = {"toy_one_tile": (8, [0, 3, 5, 7], 4, 2, 16),
         "toy_8x4_tiles": (64, [0, 27, 28], 4, 2, 16),
         "32_4_one_request": (16, [0, 15], 32, 4, 16),
         "32_4_two_requests": (16, [0, 9, 14, 15], 32, 4, 16),
         "32_4_three_requests": (32, [0, 9, 21, 30, 31], 32, 4, 16),
         "32_4_of_128": (8, [0, 5, 7], 32, 4, 128)}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_attention_kernel_reads_the_sets_alone(pool):
    """``indexed_attention`` against softmax over an explicit mask (of q
    through the passes the kernel's first lines replaced), and the sets
    it writes as bits beside its result against the mask: the same sets,
    their sizes and the tiles they reach."""
    import jax.numpy as jnp

    import keye_parent
    from rnb_tpu.ops import indexed
    rows, firsts, hq, hk, dim = POOLS[pool]
    operands, mask, position = a_pool(rows, firsts, hq, hk, dim)
    q, k, v, _, _, cut, weight, tables = operands[:8]
    tokens, topk = rows * 32, 40
    out, sets = indexed.indexed_attention(*operands)
    assert out.shape == (tokens, hq * dim) and out.dtype == jnp.bfloat16
    qs = keye_parent.replaced_passes(
        q.reshape(rows, 32, -1), weight,
        jnp.asarray(position.reshape(rows, 32)), inv_freq(dim), 1e-6,
        jnp.bfloat16)
    s = np.einsum("tgpd,sgd->tgps",
                  np.asarray(qs, np.float32).reshape(tokens, hk, -1, dim),
                  np.asarray(k, np.float32).reshape(tokens, hk, dim))
    s = np.where(mask[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("tgps,sgd->tgpd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float32).reshape(tokens, hk, dim))
    assert np.abs(np.asarray(out, np.float32)
                  - want.reshape(tokens, hq * dim)).max() < 0.02
    tile_q, tile_k = indexed.attention_tiles(tokens)
    assert sets.shape == (tokens, tile_k) and sets.dtype == jnp.uint32
    assert (indexed.unpack_sets(sets)[:, :tokens] == mask).all()
    assert not indexed.unpack_sets(sets)[:, tokens:].any()
    chose, reached = indexed.count_sets(sets, tile_q)
    assert (np.asarray(chose) == np.minimum(position + 1, topk)).all()
    tiles = mask.reshape(tokens // tile_q, tile_q, tokens // tile_k,
                         tile_k).any(axis=(1, 3))
    assert int(reached) == int(tiles.sum())
    assert int(tiles.sum()) <= indexed.causal_tiles(tokens) \
        == {8: 1, 16: 2, 32: 6, 64: 20}[rows]
    if rows == 64:
        # the last request's later query tiles do not reach the first
        # key tile: another request's keys
        assert not tiles[4:, 0].any() and 12 <= int(tiles.sum()) < 20


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_kernel_is_the_parents_passes_and_kernel_to_the_bit(pool):
    """From q as its product wrote it to ``o``'s operand: q's first
    lines inside the kernel are ``rms_norm`` + ``ops/rope.rotate`` + the
    scale + the rounding, and a step's one mask under each head's own
    scores is the parent's (a key-value head a step, the mask laid under
    itself a head: ``tests/keye_parent.py``) — the same float32
    operations in the same order, so the same bits in the result and in
    the sets, on pools with equal scores at some query's cut."""
    import jax

    import keye_parent
    from rnb_tpu.ops import indexed
    rows, firsts, hq, hk, dim = POOLS[pool]
    operands, _, _ = a_pool(rows, firsts, hq, hk, dim)
    assert (np.asarray(operands[5]) < rows * 32).any()
    # under one ``jit`` each: in one program the CPU's compiler has a
    # multiply next to an add to contract, on both sides alike
    q, k, v, keys, tau, cut, weight, tables = operands[:8]
    want, want_sets = jax.jit(lambda *a: keye_parent.indexed_attention(
        *a, tables, 1e-6, True, qlen=32, inv_freq=inv_freq(dim)))(
        q, k, v, keys, tau, cut, weight)
    got, sets = jax.jit(lambda *a: indexed.indexed_attention(
        *a, tables, 1e-6, True))(q, k, v, keys, tau, cut, weight)
    assert got.dtype == want.dtype and sets.dtype == want_sets.dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    assert np.array_equal(np.asarray(sets), np.asarray(want_sets))


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_the_toy_stack_serves_the_parents_logits_to_the_bit(
        toy, case, monkeypatch):
    """The whole toy stack with PR 54's kernel and with the parent's
    passes and kernel in its place: the same logits, the same sets, the
    same experts and the same counters, bit for bit."""
    import jax

    import keye_parent
    from rnb_tpu.models.keye_vl2 import network
    from rnb_tpu.ops import indexed
    prompts = prompts_of(DISPATCHES[case], seed=len(case))
    logits, kept, counts = run_program(toy, prompts, 32)
    monkeypatch.setattr(indexed, "indexed_attention", functools.partial(
        keye_parent.indexed_attention, qlen=Q,
        inv_freq=toy["cfg"].inv_freq()))
    tokens, meta, _ = pack(prompts, 32)
    want, chosen, *want_counts = jax.jit(lambda p, s, t, m: network.forward(
        toy["cfg"], p, s, t, m[0], m[1], m[2], interpret=True))(
        toy["params"], toy["slots"], tokens, meta)
    assert np.array_equal(logits, np.asarray(want)[:len(prompts)])
    for got, (offset, prompt) in zip(kept, zip(pack(prompts, 32)[2],
                                              prompts)):
        theirs = network.request_choices(
            toy["cfg"], tuple(np.asarray(c) for c in chosen), offset * Q,
            len(prompt))
        assert sorted(got) == sorted(theirs)
        for name in got:
            assert np.array_equal(got[name], theirs[name]), name
    for got, theirs in zip(counts, want_counts):
        assert np.array_equal(got, np.asarray(theirs))


def test_under_topk_the_reference_is_plain_causal_attention(toy):
    """In the reference a query with ``topk`` keys or fewer reads every
    key at or before it: its attention equals the same function with a
    ``topk`` no query reaches — plain causal grouped-query attention —
    bit for bit; a query past ``topk`` differs."""
    import jax
    import jax.numpy as jnp
    prompt, = prompts_of([120], seed=9)
    read = toy["read"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(read("top.embed"), jnp.asarray(prompt), axis=0)
        h = reference.rms_norm(x, read("l0.attn_norm"), 1e-6)
        w = {t: read("l0.%s" % t) for t in reference.ATTENTION}
        positions3 = jnp.broadcast_to(jnp.arange(120), (3, 120))
        chosen, own, _, bad, _ = reference.attention(TOY, w, h, positions3)
        dense, every, _, _, _ = reference.attention(TOY, w, h, positions3,
                                                    topk=120)
    chosen, dense = np.asarray(chosen), np.asarray(dense)
    assert np.array_equal(chosen[:TOPK], dense[:TOPK])
    assert not np.array_equal(chosen[TOPK:], dense[TOPK:])
    assert (np.asarray(every) == np.tril(np.ones((120, 120), bool))).all()
    assert (np.asarray(own).sum(-1)
            == np.minimum(np.arange(120) + 1, TOPK)).all()
    assert not np.asarray(bad).any()


def test_multimodal_rotary_with_equal_components_is_the_1d_rotary(toy):
    """The reference's sectioned rotary (three position components cut
    over the frequency pairs by ``mrope_section``) equals
    ``ops/rope.rotate`` where the components are equal, a text prompt's;
    and does not where they differ."""
    import jax.numpy as jnp

    from rnb_tpu.ops import rope
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(40, 4, 16)), jnp.float32)
    index = np.arange(40)
    text = reference.mrope(TOY, x, np.broadcast_to(index, (3, 40)))
    program = rope.rotate(x[None], jnp.asarray(index)[None],
                          toy["cfg"].inv_freq())[0]
    assert np.abs(np.asarray(text) - np.asarray(program)).max() < 1e-6
    image = np.stack([index, index // 5, index % 5])
    turned = np.asarray(reference.mrope(TOY, x, image))
    assert np.abs(turned - np.asarray(program)).max() > 0.1
    # pairs 0-1 turn by the temporal component (sections 2, 3, 3 of the
    # 8 pairs): those columns agree, the others do not
    same = np.abs(turned - np.asarray(program)).max(axis=(0, 1)) < 1e-6
    assert same.tolist() == [True] * 2 + [False] * 6 + [True] * 2 \
        + [False] * 6


# -- the experts: all held ---------------------------------------------------------


def test_every_expert_is_held_and_the_shares_add_up(toy):
    """The identity for slots, no capacity for the pair buffers, every
    (valid token, choice) pair served; and the layer equals the sum of
    the parts two halves of the experts give: nothing is left out."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.keye_vl2 import network
    from rnb_tpu.ops import moe
    cfg = toy["cfg"]
    assert np.asarray(toy["slots"]).tolist() == list(range(8))
    assert moe.pair_capacity(16384, 8, 128, 128) is None
    prompts = prompts_of([150, 30, 230], seed=3)
    _, kept, counts = run_program(toy, prompts, 32)
    served, gmm_rows = counts[0], counts[1]
    valid = sum(len(p) for p in prompts)
    assert served.shape == (3, 8) and gmm_rows.shape == (3,)
    assert (served.sum(1) == 2 * valid).all()
    ids = np.concatenate([k["chosen"] for k in kept], axis=1)
    assert (served == np.stack([np.bincount(layer.reshape(-1), minlength=8)
                                for layer in ids])).all()
    # the reference over experts 0-3 and over 4-7 adds up to all eight
    prompt = prompts[0]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(toy["read"]("top.embed"), jnp.asarray(prompt), axis=0)
        whole, ids, _ = toy["reference"].experts(toy["read"], 1, x, HELD)
        halves = [toy["reference"].experts(toy["read"], 1, x, half,
                                           forced=ids)[0]
                  for half in (HELD[:4], HELD[4:])]
    assert np.abs(np.asarray(whole)
                  - np.asarray(halves[0] + halves[1])).max() < 1e-5
    assert cfg.num_experts_per_tok == 2 and network.COUNTERS == (
        "expert_served", "gmm_rows", "sparse", "index_tiles",
        "index_chunks")


# -- the counters ---------------------------------------------------------------------


def test_the_counters_are_a_numpy_count(toy):
    lengths = [150, 30, 230]
    prompts = prompts_of(lengths, seed=3)
    _, kept, counts = run_program(toy, prompts, 32)
    sparse, tiles = counts[2], counts[3]
    at = [np.arange(n) + 1 for n in lengths]
    want = [sum(lengths), sum(int((a > TOPK).sum()) for a in at),
            sum(int(a[a > TOPK].sum()) for a in at),
            sum(int((a > TOPK).sum()) * TOPK for a in at)]
    assert sparse.tolist() == [want] * 3
    # the kernel's tiles: a pool of 512 tokens is 2 x 1 tiles of 256 x
    # 512, both on or under the diagonal, both with a chosen key
    assert tiles.tolist() == [[2, 2]] * 3
    # the thresholds' walk: the pool's layout, a count by hand (at the
    # module's 128 queries a step a pool of 512 tokens is 4 steps over
    # one chunk, a query with topk keys to read in each)
    tokens, meta, _ = pack(prompts, 32)
    start = np.repeat(np.asarray(meta[1]) * Q, Q)
    assert counts[4].tolist() == [visits_by_hand(
        start, np.arange(32 * Q) - start, TOPK, 128, 512)] * 3 \
        == [[4, 4]] * 3
    chosen = sum(int(sets_of(k, n)[0][TOPK:].sum())
                 for k, n in zip(kept, lengths))
    assert chosen == want[3]
    # all causal keys in the place of the sets: the control reads them
    # all and says so
    _, kept_all, counts_all = run_program(toy, prompts, 32, select="causal")
    assert counts_all[2][0].tolist() == want[:3] + [want[2]]
    # and its thresholds count nothing: no query has the pool's 512 keys
    assert counts_all[4].tolist() == [[0, 4]] * 3
    assert (sets_of(kept_all[2], 230)[0]
            == np.tril(np.ones((230, 230), bool))).all()
    _, kept_recent, _ = run_program(toy, prompts, 32, select="recent")
    late = np.arange(230)[:, None] - np.arange(230)[None, :]
    assert (sets_of(kept_recent[2], 230)[1]
            == ((late >= 0) & (late < TOPK))).all()


# -- the stages, a sample's choices and the check ---------------------------------


def toy_config(layers=4):
    """A toy-width copy of the real configuration's file, of four
    layers: the floor of the family file's ``check_config``. The stage
    below serves ``TOY``'s three, and is checked against three."""
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    config.update(TOY, num_hidden_layers=layers)
    config["published"] = {"num_hidden_layers": 48}
    config["model"] = dict(config["model"], layers=layers)
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 80, "sigma": 0.5,
                                   "min": 20, "max": 120},
                         "long": {"count": 2, "min": 100, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 110
    config["share_of_spread"] = TOY_LIMIT
    config["key_slack"] = TOY_KEY_SLACK
    config["ref_pad"] = 64
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=3, samples=8)
    return config


def serve(tmp_path, **arm):
    """``family_contract.serve``'s one 32-row dispatch under an arm of
    ``network.forward``, a sample a request, the prompts written as the
    run's request files: -> (the stage, its recipe, what
    ``check_outputs`` is handed)."""
    from rnb_tpu.models.keye_vl2 import network
    os.makedirs(tmp_path, exist_ok=True)
    forward = network.forward
    if arm:
        network.forward = functools.partial(forward, **arm)
    try:
        served = contract.serve(CONTRACT, tmp_path)
    finally:
        network.forward = forward
    return served.stage, served.recipe, request_files(served)


def request_files(served):
    files = []
    for i, prompt in enumerate(served.prompts):
        files.append(os.path.join(os.path.dirname(served.recipe),
                                  "short-%03d.npy" % i))
        np.save(files[-1], prompt)
    return {"short_files": files, "long_files": []}


def checked(tmp_path, stage, recipe, inputs):
    import jax
    stage.bind_log_dir(str(tmp_path))
    stage.finalize()
    family = mm.load_family("keye_vl2")
    return family.check_outputs(
        toy_config(layers=TOY["num_hidden_layers"]), None, None, recipe,
        SEED, inputs, jax.devices(),
        types.SimpleNamespace(log_dir=str(tmp_path)))


def the_stage_keeps_both_kinds_of_choice(served):
    """``family_contract.stage_serves``'s entry for this family: the
    stage counts the experts' assignments, the sets and the kernel's
    tiles, and a sample keeps both kinds of choice: the check holds them
    to the reference."""
    from rnb_tpu.telemetry import stage_counter_report
    stage, valid = served.stage, served.valid
    counters = stage.stage_counters()
    assert counters["experts_per_token"] == 2
    assert counters["expert_served"].shape == (3, 8)
    assert counters["expert_served"].sum() == 3 * 2 * valid
    assert counters["sparse"].tolist()[:2] == [3 * valid, 3 * (102 + 182)]
    assert counters["index_tiles"].tolist() == [6, 6]
    # 4 query steps of 128 over one chunk of 512 keys, a query with
    # topk keys in each: 3 layers
    assert counters["index_chunks"].tolist() == [12, 12]
    lines, fields = stage_counter_report([counters, counters])
    assert lines[0] == "Tokens: valid=%d shipped=%d" % (2 * valid,
                                                        2 * 32 * Q)
    assert lines[1].startswith("Experts: assignments=%d held=%d "
                               % (12 * valid, 12 * valid))
    assert lines[2].startswith("Sparse: queries=%d selecting=%d "
                               % (6 * valid, 6 * 284))
    assert lines[2].endswith(" tiles_chosen=12 tiles_causal=12"
                             " chunks_walked=24 chunks_to_diagonal=24")
    assert (fields["sparse_tiles_chosen"],
            fields["sparse_tiles_causal"]) == (12, 12)
    assert (fields["sparse_chunks_walked"],
            fields["sparse_chunks_to_diagonal"]) == (24, 24)
    assert fields["experts_held"] == fields["experts_assignments"]
    verdict = checked(os.path.dirname(served.recipe), stage, served.recipe,
                      request_files(served))
    assert len(stage._samples) == 3 and not stage._fetching \
        and not stage._sampled
    first = stage._samples[0]
    assert first["key_sets"].shape == (3, 150, 32 * Q)
    assert first["key_sets"].dtype == np.uint32 and first["first"] == 0
    assert int(stage._samples[2]["first"]) == 12 * Q
    assert verdict["ok"], verdict
    assert verdict["samples"] == 3 and verdict["key_bad"] == 0
    assert 0 < verdict["key_shortfall_max"] < TOY_KEY_SLACK
    assert verdict["key_differ"] > 0


#: ``tests/test_keye_vl2_cell.py`` runs it
CONTRACT = contract.Family(
    name="keye_vl2", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts:", " gmm_rows=", "Sparse: queries=",
          " tiles_chosen=", " chunks_walked=", " chunks_to_diagonal="),
    scopes=("/attn/select/index/", "/attn/kernel/"),
    sample_fields=("tokens", "logits", "chosen", "key_sets", "first"),
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "held_assignment_pct.bulk": "[100, 100]",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "sparse_query_pct.bulk": "(0, 100)",
        "selected_key_pct.bulk": "(0, 100)",
        "chosen_tile_pct.bulk": "(0, 100]",
        "select_chunk_walk_pct.bulk": "(0, 100]",
        "gmm_row_fill_pct.bulk": "(0, 100]"},
    not_from_a_cpu="roofline|util|_ms_per_|busy_pct",
    stage=contract.Stage(
        lengths=(150, 30, 230), rows=32, row_buckets=(32,), samples=8,
        scopes=("/attn/", "/attn/select/", "/attn/select/index/",
                "/attn/kernel/", "/experts/", "/head/", "/embed/"),
        chosen_shape=(3, 150, 2),
        also=the_stage_keeps_both_kinds_of_choice),
    # as stated inside the limit and both slacks, each of the four
    # controls outside one of them
    control=contract.Control(
        lengths="150,30,230",
        outside=("index_float8", "all_causal_keys", "recent_keys",
                 "layers_float8"),
        reads={("as_stated", "key_shortfall_max"):
               "(-inf, %r)" % TOY_KEY_SLACK,
               ("index_float8", "key_shortfall_max"):
               "(%r, inf)" % TOY_KEY_SLACK,
               ("all_causal_keys", "share_of_spread"): "(0.2, inf)",
               ("recent_keys", "share_of_spread"): "(0.2, inf)"}))


TAMPERINGS = {
    # query 100 of the third request loses one of its keys
    "a_key_less": lambda sets, first: _clear(sets, 0, 100),
    # ... and reads a key of the request in front instead
    "another_requests_key": lambda sets, first: _set(
        _clear(sets, 0, 100), 0, 100, first - 5),
    # ... and reads a key of the future instead
    "a_future_key": lambda sets, first: _set(
        _clear(sets, 0, 100), 0, 100, first + 101),
}


def _word(sets, key):
    words = sets.shape[-1]
    return key % words, np.uint32(1) << np.uint32(key // words)


def _clear(sets, layer, query):
    """One of the query's keys less: the lowest bit of its first word
    that holds any."""
    word = int(np.nonzero(sets[layer, query])[0][0])
    sets[layer, query, word] &= sets[layer, query, word] - np.uint32(1)
    return sets


def _set(sets, layer, query, key):
    word, bit = _word(sets, key)
    assert not sets[layer, query, word] & bit
    sets[layer, query, word] |= bit
    return sets


@pytest.fixture(scope="module")
def served_as_stated(tmp_path_factory):
    """The stage served once for the tampered sets below, its samples
    collected: -> (their directory, the stage, its recipe, the run's
    request files)."""
    tmp_path = tmp_path_factory.mktemp("served")
    stage, recipe, inputs = serve(tmp_path)
    stage._send_samples()
    stage._collect_samples()
    return tmp_path, stage, recipe, inputs


@pytest.mark.parametrize("how", sorted(TAMPERINGS))
def test_the_check_refuses_a_tampered_set(how, served_as_stated):
    tmp_path, stage, recipe, inputs = served_as_stated
    sample = stage._samples[2]
    as_served = sample["key_sets"]
    sample["key_sets"] = TAMPERINGS[how](as_served.copy(),
                                         int(sample["first"]))
    try:
        verdict = checked(tmp_path, stage, recipe, inputs)
    finally:
        sample["key_sets"] = as_served
    assert not verdict["ok"] and verdict["key_bad"] >= 1, verdict
    assert "another size" in verdict["why"]


@pytest.mark.parametrize("arm,why", [
    ({"select": "causal"}, "another size"),
    ({"select": "recent"}, "topk-th best score"),
    ({"index_bits": (4, 3)}, "topk-th best score"),
], ids=["all_causal_keys", "recent_keys", "index_float8"])
def test_the_check_refuses_the_attention_controls(arm, why, tmp_path):
    """What the timed path would serve under each control goes through
    the run's own check and is refused: all causal keys are sets of
    another size, the latest ``topk`` and a float8 indexer's are sets
    whose weakest key lies far under the reference's ``topk``-th."""
    verdict = checked(tmp_path, *serve(tmp_path, **arm))
    assert not verdict["ok"] and why in verdict["why"], verdict


def test_the_lower_precision_and_attention_controls(toy):
    """Against the reference *on its own sets*: as stated inside the
    limit; every matrix through float8, all causal keys and the latest
    ``topk`` outside it, the last two by far."""
    prompts = prompts_of([150, 30, 230], seed=2)
    logits, kept, _ = run_program(toy, prompts, 32)
    want = np.stack([np.asarray(run_reference(toy, p, k)["logits"])
                     for p, k in zip(prompts, kept)])
    assert compare(logits, want, TOY_LIMIT)["ok"]
    readings = {}
    for name, arm in (("causal", {"select": "causal"}),
                      ("recent", {"select": "recent"})):
        got, _, _ = run_program(toy, prompts, 32, **arm)
        readings[name] = compare(got, want, TOY_LIMIT)
    got, kept8, _ = run_program(toy, prompts, 32,
                                params=through_float8(toy["params"]))
    want8 = np.stack([np.asarray(run_reference(toy, p, k)["logits"])
                      for p, k in zip(prompts, kept8)])
    readings["float8"] = compare(got, want8, TOY_LIMIT)
    assert not any(v["ok"] for v in readings.values()), readings
    assert readings["causal"]["share_of_spread"] > 0.2
    assert readings["recent"]["share_of_spread"] > 0.2


# -- the operation counts ---------------------------------------------------------


def real_config():
    with open(os.path.join(REPO, REAL)) as f:
        return json.load(f)


def test_operation_counts_agree_with_a_count_by_hand():
    from rnb_tpu.models.keye_vl2 import flops, network
    family = mm.load_family("keye_vl2")
    config = real_config()
    layers = config["num_hidden_layers"]
    cfg = network.KeyeVL2Config.from_published(
        family.published_keys(config))
    # by hand, from the published widths
    proj = 2 * (2048 * 4096 * 2 + 2048 * 512 * 2)
    assert flops.attention_proj_flops_per_token(cfg) == proj \
        == family.attention_proj_flops(config)
    index = 2 * 2048 * (1024 + 64 + 16)
    assert flops.indexer_proj_flops_per_token(cfg) == index
    assert flops.indexer_score_flops_per_token(cfg, 1000) \
        == 2 * 16 * 64 * 1000
    assert family.indexer_flops(config, 1000) == index + 2048 * 1000
    assert flops.attention_score_flops_per_token(cfg, 2048) \
        == 4 * 2048 * 4096 == family.attention_read_flops(config, 2048)
    assert flops.expert_flops(cfg) == 6 * 2048 * 768 \
        == family.expert_flops(config)
    by_hand = layers * (proj + 4 * 1800 * 4096 + index + 2048 * 5700
                        + 2 * 2048 * 128 + 8 * 6 * 2048 * 768)
    assert flops.flops_per_token(cfg, 5700.0, 1800.0, 8.0) == by_hand \
        == family.flops_per_token(config, 5700.0, 1800.0, 8.0)
    # ISSUE 46's reckoning: four fifths of the queries choose, a mean
    # 1.8k chosen of 5.7k causal keys; some 160 MFLOP a token a layer
    causal, chosen = family.mean_reads(config)
    assert causal == family.mean_context(config)
    assert 5500 < causal < 5900 and 1750 < chosen < 1850
    assert family.request_reads(config, 3000) \
        == (3000 * 3001 // 2, 2048 * 2049 // 2 + 952 * 2048)
    assert family.flops_per_row(config) == 128 * flops.flops_per_token(
        cfg, causal, chosen, 8.0)
    per_layer = family.flops_per_row(config) / 128 / layers
    assert 1.5e8 < per_layer < 1.7e8
    # both callers: scopes.py passes the held assignments, subscopes.py
    # does not
    ops, nbytes = family.mechanism_work(config, "select", 1e6, 80.0)
    assert ops == layers * 1e6 * (index + 2048 * causal)
    assert nbytes == layers * (2 * 2048 * 1104 * 80.0
                               + 1e6 * (2 * 2048 + 4 * 1104))
    assert family.mechanism_work(config, "select", 1e6, 8e6, 80.0) \
        == (ops, nbytes)
    ops, nbytes = family.mechanism_work(config, "indexed_attn", 1e6, 80.0)
    assert ops == layers * 1e6 * 4 * chosen * 4096
    assert nbytes == layers * 1e6 * 2 * (2 * 4096 + 2 * 512)
    gmm_ops, gmm_bytes = family.mechanism_work(config, "gmm", 1e6, 8e6,
                                               80.0)
    assert gmm_ops == 8e6 * 6 * 2048 * 768
    assert gmm_bytes == layers * 2 * 3 * 2048 * 768 * 128 * 80.0 \
        + 8e6 * 4 * (2048 + 768)
    experts_ops, _ = family.mechanism_work(config, "experts", 1e6, 8e6,
                                           80.0)
    assert experts_ops == gmm_ops + layers * 1e6 * 2 * 2048 * 128
    with pytest.raises(ValueError):
        family.mechanism_work(config, "flash", 1e6, 8e6, 80.0)


# -- the five new readers, and PR 56's ------------------------------------------------

#: a reader's scope path, or the two fields of the result it divides
NEW_READERS = {"indexer_ms_per_dispatch.bulk": "attn/select/index",
               "select_roofline_pct.bulk": "attn/select",
               "indexed_attn_ms_per_dispatch.bulk": "attn/kernel",
               "indexed_attn_roofline_pct.bulk": "attn/kernel",
               "chosen_tile_pct.bulk": ("sparse_tiles_chosen",
                                        "sparse_tiles_causal"),
               "select_chunk_walk_pct.bulk": ("sparse_chunks_walked",
                                              "sparse_chunks_to_diagonal")}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_on_a_run_without_its_scope(
        name, tmp_path):
    """No trace, and a trace whose run wrote no scope table or none of
    these scopes (the parent's program): None, not a raise. With the
    scope: the seconds under it."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    # PR 55's cell, an indexer's sets under latent attention, joined the
    # three that read what both families write; PR 56's reader came
    # with both
    joined = ["dots3-note.bulk"] if name in (
        "select_roofline_pct.bulk", "chosen_tile_pct.bulk",
        "indexer_ms_per_dispatch.bulk", "select_chunk_walk_pct.bulk") \
        else []
    assert entry and entry[0]["workloads"] == [CELL] + joined
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "packed attention"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("keye_vl2")
        config = real_config()
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    assert module.read(Facts) is None
    path = NEW_READERS[name]
    if isinstance(path, tuple):
        # the parent's result has neither field: nothing, not a raise
        for part, whole in ((0, 0), (30, 40)):
            setattr(Result, path[0], part)
            setattr(Result, path[1], whole)
            assert module.read(Facts) == (75.0 if whole else None)
        return

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    from benchmarks import subscopes
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5}
    Facts.trace = Trace
    try:
        assert subscopes.seconds_under(Facts, path) is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]": "jit(apply)/jit(main)/attn/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, path) is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]":
             "jit(apply)/jit(main)/%s/pallas_call" % path}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, path) == 0.5
        assert subscopes.seconds_under(Facts, "attn") == 0.5
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
            if row["name"] == "Keye-VL-2.0-30B-A3B":
                return row
    return None


#: the catalog's ``config`` of Keye-VL-2.0-30B-A3B
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_from_published_reads_the_catalog_row():
    """The row's own ``config``, every layer held: the network's sizes
    are the published ones. And a configuration the network does not
    implement is refused by name."""
    from rnb_tpu.models.keye_vl2 import network
    row = catalog_row()
    published = dict(PUBLISHED if row is None else row["config"],
                     chunk_size=128)
    if row is not None:
        assert row["config"] == PUBLISHED
    cfg = network.KeyeVL2Config.from_published(published)
    assert cfg.num_hidden_layers == 48
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.topk,
            cfg.indexer_rotary_dim) == (16, 64, 2048, 32)
    assert (cfg.router_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (128, 8, 768)
    assert cfg.inv_freq().shape == (64,) and cfg.inv_freq()[0] == 1.0
    assert cfg.inv_freq(32).shape == (16,)
    for key, value in (("hidden_act", "gelu"),
                       ("tie_word_embeddings", True),
                       ("attention_bias", True),
                       ("mlp_only_layers", [0]),
                       ("sa_config", dict(published["sa_config"],
                                          indexer_num_kv_heads=2)),
                       ("rope_scaling", dict(published["rope_scaling"],
                                             mrope_section=[16, 24, 16]))):
        with pytest.raises(ValueError, match="not the Keye-VL-2.0"):
            network.KeyeVL2Config.from_published(
                dict(published, **{key: value}))


def test_real_configuration_keeps_the_published_sizes():
    config = real_config()
    entry = mm.config_entry(mm.load(), "keye-vl2-stage0")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    layers = config["num_hidden_layers"]
    assert 4 <= layers <= 6 and config["published"] == {
        "num_hidden_layers": 48}
    row = catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
    for key in ("chunk_size", "indexer", "indexer_precision", "chunk_sizes",
                "sets", "norms", "rotary", "tower", "weights", "precision",
                "prompts", "batch"):
        assert config["assumed"][key], key
    for key in ("indexer", "indexer_precision", "chunk_sizes", "sets",
                "norms", "rotary"):
        assert "NOT CHECKED against the model repository's code" \
            in config["assumed"][key], key
    assert "eight chips as pipeline stages" in config["deployment"]
    assert "all 128 experts" in config["deployment"]
    assert "whole vocabulary" in config["deployment"]
    assert config["size_record"]["projected_gib"] >= 4
    assert config["capacity_why"] and config["capacity_videos_per_chip_s"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    assert family.check_config(dict(
        config, num_hidden_layers=3,
        model=dict(config["model"], layers=3)))
    assert family.held_experts(config) == list(range(128))
    cell = mm.cell(mm.load(), CELL)
    assert cell["config"] == "keye-vl2-stage0" and cell["chips"] == 1 \
        and cell["traffic"] == "bulk"
    # the weights the file states, from the tensor list: ISSUE 46's
    # 625.4 M a layer (18.87 M of attention, 2.26 M of indexer, 0.26 M
    # of router, 603.98 M of experts), 622.3 M of embedding and head
    from rnb_tpu.models.keye_vl2 import checkpoint, network
    cfg = network.KeyeVL2Config.from_published(
        family.published_keys(config))
    specs = checkpoint.tensor_specs(cfg, 128)
    sizes = {group: sum(int(np.prod(spec.shape)) for spec in tensors.values())
             for group, tensors in specs.items()}
    assert abs(sizes["top"] / 1e6 - 622.3) < 0.1
    assert abs(sizes["l0"] / 1e6 - 625.4) < 0.1
    layer = specs["l0"]
    assert sum(int(np.prod(layer[t].shape))
               for t in ("q", "k", "v", "o")) == 18_874_368
    assert sum(int(np.prod(layer[t].shape)) for t in layer
               if t.startswith("index_")) == 2_260_992 + 128
    assert sum(int(np.prod(layer[t].shape))
               for t in ("gate", "up", "down")) == 603_979_776
    held = sum(sizes.values())
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 0.01
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01
    # the prompts of the three sibling cells (32 short, 8 long, one long
    # in eleven), length for length: four configurations on one traffic
    with open(os.path.join(
            REPO, "benchmarks/configs/qwen3-next-l4-ep2.json")) as f:
        sibling = json.load(f)
    assert config["dataset"] == sibling["dataset"]
    lengths = family.prompt_lengths(config)
    assert lengths == mm.load_family("qwen3_next").prompt_lengths(sibling)
    assert len(lengths) == 40
    assert min(lengths.values()) == 4096 and max(lengths.values()) <= 16384
    # and the same files: the vocabulary is the sibling's too
    assert family.dataset_key(config) \
        == mm.load_family("qwen3_next").dataset_key(sibling)
    # an expert's tokens a full dispatch: one rank's own
    assert 128 * 128 * config["num_experts_per_tok"] \
        // config["num_experts"] == 1024


# -- the lowered program ---------------------------------------------------------------


def test_the_kernel_scope_holds_the_kernel_alone(toy):
    """``indexed_attn_roofline_pct.bulk`` divides by the device time
    under ``attn/kernel``: the mixer's seven products (q, k, v, ``o`` and
    the indexer's three) are traced outside that scope, and inside it
    stands one call, the attention kernel's own ``jit``."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.keye_vl2 import network
    from rnb_tpu.ops import banded, rope
    cfg = toy["cfg"]
    start = jnp.zeros(32, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, x: network.attention_mixer(
        cfg, p, x, start, jnp.full(32, Q, jnp.int32),
        rope.pool_positions(start, Q),
        banded.band_tables(start, Q, cfg.inv_freq()), interpret=True))(
        toy["params"]["l1"], jnp.zeros((32, Q, 64), jnp.bfloat16))
    stacks = [(str(eqn.source_info.name_stack), eqn.primitive.name)
              for eqn in jaxpr.eqns]
    products = [stack for stack, name in stacks if name == "dot_general"]
    assert len(products) == 7 and not any("kernel" in s for s in products)
    assert [name for stack, name in stacks if "kernel" in stack] \
        in (["pjit"], ["jit"]), stacks


def test_no_array_of_a_head_axis_between_qs_product_and_os():
    """The real configuration's 128-row program, lowered (nothing is
    compiled or run; the kernels interpreted, their bodies a grid
    step's): q goes from its product to the attention kernel as
    (tokens, 32 x 128) float32 and the kernel's result to ``o``'s
    product as (tokens, 32 x 128) bfloat16 — the head norm, the rotary,
    the scale and the rounding are the kernel's, on slices in VMEM — so
    the program holds no array whose minor axes are 32 or 8 heads of 128
    at all. PR 53's tree held, a layer, q as ``128x128x32x128`` float32
    three times over (``rms_norm``, ``rope.rotate``, the scale) and as
    ``16384x4x8x128`` bfloat16 on both sides of the kernel. (k keeps its
    4 heads' axis through its norm and rotary: an eighth of q's.)"""
    import re

    import test_qwen3_next
    from rnb_tpu.models.keye_vl2 import checkpoint, network
    config = real_config()
    cfg = network.KeyeVL2Config.from_published(
        mm.load_family("keye_vl2").published_keys(config))
    text = test_qwen3_next.lowered_text(
        checkpoint, network, cfg, range(config["num_experts"]), rows=128)
    tokens, width = 128 * cfg.chunk_size, \
        cfg.num_attention_heads * cfg.head_dim
    # what the kernel reads and writes is there, as its neighbours
    # wrote and read it
    assert "tensor<%dx%dxf32>" % (tokens, width) in text
    assert "tensor<%dx%dxbf16>" % (tokens, width) in text
    per = cfg.num_attention_heads // cfg.num_key_value_heads
    assert sorted(set(re.findall(
        r"tensor<[\dx]*x(?:%d|%d)x%dx(?:f32|bf16)>"
        % (cfg.num_attention_heads, per, cfg.head_dim), text))) == []


# -- compiled for the chip -----------------------------------------------------------


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


@pytest.mark.parametrize("rows", [128, 80])
@pytest.mark.parametrize("piece", ["scores", "thresholds", "attention"])
def test_the_kernels_compile_at_the_published_widths(piece, rows, one_chip):
    """Each kernel of ``ops/indexed`` over the largest row bucket and
    over a smaller one (80 rows: 20 key tiles), compiled for a described
    v5e (nothing runs)."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import indexed
    config = real_config()
    tokens = rows * config["chunk_size"]
    sa = config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    hq, hk, head = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])

    def of(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    keys, column = of((tokens, tokens)), of((tokens,))
    if piece == "scores":
        lowered = jax.jit(indexed.index_keys).lower(
            of((tokens, heads, dim), jnp.bfloat16),
            of((tokens, dim), jnp.bfloat16),
            of((tokens, heads), jnp.float32), column)
        names = [indexed.SCORES_KERNEL]
    elif piece == "thresholds":
        lowered = jax.jit(lambda k, p: indexed.thresholds(
            k, p, sa["topk"])).lower(keys, column)
        names = [indexed.THRESHOLD_KERNEL, indexed.TIE_KERNEL]
    else:
        lowered = jax.jit(
            lambda q, k, v, keys, tau, cut, w, cos, sin, start:
            indexed.indexed_attention(q, k, v, keys, tau, cut, w,
                                      (cos, sin, start),
                                      config["rms_norm_eps"])).lower(
            of((tokens, hq * head), jnp.float32),
            of((tokens, hk * head), jnp.bfloat16),
            of((tokens, hk * head), jnp.bfloat16), keys, column, column,
            of((head,), jnp.bfloat16), of((tokens, head), jnp.float32),
            of((tokens, head), jnp.float32), of((tokens, 1)))
        names = [indexed.ATTENTION_KERNEL]
    text = lowered.compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == len(names)
    for name in names:
        assert name in text, name
    if piece == "attention":
        # from q's product to ``o``'s operand: nothing laid out in front
        # of the kernel or behind it
        assert "f32[%d,%d]" % (tokens, hq * head) in text
        assert "bf16[%d,%d]" % (tokens, hq * head) in text
        # (the 64 numbers of a query tile's first key tile come out of
        # a strided slice, an int32 transpose of nothing)
        import re
        assert not re.search(r"(?:f32|bf16)\[[\d,]*\]\S* transpose\(", text)
        assert "pad(" not in text


def test_the_grouped_products_compile_at_the_published_widths(one_chip):
    """All 131,072 pairs of a full dispatch in 128 groups, K 2048 -> N
    768 (six lane tiles wide, the narrowest N in the benchmark) and
    back, at ``gmm_tiling``'s tiles from the shapes alone."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import moe

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    counts = of((128,), jnp.int32)
    jax.jit(lambda x, w, c: moe.grouped_matmul(
        x, w, c, False, transposed=True)).lower(
        of((131072, 2048)), of((128, 768, 2048)), counts).compile()
    jax.jit(lambda x, w, c: moe.grouped_matmul(x, w, c, False)).lower(
        of((131072, 768)), of((128, 768, 2048)), counts).compile()
    for m, k, n in ((131072, 2048, 768), (131072, 768, 2048)):
        tm, tk, tn = moe.gmm_tiling(m, k, n)
        assert m % tm == 0 and k % tk == 0 and n % tn == 0, (tm, tk, tn)


# -- the shared code's StableHLO ---------------------------------------------------------


@pytest.mark.parametrize("family", ["nemotron_h", "deepseek_v2",
                                    "minicpm_sala", "qwen3_next",
                                    "exaone_moe", "keye_vl2"])
def test_the_toy_stacks_lower_to_the_recorded_text(family):
    """PR 46 left ``ops/moe.py``, ``ops/segattn.py``, ``ops/rope.py`` and
    the stage's program as they were (``models/token_stages.py`` changed
    how a sample's arrays reach the host, behind the launch): each of
    the five older families' toy stacks lowers to the StableHLO text the
    parent's tree gave (its SHA-256 under ``tests/recorded``, as PR 45
    left it), and this family's to the text of the tree that brought it,
    for the next PR to hold. PR 47 recorded ``nemotron_h``'s and
    ``minicpm_sala``'s again (``ops/ssd.ssd_scan`` became one Pallas
    kernel) and PR 48 ``nemotron_h``'s and ``qwen3_next``'s
    (``ops/ssd.segment_conv1d`` became one, with the callers' SiLU
    inside); the other four — the families whose cells bypass that
    convolution — are the texts their PRs left. PR 50 recorded
    ``qwen3_next``'s again (``ops/deltanet.py``'s kernel took the norms
    and the gate around the rule in; no other family calls it). PR 54
    recorded this family's again (``ops/indexed.py``'s attention kernel
    holds all the heads of a tile pair a step and took q's norm, rotary,
    scale and rounding in; nothing else imports it: the five older
    families' texts are the ones they had, and
    :func:`test_the_toy_stack_serves_the_parents_logits_to_the_bit`
    holds the new text's values to the old one's). PR 56 recorded it
    once more (the thresholds' counts walk a step's own keys in one
    loop; ``ops/indexed.py`` alone, which no older family imports;
    :func:`test_the_walk_gives_the_parents_thresholds_to_the_bit` holds
    the values). PR 58 recorded ``qwen3_next``'s again (the products in
    the bodies of ``ops/deltanet.py``'s kernels go part by part, no
    other family here calls it; its kernel against the recurrence is
    ``tests/test_qwen3_next.py``'s). PR 60 recorded ``minicpm_sala``'s
    again (the lightning mixer's lines around the scan are
    ``ops/ssd.py``'s kernel's first and last, for the caller that hands
    it raw q and k and an output norm: ``nemotron_h``, the kernel's
    other caller here, lowers to the text it had)."""
    import test_qwen3_next
    with open(os.path.join(REPO, "tests", "recorded",
                           "toy_stack_stablehlo.json")) as f:
        recorded = json.load(f)
    import jax
    if recorded["jax"] != jax.__version__:
        pytest.skip("recorded under jax %s" % recorded["jax"])
    text = test_qwen3_next.stack_text(family)
    assert hashlib.sha256(text.encode()).hexdigest() == recorded[family]
