"""The DeepSeek-V2 family against its plain reference, at a toy size on
the CPU with weights from a seed: the packed prefill, the
lower-precision control that must fail, rotary positions that restart
at each request, group-limited routing against plain numpy, the expert
share against the uncut layer, the two forms of latent attention, the
Nemotron rule of the shared router bit for bit, the recipe with its
stored forms, the shared stages, the counters, the operation counts,
the cell through the one benchmark command, and the real
configuration's published sizes. Nothing here needs the native decode
library."""

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
from benchmarks.references import deepseek_v2 as reference  # noqa: E402

REAL = "benchmarks/configs/deepseek-v2-ep8.json"
CELL = "deepseek-v2.bulk"
SEED = 3_000_000_123

#: the published shape at toy widths: 1 dense + 2 expert layers, 16
#: experts in 4 groups of 4 (top-2 groups, top-3), one group held
TOY = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "vocab_size": 256, "chunk_size": 16, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "n_routed_experts": 4, "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 3, "routed_scaling_factor": 16.0,
    "norm_topk_prob": False, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy", "moe_layer_freq": 1,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6,
    "published": {"n_routed_experts": 16, "num_hidden_layers": 60}}
HELD = (4, 5, 6, 7)
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths: narrow sums average less
#: rounding away than the real ones (the real limit is the family
#: file's SHARE_OF_SPREAD); the toy reads 1.2 to 1.8%
TOY_LIMIT = 0.03


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    cfg = network.DeepseekV2Config.from_published(TOY)
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

    from rnb_tpu.models.deepseek_v2 import network
    key = tuple(sorted(arm.items()))
    if key not in toy["programs"]:
        toy["programs"][key] = jax.jit(
            lambda p, s, t, m: network.forward(
                toy["cfg"], p, s, t, m[0], m[1], m[2], interpret=True,
                **arm))
    return toy["programs"][key]


def run_program(toy, prompts, rows, params=None):
    tokens, meta, offsets = pack(prompts, rows)
    logits, chosen, served, sent, *_ = program_of(toy)(
        toy["params"] if params is None else params, toy["slots"], tokens,
        meta)
    chosen = np.asarray(chosen)
    per_prompt = [chosen[:, o * Q:o * Q + len(p)]
                  for o, p in zip(offsets, prompts)]
    return (np.asarray(logits)[:len(prompts)], per_prompt,
            np.asarray(served), np.asarray(sent))


def run_reference(toy, prompt, forced=None, held=HELD):
    import jax
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(toy["read"], prompt, held=held,
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


def test_packed_prefill_matches_the_reference_and_the_control_fails(toy):
    family = mm.load_family("deepseek_v2")
    prompts = prompts_of([5, 16, 37, 64, 20, 70], seed=4)
    logits, chosen, served, sent = run_program(toy, prompts, 16)
    refs = [run_reference(toy, p, forced=c)
            for p, c in zip(prompts, chosen)]
    want = np.stack([np.asarray(r["logits"]) for r in refs])
    verdict = compare(logits, want, TOY_LIMIT)
    assert verdict["ok"], verdict
    assert max(float(r["shortfall"].max()) for r in refs) \
        < family.ROUTE_SLACK
    assert max(float(r["group_shortfall"].max()) for r in refs) \
        < family.GROUP_SLACK
    valid = sum(len(p) for p in prompts)
    # a token sends the held group at most k pairs, and the tokens that
    # send it anything are at most those whose top groups include it
    assert (served.sum(axis=1) <= valid * TOY["num_experts_per_tok"]).all()
    assert (sent <= valid).all() and (sent > 0).all()
    assert (served.sum(axis=1) >= sent).all()
    # the reference's own free choice agrees almost everywhere
    free = run_reference(toy, prompts[3])
    agree = (np.sort(np.asarray(free["chosen"]), -1)
             == np.sort(chosen[3], -1)).all(-1).mean()
    assert agree > 0.9
    # the control: every layer's matrices through float8, outside the
    # tolerance
    fp8 = run_program(toy, prompts, 16, through_float8(toy["params"]))
    refs8 = np.stack([np.asarray(run_reference(toy, p, forced=c)["logits"])
                      for p, c in zip(prompts, fp8[1])])
    assert not compare(fp8[0], refs8, TOY_LIMIT)["ok"]


def test_packing_is_invisible_and_positions_restart(toy):
    """A prompt's logits depend neither on what shares its dispatch,
    nor on where in the pool it lies, nor on the bucket: its rotary
    positions start at its own first row."""
    a, b, c, d = prompts_of([37, 5, 64, 20])
    alone, chosen, _, _ = run_program(toy, [a], 4)
    packed, _, _, _ = run_program(toy, [b, c, a, d], 16)
    other, _, _, _ = run_program(toy, [d, a], 8)
    want = run_reference(toy, a, forced=chosen[0])
    spread = float(np.asarray(want["logits"]).std())
    for got in (packed[2], other[1]):
        # the same arithmetic on the same rows: far inside the
        # comparison's tolerance
        assert np.abs(got - alone[0]).max() < 0.005 * spread
    assert compare(alone[0], np.asarray(want["logits"]), TOY_LIMIT)["ok"]


def test_pool_positions_restart_at_each_request():
    from rnb_tpu.ops import rope
    # requests of 2, 1 and 3 rows, then two pad rows (their own starts)
    row_start = np.array([0, 0, 2, 3, 3, 3, 6, 7], np.int32)
    got = np.asarray(rope.pool_positions(row_start, 4))
    want = np.array([[0, 1, 2, 3], [4, 5, 6, 7], [0, 1, 2, 3],
                     [0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11],
                     [0, 1, 2, 3], [0, 1, 2, 3]])
    assert np.array_equal(got, want)


def test_rotation_keeps_norms_and_depends_on_distance_alone():
    import jax.numpy as jnp

    from rnb_tpu.ops import rope
    inv_freq = rope.yarn_inv_freq(8, 10000.0, 40.0, 4096, 32.0, 1.0)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 1, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 8)), jnp.float32)

    def score(pq, pk):
        rq = rope.rotate(q, jnp.full((1, 1), pq), inv_freq)
        rk = rope.rotate(k, jnp.full((1, 1), pk), inv_freq)
        assert abs(float(jnp.linalg.norm(rq) - jnp.linalg.norm(q))) < 1e-5
        return float((rq * rk).sum())
    assert abs(score(7, 3) - score(104, 100)) < 1e-4
    assert abs(score(7, 3) - score(7, 5)) > 1e-3


def test_yarn_frequencies_are_the_published_ones():
    """The program's frequencies against the reference's own
    computation and against the numbers the real configuration gives:
    correction dimensions 10 and 23 of 32 pairs; below 10 the plain
    frequency, from 23 on the plain one over 40."""
    from rnb_tpu.ops import rope
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    yarn = config["rope_scaling"]
    got = rope.yarn_inv_freq(
        config["qk_rope_head_dim"], config["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"])
    assert np.allclose(got, np.asarray(reference.yarn_inv_freq(config)),
                       rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:11], plain[:11], rtol=1e-6)
    assert np.allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert (got[11:23] < plain[11:23]).all() \
        and (got[11:23] > plain[11:23] / 40).all()
    from rnb_tpu.models.deepseek_v2 import network
    cfg = network.DeepseekV2Config.from_published(
        mm.load_family("deepseek_v2").published_keys(config))
    assert abs(cfg.softmax_scale - 0.114721) < 1e-6
    assert cfg.rotary_mscale == 1.0


# -- the router ---------------------------------------------------------------


def numpy_group_limited(scores, n_group, topk_group, top_k):
    """Plain numpy: a group's score is its best expert's; the best
    groups stay (the lower index wins a tie); top-k of what is left."""
    tokens, experts = scores.shape
    per = experts // n_group
    ids = np.zeros((tokens, top_k), np.int64)
    for t in range(tokens):
        group_score = scores[t].reshape(n_group, per).max(-1)
        kept = np.argsort(-group_score, kind="stable")[:topk_group]
        masked = np.zeros(experts)
        for g in kept:
            masked[g * per:(g + 1) * per] = scores[t, g * per:(g + 1) * per]
        ids[t] = np.argsort(-masked, kind="stable")[:top_k]
    return ids


def hand_built_scores(case):
    rng = np.random.default_rng(11)
    if case == "random":
        logits = rng.standard_normal((64, 16))
    elif case == "tied_groups":
        # groups 1 and 3 tie for the second place: the lower index stays
        logits = np.full((4, 16), -3.0)
        logits[:, 0] = 2.0
        logits[:, [5, 13]] = 1.0
        logits[:, [6, 14]] = 0.5
    elif case == "tied_experts":
        # five experts tie inside the kept groups: the lowest ids win
        logits = np.full((4, 16), -3.0)
        logits[:, 8] = 2.0
        logits[:, [1, 2, 3, 9, 10]] = 1.0
    elif case == "one_strong_group":
        # the best group's second expert beats the other groups' best,
        # and a third-best group with a fine second expert is cut
        logits = np.full((4, 16), -3.0)
        logits[:, [0, 1, 2]] = [3.0, 2.5, 2.0]
        logits[:, [4, 5]] = [1.0, -1.0]
        logits[:, [8, 9]] = [0.9, 0.8]
    else:
        raise ValueError(case)
    return logits


@pytest.mark.parametrize("case", ["random", "tied_groups", "tied_experts",
                                  "one_strong_group"])
def test_group_limited_routing_matches_plain_numpy(case):
    """``route`` is given logits through an identity "router": hidden =
    experts."""
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    logits = hand_built_scores(case).astype(np.float32)
    ids, weights = moe.route(
        jnp.asarray(logits), jnp.eye(16, dtype=jnp.float32), None, 3, 16.0,
        score="softmax", n_group=4, topk_group=2, renormalise=False)
    shifted = np.exp(logits - logits.max(-1, keepdims=True))
    scores = shifted / shifted.sum(-1, keepdims=True)
    want = numpy_group_limited(scores, 4, 2, 3)
    assert np.array_equal(np.asarray(ids), want)
    assert np.allclose(np.asarray(weights),
                       16.0 * np.take_along_axis(scores, want, 1),
                       rtol=1e-5)
    # at most topk_group groups serve a token
    assert (np.asarray([len(set(row // 4)) for row in want]) <= 2).all()


def route_before_pr33(x, w_router, b_corr, top_k, scaling):
    """``ops/moe.route`` as it stood before the rule became its
    arguments (PR 32's tree), copied."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + b_corr.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, picked / picked.sum(-1, keepdims=True) * scaling


@pytest.mark.parametrize("length", [5, 37, 100])
def test_the_nemotron_rule_is_bit_equal_to_the_one_it_replaces(length):
    """On the inputs ``tests/test_nemotron_h.py`` gives its expert
    blocks: a normed bfloat16 activation, the toy stack's router and
    correction bias."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    import test_nemotron_h as nemotron
    from rnb_tpu.models.nemotron_h import checkpoint, network
    cfg = network.NemotronHConfig.from_published(nemotron.TOY)
    block = checkpoint.make_params(
        cfg, nemotron.SEED, nemotron.HELD, jax.devices()[0],
        groups=["b1"])["b1"]
    rng = np.random.default_rng(length)
    x = jnp.asarray(rng.standard_normal((length, cfg.hidden_size)),
                    jnp.bfloat16)
    args = (x, block["router"], block["b_corr"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
    new_ids, new_weights = jax.jit(moe.route, static_argnums=(3, 4))(*args)
    old_ids, old_weights = jax.jit(route_before_pr33,
                                   static_argnums=(3, 4))(*args)
    assert np.array_equal(np.asarray(new_ids), np.asarray(old_ids))
    assert np.array_equal(np.asarray(new_weights), np.asarray(old_weights))


# -- the share, and the two forms of latent attention -------------------------


def test_the_share_ties_to_the_model(toy):
    """The routed parts of all four shares (one routing group each, as
    the program computes them) plus the shared experts counted once are
    the uncut reference's layer."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    from rnb_tpu.ops import moe
    cfg, layer = toy["cfg"], 1
    rng = np.random.default_rng(5)
    length = 3 * Q
    h = jnp.asarray(rng.standard_normal((3, Q, cfg.hidden_size)),
                    jnp.bfloat16)
    token_ok = jnp.ones((3, Q), bool)
    shared = np.asarray(moe.dense_expert(
        h.reshape(length, -1), toy["params"]["l1"]["shared_up"],
        toy["params"]["l1"]["shared_down"],
        toy["params"]["l1"]["shared_gate"]))
    total = shared.copy()
    pairs = 0
    for group in range(cfg.n_group):
        held = tuple(range(4 * group, 4 * group + 4))
        p = checkpoint.make_params(cfg, SEED, held, toy["device"],
                                   groups=["l%d" % layer])["l%d" % layer]
        out, ids, counts, sent, _ = jax.jit(
            lambda p, h, ok, s: network.experts_ffn(
                cfg, p, h, ok, s, interpret=True))(
            p, h, token_ok, network.held_slots(cfg, held))
        total += np.asarray(out).reshape(length, -1) - shared
        pairs += int(np.asarray(counts).sum())
    assert pairs == length * cfg.num_experts_per_tok
    weights = {t: toy["read"]("l%d.%s" % (layer, t),
                              range(16) if t in reference.PER_EXPERT
                              else None)
               for t in reference.EXPERTS}
    with jax.default_matmul_precision("highest"):
        want, _, _, _ = reference.experts(
            TOY, weights, h.reshape(length, -1).astype(jnp.float32),
            jnp.arange(16), forced=jnp.asarray(ids))
    want = np.asarray(want)
    assert np.abs(total - want).max() < 0.02 * want.std()


def folded_attention(cfg, w, x):
    """Latent attention in its folded (multi-query) form, in float32:
    ``W_UK`` goes into the query, every head reads one latent key of
    ``kv_lora_rank + rotary`` columns and one latent value of
    ``kv_lora_rank``, and ``W_UV`` comes behind the softmax. The form
    the decode path of a latent cache uses; in prefill on the v5e it
    lost to the expanded one (PERF.md section 6, PR 33)."""
    import jax
    import jax.numpy as jnp
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, value = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    eps, yarn = cfg["rms_norm_eps"], cfg["rope_scaling"]
    length = x.shape[0]
    scale = (nope + rot) ** -0.5 * reference.yarn_get_mscale(
        yarn["factor"], yarn["mscale_all_dim"]) ** 2
    q = (reference.rms_norm(x @ w["q_a"], w["q_a_norm"], eps) @ w["q_b"]) \
        .reshape(length, heads, nope + rot)
    down = x @ w["kv_a"]
    c_kv = reference.rms_norm(down[:, :rank], w["kv_a_norm"], eps)
    k_pe = reference.rotary(cfg, down[:, rank:])
    up = w["kv_b"].reshape(rank, heads, nope + value)
    w_uk, w_uv = up[..., :nope], up[..., nope:]
    q_latent = jnp.einsum("lhn,rhn->lhr", q[..., :nope], w_uk)
    q_full = jnp.concatenate(
        [q_latent, reference.rotary(cfg, q[..., nope:])], -1)
    key = jnp.concatenate([c_kv, k_pe], -1)               # (L, rank + rot)
    s = jnp.einsum("lhc,mc->hlm", q_full, key) * scale
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
    latent = jnp.einsum("hlm,mr->lhr", jax.nn.softmax(s, -1), c_kv)
    out = jnp.einsum("lhr,rhv->lhv", latent, w_uv)
    return out.reshape(length, heads * value) @ w["o"]


def test_the_two_forms_of_latent_attention_agree(toy):
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((40, TOY["hidden_size"])),
                    jnp.float32)
    w = {t: toy["read"]("l0.%s" % t) for t in reference.ATTENTION}
    with jax.default_matmul_precision("highest"):
        expanded = np.asarray(reference.attention(TOY, w, x))
        folded = np.asarray(folded_attention(TOY, w, x))
    assert np.abs(expanded - folded).max() < 1e-4 * expanded.std()


def test_latent_attention_matches_one_masked_softmax(toy):
    """The program's expanded form over a packed pool, alone, against
    the reference's: two requests in one pool, each from position 0."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import network
    from rnb_tpu.ops import rope
    cfg = toy["cfg"]
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((4, Q, cfg.hidden_size)),
                    jnp.bfloat16)
    row_start = jnp.asarray([0, 0, 0, 3], jnp.int32)
    got, tiles = jax.jit(
        lambda p, h, s: network.latent_attention(
            cfg, p, h, s, rope.pool_positions(s, Q), interpret=True))(
        toy["params"]["l0"], h, row_start)
    got = np.asarray(got).reshape(4 * Q, -1)
    assert np.asarray(tiles).tolist() == [1, 1]
    w = {t: toy["read"]("l0.%s" % t) for t in reference.ATTENTION}
    flat = h.reshape(4 * Q, -1).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.concatenate([
            np.asarray(reference.attention(TOY, w, flat[:3 * Q])),
            np.asarray(reference.attention(TOY, w, flat[3 * Q:]))])
    assert np.abs(got - want).max() < 0.03 * want.std()


def plain_latent_attention(cfg, p, h, row_start, positions):
    """Latent attention as the program ran it before its queries left
    their product as the kernel's operand (PR 37's tree): every product
    tokens-first, the queries' kept in float32, sliced, rotated,
    concatenated, scaled, rounded, and all three operands laid out by
    ``packed_attention``. ``p["q_b"]`` is the stored tensor: its first
    128 + 64 columns a head are that tree's. -> (out, tiles, the
    queries before the layout)."""
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2.network import _proj, rms_norm
    from rnb_tpu.ops import rope, segattn
    rows, q, _ = h.shape
    act = h.dtype
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, value = cfg.qk_nope_head_dim, cfg.v_head_dim
    inv_freq, scale = cfg.inv_freq(), cfg.softmax_scale
    mscale = cfg.rotary_mscale
    q_b = jnp.swapaxes(p["q_b"][..., :cfg.qk_head_dim], 0, 1) \
        .reshape(cfg.q_lora_rank, heads * cfg.qk_head_dim)
    c_q = rms_norm(_proj(h, p["q_a"]), p["q_a_norm"], cfg.eps, act)
    qs = _proj(c_q, q_b).reshape(rows, q, heads, cfg.qk_head_dim)
    down = _proj(h, p["kv_a"])
    c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], cfg.eps, act)
    kv = _proj(c_kv, p["kv_b"]).astype(act) \
        .reshape(rows, q, heads, nope + value)
    q_pe = rope.rotate(qs[..., nope:], positions, inv_freq) * mscale
    query = (jnp.concatenate([qs[..., :nope], q_pe], -1) * scale) \
        .astype(act)
    k_pe = (rope.rotate(down[..., rank:], positions, inv_freq) * mscale) \
        .astype(act)
    key = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_pe[:, :, None, :],
                         (rows, q, heads, cfg.qk_rope_head_dim))], -1)
    out, tiles = segattn.packed_attention(query, key, kv[..., nope:],
                                          row_start, True)
    return _proj(out.reshape(rows, q, heads * value), p["o"]), tiles, query


def test_latent_attention_is_the_plain_form_with_its_passes_gone(toy):
    """Two requests and a pad row in one pool: the program's latent
    attention against the form it had, kept above. The products'
    shapes changed (a head's 24 columns among 128, heads a batch), so
    their sums may round otherwise; the rotation, the scale and the
    rounding may not: the queries' operand is the plain form's, laid
    out, to the last bit on this backend."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import network
    from rnb_tpu.ops import mla, rope, segattn
    cfg = toy["cfg"]
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((6, Q, cfg.hidden_size)),
                    jnp.bfloat16)
    row_start = jnp.asarray([0, 0, 0, 3, 3, 5], jnp.int32)
    p = toy["params"]["l0"]
    positions = rope.pool_positions(row_start, Q)
    got, tiles = jax.jit(lambda p, h, s: network.latent_attention(
        cfg, p, h, s, rope.pool_positions(s, Q), interpret=True))(
        p, h, row_start)
    want, plain_tiles, plain_query = jax.jit(
        lambda p, h, s: plain_latent_attention(
            cfg, p, h, s, rope.pool_positions(s, Q)))(p, h, row_start)
    assert np.array_equal(np.asarray(tiles), np.asarray(plain_tiles))
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() < 0.03 * want.std()
    flat = h.reshape(6 * Q, -1)
    c_q = network.rms_norm(network._proj(flat, p["q_a"]), p["q_a_norm"],
                           cfg.eps, h.dtype)
    query = mla.queries(c_q, p["q_b"], positions.reshape(-1),
                        cfg.inv_freq(), cfg.qk_nope_head_dim,
                        cfg.softmax_scale, cfg.rotary_mscale,
                        interpret=True)
    laid = segattn.heads_first(
        plain_query.reshape(6 * Q, cfg.num_attention_heads, -1))
    assert query.shape == (cfg.num_attention_heads, 6 * Q, 128)
    assert np.array_equal(np.asarray(query, np.float32),
                          np.asarray(laid[:, :6 * Q], np.float32))


# -- the recipe, the stages, the counters -------------------------------------


def test_recipe_gives_program_and_reference_the_same_values(toy):
    from rnb_tpu.models import seeded
    from rnb_tpu.models.deepseek_v2 import checkpoint
    params, read = toy["params"], toy["read"]
    for name, tensor in (("l0.q_a", params["l0"]["q_a"]),
                         ("top.embed", params["embed"]),
                         ("l1.router", params["l1"]["router"]),
                         ("l2.kv_b", params["l2"]["kv_b"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name)))
    # the rotary columns are stored evens first, then odds, and read as
    # published: kv_a's last 8 of 24, q_b's last 8 of each head's 24
    stored = np.asarray(params["l0"]["kv_a"], np.float32)
    published = np.asarray(read("l0.kv_a"))
    assert np.array_equal(stored[:, :16], published[:, :16])
    assert np.array_equal(stored[:, 16:20], published[:, 16:24:2])
    assert np.array_equal(stored[:, 20:24], published[:, 17:24:2])
    # q_b lies heads-first, a head's 24 columns whole lanes wide: the
    # rotary halves [x1 | x2] once more as [-x2 | x1], then zeros
    stored = np.asarray(params["l0"]["q_b"], np.float32)
    assert stored.shape == (4, 32, 128)
    stored = stored.transpose(1, 0, 2)
    published = np.asarray(read("l0.q_b")).reshape(32, 4, 24)
    assert np.array_equal(stored[..., :16], published[..., :16])
    assert np.array_equal(stored[..., 16:20], published[..., 16:24:2])
    assert np.array_equal(stored[..., 20:24], published[..., 17:24:2])
    assert np.array_equal(stored[..., 24:28], -published[..., 17:24:2])
    assert np.array_equal(stored[..., 28:32], published[..., 16:24:2])
    assert not stored[..., 32:].any()
    spec = checkpoint.tensor_specs(toy["cfg"], 4)["l0"]["q_b"]
    order = seeded.halves_order(spec)
    assert np.array_equal(order[seeded.halves_order(spec, inverse=True)],
                          np.arange(96))
    # an expert is a function of its global id, whoever holds it; its
    # first two matrices are stored transposed and read as published
    gate = np.asarray(params["l1"]["gate"], np.float32)
    inner, hidden = TOY["moe_intermediate_size"], TOY["hidden_size"]
    assert gate.shape == (len(HELD), inner, hidden)
    read_gate = np.asarray(read("l1.gate", (7, 5)))
    assert read_gate.shape == (2, hidden, inner)
    assert np.array_equal(gate[[3, 1]], read_gate.transpose(0, 2, 1))
    other = checkpoint.make_params(toy["cfg"], SEED, (6, 7, 8, 9),
                                   toy["device"], groups=["l1"])
    assert np.array_equal(np.asarray(other["l1"]["gate"], np.float32)[:2],
                          gate[2:])
    assert not np.array_equal(
        np.asarray(read("l1.up", (4,))), np.asarray(read("l1.gate", (4,))))


def test_the_stored_orders_read_back_as_the_published_draw(toy):
    """``read`` hands the reference what a plain spec of the published
    shape draws under the same name, whatever order the program's
    tensor is stored in."""
    import dataclasses

    from rnb_tpu.models import seeded
    from rnb_tpu.models.deepseek_v2 import checkpoint
    specs = checkpoint.tensor_specs(toy["cfg"], len(HELD))["l0"]
    assert specs["q_b"].heads_first is not None
    for name in ("q_b", "kv_b", "kv_a", "o"):
        spec = specs[name]
        plain = dataclasses.replace(
            spec, shape=seeded.published_shape(spec), halves=None,
            heads_first=None)
        drawn = seeded.make_tensor(SEED, "l0." + name, plain, (),
                                   toy["device"])
        assert np.array_equal(np.asarray(drawn, np.float32),
                              np.asarray(toy["read"]("l0." + name))), name
    assert seeded.published_shape(specs["q_b"]) == (32, 4 * 24)
    assert seeded.published_shape(
        checkpoint.tensor_specs(toy["cfg"], 4)["l1"]["gate"]) \
        == (4, TOY["hidden_size"], TOY["moe_intermediate_size"])


def tree_hash(params):
    import hashlib

    import jax
    digest = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(("%s%s" % (leaf.dtype, leaf.shape)).encode())
        digest.update(np.asarray(leaf.astype("float32")).tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("family,want", [
    ("nemotron_h", "1c1f2c5c241b48406811eafcaefa62ab"
                   "eb7d2ef2455a206bceb55085db0c889c"),
    ("minicpm_sala", "aff7174d3a935a9b82fd4a76167a07a1"
                     "12baa6f147efc864815d96a8c473a926")])
def test_the_other_families_seeded_trees_are_the_parents(family, want):
    """A stored order is a spec's own: the families that name none draw
    the trees they drew before ``TensorSpec.heads_first`` (the hashes
    are the parent's, PR 37's tree, over the tests' toy sizes)."""
    import importlib

    import jax
    toy_of = importlib.import_module("tests.test_" + family)
    package = "rnb_tpu.models.%s." % family
    network = importlib.import_module(package + "network")
    checkpoint = importlib.import_module(package + "checkpoint")
    config, = [c for c in vars(network).values() if isinstance(c, type)
               and hasattr(c, "from_published")]
    params = checkpoint.make_params(
        config.from_published(toy_of.TOY), toy_of.SEED,
        getattr(toy_of, "HELD", ()), jax.devices()[0])
    assert tree_hash(params) == want


def one_stage_serves_both_families(served):
    """``family_contract.stage_serves``'s entry for this family: the
    names the older configuration gives are the same classes, and the
    stage counts the held group's tokens and the flash kernel's tiles."""
    from rnb_tpu.models import token_stages
    from rnb_tpu.models.nemotron_h import stages as old
    assert old.NemotronPrefill is token_stages.PackedPrefill
    assert old.NemotronTokenLoader is token_stages.TokenLoader
    assert old.dispatch_meta is token_stages.dispatch_meta
    counters = served.stage.stage_counters()
    assert counters["expert_served"].shape == (2, 4)
    assert 0 < counters["group_tokens"] <= 2 * served.valid
    assert counters["expert_served"].sum() >= counters["group_tokens"]
    # three layers, one tile each: visited, and on or under the diagonal
    assert counters["attn_tiles"].tolist() == [3, 3]


def scopes_of_hlo_before_the_move(text):
    """``scopes_of_hlo`` as ``models/token_stages.py`` had it before
    ``rnb_tpu/hloscopes.py`` took it over (PR 37), kept word for word
    as the reference the moved one is held to."""
    import re
    instruction = re.compile(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])")
    op_name = re.compile(r'op_name="([^"]*)"')
    out = {}
    open_head = None
    for line in text.splitlines():
        head = instruction.match(line)
        if head:
            open_head = "%s %s" % head.groups()
        found = op_name.search(line)
        if found and open_head is not None:
            out[open_head] = found.group(1)
            open_head = None
    return out


def test_the_prefill_stage_writes_the_table_it_always_wrote(tmp_path):
    """Through the helper it now shares with the R(2+1)D stage: the
    file's bytes are ``json.dump`` of the old function's table over the
    same programs' text, bucket by bucket."""
    from rnb_tpu import hloscopes
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models import token_stages
    from rnb_tpu.models.deepseek_v2 import checkpoint
    recipe = str(tmp_path / "toy.recipe.json")
    checkpoint.save_recipe(recipe, TOY, SEED, HELD)
    stage = token_stages.PackedPrefill(
        DeviceSpec(-1), ckpt_path=recipe, max_rows=8, chunk=Q,
        row_buckets=[4, 8], num_warmups=0)
    expected = {}
    for rows in (4, 8):
        expected.update(scopes_of_hlo_before_the_move(
            stage._programs[rows].as_text()))
    assert len(expected) > 100
    logs = tmp_path / "logs"
    logs.mkdir()
    stage.bind_log_dir(str(logs))
    stage.finalize()
    assert os.listdir(str(logs)) == [hloscopes.TABLE_FILE]
    with open(str(logs / hloscopes.TABLE_FILE)) as f:
        assert f.read() == json.dumps(expected)


def test_every_layer_counts_the_tiles_its_dispatch_ran(toy, monkeypatch):
    """Three requests and two pad rows over 3 x 3 tiles of 128 tokens:
    each layer's counter is the block table's own sums."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import network
    from rnb_tpu.ops import segattn
    for name in ("_BLOCK_Q", "_BLOCK_KV", "_BLOCK_COMPUTE"):
        monkeypatch.setattr(segattn, name, 128)
    tokens, meta, offsets = pack(prompts_of([9 * Q, 6 * Q - 3, 7 * Q]), 24)
    assert offsets == [0, 9, 15, 22]
    *_, tiles, _ = jax.jit(lambda p, s, t, m: network.forward(
        toy["cfg"], p, s, t, m[0], m[1], m[2], interpret=True))(
        toy["params"], toy["slots"], tokens, meta)
    # query block 1 (rows 8-15) opens inside request 0, block 2 (rows
    # 16-23) inside request 2, which begins in key block 1
    run, _, causal = segattn.block_table(
        jnp.asarray([0, 0, 15 * Q]), 128, 128)
    assert np.asarray(run).tolist() == [[1, 0, 0], [1, 1, 0], [0, 1, 1]]
    assert np.asarray(tiles).tolist() \
        == [[5, causal]] * TOY["num_hidden_layers"] == [[5, 6]] * 3


def test_the_experts_line_carries_the_group_tokens():
    from rnb_tpu.telemetry import stage_counter_report
    served = np.array([[3, 1], [2, 2]])
    with_groups = {"tokens_valid": 10, "tokens_shipped": 16,
                   "expert_served": served, "experts_per_token": 3,
                   "group_tokens": 7}
    lines, fields = stage_counter_report([with_groups, with_groups])
    assert (fields["tokens_valid"], fields["tokens_shipped"]) == (20, 32)
    assert fields["experts_assignments"] == 2 * 10 * 3 * 2
    assert fields["experts_held"] == 16
    assert fields["experts_group_tokens"] == 14
    assert lines[1].endswith(" group_tokens=14")
    without = dict(with_groups)
    del without["group_tokens"]
    lines, fields = stage_counter_report([without])
    assert "experts_group_tokens" not in fields
    assert "group_tokens" not in lines[1]


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.deepseek_v2 import flops, network
    family = mm.load_family("deepseek_v2")
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.DeepseekV2Config.from_published(
        family.published_keys(config))
    assert flops.flops_per_token(cfg, 512.0, 0.75) \
        == family.flops_per_token(config, 512.0, 0.75)
    assert family.flops_per_row(config) == config["chunk_size"] \
        * flops.flops_per_token(cfg, family.mean_context(config), 0.75)
    # ISSUE 33's arithmetic: 298 MFLOP of projections a token a layer
    assert flops.attention_proj_flops_per_token(cfg) == 2 * 149_225_472
    assert family.attention_params(config) == 149_225_472
    # the kernels' work is part of the mechanisms', and bounded by it
    attn = family.mechanism_work(config, "attn", 1e6, 7.5e5 * 6, 125.0)
    flash = family.mechanism_work(config, "flash", 1e6, 7.5e5 * 6, 125.0)
    whole = family.mechanism_work(config, "experts", 1e6, 7.5e5 * 6, 125.0)
    gmm = family.mechanism_work(config, "gmm", 1e6, 7.5e5 * 6, 125.0)
    assert flash[0] < attn[0] and gmm[0] < whole[0] and gmm[1] < whole[1]


# -- through the one benchmark command ----------------------------------------


def toy_config():
    """A toy-width copy of the real configuration's file, of five
    layers: four expert layers are the floor of the family file's
    ``check_config`` (the tests above run ``TOY``'s three)."""
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    config.update(TOY, num_hidden_layers=5)
    config["model"] = dict(config["model"], layers=5)
    config["experts_held"] = {"first": 4, "count": 4}
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 24, "sigma": 0.8,
                                   "min": 4, "max": 60},
                         "long": {"count": 2, "min": 64, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 250
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=5, samples=8)
    return config


#: ``tests/test_deepseek_v2_cell.py`` runs it. One of the two families
#: that keep the untraced run (``family_contract.py``'s docstring): the
#: one that holds a share of its experts. The family came before the
#: control script and before a family file's ``build`` refused a parent
CONTRACT = contract.Family(
    name="deepseek_v2", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts: assignments=", " group_tokens=",
          "Attention: tiles_visited="),
    traced={
        # a toy pool is one tile: the counter comes through the result
        "flash_tile_visit_pct.bulk": "[100, 100]",
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        # one group of four held: a quarter of the pairs under even
        # routing, and at most half the tokens send it anything
        "held_assignment_pct.bulk": "(10, 45)",
        "group_token_pct.bulk": "(15, 50]",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "rows_per_dispatch.bulk": "(0, inf)"},
    not_from_a_cpu="roofline|util|mla_proj",
    traces=(0, 1), refuses_a_parent=False,
    stage=contract.Stage(
        lengths=(20, 9, 30), row_buckets=(4, 8),
        scopes=("/attn/", "/experts/"), chosen_shape=(2, 20, 3),
        also=one_stage_serves_both_families))


# -- the real configuration ---------------------------------------------------


PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def test_real_configuration_keeps_the_published_sizes():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    entry = mm.config_entry(mm.load(), "deepseek-v2-ep8")
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "n_routed_experts"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["n_routed_experts"] == config["experts_held"]["count"] \
        == 160 // 8
    assert config["num_hidden_layers"] >= 1 + 4
    assert config["deployment"] and config["assumed"]["rotary_permutation"]
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron3-nano-l14-ep2.json")) as f:
        assert config["dataset"] == json.load(f)["dataset"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    # the weights the file states, from the tensor list
    from rnb_tpu.models import seeded
    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    cfg = network.DeepseekV2Config.from_published(
        family.published_keys(config))
    # q_b's stored pad columns are no parameters of the model
    held = sum(int(np.prod(seeded.published_shape(spec))) for tensors in
               checkpoint.tensor_specs(cfg, 20).values()
               for spec in tensors.values())
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 0.01
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01


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
    # what is compiled for a described chip is written to the
    # persistent cache and cannot be read back without one: off for
    # this test, and on again for whatever this worker runs next
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def real_sizes():
    """-> (the real configuration, its largest row bucket, the experts
    this chip holds)."""
    from rnb_tpu.models.deepseek_v2 import network
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.DeepseekV2Config.from_published(
        mm.load_family(config["family"]).published_keys(config))
    step = config["pipeline_config"]["pipeline"][-1]
    return cfg, max(step["row_buckets"]), config["experts_held"]["count"]


def test_a_gated_expert_layer_moves_its_pairs_once_each_way(one_chip):
    """One expert layer's feed-forward of the real configuration at 64
    rows, compiled for the described v5e (nothing runs): what
    ``check_pair_buffers`` reads of the three stacks and the pair
    buffers, and the kernel is called three times."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    from tests.compiled_experts import check_pair_buffers
    cfg, rows, held = real_sizes()
    layer = next(i for i in range(cfg.num_hidden_layers)
                 if not cfg.is_dense(i))
    specs = checkpoint.tensor_specs(cfg, held)["l%d" % layer]

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def feed_forward(p, slots, x, token_ok):
        with jax.named_scope("experts"):
            h = network.rms_norm(x, p["ffn_norm"], cfg.eps, x.dtype)
            out, *counted = network.experts_ffn(cfg, p, h, token_ok, slots)
            return (x.astype(jnp.float32) + out).astype(x.dtype), *counted
    text = jax.jit(feed_forward).lower(
        {name: of(specs[name].shape, getattr(jnp, specs[name].dtype))
         for name in ("ffn_norm", "router", "up", "gate", "down",
                      "shared_up", "shared_gate", "shared_down")},
        of((cfg.router_experts,), jnp.int32),
        of((rows, cfg.chunk_size, cfg.hidden_size), jnp.bfloat16),
        of((rows, cfg.chunk_size), jnp.bool_)).compile().as_text()

    d, inner = cfg.hidden_size, cfg.moe_intermediate_size
    tokens, k = rows * cfg.chunk_size, cfg.num_experts_per_tok
    stacks = {"bf16[%d,%d,%d]" % (held, a, b)
              for a, b in ((d, inner), (inner, d))}
    assert {"bf16[%s]" % ",".join(map(str, specs[t].shape))
            for t in ("up", "gate", "down")} <= stacks
    assert (tokens, k, d) == (8192, 6, 5120)
    assert check_pair_buffers(text, tokens, k, d, stacks) \
        == ["f32[%d,%d]" % (tokens * k, inner)] * 2 \
        + ["f32[%d,%d]" % (tokens * k, d)]


@pytest.fixture(scope="module")
def stage_program(one_chip):
    """-> (the real configuration, the text of its stage program at 64
    rows compiled for the described v5e; nothing runs)."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.deepseek_v2 import checkpoint, network
    cfg, rows, held = real_sizes()
    params = {}
    for group, tensors in checkpoint.tensor_specs(cfg, held).items():
        made = {name: jax.ShapeDtypeStruct(
            spec.shape, getattr(jnp, spec.dtype), sharding=one_chip)
            for name, spec in tensors.items()}
        params.update(made if group == "top" else {group: made})

    def of(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return cfg, jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2])).lower(
        params, of((cfg.router_experts,)), of((rows, cfg.chunk_size)),
        of((3, rows))).compile().as_text()


def test_the_gather_into_expert_order_reads_the_fast_memory(stage_program):
    """The real stage program at 64 rows, compiled for the described
    v5e (nothing runs). A reading kept, not a mechanism of
    ``held_experts``: with the pairs (k, T) the compiler's memory space
    assignment keeps the tokens' rows in its fast memory for the
    gather into expert order in all four expert layers, and that gather
    takes 0.77 ms for the 3.73 it took from HBM in the (T, k) form (my
    chip runs, PR 34). A change that moves this count has moved 3 ms a
    layer of the dispatch, and says so."""
    from tests.compiled_experts import gather_in_sources
    cfg, text = stage_program
    assert gather_in_sources(text, 8192, 6, 5120) == [True] * 4
    # the flash kernel, a layer, keeps the name and the scope that two
    # readers of benchmarks/ find it by, its block table traced data
    from rnb_tpu import hloscopes
    flash = [scope for head, scope in hloscopes.scopes_of_hlo(text).items()
             if head.startswith("%splash_mqa_fwd_segmented_no_residuals")]
    assert len(flash) == cfg.num_hidden_layers == 5
    assert all("/attn/" in scope for scope in flash)


def test_the_queries_reach_the_kernel_from_their_product(stage_program):
    """The same program: what lies under ``attn`` between the products
    and the flash kernel, counted over the arrays of a whole (tokens x
    heads) operand (64 columns a head or more) that an instruction of
    the program's own writes to memory.

    No float32 array of tokens x heads x 192 elements is written: the
    queries' float32 product stays inside ``mla_queries``, which
    writes the kernel's operand ``bf16[128,8192,256]`` itself, a layer.
    Every other such array that neither a product nor a kernel writes
    is a layout pass. PR 37's tree had 12 a layer, 60 in all (the
    float32 queries sliced and copied, rotated and concatenated,
    padded and transposed; the rotary key's broadcast, the keys'
    concatenate, pad and transpose, the values' copy and transpose,
    three copies of the keys-values product, the result's transpose
    and reshape in front of ``o``), and the float32 ``[64,128,24576]``
    besides. The budget this tree ends with is **5 a layer, 25**, all
    on the keys' and values' side, which still goes through
    ``segattn.heads_first``: the rotary key's broadcast, a copy of the
    keys-values product, the keys' and the values' copies, the keys'
    pad and transpose. ``o`` reads the kernel's result as it lies."""
    import re

    from rnb_tpu import hloscopes
    from rnb_tpu.ops import mla
    cfg, text = stage_program
    tokens, heads = 64 * cfg.chunk_size, cfg.num_attention_heads
    scopes = hloscopes.scopes_of_hlo(text)
    head = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?((\w+)\[([\d,]*)\])"
                      r"\S* ([a-z\-]+)\(")
    passes, queries = [], []
    # the entry computation is the module's last: every line behind
    # its head that reads as an instruction is one of the program's own
    for line in text[text.index("\nENTRY "):].splitlines():
        found = head.match(line)
        if not found:
            continue
        name, shape, dtype, dims, opcode = found.groups()
        scope = scopes.get("%s %s" % (name, shape), "")
        elements = int(np.prod([int(d) for d in dims.split(",") if d]))
        # a copy the compiler's layout assignment put in carries no scope
        if elements < tokens * heads * 64 or not (
                "/attn/" in scope or not scope) \
                or opcode in ("parameter", "bitcast", "get-tuple-element"):
            continue
        assert not (dtype == "f32"
                    and elements >= tokens * heads * cfg.qk_head_dim), line
        if opcode == "custom-call":
            if mla.KERNEL_NAME in scope:
                queries.append(shape)
        elif "kind=kOutput" not in line:        # not a product
            passes.append(name)
    assert queries == ["bf16[%d,%d,%d]" % (heads, tokens, mla.query_lanes(
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim))] * 5
    assert len(passes) <= 25, passes


# -- ``mla.queries`` at dots3-note's sliding geometry ---------------------------


@pytest.mark.parametrize("nope,rotary,cut", [(192, 64, True), (192, 64, False),
                                            (128, 64, False), (24, 8, True)])
def test_the_queries_kernel_turns_columns_that_start_inside_a_lane_tile(
        nope, rotary, cut):
    """``mla.queries`` at nope 192 (``models/dots3_note``'s sliding
    layers): the rotary columns begin in the middle of the second lane
    tile, the turned copies fill the third, and ``out_columns`` writes
    the two tiles that hold the head; against ``ops/rope.rotate`` on the
    float32 product, with the tables handed over or built inside."""
    import jax.numpy as jnp

    from rnb_tpu.ops import mla, rope
    rng = np.random.default_rng(nope)
    tokens, rank, heads, scale = 64, 32, 3, 0.25
    lanes = mla.query_lanes(nope, rotary)
    half = rotary // 2
    w = rng.normal(size=(heads, rank, nope + rotary)) / np.sqrt(rank)
    stored = np.zeros((heads, rank, lanes), np.float32)
    stored[..., :nope + rotary] = w
    stored[..., nope + rotary:nope + rotary + half] = -w[..., nope + half:]
    stored[..., nope + rotary + half:nope + 2 * rotary] = \
        w[..., nope:nope + half]
    latent = jnp.asarray(rng.normal(size=(tokens, rank)), jnp.bfloat16)
    stored = jnp.asarray(stored, jnp.bfloat16)
    positions = jnp.asarray(rng.integers(0, 5000, tokens), jnp.int32)
    inv_freq = (5e4 ** (-np.arange(0, rotary, 2) / rotary)).astype(np.float32)
    written = -(-(nope + rotary) // 128) * 128 if cut else None
    tables = mla.turn_tables(positions, inv_freq, nope) if cut else None
    got = mla.queries(latent, stored, positions, inv_freq, nope, scale,
                      interpret=True, tables=tables, out_columns=written)
    assert got.shape == (heads, tokens, written or lanes)
    product = jnp.einsum("tr,hrc->htc", latent.astype(jnp.float32),
                         stored.astype(jnp.float32)[..., :nope + rotary])
    turned = rope.rotate(product[..., nope:].transpose(1, 0, 2)[None],
                         positions[None], inv_freq)[0].transpose(1, 0, 2)
    want = np.concatenate([np.asarray(product[..., :nope]),
                           np.asarray(turned)], -1) * scale
    got = np.asarray(got, np.float32)
    assert np.abs(got[..., :nope + rotary] - want).max() < 0.02
    assert not got[..., nope + rotary:].any()
    with pytest.raises(ValueError, match="columns written"):
        mla.queries(latent, stored, positions, inv_freq, nope, scale,
                    interpret=True, out_columns=128 if nope > 64 else 64)
