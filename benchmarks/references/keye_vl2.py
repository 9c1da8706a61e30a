"""The plain reference of the Keye-VL-2.0 family's language model: one
prompt at a time, unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it); no
kernel, no tiling, no packing. It imports nothing from the program; it
follows the catalog's row (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type`` KeyeVL2) and, for what
the row does not state, the conventions the configuration's file lists
under ``assumed``; each departure is a comment.

Every layer is ``x += attn(RMSNorm(x))``, ``x += experts(RMSNorm(x))``,
eps ``rms_norm_eps``, plain norm weights (Qwen3-MoE's convention, whose
widths the row repeats); the layers are the model's first
``num_hidden_layers``; then a final RMSNorm and an untied head on the
last position.

*Attention.* ``q = h W_q`` (32 heads of 128), ``k = h W_k``, ``v = h
W_v`` (4 heads of 128), no bias; RMSNorm over each head's columns of
``q`` and of ``k``; multimodal rotary (Qwen2-VL's chunked form): a
token has three position components (temporal, height, width), the 64
frequency pairs ``theta ** (-2i / 128)`` are cut by ``mrope_section``
[16, 24, 24] and pair i turns by the component of its section, halves
rotated. A text prompt's three components are all the token's index.

*Indexer* (``sa_config``; DeepSeek-V3.2-Exp's lightning indexer): ``qI
= h W_qI`` (16 heads of 64), ``kI = LayerNorm(h W_kI)`` (one head of
64, weight and bias), rotary on the first 32 columns of both (halves of
16 rotated, frequencies ``theta ** (-2i / 32)``, the temporal
component), ``w = h W_w * 16 ** -0.5 * 64 ** -0.5``; ``I[t, s] = sum_j
w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``. The published model
rotates its operands by a Hadamard matrix and rounds them to float8; a
rotation leaves a dot product where it was and float8 is another
result: both left out. ``S_t``: all ``s <= t`` while ``t + 1 <=
topk``, else the ``topk`` with the largest ``I`` (``lax.top_k`` over
the whole row of causal scores; a tie to the lower ``s``; ``-0.0``
counts as ``0.0``). Every head reads ``S_t``: ``softmax`` of ``q . k /
sqrt(128)`` over ``S_t``, times ``v``, then ``W_o``.

*Experts.* ``softmax(h W_r)`` over 128, the 8 largest, weights = the
chosen over their sum; an expert is ``(silu(h G) * (h U)) D`` at width
768; no shared expert.

The vision tower is left out: the catalog's ``config`` holds the
language model only.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form (``top.embed``, ``l<i>.q``, ...; for ``l<i>.gate``,
``.up`` and ``.down`` the stack of the experts named).
:func:`Reference.forward` reads one layer's tensors at a time, the
experts ``EXPERT_BLOCK`` at a time, and visits each expert once over
the tokens that chose it (a gather, the expert, a scatter); the
indexer's scores, the choice and the attention run ``QUERY_BLOCK``
queries at a time against every key, so that a long prompt's score
matrix fits the device: a block's rows of it are whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: routed experts read and run together
EXPERT_BLOCK = 64
#: queries whose rows of scores are held together
QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- rotary ---------------------------------------------------------------


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def mrope(cfg, x, positions3):
    """``x`` (L, heads, head_dim); ``positions3`` (3, L): multimodal
    rotary in Qwen2-VL's chunked form."""
    dim = cfg["head_dim"]
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.asarray(positions3, jnp.float32)[:, :, None] \
        * jnp.asarray(inv_freq, jnp.float32)               # (3, L, dim/2)
    emb = jnp.concatenate([freqs, freqs], -1)              # (3, L, dim)
    sections = list(cfg["rope_scaling"]["mrope_section"]) * 2
    edges = np.cumsum([0] + sections)
    emb = jnp.concatenate([emb[i % 3, :, edges[i]:edges[i + 1]]
                           for i in range(len(sections))], -1)
    emb = emb[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def rotary_front(cfg, x, positions, dim: int):
    """Plain rotary on the first ``dim`` columns of ``x`` (L, ...,
    width) at ``positions`` (L,); the rest pass."""
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)
    emb = emb.reshape(emb.shape[:1] + (1,) * (x.ndim - 2) + emb.shape[1:])
    turn = x[..., :dim]
    return jnp.concatenate(
        [turn * jnp.cos(emb) + rotate_half(turn) * jnp.sin(emb),
         x[..., dim:]], -1)


# -- attention under the indexer's sets -----------------------------------


def index_operands(cfg, w, x, positions):
    """-> (qI (L, heads, dim), kI (L, dim), w (L, heads))."""
    sa = cfg["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    length = x.shape[0]
    qi = rotary_front(cfg, (x @ w["index_q"]).reshape(length, heads, dim),
                      positions, dim // 2)
    ki = rotary_front(cfg, layer_norm(x @ w["index_k"], w["index_k_norm"],
                                      w["index_k_bias"],
                                      cfg["rms_norm_eps"]),
                      positions, dim // 2)
    return qi, ki, (x @ w["index_w"]) * (heads ** -0.5 * dim ** -0.5)


def attention(cfg, w, x, positions3, forced=None, forced_count=0,
              topk=None):
    """``x`` (L, hidden), normed. ``forced`` (L, L) bool, when given,
    replaces the indexer's choice of keys for the first ``forced_count``
    queries — a departure for evaluation only, as a router's forced
    choice: a near-tie at the ``topk``-th score then does not turn a
    rounding difference into another set.
    -> (out (L, hidden); the reference's own sets (L, L) bool;
    ``shortfall`` (L,): how far the weakest key of the set used lies
    under the reference's ``topk``-th best score, 0 for its own choice;
    ``bad`` (L,) bool: a set used that has another size than ``min(t +
    1, topk)`` or a key of the future; ``differ`` (L,): the keys in which
    the set used and the reference's own differ)."""
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    length = x.shape[0]
    topk = cfg["sa_config"]["topk"] if topk is None else topk
    q = mrope(cfg, rms_norm((x @ w["q"]).reshape(length, hq, dim),
                            w["q_norm"], eps), positions3)
    k = mrope(cfg, rms_norm((x @ w["k"]).reshape(length, hk, dim),
                            w["k_norm"], eps), positions3)
    v = (x @ w["v"]).reshape(length, hk, dim)
    qi, ki, wi = index_operands(cfg, w, x, positions3[0])
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    pad = blocks * block - length
    q, qi, wi = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in (q, qi, wi))
    if forced is not None:
        forced = jnp.pad(forced, ((0, pad), (0, 0)))
    at = jnp.arange(length)

    def some(lo):
        mine = lo + jnp.arange(block)
        causal = at[None, :] <= mine[:, None]
        scores = jnp.einsum(
            "th,ths->ts", lax.dynamic_slice_in_dim(wi, lo, block),
            jax.nn.relu(jnp.einsum(
                "thd,sd->ths", lax.dynamic_slice_in_dim(qi, lo, block),
                ki)))
        scores = jnp.where(causal, scores + 0.0, -jnp.inf)
        best, where = lax.top_k(scores, min(topk, length))
        own = jnp.zeros((block, length), bool) \
            .at[jnp.arange(block)[:, None], where].set(True) & causal
        used = own if forced is None else jnp.where(
            (mine < forced_count)[:, None],
            lax.dynamic_slice_in_dim(forced, lo, block), own)
        weakest = jnp.min(jnp.where(used, scores, jnp.inf), axis=1)
        bad = (used & ~causal).any(1) \
            | (used.sum(1) != jnp.minimum(mine + 1, topk))
        # a query with topk keys or fewer has no topk-th score: -inf
        shortfall = jnp.where(bad, jnp.inf, jnp.maximum(
            jnp.where(jnp.isfinite(best[:, -1]), best[:, -1] - weakest,
                      0.0), 0.0))
        s = jnp.einsum("tgpd,sgd->gpts",
                       lax.dynamic_slice_in_dim(q, lo, block)
                       .reshape(block, hk, hq // hk, dim), k) * dim ** -0.5
        s = jnp.where(used[None, None], s, -jnp.inf)
        out = jnp.einsum("gpts,sgd->tgpd", jax.nn.softmax(s, axis=-1), v)
        return out.reshape(block, hq * dim), own, shortfall, bad, \
            (own != used).sum(1)

    out, own, shortfall, bad, differ = lax.map(
        some, jnp.arange(blocks) * block)
    return (out.reshape(blocks * block, hq * dim)[:length] @ w["o"],
            own.reshape(blocks * block, length)[:length],
            shortfall.reshape(-1)[:length], bad.reshape(-1)[:length],
            differ.reshape(-1)[:length])


# -- experts --------------------------------------------------------------


def route(cfg, w, x, forced=None):
    """-> (ids (L, k), weights (L, k), shortfall (L,)). ``forced`` (L,
    k), when given, replaces the router's choice (for evaluation only:
    see :func:`attention`); the scores still give the weights.
    ``shortfall``: how far below the k-th best score the weakest of the
    ids used lies; 0 for the router's own choice."""
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    best, own = lax.top_k(scores, cfg["num_experts_per_tok"])
    ids = own if forced is None else forced
    picked = jnp.take_along_axis(scores, ids, 1)
    shortfall = best[:, -1] - picked.min(1)
    return ids, picked / picked.sum(-1, keepdims=True), shortfall


def held_part(w, x, ids, weights, experts, room: int):
    """The terms of the experts ``experts`` (ids; their stacks in ``w``):
    each visited once, over the tokens that chose it, at most ``room``
    of them."""
    length = x.shape[0]

    def add_expert(acc, e_w):
        e, gate, up, down = e_w
        hit = ids == e
        w_e = jnp.sum(jnp.where(hit, weights, 0.0), axis=-1)
        at = jnp.nonzero(hit.any(-1), size=room, fill_value=length)[0]
        rows = jnp.take(x, at, axis=0, mode="fill", fill_value=0.0)
        term = gated_mlp(rows, gate, up, down) \
            * jnp.take(w_e, at, mode="fill", fill_value=0.0)[:, None]
        return acc.at[at].add(term, mode="drop"), None

    out, _ = lax.scan(add_expert, jnp.zeros_like(x),
                      (experts, w["gate"], w["up"], w["down"]))
    return out


ATTENTION = ("q", "k", "v", "q_norm", "k_norm", "o", "index_q", "index_k",
             "index_w", "index_k_norm", "index_k_bias")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._attention = jax.jit(
            lambda w, h, positions3, forced, count, topk: attention(
                cfg, w, h, positions3, forced, count, topk),
            static_argnames=("topk",))
        self._route = jax.jit(lambda w, h, forced: route(cfg, w, h, forced))
        self._held = jax.jit(held_part, static_argnames=("room",))

    def experts(self, read, layer: int, h, held, forced=None):
        """One expert layer on ``h`` (L, hidden), normed: the terms of
        the experts ``held``. -> (out, ids, shortfall)."""
        ids, weights, shortfall = self._route(
            {"router": read("l%d.router" % layer)}, h, forced)
        held = np.asarray([int(e) for e in held], np.int32)
        chose = np.bincount(np.asarray(ids).reshape(-1),
                            minlength=int(held.max()) + 1)[held]
        # the most tokens any held expert serves, to a power of two: a
        # few compilations, not one a prompt
        room = 1 << max(3, int(chose.max() - 1).bit_length())
        routed = jnp.zeros_like(h)
        for lo in range(0, len(held), EXPERT_BLOCK):
            block = held[lo:lo + EXPERT_BLOCK]
            w = {t: read("l%d.%s" % (layer, t), block) for t in PER_EXPERT}
            routed = routed + self._held(w, h, ids, weights,
                                         jnp.asarray(block), room=room)
        return routed, ids, shortfall

    def forward(self, read, tokens, held=None, forced=None,
                forced_sets=None, forced_count=None, position=-1,
                positions3=None, topk=None, keep_sets=False):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of the
        router. ``forced``: (layers, L, k) router choices or None;
        ``forced_sets``: (layers, L, L) bool, the keys each query reads,
        for the first ``forced_count`` queries (all by default; those
        behind choose for themselves: a caller's padding), or None.
        ``position``: whose logits are returned, the last by
        default (the stack is causal, so a caller may pad a prompt
        behind its last token to a length it has compiled before, and
        ask for the last real one). ``positions3`` (3, L): the three
        position components, the index by default (text). ``topk``: in
        the place of ``sa_config.topk`` (a control's: at the prompt's
        length every query reads all its keys).
        -> {"logits": (vocab,), "chosen": (layers, L, k), "shortfall":
        (layers, L), "key_shortfall": (layers, L), "key_bad": (layers,
        L) bool, "key_differ": (layers, L) the keys in which the set used
        and the reference's own differ, and with
        ``keep_sets`` "key_sets": (layers, L, L) bool, its own}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        length = len(tokens)
        if held is None:
            held = range(cfg["num_experts"])
        if positions3 is None:
            positions3 = np.broadcast_to(np.arange(length), (3, length))
        positions3 = jnp.asarray(positions3, jnp.int32)
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        out = {key: [] for key in ("chosen", "shortfall", "key_shortfall",
                                   "key_bad", "key_differ", "key_sets")}
        for i in range(cfg["num_hidden_layers"]):
            h = rms_norm(x, read("l%d.attn_norm" % i), eps)
            used = None if forced_sets is None \
                else jnp.asarray(forced_sets[i])
            mixed, own, short, bad, differ = self._attention(
                {t: read("l%d.%s" % (i, t)) for t in ATTENTION}, h,
                positions3, used,
                length if forced_count is None else forced_count, topk=topk)
            x = x + mixed
            out["key_shortfall"].append(short)
            out["key_bad"].append(bad)
            out["key_differ"].append(differ)
            if keep_sets:
                out["key_sets"].append(own)
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            routed, ids, shortfall = self.experts(
                read, i, h, held,
                None if forced is None else jnp.asarray(forced[i]))
            out["chosen"].append(ids)
            out["shortfall"].append(shortfall)
            x = x + routed
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                **{key: jnp.stack(value) for key, value in out.items()
                   if value}}
