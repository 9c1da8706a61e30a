"""The plain reference of the K-EXAONE family (``exaone_moe``): one
prompt at a time, unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it). It
imports nothing from the program; it follows the published description
(``config.json`` of LGAI-EXAONE/K-EXAONE-236B-A23B and, where that is
silent, the EXAONE 4.0 family's convention: arXiv:2507.11407,
``Exaone4Attention`` and ``Exaone4DecoderLayer`` in ``transformers``),
and each departure is a comment.

Every layer is ``x += RMSNorm(attn(x))``, ``x += RMSNorm(ffn(x))``: the
norms stand behind the mixer and the feed-forward, eps
``rms_norm_eps``, weights plain; the layers are the model's first
``num_hidden_layers``; then a final RMSNorm and an untied head on the
last position.

*Attention.* ``q = x W_q`` (64 heads of 128 at the published sizes),
``k = x W_k``, ``v = x W_v`` (8 heads of 128); an RMSNorm over each
head's columns of queries and of keys. Where ``layer_types[i]`` is
``sliding_attention``: rotary on the whole head, halves rotated, plain
frequencies of ``rope_parameters.rope_theta``, positions 0..L-1, and
query t reads the keys s with ``t - sliding_window < s <= t`` (the
window counts the query's own key). Where it is ``full_attention``: no
rotary at all and every ``s <= t``. ``softmax(q k^T / sqrt(head_dim) +
mask) v``, query head h on key-value head ``h // (heads / kv heads)``,
then ``W_o``. The mask is explicit: a block of queries against every
key of the prompt.

*Feed-forward.* Where ``mlp_layer_types[i]`` is ``dense``: ``(silu(x G)
* (x U)) D`` of width ``intermediate_size``. Else ``s = sigmoid(x
W_r)`` over all the model's experts, the ``num_experts_per_tok``
largest of ``s + b`` (the correction bias, for the choice alone;
``n_group`` and ``topk_group`` are 1: no group limit), weights
``routed_scaling_factor * s_i / sum(s_chosen)`` (``norm_topk_prob``);
an expert is a gated MLP of width ``moe_intermediate_size``; plus
``num_shared_experts`` of the same form that every token visits (one
gated MLP of their widths together).

The multi-token-prediction module (``num_nextn_predict_layers``) is
left out: a prefill that returns last-position logits never runs it.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form (``top.embed``, ``l<i>.q``, ...; for
``l<i>.gate``, ``.up`` and ``.down`` the stack of the experts named).
:func:`Reference.forward` reads one layer's tensors at a time, the
routed experts ``EXPERT_BLOCK`` at a time, and visits each held expert
once over the tokens that chose it (a gather, the expert, a scatter);
attention runs one head and ``QUERY_BLOCK`` queries at a time, so that a
long prompt's scores fit the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: routed experts read and run together
EXPERT_BLOCK = 8
#: queries of one head whose scores are held together
QUERY_BLOCK = 2048


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def rotary(cfg, x):
    """``x`` (L, heads, head_dim) at positions 0..L-1, halves rotated."""
    dim = cfg["head_dim"]
    inv_freq = 1.0 / cfg["rope_parameters"]["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def attention(cfg, w, x, sliding: bool):
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    length = x.shape[0]
    q = rms_norm((x @ w["q"]).reshape(length, hq, dim), w["q_norm"], eps)
    k = rms_norm((x @ w["k"]).reshape(length, hk, dim), w["k_norm"], eps)
    v = (x @ w["v"]).reshape(length, hk, dim)
    if sliding:
        q, k = rotary(cfg, q), rotary(cfg, k)
    # a head's queries in blocks, so that a long prompt's scores fit
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    q = jnp.pad(q, ((0, blocks * block - length), (0, 0), (0, 0)))
    key_at = jnp.arange(length)[None, :]

    def one_head(h):
        kv = h // (hq // hk)

        def some(lo):
            s = (lax.dynamic_slice_in_dim(q[:, h], lo, block) @ k[:, kv].T) \
                * dim ** -0.5
            query_at = lo + jnp.arange(block)[:, None]
            mask = key_at <= query_at
            if sliding:
                mask = mask & (key_at > query_at - window)
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) \
                @ v[:, kv]
        return lax.map(some, jnp.arange(blocks) * block) \
            .reshape(blocks * block, dim)[:length]

    out = lax.map(one_head, jnp.arange(hq))            # (hq, L, dim)
    return out.transpose(1, 0, 2).reshape(length, hq * dim) @ w["o"]


def route(cfg, w, x, forced=None):
    """-> (ids (L, k), weights (L, k), shortfall (L,), what every chip
    computes alike: the shared experts' term (L, hidden)). ``forced``
    (L, k), when given, replaces the router's choice — a departure for
    evaluation only: it lets a comparison hold the arithmetic to a tight
    tolerance without a near-tie in the router turning a rounding
    difference into a different expert; the scores still give the
    weights. ``shortfall``: how far below the k-th best ``s + b`` the
    weakest of the ids used lies; 0 for the router's own choice."""
    scores = jax.nn.sigmoid(x @ w["router"])
    adjusted = scores + w["b_corr"]
    best, own = lax.top_k(adjusted, cfg["num_experts_per_tok"])
    ids = own if forced is None else forced
    shortfall = best[:, -1] - jnp.take_along_axis(adjusted, ids, 1).min(1)
    picked = jnp.take_along_axis(scores, ids, 1)
    weights = cfg["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)
    shared = gated_mlp(x, w["shared_gate"], w["shared_up"],
                       w["shared_down"])
    return ids, weights, shortfall, shared


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


ATTENTION = ("q", "k", "v", "q_norm", "k_norm", "o")
DENSE = ("gate", "up", "down")
ROUTE = ("router", "b_corr", "shared_gate", "shared_up", "shared_down")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
                or cfg["scoring_func"] != "sigmoid" \
                or not cfg["norm_topk_prob"]:
            raise ValueError("a router this reference does not describe")
        self.cfg = cfg
        self._attention = jax.jit(
            lambda w, h, sliding: attention(cfg, w, h, sliding),
            static_argnames=("sliding",))
        self._dense = jax.jit(lambda w, h: gated_mlp(
            h, w["gate"], w["up"], w["down"]))
        self._route = jax.jit(lambda w, h, forced: route(cfg, w, h, forced))
        self._held = jax.jit(held_part, static_argnames=("room",))

    def is_sliding(self, layer: int) -> bool:
        return self.cfg["layer_types"][layer] == "sliding_attention"

    def is_dense(self, layer: int) -> bool:
        return self.cfg["mlp_layer_types"][layer] == "dense"

    def experts(self, read, layer: int, h, held, forced=None):
        """One expert layer on ``h`` (L, hidden): the terms of the
        experts ``held`` and what every chip computes alike.
        -> (out, ids, shortfall, routed alone, shared alone)."""
        ids, weights, shortfall, shared = self._route(
            {t: read("l%d.%s" % (layer, t)) for t in ROUTE}, h, forced)
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
        return routed + shared, ids, shortfall, routed, shared

    def forward(self, read, tokens, held=None, forced=None,
                position=-1):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of
        the router. ``forced``: (expert layers, L, k) choices or None.
        ``position``: whose logits are returned, the last by default
        (attention is causal, so a caller may pad a prompt behind its
        last token to a length it has compiled before, and ask for the
        last real one).
        -> {"logits": (vocab,), "chosen": (expert layers, L, k),
        "shortfall": (expert layers, L)}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        if held is None:
            held = range(cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]))
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        chosen, short = [], []
        for i in range(cfg["num_hidden_layers"]):
            out = self._attention(
                {t: read("l%d.%s" % (i, t)) for t in ATTENTION}, x,
                sliding=self.is_sliding(i))
            x = x + rms_norm(out, read("l%d.attn_norm" % i), eps)
            if self.is_dense(i):
                out = self._dense(
                    {t: read("l%d.%s" % (i, t)) for t in DENSE}, x)
            else:
                out, ids, shortfall, _, _ = self.experts(
                    read, i, x, held,
                    None if forced is None
                    else jnp.asarray(forced[len(chosen)]))
                chosen.append(ids)
                short.append(shortfall)
            x = x + rms_norm(out, read("l%d.ffn_norm" % i), eps)
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen),
                "shortfall": jnp.stack(short)}
