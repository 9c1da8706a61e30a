"""The plain reference of the Kimi-Linear family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it), the
delta rule token by token in its sequential form. It imports nothing
from the program; it follows the published description (``config.json``
of moonshotai/Kimi-Linear-48B-A3B-Instruct, the catalog's
``described_as``; Kimi Linear, arXiv:2510.26692) as ISSUE 49 reads it,
and each departure is a comment.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``,
eps ``rms_norm_eps``, the norms' weights plain; the layers are the
model's first ``num_hidden_layers``; ``linear_attn_config`` numbers
them from 1: ``kda_layers`` mix by Kimi Delta Attention,
``full_attn_layers`` by latent attention; the first
``first_k_dense_replace`` layers' feed-forward is a dense gated MLP,
the others' the expert block; then a final RMSNorm and an untied head
on the last position.

*Kimi Delta Attention.* ``q, k, v = SiLU(conv(x W_q)), SiLU(conv(x
W_k)), SiLU(conv(x W_v))``, three causal depthwise convolutions of
``short_conv_kernel_size`` taps, no bias, zero history (here: the
columns ``[q | k | v]`` of one stored matrix and the rows of one stored
filter, which is the same three products and three convolutions);
``q``, ``k`` L2-normalised a head (1e-6 under the root), ``q`` times
``D ** -0.5``. The gate is a vector: ``alpha_t = exp(-exp(A_log[head])
softplus(W_fb (W_fa x_t) + dt_bias))``, one decay a key channel;
``beta_t = sigmoid(W_b x_t)`` one a head. A head's state ``S`` (Dk x
Dv), zero at the first token::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``out = (RMSNorm_head(o) * sigmoid(W_gb (W_ga x))) W_o``, the norm over
a head's Dv columns with one weight vector of Dv.

*Latent attention, no positions* (``mla_use_nope``). ``q = x W_q``
(heads x (nope + rope) columns, no query latent); ``[c | k_r] = x
W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb`` a head; a head's key is
``[k_nope | k_r]``, ``k_r`` shared by all heads; nothing is rotated;
causal softmax at scale ``(nope + rope) ** -0.5``; ``W_o``.

*Experts.* ``s = sigmoid(x W_r)``; the ``num_experts_per_token``
largest of ``s + b`` (a correction bias for the choice alone); weights
= the chosen ``s`` over their sum (``moe_renormalize``) times
``routed_scaling_factor``; an expert is ``(silu(x G) * (x U)) D``; plus
``num_shared_experts`` shared ones as one MLP of that many times the
width, ungated.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form (``top.embed``, ``l<i>.in_qkv``, ...; for
``l<i>.gate``, ``.up`` and ``.down`` of an expert layer the stack of
the experts named). :func:`Reference.forward` reads one layer's tensors
at a time, the routed experts ``EXPERT_BLOCK`` at a time, and visits
each held expert once over the tokens that chose it (a gather, the
expert, a scatter); attention runs one head and ``QUERY_BLOCK`` queries
at a time, and the dense MLP ``TOKEN_BLOCK`` tokens at a time, so that
a 16k-token prompt fits the device beside the program's weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: routed experts read and run together
EXPERT_BLOCK = 32
#: queries of one head whose scores are held together
QUERY_BLOCK = 2048
#: tokens the dense MLP takes together
TOKEN_BLOCK = 4096


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- Kimi Delta Attention -------------------------------------------------


def causal_conv(x, weight):
    """``x`` (L, C); ``weight`` (C, K), ``weight[:, K-1]`` on the
    current token; zero history: K shifted multiplies."""
    taps = weight.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + x.shape[0]] * weight[:, j]
               for j in range(taps))


def delta_rule(q, k, v, alpha, beta):
    """``q``, ``k``, ``alpha`` (L, H, Dk), ``v`` (L, H, Dv), ``beta``
    (L, H): the recurrence as written, a token a step."""
    def step(state, token):
        qt, kt, vt, at, bt = token
        state = state * at[:, :, None]                  # Diag(alpha) S
        read = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - read))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt)
    _, out = lax.scan(step, jnp.zeros(q.shape[1:] + v.shape[2:],
                                      jnp.float32),
                      (q, k, v, alpha, beta))
    return out


def kda(cfg, w, x):
    linear = cfg["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    length, width = x.shape[0], heads * dim
    qkv = jax.nn.silu(causal_conv(x @ w["in_qkv"], w["conv_w"]))
    q = l2_norm(qkv[:, :width].reshape(length, heads, dim)) * dim ** -0.5
    k = l2_norm(qkv[:, width:2 * width].reshape(length, heads, dim))
    v = qkv[:, 2 * width:].reshape(length, heads, dim)
    step = jax.nn.softplus((x @ w["f_a"]) @ w["f_b"] + w["dt_bias"])
    alpha = jnp.exp(-jnp.exp(w["a_log"])[:, None]
                    * step.reshape(length, heads, dim))
    beta = jax.nn.sigmoid(x @ w["in_b"])
    out = rms_norm(delta_rule(q, k, v, alpha, beta), w["o_norm"],
                   cfg["rms_norm_eps"]).reshape(length, width)
    return (out * jax.nn.sigmoid((x @ w["g_a"]) @ w["g_b"])) @ w["o"]


# -- latent attention without positions -----------------------------------


def attention(cfg, w, x):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, value = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    length = x.shape[0]
    q = (x @ w["q"]).reshape(length, heads, nope + rot)
    down = x @ w["kv_a"]
    kv = (rms_norm(down[:, :rank], w["kv_a_norm"], cfg["rms_norm_eps"])
          @ w["kv_b"]).reshape(length, heads, nope + value)
    k_r = down[:, rank:]                               # (L, rot), unrotated
    # a head's queries in blocks, so that a long prompt's scores fit
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    q = jnp.pad(q, ((0, blocks * block - length), (0, 0), (0, 0)))
    at = jnp.arange(length)

    def one_head(h):
        k = jnp.concatenate([kv[:, h, :nope], k_r], -1)

        def some(lo):
            s = (lax.dynamic_slice_in_dim(q[:, h], lo, block) @ k.T) \
                * (nope + rot) ** -0.5
            s = jnp.where(at[None, :] <= lo + jnp.arange(block)[:, None],
                          s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ kv[:, h, nope:]
        return lax.map(some, jnp.arange(blocks) * block) \
            .reshape(blocks * block, value)[:length]

    out = lax.map(one_head, jnp.arange(heads))         # (H, L, value)
    return out.transpose(1, 0, 2).reshape(length, heads * value) @ w["o"]


# -- feed-forward ---------------------------------------------------------


def dense(cfg, w, x):
    """The first layer's MLP, ``TOKEN_BLOCK`` tokens at a time."""
    length = x.shape[0]
    block = min(TOKEN_BLOCK, length)
    blocks = -(-length // block)
    x = jnp.pad(x, ((0, blocks * block - length), (0, 0)))
    out = lax.map(lambda rows: gated_mlp(rows, w["gate"], w["up"],
                                         w["down"]),
                  x.reshape(blocks, block, -1))
    return out.reshape(blocks * block, -1)[:length]


def route(cfg, w, x, forced=None):
    """-> (ids (L, k), weights (L, k), shortfall (L,), the shared
    expert's term (L, hidden), which every chip computes alike).
    ``forced`` (L, k), when given, replaces the router's choice — a
    departure for evaluation only: it lets a comparison hold the
    arithmetic to a tight tolerance without a near-tie in the router
    turning a rounding difference into a different expert; the scores
    still give the weights. ``shortfall``: how far below the k-th best
    of ``s + b`` the weakest of the ids used lies; 0 for the router's
    own choice."""
    scores = jax.nn.sigmoid(x @ w["router"])
    choice = scores + w["b_corr"]
    best, own = lax.top_k(choice, cfg["num_experts_per_token"])
    ids = own if forced is None else forced
    shortfall = best[:, -1] - jnp.take_along_axis(choice, ids, 1).min(1)
    picked = jnp.take_along_axis(scores, ids, 1)
    weights = picked / picked.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]
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


KDA = ("in_qkv", "conv_w", "in_b", "f_a", "f_b", "dt_bias", "a_log",
       "g_a", "g_b", "o_norm", "o")
ATTENTION = ("q", "kv_a", "kv_a_norm", "kv_b", "o")
DENSE = ("gate", "up", "down")
ROUTE = ("router", "b_corr", "shared_gate", "shared_up", "shared_down")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._kda = jax.jit(lambda w, h: kda(cfg, w, h))
        self._attention = jax.jit(lambda w, h: attention(cfg, w, h))
        self._dense = jax.jit(lambda w, h: dense(cfg, w, h))
        self._route = jax.jit(lambda w, h, forced: route(cfg, w, h, forced))
        self._held = jax.jit(held_part, static_argnames=("room",))

    def is_attention(self, layer: int) -> bool:
        """``layer`` counts from 0, the published lists from 1."""
        return layer + 1 in self.cfg["linear_attn_config"][
            "full_attn_layers"]

    def experts(self, read, layer: int, h, held, forced=None):
        """One expert layer on ``h`` (L, hidden), normed: the terms of
        the experts ``held`` and what every chip computes alike.
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
        (every mixer is causal, so a caller may pad a prompt behind its
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
            def w(names):
                return {t: read("l%d.%s" % (i, t)) for t in names}
            h = rms_norm(x, read("l%d.mixer_norm" % i), eps)
            if self.is_attention(i):
                x = x + self._attention(w(ATTENTION), h)
            else:
                x = x + self._kda(w(KDA), h)
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            if i < cfg["first_k_dense_replace"]:
                out = self._dense(w(DENSE), h)
            else:
                out, ids, shortfall, _, _ = self.experts(
                    read, i, h, held, None if forced is None
                    else jnp.asarray(forced[len(chosen)]))
                chosen.append(ids)
                short.append(shortfall)
            x = x + out
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen),
                "shortfall": jnp.stack(short)}
