"""The plain reference of the Qwen3-Next family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it), the
delta rule token by token. It imports nothing from the program; it
follows the published description (``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct and the ``qwen3_next`` modelling code;
Gated DeltaNet, arXiv:2412.06464), and each departure is a comment.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += experts(RMSNorm(x))``,
eps ``rms_norm_eps``, the norm's weight stored zero-centred (``x_hat (1
+ w)``: ``Qwen3NextRMSNorm``); the layers are the model's first
``num_hidden_layers``; layer ``i`` is gated attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet; then a final
RMSNorm and an untied head on the last position.

*Gated DeltaNet.* ``[q | k | v | z] = x W_qkvz`` (16 x 128, 16 x 128, 32
x 128, 32 x 128 at the published sizes), ``[b | a] = x W_ba``. (The
published code stores both products' columns interleaved a key head and
reorders them at run time; here they lie part by part, heads-major: a
permutation of the columns of a matrix that is drawn at random.) A
causal depthwise convolution of ``linear_conv_kernel_dim`` taps, no
bias, over ``[q | k | v]``, then SiLU. ``q``, ``k`` L2-normalised a head
(eps 1e-6 under the root), ``q`` times ``Dk ** -0.5``; ``beta =
sigmoid(b)``; ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``. A
value head h reads key head ``h // 2``. State ``S`` (Dk x Dv) a value
head, zero at the first token::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``out = (RMSNorm_head(o) * silu(z)) W_o``, the norm over a head's Dv
columns, its weight plain (``Qwen3NextRMSNormGated``).

*Gated attention.* ``x W_q`` gives every head's ``[query | gate]``;
RMSNorm (zero-centred weight) over each head's columns of queries and
of keys; rotary on the first ``head_dim * partial_rotary_factor``
columns, halves rotated, plain frequencies of ``rope_theta``, positions
0..L-1; causal softmax, scale ``head_dim ** -0.5``, query head h on
key-value head ``h // (heads / kv heads)``; ``out = (attn *
sigmoid(gate)) W_o``.

*Experts.* ``softmax(x W_r)`` over all the model's experts, the
``num_experts_per_tok`` largest, weights = the chosen scores over their
sum (``norm_topk_prob``); an expert is ``(silu(x G) * (x U)) D``; plus
``sigmoid(x w_s)`` times the shared expert, of the same form.

The multi-token-prediction module is left out: the catalog's config has
no key for it, and a prefill that returns last-position logits never
runs it.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form (``top.embed``, ``l<i>.in_qkvz``, ...; for
``l<i>.gate``, ``.up`` and ``.down`` the stack of the experts named).
:func:`Reference.forward` reads one layer's tensors at a time, the
routed experts ``EXPERT_BLOCK`` at a time, and visits each held expert
once over the tokens that chose it (a gather, the expert, a scatter:
an expert over every token, as the other references loop, would cost
256 experts x 16,384 tokens a layer here); attention runs one head and
``QUERY_BLOCK`` queries at a time, so that a long prompt's scores fit
the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: routed experts read and run together
EXPERT_BLOCK = 64
#: queries of one head whose scores are held together
QUERY_BLOCK = 2048


def rms_norm(x, w, eps, centred=True):
    x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + w if centred else w)


def l2_norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- Gated DeltaNet -------------------------------------------------------


def causal_conv(x, weight):
    """``x`` (L, C); ``weight`` (C, K), ``weight[:, K-1]`` on the
    current token; zero history."""
    taps = weight.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + x.shape[0]] * weight[:, j]
               for j in range(taps))


def delta_rule(q, k, v, alpha, beta):
    """``q``, ``k``, (L, Hv, Dk) (each key head repeated for its value
    heads), ``v`` (L, Hv, Dv), ``alpha``, ``beta`` (L, Hv)."""
    def step(state, token):
        qt, kt, vt, at, bt = token
        state = state * at[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - read))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt)
    _, out = lax.scan(step, jnp.zeros(q.shape[1:] + v.shape[2:],
                                      jnp.float32),
                      (q, k, v, alpha, beta))
    return out


def deltanet(cfg, w, x):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    length = x.shape[0]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = x @ w["in_qkvz"]
    ba = x @ w["in_ba"]
    z = qkvz[:, 2 * key_dim + value_dim:]
    qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * key_dim + value_dim],
                                  w["conv_w"]))
    q = l2_norm(qkv[:, :key_dim].reshape(length, hk, dk)) * dk ** -0.5
    k = l2_norm(qkv[:, key_dim:2 * key_dim].reshape(length, hk, dk))
    v = qkv[:, 2 * key_dim:].reshape(length, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    alpha = jnp.exp(-jnp.exp(w["a_log"])
                    * jax.nn.softplus(ba[:, hv:] + w["dt_bias"]))
    out = delta_rule(jnp.repeat(q, hv // hk, axis=1),
                     jnp.repeat(k, hv // hk, axis=1), v, alpha, beta)
    out = rms_norm(out, w["o_norm"], cfg["rms_norm_eps"], centred=False)
    return (out.reshape(length, value_dim) * jax.nn.silu(z)) @ w["o"]


# -- gated attention ------------------------------------------------------


def rotary(cfg, x):
    """``x`` (L, heads, head_dim) at positions 0..L-1: the first
    ``rotary_dim`` columns turned, halves rotated; the rest pass."""
    dim = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    turn, rest = x[..., :dim], x[..., dim:]
    rotated = jnp.concatenate([-turn[..., dim // 2:],
                               turn[..., :dim // 2]], -1)
    return jnp.concatenate(
        [turn * jnp.cos(emb) + rotated * jnp.sin(emb), rest], -1)


def attention(cfg, w, x):
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    length = x.shape[0]
    query_gate = (x @ w["q"]).reshape(length, hq, 2 * dim)
    gate = query_gate[..., dim:].reshape(length, hq * dim)
    q = rotary(cfg, rms_norm(query_gate[..., :dim], w["q_norm"], eps))
    k = rotary(cfg, rms_norm((x @ w["k"]).reshape(length, hk, dim),
                             w["k_norm"], eps))
    v = (x @ w["v"]).reshape(length, hk, dim)
    # a head's queries in blocks, so that a long prompt's scores fit
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    q = jnp.pad(q, ((0, blocks * block - length), (0, 0), (0, 0)))
    at = jnp.arange(length)

    def one_head(h):
        kv = h // (hq // hk)

        def some(lo):
            s = (lax.dynamic_slice_in_dim(q[:, h], lo, block) @ k[:, kv].T) \
                * dim ** -0.5
            s = jnp.where(at[None, :] <= lo + jnp.arange(block)[:, None],
                          s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, kv]
        return lax.map(some, jnp.arange(blocks) * block) \
            .reshape(blocks * block, dim)[:length]

    out = lax.map(one_head, jnp.arange(hq))            # (hq, L, dim)
    out = out.transpose(1, 0, 2).reshape(length, hq * dim)
    return (out * jax.nn.sigmoid(gate)) @ w["o"]


# -- experts --------------------------------------------------------------


def route(cfg, w, x, forced=None):
    """-> (ids (L, k), weights (L, k), shortfall (L,), what every chip
    computes alike: the gated shared expert's term (L, hidden)).
    ``forced`` (L, k), when given, replaces the router's choice — a
    departure for evaluation only: it lets a comparison hold the
    arithmetic to a tight tolerance without a near-tie in the router
    turning a rounding difference into a different expert; the scores
    still give the weights. ``shortfall``: how far below the k-th best
    score the weakest of the ids used lies; 0 for the router's own
    choice."""
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    best, own = lax.top_k(scores, cfg["num_experts_per_tok"])
    ids = own if forced is None else forced
    picked = jnp.take_along_axis(scores, ids, 1)
    shortfall = best[:, -1] - picked.min(1)
    weights = picked / picked.sum(-1, keepdims=True)
    shared = jax.nn.sigmoid(x @ w["shared_w"]) * gated_mlp(
        x, w["shared_gate"], w["shared_up"], w["shared_down"])
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


DELTANET = ("in_qkvz", "in_ba", "conv_w", "dt_bias", "a_log", "o_norm", "o")
ATTENTION = ("q", "k", "v", "q_norm", "k_norm", "o")
ROUTE = ("router", "shared_gate", "shared_up", "shared_down", "shared_w")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._deltanet = jax.jit(lambda w, h: deltanet(cfg, w, h))
        self._attention = jax.jit(lambda w, h: attention(cfg, w, h))
        self._route = jax.jit(lambda w, h, forced: route(cfg, w, h, forced))
        self._held = jax.jit(held_part, static_argnames=("room",))

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.cfg["full_attention_interval"] == 0

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
        the router. ``forced``: (layers, L, k) choices or None.
        ``position``: whose logits are returned, the last by default
        (every mixer is causal, so a caller may pad a prompt behind its
        last token to a length it has compiled before, and ask for the
        last real one).
        -> {"logits": (vocab,), "chosen": (layers, L, k), "shortfall":
        (layers, L)}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        if held is None:
            held = range(cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]))
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        chosen, short = [], []
        for i in range(cfg["num_hidden_layers"]):
            h = rms_norm(x, read("l%d.mixer_norm" % i), eps)
            if self.is_attention(i):
                x = x + self._attention(
                    {t: read("l%d.%s" % (i, t)) for t in ATTENTION}, h)
            else:
                x = x + self._deltanet(
                    {t: read("l%d.%s" % (i, t)) for t in DELTANET}, h)
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            out, ids, shortfall, _, _ = self.experts(
                read, i, h, held,
                None if forced is None else jnp.asarray(forced[i]))
            chosen.append(ids)
            short.append(shortfall)
            x = x + out
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen),
                "shortfall": jnp.stack(short)}
