"""The plain reference of the Nemotron-H family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it). It
imports nothing from the program; it follows the published
description (``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
and the family's modelling code), and each departure is a comment.

Every block is ``x + mixer(RMSNorm(x))``, eps ``layer_norm_epsilon``;
the blocks are the first ``num_hidden_layers`` characters of
``hybrid_override_pattern`` (M Mamba-2, E experts, * attention); then a
final RMSNorm and an untied head on the last position.

``read(name, expert_ids=None)`` hands over one tensor's float32
values (``top.embed``, ``b<i>.in_proj``, ...; for ``b<i>.up`` and
``b<i>.down`` the stack of the experts named). :func:`forward` reads
one block's tensors at a time and runs each kind of block as one
jitted function of (weights, activations), so that a prompt costs a
few compilations and never holds more than one block in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba(cfg, w, x):
    """Mamba-2 as the plain recurrence over t. ``x`` (L, hidden)."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    k_taps = cfg["conv_kernel"]
    d_inner = heads * p          # not expand x hidden: 64 heads of 64
    conv_dim = d_inner + 2 * groups * n
    zxbcdt = x @ w["in_proj"]
    z, xbc, dt = (zxbcdt[:, :d_inner],
                  zxbcdt[:, d_inner:d_inner + conv_dim],
                  zxbcdt[:, d_inner + conv_dim:])
    # causal depthwise conv1d, kernel 4, zero history at the start
    taps = w["conv_w"]
    padded = jnp.pad(xbc, ((k_taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + xbc.shape[0]] * taps[:, j]
               for j in range(k_taps)) + w["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(-1, heads, p)
    b = xbc[:, d_inner:d_inner + groups * n].reshape(-1, groups, n)
    c = xbc[:, d_inner + groups * n:].reshape(-1, groups, n)
    # head h reads group h // (heads / groups)
    b = jnp.repeat(b, heads // groups, axis=1)
    c = jnp.repeat(c, heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])
    d = w["d"]

    def step(state, inp):
        xs_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * xs_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * xs_t
        return state, y_t

    _, y = lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                    (xs, b, c, dt))
    y = y.reshape(-1, d_inner) * jax.nn.silu(z)
    # gated norm: RMS over each of n_groups groups of d_inner/n_groups
    yg = y.reshape(-1, groups, d_inner // groups)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                        + cfg["layer_norm_epsilon"])
    y = yg.reshape(-1, d_inner) * w["gnorm"]
    return y @ w["out_proj"]


def attention(cfg, w, x):
    """Causal grouped-query attention, one masked softmax a head. No
    rotary embedding: the family's modelling code applies none in this
    mixer although ``config.json`` carries ``rope_theta``."""
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    length = x.shape[0]
    q = (x @ w["q"]).reshape(length, hq, dim)
    k = (x @ w["k"]).reshape(length, hk, dim)
    v = (x @ w["v"]).reshape(length, hk, dim)
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one_head(h):
        kv = h // (hq // hk)
        s = (q[:, h] @ k[:, kv].T) * dim ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, kv]

    # one head at a time, so a long prompt's scores fit the device
    out = lax.map(one_head, jnp.arange(hq))            # (hq, L, dim)
    return out.transpose(1, 0, 2).reshape(length, hq * dim) \
        @ w["o"]


def experts(cfg, w, x, held, forced=None):
    """Sigmoid router over all the model's experts, top-k of score +
    correction bias, weights = chosen scores over their sum times the
    scaling factor; ``relu(x U)^2 D`` experts, one shared expert.

    ``held``: the ids of the experts whose terms are kept (the chip's
    share); the others' terms are left out, the weights stay normalised
    over all the chosen. ``forced`` (L, k), when given, replaces the
    router's choice — a departure for evaluation only: it lets a
    comparison hold the arithmetic to a tight tolerance without a
    near-tie in the router turning a rounding difference into a
    different expert; the scores still give the weights.
    -> (out, chosen ids (L, k), shortfall (L,): how far below the k-th
    best score the weakest of the ids used lies; 0 for the router's
    own choice)."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w["router"])
    adjusted = scores + w["b_corr"]
    best, own = lax.top_k(adjusted, k)
    ids = own if forced is None else forced
    shortfall = best[:, -1] - jnp.take_along_axis(adjusted, ids, 1).min(1)
    picked = jnp.take_along_axis(scores, ids, 1)
    weights = picked / picked.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]

    def add_expert(acc, e_up_down):
        e, up, down = e_up_down
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return acc + w_e[:, None] \
            * (jnp.square(jax.nn.relu(x @ up)) @ down), None

    # the loop over the chosen experts, turned inside out: one held
    # expert at a time over the tokens that chose it (weight 0 for the
    # rest), so that no per-token copy of an expert is made
    routed, _ = lax.scan(add_expert, jnp.zeros_like(x),
                         (held, w["up"], w["down"]))
    shared = jnp.square(jax.nn.relu(x @ w["shared_up"])) \
        @ w["shared_down"]
    return routed + shared, ids, shortfall


#: the tensors each kind of block reads
TENSORS = {
    "M": ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d", "gnorm",
          "out_proj"),
    "*": ("q", "k", "v", "o"),
    "E": ("router", "b_corr", "up", "down", "shared_up", "shared_down"),
}


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._mamba = jax.jit(lambda w, h: mamba(cfg, w, h))
        self._attention = jax.jit(lambda w, h: attention(cfg, w, h))
        self._experts = jax.jit(
            lambda w, h, held, forced: experts(cfg, w, h, held, forced))

    def forward(self, read, tokens, held=None, forced=None,
                position=-1):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of
        the router. ``forced``: (E blocks, L, k) choices or None.
        ``position``: whose logits are returned, the last by default
        (every mixer is causal, so a caller may pad a prompt behind its
        last token to a length it has compiled before, and ask for the
        last real one).
        -> {"logits": (vocab,), "chosen": (E blocks, L, k),
        "shortfall": (E blocks, L)}"""
        cfg = self.cfg
        eps = cfg["layer_norm_epsilon"]
        pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
        if held is None:
            held = range(cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]))
        held = jnp.asarray([int(e) for e in held], jnp.int32)
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        chosen, shortfalls = [], []
        for i, kind in enumerate(pattern):
            h = rms_norm(x, read("b%d.norm" % i), eps)
            w = {t: read("b%d.%s" % (i, t),
                         held if t in ("up", "down") else None)
                 for t in TENSORS[kind]}
            if kind == "M":
                out = self._mamba(w, h)
            elif kind == "*":
                out = self._attention(w, h)
            else:
                out, ids, shortfall = self._experts(
                    w, h, held, None if forced is None
                    else jnp.asarray(forced[len(chosen)]))
                chosen.append(ids)
                shortfalls.append(shortfall)
            x = x + out
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen) if chosen else None,
                "shortfall": jnp.stack(shortfalls) if shortfalls else None}
