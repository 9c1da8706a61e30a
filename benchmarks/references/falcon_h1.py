"""The plain reference of the Falcon-H1 family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it), the
recurrence token by token, attention as a plain masked softmax, no
kernel and no packing. It imports nothing from the program. The sizes,
switches and the twelve multipliers of the mixers, the embedding and the
head, with the MLP's two, are ``config.json``'s of
tiiuae/Falcon-H1-34B-Instruct; the equations are those of
``transformers``' ``modeling_falcon_h1.py`` as the builder of PR 53
wrote them down (arXiv:2507.22448), and every scalar stands where they
have it:

**Stack**: ``h = E[token] * embedding_multiplier``; all blocks alike:
``u = RMSNorm(h)``; ``h = h + ssm(u) + attention(u)``; ``f =
RMSNorm(h)``; ``h = h + mlp(f)``; ``logits = (RMSNorm(h) W_head) *
lm_head_multiplier``; eps ``rms_norm_eps``.

**ssm** (Mamba-2): ``p = ((u * ssm_in_multiplier) W_in) * mu`` with
``mu`` the five ``ssm_multipliers`` over the columns z (``mamba_d_ssm``)
| x (``mamba_d_ssm``) | B | C (``mamba_n_groups * mamba_d_state`` each)
| dt (``mamba_n_heads``); ``x | B | C = silu(conv(x | B | C) + bias)``,
a causal depthwise convolution of ``mamba_d_conv`` taps from zero
history; head i reads group ``i // (heads / groups)``; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt_t A)
S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``g = y *
silu(z)`` RMS-normed over each group's columns, times the norm's weight
(``mamba_rms_norm``, not ``mamba_norm_before_gate``); ``(g W_out) *
ssm_out_multiplier``.

**attention**: ``a_in = u * attention_in_multiplier``; ``q = a_in W_q``,
``k = (a_in W_k) * key_multiplier``, ``v = a_in W_v``; rotary over all
``head_dim`` columns, halves rotated, ``rope_theta``, positions 0 .. L-1;
query head j reads key-value head ``j // (heads / kv heads)``; causal
softmax of ``q k^T / sqrt(head_dim)``; ``(. W_o) *
attention_out_multiplier``. Queries are taken ``QUERY_STEP`` at a time,
so that a prompt of 8k tokens' scores fit the device.

**mlp**: ``((silu((f W_gate) * mlp_multipliers[0]) * (f W_up)) W_down) *
mlp_multipliers[1]``.

``read(name, index=None)`` hands over one tensor's float32 values
(``top.embed``, ``l<i>.in_proj``, ...; of ``stored[index]`` with an
index). :func:`Reference.forward` reads one tensor group at a time and
runs each mixer as one jitted function of (weights, activations), so
that a prompt costs a few compilations and never holds more than one
block in float32; the embedding is read by the prompt's rows and the
head in blocks of ``HEAD_COLUMNS`` columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: queries a step of the attention
QUERY_STEP = 512
#: columns of the head a product
HEAD_COLUMNS = 32768


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def ssm(cfg, w, u):
    """Mamba-2 as the plain recurrence over t. ``u`` (L, hidden)."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    taps, d_ssm = cfg["mamba_d_conv"], cfg["mamba_d_ssm"]
    wide = groups * n
    parts = (d_ssm, d_ssm, wide, wide, heads)
    mu = jnp.concatenate([jnp.full((width,), m, jnp.float32) for width, m
                          in zip(parts, cfg["ssm_multipliers"])])
    proj = ((u * cfg["ssm_in_multiplier"]) @ w["in_proj"]) * mu
    z, xbc, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * wide],
                  proj[:, 2 * d_ssm + 2 * wide:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + xbc.shape[0]] * w["conv_w"][:, j]
               for j in range(taps)) + w["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_ssm].reshape(-1, heads, p)
    b = xbc[:, d_ssm:d_ssm + wide].reshape(-1, groups, n)
    c = xbc[:, d_ssm + wide:].reshape(-1, groups, n)
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
    g = (y.reshape(-1, d_ssm) * jax.nn.silu(z)) \
        .reshape(-1, groups, d_ssm // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                      + cfg["rms_norm_eps"])
    return ((g.reshape(-1, d_ssm) * w["gnorm"]) @ w["out_proj"]) \
        * cfg["ssm_out_multiplier"]


def rotary(cfg, x):
    """``x`` (L, heads, dim) at positions 0 .. L-1: halves rotated."""
    length, dim = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def attention(cfg, w, u):
    """Causal grouped-query attention with rotary positions, a block of
    queries at a time. ``u`` (L, hidden)."""
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    length = u.shape[0]
    a_in = u * cfg["attention_in_multiplier"]
    q = rotary(cfg, (a_in @ w["q"]).reshape(length, hq, dim))
    k = rotary(cfg, ((a_in @ w["k"]) * cfg["key_multiplier"])
               .reshape(length, hk, dim))
    v = (a_in @ w["v"]).reshape(length, hk, dim)
    k = jnp.repeat(k, hq // hk, axis=1)
    v = jnp.repeat(v, hq // hk, axis=1)
    # a block of queries at a time, the last block padded with queries
    # whose results are dropped
    step = min(QUERY_STEP, length)
    blocks = -(-length // step)
    q = jnp.pad(q, ((0, blocks * step - length), (0, 0), (0, 0)))
    key_at = jnp.arange(length)

    def block(first):
        mine = lax.dynamic_slice_in_dim(q, first, step)      # (step, hq, d)
        s = jnp.einsum("qhd,khd->hqk", mine, k) * dim ** -0.5
        seen = key_at[None, :] <= (first + jnp.arange(step))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(block, jnp.arange(blocks) * step)
    return (out.reshape(blocks * step, hq * dim)[:length] @ w["o"]) \
        * cfg["attention_out_multiplier"]


def mlp(cfg, w, f):
    gate, down = cfg["mlp_multipliers"]
    return ((jax.nn.silu((f @ w["gate"]) * gate) * (f @ w["up"]))
            @ w["down"]) * down


#: the tensors each part of a block reads
SSM = ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d", "gnorm",
       "out_proj")
ATTENTION = ("q", "k", "v", "o")
MLP = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._ssm = jax.jit(lambda w, u: ssm(cfg, w, u))
        self._attention = jax.jit(lambda w, u: attention(cfg, w, u))
        self._mlp = jax.jit(lambda w, f: mlp(cfg, w, f))

    def forward(self, read, tokens, position=-1, **_):
        """``tokens`` (L,) ids. ``position``: whose logits are returned,
        the last by default (every mixer is causal, so a caller may pad
        a prompt behind its last token to a length it has compiled
        before, and ask for the last real one).
        -> {"logits": (vocab,)}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        x = read("top.embed", jnp.asarray(tokens)) \
            * cfg["embedding_multiplier"]
        for i in range(cfg["num_hidden_layers"]):
            def group(names):
                return {t: read("l%d.%s" % (i, t)) for t in names}
            u = rms_norm(x, read("l%d.input_norm" % i), eps)
            x = x + self._ssm(group(SSM), u) \
                + self._attention(group(ATTENTION), u)
            f = rms_norm(x, read("l%d.pre_ff_norm" % i), eps)
            x = x + self._mlp(group(MLP), f)
        last = rms_norm(x[position], read("top.final_norm"), eps)
        vocab = cfg["vocab_size"]
        logits = [last @ read("top.head", (slice(None),
                                           slice(lo, lo + HEAD_COLUMNS)))
                  for lo in range(0, vocab, HEAD_COLUMNS)]
        return {"logits": jnp.concatenate(logits)
                * cfg["lm_head_multiplier"]}
