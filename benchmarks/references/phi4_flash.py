"""The plain reference of the Phi-4-mini-flash family: one prompt at a
time, unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it), the
selective scan token by token, attention as plain masked softmaxes, no
kernel, no packing and **no exit: all layers run over every position**
and the logits of one position are taken at the end — where the program
runs its second half on one line a request. It imports nothing from the
program. The sizes are ``config.json``'s of
microsoft/Phi-4-mini-flash-reasoning; the equations are the published
description's (arXiv:2507.06607 on arXiv:2406.07522, 2405.05254 and
2410.05258) as ISSUE 59 wrote them down, and the configuration file's
``assumed`` lists what was not checked against the modelling code.

**Stack**: ``h = E[token]`` (no scale, no positions); layer i of n: ``h
= h + Mixer_i(LN1_i(h))``; ``h = h + MLP_i(LN2_i(h))``; ``LN`` is
LayerNorm with weight and bias, mean-centred, eps ``layer_norm_eps``;
``MLP(f) = (silu(g) * u) W_down``, ``[g | u] = f W_gate_up`` (gate
first); ``logits = LN_f(h) E^T``. The mixer by i, with ``mb_per_layer``
2 and ``half = n / 2``: even ``i <= half`` Mamba-1 (layer ``half``'s scan
output, before the gate, is the memory ``m``); odd ``i < half``
differential attention under the window; ``i = half + 1`` the same
without it (its K and V are kept); even ``i >= half + 2`` a Gated
Memory Unit on ``m``; odd ``i >= half + 3`` cross differential
attention on the kept K and V.

**Mamba-1**: ``[x | z] = u W_in``; ``x = silu(conv(x) + b_c)``, a causal
depthwise convolution of ``mamba_d_conv`` taps from zero history;
``[delta | B | C] = x W_x`` (``dt_rank`` = ceil(hidden / 16), state,
state); ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``;
``s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n]
x_t[c]``, ``y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]``; ``(y *
silu(z)) W_out``.

**Differential attention**: ``[Q | K | V] = u W_qkv + b``, heads of ``d
= hidden / heads``; query pair j is heads 2j (q1) and 2j + 1 (q2),
key-value pair g is key heads 2g, 2g + 1 and value heads 2g, 2g + 1,
pair j reads ``g = j // (heads / kv heads)``; ``P1 = softmax(q1 k1^T /
sqrt(d) + mask)``, ``P2`` likewise; ``o_j = P1 [v1 | v2] - lambda P2
[v1 | v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``o_j <- RMSNorm(o_j; w_sub,
eps) (1 - lambda_init)``; ``[o_0 ...] W_o + b_o``. Mask: ``s <= t`` and,
under the window, ``t - s < sliding_window``. A cross layer has ``Q = u
W_q + b`` alone. Queries are taken ``QUERY_STEP`` at a time, a window
layer's against the ``sliding_window - 1 + QUERY_STEP`` keys that can
reach them, and the MLP ``MLP_STEP`` tokens at a time, so that a prompt
of 16k tokens fits the device beside the program's weights.

``read(name, index=None)`` hands over one tensor's float32 values
(``top.embed``, ``l<i>.qkv``, ...; of ``stored[index]`` with an index).
:func:`Reference.forward` reads one layer's tensors at a time and runs
each mixer as one jitted function of (weights, activations); the
embedding is read by the prompt's rows and, for the tied head, in
blocks of ``HEAD_ROWS`` rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: queries a step of the attention
QUERY_STEP = 128
#: tokens a step of the MLP
MLP_STEP = 2048
#: rows of the embedding a product of the tied head
HEAD_ROWS = 32768

def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def kind_of(cfg, i):
    half = cfg["num_hidden_layers"] // 2
    if i % cfg["mb_per_layer"] == 0:
        return "mamba" if i <= half else "gmu"
    if i < half:
        return "window"
    return "full" if i == half + 1 else "cross"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def mamba(cfg, w, u):
    """``u`` (L, hidden) -> (the mixer's output (L, hidden), the scan's
    output before the gate (L, d_inner))."""
    d = cfg["hidden_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    rank = -(-d // 16)
    xz = u @ w["in_proj"]
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[j:j + x.shape[0]] * w["conv_w"][:, j]
                        for j in range(taps)) + w["conv_b"])
    dbc = x @ w["x_proj"]
    dt = jax.nn.softplus(dbc[:, :rank] @ w["dt_proj"] + w["dt_bias"])
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a = -jnp.exp(w["a_log"])

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t + w["d"] * x_t

    _, y = lax.scan(step, jnp.zeros((di, n), jnp.float32), (x, dt, b, c))
    return (y * jax.nn.silu(z)) @ w["out_proj"], y


def differential(cfg, w, q, k, v, init, window=None):
    """``q`` (L, heads d), ``k``, ``v`` (L, kv heads d) -> (L, hidden):
    the two softmaxes, the subtraction, the sub-layer norm, the output
    product. ``window``: None, or the keys a query reads, ending with
    its own."""
    length = q.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    per = hq // hk
    q = q.reshape(length, hk // 2, per, 2, d)       # (L, g, j, s, d)
    k = k.reshape(length, hk // 2, 2, d)            # (L, g, s, d)
    v = v.reshape(length, hk // 2, 2 * d)           # (L, g, [v1 | v2])
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init
    step = min(QUERY_STEP, length)
    blocks = -(-length // step)
    q = jnp.pad(q, ((0, blocks * step - length),) + ((0, 0),) * 4)
    # a window layer's block of queries reads the keys from ``reach``
    # before its first to its last
    reach = 0 if window is None else window - 1
    span = length if window is None else reach + step
    if window is not None:
        k = jnp.pad(k, ((reach, blocks * step - length),) + ((0, 0),) * 3)
        v = jnp.pad(v, ((reach, blocks * step - length),) + ((0, 0),) * 2)

    def block(first):
        mine = lax.dynamic_slice_in_dim(q, first, step)
        lo = first if window is not None else 0
        keys = lax.dynamic_slice_in_dim(k, lo, span)
        values = lax.dynamic_slice_in_dim(v, lo, span)
        t = (first + jnp.arange(step))[:, None]
        at = (lo - reach + jnp.arange(span))[None, :]
        seen = (at <= t) & (at >= 0)
        if window is not None:
            seen = seen & (t - at < window)
        s = jnp.einsum("qgjsd,kgsd->gjsqk", mine, keys) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gjsqk,kgv->qgjsv", p, values)
        return o[:, :, :, 0] - lam * o[:, :, :, 1]    # (q, g, j, 2 d)

    o = lax.map(block, jnp.arange(blocks) * step) \
        .reshape(blocks * step, hq // 2, 2 * d)[:length]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + cfg["layer_norm_eps"]) * w["sub_w"] * (1.0 - init)
    return o.reshape(length, hq * d) @ w["o"] + w["o_b"]


def attention(cfg, w, u, init, window):
    """-> (the mixer's output, the layer's K, its V)."""
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    qkv = u @ w["qkv"] + w["qkv_b"]
    q, k, v = (qkv[:, :hq * d], qkv[:, hq * d:(hq + hk) * d],
               qkv[:, (hq + hk) * d:])
    return differential(cfg, w, q, k, v, init, window), k, v


def cross(cfg, w, u, init, k, v):
    return differential(cfg, w, u @ w["q"] + w["q_b"], k, v, init)


def gmu(w, u, memory):
    return (jax.nn.silu(u @ w["g_in"]) * memory) @ w["g_out"]


def mlp(cfg, w, x):
    """The layer's second half on the stream, ``MLP_STEP`` tokens at a
    time."""
    inner = cfg["intermediate_size"]
    length = x.shape[0]
    step = min(MLP_STEP, length)
    blocks = -(-length // step)
    padded = jnp.pad(x, ((0, blocks * step - length), (0, 0)))

    def block(first):
        mine = lax.dynamic_slice_in_dim(padded, first, step)
        gu = layer_norm(mine, w["ln2_w"], w["ln2_b"],
                        cfg["layer_norm_eps"]) @ w["gate_up"]
        return mine + (jax.nn.silu(gu[:, :inner]) * gu[:, inner:]) \
            @ w["down"]
    return lax.map(block, jnp.arange(blocks) * step) \
        .reshape(blocks * step, -1)[:length]


#: the tensors each kind of layer reads, its norms' and MLP's with them
COMMON = ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "gate_up", "down")
DIFFERENTIAL = ("lq1", "lk1", "lq2", "lk2", "sub_w", "o", "o_b")
TENSORS = {
    "mamba": ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj",
              "dt_bias", "a_log", "d", "out_proj"),
    "window": ("qkv", "qkv_b") + DIFFERENTIAL,
    "full": ("qkv", "qkv_b") + DIFFERENTIAL,
    "gmu": ("g_in", "g_out"),
    "cross": ("q", "q_b") + DIFFERENTIAL}


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        eps = cfg["layer_norm_eps"]

        def normed(w, x):
            return layer_norm(x, w["ln1_w"], w["ln1_b"], eps)
        self._mamba = jax.jit(lambda w, x: mamba(cfg, w, normed(w, x)))
        # ``lambda_init`` is an argument, not a constant: one program
        # for the eight window layers and one for the seven cross ones
        self._attention = jax.jit(
            lambda w, x, init, window: attention(cfg, w, normed(w, x), init,
                                                 window),
            static_argnums=3)
        self._cross = jax.jit(
            lambda w, x, init, k, v: cross(cfg, w, normed(w, x), init, k, v))
        self._gmu = jax.jit(lambda w, x, m: gmu(w, normed(w, x), m))
        self._mlp = jax.jit(lambda w, x: mlp(cfg, w, x))

    def forward(self, read, tokens, position=-1, **_):
        """``tokens`` (L,) ids. ``position``: whose logits are returned,
        the last by default (every mixer is causal, so a caller may pad
        a prompt behind its last token to a length it has compiled
        before, and ask for the last real one).
        -> {"logits": (vocab,)}"""
        cfg = self.cfg
        x = read("top.embed", jnp.asarray(tokens))
        memory = keys = values = None
        for i in range(cfg["num_hidden_layers"]):
            kind = kind_of(cfg, i)
            w = {t: read("l%d.%s" % (i, t)) for t in COMMON + TENSORS[kind]}
            if kind == "mamba":
                out, y = self._mamba(w, x)
                if i == cfg["num_hidden_layers"] // 2:
                    memory = y
            elif kind in ("window", "full"):
                out, k, v = self._attention(
                    w, x, lambda_init(i),
                    cfg["sliding_window"] if kind == "window" else None)
                if kind == "full":
                    keys, values = k, v
            elif kind == "gmu":
                out = self._gmu(w, x, memory)
            else:
                out = self._cross(w, x, lambda_init(i), keys, values)
            x = self._mlp(w, x + out)
        last = layer_norm(x[position], read("top.final_norm_w"),
                          read("top.final_norm_b"), cfg["layer_norm_eps"])
        vocab = cfg["vocab_size"]
        logits = [read("top.embed", slice(lo, lo + HEAD_ROWS)) @ last
                  for lo in range(0, vocab, HEAD_ROWS)]
        return {"logits": jnp.concatenate(logits)}
