"""The forward pass of a Phi-4-mini-flash stack over a packed pool of
rows, with the prefill exit.

``n`` layers, ``a = Mixer_i(LN1_i(h)); h = h + a; h = h + MLP_i(LN2_i(h))``
with LayerNorm (weight and bias, mean-centred), ``MLP(f) = (silu(g) *
u) W_down``, ``[g | u] = f W_gate_up``; logits ``LN_f(h) E^T`` (tied).
The mixer by ``i`` (:meth:`Phi4FlashConfig.kind`; ``mb_per_layer`` 2)::

    mamba   i even, i <= n/2      Mamba-1: [x | z] = u W_in; x = silu(conv(x));
                                  [delta | B | C] = x W_x; dt = softplus(
                                  delta W_dt + b_dt); the selective scan;
                                  (y silu(z)) W_out. Layer n/2 hands on y:
                                  the memory m
    window  i odd,  i <  n/2      differential attention, t - s < the window
    full    i = n/2 + 1           the same without the window; hands on K, V
    gmu     i even, i >= n/2 + 2  (silu(u W_g) * m) W_o'
    cross   i odd,  i >= n/2 + 3  differential attention with a query product
                                  alone, over the full layer's K, V

Differential attention: ``[Q | K | V] = u W_qkv + b``, heads of ``d``;
query pair j is heads 2j (``q1``) and 2j + 1 (``q2``), key-value pair g
likewise, pair j reads ``g = j // (Hq / Hk)``; ``o_j = P1 [v1 | v2] -
lambda P2 [v1 | v2]`` with ``P = softmax(q k^T / sqrt(d))``, ``lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
0.6 exp(-0.3 i)``; ``o_j <- RMSNorm(o_j; w_sub) (1 - lambda_init)``;
``[o_0 ...] W_o + b_o``.

**The exit.** Layers past the full one read, at position t, ``h_t``,
``m_t`` and keys and values at ``s <= t`` only: a request's
last-position logits need them at its last position only. ``forward``
runs layers 0 .. n/2 + 1 over the pool ``(rows, Q, hidden)``, gathers
``last_idx`` there — ``h`` and ``m`` become ``(rows, .)``, one line a
request, zero past the last request — and runs the rest over those
lines: a cross layer is one query a request against its request's keys
up to its own position. Nothing is approximated or left out; the plain
reference runs every layer over every position and takes the last
logits, and the agreement of the two is the test of the exit.

**Two bodies.** The (mamba, window) pairs of layers 0 .. n/2 - 1 have
one shape, as have the (gmu, cross) pairs behind the full layer: each
group runs as one ``lax.scan`` over its parameters stacked along a
leading axis (``checkpoint.py`` stores them so), ``lambda_init`` a
scanned operand; layers n/2 and n/2 + 1 stand alone. A row bucket's
program holds four layer bodies, not 32.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
norms' statistics, the softmaxes, ``dt``, the scan's decays and states
and every product's accumulation are float32 — and so is the *residual
stream*: every norm reads it and every residual addition writes it in
float32 (:func:`_add`: the addend is rounded, the sum is not), and what
a product reads of it is the norm's bfloat16 result.
The other token families hold 4 to 14 layers and keep the stream in
bfloat16; here 64 residual additions in a row would each round it, and
on the chip that rounding alone read 7.5% of the logits' spread at the
worst logit (1.45% root mean square; my chip run, PR 59) where the
comparison allows 5 (with the stream in float32 1.4-1.5% and 0.28%);
it costs a twentieth of a dispatch (:func:`_add`).

The named scopes are ``embed``; ``ssd`` (the Mamba mixers, ``ssd/conv``
and ``ssd/scan`` inside); ``attn`` with ``attn/window`` and
``attn/full`` and the kernels' calls alone under ``/kernel``; ``mlp``
(the MLPs of the layers that run over every token, their norms with
them); ``cross`` (the cross-decoder whole: ``cross/xattn``,
``cross/gmu``, ``cross/xmlp`` — its MLPs run on one line a request and
are not ``mlp``'s, nor its attention ``attn``'s: the benchmark's readers
find a scope by its name anywhere in a path, so the names differ);
``head``. The family has no experts: ``forward``
takes ``slots`` for the stages' one call and ignores it, and nothing is
chosen: ``chosen`` is empty.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax

from rnb_tpu.ops import banded, indexed, rope, segattn, selective_scan, ssd

#: what ``forward`` returns behind the logits and the (empty) choices:
#: the full layer's flash kernel's tiles; the rows that open a request;
#: the (valid query, key) pairs the windows keep, and the causal ones;
#: the lines the dispatch sends through the cross-decoder
COUNTERS = ("attn_tiles", "scan_resets", "window_keys", "cross_lines")

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The sizes of one stack, under the published config's names (the
    Mamba mixer's are the configuration file's ``assumed`` ones)."""

    num_hidden_layers: int
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    sliding_window: int
    mb_per_layer: int
    eps: float
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @staticmethod
    def from_published(config: Mapping) -> "Phi4FlashConfig":
        layers = int(config["num_hidden_layers"])
        if layers % 4 or layers < 8 \
                or int(config["mb_per_layer"]) != 2 \
                or not config["tie_word_embeddings"] \
                or config.get("mlp_bias") or config.get("lm_head_bias") \
                or config["hidden_act"] != "silu" \
                or int(config["num_attention_heads"]) % 4 \
                or int(config["num_key_value_heads"]) % 2 \
                or int(config["hidden_size"]) \
                % int(config["num_attention_heads"]):
            raise ValueError("a switch or a size of the layers' law: not "
                             "the Phi-4-mini-flash this network implements")
        return Phi4FlashConfig(
            num_hidden_layers=layers,
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            sliding_window=int(config["sliding_window"]),
            mb_per_layer=int(config["mb_per_layer"]),
            eps=float(config["layer_norm_eps"]),
            **{key: int(config[key]) for key in (
                "mamba_d_state", "mamba_d_conv", "mamba_expand")
               if key in config})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def memory_layer(self) -> int:
        """The last Mamba layer: its scan's output is the memory."""
        return self.num_hidden_layers // 2

    @property
    def key_layer(self) -> int:
        """The full attention layer: its keys and values are handed on."""
        return self.num_hidden_layers // 2 + 1

    @property
    def qkv_parts(self):
        """``W_qkv``'s columns: Q, K, V."""
        hq = self.num_attention_heads * self.head_dim
        hk = self.num_key_value_heads * self.head_dim
        return hq, hk, hk

    def kind(self, i: int) -> str:
        """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``."""
        if i % self.mb_per_layer == 0:
            return "mamba" if i <= self.memory_layer else "gmu"
        if i < self.memory_layer:
            return "window"
        return "full" if i == self.key_layer else "cross"

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, weight, bias, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, -1, keepdims=True)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _add(x, contribution, act):
    """The residual addition: the float32 stream ``x`` plus a mixer's or
    an MLP's float32 result *rounded to the activations' dtype first*:
    what is rounded is the addend, a fifth of the stream's size, never
    the stream. The product behind the result then writes ``act`` and
    reads nothing of the stream, and the sum is the next norm's pass to
    make, in float32. The barrier holds the two apart: without it the
    compiler drops the pair of conversions and fuses the float32 sum
    into the product's epilogue, and with the stream an operand of every
    product's fusion — the sum's behind it and the norm's in front of
    the next — a 128-row dispatch took 542 ms for 510 (my chip run, PR
    59: the MLPs' three products 46-48 ms a pair of layers each for
    36-38; a bfloat16 stream 486, at 7.5% of the spread where this form
    reads the next docstring's number)."""
    return x + lax.optimization_barrier(contribution.astype(act)) \
        .astype(jnp.float32)


def mlp(cfg, p, x, act):
    """The layer's second half on the float32 stream ``x`` (...,
    hidden): the norm (-> ``act``), the gated MLP, the residual -> the
    stream."""
    inner = cfg.intermediate_size
    f = layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.eps, act)
    # gate_up's columns as two products (the slices are views of the
    # weight): the gate's activation fuses behind its own
    hidden = (jax.nn.silu(_proj(f, p["gate_up"][:, :inner]))
              * _proj(f, p["gate_up"][:, inner:])).astype(act)
    return _add(x, _proj(hidden, p["down"]), act)


def scan_memory(gated, y):
    """What a Mamba layer hands the Gated Memory Units of its scan's two
    outputs: ``y``, the output with the skip term *before* the gate (a
    function of its own so that the control's ``memory_gated`` arm can
    put the other in its place)."""
    del gated
    return y


def mamba_mixer(cfg, p, u, row_first, state_dtype=jnp.float32,
                interpret=False, memory=False):
    """``u`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden);
    with ``memory`` a pair, the memory (rows, Q, d_inner) second."""
    act = u.dtype
    di, n, rank = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    x = _proj(u, p["in_proj"][:, :di]).astype(act)
    z = _proj(u, p["in_proj"][:, di:]).astype(act)
    x = ssd.segment_conv1d(x, p["conv_w"], p["conv_b"], row_first,
                           activation="silu", out_dtype=act,
                           interpret=interpret)
    dbc = _proj(x, p["x_proj"])
    dt = jax.nn.softplus(
        _proj(dbc[..., :rank].astype(act), p["dt_proj"])
        + p["dt_bias"].astype(jnp.float32))
    outs = selective_scan.selective_scan(
        x, dt, -jnp.exp(p["a_log"].astype(jnp.float32)),
        dbc[..., rank:rank + n], dbc[..., rank + n:], p["d"], z, row_first,
        memory=memory, state_dtype=state_dtype, interpret=interpret)
    if not memory:
        return _proj(outs, p["out_proj"])
    return _proj(outs[0], p["out_proj"]), scan_memory(*outs)


def lambda_of(p, lambda_init):
    """A differential layer's ``lambda`` () float32."""
    f32 = jnp.float32
    return jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lq2"].astype(f32) * p["lk2"].astype(f32))) \
        + lambda_init


def _queries_scale(cfg, width):
    """The scores' scale on Q's columns of a product's ``width``."""
    hq = cfg.qkv_parts[0]
    return jnp.concatenate([
        jnp.full((hq,), cfg.head_dim ** -0.5, jnp.float32),
        jnp.ones((width - hq,), jnp.float32)])


def qkv_of(cfg, p, u):
    """``u`` (rows, Q, hidden), normed -> (T, Q | K | V columns) in the
    activations' dtype, the bias added and the scores' scale on Q before
    the one rounding."""
    out = (_proj(u, p["qkv"]) + p["qkv_b"].astype(jnp.float32)) \
        * _queries_scale(cfg, p["qkv"].shape[-1])
    return out.astype(u.dtype).reshape(-1, out.shape[-1])


def sub_norm(cfg, pairs, sub):
    """The differential layers' last lines on ``pairs`` (..., pairs, 2
    d) float32: the RMS norm over a pair's columns, times ``sub`` (the
    norm's weight times ``1 - lambda_init``)."""
    pairs = pairs * lax.rsqrt(
        jnp.mean(pairs * pairs, -1, keepdims=True) + cfg.eps)
    return pairs * sub


def window_attention(cfg, qkv, lam, sub, tables, interpret=False):
    """A sliding layer's attention on :func:`qkv_of`'s array -> ((T, Hq
    d) the output product's operand, the banded kernel's pair).
    ``tables``: (``banded.band_start``'s (T, 1), ``row_start``, Q)."""
    with jax.named_scope("kernel"):
        return banded.differential_banded_attention(
            qkv, lam, sub, tables[0], cfg.sliding_window,
            (cfg.num_attention_heads, cfg.num_key_value_heads), cfg.eps,
            interpret)


def full_attention(cfg, qkv, lam, sub, tables, interpret=False):
    """The same without the window, through the packed flash kernel:
    each (key-value pair, softmax) is a key-value head of the kernel's —
    its key one head's ``d`` columns under zeros up to the lanes, its
    value the pair's ``2 d`` — serving the ``Hq / Hk`` query heads that
    read it, so every softmax is computed once against the whole value;
    the subtraction, the norm and the weight are XLA's behind it.
    -> ((T, Hq d), the flash kernel's pair of tiles)."""
    _, row_start, qlen = tables
    tokens = qkv.shape[0]
    d = cfg.head_dim
    hq, hk, _ = cfg.qkv_parts
    groups = cfg.num_key_value_heads // 2
    per = cfg.num_attention_heads // cfg.num_key_value_heads
    # (T, g, j, s, d) -> a kernel head (g, s) serving its j queries
    q = qkv[:, :hq].reshape(tokens, groups, per, 2, d).swapaxes(2, 3) \
        .reshape(tokens, 2 * groups, per, d)
    k = qkv[:, hq:hq + hk].reshape(tokens, 2 * groups, d)
    v = jnp.repeat(qkv[:, hq + hk:].reshape(tokens, groups, 2 * d), 2,
                   axis=1)
    with jax.named_scope("kernel"):
        out, tiles = segattn.heads_first_attention(
            segattn.heads_first(q), segattn.heads_first(k),
            segattn.heads_first(v), row_start, qlen, interpret)
    # (2 g, j, P, 2 d) -> (T, g, s, j, 2 d)
    out = jnp.moveaxis(out, 2, 0)[:tokens].astype(jnp.float32) \
        .reshape(tokens, groups, 2, per, 2 * d)
    pairs = sub_norm(cfg, out[:, :, 0] - lam * out[:, :, 1], sub)
    return pairs.astype(qkv.dtype).reshape(tokens, hq), tiles


def attention_mixer(cfg, p, u, lambda_init, tables, attention,
                    interpret=False):
    """``u`` (rows, Q, hidden), normed -> (float32 (rows, Q, hidden),
    the kernel's pair, the layer's (T, .) ``qkv``)."""
    rows, q, _ = u.shape
    qkv = qkv_of(cfg, p, u)
    sub = p["sub_w"].astype(jnp.float32) * (1.0 - lambda_init)
    out, pair = attention(cfg, qkv, lambda_of(p, lambda_init), sub, tables,
                          interpret)
    return _proj(out.reshape(rows, q, -1), p["o"]) \
        + p["o_b"].astype(jnp.float32), pair, qkv


def cross_attention(cfg, p, u, lambda_init, k, v, first, last):
    """One query a line against its request's keys: ``u`` (lines,
    hidden), normed; ``k`` (T, Hk d), ``v`` (T, Hk d) the full layer's;
    ``first``, ``last`` (lines,) the line's request's first token and
    its own -> float32 (lines, hidden)."""
    f32 = jnp.float32
    lines = u.shape[0]
    tokens = k.shape[0]
    d = cfg.head_dim
    groups = cfg.num_key_value_heads // 2
    per = cfg.num_attention_heads // cfg.num_key_value_heads
    q = ((_proj(u, p["q"]) + p["q_b"].astype(f32)) * d ** -0.5) \
        .astype(u.dtype).reshape(lines, groups, per, 2, d)
    at = jnp.arange(tokens)
    keep = (at[None, :] >= first[:, None]) & (at[None, :] <= last[:, None])
    s = jnp.einsum("lgjsd,tgsd->lgjst", q,
                   k.reshape(tokens, groups, 2, d),
                   preferred_element_type=f32)
    s = jnp.where(keep[:, None, None, None, :], s, _MASKED)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("lgjst,tgv->lgjsv", prob.astype(u.dtype),
                     v.reshape(tokens, groups, 2 * d),
                     preferred_element_type=f32)
    sub = p["sub_w"].astype(f32) * (1.0 - lambda_init)
    pairs = sub_norm(cfg, out[..., 0, :]
                     - lambda_of(p, lambda_init) * out[..., 1, :], sub)
    return _proj(pairs.astype(u.dtype).reshape(lines, -1), p["o"]) \
        + p["o_b"].astype(f32)


def gated_memory(p, u, memory):
    """``u`` (lines, hidden), normed; ``memory`` (lines, d_inner) ->
    float32 (lines, hidden)."""
    gate = jax.nn.silu(_proj(u, p["g_in"]))
    return _proj((gate * memory.astype(jnp.float32)).astype(u.dtype),
                 p["g_out"])


def _of(p, prefix):
    """The tensors ``<prefix>.<name>`` of a stacked group, by name."""
    return {name[len(prefix) + 1:]: w for name, w in p.items()
            if name.startswith(prefix + ".")}


def forward(cfg: Phi4FlashConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``slots`` is the expert families' and is ignored;
    ``state_dtype`` is the lower-precision control's (the scans' states
    between rows); ``interpret`` runs the Pallas kernels in interpret
    mode (a device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the stack's
    choices: none, (0, tokens) int32; the full layer's flash kernel's
    tiles (1, 2) int32: run, and on or under the diagonal; the rows that
    open a request (1,) int32; the (valid query, key) pairs the windows
    keep and the causal ones (window layers, 2) int32; the lines sent
    through the cross-decoder (1,) int32).
    """
    del slots
    rows, q = tokens.shape
    f32 = jnp.float32
    row_first = row_start == jnp.arange(rows)
    tables = (banded.band_start(row_start, q), row_start, q)
    half = cfg.memory_layer
    inits = [cfg.lambda_init(i) for i in range(cfg.num_hidden_layers)]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        # the activations' dtype is the embedding's, the stream's float32
        act, x = x.dtype, x.astype(f32)

    def mamba_layer(p, x, memory=False):
        with jax.named_scope("ssd"):
            u = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.eps, act)
            out = mamba_mixer(cfg, p, u, row_first, state_dtype, interpret,
                              memory)
            kept = None
            if memory:
                out, kept = out
            x = _add(x, out, act)
        with jax.named_scope("mlp"):
            x = mlp(cfg, p, x, act)
        return (x, kept) if memory else x

    def attention_layer(p, x, lambda_init, scope, attention):
        with jax.named_scope("attn"), jax.named_scope(scope):
            u = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.eps, act)
            out, pair, qkv = attention_mixer(cfg, p, u, lambda_init, tables,
                                             attention, interpret)
            x = _add(x, out, act)
        with jax.named_scope("mlp"):
            x = mlp(cfg, p, x, act)
        return x, pair, qkv

    def self_pair(x, xs):
        p, lambda_init = xs
        x = mamba_layer(_of(p, "m"), x)
        x, _, _ = attention_layer(_of(p, "a"), x, lambda_init, "window",
                                  window_attention)
        return x, None

    x, _ = lax.scan(self_pair, x, (
        params["pairs"], jnp.asarray(inits[1:half:2], f32)))
    x, memory = mamba_layer(params["l%d" % half], x, memory=True)
    x, tiles, qkv = attention_layer(params["l%d" % (half + 1)], x,
                                    inits[half + 1], "full", full_attention)
    hq, hk, _ = cfg.qkv_parts
    keys, values = qkv[:, hq:hq + hk], qkv[:, hq + hk:]

    # the exit: one line a request from here on
    resets = jnp.sum(row_first & (row_tokens > 0), dtype=jnp.int32)
    with jax.named_scope("cross"):
        served = (jnp.arange(rows) < resets)[:, None]
        x = jnp.where(served, x.reshape(rows * q, -1)[last_idx], 0)
        memory = jnp.where(served,
                           memory.reshape(rows * q, -1)[last_idx], 0)
        first = (row_start * q)[last_idx // q]

        def cross_pair(x, xs):
            p, lambda_init = xs
            g, c = _of(p, "g"), _of(p, "c")
            with jax.named_scope("gmu"):
                u = layer_norm(x, g["ln1_w"], g["ln1_b"], cfg.eps, act)
                x = _add(x, gated_memory(g, u, memory), act)
            with jax.named_scope("xmlp"):
                x = mlp(cfg, g, x, act)
            with jax.named_scope("xattn"):
                u = layer_norm(x, c["ln1_w"], c["ln1_b"], cfg.eps, act)
                x = _add(x, cross_attention(
                    cfg, c, u, lambda_init, keys, values, first, last_idx),
                    act)
            with jax.named_scope("xmlp"):
                x = mlp(cfg, c, x, act)
            return x, None

        x, _ = lax.scan(cross_pair, x, (
            params["cross"], jnp.asarray(inits[half + 3::2], f32)))
    with jax.named_scope("head"):
        last = layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                          cfg.eps, act)
        logits = lax.dot_general(last, params["embed"],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
    at = rope.pool_positions(row_start, q).reshape(-1)
    _, valid = indexed.token_table(row_start, row_tokens, q)
    window_keys = jnp.stack([
        jnp.where(valid, jnp.minimum(at + 1, cfg.sliding_window), 0).sum(),
        jnp.where(valid, at + 1, 0).sum()]).astype(jnp.int32)
    return logits, jnp.zeros((0, rows * q), jnp.int32), tiles[None], \
        resets.reshape(1), jnp.tile(window_keys, (half // 2, 1)), \
        resets.reshape(1)
