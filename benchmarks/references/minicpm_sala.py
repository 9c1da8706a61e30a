"""The plain reference of the MiniCPM-SALA family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it), no
kernel and no blocked scan. It imports nothing from the program. It
follows ``config.json`` of openbmb/MiniCPM-SALA for the sizes and
switches; the layers' equations are written out below, and every
convention the published config does not give is listed under
``assumed`` in the configuration's file with its source.

**Stack** (MiniCPM's scalings, from ``scale_emb``, ``scale_depth``,
``dim_model_base``): ``h = scale_emb x E[token]``; each layer ``h += s
x Mixer(RMSNorm(h))``, then ``h += s x W_down(silu(W_gate x) * W_up
x)`` with ``x = RMSNorm(h)`` and ``s = scale_depth / sqrt(published
num_hidden_layers)``; ``logits = W_head RMSNorm(h) / (hidden_size /
dim_model_base)``. The layers are the model's first
``num_hidden_layers``, their mixers ``mixer_types``' first so many.

**``lightning-attn``**: ``q = RMSNorm_head(x W_q)``, ``k =
RMSNorm_head(x W_k)``, ``v = x W_v`` (``lightning_nh`` heads of
``lightning_head_dim``); rotary (``rope_theta``, all columns, halves
rotated, positions 0 .. L-1) on q and k; per head ``S_t = lambda_h
S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``, ``S`` zero before
the first token, ``lambda_h = exp(-2^(-8 (h + 1) / H))``; ``out =
(RMSNorm(o) * sigmoid(x W_g)) W_o``, the norm over all heads' columns.
Computed token by token, as written.

**``minicpm4``** (InfLLM-V2, arXiv:2509.24663; sizes from
``sparse_config``): ``q = RMSNorm_head(x W_q)``, ``k = RMSNorm_head(x
W_k)``, ``v = x W_v``, no rotary; query heads ``per x g .. per x g +
per - 1`` read key-value head g. A prompt shorter than ``dense_len``:
causal softmax attention over all its keys, scale ``1 / sqrt(d)``.
Otherwise, for the query at position p: compressed keys ``c_j =
mean(k[stride x j : stride x j + kernel_size])`` over the windows that
end at or before p; a head's ``a_j = softmax_j(q . c_j / sqrt(d))``;
``A_j`` the sum of ``a_j`` over the group's heads; a block's score the
largest ``A_j`` among the windows that overlap its keys; the first
``init_blocks`` blocks and those that hold keys ``p - window_size + 1
.. p`` are chosen whatever their score; the ``topk`` blocks chosen are
those and the best-scoring others among blocks ``0 .. p //
block_size`` (all of them when there are ``topk`` or fewer; a tie goes
to the lower block); attention is the causal softmax over the keys of
the chosen blocks. ``out = (attn * sigmoid(x W_g)) W_o``. Queries are
taken ``QUERY_STEP`` at a time, so that a prompt of 16,384 tokens'
scores fit the device.

``forced``, when given, replaces the choice of blocks — a departure for
evaluation only: it lets a comparison hold the arithmetic to a tight
tolerance without a near-tie between two blocks turning a rounding
difference into another set of keys. The reference's own free choice
is computed beside it, and ``shortfall`` says how far below the
reference's ``topk``-th best score the weakest forced block lies
(infinite for a block the query may not choose, or a wrong count).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: queries a step of the sparse attention
QUERY_STEP = 256


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(cfg, x):
    """``x`` (L, heads, dim) at positions 0 .. L-1: halves rotated."""
    length, dim = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def heads_of(x, w, norm, heads, dim, eps):
    out = (x @ w).reshape(x.shape[0], heads, dim)
    return out if norm is None else rms_norm(out, norm, eps)


def mlp(cfg, w, x):
    return (jax.nn.silu(x @ w["gate_mlp"]) * (x @ w["up"])) @ w["down"]


def decays(cfg):
    heads = cfg["lightning_nh"]
    return jnp.exp(-2.0 ** (-8.0 * jnp.arange(1, heads + 1,
                                              dtype=jnp.float32) / heads))


def lightning(cfg, w, x, state_dtype=jnp.float32):
    """The recurrence, one token a step. ``state_dtype`` rounds the
    state after every step (the tests' lower-precision control)."""
    heads, dim = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rotary(cfg, heads_of(x, w["q"], w["q_norm"], heads, dim, eps))
    k = rotary(cfg, heads_of(x, w["k"], w["k_norm"], heads, dim, eps))
    v = heads_of(x, w["v"], None, heads, dim, eps)
    lam = decays(cfg)[:, None, None]

    def step(state, qkv):
        q_t, k_t, v_t = qkv                                # (H, d) each
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        state = state.astype(state_dtype).astype(jnp.float32)
        return state, jnp.einsum("hd,hdv->hv", q_t, state) / math.sqrt(dim)

    _, out = lax.scan(step, jnp.zeros((heads, dim, dim), jnp.float32),
                      (q, k, v), unroll=8)
    out = rms_norm(out.reshape(x.shape[0], heads * dim), w["o_norm"], eps)
    return (out * jax.nn.sigmoid(x @ w["gate"])) @ w["o"]


def window_firsts(sparse, length):
    """The first key of every whole window of ``length`` keys."""
    return np.arange(0, length - sparse["kernel_size"] + 1,
                     sparse["kernel_stride"])


def compress(sparse, k):
    """``k`` (L, Hk, d) -> (windows, Hk, d): the mean of each window's
    ``kernel_size`` keys."""
    first = window_firsts(sparse, k.shape[0])
    return k[first[:, None]
             + np.arange(sparse["kernel_size"])[None, :]].mean(1)


def block_scores(sparse, q, compressed, length, at):
    """(queries, Hk, blocks of ``length`` keys): ``q`` (queries, Hk,
    per, d) at positions ``at`` (queries,), ``compressed`` (windows,
    Hk, d)."""
    dim = q.shape[-1]
    size, block = sparse["kernel_size"], sparse["block_size"]
    first = window_firsts(sparse, length)
    scores = jnp.einsum("tghd,wgd->tghw", q, compressed) / math.sqrt(dim)
    seen = jnp.asarray(first + size - 1)[None, :] <= at[:, None]
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    share = jnp.where(seen[:, None, None, :],
                      jax.nn.softmax(scores, axis=-1), 0.0)
    share = jnp.where(jnp.isnan(share), 0.0, share)        # sees no window
    summed = share.sum(2)                                  # (t, Hk, W)
    blocks = -(-length // block)
    # window j overlaps block b where their key ranges meet
    overlap = (first[None, :] < (np.arange(blocks)[:, None] + 1) * block) \
        & (first[None, :] + size > np.arange(blocks)[:, None] * block)
    return jnp.max(jnp.where(jnp.asarray(overlap)[None, None],
                             summed[:, :, None, :], 0.0), axis=-1)


def choose(sparse, scores, at, selecting, forced=None):
    """-> (chosen (queries, Hk, blocks) bool, shortfall (queries, Hk)):
    the rule's choice from ``scores`` (every block at or before the
    query unless the prompt is ``selecting``) or, where ``forced`` is
    given, that, with its distance from the rule's."""
    block, topk = sparse["block_size"], sparse["topk"]
    blocks = scores.shape[-1]
    ids = jnp.arange(blocks)
    allowed = ids[None, :] <= at[:, None] // block         # (queries, B)
    local = jnp.maximum(at - (sparse["window_size"] - 1), 0) // block
    always = (ids[None, :] < sparse["init_blocks"]) \
        | (ids[None, :] >= local[:, None])
    ranked = jnp.where(always[:, None, :], jnp.inf, scores)
    ranked = jnp.where(allowed[:, None, :], ranked, -jnp.inf)
    count = min(topk, blocks)
    best, own = lax.top_k(ranked, count)
    free = (own[..., None] == ids).any(-2) & allowed[:, None, :]
    if not selecting:
        free = jnp.broadcast_to(allowed[:, None, :], free.shape)
    if forced is None:
        return free, jnp.zeros(free.shape[:2], jnp.float32)
    weakest = jnp.min(jnp.where(forced, ranked, jnp.inf), axis=-1)
    shortfall = jnp.maximum(best[..., -1] - weakest, 0.0)
    if not selecting:
        shortfall = jnp.zeros_like(shortfall)
    # a forced set of another size than the rule's is no choice of it
    wrong = forced.sum(-1) != free.sum(-1)
    shortfall = jnp.where(jnp.isnan(shortfall) | wrong, jnp.inf, shortfall)
    return forced, shortfall


def sparse_attention(cfg, w, x, selecting, forced=None):
    """-> (out (L, hidden), chosen (L, Hk, blocks), shortfall (L, Hk)).
    ``x`` (L, hidden) with L a multiple of the query step or under it;
    ``selecting``: the prompt has ``dense_len`` real tokens or more;
    ``forced`` (L, Hk, blocks) bool or None."""
    hq, hk, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    sparse, eps = cfg["sparse_config"], cfg["rms_norm_eps"]
    total = x.shape[0]
    q = heads_of(x, w["q"], w["q_norm"], hq, dim, eps) \
        .reshape(total, hk, hq // hk, dim)
    k = heads_of(x, w["k"], w["k_norm"], hk, dim, eps)
    v = heads_of(x, w["v"], None, hk, dim, eps)
    block = sparse["block_size"]
    blocks = -(-total // block)
    key_block = jnp.arange(total) // block
    step = min(QUERY_STEP, total)
    compressed = compress(sparse, k)

    def some(lo):
        at = lo + jnp.arange(step)
        q_s = lax.dynamic_slice_in_dim(q, lo, step)
        given = None if forced is None \
            else lax.dynamic_slice_in_dim(forced, lo, step)
        chosen, shortfall = choose(
            sparse, block_scores(sparse, q_s, compressed, total, at), at,
            selecting, given)
        reads = jnp.take(chosen, key_block, axis=-1) \
            & (jnp.arange(total)[None, :] <= at[:, None])[:, None, :]
        s = jnp.einsum("tghd,kgd->tghk", q_s, k) / math.sqrt(dim)
        s = jnp.where(reads[:, :, None, :], s, -jnp.inf)
        out = jnp.einsum("tghk,kgd->tghd", jax.nn.softmax(s, axis=-1), v)
        return out.reshape(step, hq * dim), chosen, shortfall

    out, chosen, shortfall = lax.map(some, jnp.arange(0, total, step))
    out = out.reshape(total, hq * dim)
    return ((out * jax.nn.sigmoid(x @ w["gate"])) @ w["o"],
            chosen.reshape(total, hk, blocks),
            shortfall.reshape(total, hk))


MLP = ("gate_mlp", "up", "down")
SPARSE = ("q", "k", "v", "gate", "o", "q_norm", "k_norm")
LIGHTNING = SPARSE + ("o_norm",)


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._lightning = jax.jit(lambda w, h: lightning(cfg, w, h))
        self._mlp = jax.jit(lambda w, h: mlp(cfg, w, h))
        self._sparse = jax.jit(
            lambda w, h, forced, selecting: sparse_attention(
                cfg, w, h, selecting, forced), static_argnums=3)

    def forward(self, read, tokens, held=None, forced=None, position=-1,
                length=None):
        """``tokens`` (L,) ids; ``held`` is the expert families'
        argument and is ignored (the stack has no experts: callers of
        every family's reference pass it); ``forced``: (sparse layers,
        L, Hk, blocks) bool or None; ``position``: whose logits are returned,
        the last by default (every mixer is causal, so a caller may pad
        a prompt behind its last token to a length it has compiled
        before and ask for the last real one: ``length`` then says how
        many tokens are real, which decides dense or selecting).
        -> {"logits": (vocab,), "chosen": (sparse layers, L, Hk,
        blocks), "shortfall": (sparse layers, L, Hk)}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        total = len(tokens)
        length = total if length is None else int(length)
        position = position % total
        # whole query steps: padded behind the last token, like a caller's
        pad = -total % QUERY_STEP if total > QUERY_STEP else 0
        tokens = np.pad(np.asarray(tokens), (0, pad))
        if forced is not None and pad:
            block = cfg["sparse_config"]["block_size"]
            forced = np.pad(np.asarray(forced), (
                (0, 0), (0, pad), (0, 0),
                (0, -(-(total + pad) // block) - np.shape(forced)[-1])))
        depth = cfg.get("published", {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"])
        scale = cfg["scale_depth"] / math.sqrt(depth)
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0) \
            * cfg["scale_emb"]
        chosen, short = [], []
        for i in range(cfg["num_hidden_layers"]):
            def w(names):
                return {t: read("l%d.%s" % (i, t)) for t in names}
            h = rms_norm(x, read("l%d.attn_norm" % i), eps)
            if cfg["mixer_types"][i] == "minicpm4":
                out, blocks, shortfall = self._sparse(
                    w(SPARSE), h, None if forced is None
                    else jnp.asarray(forced[len(chosen)]),
                    length >= cfg["sparse_config"]["dense_len"])
                chosen.append(blocks)
                short.append(shortfall)
            else:
                out = self._lightning(w(LIGHTNING), h)
            x = x + scale * out
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            x = x + scale * self._mlp(w(MLP), h)
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": (last @ read("top.head"))
                / (cfg["hidden_size"] / cfg["dim_model_base"]),
                "chosen": jnp.stack(chosen)[
                    :, :total, :,
                    :-(-total // cfg["sparse_config"]["block_size"])],
                "shortfall": jnp.stack(short)[:, :total]}
