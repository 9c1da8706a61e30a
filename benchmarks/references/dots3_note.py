"""The plain reference of the dots3-note family's language model: one
prompt at a time, unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it); no
kernel, no tiling, no packing. It imports nothing from the program; it
follows the catalog's row (``config.json`` of dots-studio/dots3-note-prev,
``model_type`` ``dots3_note``) and, for what the row does not state, the
conventions the configuration's file lists under ``assumed``; each
departure is a comment.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``, eps
``rms_norm_eps``; the layers are the model's first ``num_hidden_layers``
and a layer's type is ``layer_types[i]``; then a final RMSNorm and an
untied head on the last position.

*The mixer* is latent attention in its published (expanded) form at the
layer type's sizes (``swa_*`` keys in a sliding layer): ``c_q = rho_q
RMSNorm(x W_dq)``, ``q[i] = c_q W_uq[i] = [q_n | q_r]``; ``[c | k_r] = x
W_dkv``, ``c_kv = rho_kv RMSNorm(c)``, ``[k_n[i] | v[i]] = c_kv
W_ukv[i]``; ``q_r`` and the one ``k_r`` rotated (the published pairs
``(2j, 2j + 1)`` de-interleaved into halves, then halves rotated, plain
frequencies of the type's theta, no scaling); ``rho = sqrt(hidden /
rank)`` where ``apply_mla_qkv_lora_rescale``; scores ``(q_n . k_n + q_r
. k_r) (nope + rope) ** -0.5``; a head's result times ``sigmoid(x
W_g)[i]``; then ``W_o``.

A *full* layer's query reads the set the indexer chooses
(DeepSeek-V3.2-Exp's form at ``index_n_heads`` / ``index_head_dim`` /
``index_topk``): ``qI = c_q W_iq`` (from the rescaled query latent), ``kI
= LayerNorm(x W_ik)`` (one head, weight and bias), rotary on the first
half of both's columns (halves rotated, frequencies ``theta ** (-2i /
(dim / 2))``), ``w = x W_w heads ** -0.5 dim ** -0.5``; ``I[t, s] = sum_j
w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t``: all ``s <= t``
while ``t + 1 <= index_topk``, else the ``index_topk`` with the largest
``I`` (``lax.top_k`` over the whole causal row; a tie to the lower
``s``; ``-0.0`` counts as ``0.0``). The published model's FP8 operands
are left out (another result: the lower-precision control). A *sliding*
layer's query reads ``{s : s <= t, t - s < sliding_window_size}``: an
explicit mask over the keys from the block's first query's window on.

*Feed-forward.* Layer < ``first_k_dense_replace``: a SiLU-gated MLP of
``intermediate_size``. Else ``s = sigmoid(x W_r)``, the
``num_experts_per_tok`` largest of ``s + b``, weights ``s_e / sum of the
chosen`` times ``routed_scaling_factor``, gated experts of
``moe_intermediate_size`` — the terms of the experts ``held`` only — and
one shared expert every token visits.

The vision and audio towers and the prediction module are left out: the
catalog's ``config`` holds the language model only.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form. :func:`Reference.forward` reads one layer's
tensors at a time, the experts ``EXPERT_BLOCK`` at a time, and visits
each expert once over the tokens that chose it (a gather, the expert, a
scatter); scores, choice and attention run ``QUERY_BLOCK`` queries at a
time, so that a 16k-token prompt's score matrix fits the device.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: routed experts read and run together
EXPERT_BLOCK = 8
#: queries whose rows of scores are held together
QUERY_BLOCK = 128
SLIDING = "sliding_attention"


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def geometry(cfg, sliding: bool) -> dict:
    """The layer type's sizes under plain names."""
    pre = "swa_" if sliding else ""
    return {"heads": cfg[pre + "num_attention_heads"],
            "q_rank": cfg[pre + "q_lora_rank"],
            "kv_rank": cfg[pre + "kv_lora_rank"],
            "nope": cfg[pre + "qk_nope_head_dim"],
            "rope": cfg[pre + "qk_rope_head_dim"],
            "value": cfg[pre + "v_head_dim"],
            "theta": cfg[pre + "rope_theta"]}


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def turn(x, theta: float, positions):
    """Halves rotated: ``x`` (L, ..., dim) at ``positions`` (L,), plain
    frequencies ``theta ** (-2i / dim)``."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)
    emb = emb.reshape(emb.shape[:1] + (1,) * (x.ndim - 2) + emb.shape[1:])
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def rotary(x, theta: float, positions):
    """The attention's rotary: the published pairs ``(2j, 2j + 1)``
    de-interleaved into halves, then halves rotated (DeepSeek-V2's
    ``apply_rotary_pos_emb``)."""
    dim = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    return turn(jnp.concatenate([x[..., 0], x[..., 1]], -1), theta,
                positions)


def latents(cfg, geo, w, x, positions):
    """-> (c_q (L, q_rank) rescaled; q (L, H, nope + rope), k_n (L, H,
    nope), k_r (L, rope), v (L, H, value), gate (L, H))."""
    eps, hidden = cfg["rms_norm_eps"], cfg["hidden_size"]
    heads, nope, rope = geo["heads"], geo["nope"], geo["rope"]
    length = x.shape[0]
    rescale = cfg["apply_mla_qkv_lora_rescale"]
    rho_q = math.sqrt(hidden / geo["q_rank"]) if rescale else 1.0
    rho_kv = math.sqrt(hidden / geo["kv_rank"]) if rescale else 1.0
    c_q = rho_q * rms_norm(x @ w["q_a"], w["q_a_norm"], eps)
    q = (c_q @ w["q_b"]).reshape(length, heads, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], geo["theta"], positions)], -1)
    down = x @ w["kv_a"]
    c_kv = rho_kv * rms_norm(down[:, :geo["kv_rank"]], w["kv_a_norm"], eps)
    kv = (c_kv @ w["kv_b"]).reshape(length, heads, nope + geo["value"])
    k_r = rotary(down[:, geo["kv_rank"]:], geo["theta"], positions)
    return c_q, q, kv[..., :nope], k_r, kv[..., nope:], \
        jax.nn.sigmoid(x @ w["attn_gate"])


def _blocks(length: int):
    block = min(QUERY_BLOCK, length)
    return block, -(-length // block)


def _pad_rows(a, rows: int):
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def window_attention(cfg, w, x):
    """A sliding layer's mixer on ``x`` (L, hidden), normed."""
    geo = geometry(cfg, True)
    length = x.shape[0]
    window = cfg["sliding_window_size"]
    positions = jnp.arange(length)
    _, q, k_n, k_r, v, gate = latents(cfg, geo, w, x, positions)
    scale = (geo["nope"] + geo["rope"]) ** -0.5
    block, blocks = _blocks(length)
    q = _pad_rows(q, blocks * block)
    # the keys a block of queries may read: from its first query's
    # window to its last query; in front of the prompt, keys that mask
    # themselves (position < 0)
    reach = block + window - 1
    front = window - 1
    k_n, v = (jnp.pad(a, ((front, blocks * block - length), (0, 0), (0, 0)))
              for a in (k_n, v))
    k_r = jnp.pad(k_r, ((front, blocks * block - length), (0, 0)))

    def some(lo):
        mine = lo + jnp.arange(block)
        theirs = lo - front + jnp.arange(reach)
        keep = (theirs[None, :] <= mine[:, None]) \
            & (mine[:, None] - theirs[None, :] < window) \
            & (theirs[None, :] >= 0)
        qs = lax.dynamic_slice_in_dim(q, lo, block)
        s = (jnp.einsum("thd,shd->hts", qs[..., :geo["nope"]],
                        lax.dynamic_slice_in_dim(k_n, lo, reach))
             + jnp.einsum("thd,sd->hts", qs[..., geo["nope"]:],
                          lax.dynamic_slice_in_dim(k_r, lo, reach))) * scale
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p,
                          lax.dynamic_slice_in_dim(v, lo, reach))

    out = lax.map(some, jnp.arange(blocks) * block) \
        .reshape(blocks * block, geo["heads"], geo["value"])[:length]
    out = out * gate[:, :, None]
    return out.reshape(length, -1) @ w["o"]


def indexed_attention(cfg, w, x, forced=None, forced_count=0, topk=None):
    """A full layer's mixer on ``x`` (L, hidden), normed. ``forced`` (L,
    L) bool, when given, replaces the indexer's choice of keys for the
    first ``forced_count`` queries — a departure for evaluation only, as
    a router's forced choice: a near-tie at the ``topk``-th score then
    does not turn a rounding difference into another set.
    -> (out (L, hidden); the reference's own sets (L, L) bool;
    ``shortfall`` (L,): how far the weakest key of the set used lies
    under the reference's ``topk``-th best score, 0 for its own choice;
    ``bad`` (L,) bool: a set used that has another size than ``min(t +
    1, topk)`` or a key of the future; ``differ`` (L,): the keys in which
    the set used and the reference's own differ)."""
    geo = geometry(cfg, False)
    length = x.shape[0]
    topk = cfg["index_topk"] if topk is None else topk
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    positions = jnp.arange(length)
    c_q, q, k_n, k_r, v, gate = latents(cfg, geo, w, x, positions)

    def front_turned(a):
        return jnp.concatenate(
            [turn(a[..., :dim // 2], geo["theta"], positions),
             a[..., dim // 2:]], -1)
    qi = front_turned((c_q @ w["index_q"]).reshape(length, heads, dim))
    ki = front_turned(layer_norm(x @ w["index_k"], w["index_k_norm"],
                                 w["index_k_bias"], cfg["rms_norm_eps"]))
    wi = (x @ w["index_w"]) * (heads ** -0.5 * dim ** -0.5)
    scale = (geo["nope"] + geo["rope"]) ** -0.5
    block, blocks = _blocks(length)
    q, qi, wi = (_pad_rows(a, blocks * block) for a in (q, qi, wi))
    if forced is not None:
        forced = _pad_rows(forced, blocks * block)
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
        qs = lax.dynamic_slice_in_dim(q, lo, block)
        s = (jnp.einsum("thd,shd->hts", qs[..., :geo["nope"]], k_n)
             + jnp.einsum("thd,sd->hts", qs[..., geo["nope"]:], k_r)) * scale
        p = jax.nn.softmax(jnp.where(used[None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("hts,shd->thd", p, v)
        return out, own, shortfall, bad, (own != used).sum(1)

    out, own, shortfall, bad, differ = lax.map(
        some, jnp.arange(blocks) * block)
    out = out.reshape(blocks * block, geo["heads"], geo["value"])[:length] \
        * gate[:, :, None]
    return (out.reshape(length, -1) @ w["o"],
            own.reshape(blocks * block, length)[:length],
            shortfall.reshape(-1)[:length], bad.reshape(-1)[:length],
            differ.reshape(-1)[:length])


# -- feed-forward ---------------------------------------------------------


def route(cfg, w, x, forced=None):
    """-> (ids (L, k), weights (L, k), shortfall (L,), what every chip
    computes alike: the shared expert's term (L, hidden)). ``forced``
    (L, k), when given, replaces the router's choice (for evaluation
    only: see :func:`indexed_attention`); the scores still give the
    weights. ``shortfall``: how far below the k-th best ``s + b`` the
    weakest of the ids used lies; 0 for the router's own choice."""
    scores = jax.nn.sigmoid(x @ w["router"])
    adjusted = scores + w["b_corr"]
    best, own = lax.top_k(adjusted, cfg["num_experts_per_tok"])
    ids = own if forced is None else forced
    shortfall = best[:, -1] - jnp.take_along_axis(adjusted, ids, 1).min(1)
    picked = jnp.take_along_axis(scores, ids, 1)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    shared = gated_mlp(x, w["shared_gate"], w["shared_up"],
                       w["shared_down"])
    return ids, cfg["routed_scaling_factor"] * picked, shortfall, shared


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


LATENT = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
          "attn_gate", "o")
INDEXER = ("index_q", "index_k", "index_w", "index_k_norm", "index_k_bias")
ROUTER = ("router", "b_corr", "shared_gate", "shared_up", "shared_down")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys, ``n_routed_experts`` the
    router's width)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._indexed = jax.jit(
            lambda w, h, forced, count, topk: indexed_attention(
                cfg, w, h, forced, count, topk), static_argnames=("topk",))
        self._window = jax.jit(lambda w, h: window_attention(cfg, w, h))
        self._route = jax.jit(lambda w, h, forced: route(cfg, w, h, forced))
        self._held = jax.jit(held_part, static_argnames=("room",))
        self._dense = jax.jit(gated_mlp)

    def is_sliding(self, layer: int) -> bool:
        return self.cfg["layer_types"][layer] == SLIDING

    def experts(self, read, layer: int, h, held, forced=None):
        """One expert layer on ``h`` (L, hidden), normed: the shared
        expert's term and the terms of the experts ``held``.
        -> (out, ids, shortfall, the routed part alone)."""
        ids, weights, shortfall, shared = self._route(
            {t: read("l%d.%s" % (layer, t)) for t in ROUTER}, h, forced)
        held = np.asarray([int(e) for e in held], np.int32)
        routed = jnp.zeros_like(h)
        if len(held):
            chose = np.bincount(np.asarray(ids).reshape(-1),
                                minlength=int(held.max()) + 1)[held]
            # the most tokens any held expert serves, to a power of two:
            # a few compilations, not one a prompt
            room = 1 << max(3, int(chose.max() - 1).bit_length())
            for lo in range(0, len(held), EXPERT_BLOCK):
                block = held[lo:lo + EXPERT_BLOCK]
                w = {t: read("l%d.%s" % (layer, t), block)
                     for t in PER_EXPERT}
                routed = routed + self._held(w, h, ids, weights,
                                             jnp.asarray(block), room=room)
        return routed + shared, ids, shortfall, routed

    def forward(self, read, tokens, held=None, forced=None,
                forced_sets=None, forced_count=None, position=-1,
                topk=None, keep_sets=False):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of the
        router. ``forced``: (expert layers, L, k) router choices or None;
        ``forced_sets``: a (L, L) bool a *full* layer, the keys each
        query reads, for the first ``forced_count`` queries (all by
        default; those behind choose for themselves: a caller's
        padding), or None. ``position``: whose logits are returned (the
        stack is causal: a caller may pad a prompt behind its last token
        and ask for the last real one). ``topk``: in the place of
        ``index_topk`` (a control's).
        -> {"logits": (vocab,), "chosen": (expert layers, L, k),
        "shortfall": (expert layers, L), "key_shortfall", "key_bad",
        "key_differ": (full layers, L), and with ``keep_sets``
        "key_sets": (full layers, L, L) bool, its own}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        length = len(tokens)
        if held is None:
            held = range(cfg["published"]["n_routed_experts"]
                         if "published" in cfg else cfg["n_routed_experts"])
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        out = {key: [] for key in ("chosen", "shortfall", "key_shortfall",
                                   "key_bad", "key_differ", "key_sets")}
        full = sparse = 0
        for i in range(cfg["num_hidden_layers"]):
            h = rms_norm(x, read("l%d.attn_norm" % i), eps)
            w = {t: read("l%d.%s" % (i, t)) for t in LATENT}
            if self.is_sliding(i):
                x = x + self._window(w, h)
            else:
                w.update({t: read("l%d.%s" % (i, t)) for t in INDEXER})
                used = None if forced_sets is None \
                    else jnp.asarray(forced_sets[full])
                mixed, own, short, bad, differ = self._indexed(
                    w, h, used,
                    length if forced_count is None else forced_count,
                    topk=topk)
                x = x + mixed
                out["key_shortfall"].append(short)
                out["key_bad"].append(bad)
                out["key_differ"].append(differ)
                if keep_sets:
                    out["key_sets"].append(own)
                full += 1
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + self._dense(h, *(read("l%d.%s" % (i, t))
                                         for t in PER_EXPERT))
            else:
                added, ids, shortfall, _ = self.experts(
                    read, i, h, held,
                    None if forced is None else jnp.asarray(forced[sparse]))
                out["chosen"].append(ids)
                out["shortfall"].append(shortfall)
                x = x + added
                sparse += 1
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                **{key: jnp.stack(value) for key, value in out.items()
                   if value}}
