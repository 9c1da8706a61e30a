"""The plain reference of the DeepSeek-V2 family: one prompt at a time,
unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it). It
imports nothing from the program; it follows the published description
(``config.json`` of deepseek-ai/DeepSeek-V2 and its modelling code,
DeepSeek-V2, arXiv:2405.04434), and each departure is a comment.

Every layer is ``x += attn(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``, eps
``rms_norm_eps``; the layers are the model's first
``num_hidden_layers``; the first ``first_k_dense_replace`` have a gated
MLP of width ``intermediate_size``, the rest the expert layer; then a
final RMSNorm and an untied head on the last position.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form (``top.embed``, ``l<i>.q_b``, ...; for
``l<i>.gate``, ``.up`` and ``.down`` of an expert layer the stack of
the experts named). :func:`forward` reads one layer's tensors at a time
and runs attention and each kind of feed-forward as one jitted function
of (weights, activations): a prompt costs a few compilations and never
holds more than one layer in float32; attention runs one head at a
time, so that a long prompt's scores fit the device.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# -- YaRN rotary ----------------------------------------------------------


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """As ``DeepseekV2YarnRotaryEmbedding``: the plain frequencies
    where a dimension turns often within the original context, those
    over ``factor`` where it turns rarely, a linear ramp between the
    correction dimensions of ``beta_fast`` and ``beta_slow``."""
    yarn = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    original = yarn["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** exponent
    freq_inter = 1.0 / (yarn["factor"] * base ** exponent)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return jnp.asarray(freq_inter * (1 - mask) + freq_extra * mask,
                       jnp.float32)


def rotary(cfg, x):
    """``x`` (L, ..., rotary dim) at positions 0..L-1. The published
    code de-interleaves the pairs ``(2j, 2j+1)`` into halves, then
    rotates halves."""
    yarn = cfg["rope_scaling"]
    length, dim = x.shape[0], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    emb = jnp.concatenate([freqs, freqs], -1)
    mscale = yarn_get_mscale(yarn["factor"], yarn["mscale"]) \
        / yarn_get_mscale(yarn["factor"], yarn["mscale_all_dim"])
    shape = (length,) + (1,) * (x.ndim - 2) + (dim,)
    cos = (jnp.cos(emb) * mscale).reshape(shape)
    sin = (jnp.sin(emb) * mscale).reshape(shape)
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


# -- the layers -----------------------------------------------------------


def attention(cfg, w, x):
    """Latent attention in its published (expanded) form: per head
    ``q = [q_nope | q_pe]``, ``k = [k_nope | k_pe]`` with ``k_pe`` one
    vector a token shared by all heads, one causal masked softmax a
    head."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, value = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    eps, yarn = cfg["rms_norm_eps"], cfg["rope_scaling"]
    length = x.shape[0]
    scale = (nope + rot) ** -0.5 \
        * yarn_get_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    q = (rms_norm(x @ w["q_a"], w["q_a_norm"], eps) @ w["q_b"]) \
        .reshape(length, heads, nope + rot)
    down = x @ w["kv_a"]
    kv = (rms_norm(down[:, :rank], w["kv_a_norm"], eps) @ w["kv_b"]) \
        .reshape(length, heads, nope + value)
    k_pe = rotary(cfg, down[:, rank:])                     # (L, rot)
    q = jnp.concatenate([q[..., :nope], rotary(cfg, q[..., nope:])], -1)
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one_head(h):
        k = jnp.concatenate([kv[:, h, :nope], k_pe], -1)
        s = (q[:, h] @ k.T) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ kv[:, h, nope:]

    out = lax.map(one_head, jnp.arange(heads))             # (H, L, value)
    return out.transpose(1, 0, 2).reshape(length, heads * value) @ w["o"]


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def dense(cfg, w, x):
    return gated_mlp(x, w["gate"], w["up"], w["down"])


def experts(cfg, w, x, held, forced=None):
    """Softmax router over all the model's experts, float32; a group's
    score is the best of its experts; the best ``topk_group`` groups
    stay, the rest are masked to 0; top-k of what is left; weights =
    the chosen scores, not renormalised (``norm_topk_prob`` false),
    times the scaling factor; gated experts; ``n_shared_experts``
    shared ones as one MLP of that many times the width.

    ``held``: the ids of the experts whose terms are kept (the chip's
    share); the others' terms are left out. ``forced`` (L, k), when
    given, replaces the router's choice — a departure for evaluation
    only: it lets a comparison hold the arithmetic to a tight tolerance
    without a near-tie in the router turning a rounding difference into
    a different group or expert; the scores still give the weights.
    -> (out, chosen ids (L, k), shortfall (L,): how far below the k-th
    best masked score the weakest of the ids used lies, under the mask
    of the groups used, group_shortfall (L,): how far below the
    ``topk_group``-th best group score the weakest group used lies;
    both 0 for the router's own choice)."""
    k, groups = cfg["num_experts_per_tok"], cfg["n_group"]
    top_groups = cfg["topk_group"]
    if cfg["norm_topk_prob"] or cfg["scoring_func"] != "softmax":
        raise NotImplementedError("the published DeepSeek-V2 router")
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    length, total = scores.shape
    per = total // groups
    group_score = scores.reshape(length, groups, per).max(-1)
    best_groups, own_groups = lax.top_k(group_score, top_groups)

    def masked(group_ids):
        keep = (group_ids[:, :, None] == jnp.arange(groups)).any(1)
        return jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)

    if forced is None:
        used_groups = own_groups
    else:
        # the groups the forced ids lie in, topped up to topk_group by
        # this router's own best: its own choice where they agree
        implied = (forced[:, :, None] // per == jnp.arange(groups)).any(1)
        _, used_groups = lax.top_k(
            jnp.where(implied, jnp.inf, group_score), top_groups)
    group_shortfall = best_groups[:, -1] - jnp.take_along_axis(
        group_score, used_groups, 1).min(1)
    best, own = lax.top_k(masked(used_groups), k)
    ids = own if forced is None else forced
    picked = jnp.take_along_axis(scores, ids, 1)
    shortfall = best[:, -1] - picked.min(1)
    weights = picked * cfg["routed_scaling_factor"]

    def add_expert(acc, e_w):
        e, gate, up, down = e_w
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return acc + w_e[:, None] * gated_mlp(x, gate, up, down), None

    # the loop over the chosen experts, turned inside out: one held
    # expert at a time over the tokens that chose it (weight 0 for the
    # rest), so that no per-token copy of an expert is made
    routed, _ = lax.scan(add_expert, jnp.zeros_like(x),
                         (held, w["gate"], w["up"], w["down"]))
    shared = gated_mlp(x, w["shared_gate"], w["shared_up"],
                       w["shared_down"])
    return routed + shared, ids, shortfall, group_shortfall


ATTENTION = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")
DENSE = ("gate", "up", "down")
EXPERTS = ("router", "gate", "up", "down", "shared_gate", "shared_up",
           "shared_down")
PER_EXPERT = ("gate", "up", "down")


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._attention = jax.jit(lambda w, h: attention(cfg, w, h))
        self._dense = jax.jit(lambda w, h: dense(cfg, w, h))
        self._experts = jax.jit(
            lambda w, h, held, forced: experts(cfg, w, h, held, forced))

    def forward(self, read, tokens, held=None, forced=None,
                position=-1):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of
        the router. ``forced``: (expert layers, L, k) choices or None.
        ``position``: whose logits are returned, the last by default
        (attention is causal, so a caller may pad a prompt behind its
        last token to a length it has compiled before, and ask for the
        last real one).
        -> {"logits": (vocab,), "chosen": (expert layers, L, k),
        "shortfall", "group_shortfall": (expert layers, L)}"""
        cfg = self.cfg
        eps = cfg["rms_norm_eps"]
        if held is None:
            held = range(cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]))
        held = jnp.asarray([int(e) for e in held], jnp.int32)
        x = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        chosen, short, group_short = [], [], []
        for i in range(cfg["num_hidden_layers"]):
            def w(names, per_expert=()):
                return {t: read("l%d.%s" % (i, t),
                                held if t in per_expert else None)
                        for t in names}
            h = rms_norm(x, read("l%d.attn_norm" % i), eps)
            x = x + self._attention(w(ATTENTION), h)
            h = rms_norm(x, read("l%d.ffn_norm" % i), eps)
            if i < cfg["first_k_dense_replace"]:
                out = self._dense(w(DENSE), h)
            else:
                out, ids, shortfall, group_shortfall = self._experts(
                    w(EXPERTS, PER_EXPERT), h, held, None if forced is None
                    else jnp.asarray(forced[len(chosen)]))
                chosen.append(ids)
                short.append(shortfall)
                group_short.append(group_shortfall)
            x = x + out
        last = rms_norm(x[position], read("top.final_norm"), eps)
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen),
                "shortfall": jnp.stack(short),
                "group_shortfall": jnp.stack(group_short)}
