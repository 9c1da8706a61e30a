"""The plain reference of the Xing4.0 family (``xing4``): one prompt at
a time, unpacked, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` (the caller sets it). It
imports nothing from the program. It follows ``config.json`` of
XingChen-AGI/Xing4.0-29B-A4B, whose ``hc_*`` / ``mhc_*`` keys are the
symbols of *mHC: Manifold-Constrained Hyper-Connections*
(arXiv:2512.24880; expansion rate n = 4 and 20 Sinkhorn steps are that
paper's settings) over *Hyper-Connections* (arXiv:2409.19606), around
DeepSeek-V3's layer. The layer's parts are the accepted references' own,
imported and not written again: latent attention under YaRN
(``references/deepseek_v2.attention``), the sigmoid router with a
correction bias for the choice alone and the visit of each held expert
(``references/exaone_moe.route``, ``held_part``).

A token carries ``X`` (n, C), n = ``hc_mult`` streams. Each sublayer
(attention and feed-forward: two a layer) has its own mappings ``phi``
(n C, 2n + n^2), ``alpha`` (3,), ``bias`` (2n + n^2,), columns in the
order pre | post | res, ``res`` row-major::

    x^      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
    h~_pre  = alpha_pre  (x^ phi_pre)  + b_pre
    h~_post = alpha_post (x^ phi_post) + b_post
    H~_res  = alpha_res  mat(x^ phi_res) + B_res
    h_pre   = sigmoid(h~_pre)          h_post = 2 sigmoid(h~_post)
    H_res   = SK(exp(clip(H~_res, mhc_h_res_clamp_min, .._max)))
    u       = h_pre X
    y       = F(RMSNorm(u))
    X'      = H_res X + h_post^T y

``SK`` is ``hc_sinkhorn_iters`` times (every column over its sum +
``hc_eps``, then every row over its sum + ``hc_eps``), a literal
``for``. ``X_0`` is the embedding repeated into the n streams; behind
the last layer the streams are summed, then the final RMSNorm and the
untied head on the asked position.

Departures and assumptions, none checked against the modelling code
(the configuration's ``assumed`` lists them): ``hc_eps`` stands in the
Sinkhorn denominators; the clip stands in front of ``exp``; columns are
normalised before rows; ``H_res[i, j]`` is stream j's weight in new
stream i; the norm over ``vec(X)`` has no weight (it folds into
``phi``) and takes ``rms_norm_eps``; ``alpha`` is one scalar a mapping;
the three ``phi`` lie side by side in one matrix; the streams are summed
at the end (the Hyper-Connections paper's convention). The prediction
module (``num_nextn_predict_layers``) is left out: a prefill that
returns one position's logits never runs it.

``read(name, expert_ids=None)`` hands over one tensor's float32 values
in the published form. :meth:`Reference.forward` reads one layer's
tensors at a time, the routed experts ``EXPERT_BLOCK`` at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import deepseek_v2, exaone_moe

rms_norm = deepseek_v2.rms_norm
gated_mlp = deepseek_v2.gated_mlp

#: routed experts read and run together
EXPERT_BLOCK = 16

ATTENTION = deepseek_v2.ATTENTION
DENSE = deepseek_v2.DENSE
ROUTE = exaone_moe.ROUTE
PER_EXPERT = exaone_moe.PER_EXPERT
MAPPINGS = ("hc_phi", "hc_alpha", "hc_bias")


def mappings(cfg, w, stream, iters=None):
    """``stream`` (L, n, C) -> (h_pre (L, n), h_post (L, n), H_res
    (L, n, n), the largest distance of a row or column sum of each
    token's H_res from 1 (L,)). ``iters``: in the place of
    ``hc_sinkhorn_iters`` (a test's)."""
    n = cfg["hc_mult"]
    length = stream.shape[0]
    flat = stream.reshape(length, -1)
    unit = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                           + cfg["rms_norm_eps"])
    moved = unit @ w["hc_phi"]
    alpha, bias = w["hc_alpha"], w["hc_bias"]
    pre = alpha[0] * moved[:, :n] + bias[:n]
    post = alpha[1] * moved[:, n:2 * n] + bias[n:2 * n]
    res = alpha[2] * moved[:, 2 * n:].reshape(length, n, n) \
        + bias[2 * n:].reshape(n, n)
    h_res = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                             cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"] if iters is None else iters):
        h_res = h_res / (h_res.sum(axis=1, keepdims=True) + cfg["hc_eps"])
        h_res = h_res / (h_res.sum(axis=2, keepdims=True) + cfg["hc_eps"])
    defect = jnp.maximum(jnp.abs(h_res.sum(axis=2) - 1.0).max(-1),
                         jnp.abs(h_res.sum(axis=1) - 1.0).max(-1))
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res, defect


def way_in(cfg, w, norm, stream):
    """-> (the sublayer's normed input (L, C), h_post, H_res, defect)."""
    h_pre, h_post, h_res, defect = mappings(cfg, w, stream)
    u = jnp.einsum("ln,lnc->lc", h_pre, stream)
    return rms_norm(u, norm, cfg["rms_norm_eps"]), h_post, h_res, defect


def way_out(stream, y, h_post, h_res):
    return jnp.einsum("lij,ljc->lic", h_res, stream) \
        + h_post[:, :, None] * y[:, None, :]


class Reference:
    """The forward pass for one configuration (``cfg``: the
    configuration file's published keys)."""

    def __init__(self, cfg):
        if cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 \
                or cfg["topk_group"] != 1 \
                or cfg["scoring_func"] != "sigmoid" \
                or not cfg["norm_topk_prob"]:
            raise ValueError("a router this reference does not describe")
        self.cfg = cfg
        self._in = jax.jit(lambda w, norm, x: way_in(cfg, w, norm, x))
        self._out = jax.jit(way_out)
        self._attention = jax.jit(
            lambda w, h: deepseek_v2.attention(cfg, w, h))
        self._dense = jax.jit(lambda w, h: gated_mlp(
            h, w["gate"], w["up"], w["down"]))
        self._route = jax.jit(
            lambda w, h, forced: exaone_moe.route(cfg, w, h, forced))
        self._held = jax.jit(exaone_moe.held_part,
                             static_argnames=("room",))

    def experts(self, read, layer: int, h, held, forced=None):
        """One expert layer on ``h`` (L, hidden), normed: the shared
        expert's term and the terms of the experts ``held``.
        -> (out, ids, shortfall)."""
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
        return routed + shared, ids, shortfall

    def forward(self, read, tokens, held=None, forced=None, position=-1):
        """``tokens`` (L,) ids. ``held`` defaults to every expert of the
        router. ``forced``: (expert layers, L, k) choices or None.
        ``position``: whose logits are returned, the last by default
        (the stack is causal, so a caller may pad a prompt behind its
        last token to a length it has compiled before, and ask for the
        last real one).
        -> {"logits": (vocab,), "chosen": (expert layers, L, k),
        "shortfall": (expert layers, L), "res_defect": (sublayers, L)}"""
        cfg = self.cfg
        if held is None:
            held = range(cfg["n_routed_experts"])
        embedded = jnp.take(read("top.embed"), jnp.asarray(tokens), axis=0)
        stream = jnp.repeat(embedded[:, None, :], cfg["hc_mult"], axis=1)
        chosen, short, defects = [], [], []
        for i in range(cfg["num_hidden_layers"]):
            def enter(sub, stream):
                w = {t: read("l%d.%s_%s" % (i, sub, t)) for t in MAPPINGS}
                h, h_post, h_res, defect = self._in(
                    w, read("l%d.%s_norm" % (i, sub)), stream)
                defects.append(defect)
                return h, h_post, h_res
            h, h_post, h_res = enter("attn", stream)
            y = self._attention(
                {t: read("l%d.%s" % (i, t)) for t in ATTENTION}, h)
            stream = self._out(stream, y, h_post, h_res)
            h, h_post, h_res = enter("ffn", stream)
            if i < cfg["first_k_dense_replace"]:
                y = self._dense(
                    {t: read("l%d.%s" % (i, t)) for t in DENSE}, h)
            else:
                y, ids, shortfall = self.experts(
                    read, i, h, held, None if forced is None
                    else jnp.asarray(forced[len(chosen)]))
                chosen.append(ids)
                short.append(shortfall)
            stream = self._out(stream, y, h_post, h_res)
        last = rms_norm(stream[position].sum(0), read("top.final_norm"),
                        cfg["rms_norm_eps"])
        return {"logits": last @ read("top.head"),
                "chosen": jnp.stack(chosen),
                "shortfall": jnp.stack(short),
                "res_defect": jnp.stack(defects)}
