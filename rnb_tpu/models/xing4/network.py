"""The forward pass of a Xing4.0 stack over a packed pool of rows.

The residual path is not ``x = x + f(norm(x))``. A token carries ``n =
hc_mult`` streams of ``hidden`` channels, ``X``; every sublayer
(attention, feed-forward: two a layer) has its own mappings, made from
the token's stream (``ops/hyper.py``, mHC, arXiv:2512.24880)::

    u  = h_pre X                    the sublayer's input, one stream wide
    y  = F(RMSNorm(u))              MLA, the dense MLP, or the experts
    X' = H_res X + h_post^T y       H_res doubly stochastic (Sinkhorn)

``X_0`` is the embedding repeated into the ``n`` streams; behind the
last layer the streams are summed, then the final RMSNorm and the
untied head, on each request's last valid token: the last sublayer's
way out gathers ``last_idx`` first, and ``n`` streams of one line a
request are summed, not of the whole pool.

A sublayer's way out and the next one's way in are one call
(``ops/hyper.leave_enter``: one Pallas kernel, a pass over the stream):
the stream is read once and written once a sublayer. What the way out
needs of the mappings and the way in does not — ``h_post`` and
``H_res`` under its Sinkhorn steps — is made between the two
(``coefficients_from``), beside the sublayer itself.

``F`` is DeepSeek-V3's and not written again: latent attention is
``models/deepseek_v2/network.latent_attention`` (expanded MLA under
YaRN through ``ops/mla.py`` and the pool's flash kernel), the routed
feed-forward that file's ``experts_ffn`` with the router's correction
bias (sigmoid scores, the bias for the choice alone, the chosen scores
renormalised and scaled: ``ops/moe.route``), every expert held. The
prediction module (``num_nextn_predict_layers``) is left out: a prefill
that returns one position's logits never runs it.

The carried value is ``(tokens, n hidden)``, the streams side by side
along lanes (``ops/hyper.py`` says why), in the activations' dtype; the
mappings' arithmetic is float32.

Named scopes: ``embed``, ``hyper`` (with ``hyper/maps``, ``hyper/in``,
``hyper/out`` inside it), ``attn``, ``experts``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp

from rnb_tpu.models.deepseek_v2 import network as deepseek
from rnb_tpu.models.deepseek_v2.network import (
    DeepseekV2Config, _proj, experts_ffn, latent_attention, rms_norm)
from rnb_tpu.ops import hyper, moe, rope

#: what ``forward`` returns behind the logits and the choices
#: (``models/token_stages.py``); ``stream_mix``: (the (valid token,
#: sublayer) mixings, the largest defect of any ``H_res`` in 1e-9)
COUNTERS = ("expert_served", "attn_tiles", "gmm_rows", "stream_mix")


@dataclasses.dataclass(frozen=True)
class Xing4Config(DeepseekV2Config):
    """DeepSeek-V2's sizes, under the published names, and the
    hyper-connections'."""

    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    @staticmethod
    def from_published(config: Mapping) -> "Xing4Config":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here."""
        if config["topk_method"] != "noaux_tc" \
                or config["scoring_func"] != "sigmoid" \
                or config["n_group"] != 1 or config["topk_group"] != 1:
            raise ValueError("topk_method, scoring_func, n_group or "
                             "topk_group: not the Xing4.0 this network "
                             "implements")
        return Xing4Config(
            **DeepseekV2Config.published_fields(config),
            hc_mult=int(config["hc_mult"]),
            hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
            hc_eps=float(config["hc_eps"]),
            mhc_h_res_clamp_min=float(config["mhc_h_res_clamp_min"]),
            mhc_h_res_clamp_max=float(config["mhc_h_res_clamp_max"]))

    @property
    def route_scale(self) -> float:
        """DeepSeek-V3's rule: renormalised *and* scaled."""
        return self.routed_scaling_factor

    @property
    def num_sublayers(self) -> int:
        return 2 * self.num_hidden_layers


held_slots = deepseek.held_slots


def merge_streams(last, n: int):
    """``last`` (lines, n hidden) -> float32 (lines, hidden): the
    streams summed (the Hyper-Connections paper's convention)."""
    lines = last.shape[0]
    return last.astype(jnp.float32).reshape(lines, n, -1).sum(1)


def request_choices(cfg: Xing4Config, chosen, first: int, count: int):
    """What a sample keeps of a dispatch's two records for the request
    of ``count`` tokens from flat token ``first``: the router's experts
    (expert layers, count, k) under ``chosen`` and each (sublayer,
    token)'s ``H_res`` defect (sublayers, count) under ``res_defect``."""
    import numpy as np
    ids, defects = chosen
    return {"chosen": np.asarray(ids)[:, first:first + count].copy(),
            "res_defect": np.asarray(defects)[:, first:first + count].copy()}


MAPPINGS = ("phi", "alpha", "bias")


def mappings_of(p, sub: str):
    """A sublayer's (phi, alpha, bias) of a layer's tensors."""
    return tuple(p["%s_hc_%s" % (sub, t)] for t in MAPPINGS)


def forward(cfg: Xing4Config, params, slots, tokens, row_tokens,
            row_start, last_idx, *, interpret=False):
    """One packed dispatch; the arguments are
    ``models/deepseek_v2/network.forward``'s.

    -> (logits (rows, vocab) float32, one line a request; (the router's
    choices (expert layers, tokens, k) int32, every mapping's ``H_res``
    defect (sublayers, tokens) float32); assignments served by each held
    expert (expert layers, held) int32, valid tokens only; the flash
    kernel's tiles (layers, 2) int32; the rows the first grouped product
    multiplied (expert layers,) int32; ``stream_mix`` (2,) int32: the
    (valid token, sublayer) mixings and the largest defect of a valid
    token's ``H_res``, in 1e-9).

    Every layer is written out. (The sparse layers but the last as one
    ``lax.scan`` over parameters stacked at set-up compiled a row bucket
    in 29-32 s for 33-52 and served 39.5 requests/s for 43.8: the loop
    copies each layer's slice of the stacked experts, 17 ms a dispatch,
    and the stacking peaked at 16.4 GiB: my chip runs, PR 62.)
    """
    rows, q = tokens.shape
    n, pool = cfg.hc_mult, rows * q
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    positions = rope.pool_positions(row_start, q)
    mixing = dict(n=n, eps=cfg.eps, interpret=interpret)
    sinkhorn = dict(n=n, iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
                    clamp=(cfg.mhc_h_res_clamp_min,
                           cfg.mhc_h_res_clamp_max))
    layers = [params["l%d" % i] for i in range(cfg.num_hidden_layers)]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype

    def layer(p, carry, behind, dense: bool):
        """One layer on the stream: ``carry`` = (``x`` (T, n hidden),
        the attention sublayer's input ``u`` (T, hidden), its logits (T,
        LANES)); ``p`` the layer's tensors; ``behind`` the next layer's
        attention mappings, or None behind the last layer, whose way
        out is made on the ``last_idx`` lines alone.
        -> (the next carry, or the lines (rows, n hidden); the layer's
        records: the flash kernel's tiles, the two sublayers' defects
        (2, T) and, of a sparse layer, the router's choices, the served
        counts and the grouped product's rows)."""
        x, u, logits = carry
        records, defects = {}, []
        for sub, then in (("attn", mappings_of(p, "ffn")),
                          ("ffn", behind)):
            with jax.named_scope("hyper"), jax.named_scope("maps"):
                coef, worst = hyper.coefficients_from(logits, **sinkhorn)
                defects.append(worst)
            u = u.reshape(rows, q, -1)
            if sub == "attn":
                with jax.named_scope("attn"):
                    h = rms_norm(u, p["attn_norm"], cfg.eps, act)
                    out, records["tiles"] = latent_attention(
                        cfg, p, h, row_start, positions, interpret)
            else:
                with jax.named_scope("experts"):
                    h = rms_norm(u, p["ffn_norm"], cfg.eps, act)
                    if dense:
                        out = moe.dense_expert(h, p["up"], p["down"],
                                               p["gate"])
                    else:
                        out, records["chosen"], records["served"], _, \
                            records["gmm_rows"] = experts_ffn(
                                cfg, p, h, token_ok, slots, interpret)
            out = out.reshape(pool, -1)
            with jax.named_scope("hyper"), jax.named_scope("out"):
                if then is not None:
                    # the way out, and the next sublayer's way in while
                    # the tile of the new stream is in fast memory
                    x, u, logits = hyper.leave_enter(x, out, coef, *then,
                                                     **mixing)
                else:
                    # the last way out: on the lines the head reads
                    return hyper.leave_lines(
                        x[last_idx], out[last_idx], coef[last_idx], n), \
                        dict(records, defects=jnp.stack(defects))
        return (x, u, logits), dict(records, defects=jnp.stack(defects))
    with jax.named_scope("hyper"):
        x = jnp.tile(x.reshape(pool, -1), (1, n))
        with jax.named_scope("in"):
            carry = (x,) + hyper.enter(x, *mappings_of(layers[0], "attn"),
                                       **mixing)
    records = []
    for i, p in enumerate(layers):
        # a layer's way out enters the layer behind it; the last one's
        # is made on the head's lines
        behind = mappings_of(layers[i + 1], "attn") \
            if i + 1 < len(layers) else None
        carry, record = layer(p, carry, behind, cfg.is_dense(i))
        records.append(record)

    def every(name):
        return jnp.stack([r[name] for r in records if name in r])
    with jax.named_scope("head"):
        lines = rms_norm(merge_streams(carry, n), params["final_norm"],
                         cfg.eps, act)
        logits = _proj(lines, params["head"])
    defects = every("defects").reshape(cfg.num_sublayers, pool)
    ok = token_ok.reshape(-1)
    worst = jnp.where(ok[None, :], defects, 0.0).max()
    stream_mix = jnp.stack([
        ok.sum().astype(jnp.int32) * cfg.num_sublayers,
        jnp.minimum(worst * 1e9, 2e9).astype(jnp.int32)])
    return logits, (every("chosen"), defects), every("served"), \
        every("tiles"), every("gmm_rows"), stream_mix
