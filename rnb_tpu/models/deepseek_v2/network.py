"""The forward pass of a DeepSeek-V2 stack over a packed pool of rows.

Every layer is ``x += attn(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``: the
attention is latent (MLA: queries and keys-values through low-rank
latents, 64 rotary key columns shared by all heads), the feed-forward a
gated MLP in the first ``first_k_dense_replace`` layers and sparse
experts (softmax router, group-limited greedy choice, gated experts,
shared experts every token visits) in the rest. After the last layer: a
final RMSNorm and an untied head, on each request's last valid token.

MLA runs in its *expanded* form: per head ``q = [q_nope | q_pe]`` and
``k = [k_nope | k_pe]`` of 128 + 64 columns, values of 128, through the
pool's flash kernel (``ops/segattn.py``) as 128 heads of their own;
the queries' up-projection (``ops/mla.py``) writes its operand
heads-first and whole lanes wide, rotated, scaled and rounded once.
The *folded* form (``W_UK`` into the query, one latent key of 512 + 64
and one latent value of 512 for all heads, ``W_UV`` behind the kernel)
is the same mathematics and the decode path's; in prefill on the v5e it
lost (PERF.md section 6, PR 33) and is not in the tree:
``tests/test_deepseek_v2.py`` keeps its algebra against the reference.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded; a token's rotary position is its index
inside its request (``ops/rope.py``). Weights and activations are
bfloat16; the router's scores, the softmax, the norms' statistics, the
rotary angles and every product's accumulation are float32. The
rotary projections' columns are stored de-interleaved
(``checkpoint.py``), which the published code does at run time.

The named scopes are the ones the benchmark's reduction knows:
``embed``, ``attn``, ``experts`` (a layer's feed-forward, the dense
first layer's too), ``head``.

*Two callers since PR 62.* ``models/xing4/network.py`` runs the same
layer on another residual path and imports :func:`latent_attention` and
:func:`experts_ffn` (and the config's fields: ``Xing4Config`` extends
:class:`DeepseekV2Config` through ``published_fields``).
:func:`latent_attention`'s ``h`` is then no norm of ``x``: it is the
norm of the sublayer's input that caller's mappings mix out of its four
streams; nothing in the function reads the stream. :func:`experts_ffn`
hands ``ops/moe.route`` the layer's ``b_corr`` where the parameters
hold one (DeepSeek-V3's rule: the bias moves the choice, never the
weights) and ``cfg.route_scale``: a caller with a correction bias and
``norm_topk_prob`` gets sigmoid scores renormalised *and* scaled; this
file's own stack has no ``b_corr`` and lowers to the text it had
(``tests/test_qwen3_next.py``: the recorded StableHLO of the toy stack).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from rnb_tpu.ops import mla, moe, rope, segattn

#: what ``forward`` returns behind the logits and the router's choices
#: (``models/token_stages.py``); ``gmm_rows``: the rows the first
#: grouped product multiplied for the pairs the held experts served
COUNTERS = ("expert_served", "group_tokens", "attn_tiles", "gmm_rows")


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    published_layers: int
    first_k_dense_replace: int
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's, not the model's
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    router_experts: int
    n_group: int
    topk_group: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    scoring_func: str
    rope_theta: float
    rope_factor: float
    rope_original: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "DeepseekV2Config":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here and
        ``published.n_routed_experts`` the width of the router."""
        if config["topk_method"] != "group_limited_greedy":
            raise ValueError("topk_method: not the DeepSeek-V2 this "
                             "network implements")
        return DeepseekV2Config(**DeepseekV2Config.published_fields(config))

    @staticmethod
    def published_fields(config: Mapping) -> dict:
        """The fields, by name, from a configuration file's keys (a
        stack that shares the layer, ``models/xing4``, adds its own)."""
        published = config.get("published", {})
        layers = int(config["num_hidden_layers"])
        yarn = config["rope_scaling"]
        if yarn.get("type") != "yarn" or config["moe_layer_freq"] != 1:
            raise ValueError("rope_scaling.type or moe_layer_freq: not "
                             "the latent-attention stack this network "
                             "implements")
        return dict(
            num_hidden_layers=layers,
            published_layers=int(published.get("num_hidden_layers",
                                               layers)),
            first_k_dense_replace=int(config["first_k_dense_replace"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            q_lora_rank=int(config["q_lora_rank"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            n_shared_experts=int(config["n_shared_experts"]),
            router_experts=int(published.get(
                "n_routed_experts", config["n_routed_experts"])),
            n_group=int(config["n_group"]),
            topk_group=int(config["topk_group"]),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            scoring_func=str(config["scoring_func"]),
            rope_theta=float(config["rope_theta"]),
            rope_factor=float(yarn["factor"]),
            rope_original=int(yarn["original_max_position_embeddings"]),
            rope_beta_fast=float(yarn["beta_fast"]),
            rope_beta_slow=float(yarn["beta_slow"]),
            rope_mscale=float(yarn["mscale"]),
            rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
            eps=float(config["rms_norm_eps"]))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def route_scale(self) -> float:
        """What the chosen scores are multiplied by: the published code
        scales them where it does not renormalise them, and the other
        way round."""
        return 1.0 if self.norm_topk_prob else self.routed_scaling_factor

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5`` times YaRN's temperature, squared."""
        return self.qk_head_dim ** -0.5 * rope.yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def rotary_mscale(self) -> float:
        """What the published code multiplies cos and sin by: 1 here."""
        return rope.yarn_mscale(self.rope_factor, self.rope_mscale) \
            / rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)

    def inv_freq(self):
        return rope.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original, self.rope_beta_fast, self.rope_beta_slow)

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


def held_slots(cfg: DeepseekV2Config, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def latent_attention(cfg, p, h, row_start, positions, interpret=False):
    """``h`` (rows, Q, hidden), normed -> (float32 (rows, Q, hidden),
    the flash kernel's tiles: run, and on or under the diagonal).

    The queries' up-projection writes the kernel's operand itself
    (``ops/mla.py``: heads first, whole lanes; the rotation and the
    scores' scale on the float32 queries, before their one rounding to
    the activations' dtype), and ``o`` contracts over (head, value
    column) from the kernel's result as it lies. Keys and values are
    still laid out behind their product (``segattn.heads_first``)."""
    rows, q, hidden = h.shape
    act = h.dtype
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, value = cfg.qk_nope_head_dim, cfg.v_head_dim
    inv_freq, mscale = cfg.inv_freq(), cfg.rotary_mscale
    tokens = rows * q
    # a pool narrower than the kernel's blocks (the tests' sizes) gets
    # its pad tokens here, where a token is still one hidden row
    flat = jnp.pad(h.reshape(tokens, hidden),
                   ((0, segattn.pool_tokens(tokens) - tokens), (0, 0)))
    pool = flat.shape[0]
    at = jnp.pad(positions.reshape(tokens), (0, pool - tokens))
    c_q = rms_norm(_proj(flat, p["q_a"]), p["q_a_norm"], cfg.eps, act)
    query = mla.queries(c_q, p["q_b"], at, inv_freq, nope,
                        cfg.softmax_scale, mscale, interpret)
    down = _proj(flat, p["kv_a"])
    c_kv = rms_norm(down[:, :rank], p["kv_a_norm"], cfg.eps, act)
    kv = _proj(c_kv, p["kv_b"]).astype(act).reshape(pool, heads,
                                                    nope + value)
    k_pe = (rope.rotate(down[None, :, rank:], at[None], inv_freq)[0]
            * mscale).astype(act)
    key = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_pe[:, None, :],
                         (pool, heads, cfg.qk_rope_head_dim))], -1)
    out, tiles = segattn.heads_first_attention(
        query[:, None], segattn.heads_first(key, query.shape[-1]),
        segattn.heads_first(kv[..., nope:]), row_start, q, interpret)
    out = jnp.einsum("htv,hvd->td", out[:, 0, :tokens, :value],
                     p["o"].reshape(heads, value, hidden),
                     preferred_element_type=jnp.float32)
    return out.reshape(rows, q, hidden), tiles


def experts_ffn(cfg, p, h, token_ok, slots, interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    valid tokens that sent the held experts anything, the rows the
    first grouped product multiplied)."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ok = token_ok.reshape(-1)
    ids, weights = moe.route(
        flat, p["router"], p.get("b_corr"), cfg.num_experts_per_tok,
        cfg.route_scale, score=cfg.scoring_func, n_group=cfg.n_group,
        topk_group=cfg.topk_group, renormalise=cfg.norm_topk_prob)
    routed, counts, gmm_rows = moe.held_experts(
        flat, ids, weights, ok, slots, p["up"], p["down"],
        interpret=interpret, gate=p["gate"])
    out = routed + moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                                    p["shared_gate"])
    sent = ((slots[ids] >= 0).any(-1) & ok).sum().astype(jnp.int32)
    return out.reshape(rows, q, hidden), ids, counts, sent, gmm_rows


def forward(cfg: DeepseekV2Config, params, slots, tokens, row_tokens,
            row_start, last_idx, *, interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``interpret`` runs the Pallas kernels in interpret
    mode (a device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the router's
    choices (expert layers, tokens, k) int32; assignments served by
    each held expert (expert layers, held) int32, valid tokens only;
    valid tokens of each expert layer that sent the held group anything
    (expert layers,) int32; the flash kernel's tiles (layers, 2) int32:
    those this dispatch's block table let run, and those on or under
    the diagonal; the rows the first grouped product multiplied (expert
    layers,) int32).
    """
    rows, q = tokens.shape
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    positions = rope.pool_positions(row_start, q)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, served, sent, tiles, gmm_rows = [], [], [], [], []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        with jax.named_scope("attn"):
            h = rms_norm(x, p["attn_norm"], cfg.eps, act)
            out, ran = latent_attention(cfg, p, h, row_start, positions,
                                        interpret)
            x = (x.astype(jnp.float32) + out).astype(act)
            tiles.append(ran)
        with jax.named_scope("experts"):
            h = rms_norm(x, p["ffn_norm"], cfg.eps, act)
            if cfg.is_dense(i):
                out = moe.dense_expert(h, p["up"], p["down"], p["gate"])
            else:
                out, ids, counts, tokens_sent, multiplied = experts_ffn(
                    cfg, p, h, token_ok, slots, interpret)
                chosen.append(ids)
                served.append(counts)
                sent.append(tokens_sent)
                gmm_rows.append(multiplied)
            x = (x.astype(jnp.float32) + out).astype(act)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, jnp.stack(chosen), jnp.stack(served), jnp.stack(sent), \
        jnp.stack(tiles), jnp.stack(gmm_rows)
