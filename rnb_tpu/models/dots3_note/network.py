"""The forward pass of a dots3-note (``dots3_note``) language stack over
a packed pool of rows.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``
(plain norm weights, eps ``rms_norm_eps``). After the last layer: a final
RMSNorm and an untied head, on each request's last valid token.

*The mixer* is latent attention (MLA) in its expanded form, and its
sizes are the layer type's (``layer_types``; :class:`Geometry`): heads,
``qk_nope`` + ``qk_rope`` key columns, value columns, the two latents'
ranks and the rotary base. ``c_q = rho_q RMSNorm(x W_dq)``, a head's
``[q_n | q_r] = c_q W_uq[i]`` with ``q_r`` rotated (``ops/mla.queries``:
heads first, whole lanes, rotated, scaled and rounded once); ``[c | k_r]
= x W_dkv``, ``c_kv = rho_kv RMSNorm(c)``, ``k_r`` rotated, one for all
heads; ``[k_n[i] | v[i]] = c_kv W_ukv[i]``; ``rho = sqrt(hidden /
rank)`` (``apply_mla_qkv_lora_rescale``), on the float32 normed latent
before its one rounding. A head's result is multiplied by ``sigmoid(x
W_g)[i]`` (the head-wise gate) in front of ``W_o``.

A *full* layer (128 heads of 128 + 64 / 128, latents 1,024 and 512,
theta 8e7) reads a learned choice of keys: DeepSeek-V3.2-Exp's
lightning indexer, ``qI = c_q W_iq`` (64 heads of 128, from the *query
latent*), ``kI = LayerNorm(x W_ik)`` (one head), the first half of
their columns rotated at the layer's theta, ``w = x W_w`` times ``heads
** -0.5 dim ** -0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
in float32 from bfloat16 operands; a query reads every key of its
request at or before it while it has ``index_topk`` or fewer, else the
``index_topk`` with the largest ``I`` (a tie to the lower key), one set
for all heads: ``ops/indexed.py``'s scores and thresholds, and its
latent kernel under the sets (``latent_indexed_attention``). A
*sliding* layer (64 heads of 192 + 64 / 128, both latents 1,024, theta
5e4) reads the ``sliding_window_size`` keys of its request that end
with the query's own, through ``ops/banded.latent_banded_attention``: a
band of two key blocks a step, never the causal triangle. Both kernels
read q as ``ops/mla.queries`` wrote it and ``kv`` as its product wrote
it, and write ``W_o``'s operand, gate applied: between the products no
array with a head axis is copied in HBM; the shared rotary key is one
(T, 128) array.

*Feed-forward*: a SiLU-gated MLP in the first ``first_k_dense_replace``
layers, else sparse experts under DeepSeek-V3's ``noaux_tc`` rule: ``s
= sigmoid(x W_r)`` in float32, the ``num_experts_per_tok`` largest of
``s + b`` (no groups), weights ``s_i / sum(s_chosen)`` times
``routed_scaling_factor`` (``ops/moe.route``), the held experts' gated
part (``ops/moe.held_experts``, its pair buffers sized by the share
held) and one shared expert every token visits.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded; a token's rotary position is its index
inside its request. Weights and activations are bfloat16; the router's
scores, the softmaxes, the norms' statistics, the rotary angles, the
indexer's scores, the gates and every product's accumulation are
float32. The rotary projections' columns are stored de-interleaved
(``checkpoint.py``).

The named scopes are ``embed``, ``attn`` (inside it ``attn/mla_proj``:
the five products with the latents' norms and the keys' rotary;
``attn/gate``; ``attn/select``: everything that decides the sets, with
``attn/select/index`` the indexer's products, norm, rotary and scores —
``models/keye_vl2``'s two names, so that the accepted readers of the
selection find this family's; ``attn/full`` and ``attn/window``: the
two attention kernels' calls), ``experts`` (a layer's feed-forward, the
dense layer's too) and ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import banded, indexed, latent, mla, moe, rope

#: what ``forward`` returns behind the logits and the two kinds of
#: choice (``models/token_stages.py``); ``sparse`` and ``index_tiles``
#: count the full layers (``models/keye_vl2``'s pair), ``window_tiles``
#: and ``window_keys`` the sliding ones: the banded kernel's steps and
#: the tiles of their size on or under the diagonal; the pairs the
#: window keeps and the causal pairs; ``index_chunks`` the full layers'
#: thresholds (``models/keye_vl2``'s)
COUNTERS = ("expert_served", "gmm_rows", "pair_rows", "sparse",
            "index_tiles", "window_tiles", "window_keys", "index_chunks")
SLIDING, FULL = "sliding_attention", "full_attention"
#: the lower-precision control's rounding of the indexer's operands
#: (``models/keye_vl2``'s): float8 e4m3's exponent and mantissa bits
FLOAT8_BITS = (4, 3)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One layer type's latent attention."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rotary: int
    value: int
    theta: float

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rotary

    @property
    def lanes(self) -> int:
        """The columns of a head's queries as the kernels read them."""
        return -(-self.qk_dim // 128) * 128

    @property
    def key_lanes(self) -> int:
        """The columns of a head's own key in ``kv_b``'s stored form."""
        return latent.key_lanes(self.nope, self.lanes)

    def inv_freq(self, dim: int = None) -> np.ndarray:
        """(dim // 2,) float32: the plain frequencies over ``dim``
        columns (the rotary ones, by default)."""
        dim = self.rotary if dim is None else dim
        return (self.theta ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)) \
            .astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    layer_types: Tuple[str, ...]    # of the layers held
    first_k_dense_replace: int
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    full: Geometry
    sliding: Geometry
    sliding_window_size: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    rescale: bool
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    router_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    scoring_func: str
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "Dots3NoteConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here (the first so many
        of ``layer_types``) and ``published.n_routed_experts`` the width
        of the router."""
        layers = int(config["num_hidden_layers"])
        kinds = tuple(config["layer_types"][:layers])
        if config["hidden_act"] != "silu" or config["tie_word_embeddings"] \
                or config["attention_bias"] \
                or config["rope_scaling"] is not None \
                or config["moe_layer_freq"] != 1 \
                or config["topk_method"] != "noaux_tc" \
                or config["attention_gate_type"] != "headwise" \
                or config["swa_attention_gate_type"] != "headwise" \
                or config["num_key_value_heads"] \
                != config["num_attention_heads"] \
                or config["swa_num_key_value_heads"] \
                != config["swa_num_attention_heads"] \
                or len(kinds) != layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError("hidden_act, tie_word_embeddings, "
                             "attention_bias, rope_scaling, moe_layer_freq, "
                             "topk_method, the gate types, the key-value "
                             "head counts or layer_types: not the "
                             "dots3-note this network implements")

        def geometry(pre: str, theta: str) -> Geometry:
            return Geometry(
                heads=int(config[pre + "num_attention_heads"]),
                q_rank=int(config[pre + "q_lora_rank"]),
                kv_rank=int(config[pre + "kv_lora_rank"]),
                nope=int(config[pre + "qk_nope_head_dim"]),
                rotary=int(config[pre + "qk_rope_head_dim"]),
                value=int(config[pre + "v_head_dim"]),
                theta=float(config[theta]))
        return Dots3NoteConfig(
            num_hidden_layers=layers, layer_types=kinds,
            first_k_dense_replace=int(config["first_k_dense_replace"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            full=geometry("", "rope_theta"),
            sliding=geometry("swa_", "swa_rope_theta"),
            sliding_window_size=int(config["sliding_window_size"]),
            index_n_heads=int(config["index_n_heads"]),
            index_head_dim=int(config["index_head_dim"]),
            index_topk=int(config["index_topk"]),
            rescale=bool(config["apply_mla_qkv_lora_rescale"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            n_shared_experts=int(config["n_shared_experts"]),
            router_experts=int(config.get("published", {}).get(
                "n_routed_experts", config["n_routed_experts"])),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            scoring_func=str(config["scoring_func"]),
            eps=float(config["rms_norm_eps"]))

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def geometry(self, layer: int) -> Geometry:
        return self.sliding if self.is_sliding(layer) else self.full

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def sliding_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    def rescales(self, geo: Geometry) -> Tuple[float, float]:
        """(rho_q, rho_kv): ``sqrt(hidden / rank)`` on the normed
        latents where ``apply_mla_qkv_lora_rescale`` says so."""
        if not self.rescale:
            return 1.0, 1.0
        return (math.sqrt(self.hidden_size / geo.q_rank),
                math.sqrt(self.hidden_size / geo.kv_rank))


def held_slots(cfg: Dots3NoteConfig, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype, scale: float = 1.0):
    """``scale``: a latent's rescale, on the float32 result before its
    one rounding."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    xf = xf * weight.astype(jnp.float32)
    return (xf if scale == 1.0 else xf * scale).astype(out_dtype)


def layer_norm(x, weight, bias, eps: float):
    """float32 in, float32 out."""
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * weight.astype(jnp.float32) + bias.astype(jnp.float32)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def rotate_front(x, positions, inv_freq):
    """Rotary on the first ``2 len(inv_freq)`` columns of ``x``
    (tokens, ..., dim) float32 at ``positions`` (tokens,); the rest
    pass."""
    dim = 2 * len(inv_freq)
    turned = rope.rotate(x[None, ..., :dim], positions[None], inv_freq)[0]
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


def index_operands(cfg, geo, p, flat, c_q, at, index_bits=None):
    """The indexer's (queries (T, heads, dim) and keys (T, dim) in the
    activations' dtype, heads' weights (T, heads) float32 with both
    scales in them): the queries from the query latent ``c_q``, keys and
    weights from the mixer's normed input ``flat``. ``index_bits``: the
    control's (exponent, mantissa) bits the operands are rounded
    through."""
    tokens = flat.shape[0]
    heads, dim = cfg.index_n_heads, cfg.index_head_dim
    inv_freq = geo.inv_freq(dim // 2)
    qi = _proj(c_q, p["index_q"]).reshape(tokens, heads, dim)
    ki = layer_norm(_proj(flat, p["index_k"]), p["index_k_norm"],
                    p["index_k_bias"], cfg.eps)
    qi = rotate_front(qi, at, inv_freq)
    ki = rotate_front(ki, at, inv_freq)
    w = _proj(flat, p["index_w"]) * (heads ** -0.5 * dim ** -0.5)
    qi, ki = qi.astype(flat.dtype), ki.astype(flat.dtype)
    if index_bits is not None:
        qi, ki = (jax.lax.reduce_precision(x, *index_bits)
                  for x in (qi, ki))
    return qi, ki, w


def latent_mixer(cfg, layer: int, p, h, at, start, valid, turn,
                 interpret=False, index_bits=None):
    """``h`` (rows, Q, hidden), normed; ``at`` (T,) positions inside the
    requests, ``start`` (T,) the requests' first tokens, ``valid`` (T,)
    bool; ``turn``: the layer type's ``ops/mla.turn_tables``, a
    dispatch's. -> (float32 (rows, Q, hidden), and of a full layer: the
    sets as bits (T, keys a tile) uint32, the ``Sparse:`` line's four
    int32 (4,), the kernel's tiles with a chosen key and on or under the
    diagonal int32 (2,), the thresholds' chunk visits and those of a
    walk from key 0 int32 (2,); of a sliding layer: the banded kernel's
    steps and causal tiles int32 (2,))."""
    rows, q, hidden = h.shape
    act = h.dtype
    sliding = cfg.is_sliding(layer)
    geo = cfg.sliding if sliding else cfg.full
    tokens = rows * q
    flat = h.reshape(tokens, hidden)
    rho_q, rho_kv = cfg.rescales(geo)
    inv_freq = geo.inv_freq()
    with jax.named_scope("mla_proj"):
        c_q = rms_norm(_proj(flat, p["q_a"]), p["q_a_norm"], cfg.eps, act,
                       rho_q)
        query = mla.queries(
            c_q, p["q_b"], at, inv_freq, geo.nope, geo.qk_dim ** -0.5,
            interpret=interpret, out_columns=geo.lanes, tables=turn)
        down = _proj(flat, p["kv_a"])
        c_kv = rms_norm(down[:, :geo.kv_rank], p["kv_a_norm"], cfg.eps, act,
                        rho_kv)
        kv_b = p["kv_b"]
        if kv_b.ndim == 3:
            # stored a head in front where a head's own key is padded
            # to whole lanes (``checkpoint.py``): one matrix here
            kv_b = jnp.swapaxes(kv_b, 0, 1).reshape(geo.kv_rank, -1)
        kv = _proj(c_kv, kv_b).astype(act)
        k_pe = rope.rotate(down[None, :, geo.kv_rank:], at[None],
                           inv_freq)[0].astype(act)
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(_proj(flat, p["attn_gate"]))
    if sliding:
        with jax.named_scope("window"):
            out, tiles = banded.latent_banded_attention(
                query, kv, k_pe, gate, start[:, None],
                cfg.sliding_window_size, geo.nope, geo.value,
                interpret=interpret)
        extras = (tiles,)
    else:
        with jax.named_scope("select"):
            with jax.named_scope("index"):
                keys = indexed.index_keys(
                    *index_operands(cfg, geo, p, flat, c_q, at, index_bits),
                    start, interpret)
            tau, cut = indexed.thresholds(keys, at, cfg.index_topk,
                                          interpret)
        with jax.named_scope("full"):
            out, sets = indexed.latent_indexed_attention(
                query, kv, k_pe, gate, keys, tau, cut, start[:, None],
                geo.nope, geo.value, interpret=interpret)
        chose, reached = indexed.count_sets(
            sets, indexed.latent_tiles(tokens)[0])
        chooses = valid & (at + 1 > cfg.index_topk)
        counts = jnp.stack([
            valid.sum(), chooses.sum(), jnp.where(chooses, at + 1, 0).sum(),
            jnp.where(chooses, chose, 0).sum()]).astype(jnp.int32)
        extras = (sets, counts, jnp.stack(
            [reached, jnp.int32(indexed.latent_causal_tiles(tokens))]),
            indexed.chunk_visits(at, cfg.index_topk))
    with jax.named_scope("mla_proj"):
        out = _proj(out.reshape(rows, q, geo.heads * geo.value), p["o"])
    return (out,) + extras


def experts_ffn(cfg, p, h, token_ok, slots, interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    rows the first grouped product multiplied, the pair rows the held
    experts' buffers held and the T k they would hold unsized). The
    buffers are sized by the share of experts held
    (``ops/moe.pair_capacity``), whatever the router does."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ok = token_ok.reshape(-1)
    pairs = rows * q * cfg.num_experts_per_tok
    capacity = moe.pair_capacity(rows * q, cfg.num_experts_per_tok,
                                 p["up"].shape[0], cfg.router_experts)
    ids, weights = moe.route(
        flat, p["router"], p["b_corr"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, score=cfg.scoring_func,
        renormalise=cfg.norm_topk_prob)
    routed, counts, gmm_rows, *moved = moe.held_experts(
        flat, ids, weights, ok, slots, p["up"], p["down"],
        interpret=interpret, gate=p["gate"], capacity=capacity)
    out = routed + moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                                    p["shared_gate"])
    # without a capacity (a dispatch too small for one) all pairs move
    pair_rows = jnp.stack([moved[0] if moved else jnp.int32(pairs),
                           jnp.int32(pairs)])
    return out.reshape(rows, q, hidden), ids, counts, gmm_rows, pair_rows


def request_choices(cfg: Dots3NoteConfig, chosen, first: int, count: int):
    """What a sample keeps of a dispatch's two kinds of choice for the
    request of ``count`` tokens from flat token ``first``
    (``models/keye_vl2``'s form): the router's experts (expert layers,
    count, k) under ``chosen`` and the *full* layers' sets as the pool's
    bits (full layers, count, keys a tile) under ``key_sets``, with the
    pool position of the request's first token."""
    ids, sets = chosen
    return {"chosen": np.asarray(ids)[:, first:first + count].copy(),
            "key_sets": np.asarray(sets)[:, first:first + count].copy(),
            "first": np.int64(first)}


def forward(cfg: Dots3NoteConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, interpret=False, index_bits=None):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``interpret`` runs the Pallas kernels in interpret
    mode (a device that is no TPU); ``index_bits`` is the
    lower-precision control's (:func:`index_operands`).

    -> (logits (rows, vocab) float32, one line a request; the two kinds
    of choice: the router's (expert layers, tokens, k) int32 and the
    full layers' sets of keys as bits (full layers, tokens, keys a tile)
    uint32; assignments served by each held expert (expert layers, held)
    int32, valid tokens only; the rows the first grouped product
    multiplied (expert layers,); the pair rows the held experts' buffers
    held and tokens x k (expert layers, 2); the ``Sparse:`` line's four
    (full layers, 4); the full layers' kernel's tiles with a chosen key
    and on or under the diagonal (full layers, 2); the banded kernel's
    steps and causal tiles (sliding layers, 2); the pairs the window
    keeps and the causal pairs of valid queries (sliding layers, 2);
    the thresholds' chunk visits and those of a walk from key 0 (full
    layers, 2)).
    """
    rows, q = tokens.shape
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    at = rope.pool_positions(row_start, q).reshape(-1)
    start, valid = indexed.token_table(row_start, row_tokens, q)
    # the queries' rotary tables, once a dispatch a layer type
    turn = {sliding: mla.turn_tables(at, geo.inv_freq(), geo.nope)
            for sliding, geo in ((False, cfg.full), (True, cfg.sliding))}
    window_keys = jnp.stack([
        jnp.where(valid, jnp.minimum(at + 1, cfg.sliding_window_size),
                  0).sum(),
        jnp.where(valid, at + 1, 0).sum()]).astype(jnp.int32)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, served, gmm_rows, pair_rows = [], [], [], []
    key_sets, sparse, index_tiles, window_tiles, kept = [], [], [], [], []
    index_chunks = []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        sliding = cfg.is_sliding(i)
        with jax.named_scope("attn"):
            h = rms_norm(x, p["attn_norm"], cfg.eps, act)
            out, *extras = latent_mixer(
                cfg, i, p, h, at, start, valid, turn[sliding], interpret,
                index_bits)
            x = (x.astype(jnp.float32) + out).astype(act)
            if sliding:
                window_tiles.append(extras[0])
                kept.append(window_keys)
            else:
                key_sets.append(extras[0])
                sparse.append(extras[1])
                index_tiles.append(extras[2])
                index_chunks.append(extras[3])
        with jax.named_scope("experts"):
            h = rms_norm(x, p["ffn_norm"], cfg.eps, act)
            if cfg.is_dense(i):
                out = moe.dense_expert(h, p["up"], p["down"], p["gate"])
            else:
                out, ids, counts, multiplied, moved = experts_ffn(
                    cfg, p, h, token_ok, slots, interpret)
                chosen.append(ids)
                served.append(counts)
                gmm_rows.append(multiplied)
                pair_rows.append(moved)
            x = (x.astype(jnp.float32) + out).astype(act)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, (jnp.stack(chosen), jnp.stack(key_sets)), \
        jnp.stack(served), jnp.stack(gmm_rows), jnp.stack(pair_rows), \
        jnp.stack(sparse), jnp.stack(index_tiles), \
        jnp.stack(window_tiles), jnp.stack(kept), jnp.stack(index_chunks)
