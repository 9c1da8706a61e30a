"""The forward pass of a Keye-VL-2.0 (``KeyeVL2``) language stack over a
packed pool of rows.

Every layer is ``x += attn(RMSNorm(x))``, ``x += experts(RMSNorm(x))``
(plain norm weights, eps ``rms_norm_eps``: Qwen3-MoE's convention, whose
widths the published config repeats number for number). After the last
layer: a final RMSNorm and an untied head, on each request's last valid
token.

*Attention*: grouped queries (``num_attention_heads`` query heads on
``num_key_value_heads`` key-value heads of ``head_dim``, no bias), an
RMSNorm over each head's columns on queries and on keys, rotary over the
whole head (halves rotated, plain frequencies of ``rope_theta``). The
published rotary is multimodal (``mrope_section``: which of a token's
three position components turns which frequency pair); a text prompt's
three components are equal, the index inside the request, and the
rotation is then ``ops/rope.rotate``'s, to the bit.

*The indexer* (``sa_config``; DeepSeek-V3.2-Exp's lightning indexer):
``qI = h W_qI`` (``indexer_num_heads`` of ``indexer_head_dim``), ``kI =
LayerNorm(h W_kI)`` (one head, shared), rotary on the first half of
their columns, ``w = h W_w`` times ``heads ** -0.5 * dim ** -0.5``;
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32 from
bfloat16 operands, over the keys of t's request at or before it. A
query reads all of them while it has ``topk`` or fewer, else the
``topk`` with the largest ``I`` (a tie to the lower key), the same set
for every head (``ops/indexed.py``: scores as sort keys, a threshold a
query, a flash kernel under the sets that holds all the heads of a
(query tile, key tile) pair in a step; exact).

*Experts*: a softmax router over all the model's experts in float32,
the ``num_experts_per_tok`` largest renormalised (``ops/moe.route``),
and the held experts' gated part (``ops/moe.held_experts``); every
expert is held here, so the pair buffers hold all tokens x k pairs (no
``capacity``). No shared expert.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
router's scores, the softmaxes, the norms' statistics, the rotary
angles, the indexer's scores and every product's accumulation are
float32.

The named scopes are ``embed``, ``attn`` (inside it ``attn/select``:
everything that decides the sets, with ``attn/select/index`` the
indexer's three products, its norm, rotary and scores; and
``attn/kernel``: the attention kernel's call alone, from q as its
product wrote it — the head norm, the rotary, the scale and the rounding
of q are the kernel's first lines — to ``o``'s operand, with the sets
as bits beside it), ``experts`` and ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import banded, indexed, moe, rope

#: what ``forward`` returns behind the logits and the two kinds of
#: choice (``models/token_stages.py``); ``sparse``: the four of the
#: ``Sparse:`` line, a (query, layer) once, for the heads share a set;
#: ``index_tiles``: the attention kernel's (query tile, key tile) pairs
#: in which any query chose any key, and those on or under the diagonal;
#: ``index_chunks``: the (query step, key chunk) visits of a count of
#: the thresholds, and those of a walk from key 0 to the diagonal
COUNTERS = ("expert_served", "gmm_rows", "sparse", "index_tiles",
            "index_chunks")
#: the lower-precision control's rounding of the indexer's operands
#: (``scripts/prefill_control.py``): float8 e4m3's exponent and mantissa
#: bits, through ``lax.reduce_precision`` (a pair of conversions the
#: v5e's compiler drops in front of a product, PR 29)
FLOAT8_BITS = (4, 3)


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    router_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "KeyeVL2Config":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here."""
        sa, scaling = config["sa_config"], config["rope_scaling"]
        if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] \
                or not config["norm_topk_prob"] \
                or config["hidden_act"] != "silu" \
                or config["attention_bias"] \
                or config["tie_word_embeddings"] \
                or scaling["rope_type"] != "default" \
                or 2 * sum(scaling["mrope_section"]) != config["head_dim"] \
                or sa["indexer_num_kv_heads"] != 1 \
                or config["num_local_experts"] != config["num_experts"]:
            raise ValueError("decoder_sparse_step, mlp_only_layers, "
                             "norm_topk_prob, hidden_act, attention_bias, "
                             "tie_word_embeddings, rope_scaling, "
                             "sa_config.indexer_num_kv_heads or "
                             "num_local_experts: not the Keye-VL-2.0 this "
                             "network implements")
        return KeyeVL2Config(
            num_hidden_layers=int(config["num_hidden_layers"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            rope_theta=float(config["rope_theta"]),
            indexer_num_heads=int(sa["indexer_num_heads"]),
            indexer_head_dim=int(sa["indexer_head_dim"]),
            topk=int(sa["topk"]),
            router_experts=int(config["num_experts"]),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            eps=float(config["rms_norm_eps"]))

    @property
    def indexer_rotary_dim(self) -> int:
        return self.indexer_head_dim // 2

    def inv_freq(self, dim: int = None) -> np.ndarray:
        """(dim // 2,) float32: the plain frequencies over ``dim``
        columns (a head's, by default)."""
        dim = self.head_dim if dim is None else dim
        return (self.rope_theta ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)) \
            .astype(np.float32)


def held_slots(cfg: KeyeVL2Config, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts: the identity
    where every expert is held."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def layer_norm(x, weight, bias, eps: float):
    """float32 in, float32 out."""
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * weight.astype(jnp.float32) + bias.astype(jnp.float32)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def rotate_front(x, positions, inv_freq):
    """Rotary on the first ``2 len(inv_freq)`` columns of ``x`` (rows,
    Q, ..., dim) float32; the rest pass."""
    dim = 2 * len(inv_freq)
    return jnp.concatenate([rope.rotate(x[..., :dim], positions, inv_freq),
                            x[..., dim:]], axis=-1)


def index_operands(cfg, p, h, positions, index_bits=None):
    """The indexer's (queries (T, heads, dim) and keys (T, dim) in the
    activations' dtype, heads' weights (T, heads) float32 with both
    scales in them). ``index_bits``: the control's (exponent, mantissa)
    bits the operands are rounded through."""
    rows, q, _ = h.shape
    heads, dim = cfg.indexer_num_heads, cfg.indexer_head_dim
    inv_freq = cfg.inv_freq(cfg.indexer_rotary_dim)
    qi = rotate_front(_proj(h, p["index_q"]).reshape(rows, q, heads, dim),
                      positions, inv_freq)
    ki = rotate_front(layer_norm(_proj(h, p["index_k"]), p["index_k_norm"],
                                 p["index_k_bias"], cfg.eps),
                      positions, inv_freq)
    w = _proj(h, p["index_w"]) * (heads ** -0.5 * dim ** -0.5)
    qi, ki = qi.astype(h.dtype), ki.astype(h.dtype)
    if index_bits is not None:
        qi, ki = (jax.lax.reduce_precision(x, *index_bits)
                  for x in (qi, ki))
    tokens = rows * q
    return qi.reshape(tokens, heads, dim), ki.reshape(tokens, dim), \
        w.reshape(tokens, heads)


def recency_keys(start):
    """Sort keys under which a query's best keys are its latest (the
    control that reads the most recent ``topk`` in place of the sets)."""
    at = jnp.arange(start.shape[0], dtype=jnp.int32)
    mine = (at[None, :] <= at[:, None]) & (at[None, :] >= start[:, None])
    return jnp.where(mine, at[None, :], indexed.LOWEST)


def attention_mixer(cfg, p, h, row_start, row_tokens, positions, tables,
                    interpret=False, index_bits=None, select=None):
    """``h`` (rows, Q, hidden), normed; ``tables``: the dispatch's
    ``ops/banded.band_tables`` -> (float32 (rows, Q, hidden);
    the sets as bits (T, T // 32) uint32; int32 (4,): valid queries,
    those that choose (more than ``topk`` keys to read), the keys those
    could read, the keys they chose; int32 (2,): the attention kernel's
    tiles in which any query chose any key, and those on or under the
    diagonal; int32 (2,): ``ops/indexed.chunk_visits``, the thresholds'
    walk). ``select`` is a control's: ``"causal"`` reads every key
    a query may read, ``"recent"`` the latest ``topk``."""
    rows, q, _ = h.shape
    act = h.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    tokens = rows * q
    start, valid = indexed.token_table(row_start, row_tokens, q)
    at = positions.reshape(tokens)
    topk = tokens if select == "causal" else cfg.topk
    with jax.named_scope("select"):
        with jax.named_scope("index"):
            if select == "recent":
                keys = recency_keys(start)
            else:
                keys = indexed.index_keys(
                    *index_operands(cfg, p, h, positions, index_bits),
                    start, interpret)
        tau, cut = indexed.thresholds(keys, at, topk, interpret)
    # q goes to the kernel as its product wrote it: its norm, rotary,
    # scale and rounding are the kernel's first lines. A key tile is
    # read by up to 64 query tiles, so k's stay here, on an eighth of
    # q's columns
    qs = _proj(h, p["q"]).reshape(tokens, hq * dim)
    ks = rms_norm(_proj(h, p["k"]).reshape(rows, q, hk, dim), p["k_norm"],
                  cfg.eps, jnp.float32)
    ks = rope.rotate(ks, positions, cfg.inv_freq()).astype(act) \
        .reshape(tokens, hk * dim)
    vs = _proj(h, p["v"]).astype(act).reshape(tokens, hk * dim)
    with jax.named_scope("kernel"):
        out, sets = indexed.indexed_attention(
            qs, ks, vs, keys, tau, cut, p["q_norm"], tables, cfg.eps,
            interpret)
    chose, reached = indexed.count_sets(
        sets, indexed.attention_tiles(tokens)[0])
    chooses = valid & (at + 1 > cfg.topk)
    counts = jnp.stack([
        valid.sum(), chooses.sum(), jnp.where(chooses, at + 1, 0).sum(),
        jnp.where(chooses, chose, 0).sum()]).astype(jnp.int32)
    tiles = jnp.stack([reached, jnp.int32(indexed.causal_tiles(tokens))])
    return _proj(out.reshape(rows, q, hq * dim), p["o"]), sets, counts, \
        tiles, indexed.chunk_visits(at, topk)


def experts_ffn(cfg, p, h, token_ok, slots, interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    rows the first grouped product multiplied)."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ids, weights = moe.route(flat, p["router"], None,
                             cfg.num_experts_per_tok, 1.0, score="softmax")
    routed, counts, gmm_rows = moe.held_experts(
        flat, ids, weights, token_ok.reshape(-1), slots, p["up"],
        p["down"], interpret=interpret, gate=p["gate"],
        capacity=moe.pair_capacity(rows * q, cfg.num_experts_per_tok,
                                   p["up"].shape[0], cfg.router_experts))
    return routed.reshape(rows, q, hidden), ids, counts, gmm_rows


def request_choices(cfg: KeyeVL2Config, chosen, first: int, count: int):
    """What a sample keeps of a dispatch's two kinds of choice for the
    request of ``count`` tokens from flat token ``first``: the router's
    experts (layers, count, k) under ``chosen`` and the queries' sets as
    the pool's bits (layers, count, keys a tile) under ``key_sets``, with
    the pool position of the request's first token (``ops/indexed``'s
    ``unpack_sets`` reads them)."""
    ids, sets = chosen
    return {"chosen": np.asarray(ids)[:, first:first + count].copy(),
            "key_sets": np.asarray(sets)[:, first:first + count].copy(),
            "first": np.int64(first)}


def forward(cfg: KeyeVL2Config, params, slots, tokens, row_tokens,
            row_start, last_idx, *, interpret=False, index_bits=None,
            select=None):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``interpret`` runs the Pallas kernels in interpret
    mode (a device that is no TPU); ``index_bits`` and ``select`` are
    the lower-precision and the attention controls'
    (:func:`index_operands`, :func:`attention_mixer`).

    -> (logits (rows, vocab) float32, one line a request; the two kinds
    of choice: the router's (layers, tokens, k) int32 and the queries'
    sets of keys as bits (layers, tokens, keys a tile) uint32;
    assignments served by each expert (layers, experts) int32, valid
    tokens only; the rows the first grouped product multiplied
    (layers,) int32; the ``Sparse:`` line's four (layers, 4) int32; the
    attention kernel's tiles with a chosen key and on or under the
    diagonal (layers, 2) int32; the thresholds' chunk visits and those
    of a walk from key 0 (layers, 2) int32).
    """
    rows, q = tokens.shape
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    positions = rope.pool_positions(row_start, q)
    # what the attention kernel turns q by, the same in every layer
    tables = banded.band_tables(row_start, q, cfg.inv_freq())
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, key_sets, served, gmm_rows, sparse, tiles, chunks = \
        [], [], [], [], [], [], []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        with jax.named_scope("attn"):
            h = rms_norm(x, p["attn_norm"], cfg.eps, act)
            out, sets, counts, ran, walked = attention_mixer(
                cfg, p, h, row_start, row_tokens, positions, tables,
                interpret, index_bits, select)
            x = (x.astype(jnp.float32) + out).astype(act)
            key_sets.append(sets)
            sparse.append(counts)
            tiles.append(ran)
            chunks.append(walked)
        with jax.named_scope("experts"):
            h = rms_norm(x, p["ffn_norm"], cfg.eps, act)
            out, ids, counts, multiplied = experts_ffn(
                cfg, p, h, token_ok, slots, interpret)
            x = (x.astype(jnp.float32) + out).astype(act)
            chosen.append(ids)
            served.append(counts)
            gmm_rows.append(multiplied)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, (jnp.stack(chosen), jnp.stack(key_sets)), \
        jnp.stack(served), jnp.stack(gmm_rows), jnp.stack(sparse), \
        jnp.stack(tiles), jnp.stack(chunks)
