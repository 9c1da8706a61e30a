"""The forward pass of a K-EXAONE (``exaone_moe``) stack over a packed
pool of rows.

Every layer is ``x += RMSNorm(attn(x))``, ``x += RMSNorm(ffn(x))``: the
norms stand *behind* the mixer and the feed-forward (EXAONE 4.0's
convention, arXiv:2507.11407). After the last layer: a final RMSNorm and
an untied head, on each request's last valid token.

*Attention*, every layer: grouped queries (``num_attention_heads``
query heads on ``num_key_value_heads`` key-value heads of
``head_dim``), an RMSNorm over each head's columns on queries and on
keys, then one of two kinds by ``layer_types``. A *sliding* layer
rotates queries and keys (``ops/rope.py``: halves rotated, plain
frequencies, positions inside the request) and a query reads the
``sliding_window`` keys of its request that end with its own; a *full*
layer reads every key of its request at or before the query and has no
rotary at all ("global NoPE"). A full layer runs through the pool's
flash kernel (``ops/segattn.py``); a sliding layer has a kernel of its
own (``ops/banded.py``) that reads q, k and v as their products wrote
them, norms and turns them as its first lines, and writes ``o``'s
operand: a band of two key blocks a step, no table.

*Feed-forward*: a SiLU-gated MLP where ``mlp_layer_types`` says
``dense`` (the first ``first_k_dense_replace`` layers), else sparse
experts under DeepSeek-V3's rule: ``s = sigmoid(x W_r)``, the
``num_experts_per_tok`` largest of ``s + b`` (a correction bias, for
the choice alone; ``n_group`` 1: no group limit), weights
``routed_scaling_factor * s_i / sum(s_chosen)`` (``ops/moe.route``),
the held experts' gated part (``ops/moe.held_experts``), and
``num_shared_experts`` of the same form every token visits, as one
gated MLP of their widths together.

The multi-token prediction module behind the published stack is not
here: a prefill that returns one position's logits never runs it.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
router's scores, the softmax, the norms' statistics, the rotary angles
and every product's accumulation are float32.

The named scopes are ``embed``, ``attn`` (with ``attn/window`` or
``attn/full`` inside it: a layer's mixer by its kind, the kernel's call
with its table under ``.../kernel``), ``experts`` (a layer's
feed-forward, the dense first layer's too), ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import banded, moe, segattn

#: what ``forward`` returns behind the logits and the router's choices
#: (``models/token_stages.py``): ``attn_tiles`` counts the full layers,
#: ``window_tiles`` the sliding ones' steps, at their own tile sizes;
#: ``pair_rows`` the pair rows ``held_experts``' buffers held and the
#: tokens x k a layer has
COUNTERS = ("expert_served", "group_tokens", "attn_tiles", "window_tiles",
            "pair_rows")

SLIDING, FULL = "sliding_attention", "full_attention"
#: the grouped product's (rows, contraction, columns) a tile for an
#: expert's second matrix, K 2,048 -> N 6,144: ``ops/moe.py``'s wide
#: (512, 2048, 1024) run out of VMEM there (its (128, 2048, 1024), PR
#: 44's, fit and were not read against these). Read on the v5e, 131,072
#: pair rows of which 16,384 in 16 groups (my chip runs, PR 42): (256,
#: 2048, 1024) 3.66 ms; (512, 2048, 768) 4.01; (512, 2048, 512) 4.08;
#: (512, 1024, 1024) 4.38; (512, 512, 1024) 4.73; (256, 1024, 1024)
#: 4.77; (512, 1024, 512) 5.05; (1024, 1024, 512) 5.55. The first
#: products, K 6,144 -> N 2,048, keep the module's (512, 1024, 1024):
#: 4.17 ms, and a longer contraction or wider columns do not fit
_DOWN_TILING = (256, 2048, 1024)


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    layer_types: Tuple[str, ...]    # of the layers held
    mlp_layer_types: Tuple[str, ...]
    sliding_window: int
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    intermediate_size: int
    moe_intermediate_size: int
    num_shared_experts: int
    router_experts: int
    n_group: int
    topk_group: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    scoring_func: str
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "ExaoneMoeConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here (the first so many
        of ``layer_types`` and ``mlp_layer_types``) and
        ``published.num_experts`` the width of the router."""
        published = config.get("published", {})
        layers = int(config["num_hidden_layers"])
        kinds = tuple(config["layer_types"][:layers])
        ffns = tuple(config["mlp_layer_types"][:layers])
        dense = int(config["first_k_dense_replace"])
        if config["hidden_act"] != "silu" or config["tie_word_embeddings"] \
                or config["rope_parameters"]["rope_type"] != "default" \
                or len(kinds) != layers or set(kinds) - {SLIDING, FULL} \
                or ffns != tuple("dense" if i < dense else "sparse"
                                 for i in range(layers)):
            raise ValueError("hidden_act, tie_word_embeddings, "
                             "rope_parameters.rope_type, layer_types or "
                             "mlp_layer_types: not the K-EXAONE this "
                             "network implements")
        return ExaoneMoeConfig(
            num_hidden_layers=layers, layer_types=kinds,
            mlp_layer_types=ffns,
            sliding_window=int(config["sliding_window"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_shared_experts=int(config["num_shared_experts"]),
            router_experts=int(published.get("num_experts",
                                             config["num_experts"])),
            n_group=int(config["n_group"]),
            topk_group=int(config["topk_group"]),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            scoring_func=str(config["scoring_func"]),
            eps=float(config["rms_norm_eps"]))

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"

    @property
    def sliding_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def num_expert_layers(self) -> int:
        return self.mlp_layer_types.count("sparse")

    @property
    def shared_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    def inv_freq(self) -> np.ndarray:
        """(head_dim // 2,) float32: the plain frequencies."""
        dim = self.head_dim
        return (self.rope_theta ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)) \
            .astype(np.float32)


def held_slots(cfg: ExaoneMoeConfig, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def attention_mixer(cfg, p, x, row_start, band=None, interpret=False):
    """``x`` (rows, Q, hidden), the stream as it is; ``band``: a sliding
    layer's ``ops/banded.band_tables``, None for a full layer ->
    (float32 (rows, Q, hidden) before the norm behind it, the kernel's
    tiles: run, and on or under the diagonal)."""
    rows, q, _ = x.shape
    act = x.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    tokens = rows * q
    if band is not None:
        # the kernel reads the three products' results as they are, and
        # writes the fourth's operand
        operands = (_proj(x, p["q"]).reshape(tokens, hq * dim),
                    _proj(x, p["k"]).reshape(tokens, hk * dim),
                    _proj(x, p["v"]).astype(act).reshape(tokens, hk * dim))
        with jax.named_scope("kernel"):
            out, tiles = banded.banded_attention(
                *operands, p["q_norm"], p["k_norm"], band,
                cfg.sliding_window, cfg.eps, interpret)
        return _proj(out.reshape(rows, q, hq * dim), p["o"]), tiles
    qs = rms_norm(_proj(x, p["q"]).reshape(rows, q, hq, dim), p["q_norm"],
                  cfg.eps, jnp.float32)
    ks = rms_norm(_proj(x, p["k"]).reshape(rows, q, hk, dim), p["k_norm"],
                  cfg.eps, jnp.float32)
    # the scores' scale goes onto the float32 queries, before their one
    # rounding to the activations' dtype
    qs = (qs * dim ** -0.5).astype(act)
    vs = _proj(x, p["v"]).astype(act).reshape(rows, q, hk, dim)
    operands = (
        segattn.heads_first(qs.reshape(tokens, hk, hq // hk, dim)),
        segattn.heads_first(ks.astype(act).reshape(tokens, hk, dim)),
        segattn.heads_first(vs.reshape(tokens, hk, dim)))
    with jax.named_scope("kernel"):
        out, tiles = segattn.heads_first_attention(
            *operands, row_start, q, interpret)
    out = jnp.moveaxis(out, -2, 0)[:tokens, ..., :dim] \
        .reshape(rows, q, hq * dim)
    return _proj(out, p["o"]), tiles


def experts_ffn(cfg, p, h, token_ok, slots, interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    valid tokens that sent the held experts anything, the pair rows the
    held experts' buffers held and the T k they would hold unsized).

    The buffers are sized by the share of experts held
    (``ops/moe.pair_capacity``: 32,768 of 131,072 pair rows at 128 rows
    of 128 tokens, 16 of 128 experts), whatever the router does: pairs
    over the size take further passes."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ok = token_ok.reshape(-1)
    pairs = rows * q * cfg.num_experts_per_tok
    capacity = moe.pair_capacity(rows * q, cfg.num_experts_per_tok,
                                 p["up"].shape[0], cfg.router_experts)
    ids, weights = moe.route(
        flat, p["router"], p["b_corr"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, score=cfg.scoring_func,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        renormalise=cfg.norm_topk_prob)
    # the rows the first product multiplied are not counted here: a
    # sixth counter a dispatch is one more fetch in front of the next
    # program, and this stack's tiles are the wide ones in every bucket
    routed, counts, _, *moved = moe.held_experts(
        flat, ids, weights, ok, slots, p["up"], p["down"],
        interpret=interpret, gate=p["gate"],
        down_tiling=_down_tiling(cfg, capacity or pairs), capacity=capacity)
    out = routed + moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                                    p["shared_gate"])
    sent = ((slots[ids] >= 0).any(-1) & ok).sum().astype(jnp.int32)
    # without a capacity (a dispatch too small for one) all pairs move
    pair_rows = jnp.stack([moved[0] if moved else jnp.int32(pairs),
                           jnp.int32(pairs)])
    return out.reshape(rows, q, hidden), ids, counts, sent, pair_rows


def _down_tiling(cfg, pairs: int):
    """``_DOWN_TILING`` where its sizes divide the product's (the
    published widths and whole rows), else None: ``ops/moe.py``'s own
    (the tests' toy widths)."""
    sizes = (pairs, cfg.moe_intermediate_size, cfg.hidden_size)
    return None if any(size % tile for size, tile
                       in zip(sizes, _DOWN_TILING)) else _DOWN_TILING


def forward(cfg: ExaoneMoeConfig, params, slots, tokens, row_tokens,
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
    valid tokens of each expert layer that sent the held experts
    anything (expert layers,) int32; the flash kernel's tiles in the
    full layers (full layers, 2) int32: those this dispatch's block
    table let run, and those on or under the diagonal; the banded
    kernel's steps in the sliding layers and the tiles of their size on
    or under the diagonal (sliding layers, 2); the pair
    rows the held experts' buffers held and tokens x k (expert layers,
    2)).
    """
    rows, q = tokens.shape
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    # the sliding layers' rotary tables and requests' first tokens, once
    band = banded.band_tables(row_start, q, cfg.inv_freq())
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, served, sent, full_tiles, window_tiles = [], [], [], [], []
    pair_rows = []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        sliding = cfg.is_sliding(i)
        with jax.named_scope("attn"):
            with jax.named_scope("window" if sliding else "full"):
                out, ran = attention_mixer(cfg, p, x, row_start,
                                           band if sliding else None,
                                           interpret)
            out = rms_norm(out, p["attn_norm"], cfg.eps, jnp.float32)
            x = (x.astype(jnp.float32) + out).astype(act)
            (window_tiles if sliding else full_tiles).append(ran)
        with jax.named_scope("experts"):
            if cfg.is_dense(i):
                out = moe.dense_expert(x, p["up"], p["down"], p["gate"])
            else:
                out, ids, counts, tokens_sent, moved = experts_ffn(
                    cfg, p, x, token_ok, slots, interpret)
                chosen.append(ids)
                served.append(counts)
                sent.append(tokens_sent)
                pair_rows.append(moved)
            out = rms_norm(out, p["ffn_norm"], cfg.eps, jnp.float32)
            x = (x.astype(jnp.float32) + out).astype(act)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, jnp.stack(chosen), jnp.stack(served), jnp.stack(sent), \
        jnp.stack(full_tiles), jnp.stack(window_tiles), \
        jnp.stack(pair_rows)
