"""The forward pass of a Qwen3-Next stack over a packed pool of rows.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += experts(RMSNorm(x))``;
layer ``i`` mixes by gated softmax attention where ``(i + 1) %
full_attention_interval == 0`` and by Gated DeltaNet otherwise. After
the last layer: a final RMSNorm and an untied head, on each request's
last valid token. The layers', the final and the query-key norms store
their weights zero-centred (``x_hat * (1 + w)``); the DeltaNet's output
norm stores them plain.

*Gated DeltaNet*: one product gives ``[q | k | v | z]`` (``Hk`` key
heads of ``Dk`` twice, ``Hv`` value heads of ``Dv`` twice), one gives
``[b | a]`` (``Hv`` each); a causal depthwise convolution over ``[q | k
| v]`` with zero history at a request's first token
(``ops/ssd.segment_conv1d``), SiLU; ``beta = sigmoid(b)``, ``log alpha
= -exp(A_log) softplus(a + dt_bias)``; then one Pallas kernel a layer
(``ops/deltanet.py``) from the convolution's result to the output
product's operand: ``q``, ``k`` L2-normalised a head, ``q`` scaled by
``Dk ** -0.5``; the gated delta rule (its grid (head group, row) with
the rows innermost and in order; a grid step holds one row's scores,
decay triangle, solve and updates and the head group's states in VMEM
and carries the states in their sequential form, ``S <- exp(g_Q) S +
(exp(g_Q - g) k)^T v_new``, zeroed where a request opens), value head h
reading key head ``h // (Hv // Hk)``; an RMSNorm over each head's
``Dv`` columns times ``silu(z)``. Then the output product.

*Gated attention*: one product gives every head's ``[query | gate]``,
one each keys and values; an RMSNorm over each head's columns on
queries and on keys; rotary on the first ``partial_rotary_factor`` of a
head's columns (halves rotated, positions inside the request:
``ops/rope.py``), the rest pass; causal softmax inside the request
through the pool's flash kernel (``ops/segattn.py``); the result times
``sigmoid(gate)``; the output product.

*Experts*: a softmax router over all the model's experts, the
``num_experts_per_tok`` largest renormalised (``ops/moe.route``), the
held experts' gated part (``ops/moe.held_experts``), and a shared
expert of the same form times ``sigmoid(x w_s)``, a gate of one column.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
router's scores, the softmaxes, the norms' statistics, the rule's
decays, steps and states, the rotary angles and every product's
accumulation are float32.

The named scopes are ``embed``, ``deltanet`` (a DeltaNet layer's mixer
whole, the rule alone under ``deltanet/rule``: the kernel's custom call
and the running sums in front of it), ``attn``, ``experts`` and
``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import deltanet, moe, rope, segattn, ssd

#: what ``forward`` returns behind the logits and the router's choices
#: (``models/token_stages.py``); ``gmm_rows``: the rows the first
#: grouped product multiplied for the pairs the held experts served
COUNTERS = ("expert_served", "group_tokens", "attn_tiles", "gmm_rows")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    full_attention_interval: int
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    router_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "Qwen3NextConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here and
        ``published.num_experts`` the width of the router."""
        published = config.get("published", {})
        layers = int(config["num_hidden_layers"])
        if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] \
                or not config["norm_topk_prob"] \
                or config["rope_scaling"] is not None \
                or config["hidden_act"] != "silu":
            raise ValueError("decoder_sparse_step, mlp_only_layers, "
                             "norm_topk_prob, rope_scaling or hidden_act: "
                             "not the Qwen3-Next this network implements")
        return Qwen3NextConfig(
            num_hidden_layers=layers,
            full_attention_interval=int(config["full_attention_interval"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            partial_rotary_factor=float(config["partial_rotary_factor"]),
            rope_theta=float(config["rope_theta"]),
            linear_num_key_heads=int(config["linear_num_key_heads"]),
            linear_num_value_heads=int(config["linear_num_value_heads"]),
            linear_key_head_dim=int(config["linear_key_head_dim"]),
            linear_value_head_dim=int(config["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(config["linear_conv_kernel_dim"]),
            router_experts=int(published.get("num_experts",
                                             config["num_experts"])),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(
                config["shared_expert_intermediate_size"]),
            eps=float(config["rms_norm_eps"]))

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def attention_layers(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def deltanet_layers(self) -> int:
        return self.num_hidden_layers - self.attention_layers

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def inv_freq(self) -> np.ndarray:
        """(rotary_dim // 2,) float32: the plain frequencies."""
        dim = self.rotary_dim
        return (self.rope_theta ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)) \
            .astype(np.float32)


def held_slots(cfg: Qwen3NextConfig, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    """The weight is stored as its distance from one."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * (1.0 + weight.astype(jnp.float32))).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def deltanet_mixer(cfg, p, h, row_first, state_dtype=jnp.float32,
                   interpret=False):
    """``h`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden)."""
    act = h.dtype
    hv = cfg.linear_num_value_heads
    # in_qkvz's columns as two products, qkv and z: of one float32 result
    # of 12,288 columns the convolution's kernel would read a rounded
    # slice, a pass of its own over them (PERF.md section 6, PR 48); its
    # own product rounds qkv as it writes
    qkv = _proj(h, p["in_qkvz"][:, :cfg.conv_dim]).astype(act)
    z = _proj(h, p["in_qkvz"][:, cfg.conv_dim:])
    ba = _proj(h, p["in_ba"])
    # no bias; the rule's kernel normalises q and k in float32 behind the
    # SiLU, so this kernel writes them in float32; v is rounded at once
    qk, vs = ssd.segment_conv1d(
        qkv, p["conv_w"], None, row_first, activation="silu",
        out_dtype=(jnp.float32, act), interpret=interpret,
        split=(2 * cfg.key_dim, cfg.value_dim))
    beta = jax.nn.sigmoid(ba[..., :hv])
    log_alpha = -jnp.exp(p["a_log"].astype(jnp.float32)) \
        * jax.nn.softplus(ba[..., hv:] + p["dt_bias"].astype(jnp.float32))
    # the heads' L2 norms in front, the head norm (its weight as stored)
    # and the gate behind are the kernel's: it reads the arrays above as
    # they lie and writes ``o``'s operand
    with jax.named_scope("rule"):
        out = deltanet.gated_delta_rule(
            qk, vs, log_alpha, beta, z, p["o_norm"], row_first,
            key_heads=cfg.linear_num_key_heads, eps=cfg.eps,
            activation="silu", state_dtype=state_dtype, interpret=interpret)
    return _proj(out, p["o"])


def rotate_front(cfg, x, positions):
    """Rotary on the first ``rotary_dim`` columns of every head of ``x``
    (rows, Q, heads, head_dim) float32; the rest pass untouched."""
    dim = cfg.rotary_dim
    return jnp.concatenate([
        rope.rotate(x[..., :dim], positions, cfg.inv_freq()),
        x[..., dim:]], axis=-1)


def attention_mixer(cfg, p, h, row_start, positions, interpret=False):
    """-> (float32 (rows, Q, hidden), the flash kernel's tiles: run, and
    on or under the diagonal)."""
    rows, q, _ = h.shape
    act = h.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    query_gate = _proj(h, p["q"]).reshape(rows, q, hq, 2 * dim)
    gate = query_gate[..., dim:].reshape(rows, q, hq * dim)
    qs = rms_norm(query_gate[..., :dim], p["q_norm"], cfg.eps, jnp.float32)
    ks = rms_norm(_proj(h, p["k"]).reshape(rows, q, hk, dim), p["k_norm"],
                  cfg.eps, jnp.float32)
    # the scores' scale goes onto the float32 queries, before their one
    # rounding to the activations' dtype
    qs = (rotate_front(cfg, qs, positions) * dim ** -0.5).astype(act)
    ks = rotate_front(cfg, ks, positions).astype(act)
    vs = _proj(h, p["v"]).astype(act).reshape(rows, q, hk, dim)
    out, tiles = segattn.packed_attention(qs, ks, vs, row_start, interpret)
    out = out.reshape(rows, q, hq * dim).astype(jnp.float32) \
        * jax.nn.sigmoid(gate)
    return _proj(out.astype(act), p["o"]), tiles


def experts_ffn(cfg, p, h, token_ok, slots, interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    valid tokens that sent the held experts anything, the rows the
    first grouped product multiplied)."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ok = token_ok.reshape(-1)
    ids, weights = moe.route(flat, p["router"], None,
                             cfg.num_experts_per_tok, 1.0, score="softmax")
    routed, counts, gmm_rows = moe.held_experts(
        flat, ids, weights, ok, slots, p["up"], p["down"],
        interpret=interpret, gate=p["gate"])
    shared = moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                              p["shared_gate"])
    out = routed + jax.nn.sigmoid(_proj(flat, p["shared_w"])) * shared
    sent = ((slots[ids] >= 0).any(-1) & ok).sum().astype(jnp.int32)
    return out.reshape(rows, q, hidden), ids, counts, sent, gmm_rows


def forward(cfg: Qwen3NextConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``state_dtype`` is the lower-precision control's (the
    rule's states between rows); ``interpret`` runs the Pallas kernels
    in interpret mode (a device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the router's
    choices (layers, tokens, k) int32; assignments served by each held
    expert (layers, held) int32, valid tokens only; valid tokens of each
    layer that sent the held experts anything (layers,) int32; the flash
    kernel's tiles (attention layers, 2) int32: those this dispatch's
    block table let run, and those on or under the diagonal; the rows
    the first grouped product multiplied (layers,) int32).
    """
    rows, q = tokens.shape
    row_first = row_start == jnp.arange(rows)
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    positions = rope.pool_positions(row_start, q)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, served, sent, tiles, gmm_rows = [], [], [], [], []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        if cfg.is_attention(i):
            with jax.named_scope("attn"):
                h = rms_norm(x, p["mixer_norm"], cfg.eps, act)
                out, ran = attention_mixer(cfg, p, h, row_start, positions,
                                           interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
                tiles.append(ran)
        else:
            with jax.named_scope("deltanet"):
                h = rms_norm(x, p["mixer_norm"], cfg.eps, act)
                out = deltanet_mixer(cfg, p, h, row_first, state_dtype,
                                     interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
        with jax.named_scope("experts"):
            h = rms_norm(x, p["ffn_norm"], cfg.eps, act)
            out, ids, counts, tokens_sent, multiplied = experts_ffn(
                cfg, p, h, token_ok, slots, interpret)
            x = (x.astype(jnp.float32) + out).astype(act)
            chosen.append(ids)
            served.append(counts)
            sent.append(tokens_sent)
            gmm_rows.append(multiplied)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, jnp.stack(chosen), jnp.stack(served), jnp.stack(sent), \
        jnp.stack(tiles), jnp.stack(gmm_rows)
