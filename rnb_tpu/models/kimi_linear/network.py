"""The forward pass of a Kimi-Linear stack over a packed pool of rows.

Every layer is ``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``;
``linear_attn_config`` numbers the layers from 1 and says which mix by
Kimi Delta Attention (``kda_layers``) and which by latent attention
(``full_attn_layers``); the feed-forward is a dense gated MLP in the
first ``first_k_dense_replace`` layers and the expert block in the
rest. After the last layer: a final RMSNorm and an untied head, on each
request's last valid token. The norms store their weights plain.

*Kimi Delta Attention* (Kimi Linear, arXiv:2510.26692): one product
gives ``[q | k | v]`` (``H`` heads of ``D`` each), a causal depthwise
convolution over each with zero history at a request's first token
(``ops/ssd.segment_conv1d``: the three side by side), SiLU; **the gate
is a vector**: ``log alpha = -exp(A_log[head]) softplus(f_b (f_a h) +
dt_bias)``, one decay a key channel, through a low-rank pair; ``beta =
sigmoid(b h)`` one a head; then one Pallas kernel a layer
(``ops/deltanet.channel_gated_delta_rule``) from the convolution's
result to the output product's operand: ``q``, ``k`` L2-normalised a
head, ``q`` scaled by ``D ** -0.5``; the rule (the kernel sums ``log
alpha`` down a row itself and keeps every exponent at or under zero);
an RMSNorm over each head's ``D`` columns times ``sigmoid(g_b (g_a
h))``, a second low-rank pair. Then the output product.

*Latent attention* without positions (``mla_use_nope``): queries
straight from the hidden state (no query latent), ``[c | k_r] = kv_a
h``, ``[k_nope | v] = kv_b RMSNorm(c)``; a head's key is ``[k_nope |
k_r]``, ``k_r`` shared by all heads; **nothing is rotated** (the KDA
layers carry position); causal softmax inside the request at scale
``(nope + rope) ** -0.5`` through the pool's flash kernel in its
heads-first form (``ops/segattn.heads_first_attention``: keys of 192
in 256 lanes, values of 128). The queries' weight is stored heads-first
and whole lanes wide (``checkpoint.py``), so one batched product writes
the kernel's operand as it reads it; ``ops/mla.queries``, which turns
rotary columns, is DeepSeek-V2's and not called here.

*Experts*: ``sigmoid`` scores over all the model's experts in float32,
the ``num_experts_per_token`` largest of ``s + b`` (a correction bias
for the choice alone), the chosen scores over their sum times
``routed_scaling_factor`` (``ops/moe.route``), the held experts' gated
part (``ops/moe.held_experts``), and one shared expert of the same
form, ungated.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
router's scores, the softmax, the norms' statistics, the rule's decays,
steps, solve and states and every product's accumulation are float32.

The named scopes are ``embed``, ``deltanet`` (a KDA mixer whole;
``deltanet/conv`` the convolution, ``deltanet/gate`` the low-rank pair,
the softplus and ``-exp(A_log)`` that make ``log alpha``,
``deltanet/rule`` the kernel), ``attn``, ``experts`` (a layer's
feed-forward, the dense first layer's too) and ``head``: the scalar
rule's scope names on purpose, one layer of PERF.md section 3.

``forward``'s keywords beyond the siblings' are the lower-precision
control's arms (``scripts/prefill_control.py``): ``gate="scalar"`` puts
a head's mean ``log alpha`` in the place of its 128 channels' (Gated
DeltaNet under Kimi's name) and ``rotary=True`` turns ``k_r`` and the
queries' last columns by their positions inside the request.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import deltanet, moe, rope, segattn, ssd

#: what ``forward`` returns behind the logits and the router's choices
#: (``models/token_stages.py``); ``gmm_rows``: the rows the first
#: grouped product multiplied for the pairs the held experts served
COUNTERS = ("expert_served", "group_tokens", "attn_tiles", "gmm_rows")

_LANES = 128


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The sizes of one stack, under the published config's names."""

    num_hidden_layers: int          # held here: the model's first so many
    kda_layers: Tuple[int, ...]     # numbered from 1, as published
    full_attn_layers: Tuple[int, ...]
    first_k_dense_replace: int
    hidden_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    kda_num_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float               # read by the rotary control arm alone
    intermediate_size: int
    moe_intermediate_size: int
    num_shared_experts: int
    router_experts: int
    num_experts_per_token: int
    routed_scaling_factor: float
    eps: float

    @staticmethod
    def from_published(config: Mapping) -> "KimiLinearConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here and
        ``published.num_experts`` the width of the router."""
        published = config.get("published", {})
        linear = config["linear_attn_config"]
        layers = int(config["num_hidden_layers"])
        if not config["mla_use_nope"] or config["q_lora_rank"] is not None \
                or config["moe_layer_freq"] != 1 \
                or config["moe_router_activation_func"] != "sigmoid" \
                or not config["moe_renormalize"] \
                or config["num_expert_group"] != 1 \
                or config["topk_group"] != 1 \
                or config["hidden_act"] != "silu":
            raise ValueError(
                "mla_use_nope, q_lora_rank, moe_layer_freq, "
                "moe_router_activation_func, moe_renormalize, "
                "num_expert_group, topk_group or hidden_act: not the "
                "Kimi-Linear this network implements")
        kda = tuple(int(i) for i in linear["kda_layers"])
        full = tuple(int(i) for i in linear["full_attn_layers"])
        if sorted(kda + full) != list(range(1, layers + 1)):
            raise ValueError("kda_layers %r and full_attn_layers %r do not "
                             "number layers 1 to %d once each"
                             % (kda, full, layers))
        return KimiLinearConfig(
            num_hidden_layers=layers, kda_layers=kda, full_attn_layers=full,
            first_k_dense_replace=int(config["first_k_dense_replace"]),
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            kda_num_heads=int(linear["num_heads"]),
            kda_head_dim=int(linear["head_dim"]),
            short_conv_kernel_size=int(linear["short_conv_kernel_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            rope_theta=float(config["rope_theta"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_shared_experts=int(config["num_shared_experts"]),
            router_experts=int(published.get("num_experts",
                                             config["num_experts"])),
            num_experts_per_token=int(config["num_experts_per_token"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            eps=float(config["rms_norm_eps"]))

    def is_attention(self, layer: int) -> bool:
        """``layer`` counts from 0, the published lists from 1."""
        return layer + 1 in self.full_attn_layers

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def num_experts_per_tok(self) -> int:
        """The published ``num_experts_per_token`` under the name the
        shared stages read (``models/token_stages.py``)."""
        return self.num_experts_per_token

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def kda_dim(self) -> int:
        """The columns of all heads' ``q`` (or ``k``, or ``v``)."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def query_lanes(self) -> int:
        """A head's query and key columns as the flash kernel reads
        them: whole lanes."""
        return -(-self.qk_head_dim // _LANES) * _LANES

    @property
    def shared_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    def inv_freq(self) -> np.ndarray:
        """(qk_rope_head_dim // 2,) float32: the plain frequencies, for
        the rotary control arm."""
        dim = self.qk_rope_head_dim
        return (self.rope_theta ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)) \
            .astype(np.float32)


def held_slots(cfg: KimiLinearConfig, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _low_rank(h, down, up):
    """``up (down h)``, the inner width rounded to the stream's dtype."""
    return _proj(_proj(h, down).astype(h.dtype), up)


def kda_mixer(cfg, p, h, row_first, state_dtype=jnp.float32,
              gate="channel", interpret=False):
    """``h`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden)."""
    rows, q, _ = h.shape
    act = h.dtype
    heads, dim, width = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_dim
    qkv = _proj(h, p["in_qkv"]).astype(act)
    # no bias; the rule's kernel normalises q and k in float32 behind the
    # SiLU, so this kernel writes them in float32; v is rounded at once
    qk, vs = ssd.segment_conv1d(
        qkv, p["conv_w"], None, row_first, activation="silu",
        out_dtype=(jnp.float32, act), interpret=interpret,
        split=(2 * width, width))
    beta = jax.nn.sigmoid(_proj(h, p["in_b"]))
    with jax.named_scope("gate"):
        step = jax.nn.softplus(_low_rank(h, p["f_a"], p["f_b"])
                               + p["dt_bias"].astype(jnp.float32))
        # a head's rate over its channels: no array of a head axis
        log_alpha = jnp.repeat(-jnp.exp(p["a_log"].astype(jnp.float32)),
                               dim) * step
        if gate == "scalar":
            log_alpha = jnp.broadcast_to(
                log_alpha.reshape(rows, q, heads, dim)
                .mean(-1, keepdims=True), (rows, q, heads, dim)) \
                .reshape(rows, q, width)
    z = _low_rank(h, p["g_a"], p["g_b"])
    # the heads' L2 norms in front, the head norm and the gate behind are
    # the kernel's: it reads the arrays above as they lie and writes
    # ``o``'s operand
    with jax.named_scope("rule"):
        out = deltanet.channel_gated_delta_rule(
            qk, vs, log_alpha, beta, z, p["o_norm"], row_first, eps=cfg.eps,
            activation="sigmoid", state_dtype=state_dtype,
            interpret=interpret)
    return _proj(out, p["o"])


def latent_attention(cfg, p, h, row_start, positions, rotary=False,
                     interpret=False):
    """``h`` (rows, Q, hidden), normed -> (float32 (rows, Q, hidden),
    the flash kernel's tiles: run, and on or under the diagonal).

    The queries' product writes the kernel's operand itself: heads
    first, whole lanes (the weight's pad columns are zeros), the scores'
    scale on the float32 queries before their one rounding; ``o``
    contracts over (head, value column) from the kernel's result as it
    lies. Keys and values are laid out behind their product
    (``segattn.heads_first``)."""
    rows, q, hidden = h.shape
    act = h.dtype
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, value = cfg.qk_nope_head_dim, cfg.v_head_dim
    tokens = rows * q
    # a pool narrower than the kernel's blocks (the tests' sizes) gets
    # its pad tokens here, where a token is still one hidden row
    flat = jnp.pad(h.reshape(tokens, hidden),
                   ((0, segattn.pool_tokens(tokens) - tokens), (0, 0)))
    pool = flat.shape[0]
    query = jnp.einsum("td,hdc->htc", flat, p["q"],
                       preferred_element_type=jnp.float32)
    down = _proj(flat, p["kv_a"])
    k_r = down[:, rank:]
    if rotary:
        # the control arm: positions inside the request, halves rotated
        at = jnp.pad(positions.reshape(tokens), (0, pool - tokens))
        k_r = rope.rotate(k_r[None], at[None], cfg.inv_freq())[0]
        query = jnp.concatenate([
            query[..., :nope],
            rope.rotate(query[..., nope:cfg.qk_head_dim],
                        jnp.broadcast_to(at, (heads, pool)),
                        cfg.inv_freq()),
            query[..., cfg.qk_head_dim:]], axis=-1)
    query = (query * cfg.qk_head_dim ** -0.5).astype(act)
    c_kv = rms_norm(down[:, :rank], p["kv_a_norm"], cfg.eps, act)
    kv = _proj(c_kv, p["kv_b"]).astype(act).reshape(pool, heads,
                                                    nope + value)
    key = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_r.astype(act)[:, None, :],
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
        flat, p["router"], p["b_corr"], cfg.num_experts_per_token,
        cfg.routed_scaling_factor, score="sigmoid")
    routed, counts, gmm_rows = moe.held_experts(
        flat, ids, weights, ok, slots, p["up"], p["down"],
        interpret=interpret, gate=p["gate"])
    out = routed + moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                                    p["shared_gate"])
    sent = ((slots[ids] >= 0).any(-1) & ok).sum().astype(jnp.int32)
    return out.reshape(rows, q, hidden), ids, counts, sent, gmm_rows


def forward(cfg: KimiLinearConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            gate="channel", rotary=False, interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``state_dtype`` (the rule's states between rows),
    ``gate`` and ``rotary`` are the lower-precision control's arms;
    ``interpret`` runs the Pallas kernels in interpret mode (a device
    that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the router's
    choices (expert layers, tokens, k) int32; assignments served by
    each held expert (expert layers, held) int32, valid tokens only;
    valid tokens of each expert layer that sent the held experts
    anything (expert layers,) int32; the flash kernel's tiles
    (attention layers, 2) int32: those this dispatch's block table let
    run, and those on or under the diagonal; the rows the first grouped
    product multiplied (expert layers,) int32).
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
                out, ran = latent_attention(cfg, p, h, row_start, positions,
                                            rotary, interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
                tiles.append(ran)
        else:
            with jax.named_scope("deltanet"):
                h = rms_norm(x, p["mixer_norm"], cfg.eps, act)
                out = kda_mixer(cfg, p, h, row_first, state_dtype, gate,
                                interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
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
