"""The forward pass of a Nemotron-H stack over a packed pool of rows.

Every block is ``x + mixer(RMSNorm(x))``; a block is one mixer alone:
``M`` Mamba-2, ``E`` sparse experts with one shared expert, ``*``
causal grouped-query attention (no rotary embedding: the family's
modelling code applies none in this mixer; position comes from the M
blocks). After the last block: a final RMSNorm and an untied head, on
each request's last valid token only.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
router's scores, the softmax, the scan's decays and states, the norms'
statistics and every product's accumulation are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp

from rnb_tpu.ops import moe, segattn, ssd

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: what ``forward`` returns behind the logits and the router's choices
#: (``models/token_stages.py``); ``gmm_rows``: the rows the first
#: grouped product multiplied for the pairs the held experts served
COUNTERS = ("expert_served", "attn_tiles", "gmm_rows")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The sizes of one stack, under the published config's names."""

    pattern: str
    hidden_size: int
    vocab_size: int
    chunk_size: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    router_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    eps: float
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    published_layers: int = 52

    @staticmethod
    def from_published(config: Mapping) -> "NemotronHConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the blocks held here (the pattern's
        first so many) and ``published.n_routed_experts`` the width of
        the router."""
        layers = int(config["num_hidden_layers"])
        published = config.get("published", {})
        return NemotronHConfig(
            pattern=str(config["hybrid_override_pattern"])[:layers],
            hidden_size=int(config["hidden_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            mamba_num_heads=int(config["mamba_num_heads"]),
            mamba_head_dim=int(config["mamba_head_dim"]),
            n_groups=int(config["n_groups"]),
            ssm_state_size=int(config["ssm_state_size"]),
            conv_kernel=int(config["conv_kernel"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            router_experts=int(published.get(
                "n_routed_experts", config["n_routed_experts"])),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            moe_shared_expert_intermediate_size=int(
                config["moe_shared_expert_intermediate_size"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            eps=float(config["layer_norm_epsilon"]),
            time_step_min=float(config["time_step_min"]),
            time_step_max=float(config["time_step_max"]),
            time_step_floor=float(config["time_step_floor"]),
            published_layers=int(published.get("num_hidden_layers",
                                               layers)))

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def blocks_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == kind)

    @property
    def num_expert_layers(self) -> int:
        return self.pattern.count(EXPERTS)


def held_slots(cfg: NemotronHConfig, held: Sequence[int]):
    """``ops/moe.held_slots`` over the router's experts."""
    return moe.held_slots(cfg.router_experts, held)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def mamba_mixer(cfg, p, h, row_first, state_dtype=jnp.float32,
                interpret=False):
    """``h`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden)."""
    rows, q, _ = h.shape
    act = h.dtype
    heads, hd = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    # in_proj's columns as three products, z, xBC and dt: one result of
    # 10,304 columns (80.5 lane tiles) the v5e's compiler lays out with
    # the tokens minor, and the convolution, the scan's kernel and the
    # gate, which read rows, each pay a transposing copy of it (PERF.md
    # section 6, PR 47); 4,096 and 6,144 columns it lays out in rows,
    # and z need not live through the convolution
    edges = (0, cfg.d_inner, cfg.d_inner + cfg.conv_dim,
             p["in_proj"].shape[1])
    z, xbc, dt = (_proj(h, p["in_proj"][:, lo:hi])
                  for lo, hi in zip(edges, edges[1:]))
    xbc = xbc.astype(act)
    # xs, B and C each leave the convolution's kernel as an array of
    # their own: the scan's kernel reads them so, and a slice of the
    # convolution's result would be a copy in HBM
    xs, b, c = ssd.segment_conv1d(
        xbc, p["conv_w"], p["conv_b"], row_first, activation="silu",
        out_dtype=act, interpret=interpret,
        split=(cfg.d_inner, groups * n, groups * n))
    xs = xs.reshape(rows, q, heads, hd)
    b, c = b.reshape(rows, q, groups, n), c.reshape(rows, q, groups, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    # the gate and the gated norm (RMS over each of the n_groups groups,
    # one weight) are the kernel's last lines: a group's columns are the
    # columns a step of the scan holds
    y = ssd.ssd_scan(xs, dt, a, b, c, p["d"].astype(jnp.float32),
                     row_first, state_dtype=state_dtype, interpret=interpret,
                     gated_norm=(z, p["gnorm"], cfg.eps))
    y = y.reshape(rows, q, cfg.d_inner)
    return _proj(y, p["out_proj"])


def attention_mixer(cfg, p, h, row_start, interpret=False):
    """-> (float32 (rows, Q, hidden), the flash kernel's tiles: run, and
    on or under the diagonal)."""
    rows, q, _ = h.shape
    act = h.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    # the scores' scale goes onto the float32 queries, before their
    # one rounding to the activations' dtype
    qs = (_proj(h, p["q"]) * dim ** -0.5).astype(act) \
        .reshape(rows, q, hq, dim)
    ks = _proj(h, p["k"]).astype(act).reshape(rows, q, hk, dim)
    vs = _proj(h, p["v"]).astype(act).reshape(rows, q, hk, dim)
    out, tiles = segattn.packed_attention(qs, ks, vs, row_start, interpret)
    return _proj(out.reshape(rows, q, hq * dim), p["o"]), tiles


def experts_mixer(cfg, p, h, token_ok, slots, expert_cast=None,
                  interpret=False):
    """-> (float32 (rows, Q, hidden), ids (T, k), counts (held,), the
    rows the first grouped product multiplied).
    ``expert_cast`` rounds the experts' weights through a lower
    precision: the tests' control on the CPU, never the program (the
    v5e's compiler fuses such a round trip in front of the grouped
    product and keeps the excess precision: on the chip
    ``scripts/prefill_control.py`` rounds the stored weights);
    ``interpret`` runs the grouped product's kernel in interpret mode
    (a device that is no TPU)."""
    rows, q, hidden = h.shape
    flat = h.reshape(rows * q, hidden)
    ids, weights = moe.route(
        flat, p["router"], p["b_corr"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor)
    up, down = p["up"], p["down"]
    s_up, s_down = p["shared_up"], p["shared_down"]
    if expert_cast is not None:
        up, down, s_up, s_down = (expert_cast(w)
                                  for w in (up, down, s_up, s_down))
    routed, counts, gmm_rows = moe.held_experts(
        flat, ids, weights, token_ok.reshape(-1), slots, up, down,
        interpret=interpret)
    out = routed + moe.dense_expert(flat, s_up, s_down)
    return out.reshape(rows, q, hidden), ids, counts, gmm_rows


def forward(cfg: NemotronHConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            expert_cast=None, interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``interpret`` runs the Pallas kernels in interpret
    mode (a device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the router's
    choices (E blocks, tokens, k) int32; assignments served by each
    held expert (E blocks, held) int32, valid tokens only; the flash
    kernel's tiles (attention blocks, 2) int32: those this dispatch's
    block table let run, and those on or under the diagonal; the rows
    the first grouped product multiplied (E blocks,) int32).
    """
    rows, q = tokens.shape
    row_first = row_start == jnp.arange(rows)
    token_ok = jnp.arange(q)[None, :] < row_tokens[:, None]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    act = x.dtype
    chosen, served, tiles, gmm_rows = [], [], [], []
    for i, kind in enumerate(cfg.pattern):
        p = params["b%d" % i]
        if kind == MAMBA:
            with jax.named_scope("ssd"):
                h = rms_norm(x, p["norm"], cfg.eps, act)
                out = mamba_mixer(cfg, p, h, row_first, state_dtype,
                                  interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
        elif kind == ATTENTION:
            with jax.named_scope("attn"):
                h = rms_norm(x, p["norm"], cfg.eps, act)
                out, ran = attention_mixer(cfg, p, h, row_start, interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
                tiles.append(ran)
        elif kind == EXPERTS:
            with jax.named_scope("experts"):
                h = rms_norm(x, p["norm"], cfg.eps, act)
                out, ids, counts, multiplied = experts_mixer(
                    cfg, p, h, token_ok, slots, expert_cast, interpret)
                x = (x.astype(jnp.float32) + out).astype(act)
                chosen.append(ids)
                served.append(counts)
                gmm_rows.append(multiplied)
        else:
            raise ValueError("block kind %r" % (kind,))
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"])
    return logits, jnp.stack(chosen), jnp.stack(served), jnp.stack(tiles), \
        jnp.stack(gmm_rows)
