"""The forward pass of a Falcon-H1 stack over a packed pool of rows.

Every block runs two mixers *side by side on one normed input* and adds
both to the stream, then a gated MLP; the model's muP multipliers are
scalars of the configuration and stand where the published code has
them (``transformers``' ``modeling_falcon_h1.py``)::

    h0 = E[token] * embedding_multiplier
    u  = RMSNorm(h)
    p  = ((u * ssm_in_multiplier) W_in) (.) mu        mu: ssm_multipliers
         over the columns of z | x | B | C | dt
    xBC = silu(conv(xBC) + bias);  dt = softplus(dt + dt_bias)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    m  = (GroupRMSNorm(y silu(z)) W_out) * ssm_out_multiplier
    q, k, v = (u * attention_in_multiplier) W_q, W_k, W_v;
    k = k * key_multiplier;  rotary on q and k (all columns, halves)
    a  = (softmax(q k^T / sqrt(d)) v W_o) * attention_out_multiplier
    h  = h + m + a
    f  = RMSNorm(h)
    h  = h + ((silu((f W_gate) * mlp_multipliers[0]) * (f W_up)) W_down)
             * mlp_multipliers[1]
    logits = (RMSNorm(h_L) W_head) * lm_head_multiplier

A scalar in front of a product is applied behind it, to the float32
result (a scalar commutes with the product, and the operand keeps its
one rounding); ``mu`` is a factor a column of ``in_proj``'s result. The
state-space branch is ``ops/ssd.py``'s two kernels (the convolution
over the 5,120 channels of x | B | C, the scan with the gate and the
gated norm as its last lines: a norm group is a scan group's 16 heads
of 128); the attention branch is ``ops/segattn.py``'s flash kernel at
five query heads a key-value head, positions inside the request
(``ops/rope.py``).

A *row* is ``chunk_size`` tokens; a request is a run of consecutive rows
with its tail padded. Weights and activations are bfloat16; the norms'
statistics, the softmax, the rotary angles, the scan's steps, decays
and states and every product's accumulation are float32.

The named scopes are ``embed``, ``norm`` (the block's first norm, which
both mixers read: in neither's share), ``ssd`` (the state-space branch
whole, ``ssd/conv`` and ``ssd/scan`` inside), ``attn`` (the attention
branch whole), ``mlp`` and ``head``. The family has no experts:
``forward`` takes ``slots`` for the stages' one call and ignores it,
and nothing is chosen: ``chosen`` is empty.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import rope, segattn, ssd

#: what ``forward`` returns behind the logits and the (empty) choices:
#: the flash kernel's tiles a layer, and the rows of the dispatch that
#: open a request (where the scan zeroes its state and the convolution
#: its history), pad rows not counted
COUNTERS = ("attn_tiles", "scan_resets")

#: the configuration's scalars (a list's entries by index): the twelve
#: of the mixers, the embedding and the head, and the MLP's two
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers.0", "ssm_multipliers.1",
    "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4",
    "attention_in_multiplier", "attention_out_multiplier",
    "key_multiplier", "mlp_multipliers.0", "mlp_multipliers.1")


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The sizes and scalars of one stack, under the published config's
    names."""

    num_hidden_layers: int          # held here: the model's first so many
    published_layers: int
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    rope_theta: float
    eps: float
    embedding_multiplier: float
    lm_head_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Tuple[float, ...]      # z, x, B, C, dt
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    mlp_multipliers: Tuple[float, float]    # gate, down
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @staticmethod
    def from_published(config: Mapping) -> "FalconH1Config":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here (all 72 are alike)."""
        heads, width = int(config["mamba_n_heads"]), \
            int(config["mamba_d_head"])
        if heads * width != int(config["mamba_d_ssm"]) \
                or not config["mamba_rms_norm"] \
                or config["mamba_norm_before_gate"] \
                or not config["mamba_conv_bias"] \
                or any(config.get(key) for key in (
                    "attention_bias", "mlp_bias", "mamba_proj_bias",
                    "projectors_bias", "rope_scaling",
                    "tie_word_embeddings")) \
                or config["hidden_act"] != "silu" \
                or len(config["ssm_multipliers"]) != 5 \
                or len(config["mlp_multipliers"]) != 2:
            raise ValueError("a switch or a size of the mixers: not the "
                             "Falcon-H1 this network implements")
        layers = int(config["num_hidden_layers"])
        return FalconH1Config(
            num_hidden_layers=layers,
            published_layers=int(config.get("published", {}).get(
                "num_hidden_layers", layers)),
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            mamba_n_heads=heads, mamba_d_head=width,
            mamba_n_groups=int(config["mamba_n_groups"]),
            mamba_d_state=int(config["mamba_d_state"]),
            mamba_d_conv=int(config["mamba_d_conv"]),
            rope_theta=float(config["rope_theta"]),
            eps=float(config["rms_norm_eps"]),
            ssm_multipliers=tuple(float(m)
                                  for m in config["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m)
                                  for m in config["mlp_multipliers"]),
            **{key: float(config[key]) for key in MULTIPLIERS
               if "." not in key})

    @property
    def d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_parts(self) -> Tuple[int, int, int]:
        """The convolution's channels: x, B, C."""
        wide = self.mamba_n_groups * self.mamba_d_state
        return self.d_ssm, wide, wide

    @property
    def in_proj_parts(self) -> Tuple[int, ...]:
        """``in_proj``'s columns: z, x, B, C, dt — a multiplier each."""
        return (self.d_ssm,) + self.conv_parts + (self.mamba_n_heads,)

    def inv_freq(self) -> np.ndarray:
        dim = self.head_dim
        return (self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64)
                                    / dim)).astype(np.float32)

    def multiplier_values(self) -> Tuple[float, ...]:
        """The scalars :data:`MULTIPLIERS` names, in that order."""
        out = []
        for name in MULTIPLIERS:
            key, _, index = name.partition(".")
            value = getattr(self, key)
            out.append(value[int(index)] if index else value)
        return tuple(out)

    def with_multipliers(self, values) -> "FalconH1Config":
        """This stack under other scalars, in :data:`MULTIPLIERS`' order
        (the tests: a scalar changed in the program alone; traced
        scalars do, so that one program serves every change)."""
        by = dict(zip(MULTIPLIERS, values))
        lists = {key: tuple(by["%s.%d" % (key, i)]
                            for i in range(len(getattr(self, key))))
                 for key in ("ssm_multipliers", "mlp_multipliers")}
        return dataclasses.replace(
            self, **lists, **{key: by[key] for key in MULTIPLIERS
                              if "." not in key})


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def ssm_mixer(cfg, p, u, row_first, state_dtype=jnp.float32,
              interpret=False):
    """``u`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden)."""
    rows, q, _ = u.shape
    act = u.dtype
    heads, hd = cfg.mamba_n_heads, cfg.mamba_d_head
    groups, n = cfg.mamba_n_groups, cfg.mamba_d_state
    # in_proj's columns as three products, z, xBC and dt (the layouts'
    # reason is Nemotron-H's: ``models/nemotron_h/network.py``); the
    # scalar in front of the product and the multiplier a segment behind
    # it are one factor a column of the float32 result
    scale = cfg.ssm_in_multiplier * jnp.repeat(
        jnp.stack([jnp.float32(m) for m in cfg.ssm_multipliers]),
        np.asarray(cfg.in_proj_parts),
        total_repeat_length=sum(cfg.in_proj_parts))
    edges = (0, cfg.d_ssm, cfg.d_ssm + sum(cfg.conv_parts),
             p["in_proj"].shape[1])
    z, xbc, dt = (_proj(u, p["in_proj"][:, lo:hi]) * scale[lo:hi]
                  for lo, hi in zip(edges, edges[1:]))
    xs, b, c = ssd.segment_conv1d(
        xbc.astype(act), p["conv_w"], p["conv_b"], row_first,
        activation="silu", out_dtype=act, interpret=interpret,
        split=cfg.conv_parts)
    xs = xs.reshape(rows, q, heads, hd)
    b, c = b.reshape(rows, q, groups, n), c.reshape(rows, q, groups, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    # the gate and the gated norm (RMS over each group's 16 heads of
    # 128, one weight) are the kernel's last lines
    y = ssd.ssd_scan(xs, dt, a, b, c, p["d"].astype(jnp.float32),
                     row_first, state_dtype=state_dtype, interpret=interpret,
                     gated_norm=(z, p["gnorm"], cfg.eps))
    return _proj(y.reshape(rows, q, cfg.d_ssm), p["out_proj"]) \
        * cfg.ssm_out_multiplier


def attention_mixer(cfg, p, u, row_start, positions, interpret=False):
    """-> (float32 (rows, Q, hidden), the flash kernel's tiles: run, and
    on or under the diagonal)."""
    rows, q, _ = u.shape
    act = u.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    inv_freq = cfg.inv_freq()
    into = cfg.attention_in_multiplier

    def heads_of(w, heads, factor, turn):
        out = (_proj(u, w) * factor).reshape(rows, q, heads, dim)
        return (rope.rotate(out, positions, inv_freq) if turn else out) \
            .astype(act)
    # the scores' scale goes onto the float32 queries, before their one
    # rounding to the activations' dtype
    qs = heads_of(p["q"], hq, into * dim ** -0.5, True)
    ks = heads_of(p["k"], hk, into * cfg.key_multiplier, True)
    vs = heads_of(p["v"], hk, into, False)
    out, tiles = segattn.packed_attention(qs, ks, vs, row_start, interpret)
    return _proj(out.reshape(rows, q, hq * dim), p["o"]) \
        * cfg.attention_out_multiplier, tiles


def mlp(cfg, p, f):
    """``f`` (rows, Q, hidden), normed -> float32 (rows, Q, hidden)."""
    gate = jax.nn.silu(_proj(f, p["gate"]) * cfg.mlp_multipliers[0])
    hidden = (gate * _proj(f, p["up"])).astype(f.dtype)
    return _proj(hidden, p["down"]) * cfg.mlp_multipliers[1]


def forward(cfg: FalconH1Config, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``slots`` is the expert families' and is ignored;
    ``state_dtype`` is the lower-precision control's (the scan's
    states); ``interpret`` runs the Pallas kernels in interpret mode (a
    device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the stack's
    choices: none, (0, tokens) int32; the flash kernel's tiles (layers,
    2) int32: those this dispatch's block table let run, and those on or
    under the diagonal; the rows that open a request (1,) int32).
    """
    del slots
    rows, q = tokens.shape
    row_first = row_start == jnp.arange(rows)
    positions = rope.pool_positions(row_start, q)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        act = x.dtype
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(act)
    tiles = []
    for i in range(cfg.num_hidden_layers):
        p = params["l%d" % i]
        with jax.named_scope("norm"):
            u = rms_norm(x, p["input_norm"], cfg.eps, act)
        with jax.named_scope("ssd"):
            mixed = ssm_mixer(cfg, p, u, row_first, state_dtype, interpret)
        with jax.named_scope("attn"):
            attended, ran = attention_mixer(cfg, p, u, row_start, positions,
                                            interpret)
            tiles.append(ran)
        x = (x.astype(jnp.float32) + mixed + attended).astype(act)
        with jax.named_scope("mlp"):
            f = rms_norm(x, p["pre_ff_norm"], cfg.eps, act)
            x = (x.astype(jnp.float32) + mlp(cfg, p, f)).astype(act)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"]) * cfg.lm_head_multiplier
    resets = jnp.sum(row_first & (row_tokens > 0), dtype=jnp.int32)
    return logits, jnp.zeros((0, rows * q), jnp.int32), jnp.stack(tiles), \
        resets.reshape(1)
