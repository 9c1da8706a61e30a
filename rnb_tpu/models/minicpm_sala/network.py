"""The forward pass of a MiniCPM-SALA stack over a packed pool of rows.

``h = scale_emb x E[token]``; every layer is ``h += s x mixer(RMSNorm(
h))``, ``h += s x mlp(RMSNorm(h))`` with ``s = scale_depth /
sqrt(published layers)`` and a SiLU-gated MLP; then ``logits = head(
RMSNorm(h)) / (hidden_size / dim_model_base)`` on each request's last
valid token. A layer's mixer is, by ``mixer_types``:

``minicpm4``: grouped-query attention with per-head RMSNorm on queries
and keys, no rotary, every query over the keys of the blocks it chose
(``ops/blocksparse.py``: InfLLM-V2; a request under ``dense_len`` over
all its keys), a sigmoid gate on the result.

``lightning-attn``: linear attention, ``S_t = lambda_h S_{t-1} + k_t^T
v_t``, ``o_t = q_t S_t / sqrt(d)`` with per-head RMSNorm and rotary
(positions inside the request, ``ops/rope.py``) on queries and keys, the
state zero at a request's first token, ``lambda_h = exp(-2^(-8 (h + 1)
/ H))``; an RMSNorm over all heads' outputs, a sigmoid gate. It runs
through ``ops/ssd.ssd_scan`` with unit steps, every line between the
five products inside that kernel.

A *row* is ``chunk_size`` tokens; a request is a run of consecutive
rows with its tail padded. Weights and activations are bfloat16; the
norms' statistics, the softmaxes, the selection's scores, the scan's
decays and states, the rotary angles and every product's accumulation
are float32.

The named scopes are ``embed``, ``attn`` (a sparse layer's mixer whole,
its selection under ``attn/select``), ``ssd`` (a lightning layer's mixer
whole), ``mlp`` and ``head``. The family has no experts: ``forward``
takes ``slots`` for the stages' one call and ignores it, and
``COUNTERS`` names what it returns behind the logits and the choices.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops import banded, blocksparse, moe, ssd

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
#: what ``forward`` returns behind the logits and the choices
COUNTERS = ("sparse",)


@dataclasses.dataclass(frozen=True)
class MinicpmSalaConfig:
    """The sizes of one stack, under the published config's names."""

    mixer_types: Tuple[str, ...]    # held here: the model's first so many
    published_layers: int
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    chunk_size: int                 # tokens a row: the pipeline's
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    rope_theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    eps: float
    sparse: blocksparse.SparseConfig

    @staticmethod
    def from_published(config: Mapping) -> "MinicpmSalaConfig":
        """From a configuration file's keys: the published ones, with
        ``num_hidden_layers`` the layers held here and ``mixer_types``
        their kinds (the published list's first so many)."""
        published = config.get("published", {})
        layers = int(config["num_hidden_layers"])
        kinds = tuple(config["mixer_types"])[:layers]
        if len(kinds) != layers or set(kinds) - {SPARSE, LIGHTNING} \
                or config["lightning_nkv"] != config["lightning_nh"] \
                or config["attn_use_rope"] or not config["lightning_use_rope"] \
                or not (config["qk_norm"] and config["use_output_gate"]
                        and config["use_output_norm"]
                        and config["attn_use_output_gate"]) \
                or config["lightning_scale"] != "1/sqrt(d)":
            raise ValueError("mixer_types or a switch of the mixers: not "
                             "the MiniCPM-SALA this network implements")
        return MinicpmSalaConfig(
            mixer_types=kinds,
            published_layers=int(published.get("num_hidden_layers",
                                               layers)),
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            vocab_size=int(config["vocab_size"]),
            chunk_size=int(config["chunk_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            lightning_nh=int(config["lightning_nh"]),
            lightning_head_dim=int(config["lightning_head_dim"]),
            rope_theta=float(config["rope_theta"]),
            scale_emb=float(config["scale_emb"]),
            scale_depth=float(config["scale_depth"]),
            dim_model_base=int(config["dim_model_base"]),
            eps=float(config["rms_norm_eps"]),
            sparse=blocksparse.SparseConfig.from_mapping(
                config["sparse_config"]))

    @property
    def num_hidden_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.hidden_size

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.mixer_types) if k == kind)

    def inv_freq(self) -> np.ndarray:
        dim = self.lightning_head_dim
        return (self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64)
                                    / dim)).astype(np.float32)

    def log_decay(self) -> np.ndarray:
        """(heads,) float32: ``log lambda_h = -2^(-8 (h + 1) / H)``."""
        heads = self.lightning_nh
        return (-2.0 ** (-8.0 * np.arange(1, heads + 1) / heads)) \
            .astype(np.float32)


def rms_norm(x, weight, eps: float, out_dtype):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(out_dtype)


def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _heads(cfg, x, w, norm, heads: int, dim: int):
    """``x W`` as (rows, Q, heads, dim) float32, each head RMS-normed
    where ``norm`` (dim,) is given."""
    out = _proj(x, w).reshape(x.shape[:2] + (heads, dim))
    return out if norm is None else rms_norm(out, norm, cfg.eps,
                                             jnp.float32)


def sparse_mixer(cfg, p, h, row_start, row_tokens, interpret=False):
    """``h`` (rows, Q, hidden), normed -> (float32 (rows, Q, hidden), the
    chosen blocks, the selection's counts: ``ops/blocksparse.py``)."""
    rows, q, _ = h.shape
    act = h.dtype
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    # the scores' scale goes onto the float32 queries, before their one
    # rounding to the activations' dtype
    qs = (_heads(cfg, h, p["q"], p["q_norm"], hq, dim) * dim ** -0.5) \
        .astype(act)
    ks = _heads(cfg, h, p["k"], p["k_norm"], hk, dim).astype(act)
    vs = _heads(cfg, h, p["v"], None, hk, dim).astype(act)
    out, chosen, counts = blocksparse.sparse_attention(
        qs, ks, vs, row_start, row_tokens, cfg.sparse, interpret)
    gate = jax.nn.sigmoid(_proj(h, p["gate"]))
    out = (out.reshape(rows, q, hq * dim).astype(jnp.float32) * gate) \
        .astype(act)
    return _proj(out, p["o"]), chosen, counts


def rotary_tables(cfg, row_start, qlen: int):
    """The lightning layers' rotary (cos, sin), float32 (rows, Q, dim),
    of each token's position inside its request, the sign in the sine
    (``ops/banded.band_tables``): one pair a dispatch for every layer."""
    rows = row_start.shape[0]
    return [t.reshape(rows, qlen, -1) for t in banded.band_tables(
        row_start, qlen, cfg.inv_freq())[:2]]


def lightning_mixer(cfg, p, h, row_first, tables,
                    state_dtype=jnp.float32, interpret=False):
    """``h`` (rows, Q, hidden), normed; ``tables``: :func:`rotary_tables`
    -> float32 (rows, Q, hidden). Between the five
    products nothing with a head axis is written but what the scan's
    kernel reads and writes: the head norms, the rotation, the scale and
    the rounding of q and k are its first lines, the output norm and the
    gate its last (``ops/ssd.py``)."""
    rows, q, _ = h.shape
    heads, dim = cfg.lightning_nh, cfg.lightning_head_dim

    def of_heads(name):
        return _proj(h, p[name]).reshape(rows, q, heads, dim)
    # the Mamba-2 scan with unit steps: xs = v, B = k, C = q / sqrt(d),
    # one group a head, no skip term
    out = ssd.ssd_scan(
        of_heads("v").astype(h.dtype), None, jnp.asarray(cfg.log_decay()),
        of_heads("k"), of_heads("q"), None, row_first,
        state_dtype=state_dtype, interpret=interpret,
        head_norm=(p["k_norm"], p["q_norm"], cfg.eps, dim ** -0.5, *tables),
        out_norm=(_proj(h, p["gate"]), p["o_norm"], cfg.eps))
    return _proj(out.reshape(rows, q, heads * dim), p["o"])


def forward(cfg: MinicpmSalaConfig, params, slots, tokens, row_tokens,
            row_start, last_idx, *, state_dtype=jnp.float32,
            interpret=False):
    """One packed dispatch.

    ``tokens`` (rows, Q) int32; ``row_tokens`` (rows,) the valid tokens
    of each row (0 on a pad row); ``row_start`` (rows,) the first row of
    each row's request (its own index on a pad row); ``last_idx``
    (rows,) the flat index of request i's last valid token (0 past the
    last request); ``slots`` is the expert families' and is ignored;
    ``state_dtype`` is the lower-precision control's (the lightning
    layers' states); ``interpret`` runs the Pallas kernel in interpret
    mode (a device that is no TPU).

    -> (logits (rows, vocab) float32, one line a request; the blocks
    each query chose (sparse layers, tokens, key-value heads, pool
    blocks) bool; the selection's counts (sparse layers, 4) int32).
    """
    del slots
    rows, q = tokens.shape
    row_first = row_start == jnp.arange(rows)
    tables = rotary_tables(cfg, row_start, q)
    scale = cfg.residual_scale
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        act = x.dtype
        x = (x.astype(jnp.float32) * cfg.scale_emb).astype(act)
    chosen, counts = [], []
    for i, kind in enumerate(cfg.mixer_types):
        p = params["l%d" % i]
        if kind == SPARSE:
            with jax.named_scope("attn"):
                h = rms_norm(x, p["attn_norm"], cfg.eps, act)
                out, blocks, counted = sparse_mixer(
                    cfg, p, h, row_start, row_tokens, interpret)
                x = (x.astype(jnp.float32) + scale * out).astype(act)
                chosen.append(blocks)
                counts.append(counted)
        else:
            with jax.named_scope("ssd"):
                h = rms_norm(x, p["attn_norm"], cfg.eps, act)
                out = lightning_mixer(cfg, p, h, row_first, tables,
                                      state_dtype, interpret)
                x = (x.astype(jnp.float32) + scale * out).astype(act)
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["ffn_norm"], cfg.eps, act)
            out = moe.dense_expert(h, p["up"], p["down"], p["gate_mlp"])
            x = (x.astype(jnp.float32) + scale * out).astype(act)
    with jax.named_scope("head"):
        last = x.reshape(rows * q, -1)[last_idx]
        last = rms_norm(last, params["final_norm"], cfg.eps, act)
        logits = _proj(last, params["head"]) * cfg.logit_scale
    return logits, jnp.stack(chosen), jnp.stack(counts)


def request_choices(cfg: MinicpmSalaConfig, chosen, first: int, count: int):
    """What a sample keeps of one request's choices: its own tokens'
    rows of ``chosen`` over its own blocks, the block axis packed to
    bits (``np.unpackbits(..., axis=-1, bitorder="little")``)."""
    size = cfg.sparse.block_size
    blocks = -(-count // size)
    own = np.asarray(chosen)[:, first:first + count, :,
                             first // size:first // size + blocks]
    return np.packbits(own, axis=-1, bitorder="little")
