"""The tensors of a dots3-note stack, made on the device from a seed by
the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

Stored forms that differ from the published one, each made once at
set-up (``models/deepseek_v2/checkpoint.py``'s, at two geometries): a
routed expert's first two matrices (``gate``, ``up``) lie ``[held,
inner, hidden]``; the rotary columns of ``q_b`` (the last
``qk_rope_head_dim`` of each head's) and of ``kv_a`` (its last ones) are
stored evens first, then odds: the de-interleaving the published code
makes at run time before it rotates halves (``ops/rope.py``). ``q_b``
lies heads-first, ``[heads, latent, columns]``, a head's columns the
whole lanes ``ops/mla.py`` reads: ``[q_nope | q_pe | q_pe turned | 0]``
(128 + 64 + 64 in a full layer, 192 + 64 + 64 + 64 in a sliding one).
``kv_b``'s columns are a head's ``[k_nope | v]`` as published where
``qk_nope_head_dim`` is whole lane tiles (the full layers' 128 + 128);
where it is not (the sliding layers' 192) the matrix lies heads-first
with a head's columns ``[k_nope | 0 | v]``, the own key padded to the
queries' lanes (192 + 64 + 128): the attention kernel adds the shared
rotary key into the empty columns (``ops/banded.py``).

Initial scales (this repo's assumption: the published checkpoint is
trained, not initialised; ``models/keye_vl2/checkpoint.py`` has the
counts behind the choices): embedding N(0, 1); every projection into a
mixer, and from a latent, N(0, 1/fan_in); the projections back onto the
residual stream (``o``, an MLP's or expert's last matrix) N(0, 1/fan_in)
times ``BACK``; head and router N(0, 1/hidden); norm weights 1, but the
two latents' norms ``LATENT_SPREAD / rho``, ``rho`` the latent's rescale:
the rescaled latents then have a root mean square of ``LATENT_SPREAD``
(1.5, ``models/keye_vl2``'s query-key gain) and the scores a spread of
about 2.25, enough that *which* keys a query reads moves the logits and
a softmax a bfloat16 program can track. With weights of one the rescale
alone gives the scores a spread of sqrt(5) x sqrt(10) = 7.1 in a full
layer and 5 in a sliding one at the published sizes — a softmax that is
one key's, where a rounding of q or k that flips the best two keys moves
the result by a value's whole width: the stated precision then read 24%
of the logits' spread against the float32 reference on the v5e (my chip
run, PR 55), and the toy's 2.2% becomes 20% at that spread (CPU counts).
A trained model's norm weights are whatever its training left; a draw
is held to what the comparison can see. The correction bias N(0,
0.02^2) as ``models/exaone_moe`` draws it; the gate's matrix N(0,
1/hidden); the indexer's three matrices N(0, 1/fan_in), its key norm's
weight 1 and bias N(0, 0.1^2): a request's scores then have a spread of
order one.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.dots3_note.network import Dots3NoteConfig, Geometry
from rnb_tpu.models.seeded import TensorSpec
from rnb_tpu.ops import mla

FAMILY = "dots3_note"
B_CORR_STD = 0.02
INDEX_BIAS_STD = 0.1
#: the two projections back onto the stream: a layer's two additions
BACK = 1.0 / math.sqrt(2.0)
#: the root mean square of a normed, rescaled latent: its norm's weight
#: is this over the rescale
LATENT_SPREAD = 1.5


def query_columns(geo: Geometry):
    """``q_b``'s stored columns of one head (``TensorSpec.heads_first``)."""
    nope, half = geo.nope, geo.rotary // 2
    order = list(range(geo.qk_dim)) \
        + [-1 - (nope + half + i) for i in range(half)] \
        + [nope + i for i in range(half)]
    return tuple(order) + (None,) * (mla.query_lanes(nope, geo.rotary)
                                     - len(order))


def key_value_columns(geo: Geometry):
    """``kv_b``'s stored columns of one head where its own key is padded
    to the queries' lanes: ``[k_nope | 0 | v]``."""
    return tuple(range(geo.nope)) + (None,) * (geo.key_lanes - geo.nope) \
        + tuple(range(geo.nope, geo.nope + geo.value))


def tensor_specs(cfg: Dots3NoteConfig, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf = cfg.hidden_size, "bfloat16"

    def lin(fan_in, fan_out, scale=1.0, halves=None):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in), halves=halves)

    def ones(width, scale=1.0):
        return TensorSpec((width,), bf, "ones", scale)

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        geo = cfg.geometry(i)
        rho_q, rho_kv = cfg.rescales(geo)
        columns = query_columns(geo)
        own = geo.nope + geo.value
        layer = {
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "q_a": lin(d, geo.q_rank),
            "q_a_norm": ones(geo.q_rank, LATENT_SPREAD / rho_q),
            "q_b": TensorSpec(
                (geo.heads, geo.q_rank, len(columns)), bf, "normal",
                1.0 / math.sqrt(geo.q_rank),
                halves=(geo.qk_dim, geo.nope, geo.rotary),
                heads_first=(geo.qk_dim, columns)),
            "kv_a": lin(d, geo.kv_rank + geo.rotary,
                        halves=(geo.kv_rank + geo.rotary, geo.kv_rank,
                                geo.rotary)),
            "kv_a_norm": ones(geo.kv_rank, LATENT_SPREAD / rho_kv),
            "kv_b": lin(geo.kv_rank, geo.heads * own)
            if geo.key_lanes == geo.nope else TensorSpec(
                (geo.heads, geo.kv_rank, geo.key_lanes + geo.value), bf,
                "normal", 1.0 / math.sqrt(geo.kv_rank),
                heads_first=(own, key_value_columns(geo))),
            "attn_gate": lin(d, geo.heads),
            "o": lin(geo.heads * geo.value, d, BACK)}
        if not cfg.is_sliding(i):
            heads, dim = cfg.index_n_heads, cfg.index_head_dim
            layer.update({
                "index_q": lin(geo.q_rank, heads * dim),
                "index_k": lin(d, dim), "index_w": lin(d, heads),
                "index_k_norm": ones(dim),
                "index_k_bias": TensorSpec((dim,), bf, "normal",
                                           INDEX_BIAS_STD)})
        if cfg.is_dense(i):
            inner = cfg.intermediate_size
            layer.update({"gate": lin(d, inner), "up": lin(d, inner),
                          "down": lin(inner, d, BACK)})
        else:
            inner, shared = (cfg.moe_intermediate_size,
                             cfg.shared_intermediate_size)

            def first(inner=inner):
                return TensorSpec((num_held, inner, d), bf, "normal",
                                  1.0 / math.sqrt(d), per_expert=True,
                                  transposed=True)
            layer.update({
                "router": lin(d, cfg.router_experts),
                "b_corr": TensorSpec((cfg.router_experts,), "float32",
                                     "normal", B_CORR_STD),
                "gate": first(), "up": first(),
                "down": TensorSpec((num_held, inner, d), bf, "normal",
                                   BACK / math.sqrt(inner),
                                   per_expert=True),
                "shared_gate": lin(d, shared), "shared_up": lin(d, shared),
                "shared_down": lin(shared, d, BACK)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: Dots3NoteConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: Dots3NoteConfig, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (Dots3NoteConfig, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (Dots3NoteConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
