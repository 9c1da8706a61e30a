"""The Xing4.0 family against its plain reference, at a toy size on the
CPU with weights from a seed: the packed prefill at the serving
precision, the same forward in float32 against the reference under a
limit that four planted faults each fail, the mappings' coefficients
and their Sinkhorn iteration, the stream one wide as the plain pre-norm
residual stack, the recipe, the counters, the operation counts, the new
readers, and the real configuration's published sizes. The cell through
the one benchmark command is ``test_xing4_cell.py``'s."""

import functools
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import xing4 as reference  # noqa: E402

REAL = "benchmarks/configs/xing4-29b-ep1.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "xing4.bulk"
SEED = 3_000_000_123

#: the published shape at toy widths: 1 dense + 2 expert layers, 8
#: sigmoid-routed experts (top-2) and a shared one, all held, under a
#: stream 4 wide
TOY = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "vocab_size": 256, "chunk_size": 16, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "n_routed_experts": 8, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.0,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "moe_layer_freq": 1, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "published": {"num_hidden_layers": 40, "first_k_dense_replace": 2}}
HELD = tuple(range(8))
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths and the serving precision
#: (bfloat16 weights and stream): narrow sums average less rounding away
#: than the real ones; the toy reads 1.1%
TOY_LIMIT = 0.03
#: the limit of the *float32* forward: the program with its stored
#: values upcast, so that nothing but the order of float32 sums differs
#: from the reference. It reads 7e-7 of the spread; the mildest planted
#: fault (``H_res`` transposed) reads 2e-3. A limit at the serving
#: precision cannot tell the mappings in bfloat16 (1.7%) from the
#: stream's own rounding (1.1%): this one can
TIGHT_LIMIT = 1e-4


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.xing4 import checkpoint, network
    cfg = network.Xing4Config.from_published(TOY)
    device = jax.devices()[0]
    return {"cfg": cfg, "device": device,
            "params": checkpoint.make_params(cfg, SEED, HELD, device),
            "slots": network.held_slots(cfg, HELD),
            "read": checkpoint.reference_reader(cfg, SEED, device),
            "reference": reference.Reference(TOY)}


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def float32_of(params):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32) if w.dtype == jnp.bfloat16 else w,
        params)


def edited(params, edit):
    """The parameter tree with ``edit(layer's tensors)`` on a copy of
    every layer."""
    return {group: edit(dict(tensors)) if isinstance(tensors, dict)
            else tensors for group, tensors in params.items()}


def run_program(toy, prompts, rows, params=None, cfg=None):
    """-> (logits a prompt, the router's choices a prompt, the defects a
    prompt (sublayers, tokens), the counters)."""
    import jax

    from rnb_tpu.models import token_stages
    from rnb_tpu.models.xing4 import network
    cfg = toy["cfg"] if cfg is None else cfg
    tokens, meta, offsets = token_stages.pack_prompts(prompts, rows, Q)
    logits, (chosen, defects), *counts = jax.jit(
        lambda p, s, t, m: network.forward(
            cfg, p, s, t, m[0], m[1], m[2], interpret=True))(
        toy["params"] if params is None else params, toy["slots"], tokens,
        meta)
    chosen, defects = np.asarray(chosen), np.asarray(defects)
    cut = [slice(o * Q, o * Q + len(p)) for o, p in zip(offsets, prompts)]
    return (np.asarray(logits)[:len(prompts)], [chosen[:, c] for c in cut],
            [defects[:, c] for c in cut], [np.asarray(c) for c in counts])


def run_reference(toy, prompt, forced=None, read=None):
    import jax
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(
            toy["read"] if read is None else read, prompt, forced=forced)


def against_the_reference(toy, limit, prompts, rows, **program):
    logits, chosen, defects, counts = run_program(toy, prompts, rows,
                                                  **program)
    refs = [run_reference(toy, p, forced=c)
            for p, c in zip(prompts, chosen)]
    want = np.stack([np.asarray(r["logits"]) for r in refs])
    return compare(logits, want, limit), refs, defects, counts


# -- the whole stack ----------------------------------------------------------


def test_packed_prefill_matches_the_reference(toy):
    """Six requests and a pad row in one pool, at the serving precision:
    every request's last-position logits, the router's slack, each
    sample's defect against the reference's own, the counter."""
    family = mm.load_family("xing4")
    prompts = prompts_of([5, 16, 37, 64, 20, 70], seed=4)
    verdict, refs, defects, counts = against_the_reference(
        toy, TOY_LIMIT, prompts, 16)
    assert verdict["ok"], verdict
    assert max(float(r["shortfall"].max()) for r in refs) \
        < family.ROUTE_SLACK
    assert all(got.shape == (6, len(p)) for got, p in zip(defects, prompts))
    assert family.defects_apart(
        defects, [r["res_defect"] for r in refs]) < family.DEFECT_APART
    served, tiles, gmm_rows, stream_mix = counts
    valid = sum(len(p) for p in prompts)
    # every expert is held: every valid (token, choice) pair is served
    assert (served.sum(axis=1) == valid * TOY["num_experts_per_tok"]).all()
    assert stream_mix[0] == valid * 2 * TOY["num_hidden_layers"]
    worst = max(float(d.max()) for d in defects)
    assert stream_mix[1] == int(np.float32(worst) * np.float32(1e9))
    # the control: every layer's matrices through float8, outside
    import jax.numpy as jnp
    fp8 = edited(toy["params"], lambda t: {
        name: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
               if w.ndim >= 2 else w) for name, w in t.items()})
    assert not against_the_reference(toy, TOY_LIMIT, prompts, 16,
                                     params=fp8)[0]["ok"]


def test_packing_is_invisible(toy):
    """A prompt's logits depend neither on what shares its dispatch nor
    on where in the pool it lies."""
    a, b, c, d = prompts_of([37, 5, 64, 20])
    alone = run_program(toy, [a], 8)[0]
    packed = run_program(toy, [b, c, a, d], 16)[0]
    spread = float(alone.std())
    assert np.abs(packed[2] - alone[0]).max() < 0.005 * spread


def mappings_in_bfloat16(monkeypatch, params):
    import jax.numpy as jnp
    from jax import lax

    from rnb_tpu.ops import hyper
    monkeypatch.setattr(hyper, "COMPUTE", jnp.bfloat16)
    # the same steps under a loop: XLA's CPU compiler takes eleven
    # seconds a sublayer for them written out in bfloat16
    monkeypatch.setattr(hyper, "sinkhorn", lambda m, iters, hc_eps:
                        lax.fori_loop(0, iters, lambda _, m:
                                      hyper.sinkhorn_step(m, hc_eps), m))
    return params


def dynamic_term_dropped(monkeypatch, params):
    import jax.numpy as jnp

    def edit(t):
        for sub in ("attn", "ffn"):
            t[sub + "_hc_alpha"] = jnp.zeros_like(t[sub + "_hc_alpha"])
        return t
    return edited(params, edit)


def res_transposed(monkeypatch, params):
    """``H~_res`` transposed: ``phi``'s and the bias's res entries (i,
    j) <-> (j, i)."""
    n = TOY["hc_mult"]
    order = np.concatenate([np.arange(2 * n), 2 * n + np.arange(
        n * n).reshape(n, n).T.reshape(-1)])

    def edit(t):
        for sub in ("attn", "ffn"):
            t[sub + "_hc_phi"] = t[sub + "_hc_phi"][..., order]
            t[sub + "_hc_bias"] = t[sub + "_hc_bias"][..., order]
        return t
    return edited(params, edit)


def one_stream_read(monkeypatch, params):
    import jax.numpy as jnp

    from rnb_tpu.models.xing4 import network
    monkeypatch.setattr(
        network, "merge_streams",
        lambda last, n: last.astype(jnp.float32)[:, :last.shape[1] // n])
    return params


def streams_averaged(monkeypatch, params):
    from rnb_tpu.models.xing4 import network
    summed = network.merge_streams
    monkeypatch.setattr(network, "merge_streams",
                        lambda last, n: summed(last, n) / n)
    return params


@pytest.mark.parametrize("fault,fails", [
    (None, False),
    (mappings_in_bfloat16, True),
    (dynamic_term_dropped, True),
    (res_transposed, True),
    (one_stream_read, True),
    (streams_averaged, False),
], ids=["as_stated", "mappings_bfloat16", "no_dynamic_term",
        "res_transposed", "one_stream_read", "streams_averaged"])
def test_in_float32_the_forward_is_the_reference_and_a_fault_is_not(
        toy, monkeypatch, fault, fails):
    """The program on its stored values upcast to float32 (the stream
    follows the embedding's dtype) reads the reference to float32
    rounding, and each fault, planted from outside the program, reads
    over ``TIGHT_LIMIT``: the mappings' arithmetic in bfloat16, the
    dynamic term ``alpha (x^ phi)`` dropped, ``H_res`` transposed, one
    stream read at the end in the place of their sum. Averaging the
    streams (ISSUE 62's fourth case) *cannot* fail any limit: the final
    RMSNorm takes the scale back out, and it reads as the stated
    program does — recorded here so that nobody looks for it again."""
    params = float32_of(toy["params"])
    if fault is not None:
        params = fault(monkeypatch, params)
    prompts = prompts_of([5, 16, 37, 20], seed=4)
    verdict = against_the_reference(toy, TIGHT_LIMIT, prompts, 8,
                                    params=params)[0]
    assert verdict["ok"] is not fails, verdict


@pytest.mark.parametrize("arm", ["sinkhorn_5", "sinkhorn_19",
                                 "mappings_bfloat16"])
def test_a_defect_apart_from_the_references_is_not_correct(
        toy, monkeypatch, arm):
    """At the serving precision the logits see neither a shorter
    Sinkhorn iteration (five steps for twenty, or nineteen: twenty
    means twenty) nor the mappings' arithmetic in bfloat16 (all inside
    ``TOY_LIMIT``); the defects do: as stated the (sublayer, token)s'
    lie by the reference's own
    (``test_packed_prefill_matches_the_reference``), under each fault
    the family's whole verdict says not correct, by ``DEFECT_APART``."""
    import dataclasses
    family = mm.load_family("xing4")
    program = {}
    if arm.startswith("sinkhorn_"):
        program["cfg"] = dataclasses.replace(
            toy["cfg"], hc_sinkhorn_iters=int(arm.split("_")[1]))
    else:
        mappings_in_bfloat16(monkeypatch, None)
    prompts = prompts_of([5, 16, 37, 20], seed=4)
    verdict, refs, defects, _ = against_the_reference(
        toy, TOY_LIMIT, prompts, 8, **program)
    assert verdict["ok"], verdict
    apart = family.defects_apart(defects,
                                 [r["res_defect"] for r in refs])
    verdict = family.held_to_the_limits(TOY, verdict, {
        "route_shortfall_max": max(float(r["shortfall"].max())
                                   for r in refs),
        "res_defect_apart": apart})
    assert not verdict["ok"] and "H_res" in verdict["why"], verdict
    print(arm, apart)


def test_a_stream_one_wide_is_the_plain_residual_stack(toy):
    """``hc_mult`` 1, ``phi`` 0, ``b_pre`` 30 and ``b_post`` 0: ``h_pre``
    = sigmoid(30), ``h_post`` = 2 sigmoid(0) = 1, ``H_res`` = 1 by the
    first Sinkhorn step, and the forward is ``x = x + f(norm(x))`` — the
    loop of ``models/deepseek_v2/network.forward``, which runs here on
    the same parameters — to float32 rounding."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from rnb_tpu.models import token_stages
    from rnb_tpu.models.deepseek_v2 import network as plain
    from rnb_tpu.models.xing4 import checkpoint
    cfg = dataclasses.replace(toy["cfg"], hc_mult=1)
    params = float32_of(checkpoint.make_params(cfg, SEED, HELD,
                                               toy["device"]))

    def edit(t):
        for sub in ("attn", "ffn"):
            t[sub + "_hc_phi"] = jnp.zeros_like(t[sub + "_hc_phi"])
            t[sub + "_hc_bias"] = jnp.broadcast_to(
                jnp.asarray([30.0, 0.0, 0.7], jnp.float32),
                t[sub + "_hc_bias"].shape)
        return t
    params = edited(params, edit)
    prompts = prompts_of([5, 16, 37, 20], seed=4)
    got, chosen, _, _ = run_program(toy, prompts, 8, params=params, cfg=cfg)
    tokens, meta, _ = token_stages.pack_prompts(prompts, 8, Q)
    want, ids, *_ = jax.jit(lambda p, s, t, m: plain.forward(
        cfg, p, s, t, m[0], m[1], m[2], interpret=True))(
        params, toy["slots"], tokens, meta)
    want = np.asarray(want)[:len(prompts)]
    assert np.abs(got - want).max() < 1e-4 * want.std()


# -- the mappings -------------------------------------------------------------


def drawn_mappings(seed, tokens=96, spread=0.4, diagonal=1.0):
    """A pool's stream and one sublayer's weights at the toy widths,
    drawn so mildly that 20 Sinkhorn steps reach float32's end and 5 do
    not."""
    import jax.numpy as jnp
    n, wide = TOY["hc_mult"], TOY["hc_mult"] * TOY["hidden_size"]
    rng = np.random.default_rng(seed)
    bias = rng.standard_normal(2 * n + n * n) * spread
    bias[2 * n:] += diagonal * np.eye(n).reshape(-1)
    return {"x": jnp.asarray(rng.standard_normal((tokens, wide)),
                             jnp.float32),
            "phi": jnp.asarray(rng.standard_normal((wide, 2 * n + n * n))
                               * spread / np.sqrt(wide), jnp.float32),
            "alpha": jnp.ones(3, jnp.float32),
            "bias": jnp.asarray(bias, jnp.float32)}


def coefficients_of(drawn, **other):
    import jax

    from rnb_tpu.ops import hyper
    maps = dict(n=TOY["hc_mult"], iters=20, eps=TOY["rms_norm_eps"],
                hc_eps=TOY["hc_eps"], clamp=(-30.0, 30.0))
    maps.update(other)
    with jax.default_matmul_precision("highest"):
        coef, worst = hyper.coefficients(
            drawn["x"], drawn["phi"], drawn["alpha"], drawn["bias"], **maps)
    return np.asarray(coef), np.asarray(worst)


def reference_mappings(drawn, iters=None):
    """-> the reference's coefficients as the program lays them: (2n +
    n^2, tokens); and its defect."""
    import jax
    n = TOY["hc_mult"]
    w = {"hc_phi": drawn["phi"], "hc_alpha": drawn["alpha"],
         "hc_bias": drawn["bias"]}
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res, defect = reference.mappings(
            TOY, w, drawn["x"].reshape(len(drawn["x"]), n, -1), iters)
    return np.concatenate([np.asarray(h_pre), np.asarray(h_post), np.asarray(
        h_res).reshape(-1, n * n)], -1).T, np.asarray(defect)


def test_the_coefficients_are_the_references(toy):
    n = TOY["hc_mult"]
    drawn = drawn_mappings(62)
    want, ref_defect = reference_mappings(drawn)
    got, worst = coefficients_of(drawn)
    assert np.abs(got - want).max() < 1e-5
    # 20 steps means 20: 5 are not there yet, by more than the limit
    assert np.abs(reference_mappings(drawn, iters=5)[0] - want).max() > 1e-4
    assert np.abs(coefficients_of(drawn, iters=5)[0] - got).max() > 1e-4
    # doubly stochastic: rows and columns
    res = got[2 * n:].reshape(n, n, -1)
    assert np.abs(res.sum(0) - 1).max() < 1e-5
    assert np.abs(res.sum(1) - 1).max() < 1e-5
    assert np.abs(worst - np.maximum(np.abs(res.sum(0) - 1).max(0),
                                     np.abs(res.sum(1) - 1).max(0))
                  ).max() < 1e-6
    assert worst.max() < 1e-5 and ref_defect.max() < 1e-5
    assert (got[:n] > 0).all() and (got[:n] < 1).all()
    assert (got[n:2 * n] > 0).all() and (got[n:2 * n] < 2).all()


def test_the_clip_stands_in_front_of_exp(toy):
    """Two entries of one row of ``B_res`` at 40 and 35 and one at -45:
    with the clip both forms agree (the two read 30 alike), and the
    program with a wider one reads another matrix. (One entry alone
    beyond the clip shows nothing: the Sinkhorn steps scale it away.)"""
    import jax.numpy as jnp
    n = TOY["hc_mult"]
    drawn = drawn_mappings(63)
    bias = np.asarray(drawn["bias"]).copy()
    bias[2 * n + 1], bias[2 * n + 2], bias[2 * n + n + 2] = 40.0, 35.0, -45.0
    drawn["bias"] = jnp.asarray(bias)
    want, _ = reference_mappings(drawn)
    got, _ = coefficients_of(drawn)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5
    loose, _ = coefficients_of(drawn, clamp=(-80.0, 80.0))
    assert np.abs(loose - got).max() > 1e-3


def test_the_mixings_are_the_references(toy):
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import hyper
    n, c = TOY["hc_mult"], TOY["hidden_size"]
    drawn = drawn_mappings(64)
    coef, _ = coefficients_of(drawn)
    x = drawn["x"]
    y = jnp.asarray(np.random.default_rng(5).standard_normal(
        (x.shape[0], c)), jnp.float32)
    stream = np.asarray(x).reshape(-1, n, c)
    res = coef[2 * n:].T.reshape(-1, n, n)
    with jax.default_matmul_precision("highest"):
        u = hyper.mix_in(x, jnp.asarray(coef), n, jnp.float32)
        out = hyper.mix_out(x, y, jnp.asarray(coef), n)
        lines = hyper.leave_lines(x[:5], y[:5],
                                  hyper.token_major(coef)[:5], n)
        want_out = np.asarray(reference.way_out(
            jnp.asarray(stream), y, jnp.asarray(coef[n:2 * n].T),
            jnp.asarray(res)))
    want_u = np.einsum("ln,lnc->lc", coef[:n].T, stream)
    assert np.abs(np.asarray(u) - want_u).max() < 1e-5
    # H_res[i, j] is stream j's weight in new stream i
    assert np.abs(np.asarray(out).reshape(-1, n, c) - want_out).max() < 1e-5
    assert np.array_equal(np.asarray(lines), np.asarray(out)[:5])


def plain_enter(x, phi, alpha, bias, *, n, eps):
    """``hyper.enter`` composed from the module's plain statement."""
    import jax

    from rnb_tpu.ops import hyper
    logits = hyper.logits_of(x, phi, alpha, bias, n, eps)
    return hyper.mix_in(x, jax.nn.sigmoid(logits[:n]), n, x.dtype), \
        hyper.token_major(logits)


def plain_leave_enter(x, y, coef_tm, phi, alpha, bias, *, n, eps):
    """``hyper.leave_enter`` composed from the module's plain
    statement: the way out over the whole pool, then the way in."""
    from rnb_tpu.ops import hyper
    new = hyper.leave_lines(x, y, coef_tm, n)
    return (new,) + plain_enter(new, phi, alpha, bias, n=n, eps=eps)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_is_the_plain_statement(toy, dtype):
    """``enter`` and ``leave_enter`` — one Pallas kernel, interpreted —
    against their composition from ``logits_of``, ``mix_in`` and
    ``mix_out``, two tiles of tokens and two chunks of channels: the
    logits and the coefficients to float32 rounding, ``u`` and ``X'``
    to one rounding of the stream's dtype."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import hyper
    n, c, tokens = TOY["hc_mult"], 256, 2 * hyper.LANES
    rng = np.random.default_rng(7)
    dt = getattr(jnp, dtype)
    x = jnp.asarray(rng.standard_normal((tokens, n * c)), dt)
    y = jnp.asarray(rng.standard_normal((tokens, c)) * 0.3, jnp.float32)

    def weights():
        bias = rng.standard_normal(2 * n + n * n)
        bias[2 * n:] += 3 * np.eye(n).reshape(-1)
        return (jnp.asarray(rng.standard_normal((n * c, 2 * n + n * n))
                            / np.sqrt(n * c), dt),
                jnp.asarray([1.0, 0.7, 1.3], jnp.float32),
                jnp.asarray(bias, jnp.float32))
    first, second = weights(), weights()
    sizes = dict(n=n, eps=1e-6)

    def run(enter, leave_enter):
        with jax.default_matmul_precision("highest"):
            u, logits = enter(x, *first, **sizes)
            coef, worst = hyper.coefficients_from(
                logits, n=n, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))
            new, u2, logits2 = leave_enter(x, y, coef, *second, **sizes)
        return [np.asarray(a, np.float32) for a in
                (u, logits, coef, worst, new, u2, logits2)]
    monkey = pytest.MonkeyPatch()
    monkey.setattr(hyper, "_CHANNELS", 128)
    try:
        got = run(functools.partial(hyper.enter, interpret=True),
                  functools.partial(hyper.leave_enter, interpret=True))
    finally:
        monkey.undo()
    want = run(plain_enter, plain_leave_enter)
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 1e-5
    for name, g, w, tol in zip(
            ("u", "logits", "coef", "defect", "new", "u2", "logits2"),
            got, want, (ulp, 1e-5, 1e-5, 1e-5, ulp, ulp, 1e-3)):
        assert g.shape == w.shape, name
        assert np.abs(g - w).max() <= tol * (1 + np.abs(w).max()), name
    # the coefficients stand in front of whole lanes, zeros behind
    assert got[2].shape == (tokens, hyper.LANES)
    assert not got[2][:, 2 * n + n * n:].any()
    # a pool that is not whole rows of lanes is refused, by both steps
    with pytest.raises(ValueError):
        hyper.coefficients_from(jnp.zeros((96, hyper.LANES)), n=n, iters=20,
                                hc_eps=1e-6, clamp=(-30.0, 30.0))
    with pytest.raises(ValueError):
        hyper.enter(x[:96], *first, interpret=True, **sizes)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    from jax.experimental.compilation_cache import compilation_cache
    # what is compiled for a described chip is written to the
    # persistent cache and cannot be read back without one: off for
    # this test, and on again for whatever this worker runs next
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_kernel_compiles_for_the_chip_at_the_published_widths(one_chip):
    """Both of its shapes — the first sublayer's way in, and a way out
    with the next way in — at 8,192 tokens of 4 x 3,584, for a described
    v5e: a Mosaic call each, inside the scoped VMEM the module asks
    for, and no float32 copy of the stream beside it."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import hyper
    tokens, n, c = 8192, 4, 3584

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, y = of((tokens, n * c), jnp.bfloat16), of((tokens, c), jnp.float32)
    coef = of((tokens, hyper.LANES), jnp.float32)
    weights = (of((n * c, 2 * n + n * n), jnp.bfloat16),
               of((3,), jnp.float32), of((2 * n + n * n,), jnp.float32))
    for compiled in (
            jax.jit(lambda x, *w: hyper.enter(
                x, *w, n=n, eps=1e-6)).lower(x, *weights).compile(),
            jax.jit(lambda x, y, coef, *w: hyper.leave_enter(
                x, y, coef, *w, n=n, eps=1e-6)).lower(
                x, y, coef, *weights).compile()):
        text = compiled.as_text()
        assert "tpu_custom_call" in text and hyper.KERNEL_NAME in text
        assert "f32[8192,14336]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


# -- the recipe, the counters, the counts -------------------------------------


def test_the_seeded_draw_makes_the_mechanism_bite(toy):
    """alpha 1, ``B_res`` = 3 I + N(0, 1), phi N(0, 1 / (n C)) stored
    transposed; the reader hands the reference the published forms of
    the same stored values."""
    n, wide = TOY["hc_mult"], TOY["hc_mult"] * TOY["hidden_size"]
    layer = toy["params"]["l1"]
    assert layer["attn_hc_phi"].shape == (wide, 2 * n + n * n)
    assert layer["ffn_hc_alpha"].tolist() == [1.0, 1.0, 1.0]
    read = toy["read"]
    phi = np.asarray(read("l1.attn_hc_phi"))
    assert phi.shape == (wide, 2 * n + n * n)
    assert np.array_equal(phi, np.asarray(
        layer["attn_hc_phi"]).astype(np.float32))
    assert 0.8 < phi.std() * np.sqrt(wide) < 1.2
    biases = np.stack([np.asarray(read("l%d.%s_hc_bias" % (i, sub)))
                       for i in range(3) for sub in ("attn", "ffn")])
    res = biases[:, 2 * n:].reshape(-1, n, n)
    diagonal = res[:, np.arange(n), np.arange(n)]
    assert 2.0 < diagonal.mean() < 4.0
    assert abs((res.sum((1, 2)) - diagonal.sum(1)).mean()) < 3.0
    assert np.asarray(read("l1.b_corr")).shape == (8,)
    assert "b_corr" not in toy["params"]["l0"]


def test_a_largest_value_is_not_summed():
    """``stream_mix``'s second key over dispatches and over stage
    instances: the largest, where every other counter adds."""
    from rnb_tpu.telemetry import STAGE_COUNTERS, stage_counter_report
    row, = [r for r in STAGE_COUNTERS if r.counter == "stream_mix"]
    counted = None
    for count in ([10, 700], [12, 300], [8, 900]):
        counted = row.merge(counted, np.asarray(count))
    assert counted.tolist() == [30, 900]
    plain, = [r for r in STAGE_COUNTERS if r.counter == "gmm_rows"]
    assert plain.merge(np.asarray([3]), np.asarray([4])).tolist() == [7]
    snaps = [{"tokens_valid": 5, "tokens_shipped": 8,
              "stream_mix": np.asarray([30, 900])},
             {"tokens_valid": 5, "tokens_shipped": 8,
              "stream_mix": np.asarray([20, 400])}]
    lines, fields = stage_counter_report(snaps)
    assert lines == ["Tokens: valid=10 shipped=16 mixes=50 "
                     "res_defect_e9=900"]
    assert fields["tokens_mixes"] == 50
    assert fields["tokens_res_defect_e9"] == 900


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.xing4 import flops, network
    family = mm.load_family("xing4")
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.Xing4Config.from_published(family.published_keys(config))
    assert flops.flops_per_token(cfg, 512.0, 4.0) \
        == family.flops_per_token(config, 512.0, 4.0)
    assert flops.hyper_flops_per_token(cfg) \
        == family.hyper_flops_per_token(config)
    assert family.flops_per_row(config) == config["chunk_size"] \
        * flops.flops_per_token(cfg, family.mean_context(config), 4.0)
    # ISSUE 62's arithmetic: 71,680 B a token a sublayer, 7.05 GB and
    # 8.6 ms a full dispatch at 819 GB/s
    ops, nbytes = family.mechanism_work(config, "hyper", 8192.0, 1.0)
    assert nbytes == 12 * (8192 * 71_680 + 4 * 3584 * 24 * 2)
    assert abs(nbytes / 819e9 * 1e3 - 8.6) < 0.05
    assert ops / 1.97e14 < nbytes / 819e9
    # the kernel's share: eleven ways out with the next way in and the
    # first way in, which writes no stream; the last way out is not its
    mix_ops, mix_bytes = family.mechanism_work(config, "hyper_mix", 8192.0,
                                               1.0)
    assert mix_bytes == 8192 * (11 * 10 + 5) * 3584 * 2 \
        + 12 * 4 * 3584 * 24 * 2
    assert mix_bytes < nbytes and mix_ops < ops
    # the layer's mechanisms are the sibling's count, by either caller
    sibling = mm.load_family("deepseek_v2")
    as_layer = dict(config, published=dict(config["published"],
                                           n_routed_experts=64))
    for mechanism in ("attn", "flash", "experts", "gmm"):
        assert family.mechanism_work(config, mechanism, 1e6, 4e6, 125.0) \
            == sibling.mechanism_work(as_layer, mechanism, 1e6, 4e6, 125.0)
    with pytest.raises(ValueError):
        family.mechanism_work(config, "gmm", 1e6, 125.0)
    # about 1.09 GFLOP of products and 0.23 of scores a token
    assert 1.30e9 < family.flops_per_row(config) / 128 < 1.36e9


# -- through the one benchmark command ----------------------------------------


def toy_config():
    """A toy-width copy of the real configuration's file, of five
    layers: four expert layers are the floor of the family file's
    ``check_config`` (the tests above run ``TOY``'s three)."""
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    config.update(TOY, num_hidden_layers=5)
    config["model"] = dict(config["model"], layers=5)
    config["experts_held"] = {"first": 0, "count": 8}
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 24, "sigma": 0.8,
                                   "min": 4, "max": 60},
                         "long": {"count": 2, "min": 64, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 150
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    # a pool is whole rows of 128 lanes (``ops/hyper.py``): 8 rows of 16
    loader.update(max_rows=16, chunk=16)
    batcher.update(batch=16, shapes=[[16, 16], [16]], row_buckets=[8, 16])
    prefill.update(max_rows=16, chunk=16, row_buckets=[8, 16],
                   sample_every=5, samples=8)
    return config


def the_stage_counts_the_mixings(served):
    """``family_contract.stage_serves``'s entry for this family: the
    Tokens: line carries the mixings and the worst defect, a sample the
    defects of its own tokens."""
    from rnb_tpu.telemetry import stage_counter_report
    stage = served.stage
    counters = stage.stage_counters()
    mixes, defect = counters["stream_mix"]
    assert mixes == 2 * served.valid * 2 * TOY["num_hidden_layers"]
    lines, fields = stage_counter_report([counters])
    assert lines[0] == "Tokens: valid=%d shipped=%d mixes=%d " \
        "res_defect_e9=%d" % (2 * served.valid, 2 * 8 * Q, mixes, defect)
    assert fields["tokens_res_defect_e9"] == defect > 0
    first = stage._samples[0]
    assert first["res_defect"].shape == (2 * TOY["num_hidden_layers"], 20)
    # the run's worst is at least any sample's
    assert int(first["res_defect"].max() * 1e9) <= defect


CONTRACT = contract.Family(
    name="xing4", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", " mixes=", " res_defect_e9=",
          "Experts: assignments=", "Attention: tiles_visited="),
    meta_absent=(" group_tokens=",),
    scopes=("/hyper/maps/", "/hyper/in/", "/hyper/out/", "/attn/",
            "/experts/"),
    sample_fields=("tokens", "logits", "chosen", "res_defect"),
    sample_shapes={"chosen": (4,), "res_defect": (10,)},
    traced={
        "flash_tile_visit_pct.bulk": "[100, 100]",
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        # every expert held: every pair served here
        "held_assignment_pct.bulk": "[100, 100]",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "rows_per_dispatch.bulk": "(0, inf)",
        "hyper_res_defect_e9.bulk": "(0, 2e9)"},
    not_from_a_cpu="roofline|util|mla_proj|hyper_ms|hyper_maps",
    stage=contract.Stage(
        # a pool is whole rows of 128 lanes: one bucket of 8 rows of 16
        lengths=(20, 9, 30), row_buckets=(8,),
        scopes=("/hyper/maps/", "/hyper/in/", "/hyper/out/", "/attn/",
                "/experts/", "/head/"),
        chosen_shape=(2, 20, 2), dispatches=2,
        also=the_stage_counts_the_mixings),
    # as stated inside the limits; the dynamic term dropped, one stream
    # read and the matrices through float8 outside the logits'; five
    # Sinkhorn steps and the mappings in bfloat16 inside the logits' and
    # outside the defects'
    control=contract.Control(
        lengths="120,37,70",
        outside=("no_dynamic_term", "one_stream_read", "sinkhorn_5",
                 "sinkhorn_19", "mappings_bfloat16", "experts_float8",
                 "layers_float8"),
        reads={("as_stated", "res_defect_apart"): "[0, 0.1)",
               ("sinkhorn_5", "share_of_spread"): "[0, 0.03)",
               ("sinkhorn_19", "share_of_spread"): "[0, 0.03)",
               ("mappings_bfloat16", "share_of_spread"): "[0, 0.03)",
               ("sinkhorn_19", "res_defect_apart"): "(0.1, inf)",
               ("sinkhorn_5", "res_defect_apart"): "(10, inf)",
               ("mappings_bfloat16", "res_defect_apart"): "(10, inf)"}))


# -- the five new readers -----------------------------------------------------

NEW_READERS = ("hyper_ms_per_dispatch.bulk", "hyper_roofline_pct.bulk",
               "hyper_maps_ms_per_dispatch.bulk",
               "hyper_res_defect_e9.bulk", "hyper_mix_roofline_pct.bulk")


class Result:
    tokens_valid = 100
    pad_emissions = 2
    tokens_mixes = 0
    tokens_res_defect_e9 = 0


def facts_of(tmp_path, family="xing4", **counted):
    class Facts:
        trace = None
        result = type("R", (Result,), dict(counted, log_dir=str(tmp_path)))
        config = json.load(open(os.path.join(REPO, REAL)))
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    Facts.family = mm.load_family(family)
    return Facts


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_on_a_run_without_its_source(
        name, tmp_path):
    """No trace, no counter (the parent's programs have neither the
    scopes nor the counter): None, not a raise; and the manifest repeats
    what the file declares."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "residual stream"
    assert module.read(facts_of(tmp_path)) is None
    # an older family's file counts no such mechanism: nothing
    assert module.read(facts_of(tmp_path, "deepseek_v2")) is None
    assert module.read(facts_of(tmp_path, "falcon_h1")) is None


def test_the_counter_reads_through_its_reader(tmp_path):
    module = mm.load_layer_metric("hyper_res_defect_e9.bulk")
    facts = facts_of(tmp_path, tokens_mixes=1200,
                     tokens_res_defect_e9=31_000_000)
    assert module.read(facts) == 31_000_000.0


def test_the_cell_joins_the_lists_its_sibling_stands_in():
    """``xing4.bulk`` stands last in every list ``deepseek-v2.bulk`` is
    in but ``group_token_pct.bulk`` (one group here), and its five
    readers stand at the end of the manifest."""
    per_layer = mm.load()["per_layer"]
    for m in per_layer:
        listed = m.get("workloads", ())
        if "deepseek-v2.bulk" in listed \
                and m["name"] != "group_token_pct.bulk":
            assert listed[-1] == CELL, m["name"]
        elif m["name"] not in NEW_READERS:
            assert CELL not in listed, m["name"]
    assert tuple(m["name"] for m in per_layer[-5:]) == NEW_READERS


# -- the real configuration ---------------------------------------------------


def test_real_configuration_keeps_the_published_sizes():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    # the catalog's row, where this machine has the guide
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f
                    if "Xing4.0-29B-A4B" in line]
    entry = mm.config_entry(mm.load(), "xing4-29b-ep1")
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "first_k_dense_replace"]
    assert entry["source"] == config["source"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "first_k_dense_replace": 2}
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    for row in rows:
        assert row["config"] == PUBLISHED and row["source_url"] \
            == config["source"]
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] \
        >= 4
    assert config["experts_held"] == {"first": 0, "count": 64}
    assert config["deployment"] and config["assumed"]["mtp"]
    for key in ("hc_eps", "clip", "sinkhorn_order", "res_orientation",
                "stream_norm", "alpha", "streams_at_the_end"):
        assert "not checked against the modelling code" \
            in config["assumed"][key], key
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "deepseek-v2-ep8.json")) as f:
        sibling = json.load(f)
    assert config["dataset"] == sibling["dataset"]
    assert config["pipeline_config"]["pipeline"][1] \
        == sibling["pipeline_config"]["pipeline"][1]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    # the weights the file states, from the tensor list
    from rnb_tpu.models import seeded
    from rnb_tpu.models.xing4 import checkpoint, network
    cfg = network.Xing4Config.from_published(family.published_keys(config))
    held = sum(int(np.prod(seeded.published_shape(spec))) for tensors in
               checkpoint.tensor_specs(cfg, 64).values()
               for spec in tensors.values())
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 0.01
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01


PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
