"""The Phi-4-mini-flash family against its plain reference, at a toy
size on the CPU with weights from a seed (kernels interpreted): the
packed prefill *with the exit* through dispatches with several requests,
boundaries inside the pool and pad rows, against a reference that runs
every layer over every position; packing that is invisible; the exit in
the traced program (the cross-decoder's products have ``rows`` lines,
not ``rows x Q``) and in its counter; each fault arm of the control
script seen by the comparison; the layers' law at 8, 12 and 32 layers;
the recipe's values and spreads; the operation counts, the 3,852 M
parameters and the four counts a layer kind; the real configuration
against the catalog's row. The toy keeps what makes the shape: 8 layers
(every one of the five kinds once or more), heads of 64 paired into 128
lanes, two query pairs a key-value pair, 16 states a channel, a window
shorter than the prompts. Then the family's record for
``family_contract.py``, by which ``test_phi4_flash_cell.py`` runs the
stage, the control script and the cell, and the four new readers with
and without their sources. Nothing here needs the native decode library
or a chip."""

import contextlib
import json
import os
import sys
from unittest import mock

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import phi4_flash as reference  # noqa: E402

REAL = "benchmarks/configs/phi4-mini-flash.json"
CELL = "phi4-flash.bulk"
SEED = 3_000_000_159

#: the catalog's ``config`` of Phi-4-mini-flash-reasoning
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
#: the Mamba mixer's sizes the configuration's file adds (``assumed``)
MAMBA = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}

#: 8 layers at toy widths — mamba, window, mamba, window, mamba (the
#: memory's), full, gmu, cross — 4 query / 2 key-value heads of 64 (two
#: query pairs on one key-value pair), 512 channels of 16 states, a
#: window of 24 under rows of 16, an MLP of 512
TOY = dict(
    PUBLISHED, **MAMBA, num_hidden_layers=8, hidden_size=256,
    vocab_size=512, chunk_size=16, num_attention_heads=4,
    num_key_value_heads=2, sliding_window=24, intermediate_size=512)
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths, between two readings over
#: three seeds of weights and the three dispatches below: as stated
#: 2.0-2.9% of the spread, every layer's matrices through float8
#: 12.7-19.0% (the root mean square 0.65-0.85% for 3.4-4.5%)
TOY_LIMIT = 0.05


def judged(got, want):
    """The family's comparison: the worst logit under ``TOY_LIMIT`` and
    the root mean square under the family's own limit, which the toy
    widths keep."""
    return mm.load_family("phi4_flash").compare_logits(
        {"share_of_spread": TOY_LIMIT}, got, want)


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.phi4_flash import checkpoint, network
    cfg = network.Phi4FlashConfig.from_published(TOY)
    device = jax.devices()[0]
    return {"cfg": cfg, "device": device,
            "params": checkpoint.make_params(cfg, SEED, (), device),
            "read": checkpoint.reference_reader(cfg, SEED, device),
            "reference": reference.Reference(TOY)}


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def pack(prompts, rows):
    from rnb_tpu.models import token_stages
    return token_stages.pack_prompts(prompts, rows, Q)


_PROGRAMS = {}


def run_program(toy, prompts, rows, params=None, patch=None, **kwargs):
    """-> (logits a prompt, the counters). ``patch``: (a name for the
    program, attributes of ``network`` replaced while it is traced)."""
    import jax

    from rnb_tpu.models.phi4_flash import network
    cfg = toy["cfg"]
    tokens, meta, _ = pack(prompts, rows)
    key = (rows, patch and patch[0],
           tuple(sorted((k, str(v)) for k, v in kwargs.items())))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(
            lambda p, t, m: network.forward(
                cfg, p, None, t, m[0], m[1], m[2], interpret=True,
                **kwargs))
    with mock.patch.multiple(network, **patch[1]) if patch \
            else contextlib.nullcontext():
        logits, chosen, *counts = _PROGRAMS[key](
            toy["params"] if params is None else params, tokens, meta)
    assert chosen.shape == (0, rows * Q)
    return np.asarray(logits)[:len(prompts)], \
        [np.asarray(c) for c in counts]


#: the reference runs every prompt padded to this many tokens behind its
#: last (every mixer is causal), so that it compiles one length
REF_LENGTH = 256


def run_reference(toy, prompt):
    import jax
    with jax.default_matmul_precision("highest"):
        out = toy["reference"].forward(
            toy["read"], np.pad(prompt, (0, REF_LENGTH - len(prompt))),
            position=len(prompt) - 1)
    return np.asarray(out["logits"])


def references_of(toy, prompts):
    return np.stack([run_reference(toy, p) for p in prompts])


def through_float8(params):
    """The stored matrices of every layer rounded through float8 (e4m3):
    the nearest precision below the one the configuration states (by
    name: a stacked group's vectors have two axes too)."""
    import jax.numpy as jnp
    from prefill_control import PHI4_FLASH_MATRICES as matrices
    return {group: ({name: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                            if name.split(".")[-1] in matrices else w)
                     for name, w in block.items()}
                    if isinstance(block, dict) else block)
            for group, block in params.items()}


# -- the whole stack ------------------------------------------------------

#: dispatches of 16 rows: several requests, one that ends inside a row,
#: one that fills its rows, pad rows behind; one request over the pool
#: (ten windows long)
DISPATCHES = {"three": [120, 37, 70], "whole_rows": [16, 96, 5, 64],
              "one_long": [250]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_with_the_exit_matches_every_layer_everywhere(
        toy, case):
    """The program runs layers 6 and 7 on one line a request; the
    reference runs all 8 over every position: their agreement is the
    test of the exit."""
    prompts = prompts_of(DISPATCHES[case], seed=4)
    logits, (tiles, resets, window_keys, lines) = run_program(
        toy, prompts, 16)
    verdict = judged(logits, references_of(toy, prompts))
    assert verdict["ok"], verdict
    # the full layer's flash tiles, the rows that open a request, the
    # two window layers' pairs, and the lines through the cross-decoder:
    # the requests served
    assert tiles.shape == (1, 2) and (tiles == 1).all()
    assert resets.tolist() == lines.tolist() == [len(prompts)]
    at = np.concatenate([np.arange(len(p)) for p in prompts]) + 1
    assert window_keys.tolist() == [
        [int(np.minimum(at, 24).sum()), int(at.sum())]] * 2


def test_packing_is_invisible_and_states_and_windows_restart(toy):
    """A prompt's logits depend neither on what shares its dispatch, nor
    on where in the pool it lies, nor on the bucket: the scans' states,
    the convolutions' history, the windows and the cross-decoder's keys
    begin at its first token."""
    a, b, c, d = prompts_of([100, 5, 70, 20])
    alone, _ = run_program(toy, [a], 8)
    packed, _ = run_program(toy, [b, c, a, d], 16)
    other, _ = run_program(toy, [d, a], 16)
    spread = float(run_reference(toy, a).std())
    # not bit for bit: the band's blocks and the flash kernel's tiles
    # differ with the pool
    assert np.abs(packed[2] - alone[0]).max() < 0.005 * spread
    assert np.abs(other[1] - alone[0]).max() < 0.005 * spread


def test_the_cross_decoder_runs_on_one_line_a_request(toy):
    """In the traced program every product of the cross-decoder (the
    scope ``cross``) has ``rows`` lines, never ``rows x Q``, and its
    attention reads the full layer's keys, a product against all the
    pool's tokens with ``rows`` queries; the layers in front of it run
    over the pool."""
    import jax

    from rnb_tpu.models.phi4_flash import network
    rows = 12        # rows x Q is no width of the toy
    tokens, meta, _ = pack(prompts_of([120, 37]), rows)
    jaxpr = jax.make_jaxpr(lambda p, t, m: network.forward(
        toy["cfg"], p, None, t, m[0], m[1], m[2], interpret=True))(
        toy["params"], tokens, meta)
    cross, pool = [], []

    def walk(inner, scope):
        for eqn in inner.eqns:
            at = scope + "/" + str(eqn.source_info.name_stack)
            if eqn.primitive.name == "dot_general":
                (cross if "/cross" in at else pool).append(
                    [v.aval.shape for v in eqn.invars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, at)
    walk(jaxpr.jaxpr, "")
    # a pair's body: the unit's two products, the attention's query,
    # scores, values and output products, two MLPs of three
    assert len(cross) == 12 and pool
    for pair in cross:
        # the activations' operand has one line a row of the dispatch
        assert any(shape[0] == rows for shape in pair), pair
        assert not any(shape[:2] == (rows, Q) for shape in pair), pair
    # one query a line against all the pool's tokens: the scores' and
    # the values' products
    assert sum(any(shape[0] == rows * Q for shape in pair)
               for pair in cross) == 2
    # the stream's products in front of the exit: (rows, Q, hidden)
    assert sum(left == (rows, Q, 256) for left, _ in pool) >= 8


ARMS = ("one_softmax", "window_off", "memory_gated")


@pytest.fixture(scope="module")
def stated(toy):
    prompts = prompts_of(DISPATCHES["three"], seed=4)
    want = references_of(toy, prompts)
    logits, _ = run_program(toy, prompts, 16)
    assert judged(logits, want)["ok"]
    return prompts, want, logits


@pytest.mark.parametrize("arm", ARMS)
def test_a_fault_arm_is_seen(toy, stated, arm):
    """Each fault the control script plants (``lambda P2 V`` dropped,
    the sliding layers reading the whole context, the Gated Memory Units
    reading the gated output) reads many times the limit: over three
    seeds 48-67%, 85-94% and 15-23% of the spread."""
    from prefill_control import phi4_flash_faults
    prompts, want, _ = stated
    logits, _ = run_program(toy, prompts, 16, patch=(
        arm, phi4_flash_faults()[arm]["patch"]))
    verdict = judged(logits, want)
    assert not verdict["ok"] and verdict["share_of_spread"] > 0.1, verdict


def test_the_lower_precision_controls(toy, stated):
    """Every layer's stored matrices through float8 (the nearest
    precision below the stated one) reads outside both limits. The
    scans' states carried in bfloat16 between rows move the logits and
    do not discriminate at this depth, as in three families (the kernel
    rounds a state once a row of tokens): recorded, and float8 decides."""
    import jax.numpy as jnp
    prompts, want, logits = stated
    eighth, _ = run_program(toy, prompts, 16,
                            params=through_float8(toy["params"]))
    verdict = judged(eighth, want)
    assert verdict["share_of_spread"] > 2 * verdict["limit"] \
        and verdict["limit"] == TOY_LIMIT
    assert verdict["rms_share_of_spread"] > 2 * verdict["rms_limit"]
    rounded, _ = run_program(toy, prompts, 16, state_dtype=jnp.bfloat16)
    moved = np.abs(rounded - logits).max() / want.std()
    assert 1e-4 < moved < TOY_LIMIT, moved


@pytest.mark.parametrize("layers,law", [
    (8, "mwmwmfgc"), (12, "mwmwmwmfgcgc"),
    (32, "mwmwmwmwmwmwmwmwmfgcgcgcgcgcgcgc")])
def test_the_layers_law(layers, law):
    """``mb_per_layer`` 2: even layers Mamba up to n/2 (the memory's),
    odd ones under the window below it, the full layer at n/2 + 1, then
    Gated Memory Units and cross layers; program and reference alike."""
    from rnb_tpu.models.phi4_flash import checkpoint, network
    config = dict(TOY, num_hidden_layers=layers)
    cfg = network.Phi4FlashConfig.from_published(config)
    kinds = [cfg.kind(i) for i in range(layers)]
    letters = {"m": "mamba", "w": "window", "f": "full", "g": "gmu",
               "c": "cross"}
    assert kinds == [letters[c] for c in law]
    assert kinds == [reference.kind_of(config, i) for i in range(layers)]
    assert cfg.memory_layer == layers // 2 \
        and cfg.key_layer == layers // 2 + 1
    for i in range(layers):
        assert cfg.lambda_init(i) == reference.lambda_init(i)
    # every layer lies in one group, the stacks in order
    where = [checkpoint._where(cfg, i) for i in range(layers)]
    assert [w[0] for w in where] == \
        ["pairs"] * (layers // 2) + ["l%d" % (layers // 2),
                                     "l%d" % (layers // 2 + 1)] \
        + ["cross"] * (layers // 2 - 2)
    assert [w[2] for w in where if w[0] == "pairs"] \
        == [i // 2 for i in range(layers // 2)]
    for bad in (6, 10, 4):
        with pytest.raises(ValueError):
            network.Phi4FlashConfig.from_published(
                dict(TOY, num_hidden_layers=bad))


def test_recipe_gives_program_and_reference_the_same_values(toy):
    params, read = toy["params"], toy["read"]
    for name, tensor in (
            ("l0.in_proj", params["pairs"]["m.in_proj"][0]),
            ("l3.qkv", params["pairs"]["a.qkv"][1]),
            ("l2.a_log", params["pairs"]["m.a_log"][1]),
            ("l4.dt_bias", params["l4"]["dt_bias"]),
            ("l5.lq1", params["l5"]["lq1"]),
            ("l6.g_in", params["cross"]["g.g_in"][0]),
            ("l7.q", params["cross"]["c.q"][0]),
            ("l7.gate_up", params["cross"]["c.gate_up"][0]),
            ("top.final_norm_b", params["final_norm_b"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name))), name
    # the embedding by a prompt's rows and, for the tied head, by a
    # block of rows
    at = np.array([5, 0, 511, 5])
    assert np.array_equal(np.asarray(read("top.embed", at)),
                          np.asarray(params["embed"], np.float32)[at])
    assert np.array_equal(np.asarray(read("top.embed", slice(64, 128))),
                          np.asarray(params["embed"], np.float32)[64:128])
    # two layers of a stack are two draws
    assert not np.array_equal(np.asarray(params["pairs"]["a.qkv"][0]),
                              np.asarray(params["pairs"]["a.qkv"][1]))
    assert not np.array_equal(np.asarray(params["pairs"]["m.ln1_b"][0]),
                              np.asarray(params["pairs"]["a.ln1_b"][0]))


def test_the_draw():
    """At a wider toy, so that a column's spread is measured: matrices
    at the other families' spread, the keys' columns at ``KEY_GAIN``,
    Mamba's own initialisation."""
    import jax

    from rnb_tpu.models.phi4_flash import checkpoint, network
    cfg = network.Phi4FlashConfig.from_published(
        dict(TOY, hidden_size=512, num_attention_heads=8,
             num_key_value_heads=4))
    params = checkpoint.make_params(cfg, 7, (), jax.devices()[0])
    mamba = {k: np.asarray(v, np.float32) for k, v in params["l4"].items()}
    full = {k: np.asarray(v, np.float32) for k, v in params["l5"].items()}
    d, back = cfg.hidden_size, np.sqrt(8.0)

    def unit(w, times=1.0, fan_in=d):
        return np.std(w) * times * np.sqrt(fan_in)
    assert abs(unit(mamba["in_proj"]) - 1) < 0.05
    assert abs(unit(mamba["out_proj"], back, cfg.d_inner) - 1) < 0.05
    assert abs(unit(mamba["gate_up"]) - 1) < 0.05
    assert abs(unit(mamba["down"], back, cfg.intermediate_size) - 1) < 0.05
    hq, hk, _ = cfg.qkv_parts
    assert abs(unit(full["qkv"][:, :hq]) - 1) < 0.05
    assert abs(unit(full["qkv"][:, hq:hq + hk]) - checkpoint.KEY_GAIN) < 0.1
    assert abs(unit(full["qkv"][:, hq + hk:]) - 1) < 0.05
    assert abs(unit(full["o"], back, hq) - 1) < 0.05
    assert abs(np.std(np.asarray(params["embed"], np.float32)) - 1) < 0.05
    assert (mamba["d"] == 1).all() and (full["sub_w"] == 1).all()
    assert np.allclose(np.exp(mamba["a_log"]),
                       np.tile(np.arange(1, 17), (cfg.d_inner, 1)))
    steps = np.log1p(np.exp(mamba["dt_bias"]))
    assert 0.001 <= steps.min() < 0.003 and 0.04 < steps.max() <= 0.1001
    assert abs(np.std(full["lq1"]) - 0.1) < 0.03
    assert 0.05 < np.std(full["qkv_b"]) < 0.15


# -- operations, bytes and sizes ------------------------------------------


def real_config():
    with open(os.path.join(REPO, REAL)) as f:
        return json.load(f)


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.phi4_flash import checkpoint, flops, network
    family = mm.load_family("phi4_flash")
    config = real_config()
    cfg = network.Phi4FlashConfig.from_published(
        family.published_keys(config))
    # ISSUE 59's arithmetic: 3.85 B parameters, and a layer of each kind
    # (its two norms' 10,240 and its MLP's 78,643,200 with it)
    kinds = {cfg.kind(i): checkpoint.layer_params(cfg, i)
             for i in range(32)}
    mlp = 3 * 2560 * 10240 + 4 * 2560
    assert kinds["mamba"] - mlp == 41_241_600
    assert kinds["window"] - mlp == kinds["full"] - mlp == 19_668_864
    assert kinds["cross"] - mlp == 13_112_704
    assert kinds["gmu"] - mlp == 26_214_400
    held = checkpoint.total_params(cfg)
    assert held == 9 * kinds["mamba"] + 9 * kinds["window"] \
        + 7 * kinds["cross"] + 7 * kinds["gmu"] + 200_064 * 2560 + 2 * 2560
    assert held // 10 ** 6 == 3852
    specs = checkpoint.tensor_specs(cfg)
    assert held == sum(int(np.prod(spec.shape)) for tensors in
                       specs.values() for spec in tensors.values())
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01
    assert abs(held / 1e9 - config["model"]["params_billions_held"]) < 5e-4
    # the two counts, term by term
    for name in ("mlp_flops", "scan_flops_per_token",
                 "mamba_flops_per_token", "pair_flops"):
        assert getattr(flops, name)(cfg) == getattr(family, name)(config)
    assert flops.pair_flops(cfg) == 40 * 384
    assert flops.scan_flops_per_token(cfg) == 5120 * (7 * 16 + 2)
    assert flops.attention_flops_per_token(cfg, 700.0) \
        == family.attention_flops_per_token(config, 700.0)
    assert flops.flops_per_token(cfg, 5700.0, 499.0) \
        == family.flops_per_token(config, 5700.0, 499.0)
    assert flops.flops_per_request(cfg, 10300.0) \
        == family.flops_per_request(config, 10300.0)
    # the exit's path: 18 layers a token, 14 a request's line
    tokens, rows = family.mean_request(config)
    assert family.flops_per_row(config) == int(
        128 * flops.flops_per_token(cfg, family.mean_context(config),
                                    family.mean_window_keys(config))
        + flops.flops_per_request(cfg, tokens) / rows)
    per_token = family.flops_per_row(config) / 128
    assert 3.9e9 < per_token < 4.3e9
    # all 32 layers a token would be 1.7 times that
    every = per_token + 14 * family.mlp_flops(config)
    assert 1.5 < every / per_token < 1.9
    assert abs(family.mean_context(config) - 5700) < 150
    assert 495 < family.mean_window_keys(config) < 512
    assert family.layer_counts(config) == (9, 8, 7)
    # the mechanisms the readers count, by hand
    tokens, dispatches = 1e6, 60.0
    ops, nbytes = family.mechanism_work(config, "selective_scan", tokens,
                                        dispatches)
    assert ops == 9 * tokens * 5120 * (7 * 16 + 2)
    assert nbytes == 9 * tokens * (5120 * 10 + 2 * 4 * 16)
    ops, nbytes = family.mechanism_work(config, "window_attn", tokens,
                                        dispatches)
    assert ops == 8 * tokens * family.mean_window_keys(config) * 15360
    assert nbytes == 8 * tokens * 2 * (2 * 2560 + 2 * 1280)
    ops, nbytes = family.mechanism_work(config, "diff_attn", tokens,
                                        dispatches)
    assert ops == tokens * family.mean_context(config) * 15360
    assert nbytes == tokens * 2 * (2 * 2560 + 2 * 1280)
    ops, nbytes = family.mechanism_work(config, "mlp", tokens, dispatches)
    assert ops == 18 * tokens * 6 * 2560 * 10240
    assert nbytes == 18 * (6 * 2560 * 10240 * dispatches
                           + 4 * 2560 * tokens)
    ops, nbytes = family.mechanism_work(config, "conv", tokens, dispatches)
    assert ops == 9 * tokens * 5120 * 13
    assert nbytes == 9 * tokens * 5120 * 4
    with pytest.raises(ValueError):
        family.mechanism_work(config, "experts", tokens, dispatches)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Phi-4-mini-flash-reasoning":
                return row
    return None


def test_real_configuration_is_the_published_one_whole():
    config = real_config()
    entry = mm.config_entry(mm.load(), "phi4-mini-flash")
    assert entry["reduced"] == config["reduced"] == []
    assert config["published"] == {}
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    for key, value in MAMBA.items():
        assert config[key] == value, key
    row = catalog_row()
    if row is None:
        pytest.skip("no catalog beside the model-configs guide here")
    assert row["source_url"] == config["source"]
    assert row["config"] == PUBLISHED
    for key in ("chunk_size", "mamba", "layers", "attention", "norms",
                "mlp", "weights"):
        assert config["assumed"][key], key
        if key not in ("chunk_size", "weights"):
            assert "NOT CHECKED" in config["assumed"][key], key
    assert "one chip holds the model whole" in config["deployment"]
    assert 4 * 2 ** 30 <= config["size_record"]["projected_gib"] * 2 ** 30 \
        <= 14 * 2 ** 30
    assert config["capacity_why"] and config["capacity_videos_per_chip_s"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    assert family.check_config(dict(config, num_hidden_layers=30)) != []
    assert family.check_config(dict(config, mamba_d_state=32)) != []
    cell = mm.cell(mm.load(), CELL)
    assert cell["config"] == "phi4-mini-flash" and cell["chips"] == 1 \
        and cell["traffic"] == "bulk"
    # qwen3-next-l4-ep2's dataset block to the letter and
    # kimi-linear-l5-ep2's pipeline with this family: the long-prompt
    # cells are measured on the same lengths
    with open(os.path.join(
            REPO, "benchmarks/configs/qwen3-next-l4-ep2.json")) as f:
        sibling = json.load(f)
    assert sibling["dataset"] == config["dataset"]
    assert sibling["runtime_env"] == config["runtime_env"]
    with open(os.path.join(
            REPO, "benchmarks/configs/kimi-linear-l5-ep2.json")) as f:
        kimi = json.load(f)["pipeline_config"]["pipeline"]
    mine = config["pipeline_config"]["pipeline"]
    assert mine[:2] == kimi[:2]
    assert dict(mine[2], family=None, sample_every=None) \
        == dict(kimi[2], family=None, sample_every=None)
    assert mine[2]["family"] == "phi4_flash"
    lengths = family.prompt_lengths(config)
    assert min(lengths.values()) >= 4096 \
        and max(lengths.values()) <= 16384
    # and another vocabulary: its request files are its own
    assert family.dataset_key(config) != family.dataset_key(sibling)


def toy_config():
    """A toy-width copy of the real configuration's file."""
    config = real_config()
    config.update(TOY)
    from rnb_tpu.models.phi4_flash import checkpoint, network
    held = checkpoint.total_params(
        network.Phi4FlashConfig.from_published(TOY))
    config["model"] = dict(config["model"], layers=8,
                           params_billions_held=held / 1e9)
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 60, "sigma": 0.5,
                                   "min": 20, "max": 100},
                         "long": {"count": 2, "min": 100, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    # (``left_share`` 0.58 on an idle machine, PR 61: a scan step of all
    # the channels is a fifth of the interpreter's grid steps, and the
    # 12 of PR 59 was gone before the window opened)
    config["capacity_videos_per_chip_s"] = 40
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=3, samples=8)
    return config


def the_stage_counts_tiles_resets_keys_and_lines(served):
    """``family_contract.stage_serves``'s entry for this family: the
    full layer's tiles, the rows that open a request, the windows' pairs
    and the cross-decoder's lines, and no expert."""
    from rnb_tpu.telemetry import stage_counter_report
    counters, valid = served.stage.stage_counters(), served.valid
    # one full layer's tiles, two dispatches; three requests a dispatch
    assert counters["attn_tiles"].tolist() == [2, 2]
    assert counters["scan_resets"].tolist() == [6]
    assert counters["cross_lines"].tolist() == [6]
    at = np.concatenate([np.arange(n) for n in (80, 9, 30)]) + 1
    kept, causal = 4 * int(np.minimum(at, 24).sum()), 4 * int(at.sum())
    assert counters["window_keys"].tolist() == [kept, causal]
    lines, fields = stage_counter_report([counters])
    assert lines == [
        "Tokens: valid=%d shipped=%d scan_resets=6 cross_lines=6"
        % (2 * valid, 16 * Q),
        "Attention: tiles_visited=2 tiles_causal=2 window_keys_kept=%d "
        "window_keys_causal=%d" % (kept, causal)]
    assert fields["tokens_cross_lines"] == 6
    assert served.stage._samples[0]["logits"].shape \
        == (TOY["vocab_size"],)


#: ``tests/test_phi4_flash_cell.py`` runs it
CONTRACT = contract.Family(
    name="phi4_flash", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED),
    meta=("Tokens: valid=", " scan_resets=", " cross_lines=", "Attention:",
          " window_keys_kept="),
    meta_absent=("Experts:", "Sparse:"),
    scopes=("/ssd/scan/", "/ssd/conv/", "/attn/window/kernel/",
            "/attn/full/kernel/", "/mlp/", "/cross/", "/xattn/", "/gmu/",
            "/xmlp/", "/head/"),
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "window_key_pct.bulk": "(0, 100)",
        "cross_line_pct.bulk": "(0, 10)"},
    not_from_a_cpu="roofline|util|busy_pct|ms_per_dispatch",
    stage=contract.Stage(
        lengths=(80, 9, 30), row_buckets=(8,), dispatches=2,
        scopes=("/embed/", "/ssd/", "/ssd/conv/", "/ssd/scan/",
                "/attn/window/", "/attn/full/", "/mlp/", "/cross/",
                "/head/"),
        chosen_shape=(0, 80),
        also=the_stage_counts_tiles_resets_keys_and_lines),
    # as stated inside both limits; every layer's matrices through
    # float8 and each planted fault outside one; the scans' states
    # through bfloat16 reported and free to pass
    # (three of the five arms through the script, a program each: the
    # other two, ``window_off`` and ``state_bfloat16``, are held in
    # process above, and the script's run stays under a minute and a
    # half)
    control=contract.Control(
        lengths="120,37,70", arms="one_softmax,memory_gated,layers_float8",
        outside=("layers_float8", "one_softmax", "memory_gated"),
        reads={("one_softmax", "share_of_spread"): "(0.1, inf)",
               ("memory_gated", "share_of_spread"): "(0.1, inf)"},
        may_pass=("state_bfloat16",)))


# -- the four new readers and the lists -----------------------------------

NEW_READERS = {
    "selective_scan_roofline_pct.bulk": "state-space scan",
    "diff_attn_roofline_pct.bulk": "packed attention",
    "cross_decoder_ms_per_dispatch.bulk": "network",
    "cross_line_pct.bulk": "network"}
#: the accepted readers whose lists gained the cell: each read a number
#: in the cell's traced run on the chip (PR 59)
LISTED = (
    "host_cores_busy", "rows_per_dispatch", "pad_row_pct",
    "pad_row_traced_pct", "pad_token_pct", "tokens_per_s",
    "net_flops_util_pct", "net_roofline_pct", "device_idle_pct",
    "hbm_peak_gib", "mlp_busy_pct", "mlp_roofline_pct", "ssd_busy_pct",
    "attn_busy_pct", "ssd_scan_ms_per_dispatch",
    "segment_conv_ms_per_dispatch", "window_attn_ms_per_dispatch",
    "full_attn_ms_per_dispatch", "window_attn_roofline_pct",
    "window_key_pct", "flash_tile_visit_pct", "scan_resets_per_dispatch")


def test_the_accepted_readers_list_the_cell_last():
    manifest = mm.load()
    by_name = {m["name"]: m for m in manifest["per_layer"]}

    def last_of_its_pr(workloads):
        """The cell stands last but for the cells later PRs appended
        (PR 62's ``xing4.bulk``)."""
        return set(workloads[workloads.index(CELL) + 1:]) <= {"xing4.bulk"}
    for name in LISTED:
        assert last_of_its_pr(by_name[name + ".bulk"]["workloads"]), name
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", ())
              and m["moves"] == "videos_per_s"}
    assert listed == {n + ".bulk" for n in LISTED} | set(NEW_READERS)
    setup = [n for n, m in by_name.items() if m["moves"] == "setup_s"]
    assert len(setup) == 8
    for name in setup:
        assert last_of_its_pr(by_name[name]["workloads"]), name
    # this PR's four stand last but for PR 62's four, in the order of
    # ``NEW_READERS``
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(next(iter(NEW_READERS)))
    assert names[at:at + len(NEW_READERS)] == list(NEW_READERS)
    assert all(by_name[n]["workloads"] == ["xing4.bulk"]
               for n in names[at + len(NEW_READERS):])
    # a reader that finds nothing in the cell does not list it: another
    # family's kernel by name, the r34's transfers, and the three idle
    # shares (the cell's spans did not pair one to one in its traced
    # runs: PR 53's rule)
    for name in ("flash_roofline_pct.bulk", "hybrid_flash_roofline_pct.bulk",
                 "ssd_kernel_roofline_pct.bulk", "window_tile_visit_pct.bulk",
                 "put_ms_per_dispatch.bulk", "experts_busy_pct.bulk",
                 "idle_starved_pct.bulk", "idle_launch_pct.bulk",
                 "idle_host_loop_pct.bulk"):
        assert CELL not in by_name[name]["workloads"], name
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[cells.index(CELL) + 1:] == ["xing4.bulk"]
    assert [c["name"] for c in manifest["configs"]][-2] == "phi4-mini-flash"
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])


class Result:
    tokens_valid = 100
    pad_emissions = 2
    tokens_cross_lines = 0


def facts_of(tmp_path, family="phi4_flash"):
    class Facts:
        trace = None
        result = type("R", (Result,), {"log_dir": str(tmp_path)})
        config = json.load(open(os.path.join(REPO, REAL)))
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    Facts.family = mm.load_family(family)
    return Facts


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_on_a_run_without_its_source(
        name, tmp_path):
    """No trace, no counter (the parent's programs have neither the
    scopes nor the counter): None, not a raise; and the manifest repeats
    what the file declares."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == NEW_READERS[name]
    assert module.read(facts_of(tmp_path)) is None
    # a family whose file counts no such mechanism, and a result with no
    # such counter: nothing, not a raise
    assert module.read(facts_of(tmp_path, "falcon_h1")) is None
    bare = facts_of(tmp_path, "nemotron_h")
    bare.result = type("Bare", (), {"log_dir": str(tmp_path)})
    assert module.read(bare) is None


def test_the_readers_read_a_run_that_has_their_sources(tmp_path,
                                                       monkeypatch):
    """A trace reduced to three instructions and a kernel's call: the
    shares are the family's work over those seconds, the cross-decoder's
    time the seconds under its scope, the lines' share the counter's."""
    from benchmarks import scopes, subscopes
    facts = facts_of(tmp_path)

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    facts.trace = Trace
    facts.result.tokens_cross_lines = 3
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5,
                                    "%fusion.2 f32[8,8]": 0.25,
                                    "%fusion.3 f32[8,8]": 0.125,
                                    "%fusion.4 f32[8,8]": 0.0625}
    (tmp_path / "hlo-scopes.json").write_text(json.dumps({
        "%fusion.1 f32[8,8]": "jit(apply)/jit(main)/mlp/dot",
        "%fusion.2 f32[8,8]":
            "jit(apply)/attn/full/kernel/vmap(jit(_splash_attention))",
        "%fusion.3 f32[8,8]":
            "jit(apply)/cross/while/body/closed_call/xmlp/dot",
        "%fusion.4 f32[8,8]":
            "jit(apply)/cross/while/body/closed_call/xattn/dot"}))
    subscopes._op_names.cache_clear()
    tokens = 16384.0
    monkeypatch.setattr(scopes, "traced_tokens", lambda facts: tokens)
    monkeypatch.setattr(scopes, "kernel_seconds",
                        lambda facts, kernel: 0.01)
    try:
        family, config = facts.family, facts.config
        dispatches = tokens * 2 / 100

        def least(mechanism):
            ops, nbytes = family.mechanism_work(config, mechanism, tokens,
                                                dispatches)
            return max(ops / 1.97e14, nbytes / 8.19e11)
        read = {name: mm.load_layer_metric(name).read(facts)
                for name in NEW_READERS}
        assert read["selective_scan_roofline_pct.bulk"] \
            == pytest.approx(100 * least("selective_scan") / 0.01)
        assert read["diff_attn_roofline_pct.bulk"] \
            == pytest.approx(100 * least("diff_attn") / 0.25)
        # the cross-decoder's MLPs are its own, not ``mlp``'s
        assert read["cross_decoder_ms_per_dispatch.bulk"] \
            == pytest.approx(1e3 * (0.125 + 0.0625) / dispatches)
        assert mm.load_layer_metric("mlp_roofline_pct.bulk").read(facts) \
            == pytest.approx(100 * least("mlp") / 0.5)
        assert read["cross_line_pct.bulk"] == 3.0
        # the recurrence's bytes bound it against the matrix unit's peak
        ops, nbytes = family.mechanism_work(config, "selective_scan",
                                            tokens, dispatches)
        assert nbytes / 8.19e11 > ops / 1.97e14
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


def test_the_kernels_names_are_the_readers():
    from rnb_tpu.ops import banded, selective_scan
    assert mm.load_layer_metric(
        "selective_scan_roofline_pct.bulk").KERNEL \
        == selective_scan.KERNEL_NAME
    # no accepted reader finds a kernel by a name one of this family's
    # holds as a part
    for name in (selective_scan.KERNEL_NAME,
                 banded.DIFFERENTIAL_KERNEL_NAME):
        assert name not in ("ssd_scan", banded.KERNEL_NAME,
                            banded.LATENT_KERNEL_NAME)
