"""The Falcon-H1 family against its plain reference, at a toy size on the
CPU with weights from a seed (kernels interpreted): the packed prefill
through dispatches with several requests, boundaries inside the pool and
pad rows; packing that is invisible (state, convolution history and
rotary positions restart at every request); the lower-precision
controls; a lost branch and each of the fourteen multipliers (the
twelve of the mixers, the embedding and the head, and the MLP's two)
changed in the program alone, each seen by the comparison; the recipe's
spreads; the operation counts and the issue's 430,120,032 parameters a
layer; the real configuration against the catalog's row. The toy keeps
what makes the shape: 5 query heads a key-value head, 2 groups of 16
scan heads, a state twice a head's width, all multipliers as published.
Then the family's record for ``family_contract.py``, by which
``test_falcon_h1_cell.py`` runs the stage, the control script and the
cell (one file is one worker's under ``--dist loadfile``), and the six
new readers on a run without their scope, kernel or counter. Nothing
here needs the native decode library or a chip."""

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
from benchmarks.references import falcon_h1 as reference  # noqa: E402

REAL = "benchmarks/configs/falcon-h1-34b-l6.json"
CELL = "falcon-h1.bulk"
SEED = 3_000_000_123

#: the catalog's ``config`` of Falcon-H1-34B-Instruct
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}

#: four of the 72 blocks at toy widths, every switch and scalar as
#: published: 10 query / 2 key-value heads of 16, 32 scan heads of 8 in 2
#: groups of 16 with a state of 16, a 4-tap convolution over 320
#: channels, an MLP of 128
TOY = dict(
    PUBLISHED, num_hidden_layers=4, hidden_size=64, vocab_size=256,
    chunk_size=16, mamba_chunk_size=16, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, mamba_n_heads=32, mamba_d_head=8,
    mamba_d_ssm=256, mamba_n_groups=2, mamba_d_state=16,
    intermediate_size=128, published={"num_hidden_layers": 72})
Q = TOY["chunk_size"]
#: the comparison's limit at the toy widths, between two readings over
#: three seeds of weights (narrow sums average less rounding away than
#: the real ones): as stated 1.3-1.9% of the spread, every layer's
#: matrices through float8 3.7-7.7% (5.3-7.7 on the dispatch the control
#: below runs)
TOY_LIMIT = 0.03


def judged(got, want):
    """The family's comparison: the worst logit under ``TOY_LIMIT`` and
    the root mean square under the family's own limit, which the toy
    widths keep (as stated 0.45-0.60% of the spread, float8 1.5-2.1%,
    over the three dispatches below and three seeds of weights)."""
    return mm.load_family("falcon_h1").compare_logits(
        {"share_of_spread": TOY_LIMIT}, got, want)


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.falcon_h1 import checkpoint, network
    cfg = network.FalconH1Config.from_published(TOY)
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


def run_program(toy, prompts, rows, params=None, cfg=None, **kwargs):
    """-> (logits a prompt, the counters)."""
    import jax

    from rnb_tpu.models.falcon_h1 import network
    cfg = toy["cfg"] if cfg is None else cfg
    tokens, meta, _ = pack(prompts, rows)
    key = (cfg, rows, tuple(sorted((k, str(v)) for k, v in kwargs.items())))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(
            lambda p, t, m: network.forward(
                cfg, p, None, t, m[0], m[1], m[2], interpret=True,
                **kwargs))
    logits, chosen, *counts = _PROGRAMS[key](
        toy["params"] if params is None else params, tokens, meta)
    assert chosen.shape == (0, rows * Q)
    return np.asarray(logits)[:len(prompts)], \
        [np.asarray(c) for c in counts]


#: the reference runs every prompt padded to this many tokens behind its
#: last (every mixer is causal), so that it compiles one length
REF_LENGTH = 256


def run_reference(toy, prompt):
    """-> the reference's logits at the prompt's last token."""
    import jax
    with jax.default_matmul_precision("highest"):
        out = toy["reference"].forward(
            toy["read"], np.pad(prompt, (0, REF_LENGTH - len(prompt))),
            position=len(prompt) - 1)
    return np.asarray(out["logits"])


def references_of(toy, prompts):
    return np.stack([run_reference(toy, p) for p in prompts])


def changed(params, **tensors):
    """``params`` with the named tensors of every layer replaced by what
    the function makes of them."""
    return {group: (dict(block, **{name: make(block[name])
                                   for name, make in tensors.items()})
                    if isinstance(block, dict) else block)
            for group, block in params.items()}


def through_float8(params):
    """The stored matrices of every layer rounded through float8 (e4m3):
    the nearest precision below the one the configuration states."""
    import jax.numpy as jnp
    return {group: ({name: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                            if w.ndim >= 2 else w)
                     for name, w in block.items()}
                    if isinstance(block, dict) else block)
            for group, block in params.items()}


# -- the whole stack ------------------------------------------------------

#: dispatches of 16 rows: several requests, one that ends inside a row,
#: one that fills its rows, pad rows behind; one request over the pool
DISPATCHES = {"three": [120, 37, 70], "whole_rows": [16, 96, 5, 64],
              "one_long": [250]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_matches_the_reference(toy, case):
    prompts = prompts_of(DISPATCHES[case], seed=4)
    logits, (tiles, resets) = run_program(toy, prompts, 16)
    verdict = judged(logits, references_of(toy, prompts))
    assert verdict["ok"], verdict
    # the flash kernel's tiles a layer, and the rows that open a request
    assert tiles.shape == (4, 2) and (tiles == 1).all()
    assert resets.tolist() == [len(prompts)]


def test_packing_is_invisible_and_state_and_positions_restart(toy):
    """A prompt's logits depend neither on what shares its dispatch, nor
    on where in the pool it lies, nor on the bucket: the scan's state,
    the convolution's history and the rotary positions restart at its
    first row."""
    a, b, c, d = prompts_of([100, 5, 70, 20])
    alone, _ = run_program(toy, [a], 8)
    packed, _ = run_program(toy, [b, c, a, d], 16)
    other, _ = run_program(toy, [d, a], 16)
    spread = float(run_reference(toy, a).std())
    # not bit for bit: the flash kernel's tiles differ with the pool
    assert np.abs(packed[2] - alone[0]).max() < 0.005 * spread
    assert np.abs(other[1] - alone[0]).max() < 0.005 * spread


def test_the_lower_precision_controls(toy):
    """Every layer's stored matrices through float8 (the nearest
    precision below the stated one) reads outside both limits. The scan's
    states carried in bfloat16 between rows move the logits and do *not*
    discriminate at this depth, as in Nemotron-H and MiniCPM-SALA (the
    kernel rounds a state once a row of tokens, and the bfloat16 stream
    rounds every activation of every layer): 1.4-2.0% for 1.3-1.9% as
    stated over three seeds. ISSUE 53 asked for that arm to fail; it is
    recorded, and float8 is the control that decides."""
    import jax.numpy as jnp
    prompts = prompts_of(DISPATCHES["three"], seed=4)
    want = references_of(toy, prompts)
    stated, _ = run_program(toy, prompts, 16)
    assert judged(stated, want)["ok"]
    eighth, _ = run_program(toy, prompts, 16,
                            params=through_float8(toy["params"]))
    verdict = judged(eighth, want)
    assert verdict["share_of_spread"] > verdict["limit"] == TOY_LIMIT
    assert verdict["rms_share_of_spread"] > 1.5 * verdict["rms_limit"]
    rounded, _ = run_program(toy, prompts, 16, state_dtype=jnp.bfloat16)
    moved = np.abs(rounded - stated).max() / want.std()
    assert 1e-4 < moved < TOY_LIMIT, moved


@pytest.mark.parametrize("tensor", ["out_proj", "o"])
def test_a_lost_branch_is_seen(toy, tensor):
    """With the state-space branch's result zeroed (``out_proj``), or
    attention's (``o``), in every block, the logits move by many times
    the limit: the two mixers both reach the stream."""
    import jax.numpy as jnp
    prompts = prompts_of(DISPATCHES["three"], seed=4)
    logits, _ = run_program(toy, prompts, 16, params=changed(
        toy["params"], **{tensor: jnp.zeros_like}))
    verdict = compare(logits, references_of(toy, prompts), TOY_LIMIT)
    assert not verdict["ok"] and verdict["share_of_spread"] > 0.2, verdict


def multipliers():
    from rnb_tpu.models.falcon_h1 import network
    return network.MULTIPLIERS


@pytest.fixture(scope="module")
def under_scalars(toy):
    """(prompts, their references, logits under a vector of the fourteen
    scalars): one program, the scalars its argument."""
    import jax

    from rnb_tpu.models.falcon_h1 import network
    prompts = prompts_of(DISPATCHES["three"], seed=4)
    tokens, meta, _ = pack(prompts, 16)
    program = jax.jit(lambda p, t, m, scalars: network.forward(
        toy["cfg"].with_multipliers(list(scalars)), p, None, t, m[0], m[1],
        m[2], interpret=True)[0])
    return prompts, references_of(toy, prompts), lambda scalars: np.asarray(
        program(toy["params"], tokens, meta,
                np.asarray(scalars, np.float32)))[:len(prompts)]


@pytest.mark.parametrize("name", multipliers())
def test_a_multiplier_doubled_in_the_program_alone_is_seen(
        toy, under_scalars, name):
    """Each scalar of the configuration stands in the program where it
    stands in the reference: doubled in the program alone, with the same
    weights, the comparison fails (as published it holds)."""
    _, want, logits_under = under_scalars
    scalars = list(toy["cfg"].multiplier_values())
    assert judged(logits_under(scalars), want)["ok"]
    scalars[multipliers().index(name)] *= 2.0
    verdict = compare(logits_under(scalars), want, TOY_LIMIT)
    assert not verdict["ok"], (name, verdict)


def test_the_configuration_names_fourteen_scalars():
    from rnb_tpu.models.falcon_h1 import network
    assert len(network.MULTIPLIERS) == 14 == len(set(network.MULTIPLIERS))
    # the issue's twelve, and the MLP's two
    assert sum("mlp" not in name for name in network.MULTIPLIERS) == 12
    cfg = network.FalconH1Config.from_published(TOY)
    assert cfg.multiplier_values() == tuple(
        PUBLISHED[n.partition(".")[0]][int(n.partition(".")[2])]
        if "." in n else PUBLISHED[n] for n in network.MULTIPLIERS)
    twice = cfg.with_multipliers([2 * m for m in cfg.multiplier_values()])
    assert twice.key_multiplier == 2 * cfg.key_multiplier \
        and twice.ssm_multipliers[3] == 1.0 and twice.mlp_multipliers \
        == tuple(2 * m for m in cfg.mlp_multipliers)
    assert twice.with_multipliers(cfg.multiplier_values()) == cfg
    # a switch the network does not implement is refused
    with pytest.raises(ValueError):
        network.FalconH1Config.from_published(
            dict(TOY, mamba_norm_before_gate=True))
    with pytest.raises(ValueError):
        network.FalconH1Config.from_published(dict(TOY, attention_bias=True))


def test_the_references_attention_takes_any_length(monkeypatch):
    """Queries a block at a time, the last block padded: the same
    numbers as one block over the whole prompt, at a length that is no
    multiple of the step."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    w = {name: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
         for name, shape, scale in (
             ("q", (64, 160), 0.1), ("k", (64, 32), 5), ("v", (64, 32), 0.1),
             ("o", (160, 64), 0.1))}
    u = jnp.asarray(rng.normal(size=(75, 64)), jnp.float32)
    whole = np.asarray(reference.attention(TOY, w, u))
    monkeypatch.setattr(reference, "QUERY_STEP", 32)
    blocks = np.asarray(reference.attention(TOY, w, u))
    assert blocks.shape == (75, 64)
    assert np.abs(blocks - whole).max() < 1e-6 * np.abs(whole).max() + 1e-7


# -- the recipe -----------------------------------------------------------


def test_recipe_gives_program_and_reference_the_same_values(toy):
    params, read = toy["params"], toy["read"]
    for name, tensor in (("l0.in_proj", params["l0"]["in_proj"]),
                         ("top.final_norm", params["final_norm"]),
                         ("l3.dt_bias", params["l3"]["dt_bias"]),
                         ("l2.k", params["l2"]["k"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name)))
    # the embedding by a prompt's rows, the head by a block of columns
    at = np.array([5, 0, 255, 5])
    assert np.array_equal(np.asarray(read("top.embed", at)),
                          np.asarray(params["embed"], np.float32)[at])
    assert np.array_equal(
        np.asarray(read("top.head", (slice(None), slice(64, 128)))),
        np.asarray(params["head"], np.float32)[:, 64:128])
    assert not np.array_equal(np.asarray(params["l0"]["q"]),
                              np.asarray(params["l1"]["q"]))


def test_a_matrix_is_drawn_over_the_scalar_on_its_product():
    """At the published widths' spreads (a wider toy, so that a column's
    spread is measured): each product times its multiplier has the
    spread the other families draw."""
    import jax

    from rnb_tpu.models.falcon_h1 import checkpoint, network
    cfg = network.FalconH1Config.from_published(
        dict(TOY, hidden_size=512, num_hidden_layers=1))
    params = checkpoint.make_params(cfg, 7, (), jax.devices()[0])
    layer = {k: np.asarray(v, np.float32) for k, v in params["l0"].items()}
    d = cfg.hidden_size

    def unit(w, times, fan_in=d):
        return np.std(w) * times * np.sqrt(fan_in)
    edges = np.cumsum((0,) + cfg.in_proj_parts)
    for lo, hi, mu in zip(edges, edges[1:], cfg.ssm_multipliers):
        assert abs(unit(layer["in_proj"][:, lo:hi],
                        cfg.ssm_in_multiplier * mu) - 1) < 0.05
    back = np.sqrt(72.0)
    assert abs(unit(layer["out_proj"], cfg.ssm_out_multiplier * back,
                    cfg.d_ssm) - 1) < 0.05
    assert abs(unit(layer["o"], cfg.attention_out_multiplier * back,
                    160) - 1) < 0.05
    assert abs(unit(layer["down"], cfg.mlp_multipliers[1] * back,
                    cfg.intermediate_size) - 1) < 0.05
    assert abs(unit(layer["gate"], cfg.mlp_multipliers[0]) - 1) < 0.05
    assert abs(unit(layer["q"], cfg.attention_in_multiplier) - 1) < 0.05
    assert abs(unit(layer["k"], cfg.key_multiplier)
               - checkpoint.KEY_GAIN) < 0.1
    assert abs(np.std(np.asarray(params["embed"], np.float32))
               * cfg.embedding_multiplier - 1) < 0.05
    assert abs(unit(np.asarray(params["head"], np.float32),
                    cfg.lm_head_multiplier) - 1) < 0.05
    assert (layer["d"] == 1).all() and (layer["gnorm"] == 1).all()
    assert (np.exp(layer["a_log"]) >= 1).all() \
        and (np.exp(layer["a_log"]) <= 16).all()


# -- operations, bytes and sizes ------------------------------------------


def real_config():
    with open(os.path.join(REPO, REAL)) as f:
        return json.load(f)


def test_operation_counts_agree_with_the_family_file():
    from rnb_tpu.models.falcon_h1 import checkpoint, flops, network
    family = mm.load_family("falcon_h1")
    config = real_config()
    cfg = network.FalconH1Config.from_published(
        family.published_keys(config))
    # ISSUE 53's arithmetic: 430,120,032 parameters a layer, 5,254,594,112
    # with embedding and head (and the final norm's 5,120)
    assert checkpoint.params_per_layer(cfg) == 430_120_032
    specs = checkpoint.tensor_specs(cfg)
    held = sum(int(np.prod(spec.shape)) for tensors in specs.values()
               for spec in tensors.values())
    assert held == 6 * 430_120_032 + 2 * 261_120 * 5120 + 5120
    assert abs(2 * held / 2 ** 30 - config["model"]["weights_gib"]) < 0.01
    assert flops.flops_per_token(cfg, 1800.0) \
        == family.flops_per_token(config, 1800.0)
    assert flops.ssm_flops_per_token(cfg) \
        == family.ssm_flops_per_token(config)
    assert flops.mlp_flops(cfg) == family.mlp_flops(config) \
        == 6 * 5120 * 21504
    assert family.flops_per_row(config) == 128 * flops.flops_per_token(
        cfg, family.mean_context(config))
    # 884 MFLOP a token a layer: MLP 661, the mixers' projections 137 and
    # 63, attention's scores 19 at the mix's mean context, the scan 5
    per_layer = family.flops_per_row(config) / 128 / 6
    assert abs(per_layer / 1e6 - 884) < 6
    assert abs(family.mlp_flops(config) / 1e6 - 661) < 1
    assert abs(family.mean_context(config) - 1800) < 150
    # the mechanisms the new readers count
    tokens, dispatches = 1e6, 150.0
    ops, nbytes = family.mechanism_work(config, "scan", tokens, dispatches)
    assert ops == 6 * tokens * 32 * (5 * 128 * 256 + 2 * 128)
    assert nbytes == 6 * tokens * (2 * 3 * 4096 + 2 * 2 * 512 + 4 * 32)
    ops, nbytes = family.mechanism_work(config, "ssm", tokens, dispatches)
    assert ops == 6 * tokens * flops.ssm_flops_per_token(cfg)
    assert nbytes == 6 * (2 * (5120 * 9248 + 4096 * 5120) * dispatches
                          + 4 * 5120 * tokens)
    ops, nbytes = family.mechanism_work(config, "mlp", tokens, dispatches)
    assert ops == 6 * tokens * 6 * 5120 * 21504
    assert nbytes == 6 * (6 * 5120 * 21504 * dispatches + 4 * 5120 * tokens)
    ops, nbytes = family.mechanism_work(config, "flash", tokens, dispatches)
    assert ops == 6 * tokens * 4 * family.mean_context(config) * 2560
    assert nbytes == 6 * tokens * 2 * (2 * 2560 + 2 * 512)
    with pytest.raises(ValueError):
        family.mechanism_work(config, "experts", tokens, dispatches)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Falcon-H1-34B-Instruct":
                return row
    return None


def test_real_configuration_keeps_the_published_sizes():
    config = real_config()
    entry = mm.config_entry(mm.load(), "falcon-h1-34b-l6")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 6
    row = catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
        assert row["config"] == PUBLISHED
    for key in ("chunk_size", "equations", "rotary", "time_steps", "weights",
                "precision", "prompts", "batch"):
        assert config["assumed"][key], key
    assert "twelve chips as pipeline stages of six layers" \
        in config["deployment"]
    assert 4 * 2 ** 30 <= config["size_record"]["projected_gib"] * 2 ** 30 \
        <= 14 * 2 ** 30
    assert config["capacity_why"] and config["capacity_videos_per_chip_s"]
    family = mm.load_family(config["family"])
    assert family.check_config(config) == []
    assert family.check_config(dict(config, num_hidden_layers=3)) != []
    cell = mm.cell(mm.load(), CELL)
    assert cell["config"] == "falcon-h1-34b-l6" and cell["chips"] == 1 \
        and cell["traffic"] == "bulk"
    # nemotron3-nano-l14-ep2's dataset block to the letter: the two
    # Mamba-2 configurations are measured on the same requests
    with open(os.path.join(
            REPO, "benchmarks/configs/nemotron3-nano-l14-ep2.json")) as f:
        sibling = json.load(f)
    assert sibling["dataset"] == config["dataset"]
    assert sibling["runtime_env"] == config["runtime_env"]
    prefill = config["pipeline_config"]["pipeline"][-1]
    assert prefill["row_buckets"] == [16, 32, 48, 64] \
        and prefill["family"] == "falcon_h1"
    lengths = family.prompt_lengths(config)
    assert min(lengths.values()) >= 32 and max(lengths.values()) <= 8192
    # and another vocabulary: its request files are its own
    assert family.dataset_key(config) != family.dataset_key(sibling)


def toy_config():
    """A toy-width copy of the real configuration's file."""
    config = real_config()
    config.update(TOY)
    from rnb_tpu.models.falcon_h1 import checkpoint, network
    cfg = network.FalconH1Config.from_published(TOY)
    held = 4 * checkpoint.params_per_layer(cfg) + (2 * 256 + 1) * 64
    config["model"] = dict(config["model"], layers=4,
                           params_billions_held=held / 1e9)
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 60, "sigma": 0.5,
                                   "min": 20, "max": 100},
                         "long": {"count": 2, "min": 100, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 40
    config["share_of_spread"] = TOY_LIMIT
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=3, samples=8)
    return config


def the_stage_counts_tiles_and_resets(served):
    """``family_contract.stage_serves``'s entry for this family: the
    flash kernel's tiles and the rows that open a request, and no
    expert."""
    from rnb_tpu.telemetry import stage_counter_report
    counters, valid = served.stage.stage_counters(), served.valid
    # four layers' tiles, two dispatches; three requests a dispatch
    assert counters["attn_tiles"].tolist() == [8, 8]
    assert counters["scan_resets"].tolist() == [6]
    lines, fields = stage_counter_report([counters])
    assert lines == [
        "Tokens: valid=%d shipped=%d scan_resets=6" % (2 * valid, 16 * Q),
        "Attention: tiles_visited=8 tiles_causal=8"]
    assert fields["tokens_scan_resets"] == 6
    assert served.stage._samples[0]["logits"].shape \
        == (TOY["vocab_size"],)


#: ``tests/test_falcon_h1_cell.py`` runs it. One of the two families
#: that keep the untraced run (``family_contract.py``'s docstring): the
#: dense one
CONTRACT = contract.Family(
    name="falcon_h1", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED),
    meta=("Tokens: valid=", " scan_resets=", "Attention:"),
    meta_absent=("Experts:",),
    scopes=("/ssd/scan/", "/ssd/conv/", "/attn/", "/mlp/", "/head/"),
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "flash_tile_visit_pct.bulk": "(0, 100]",
        "scan_resets_per_dispatch.bulk": "[1, 8]"},
    not_from_a_cpu="roofline|util|busy_pct|ms_per_dispatch",
    traces=(0, 1),
    stage=contract.Stage(
        lengths=(80, 9, 30), row_buckets=(8,), dispatches=2,
        scopes=("/embed/", "/norm/", "/ssd/", "/ssd/conv/", "/ssd/scan/",
                "/attn/", "/mlp/", "/head/"),
        chosen_shape=(0, 80), also=the_stage_counts_tiles_and_resets),
    # as stated inside the limit, every layer's matrices through float8
    # outside it, the scan's states through bfloat16 reported and free
    # to pass
    control=contract.Control(
        lengths="120,37,70", outside=("layers_float8",),
        reads={("state_bfloat16", "share_of_spread"): "[0, 0.2)"},
        may_pass=("state_bfloat16",)))


# -- the six new readers --------------------------------------------------

NEW_READERS = {
    "ssm_branch_roofline_pct.bulk": "state-space scan",
    "ssd_kernel_roofline_pct.bulk": "state-space scan",
    "hybrid_flash_roofline_pct.bulk": "packed attention",
    "mlp_roofline_pct.bulk": "network",
    "attn_branch_ms_per_dispatch.bulk": "packed attention",
    "scan_resets_per_dispatch.bulk": "state-space scan"}
#: the accepted readers whose lists gained the cell
LISTED = (
    "host_cores_busy", "rows_per_dispatch", "pad_row_pct",
    "net_flops_util_pct", "net_roofline_pct", "device_idle_pct",
    "hbm_peak_gib", "pad_row_traced_pct", "tokens_per_s", "pad_token_pct",
    "flash_tile_visit_pct", "ssd_busy_pct", "attn_busy_pct", "mlp_busy_pct",
    "ssd_scan_ms_per_dispatch", "segment_conv_ms_per_dispatch")


def test_the_accepted_readers_list_the_cell_last():
    by_name = {m["name"]: m for m in mm.load()["per_layer"]}

    def last_of_its_pr(workloads):
        """The cell stands last but for the cells later PRs appended
        (PR 55's ``dots3-note.bulk``, PR 59's ``phi4-flash.bulk``, PR
        62's ``xing4.bulk``)."""
        behind = workloads[workloads.index(CELL) + 1:]
        return set(behind) <= {"dots3-note.bulk", "phi4-flash.bulk",
                               "xing4.bulk"}
    for name in LISTED:
        assert last_of_its_pr(by_name[name + ".bulk"]["workloads"]), name
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", ())
              and m["moves"] == "videos_per_s"}
    assert listed == {n + ".bulk" for n in LISTED} | set(NEW_READERS)
    # this PR's six stand behind the eight set-up metrics, appended in
    # the order of ``NEW_READERS``; the eight themselves are the
    # harness's test's (``tests/harness/test_harness_setup_account.py``)
    names = [m["name"] for m in mm.load()["per_layer"]]
    at = names.index(next(iter(NEW_READERS)))
    assert names[at:at + len(NEW_READERS)] == list(NEW_READERS)
    assert by_name[names[at - 1]]["moves"] == "setup_s"
    assert last_of_its_pr(by_name[names[at - 1]]["workloads"])
    # a reader that gives a dense family nothing does not list it
    # nor the three idle shares: the cell's spans paired under
    # ``hostspans.PAIR_RADIUS_NS`` in two of three traced runs only
    # (PERF.md section 6)
    for name in ("flash_roofline_pct.bulk", "ssd_roofline_pct.bulk",
                 "experts_busy_pct.bulk", "idle_starved_pct.bulk",
                 "idle_launch_pct.bulk", "idle_host_loop_pct.bulk"):
        assert CELL not in by_name[name]["workloads"], name


class Result:
    tokens_valid = 100
    pad_emissions = 2
    tokens_scan_resets = 0


def facts_of(tmp_path, family="falcon_h1"):
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
    # PR 59's cell, a dense MLP at a second width and a scan's resets,
    # joined two
    joined = ["phi4-flash.bulk"] if name in (
        "mlp_roofline_pct.bulk", "scan_resets_per_dispatch.bulk") else []
    assert entry and entry[0]["workloads"] == [CELL] + joined
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == NEW_READERS[name]
    assert module.read(facts_of(tmp_path)) is None
    # an older family's file counts by another signature, or no such
    # mechanism: nothing, not a raise
    assert module.read(facts_of(tmp_path, "nemotron_h")) is None
    assert module.read(facts_of(tmp_path, "minicpm_sala")) is None


def test_the_readers_read_a_run_that_has_their_sources(tmp_path,
                                                       monkeypatch):
    """A trace reduced to two instructions and a kernel's call: the
    shares are the family's work over those seconds, the counter its
    rows a dispatch."""
    from benchmarks import scopes, subscopes
    facts = facts_of(tmp_path)

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    facts.trace = Trace
    facts.result.tokens_scan_resets = 14
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5,
                                    "%fusion.2 f32[8,8]": 0.25,
                                    "%fusion.3 f32[8,8]": 0.125}
    (tmp_path / "hlo-scopes.json").write_text(json.dumps({
        "%fusion.1 f32[8,8]": "jit(apply)/jit(main)/mlp/dot",
        "%fusion.2 f32[8,8]": "jit(apply)/jit(main)/ssd/scan/ssd_scan",
        "%fusion.3 f32[8,8]": "jit(apply)/jit(main)/attn/dot"}))
    subscopes._op_names.cache_clear()
    tokens, dispatches = 16384.0, 2.0
    monkeypatch.setattr(scopes, "traced_tokens", lambda facts: tokens)
    monkeypatch.setattr(scopes, "kernel_seconds",
                        lambda facts, kernel: 0.01)
    try:
        family, config = facts.family, facts.config

        def least(mechanism):
            ops, nbytes = family.mechanism_work(config, mechanism, tokens,
                                                tokens * 2 / 100)
            return max(ops / 1.97e14, nbytes / 8.19e11)
        read = {name: mm.load_layer_metric(name).read(facts)
                for name in NEW_READERS}
        assert read["mlp_roofline_pct.bulk"] \
            == pytest.approx(100 * least("mlp") / 0.5)
        assert read["ssm_branch_roofline_pct.bulk"] \
            == pytest.approx(100 * least("ssm") / 0.25)
        assert read["ssd_kernel_roofline_pct.bulk"] \
            == pytest.approx(100 * least("scan") / 0.01)
        assert read["hybrid_flash_roofline_pct.bulk"] \
            == pytest.approx(100 * least("flash") / 0.01)
        assert read["attn_branch_ms_per_dispatch.bulk"] \
            == pytest.approx(1e3 * 0.125 / (tokens * 2 / 100))
        assert read["scan_resets_per_dispatch.bulk"] == 7.0
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


def test_the_kernels_names_are_the_readers():
    from rnb_tpu.ops import ssd
    assert mm.load_layer_metric("ssd_kernel_roofline_pct.bulk").KERNEL \
        == ssd.KERNEL_NAME
    # the flash kernel's calls are the ones ``flash_roofline_pct.bulk``
    # reads for the expert families
    with open(os.path.join(mm.LAYER_METRICS_DIR,
                           "flash_roofline_pct.bulk.py")) as f:
        assert '"%s"' % mm.load_layer_metric(
            "hybrid_flash_roofline_pct.bulk").KERNEL in f.read()
