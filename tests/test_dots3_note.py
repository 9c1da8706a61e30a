"""The dots3-note family at a toy size on the CPU (kernels in interpret
mode, seeded random weights): the packed stack against the plain
reference for a pool of several requests with boundaries inside it and
a pad row — logits and the full layers' chosen sets —, the float8
control failing the same comparison, ten faults planted from outside
the program (``scripts/prefill_control.dots3_note_faults``: a tensor
scaled, a field of the configuration, a function of the module
replaced) that must each move the result past a limit, the share test
(eight shares' expert parts and the shared expert counted once add up to
the uncut layer), the counters against a numpy count, and the operation
and parameter counts against the issue's arithmetic. The toy keeps what
makes the model: two geometries with different head counts, key widths
and ranks, keys wider than values (a full layer's own key whole lane
tiles, a sliding layer's padded), a window that is no multiple of a tile
and shorter than the prompts, a top-k smaller than the prompts, 8 experts
a share of 64. Then the family's record for ``family_contract.py``, by
which ``test_dots3_note_cell.py`` drives the stages, the control script
and the one benchmark command over the same toy, and the seven new
readers on a run without their kernel or counter."""

import contextlib
import functools
import inspect
import json
import os
import sys
from unittest import mock

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "scripts")]

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import dots3_note as reference  # noqa: E402

REAL = "benchmarks/configs/dots3-note-l5-ep8.json"
CELL = "dots3-note.bulk"
SEED = 5_500_000_011
FULL, SLIDING = "full_attention", "sliding_attention"

#: five layers ``F(dense) F S S S`` at toy widths: a full layer 4 heads
#: of 128 + 16 / 32 from latents 32 and 24 under 4 index heads of 16
#: choosing 48 keys, a sliding layer 2 heads of 24 + 8 / 16 from latents
#: 32 and 40 under a window of 37, rows of 32 tokens, 8 of 64 experts
TOY = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "index_head_dim": 16,
    "index_n_heads": 4, "index_topk": 48, "intermediate_size": 96,
    "kv_lora_rank": 24,
    "layer_types": [FULL, FULL, SLIDING, SLIDING, SLIDING, FULL],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 8,
    "num_hidden_layers": 5, "num_key_value_heads": 4, "q_lora_rank": 32,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 16, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 37, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 40, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_rope_theta": 50000, "swa_v_head_dim": 16,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 32, "vocab_size": 256, "chunk_size": 32,
    "published": {"num_hidden_layers": 46, "n_routed_experts": 64,
                  "vocab_size": 2048}}
HELD = tuple(range(8))
Q = TOY["chunk_size"]
TOPK = TOY["index_topk"]
WINDOW = TOY["sliding_window_size"]
#: the comparison's limits at the toy widths (the real ones are the
#: family file's): as stated the toy reads 2.0-2.3% of the spread, a
#: route shortfall of 0.006 and a key shortfall of 0.025-0.032 over the
#: dispatches below; the float8 control 0.13-0.15 on the keys
TOY_LIMIT = 0.045
TOY_KEY_SLACK = 0.07
#: the full layers' kernel's (queries, keys, heads) a step in these
#: tests: a pool of 512 tokens is then 4 x 4 tiles and two groups
TILES = (128, 128, 2)


@pytest.fixture(scope="module", autouse=True)
def small_tiles():
    from rnb_tpu.ops import indexed
    before, indexed._LATENT_TILES = indexed._LATENT_TILES, TILES
    yield
    indexed._LATENT_TILES = before


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.dots3_note import checkpoint, network
    cfg = network.Dots3NoteConfig.from_published(TOY)
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


def pack(prompts, rows):
    from rnb_tpu.models import token_stages
    return token_stages.pack_prompts(prompts, rows, Q)


@functools.lru_cache(maxsize=None)
def _program(**arm):
    import jax

    from rnb_tpu.models.dots3_note import network
    cfg = network.Dots3NoteConfig.from_published(TOY)
    return jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2], interpret=True, **arm))


def run_program(toy, prompts, rows, fault=None, **arm):
    """-> (logits a prompt, each prompt's choices as a sample keeps them
    (``network.request_choices``), the counters by name). ``fault``: one
    of ``scripts/prefill_control.dots3_note_faults``, planted on what
    the program is given."""
    import jax

    import prefill_control
    from rnb_tpu.models.dots3_note import network
    tokens, meta, offsets = pack(prompts, rows)
    fault = fault or {}
    patch = fault.get("patch", {})
    if "cfg" in fault or patch:
        cfg = fault.get("cfg", toy["cfg"])
        program = jax.jit(lambda p, s, t, m: network.forward(
            cfg, p, s, t, m[0], m[1], m[2], interpret=True, **arm))
    else:
        # a fault of the parameter tree alone is the stated program
        # given other values: what a test before it compiled
        program = _program(**arm)
    params = prefill_control.planted(toy["params"], fault)
    with mock.patch.multiple(network, **patch) if patch \
            else contextlib.nullcontext():
        logits, chosen, *counts = program(params, toy["slots"], tokens,
                                          meta)
    chosen = tuple(np.asarray(c) for c in chosen)
    kept = [network.request_choices(toy["cfg"], chosen, o * Q, len(p))
            for o, p in zip(offsets, prompts)]
    return np.asarray(logits)[:len(prompts)], kept, \
        dict(zip(network.COUNTERS, (np.asarray(c) for c in counts)))


def sets_of(kept, count):
    """A sample's sets -> bool (full layers, count, count): query t
    reads key s of its own request."""
    from rnb_tpu.ops import indexed
    first = int(kept["first"])
    return indexed.unpack_sets(kept["key_sets"])[:, :, first:first + count]


def run_reference(toy, prompt, kept=None, **kwargs):
    import jax
    given = {} if kept is None else {
        "forced": kept["chosen"],
        "forced_sets": list(sets_of(kept, len(prompt)))}
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(toy["read"], prompt, held=HELD,
                                        **given, **kwargs)


def held_to_the_check(toy, prompts, logits, kept):
    """The run's check on a dispatch (``families/dots3_note.py``'s three
    limits, the toy's numbers): -> {"share", "route", "key", "bad"}, the
    worst of each over the prompts."""
    worst = {"share": 0.0, "route": 0.0, "key": 0.0, "bad": 0}
    for prompt, got, choices in zip(prompts, logits, kept):
        ref = run_reference(toy, prompt, choices)
        verdict = compare(got, np.asarray(ref["logits"]), TOY_LIMIT)
        worst["share"] = max(worst["share"], verdict["share_of_spread"])
        worst["route"] = max(worst["route"],
                             float(np.asarray(ref["shortfall"]).max()))
        bad = np.asarray(ref["key_bad"])
        worst["key"] = max(worst["key"], float(np.where(
            bad, 0.0, np.asarray(ref["key_shortfall"])).max()))
        worst["bad"] += int(bad.sum())
    return worst


def passes(worst):
    family = mm.load_family("dots3_note")
    return worst["share"] <= TOY_LIMIT and worst["key"] <= TOY_KEY_SLACK \
        and worst["route"] <= family.ROUTE_SLACK and not worst["bad"]


# -- the whole stack --------------------------------------------------------

#: dispatches of 16 rows (512 tokens): requests under ``index_topk`` 48
#: and the window 37 and over them in one pool, boundaries inside the
#: kernels' tiles and blocks, one request that ends inside a row, one of
#: exactly ``index_topk`` tokens, a pad row or two behind
DISPATCHES = {"under_and_over": [230, 150, 30], "at_topk": [48, 49, 300]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_matches_the_reference(toy, case):
    prompts = prompts_of(DISPATCHES[case], seed=3)
    logits, kept, _ = run_program(toy, prompts, 16)
    worst = held_to_the_check(toy, prompts, logits, kept)
    assert passes(worst), worst
    # the reference's own free run: its sets are the program's but for
    # scores within rounding of the cut, and a set has min(t + 1, topk)
    # keys, none of the future or of another request
    for prompt, choices in zip(prompts, kept):
        count = len(prompt)
        own = np.asarray(run_reference(toy, prompt, keep_sets=True)
                         ["key_sets"])
        mine = sets_of(choices, count)
        assert mine.shape == own.shape == (2, count, count)
        assert (mine.sum(-1) == np.minimum(np.arange(count) + 1, TOPK)).all()
        assert not np.triu(mine, 1).any()
        assert (mine != own).sum() <= 0.02 * own.sum()
        from rnb_tpu.ops import indexed
        bits = indexed.unpack_sets(choices["key_sets"])
        assert bits.sum() == mine.sum()      # nothing outside the request


def test_packing_is_invisible(toy):
    """A request's logits do not depend on what it is packed beside, nor
    on where in the pool it lies."""
    prompts = prompts_of([230, 150, 30], seed=3)
    together, _, _ = run_program(toy, prompts, 16)
    alone, _, _ = run_program(toy, prompts[1:2], 16)
    np.testing.assert_allclose(alone[0], together[1], atol=2e-2)


def test_the_float8_control_fails_the_same_comparison(toy):
    from rnb_tpu.models.dots3_note import network
    prompts = prompts_of(DISPATCHES["under_and_over"], seed=3)
    logits, kept, _ = run_program(toy, prompts, 16,
                                  index_bits=network.FLOAT8_BITS)
    worst = held_to_the_check(toy, prompts, logits, kept)
    assert not passes(worst), worst
    # given the program's sets the logits do not notice: the sets do
    assert worst["share"] <= TOY_LIMIT and worst["key"] > TOY_KEY_SLACK


#: a fault -> the limit it must break: the share of the logits' spread
#: or the key slack (with the program's sets given, the logits do not
#: notice a set that is not the indexer's)
FAULTS = {"gate_flat_full": "share", "gate_flat_sliding": "share",
          "rho_q_full": "share", "rho_kv_full": "share",
          "rho_q_sliding": "share", "rho_kv_sliding": "share",
          "window_a_key_short": "share", "index_no_rotary": "key",
          "theta_swapped": "share", "set_noise": "key"}


def noise_on_one_set(network):
    """A fault of the tests' own: a noise as large as their spread on
    the index weights of the first full layer the program traces."""
    import jax
    import jax.numpy as jnp
    stated, traced = network.index_operands, []

    def noisy(*args, **kwargs):
        qi, ki, w = stated(*args, **kwargs)
        if not traced:
            w = w + jax.random.normal(jax.random.PRNGKey(0), w.shape,
                                      w.dtype) * jnp.std(w)
        traced.append(1)
        return qi, ki, w
    return {"patch": {"index_operands": noisy}}


def test_the_program_has_no_switch_for_a_fault():
    """What a control changes it changes from outside: the program's
    keywords are the interpreter's and the lower-precision control's."""
    import prefill_control
    from rnb_tpu.models.dots3_note import network
    keywords = [name for name, p in inspect.signature(
        network.forward).parameters.items() if p.kind is p.KEYWORD_ONLY]
    assert keywords == ["interpret", "index_bits"]
    faults = prefill_control.dots3_note_faults(
        network.Dots3NoteConfig.from_published(TOY))
    assert set(FAULTS) - {"set_noise"} <= set(faults)
    assert {"flat_gates", "no_rescale", "old_draw"} <= set(faults)
    for name, _, _ in prefill_control.arms_of("dots3_note")[2:-1]:
        assert name in faults


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_fault_moves_the_result_past_a_limit(toy, name):
    """Each changes what the program is given and nothing in it — a
    type's gates flat at a half, a rescale left out, the window a key
    short, the indexer's rotary left out, the two rotary bases swapped,
    a noise on layer 0's index weights — and the comparison with the
    reference must fail by the limit named."""
    import prefill_control
    from rnb_tpu.models.dots3_note import network
    fault = noise_on_one_set(network) if name == "set_noise" \
        else prefill_control.dots3_note_faults(toy["cfg"])[name]
    prompts = prompts_of(DISPATCHES["under_and_over"], seed=3)
    logits, kept, _ = run_program(toy, prompts, 16, fault=fault)
    worst = held_to_the_check(toy, prompts, logits, kept)
    assert not passes(worst), worst
    if FAULTS[name] == "share":
        # one key of 37 in three layers moves the least of them
        assert worst["share"] > (1.5 if name == "window_a_key_short"
                                 else 2) * TOY_LIMIT, worst
    else:
        assert worst["key"] > 4 * TOY_KEY_SLACK, worst


# -- the share ----------------------------------------------------------------


def test_eight_shares_and_the_shared_expert_add_up_to_the_layer(toy):
    """The guide's share test: the routed parts the eight shares of 8
    experts give, with what every chip computes alike — the shared
    expert — counted once, add up to the uncut reference's layer."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.dots3_note import checkpoint, network
    from rnb_tpu.ops import moe
    cfg, layer = toy["cfg"], 2
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((4, Q, cfg.hidden_size)),
                    jnp.bfloat16)
    ok = jnp.ones((4, Q), bool)
    flat = h.reshape(-1, cfg.hidden_size)
    total, shared, ids = 0.0, None, None
    for share in range(8):
        held = tuple(range(8 * share, 8 * share + 8))
        p = checkpoint.make_params(cfg, SEED, held, toy["device"],
                                   groups=["l%d" % layer])["l%d" % layer]
        out, ids, counts, _, _ = network.experts_ffn(
            cfg, p, h, ok, network.held_slots(cfg, held), interpret=True)
        if shared is None:
            shared = moe.dense_expert(flat, p["shared_up"], p["shared_down"],
                                      p["shared_gate"])
        assert int(counts.sum()) == int(np.isin(np.asarray(ids), held).sum())
        total = total + (np.asarray(out).reshape(flat.shape)
                         - np.asarray(shared))
    total = total + np.asarray(shared)
    with jax.default_matmul_precision("highest"):
        whole, ref_ids, _, _ = toy["reference"].experts(
            toy["read"], layer, flat.astype(jnp.float32), range(64),
            forced=jnp.asarray(ids))
    whole = np.asarray(whole)
    assert np.abs(total - whole).max() <= 0.03 * whole.std()
    # and one share alone is not the layer
    assert np.abs(np.asarray(out).reshape(flat.shape) - whole).max() \
        > 0.5 * whole.std()


# -- the counters -------------------------------------------------------------


def test_the_counters_are_a_numpy_count(toy):
    lengths = DISPATCHES["under_and_over"]
    prompts = prompts_of(lengths, seed=3)
    _, kept, counts = run_program(toy, prompts, 16)
    at = np.concatenate([np.arange(n) for n in lengths]) + 1
    chooses = at > TOPK
    want = [len(at), int(chooses.sum()), int(at[chooses].sum()),
            int(chooses.sum()) * TOPK]
    assert counts["sparse"].tolist() == [want, want]
    kept_pairs = [int(np.minimum(at, WINDOW).sum()), int(at.sum())]
    assert counts["window_keys"].tolist() == [kept_pairs] * 3
    # 512 tokens in blocks of 64 (the least whole sublane tiles over the
    # window's 36 that divide the pool): 8 steps a head
    assert counts["window_tiles"].tolist() == [[8, 20]] * 3
    # 4 x 4 tiles of 128, 10 on or under the diagonal; a request's tiles
    # alone hold its chosen keys
    assert counts["index_tiles"][:, 1].tolist() == [10, 10]
    assert (counts["index_tiles"][:, 0] <= 10).all()
    # the thresholds' walk, by hand from the pool's layout: 4 query
    # steps of 128 over one chunk of 512 keys, none counted in a step
    # no query of which has topk keys to read
    row_start = np.asarray(pack(prompts, 16)[1][1])
    position = np.arange(16 * Q) - np.repeat(row_start * Q, Q)
    walked = sum(int((position[i:i + 128] + 1 >= TOPK).any())
                 for i in range(0, 16 * Q, 128))
    assert counts["index_chunks"].tolist() == [[walked, 4]] * 2
    assert 0 < walked <= 4
    assert counts["expert_served"].shape == (4, 8)
    assert counts["pair_rows"].shape == (4, 2) \
        and counts["gmm_rows"].shape == (4,)
    assert (counts["pair_rows"][:, 1] == 512 * 8).all()


# -- operations and parameters ------------------------------------------------


def real_config():
    with open(os.path.join(REPO, REAL)) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues():
    """144.05 M a full mixer, 90.83 M a sliding one, 4.087 B held."""
    from rnb_tpu.models.dots3_note import flops, network
    family = mm.load_family("dots3_note")
    config = real_config()
    cfg = network.Dots3NoteConfig.from_published(
        family.published_keys(config))
    full = 5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 \
        + 16384 * 5120 + 5120 * 128 \
        + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    sliding = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 \
        + 1024 * 64 * 320 + 8192 * 5120 + 5120 * 64
    assert (full, sliding) == (144_048_128, 90_832_896)
    assert flops.mixer_params(cfg, cfg.full) == full \
        == family.mixer_params(config, False)
    assert flops.mixer_params(cfg, cfg.sliding) == sliding \
        == family.mixer_params(config, True)
    held = flops.held_params(cfg, 32)
    assert round(held / 1e9, 3) == 4.087
    assert round(2 * held / 2 ** 30, 2) == 7.61 \
        == config["model"]["weights_gib"]
    # the tensors the program makes are those parameters and the norms'
    # vectors, the indexer's bias and the correction bias
    from rnb_tpu.models.dots3_note import checkpoint
    made = sum(int(np.prod(spec.shape))
               for group in checkpoint.tensor_specs(cfg, 32).values()
               for spec in group.values())
    # and the stored pads: q_b's turned and empty columns (64 of 256 a
    # head in 2 full layers, 128 of 384 in 3 sliding ones), kv_b's 64
    # empty key columns a head in the sliding layers
    pads = 2 * 128 * 1024 * 64 + 3 * 64 * 1024 * 128 + 3 * 64 * 1024 * 64
    assert 0 <= made - held - pads < 200_000


def test_operation_counts_agree_with_the_programs():
    from rnb_tpu.models.dots3_note import flops, network
    family = mm.load_family("dots3_note")
    config = real_config()
    cfg = network.Dots3NoteConfig.from_published(
        family.published_keys(config))
    causal, chosen, window = family.mean_reads(config)
    assert 5600 < causal < 5800 and 1800 < chosen < 1900 \
        and 495 < window < 505
    assert family.flops_per_row(config) == 128 * flops.flops_per_token(
        cfg, causal, chosen, window, 8 * 32 / 256)
    tokens, dispatches = 1e6, 70.0
    for mechanism in ("index_scores", "select", "full_attn", "chosen_attn",
                      "window_attn", "mla_proj"):
        ops, nbytes = family.mechanism_work(config, mechanism, tokens,
                                            dispatches)
        assert ops > 0 and nbytes > 0, mechanism
    assert family.mechanism_work(config, "index_scores", tokens,
                                 dispatches)[0] \
        == 2 * tokens * causal * 64 * 128 * 2
    assert family.mechanism_work(config, "window_attn", tokens,
                                 dispatches)[0] \
        == 3 * tokens * 2.0 * window * 64 * (256 + 128)
    full = family.mechanism_work(config, "full_attn", tokens, dispatches)
    asked = family.mechanism_work(config, "chosen_attn", tokens, dispatches)
    assert full[0] > asked[0] and full[1] == asked[1]
    for mechanism in ("experts", "gmm"):
        ops, nbytes = family.mechanism_work(config, mechanism, tokens,
                                            4e6, dispatches)
        assert ops > 0 and nbytes > 0
    with pytest.raises(ValueError):
        family.mechanism_work(config, "flash", tokens, dispatches)


# -- the real configuration ---------------------------------------------------


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "dots3-note-prev":
                return row
    return None


def test_the_real_configuration_keeps_the_published_sizes():
    config = real_config()
    family = mm.load_family("dots3_note")
    assert family.check_config(config) == []
    entry = mm.config_entry(mm.load(), "dots3-note-l5-ep8")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 46,
                                   "n_routed_experts": 256,
                                   "vocab_size": 152064}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 32, 19008)
    assert config["layer_types"][:5] == [FULL, FULL, SLIDING, SLIDING,
                                         SLIDING]
    assert len(config["layer_types"]) == 46
    for text in config["assumed"].values():
        assert isinstance(text, str) and text
    row = catalog_row()
    if row is None:
        pytest.skip("no catalog beside the model-configs guide here")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


def test_the_toy_keeps_what_makes_the_model():
    from rnb_tpu.models.dots3_note import network
    cfg = network.Dots3NoteConfig.from_published(TOY)
    full, sliding = cfg.full, cfg.sliding
    assert cfg.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    assert full.heads != sliding.heads and full.qk_dim != sliding.qk_dim \
        and full.kv_rank != sliding.kv_rank and full.theta != sliding.theta
    assert full.qk_dim > full.value and sliding.qk_dim > sliding.value
    # a full layer's own key is whole lane tiles, a sliding layer's is
    # padded to the queries' lanes: the two forms the real widths take
    assert full.key_lanes == full.nope and sliding.key_lanes == sliding.lanes
    real = network.Dots3NoteConfig.from_published(
        mm.load_family("dots3_note").published_keys(real_config()))
    assert real.full.key_lanes == real.full.nope == 128
    assert real.sliding.key_lanes == real.sliding.lanes == 256
    assert WINDOW % 16 and TOPK < min(DISPATCHES["under_and_over"][:2])
    assert cfg.rescales(full) == (2 ** 0.5, (64 / 24) ** 0.5)


# -- the family's record for ``family_contract.py`` -----------------------


def toy_config():
    config = real_config()
    config.update(TOY)
    config["experts_held"] = {"first": 0, "count": 8}
    config["vocab_held"] = {"first": 0, "count": TOY["vocab_size"]}
    config["model"] = dict(config["model"], layers=5)
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 110, "sigma": 0.5,
                                   "min": 30, "max": 200},
                         "long": {"count": 2, "min": 200, "max": 256}}
    config["capacity_videos_per_chip_s"] = 60
    config["share_of_spread"] = TOY_LIMIT
    config["key_slack"] = TOY_KEY_SLACK
    config["ref_pad"] = 64
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=Q)
    batcher.update(batch=8, shapes=[[8, Q], [8]], row_buckets=[8])
    prefill.update(max_rows=8, chunk=Q, row_buckets=[8],
                   sample_every=3, samples=8)
    return config


def the_stage_counts_what_the_four_lines_carry(served):
    """``family_contract.stage_serves``'s entry for this family: what
    the four log-meta lines carry, the kernels' names, and both kinds of
    choice in a sample."""
    from rnb_tpu.ops import banded, indexed
    from rnb_tpu.telemetry import stage_counter_report
    stage, valid = served.stage, served.valid
    counters = stage.stage_counters()
    at = np.concatenate([np.arange(len(p)) for p in served.prompts]) + 1
    # two full layers, two dispatches
    assert counters["sparse"].tolist() == [
        4 * valid, 4 * int((at > TOPK).sum()),
        4 * int(at[at > TOPK].sum()), 4 * int((at > TOPK).sum()) * TOPK]
    # three sliding layers, two dispatches: the pairs a window of 37 keeps
    assert counters["window_keys"].tolist() == [
        6 * int(np.minimum(at, 37).sum()), 6 * int(at.sum())]
    assert counters["window_tiles"].tolist() == [6 * 4, 6 * 6]
    assert counters["index_tiles"].tolist()[1] == 4
    assert counters["expert_served"].shape == (4, 8)
    lines, fields = stage_counter_report([counters])
    assert [line.split(":")[0] for line in lines] \
        == ["Tokens", "Experts", "Sparse", "Attention"]
    assert " pair_rows_moved=" in lines[1] and " gmm_rows=" in lines[1]
    assert " tiles_chosen=" in lines[2] \
        and " chunks_walked=" in lines[2] \
        and lines[2].endswith(" chunks_to_diagonal=%d" % (2 * 2 * 2))
    assert lines[3].startswith("Attention: window_tiles_visited=24 ") \
        and " window_keys_kept=" in lines[3]
    assert fields["window_keys_causal"] == 6 * int(at.sum())
    kernels = " ".join(stage.hlo_scopes)
    for kernel in (indexed.LATENT_KERNEL, banded.LATENT_KERNEL_NAME,
                   indexed.SCORES_KERNEL, "mla_queries"):
        assert kernel in kernels or stage._jax_device.platform != "tpu"
    first = stage._samples[0]
    assert first["key_sets"].shape[:2] == (2, 120) and first["first"] == 0
    assert first["logits"].shape == (TOY["vocab_size"],)


def the_controls_read(out):
    # the witness is recorded either way: the toy's rescale is 1.4 and
    # 1.6, not the published 2.2 and 3.2, and its old draw is no sharper
    assert "old_draw" in mm.load_family("dots3_note").CONTROL_MAY_PASS
    assert "no_rescale" not in out


#: ``tests/test_dots3_note_cell.py`` runs it
CONTRACT = contract.Family(
    name="dots3_note", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts:", " gmm_rows=", " pair_rows_moved=",
          "Sparse: queries=", " tiles_chosen=", " chunks_walked=",
          " chunks_to_diagonal=", "Attention: window_tiles_visited=",
          " window_keys_kept="),
    scopes=("/attn/select/index/", "/attn/full/", "/attn/window/",
            "/attn/mla_proj/", "/attn/gate/"),
    sample_fields=("tokens", "logits", "chosen", "key_sets", "first"),
    sample_shapes={"key_sets": (2,)},      # the full layers'
    traced={
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "held_assignment_pct.bulk": "(0, 100)",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "sparse_query_pct.bulk": "(0, 100)",
        "selected_key_pct.bulk": "(0, 100)",
        "chosen_tile_pct.bulk": "(0, 100]",
        "select_chunk_walk_pct.bulk": "(0, 100]",
        "window_key_pct.bulk": "(0, 100)",
        "gmm_row_fill_pct.bulk": "(0, 100]",
        "pair_rows_moved_pct.bulk": "(0, 100]"},
    not_from_a_cpu="roofline|util|_ms_per_|busy_pct",
    stage=contract.Stage(
        lengths=(120, 70, 30), row_buckets=(8,), dispatches=2,
        scopes=("/embed/", "/attn/", "/attn/mla_proj/", "/attn/gate/",
                "/attn/select/", "/attn/select/index/", "/attn/full/",
                "/attn/window/", "/experts/", "/head/"),
        chosen_shape=(4, 120, 8),
        also=the_stage_counts_what_the_four_lines_carry),
    # as stated inside the limit and both slacks, the arms asked for
    # outside one of them
    control=contract.Control(
        lengths="150,30,230",
        arms="index_float8,flat_gates,old_draw,layers_float8",
        outside=("index_float8", "flat_gates", "layers_float8"),
        reads={("as_stated", "key_shortfall_max"):
               "(-inf, %r)" % TOY_KEY_SLACK,
               ("old_draw", "share_of_spread"): "(0, inf)",
               ("index_float8", "key_shortfall_max"):
               "(%r, inf)" % TOY_KEY_SLACK,
               ("flat_gates", "share_of_spread"): "(0.2, inf)"},
        also=the_controls_read))


# -- the seven new readers ------------------------------------------------

NEW_READERS = {
    "mla_index_scores_roofline_pct.bulk": "index_scores",
    "mla_indexed_attn_ms_per_dispatch.bulk": "latent_indexed_attention",
    "mla_indexed_attn_roofline_pct.bulk": "latent_indexed_attention",
    "mla_window_attn_ms_per_dispatch.bulk": "latent_banded_attention",
    "mla_window_attn_roofline_pct.bulk": "latent_banded_attention",
    "mla_latent_proj_ms_per_dispatch.bulk": "attn/mla_proj",
    "window_key_pct.bulk": None}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_on_a_run_without_its_kernel(
        name, tmp_path):
    """No trace, or a counter that counted nothing (the parent's
    program): None, not a raise. The kernels' names are the program's."""
    from rnb_tpu.ops import banded, indexed
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    # PR 59's cell, windows of 512 over the same counter, joined one
    joined = ["phi4-flash.bulk"] if name == "window_key_pct.bulk" else []
    assert entry and entry[0]["workloads"] == [CELL] + joined
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "packed attention"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("dots3_note")
        config = real_config()
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    assert module.read(Facts) is None
    if NEW_READERS[name] is None:
        Result.window_keys_kept, Result.window_keys_causal = 0, 0
        assert module.read(Facts) is None
        Result.window_keys_kept, Result.window_keys_causal = 9, 100
        assert module.read(Facts) == 9.0
        return
    if "/" in NEW_READERS[name]:
        # a reader of a scope the family names, not of a kernel
        assert not hasattr(module, "KERNEL")
        assert '"%s"' % NEW_READERS[name] in inspect.getsource(module.read)
        return
    assert module.KERNEL == NEW_READERS[name] and module.KERNEL in (
        indexed.SCORES_KERNEL, indexed.LATENT_KERNEL,
        banded.LATENT_KERNEL_NAME)
    # another family's file counts none of these mechanisms: no raise
    Facts.family = mm.load_family("keye_vl2")
    assert module.read(Facts) is None


def test_the_cell_joins_the_accepted_metrics_its_readers_serve():
    per_layer = {m["name"]: m for m in mm.load()["per_layer"]}
    joined = [name for name, m in per_layer.items()
              if CELL in m["workloads"]]
    # ISSUE 55's 23 and the eight of set-up, the two accepted readers
    # that find this family's scope and counter (the indexer's, the
    # window's tiles), the cell's own seven, and PR 56's reader of the
    # thresholds' walk, which came with both cells that run it
    assert len(joined) == 23 + 8 + 2 + 7 + 1
    assert {"indexer_ms_per_dispatch.bulk",
            "window_tile_visit_pct.bulk"} <= set(joined)
    # readers of another family's kernel or scope by name stay as they were
    for name in ("mla_proj_ms_per_dispatch.bulk", "flash_roofline_pct.bulk",
                 "indexed_attn_roofline_pct.bulk",
                 "window_attn_roofline_pct.bulk"):
        assert CELL not in per_layer[name]["workloads"]
    for m in per_layer.values():
        assert m["workloads"].count(CELL) <= 1
        if CELL in m["workloads"] and len(m["workloads"]) > 1:
            # last but for the cells later PRs appended (PR 59's, PR 62's)
            behind = m["workloads"][m["workloads"].index(CELL) + 1:]
            assert set(behind) <= {"phi4-flash.bulk", "xing4.bulk"}
