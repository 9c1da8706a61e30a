"""The Nemotron-H family against its plain reference, at a toy size on
the CPU with weights from a seed: every mixer alone, the blocked scan
against the recurrence, packing, routing under skew, the expert share
against the uncut layer, the experts' way back against the scatter
form it replaced, the whole 14-block pattern through the one benchmark
command, the recipe, and the lower-precision control that must fail.
Then the real configuration: its pipeline through the program's own
checks and, where a v5e can be described, the compile of its largest
bucket and of one E block (one file, the topology inside a fixture)."""

import json
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import family_contract as contract  # noqa: E402
from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import nemotron_h as reference  # noqa: E402

REAL = "benchmarks/configs/nemotron3-nano-l14-ep2.json"
CELL = "nemotron3-nano.bulk"
SEED = 3_000_000_123

#: the published pattern's first 14 blocks at toy widths
TOY = {
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "num_hidden_layers": 14, "hidden_size": 64, "vocab_size": 256,
    "chunk_size": 16, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "published": {"n_routed_experts": 8, "num_hidden_layers": 52},
    "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 1e-4}
HELD = (0, 1, 2, 3)
Q = TOY["chunk_size"]


@pytest.fixture(scope="module")
def toy():
    import jax

    from rnb_tpu.models.nemotron_h import checkpoint, network
    cfg = network.NemotronHConfig.from_published(TOY)
    device = jax.devices()[0]
    return {"cfg": cfg, "device": device, "programs": {},
            "params": checkpoint.make_params(cfg, SEED, HELD, device),
            "slots": network.held_slots(cfg, HELD),
            "read": checkpoint.reference_reader(cfg, SEED, device),
            "reference": reference.Reference(TOY)}


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def pack(prompts, rows):
    """-> (tokens (rows, Q), meta, offsets): the prompts packed in
    order, as the loader and the Batcher pack them."""
    from rnb_tpu.models.nemotron_h import stages
    tokens = np.zeros((rows, Q), np.int32)
    per_row = np.zeros(rows, np.int32)
    offsets, row = [0], 0
    for prompt in prompts:
        n = stages.rows_of_tokens(len(prompt), Q)
        tokens.reshape(-1)[row * Q:row * Q + len(prompt)] = prompt
        per_row[row:row + n] = Q
        per_row[row + n - 1] = len(prompt) - (n - 1) * Q
        row += n
        offsets.append(row)
    return tokens, stages.dispatch_meta(offsets, per_row, rows, Q), offsets


def program_of(toy, **arm):
    """The toy stack jitted once an arm of ``forward``, kept on the
    module's ``toy``: a test that runs it at rows another has run traces
    and compiles nothing."""
    import jax

    from rnb_tpu.models.nemotron_h import network
    key = tuple(sorted(arm.items()))
    if key not in toy["programs"]:
        toy["programs"][key] = jax.jit(
            lambda p, s, t, m: network.forward(
                toy["cfg"], p, s, t, m[0], m[1], m[2], interpret=True,
                **arm))
    return toy["programs"][key]


def run_program(toy, prompts, rows, **kwargs):
    tokens, meta, offsets = pack(prompts, rows)
    logits, chosen, served, *_ = program_of(toy, **kwargs)(
        toy["params"], toy["slots"], tokens, meta)
    chosen = np.asarray(chosen)
    per_prompt = [chosen[:, o * Q:o * Q + len(p)]
                  for o, p in zip(offsets, prompts)]
    return np.asarray(logits)[:len(prompts)], per_prompt, np.asarray(served)


def run_reference(toy, prompt, forced=None, held=HELD):
    import jax
    with jax.default_matmul_precision("highest"):
        return toy["reference"].forward(toy["read"], prompt, held=held,
                                        forced=forced)


# -- every mixer alone ------------------------------------------------------


def block_inputs(toy, length, kind):
    """A normed activation (rows, Q, hidden) for one prompt of
    ``length`` tokens, the first block of ``kind`` and its weights for
    program and reference."""
    import jax.numpy as jnp
    cfg = toy["cfg"]
    index = cfg.blocks_of(kind)[0]
    rng = np.random.default_rng(length)
    rows = -(-length // Q)
    h = np.zeros((rows * Q, cfg.hidden_size), np.float32)
    h[:length] = rng.standard_normal((length, cfg.hidden_size))
    h = jnp.asarray(h, jnp.bfloat16)
    weights = {t: toy["read"]("b%d.%s" % (index, t),
                              HELD if t in ("up", "down") else None)
               for t in reference.TENSORS[kind]}
    return h, rows, toy["params"]["b%d" % index], weights


@pytest.mark.parametrize("length", [5, 16, 37, 100])
def test_mamba_mixer_and_its_blocked_scan_match_the_recurrence(toy, length):
    """Lengths that are no multiple of the chunk: the blocked scan
    (rows of 16 in the kernel of ``ops/ssd.py``, states carried between
    them) against the plain recurrence over t."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import network
    h, rows, params, weights = block_inputs(toy, length, "M")
    first = jnp.arange(rows) == 0
    got = network.mamba_mixer(toy["cfg"], params,
                              h.reshape(rows, Q, -1), first, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = reference.mamba(TOY, weights, h.astype(jnp.float32)[:length])
    got = np.asarray(got).reshape(rows * Q, -1)[:length]
    assert compare(got, np.asarray(want), 0.03)["ok"]


def test_attention_mixer_matches_one_masked_softmax(toy):
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import network
    length = 45
    h, rows, params, weights = block_inputs(toy, length, "*")
    got, tiles = network.attention_mixer(
        toy["cfg"], params, h.reshape(rows, Q, -1),
        jnp.zeros(rows, jnp.int32), interpret=True)
    assert np.asarray(tiles).tolist() == [1, 1]
    with jax.default_matmul_precision("highest"):
        want = reference.attention(TOY, weights,
                                   h.astype(jnp.float32)[:length])
    got = np.asarray(got).reshape(rows * Q, -1)[:length]
    assert compare(got, np.asarray(want), 0.03)["ok"]


def experts_both(toy, length, held, b_corr=None):
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import checkpoint, network
    cfg = toy["cfg"]
    index = cfg.blocks_of("E")[0]
    h, rows, _, weights = block_inputs(toy, length, "E")
    params = checkpoint.make_params(cfg, SEED, held, toy["device"],
                                    groups=["b%d" % index])["b%d" % index]
    weights = dict(weights)
    for t in ("up", "down"):
        weights[t] = toy["read"]("b%d.%s" % (index, t), held)
    if b_corr is not None:
        params = dict(params, b_corr=jnp.asarray(b_corr, jnp.float32))
        weights["b_corr"] = jnp.asarray(b_corr, jnp.float32)
    ok = jnp.arange(rows * Q).reshape(rows, Q) < length
    got, ids, counts, _ = network.experts_mixer(
        cfg, params, h.reshape(rows, Q, -1), ok,
        network.held_slots(cfg, held), interpret=True)
    with jax.default_matmul_precision("highest"):
        want, _, shortfall = reference.experts(
            TOY, weights, h.astype(jnp.float32)[:length],
            jnp.asarray(held, jnp.int32), forced=ids[:length])
    assert float(shortfall.max()) < 0.02
    return (np.asarray(got).reshape(rows * Q, -1)[:length],
            np.asarray(want), np.asarray(counts), weights)


def test_experts_mixer_matches_the_loop_over_chosen_experts(toy):
    got, want, counts, _ = experts_both(toy, 50, HELD)
    assert compare(got, want, 0.03)["ok"]
    assert 0 < counts.sum() < 50 * TOY["num_experts_per_tok"]


def test_routing_drops_nothing_under_a_skewed_router(toy):
    """Every token chooses experts 0 and 1: two held experts serve
    every pair, the others none, and nothing is dropped."""
    skew = np.zeros(8, np.float32)
    skew[:2] = 10.0
    got, want, counts, _ = experts_both(toy, 61, HELD, b_corr=skew)
    assert counts.tolist() == [61, 61, 0, 0]
    assert compare(got, want, 0.03)["ok"]


def test_the_share_ties_to_the_model(toy):
    """Experts 0-3 here plus experts 4-7 on the other chip, the shared
    expert counted once, are the uncut reference's E block."""
    import jax
    import jax.numpy as jnp
    length = 50
    low, _, low_n, weights = experts_both(toy, length, (0, 1, 2, 3))
    high, _, high_n, _ = experts_both(toy, length, (4, 5, 6, 7))
    assert low_n.sum() + high_n.sum() \
        == length * TOY["num_experts_per_tok"]
    index = toy["cfg"].blocks_of("E")[0]
    h = block_inputs(toy, length, "E")[0].astype(jnp.float32)[:length]
    every = tuple(range(8))
    whole = dict(weights)
    for t in ("up", "down"):
        whole[t] = toy["read"]("b%d.%s" % (index, t), every)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = reference.experts(TOY, whole, h,
                                        jnp.asarray(every, jnp.int32))
        shared = jnp.square(jax.nn.relu(h @ whole["shared_up"])) \
            @ whole["shared_down"]
    assert compare(low + high - np.asarray(shared), np.asarray(uncut),
                   0.03)["ok"]


def held_experts_by_scatter(x, ids, weights, token_ok, held_slot, up, down,
                            gate=None):
    """The oracle of the way back, as the program ran it until PR 29:
    the pairs flattened token-major, every row of the second product
    times its weight under a mask, scattered onto zeros in (token, k)
    order, summed over k."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    tokens, k = ids.shape
    held = up.shape[0]
    slot = held_slot[ids]
    slot = jnp.where(token_ok[:, None] & (slot >= 0), slot, held)
    flat_slot = slot.reshape(-1)
    order = jnp.argsort(flat_slot, stable=True)
    counts = jnp.bincount(flat_slot, length=held + 1)[:held] \
        .astype(jnp.int32)
    rows = x[order // k]
    hidden = moe.grouped_matmul(rows, up, counts, True, transposed=True)
    if gate is None:
        hidden = moe.relu2(hidden)
    else:
        hidden = jax.nn.silu(moe.grouped_matmul(
            rows, gate, counts, True, transposed=True)) * hidden
    out = moe.grouped_matmul(hidden.astype(x.dtype), down, counts, True)
    served = jnp.arange(tokens * k) < counts.sum()
    w = weights.reshape(-1)[order]
    out = jnp.where(served[:, None], out * w[:, None], 0.0)
    back = jnp.zeros((tokens * k, out.shape[1]), jnp.float32) \
        .at[order].set(out)
    return back.reshape(tokens, k, -1).sum(axis=1), counts


@pytest.mark.parametrize("case", [
    "mixed", "no_pair_held", "one_expert", "padding",
    "nan_behind_the_last_group", "gated_mixed", "gated_padding",
    "gated_nan_behind_the_last_group", "three_of_eight",
    "gated_three_of_eight"])
def test_the_way_back_is_the_scatter_forms(toy, case, monkeypatch):
    """One gather by the inverse permutation and a masked weighted sum
    over pairs that lie (k, T) give what the scatter onto zeros of
    pairs that lay (T, k) gave, whatever the kernel leaves behind the
    last group: for both forms of an expert, and for a ``k`` that is
    neither the toy's 2 nor the real 6."""
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import network
    from rnb_tpu.ops import moe
    cfg = toy["cfg"]
    gated = case.startswith("gated_")
    case = case[len("gated_"):] if gated else case
    tokens = 48
    k = 3 if case == "three_of_eight" else cfg.num_experts_per_tok
    rng = np.random.default_rng(29)
    x = jnp.asarray(rng.standard_normal((tokens, cfg.hidden_size)),
                    jnp.bfloat16)
    ids = np.argsort(rng.random((tokens, cfg.router_experts)))[:, :k]
    ok = np.ones(tokens, bool)
    if case == "no_pair_held":
        ids = 4 + ids % 4
    elif case == "one_expert":
        ids[:] = 2
    elif case == "padding":
        ok[[3, 17]] = False
        ok[31:] = False
    weights = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    block = toy["params"]["b%d" % cfg.blocks_of("E")[0]]
    gate = jnp.asarray(rng.standard_normal(block["up"].shape) * 0.2,
                       jnp.bfloat16) if gated else None
    if case == "nan_behind_the_last_group":
        product = moe.grouped_matmul

        def planted(rows, stack, counts, interpret, transposed=False,
                    tiling=None):
            out = product(rows, stack, counts, interpret, transposed, tiling)
            behind = jnp.arange(out.shape[0]) >= counts.sum()
            return jnp.where(behind[:, None], jnp.nan, out)
        monkeypatch.setattr(moe, "grouped_matmul", planted)
    args = (x, jnp.asarray(ids, jnp.int32), weights, jnp.asarray(ok),
            network.held_slots(cfg, HELD), block["up"], block["down"])
    got, counts, gmm_rows = moe.held_experts(*args, interpret=True,
                                             gate=gate)
    assert int(counts.sum()) <= int(gmm_rows)
    want, want_counts = held_experts_by_scatter(*args, gate=gate)
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    on_held = (ids < 4) & ok[:, None]
    assert int(np.asarray(counts).sum()) == int(on_held.sum())
    assert not got[~on_held.any(axis=1)].any()      # exact zeros
    if case == "no_pair_held":
        assert not got.any()
    elif case == "one_expert":
        assert np.asarray(counts).tolist() == [0, 0, tokens * k, 0]
    else:
        assert np.abs(got).max() > 0


# -- the whole pattern ------------------------------------------------------


def fuse(rows_of_requests):
    """The Batcher's emissions for requests of so many rows, driven as
    the executor drives it: ``take_ready`` ahead of each arrival,
    ``flush`` until dry at the end. -> [(request ids, valid rows,
    bucket rows, segment offsets)]."""
    from rnb_tpu.batcher import Batcher
    from rnb_tpu.stage import PaddedBatch
    from rnb_tpu.telemetry import TimeCard
    b = Batcher("host", batch=64, segments=True, shapes=[[8, Q], [8]],
                row_buckets=[4, 8])
    out = []

    def note(emission):
        if emission is not None and emission[2] is not None:
            ids, lens = emission[0]
            assert ids.max_rows == lens.max_rows
            # each request's rows carry its id: the table cuts them
            offsets = list(ids.segment_offsets)
            cards = [tc.id for tc in emission[2].time_cards]
            for card, lo, hi in zip(cards, offsets, offsets[1:]):
                assert (np.asarray(ids.data)[lo:hi] == card).all()
            out.append((cards, ids.valid, ids.max_rows, offsets))

    for rid, rows in enumerate(rows_of_requests):
        note(b.take_ready())
        tokens = np.full((rows, Q), rid, np.int32)
        note(b((PaddedBatch(tokens, rows),
                PaddedBatch(np.full((rows,), Q, np.int32), rows)),
               None, TimeCard(rid)))
    while True:
        last = b.flush()
        if last is None:
            break
        note(last)
    return out


@pytest.mark.parametrize("rows,want,shipped", [
    # a request that does not fit waits aside while those behind it
    # fill the batch, and opens the next one (first in, first out
    # would ship 32 rows: [0], [1], [2, 3], [4, 5])
    ([5, 6, 3, 2, 7, 1], [[0, 2], [1, 3], [4, 5]], 24),
    # more than four aside: the batch goes as it is, and the five open
    # the next ones in their order, each with what still fits
    ([5, 6, 7, 6, 5, 4, 3, 2], [[0], [1], [2], [3, 7], [4, 6], [5]], 44),
    # requests from aside fill a batch: it goes ahead of the next
    # arrival
    ([5, 4, 4, 3, 8], [[0, 3], [1, 2], [4]], 24),
])
def test_a_packed_batch_fills_past_a_request_that_does_not_fit(
        rows, want, shipped):
    got = fuse(rows)
    assert [cards for cards, _, _, _ in got] == want
    # every request once, whole, at a bucket that holds it
    assert sorted(r for cards, _, _, _ in got for r in cards) \
        == list(range(len(rows)))
    for cards, valid, bucket, offsets in got:
        assert valid == sum(rows[r] for r in cards) <= bucket <= 8
        assert offsets == list(np.cumsum([0] + [rows[r] for r in cards]))
    assert sum(bucket for _, _, bucket, _ in got) == shipped


def test_scope_table_reads_an_instruction_that_spans_lines():
    """A Pallas kernel's attributes hold line breaks: its scope is on
    the instruction's last line."""
    from rnb_tpu import hloscopes as stages
    text = """  %fusion.1 = f32[8,4]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(f)/ssd/mul"}
  %kernel.2 = (f32[2,8]{1,0}, bf16[2,16]{1,0}) custom-call(%a), frontend_attributes={kernel_metadata={
"xprof_metadata":"{}"
}}, metadata={op_name="jit(f)/attn/vmap(k)/pallas_call" stack_frame_id=3}
  %copy.3 = f32[8,4]{1,0} copy(%fusion.1)
  ROOT %add.4 = f32[8,4]{1,0} add(%copy.3, %p), metadata={op_name="jit(f)/head/add"}"""
    assert stages.scopes_of_hlo(text) == {
        "%fusion.1 f32[8,4]": "jit(f)/ssd/mul",
        "%kernel.2 f32[2,8]": "jit(f)/attn/vmap(k)/pallas_call",
        "%add.4 f32[8,4]": "jit(f)/head/add"}


def test_packing_is_invisible(toy):
    """A prompt's logits depend neither on what shares its dispatch
    nor on the bucket."""
    a, b, c, d = prompts_of([37, 5, 64, 20])
    alone, chosen, _ = run_program(toy, [a], 4)
    packed, _, _ = run_program(toy, [b, c, a, d], 16)
    other, _, _ = run_program(toy, [d, a], 8)
    want = run_reference(toy, a, forced=chosen[0])
    spread = float(np.asarray(want["logits"]).std())
    for got in (packed[2], other[1]):
        # the same arithmetic on the same rows: far inside the
        # comparison's tolerance
        assert np.abs(got - alone[0]).max() < 0.005 * spread
    assert compare(alone[0], np.asarray(want["logits"]), 0.03)["ok"]


def test_full_pattern_matches_the_reference_and_the_control_fails(toy):
    import jax.numpy as jnp
    family = mm.load_family("nemotron_h")
    prompts = prompts_of([5, 16, 37, 64, 20, 70], seed=4)
    logits, chosen, served = run_program(toy, prompts, 16)
    refs = [run_reference(toy, p, forced=c)
            for p, c in zip(prompts, chosen)]
    want = np.stack([np.asarray(r["logits"]) for r in refs])
    verdict = compare(logits, want, family.SHARE_OF_SPREAD)
    assert verdict["ok"], verdict
    assert max(float(r["shortfall"].max()) for r in refs) \
        < family.ROUTE_SLACK
    valid = sum(len(p) for p in prompts)
    assert served.sum(axis=1).max() <= valid * TOY["num_experts_per_tok"]
    # the reference's own free choice agrees almost everywhere
    free = run_reference(toy, prompts[3])
    agree = (np.sort(np.asarray(free["chosen"]), -1)
             == np.sort(chosen[3], -1)).all(-1).mean()
    assert agree > 0.9
    # the control: the experts' matrices through float8, and the
    # scan's states carried in bfloat16, each outside the tolerance
    fp8 = run_program(
        toy, prompts, 16,
        expert_cast=lambda w: w.astype(jnp.float8_e4m3fn)
        .astype(jnp.bfloat16))
    refs8 = np.stack([np.asarray(run_reference(toy, p, forced=c)["logits"])
                      for p, c in zip(prompts, fp8[1])])
    assert not compare(fp8[0], refs8, family.SHARE_OF_SPREAD)["ok"]


def test_recipe_gives_program_and_reference_the_same_values(toy):
    from rnb_tpu.models.nemotron_h import checkpoint
    params, read = toy["params"], toy["read"]
    for name, tensor in (("b0.in_proj", params["b0"]["in_proj"]),
                         ("top.embed", params["embed"]),
                         ("b1.b_corr", params["b1"]["b_corr"])):
        assert np.array_equal(np.asarray(tensor, np.float32),
                              np.asarray(read(name)))
    # an expert is a function of its global id, whoever holds it; its
    # first matrix is stored transposed ([held, inner, hidden]: what the
    # grouped product reads in place) and read as published
    up = np.asarray(params["b1"]["up"], np.float32)
    down = np.asarray(params["b1"]["down"], np.float32)
    inner, hidden = TOY["moe_intermediate_size"], TOY["hidden_size"]
    assert up.shape == down.shape == (len(HELD), inner, hidden)
    read_up = np.asarray(read("b1.up", (3, 1)))
    read_down = np.asarray(read("b1.down", (3, 1)))
    assert read_up.shape == (2, hidden, inner)
    assert np.array_equal(up[[3, 1]], read_up.transpose(0, 2, 1))
    assert np.array_equal(down[[3, 1]], read_down)
    # the reader's values are the ones it gave before the stored
    # orientation changed (checksums taken on PR 28's tree)
    assert read_up.dtype == read_down.dtype == np.float32
    assert zlib.crc32(read_up.tobytes()) == 1547041046
    assert zlib.crc32(read_down.tobytes()) == 502981089
    other = checkpoint.make_params(toy["cfg"], SEED, (2, 3, 4, 5),
                                   toy["device"], groups=["b1"])
    assert np.array_equal(np.asarray(other["b1"]["up"], np.float32)[:2],
                          up[2:])
    assert not np.array_equal(
        np.asarray(read("b1.up", (0,))),
        np.asarray(checkpoint.reference_reader(
            toy["cfg"], SEED + 1, toy["device"])("b1.up", (0,))))


def test_operation_counts_agree_with_the_family_file(toy):
    from rnb_tpu.models.nemotron_h import flops, network
    family = mm.load_family("nemotron_h")
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.NemotronHConfig.from_published(config)
    assert flops.flops_per_token(cfg, 512.0, 3.0) \
        == family.flops_per_token(config, 512.0, 3.0)
    assert family.flops_per_row(config) == config["chunk_size"] \
        * flops.flops_per_token(cfg, family.mean_context(config), 3.0)


# -- through the one benchmark command --------------------------------------


def toy_config():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    config.update(TOY)
    config["experts_held"] = {"first": 0, "count": 4}
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 24, "sigma": 0.8,
                                   "min": 4, "max": 60},
                         "long": {"count": 2, "min": 64, "max": 128}}
    # sized to what a CPU serves: ``family_contract.py``, "The backlog"
    config["capacity_videos_per_chip_s"] = 220
    config["share_of_spread"] = 0.06
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=16)
    batcher.update(batch=8, shapes=[[8, 16], [8]], row_buckets=[4, 8])
    prefill.update(max_rows=8, chunk=16, row_buckets=[4, 8],
                   sample_every=5, samples=8)
    return config


#: the whole 14-block pattern through ``benchmarks/run.py``
#: (``tests/test_nemotron_h_cell.py``); the family came before the
#: control script and before a family file's ``build`` refused a parent
CONTRACT = contract.Family(
    name="nemotron_h", cell=CELL, real=REAL, toy_config=toy_config,
    recipe=(TOY, SEED, HELD),
    meta=("Tokens: valid=", "Experts: assignments=",
          "Attention: tiles_visited="),
    traced={
        # a toy pool is one tile: the counter comes through the result
        "flash_tile_visit_pct.bulk": "[100, 100]",
        "tokens_per_s.bulk": "(0, inf)",
        "pad_token_pct.bulk": "(0, 100)",
        "held_assignment_pct.bulk": "(30, 70)",
        "expert_load_max_over_mean.bulk": "[1, inf)",
        "rows_per_dispatch.bulk": "(0, inf)"},
    refuses_a_parent=False)


# -- the real configuration -------------------------------------------------


def test_real_configuration_keeps_the_published_sizes():
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    entry = mm.config_entry(mm.load(), "nemotron3-nano-l14-ep2")
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "n_routed_experts"]
    assert config["family"] == "nemotron_h" and config["assumed"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        differ = sorted(k for k, v in row["config"].items()
                        if config.get(k) != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {
            k: row["config"][k] for k in config["reduced"]}
    assert len(config["hybrid_override_pattern"]) == 52


def test_real_pipeline_passes_the_programs_own_checks(tmp_path):
    from rnb_tpu.config import parse_config
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    parsed = parse_config(config["pipeline_config"])
    assert parsed.video_path_iterator \
        == "benchmarks.traffic.ScheduledPathIterator"
    path = tmp_path / "pipeline.json"
    path.write_text(json.dumps(config["pipeline_config"]))
    lint = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "rnb_lint.py"),
         "--config", str(path)], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert lint.returncode == 0, lint.stdout + lint.stderr


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
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def stage_program(one_chip):
    """The real stage program at 64 rows, compiled for a described v5e
    (nothing runs)."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import checkpoint, network
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.NemotronHConfig.from_published(config)
    step = config["pipeline_config"]["pipeline"][-1]
    rows, held = max(step["row_buckets"]), config["experts_held"]["count"]
    params = {}
    for group, tensors in checkpoint.tensor_specs(cfg, held).items():
        made = {name: jax.ShapeDtypeStruct(
            spec.shape, getattr(jnp, spec.dtype), sharding=one_chip)
            for name, spec in tensors.items()}
        params.update(made if group == "top" else {group: made})

    def of(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return jax.jit(lambda p, s, t, m: network.forward(
        cfg, p, s, t, m[0], m[1], m[2])).lower(
        params, of((cfg.router_experts,)), of((rows, cfg.chunk_size)),
        of((3, rows))).compile()


def test_largest_bucket_fits_the_chip_and_clears_the_floor(stage_program):
    """Weights and temporaries between 4 and 14 GiB."""
    memory = stage_program.memory_analysis()
    projected = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 4 * 2 ** 30 <= projected <= 14 * 2 ** 30, projected / 2 ** 30


def test_the_gather_into_expert_order_reads_the_fast_memory(stage_program):
    """A reading kept, not a mechanism of ``held_experts``: with the
    pairs (k, T) the compiler's memory space assignment keeps the
    tokens' rows in its fast memory for the gather into expert order
    in five E blocks of six, and that gather takes 0.405 ms for the
    2.08 it takes from HBM (the sixth, and all six of the (T, k) form;
    my chip runs, PR 34). ``held_slot[ids].T`` for ``held_slot[ids.T]``
    is enough to lose all five: a change that moves this count has
    moved 1.7 ms a block of the dispatch, and says so."""
    from tests.compiled_experts import gather_in_sources
    assert gather_in_sources(stage_program.as_text(), 8192, 6, 2688) \
        == [True] * 5 + [False]


def test_an_expert_block_moves_its_pairs_once_each_way(one_chip):
    """One E block of the real configuration at 64 rows, compiled for
    the described v5e (nothing runs): what ``check_pair_buffers``
    reads, and the kernel is called twice."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.nemotron_h import checkpoint, network
    from tests.compiled_experts import check_pair_buffers
    with open(os.path.join(REPO, REAL)) as f:
        config = json.load(f)
    cfg = network.NemotronHConfig.from_published(config)
    step = config["pipeline_config"]["pipeline"][-1]
    rows, held = max(step["row_buckets"]), config["experts_held"]["count"]
    specs = checkpoint.tensor_specs(cfg, held)[
        "b%d" % cfg.blocks_of(network.EXPERTS)[0]]

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def block(p, slots, x, token_ok):
        with jax.named_scope("experts"):
            h = network.rms_norm(x, p["norm"], cfg.eps, x.dtype)
            out, ids, counts, gmm_rows = network.experts_mixer(
                cfg, p, h, token_ok, slots)
            return (x.astype(jnp.float32) + out).astype(x.dtype), ids, \
                counts, gmm_rows
    text = jax.jit(block).lower(
        {name: of(spec.shape, getattr(jnp, spec.dtype))
         for name, spec in specs.items()},
        of((cfg.router_experts,), jnp.int32),
        of((rows, cfg.chunk_size, cfg.hidden_size), jnp.bfloat16),
        of((rows, cfg.chunk_size), jnp.bool_)).compile().as_text()

    d, inner = cfg.hidden_size, cfg.moe_intermediate_size
    tokens, k = rows * cfg.chunk_size, cfg.num_experts_per_tok
    stacks = {"bf16[%d,%d,%d]" % (held, a, b)
              for a, b in ((d, inner), (inner, d))}
    assert {"bf16[%s]" % ",".join(map(str, specs[t].shape))
            for t in ("up", "down")} <= stacks
    assert (tokens, k, d) == (8192, 6, 2688)
    assert check_pair_buffers(text, tokens, k, d, stacks) \
        == ["f32[%d,%d]" % (tokens * k, inner),
            "f32[%d,%d]" % (tokens * k, d)]
