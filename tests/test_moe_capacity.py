"""``ops/moe.held_experts`` with pair buffers sized by the share of
experts held (``capacity``) against the same call with buffers of all
T k pairs: the same inputs give the same sums and counts, whatever the
router does — no pair held, a typical load, exactly the capacity, and
every pair held (the overflow takes further passes and computes all of
them); the counter of pair rows moved; ``pair_capacity``'s table; the
``Experts:`` line with and without the pair and the reader of it.
Toy widths on the CPU, the kernels in Pallas's interpret mode. Nothing
here needs the native decode library or a chip."""

import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import manifest as mm  # noqa: E402

TOKENS, K, HIDDEN, INNER = 64, 4, 64, 32
#: the last tokens of the pool are padding
PAD = 5


def layer(experts, held, routing, exact_weights, seed=0):
    """-> the arguments of ``held_experts`` for ``held`` of ``experts``
    experts held: ``routing`` says where the router sends the tokens."""
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((TOKENS, HIDDEN))
    if routing == "none_held" or isinstance(routing, int):
        ids = np.stack([held + rng.permutation(experts - held)[:K]
                        for _ in range(TOKENS)])
        # ... but for so many pairs, one a valid token, in any place
        for token in range(routing if isinstance(routing, int) else 0):
            ids[token, rng.integers(K)] = token % held
    elif routing == "all_held":
        ids = np.stack([rng.permutation(held)[:K] for _ in range(TOKENS)])
    else:
        ids = np.stack([rng.permutation(experts)[:K]
                        for _ in range(TOKENS)])
    if exact_weights:
        # powers of two: a row times its weight is exact, so a fused
        # multiply-add (XLA's CPU backend contracts the kernel's
        # product and sum; the TPU's vector unit has none) rounds as
        # the separate product and sum do, and what is left to differ
        # is the order of a token's additions
        weights = 2.0 ** rng.integers(-3, 2, (TOKENS, K))
    else:
        weights = rng.random((TOKENS, K)) + 0.1

    def stack():
        return jnp.asarray(rng.standard_normal((held, INNER, HIDDEN))
                           * HIDDEN ** -0.5, jnp.bfloat16)
    return (jnp.asarray(x, jnp.bfloat16), jnp.asarray(ids, jnp.int32),
            jnp.asarray(weights, jnp.float32),
            jnp.asarray(np.arange(TOKENS) < TOKENS - PAD),
            moe.held_slots(experts, tuple(range(held))),
            stack(), stack(), stack())


def both(args, capacity, gated=True):
    """-> ((sums, counts, the rows the first grouped product
    multiplied) of all T k pairs, (the same and the pair rows moved)
    under ``capacity``)."""
    import jax

    from rnb_tpu.ops import moe

    def run(capacity):
        return jax.jit(lambda *a: moe.held_experts(
            *a[:7], interpret=True, gate=a[7] if gated else None,
            capacity=capacity))(*args)
    return [[np.asarray(part) for part in run(c)] for c in (None, capacity)]


def held_pairs(args):
    ids, ok, slots = (np.asarray(args[i]) for i in (1, 3, 4))
    return int(((slots[ids] >= 0) & ok[:, None]).sum())


#: (experts, held, routing (a number: so many pairs held), capacity, exact
#: weights, passes the held pairs take)
CASES = {
    "eighth_typical": (16, 2, "uniform", 64, True, 1),
    "quarter_typical": (16, 4, "uniform", 96, True, 1),
    "eighth_typical_any_weights": (16, 2, "uniform", 64, False, 1),
    "quarter_relu2": (16, 4, "uniform", 96, True, 1),
    "no_pair_held": (16, 2, "none_held", 64, True, 0),
    "exactly_the_capacity": (16, 2, 32, 32, True, 1),
    "one_over_the_capacity": (16, 2, 33, 32, True, 2),
    "every_pair_held": (8, 4, "all_held", 64, True, 4),
    "every_pair_held_any_weights": (8, 4, "all_held", 96, False, 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sized_buffers_give_the_sums_of_all_pairs(case):
    """Nothing is dropped, whatever the load: the counts are equal, the
    counter says how many passes of ``capacity`` rows the held pairs
    took, and the sums are equal to the bit where a token's rows are
    added in the order j = 0 ... k-1 — one pass, and weights whose
    products are exact (``layer`` says why). Over the capacity the
    passes add a token's rows expert group by expert group, and with
    any weights the CPU contracts: within 2 float32 ulp of the largest
    sum."""
    experts, held, routing, capacity, exact, passes = CASES[case]
    args = layer(experts, held, routing, exact)
    n_here = held_pairs(args)
    if isinstance(routing, int):
        assert n_here == routing
    (want, counts, gmm_rows), (got, counts_sized, gmm_rows_sized, moved) = \
        both(args, capacity, gated="relu2" not in case)
    assert counts.tolist() == counts_sized.tolist()
    # the rows the first product multiplied for the held pairs: a
    # pass's grid steps over its share of each group
    assert (n_here == 0) == (gmm_rows_sized == 0) == (gmm_rows == 0)
    assert gmm_rows >= n_here and gmm_rows_sized >= n_here
    assert counts.sum() == n_here
    assert (n_here == 0) == (routing == "none_held")
    assert (n_here == (TOKENS - PAD) * K) == (routing == "all_held")
    assert moved == passes * capacity
    assert -(-n_here // capacity) == passes
    assert np.isfinite(got).all()
    assert (got[TOKENS - PAD:] == 0).all() and (want[TOKENS - PAD:] == 0).all()
    if exact and passes == 1:
        assert np.array_equal(got, want)
    else:
        ulp = np.spacing(np.float32(np.abs(want).max()))
        assert np.abs(got - want).max() <= 2 * ulp
    if n_here:
        assert np.abs(want).max() > 0.01


def test_an_unserved_pairs_row_is_never_read():
    """What the grouped product leaves behind the last group is
    unspecified; the combine copies served pairs' rows and no other, so
    a NaN there stays there. An earlier pass's sums are added to."""
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    rng = np.random.default_rng(1)
    out = rng.standard_normal((16, HIDDEN)).astype(np.float32)
    out[5:] = np.nan
    # three served pairs, two of token 3 and one of token 6, of 8 tokens
    rows = np.array([4, 0, 2] + [9] * 13, np.int32)
    token = np.array([3, 3, 6] + [8] * 13, np.int32)
    weight = rng.random(16).astype(np.float32)

    def combine(acc):
        return moe.combine_pairs(
            jnp.asarray(out), jnp.asarray(rows), jnp.asarray(token),
            jnp.asarray(weight), acc, interpret=True)
    zeros = jnp.zeros((8, 8, HIDDEN // 8), jnp.float32)
    got = np.asarray(combine(zeros)).reshape(8, HIDDEN)
    want = np.zeros((8, HIDDEN), np.float32)
    want[3] = out[4] * weight[0] + out[0] * weight[1]
    want[6] = out[2] * weight[2]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    again = np.asarray(combine(combine(zeros))).reshape(8, HIDDEN)
    np.testing.assert_allclose(again, 2 * want, rtol=1e-6)


#: (tokens, k, held, experts) -> capacity: K-EXAONE's five row buckets of
#: 128 tokens (16 of 128 experts, top-8: a quarter of the pairs);
#: DeepSeek-V2's 64 rows (20 of 160, top-6); a size that is no multiple
#: of 512 rounds up; Nemotron-H's and Qwen3-Next's half shares, and a
#: dispatch too small for a 512-row granule: None
CAPACITIES = [
    ((64 * 128, 8, 16, 128), 16384), ((80 * 128, 8, 16, 128), 20480),
    ((96 * 128, 8, 16, 128), 24576), ((112 * 128, 8, 16, 128), 28672),
    ((128 * 128, 8, 16, 128), 32768), ((64 * 128, 6, 20, 160), 12288),
    ((1000, 8, 16, 128), 2048), ((64 * 128, 6, 64, 128), None),
    ((128 * 128, 10, 256, 512), None), ((128 * 128, 8, 32, 128), None),
    ((8 * 16, 4, 2, 16), None), ((32 * 16, 4, 2, 16), 512),
]


@pytest.mark.parametrize("shapes,capacity", CAPACITIES)
def test_the_capacity_is_a_function_of_the_shapes(shapes, capacity):
    from rnb_tpu.ops import moe
    assert moe.pair_capacity(*shapes) == capacity
    if capacity is not None:
        tokens, k, held, experts = shapes
        assert capacity % 512 == 0 and 2 * capacity < tokens * k
        assert capacity >= 2 * tokens * k * held / experts > capacity - 512


def test_k_exaones_row_buckets_are_the_tables():
    """The real configuration's row buckets are the five of the table
    above, and its family passes the capacity where the three older
    callers of ``held_experts`` pass none."""
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "k-exaone-l5-ep8.json")) as f:
        config = json.load(f)
    buckets = config["pipeline_config"]["pipeline"][-1]["row_buckets"]
    assert [rows * config["chunk_size"] for rows in buckets] \
        == [shapes[0] for shapes, _ in CAPACITIES[:5]]
    for family, passes in (("exaone_moe", True), ("nemotron_h", False),
                           ("deepseek_v2", False), ("qwen3_next", False)):
        with open(os.path.join(REPO, "rnb_tpu", "models", family,
                               "network.py")) as f:
            assert ("capacity=" in f.read()) == passes, family


# -- the counter's line and its reader ------------------------------------------------

#: the line -> (pair_rows_moved_pct.bulk, gmm_row_fill_pct.bulk) its
#: readers give; the first four are the parent's lines (PR 44's program
#: writes ``gmm_rows=`` for the three families that count it)
EXPERTS_LINES = {
    "nemotron_h": ("Experts: assignments=900 held=450 max_per_expert=40 "
                   "mean_per_expert=28.125\n", None, None),
    "deepseek_v2": ("Experts: assignments=900 held=120 max_per_expert=40 "
                    "mean_per_expert=7.500 group_tokens=95\n", None, None),
    "exaone_moe": ("Experts: assignments=900 held=110 max_per_expert=40 "
                   "mean_per_expert=6.875 group_tokens=95 "
                   "pair_rows_moved=512 pair_rows_all=2048\n", 25.0, None),
    "exaone_moe_overflowing": (
        "Experts: assignments=900 held=880 max_per_expert=400 "
        "mean_per_expert=55.000 group_tokens=150 "
        "pair_rows_moved=2048 pair_rows_all=2048\n", 100.0, None),
    "nemotron_h_rows": (
        "Experts: assignments=900 held=450 max_per_expert=40 "
        "mean_per_expert=28.125 gmm_rows=1024\n", None, 100.0 * 450 / 1024),
    "sized_and_counting_rows": (
        "Experts: assignments=900 held=110 max_per_expert=40 "
        "mean_per_expert=6.875 group_tokens=95 pair_rows_moved=512 "
        "pair_rows_all=2048 gmm_rows=512\n", 25.0, 100.0 * 110 / 512),
    "no_pair_held_rows": (
        "Experts: assignments=900 held=0 max_per_expert=0 "
        "mean_per_expert=0.000 gmm_rows=0\n", None, None),
}


@pytest.mark.parametrize("family", sorted(EXPERTS_LINES))
def test_the_experts_line_with_and_without_the_rows(family, tmp_path):
    """The line as ``rnb_tpu.benchmark`` writes it for each family — the
    parent's byte for byte what they were — parses, and each reader
    gives its share (the pair rows moved; the multiplied rows kept), or
    None where the program counts none or the count is 0."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import parse_utils
    from rnb_tpu.benchmark import BenchmarkResult
    from rnb_tpu.telemetry import STAGE_COUNTERS, stage_counter_report
    PAIR_ROW_COUNTS, = [row.keys for row in STAGE_COUNTERS
                        if row.counter == "pair_rows"]
    line, moved_share, fill_share = EXPERTS_LINES[family]
    (tmp_path / "log-meta.txt").write_text("Tokens: valid=10 shipped=16\n"
                                           + line)
    meta = parse_utils.parse_meta(str(tmp_path))
    assert meta["experts_assignments"] == 900
    assert ("experts_pair_rows_all" in meta) == (moved_share is not None)
    assert ("experts_gmm_rows" in meta) == ("gmm_rows" in line)
    fields = BenchmarkResult.__dataclass_fields__
    assert all(fields["experts_" + key].default == 0
               for key in PAIR_ROW_COUNTS + ("gmm_rows",))
    result = types.SimpleNamespace(**{
        key: meta[key] for key in meta
        if key.startswith(("experts_pair_", "experts_gmm_",
                           "experts_held"))})
    for reader, share in (("pair_rows_moved_pct.bulk", moved_share),
                          ("gmm_row_fill_pct.bulk", fill_share)):
        got = mm.load_layer_metric(reader).read(
            types.SimpleNamespace(result=result))
        assert got == (None if share is None else pytest.approx(share))
    # the writer gives that line back from a stage's counters with the
    # parsed numbers: 150 tokens x 3 choices x 2 expert layers routed,
    # 16 (layer, held expert) loads that sum to held= under max=
    pair = {key: meta["experts_" + key] for key in PAIR_ROW_COUNTS
            if "experts_" + key in meta}
    loads, left = [], meta["experts_held"]
    for _ in range(16):
        loads.append(min(left, meta["experts_max_per_expert"]))
        left -= loads[-1]
    snap = {"tokens_valid": 150, "tokens_shipped": 256,
            "experts_per_token": 3,
            "expert_served": np.array(loads, np.int64).reshape(2, 8)}
    if "group_tokens" in line:
        snap["group_tokens"] = meta["experts_group_tokens"]
    if "gmm_rows" in line:
        snap["gmm_rows"] = meta["experts_gmm_rows"]
    if moved_share is not None:
        snap["pair_rows"] = np.array([pair[key] for key in PAIR_ROW_COUNTS])
    lines, _ = stage_counter_report([snap])
    assert lines == ["Tokens: valid=150 shipped=256", line[:-1]]
    # and two stages' counters sum
    _, experts = stage_counter_report([snap, snap])
    assert ("experts_group_tokens" in experts) == ("group_tokens" in line)
    assert experts.get("experts_gmm_rows") == (
        2 * meta["experts_gmm_rows"] if "gmm_rows" in line else None)
    summed = {key: experts["experts_" + key] for key in PAIR_ROW_COUNTS
              if "experts_" + key in experts}
    assert summed == {key: 2 * count for key, count in pair.items()}


def test_the_readers_entry_in_the_manifest():
    module = mm.load_layer_metric("pair_rows_moved_pct.bulk")
    entry = next(e for e in mm.load()["per_layer"]
                 if e["name"] == "pair_rows_moved_pct.bulk")
    # PR 55's cell, whose buffers hold a quarter of the pairs too, joined
    assert entry["workloads"] == ["k-exaone.bulk", "dots3-note.bulk"]
    assert mm.describe(module) == {k: entry[k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "sparse experts" and module.BETTER == "lower"
    assert module.read(types.SimpleNamespace(
        result=types.SimpleNamespace(experts_pair_rows_moved=0,
                                     experts_pair_rows_all=0))) is None
