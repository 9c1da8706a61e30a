"""The grouped product's row tile follows the rows a group holds (PR
44): ``rnb_tpu.ops.moe.gmm_tiling``'s rule at the cells' real shapes, the
kernel against a per-group dense product at every tile the rule returns,
``gmm_visits`` against megablox's own group metadata, the swept products
compiled for a described v5e, and the counter's way to its reader (its
line: ``tests/test_moe_capacity.py``)."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import manifest as mm  # noqa: E402

#: product -> (M, K, N, the weights lie (G, N, K), groups, the tiles the
#: sweep chose: ``gmm_tiling``'s table)
SWEPT = {
    "nemotron_first": (49152, 2688, 1856, True, 64, (128, 2688, 1024)),
    "nemotron_second": (49152, 1856, 2688, False, 64, (128, 1856, 896)),
    "qwen3_next_first": (163840, 2048, 512, True, 256, (128, 2048, 512)),
    "qwen3_next_second": (163840, 512, 2048, False, 256, (128, 512, 2048)),
    "deepseek_v2_first": (49152, 5120, 1536, True, 20, (128, 5120, 512)),
    "deepseek_v2_second": (49152, 1536, 5120, False, 20, (128, 1536, 1024)),
}


def wide(m, k, n):
    """The parent's tiles, as ``grouped_matmul`` chose them before PR
    44."""
    from rnb_tpu.ops import moe
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, 1) if m % t == 0)
    return (tm, moe._tile(k, 2048, 1024), moe._tile(n, 1024, 1024))


# -- the rule ------------------------------------------------------------------------


@pytest.mark.parametrize("product", sorted(SWEPT))
def test_the_rule_at_the_swept_shapes(product):
    """The tiles ``gmm_tiling``'s sweep names, in every row bucket of
    the cell, fit its own VMEM account; the parent's were 512 rows."""
    from rnb_tpu.ops import moe
    m, k, n, _, _, tiles = SWEPT[product]
    assert moe._tile_bytes(*tiles) <= moe._VMEM
    for share in (1, 2, 4):
        assert moe.gmm_tiling(m // share, k, n) == tiles
    assert wide(m, k, n) != tiles and wide(m, k, n)[0] == 512


@pytest.mark.parametrize("rows", [64, 80, 96, 112, 128])
def test_k_exaones_tiles_are_the_parents(rows):
    """A whole K of 6,144 fits beside 256 columns at most: in every row
    bucket the first products keep the wide tiles at the pass's M, the
    second the caller's own."""
    from rnb_tpu.models.exaone_moe import network
    from rnb_tpu.ops import moe
    tokens, k, experts, held = rows * 128, 8, 128, 16
    capacity = moe.pair_capacity(tokens, k, held, experts)
    assert capacity == rows * 256
    assert moe.gmm_tiling(capacity, 6144, 2048) \
        == wide(capacity, 6144, 2048) == (512, 1024, 1024)
    assert network._DOWN_TILING == (256, 2048, 1024)


@pytest.mark.parametrize("case", ["128_divides_no_m", "m_of_128s",
                                  "no_room_for_a_whole_k", "whole_n",
                                  "even_split", "ragged"])
def test_the_rules_edges(case):
    from rnb_tpu.ops import moe
    if case == "128_divides_no_m":  # the largest row tile that does
        assert moe.gmm_tiling(192, 2688, 1856) == wide(192, 2688, 1856) \
            == (64, 896, 1024)
    elif case == "m_of_128s":       # where the parent's cut K at 128 rows
        assert wide(384, 2688, 1856) == (128, 896, 1024)
        assert moe.gmm_tiling(384, 2688, 1856) == (128, 2688, 1024)
    elif case == "no_room_for_a_whole_k":
        assert moe._tile_bytes(128, 6144, 512) > moe._VMEM
        assert moe.gmm_tiling(32768, 6144, 2048) == wide(32768, 6144, 2048)
    elif case == "whole_n":
        assert moe.gmm_tiling(2048, 256, 384) == (128, 256, 384)
    elif case == "even_split":      # 3,072 columns: not whole, 3 x 1,024
        assert moe.gmm_tiling(4096, 2048, 3072) == (128, 2048, 1024)
    else:                           # 1,856 = 14.5 x 128: no even split
        assert moe.gmm_tiling(4096, 2688, 1856) == (128, 2688, 1024)


def test_held_experts_counts_the_rows_at_the_first_products_tile(monkeypatch):
    """The first products run at one tiling, ``gmm_tiling`` of their
    shapes, and the rows counted are ``gmm_visits`` at its row tile, with
    and without a capacity; the second product gets the caller's."""
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    seen = []
    product = moe.grouped_matmul

    def recorded(*args, tiling=None, **kwargs):
        seen.append(tiling)
        return product(*args, tiling=tiling, **kwargs)
    monkeypatch.setattr(moe, "grouped_matmul", recorded)
    tokens, k, experts, held, hidden, inner = 64, 4, 16, 2, 64, 32
    rng = np.random.default_rng(0)
    ids = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    stack = jnp.asarray(rng.standard_normal((held, inner, hidden)) * 0.1,
                        jnp.bfloat16)
    args = (jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.bfloat16),
            jnp.asarray(ids, jnp.int32),
            jnp.asarray(rng.random((tokens, k)), jnp.float32),
            jnp.ones(tokens, bool), moe.held_slots(experts, range(held)),
            stack, stack)
    plain = moe.held_experts(*args, interpret=True, gate=stack)
    sized = moe.held_experts(*args, interpret=True, gate=stack, capacity=64,
                             down_tiling=(64, 32, 64))
    assert seen == [(128, 64, 32)] * 2 + [None] \
        + [(64, 64, 32)] * 2 + [(64, 32, 64)]
    # both groups' pairs lie in the first tile: two steps of 128 rows of
    # all 256, two of 64 in the one pass of 64
    assert 1 < int((ids < held).sum()) <= 64
    assert int(plain[2]) == 256 and int(sized[2]) == 128


# -- the kernel at the rule's tiles -------------------------------------------------------


def ragged_counts(tm):
    """Rows of six groups over ten row tiles of ``tm``: an empty group,
    one larger than two tiles, one that ends on a tile's edge, a small
    one, one of a single row, one that crosses an edge; a tile and a
    half of rows lie behind the last group."""
    counts = [0, 2 * tm + tm // 2, tm + tm // 2, 37, 1, tm + 11]
    assert sum(counts[:3]) == 4 * tm
    return np.asarray(counts, np.int32), 10 * tm


def check_against_dense(tm, transposed, **tiles):
    """``grouped_matmul`` in interpret mode over ``ragged_counts(tm)``,
    K 256 -> N 384, against a dense product a group; ``tiles``: its
    ``tiling``, where not the rule's."""
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    counts, m = ragged_counts(tm)
    k, n = 256, 384
    rng = np.random.default_rng(tm)
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    weights = jnp.asarray(
        rng.standard_normal((len(counts), n, k) if transposed
                            else (len(counts), k, n)) * 0.1, jnp.bfloat16)
    got = np.asarray(moe.grouped_matmul(
        rows, weights, jnp.asarray(counts), True, transposed=transposed,
        **tiles))
    rows, weights = (np.asarray(a, np.float32) for a in (rows, weights))
    first = 0
    for group, count in enumerate(counts):
        w = weights[group].T if transposed else weights[group]
        np.testing.assert_allclose(
            got[first:first + count], rows[first:first + count] @ w,
            rtol=2e-5, atol=2e-5)
        first += count
    assert first == counts.sum() < m - tm


#: tiles as the rule returns them (a whole K; all of N, an even split of
#: it, a ragged last tile) and as the parent's did (512 rows, a cut K)
TILES = {
    "narrow_whole_n": (128, 256, 384),
    "narrow_even_split": (128, 256, 128),
    "narrow_ragged": (128, 256, 256),
    "wide_cut_k": (512, 128, 384),
    "wide_256": (256, 256, 384),
}


@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("tiles", sorted(TILES))
def test_the_kernel_equals_a_dense_product_a_group(tiles, transposed):
    check_against_dense(TILES[tiles][0], transposed, tiling=TILES[tiles])


@pytest.mark.parametrize("unit", [512, 128, 40])
def test_the_kernel_through_the_rule(unit):
    """``grouped_matmul`` with no ``tiling``: the rule's tiles (128 rows
    where 128 divides M), the same product."""
    from rnb_tpu.ops import moe
    assert moe.gmm_tiling(10 * unit, 256, 384) \
        == ((16, 256, 384) if unit == 40 else (128, 256, 384))
    check_against_dense(unit, False)


# -- the visits ------------------------------------------------------------------------


VISITS = {
    "ragged": lambda tm: ragged_counts(tm),
    "uniform_320": lambda tm: (np.full(16, 320, np.int32), 16 * 512),
    "all_empty": lambda tm: (np.zeros(5, np.int32), 4 * tm),
    "one_group_all_rows": lambda tm: (np.asarray([0, 4 * tm, 0], np.int32),
                                      4 * tm),
    "drawn": lambda tm: (np.random.default_rng(tm).integers(
        0, 900, 64).astype(np.int32), 64 * 1024),
}


@pytest.mark.parametrize("tm", [128, 256, 512])
@pytest.mark.parametrize("case", sorted(VISITS))
def test_the_visits_are_megabloxs_own(case, tm):
    """``gmm_visits`` counts the grid steps the kernel's own metadata
    gives its grid (``num_tiles`` of ``make_group_metadata`` as ``gmm``
    calls it), so the counter's rows are the rows it multiplied."""
    import importlib

    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    # the package's ``gmm`` is the function: the module by its name
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    counts, m = VISITS[case](tm)
    _, steps = megablox.make_group_metadata(
        group_sizes=jnp.asarray(counts), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(counts),
        visit_empty_groups=False)
    visits = int(moe.gmm_visits(jnp.asarray(counts), tm))
    assert visits == int(steps)
    assert visits * tm >= counts.sum()
    if case == "uniform_320":
        # 5,120 rows: their tiles, and one more for every group that
        # starts inside one (320 i is a multiple of 512 for i = 0, 8)
        assert visits == {512: 10 + 14, 256: 20 + 12, 128: 40 + 8}[tm]


# -- the swept products on a described v5e -------------------------------------------------


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


@pytest.mark.parametrize("product", sorted(SWEPT))
def test_the_swept_products_compile_at_the_published_widths(product,
                                                            one_chip):
    """Nothing runs: a tile that runs out of VMEM fails here, on a CPU."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import moe
    m, k, n, transposed, groups, _ = SWEPT[product]

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda x, w, c: moe.grouped_matmul(
        x, w, c, False, transposed=transposed)).lower(
        of((m, k)), of((groups, n, k) if transposed else (groups, k, n)),
        of((groups,), jnp.int32)).compile().as_text()
    assert "f32[%d,%d]" % (m, n) in text
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


# -- the counter's line and its reader ------------------------------------------------


def test_the_readers_entry_in_the_manifest():
    module = mm.load_layer_metric("gmm_row_fill_pct.bulk")
    entry, = [m for m in mm.load()["per_layer"]
              if m["name"] == "gmm_row_fill_pct.bulk"]
    # PR 46's, PR 49's, PR 55's and PR 62's cells joined the list behind
    # the three it was written for
    assert entry["workloads"] == ["nemotron3-nano.bulk", "deepseek-v2.bulk",
                                  "qwen3-next.bulk", "keye-vl2.bulk",
                                  "kimi-linear.bulk", "dots3-note.bulk",
                                  "xing4.bulk"]
    assert mm.describe(module) == {k: entry[k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "sparse experts" and module.BETTER == "higher"
    assert module.MOVES == "videos_per_s"


@pytest.mark.parametrize("family", ["nemotron_h", "deepseek_v2",
                                    "qwen3_next", "exaone_moe"])
def test_the_expert_families_count_the_rows(family):
    """``gmm_rows`` is one of ``network.COUNTERS`` in the families with
    experts whose tiles the rule changed (K-EXAONE's are the wide ones,
    and a sixth counter is one more fetch a dispatch: it counts none),
    and ``token_stages.py`` sums it like ``group_tokens``; no
    ``network.py`` states a tile for the first products: the rule reads
    the shapes."""
    import importlib

    from rnb_tpu.models import token_stages
    network = importlib.import_module("rnb_tpu.models.%s.network" % family)
    assert ("gmm_rows" in network.COUNTERS) == (family != "exaone_moe")
    stage = object.__new__(token_stages.PackedPrefill)
    stage._pending = None
    stage.tokens_valid = stage.tokens_shipped = 0
    stage._counted = {"gmm_rows": np.array([128, 256])}
    assert stage.stage_counters()["gmm_rows"] == 384
    with open(os.path.join(REPO, "rnb_tpu", "models", family,
                           "network.py")) as f:
        text = f.read()
    assert "gmm_tiling" not in text and "gmm_visits" not in text
