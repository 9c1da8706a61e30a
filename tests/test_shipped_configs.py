"""Every shipped config must have EXECUTED end-to-end at least once.

The reference shipped config/r2p1d-segment.json broken for years
because its sanity_check only parsed. Here scripts/run_shipped_configs.py
runs each configs/*.json through run_benchmark on the 8-virtual-device
CPU backend and records one row per config in MULTICHIP_CONFIGS.json;
this test pins the committed artifact to the shipped set, so adding a
config without ever executing it (or committing a failing sweep) fails
the suite. Re-run the sweep — full, or ``--only <new-config>.json`` to
merge one row — whenever configs change.
"""

import glob
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "MULTICHIP_CONFIGS.json")


def test_every_shipped_config_validates_under_extended_schema():
    """Every configs/*.json parses under the full schema including the
    robustness keys (overload_policy, fault_containment, fault_plan,
    per-step retry knobs) — and the shipped set exercises the "shed"
    overload policy at least once so the non-default path cannot rot
    unvalidated."""
    from rnb_tpu.config import load_config
    policies = set()
    for path in sorted(glob.glob(os.path.join(REPO, "configs",
                                              "*.json"))):
        cfg = load_config(path)  # raises ConfigError on any violation
        assert cfg.overload_policy in ("abort", "shed")
        policies.add(cfg.overload_policy)
        for step in cfg.steps:
            assert step.max_retries >= 0
            assert step.retry_backoff_ms >= 0
    assert "shed" in policies, (
        "no shipped config exercises overload_policy: \"shed\" — keep "
        "configs/r2p1d-tiny-shed.json (or an equivalent) in the tree")


def test_every_shipped_config_has_an_ok_execution_row():
    assert os.path.exists(ARTIFACT), (
        "MULTICHIP_CONFIGS.json missing — run "
        "scripts/run_shipped_configs.py")
    with open(ARTIFACT) as f:
        artifact = json.load(f)
    rows = {r["config"]: r for r in artifact["configs"]}
    shipped = sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "configs", "*.json")))
    missing = [c for c in shipped if c not in rows]
    assert not missing, (
        "configs never executed end-to-end: %s — run "
        "scripts/run_shipped_configs.py --only '<name>.json'" % missing)
    failed = [c for c in shipped if not rows[c].get("ok")]
    assert not failed, (
        "configs whose last end-to-end execution failed: %s (see "
        "MULTICHIP_CONFIGS.json for the error rows)" % failed)
    assert artifact["all_ok"] is True


def test_scaleout_arms_ship_executed_and_scale():
    """The PR 9 replica/handoff arms must land in BOTH configs/ and
    the matrix (the two-way sync tests above enforce the general
    rule; this pins the specific pair), and the committed execution
    rows must back the headline claim: the 4-replica arm >= 2.5x the
    single-replica same-workload arm. A re-sweep that drops below the
    floor invalidates the headline and must fail here, not silently
    rot in the artifact (`make multichip` asserts the same bound
    end-to-end with --check)."""
    arms = ("configs/rnb-scaleout-r1.json",
            "configs/rnb-scaleout-r4.json")
    for rel in arms:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        from rnb_tpu.config import load_config
        cfg = load_config(os.path.join(REPO, rel))
        # both arms declare device-resident handoff + the planner
        assert cfg.handoff and cfg.handoff.get("mode") == "device"
        assert cfg.placement is not None
    # the apply arm really expands to 4 replica lanes
    r4_cfg = load_config(os.path.join(REPO, arms[1]))
    assert r4_cfg.steps[1].replica_queues is not None
    assert len(r4_cfg.steps[1].replica_queues) == 4
    with open(ARTIFACT) as f:
        rows = {r["config"]: r
                for r in json.load(f)["configs"]}
    for rel in arms:
        assert rows[rel].get("ok"), rel
    ratio = (rows[arms[1]]["videos_per_sec"]
             / rows[arms[0]]["videos_per_sec"])
    assert ratio >= 2.5, (
        "committed scale-out rows show only %.2fx (4-replica vs "
        "1-replica); the headline requires >= 2.5x — re-run "
        "scripts/run_shipped_configs.py --only 'rnb-scaleout-*' on an "
        "idle host or retune the arms" % ratio)


def test_chaos_arm_ships_executed_with_the_full_healing_layer():
    """The replica-loss chaos arm (PR 10 self-healing) must land in
    BOTH configs/ and the matrix with an ok execution row, and must
    actually declare the whole healing surface — lane health, a
    lane-addressed replica_stall kill, p95x hedging on the replicated
    step — so `make chaos` exercises circuit breaking + eviction +
    redispatch, not a watered-down arm."""
    rel = "configs/rnb-scaleout-r4-chaos.json"
    path = os.path.join(REPO, rel)
    assert os.path.exists(path), rel
    from rnb_tpu.config import load_config
    cfg = load_config(path)
    assert cfg.health is not None
    assert cfg.steps[1].replica_queues is not None
    assert len(cfg.steps[1].replica_queues) == 4
    assert cfg.steps[1].hedge_ms == "p95x"
    kinds = {f["kind"] for f in cfg.fault_plan["faults"]}
    assert "replica_stall" in kinds, (
        "the chaos arm must kill a lane mid-stream (replica_stall/"
        "replica_crash), got fault kinds %s" % sorted(kinds))
    lane_faults = [f for f in cfg.fault_plan["faults"]
                   if f["kind"] == "replica_stall"]
    assert lane_faults[0]["lane"] in cfg.steps[1].replica_queues
    with open(ARTIFACT) as f:
        rows = {r["config"]: r for r in json.load(f)["configs"]}
    assert rel in rows and rows[rel].get("ok"), (
        "the chaos arm has no ok execution row — run "
        "scripts/run_shipped_configs.py --only "
        "'rnb-scaleout-r4-chaos.json'")


def test_dct_arm_ships_executed_with_half_the_wire_bytes():
    """The DCT-domain ingest headline cell (PR 12) must land in BOTH
    configs/ and the matrix with an ok execution row, must be the
    same topology as rnb-fused-yuv-ragged differing by the pixel path
    alone, must declare wire rows at <= HALF the yuv420 arm's
    bytes/frame (the byte headline, computed from the stages' own
    declarations), and the committed pair must back the 'no slower'
    claim within host noise (>= 0.9x — `make dct` asserts the strict
    byte bound and logit parity end-to-end)."""
    rel = "configs/rnb-fused-dct-ragged.json"
    base = "configs/rnb-fused-yuv-ragged.json"
    path = os.path.join(REPO, rel)
    assert os.path.exists(path), rel
    from rnb_tpu.config import load_config
    from rnb_tpu.utils.class_utils import load_class
    cfg = load_config(path)
    base_cfg = load_config(os.path.join(REPO, base))
    assert [s.model for s in cfg.steps] \
        == [s.model for s in base_cfg.steps]
    assert cfg.ragged == base_cfg.ragged
    kw = cfg.steps[0].kwargs_for_group(0)
    base_kw = base_cfg.steps[0].kwargs_for_group(0)
    assert kw["pixel_path"] == "dct"
    assert base_kw["pixel_path"] == "yuv420"
    # the wire-byte headline, from the loader's own declarations
    loader_cls = load_class(cfg.steps[0].model)
    dct_shape = loader_cls.output_shape_for(**kw)[0]
    yuv_shape = loader_cls.output_shape_for(**base_kw)[0]
    dct_bytes = dct_shape[-1] * 2   # int16 coefficient rows
    yuv_bytes = yuv_shape[-1]       # u8 packed planes
    assert loader_cls.output_dtype_for(**kw) == "int16"
    assert dct_bytes * 2 <= yuv_bytes, (
        "the dct wire row (%d B/frame) must stay <= half the yuv420 "
        "row (%d B/frame)" % (dct_bytes, yuv_bytes))
    with open(ARTIFACT) as f:
        rows = {r["config"]: r for r in json.load(f)["configs"]}
    assert rel in rows and rows[rel].get("ok"), (
        "the dct arm has no ok execution row — run "
        "scripts/run_shipped_configs.py --only "
        "'rnb-fused-dct-ragged.json'")
    ratio = rows[rel]["videos_per_sec"] / rows[base]["videos_per_sec"]
    assert ratio >= 0.9, (
        "dct arm runs at %.2fx the yuv420 ragged baseline — the "
        "fused on-device ingest should be throughput-neutral on the "
        "CPU harness (and a win on real TPUs, where the wire is the "
        "bottleneck); profile the unpack/IDCT before re-executing "
        "the row" % ratio)


def test_paged_zipf_arm_ships_executed_and_beats_its_blob_twin():
    """The paged-memory headline pair (PR 17) must land in BOTH
    configs/ and the matrix with ok execution rows, and the committed
    rows must back the headline claim: the paged + feature-pages cell
    >= 1.15x the blob-cache twin under the same seeded Zipf workload,
    executed back-to-back by the same sweep. The twins cannot be
    byte-identical pipelines — the pager requires the ragged plane by
    construction — so the honesty anchor is the WORKLOAD: the same
    popularity block, the same fusing shape, the same cache budget.
    What the ratio then measures is the paged seam itself (page-slab
    hits gathered on-device instead of host-copied blobs, plus
    feature pages answering repeats before any decode). A re-sweep
    that drops below the floor invalidates the headline and must fail
    here (`make pages` asserts the numerics contract end-to-end)."""
    rel = "configs/rnb-fused-yuv-paged-zipf.json"
    base = "configs/rnb-fused-yuv-zipf-cache.json"
    from rnb_tpu.config import load_config
    for p in (rel, base):
        assert os.path.exists(os.path.join(REPO, p)), p
    cfg = load_config(os.path.join(REPO, rel))
    assert cfg.pager is not None and cfg.pager.get("enabled")
    assert cfg.pager.get("feature_cache"), (
        "the headline cell must exercise feature pages — without them "
        "the row only measures the clip-page gather")
    assert cfg.ragged is not None and cfg.ragged.get("enabled"), (
        "pager requires the ragged plane (page gathers land in the "
        "ragged pool)")
    base_cfg = load_config(os.path.join(REPO, base))
    assert cfg.pager.get("page_rows", 0) >= 1
    assert base_cfg.pager is None, (
        "the blob twin must not enable the pager — the ratio stops "
        "meaning 'the paged seam' otherwise")
    # same seeded Zipf workload and the same cache budget on both
    # arms: the only intended deltas are the pager + ragged planes
    with open(os.path.join(REPO, rel)) as f:
        rel_raw = json.load(f)
    with open(os.path.join(REPO, base)) as f:
        base_raw = json.load(f)
    assert rel_raw["popularity"] == base_raw["popularity"], (
        "the twins drifted apart on the popularity block — the ratio "
        "is only honest over identical traffic")
    rel_kw = cfg.steps[0].kwargs_for_group(0)
    base_kw = base_cfg.steps[0].kwargs_for_group(0)
    for key in ("cache_mb", "fuse", "max_clips", "pixel_path"):
        assert rel_kw.get(key) == base_kw.get(key), (
            "twins differ on loader %r — re-align the arms before "
            "trusting the committed ratio" % key)
    with open(ARTIFACT) as f:
        rows = {r["config"]: r for r in json.load(f)["configs"]}
    for p in (rel, base):
        assert p in rows and rows[p].get("ok"), (
            "%s has no ok execution row — run "
            "scripts/run_shipped_configs.py --only '%s'"
            % (p, os.path.basename(p)))
    ratio = rows[rel]["videos_per_sec"] / rows[base]["videos_per_sec"]
    assert ratio >= 1.15, (
        "paged Zipf cell runs at %.2fx its blob-cache twin — the "
        "headline floor is 1.15x (committed pair: 1.21x). Re-execute "
        "BOTH rows back-to-back on one idle host "
        "(scripts/run_shipped_configs.py --only "
        "'rnb-fused-yuv-*zipf*') before concluding a regression; if "
        "it reproduces, profile the gather path (`make pages`) "
        "before touching the floor" % ratio)


def test_shard_arms_ship_executed_and_pin_the_feasibility_headline():
    """The intra-stage sharding pair (PR 19) must land in BOTH
    configs/ and the matrix with ok execution rows. The headline is a
    FEASIBILITY claim, not a speed claim — weight-gathered sharding
    never divides compute — so the pin is analytic: project the d2
    arm's per-device bytes from the abstract parameter tree
    (jax.eval_shape — no weight is ever materialized) plus the
    declared ragged pool, and assert the shipped 120 MiB budget
    strictly separates degree 1 (launch-rejected, ~129.6 MiB) from
    degree 2 (runs, ~112.1 MiB). A checkpoint/pool-geometry change
    that collapses the separation invalidates the headline and must
    fail here, not silently rot in the config comments (`make shard`
    asserts the reject + bit parity end-to-end on a reduced net)."""
    rel = "configs/rnb-shard-d2.json"
    base = "configs/rnb-shard-d1.json"
    from rnb_tpu.config import load_config
    for p in (rel, base):
        assert os.path.exists(os.path.join(REPO, p)), p
    cfg = load_config(os.path.join(REPO, rel))
    base_cfg = load_config(os.path.join(REPO, base))
    kw = cfg.steps[1].kwargs_for_group(0)
    base_kw = base_cfg.steps[1].kwargs_for_group(0)
    assert kw["shard_degree"] == 2
    assert len(kw["shard_devices"]) == 2
    budget = kw["shard_hbm_budget_mb"]
    assert budget == 120.0
    # the baseline arm declares degree 1 (telemetry armed, no mesh),
    # ships WITHOUT the budget (it could not launch under it), and
    # pins whole-pool apply — the only program shape the sharded arm
    # is bitwise-comparable against
    assert base_kw["shard_degree"] == 1
    assert "shard_hbm_budget_mb" not in base_kw
    assert base_kw["ragged_chunk_rows"] == 0
    # same workload on both arms: the pair differs by the runner's
    # devices + shard key alone
    with open(os.path.join(REPO, rel)) as f:
        rel_raw = json.load(f)
    with open(os.path.join(REPO, base)) as f:
        base_raw = json.load(f)
    assert rel_raw["pipeline"][0] == base_raw["pipeline"][0]
    assert rel_raw["ragged"] == base_raw["ragged"]
    # the analytic feasibility pin: abstract init (eval_shape) of the
    # shipped network -> split by the shard partitioning rule -> the
    # per-device projection the launch gate enforces
    import jax
    import numpy as np
    from rnb_tpu.models.r2p1d.network import (LAYER_INPUT_SHAPES,
                                              R2Plus1DClassifier)
    from rnb_tpu.ops.yuv import packed_frame_bytes
    from rnb_tpu.parallel.shardplan import (min_feasible_degree,
                                            projected_device_mb,
                                            split_param_bytes)
    model = R2Plus1DClassifier(
        start=cfg.steps[1].kwargs_for_group(0).get("start_index", 1),
        end=5, num_classes=400)
    dummy = jax.ShapeDtypeStruct(
        (1, 2, 14, 14, LAYER_INPUT_SHAPES[1][-1]), np.float32)
    abstract = jax.eval_shape(
        lambda k, x: model.init(k, x, train=False),
        jax.random.key(0), dummy)
    rep, sh = split_param_bytes(abstract)
    pool_bytes = (rel_raw["ragged"]["pool_rows"] * 8
                  * packed_frame_bytes(112, 112))
    d1_mb = projected_device_mb(rep, sh, pool_bytes, 1)
    d2_mb = projected_device_mb(rep, sh, pool_bytes, 2)
    assert d2_mb <= budget < d1_mb, (
        "the shipped 120 MiB budget no longer separates the arms "
        "(d1 projects %.1f MiB, d2 %.1f) — the feasibility headline "
        "is void; re-derive the budget from the current network"
        % (d1_mb, d2_mb))
    assert min_feasible_degree(rep, sh, pool_bytes, budget,
                               (1, 2, 4)) == 2
    with open(ARTIFACT) as f:
        rows = {r["config"]: r for r in json.load(f)["configs"]}
    for p in (rel, base):
        assert p in rows and rows[p].get("ok"), (
            "%s has no ok execution row — run "
            "scripts/run_shipped_configs.py --only '%s'"
            % (p, os.path.basename(p)))


def test_every_executed_config_is_still_shipped():
    """The reverse direction: MULTICHIP_CONFIGS.json and configs/ stay
    in sync BOTH ways. A row for a config that no longer ships is a
    stale execution claim — it reads as coverage for a topology the
    tree no longer contains (delete the row when retiring a config, or
    restore the config)."""
    with open(ARTIFACT) as f:
        artifact = json.load(f)
    shipped = {
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "configs", "*.json"))}
    stale = sorted({r["config"] for r in artifact["configs"]} - shipped)
    assert not stale, (
        "MULTICHIP_CONFIGS.json rows for configs that no longer ship: "
        "%s — prune the rows (scripts/run_shipped_configs.py rewrites "
        "the artifact) or restore the configs" % stale)
