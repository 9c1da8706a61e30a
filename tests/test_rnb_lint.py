"""Tier-1 gate for the static analyzer (scripts/rnb_lint.py).

Three layers:

* fixture pairs per rule — every ``bad_*`` fixture triggers exactly
  its rule id, the ``good*`` fixtures trigger nothing;
* the repo itself (rnb_tpu/ + every shipped config) is lint-clean
  modulo the checked-in baseline, via the real CLI under
  ``JAX_PLATFORMS=cpu`` with no device or dataset;
* the schema checker's cross-checks fire on synthetic drift
  (unparsed registry entries, BenchmarkResult counter drift).
"""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")


def _fixture(name):
    return os.path.join(FIXTURES, name)


# -- pipeline graph checker -------------------------------------------

GRAPH_CASES = [
    ("bad_g001_parse.json", "RNB-G001"),
    ("bad_g002_class.json", "RNB-G002"),
    ("bad_g003_shape.json", "RNB-G003"),
    ("bad_g004_selector.json", "RNB-G004"),
    ("bad_g005_key.json", "RNB-G005"),
    ("bad_g006_buckets.json", "RNB-G006"),
    ("bad_g006_autotune.json", "RNB-G006"),
    ("bad_g007_cache.json", "RNB-G007"),
    ("bad_g008_dtype.json", "RNB-G008"),
    ("bad_g008_dct.json", "RNB-G008"),
    ("bad_g009_ragged.json", "RNB-G009"),
    ("bad_g010_degree.json", "RNB-G010"),
    ("bad_g010_no_spec.json", "RNB-G010"),
]


def test_good_config_fixture_is_clean():
    from rnb_tpu.analysis.graph import check_config
    assert check_config(_fixture("good.json")) == []


def test_good_autotune_fixture_is_clean():
    # the root 'autotune' key and the reserved per-step opt-out are
    # consumed by the checker: no RNB-G005 "unconsumed key", and an
    # in-warmed-set bucket restriction passes RNB-G006
    from rnb_tpu.analysis.graph import check_config
    assert check_config(_fixture("good_autotune.json")) == []


def test_good_dct_fixture_is_clean():
    # pixel_path "dct": the checker derives the loader's packed
    # coefficient row shape/dtype ((15, 8, nb + 2*C), int16) from the
    # stage classmethods and matches it against the runner's dct
    # ingest declaration — no RNB-G001/G003/G005/G008, and
    # dct_coeffs_per_frame is a consumed constructor key on both
    # stages
    from rnb_tpu.analysis.graph import check_config
    findings = check_config(_fixture("good_dct.json"))
    assert findings == [], [f.render() for f in findings]


def test_good_shard_fixture_is_clean():
    # degree 2 divides every declared channel width of [1..5] and the
    # ring is 2 distinct devices on a SUPPORTS_SHARD class — nothing
    # fires (in particular no RNB-G005: the parse-time shard_* wiring
    # keys are not user config typos)
    from rnb_tpu.analysis.graph import check_config
    findings = check_config(_fixture("good_shard.json"))
    assert findings == [], [f.render() for f in findings]


def test_good_ragged_fixture_is_clean():
    # the root 'ragged' key is consumed (no RNB-G001/G005), a matching
    # pool_rows passes RNB-G009, and an autotune.buckets restriction
    # naming counts the bucketed rule never warms (4, 10) passes
    # RNB-G006 — legal only under ragged, where the candidate set is
    # continuous up to the pool capacity
    from rnb_tpu.analysis.graph import check_config
    findings = check_config(_fixture("good_ragged.json"))
    assert findings == [], [f.render() for f in findings]


def test_ragged_pool_mismatch_across_stages_triggers_g006():
    # omitted ragged.pool_rows: each stage resolves its OWN declared
    # max, so a loader pool (15) feeding a bigger runner pool (30)
    # would be a mid-run recompile — the edge check must treat the
    # ragged consumer's warmed set as exactly its pool, not its
    # counterfactual row_buckets
    import json
    import os as _os
    import tempfile
    from rnb_tpu.analysis.graph import check_config
    raw = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "ragged": {"enabled": True},
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "fuse": 6},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": [0], "in_queue": 0}],
             "max_rows": 30, "row_buckets": [15, 30]}],
    }
    with tempfile.TemporaryDirectory() as tmp:
        path = _os.path.join(tmp, "pool_mismatch.json")
        with open(path, "w") as f:
            json.dump(raw, f)
        findings = check_config(path)
    assert {f.rule for f in findings} == {"RNB-G006"}, \
        [f.render() for f in findings]


def test_ragged_buckets_without_ragged_still_trigger_g006():
    # the same out-of-warmed-set restriction WITHOUT the ragged key
    # must keep firing — the relaxation is scoped to ragged configs
    import json
    from rnb_tpu.analysis.graph import check_config
    with open(_fixture("good_ragged.json")) as f:
        raw = json.load(f)
    del raw["ragged"]
    import tempfile, os as _os
    with tempfile.TemporaryDirectory() as tmp:
        path = _os.path.join(tmp, "no_ragged.json")
        with open(path, "w") as f:
            json.dump(raw, f)
        findings = check_config(path)
    assert {f.rule for f in findings} == {"RNB-G006"}, \
        [f.render() for f in findings]


@pytest.mark.parametrize("name,rule", GRAPH_CASES)
def test_bad_config_fixture_triggers_exactly_its_rule(name, rule):
    from rnb_tpu.analysis.graph import check_config
    findings = check_config(_fixture(name))
    assert findings, "expected a %s finding for %s" % (rule, name)
    assert {f.rule for f in findings} == {rule}, \
        "expected only %s, got: %s" % (
            rule, [f.render() for f in findings])


def test_every_shipped_config_passes_the_graph_checker():
    from rnb_tpu.analysis.graph import check_configs
    paths = sorted(glob.glob(os.path.join(REPO, "configs", "*.json")))
    assert paths
    findings = check_configs(paths)
    assert findings == [], "\n".join(f.render() for f in findings)


# -- hot-path AST lint ------------------------------------------------

HOTPATH_CASES = [
    ("bad_h001_jit.py", "RNB-H001"),
    ("bad_h002_import.py", "RNB-H002"),
    ("bad_h003_loop_put.py", "RNB-H003"),
    ("bad_h004_random.py", "RNB-H004"),
    ("bad_h005_shed.py", "RNB-H005"),
    ("bad_h006_sync.py", "RNB-H006"),
    ("bad_h007_alloc.py", "RNB-H007"),
    ("bad_h008_handoff.py", "RNB-H008"),
    ("bad_h009_block.py", "RNB-H009"),
    ("bad_h009_socket.py", "RNB-H009"),
    ("bad_h010_device_alloc.py", "RNB-H010"),
]


def test_good_hotpath_fixture_is_clean():
    from rnb_tpu.analysis.hotpath import check_file
    assert check_file(_fixture("good_hot.py"), root=FIXTURES) == []


def test_good_h009_fixture_is_clean():
    # timeout-bounded waits with a liveness re-check each lap are the
    # sanctioned shape (the runner's own queue polls); RNB-H009 must
    # stay quiet on them — including on a wait-named leaf method
    from rnb_tpu.analysis.hotpath import check_file
    assert check_file(_fixture("good_h009_wait.py"),
                      root=FIXTURES) == []


def test_good_h009_socket_fixture_is_clean():
    # the socket face of RNB-H009: settimeout-ing the sockets you
    # block on, or gettimeout-guarding a handed-in one (the
    # wire.recv_exact idiom), are the sanctioned shapes
    from rnb_tpu.analysis.hotpath import check_file
    assert check_file(_fixture("good_h009_socket.py"),
                      root=FIXTURES) == []


def test_good_h010_fixture_is_clean():
    # pool-shaped device memory allocated once at stage init and
    # reused per emission is the sanctioned shape; RNB-H010 must stay
    # quiet on it
    from rnb_tpu.analysis.hotpath import check_file
    assert check_file(_fixture("good_h010_device_alloc.py"),
                      root=FIXTURES) == []


def test_good_handoff_fixture_is_clean():
    # host materialization confined to the '*host*'-named path of a
    # Handoff class is the sanctioned shape (rnb_tpu.handoff's own
    # _take_host); RNB-H008 must stay quiet on it
    from rnb_tpu.analysis.hotpath import check_file
    assert check_file(_fixture("good_handoff.py"), root=FIXTURES) == []


@pytest.mark.parametrize("name,rule", HOTPATH_CASES)
def test_bad_hotpath_fixture_triggers_exactly_its_rule(name, rule):
    from rnb_tpu.analysis.hotpath import check_file
    findings = check_file(_fixture(name), root=FIXTURES)
    assert findings, "expected a %s finding for %s" % (rule, name)
    assert {f.rule for f in findings} == {rule}, \
        "expected only %s, got: %s" % (
            rule, [f.render() for f in findings])


# -- telemetry schema checker -----------------------------------------

def _parse_utils_src():
    with open(os.path.join(REPO, "scripts", "parse_utils.py")) as f:
        return f.read()


def test_registered_stamps_fixture_is_clean():
    from rnb_tpu.analysis.schema import check_stamps
    findings = check_stamps([_fixture("stamps_registered.py")],
                            _parse_utils_src(), root=FIXTURES)
    assert findings == [], [f.render() for f in findings]


def test_unregistered_stamp_triggers_t001():
    from rnb_tpu.analysis.schema import check_stamps
    findings = check_stamps([_fixture("bad_t001_stamp.py")],
                            _parse_utils_src(), root=FIXTURES)
    assert {f.rule for f in findings} == {"RNB-T001"}
    assert findings[0].anchor == "mystery_stamp"


def test_unregistered_content_stamp_triggers_t007():
    from rnb_tpu.analysis.schema import check_content_stamps
    findings = check_content_stamps([_fixture("bad_t007_content.py")],
                                    root=FIXTURES)
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T007", "mystery_attr")}


def test_trace_event_fixture_is_clean():
    from rnb_tpu.analysis.schema import check_trace_events
    from rnb_tpu.telemetry import StampSpec
    registry = (StampSpec("good.event", "f", "instant"),
                StampSpec("good.gauge", "f", "counter"),
                StampSpec("good.e{step}.depth", "f", "span via name"))
    findings = check_trace_events([_fixture("good_t008_trace.py")],
                                  root=FIXTURES, registry=registry)
    assert findings == [], [f.render() for f in findings]


def test_unregistered_trace_event_triggers_t008():
    from rnb_tpu.analysis.schema import check_trace_events
    from rnb_tpu.telemetry import StampSpec
    registry = (StampSpec("good.event", "f", "instant"),
                StampSpec("good.gauge", "f", "counter"),
                StampSpec("good.e{step}.depth", "f", "span via name"))
    findings = check_trace_events([_fixture("bad_t008_trace.py")],
                                  root=FIXTURES, registry=registry)
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T008", "mystery.event")}


def test_dead_trace_registry_entry():
    # a registered trace event no site emits is an RNB-T003 dead entry
    from rnb_tpu.analysis.schema import check_trace_events
    from rnb_tpu.telemetry import StampSpec
    registry = (StampSpec("good.event", "f", "instant"),
                StampSpec("good.gauge", "f", "counter"),
                StampSpec("good.e{step}.depth", "f", "span via name"),
                StampSpec("ghost.event", "nowhere", "never emitted"))
    findings = check_trace_events([_fixture("good_t008_trace.py")],
                                  root=FIXTURES, registry=registry)
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T003", "ghost.event")}


def test_repo_trace_events_all_registered():
    # the real tree: every emitted trace event name is declared and
    # every declared name is still emitted somewhere
    from rnb_tpu.analysis.findings import package_py_files
    from rnb_tpu.analysis.schema import check_trace_events
    findings = check_trace_events(
        package_py_files(os.path.join(REPO, "rnb_tpu")), root=REPO)
    assert findings == [], [f.render() for f in findings]


def test_dead_and_unparsed_registry_stamp(tmp_path):
    # a registered stamp nothing records and parse_utils never read:
    # both directions of the cross-check fire
    from rnb_tpu.analysis.schema import check_stamps
    from rnb_tpu.telemetry import STAMP_REGISTRY, StampSpec
    registry = STAMP_REGISTRY + (
        StampSpec("ghost_stamp", "nowhere", "never produced"),)
    findings = check_stamps([_fixture("stamps_registered.py")],
                            _parse_utils_src(), root=FIXTURES,
                            registry=registry)
    assert {(f.rule, f.anchor) for f in findings} == {
        ("RNB-T003", "ghost_stamp"), ("RNB-T002", "ghost_stamp")}


def test_unregistered_meta_line_triggers_t004(tmp_path):
    from rnb_tpu.analysis.schema import check_meta_lines
    bench = tmp_path / "bench_like.py"
    bench.write_text('f.write("Args: %s\\n" % args)\n'
                     'f.write("Termination flag: %d\\n" % flag)\n'
                     'f.write("Faults: num_failed=%d\\n" % n)\n'
                     'f.write("Failure reasons: %s\\n" % r)\n'
                     'f.write("Shed sites: %s\\n" % s)\n'
                     'f.write("Queue overflows: %s\\n" % q)\n'
                     'f.write("Cache: hits=%d\\n" % h)\n'
                     'f.write("Staging: slots=%d\\n" % s)\n'
                     'f.write("Autotune: decisions=%d\\n" % d)\n'
                     'f.write("Autotune buckets: %s\\n" % b)\n'
                     'f.write("Trace: events=%d\\n" % t)\n'
                     'f.write("Phases: %s\\n" % p)\n'
                     'f.write("Ragged: pool_rows=%d\\n" % r)\n'
                     'f.write("Padding: pad_rows=%d\\n" % pd)\n'
                     'f.write("Tokens: valid=%d\\n" % tk)\n'
                     'f.write("Experts: assignments=%d\\n" % ex)\n'
                     'f.write("Sparse: queries=%d\\n" % sq)\n'
                     'f.write("Attention: tiles_visited=%d\\n" % at)\n'
                     'f.write("Handoff: edges=%d\\n" % ho)\n'
                     'f.write("Handoff edges: %s\\n" % he)\n'
                     'f.write("Placement: %s\\n" % pl)\n'
                     'f.write("Health: lanes=%d\\n" % hl)\n'
                     'f.write("Health lanes: %s\\n" % hd)\n'
                     'f.write("Deadline: budget_ms=%d\\n" % dl)\n'
                     'f.write("Deadline sites: %s\\n" % ds)\n'
                     'f.write("Hedge: fired=%d\\n" % hg)\n'
                     'f.write("Compiles: %s\\n" % c)\n'
                     'f.write("Warmup: %s\\n" % w)\n'
                     'f.write("Setup: %s\\n" % su)\n'
                     'f.write("Pages: allocs=%d\\n" % pg)\n'
                     'f.write("Shard: steps=%d\\n" % sh)\n'
                     'f.write("Shard steps: %s\\n" % ss)\n'
                     'f.write("Locks: tracked=%d\\n" % lk)\n'
                     'f.write("Lock edges: %s\\n" % le)\n'
                     'f.write("Bogus line: %s\\n" % b)\n')
    findings = check_meta_lines(str(bench), _parse_utils_src(),
                                root=str(tmp_path))
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T004", "Bogus line:")}


def test_unparsed_meta_line_triggers_t005(tmp_path):
    from rnb_tpu.analysis.schema import check_meta_lines
    from rnb_tpu.telemetry import META_LINE_REGISTRY, StampSpec
    bench = tmp_path / "bench_like.py"
    bench.write_text('f.write("Ghost: %s\\n" % g)\n')
    registry = (StampSpec("Ghost:", "here", "written, never parsed"),)
    findings = check_meta_lines(str(bench), "startswith nothing",
                                root=str(tmp_path), registry=registry)
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T005", "Ghost:")}


#: every key=value counter family a benchmark-like module writes,
#: shared by the RNB-T006 tests below
REPO_BENCH_LIKE = (
        'f.write("Faults: num_failed=%d num_shed=%d num_retries=%d '
        '\\n" % x)\n'
        'f.write("Cache: hits=%d misses=%d inserts=%d evictions=%d '
        'coalesced=%d oversize=%d bytes_resident=%d\\n" % y)\n'
        'f.write("Staging: slots=%d slot_bytes=%d acquires=%d '
        'acquire_waits=%d staged_batches=%d copied_batches=%d '
        'reallocs=%d\\n" % z)\n'
        'f.write("Autotune: decisions=%d immediate=%d held=%d '
        'emissions=%d deadline_us_min=%d deadline_us_max=%d '
        'deadline_us_sum=%d\\n" % w)\n'
        'f.write("Trace: events=%d dropped=%d\\n" % v)\n'
        'f.write("Ragged: pool_rows=%d emissions=%d rows=%d '
        'pad_rows_eliminated=%d cache_hit_rows=%d\\n" % r)\n'
        'f.write("Padding: pad_rows=%d total_rows=%d '
        'pad_emissions=%d\\n" % p)\n'
        'f.write("Handoff: edges=%d d2d_edges=%d host_edges=%d '
        'd2d_bytes=%d host_bytes=%d\\n" % h)\n'
        'f.write("Health: lanes=%d transitions=%d opens=%d '
        'evictions=%d probes=%d redispatches=%d '
        'routes_after_open=%d\\n" % hl)\n'
        'f.write("Deadline: budget_ms=%d expired=%d\\n" % dl)\n'
        'f.write("Hedge: fired=%d won=%d lost=%d wasted_ms=%d\\n" '
        '% hg)\n'
        'f.write("Shard: steps=%d max_degree=%d gathers=%d '
        'collective_us=%d rows=%d\\n" % sh)\n'
        'f.write("Locks: tracked=%d acquires=%d edges=%d '
        'violations=%d\\n" % lk)\n')


def test_benchmark_result_counter_drift_triggers_t006(tmp_path):
    from rnb_tpu.analysis.schema import check_benchmark_result
    bench = tmp_path / "bench_like.py"
    bench.write_text(REPO_BENCH_LIKE.replace(
        'num_retries=%d \\n', 'num_retries=%d num_bogus=%d\\n'))
    findings = check_benchmark_result(str(bench), root=str(tmp_path))
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T006", "num_bogus")}


@pytest.mark.parametrize("line_end,prefix", [
    ('host_bytes=%d\\n', "handoff_"),
    ('routes_after_open=%d\\n', "health_"),
    ('wasted_ms=%d\\n', "hedges_"),
])
def test_counter_family_drift_triggers_t006(tmp_path, line_end, prefix):
    """The RNB-T006 family covers the Handoff:/Health:/Hedge: lines:
    the good fixture (REPO_BENCH_LIKE, which writes their full counter
    sets) is clean, and a bogus counter on a line surfaces as exactly
    its drifted field."""
    from rnb_tpu.analysis.schema import check_benchmark_result
    good = tmp_path / "good_bench_like.py"
    good.write_text(REPO_BENCH_LIKE)
    assert check_benchmark_result(str(good), root=str(tmp_path)) == []
    bad = tmp_path / "bad_bench_like.py"
    assert line_end in REPO_BENCH_LIKE
    bad.write_text(REPO_BENCH_LIKE.replace(
        line_end, line_end[:-2] + ' bogus=%d\\n'))
    findings = check_benchmark_result(str(bad), root=str(tmp_path))
    assert {(f.rule, f.anchor) for f in findings} \
        == {("RNB-T006", prefix + "bogus")}


def test_schema_checker_clean_on_repo():
    from rnb_tpu.analysis.schema import check_repo
    findings = check_repo(REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


# -- concurrency contracts + lock discipline --------------------------

CONCURRENCY_CASES = [
    ("bad_c001_unguarded.py", "RNB-C001"),
    ("bad_c002_role_write.py", "RNB-C002"),
    ("bad_c003_undeclared.py", "RNB-C003"),
    ("bad_c004_cycle.py", "RNB-C004"),
    ("bad_c005_block.py", "RNB-C005"),
]


@pytest.mark.parametrize("name", ["good_c001_guarded.py",
                                  "good_c002_role_read.py",
                                  "good_c003_declared.py",
                                  "good_c004_order.py",
                                  "good_c005_outside.py"])
def test_good_concurrency_fixture_is_clean(name):
    from rnb_tpu.analysis.concurrency import check_file
    findings = check_file(_fixture(name), root=FIXTURES)
    assert findings == [], [f.render() for f in findings]


@pytest.mark.parametrize("name,rule", CONCURRENCY_CASES)
def test_bad_concurrency_fixture_triggers_exactly_its_rule(name, rule):
    from rnb_tpu.analysis.concurrency import check_file
    findings = check_file(_fixture(name), root=FIXTURES)
    assert findings, "expected a %s finding for %s" % (rule, name)
    assert {f.rule for f in findings} == {rule}, \
        "expected only %s, got: %s" % (
            rule, [f.render() for f in findings])


def test_concurrency_checker_clean_on_repo_modulo_baseline():
    """The analyzer over the real package yields nothing beyond the
    justified baseline (the health/hedge/pager/staging sweep
    is fixed or documented, not ignored)."""
    from rnb_tpu.analysis.concurrency import check_package
    from rnb_tpu.analysis.findings import Baseline, apply_baseline
    findings = check_package(os.path.join(REPO, "rnb_tpu"), root=REPO)
    baseline = Baseline.load(os.path.join(REPO, "rnb-lint-baseline.txt"))
    active, _, _ = apply_baseline(findings, baseline)
    assert active == [], [f.render() for f in active]


def test_static_lock_order_edges_cover_the_cache_pager_nesting():
    """The exported static graph carries the one real cross-class
    nesting the runtime witness will observe: the clip cache takes the
    pager's lock inside its own (acquire/insert_pages page pinning)."""
    from rnb_tpu.analysis.concurrency import static_lock_order_edges
    edges = static_lock_order_edges()
    assert ("ClipCache._lock", "Pager.lock") in edges
    # and the reverse order is never declared — the graph is acyclic
    assert ("Pager.lock", "ClipCache._lock") not in edges


def test_contract_registry_names_the_core_classes():
    from rnb_tpu.analysis.concurrency import contract_registry
    classes = {cls for _, cls, _, _ in contract_registry()}
    for expected in ("ClipCache", "StagingPool", "HedgeGovernor",
                     "LaneHealthBoard", "Pager", "Tracer"):
        assert expected in classes, expected


TOKEN_STAGES = os.path.join(REPO, "rnb_tpu", "models", "token_stages.py")


def test_the_token_stages_loader_thread_is_a_declared_read_only_role():
    """PR 63's worker (``prefill-load``: a row bucket's executable made
    while the constructor lowers the next) is an entry point the
    analyzer sees, under a role the class declares read-only."""
    from rnb_tpu.analysis import concurrency
    from rnb_tpu.analysis.findings import parse_py
    (cls,) = [c for c in concurrency._classes_of(parse_py(TOKEN_STAGES))
              if c.name == "PackedPrefill"]
    info = concurrency._extract_contracts(cls, "token_stages.py")
    assert info.entry_roles["_load_programs"] == "prefill-load"
    assert set(info.read_only_roles) == {"prefill-load"}
    assert not info.locks and not info.guarded
    assert "PackedPrefill" in {
        cls for _, cls, _, _ in concurrency.contract_registry()}
    assert concurrency.check_file(TOKEN_STAGES, root=REPO) == []


@pytest.mark.parametrize("write", [
    "self._programs[rows] = program",
    "self.hlo_scopes = scopes",
    "self._note(rows)"])
def test_a_write_on_the_loader_thread_triggers_c002(tmp_path, write):
    """... and a stage attribute written there, directly or through a
    method of the stage, is a finding."""
    from rnb_tpu.analysis.concurrency import check_file
    with open(TOKEN_STAGES) as f:
        source = f.read()
    at = "                loaded.append((rows, program, scopes))\n"
    assert source.count(at) == 1
    source = source.replace(at, at + "                %s\n" % write)
    source += ("\n    def _note(self, rows):\n"
               "        self._noted = rows\n")
    path = tmp_path / "token_stages.py"
    path.write_text(source)
    findings = check_file(str(path), root=str(tmp_path))
    assert {f.rule for f in findings} == {"RNB-C002"}, \
        [f.render() for f in findings]
    assert all("prefill-load" in f.render() for f in findings)


def test_rnb_lint_concurrency_family_runs_without_jax(tmp_path):
    """Acceptance: `--family concurrency` must not import jax (the
    analyzer is pure-AST, budgeted at seconds not minutes) — a
    poisoned jax shim on PYTHONPATH proves the import never happens."""
    (tmp_path / "jax.py").write_text(
        'raise AssertionError("the concurrency family imported jax")\n')
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "%s%s%s" % (tmp_path, os.pathsep,
                                    env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "rnb_lint.py"),
         "--family", "concurrency"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_rnb_lint_stamps_prints_contract_registry():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "rnb_lint.py"),
         "--stamps"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for needle in ("guarded by", "ClipCache", "StagingPool"):
        assert needle in proc.stdout


# -- the real CLI over the real repo ----------------------------------

def test_rnb_lint_cli_clean_on_repo_and_shipped_configs():
    """Acceptance: `python scripts/rnb_lint.py` exits 0 on the repo +
    all shipped configs, with no JAX device and no dataset."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RNB_TPU_DATA_ROOT", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "rnb_lint.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_rnb_lint_cli_fails_on_bad_config_with_rule_id():
    """Acceptance: non-zero exit on a bad fixture, naming its rule."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "rnb_lint.py"),
         "--family", "graph",
         "--config", _fixture("bad_g006_buckets.json")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "RNB-G006" in proc.stdout


def test_parse_utils_stamps_reference():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "parse_utils.py"), "--stamps"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for needle in ("runner{step}_start", "inference{step}_finish",
                   "Cache:", "# <kind>"):
        assert needle in proc.stdout


def test_baseline_file_parses_and_documents_every_entry():
    from rnb_tpu.analysis.findings import Baseline
    baseline = Baseline.load(os.path.join(REPO, "rnb-lint-baseline.txt"))
    assert not baseline.empty()
    for key, justification in baseline.entries.items():
        assert justification, "baseline entry %r needs a justification" \
            % (key,)
