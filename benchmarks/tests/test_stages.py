"""The account by stage (``benchmarks/stages.py``): the work count
against the family's, the reduction on hand-made intervals, and the
readers on the recorded trace of five real dispatches with a table made
for it (``recorded/stages.json`` says how its numbers were taken)."""

import json
import os
import shutil
import types

import pytest

from benchmarks import manifest as mm
from benchmarks import stages, xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded")
with open(os.path.join(RECORDED, "stages.json")) as f:
    EXPECTED = json.load(f)
with open(os.path.join(mm.BENCH_DIR, "configs",
                       "r2p1d34-32f-yuv.json")) as f:
    R34 = json.load(f)
R18 = {"family": "r2p1d",
       "model": {"layer_sizes": [2, 2, 2, 2],
                 "stage_widths": [64, 128, 256, 512],
                 "consecutive_frames": 8, "frame_hw": 112,
                 "num_classes": 400}}


@pytest.mark.parametrize("config", [R34, R18],
                         ids=["r2p1d34-32f-yuv", "r2p1d18-8f"])
def test_the_parts_of_the_stage_work_sum_to_the_familys_count(config):
    family = mm.load_family("r2p1d")
    parts = stages.stage_work(config)
    assert list(parts) == ["stem", "stage2", "stage3", "stage4", "stage5",
                           "head"]
    assert sum(work.flops for work in parts.values()) \
        == family.flops_per_row(config)
    assert all(work.flops > 0 and work.activation_bytes > 0
               and work.weight_bytes > 0 for work in parts.values())


def test_a_family_that_brings_no_stage_work_reads_none(tmp_path):
    assert stages.stage_work({"family": "nemotron_h", "model": {}}) is None
    facts = run_facts(tmp_path)
    facts.config = {"family": "nemotron_h", "model": {}}
    assert stages.roofline_pct(facts, "stage3") is None
    assert stages.ms_per_row(facts, "stage3") is not None


def test_the_work_of_the_published_models_stages():
    """By hand for R(2+1)D-34 on 32 x 112 x 112: stage 2's first
    convolution is 3 x 3 over 64 channels to M = 144 at 32 x 56 x 56."""
    parts = stages.stage_work(R34)
    assert sum(work.flops for work in parts.values()) == 307296899072
    assert [round(parts[name].flops / 1e9, 1) for name in (
        "stem", "stage2", "stage3", "stage4", "stage5")] \
        == [5.6, 133.2, 86.8, 65.6, 16.1]
    points = 32 * 56 * 56
    pair = 2 * points * 144 * (9 * 64) + 2 * points * 64 * (3 * 144)
    assert parts["stage2"].flops == 6 * pair
    # a pair reads 64 channels, writes and reads 144, writes 64; a block
    # is two pairs and the sum's second operand
    assert parts["stage2"].activation_bytes \
        == 3 * (2 * 2 * points * (64 + 144 + 144 + 64) + 2 * points * 64)
    assert parts["stage2"].weight_bytes == 6 * 2 * (9 * 64 * 144 + 3 * 144 * 64)


def test_reduce_counts_self_time_inside_the_paired_programs_only():
    table = {"%conv.1 bf16[8,4]": "jit(apply)/net/stage2/conv2/conv",
             "%conv.1 bf16[16,4]": "jit(apply)/net/stage2/conv2/conv",
             "%while.2 s32[]": "jit(apply)/net/stage3/while",
             "%body.3 f32[8]": "jit(apply)/net/stage3/conv3/add",
             "%mean.4 f32[8,4]": "jit(apply)/net/head/reduce"}
    ms = 1e6  # the intervals below are in ms, the trace's in ns
    ops = sorted((lo * ms, hi * ms, key) for lo, hi, key in [
        (5, 9, "%conv.1 bf16[8,4]"),         # before any program
        (100, 140, "%conv.1 bf16[8,4]"),
        (140, 200, "%while.2 s32[]"),
        (150, 170, "%body.3 f32[8]"),        # inside the while
        (200, 210, "%copy.9 f32[3]"),        # no op_name: other
        (210, 215, "%mean.4 f32[8,4]"),
        (500, 590, "%conv.1 bf16[16,4]"),
        (590, 600, "%copy.9 f32[3]"),
        (900, 950, "%conv.1 bf16[8,4]"),     # a program with no span
    ])
    account = stages.reduce(ops, [(8, 5, (100 * ms, 216 * ms)),
                                  (16, 16, (500 * ms, 601 * ms))], table)
    assert account.dispatches() == 2 and account.rows_valid() == 21
    assert account.seconds("stage2") == pytest.approx((40 + 90) / 1e3)
    assert account.seconds("stage2", 8) == pytest.approx(40 / 1e3)
    assert account.seconds("stage3") == pytest.approx((40 + 20) / 1e3)
    assert account.seconds("head") == pytest.approx(5 / 1e3)
    assert account.seconds(stages.OTHER) == pytest.approx(20 / 1e3)
    assert account.seconds("stage5") == 0.0
    # the identity: the scopes and other are all of the paired time
    assert account.seconds() == pytest.approx((115 + 100) / 1e3)
    text = stages.describe(account).splitlines()
    assert text[0].split() == ["scope", "rows", "calls", "ms/dispatch",
                               "ms/row", "share%"]
    row = [line.split() for line in text if line.split()[:2]
           == ["stage2", "16"]][0]
    assert row[2] == "1" and float(row[3]) == pytest.approx(90.0)
    assert float(row[4]) == pytest.approx(90.0 / 16, abs=1e-4)
    assert text[-1].split()[:3] == ["total", "all", "2"]
    assert float(text[-1].split()[-1]) == 100.0


def test_an_operation_falls_to_the_leftmost_known_scope():
    assert stages.scope_of("jit(apply)/R2Plus1DClassifier/net/stage2/conv2/"
                           "block0/conv1/spatial/conv") == "stage2"
    assert stages.scope_of("jit(apply)/attn/select/top_k") == "attn"
    assert stages.scope_of("jit(apply)/R2Plus1DClassifier/head/linear/dot") \
        == "head"
    assert stages.scope_of("") == stages.OTHER
    assert stages.scope_of("jit(apply)/conv2/stage22/x") == stages.OTHER


def run_facts(tmp_path, trace="hostspans.xplane.pb", table=EXPECTED["table"],
              window_s=3.0, peak=197e12):
    """What a reader is handed, of a traced run that left ``table``."""
    log_dir = str(tmp_path)
    if table is not None:
        with open(os.path.join(log_dir, stages.TABLE_FILE), "w") as f:
            json.dump(table, f)
    return types.SimpleNamespace(
        trace=xplane.TraceFacts(os.path.join(RECORDED, trace), window_s),
        result=types.SimpleNamespace(log_dir=log_dir),
        config=R34, peak_flops_per_s=peak, device_kind="TPU v5 lite")


@pytest.mark.parametrize("scope", sorted(EXPECTED["scope_ns"]))
def test_ms_a_clip_on_the_recorded_dispatches(scope, tmp_path):
    facts = run_facts(tmp_path)
    account = stages.of(facts)
    assert account.dispatches() == EXPECTED["dispatches"]
    assert account.rows_valid() == EXPECTED["rows_valid"]
    assert list(account.calls) == [EXPECTED["rows"]]
    assert account.seconds() * 1e9 \
        == pytest.approx(EXPECTED["paired_op_ns"], rel=1e-12)
    assert stages.ms_per_row(facts, scope) == pytest.approx(
        EXPECTED["scope_ns"][scope] / 1e6 / EXPECTED["rows_valid"],
        rel=1e-12)
    assert stages.of(facts) is account  # reduced once a run


def test_roofline_share_on_the_recorded_dispatches(tmp_path):
    """5 valid rows of the published stage 3 against 133.202 us under
    the scope: by hand, and never a number for a scope with no work
    count of its own (the ingest)."""
    facts = run_facts(tmp_path)
    work = stages.stage_work(R34)["stage3"]
    least_s = max(5 * work.flops / 197e12,
                  (5 * work.activation_bytes + 5 * work.weight_bytes)
                  / 819e9)
    assert least_s == pytest.approx(5 * 86755508224 / 197e12)
    assert stages.roofline_pct(facts, "stage3") == pytest.approx(
        100.0 * least_s / (EXPECTED["scope_ns"]["stage3"] / 1e9))
    assert stages.roofline_pct(facts, "ingest") is None
    facts.peak_flops_per_s = None  # a CPU dry run has no peak
    assert stages.roofline_pct(facts, "stage3") is None


@pytest.mark.parametrize("kwargs,scope", [
    (dict(table=None), "stage2"),
    (dict(table={}), "stage2"),
    (dict(), "stage4"),  # in the table, on no event
    (dict(), "stage5"),  # in neither
    (dict(trace="recorded.xplane.pb", window_s=0.04), "stage2"),
], ids=["no_table", "empty_table", "scope_on_no_event",
        "scope_in_no_table", "no_spans_to_pair"])
def test_nothing_to_read_is_none_and_raises_nothing(kwargs, scope,
                                                    tmp_path):
    facts = run_facts(tmp_path, **kwargs)
    assert stages.ms_per_row(facts, scope) is None
    assert stages.roofline_pct(facts, scope) is None
    facts.trace = None  # an untraced run
    facts._stages = False
    assert stages.ms_per_row(facts, scope) is None


def test_a_failed_clock_check_is_no_account(tmp_path, monkeypatch):
    from benchmarks import hostspans
    facts = run_facts(tmp_path)
    monkeypatch.setattr(hostspans, "MAX_SHIFT_NS", 1e5)  # it needs 1.2 ms
    assert stages.of(facts) is None
    assert stages.ms_per_row(facts, "stage2") is None


NEW = [m for m in mm.load()["per_layer"]
       if m["name"].split(".")[0].endswith("_ms_per_clip")
       or m["name"].startswith("stage")]


def test_the_manifest_names_the_twelve_and_each_has_its_reader(tmp_path):
    assert len(NEW) == 12
    assert sum(m["workloads"] == ["r34-yuv.bulk"] for m in NEW) == 10
    assert sum(m["workloads"] == ["r34-yuv.poisson-p80"] for m in NEW) == 2
    facts = run_facts(tmp_path)
    read = {m["name"]: mm.load_layer_metric(m["name"]).read(facts)
            for m in NEW}
    assert read["stage2_ms_per_clip.bulk"] == read["stage2_ms_per_clip.open"] \
        == pytest.approx(EXPECTED["scope_ns"]["stage2"] / 5e6)
    assert read["ingest_ms_per_clip.bulk"] \
        == pytest.approx(EXPECTED["scope_ns"]["ingest"] / 5e6)
    assert read["stage3_roofline_pct.bulk"] > 0
    assert read["stage5_ms_per_clip.bulk"] is None


def test_the_operators_view_of_a_run_directory(tmp_path, capsys):
    run = tmp_path / "run"
    capture = run / "xplane" / "plugins" / "profile" / "2026_01_01"
    os.makedirs(str(capture))
    shutil.copy(os.path.join(RECORDED, "hostspans.xplane.pb"),
                str(capture / "host.xplane.pb"))
    assert stages.main(["stages", str(run)]) == 1  # no table yet
    logs = run / "logs" / "run"
    os.makedirs(str(logs))
    with open(str(logs / stages.TABLE_FILE), "w") as f:
        json.dump(EXPECTED["table"], f)
    capsys.readouterr()
    assert stages.main(["stages", str(run)]) == 0
    lines = capsys.readouterr().out.splitlines()
    stage2 = [line.split() for line in lines
              if line.split()[:2] == ["stage2", "2"]][0]
    assert int(stage2[2]) == 5
    assert float(stage2[3]) == pytest.approx(
        EXPECTED["scope_ns"]["stage2"] / 5e6, abs=1e-3)
    assert float(stage2[5]) == pytest.approx(
        100.0 * EXPECTED["scope_ns"]["stage2"] / EXPECTED["paired_op_ns"],
        abs=0.01)
    assert lines[-1].startswith("5 dispatches paired")
