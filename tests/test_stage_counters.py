"""One table says what a stage counter is (``telemetry.STAGE_COUNTERS``):
the stage reduces by it, the launcher writes its lines and sets its
fields through ``telemetry.stage_counter_report``, and
``parse_utils.parse_meta`` reads the lines back. The strings below are
what the launcher wrote before the table (PR 44's code, byte for byte)
for the same counts."""

import dataclasses
import importlib
import os
import sys
import types

import numpy as np
import pytest

from rnb_tpu.benchmark import BenchmarkResult
from rnb_tpu.models import token_stages
from rnb_tpu.telemetry import (META_LINE_REGISTRY, STAGE_COUNTERS,
                               stage_counter_report)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = ("nemotron_h", "deepseek_v2", "minicpm_sala", "qwen3_next",
            "exaone_moe", "keye_vl2", "falcon_h1", "dots3_note",
            "phi4_flash", "xing4")

#: what the dispatches of one stage summed to, as ``network.forward``
#: hands each counter back: the layers that count first
RAW = {
    "expert_served": [[3, 1, 0, 2], [2, 2, 5, 2]],
    "group_tokens": [7, 6],
    "attn_tiles": [[5, 6], [4, 9]],
    "window_tiles": [[2, 3], [1, 1]],
    "pair_rows": [[10, 40], [12, 40]],
    "gmm_rows": [128, 256],
    "sparse": [[20, 12, 90, 60], [20, 8, 70, 50]],
    "index_tiles": [[3, 4], [2, 4]],
    "index_chunks": [[9, 16], [9, 16]],
    "scan_resets": [21],
    "window_keys": [[30, 70], [30, 70]],
    "cross_lines": [21],
    "stream_mix": [120, 31000000],
}

TOKENS = "Tokens: valid=10 shipped=16"
EXPERTS = ("Experts: assignments=60 held=17 max_per_expert=5 "
           "mean_per_expert=2.125")
ATTENTION = "Attention: tiles_visited=9 tiles_causal=15"
GOLDEN = {
    "nemotron_h": [TOKENS, EXPERTS + " gmm_rows=384", ATTENTION],
    "deepseek_v2": [TOKENS, EXPERTS + " group_tokens=13 gmm_rows=384",
                    ATTENTION],
    "minicpm_sala": [TOKENS, "Sparse: queries=40 selecting=20 "
                             "causal_keys=160 chosen_keys=110"],
    "qwen3_next": [TOKENS, EXPERTS + " group_tokens=13 gmm_rows=384",
                   ATTENTION],
    "exaone_moe": [TOKENS, EXPERTS + " group_tokens=13 pair_rows_moved=22 "
                                     "pair_rows_all=80",
                   ATTENTION + " window_tiles_visited=3 "
                               "window_tiles_causal=4"],
    "keye_vl2": [TOKENS, EXPERTS + " gmm_rows=384",
                 "Sparse: queries=40 selecting=20 causal_keys=160 "
                 "chosen_keys=110 tiles_chosen=5 tiles_causal=8 "
                 "chunks_walked=18 chunks_to_diagonal=32"],
    "falcon_h1": [TOKENS + " scan_resets=21", ATTENTION],
    "phi4_flash": [TOKENS + " scan_resets=21 cross_lines=21",
                   ATTENTION + " window_keys_kept=60 "
                               "window_keys_causal=140"],
    "xing4": [TOKENS + " mixes=120 res_defect_e9=31000000",
              EXPERTS + " gmm_rows=384", ATTENTION],
    "dots3_note": [TOKENS, EXPERTS + " pair_rows_moved=22 pair_rows_all=80 "
                                     "gmm_rows=384",
                   "Sparse: queries=40 selecting=20 causal_keys=160 "
                   "chosen_keys=110 tiles_chosen=5 tiles_causal=8 "
                   "chunks_walked=18 chunks_to_diagonal=32",
                   "Attention: window_tiles_visited=3 window_tiles_causal=4 "
                   "window_keys_kept=60 window_keys_causal=140"],
}


def counters_of(family):
    network = importlib.import_module("rnb_tpu.models.%s.network" % family)
    return network.COUNTERS


def snapshot_of(names):
    """``PackedPrefill.stage_counters()`` of a stage that counted
    ``RAW``'s numbers under ``names`` over 10 valid tokens of 16."""
    stage = object.__new__(token_stages.PackedPrefill)
    stage._pending = None
    stage.tokens_valid, stage.tokens_shipped = 10, 16
    stage.cfg = types.SimpleNamespace(num_experts_per_tok=3)
    stage._counted = {name: np.asarray(RAW[name], np.int64)
                      for name in names}
    return stage.stage_counters()


def parse(tmp_path, *lines):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import parse_utils
    (tmp_path / "log-meta.txt").write_text(
        "".join(line + "\n" for line in lines))
    meta = parse_utils.parse_meta(str(tmp_path))
    del meta["job_id"]  # the directory's name: no line's
    return meta


@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_counters_give_the_lines_the_launcher_wrote(family,
                                                              tmp_path):
    lines, fields = stage_counter_report(
        [snapshot_of(counters_of(family))])
    assert lines == GOLDEN[family]
    # the fields are the numbers on the lines, under the parser's keys
    # (the window's pair apart: its fields carry no line prefix)
    meta = parse(tmp_path, *lines)
    assert {key.replace("attention_window_", "window_"): count
            for key, count in meta.items()} == fields
    assert all(type(fields[f.name]) is type(f.default)
               for f in dataclasses.fields(BenchmarkResult)
               if f.name in fields)


@pytest.mark.parametrize("line,keys", [
    (TOKENS + " scan_resets=21",
     {"tokens_valid": 10, "tokens_shipped": 16, "tokens_scan_resets": 21}),
    (GOLDEN["phi4_flash"][0],
     {"tokens_valid": 10, "tokens_shipped": 16, "tokens_scan_resets": 21,
      "tokens_cross_lines": 21}),
    (EXPERTS + " group_tokens=13 pair_rows_moved=22 pair_rows_all=80 "
               "gmm_rows=384",
     {"experts_assignments": 60, "experts_held": 17,
      "experts_max_per_expert": 5, "experts_mean_per_expert": 2.125,
      "experts_group_tokens": 13, "experts_pair_rows_moved": 22,
      "experts_pair_rows_all": 80, "experts_gmm_rows": 384}),
    (GOLDEN["keye_vl2"][2],
     {"sparse_queries": 40, "sparse_selecting": 20,
      "sparse_causal_keys": 160, "sparse_chosen_keys": 110,
      "sparse_tiles_chosen": 5, "sparse_tiles_causal": 8,
      "sparse_chunks_walked": 18, "sparse_chunks_to_diagonal": 32}),
    (GOLDEN["exaone_moe"][2],
     {"attention_tiles_visited": 9, "attention_tiles_causal": 15,
      "attention_window_tiles_visited": 3,
      "attention_window_tiles_causal": 4}),
], ids=["Tokens", "Tokens-lines", "Experts", "Sparse", "Attention"])
def test_a_line_parses_to_the_keys_it_always_had(line, keys, tmp_path):
    meta = parse(tmp_path, line)
    assert meta == keys
    assert all(type(meta[key]) is type(keys[key]) for key in keys)


@pytest.mark.parametrize("row", STAGE_COUNTERS,
                         ids=[row.counter for row in STAGE_COUNTERS])
def test_a_rows_fields_are_the_results(row):
    declared = {f.name: f for f in dataclasses.fields(BenchmarkResult)}
    assert len(row.fields) == len(row.keys) > 0 and row.doc
    for name in row.fields:
        want = float if name == "experts_mean_per_expert" else int
        assert declared[name].type in (want, want.__name__), name
        assert declared[name].default == want(0)
    assert any(spec.pattern == row.line for spec in META_LINE_REGISTRY)
    assert row.line[:-1].isalpha() and row.line.endswith(":")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_counter_of_a_family_has_a_row(family):
    rows = [row.counter for row in STAGE_COUNTERS]
    assert len(set(rows)) == len(rows)
    assert set(counters_of(family)) <= set(rows)
    # and the stage hands each on under its name, at its row's keys
    snap = snapshot_of(counters_of(family))
    for row in STAGE_COUNTERS:
        if row.counter in counters_of(family):
            want = np.shape(RAW[row.counter]) if row.reduce \
                else (len(row.keys),)
            assert np.shape(snap[row.counter]) == want, row.counter


def test_two_instances_of_one_stage_sum():
    snap = snapshot_of(counters_of("exaone_moe"))
    lines, fields = stage_counter_report([snap, {}, snap])
    assert lines == [
        "Tokens: valid=20 shipped=32",
        "Experts: assignments=120 held=34 max_per_expert=10 "
        "mean_per_expert=4.250 group_tokens=26 pair_rows_moved=44 "
        "pair_rows_all=160",
        "Attention: tiles_visited=18 tiles_causal=30 "
        "window_tiles_visited=6 window_tiles_causal=8"]
    assert fields["experts_pair_rows_all"] == 160
    assert fields["window_tiles_causal"] == 8


def test_the_most_loaded_expert_is_taken_after_the_sum():
    one = dict(snapshot_of(["expert_served"]),
               expert_served=np.array([[4, 0], [0, 1]]))
    other = dict(one, expert_served=np.array([[0, 3], [0, 3]]))
    _, fields = stage_counter_report([one, other])
    # 4 and 3 are the instances' own maxima; summed, (1, 1) serves 4
    # and so does (0, 0): not 7
    assert fields["experts_max_per_expert"] == 4
    assert fields["experts_held"] == 11
    assert fields["experts_mean_per_expert"] == 2.75
    assert fields["experts_assignments"] == 2 * 10 * 3 * 2


@pytest.mark.parametrize("names,absent", [
    ((), ("Experts:", "Sparse:", "Attention:")),
    (("sparse",), ("Experts:", "Attention:")),
    (("expert_served", "attn_tiles"),
     ("Sparse:", "group_tokens", "pair_rows", "gmm_rows", "window_")),
    (("sparse",), ("tiles_chosen",)),
    (("sparse", "index_tiles"), ("chunks_",)),
], ids=["tokens-only", "no-experts", "no-tails", "no-index-tiles",
        "no-index-chunks"])
def test_what_no_stage_counts_is_not_written(names, absent):
    lines, fields = stage_counter_report([snapshot_of(names)])
    text = "\n".join(lines)
    assert lines[0] == TOKENS
    assert not any(word in text for word in absent)
    assert len(lines) == 1 + len({row.line for row in STAGE_COUNTERS
                                  if row.counter in names})
    assert set(fields) == {name for row in STAGE_COUNTERS
                           if row.counter in names + ("tokens",)
                           for name in row.fields}
    assert stage_counter_report([]) == ([], {})
