"""The trace reduction, on hand-made intervals and on the recorded
trace kept beside this file (a TPU v5e window of r34-yuv.bulk)."""

import os

import pytest

from benchmarks import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded", "recorded.xplane.pb")


def test_busy_is_the_union_not_the_sum():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (22, 25, "d")]
    assert xplane.merge(ivs) == [(0, 15), (20, 30)]
    assert xplane.busy_ns(ivs) == 25


def test_self_time_takes_nested_events_out_of_their_parent():
    ivs = [(0, 100, "while"), (10, 30, "conv"), (40, 60, "conv"),
           (60, 70, "copy"), (200, 210, "conv")]
    assert xplane.self_times(ivs) == {"while": 50, "conv": 50, "copy": 10}


def test_idle_gaps_longest_first_with_what_ended_them():
    ivs = [(10, 20, "a"), (50, 60, "b"), (65, 70, "c")]
    gaps = xplane.idle_gaps(ivs, (0, 100), label=lambda lo, hi: "w")
    assert gaps[0] == ("w|before:b", 30e-9)
    assert gaps[1] == ("w|before:end_of_window", 30e-9)
    assert [round(g[1] * 1e9) for g in gaps] == [30, 30, 10, 5]


@pytest.mark.parametrize("text,short,conv", [
    ("%fusion.67 = bf16[48,32,56,56,64]{4,0,3,2,1:T(8,128)(2,1)} "
     "fusion(bf16[48,32,56,56,64]{4,0,3,2,1} %fusion.62, f32[144]{0} "
     "%copy-done.269), kind=kOutput, calls=%fused_computation.60",
     "%fusion.67 fusion/kOutput bf16[48,32,56,56,64]", True),
    ("%rsqrt_multiply_fusion.50 = f32[144]{0:T(256)S(1)} fusion(f32[144]"
     "{0} %copy-done.81), kind=kLoop, calls=%fused_computation.553",
     "%rsqrt_multiply_fusion.50 fusion/kLoop f32[144]", False),
    ("%reshape.1 = bf16[48,32,112,112,3]{4,3,2,1,0:T(8,128)(2,1)} "
     "reshape(bf16[451584,128]{1,0} %_normalize_u8_pallas.1)",
     "%reshape.1 reshape bf16[48,32,112,112,3]", False),
    ("%copy.34 = u8[48,32,112,112,3]{4,3,2,1,0} copy(u8[48,32,112,112,3]"
     "{3,2,1,0,4} %fusion.184)", "%copy.34 copy u8[48,32,112,112,3]",
     False),
    ("%convolution.4 = bf16[8,56,56,64]{3,2,1,0} convolution(bf16[8,56,"
     "56,64]{3,2,1,0} %a, bf16[3,3,64,64]{3,2,1,0} %k), window={size=3x3}",
     "%convolution.4 convolution bf16[8,56,56,64]", True)])
def test_operation_names(text, short, conv):
    assert xplane.short_name(text) == short
    assert xplane.is_convolution(short) is conv


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_recorded_trace_reduces_to_what_was_read_by_hand():
    import json
    with open(RECORDED[:-len(".xplane.pb")] + ".json") as f:
        want = json.load(f)
    facts = xplane.TraceFacts(RECORDED, window_s=want["window_s"])
    assert sorted(facts.by_device) == want["devices"]
    assert facts.mean_busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < facts.mean_busy_s <= facts.window_s
    top = facts.top_ops(3)
    assert [n for n, _ in top] == want["top_ops"]
    # self times partition the busy time when nothing overlaps a sibling
    assert sum(facts.self_s.values()) == pytest.approx(
        want["self_total_s"], rel=1e-9)
