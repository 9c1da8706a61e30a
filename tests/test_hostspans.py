"""The benchmark's reader of the program's spans (benchmarks/hostspans.py)
on hand-made intervals, on its copy of the phase rules, and on the
recorded v5e trace with host spans kept beside the benchmark's tests.
Times are nanoseconds on one clock, as in an ``.xplane.pb``."""

import json
import os

import pytest

from benchmarks import hostspans as hs
from benchmarks.hostspans import HOST_LOOP, LAUNCH, STARVED, HostSpans, Span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(REPO, "benchmarks", "tests", "recorded",
                        "hostspans.xplane.pb")
MS = 1e6


def _executor(cycles, step=1, line=3):
    """Spans of one executor thread: per cycle (t0, wait_ms, call_ms,
    sync_ms) -> queue_get, model_call, device_sync back to back."""
    spans = []
    for t0, wait, call, sync in cycles:
        a, b, c = t0 + wait * MS, t0 + (wait + call) * MS, \
            t0 + (wait + call + sync) * MS
        spans += [Span(t0, a, "exec%d.queue_get" % step, line=line),
                  Span(a, b, "exec%d.model_call" % step,
                       {"rows": 8, "rows_valid": 6, "device": 0}, line),
                  Span(b, c, "exec%d.device_sync" % step, line=line)]
    return spans


def _two_cycles(wait=0.0, loop=0.0, head=0.0, tail=0.0):
    """Two dispatches of 8 ms on the chip. Each starts ``head`` ms into
    its model_call span and ends ``tail`` ms before its device_sync
    closes; between the first sync's end and the second model_call lie
    ``loop`` ms unspanned and ``wait`` ms of queue_get. -> (cycles, ops)"""
    sync = 8.0 + head + tail - 0.5
    second = (1.0 + 0.5 + sync + loop) * MS
    cycles = [(0.0, 1.0, 0.5, sync), (second, wait, 0.5, sync)]
    ops = [((1.0 + head) * MS, (9.0 + head) * MS),
           (second + (wait + head) * MS, second + (wait + head + 8.0) * MS)]
    return cycles, ops


@pytest.mark.parametrize("state,shape,idle_ms", [
    (STARVED, dict(wait=1.0), 1.0),        # the second queue_get
    (LAUNCH, dict(head=0.3), 0.6),         # handed over, not started
    (LAUNCH, dict(head=0.0, tail=0.4), 0.8),  # ended, host not awake
    (HOST_LOOP, dict(loop=0.7), 0.7),      # after the sync, unspanned
])
def test_idle_under_each_state(state, shape, idle_ms):
    cycles, ops = _two_cycles(**shape)
    stretch, idle = hs.classify(_executor(cycles), ops)
    # from the first model_call's opening to the last sync's closing:
    # the first queue_get lies before it
    assert stretch == (1.0 * MS, max(s.end for s in _executor(cycles)))
    assert idle[state] == pytest.approx(idle_ms * MS)
    assert sum(idle.values()) == pytest.approx(idle_ms * MS)
    assert sum(idle.values()) == pytest.approx(
        stretch[1] - stretch[0] - 16 * MS)


def test_a_gap_over_three_states_is_split_at_the_boundaries():
    # one gap of the chip, 2.2 ms from the first program's end to the
    # second one's start: 0.2 of device_sync, 0.7 unspanned, 1.0 of
    # queue_get, 0.3 of model_call
    cycles, ops = _two_cycles(wait=1.0, loop=0.7, head=0.3, tail=0.2)
    assert ops[1][0] - ops[0][1] == pytest.approx(2.2 * MS)
    _, idle = hs.classify(_executor(cycles), ops)
    assert idle[STARVED] == pytest.approx(1.0 * MS)
    assert idle[HOST_LOOP] == pytest.approx(0.7 * MS)
    # ... and the first program's head and the second one's tail
    assert idle[LAUNCH] == pytest.approx((0.2 + 0.3 + 0.3 + 0.2) * MS)


def test_a_chip_busy_outside_the_launch_spans_gives_none_and_a_note():
    cycles, ops = _two_cycles(wait=6.0)
    # another thread's program fills most of the executor's wait
    stretch, note = hs.classify(_executor(cycles),
                                sorted(ops + [(9.5 * MS, 14.5 * MS)]))
    assert stretch is None and "busy 5.000 ms longer" in note


def _run(cycles, ops, modules, window_s=None, **kw):
    if window_s is None:
        window_s = (max(o[1] for o in ops) - min(o[0] for o in ops)) / 1e9
    return HostSpans(_executor(cycles, **kw), ops, modules, window_s, 0)


def test_three_shares_add_up_to_the_idle_share():
    cycles, ops = _two_cycles(wait=1.0, loop=1.0, head=0.3, tail=0.2)
    run = _run(cycles, ops, modules=list(ops))
    assert (run.checked, run.raw_violations, run.violations,
            run.notes) == (2, 0, 0, [])
    stretch_ns = run.classified_ns
    assert stretch_ns == pytest.approx((8.5 + 2.0 + 8.5) * MS)
    idle_pct = 100.0 * (stretch_ns - 16 * MS) / stretch_ns
    shares = [run.idle_pct(s) for s in (STARVED, LAUNCH, HOST_LOOP)]
    assert sum(shares) == pytest.approx(idle_pct)
    assert shares[0] == pytest.approx(100.0 * 1.0 * MS / stretch_ns)
    assert shares[1] == pytest.approx(100.0 * 1.0 * MS / stretch_ns)
    assert shares[2] == pytest.approx(100.0 * 1.0 * MS / stretch_ns)
    assert run.pad_row_pct() == pytest.approx(25.0)
    assert run.rows_per_dispatch() == pytest.approx(8.0)


def test_a_capture_shorter_than_the_window_is_noted_and_not_divided_by():
    # the profiler lost the window's start: 18.5 ms captured of 30
    cycles, ops = _two_cycles(wait=1.0, loop=1.0, head=0.3, tail=0.2)
    whole = _run(cycles, ops, modules=list(ops))
    short = _run(cycles, ops, modules=list(ops), window_s=0.030)
    assert "holds 0.018 s of the 0.030 s window" in short.notes[0]
    for state in (STARVED, LAUNCH, HOST_LOOP):
        assert short.idle_pct(state) == pytest.approx(whole.idle_pct(state))
    assert short.summary()["capture_s"] == pytest.approx(0.0185)
    assert short.summary()["classified_s"] == pytest.approx(0.019)


def test_unpaired_dispatches_give_none_and_a_note():
    cycles = [(0.0, 1.0, 0.5, 8.0), (10.5 * MS, 1.0, 0.5, 8.0)]
    ops = [(1.3 * MS, 5.0 * MS), (5.1 * MS, 9.3 * MS),
           (11.8 * MS, 15.0 * MS), (15.1 * MS, 19.8 * MS)]
    run = _run(cycles, ops, modules=list(ops))  # two programs a call
    assert all(run.idle_pct(s) is None
               for s in (STARVED, LAUNCH, HOST_LOOP))
    assert run.checked == 0 and len(run.notes) == 1
    assert "2 model_call spans against 4 module events" in run.notes[0]
    assert "do not pair one to one" in run.notes[0]
    # what needs no device clock is still read
    assert run.rows_per_dispatch() == pytest.approx(8.0)


@pytest.mark.parametrize("offset_ms,shift_ms,raw", [
    (-0.5, 0.2, 4),   # the device plane ahead, as the v5e's captures are
    (0.0, 0.0, 0),
    (1.5, -0.8, 4),   # ... or behind
])
def test_a_constant_offset_between_the_planes_is_shifted_out(
        offset_ms, shift_ms, raw):
    # per cycle: model_call opens at +1.0 ms, device_sync closes at +10;
    # the program runs from +1.3 to +9.3, so the planes may lie 0.3 ms
    # apart one way and 0.7 the other before the rule breaks
    cycles = [(i * 10.5 * MS, 1.0, 0.5, 8.5) for i in range(4)]
    true = [(i * 10.5 * MS + 1.3 * MS, i * 10.5 * MS + 9.3 * MS)
            for i in range(4)]
    ops = [(lo + offset_ms * MS, hi + offset_ms * MS) for lo, hi in true]
    run = _run(cycles, ops, modules=list(ops))
    assert (run.checked, run.raw_violations, run.violations) == (4, raw, 0)
    # the shift is the smallest that mends the rule, with 1.0 ms of room
    assert run.shift_ns == pytest.approx(shift_ms * MS)
    assert run.slack_ns == pytest.approx(1.0 * MS)
    assert run.summary()["shift_slack_ms"] == pytest.approx(1.0)
    if raw:
        assert "shifted by %.3f ms" % shift_ms in run.notes[0]
    want = _run(cycles, true, modules=list(true))
    for state in (STARVED, LAUNCH, HOST_LOOP):
        assert run.idle_ns[state] == pytest.approx(want.idle_ns[state])


def test_planes_farther_apart_than_a_captures_constant_give_none():
    cycles = [(i * 10.5 * MS, 1.0, 0.5, 8.5) for i in range(4)]
    ops = [(i * 10.5 * MS - 1.7 * MS, i * 10.5 * MS + 6.3 * MS)
           for i in range(4)]  # 3 ms ahead: they pair, and a shift mends it
    run = _run(cycles, ops, modules=list(ops))
    assert (run.checked, run.violations) == (4, 0)
    assert run.shift_ns == pytest.approx(2.7 * MS) and run.idle_ns is None
    assert all(run.idle_pct(s) is None
               for s in (STARVED, LAUNCH, HOST_LOOP))
    assert "2.700 ms apart, over the 2.0 ms" in run.notes[-1]


def test_planes_that_no_constant_reconciles_give_none_and_a_note():
    cycles = [(i * 10.5 * MS, 1.0, 0.5, 8.0) for i in range(4)]
    # programs of 9.2 ms between a span's opening and its sync's closing
    # 9 ms later: no shift puts both ends inside
    ops = [(i * 10.5 * MS + 0.9 * MS, i * 10.5 * MS + 10.1 * MS)
           for i in range(4)]
    run = _run(cycles, ops, modules=list(ops))
    assert (run.checked, run.violations) == (4, 4)
    assert all(run.idle_pct(s) is None
               for s in (STARVED, LAUNCH, HOST_LOOP))
    assert "under any constant shift" in run.notes[-1]
    # ... and planes three and a half dispatches apart do not even pair
    far = [(lo + 36.75 * MS, hi + 36.75 * MS) for lo, hi in ops]
    run = _run(cycles, far, modules=list(far))
    assert run.checked == 0 and run.idle_ns is None
    assert "pair" in run.notes[0]


def test_the_edges_of_a_capture_are_cut_before_pairing():
    cycles = [(10.5 * MS, 1.0, 0.5, 8.0), (21 * MS, 1.0, 0.5, 8.0),
              (31.5 * MS, 1.0, 0.5, 8.0)]
    calls = [s for s in _executor(cycles) if s.name.endswith("model_call")]
    # a program that ran when the capture began (no span for it) and a
    # last span whose program the capture never saw
    modules = [(1.3 * MS, 9.3 * MS), (11.8 * MS, 19.8 * MS),
               (22.3 * MS, 30.3 * MS)]
    pairs, loose, note = hs.pair_dispatches(calls, modules)
    assert (loose, note) == (0, None)
    assert [(c.start, m) for c, m in pairs] == [
        (11.5 * MS, modules[1]), (22 * MS, modules[2])]


def test_one_stray_program_in_two_hundred_is_left_out_with_a_note():
    cycles = [(i * 10.5 * MS, 1.0, 0.5, 8.0) for i in range(200)]
    ops = [(i * 10.5 * MS + 1.3 * MS, i * 10.5 * MS + 9.3 * MS)
           for i in range(200)]
    stray = (50 * 10.5 * MS + 9.6 * MS, 50 * 10.5 * MS + 9.7 * MS)
    run = _run(cycles, sorted(ops + [stray]), modules=sorted(ops + [stray]))
    assert (run.checked, run.unpaired, run.violations) == (200, 1, 0)
    assert "1 without a partner" in run.notes[0] and "left out" in run.notes[0]
    assert run.idle_pct(LAUNCH) is not None
    # its operations still count as busy time of the chip
    clean = _run(cycles, ops, modules=list(ops))
    assert sum(run.idle_ns.values()) == pytest.approx(
        sum(clean.idle_ns.values()) - 0.1 * MS)


def test_a_program_from_before_pr24_reads_none_and_raises_nothing():
    run = HostSpans([], [(0.0, 1.0)], [(0.0, 1.0)], 1.0, 0)
    assert run.step is None and run.idle_pct(STARVED) is None
    assert run.pad_row_pct() is None and run.rows_per_dispatch() is None
    assert run.put_ms() is None
    rows = {"g": [{"enqueue_filename": 1.0, "runner0_start": 1.1,
                   "inference0_start": 1.1, "inference0_finish": 1.4}]}
    assert hs.mean_phases(rows, [0.5], [True]) is None


def test_cpu_stand_in_has_no_modules_line_and_says_so():
    cycles = [(0.0, 1.0, 0.5, 8.0)]
    run = _run(cycles, [(1.3 * MS, 9.3 * MS)], modules=None)
    assert run.checked == 0 and "clock not checked" in run.notes[0]
    assert run.idle_pct(STARVED) == pytest.approx(0.0)


REFINED = {"enqueue_filename": 100.000, "runner0_start": 100.002,
           "inference0_start": 100.003, "decode0_done": 100.020,
           "transfer0_start": 100.024, "transfer0_done": 100.030,
           "inference0_finish": 100.031, "runner1_start": 100.531,
           "inference1_start": 100.532, "inference1_finish": 100.630}
SEGMENTED = dict(REFINED, **{"inference1_start-0": 100.540,
                             "inference1_finish-0": 100.640})


@pytest.mark.parametrize("row", [REFINED, SEGMENTED],
                         ids=["refined", "merged-segments"])
def test_the_copied_phase_rules_agree_with_the_programs(row):
    from rnb_tpu.trace import attribute_phases
    theirs = attribute_phases(row)
    ours = hs.phases_ms(row)
    assert ours["client_queue"] == pytest.approx(theirs["client_queue"])
    assert ours["decode"] == pytest.approx(theirs["decode"])
    assert ours["hold"] == pytest.approx(theirs["hold"])
    assert ours["transfer"] == pytest.approx(theirs["transfer"])
    assert ours["ring_wait"] == pytest.approx(
        theirs["drain"] + theirs["inter_stage_queue"])
    assert ours["device"] == pytest.approx(theirs["inference1"])
    latency = (max(row.values()) - row["enqueue_filename"]) * 1e3
    assert sum(ours.values()) == pytest.approx(latency)


def test_mean_phases_over_the_requests_due_in_the_window():
    late = {k: v + 50.0 for k, v in REFINED.items()}
    late["inference1_finish"] += 0.1  # 100 ms more on the device
    tables = {"tpu0-group0-0": [REFINED, late]}
    sent = [99.9995, 149.9995]
    both = hs.mean_phases(tables, sent, [True, True])
    assert both["device"] == pytest.approx((98.0 + 198.0) / 2)
    assert both["ring_wait"] == pytest.approx(1.0 + 500.0 + 1.0)
    assert sum(both[k] for k in hs.PHASE_CLASSES) \
        == pytest.approx(both["total"])
    first = hs.mean_phases(tables, sent, [True, False])
    assert first["device"] == pytest.approx(98.0)
    assert hs.mean_phases(tables, sent, [False, False]) is None


def test_only_registered_names_are_read():
    known = hs.registered_names()
    assert known("exec1.model_call") and known("exec12.finish")
    assert known("loader.emit_wait") and known("compile.steady")
    assert not known("exec.model_call") and not known("TfrtCpuExecutable")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the benchmark's tests")
def test_recorded_v5e_trace_with_host_spans_reads_as_counted_by_hand():
    from benchmarks import xplane
    with open(RECORDED[:-len(".xplane.pb")] + ".json") as f:
        want = json.load(f)
    facts = xplane.TraceFacts(RECORDED, window_s=want["window_s"])
    run = hs.from_trace(facts)
    assert run.step == want["step"]
    assert (run.checked, run.violations) == (want["dispatches"], 0)
    assert run.raw_violations == want["raw_violations"]
    assert run.shift_ns == pytest.approx(want["shift_ns"], abs=1.0)
    assert run.slack_ns == pytest.approx(want["slack_ns"], abs=1.0)
    assert [list(r) for r in run.dispatch_rows()] == want["rows"]
    for state in (STARVED, LAUNCH, HOST_LOOP):
        assert run.idle_ns[state] == pytest.approx(
            want["idle_ns"][state], abs=1.0)
    assert run.classified_ns == pytest.approx(want["stretch_ns"], abs=1.0)
    assert sum(run.idle_ns.values()) == pytest.approx(
        want["stretch_ns"] - want["busy_in_stretch_ns"], abs=1.0)
    counts = {name: calls for name, _, calls, _ in run.table()}
    assert {k: counts[k] for k in want["span_calls"]} == want["span_calls"]
