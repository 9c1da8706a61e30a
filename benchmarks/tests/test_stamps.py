"""Percentiles, window selection and the row-to-request match."""

import numpy as np
import pytest

from benchmarks import stamps
from benchmarks.facts import RunFacts
from benchmarks.traffic import Schedule


@pytest.mark.parametrize("p", [50.0, 95.0, 99.0])
def test_percentile_is_numpys_linear_interpolation(p):
    from rnb_tpu.telemetry import latency_percentiles
    values = list(np.random.default_rng(0).exponential(100.0, 501))
    assert stamps.percentile(values, p) \
        == latency_percentiles(values, (p,))[p]


def test_window_is_half_open_and_nan_is_outside():
    got = stamps.in_window(np.array([0.9, 1.0, 1.5, 2.0, np.nan]), (1, 2))
    assert got.tolist() == [False, True, True, False, False]


def table(tmp_path, name, rows):
    with open(tmp_path / (name + ".txt"), "w") as f:
        f.write("enqueue_filename inference0_start inference0_finish "
                "inference1_start inference1_finish device0 device1\n")
        for enq, fin in rows:
            f.write("%r %r %r %r %r tpu0 tpu0\n"
                    % (enq, enq + .1, enq + .2, enq + .3, fin))
        f.write("# faults num_failed=0\n")


def run_facts(tmp_path, process):
    # four requests sent at 100..103; #2 never completes
    sent = np.array([100.0, 101.0, 102.0, 103.0])
    table(tmp_path, "tpu0-group0-0", [(100.00001, 104.5), (103.00001, 106.0)])
    table(tmp_path, "tpu1-group0-1", [(101.00001, 105.5)])
    finish, instance = stamps.match_requests(
        stamps.read_tables(str(tmp_path)), sent)
    s = Schedule(np.array([0.0, 1.0, 2.0, 3.0]), list("abcd"),
                 np.array([1, 9, 1, 1]), ramp_s=1.0, seconds=5.0,
                 process=process)
    s.t0, s.sent = 100.0, sent + 0.002

    class Result:
        num_failed, num_shed = 1, 0
        total_rows, pad_rows, pad_emissions = 30, 6, 3
    return finish, instance, RunFacts(
        schedule=s, finish=finish, instance=instance, result=Result(),
        chips=2, device_kind="cpu", platform="cpu", config={}, family=None,
        flops_per_row=10, peak_flops_per_s=None, window_cpu_s=10.0,
        memory_peak_bytes=0, wire_bytes_per_row=1)


def test_rows_match_requests_by_enqueue_stamp(tmp_path):
    finish, instance, _ = run_facts(tmp_path, "poisson")
    assert finish[[0, 1, 3]].tolist() == [104.5, 105.5, 106.0]
    assert np.isnan(finish[2])
    assert instance == ["tpu0-group0-0", "tpu1-group0-1", None,
                        "tpu0-group0-0"]


def test_open_loop_counts_from_due_and_unfinished_fail(tmp_path):
    _, _, f = run_facts(tmp_path, "poisson")
    # window [101, 106): due at 101, 102, 103 -> attempted 3, one unfinished
    assert (f.attempted(), f.failed()) == (3, 1)
    assert sorted(f.latencies_ms()) == pytest.approx([3000.0, 4500.0])
    assert f.gen_late_ms() == pytest.approx([2.0, 2.0, 2.0])


def test_backlog_counts_finishes_inside_the_window(tmp_path):
    _, _, f = run_facts(tmp_path, "backlog")
    # finishes at 104.5 and 105.5 are inside [101, 106); 106.0 is not
    assert f.videos_per_s() == pytest.approx(2 / 5.0)
    assert f.clips_per_s() == pytest.approx(10 / 5.0)
    assert (f.attempted(), f.failed()) == (3, 1)
    assert f.replica_imbalance_pct() == 0.0
    assert f.rows_per_dispatch() == 10.0 and f.pad_row_pct() == 20.0
    assert f.host_cores_busy() == 2.0
    assert f.net_flops_util_pct() is None  # no peak on a CPU
