"""The schedule: what a seed may and may not change."""

import json
import os

import numpy as np
import pytest

from benchmarks import manifest as mm
from benchmarks import traffic

MIXES = sorted(f[:-5] for f in os.listdir(traffic.TRAFFIC_DIR)
               if f.endswith(".json"))


def files(root):
    shorts = ["%s/pick/short-%d-0.y4m" % (root, i) for i in range(4)]
    longs = ["%s/pick/long-%d-0.y4m" % (root, i) for i in range(4)]
    clips = {p: 1 for p in shorts}
    clips.update({p: 9 for p in longs})
    return shorts, longs, clips


def build(mix, seed, root="/a", seconds=20.0, chips=1):
    return traffic.build_schedule(traffic.load_mix(mix), seed, seconds,
                                  chips, *files(root), capacity_hint=80.0)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule_under_two_roots(mix):
    a, b = build(mix, 3000000019, "/a"), build(mix, 3000000019, "/b/c")
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.clips, b.clips)  # long videos at the same places
    assert [os.path.basename(p) for p in a.paths] \
        == [os.path.basename(p) for p in b.paths]


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_offer_the_same_work_in_another_order(mix):
    a, b = build(mix, 1), build(mix, 2)
    assert len(a) == len(b) and a.clips.sum() == b.clips.sum()
    every = traffic.load_mix(mix)["videos"]["long_every"]
    assert len(a) % every == 0
    for s in (a, b):  # exactly one long video in every block
        assert (s.clips.reshape(-1, every) > 1).sum(axis=1).tolist() \
            == [1] * (len(s) // every)
    gaps = lambda s: np.sort(np.diff(np.concatenate([[0.0], s.due])))
    assert np.allclose(gaps(a), gaps(b))
    if a.process == "poisson":
        assert not np.array_equal(a.due, b.due)


def test_unit_gaps_are_exponential_quantiles_with_exact_sum():
    g = traffic.unit_gaps(1000)
    assert abs(g.sum() - 1000) < 1e-6
    assert abs(np.median(g) - np.log(2)) < 0.01
    assert abs(g.std() - 1.0) < 0.05


def test_poisson_rate_and_horizon(tmp_path):
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 50.0},
           "ramp_s": 2.0, "videos": {"long_every": 11}}
    s = traffic.build_schedule(mix, 7, 20.0, 1, *files("/a"))
    assert abs(len(s) - 50 * 22) <= 11
    assert s.due[-1] <= 22.0 + 1e-9 and np.all(np.diff(s.due) >= 0)


def test_burst_keeps_the_mean_rate_and_concentrates_arrivals():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 50.0,
                        "burst": {"period_s": 5.0, "on_s": 1.0,
                                  "factor": 4.0}},
           "ramp_s": 0.0, "videos": {"long_every": 11}}
    s = traffic.build_schedule(mix, 7, 20.0, 1, *files("/a"))
    assert abs(len(s) - 1000) <= 11
    on = (np.mod(s.due, 5.0) < 1.0).mean()
    assert 0.75 < on < 0.85  # 4x for a fifth of the time: 80% of arrivals


def test_backlog_count_follows_capacity_chips_and_horizon():
    one, four = build("bulk", 1, chips=1), build("bulk", 1, chips=4)
    mix = traffic.load_mix("bulk")
    want = mix["arrivals"]["backlog_factor"] * 80.0 * (mix["ramp_s"] + 20)
    assert want <= len(one) < want + 11
    assert abs(len(four) - 4 * len(one)) <= 33
    assert not one.due.any()


def test_zipf_popularity_prefers_the_head():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 100.0},
           "ramp_s": 0.0, "videos": {"long_every": 11, "popularity": {
               "dist": "zipf", "s": 1.1, "universe": 4}}}
    s = traffic.build_schedule(mix, 3, 20.0, 1, *files("/a"))
    shorts = [p for p in s.paths if "short" in p]
    assert shorts.count("/a/pick/short-0-0.y4m") \
        > 2 * shorts.count("/a/pick/short-3-0.y4m")


def test_release_paces_and_stamps():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 100.0},
           "ramp_s": 0.0, "videos": {"long_every": 11}}
    s = traffic.build_schedule(mix, 3, 0.33, 1, *files("/a"))
    traffic.ACTIVE = s
    try:
        it = iter(traffic.ScheduledPathIterator())
        got = [next(it) for _ in range(len(s))]
    finally:
        traffic.ACTIVE = None
    assert got == s.paths
    late = s.sent - (s.t0 + s.due)
    assert (late >= 0).all() and np.median(late) < 0.01


def test_names_pin_the_mix_wherever_the_checkout_lies(tmp_path):
    from rnb_tpu.models.r2p1d.sampler import R2P1DSampler
    spec = {"format": "y4m", "colorspace": "420", "size": [32, 48],
            "frames": 70, "labels": 1, "videos_per_label": 2, "seed": 0}
    sampler = R2P1DSampler(consecutive_frames=32)
    for sub in ("x", "some/other/place"):
        shorts, longs, clips = mm.load_family("r2p1d").prepare(
            spec, str(tmp_path / sub), sampler, 48)
        assert [clips[p] for p in shorts] == [1, 1]
        assert [clips[p] for p in longs] == [2, 2]  # 70 frames hold two
        assert all(os.path.exists(p) for p in shorts + longs)


@pytest.mark.parametrize("finished,rate,emptied", [
    # ledger and chiprun_out/p30, PR 30: r34-yuv.bulk on 1.35 x 128
    (4663, 138.37, False),
    # PR 29's (k, T) form of nemotron3-nano.bulk on 1.35 x 29.9: 4.6%
    # of 1,375 left, the refusal ISSUE 31 is about
    (1312, 38.63, True)])
def test_backlog_room_and_the_sentence_that_refuses_a_run(finished, rate,
                                                          emptied):
    requests = 5885 if finished == 4663 else 1375
    room = traffic.backlog_room(requests, finished, 0.05, rate)
    assert set(room) == {"requests", "left_share", "min_left_share",
                         "empties_at_videos_per_s"}
    assert room["left_share"] == pytest.approx(1 - finished / requests)
    # the run's own rate x the finishes 5% left allows / those it had
    assert room["empties_at_videos_per_s"] == pytest.approx(
        rate * 0.95 * requests / finished)
    sentence = traffic.backlog_problem(room)
    if not emptied:
        assert sentence is None
        assert 165 < room["empties_at_videos_per_s"] < 167
    else:
        assert "the backlog emptied: 4.6% of the 1375 requests" in sentence
        assert "at most %.1f requests/s" \
            % room["empties_at_videos_per_s"] in sentence
        assert room["empties_at_videos_per_s"] < rate


def test_backlog_room_of_a_run_that_finished_nothing():
    room = traffic.backlog_room(22, 0, 0.05, 0.0)
    assert room["left_share"] == 1.0
    assert room["empties_at_videos_per_s"] is None
    assert traffic.backlog_problem(room) is None
