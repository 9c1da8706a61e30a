"""The one traffic generator: a mix file -> a schedule -> paced requests.

A traffic mix is ``benchmarks/traffic/<name>.json``: parameters only.
:func:`build_schedule` turns one into a fixed list of requests (due
time, file, clip count) from ``--seed``, and
:class:`ScheduledPathIterator` — the class path the benchmark writes
into the run's ``video_path_iterator`` — releases each request to the
program's client at its due time and keeps the due and sent stamps.

Every seed gets the same *set* of gaps and the same number of long
videos, in another order: gaps are the stratified quantiles of the
exponential distribution (their sum is exact), permuted by the seed,
and exactly one video in every ``long_every`` is a long one, at a
position drawn from the seed. So two seeds offer the same work, and a
difference between runs is the system's, not the draw's.

Mix file keys:

``arrivals.process``
    ``"backlog"``: every request is due at time 0 — a queue that must
    not empty inside the window. ``backlog_factor`` x the
    configuration's ``capacity_videos_per_chip_s`` (its measured rate,
    kept in the configuration's file) x chips x (``ramp_s`` + seconds)
    requests are offered: enough that the queue outlasts the window,
    few enough that the drain after it is short. A run that leaves
    under ``min_left_share`` of them unfinished when the window closes
    is not correct, so the cell can show a rate of ``backlog_factor`` x
    (1 - ``min_left_share``) x the capacity key and no more. **The
    rule** (PR 31): the key is the cell's rate on the newest accepted
    ledger line when it was anchored (``capacity_why`` names the line),
    the factor leaves room for at least +50% over it, and the first
    ``benchmark`` PR after a bulk cell's ``level`` has risen by a
    quarter since the anchor re-anchors the key. Every run's
    ``notes.backlog`` (:func:`backlog_room`) says at what rate it would
    have emptied: whoever asks a bulk cell for a gain reckons that
    first.
    ``"poisson"``: open loop at ``rate_per_s`` (a fixed number; the
    knee it was taken from and the sweep's readings sit beside it).
    Optional ``burst`` ``{"period_s", "on_s", "factor"}``: the rate is
    ``factor`` x the mean for ``on_s`` in every ``period_s`` and lower
    in between, the mean unchanged.
``ramp_s``
    seconds between the first request and the start of the measured
    window (counted as set-up): the pipeline fills.
``videos.long_every``
    one long video (as many clips as fit) in every so many requests.
``videos.popularity``
    optional ``{"dist": "zipf", "s": 1.1, "universe": N}``: files are
    drawn by rank from the first N of each kind instead of cycled.
``trace_s``
    length of the profiler's window in a ``--trace 1`` run.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")

#: numpy seeds are non-negative and the driver's may exceed 2**31
_SEED_MOD = 2 ** 63


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    path = os.path.join(traffic_dir, name + ".json")
    with open(path) as f:
        mix = json.load(f)
    arrivals = mix.get("arrivals", {})
    if arrivals.get("process") not in ("backlog", "poisson"):
        raise ValueError("%s: arrivals.process must be 'backlog' or "
                         "'poisson'" % path)
    if float(mix.get("ramp_s", -1)) < 0:
        raise ValueError("%s: ramp_s must be >= 0" % path)
    if int(mix.get("videos", {}).get("long_every", 0)) < 2:
        raise ValueError("%s: videos.long_every must be >= 2" % path)
    return mix


def unit_gaps(n: int) -> np.ndarray:
    """The n stratified quantiles of Exp(1), scaled so they sum to n:
    the same multiset for every seed."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / gaps.sum())


def _intensity_grid(rate: float, horizon: float, burst: Optional[dict],
                    dt: float = 0.001):
    """(t_grid, cumulative expected arrivals) for the mix's rate."""
    steps = int(math.ceil(horizon / dt)) + 1
    t = np.arange(steps, dtype=np.float64) * dt
    r = np.full(steps, float(rate))
    if burst:
        period, on = float(burst["period_s"]), float(burst["on_s"])
        factor = float(burst["factor"])
        share = on / period
        low = (1.0 - share * factor) / (1.0 - share)
        if not (0 < share < 1) or low < 0:
            raise ValueError("burst %r leaves no non-negative rate "
                             "between bursts" % (burst,))
        r *= np.where(np.mod(t, period) < on, factor, low)
    cum = np.concatenate([[0.0], np.cumsum(r[:-1]) * dt])
    return t, cum


def sleep_until(epoch: float) -> None:
    """Sleep until the wall clock reads ``epoch`` (sleep may return
    early or late; ask the clock again)."""
    while True:
        left = epoch - time.time()
        if left <= 0:
            return
        time.sleep(left)


class Schedule:
    """The requests of one run, in order. ``due`` is seconds after the
    first release; the iterator fills ``t0`` (epoch of the first
    release) and ``sent`` (epoch at which each request was handed to
    the client)."""

    def __init__(self, due: np.ndarray, paths: List[str],
                 clips: np.ndarray, ramp_s: float, seconds: float,
                 process: str):
        self.due = due
        self.paths = paths
        self.clips = clips
        self.ramp_s = float(ramp_s)
        self.seconds = float(seconds)
        self.process = process
        self.sent = np.full(len(due), np.nan)
        self.t0: Optional[float] = None
        self.started = threading.Event()
        self._next = 0

    def __len__(self) -> int:
        return len(self.due)

    @property
    def window(self):
        """(start, end) of the measured window in epoch seconds."""
        return (self.t0 + self.ramp_s,
                self.t0 + self.ramp_s + self.seconds)

    def release(self) -> str:
        """Block until the next request is due; stamp and return it."""
        i = self._next
        if self.t0 is None:
            self.t0 = time.time()
            self.started.set()
        if i >= len(self.due):
            # the client stops at the run's target, which is
            # len(self); a caller that asks again gets the last file
            return self.paths[-1]
        sleep_until(self.t0 + self.due[i])
        self.sent[i] = time.time()
        self._next = i + 1
        return self.paths[i]


def build_schedule(mix: dict, seed: int, seconds: float, chips: int,
                   short_files: Sequence[str], long_files: Sequence[str],
                   clips_of: dict,
                   capacity_hint: Optional[float] = None) -> Schedule:
    """The run's requests from the mix's parameters and ``--seed``."""
    if not short_files or not long_files:
        raise ValueError("the dataset needs at least one short and one "
                         "long video")
    arrivals = mix["arrivals"]
    ramp_s = float(mix["ramp_s"])
    horizon = ramp_s + float(seconds)
    every = int(mix["videos"]["long_every"])
    rng = np.random.default_rng([int(seed) % _SEED_MOD, 0x726e62])
    if arrivals["process"] == "backlog":
        if not capacity_hint:
            raise ValueError("a backlog mix needs the configuration's "
                             "capacity_videos_per_chip_s")
        n = int(math.ceil(float(arrivals["backlog_factor"])
                          * float(capacity_hint) * chips * horizon))
        n = -(-n // every) * every
        due = np.zeros(n)
    else:
        t, cum = _intensity_grid(float(arrivals["rate_per_s"]), horizon,
                                 arrivals.get("burst"))
        n = max(every, int(round(cum[-1])) // every * every)
        unit = np.cumsum(rng.permutation(unit_gaps(n))) * (cum[-1] / n)
        due = np.interp(unit, cum, t)
    # one long video in every block of `every`, at a seeded position
    is_long = np.zeros(n, bool)
    blocks = n // every
    is_long[np.arange(blocks) * every
            + rng.integers(0, every, blocks)] = True
    popularity = mix["videos"].get("popularity")
    paths: List[str] = []
    picks = {}
    for kind, files, count in (("short", list(short_files),
                                int((~is_long).sum())),
                               ("long", list(long_files),
                                int(is_long.sum()))):
        if popularity:
            universe = files[:int(popularity.get("universe",
                                                 len(files)))]
            w = np.arange(1, len(universe) + 1,
                          dtype=np.float64) ** -float(popularity["s"])
            order = rng.choice(len(universe), size=count, p=w / w.sum())
            picks[kind] = [universe[j] for j in order]
        else:
            # every file equally often, in a seeded order
            reps = -(-count // len(files))
            order = np.concatenate([rng.permutation(len(files))
                                    for _ in range(reps)])[:count]
            picks[kind] = [files[j] for j in order]
    taken = {"short": 0, "long": 0}
    for flag in is_long:
        kind = "long" if flag else "short"
        paths.append(picks[kind][taken[kind]])
        taken[kind] += 1
    clips = np.array([clips_of[p] for p in paths], dtype=np.int64)
    return Schedule(due, paths, clips, ramp_s, seconds,
                    arrivals["process"])


def backlog_room(requests: int, finished: int, min_left_share: float,
                 videos_per_s: float) -> dict:
    """How far a backlog run was from emptying its queue: the share of
    the ``requests`` left when the window closed (``finished`` of them
    were done by then, the ramp's among them), and the rate at which
    the run would have read ``min_left_share`` — its own
    ``videos_per_s`` x the finishes that share allows / the finishes it
    had: ramp and window speed up together. None where nothing
    finished inside the window."""
    allowed = requests * (1.0 - min_left_share)
    return {"requests": int(requests),
            "left_share": (requests - finished) / requests,
            "min_left_share": min_left_share,
            "empties_at_videos_per_s":
                videos_per_s * allowed / finished
                if finished and videos_per_s else None}


def backlog_problem(room: dict):
    """The sentence ``correct`` is refused with where the backlog
    emptied, None where enough of it was left."""
    if room["left_share"] >= room["min_left_share"]:
        return None
    sentence = ("the backlog emptied: %.1f%% of the %d requests were left "
                "when the window closed, under %.1f%%"
                % (100 * room["left_share"], room["requests"],
                   100 * room["min_left_share"]))
    rate = room["empties_at_videos_per_s"]
    if rate is not None:
        sentence += ("; this cell can show at most %.1f requests/s (its "
                     "mix's backlog_factor x its configuration's "
                     "capacity_videos_per_chip_s: a benchmark PR re-anchors "
                     "them)" % rate)
    return sentence


#: the schedule of the run in progress: set by benchmarks/run.py before
#: it calls run_benchmark, read by the iterator the program's client
#: constructs from the config's class path (it takes no arguments)
ACTIVE: Optional[Schedule] = None


class ScheduledPathIterator:
    """``video_path_iterator`` of a benchmark run: hands the client the
    active schedule's files, each at its due time."""

    def __init__(self):
        if ACTIVE is None:
            raise RuntimeError("no schedule is active: this iterator "
                               "serves benchmarks/run.py only")
        self._schedule = ACTIVE

    def __iter__(self):
        return self

    def __next__(self) -> str:
        return self._schedule.release()
