"""R2P1DFusingLoader: loader-side dynamic batching.

Contract: every request is submitted to the decode pool on receipt;
completed decodes are harvested FIFO and emitted as one fused padded
batch with a TimeCardList; partial batches emit when nothing is in
flight, on hold-timeout, or at end-of-stream (flush). Backpressure
blocks on the oldest decode once `depth` requests are pending.
"""

import os

import numpy as np
import pytest

from rnb_tpu.decode import write_y4m
from rnb_tpu.telemetry import TimeCard, TimeCardList


def _dataset(tmp_path, n=12, frames=40, h=64, w=96):
    rng = np.random.default_rng(5)
    paths = []
    for i in range(n):
        p = os.path.join(str(tmp_path), "v%02d.y4m" % i)
        write_y4m(p, rng.integers(0, 256, (frames, h, w, 3),
                                  dtype=np.uint8))
        paths.append(p)
    return paths


def _loader(fuse=3, **kw):
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DFusingLoader
    kw.setdefault("num_clips_population", [1])
    kw.setdefault("weights", [1])
    kw.setdefault("num_warmups", 0)
    return R2P1DFusingLoader(jax.devices()[0], fuse=fuse, **kw)


def test_fuses_to_target(tmp_path):
    paths = _dataset(tmp_path)
    loader = _loader(fuse=3, max_hold_ms=10000.0, depth=50)
    emitted = []
    for i, p in enumerate(paths[:9]):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            emitted.append(out)
    # 9 requests x 1 clip, fuse=3 -> 3 fused batches once decodes land
    # (timing-dependent: the early calls may swallow while decodes run,
    # so drain the rest through flush and count totals)
    while True:
        out = loader.flush()
        if out is None:
            break
        emitted.append(out)
    total = sum(len(tc) for _, _, tc in emitted)
    assert total == 9
    for (pb,), _, cards in emitted:
        assert isinstance(cards, TimeCardList)
        assert pb.valid == len(cards)  # 1 clip per request here
        assert pb.data.shape[0] in (3, 6, 15)  # row buckets or max


def test_emit_partial_when_idle(tmp_path):
    """The nothing-in-flight rule: once decode catches up and no later
    request is pending, a sub-fuse batch must emit rather than wait
    for a fill that may never come. Driven through poll() (the
    executor's idle tick) so the assertion does not depend on decode
    finishing faster than the next submit."""
    import time
    paths = _dataset(tmp_path, n=2)
    loader = _loader(fuse=5, max_hold_ms=10000.0)
    got = 0
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            got += len(out[2])
    deadline = time.time() + 10
    while got < 2 and time.time() < deadline:
        time.sleep(0.01)
        out = loader.poll()  # fires the nothing-in-flight rule
        if out is not None and out[2] is not None:
            got += len(out[2])
    assert got == 2
    assert loader.flush() is None


def test_flush_drains_everything(tmp_path):
    paths = _dataset(tmp_path, n=7)
    loader = _loader(fuse=100, max_hold_ms=1e9, depth=100)
    seen = 0
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            # "nothing in flight" emissions are legal mid-stream when
            # decode outruns arrivals — count them too
            seen += len(out[2])
    while True:
        out = loader.flush()
        if out is None:
            break
        seen += len(out[2])
    assert seen == 7
    assert loader.flush() is None


def test_backpressure_blocks_and_emits(tmp_path):
    paths = _dataset(tmp_path, n=6)
    loader = _loader(fuse=100, max_hold_ms=1e9, depth=2)
    emitted = []
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            emitted.append(out)
    # depth=2: by request 3 the loader must start retiring decodes
    assert emitted, "backpressure never forced an emission"
    total = sum(len(tc) for _, _, tc in emitted)
    while True:
        out = loader.flush()
        if out is None:
            break
        total += len(out[2])
    assert total == 6


def test_idle_poll_emits_on_hold_timeout(tmp_path):
    """The executor's idle tick must release a held batch once
    max_hold_ms expires — without waiting for the next arrival."""
    import time
    paths = _dataset(tmp_path, n=3)
    loader = _loader(fuse=100, max_hold_ms=30.0, depth=100)
    got = 0
    for i, p in enumerate(paths[:2]):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            got += len(out[2])
    # no further arrivals: only the executor's idle poll can release
    # what is still held — it must fire within ~max_hold_ms
    deadline = time.time() + 10
    while got < 2 and time.time() < deadline:
        time.sleep(0.01)
        out = loader.poll()
        if out is not None and out[2] is not None:
            got += len(out[2])
    assert got == 2
    assert loader.flush() is None


def test_next_deadline_drives_poll_timeout(tmp_path):
    """The stage's deadline hook and the executor's timeout clamp: a
    held batch's hold expiry must shrink the queue-poll window (the
    round-5 frontier measured the fixed 50 ms poll as the light-load
    p99 floor)."""
    import time

    from rnb_tpu.runner import MIN_POLL_S, QUEUE_POLL_S, poll_timeout
    paths = _dataset(tmp_path, n=3)
    loader = _loader(fuse=100, max_hold_ms=30.0, depth=100)
    assert loader.next_deadline_s() is None  # no work held
    assert poll_timeout(loader) == QUEUE_POLL_S
    out = loader(None, paths[0], TimeCard(0))
    if out[2] is None:  # swallowed (the usual case: decode in flight)
        # decode in flight or already ready: the deadline must be at
        # most the harvest tick / the remaining hold — far below the
        # 50 ms poll
        deadline = loader.next_deadline_s()
        assert deadline is not None and deadline <= 0.031
        assert MIN_POLL_S <= poll_timeout(loader) <= 0.031
        # once the decode lands and the hold expires, the deadline
        # collapses to zero (generous cap: slow CI host)
        cap = time.time() + 10
        while loader.next_deadline_s() != 0.0 and time.time() < cap:
            time.sleep(0.005)
        assert loader.next_deadline_s() == 0.0
        assert poll_timeout(loader) == MIN_POLL_S
        assert loader.poll() is not None  # and the poll emits
    assert loader.next_deadline_s() is None
    # stages without the hook keep the coarse default
    assert poll_timeout(object()) == QUEUE_POLL_S


def test_discard_pending_retires_all_tickets(tmp_path):
    """Abort path: every submitted decode (in flight AND harvested but
    unemitted) must be retired so the shared pool pins no buffers."""
    from rnb_tpu.decode.native import DecodePool, native_available
    if not native_available():
        pytest.skip("native decoder not built")
    paths = _dataset(tmp_path, n=4)
    loader = _loader(fuse=100, max_hold_ms=1e9, depth=100)
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        assert out[2] is None or len(out[2])  # swallow or emit
    loader._harvest()  # some land in _ready with live tickets
    loader.discard_pending()
    assert not loader._inflight and not loader._ready
    assert not DecodePool.shared()._pending


def test_rejects_prefetch_kwarg():
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DFusingLoader
    with pytest.raises(ValueError):
        R2P1DFusingLoader(jax.devices()[0], prefetch=4, num_warmups=0)


def test_drain_survives_more_batches_than_exit_markers(tmp_path):
    """EOS drain regression: a stage holding MORE pending batches than
    NUM_EXIT_MARKERS must still complete every request. The old drain
    consumed one exit marker per flush() emission and broke the hot
    loop after the first, stranding the tail (UNSET termination).
    Driven with a deterministic hoarding stage that swallows every item
    and releases exactly one per flush() call."""
    import json

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.control import NUM_EXIT_MARKERS, TerminationFlag

    n = NUM_EXIT_MARKERS + 5  # strictly more flushes than markers
    cfg = {
        "video_path_iterator":
            "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.HoardingSink",
             "queue_groups": [{"devices": [-1]}]},
        ],
    }
    cfg_path = os.path.join(str(tmp_path), "drain.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    res = run_benchmark(cfg_path, mean_interval_ms=0, num_videos=n,
                        log_base=os.path.join(str(tmp_path), "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    # completion-derived evidence (BenchmarkResult.num_videos merely
    # echoes the request): every held card was registered at drain
    assert res.clips_completed == n


def test_fused_pipeline_end_to_end(tmp_path):
    """Client -> FusingLoader -> net through the real runtime."""
    import json

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.control import TerminationFlag
    from rnb_tpu.models.r2p1d import checkpoint as ckpt

    root = os.path.join(str(tmp_path), "data")
    os.makedirs(os.path.join(root, "label0"))
    rng = np.random.default_rng(0)
    for i in range(4):
        write_y4m(os.path.join(root, "label0", "v%d.y4m" % i),
                  rng.integers(0, 256, (30, 64, 64, 3), dtype=np.uint8))
    os.environ["RNB_TPU_DATA_ROOT"] = root
    try:
        ckpt_path = os.path.join(str(tmp_path), "tiny.msgpack")
        ckpt.save_checkpoint(ckpt_path, ckpt.init_variables(
            seed=1, num_classes=8, layer_sizes=(1, 1, 1, 1)))
        cfg = {
            "video_path_iterator":
                "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
            "pipeline": [
                {"model":
                    "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
                 "queue_groups": [{"devices": [0], "out_queues": [0]}],
                 "num_shared_tensors": 10,
                 "fuse": 2, "max_clips": 4,
                 "num_clips_population": [2], "weights": [1],
                 "consecutive_frames": 2, "num_warmups": 0,
                 "pixel_path": "yuv420"},
                {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
                 "queue_groups": [{"devices": [0], "in_queue": 0}],
                 "start_index": 1, "end_index": 5, "num_classes": 8,
                 "layer_sizes": [1, 1, 1, 1], "max_rows": 4,
                 "consecutive_frames": 2, "num_warmups": 0,
                 "ckpt_path": ckpt_path, "pixel_path": "yuv420"},
            ],
        }
        cfg_path = os.path.join(str(tmp_path), "fused.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        res = run_benchmark(cfg_path, mean_interval_ms=0, num_videos=9,
                            log_base=os.path.join(str(tmp_path), "logs"),
                            print_progress=False)
        assert res.termination_flag == \
            TerminationFlag.TARGET_NUM_VIDEOS_REACHED
        assert res.num_videos == 9
    finally:
        os.environ.pop("RNB_TPU_DATA_ROOT", None)


def test_wide_caps_bucket_and_conserve(tmp_path):
    """Wide-dispatch caps (configs/rnb-fused-yuv-big/-mid): fused rows
    never exceed max_clips, every emission pads to the smallest bucket
    that fits, and no request/clip is lost. Emission *sizes* here are
    timing-dependent (decode may outrun the submit loop and trigger
    nothing-in-flight partial emits), so this test asserts only the
    invariants that hold for every emission; the deterministic
    per-size cases live in test_flush_take_hits_exact_buckets."""
    paths = _dataset(tmp_path, n=15)
    loader = _loader(fuse=12, max_hold_ms=1e9, depth=100,
                     max_clips=36, row_buckets=[6, 15, 24, 36],
                     num_clips_population=[3], weights=[1])
    emitted = []
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            emitted.append(out)
    while True:
        out = loader.flush()
        if out is None:
            break
        emitted.append(out)
    total_reqs = sum(len(tc) for _, _, tc in emitted)
    total_rows = sum(pb.valid for (pb,), _, tc in emitted)
    assert total_reqs == 15
    assert total_rows == 45  # 15 requests x 3 clips, none lost
    for (pb,), _, cards in emitted:
        assert pb.valid <= 36  # cap respected
        assert pb.data.shape[0] in (6, 15, 24, 36)  # a real bucket
        # smallest bucket that fits the valid rows — no over-padding
        fitting = [b for b in (6, 15, 24, 36) if b >= pb.valid]
        assert pb.data.shape[0] == fitting[0], (pb.valid,
                                                pb.data.shape[0])


def test_flush_take_hits_exact_buckets(tmp_path):
    """Deterministic bucket selection for wide caps. Submits bypass
    __call__ (whose poll can emit early whenever decode outruns the
    loop) and go straight into the in-flight window, so flush() —
    which retires every decode, then takes exactly ``fuse`` requests
    per call — produces known emission sizes. The case this pins: a
    24-row fusion must ship the 24-row bucket, not the 36-row cap."""
    paths = _dataset(tmp_path, n=15)
    for fuse, want in ((8, [(24, 24), (21, 24)]),
                       (12, [(36, 36), (9, 15)])):
        loader = _loader(fuse=fuse, max_hold_ms=1e9, depth=100,
                         max_clips=36, row_buckets=[6, 15, 24, 36],
                         num_clips_population=[3], weights=[1])
        from rnb_tpu.models.r2p1d.model import _FuseRecord
        for i, p in enumerate(paths):
            tc = TimeCard(i)
            handle = loader.submit(p, tc)
            loader._inflight.append(_FuseRecord(handle, p, tc))
        got = []
        while True:
            out = loader.flush()
            if out is None:
                break
            (pb,), _, cards = out
            got.append((pb.valid, pb.data.shape[0]))
        # fuse=8: takes of 8, 7(=15-8) requests x 3 clips + remainder
        # rows 24->bucket 24 (NOT 36), 21->24, 9->15
        assert got == want, (fuse, got)


# -- emission under downstream back-pressure (the publish probe) -------
#
# Counts only. Decodes are stood in for by completed handles (no
# tickets, no future: ready at once, copy-path assembly) and by handles
# on a future nobody completes (a decode still in flight), so every
# scenario is deterministic: which rule fires, how many rows a take
# closes at, what it leaves behind.

BUCKETS = [8, 16, 24, 32, 40, 48]


class _Probe:
    """The executor's publish probe, by hand."""

    def __init__(self, full=True):
        self.full = full
        self.asked = []

    def __call__(self, ahead=0):
        self.asked.append(ahead)
        return self.full


def _wide_loader(probe=None, **kw):
    kw.setdefault("fuse", 64)
    kw.setdefault("depth", 1000)
    kw.setdefault("max_hold_ms", 1e9)
    kw.setdefault("max_clips", 48)
    if not kw.get("ragged"):
        kw.setdefault("row_buckets", BUCKETS)
    kw.setdefault("consecutive_frames", 2)
    kw.setdefault("pixel_path", "yuv420")
    loader = _loader(**kw)
    if probe is not None:
        loader.bind_publish_probe(probe)
    return loader


def _decoded(loader, rows, first_id=0):
    """Put completed decodes of the given row counts in flight; the
    next harvest finds them ready. Row r of request i holds i + 1."""
    from rnb_tpu.models.r2p1d.model import _DecodeHandle, _FuseRecord
    for i, n in enumerate(rows, first_id):
        tc = TimeCard(i)
        tc.num_clips = n
        out = np.full(loader._batch_shape(n), (i + 1) % 251,
                      loader._wire_dtype)
        loader._inflight.append(
            _FuseRecord(_DecodeHandle(out, n), "v%d" % i, tc))


def _still_decoding(loader, req_id=999):
    """One request whose decode never completes."""
    from concurrent.futures import Future

    from rnb_tpu.models.r2p1d.model import _DecodeHandle, _FuseRecord
    tc = TimeCard(req_id)
    tc.num_clips = 1
    handle = _DecodeHandle(np.zeros(loader._batch_shape(1),
                                    loader._wire_dtype), 1,
                           future=Future())
    loader._inflight.append(_FuseRecord(handle, "pending", tc))
    return handle


def _abort(loader):
    """The executor's abort path, once the stand-in decode has landed
    (discard waits for every decode it retires)."""
    for rec in loader._inflight:
        if rec.handle.future is not None:
            rec.handle.future.set_result(None)
    loader.discard_pending()
    assert not loader._ready and not loader._inflight


def _shape(out):
    """(valid rows, rows shipped, request ids) of one emission."""
    (batch,), _, cards = out
    return (batch.valid, int(batch.data.shape[0]),
            [tc.id for tc in cards.time_cards])


def _parent_take(ready, fuse, cap, buckets):
    """The parent commit's take rule, as a loop, over ``[(id, rows)]``:
    requests in order until ``fuse`` or until the next one would pass
    the cap, padded to the smallest bucket that fits
    -> ((valid, shipped, ids), what is left)."""
    n = valid = 0
    while n < len(ready) and n < fuse \
            and (n == 0 or valid + ready[n][1] <= cap):
        valid += ready[n][1]
        n += 1
    return ((valid, next(b for b in buckets if b >= valid),
             [i for i, _ in ready[:n]]), ready[n:])


@pytest.mark.parametrize("rule", ["hold", "idle"])
def test_full_ring_holds_back_the_latency_rules(rule):
    """Hold expired / nothing in flight: with a probe that reads full,
    neither emits — the batch could not reach the ring anyway."""
    probe = _Probe(full=True)
    loader = _wide_loader(probe,
                          max_hold_ms=0.0 if rule == "hold" else 1e9)
    _decoded(loader, [1, 9, 1])
    if rule == "hold":
        _still_decoding(loader)
    for _ in range(3):
        assert loader.poll() is None
    assert probe.asked and set(probe.asked) == {0}
    assert [rec.handle.n for rec in loader._ready] == [1, 9, 1]
    _abort(loader)


@pytest.mark.parametrize("fuse,rows,ships", [
    (4, [1, 1, 5, 1], 8),                     # `fuse` requests ready
    (64, [9, 9, 9, 9, 9, 1, 1, 1], 48),       # max_clips rows ready
], ids=["fuse", "max_clips"])
def test_full_ring_does_not_hold_back_a_full_batch(fuse, rows, ships):
    """The rules that mean "the batch is full" fire whatever the probe
    reads: publish then blocks, and the ring bounds what is in flight."""
    loader = _wide_loader(_Probe(full=True), fuse=fuse)
    _decoded(loader, rows)
    _still_decoding(loader)
    out = loader.poll()
    assert out is not None
    valid, shipped, ids = _shape(out)
    assert valid == shipped == ships
    assert ids == list(range(len(ids)))
    _abort(loader)


def test_probe_turning_free_releases_the_next_poll():
    probe = _Probe(full=True)
    loader = _wide_loader(probe, max_hold_ms=0.0)
    _decoded(loader, [1, 9, 1])
    _still_decoding(loader)
    assert loader.poll() is None
    probe.full = False
    # with a free slot the take is the parent's: everything that fits,
    # padded to its bucket
    assert _shape(loader.poll()) == (11, 16, [0, 1, 2])
    _abort(loader)


def test_probe_counts_the_emissions_still_ahead_of_it():
    """What the executor binds: a peek at the slots its next publishes
    write. An emission made but not yet published (the transfer worker
    holds it, or it waits for take_ready) will take the first free
    slot, so the stage asks about the one behind it."""
    from rnb_tpu.control import BufferRing
    ring = BufferRing(3, None, ())
    assert not ring.would_block(0) and not ring.would_block(2, 3)
    assert ring.would_block(0, 4)          # more than the ring holds
    ring.slots[1].write(("batch",))
    assert not ring.would_block(0) and ring.would_block(1)
    assert ring.would_block(0, 2) and not ring.would_block(2, 2)  # wraps
    ring.slots[1].release()
    assert not ring.would_block(1)

    probe = _Probe(full=False)
    loader = _wide_loader(probe, max_hold_ms=0.0)
    _decoded(loader, [1, 1])
    _still_decoding(loader)
    loader._push_ready("an emission awaiting take_ready")
    assert loader.next_deadline_s() == 0.0 and probe.asked == []
    assert loader._ring_full() is False and probe.asked == [1]
    assert loader.take_ready() == "an emission awaiting take_ready"
    assert loader._ring_full() is False and probe.asked == [1, 0]
    _abort(loader)


@pytest.mark.parametrize("inflight", [True, False])
def test_next_deadline_is_a_tick_while_held_back(inflight):
    """Held back for a slot, the stage asks to be looked at again
    within HARVEST_TICK_S — not at once (no spin at the executor's
    1 ms floor), and not after the 50 ms poll."""
    from rnb_tpu.runner import poll_plan
    probe = _Probe(full=True)
    loader = _wide_loader(probe, max_hold_ms=0.0)
    _decoded(loader, [1, 1])
    if inflight:
        _still_decoding(loader)
    assert loader.next_deadline_s() == loader.HARVEST_TICK_S
    assert poll_plan(loader) == (loader.HARVEST_TICK_S, True)
    probe.full = False
    assert loader.next_deadline_s() == 0.0  # the parent's answer
    _abort(loader)


_PARENT_SCENARIOS = {
    # name: (loader kwargs, ready rows, a decode still in flight)
    "idle-partial": ({}, [1, 9, 1], False),
    "hold-expired": ({"max_hold_ms": 0.0}, [1] * 5 + [9], True),
    "fuse-reached": ({"fuse": 3}, [1, 1, 1, 1, 1], True),
    "cap-stops-before-long-video": ({}, [1] * 41 + [9] + [1] * 5, True),
    "cap-reached-exactly": ({}, [1] * 39 + [9] + [1] * 5, True),
    "all-long": ({}, [9] * 6, True),
    "nothing-fires": ({}, [1, 1, 1], True),
    "wide-caps-of-the-file": ({"fuse": 12, "max_clips": 36,
                               "row_buckets": [6, 15, 24, 36]},
                              [3] * 15, False),
}


@pytest.mark.parametrize("probe", [None, "free"])
@pytest.mark.parametrize("name", sorted(_PARENT_SCENARIOS))
def test_without_back_pressure_the_emissions_are_the_parents(name, probe):
    """No probe bound, or one that reads free: poll() fires the
    parent's rules and every take is the parent's (the loop above),
    through to the drain."""
    kw, rows, inflight = _PARENT_SCENARIOS[name]
    loader = _wide_loader(_Probe(full=False) if probe else None, **kw)
    _decoded(loader, rows)
    if inflight:
        pending = _still_decoding(loader)
    fuse, cap = loader.fuse, loader.max_clips
    fires = (len(rows) >= fuse or sum(rows) >= cap or not inflight
             or loader.max_hold_ms == 0.0)
    got = []
    out = loader.poll()
    assert (out is not None) == fires
    if out is not None:
        got.append(_shape(out))
    if inflight:
        pending.future.set_result(None)
    while True:
        out = loader.flush()
        if out is None:
            break
        got.append(_shape(out))
    want, ready = [], list(enumerate(rows))
    if fires:      # the poll sees the decoded requests only
        take, ready = _parent_take(ready, fuse, cap, loader.row_buckets)
        want.append(take)
    if inflight:
        ready.append((999, 1))
    while ready:
        take, ready = _parent_take(ready, fuse, cap, loader.row_buckets)
        want.append(take)
    assert got == want


@pytest.mark.parametrize("rows,closes_at,left", [
    ([1] * 41 + [9] + [1] * 5, 40, [1, 9, 1, 1, 1, 1, 1]),
    ([1] * 39 + [9], 48, []),
    # ISSUE 26's second example as written: 39 + 9 is 48, a boundary
    ([1] * 39 + [9] + [1] * 5, 48, [1] * 5),
    # the longest prefix ON a boundary, though a longer one fits (43,
    # padded to 48): the three long videos ride the next batch
    ([1] * 7 + [9] * 5, 16, [9, 9, 9, 9]),
    ([3] * 17, 48, [3]),
], ids=["stops-at-40-before-a-long-video", "exactly-48",
        "48-then-five-left", "short-boundary-over-padded-fit", "three-clip-videos"])
def test_blocked_take_closes_on_a_bucket_boundary(rows, closes_at, left):
    """Ring full at the take: close at the longest in-order prefix on a
    row bucket, no pad rows; what is left stays at the head, in order,
    and ships first next time."""
    probe = _Probe(full=True)
    loader = _wide_loader(probe)
    _decoded(loader, rows)
    _still_decoding(loader)
    valid, shipped, ids = _shape(loader.poll())  # rows >= max_clips
    assert valid == shipped == closes_at
    assert ids == list(range(len(ids)))
    assert [rec.handle.n for rec in loader._ready] == left
    assert loader.padding.pad_rows == 0
    if left:
        probe.full = False
        loader._inflight.clear()    # nothing in flight: the idle rule
        valid, _shipped, more = _shape(loader.poll())
        assert more == list(range(len(ids), len(rows)))
        assert valid == sum(left)


def test_blocked_take_without_a_boundary_takes_the_longest_fit():
    """No prefix lands on a bucket: the parent's take, padding and all
    (nothing is held back for a boundary that may never come)."""
    loader = _wide_loader(_Probe(full=True))
    _decoded(loader, [9] * 6)
    _still_decoding(loader)
    assert _shape(loader.poll()) == (45, 48, [0, 1, 2, 3, 4])
    assert [rec.handle.n for rec in loader._ready] == [9]
    _abort(loader)


def test_blocked_take_keeps_the_rows_of_each_request():
    """A leftover rides the next batch through the assembly copy: the
    rows that ship are each request's own, in order."""
    probe = _Probe(full=True)
    loader = _wide_loader(probe, raw_output=True, row_buckets=None,
                          max_clips=16)
    # one bucket (16): boundary only at the cap
    _decoded(loader, [1] * 7 + [9] + [1] * 3)
    _still_decoding(loader)
    (first,), _, cards = loader.poll()
    assert first.valid == 16 and len(cards) == 8
    probe.full = False
    loader._inflight.clear()
    (second,), _, cards = loader.poll()
    assert second.valid == 3
    assert [tc.id for tc in cards.time_cards] == [8, 9, 10]
    rows = np.asarray(second.data)[:3].reshape(3, -1)
    assert [int(r[0]) for r in rows] == [9, 10, 11]
    assert (rows == rows[:, :1]).all()


def test_ragged_take_is_unchanged_under_a_full_ring():
    """One pool shape, explicit valid rows: nothing to close on. The
    take under a full ring is the longest fit, as with a free one."""
    got = []
    for full in (True, False):
        loader = _wide_loader(_Probe(full=full), ragged=True,
                              max_clips=48)
        _decoded(loader, [1] * 41 + [9] + [1] * 5)
        _still_decoding(loader)
        (batch,), _, cards = loader.poll()
        got.append((batch.valid, len(cards)))
        _abort(loader)
    assert got == [(41, 41), (41, 41)]


def test_flush_and_termination_drain_a_stage_that_is_held_back():
    """End of stream and the abort path take no notice of the probe's
    answer: flush ships everything, discard_pending leaves nothing."""
    loader = _wide_loader(_Probe(full=True), max_hold_ms=0.0)
    _decoded(loader, [1, 9, 1, 1])
    assert loader.poll() is None     # held back: the ring is full
    seen = []
    while True:
        out = loader.flush()
        if out is None:
            break
        seen += _shape(out)[2]
    assert seen == [0, 1, 2, 3]
    assert loader.next_deadline_s() is None
    _decoded(loader, [1, 1], first_id=4)
    assert loader.poll() is None
    _abort(loader)
    assert loader.next_deadline_s() is None


def test_emit_deferred_fires_once_a_batch_and_emit_says_why():
    """The counter that says how often the mechanism engages: one
    registered instant the first time a batch's latency rule is held
    back, and reason / rows / bucket / left on the loader.emit span."""
    from rnb_tpu import trace
    from rnb_tpu.analysis.schema import check_trace_events, \
        package_py_files
    from rnb_tpu.telemetry import TRACE_EVENT_REGISTRY
    assert "loader.emit_deferred" in {
        spec.pattern for spec in TRACE_EVENT_REGISTRY}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert check_trace_events(
        package_py_files(os.path.join(root, "rnb_tpu")), root) == []

    probe = _Probe(full=True)
    loader = _wide_loader(probe)
    tracer = trace.Tracer()
    trace.ACTIVE = tracer
    try:
        _decoded(loader, [1] * 30)
        for _ in range(3):           # nothing in flight, ring full
            assert loader.poll() is None
        _decoded(loader, [1] * 11 + [9] + [1] * 5, first_id=30)
        assert loader.poll() is not None   # 55 rows: full; closes at 40
        for _ in range(2):           # the leftover's own batch
            assert loader.poll() is None
        probe.full = False
        assert loader.poll() is not None   # idle rule, free slot
    finally:
        trace.ACTIVE = None
    events = tracer.snapshot_events()
    assert [e[0] for e in events if e[0].startswith("loader.emit")
            and e[0] != "loader.emit_wait"] == [
        "loader.emit_deferred", "loader.emit",
        "loader.emit_deferred", "loader.emit"]
    stats = [e[6] for e in events if e[0] == "loader.emit"]
    assert stats == [
        {"reason": "full", "rows": 40, "bucket": 40, "left": 15},
        {"reason": "idle", "rows": 15, "bucket": 16, "left": 0}]
