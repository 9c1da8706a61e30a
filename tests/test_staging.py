"""Zero-copy decode staging + pipelined transfer (rnb_tpu.staging).

Safety contract under test:

* golden parity — the staged path (native decode straight into slot
  row-slices, emission = the slot's bucket prefix) is byte-identical
  to the seed copy path on both pixel paths, padding included;
* slot reuse-after-transfer can never corrupt a published batch
  (drive real slot cycling after an emission, assert bytes stable);
* slot exhaustion backpressures (counted), never drops;
* a contained decode failure releases its slot; the abort path leaks
  neither slots nor native tickets;
* the transfer_async worker delivers every emission through
  take_ready()/flush() and its accounting reaches BenchmarkResult,
  log-meta.txt and `parse_utils --check`.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from rnb_tpu.decode import write_y4m
from rnb_tpu.decode.native import native_available
from rnb_tpu.staging import StagingPool, aggregate_snapshots
from rnb_tpu.telemetry import TimeCard

needs_native = pytest.mark.skipif(
    not native_available(), reason="native decoder not built")


def _dataset(tmp_path, n=8, frames=30, h=48, w=64, seed=3):
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n):
        p = os.path.join(str(tmp_path), "s%02d.y4m" % i)
        write_y4m(p, rng.integers(0, 256, (frames, h, w, 3),
                                  dtype=np.uint8))
        paths.append(p)
    return paths


def _fusing(device=None, **kw):
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DFusingLoader
    kw.setdefault("num_clips_population", [2])
    kw.setdefault("weights", [1])
    kw.setdefault("consecutive_frames", 2)
    kw.setdefault("num_warmups", 0)
    kw.setdefault("max_hold_ms", 1e9)
    kw.setdefault("depth", 100)
    return R2P1DFusingLoader(device or jax.devices()[0], **kw)


def _drain(loader, emitted):
    while True:
        out = loader.flush()
        if out is None:
            return emitted
        emitted.append(out)


def _run_all(loader, paths, start_id=0):
    emitted = []
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(start_id + i))
        if out[2] is not None:
            emitted.append(out)
    return _drain(loader, emitted)


# -- StagingPool unit behavior ----------------------------------------

def test_pool_exhaustion_backpressures_and_counts():
    shape = (2, 3, 4)
    pool = StagingPool([shape], 2)
    a = pool.acquire(shape)
    b = pool.acquire(shape)
    assert pool.try_acquire(shape) is None
    assert pool.available(shape) == 0
    pool.add_ref(a)
    got = []

    def blocked_acquire():
        got.append(pool.acquire(shape))

    t = threading.Thread(target=blocked_acquire, daemon=True)
    t.start()
    time.sleep(0.1)
    assert not got, "acquire must block while every slot is held"
    pool.retire_ref(a)  # a: refs 0, never transferred -> free
    t.join(timeout=5)
    assert got and got[0] is a
    snap = pool.snapshot()
    assert snap["acquires"] == 3
    assert snap["acquire_waits"] == 1  # counted, never dropped
    # b is still held; a second slot remains unavailable
    assert pool.available(shape) == 0


def test_pool_recycles_only_after_transfer_confirms():
    import jax
    shape = (4, 8)
    pool = StagingPool([shape], 1)
    slot = pool.acquire(shape)
    pool.add_ref(slot)
    slot.buf[:] = 7
    pool.begin_transfer(slot)
    arr = jax.device_put(slot.buf, jax.devices()[0])
    pool.finish_transfer(slot, arr)  # lazy confirm
    pool.retire_ref(slot)
    # re-acquiring the single slot forces the confirm; whatever the
    # backend did (copy or alias+realloc), the device bytes survive
    slot2 = pool.acquire(shape)
    slot2.buf[:] = 200
    np.testing.assert_array_equal(np.asarray(arr),
                                  np.full(shape, 7, np.uint8))


def test_pool_realloc_on_alias(monkeypatch):
    """An aliasing backend must cost a buffer swap, not a corruption."""
    import jax
    import rnb_tpu.staging as staging
    monkeypatch.setattr(staging, "_aliases", lambda arr, buf: True)
    shape = (2, 4)
    pool = StagingPool([shape], 1)
    slot = pool.acquire(shape)
    old_ptr = slot.buf.ctypes.data
    pool.begin_transfer(slot)
    pool.finish_transfer(slot, jax.device_put(np.zeros(shape, np.uint8)))
    slot2 = pool.acquire(shape)
    assert slot2 is slot
    assert slot2.buf.ctypes.data != old_ptr
    assert pool.snapshot()["reallocs"] == 1


def test_pool_failure_raises_instead_of_hanging():
    shape = (1, 1)
    pool = StagingPool([shape], 1)
    pool.acquire(shape)
    pool.fail(RuntimeError("transfer worker died"))
    with pytest.raises(RuntimeError, match="worker died"):
        pool.acquire(shape)


def test_plain_loader_without_prefetch_builds_no_pool():
    """An explicit staging_slots on a loader whose only decode path is
    synchronous must not allocate dead slots (nor report Staging:
    telemetry for a pool nothing can use)."""
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models.r2p1d.model import R2P1DLoader
    loader = R2P1DLoader(DeviceSpec(0), num_warmups=0, staging_slots=3)
    assert loader.staging is None


def test_aggregate_snapshots_sums():
    agg = aggregate_snapshots([
        {"slots": 3, "slot_bytes": 10, "acquires": 5, "acquire_waits": 1,
         "staged_batches": 4, "copied_batches": 1,
         "bypassed_batches": 2, "reallocs": 0},
        {"slots": 2, "slot_bytes": 20, "acquires": 2, "acquire_waits": 0,
         "staged_batches": 1, "copied_batches": 0, "reallocs": 2},
    ])
    assert agg == {"slots": 5, "slot_bytes": 30, "acquires": 7,
                   "acquire_waits": 1, "staged_batches": 5,
                   "copied_batches": 1, "bypassed_batches": 2,
                   "reallocs": 2}


# -- golden parity: staged path vs seed copy path ---------------------

def _run_all_deferred(loader, paths):
    """Submit every request with decode completion invisible, then
    drain — pinning the flush-driven grouping this parity test is
    about. Without the deferral the emission cadence races the C++
    decode pool: on a fast/idle box every tiny decode completes
    between submissions and the nothing-in-flight rule legally emits
    singles, at a machine-load-dependent rate that can differ between
    the arms (observed 6-vs-3 splits), failing the grouping assertion
    for timing reasons the byte-parity contract does not care about."""
    from rnb_tpu.models.r2p1d import model as model_mod
    real_ready = model_mod._DecodeHandle.ready
    model_mod._DecodeHandle.ready = property(lambda self: False)
    try:
        emitted = []
        for i, p in enumerate(paths):
            out = loader(None, p, TimeCard(i))
            if out[2] is not None:
                emitted.append(out)
    finally:
        model_mod._DecodeHandle.ready = real_ready
    return _drain(loader, emitted)


@needs_native
@pytest.mark.parametrize("pixel_path", ["rgb", "yuv420"])
def test_fused_staged_emissions_bit_identical_to_copy_path(
        tmp_path, pixel_path):
    paths = _dataset(tmp_path, n=6)
    kw = dict(fuse=3, pixel_path=pixel_path, row_buckets=[6, 15])
    staged = _run_all_deferred(_fusing(staging_slots=3, **kw), paths)
    seed = _run_all_deferred(_fusing(staging_slots=0, **kw), paths)
    assert sum(len(tc) for _, _, tc in staged) == 6
    assert len(staged) == len(seed)
    for (pb_s,), _, cards_s in staged:
        # same request sets fuse identically under flush-driven drain
        match = [e for e in seed
                 if [tc.id for tc in e[2].time_cards]
                 == [tc.id for tc in cards_s.time_cards]]
        assert match, "emission grouping diverged between paths"
        pb_c = match[0][0][0]
        assert pb_s.valid == pb_c.valid
        # full-array equality: valid rows AND zeroed padding
        np.testing.assert_array_equal(np.asarray(pb_s.data),
                                      np.asarray(pb_c.data))


@needs_native
def test_staged_run_actually_staged(tmp_path):
    """The zero-copy path must really engage on native y4m input —
    otherwise the parity test above compares copy against copy."""
    paths = _dataset(tmp_path, n=6)
    loader = _fusing(fuse=3, staging_slots=3)
    _run_all(loader, paths)
    snap = loader.staging.snapshot()
    assert snap["staged_batches"] >= 1
    assert snap["acquires"] >= 1


@needs_native
def test_plain_loader_staged_submit_matches_sync_path(tmp_path):
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models.r2p1d.model import R2P1DLoader
    paths = _dataset(tmp_path, n=3)
    loader = R2P1DLoader(DeviceSpec(0), max_clips=2,
                         consecutive_frames=2,
                         num_clips_population=[1, 2], weights=[1, 1],
                         num_warmups=0, prefetch=2)
    assert loader.staging is not None  # auto-enabled with prefetch
    for i, p in enumerate(paths):
        tc_a, tc_b = TimeCard(i), TimeCard(100 + i)
        handle = loader.submit(p, tc_a)
        (pb_staged,), _, _ = loader.complete(handle, p, tc_a)
        (pb_sync,), _, _ = loader(None, p, tc_b)  # seed copy path
        np.testing.assert_array_equal(np.asarray(pb_staged.data),
                                      np.asarray(pb_sync.data))
    assert loader.staging.snapshot()["staged_batches"] == 3


# -- slot reuse safety ------------------------------------------------

@needs_native
def test_slot_cycling_never_corrupts_published_batches(tmp_path):
    """The acceptance hazard: recycling a slot (and decoding new
    requests into it) must never mutate an already-published batch,
    even on backends where device_put aliases host memory."""
    paths = _dataset(tmp_path, n=10)
    loader = _fusing(fuse=2, staging_slots=2)  # tight pool: fast reuse
    published = []  # (snapshot, PaddedBatch)
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            pb = out[0][0]
            published.append((np.array(np.asarray(pb.data), copy=True),
                              pb))
    _drain(loader, [])
    # by now the tight pool has cycled each slot several times and
    # decoded fresh pixels into recycled buffers
    assert loader.staging.snapshot()["acquires"] >= 3
    assert published
    for snap, pb in published:
        np.testing.assert_array_equal(snap, np.asarray(pb.data))


@needs_native
def test_post_emit_slot_mutation_cannot_reach_device_batch(tmp_path):
    """White-box variant: scribbling over every slot buffer after the
    transfer confirmed must leave the emitted device batch unchanged
    (the alias probe forces a buffer swap when the backend aliased)."""
    paths = _dataset(tmp_path, n=2)
    loader = _fusing(fuse=2, staging_slots=2)
    emitted = _run_all(loader, paths)
    assert emitted
    pb = emitted[0][0][0]
    snap = np.array(np.asarray(pb.data), copy=True)
    pool = loader.staging
    # force lazy confirms, then scribble — the published array must
    # either own a copy or own the old (swapped-out) buffer
    for slots in pool._slots.values():
        for slot in slots:
            with pool._lock:
                pending = pool._claim_pending_locked(slot)
            pool._confirm_claimed(slot, pending)
            slot.buf[:] = 255
    np.testing.assert_array_equal(snap, np.asarray(pb.data))


# -- faults + abort ---------------------------------------------------

@needs_native
def test_contained_failure_releases_slot(tmp_path):
    from rnb_tpu.decode import get_decoder
    paths = _dataset(tmp_path, n=4)
    corrupt = os.path.join(str(tmp_path), "corrupt.y4m")
    write_y4m(corrupt, np.zeros((30, 48, 64, 3), np.uint8))
    # prime the per-process frame-count cache on the intact file, then
    # truncate: the submit-time probe succeeds and the failure lands
    # inside the fused batch's decode wait — the containment path
    get_decoder(corrupt).num_frames(corrupt)
    with open(corrupt, "r+b") as f:
        f.truncate(200)
    loader = _fusing(fuse=5, staging_slots=2)
    order = paths[:2] + [corrupt] + paths[2:]
    # deferred drain (see _run_all_deferred): the corrupt request must
    # land INSIDE the fused batch — on a fast box the undeferred
    # submit loop emits completed decodes singly and the corrupt video
    # fails alone, which never exercises the gapped-batch copy
    # fallback this test pins
    emitted = _run_all_deferred(loader, order)
    failed = loader.take_failed()
    assert len(failed) == 1  # the corrupt video was contained
    assert sum(len(tc) for _, _, tc in emitted) == 4
    # every slot is back: the parked failure released its reference
    pool = loader.staging
    assert pool.available() == pool.total_slots()
    # survivors of the gapped batch shipped via the copy fallback
    assert pool.snapshot()["copied_batches"] >= 1


@needs_native
def test_discard_pending_releases_slots_and_tickets(tmp_path):
    from rnb_tpu.decode.native import DecodePool
    # the shared pool may carry tickets from other tests' loaders;
    # assert only that THIS loader leaks nothing new
    before = set(DecodePool.shared()._pending)
    paths = _dataset(tmp_path, n=5)
    loader = _fusing(fuse=5, staging_slots=3)
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        assert out[2] is None or len(out[2])
    loader.discard_pending()
    assert set(DecodePool.shared()._pending) <= before
    pool = loader.staging
    assert pool.available() == pool.total_slots()


# -- transfer_async ---------------------------------------------------

@needs_native
def test_transfer_async_delivers_via_take_ready_and_flush(tmp_path):
    paths = _dataset(tmp_path, n=8)
    loader = _fusing(fuse=2, staging_slots=3, transfer_async=True)
    got = 0
    try:
        for i, p in enumerate(paths):
            out = loader(None, p, TimeCard(i))
            if out is not None and out[2] is not None:
                got += len(out[2])
            ready = loader.take_ready()
            if ready is not None:
                got += len(ready[2])
        while True:
            out = loader.flush()
            if out is None:
                break
            got += len(out[2])
        assert got == 8
        assert loader.staging.snapshot()["staged_batches"] >= 1
    finally:
        loader.discard_pending()  # stops the worker thread


def test_transfer_async_requires_fusing_loader():
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models.r2p1d.model import R2P1DLoader
    with pytest.raises(ValueError, match="transfer_async"):
        R2P1DLoader(DeviceSpec(0), num_warmups=0, transfer_async=True)


def test_worker_error_surfaces_through_take_ready(tmp_path):
    loader = _fusing(staging_slots=0, transfer_async=True)
    try:
        loader._worker.submit(lambda: (_ for _ in ()).throw(
            RuntimeError("boom-transfer")))
        deadline = time.time() + 5
        with pytest.raises(RuntimeError, match="boom-transfer"):
            while time.time() < deadline:
                loader.take_ready()
                time.sleep(0.01)
            raise AssertionError("worker error never surfaced")
    finally:
        loader.discard_pending()


# -- config validation ------------------------------------------------

def test_config_rejects_bad_staging_knobs():
    from rnb_tpu.config import ConfigError, parse_config

    def cfg(**extra):
        step = {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
                "queue_groups": [{"devices": [0]}]}
        step.update(extra)
        return {"video_path_iterator":
                "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
                "pipeline": [step]}

    with pytest.raises(ConfigError, match="staging_slots"):
        parse_config(cfg(staging_slots=-1))
    with pytest.raises(ConfigError, match="staging_slots"):
        parse_config(cfg(staging_slots=True))
    with pytest.raises(ConfigError, match="transfer_async"):
        parse_config(cfg(transfer_async="yes"))
    with pytest.raises(ConfigError, match="fallback_decode_threads"):
        parse_config(cfg(fallback_decode_threads=0))
    # the happy path parses
    parse_config(cfg(staging_slots=3, transfer_async=True,
                     fallback_decode_threads=2))


def test_fallback_decode_threads_defaults_to_native_rule():
    from rnb_tpu.decode.native import default_decode_threads
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models.r2p1d.model import R2P1DLoader
    loader = R2P1DLoader(DeviceSpec(0), num_warmups=0)
    assert loader.fallback_decode_threads == default_decode_threads()
    loader2 = R2P1DLoader(DeviceSpec(0), num_warmups=0,
                          fallback_decode_threads=2)
    assert loader2.fallback_decode_threads == 2
    with pytest.raises(ValueError):
        R2P1DLoader(DeviceSpec(0), num_warmups=0,
                    fallback_decode_threads=0)


# -- end-to-end through the runtime -----------------------------------

@needs_native
def test_staged_pipeline_end_to_end_with_accounting(tmp_path):
    """transfer_async pipeline through the real executor: every
    request completes, the Staging: line lands in log-meta.txt,
    BenchmarkResult carries the counters, and the cross-artifact
    `parse_utils --check` holds."""
    import sys

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.control import TerminationFlag
    from rnb_tpu.models.r2p1d import checkpoint as ckpt

    root = os.path.join(str(tmp_path), "data")
    os.makedirs(os.path.join(root, "label0"))
    rng = np.random.default_rng(11)
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
                 "pixel_path": "yuv420",
                 "staging_slots": 3, "transfer_async": True},
                {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
                 "queue_groups": [{"devices": [0], "in_queue": 0}],
                 "start_index": 1, "end_index": 5, "num_classes": 8,
                 "layer_sizes": [1, 1, 1, 1], "max_rows": 4,
                 "consecutive_frames": 2, "num_warmups": 0,
                 "ckpt_path": ckpt_path, "pixel_path": "yuv420"},
            ],
        }
        cfg_path = os.path.join(str(tmp_path), "staged.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        res = run_benchmark(cfg_path, mean_interval_ms=0, num_videos=10,
                            log_base=os.path.join(str(tmp_path), "logs"),
                            print_progress=False)
        assert res.termination_flag == \
            TerminationFlag.TARGET_NUM_VIDEOS_REACHED
        assert res.staging_slots >= 3
        assert res.staging_staged_batches >= 1
        with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
            meta_text = f.read()
        assert "Staging: " in meta_text
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        try:
            import parse_utils
        finally:
            sys.path.pop(0)
        meta = parse_utils.parse_meta(res.log_dir)
        assert meta["staging_staged_batches"] \
            == res.staging_staged_batches
        assert parse_utils.main(["--check", res.log_dir]) == 0
    finally:
        os.environ.pop("RNB_TPU_DATA_ROOT", None)
