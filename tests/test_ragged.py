"""Ragged row-pool dispatch (rnb_tpu/ops/ragged.py + the stage wiring).

Contract under test: one compiled shape per ragged stage (the pool),
valid-row outputs bit-identical to the bucketed path on BOTH pixel
paths, pad rows computed by nobody, segment offsets partitioning
rows_valid on every emission, cache hits filling pool rows, contained
decode failures excluded from the pool without poisoning batchmates,
and the bucketed arm's pad_rows equaling the ragged arm's
pad_rows_eliminated under the same seed.
"""

import json
import os

import numpy as np
import pytest

from rnb_tpu.stage import PadCounter, PaddedBatch, RaggedBatch
from rnb_tpu.telemetry import TimeCard, TimeCardList

LS = (1, 1, 1, 1)  # minimal layer sizes: fast compile, full topology


# -- the primitive ----------------------------------------------------

def test_ragged_normalize_matches_bucketed_and_zeroes_pads():
    import jax.numpy as jnp
    from rnb_tpu.ops.preprocess import normalize_u8
    from rnb_tpu.ops.ragged import ragged_normalize_u8
    pool = np.random.RandomState(0).randint(
        0, 256, (4, 2, 8, 8, 3), np.uint8)
    out = np.asarray(ragged_normalize_u8(jnp.asarray(pool), 2,
                                         dtype=jnp.float32))
    ref = np.asarray(normalize_u8(pool[:2], dtype=jnp.float32))
    assert np.array_equal(out[:2], ref)
    assert not out[2:].any()


def test_pallas_interpret_kernel_matches_jnp_fallback():
    # the TPU kernel body itself (grid skip via pl.when, scalar-
    # prefetched rows_valid) runs under interpret=True and must be
    # bit-identical to the masked jnp formulation tier-1 exercises
    import jax.numpy as jnp
    from rnb_tpu.ops.ragged import ragged_normalize_u8
    pool = np.random.RandomState(1).randint(
        0, 256, (5, 2, 8, 8, 3), np.uint8)  # row bytes 384 = 3*128
    for valid in (0, 1, 3, 5):
        jnp_out = np.asarray(ragged_normalize_u8(
            jnp.asarray(pool), valid, dtype=jnp.float32))
        pl_out = np.asarray(ragged_normalize_u8(
            jnp.asarray(pool), valid, dtype=jnp.float32,
            interpret=True))
        assert np.array_equal(jnp_out, pl_out), valid


def test_ragged_mask_rows_zeroes_tail_only():
    import jax.numpy as jnp
    from rnb_tpu.ops.ragged import ragged_mask_rows
    pool = np.random.RandomState(2).randint(1, 256, (4, 3, 7), np.uint8)
    out = np.asarray(ragged_mask_rows(jnp.asarray(pool), 3))
    assert np.array_equal(out[:3], pool[:3])
    assert not out[3:].any()


def test_segment_offsets_validation():
    from rnb_tpu.ops.ragged import check_segment_offsets
    check_segment_offsets((0, 2, 5), 5)
    check_segment_offsets((0, 0, 5), 5)  # zero-row segment is legal
    for offsets, valid in (((0, 2), 5), ((1, 5), 5), ((0, 3, 2), 3),
                           ((0,), 0)):
        with pytest.raises(ValueError):
            check_segment_offsets(offsets, valid)


def test_resolve_pool_rows_and_settings():
    from rnb_tpu.ops.ragged import RaggedSettings, resolve_pool_rows
    assert resolve_pool_rows(None, 15, "max") == 15
    assert resolve_pool_rows(15, 15, "max") == 15
    with pytest.raises(ValueError):
        resolve_pool_rows(9, 15, "max")
    assert RaggedSettings.from_config(None) is None
    assert RaggedSettings.from_config({"enabled": False}) is None
    # an empty object is treated as absent (autotune precedent)
    assert RaggedSettings.from_config({}) is None
    assert RaggedSettings.from_config(
        {"enabled": True}).pool_rows is None
    assert RaggedSettings.from_config(
        {"pool_rows": 15}).pool_rows == 15


def test_default_ragged_chunk_divides_pool():
    from rnb_tpu.models.r2p1d.model import default_ragged_chunk
    for rows in (1, 2, 3, 6, 12, 15, 16):
        c = default_ragged_chunk(rows)
        assert c >= 1 and rows % c == 0
        assert c <= max(1, rows // 3)
    assert default_ragged_chunk(15) == 5


# -- stage contract ---------------------------------------------------

def test_ragged_batch_payload_validation():
    from rnb_tpu.runner import validate_payload
    data = np.zeros((4, 3), np.float32)
    validate_payload(((4, 3),),
                     (RaggedBatch(data, 3, (0, 1, 3)),), "t")
    with pytest.raises(ValueError):
        validate_payload(((4, 3),),
                         (RaggedBatch(data, 3, (0, 1, 2)),), "t")
    assert RaggedBatch(data, 3, (0, 1, 3)).num_segments == 2


def test_config_ragged_root_key():
    from rnb_tpu.config import ConfigError, parse_config

    def base(**root):
        raw = {
            "video_path_iterator": "x.Y",
            "pipeline": [
                {"model": "a.B",
                 "queue_groups": [{"devices": [0], "out_queues": [0]}]},
                {"model": "c.D",
                 "queue_groups": [{"devices": [0], "in_queue": 0}]}],
        }
        raw.update(root)
        return raw

    cfg = parse_config(base(ragged={"enabled": True, "pool_rows": 15}))
    assert cfg.ragged == {"enabled": True, "pool_rows": 15}
    assert parse_config(base()).ragged is None
    for bad in ({"pool_rows": 0}, {"pool_rows": True},
                {"enabled": "yes"}, {"bogus": 1}, ["x"]):
        with pytest.raises(ConfigError):
            parse_config(base(ragged=bad))
    # one fixed pool shape cannot be row-split into segments
    raw = base(ragged={"enabled": True})
    raw["pipeline"][0]["num_segments"] = 2
    with pytest.raises(ConfigError):
        parse_config(raw)


def test_batcher_ragged_emits_pool_with_offsets():
    from rnb_tpu.batcher import Batcher
    b = Batcher("host", batch=3, max_rows=6, consecutive_frames=2,
                frame_hw=8, row_buckets=[4, 6], ragged=True)
    shape = (2, 8, 8, 3)
    cards = [TimeCard(i) for i in range(3)]
    for i, card in enumerate(cards):
        rows = np.full((i + 1,) + shape, i, np.float32)
        out = b((PaddedBatch.from_rows(rows, i + 1),), None, card)
    tensors, _, tcl = out
    pb = tensors[0]
    assert isinstance(pb, RaggedBatch)
    assert pb.data.shape[0] == 6          # the one pool shape
    assert pb.valid == 6
    assert pb.segment_offsets == (0, 1, 3, 6)
    assert isinstance(tcl, TimeCardList) and len(tcl) == 3
    # 6 valid rows in a 6-row pool: nothing padded, nothing eliminated
    assert b.padding.snapshot() == {"pad_rows": 0, "total_rows": 6,
                                    "emissions": 1}
    assert b.ragged_stats["emissions"] == 1
    assert b.ragged_stats["rows"] == 6
    assert b.ragged_stats["pad_rows_eliminated"] == 0
    # a partial batch: flush pads nothing but eliminates the
    # counterfactual bucket's pad (3 valid rows -> 4-bucket)
    b((PaddedBatch.from_rows(np.zeros((3,) + shape, np.float32), 3),),
      None, TimeCard(9))
    tensors, _, _ = b.flush()
    assert tensors[0].valid == 3
    assert tensors[0].data.shape[0] == 6
    assert b.ragged_stats["pad_rows_eliminated"] == 1
    assert b.padding.snapshot()["pad_rows"] == 0


def test_pad_counter_and_bucketed_batcher_accounting():
    from rnb_tpu.batcher import Batcher
    c = PadCounter()
    assert c.note(4, 6) == 2 and c.note(6, 6) == 0
    assert c.snapshot() == {"pad_rows": 2, "total_rows": 12,
                            "emissions": 2}
    b = Batcher("host", batch=2, max_rows=6, consecutive_frames=2,
                frame_hw=8, row_buckets=[4, 6])
    shape = (2, 8, 8, 3)
    cards = [TimeCard(0), TimeCard(1)]
    for card in cards:
        out = b((PaddedBatch.from_rows(
            np.zeros((1,) + shape, np.float32), 1),), None, card)
    assert not isinstance(out[0][0], RaggedBatch)
    assert out[0][0].data.shape[0] == 4   # padded to the 4-bucket
    assert b.padding.snapshot() == {"pad_rows": 2, "total_rows": 4,
                                    "emissions": 1}
    # emission pad attributed to the first constituent card only
    assert getattr(cards[0], "pad_rows") == 2
    assert getattr(cards[1], "pad_rows") == 0


# -- golden-logit parity, both pixel paths ----------------------------

def _runner(ragged, pixel_path, chunk=None, num_warmups=1):
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    kw = dict(start_index=1, end_index=5, num_classes=8,
              layer_sizes=LS, max_rows=4, consecutive_frames=2,
              num_warmups=num_warmups, pixel_path=pixel_path)
    if ragged:
        kw.update(ragged=True, ragged_pool_rows=4,
                  ragged_chunk_rows=chunk)
    return R2P1DRunner(jax.devices()[0], **kw)


def test_golden_logit_parity_rgb():
    import jax.numpy as jnp
    from rnb_tpu.ops.ragged import ragged_normalize_u8
    pool_u8 = np.random.RandomState(3).randint(
        0, 256, (4, 2, 112, 112, 3), np.uint8)
    bucketed = _runner(False, "rgb")
    ragged = _runner(True, "rgb", chunk=2)
    for valid in (1, 3, 4):
        # the loader-side ragged preprocess masks + normalizes the
        # pool; the bucketed loader normalizes the padded bucket
        pool = jnp.asarray(ragged_normalize_u8(
            jnp.asarray(pool_u8), valid, dtype=jnp.bfloat16))
        from rnb_tpu.ops.preprocess import normalize_u8
        bucket = jnp.asarray(normalize_u8(
            np.where(np.arange(4)[:, None, None, None, None] < valid,
                     pool_u8, 0), dtype=jnp.bfloat16))
        (rg,), _, _ = ragged(
            (RaggedBatch(pool, valid, (0, valid)),), None, TimeCard(0))
        (bk,), _, _ = bucketed((PaddedBatch(bucket, valid),), None,
                               TimeCard(1))
        assert isinstance(rg, RaggedBatch)
        assert rg.data.shape[0] == 4
        assert np.array_equal(np.asarray(rg.data)[:valid],
                              np.asarray(bk.data)[:valid]), valid
    assert ragged.compiles.snapshot()["warmup"] == 1


def test_golden_logit_parity_yuv420():
    import jax.numpy as jnp
    from rnb_tpu.ops.yuv import packed_frame_bytes
    pk = packed_frame_bytes(112, 112)
    pool_u8 = np.random.RandomState(4).randint(
        0, 256, (4, 2, pk), np.uint8)
    bucketed = _runner(False, "yuv420")
    ragged = _runner(True, "yuv420", chunk=2)
    for valid in (1, 2, 4):
        masked = np.where(np.arange(4)[:, None, None] < valid,
                          pool_u8, 0)
        (rg,), _, _ = ragged(
            (RaggedBatch(jnp.asarray(pool_u8), valid, (0, valid)),),
            None, TimeCard(0))
        (bk,), _, _ = bucketed(
            (PaddedBatch(jnp.asarray(masked), valid),), None,
            TimeCard(1))
        assert np.array_equal(np.asarray(rg.data)[:valid],
                              np.asarray(bk.data)[:valid]), valid
    # the ragged stage's whole life is ONE compiled signature; the
    # parity loop above added none (steady tracking starts at freeze)
    ragged.compiles.freeze()
    (void,), _, _ = ragged(
        (RaggedBatch(jnp.asarray(pool_u8), 3, (0, 3)),), None,
        TimeCard(2))
    snap = ragged.compiles.snapshot()
    assert snap["warmup"] == 1 and snap["steady_new"] == 0


def test_runner_rejects_bad_ragged_knobs():
    with pytest.raises(ValueError):
        _runner(True, "rgb", chunk=3)  # 3 does not divide pool 4
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    with pytest.raises(ValueError):
        R2P1DRunner(jax.devices()[0], start_index=1, end_index=5,
                    num_classes=8, layer_sizes=LS, max_rows=4,
                    consecutive_frames=2, num_warmups=0,
                    ragged=True, ragged_pool_rows=6)


# -- pool fill / seal / flush (fusing loader) -------------------------

def _write_y4m_dataset(tmp_path, n=6, frames=8):
    from rnb_tpu.decode import write_y4m
    rng = np.random.default_rng(7)
    paths = []
    for i in range(n):
        p = os.path.join(str(tmp_path), "v%02d.y4m" % i)
        write_y4m(p, rng.integers(0, 256, (frames, 32, 32, 3),
                                  dtype=np.uint8))
        paths.append(p)
    return paths


def _ragged_loader(**kw):
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DFusingLoader
    kw.setdefault("num_clips_population", [1])
    kw.setdefault("weights", [1])
    kw.setdefault("num_warmups", 0)
    kw.setdefault("max_clips", 4)
    kw.setdefault("consecutive_frames", 2)
    kw.setdefault("ragged", True)
    return R2P1DFusingLoader(jax.devices()[0], **kw)


def _drain(loader, emitted):
    while True:
        out = loader.flush()
        if out is None:
            return
        emitted.append(out)


def test_pool_fill_emits_ragged_with_partitioning_offsets(tmp_path):
    paths = _write_y4m_dataset(tmp_path)
    loader = _ragged_loader(fuse=3, max_hold_ms=10000.0, depth=50)
    emitted = []
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            emitted.append(out)
    _drain(loader, emitted)
    assert sum(len(tc) for _, _, tc in emitted) == len(paths)
    for (pb,), _, cards in emitted:
        assert isinstance(pb, RaggedBatch)
        assert pb.data.shape[0] == 4          # the one pool shape
        assert pb.segment_offsets[0] == 0
        assert pb.segment_offsets[-1] == pb.valid
        assert pb.num_segments == len(cards)
    stats = loader.ragged_stats
    assert stats["emissions"] == len(emitted)
    assert stats["rows"] == len(paths)        # 1 clip per request
    # no bucket vocabulary configured: the counterfactual is max-shape
    # padding, so every emission eliminates pool - valid rows
    assert stats["pad_rows_eliminated"] == sum(
        4 - pb.valid for (pb,), _, _ in emitted)
    assert loader.padding.snapshot()["pad_rows"] == 0


def test_pool_cache_hit_rows_fill_the_pool(tmp_path):
    paths = _write_y4m_dataset(tmp_path, n=2)
    loader = _ragged_loader(fuse=2, max_hold_ms=10000.0, depth=50,
                            cache_mb=64)
    emitted = []
    for i, p in enumerate(paths):
        out = loader(None, p, TimeCard(i))
        if out[2] is not None:
            emitted.append(out)
    _drain(loader, emitted)
    inserted = loader.cache.snapshot()["inserts"]
    assert inserted == len(paths)
    # the same video again: a hit — its cached HOST rows fill pool
    # rows (no second decode) and ride a normal ragged emission
    hit_card = TimeCard(99)
    out = loader(None, paths[0], hit_card)
    if out[2] is None:
        emitted = []
        _drain(loader, emitted)
        out = emitted[0]
    (pb,), _, cards = out
    assert isinstance(pb, RaggedBatch)
    assert hit_card.cache_hit is True
    assert loader.ragged_stats["cache_hit_rows"] >= 1
    assert loader.cache.snapshot()["hits"] == 1


def test_autotune_candidates_continuous_under_ragged():
    from rnb_tpu.autotune import AutotuneSettings
    from rnb_tpu.batcher import Batcher
    settings = AutotuneSettings.from_config(
        {"enabled": True, "slo_ms": 20.0})
    loader = _ragged_loader(fuse=3)
    ctl = loader.enable_autotune(settings)
    assert ctl.candidates == tuple(range(1, 5))   # 1..pool_rows
    assert ctl.bucket_for(2) == 2                 # no quantization
    b = Batcher("host", batch=2, max_rows=6, consecutive_frames=2,
                frame_hw=8, row_buckets=[4, 6], ragged=True)
    ctl_b = b.enable_autotune(settings)
    assert ctl_b.candidates == tuple(range(1, 7))
    # a restriction naming a non-warmed count is legal under ragged
    restricted = AutotuneSettings.from_config(
        {"enabled": True, "slo_ms": 20.0, "buckets": [3, 5]})
    assert b.enable_autotune(restricted).candidates == (3, 5)


def test_contained_decode_failure_mid_pool(tmp_path):
    """A permanent decode failure planned into the middle of an open
    pool is excluded from the emission (take_failed) without poisoning
    its pool-mates, and the shipped segment table still partitions the
    surviving rows."""
    import time as _time
    from rnb_tpu.faults import CorruptVideoError
    from rnb_tpu.models.r2p1d.model import _FuseRecord
    paths = _write_y4m_dataset(tmp_path, n=4)
    loader = _ragged_loader(fuse=5, max_hold_ms=10000.0, depth=50)
    emitted = []
    cards = [TimeCard(i) for i in range(5)]
    for card, p in zip(cards[:2], paths[:2]):
        out = loader(None, p, card)
        if out[2] is not None:
            emitted.append(out)

    class BoomHandle:
        n = 1
        out = None
        error = None
        slot = None
        row0 = 0
        ready = True

        def wait(self, v):
            raise CorruptVideoError("mid-pool corruption")

    boom = _FuseRecord(BoomHandle(), "boom.y4m", cards[2])
    boom.t_ready = _time.monotonic()
    loader._inflight.append(boom)
    for card, p in zip(cards[3:], paths[2:]):
        out = loader(None, p, card)
        if out[2] is not None:
            emitted.append(out)
    _drain(loader, emitted)
    failed = loader.take_failed()
    assert [tc.id for tc, _reason in failed] == [2]
    assert failed[0][1] == "corrupt-video"
    survivors = [tc.id for _, _, tcl in emitted
                 for tc in tcl.time_cards]
    assert sorted(survivors) == [0, 1, 3, 4]
    for (pb,), _, tcl in emitted:
        # the failed request's planned rows are excluded: offsets
        # still partition the rows that actually shipped
        assert isinstance(pb, RaggedBatch)
        assert pb.segment_offsets[-1] == pb.valid
        assert pb.num_segments == len(tcl)


# -- mixed clip-count e2e: bucketed pad_rows == ragged eliminated -----

def _e2e_config(ragged):
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 20,
             "max_clips": 3, "consecutive_frames": 2,
             "num_clips_population": [1, 2, 3],
             "weights": [2, 1, 1],
             "row_buckets": [2, 3],
             "num_warmups": 1},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": [1], "in_queue": 0}],
             "start_index": 1, "end_index": 5, "num_classes": 8,
             "layer_sizes": list(LS), "max_rows": 3,
             "row_buckets": [2, 3],
             "consecutive_frames": 2, "num_warmups": 1}],
    }
    if ragged:
        cfg["ragged"] = {"enabled": True, "pool_rows": 3}
    return cfg


def test_mixed_clip_e2e_pad_parity_and_check(tmp_path):
    """The A/B invariant the whole feature is measured by: under the
    same seed, the ragged arm eliminates EXACTLY the pad rows the
    bucketed arm ships, the segment/offset invariants hold end-to-end
    (parse_utils --check green on both arms), and the ragged network
    stage compiles exactly one signature with none added mid-run."""
    import subprocess
    import sys
    from rnb_tpu.benchmark import run_benchmark
    results = {}
    for arm in ("bucketed", "ragged"):
        path = os.path.join(str(tmp_path), arm + ".json")
        with open(path, "w") as f:
            json.dump(_e2e_config(ragged=(arm == "ragged")), f)
        res = run_benchmark(path, mean_interval_ms=0, num_videos=6,
                            queue_size=64,
                            log_base=os.path.join(str(tmp_path),
                                                  "logs-" + arm),
                            print_progress=False, seed=11)
        assert res.termination_flag == 0
        results[arm] = res
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))), "scripts",
                 "parse_utils.py"),
             "--check", res.log_dir],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
    bucketed, ragged = results["bucketed"], results["ragged"]
    # the headline equality: same seed, same requests, same
    # per-request bucket rule — pads eliminated == pads shipped
    assert bucketed.pad_rows > 0
    assert ragged.ragged_pad_rows_eliminated == bucketed.pad_rows
    assert ragged.pad_rows == 0
    assert ragged.ragged_rows == bucketed.total_rows \
        - bucketed.pad_rows
    # one compiled signature per ragged stage, none added mid-run;
    # the bucketed arm warms one per bucket
    assert ragged.compile_signatures["step1"]["warmup"] == 1
    assert ragged.compile_signatures["step1"]["steady_new"] == 0
    assert bucketed.compile_signatures["step1"]["warmup"] == 2
    # both arms completed the same workload successfully
    assert bucketed.num_completed == ragged.num_completed == 6
