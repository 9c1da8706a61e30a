"""Native C++ decoder vs numpy Y4MDecoder: bit parity + pool behavior.

The native backend must be indistinguishable from the numpy one (same
frames, same clamp-past-EOF semantics, same resize index map) so the
pipeline can switch between them freely.  Tests auto-build the library
if a toolchain is present and skip otherwise.
"""

import os
import subprocess

import numpy as np
import pytest

from rnb_tpu.decode import Y4MDecoder, write_y4m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "native", "build", "librnb_decode.so")


def _ensure_lib():
    if not os.path.exists(LIB):
        try:
            subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                           check=True, capture_output=True)
        except (OSError, subprocess.CalledProcessError):
            pytest.skip("native toolchain unavailable")
    from rnb_tpu.decode.native import native_available
    if not native_available():
        pytest.skip("native decode library failed to load")


@pytest.fixture(scope="module")
def native():
    _ensure_lib()
    from rnb_tpu.decode.native import NativeY4MDecoder
    return NativeY4MDecoder()


def _write_video(path, n=12, h=24, w=32, seed=0):
    rng = np.random.default_rng(seed)
    frames = rng.integers(0, 256, (n, h, w, 3), dtype=np.uint8)
    write_y4m(str(path), frames)
    return frames


def test_probe_matches_numpy(tmp_path, native):
    path = tmp_path / "a.y4m"
    _write_video(path, n=9)
    assert native.num_frames(str(path)) == 9
    assert Y4MDecoder().num_frames(str(path)) == 9


@pytest.mark.parametrize("geometry", [(24, 32, 16, 16), (24, 32, 24, 32),
                                      (16, 16, 20, 28)])
def test_decode_parity_with_numpy(tmp_path, native, geometry):
    h, w, out_h, out_w = geometry
    path = tmp_path / "b.y4m"
    _write_video(path, n=10, h=h, w=w, seed=1)
    starts = [0, 3, 7]
    got = native.decode_clips(str(path), starts, consecutive_frames=4,
                              width=out_w, height=out_h)
    want = Y4MDecoder().decode_clips(str(path), starts,
                                     consecutive_frames=4,
                                     width=out_w, height=out_h)
    assert got.shape == want.shape == (3, 4, out_h, out_w, 3)
    # float rounding at truncation boundaries may differ by 1
    diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
    assert diff.max() <= 1, "max pixel delta %d" % diff.max()
    assert (diff > 0).mean() < 0.01


@pytest.mark.parametrize("colorspace", ["444", "420"])
@pytest.mark.parametrize("geometry", [(24, 32, 16, 16), (16, 16, 20, 28),
                                      (30, 42, 12, 18), (48, 20, 48, 20)])
def test_yuv_gather_parity_sweep(tmp_path, native, geometry, colorspace):
    """Packed-plane gathers are pure byte moves — the two backends must
    be BIT-exact across upscale/downscale/identity geometries and both
    source colourspaces."""
    h, w, out_h, out_w = geometry
    rng = np.random.default_rng(h * 1000 + w)
    frames = rng.integers(0, 256, (9, h, w, 3), dtype=np.uint8)
    path = tmp_path / ("c_%s.y4m" % colorspace)
    write_y4m(str(path), frames, colorspace=colorspace)
    # >= POOL_SPLIT_MIN_CLIPS so the native side exercises the POOLED
    # yuv fan-out (per-chunk slices of one packed batch buffer), not
    # just the synchronous path
    starts = [0, 2, 4, 6]
    got = native.decode_clips_yuv(str(path), starts,
                                  consecutive_frames=3,
                                  width=out_w, height=out_h)
    want = Y4MDecoder().decode_clips_yuv(str(path), starts,
                                         consecutive_frames=3,
                                         width=out_w, height=out_h)
    assert got.shape == want.shape == (4, 3, out_h * out_w * 3 // 2)
    np.testing.assert_array_equal(got, want)


def test_yuv_odd_geometry_rejected_numpy(tmp_path):
    # toolchain-independent: the numpy backend's check must hold even
    # where the native library cannot build
    path = tmp_path / "d.y4m"
    _write_video(path, n=4)
    with pytest.raises(ValueError):
        Y4MDecoder().decode_clips_yuv(str(path), [0], 2,
                                      width=15, height=16)


def test_yuv_odd_geometry_rejected_native(tmp_path, native):
    path = tmp_path / "d.y4m"
    _write_video(path, n=4)
    with pytest.raises(ValueError):
        native.decode_clips_yuv(str(path), [0], 2, width=15, height=16)


def test_clamp_past_eof_matches_numpy(tmp_path, native):
    path = tmp_path / "c.y4m"
    _write_video(path, n=5, seed=2)
    got = native.decode_clips(str(path), [3], consecutive_frames=6,
                              width=16, height=16)
    want = Y4MDecoder().decode_clips(str(path), [3], consecutive_frames=6,
                                     width=16, height=16)
    # frames past EOF repeat the last frame
    np.testing.assert_array_equal(got[0, 2], got[0, 5])
    diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
    assert diff.max() <= 1


def test_negative_start_rejected_by_both_backends(tmp_path, native):
    path = tmp_path / "neg.y4m"
    _write_video(path, n=4, seed=3)
    with pytest.raises(ValueError):
        native.decode_clips(str(path), [-1], consecutive_frames=2,
                            width=16, height=16)
    with pytest.raises(ValueError):
        Y4MDecoder().decode_clips(str(path), [-1], consecutive_frames=2,
                                  width=16, height=16)


def test_errors_surface(tmp_path, native):
    bad = tmp_path / "bad.y4m"
    bad.write_bytes(b"not a y4m header\n")
    with pytest.raises(ValueError):
        native.num_frames(str(bad))
    with pytest.raises(ValueError):
        native.decode_clips(str(tmp_path / "missing.y4m"), [0])


def test_pool_concurrent_decodes(tmp_path, native):
    from rnb_tpu.decode.native import DecodePool
    paths, frames = [], []
    for i in range(6):
        p = tmp_path / ("v%d.y4m" % i)
        frames.append(_write_video(p, n=8, seed=10 + i))
        paths.append(str(p))
    pool = DecodePool(num_threads=3)
    try:
        tickets = [pool.submit(p, [0, 2], 3, 16, 16) for p in paths]
        sync = native
        for p, (ticket, out) in zip(paths, tickets):
            pool.wait(ticket, p)
            want = sync.decode_clips(p, [0, 2], consecutive_frames=3,
                                     width=16, height=16)
            np.testing.assert_array_equal(out, want)
    finally:
        pool.close()


def test_pool_stats_count_frames_and_busy_time(tmp_path, native):
    from rnb_tpu.decode.native import DecodePool
    p = tmp_path / "stats.y4m"
    _write_video(p, n=12, seed=11)
    pool = DecodePool(num_threads=2)
    try:
        assert pool.stats() == {"busy_s": 0.0, "frames": 0}
        tickets = [pool.submit(str(p), [0, 4, 8], 3, 16, 16)[0]
                   for _ in range(4)]
        for ticket in tickets:
            pool.wait(ticket, str(p))
        stats = pool.stats()
        # 4 jobs of 3 clips of 3 frames; the workers' own clock ran
        assert stats["frames"] == 4 * 3 * 3
        assert 0.0 < stats["busy_s"] < 60.0
        # a job that fails is timed and decodes no frame
        bad = tmp_path / "gone.y4m"
        ticket, _ = pool.submit(str(bad), [0], 2, 16, 16)
        with pytest.raises(ValueError):
            pool.wait(ticket, str(bad))
        after = pool.stats()
        assert after["frames"] == stats["frames"]
        assert after["busy_s"] >= stats["busy_s"]
    finally:
        pool.close()


def test_pool_double_wait_fails_fast(tmp_path, native):
    from rnb_tpu.decode.native import DecodePool
    p = tmp_path / "dw.y4m"
    _write_video(p, n=4)
    pool = DecodePool(num_threads=1)
    try:
        ticket, _ = pool.submit(str(p), [0], 2, 16, 16)
        pool.wait(ticket)
        with pytest.raises(ValueError):
            pool.wait(ticket)  # retired ticket must not hang
    finally:
        pool.close()


def test_get_decoder_prefers_native(tmp_path, native):
    from rnb_tpu.decode import get_decoder
    from rnb_tpu.decode.native import NativeY4MDecoder
    path = tmp_path / "d.y4m"
    _write_video(path, n=3)
    assert isinstance(get_decoder(str(path)), NativeY4MDecoder)
