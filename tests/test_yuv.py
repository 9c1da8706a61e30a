"""The yuv420 pixel path: packed-plane decode backends + on-device
colourspace conversion.

Contract under test (rnb_tpu/ops/yuv.py docstring):
  * numpy vs native packed-plane gathers are BIT-EXACT;
  * the jnp converter matches the numpy oracle within 1 u8 LSB (XLA
    may contract mul+add into FMA);
  * luma is bit-exact with the RGB pixel path (same index map);
  * the loader's yuv420 mode ships packed u8 and the network stage's
    fused ingest produces the same predictions as the rgb path;
  * ``normalize_yuv420`` is the reference composition to the bit, and
    the stage program compiled for a TPU holds no Pallas kernel and
    no flat ``(M, 128)`` view of the clip between planes and stem.
"""

import os

import numpy as np
import pytest

from rnb_tpu.decode import (SyntheticDecoder, Y4MDecoder, get_decoder,
                            write_y4m)
from rnb_tpu.ops.yuv import (packed_frame_bytes, yuv420_to_rgb_numpy,
                             yuv420_to_rgb_u8)


def _make_y4m(tmp_path, name="vid.y4m", frames=24, h=96, w=128, seed=7):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (frames, h, w, 3), dtype=np.uint8)
    path = os.path.join(str(tmp_path), name)
    write_y4m(path, data)
    return path


def test_packed_frame_bytes():
    assert packed_frame_bytes(112, 112) == 112 * 112 * 3 // 2
    with pytest.raises(ValueError):
        packed_frame_bytes(111, 112)


def test_numpy_yuv_matches_rgb_exactly_when_chroma_constant(tmp_path):
    """The two pixel paths differ ONLY in chroma index choice, so on a
    video with exactly constant chroma planes (U=V=128 raw) re-deriving
    RGB from the packed planes must be bit-exact with the direct RGB
    decode. (write_y4m's RGB->YUV roundtrip would leave ±1 chroma
    residue, so the 4:2:0 payload is written directly.)"""
    rng = np.random.default_rng(3)
    h, w, n = 96, 128, 20
    path = os.path.join(str(tmp_path), "gray.y4m")
    with open(path, "wb") as f:
        f.write(b"YUV4MPEG2 W%d H%d F25:1 Ip A1:1 C420\n" % (w, h))
        for _ in range(n):
            f.write(b"FRAME\n")
            f.write(rng.integers(0, 256, h * w, dtype=np.uint8)
                    .tobytes())
            f.write(np.full((h // 2) * (w // 2) * 2, 128,
                            np.uint8).tobytes())
    dec = Y4MDecoder()
    packed = dec.decode_clips_yuv(path, [0, 5], consecutive_frames=4,
                                  width=56, height=48)
    assert packed.shape == (2, 4, packed_frame_bytes(48, 56))
    assert packed.dtype == np.uint8
    rgb = dec.decode_clips(path, [0, 5], consecutive_frames=4,
                           width=56, height=48)
    re_rgb = yuv420_to_rgb_numpy(packed, 48, 56)
    np.testing.assert_array_equal(re_rgb, rgb)


def _smooth_frames(n=12, h=96, w=128):
    """Real-video-like moving gradients (noise frames would make the
    rgb-vs-yuv420 chroma index difference look maximal; real chroma is
    locally smooth)."""
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    t = np.arange(n, dtype=np.float32)[:, None, None]
    frames = np.empty((n, h, w, 3), np.uint8)
    for c in range(3):
        frames[..., c] = (127.5 * (1 + np.sin(
            2 * np.pi * (yy / h + xx / w) + 0.3 * c + 0.1 * t))
        ).astype(np.uint8)
    return frames


def test_numpy_yuv_close_on_smooth_content(tmp_path):
    """On smooth (real-video-like) content the half-res chroma map
    stays within a few LSB of the rgb path everywhere."""
    frames = _smooth_frames()
    path = os.path.join(str(tmp_path), "smooth.y4m")
    write_y4m(path, frames)
    dec = Y4MDecoder()
    packed = dec.decode_clips_yuv(path, [0], consecutive_frames=8,
                                  width=56, height=48)
    rgb = dec.decode_clips(path, [0], consecutive_frames=8,
                           width=56, height=48)
    re_rgb = yuv420_to_rgb_numpy(packed, 48, 56)
    diff = np.abs(re_rgb.astype(int) - rgb.astype(int))
    # the chroma sample position can shift by ~1 source pixel in each
    # axis; on this gradient that is a handful of LSB
    assert np.percentile(diff, 50) <= 2
    assert np.percentile(diff, 99) <= 12
    assert diff.max() <= 24


def test_numpy_vs_native_yuv_bit_exact(tmp_path):
    from rnb_tpu.decode.native import NativeY4MDecoder, native_available
    if not native_available():
        pytest.skip("native decoder not built")
    path = _make_y4m(tmp_path, frames=30, h=120, w=160)
    a = Y4MDecoder().decode_clips_yuv(path, [0, 3, 25],
                                      consecutive_frames=8,
                                      width=112, height=112)
    b = NativeY4MDecoder(use_pool=False).decode_clips_yuv(
        path, [0, 3, 25], consecutive_frames=8, width=112, height=112)
    np.testing.assert_array_equal(a, b)


def test_native_pool_yuv_bit_exact(tmp_path):
    from rnb_tpu.decode.native import (DecodePool, NativeY4MDecoder,
                                       native_available)
    from rnb_tpu.decode.native import PIX_YUV420
    if not native_available():
        pytest.skip("native decoder not built")
    path = _make_y4m(tmp_path, frames=16, h=96, w=128)
    want = Y4MDecoder().decode_clips_yuv(path, [0, 8],
                                         consecutive_frames=8,
                                         width=112, height=112)
    pool = DecodePool(num_threads=2)
    try:
        out = np.empty_like(want)
        t = pool.submit_into(path, [0, 8], 8, out, pixfmt=PIX_YUV420)
        pool.wait(t, path)
        np.testing.assert_array_equal(out, want)
    finally:
        pool.close()


def test_write_y4m_420_roundtrip(tmp_path):
    """4:2:0 dataset files decode through both pixel paths, and the
    numpy/native backends stay bit-exact on them."""
    frames = _smooth_frames(n=10, h=64, w=96)
    path = os.path.join(str(tmp_path), "v420.y4m")
    write_y4m(path, frames, colorspace="420")
    dec = Y4MDecoder()
    assert dec.num_frames(path) == 10
    assert dec._parse_header(path)["subsample"] == 2
    rgb = dec.decode_clips(path, [0], 4, width=48, height=32)
    assert rgb.shape == (1, 4, 32, 48, 3)
    # the numpy yuv gather of the production (4:2:0) format must hold
    # regardless of whether the native library is built
    a = dec.decode_clips_yuv(path, [0, 3], 4, width=48, height=32)
    assert a.shape == (2, 4, packed_frame_bytes(32, 48))
    re_rgb = yuv420_to_rgb_numpy(a, 32, 48)
    got = dec.decode_clips(path, [0, 3], 4, width=48, height=32)
    assert np.abs(re_rgb.astype(int) - got.astype(int)).max() <= 24
    from rnb_tpu.decode.native import NativeY4MDecoder, native_available
    if native_available():
        b = NativeY4MDecoder(use_pool=False).decode_clips_yuv(
            path, [0, 3], 4, width=48, height=32)
        np.testing.assert_array_equal(a, b)
        c = NativeY4MDecoder(use_pool=False).decode_clips(
            path, [0, 3], 4, width=48, height=32)
        d = dec.decode_clips(path, [0, 3], 4, width=48, height=32)
        np.testing.assert_array_equal(c, d)


def test_write_y4m_rejects_bad_colorspace(tmp_path):
    with pytest.raises(ValueError):
        write_y4m(os.path.join(str(tmp_path), "x.y4m"),
                  np.zeros((1, 4, 4, 3), np.uint8), colorspace="422")
    with pytest.raises(ValueError):
        write_y4m(os.path.join(str(tmp_path), "x.y4m"),
                  np.zeros((1, 5, 4, 3), np.uint8), colorspace="420")


def test_synthetic_yuv_deterministic():
    dec = SyntheticDecoder()
    a = dec.decode_clips_yuv("synth://v1", [0, 10], 8, 112, 112)
    b = dec.decode_clips_yuv("synth://v1", [0, 10], 8, 112, 112)
    assert a.shape == (2, 8, packed_frame_bytes(112, 112))
    np.testing.assert_array_equal(a, b)
    c = dec.decode_clips_yuv("synth://v2", [0, 10], 8, 112, 112)
    assert not np.array_equal(a, c)


def test_device_converter_matches_numpy_oracle(tmp_path):
    import jax
    path = _make_y4m(tmp_path, frames=12, h=96, w=128)
    packed = Y4MDecoder().decode_clips_yuv(path, [0], 8, 112, 112)
    want = yuv420_to_rgb_numpy(packed, 112, 112)
    got = np.asarray(jax.jit(
        lambda x: yuv420_to_rgb_u8(x, 112, 112))(packed))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_normalize_yuv420_range():
    import jax.numpy as jnp
    from rnb_tpu.ops.yuv import normalize_yuv420
    rng = np.random.default_rng(0)
    packed = rng.integers(0, 256, (2, 4, packed_frame_bytes(112, 112)),
                          dtype=np.uint8)
    out = normalize_yuv420(packed, 112, 112)
    assert out.shape == (2, 4, 112, 112, 3)
    assert out.dtype == jnp.bfloat16
    f = np.asarray(out, dtype=np.float32)
    assert f.min() >= -1.0 and f.max() <= 1.0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("lead,hw", [((2, 4), (112, 112)),
                                     ((1, 3), (6, 10))])
def test_normalize_yuv420_is_the_reference_composition(lead, hw, dtype):
    """Jitted whole, the ingest equals conversion to u8 and the jnp
    normalization run one after the other, to the bit: at the
    production geometry and at one whose element count (540) is no
    multiple of 128, the case the Pallas form never took."""
    import jax
    import jax.numpy as jnp
    from rnb_tpu.ops.preprocess import normalize_u8
    from rnb_tpu.ops.yuv import normalize_yuv420
    h, w = hw
    dtype = getattr(jnp, dtype)
    packed = np.random.default_rng(3).integers(
        0, 256, lead + (packed_frame_bytes(h, w),), dtype=np.uint8)
    rgb = jax.jit(lambda x: yuv420_to_rgb_u8(x, h, w))(packed)
    assert rgb.dtype == jnp.uint8 and (rgb.size % 128 == 0) == (h == 112)
    want = normalize_u8(rgb, dtype=dtype)
    got = jax.jit(lambda x: normalize_yuv420(x, h, w, dtype))(packed)
    assert got.dtype == dtype and got.shape == lead + (h, w, 3)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_ragged_normalize_yuv420_zeroes_the_tail_and_matches_bucketed():
    """Rows past ``rows_valid`` enter the converter as zero bytes
    whatever the pool's tail held; the valid rows are the bucketed
    ingest's, to the bit, for every ``rows_valid`` of one program."""
    import jax
    from rnb_tpu.ops.ragged import ragged_normalize_yuv420
    from rnb_tpu.ops.yuv import normalize_yuv420
    pool = np.random.default_rng(5).integers(
        0, 256, (4, 2, packed_frame_bytes(112, 112)), dtype=np.uint8)
    ragged = jax.jit(lambda x, n: ragged_normalize_yuv420(x, n, 112, 112))
    bucketed = np.asarray(jax.jit(
        lambda x: normalize_yuv420(x, 112, 112))(pool), np.float32)
    pad = np.asarray(normalize_yuv420(np.zeros_like(pool[:1]), 112, 112),
                     np.float32)
    for valid in (0, 1, 3, 4):
        out = np.asarray(ragged(pool, valid), np.float32)
        np.testing.assert_array_equal(out[:valid], bucketed[:valid])
        np.testing.assert_array_equal(
            out[valid:], np.broadcast_to(pad, out[valid:].shape))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    from jax.experimental.compilation_cache import compilation_cache
    # what is compiled for a described chip is written to the
    # persistent cache and cannot be read back without one: off for
    # these tests, and on again for whatever this worker runs next
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", [8, 48])
def test_stage_program_has_no_kernel_between_planes_and_stem(rows,
                                                             one_chip):
    """The bucketed yuv420 stage program of R(2+1)D-34 on 32-frame
    clips, compiled for a described v5e (nothing runs): no Mosaic
    custom call, and no instruction that views the clip as a flat
    ``u8[M, 128]`` or ``bf16[M, 128]`` — the relayouts into and out of
    the Pallas normalization, which cost a sixth of the device's time
    (PERF.md section 6, PR 32). At the smallest and the largest
    bucket: the compiler lays the ingest out by the row count."""
    import re

    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.r2p1d import model as stage
    from rnb_tpu.models.r2p1d.network import R2Plus1DClassifier
    sizes, frames, hw = (3, 4, 6, 3), 32, stage.FRAME_HW
    apply = stage._shared_apply(1, 5, 400, sizes, pixel_path="yuv420")
    shapes = jax.eval_shape(
        lambda k: R2Plus1DClassifier(layer_sizes=sizes).init(
            k, np.zeros((1, 2, 14, 14, 3), np.float32), train=False),
        jax.random.key(0))
    variables = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), shapes)
    text = apply.lower(variables, jax.ShapeDtypeStruct(
        (rows, frames, packed_frame_bytes(hw, hw)), jnp.uint8,
        sharding=one_chip)).compile().as_text()
    assert "tpu_custom_call" not in text
    flat = rows * frames * hw * hw * 3 // 128
    views = re.findall(r"= (?:u8|bf16)\[%d,128\]" % flat, text)
    assert not views, views
    # the stem's convolution is there, and reads a clip of 3 colours
    assert re.search(r"bf16\[%d,%d,%d,%d,3\]" % (rows, frames, hw, hw),
                     text)


def test_loader_yuv_output_shape_and_pipeline_parity(tmp_path):
    """yuv420 loader ships packed u8; a start_index=1 runner configured
    for yuv420 accepts it and its logits track the rgb path's."""
    import jax
    from rnb_tpu.models.r2p1d.model import (R2P1DLoader, R2P1DRunner,
                                            FRAME_HW)
    shape = R2P1DLoader.output_shape_for(max_clips=15,
                                         consecutive_frames=8,
                                         pixel_path="yuv420")
    assert shape == ((15, 8, packed_frame_bytes(FRAME_HW, FRAME_HW)),)

    frames = _smooth_frames(n=40)
    path = os.path.join(str(tmp_path), "vid.y4m")
    write_y4m(path, frames)
    dev = jax.devices()[0]
    fixed = dict(num_clips_population=[2], weights=[1], max_clips=2,
                 num_warmups=0)
    loader = R2P1DLoader(dev, pixel_path="yuv420", **fixed)
    (pb,), _, tc = loader(None, path, _card(path))
    assert pb.data.shape == (2, 8, packed_frame_bytes(FRAME_HW,
                                                      FRAME_HW))
    assert str(pb.data.dtype) == "uint8"

    net = dict(start_index=1, end_index=5, num_warmups=0,
               layer_sizes=(1, 1, 1, 1), max_rows=2, num_classes=16)
    runner = R2P1DRunner(dev, pixel_path="yuv420", **net)
    (logits,), _, _ = runner((pb,), None, tc)
    assert logits.data.shape == (2, 16)

    # rgb reference prediction on the same video, same weights
    loader_rgb = R2P1DLoader(dev, **fixed)
    runner_rgb = R2P1DRunner(dev, **net)
    (pb2,), _, tc2 = loader_rgb(None, path, _card(path))
    (logits2,), _, _ = runner_rgb((pb2,), None, tc2)
    a = np.asarray(logits.data, dtype=np.float32)
    b = np.asarray(logits2.data, dtype=np.float32)
    # the pixel paths differ by <=1 chroma source pixel on smooth
    # content; logits must track closely (bf16 activations)
    np.testing.assert_allclose(a, b, atol=0.05 * np.abs(b).max())


def test_runner_yuv_requires_layer1():
    import jax
    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    with pytest.raises(ValueError):
        R2P1DRunner(jax.devices()[0], start_index=2, end_index=5,
                    num_warmups=0, layer_sizes=(1, 1, 1, 1),
                    pixel_path="yuv420")


def _card(video):
    from rnb_tpu.telemetry import TimeCard
    return TimeCard(0)
