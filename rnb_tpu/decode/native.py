"""ctypes bindings for the native C++ decoder (native/decode.cpp).

The native library is the performance path for .y4m decode — a fused
probe/decode/convert/resize in C++ with an internal worker pool, the
TPU-native replacement for the role NVVL's GPU decoder played in the
reference (SURVEY.md §2.2 N2).  If the shared library has not been
built (``make -C native``) the pure-numpy
:class:`~rnb_tpu.decode.Y4MDecoder` carries the same contract on the
CPU harness; a loader placed on a TPU refuses to start without it
(:func:`require_native`).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional

import numpy as np

from rnb_tpu.decode import (DEFAULT_HEIGHT, DEFAULT_WIDTH, VideoDecoder)
from rnb_tpu.faults import CorruptVideoError, TransientDecodeError
from rnb_tpu.ops.dct import coeffs_from_elems, dct_frame_elems

_ERR_MSGS = {
    -1: "I/O error",
    -2: "not a y4m/mjpeg file / malformed stream (the dct path also "
        "needs an MJPEG container)",
    -3: "unsupported colourspace/sampling/geometry for this pixel "
        "format",
    -4: "bad argument",
    -5: "DCT spectrum exceeds the wire coefficient budget — raise "
        "dct_coeffs_per_frame or use pixel_path yuv420",
}

#: pixel formats of the native decoder (native/decode.cpp kPix*)
PIX_RGB = 0       # fused convert+resize -> (n, F, H, W, 3) u8
PIX_YUV420 = 1    # gather-only packed planes -> (n, F, H*W*3//2) u8
PIX_DCT = 2       # dequantized coefficients -> (n, F, elems) int16

_lib = None
_lib_checked = False
_lib_lock = threading.Lock()


def _lib_path() -> str:
    override = os.environ.get("RNB_NATIVE_LIB")
    if override:
        return override
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, "native", "build", "librnb_decode.so")


def load_native():
    """-> the loaded ctypes library, or None if unavailable/disabled."""
    global _lib, _lib_checked
    if os.environ.get("RNB_DISABLE_NATIVE"):
        return None
    with _lib_lock:
        if _lib_checked:
            return _lib
        _lib_checked = True
        path = _lib_path()
        if not os.path.exists(path):
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        # a stale prebuilt library missing newer exports must degrade
        # to the numpy backend like a missing library, not crash
        # (rnb_video_probe marks mjpeg-capable builds)
        for sym in ("rnb_y4m_probe", "rnb_y4m_decode_clips",
                    "rnb_y4m_decode_clips_fmt", "rnb_pool_create",
                    "rnb_pool_destroy", "rnb_pool_submit",
                    "rnb_pool_submit_fmt", "rnb_pool_wait",
                    "rnb_pool_peek", "rnb_video_probe",
                    "rnb_y4m_decode_clips_dct", "rnb_pool_submit_dct",
                    "rnb_pool_stats"):
            if not hasattr(lib, sym):
                return None
        lib.rnb_y4m_probe.restype = ctypes.c_int
        lib.rnb_y4m_probe.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_longlong)]
        lib.rnb_video_probe.restype = ctypes.c_int
        lib.rnb_video_probe.argtypes = lib.rnb_y4m_probe.argtypes
        lib.rnb_y4m_decode_clips.restype = ctypes.c_int
        lib.rnb_y4m_decode_clips.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p]
        lib.rnb_pool_create.restype = ctypes.c_void_p
        lib.rnb_pool_create.argtypes = [ctypes.c_int]
        lib.rnb_pool_destroy.restype = None
        lib.rnb_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.rnb_pool_submit.restype = ctypes.c_longlong
        lib.rnb_pool_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p]
        lib.rnb_pool_wait.restype = ctypes.c_int
        lib.rnb_pool_wait.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.rnb_pool_peek.restype = ctypes.c_int
        lib.rnb_pool_peek.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.rnb_pool_stats.restype = ctypes.c_int
        lib.rnb_pool_stats.argtypes = [
            ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_longlong)] * 2
        lib.rnb_y4m_decode_clips_fmt.restype = ctypes.c_int
        lib.rnb_y4m_decode_clips_fmt.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_char_p]
        lib.rnb_pool_submit_fmt.restype = ctypes.c_longlong
        lib.rnb_pool_submit_fmt.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_char_p]
        lib.rnb_y4m_decode_clips_dct.restype = ctypes.c_int
        lib.rnb_y4m_decode_clips_dct.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p]
        lib.rnb_pool_submit_dct.restype = ctypes.c_longlong
        lib.rnb_pool_submit_dct.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native() is not None


def require_native(platform: str) -> None:
    """Raise when a loader on ``platform`` would decode without the
    native library nobody turned off. On the CPU harness the numpy/PIL
    decoders carry the same contract and tests rely on that; on a TPU
    they are references, and a run that quietly measured them is worse
    than one that stops (``RNB_DISABLE_NATIVE=1`` is the explicit way
    to ask for them)."""
    if (platform == "tpu" and not os.environ.get("RNB_DISABLE_NATIVE")
            and not native_available()):
        raise RuntimeError(
            "the native decode library %s is missing or does not load; "
            "build it (make -C native) before serving from a TPU, or "
            "set RNB_DISABLE_NATIVE=1 to run the Python decoders on "
            "purpose" % _lib_path())


def default_decode_threads() -> int:
    """The one decode-pool sizing rule: ``RNB_DECODE_THREADS`` env
    override, else min(8, cores). Shared by the native
    :class:`DecodePool` and the loaders' non-native Python fallback
    pool (rnb_tpu/models/r2p1d/model.py ``fallback_decode_threads``),
    so the two backends degrade with identical parallelism."""
    return int(os.environ.get("RNB_DECODE_THREADS",
                              min(8, os.cpu_count() or 1)))


def _check(rc: int, path: str) -> None:
    """Raise the native error code as a *classified* exception
    (rnb_tpu.faults): -1 (read failed; may succeed on retry) is
    transient, -2/-3 (malformed/unsupported stream; retrying cannot
    help) are permanent. Both subclass ValueError, so pre-containment
    callers are unaffected. -4 (bad argument) stays a plain ValueError
    — a caller bug should abort, not dead-letter a request."""
    if rc == 0:
        return
    msg = ("native y4m decode of %r failed: %s"
           % (path, _ERR_MSGS.get(rc, "error %d" % rc)))
    if rc == -1:
        raise TransientDecodeError(msg)
    if rc in (-2, -3, -5):
        # -5 (over-budget spectrum) is permanent: re-decoding cannot
        # shrink a frame's nonzero coefficient count
        raise CorruptVideoError(msg)
    raise ValueError(msg)


class DecodePool:
    """Worker pool over the native library; submit/wait across videos.

    One pool is shared per process (``DecodePool.shared()``); the
    loader stage uses it to overlap decode of queued videos the way the
    reference's NVVL loader overlapped NVDEC work with inference
    (reference README.md:46-110).
    """

    GUARDED_BY = {"_pending": "_pending_lock"}

    UNGUARDED_OK = {
        "_pool": "set in __init__, cleared only by close() at "
                 "teardown after in-flight tickets drain",
    }

    def __init__(self, num_threads: Optional[int] = None):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native decode library not built; run "
                               "`make -C native`")
        if num_threads is None:
            num_threads = default_decode_threads()
        self._lib = lib
        self._pool = lib.rnb_pool_create(int(num_threads))
        self.num_threads = int(num_threads)
        # ticket -> (out, starts): keeps the buffers a worker thread
        # writes into alive until wait() retires the job, even if the
        # caller drops its references mid-flight
        self._pending = {}
        self._pending_lock = threading.Lock()

    _shared = None
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls) -> "DecodePool":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            return cls._shared

    def submit(self, path: str, clip_starts: List[int],
               consecutive_frames: int, width: int, height: int):
        """-> (ticket, out_array); pass ticket to :meth:`wait`."""
        out = np.empty((len(clip_starts), consecutive_frames, height,
                        width, 3), dtype=np.uint8)
        ticket = self.submit_into(path, clip_starts, consecutive_frames,
                                  out)
        return ticket, out

    def submit_into(self, path: str, clip_starts: List[int],
                    consecutive_frames: int, out: np.ndarray,
                    pixfmt: int = PIX_RGB,
                    width: int = DEFAULT_WIDTH,
                    height: int = DEFAULT_HEIGHT) -> int:
        """Decode into a caller-provided C-contiguous view — uint8
        (clips, frames, H, W, 3) for PIX_RGB, uint8 (clips, frames,
        H*W*3//2) packed planes for PIX_YUV420, int16 (clips, frames,
        num_blocks + 2*C) coefficient rows for PIX_DCT (geometry comes
        from width/height; a packed length alone is ambiguous, and the
        dct coefficient budget C is recovered from the trailing axis).
        Lets one logical decode fan out over the pool by submitting
        chunks that target disjoint slices of a single batch buffer."""
        want_dtype = np.int16 if pixfmt == PIX_DCT else np.uint8
        if out.dtype != want_dtype or not out.flags["C_CONTIGUOUS"] \
                or out.shape[:2] != (len(clip_starts),
                                     consecutive_frames):
            raise ValueError("bad output buffer %r/%s for %d clips x %d "
                             "frames" % (out.shape, out.dtype,
                                         len(clip_starts),
                                         consecutive_frames))
        dct_coeffs = 0
        if pixfmt == PIX_RGB:
            if out.ndim != 5 or out.shape[4] != 3:
                raise ValueError("PIX_RGB wants (clips, frames, H, W, 3)"
                                 ", got %r" % (out.shape,))
            out_w, out_h = out.shape[3], out.shape[2]
        elif pixfmt == PIX_YUV420:
            if out.ndim != 3 or out.shape[2] != height * width * 3 // 2:
                raise ValueError(
                    "PIX_YUV420 wants (clips, frames, %d) for %dx%d, "
                    "got %r" % (height * width * 3 // 2, height, width,
                                out.shape))
            out_w, out_h = width, height
        elif pixfmt == PIX_DCT:
            if out.ndim != 3:
                raise ValueError("PIX_DCT wants (clips, frames, elems) "
                                 "int16, got %r" % (out.shape,))
            dct_coeffs = coeffs_from_elems(height, width, out.shape[2])
            out_w, out_h = width, height
        else:
            raise ValueError("unknown pixfmt %r" % (pixfmt,))
        starts = (ctypes.c_longlong * len(clip_starts))(*clip_starts)
        if pixfmt == PIX_DCT:
            ticket = self._lib.rnb_pool_submit_dct(
                self._pool, path.encode(), starts, len(clip_starts),
                consecutive_frames, out_w, out_h, dct_coeffs,
                out.ctypes.data_as(ctypes.c_void_p))
        else:
            ticket = self._lib.rnb_pool_submit_fmt(
                self._pool, path.encode(), starts, len(clip_starts),
                consecutive_frames, out_w, out_h, pixfmt,
                out.ctypes.data_as(ctypes.c_char_p))
        if ticket <= 0:
            raise RuntimeError("native pool rejected submit for %r" % path)
        with self._pending_lock:
            self._pending[ticket] = (out, starts)
        return ticket

    def peek(self, ticket: int) -> bool:
        """Non-blocking: True when the ticket's decode has finished.
        Does not retire the ticket — pair with :meth:`wait`."""
        with self._pending_lock:
            if ticket not in self._pending:
                raise ValueError("unknown or already-waited ticket %r"
                                 % (ticket,))
        return bool(self._lib.rnb_pool_peek(self._pool, ticket))

    def wait(self, ticket: int, path: str = "<submitted>") -> None:
        # claim the ticket atomically before touching the native side:
        # rnb_pool_wait blocks forever on unknown/retired tickets, and a
        # check-then-act race between two waiters would send the loser
        # into exactly that hang — the loser must fail fast here instead
        with self._pending_lock:
            buffers = self._pending.pop(ticket, None)
            if buffers is None:
                raise ValueError("unknown or already-waited ticket %r"
                                 % (ticket,))
        # `buffers` pins (out, starts) until the native workers finish
        _check(self._lib.rnb_pool_wait(self._pool, ticket), path)
        del buffers

    def stats(self) -> dict:
        """What the workers did since the pool was made:
        ``busy_s`` (seconds inside the decoder, summed over the
        workers) and ``frames`` of the jobs that succeeded. A reader
        takes two and subtracts."""
        busy, frames = ctypes.c_longlong(), ctypes.c_longlong()
        _check(self._lib.rnb_pool_stats(
            self._pool, ctypes.byref(busy), ctypes.byref(frames)),
            "<pool stats>")
        return {"busy_s": busy.value / 1e9, "frames": frames.value}

    @classmethod
    def shared_stats(cls) -> dict:
        """:meth:`stats` of the shared pool; zeros while the process
        has made none (no native library, or no decode yet)."""
        with cls._shared_lock:
            pool = cls._shared
        if pool is None:
            return {"busy_s": 0.0, "frames": 0}
        return pool.stats()

    def close(self) -> None:
        if self._pool:
            self._lib.rnb_pool_destroy(self._pool)
            self._pool = None


#: one logical decode fans out over the shared pool only past this many
#: clips — tiny requests aren't worth the submit/wait round trip
POOL_SPLIT_MIN_CLIPS = 4


class NativeY4MDecoder(VideoDecoder):
    """VideoDecoder backed by the C++ library.

    Despite the historical name this handles BOTH containers — the
    library sniffs y4m vs MJPEG from the magic bytes, so .mjpg files
    (self-contained baseline-JPEG decode, native/decode.cpp) ride the
    same entry points, pool and pixel formats.

    Single-clip requests decode synchronously on the calling thread;
    larger requests split their clip list into chunks fanned out over
    the process-shared :class:`DecodePool`, each chunk writing a
    disjoint slice of the one output batch — the intra-video
    parallelism NVVL got from async NVDEC (reference README.md:46-110).
    """

    def __init__(self, use_pool: bool = True):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native decode library not built; run "
                               "`make -C native`")
        self._lib = lib
        self._use_pool = use_pool and not os.environ.get(
            "RNB_DECODE_NO_POOL")
        self._count_cache = {}

    def num_frames(self, video: str) -> int:
        if video not in self._count_cache:
            n = ctypes.c_longlong()
            _check(self._lib.rnb_video_probe(video.encode(), None, None,
                                             ctypes.byref(n)), video)
            self._count_cache[video] = int(n.value)
        return self._count_cache[video]

    def _pool_fanout(self, video: str, clip_starts: List[int],
                     consecutive_frames: int, out: np.ndarray,
                     pixfmt: int, width: int, height: int) -> np.ndarray:
        """Split one logical decode into per-chunk pool tickets writing
        disjoint slices of ``out``; retire EVERY submitted ticket even
        if one fails — un-waited tickets would pin the batch buffer in
        _pending and leak done-map entries in the native pool."""
        pool = DecodePool.shared()
        chunk = max(1, -(-len(clip_starts) // pool.num_threads))
        tickets = []
        first_error = None
        try:
            for lo in range(0, len(clip_starts), chunk):
                hi = min(lo + chunk, len(clip_starts))
                tickets.append(pool.submit_into(
                    video, clip_starts[lo:hi], consecutive_frames,
                    out[lo:hi], pixfmt=pixfmt, width=width,
                    height=height))
        finally:
            for ticket in tickets:
                try:
                    pool.wait(ticket, video)
                except ValueError as e:
                    first_error = first_error or e
        if first_error is not None:
            raise first_error
        return out

    def decode_clips(self, video: str, clip_starts: List[int],
                     consecutive_frames: int = 8,
                     width: int = DEFAULT_WIDTH,
                     height: int = DEFAULT_HEIGHT) -> np.ndarray:
        out = np.empty((len(clip_starts), consecutive_frames, height,
                        width, 3), dtype=np.uint8)
        if self._use_pool and len(clip_starts) >= POOL_SPLIT_MIN_CLIPS:
            return self._pool_fanout(video, clip_starts,
                                     consecutive_frames, out, PIX_RGB,
                                     width, height)
        starts = (ctypes.c_longlong * len(clip_starts))(*clip_starts)
        _check(self._lib.rnb_y4m_decode_clips(
            video.encode(), starts, len(clip_starts), consecutive_frames,
            width, height, out.ctypes.data_as(ctypes.c_char_p)), video)
        return out

    def decode_clips_yuv(self, video: str, clip_starts: List[int],
                         consecutive_frames: int = 8,
                         width: int = DEFAULT_WIDTH,
                         height: int = DEFAULT_HEIGHT) -> np.ndarray:
        if width % 2 or height % 2:
            raise ValueError("packed 4:2:0 needs even geometry")
        out = np.empty((len(clip_starts), consecutive_frames,
                        height * width * 3 // 2), dtype=np.uint8)
        if self._use_pool and len(clip_starts) >= POOL_SPLIT_MIN_CLIPS:
            return self._pool_fanout(video, clip_starts,
                                     consecutive_frames, out,
                                     PIX_YUV420, width, height)
        starts = (ctypes.c_longlong * len(clip_starts))(*clip_starts)
        _check(self._lib.rnb_y4m_decode_clips_fmt(
            video.encode(), starts, len(clip_starts), consecutive_frames,
            width, height, PIX_YUV420,
            out.ctypes.data_as(ctypes.c_char_p)), video)
        return out

    def decode_clips_dct(self, video: str, clip_starts: List[int],
                         consecutive_frames: int = 8,
                         width: int = DEFAULT_WIDTH,
                         height: int = DEFAULT_HEIGHT,
                         coeffs=None) -> np.ndarray:
        """Packed dequantized-coefficient rows (rnb_tpu/ops/dct.py
        wire format) straight from the C++ entropy decoder — the
        per-pixel IDCT/convert work never runs on the host."""
        elems = dct_frame_elems(height, width, coeffs)
        out = np.empty((len(clip_starts), consecutive_frames, elems),
                       dtype=np.int16)
        if self._use_pool and len(clip_starts) >= POOL_SPLIT_MIN_CLIPS:
            return self._pool_fanout(video, clip_starts,
                                     consecutive_frames, out, PIX_DCT,
                                     width, height)
        starts = (ctypes.c_longlong * len(clip_starts))(*clip_starts)
        _check(self._lib.rnb_y4m_decode_clips_dct(
            video.encode(), starts, len(clip_starts),
            consecutive_frames, width, height,
            coeffs_from_elems(height, width, elems),
            out.ctypes.data_as(ctypes.c_void_p)), video)
        return out
