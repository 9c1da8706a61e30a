"""R(2+1)D pipeline stages: loader, partial-net runner, fused
single-step, logit aggregator, path iterator, Large/Small router.

Capability parity with the reference stage library
(models/r2p1d/model.py:1-296), re-designed for the TPU runtime:

* the loader decodes on the host (no NVDEC on TPU; see rnb_tpu.decode)
  and immediately re-homes padded uint8 clips onto its TPU core where a
  jitted preprocess casts/normalizes to bfloat16 NDHWC — decode cost on
  host threads, math on device;
* every stage computes on static-shape batches with valid-row counts —
  one max shape per topology, or a small fixed set of row buckets when
  ``row_buckets`` is configured — so XLA compiles a bounded number of
  executables, never per-request shapes;
* jitted appliers and device-resident weights are cached per
  (layer-range, device) so N replicas on one device share one
  executable and one parameter copy.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Dict, Optional, Tuple

import numpy as np

from rnb_tpu import hloscopes, trace
from rnb_tpu.autotune import BatchController
from rnb_tpu.cache import content_key
from rnb_tpu.compilestats import SignatureTracker
from rnb_tpu.decode import get_decoder
from rnb_tpu.devices import DeviceSpec
from rnb_tpu.decode.native import (DecodePool, NativeY4MDecoder, PIX_DCT,
                                   PIX_RGB, PIX_YUV420,
                                   default_decode_threads,
                                   native_available, require_native)
from rnb_tpu.faults import (FATAL, TRANSIENT, TransientDecodeError,
                            classify_error, fault_reason)
from rnb_tpu.health import expired as _deadline_expired
from rnb_tpu.models.r2p1d import checkpoint as ckpt
from rnb_tpu.models.r2p1d.network import (KINETICS_CLASSES,
                                          LAYER_INPUT_SHAPES, NUM_LAYERS,
                                          R2Plus1DClassifier,
                                          R18_LAYER_SIZES)
from rnb_tpu.models.r2p1d.sampler import R2P1DSampler
from rnb_tpu.ops.dct import dct_frame_elems, default_dct_coeffs
from rnb_tpu.ops.ragged import resolve_pool_rows, segment_offsets_of
from rnb_tpu.ops.yuv import packed_frame_bytes
from rnb_tpu.selector import QueueSelector
from rnb_tpu.stage import (PadCounter, PaddedBatch, RaggedBatch,
                           StageModel, normalize_row_buckets,
                           note_emission_accounting)
from rnb_tpu.staging import StagingPool, TransferWorker
from rnb_tpu.telemetry import TimeCard, TimeCardList
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy
from rnb_tpu import video_path_provider
from rnb_tpu.video_path_provider import VideoPathIterator

MAX_CLIPS = 15
CONSECUTIVE_FRAMES = 8
FRAME_HW = 112
NUM_WARMUPS = 3  # reference warm-up convention (models/r2p1d/model.py:65-71)

_cache_lock = threading.Lock()
_apply_cache: Dict[tuple, Any] = {}
_params_cache: Dict[tuple, Any] = {}
_preprocess_cache: Dict[tuple, Any] = {}


def _record_clamped(card, key: str, at: float) -> None:
    """Record a phase-refinement stamp (rnb_tpu.trace) no earlier
    than the card's latest stamp: each card's stamps must stay
    time-ordered or attribution gaps go negative — e.g. a coalesced
    follower can be swallowed AFTER its leader's decode completed, so
    its decode phase legitimately clamps to zero."""
    if card.timings:
        last = next(reversed(card.timings.values()))
        if at < last:
            at = last
    card.record(key, at=at)


def _resolve(device):
    """Accept a DeviceSpec or a raw jax.Device."""
    return device.resolve() if hasattr(device, "resolve") else device


#: shared bucket validation (rnb_tpu.stage) — loader and Batcher must
#: reject a typo'd bucket set identically
_normalize_row_buckets = normalize_row_buckets


def default_ragged_chunk(pool_rows: int) -> int:
    """Auto row-chunk for the ragged applier's dynamic grid: the
    largest divisor of the pool capacity no bigger than a third of it
    (so a typical partial pool skips real work), floored at 1. 15 ->
    5, 12 -> 4, 2 -> 1."""
    pool_rows = int(pool_rows)
    cap = max(1, pool_rows // 3)
    for d in range(cap, 0, -1):
        if pool_rows % d == 0:
            return d
    return 1


def _shared_apply(start: int, end: int, num_classes: int,
                  layer_sizes: tuple, factored_shortcut: bool = False,
                  pixel_path: str = "rgb", ragged: bool = False,
                  ragged_chunk: int = 0):
    """One jitted inference applier shared by every replica of a range.

    ``pixel_path="yuv420"`` (layer-1 stages only) prepends the fused
    ingest — packed 4:2:0 planes -> chroma upsample -> BT.601 ->
    normalize (rnb_tpu/ops/yuv.py) — inside the same jit, all of it
    plain jnp, so XLA makes one producer of it and lays its result out
    for the first convolution (no Pallas kernel stands between them).
    Whatever stands in front of layer 1 runs under the named scope
    ``ingest``; the network opens its own (``stem``, ``stage2`` ...
    ``stage5``, ``head``: network.R2Plus1DNet).

    ``ragged`` swaps the contract for the ragged row-pool one
    (rnb_tpu/ops/ragged.py): the applier takes the flat pool plus a
    *traced* ``rows_valid`` scalar and compiles exactly ONCE for any
    batch composition — for yuv420 the fused ingest masks the pool
    tail at the u8 level first. With ``ragged_chunk`` > 0 (must
    divide the pool capacity) the network body runs as a dynamic grid
    over fixed ``ragged_chunk``-row tiles: a ``fori_loop`` whose trip
    count is ``ceil(rows_valid / chunk)``, so network FLOPs scale
    with the valid rows (rounded up to one tile) instead of the pool
    capacity — the CPU/compile-once analog of the TPU kernel's
    ``pl.when`` grid skip, and bit-identical per row (tiles of any
    size produce the same per-row outputs; asserted in
    tests/test_ragged.py). ``ragged_chunk=0`` applies the whole pool
    in one call (preferable on real TPUs, where the MXU wants the
    large batch; the RGB loader's ragged Pallas preprocess and the
    dct ingest's kernel skip pad arithmetic, the yuv420 ingest, plain
    jnp inside this jit, masks the pads at the u8 level).
    """
    key = (start, end, num_classes, layer_sizes, factored_shortcut,
           pixel_path, bool(ragged), int(ragged_chunk))
    with _cache_lock:
        fn = _apply_cache.get(key)
        if fn is None:
            import jax
            model = R2Plus1DClassifier(start=start, end=end,
                                       num_classes=num_classes,
                                       layer_sizes=layer_sizes,
                                       factored_shortcut=factored_shortcut)

            if ragged:
                if pixel_path == "yuv420":
                    from rnb_tpu.ops.ragged import ragged_normalize_yuv420

                    def ingest(x, rows_valid):
                        return ragged_normalize_yuv420(
                            x, rows_valid, FRAME_HW, FRAME_HW)
                elif pixel_path == "dct":
                    from rnb_tpu.ops.dct import ragged_normalize_dct

                    def ingest(x, rows_valid):
                        return ragged_normalize_dct(
                            x, rows_valid, FRAME_HW, FRAME_HW)
                else:
                    # rgb/mid-pipeline pools arrive already normalized
                    # and masked by the producing loader's ragged
                    # preprocess
                    def ingest(x, rows_valid):
                        del rows_valid
                        return x
                chunk = int(ragged_chunk)

                def apply(variables, x, rows_valid):
                    import jax.numpy as jnp
                    from jax import lax
                    with jax.named_scope("ingest"):
                        xin = ingest(x, rows_valid)
                    if chunk <= 0 or chunk >= xin.shape[0]:
                        return model.apply(variables, xin, train=False)

                    def tile(i):
                        part = lax.dynamic_slice_in_dim(
                            xin, i * chunk, chunk, axis=0)
                        return model.apply(variables, part, train=False)

                    # tile 0 is computed unconditionally (every real
                    # emission carries >= 1 valid row) — it also fixes
                    # the output row shape/dtype without re-tracing
                    first = tile(0)
                    out = lax.dynamic_update_slice_in_dim(
                        jnp.zeros((xin.shape[0],) + first.shape[1:],
                                  first.dtype), first, 0, axis=0)
                    num_tiles = jnp.minimum(
                        (rows_valid + chunk - 1) // chunk,
                        xin.shape[0] // chunk)

                    def body(i, acc):
                        return lax.dynamic_update_slice_in_dim(
                            acc, tile(i), i * chunk, axis=0)

                    return lax.fori_loop(1, num_tiles, body, out)
            elif pixel_path == "yuv420":
                from rnb_tpu.ops.yuv import normalize_yuv420

                def apply(variables, x):
                    with jax.named_scope("ingest"):
                        x = normalize_yuv420(x, FRAME_HW, FRAME_HW)
                    return model.apply(variables, x, train=False)
            elif pixel_path == "dct":
                from rnb_tpu.ops.dct import normalize_dct

                def apply(variables, x):
                    with jax.named_scope("ingest"):
                        x = normalize_dct(x, FRAME_HW, FRAME_HW)
                    return model.apply(variables, x, train=False)
            else:
                def apply(variables, x):
                    return model.apply(variables, x, train=False)

            fn = jax.jit(apply)
            _apply_cache[key] = fn
        return fn


def _shared_params(start: int, end: int, num_classes: int,
                   layer_sizes: tuple, ckpt_path: Optional[str], device,
                   factored_shortcut: bool = False):
    """Device-resident filtered weights, one copy per (range, device)."""
    import jax
    key = (start, end, num_classes, layer_sizes, ckpt_path, id(device),
           factored_shortcut)
    with _cache_lock:
        params = _params_cache.get(key)
        if params is None:
            with trace.span(trace.name("setup.s%d.weights",
                                       trace.building_step())):
                variables = ckpt.load_or_init(
                    start, end, num_classes, layer_sizes, ckpt_path,
                    factored_shortcut=factored_shortcut)
                params = jax.device_put(variables, device)
            _params_cache[key] = params
        return params


def _shared_preprocess(device):
    """Jitted uint8 -> normalized bfloat16 cast, one per device."""
    key = id(device)
    with _cache_lock:
        fn = _preprocess_cache.get(key)
        if fn is None:
            import jax
            from rnb_tpu.models.r2p1d.network import normalize_u8
            fn = jax.jit(normalize_u8)
            _preprocess_cache[key] = fn
        return fn


def _shared_ragged_preprocess(device):
    """Jitted ragged uint8 pool -> normalized bfloat16, one per
    device: the ragged forward primitive (rnb_tpu/ops/ragged.py) with
    a *traced* rows_valid scalar — one executable serves every batch
    composition, and rows past rows_valid cost no arithmetic on the
    TPU grid-skip path."""
    key = ("ragged", id(device))
    with _cache_lock:
        fn = _preprocess_cache.get(key)
        if fn is None:
            import jax
            from rnb_tpu.ops.ragged import ragged_normalize_u8

            def preprocess(pool, rows_valid):
                with jax.named_scope("ingest"):
                    return ragged_normalize_u8(pool, rows_valid)

            fn = jax.jit(preprocess)
            _preprocess_cache[key] = fn
        return fn


#: ceiling on one fallback-pool decode's wait: far above any real
#: decode (tiny y4m/MJPEG clips decode in milliseconds), so hitting it
#: is a liveness verdict on the worker thread, not a slow file
FALLBACK_DECODE_TIMEOUT_S = 120.0


class _DecodeHandle:
    """In-flight decode work submitted ahead of its turn.

    Mirrors what NVVL's async ``loadfile`` represented (reference
    README.md:46-110): decode has been kicked off, ``wait()`` blocks
    until the clip batch is materialized in ``out``.

    Cache/coalescing variants (rnb_tpu.cache): a ``cached`` handle
    carries a device-resident hit and owns no decode work at all; a
    ``leader`` handle is a coalesced follower that shares another
    in-flight request's decode. A failed ``wait()`` remembers its
    error and re-raises it on every later wait, so a follower parked
    on a failed leader observes the same classified failure instead
    of silently reading a garbage buffer.
    """

    __slots__ = ("out", "n", "pool", "tickets", "future", "cached",
                 "leader", "key", "error", "slot", "row0",
                 "gather_plan", "feature_plan")

    def __init__(self, out, n, pool=None, tickets=None, future=None,
                 cached=None, leader=None, key=None, slot=None,
                 row0=0):
        self.out = out          # uint8 (n, F, H, W, 3), filled async
        self.n = n              # valid clip count
        self.pool = pool        # the DecodePool the tickets belong to
        self.tickets = tickets  # native DecodePool tickets, or None
        self.future = future    # fallback executor future, or None
        self.cached = cached    # CacheEntry on a cache hit, or None
        self.leader = leader    # coalesced: the leader's handle, or None
        self.key = key          # cache key of this decode, or None
        self.error = None       # sticky decode failure (see class doc)
        self.slot = slot        # StagingSlot the decode targets, or None
        self.row0 = row0        # first row of this decode in the slot
        self.gather_plan = None  # pinned paged-cache hit (rnb_tpu.pager)
        self.feature_plan = None  # pinned feature-page hit, or None

    def wait(self, video: str = "<video>") -> None:
        if self.leader is not None:
            self.leader.wait(video)
            self.out = self.leader.out
            return
        if self.error is not None:
            raise self.error
        try:
            if self.tickets:
                first_error = None
                for ticket in self.tickets:
                    try:
                        self.pool.wait(ticket, video)
                    except ValueError as e:
                        first_error = first_error or e
                self.tickets = None
                if first_error is not None:
                    raise first_error
            if self.future is not None:
                # bounded wait + liveness verdict (the RNB-H009
                # discipline): a wedged fallback-pool decode thread
                # dead-letters ONE request as a classified transient
                # instead of hanging the stage — and, behind it, the
                # whole replica lane — forever
                try:
                    self.future.result(
                        timeout=FALLBACK_DECODE_TIMEOUT_S)
                except FuturesTimeout:
                    raise TransientDecodeError(
                        "fallback decode of %s unresponsive for %.0fs"
                        % (video, FALLBACK_DECODE_TIMEOUT_S)) from None
                self.future = None
        except Exception as e:
            self.error = e
            raise

    @property
    def ready(self) -> bool:
        """Non-blocking: has the decode finished? (wait() still
        required to retire tickets / surface errors.)"""
        if self.leader is not None:
            return self.leader.ready
        if self.tickets:
            return all(self.pool.peek(t) for t in self.tickets)
        if self.future is not None:
            return self.future.done()
        return True


class R2P1DLoader(StageModel):
    """Decode stage: video path/id -> padded bf16 clip batch on device.

    Reference equivalent: R2P1DLoader over NVVL
    (models/r2p1d/model.py:116-158). Samples 1..max_clips clips, decodes
    them on the host, pads to the static max shape, transfers once to
    the stage device and normalizes there. Stamps ``num_clips`` on the
    TimeCard for content-aware routing.

    **Prefetch** (NVVL parity, reference README.md:46-110): with a
    ``prefetch`` depth configured, the stage exposes ``submit()`` /
    ``complete()`` and the executor kicks off decode of request N+1..N+k
    while request N's device work runs — native-pool tickets for .y4m
    files, a small thread pool for the numpy/synthetic backends. The
    TimeCard decode span (``inference{i}``) then measures only the
    *residual* wait, which is exactly the overlap being bought.
    """

    #: transfer_async moves ``device_put`` to a dedicated worker thread
    #: between emissions — only meaningful for a stage that emits
    #: asynchronously of its model call (the fusing loader); the plain
    #: loader's complete() contract is synchronous
    SUPPORTS_TRANSFER_ASYNC = False

    #: emissions can ship as a flat row pool at ONE compiled shape
    #: with a rows_valid count + per-request segment offsets instead
    #: of padding to buckets (root 'ragged' config key; the launcher
    #: injects the kwargs — rnb_tpu.ops.ragged)
    SUPPORTS_RAGGED = True

    #: with the root 'pager' config key the clip cache's blob storage
    #: becomes page-table entries in a pager arena and hits gather on
    #: device with zero host bytes (rnb_tpu.pager; enable_pager below)
    SUPPORTS_PAGER = True

    def __init__(self, device, max_clips: int = MAX_CLIPS,
                 consecutive_frames: int = CONSECUTIVE_FRAMES,
                 num_clips_population=None, weights=None,
                 num_warmups: int = NUM_WARMUPS,
                 raw_output: bool = False,
                 row_buckets=None, prefetch: int = 0,
                 pixel_path: str = "rgb", cache_mb: float = 0,
                 staging_slots=None, transfer_async: bool = False,
                 fallback_decode_threads=None,
                 ragged: bool = False, ragged_pool_rows=None,
                 dct_coeffs_per_frame=None,
                 **kwargs):
        super().__init__(device)
        import jax
        self._jax_device = _resolve(device)
        require_native(self._jax_device.platform)
        #: raw mode emits the padded uint8 batch itself (half the bytes
        #: of bf16 on the wire) for consumers that normalize on their
        #: own mesh, e.g. R2P1DMeshRunner
        self.raw_output = bool(raw_output)
        # "yuv420": host decode stops at packed output-res 4:2:0 planes
        # (pure gathers, 1.5 bytes/pixel on the wire); the consuming
        # network stage fuses upsample+BT.601+normalize into its jit
        # (rnb_tpu/ops/yuv.py). Round 5's single host core was the
        # throughput ceiling (2026-07, previous transport, not
        # reproduced), so moving the colourspace arithmetic on-device
        # lifted end-to-end throughput directly.
        # "dct": the MJPEG decode stops at entropy-decoded, dequantized
        # DCT coefficients shipped as packed sparse int16 rows
        # (rnb_tpu/ops/dct.py — ~0.5x the yuv420 wire bytes at the
        # default budget); IDCT + chroma upsample + BT.601 + normalize
        # run fused on-device ahead of conv1, deleting the host's
        # remaining per-pixel work.
        if pixel_path not in ("rgb", "yuv420", "dct"):
            raise ValueError("pixel_path must be 'rgb', 'yuv420' or "
                             "'dct', got %r" % (pixel_path,))
        # raw_output + yuv420 composes: the loader ships packed planes
        # and the mesh consumer's sharded program runs the fused yuv
        # ingest (configure the SAME pixel_path on both stages)
        self.pixel_path = pixel_path
        self.dct_coeffs = None
        if pixel_path == "dct":
            if raw_output:
                raise ValueError(
                    "pixel_path='dct' cannot combine with raw_output: "
                    "mesh consumers ingest raw pixel batches, not "
                    "packed coefficient rows")
            self.dct_coeffs = (int(dct_coeffs_per_frame)
                               if dct_coeffs_per_frame is not None
                               else default_dct_coeffs(FRAME_HW,
                                                       FRAME_HW))
            if self.dct_coeffs < 1:
                raise ValueError("dct_coeffs_per_frame must be >= 1, "
                                 "got %r" % (dct_coeffs_per_frame,))
        elif dct_coeffs_per_frame is not None:
            raise ValueError("dct_coeffs_per_frame only applies to "
                             "pixel_path='dct'")
        #: the wire dtype every decode/staging/transfer buffer of this
        #: stage uses: int16 packed coefficient rows under dct, u8
        #: pixel/plane rows otherwise
        self._wire_dtype = (np.int16 if pixel_path == "dct"
                            else np.uint8)
        sampler_kwargs = {}
        if num_clips_population is not None:
            sampler_kwargs["num_clips_population"] = num_clips_population
        if weights is not None:
            sampler_kwargs["weights"] = weights
        self.sampler = R2P1DSampler(consecutive_frames=consecutive_frames,
                                    **sampler_kwargs)
        self.max_clips = int(max_clips)
        self.consecutive_frames = int(consecutive_frames)
        # Row bucketing: pad each video to the smallest bucket >= its
        # clip count instead of always to max_clips. jit caches one
        # executable per bucket shape, so with the default skewed clip
        # population ([1,15]@[10,1], sampler.py) ~91% of videos move
        # and compute 15x less than max-shape padding. Opt-in per
        # config; downstream stages must warm the same buckets.
        self.row_buckets = _normalize_row_buckets(row_buckets,
                                                  self.max_clips,
                                                  "max_clips")
        # Ragged row-pool dispatch (rnb_tpu.ops.ragged): every emission
        # ships the ONE pool shape with an explicit rows_valid + per-
        # request segment offsets — no bucket padding, one warmup
        # compile, continuous autotune. row_buckets, if configured,
        # stop being shipped shapes and become the COUNTERFACTUAL pad
        # rule the pad_rows_eliminated counter is measured against.
        self.ragged = bool(ragged)
        self.pool_rows = (resolve_pool_rows(ragged_pool_rows,
                                            self.max_clips, "max_clips")
                          if self.ragged else None)
        if self.ragged and self.raw_output:
            raise ValueError("ragged cannot be combined with "
                             "raw_output: mesh consumers shard a fixed "
                             "clip axis, not a rows_valid pool")
        #: padding-waste accounting (PadCounter; 0-pad under ragged)
        self.padding = PadCounter()
        #: ragged accounting, drained via the executor's ragged sink
        self.ragged_stats = ({"pool_rows": self.pool_rows,
                              "emissions": 0, "rows": 0,
                              "pad_rows_eliminated": 0,
                              "cache_hit_rows": 0}
                             if self.ragged else None)
        if self.raw_output and len(self.row_buckets) > 1:
            # raw consumers (R2P1DMeshRunner) shard the clip axis over a
            # fixed mesh — a variable bucketed clip axis cannot satisfy
            # the sp divisibility requirement
            raise ValueError("row_buckets cannot be combined with "
                             "raw_output: mesh consumers need a fixed "
                             "clip axis")
        self.prefetch_depth = int(prefetch)
        self._fallback_pool = None  # lazily built thread pool
        # non-native fallback decode pool sizing: defaults to the
        # native DecodePool rule (RNB_DECODE_THREADS env, else
        # min(8, cores)) instead of a hardcoded width
        if fallback_decode_threads is None:
            self.fallback_decode_threads = default_decode_threads()
        else:
            self.fallback_decode_threads = int(fallback_decode_threads)
            if self.fallback_decode_threads < 1:
                raise ValueError("fallback_decode_threads must be >= 1, "
                                 "got %r" % (fallback_decode_threads,))
        self._starts_cache = {}  # video -> clip starts (see _sample_starts)
        #: this stage's pipeline-step index, handed over by the
        #: executor (bind_step): names the phase-refinement stamps
        #: (decode{step}_done / transfer{step}_start/_done) every
        #: request carries. None only for a loader driven without an
        #: executor (unit tests), which then writes none
        self._stamp_step: Optional[int] = None
        # Zero-copy decode staging (rnb_tpu.staging): pre-allocated
        # host slots the native decoder writes straight into, removing
        # the per-request/per-emission bucket-shaped allocation and
        # assembly memcpy from the hot path. staging_slots=0 disables
        # (the seed copy path); None auto-sizes per loader kind.
        self.transfer_async = bool(transfer_async)
        if self.transfer_async and not self.SUPPORTS_TRANSFER_ASYNC:
            raise ValueError(
                "transfer_async requires a stage that emits "
                "asynchronously (R2P1DFusingLoader); %s completes "
                "requests synchronously" % type(self).__name__)
        if staging_slots is not None:
            staging_slots = int(staging_slots)
            if staging_slots < 0:
                raise ValueError("staging_slots must be >= 0 "
                                 "(0 disables staging), got %r"
                                 % (staging_slots,))
        slots = (self._staging_default_slots() if staging_slots is None
                 else staging_slots)
        self.staging = None
        if slots and native_available() \
                and self._staging_default_slots() > 0:
            # floor the explicit knob at the loader's structural
            # minimum: the plain loader's submit window holds
            # prefetch+1 slots before the first complete() (same
            # thread) can release one, so fewer than prefetch+2 slots
            # would deadlock submit against itself. The fusing loader
            # pressure-drains in _acquire_fused_slot and works at 1.
            slots = max(slots, self._staging_min_slots())
            # the zero-copy path exists only for the native decoder
            # (submit_into writes caller buffers) and only on code
            # paths that decode into caller targets — a plain loader
            # without prefetch decodes synchronously in __call__ and
            # would never touch a pool, so an explicit staging_slots
            # is ignored there (default_slots()==0) rather than
            # allocating dead slots and reporting misleading Staging:
            # telemetry. Non-native backends keep the copy fallback.
            self.staging = StagingPool(self._staging_shapes(), slots,
                                       dtype=self._wire_dtype)
        # Device-resident decoded-clip cache + in-flight coalescing
        # (rnb_tpu.cache): opt-in per config via `cache_mb`. The cached
        # value is the padded on-device uint8 batch (post-device_put,
        # pre-preprocess), so a hit skips decode AND host->device
        # transfer — round 5's two dominant host terms (2026-07,
        # previous transport, not reproduced) — and feeds the identical jitted path a miss would, keeping
        # hit/miss logits bit-identical.
        self.cache = None
        self._inflight_keys = None
        if cache_mb:
            from rnb_tpu.cache import ClipCache, InflightTable
            self.cache = ClipCache(cache_mb, device=self._jax_device)
            self._inflight_keys = InflightTable()
            # decode-config fingerprint: everything that changes the
            # decoded bytes or the padded value shape. Clip starts are
            # deterministic per video id given the sampler config
            # (sampler.py seeds per id), so no seed belongs here.
            self._cache_cfg = (
                "r2p1d", tuple(self.sampler.num_clips_population),
                tuple(float(p) for p in self.sampler.probabilities),
                self.consecutive_frames, FRAME_HW, self.pixel_path,
                self.max_clips, self.row_buckets,
                # ragged entries hold host row extents, bucketed ones
                # padded device batches — the two must never alias
                self.ragged,
                # the dct wire row length depends on the coefficient
                # budget: two budgets must never alias one entry
                self.dct_coeffs)
        # Paged device memory (rnb_tpu.pager), wired by the executor
        # via enable_pager(): the clip cache's blob storage becomes
        # page-table entries in a pager arena (hits gather on device,
        # zero host bytes) and — under pager.feature_cache — repeat
        # requests can skip the downstream forward entirely
        self.pager = None
        self._clip_arena = None
        self._zero_pool = None
        self._feature_stub = None
        self._preprocess_ragged = None
        #: jit-entry signature accounting (rnb_tpu.compilestats):
        #: distinct preprocess input signatures == executables this
        #: stage requires; frozen by the executor at window start so
        #: any later new signature surfaces as a mid-run recompile
        self.compiles = None
        # set-up's spans of the warm-up below, a warmed shape each (the
        # launcher's Tracer collects them until the start barrier)
        tr_program = trace.name("setup.s%d.program", trace.building_step())
        tr_first_call = trace.name("setup.s%d.first_call",
                                   trace.building_step())
        if self.raw_output or self.pixel_path in ("yuv420", "dct"):
            # raw mode: consumer normalizes on its mesh. yuv420/dct:
            # the network stage's jit owns the whole ingest; the
            # loader ships packed u8 planes / int16 coefficient rows —
            # warm only the transfer path (one shape per bucket; ONE
            # pool shape under ragged — device_put itself never
            # compiles)
            self._preprocess = None
            for rows in self._warm_shapes():
                dummy = np.zeros(self._batch_shape(rows),
                                 dtype=self._wire_dtype)
                with trace.span(tr_program, rows=rows), \
                        trace.span(tr_first_call):
                    for _ in range(num_warmups):
                        jax.block_until_ready(
                            jax.device_put(dummy, self._jax_device))
        elif self.ragged:
            # ragged ingest: ONE compiled executable serves every
            # batch composition — the rows_valid scalar is traced,
            # and the TPU kernel's grid skip spends no arithmetic on
            # rows past it (rnb_tpu/ops/ragged.py)
            self._preprocess = None
            self._preprocess_ragged = _shared_ragged_preprocess(
                self._jax_device)
            self.compiles = SignatureTracker()
            dummy = np.zeros(self._batch_shape(self.pool_rows),
                             dtype=np.uint8)
            # vocabulary declared even under num_warmups=0 (see the
            # runner's warmup loop)
            self.compiles.observe(dummy)
            with trace.span(tr_program, rows=self.pool_rows), \
                    trace.span(tr_first_call):
                for _ in range(num_warmups):
                    jax.block_until_ready(self._preprocess_ragged(
                        jax.device_put(dummy, self._jax_device),
                        np.int32(self.pool_rows)))
        else:
            self._preprocess = _shared_preprocess(self._jax_device)
            self.compiles = SignatureTracker()
            # warm-up: compile the preprocess for every bucket shape and
            # fault in the transfer path
            for rows in self._warm_shapes():
                dummy = np.zeros(self._batch_shape(rows),
                                 dtype=np.uint8)
                self.compiles.observe(dummy)
                with trace.span(tr_program, rows=rows), \
                        trace.span(tr_first_call):
                    for _ in range(num_warmups):
                        jax.block_until_ready(self._preprocess(
                            jax.device_put(dummy, self._jax_device)))
        # decode warm-up on real sample files (the reference warmed its
        # NVVL loader on 3 sample mp4s, models/r2p1d/model.py:133-138):
        # faults in file IO, header parse and the native pool so the
        # first measured request pays no cold cost. num_warmups=0 is the
        # opt-out and must skip this too.
        if num_warmups > 0:
            self._warm_decode(num_samples=3)

    def _warm_decode(self, num_samples: int = 3) -> None:
        import os
        root = os.environ.get("RNB_TPU_DATA_ROOT")
        if not root or not os.path.isdir(root):
            return
        samples = []
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for fn in sorted(filenames):
                if fn.endswith(".y4m"):
                    samples.append(os.path.join(dirpath, fn))
                    if len(samples) >= num_samples:
                        break
            if len(samples) >= num_samples:
                break
        for path in samples:
            try:
                decoder = get_decoder(path)
                length = decoder.num_frames(path)
                starts = self.sampler.sample(
                    length, video_id=path)[: self.max_clips]
                self._decode_sync(decoder, path, starts)
            except Exception as e:
                # warm-up is best-effort: a corrupt sample file must
                # not kill stage init (the hot loop contains the same
                # error per-request); unclassified errors still abort
                if classify_error(e) is FATAL:
                    raise
                print("[rnb-tpu] WARNING: decode warm-up skipped %s: %s"
                      % (path, e))

    def bind_step(self, step_idx: int) -> None:
        """Executor protocol (rnb_tpu.runner): the stage learns its
        step index, which names the per-request phase-refinement
        stamps. Called on every run."""
        self._stamp_step = int(step_idx)

    def enable_trace(self, tracer, step_idx: int) -> None:
        """Executor protocol (rnb_tpu.runner): register this stage's
        sampled occupancy sources with the job tracer. Called only on
        runs with the ``trace`` config key."""
        if self.staging is not None:
            tracer.add_counter_source(
                trace.name("staging.s%d.free", step_idx),
                self.staging.available)

    def _stamp_decode_done(self, time_card) -> None:
        """Phase-refinement: this request's decode completed."""
        if self._stamp_step is None:
            return
        _record_clamped(time_card,
                        "decode%d_done" % self._stamp_step, time.time())
        trace.instant("loader.decode_ready", rid=time_card.id)

    def _staging_default_slots(self) -> int:
        """Auto slot budget: the prefetch window plus one transferring
        slot (submit must never deadlock waiting on a complete() that
        runs later on the same executor thread). 0 = no pool: without
        prefetch the plain loader decodes synchronously in __call__
        and never targets a slot."""
        return self.prefetch_depth + 2 if self.prefetch_depth > 0 else 0

    def _staging_min_slots(self) -> int:
        """Smallest slot count this loader can run without submit
        deadlocking against its own complete() (see __init__)."""
        return self.prefetch_depth + 2

    def _warm_shapes(self):
        """Row counts warm-up must fault in: the bucket vocabulary —
        or the ONE pool shape under ragged dispatch."""
        return (self.pool_rows,) if self.ragged else self.row_buckets

    def _ship_rows(self, n: int) -> int:
        """Rows an emission holding ``n`` valid rows actually ships:
        its pad bucket — or the fixed pool capacity under ragged."""
        return self.pool_rows if self.ragged else self._bucket_for(n)

    def _note_emission_padding(self, valid: int, shipped: int,
                               cards) -> None:
        """Padding-waste + ragged accounting for one emission (the
        shared rule, rnb_tpu.stage.note_emission_accounting); the
        counterfactual under ragged is this stage's configured bucket
        vocabulary (max-shape padding when none is named), so a
        same-seed bucketed arm's pad_rows equals pad_rows_eliminated
        exactly."""
        note_emission_accounting(
            self.padding, self.ragged_stats, cards, valid, shipped,
            self._bucket_for(valid) if self.ragged else 0)

    def _normalize_emission(self, device_u8, valid: int):
        """The one preprocess dispatch every emission path shares:
        bucketed jit, ragged jit (traced rows_valid scalar), or a
        pass-through for raw/yuv consumers. Observes the jit-entry
        signature for the Compiles: accounting."""
        if self._preprocess_ragged is not None:
            self.compiles.observe(device_u8)
            return self._preprocess_ragged(device_u8, np.int32(valid))
        if self._preprocess is not None:
            self.compiles.observe(device_u8)
            return self._preprocess(device_u8)
        return device_u8

    def _wrap_batch(self, data, valid: int, offsets=None):
        """The emitted tensor: a RaggedBatch carrying the segment
        table under ragged dispatch, the seed PaddedBatch otherwise."""
        if self.ragged:
            return RaggedBatch(data, valid,
                               tuple(offsets) if offsets is not None
                               else (0, int(valid)))
        return PaddedBatch(data, valid)

    def _staging_shapes(self):
        """One sub-pool per emitted bucket shape (ONE pool shape under
        ragged dispatch)."""
        return [self._batch_shape(rows) for rows in self._warm_shapes()]

    def _stage_target(self, n: int):
        """Decode-target buffer for one native request:
        ``(buffer, slot, row0)`` — a staging-slot row view on the
        zero-copy path, or a fresh allocation when staging is off
        (the copy fallback, baselined under RNB-H007)."""
        if self.staging is not None:
            slot = self.staging.acquire(
                self._batch_shape(self._ship_rows(n)))
            self.staging.add_ref(slot)
            return slot.buf[:n], slot, 0
        return (np.empty(self._batch_shape(n), dtype=self._wire_dtype),
                None, 0)

    def _release_handle_slot(self, handle) -> None:
        """Retire a handle's staging-slot reference (idempotent): its
        rows are consumed, dead, or replaced by a re-decode."""
        slot = getattr(handle, "slot", None)
        if slot is not None and self.staging is not None:
            self.staging.retire_ref(slot)
            handle.slot = None

    def _release_handle_plan(self, handle) -> None:
        """Release a handle's pinned page plans (drop/shed/failure
        paths, idempotent): pages an eviction parked in limbo under
        the pin re-enter the free list, so a shed hit can never leak
        pages (rnb_tpu.pager pin/limbo discipline)."""
        for attr in ("gather_plan", "feature_plan", "cached"):
            plan = getattr(handle, attr, None)
            if plan is not None and hasattr(plan, "release"):
                plan.release()
                if attr != "cached":
                    setattr(handle, attr, None)

    def enable_pager(self, pager) -> None:
        """Executor protocol (rnb_tpu.runner): install the page
        allocator. The clip cache switches to page-table entries in a
        fresh ``clips`` arena sized from the cache's own byte budget
        (the bytes the blob cache would have owned), and the loader
        preallocates the ONE pool-shaped device zero array that
        full-gather hits and feature hits dispatch with — a hit then
        ships zero host memcpy bytes. Requires ragged dispatch (the
        pool is the one gather seam) and an enabled clip cache."""
        import jax
        if not self.ragged:
            raise ValueError(
                "pager requires ragged dispatch: paged gathers "
                "overlay rows of the ONE pool shape (configure the "
                "root 'ragged' key)")
        if self.cache is None:
            raise ValueError(
                "pager requires an enabled clip cache (cache_mb): "
                "the page arena replaces its blob storage")
        self.pager = pager
        pager.size_hint(self.cache.capacity_bytes)
        self._clip_arena = pager.create_arena(
            "clips", self._batch_shape(1)[1:], self._wire_dtype,
            budget_bytes=self.cache.capacity_bytes,
            device=self._jax_device)
        self.cache.attach_arena(self._clip_arena)
        zeros = np.zeros(self._batch_shape(self.pool_rows),
                         dtype=self._wire_dtype)
        self._zero_pool = jax.device_put(zeros, self._jax_device)
        # feature hits ship a stub emission downstream (the consumer
        # gathers its own output rows and never reads the payload);
        # the stub must still BE the declared wire value — normalized
        # once here, outside the measured window
        stub = self._normalize_emission(self._zero_pool, 0)
        if stub is not self._zero_pool:
            import jax as _jax
            _jax.block_until_ready(stub)
        self._feature_stub = stub

    def _decode_sync(self, decoder, video, starts):
        """Synchronous decode through this loader's pixel path."""
        if self.pixel_path == "yuv420":
            return decoder.decode_clips_yuv(video, starts,
                                            self.consecutive_frames,
                                            width=FRAME_HW,
                                            height=FRAME_HW)
        if self.pixel_path == "dct":
            return decoder.decode_clips_dct(video, starts,
                                            self.consecutive_frames,
                                            width=FRAME_HW,
                                            height=FRAME_HW,
                                            coeffs=self.dct_coeffs)
        return decoder.decode_clips(video, starts,
                                    self.consecutive_frames,
                                    width=FRAME_HW, height=FRAME_HW)

    def _batch_shape(self, rows: Optional[int] = None):
        n = rows if rows is not None else self.max_clips
        if self.pixel_path == "yuv420":
            return (n, self.consecutive_frames,
                    packed_frame_bytes(FRAME_HW, FRAME_HW))
        if self.pixel_path == "dct":
            return (n, self.consecutive_frames,
                    dct_frame_elems(FRAME_HW, FRAME_HW,
                                    self.dct_coeffs))
        return (n, self.consecutive_frames, FRAME_HW, FRAME_HW, 3)

    def _bucket_for(self, n: int) -> int:
        for bucket in self.row_buckets:
            if n <= bucket:
                return bucket
        return self.row_buckets[-1]

    def input_shape(self):
        return None

    @staticmethod
    def output_shape():
        return ((MAX_CLIPS, CONSECUTIVE_FRAMES, FRAME_HW, FRAME_HW, 3),)

    @classmethod
    def output_shape_for(cls, max_clips: int = MAX_CLIPS,
                         consecutive_frames: int = CONSECUTIVE_FRAMES,
                         pixel_path: str = "rgb",
                         dct_coeffs_per_frame=None, **_kwargs):
        if pixel_path == "yuv420":
            return ((int(max_clips), int(consecutive_frames),
                     packed_frame_bytes(FRAME_HW, FRAME_HW)),)
        if pixel_path == "dct":
            return ((int(max_clips), int(consecutive_frames),
                     dct_frame_elems(FRAME_HW, FRAME_HW,
                                     dct_coeffs_per_frame)),)
        return ((int(max_clips), int(consecutive_frames),
                 FRAME_HW, FRAME_HW, 3),)

    @classmethod
    def output_dtype_for(cls, raw_output: bool = False,
                         pixel_path: str = "rgb", **_kwargs):
        # raw mode ships the padded uint8 batch; yuv420 ships packed u8
        # planes and dct ships packed int16 coefficient rows for the
        # consumer's fused ingest; otherwise the jitted preprocess
        # emits normalized bfloat16
        if pixel_path == "dct":
            return "int16"
        if raw_output or pixel_path == "yuv420":
            return "uint8"
        return "bfloat16"

    #: clips per native-pool ticket when a submitted video fans out:
    #: small enough that a 15-clip video engages several workers, large
    #: enough that 1-clip videos cost one submit/wait round trip
    POOL_CHUNK_CLIPS = 4

    #: per-video clip-start cache cap: benchmark datasets cycle a small
    #: id population; anything larger falls back to re-sampling
    STARTS_CACHE_MAX = 8192

    def _sample_starts(self, decoder, video: str):
        """Clip starts for one video — cached. The sampler is
        deterministic per video id (sampler.py seeds per id) and a
        file's frame count is fixed, so a repeated id re-derives
        identical starts; before caching, the probe+sample path cost
        ~200 us/request = 20% of the host core at ~1k videos/s
        (host profile, round 5). A file replaced on disk mid-run keeps its
        cached starts — benchmark semantics, same as the native
        decoder's per-video metadata caches."""
        starts = self._starts_cache.get(video)
        if starts is None:
            length = decoder.num_frames(video)
            starts = [int(s) for s in
                      self.sampler.sample(length, video_id=video)]
            starts = starts[: self.max_clips]
            if len(self._starts_cache) < self.STARTS_CACHE_MAX:
                self._starts_cache[video] = starts
        return starts

    def _cache_lookup(self, video: str, key=None):
        """(key, entry) for one request — (None, None) when caching is
        off. Counted: the lookup (one stat + one
        dict probe) is the only cost a cache-enabled miss adds. Under
        a paged cache the hit value is a pinned GatherPlan
        (rnb_tpu.cache.ClipCache.acquire), not a blob entry. ``key``
        short-circuits the content hash when the caller already
        computed it (the feature-page probe)."""
        if self.cache is None:
            return None, None
        if key is None:
            key = content_key(video, self._cache_cfg)
        if self.cache.paged:
            entry = self.cache.acquire(key)
        else:
            entry = self.cache.lookup(key)
        return key, entry

    def _feature_probe(self, video: str):
        """(content_key, plan): probe the feature-page cache ahead of
        the clip cache — a hit there supersedes everything (the whole
        stage-0..N work is skipped). (None, None) when feature pages
        are off; (key, None) on a plain miss, the key then feeds
        :meth:`_cache_lookup` so the content hash runs once."""
        if self.pager is None or self.pager.feature is None \
                or self.cache is None:
            return None, None
        key = content_key(video, self._cache_cfg)
        return key, self.pager.feature.acquire(key)

    def _stamp_feature_insert(self, time_card, key, row0: int,
                              n: int) -> None:
        """Mark one successfully transferred request's pool row range
        as a feature-insert candidate: the CONSUMING stage performs
        the insert strictly after its forward returned
        (insert-after-success), reading the stamp off the card."""
        if self.pager is not None and self.pager.feature is not None \
                and self.pager.feature.ready and key is not None:
            time_card.feature_insert = (key, int(row0), int(n))

    def _materialize_hit(self, entry, time_card):
        """Serve one request from a cache entry: no decode, no
        transfer — straight into the same jitted preprocess a miss
        feeds (or as-is for raw/yuv420 consumers).

        Under ragged dispatch the entry is a **host row extent**
        (rnb_tpu.cache.insert_rows): the decode is skipped but the
        rows re-pad into the pool and ride a fresh transfer — the
        pool is the one dispatch shape, so there is no per-request
        padded device value to serve zero-copy (README "Ragged
        dispatch" documents the trade)."""
        time_card.num_clips = entry.valid
        time_card.cache_hit = True
        if self.ragged:
            if self.ragged_stats is not None:
                self.ragged_stats["cache_hit_rows"] += entry.valid
            if self._stamp_step is not None:
                _record_clamped(time_card, "decode%d_done"
                                % self._stamp_step, time.time())
            if self.cache.paged:
                return self._materialize_pages(entry, time_card)
            return self._materialize(entry.batch, entry.valid,
                                     time_card)
        if self._stamp_step is not None:
            # a hit pays no decode/hold/transfer: zero-length phases
            # keep every card's key sequence identical per instance
            # (TimeCardSummary asserts one schema per run)
            now = time.time()
            step = self._stamp_step
            _record_clamped(time_card, "decode%d_done" % step, now)
            _record_clamped(time_card, "transfer%d_start" % step, now)
            _record_clamped(time_card, "transfer%d_done" % step, now)
        self._note_emission_padding(entry.valid,
                                    int(entry.batch.shape[0]),
                                    [time_card])
        return (PaddedBatch(self._normalize_emission(entry.batch,
                                                     entry.valid),
                            entry.valid),), None, time_card

    def _materialize_pages(self, plan, time_card):
        """Serve a paged ragged hit with ZERO host bytes: the entry's
        page rows gather straight over the preallocated device zero
        pool — no decode, no staging rows, no host memcpy, no
        device_put (the staging plane counts a bypassed emission).
        The gather feeds the identical normalize dispatch a miss
        feeds, so hit/miss logits stay bit-identical."""
        n = plan.valid
        if self._stamp_step is not None:
            # no transfer happens: zero-length phases keep the card's
            # key sequence identical to a miss (TimeCardSummary
            # asserts one schema per step instance)
            now = time.time()
            step = self._stamp_step
            _record_clamped(time_card, "transfer%d_start" % step, now)
            _record_clamped(time_card, "transfer%d_done" % step, now)
        src = np.full((self.pool_rows,), -1, np.int32)
        src[:n] = plan.src_rows
        device_u8 = self._clip_arena.gather(self._zero_pool, src)
        plan.release()
        if self.staging is not None:
            self.staging.note_bypassed()
        self._note_emission_padding(n, self.pool_rows, [time_card])
        batch = self._normalize_emission(device_u8, n)
        return (self._wrap_batch(batch, n),), None, time_card

    def _materialize_feature(self, plan, time_card):
        """A feature-page hit: the request skips decode, transfer AND
        the downstream forward. The emission ships the preallocated
        stub pool (never read downstream) and the pinned plan rides
        the time card to the consuming stage, which gathers the exact
        output rows the original request computed and releases the
        pin. Insert-after-success upstream guarantees those rows came
        from a forward that returned."""
        n = plan.valid
        time_card.num_clips = n
        time_card.feature_hit = True
        time_card.feature_plan = plan
        if self._stamp_step is not None:
            now = time.time()
            step = self._stamp_step
            _record_clamped(time_card, "decode%d_done" % step, now)
            _record_clamped(time_card, "transfer%d_start" % step, now)
            _record_clamped(time_card, "transfer%d_done" % step, now)
        self.pager.note_feature_saved(n * self._clip_arena.row_bytes)
        if self.staging is not None:
            self.staging.note_bypassed()
        self._note_emission_padding(n, self.pool_rows, [time_card])
        return (self._wrap_batch(self._feature_stub, n),), None, \
            time_card

    def submit(self, non_tensors, time_card) -> _DecodeHandle:
        """Kick off decode of one request; pair with :meth:`complete`.

        Native .y4m requests become DecodePool tickets (decode runs on
        the C++ worker pool immediately); other backends decode on a
        small fallback thread pool. Either way the calling executor
        thread returns without blocking on pixel work.

        With the clip cache enabled, a hit returns a work-free cached
        handle, and a request whose key is already decoding in the
        prefetch window coalesces onto that leader (shares its decoded
        buffer — no second decode) instead of re-submitting.
        """
        video = str(non_tensors)
        fkey, fplan = self._feature_probe(video)
        if fplan is not None:
            handle = _DecodeHandle(None, fplan.valid)
            handle.feature_plan = fplan
            time_card.num_clips = fplan.valid
            time_card.feature_hit = True
            return handle
        key, entry = self._cache_lookup(video, key=fkey)
        if entry is not None:
            time_card.num_clips = entry.valid
            time_card.cache_hit = True
            return _DecodeHandle(None, entry.valid, cached=entry)
        if key is not None:
            time_card.cache_hit = False
            leader = self._inflight_keys.get(key)
            if leader is not None:
                time_card.num_clips = leader.n
                time_card.cache_coalesced = True
                self.cache.note_coalesced()
                follower = _DecodeHandle(None, leader.n, leader=leader)
                if leader.slot is not None and self.staging is not None:
                    # the follower reads the leader's slot rows for its
                    # own transfer — it must hold its own reference or
                    # the leader's completion could recycle the slot
                    # under the follower's still-pending read
                    self.staging.add_ref(leader.slot)
                    follower.slot = leader.slot
                    follower.row0 = leader.row0
                return follower
        handle = self._decode_submit(video, time_card)
        if key is not None:
            handle.key = key
            self._inflight_keys.put(key, handle)
        return handle

    def _decode_submit(self, video: str, time_card) -> _DecodeHandle:
        """The raw async-decode kickoff behind :meth:`submit` — no
        cache interaction (the fusing loader runs its own lookup and
        coalescing around this)."""
        decoder = get_decoder(video)
        starts = self._sample_starts(decoder, video)
        n = len(starts)
        time_card.num_clips = n
        # flow anchor: decode kicked off for this request
        trace.instant("loader.decode_submit", rid=time_card.id)
        # trust the backend get_decoder() chose: a .y4m path whose file
        # vanished resolves to SyntheticDecoder there, and submitting it
        # to the native pool anyway would kill the run the synchronous
        # path survives
        if isinstance(decoder, NativeY4MDecoder):
            out, slot, row0 = self._stage_target(n)
            pixfmt = {"yuv420": PIX_YUV420,
                      "dct": PIX_DCT}.get(self.pixel_path, PIX_RGB)
            pool = DecodePool.shared()
            tickets = []
            try:
                for lo in range(0, n, self.POOL_CHUNK_CLIPS):
                    hi = min(lo + self.POOL_CHUNK_CLIPS, n)
                    tickets.append(pool.submit_into(
                        video, starts[lo:hi], self.consecutive_frames,
                        out[lo:hi], pixfmt=pixfmt, width=FRAME_HW,
                        height=FRAME_HW))
            except Exception:
                # a partial submit must not leak the earlier tickets —
                # un-waited tickets pin the batch buffer in the pool's
                # pending map for the process's life
                partial = _DecodeHandle(out, n, pool=pool,
                                        tickets=tickets, slot=slot,
                                        row0=row0)
                try:
                    partial.wait(video)
                except ValueError:
                    pass
                self._release_handle_slot(partial)
                raise
            return _DecodeHandle(out, n, pool=pool, tickets=tickets,
                                 slot=slot, row0=row0)
        if self._fallback_pool is None:
            self._fallback_pool = ThreadPoolExecutor(
                max_workers=self.fallback_decode_threads,
                thread_name_prefix="rnb-decode")

        handle = _DecodeHandle(None, n)
        rid = time_card.id

        def _work():
            # hand the decoded batch to the handle directly — no
            # staging copy into the preallocated buffer (the span puts
            # the decode body on the rnb-decode thread's trace track;
            # native-pool decodes run in C++ and are delimited by the
            # submit/ready instants instead)
            with trace.span("loader.decode", rid):
                handle.out = self._decode_sync(decoder, video, starts)

        handle.future = self._fallback_pool.submit(_work)
        return handle

    def _materialize(self, clips: np.ndarray, n: int, time_card,
                     cache_key=None):
        """Pad decoded clips to their row bucket, transfer, normalize.

        With ``cache_key`` set, the freshly transferred padded device
        batch is inserted into the clip cache — insert-after-success
        only: this line is reached only once decode and transfer both
        completed, so failed/contained requests never populate entries.
        """
        jax, _ = _jax_numpy()
        target = self._batch_shape(self._ship_rows(n))
        if clips.shape == target:
            # bucket == clip count (the dominant 1-clip case): the
            # decode buffer already is the transfer buffer — no pad copy
            padded = clips
        elif self.ragged:
            # ragged consumers mask rows >= rows_valid in-jit, so the
            # pool tail can stay uninitialized — for the dominant
            # 1-clip request that skips a pool-minus-one-row memset
            padded = np.empty(target, dtype=self._wire_dtype)
            padded[:n] = clips
        else:
            padded = np.zeros(target, dtype=self._wire_dtype)
            padded[:n] = clips
        if cache_key is not None and self.cache is not None \
                and self.ragged and not self.cache.paged:
            # ragged entries are host row extents (exactly n rows,
            # no pool padding) — copied out here, before the transfer,
            # while the decode buffer is live
            self.cache.insert_rows(cache_key, clips, n)
        if self._stamp_step is not None:
            _record_clamped(time_card,
                            "transfer%d_start" % self._stamp_step,
                            time.time())
        with trace.span("loader.transfer", time_card.id):
            device_u8 = jax.device_put(padded, self._jax_device)
        if self._stamp_step is not None:
            _record_clamped(time_card,
                            "transfer%d_done" % self._stamp_step,
                            time.time())
        if cache_key is not None and self.cache is not None \
                and self.ragged and self.cache.paged:
            # paged insert is post-transfer DEVICE work (insert-after-
            # success and zero extra host copies): pool rows [0, n)
            # publish into pages by donated on-device writes
            self.cache.insert_pages(cache_key, device_u8, 0, n)
            self._stamp_feature_insert(time_card, cache_key, 0, n)
        if cache_key is not None and self.cache is not None \
                and not self.ragged:
            # zero-copy insert: the padded device array IS the cached
            # value (immutable jax.Array) — no extra transfer
            self.cache.insert_device(cache_key, device_u8, n)
        self._note_emission_padding(n, int(target[0]), [time_card])
        batch = self._normalize_emission(device_u8, n)
        return (self._wrap_batch(batch, n),), None, time_card

    def _materialize_slot(self, handle: _DecodeHandle, time_card,
                          cache_key=None):
        """The staged twin of :meth:`_materialize`: the decode landed
        directly in a bucket-shaped staging slot, so the slot IS the
        transfer buffer — no pad allocation, no assembly copy. Only
        the padding tail is zeroed (seed byte parity), the transfer is
        confirmed lazily at the slot's next acquire, and the slot is
        recycled strictly after that confirmation (rnb_tpu.staging
        alias handling keeps an aliasing backend from ever reusing
        memory a live device batch still reads)."""
        jax, _ = _jax_numpy()
        slot, n = handle.slot, handle.n
        if n < slot.buf.shape[0] and not self.ragged:
            # bucketed byte parity needs a zeroed pad tail; under
            # ragged every consumer masks rows >= rows_valid inside
            # its jit (rnb_tpu/ops/ragged.py contract), so the memset
            # — up to pool-1 rows per request — is pure host waste
            slot.buf[n:] = 0
        if cache_key is not None and self.cache is not None \
                and self.ragged and not self.cache.paged:
            # ragged entries are host row extents, copied out of the
            # slot while its rows are still live (pre-handoff)
            self.cache.insert_rows(cache_key, slot.buf, n)
        self.staging.begin_transfer(slot)
        if self._stamp_step is not None:
            _record_clamped(time_card,
                            "transfer%d_start" % self._stamp_step,
                            time.time())
        with trace.span("loader.transfer", time_card.id):
            device_u8 = jax.device_put(slot.buf, self._jax_device)
        self.staging.finish_transfer(slot, device_u8)
        self.staging.note_staged()
        if self._stamp_step is not None:
            _record_clamped(time_card,
                            "transfer%d_done" % self._stamp_step,
                            time.time())
        self._release_handle_slot(handle)
        if cache_key is not None and self.cache is not None \
                and self.ragged and self.cache.paged:
            # paged insert, post-transfer (see _materialize)
            self.cache.insert_pages(cache_key, device_u8, 0, n)
            self._stamp_feature_insert(time_card, cache_key, 0, n)
        if cache_key is not None and self.cache is not None \
                and not self.ragged:
            # still zero-copy: the cached device array owns its bytes
            # once the transfer is confirmed; the slot recycle gate
            # (and the alias probe behind it) guarantees exactly that
            self.cache.insert_device(cache_key, device_u8, n)
        self._note_emission_padding(n, int(device_u8.shape[0]),
                                    [time_card])
        return (self._wrap_batch(self._normalize_emission(device_u8, n),
                                 n),), None, time_card

    def complete(self, handle: _DecodeHandle, non_tensors, time_card):
        """Wait for a submitted decode, then pad/transfer/normalize
        (or serve the cached/coalesced result without decode work)."""
        if handle.feature_plan is not None:
            plan, handle.feature_plan = handle.feature_plan, None
            return self._materialize_feature(plan, time_card)
        if handle.cached is not None:
            return self._materialize_hit(handle.cached, time_card)
        if handle.leader is not None:
            # coalesced follower: the leader decoded for both; a failed
            # leader re-raises its classified error here (containment
            # then dead-letters this request too). No cache insert —
            # the leader already did it.
            try:
                handle.wait(str(non_tensors))
            except Exception:
                self._release_handle_slot(handle)
                raise
            self._stamp_decode_done(time_card)
            if handle.slot is not None:
                # the follower pays its own transfer straight from the
                # leader's slot rows (its own reference keeps them live)
                return self._materialize_slot(handle, time_card)
            return self._materialize(handle.out, handle.n, time_card)
        try:
            handle.wait(str(non_tensors))
        except Exception:
            self._release_handle_slot(handle)
            raise
        finally:
            # the decode is finalized either way: later requests for
            # this key consult the cache (success) or decode afresh
            if self._inflight_keys is not None:
                self._inflight_keys.pop(handle.key)
        self._stamp_decode_done(time_card)
        if handle.slot is not None:
            return self._materialize_slot(handle, time_card,
                                          cache_key=handle.key)
        return self._materialize(handle.out, handle.n, time_card,
                                 cache_key=handle.key)

    def discard(self, handle: _DecodeHandle, non_tensors=None) -> None:
        """Retire a submitted decode whose result will never be used
        (abort path) so native tickets don't pin buffers forever —
        and release its staging-slot reference, so a contained or
        aborted request can never leak a slot."""
        try:
            handle.wait(str(non_tensors))
        except Exception:
            pass  # abort path: decode errors are moot
        self._release_handle_slot(handle)
        self._release_handle_plan(handle)
        if self._inflight_keys is not None:
            self._inflight_keys.pop(getattr(handle, "key", None))

    def __call__(self, tensors, non_tensors, time_card):
        # synchronous path (no prefetching executor, R2P1DSingleStep):
        # decode inline on the calling thread — no thread-pool hop, no
        # extra staging copy on the hot path
        video = str(non_tensors)
        fkey, fplan = self._feature_probe(video)
        if fplan is not None:
            return self._materialize_feature(fplan, time_card)
        key, entry = self._cache_lookup(video, key=fkey)
        if entry is not None:
            return self._materialize_hit(entry, time_card)
        decoder = get_decoder(video)
        starts = self._sample_starts(decoder, video)
        clips = self._decode_sync(decoder, video, starts)
        n = clips.shape[0]
        time_card.num_clips = n
        self._stamp_decode_done(time_card)
        if key is not None:
            time_card.cache_hit = False
        return self._materialize(clips, n, time_card, cache_key=key)


class _FuseRecord:
    """One in-flight/ready request of the fusing loader: the decode
    handle plus every TimeCard riding on it — the leader's and any
    coalesced followers' (rnb_tpu.cache), which share the single
    decode and the single fused emission."""

    __slots__ = ("handle", "video", "cards", "key", "fkey", "t_ready")

    def __init__(self, handle, video, card, key=None, fkey=None):
        self.handle = handle
        self.video = video
        self.cards = [card]
        self.key = key       # cache key, or None when caching is off
        self.fkey = fkey     # content key for feature-page inserts
        self.t_ready = 0.0   # monotonic instant the decode was harvested


class R2P1DFusingLoader(R2P1DLoader):
    """Decode stage with loader-side dynamic batching.

    Replicate & Batch without the extra stage: every incoming request
    is submitted to the decode pool immediately; requests whose decode
    has completed are harvested in FIFO order and emitted as ONE fused
    device batch — a single ``device_put``, a single downstream
    dispatch carrying a TimeCardList. This removes the per-request
    ring hop, executor thread and per-request transfers that made the
    standalone loader->Batcher->net topology host-bound on a 1-core
    host (round 4: the batched topology's device sat at 69%
    occupancy while the 2-stage pipeline's ran ~97%), while keeping
    the Batcher's device-efficiency win: a fused 6-row dispatch ran
    ~1.45x more FLOPs/s than six 1-row ones (both 2026-07, previous
    transport, not reproduced).

    Emission policy (adaptive, unlike the fixed-k Batcher):
      * the batch is full — ``fuse`` requests are ready or their
        combined clip rows reach the ring's max shape (under autotune,
        the controller's target): emit, whatever the ring's state;
        ``publish`` then blocks while the ring is full, so the ring
        still bounds what is in flight;
      * latency rules, which fire only while the executor's publish
        probe (:meth:`bind_publish_probe`) reads a free ring slot:
        emit a partial batch when nothing is left in flight, so light
        Poisson load pays no batch-fill latency, and emit when the
        oldest ready request has waited longer than ``max_hold_ms``
        (bounds p99 at mid load). While the ring is full a batch
        could not reach it anyway, so the loader keeps filling and
        looks again within ``HARVEST_TICK_S``; no probe bound (unit
        tests, no output ring) reads free;
      * a take made while the ring is full closes on a bucket
        boundary (:meth:`_plan_take`): the leftover stays at the head
        of the ready list and rides the next batch, which is due as
        soon as a slot frees; with a free slot the take is the
        longest that fits, padded to its bucket — latency first;
      * block on the oldest in-flight decode only once ``depth``
        requests are pending (backpressure toward the client queue).

    Reference lineage: batcher.py:17-34 (the fixed-k Batcher) +
    README.md:46-110 (NVVL's async loadfile) — fused into one stage
    the way NVVL fused sampling+decode+batch assembly.

    **Zero-copy staging + transfer pipeline** (rnb_tpu.staging): with
    a staging pool (default on over the native decoder), submit-time
    row planning makes the decode pool write each request directly
    into its slice of a pre-allocated slot — a full take emits the
    slot's bucket prefix with no allocation and no assembly copy —
    and ``transfer_async`` moves the ``device_put`` to a dedicated
    worker so batch N transfers while batch N+1 decodes. Completed
    emissions surface through :meth:`take_ready`, which the executor
    drains ahead of new input. README "Transfer pipeline".
    """

    #: emissions happen between model calls, so device_put can move to
    #: the transfer worker without breaking any synchronous contract
    SUPPORTS_TRANSFER_ASYNC = True

    #: the emission policy's hold/target/bucket knobs can be driven by
    #: the load-adaptive controller (rnb_tpu.autotune)
    SUPPORTS_AUTOTUNE = True

    #: this stage feeds the controller's service-time EWMA itself
    #: (batch close -> ready-queue span, _pop_ready): under
    #: transfer_async every emission surfaces via take_ready()/poll(),
    #: so the executor's stamp-based feed — which skips `flushed`
    #: emissions — would never observe a sample and the controller
    #: would price service at 0 forever; the executor must NOT also
    #: feed this stage from the TimeCard stamps (rnb_tpu.runner)
    AUTOTUNE_SELF_SERVICE = True

    #: default staging depth: one slot filling with planned decodes,
    #: one transferring, one spare so a hold-timeout partial emission
    #: cannot stall planning (double/triple buffering)
    DEFAULT_STAGING_SLOTS = 3

    GUARDED_BY = {"_out_ready": "_out_lock"}

    UNGUARDED_OK = {
        "_ready": "executor-thread confined; only the _out_ready "
                  "handoff crosses the transfer-worker boundary",
        "_inflight": "executor-thread confined (see _ready)",
        "_open_slot": "executor-thread confined (see _ready)",
        "_open_rows": "executor-thread confined (see _ready)",
        "_open_count": "executor-thread confined (see _ready)",
        "_failed": "executor-thread confined (see _ready)",
        "_stage_retries": "executor-thread confined (see _ready)",
        "_deadline_shed": "executor-thread confined (see _ready)",
        "_deferred": "executor-thread confined (see _ready)",
        "_publish_probe": "bound once by the executor thread before "
                          "its loop; read on that thread only",
        "autotune": "executor-thread confined (see _ready)",
        "ragged_stats": "executor-thread confined (see _ready)",
    }

    def __init__(self, device, fuse: int = 6, depth: Optional[int] = None,
                 max_hold_ms: float = 5.0, **kwargs):
        if kwargs.get("prefetch"):
            raise ValueError(
                "R2P1DFusingLoader manages its own decode pipeline; "
                "its in-flight window is `depth`, not `prefetch`")
        super().__init__(device, **kwargs)
        if int(fuse) < 1:
            raise ValueError("fuse must be >= 1, got %r" % (fuse,))
        self.fuse = int(fuse)
        self.depth = int(depth) if depth is not None else 2 * self.fuse
        self.max_hold_ms = float(max_hold_ms)
        self._inflight = deque()  # _FuseRecord, decode still running
        self._ready = deque()     # _FuseRecord, decode complete
        # -- zero-copy staging + transfer pipeline (rnb_tpu.staging) --
        #: the one slot shape fused planning targets: buckets are
        #: emitted as C-contiguous row prefixes of the max shape
        self._slot_shape = self._batch_shape(self.max_clips)
        self._open_slot = None   # slot currently accepting row plans
        self._open_rows = 0      # rows planned into the open slot
        self._open_count = 0     # requests planned into the open slot
        #: completed emissions awaiting pickup (take_ready/poll/flush);
        #: appended by the transfer worker under transfer_async
        self._out_ready = deque()
        self._out_lock = threading.Lock()
        self._worker = None
        if self.transfer_async:
            self._worker = TransferWorker(pool=self.staging)
        # requests whose decode failed with a *classified* error while
        # their batch was being assembled: (time_card, reason), drained
        # by the executor's take_failed() protocol (rnb_tpu.runner)
        self._failed = []
        # transient re-decode attempts performed inside _wait_contained,
        # drained by the executor's take_retries() protocol so they
        # land in the job-wide num_retries accounting
        self._stage_retries = 0
        #: (max_retries, retry_backoff_ms) — the executor copies the
        #: step's schema knobs here after construction (the knobs are
        #: schema, not model kwargs, so they never arrive via **kwargs)
        self.fault_retry_budget = (0, 0.0)
        #: load-adaptive batching controller (rnb_tpu.autotune), set
        #: by the executor via enable_autotune(); None = the static
        #: fuse/max_hold_ms emission policy exactly as configured
        self.autotune = None
        #: deadline-expired requests dropped from the ready queue
        #: before emission (rnb_tpu.health), parked for the
        #: executor's take_shed() drain — inert without deadlines
        self._deadline_shed = []
        #: the executor's peek at the output ring, ``probe(ahead) ->
        #: would a publish behind `ahead` unpublished emissions block
        #: now?`` (bind_publish_probe); None reads "free"
        self._publish_probe = None
        #: a latency rule was already held back for the batch now
        #: filling (loader.emit_deferred fires once per batch)
        self._deferred = False

    def bind_publish_probe(self, probe) -> None:
        """Executor protocol (rnb_tpu.runner): downstream
        back-pressure as the emission policy observes it."""
        self._publish_probe = probe

    def _ring_full(self) -> bool:
        """Whether an emission made now would wait for a ring slot."""
        probe = self._publish_probe
        if probe is None:
            return False
        with self._out_lock:
            ahead = len(self._out_ready)
        if self._worker is not None:
            ahead += self._worker.outstanding()
        return probe(ahead)

    def take_shed(self):
        """Executor hook (rnb_tpu.runner): requests this stage shed
        internally because their deadline expired while the loader
        held their decoded rows -> [(card, where)]."""
        out, self._deadline_shed = self._deadline_shed, []
        return out

    def _drop_expired_ready(self) -> None:
        """The 'loader hold' deadline boundary (rnb_tpu.health): a
        decoded request whose absolute deadline passed while it waited
        on the ready queue is dropped before fusing — its slot rows
        are released (the emission takes the gapped copy path, exactly
        like a contained mid-slot decode failure) and it never burns a
        transfer or downstream service. A record is only dropped when
        EVERY card riding it (leader + coalesced followers) expired:
        the rows are shared, and one live follower still needs them.
        Inert when no card carries a deadline stamp."""
        if not self._ready or not any(
                getattr(rec.cards[0], "deadline_s", None) is not None
                for rec in self._ready):
            return
        kept = deque()
        for rec in self._ready:
            if all(_deadline_expired(tc) for tc in rec.cards):
                self._drop_coalesce(rec)
                self._release_handle_slot(rec.handle)
                # a shed paged hit releases its pin before its gather
                # ever dispatches — counted hit rows therefore bound
                # gather rows from above, never equal them exactly
                self._release_handle_plan(rec.handle)
                self._deadline_shed.extend((tc, "hold")
                                           for tc in rec.cards)
            else:
                kept.append(rec)
        self._ready = kept

    def enable_autotune(self, settings) -> BatchController:
        """Executor protocol (rnb_tpu.runner): drive this stage's
        hold deadline / accumulation target with a BatchController
        over the stage's own warmed bucket set — decisions can only
        name shapes warm-up already compiled. Under ragged dispatch
        every row count hits the same executable, so the candidate
        set is continuous (1..pool_rows): hold/batch decisions stop
        being quantized to the warmed-bucket vocabulary."""
        if self.ragged:
            self.autotune = BatchController.for_stage(
                settings, tuple(range(1, self.pool_rows + 1)),
                self.pool_rows)
            return self.autotune
        self.autotune = BatchController.for_stage(
            settings, self.row_buckets, self.max_clips)
        return self.autotune

    def enable_trace(self, tracer, step_idx: int) -> None:
        """On top of the base wiring (staging occupancy): sample
        this stage's decode window — decodes in
        flight plus decoded-but-unemitted requests (deque len reads
        are GIL-atomic, safe from the sampler thread)."""
        super().enable_trace(tracer, step_idx)
        tracer.add_counter_source(
            trace.name("loader.s%d.inflight", step_idx),
            lambda: len(self._inflight) + len(self._ready))

    def _harvest(self) -> None:
        """Move decode-complete requests from in-flight to ready,
        preserving FIFO order (a slow head occupies the whole pool
        anyway, so out-of-order harvest buys nothing)."""
        while self._inflight and self._inflight[0].handle.ready:
            rec = self._inflight.popleft()
            rec.t_ready = time.monotonic()
            trace.instant("loader.decode_ready", rid=rec.cards[0].id)
            self._ready.append(rec)

    def _drop_coalesce(self, rec: "_FuseRecord") -> None:
        """Close a record's coalescing window (it is being finalized):
        later requests for its key consult the cache or re-decode."""
        if self._inflight_keys is not None:
            self._inflight_keys.pop(rec.key)

    def _park_failed(self, rec: "_FuseRecord", reason: str) -> None:
        """Every card riding this record — leader and coalesced
        followers — fails as a unit; none is ever cached. A contained
        failure releases its staging-slot rows (the slot recycles once
        its surviving batchmates are through) and any pinned page
        plan, and never stamps a feature insert."""
        self._drop_coalesce(rec)
        self._release_handle_slot(rec.handle)
        self._release_handle_plan(rec.handle)
        self._failed.extend((tc, reason) for tc in rec.cards)

    def _staging_default_slots(self) -> int:
        return self.DEFAULT_STAGING_SLOTS

    def _staging_min_slots(self) -> int:
        # _acquire_fused_slot frees slots by emitting before it ever
        # blocks, so even a single slot cannot self-deadlock
        return 1

    def _staging_shapes(self):
        # fused emissions ship bucket-sized row prefixes of ONE slot
        # shape — smaller buckets are contiguous prefix views, so no
        # per-bucket sub-pools are needed
        return [self._batch_shape(self.max_clips)]

    def _stage_target(self, n: int):
        """Submit-time row planning: place this request's rows into
        the open staging slot so the native pool decodes straight into
        its final position in the fused batch. The slot seals (next
        request opens a fresh one) exactly on the emission take rules
        — ``fuse`` requests or the row cap — so a full take is a
        contiguous row prefix and ships zero-copy."""
        if self.staging is None:
            return super()._stage_target(n)
        cap = self.max_clips
        if (self._open_slot is None or self._open_count >= self.fuse
                or self._open_rows + n > cap):
            self._open_slot = self._acquire_fused_slot()
            self._open_rows = 0
            self._open_count = 0
        slot = self._open_slot
        row0 = self._open_rows
        self.staging.add_ref(slot)
        self._open_rows += n
        self._open_count += 1
        return slot.buf[row0:row0 + n], slot, row0

    def _acquire_fused_slot(self):
        """A fresh slot for planning. On exhaustion, free slots by
        finishing our own work first (retire the oldest decode, emit)
        — the emission path is what releases slots, and it runs on
        this same executor thread, so blocking before draining would
        be a self-deadlock. Only when every slot is held by an
        in-flight transfer does this block (counted backpressure,
        bounded by the transfer worker)."""
        slot = self.staging.try_acquire(self._slot_shape)
        while slot is None:
            if self._inflight or self._ready:
                if not self._ready and self._inflight:
                    rec = self._inflight.popleft()
                    if self._wait_contained(rec):
                        rec.t_ready = time.monotonic()
                        self._ready.append(rec)
                self._harvest()
                self._emit()
                slot = self.staging.try_acquire(self._slot_shape)
                continue
            slot = self.staging.acquire(self._slot_shape)
        return slot

    def _wait_contained(self, rec: "_FuseRecord") -> bool:
        """Wait one decode; True on success. A *transient* failure
        (rnb_tpu.faults taxonomy) is retried by synchronous re-decode
        up to the step's ``fault_retry_budget``; a *permanent* failure
        (or an exhausted budget) parks the request(s) on the
        take_failed() queue instead of poisoning its batchmates or
        being mis-attributed to whichever request triggered the
        emission; unclassified errors stay fatal."""
        handle, video = rec.handle, rec.video
        try:
            handle.wait(video)
            return True
        except Exception as e:
            kind = classify_error(e)
            if kind is FATAL:
                raise
            reason = fault_reason(e)
            if kind is TRANSIENT:
                max_retries, backoff_ms = self.fault_retry_budget
                for _ in range(int(max_retries)):
                    self._stage_retries += 1
                    if backoff_ms > 0:
                        time.sleep(backoff_ms / 1000.0)
                    try:
                        # the failed handle's tickets are already
                        # retired (wait() retires before raising);
                        # re-decode synchronously into the handle
                        decoder = get_decoder(video)
                        starts = self._sample_starts(decoder, video)
                        handle.out = self._decode_sync(decoder, video,
                                                       starts)
                        handle.error = None  # recovered (sticky wait)
                        # the re-decode owns a fresh buffer; the slot
                        # rows are dead (the emission for this record
                        # takes the copy path)
                        self._release_handle_slot(handle)
                        return True
                    except Exception as e2:
                        kind2 = classify_error(e2)
                        if kind2 is FATAL:
                            raise
                        reason = fault_reason(e2)
                        if kind2 is not TRANSIENT:
                            # re-decode reached a permanent verdict:
                            # further retries cannot help
                            self._park_failed(rec, reason)
                            return False
                reason = "retries-exhausted:" + reason
            self._park_failed(rec, reason)
            return False

    def take_failed(self):
        """Drain internally-contained requests (executor protocol,
        rnb_tpu.runner._drain_stage_failures)."""
        out, self._failed = self._failed, []
        return out

    def take_retries(self) -> int:
        """Drain the internal transient-retry count (executor
        protocol): retries performed during fused-batch assembly, fed
        into the job-wide num_retries accounting."""
        n, self._stage_retries = self._stage_retries, 0
        return n

    def _plan_take(self, blocked: bool):
        """``(requests, rows)`` of the next take: the longest in-order
        prefix of the ready list within ``fuse`` requests and the row
        cap. ``blocked`` (the ring is full, so what is left behind
        loses nothing: the next batch is due when a slot frees) closes
        instead at the longest such prefix whose rows are exactly a
        row bucket, where there is one. Ragged stages ship one shape
        and have no boundary to close on."""
        cap = self.max_clips
        count = rows = 0
        on_bucket = None
        for rec in self._ready:
            n = rec.handle.n
            if count >= self.fuse or (count and rows + n > cap):
                break
            count += 1
            rows += n
            if blocked and rows in self.row_buckets:
                on_bucket = (count, rows)
        return on_bucket or (count, rows)

    def _emit(self, reason: str = "drain") -> bool:
        """Fuse ready requests (up to ``fuse`` / the ring max rows)
        into one padded batch + TimeCardList and ship it — zero-copy
        straight from the staging slot when the take is the slot's
        contiguous row prefix, else through the seed copy path. The
        finished emission lands on the ready queue (``_pop_ready``):
        synchronously after the inline transfer, or from the transfer
        worker under ``transfer_async``. Returns True when ready
        records were consumed (progress), False when nothing was
        takeable; a take whose every decode failed still returns True
        (the failures are on the take_failed() queue). ``reason``
        names the rule that fired (``full``, ``hold``, ``idle``; every
        forced path — flush, staging exhaustion, ``depth`` — is
        ``drain``) for the span's stats."""
        count, rows = self._plan_take(
            not self.ragged and self._ring_full())
        if not count:
            return False
        self._deferred = False
        with trace.span(
                "loader.emit", reason=reason, rows=rows,
                bucket=self.pool_rows if self.ragged
                else self._bucket_for(rows),
                left=sum(rec.handle.n for rec in self._ready) - rows):
            return self._emit_take(count)

    def _emit_take(self, count: int) -> bool:
        """:meth:`_emit` body (split out so that one span wraps the
        whole take/assemble/handoff): ship the first ``count`` ready
        requests."""
        take = []
        for _ in range(count):
            rec = self._ready.popleft()
            # finalizing: close the coalescing window now — by the time
            # a later same-key request arrives, the successful decode is
            # in the cache (inserted below, same call)
            self._drop_coalesce(rec)
            take.append(rec)
        rows = sum(rec.handle.n for rec in take)
        # the take loop guarantees this (submit caps each request at
        # max_clips); a silent min() here would mask clip loss instead
        # of surfacing the broken invariant
        assert rows <= self.max_clips, (rows, self.max_clips)
        for rec in take:
            if rec.handle.slot is not None \
                    and rec.handle.slot is self._open_slot:
                # taking from the open slot seals it: later submits
                # must not plan rows into a buffer that is about to
                # be (or already is) handed to a transfer
                self._open_slot = None
                break
        ok = []
        with trace.span("loader.emit_wait"):
            for rec in take:
                if self._wait_contained(rec):
                    ok.append(rec)
        if not ok:
            return True
        rows = sum(rec.handle.n for rec in ok)
        # under ragged the emission ships the ONE pool shape with an
        # explicit rows_valid; the segment table maps each constituent
        # request to its row range
        bucket = self.pool_rows if self.ragged else \
            self._bucket_for(rows)
        offsets = None
        if self.ragged:
            offsets = segment_offsets_of(rec.handle.n for rec in ok)
        if self.autotune is not None:
            # every batched emission is attributed to its shipped
            # bucket (the actual row count under ragged, where every
            # count is a legal dispatch); emissions with no preceding
            # decision (forced drains) are back-filled as immediate
            # decisions so the --check invariant decisions >=
            # emissions holds
            self.autotune.note_emission(rows if self.ragged else bucket)
        # service-span origin for the autotune estimator: the batch
        # just closed (stopped accumulating); everything from here to
        # the emission landing on the ready queue — assemble, cache
        # insert, device_put (inline or on the worker), preprocess
        # dispatch — is this stage's residual service, the term
        # decide() budgets against slo_ms alongside the residual-fill
        # wait
        t_close = time.monotonic()
        if self._stamp_step is not None:
            # phase-refinement stamps for every card shipping in this
            # emission: its decode ended at the record's harvest
            # instant (epoch-converted from the monotonic t_ready, and
            # clamped so a follower swallowed after the decode reads a
            # zero-length decode phase), and its hold ended NOW — the
            # batch just closed and the transfer path begins
            now_epoch = time.time()
            now_mono = time.monotonic()
            step = self._stamp_step
            for rec in ok:
                decoded_at = now_epoch - max(0.0, now_mono - rec.t_ready)
                for tc in rec.cards:
                    _record_clamped(tc, "decode%d_done" % step,
                                    decoded_at)
                    _record_clamped(tc, "transfer%d_start" % step,
                                    now_epoch)
        out, slot = self._assemble(ok, rows, bucket)
        gather_plans = None
        insert_jobs = None
        if self.cache is not None and self.cache.paged:
            # paged cache: hit rows overlay from the clip arena and
            # miss rows publish into pages — both on DEVICE, after
            # the pool's transfer (_overlay_pages in the transfer
            # body), so the host-side insert/hit memcpys of the blob
            # path below are deleted outright. Insert-after-success
            # holds: the jobs run only once device_put returned.
            gather_plans = []
            insert_jobs = []
            for i, rec in enumerate(ok):
                h = rec.handle
                row0 = int(offsets[i])
                if h.gather_plan is not None:
                    gather_plans.append((row0, h.gather_plan))
                    h.gather_plan = None
                elif rec.key is not None:
                    insert_jobs.append((rec.key, row0, h.n))
                self._stamp_feature_insert(rec.cards[0], rec.fkey,
                                           row0, h.n)
        elif self.cache is not None:
            # insert-after-success: only decodes that reached this
            # point populate the cache. Both insert flavors copy the
            # rows out of the slot BEFORE the transfer/recycle below,
            # so a cached entry can never alias recycled staging
            # memory. Ragged entries are host row extents (exactly n
            # rows, no bucket padding, no insert-time device_put —
            # hits re-enter the pool fill); bucketed entries stay the
            # padded device batch hits serve zero-copy.
            for rec in ok:
                if rec.key is not None:
                    n = rec.handle.n
                    if self.ragged:
                        self.cache.insert_rows(rec.key,
                                               rec.handle.out, n)
                    else:
                        self.cache.insert_host(
                            rec.key, rec.handle.out, n,
                            self._batch_shape(self._bucket_for(n)),
                            dtype=self._wire_dtype)
        cards = []
        for rec in ok:
            cards.extend(rec.cards)
        self._note_emission_padding(rows, bucket, cards)
        if slot is not None:
            # the taken rows are consumed once the transfer below
            # confirms; the begin/finish_transfer hold keeps the slot
            # unreusable until then, so the refs can retire now
            self.staging.begin_transfer(slot)
            for rec in ok:
                self._release_handle_slot(rec.handle)
        # the controller's service estimator is keyed by the same
        # vocabulary its decisions use: the shipped bucket — or, under
        # ragged, the VALID row count (every emission ships the pool
        # shape, but with a chunked network body the real service
        # scales with valid rows; keying all samples at pool_rows
        # would blend every candidate's estimate into one EWMA)
        service_key = rows if self.ragged else bucket
        if self._worker is not None:
            # pipelined handoff: the worker transfers batch N while
            # this thread plans/harvests batch N+1
            self._worker.submit(
                lambda: self._transfer_job(out, slot, rows, cards,
                                           service_key, t_close,
                                           offsets, gather_plans,
                                           insert_jobs))
            return True
        self._transfer_sync(out, slot, rows, cards, service_key,
                            t_close, offsets, gather_plans,
                            insert_jobs)
        return True

    def _min_live_row(self, slot) -> int:
        """Lowest row of a not-yet-taken decode planned into ``slot``
        (records still in the ready/in-flight windows); the slot's row
        capacity when none. Bounds how far an emission may read/zero
        the slot without racing a live decode."""
        lo = slot.buf.shape[0]
        for rec in self._ready:
            h = rec.handle
            if h.slot is slot and h.row0 < lo:
                lo = h.row0
        for rec in self._inflight:
            h = rec.handle
            if h.slot is slot and h.row0 < lo:
                lo = h.row0
        return lo

    def _assemble(self, ok, rows: int, bucket: int):
        """The fused batch bytes for one emission: ``(array, slot)``.
        A non-None slot means zero-copy — the array is the slot's
        C-contiguous bucket prefix, assembled by the decoder itself.
        None means the copy fallback ran: non-native decodes, re-decoded
        retries, partial-slot takes (hold-timeout leftovers), a
        contained failure's row gap, or staging disabled."""
        slot = ok[0].handle.slot
        if slot is not None and ok[0].handle.row0 == 0 \
                and bucket <= slot.buf.shape[0]:
            staged, row = True, 0
            for rec in ok:
                h = rec.handle
                if h.slot is not slot or h.row0 != row:
                    staged = False  # gap: failure/retry/partial history
                    break
                row += h.n
            if staged and bucket > self._min_live_row(slot):
                # the transfer window would cover rows a live decode
                # is still writing — only possible after a partial
                # (hold-timeout) take left batchmates in flight
                staged = False
            if staged:
                if bucket > rows and not self.ragged:
                    # seed byte parity: padding rows stay zeroed.
                    # Under ragged the consumer's kernel masks the
                    # pool tail, so the memset is skipped
                    slot.buf[rows:bucket] = 0
                self.staging.note_staged()
                return slot.buf[:bucket], slot
        # copy fallback (RNB-H007 baselined): rows [0, rows) are
        # overwritten below; only the padding tail needs zeroing
        out = np.empty(self._batch_shape(bucket),
                       dtype=self._wire_dtype)
        row = 0
        for rec in ok:
            n = rec.handle.n
            out[row:row + n] = rec.handle.out[:n]
            row += n
        if row < out.shape[0] and not self.ragged:
            # ragged consumers mask the pool tail in-jit; only the
            # bucketed path needs zeroed padding bytes
            out[row:] = 0
        for rec in ok:
            # rows copied out: slot references retire immediately
            self._release_handle_slot(rec.handle)
        if self.staging is not None:
            self.staging.note_copied()
        return out, None

    def _overlay_pages(self, batch, gather_plans, insert_jobs):
        """Paged-cache device work for one emission, strictly after
        its pool transfer: overlay hit rows from the clip arena (the
        only place they ever materialize — their slot rows shipped
        uninitialized) and publish miss rows into pages
        (insert-after-success: decode and transfer both completed by
        now). Runs before the normalize dispatch, so gathered hit
        rows feed the identical jitted path a miss feeds."""
        if gather_plans:
            src = np.full((int(batch.shape[0]),), -1, np.int32)
            for row0, plan in gather_plans:
                src[row0:row0 + plan.valid] = plan.src_rows
            batch = self._clip_arena.gather(batch, src)
            for _, plan in gather_plans:
                # dispatched: the gather captured the slab value, so
                # the pins can release (rnb_tpu.pager limbo rule)
                plan.release()
        if insert_jobs:
            for key, row0, n in insert_jobs:
                self.cache.insert_pages(key, batch, row0, n)
        return batch

    def _transfer_sync(self, out, slot, rows: int, cards,
                       bucket: int, t_close: float,
                       offsets=None, gather_plans=None,
                       insert_jobs=None) -> None:
        """Inline transfer on the executor thread (transfer_async
        off): the seed path minus the assembly — the transfer is
        confirmed lazily at the slot's next acquire, so the executor
        still never blocks on transfer completion."""
        jax, _ = _jax_numpy()
        with trace.span("loader.transfer"):
            batch = jax.device_put(out, self._jax_device)
        if slot is not None:
            self.staging.finish_transfer(slot, batch)
        if gather_plans is not None or insert_jobs is not None:
            batch = self._overlay_pages(batch, gather_plans,
                                        insert_jobs)
        if self._stamp_step is not None:
            at = time.time()
            for tc in cards:
                _record_clamped(tc, "transfer%d_done" % self._stamp_step,
                                at)
        if self._preprocess is not None or \
                self._preprocess_ragged is not None:
            batch = self._normalize_emission(batch, rows)
        self._push_ready(((self._wrap_batch(batch, rows, offsets),),
                          None, TimeCardList(cards)),
                         bucket, time.monotonic() - t_close)

    def _transfer_job(self, out, slot, rows: int, cards,
                      bucket: int, t_close: float,
                      offsets=None, gather_plans=None,
                      insert_jobs=None) -> None:
        """Transfer-worker body: issue the device_put for batch N
        while the executor decodes batch N+1 into the next slot;
        confirm completion (alias-probed) before releasing the slot's
        transfer hold. Runs off the executor thread."""
        jax, _ = _jax_numpy()
        with trace.span("loader.transfer"):
            batch = jax.device_put(out, self._jax_device)
        if slot is not None:
            self.staging.confirm_now(slot, batch)
        if gather_plans is not None or insert_jobs is not None:
            batch = self._overlay_pages(batch, gather_plans,
                                        insert_jobs)
        if self._stamp_step is not None:
            at = time.time()
            for tc in cards:
                _record_clamped(tc, "transfer%d_done" % self._stamp_step,
                                at)
        if self._preprocess is not None or \
                self._preprocess_ragged is not None:
            batch = self._normalize_emission(batch, rows)
        self._push_ready(((self._wrap_batch(batch, rows, offsets),),
                          None, TimeCardList(cards)),
                         bucket, time.monotonic() - t_close)

    def _push_ready(self, emission, bucket=None,
                    service_s=None) -> None:
        """Queue a finished emission; ``bucket``/``service_s`` carry
        the batch-close -> ready service span alongside it. The span
        is measured where completion happens (possibly the transfer
        worker thread) but fed to the single-threaded controller only
        at ``_pop_ready``, on the owning executor thread."""
        with self._out_lock:
            self._out_ready.append((emission, bucket, service_s))

    def _pop_ready(self):
        with self._out_lock:
            if self._out_ready:
                emission, bucket, service_s = self._out_ready.popleft()
            else:
                return None
        if self.autotune is not None and bucket is not None:
            # self-reported service estimator: under transfer_async
            # every emission surfaces here (never through a stamp-
            # bearing __call__ return), so the runner's stamp-based
            # feed would otherwise starve and service_for() would
            # stay optimistically 0 — the loader reports its own
            # close->ready span instead (AUTOTUNE_SELF_SERVICE)
            self.autotune.observe_service(bucket, service_s)
        return emission

    def take_ready(self):
        """Executor protocol (rnb_tpu.runner): a completed fused
        emission ready to publish, or None. Drained at the top of the
        hot loop so finished transfers publish ahead of new input.
        Re-raises transfer-pipeline failures on the executor thread —
        a dead worker must abort the job, not hang it."""
        if self._worker is not None:
            self._worker.raise_if_failed()
        if self.staging is not None:
            self.staging.raise_if_failed()
        return self._pop_ready()

    def _emit_hit(self, entry, time_card):
        """A cache hit emits immediately as its own dispatch: there is
        no decode to overlap and no host work to amortize, so holding
        it for fusion would only add latency. Wrapped in a TimeCardList
        for schema uniformity with fused emissions."""
        tensors, non_tensors, tc = self._materialize_hit(entry, time_card)
        return tensors, non_tensors, TimeCardList([tc])

    #: harvest-check tick while decodes are in flight but nothing is
    #: ready: bounds how late a completed decode is noticed
    HARVEST_TICK_S = 0.005

    def next_deadline_s(self):
        """Seconds until this stage next needs an idle poll, or None
        when it holds no work. The executor shrinks its queue-poll
        timeout to this, so hold-timeout emissions fire ~on time
        instead of on the next 50 ms poll tick — the round-5 frontier
        measured that granularity as the light-load p99 floor
        (57-61 ms at 111 req/s vs the 5-8 ms configured hold)."""
        with self._out_lock:
            if self._out_ready:
                return 0.0  # a completed emission awaits publishing
        self._harvest()  # peek-only: fresh view of completed decodes
        if self._ready:
            if self._ring_full():
                # the latency rules wait for a slot: look again within
                # a tick (a full batch emits on the arrival that fills
                # it, not on this clock)
                return self.HARVEST_TICK_S
            if not self._inflight:
                return 0.0  # nothing else can fuse: emit now
            waited = time.monotonic() - self._ready[0].t_ready
            if self.autotune is not None:
                # the executor's poll clamp derives from the
                # controller's deadline, not the static constant —
                # peek: this runs every poll tick, and counting ticks
                # as decisions would corrupt the Autotune: accounting
                dec = self.autotune.peek(
                    len(self._ready),
                    sum(rec.handle.n for rec in self._ready), waited)
                remaining = max(0.0, dec.hold_s - waited)
            else:
                remaining = max(0.0, self.max_hold_ms / 1000.0 - waited)
            # two triggers race: the hold expiry AND an in-flight
            # decode completing (which can satisfy the fuse/rows/
            # nothing-in-flight rules early) — bound by the sooner
            return min(remaining, self.HARVEST_TICK_S)
        if self._inflight:
            return self.HARVEST_TICK_S
        if self._worker is not None and self._worker.outstanding():
            return self.HARVEST_TICK_S  # a transfer is still in flight
        return None

    def poll(self):
        """Idle tick from the executor (no arrival within its queue
        poll window): emit a held batch that has met an emission rule
        — most importantly the hold-timeout, which otherwise could
        only fire on the NEXT arrival and would pay a full
        inter-arrival gap instead of max_hold_ms (+ the executor's
        poll granularity). Returns an emission or None (an emission
        handed to the transfer worker surfaces on a later poll /
        take_ready once its transfer completes)."""
        out = self._pop_ready()
        if out is not None:
            return out
        self._harvest()
        self._drop_expired_ready()
        if not self._ready:
            return None
        rows_ready = sum(rec.handle.n for rec in self._ready)
        waited_s = time.monotonic() - self._ready[0].t_ready
        if self.autotune is not None:
            # controller-supplied deadline and accumulation target
            # replace the static max_hold_ms / fixed-fuse comparison:
            # immediate dispatch when growing the batch cannot meet
            # the latency budget, a grown target when it can — always
            # capped by the static fuse/row ceilings
            dec = self.autotune.decide(len(self._ready), rows_ready,
                                       waited_s)
            full = rows_ready >= dec.target_rows
            held_out = waited_s >= dec.hold_s
        else:
            full = False
            held_out = waited_s * 1000.0 > self.max_hold_ms
        if (full or len(self._ready) >= self.fuse
                or rows_ready >= self.max_clips):
            reason = "full"
        elif not held_out and self._inflight:
            return None
        elif self._ring_full():
            # a latency rule fired, and emitting could buy no latency:
            # the batch cannot reach the ring before a slot frees, so
            # it keeps filling (next_deadline_s looks again in a tick)
            if not self._deferred:
                self._deferred = True
                trace.instant("loader.emit_deferred")
            return None
        else:
            reason = "hold" if held_out else "idle"
        self._emit(reason)
        return self._pop_ready()

    def __call__(self, tensors, non_tensors, time_card):
        video = str(non_tensors)
        fkey, fplan = self._feature_probe(video)
        if fplan is not None:
            # feature-page hit: no decode, no transfer, no downstream
            # forward — emit standalone immediately (holding it for
            # fusion would only add latency; there is nothing to
            # amortize), like the bucketed _emit_hit below
            tensors_out, nt, tc = self._materialize_feature(
                fplan, time_card)
            return tensors_out, nt, TimeCardList([tc])
        key, entry = self._cache_lookup(video, key=fkey)
        if entry is not None and self.ragged:
            # ragged hit: the hit fills its pool rows like a decode
            # that completed instantly — it rides the next fused
            # emission (one pool transfer for hits and misses alike)
            # instead of dispatching standalone.
            n = entry.valid
            time_card.num_clips = n
            time_card.cache_hit = True
            if self.ragged_stats is not None:
                self.ragged_stats["cache_hit_rows"] += n
            target, hit_slot, hit_row0 = self._stage_target(n)
            if self.cache.paged:
                # zero-copy paged hit: the reserved slot rows ship
                # UNINITIALIZED — the pinned plan rides the handle and
                # the entry's page rows overlay them on device, after
                # the pool's transfer (_overlay_pages). No host byte
                # of this request ever moves.
                handle = _DecodeHandle(target, n, slot=hit_slot,
                                       row0=hit_row0)
                handle.gather_plan = entry
            else:
                # blob hit: the decode is skipped; the memcpy into
                # the slot slice is the whole cost
                np.copyto(target, entry.batch[:n])
                handle = _DecodeHandle(target, n, slot=hit_slot,
                                       row0=hit_row0)
            self._stamp_decode_done(time_card)
            if self.autotune is not None:
                self.autotune.observe_rows(n)
            rec = _FuseRecord(handle, video, time_card, key=None,
                              fkey=fkey)
            # join the in-flight window IN ARRIVAL ORDER (the handle
            # is already complete, so harvest promotes it at its FIFO
            # turn): jumping straight to _ready would reorder the
            # slot's planned row ranges and force every such take off
            # the zero-copy staged path onto the assembly-copy
            # fallback
            self._inflight.append(rec)
            out = self.poll()
            if out is not None:
                return out
            return None, None, None
        if entry is not None:
            # hit: serve from the device-resident entry right now — no
            # decode, no transfer, no fuse wait
            return self._emit_hit(entry, time_card)
        if key is not None:
            time_card.cache_hit = False
            live = self._inflight_keys.get(key)
            if live is not None:
                # coalesce: park this request on the in-flight decode;
                # it rides the leader's fused emission through the
                # TimeCardList fan-out (one decode, one row range, N
                # stamped cards)
                time_card.num_clips = live.handle.n
                time_card.cache_coalesced = True
                self.cache.note_coalesced()
                live.cards.append(time_card)
                out = self.poll()
                if out is not None:
                    return out
                return None, None, None
        handle = self._decode_submit(video, time_card)
        if self.autotune is not None:
            # rows-per-request estimator: converts a bucket-growth
            # target into a residual request count (coalesced
            # followers add cards, not rows, so they do not feed this)
            self.autotune.observe_rows(handle.n)
        rec = _FuseRecord(handle, video, time_card, key=key, fkey=fkey)
        if key is not None:
            self._inflight_keys.put(key, rec)
        self._inflight.append(rec)
        out = self.poll()  # harvest + the emission rules
        if out is not None:
            return out
        if len(self._inflight) >= self.depth:
            # backpressure: retire the oldest decode before accepting
            # more work, then ship what is ready
            rec = self._inflight.popleft()
            if self._wait_contained(rec):
                rec.t_ready = time.monotonic()
                self._ready.append(rec)
            self._harvest()
            self._emit()
            out = self._pop_ready()
            if out is not None:
                return out
        return None, None, None

    #: ready-queue poll tick while waiting on the transfer worker at
    #: end-of-stream — bounded by one transfer's latency
    FLUSH_TICK_S = 0.0005

    def flush(self):
        """End-of-stream: drain everything, one fused batch per call
        (the executor calls flush() until it returns None). Under
        ``transfer_async`` this also drains the transfer worker —
        emissions it still holds surface here before the stage
        reports itself dry."""
        out = self._pop_ready()
        if out is not None:
            return out
        while self._inflight:
            rec = self._inflight.popleft()
            if self._wait_contained(rec):
                rec.t_ready = time.monotonic()
                self._ready.append(rec)
        while True:
            if self._ready:
                self._emit()
                out = self._pop_ready()
                if out is not None:
                    return out
                # that whole batch failed (cards on the take_failed()
                # queue) or it was handed to the transfer worker —
                # keep draining either way
                continue
            if self._worker is not None and self._worker.outstanding():
                self._worker.raise_if_failed()
                time.sleep(self.FLUSH_TICK_S)
                out = self._pop_ready()
                if out is not None:
                    return out
                continue
            if self._worker is not None:
                # a failing last job can drop outstanding() to 0 with
                # its error recorded but not yet observed — re-check
                # before reporting a clean drain, or the runner would
                # break out silently with the batch's requests lost
                self._worker.raise_if_failed()
            if self.staging is not None:
                self.staging.raise_if_failed()
            return None

    def discard_pending(self) -> None:
        """Abort path (called from the executor's finally): retire
        every submitted decode so native tickets don't pin buffers
        forever — and every staging-slot reference, then stop the
        transfer worker (draining its queue keeps the slot accounting
        balanced). Ready-but-unemitted handles hold un-retired tickets
        too — harvest only peeks, it never waits."""
        for rec in list(self._inflight) + list(self._ready):
            self._drop_coalesce(rec)
            self.discard(rec.handle, rec.video)
        self._inflight.clear()
        self._ready.clear()
        self._open_slot = None
        if self._worker is not None:
            self._worker.close()
        with self._out_lock:
            # abort path: completed-but-unpublished emissions are
            # dropped, exactly like ready-but-unemitted records
            self._out_ready.clear()


class R2P1DRunner(StageModel):
    """Neural-net stage over any contiguous layer range [start..end].

    Reference equivalent: R2P1DRunner (models/r2p1d/model.py:20-84).
    Weights come from the shared checkpoint filtered to the range;
    replicas share one executable and one device parameter copy.
    ``max_rows`` must match the row count this stage actually receives
    (max clips, or the segment row count under segment parallelism) so
    warm-up compiles the exact shape.
    """

    #: dispatches can arrive as a flat row pool at ONE compiled shape
    #: (RaggedBatch) — the stage then warms exactly one executable and
    #: its yuv420 fused ingest masks the pool tail via the ragged
    #: primitive (root 'ragged' config key, rnb_tpu.ops.ragged)
    SUPPORTS_RAGGED = True

    #: under pager.feature_cache this stage is the feature-page
    #: consumer: it inserts its output rows after each successful
    #: forward and serves feature hits by gathering them back
    #: (rnb_tpu.pager; enable_pager below)
    SUPPORTS_PAGER = True

    #: this stage declares a partition spec for the step-level `shard`
    #: key (rnb_tpu.parallel.shardplan): temporal conv kernels and the
    #: head shard their output-channel axis. rnb-lint RNB-G010 rejects
    #: `shard` on steps whose model class does not declare this.
    SUPPORTS_SHARD = True

    def __init__(self, device, start_index: int = 1,
                 end_index: int = NUM_LAYERS,
                 num_classes: int = KINETICS_CLASSES,
                 layer_sizes=R18_LAYER_SIZES,
                 max_rows: int = MAX_CLIPS,
                 consecutive_frames: int = CONSECUTIVE_FRAMES,
                 num_warmups: int = NUM_WARMUPS,
                 ckpt_path: Optional[str] = None,
                 row_buckets=None, factored_shortcut: bool = False,
                 pixel_path: str = "rgb",
                 ragged: bool = False, ragged_pool_rows=None,
                 ragged_chunk_rows=None, dct_coeffs_per_frame=None,
                 shard_devices=None, shard_degree=None,
                 shard_axis: str = "tp",
                 shard_hbm_budget_mb=None,
                 **kwargs):
        super().__init__(device)
        import jax
        if not (1 <= start_index <= end_index <= NUM_LAYERS):
            raise ValueError("invalid layer range [%s..%s]"
                             % (start_index, end_index))
        if pixel_path not in ("rgb", "yuv420", "dct"):
            raise ValueError("pixel_path must be 'rgb', 'yuv420' or "
                             "'dct', got %r" % (pixel_path,))
        if pixel_path in ("yuv420", "dct") and start_index != 1:
            raise ValueError("pixel_path=%r fuses the ingest in "
                             "front of layer 1; a [%d..%d] stage "
                             "receives activations, not frames"
                             % (pixel_path, start_index, end_index))
        if dct_coeffs_per_frame is not None and pixel_path != "dct":
            raise ValueError("dct_coeffs_per_frame only applies to "
                             "pixel_path='dct'")
        self.start_index = int(start_index)
        self.end_index = int(end_index)
        self.max_rows = int(max_rows)
        self.pixel_path = pixel_path
        self.dct_coeffs_per_frame = dct_coeffs_per_frame
        # Ragged row-pool dispatch (rnb_tpu.ops.ragged): the stage's
        # input is always the ONE pool shape (== the declared max row
        # axis) plus a traced rows_valid scalar — one warmup compile
        # covers every batch composition, and for yuv420 the fused
        # ingest's Pallas grid skip spends no arithmetic on pad rows.
        self.ragged = bool(ragged)
        self.pool_rows = (resolve_pool_rows(ragged_pool_rows,
                                            self.max_rows, "max_rows")
                          if self.ragged else None)
        # the ragged applier's dynamic row-tile grid: None = auto
        # (default_ragged_chunk), 0 = whole-pool apply, else a divisor
        # of the pool capacity
        self.ragged_chunk_rows = 0
        if self.ragged:
            if ragged_chunk_rows is None:
                self.ragged_chunk_rows = default_ragged_chunk(
                    self.pool_rows)
            else:
                self.ragged_chunk_rows = int(ragged_chunk_rows)
                if self.ragged_chunk_rows < 0 or (
                        self.ragged_chunk_rows
                        and self.pool_rows % self.ragged_chunk_rows):
                    raise ValueError(
                        "ragged_chunk_rows=%r must be 0 (whole-pool "
                        "apply) or a positive divisor of pool_rows=%d"
                        % (ragged_chunk_rows, self.pool_rows))
        # Intra-stage tensor parallelism (rnb_tpu.parallel.shardplan):
        # shard_degree=None means the step declared no `shard` key at
        # all — a declared degree (1 included) arms the feasibility
        # gate and the Shard: accounting, so an operator iterating
        # degrees sees the same telemetry shape at every point
        self.shard_declared = shard_degree is not None
        self.shard_degree = int(shard_degree) if self.shard_declared \
            else 1
        self.shard_axis = str(shard_axis)
        self.shard_hbm_budget_mb = (
            float(shard_hbm_budget_mb)
            if shard_hbm_budget_mb is not None else None)
        if self.shard_degree < 1:
            raise ValueError("shard_degree must be >= 1, got %r"
                             % (shard_degree,))
        if self.shard_degree > 1:
            from rnb_tpu.parallel.shardplan import validate_degree
            validate_degree(self.shard_degree, start_index, end_index,
                            num_classes)
            if self.ragged and self.ragged_chunk_rows:
                if ragged_chunk_rows is not None:
                    raise ValueError(
                        "ragged_chunk_rows=%r cannot be combined with "
                        "shard_degree=%d: the sharded applier is ONE "
                        "whole-pool program (chunking would change the "
                        "op graph and break bit parity with the "
                        "unsharded forward)"
                        % (ragged_chunk_rows, self.shard_degree))
                # the auto-chunk default collapses to whole-pool apply
                self.ragged_chunk_rows = 0
        layer_sizes = tuple(layer_sizes)
        self._jax_device = _resolve(device)
        #: the network-shape arguments this stage compiled — the
        #: feature cache's fingerprint, and what the analytic FLOP walk
        #: (rnb_tpu/models/r2p1d/flops.py) takes
        self._flops_args = dict(
            consecutive_frames=int(consecutive_frames),
            num_classes=int(num_classes),
            layer_sizes=layer_sizes,
            factored_shortcut=bool(factored_shortcut))
        # factored_shortcut matches converted reference checkpoints
        # (models/r2p1d/convert.py); default is the plain projection
        self._merge = None
        self._input_sharding = None
        self._shard_mesh = None
        if self.shard_degree > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            from rnb_tpu.parallel.shardplan import (
                build_shard_mesh, make_sharded_apply, make_merge,
                shard_variables)
            if shard_devices is not None:
                ring = [_resolve(DeviceSpec(d)) for d in shard_devices]
            else:
                ring = list(jax.devices()[:self.shard_degree])
            if len(ring) != self.shard_degree:
                raise ValueError(
                    "shard_degree=%d needs exactly that many devices, "
                    "got %d" % (self.shard_degree, len(ring)))
            self._shard_mesh = build_shard_mesh(ring, self.shard_degree,
                                                self.shard_axis)
            host_vars = _shared_params(self.start_index, self.end_index,
                                       num_classes, layer_sizes,
                                       ckpt_path, self._jax_device,
                                       bool(factored_shortcut))
            self._variables = shard_variables(host_vars,
                                              self._shard_mesh,
                                              self.shard_axis)
            self._apply = make_sharded_apply(
                self.start_index, self.end_index, num_classes,
                layer_sizes, self._shard_mesh,
                factored_shortcut=bool(factored_shortcut),
                pixel_path=pixel_path, ragged=self.ragged,
                axis_name=self.shard_axis)(self._variables)
            if self.end_index == NUM_LAYERS:
                self._merge = make_merge(self._shard_mesh,
                                         self.shard_axis)
            self._input_sharding = NamedSharding(self._shard_mesh,
                                                 PartitionSpec())
        else:
            self._apply = _shared_apply(self.start_index, self.end_index,
                                        num_classes, layer_sizes,
                                        bool(factored_shortcut),
                                        pixel_path=pixel_path,
                                        ragged=self.ragged,
                                        ragged_chunk=self.ragged_chunk_rows)
            self._variables = _shared_params(self.start_index,
                                             self.end_index,
                                             num_classes, layer_sizes,
                                             ckpt_path, self._jax_device,
                                             bool(factored_shortcut))
        # warm-up on the exact steady-state shape and dtype — both come
        # from the same static declarations (input_shape_for /
        # input_dtype_for) the pipeline checker matches against the
        # upstream step, so the compiled signature and the declared
        # wire contract can never diverge. A wrong-shape/dtype dummy
        # would compile a signature the hot loop never uses and pay the
        # real compile on the first request instead.
        self._steady_shape = self.input_shape_for(
            start_index=self.start_index, max_rows=self.max_rows,
            consecutive_frames=consecutive_frames,
            pixel_path=self.pixel_path,
            dct_coeffs_per_frame=self.dct_coeffs_per_frame)[0]
        import jax.numpy as jnp
        warm_dtype = getattr(jnp, self.input_dtype_for(
            start_index=self.start_index, pixel_path=self.pixel_path))
        self._warm_dtype = warm_dtype
        # match the loader's row bucketing: compile one executable per
        # bucket row count so no compile lands in the measured window.
        # Under ragged dispatch the warmup matrix collapses to the ONE
        # pool shape — any row_buckets in the config are the bucketed
        # counterfactual, never warmed shapes — which is exactly what
        # the Compiles: accounting asserts at runtime.
        if self.ragged:
            warm_rows = (self.pool_rows,)
        else:
            warm_rows = _normalize_row_buckets(row_buckets,
                                               self.max_rows,
                                               "max_rows")
        # feature pages (rnb_tpu.pager), wired via enable_pager()
        self.pager = None
        self._feature_arena = None
        self._logit_pool = None
        # Shard feasibility gate + accounting: a declared `shard` key
        # (any degree, 1 included) projects the per-device HBM
        # footprint with the ONE formula the planner also uses
        # (shardplan.projected_device_mb) and — when hbm_budget_mb is
        # armed — REJECTS the launch when the projection does not fit.
        # This is the honest "this stage does not fit at this degree"
        # failure the headline shard config demonstrates at degree 1.
        self.shard_stats = None
        if self.shard_declared:
            from rnb_tpu.parallel.shardplan import (
                min_feasible_degree, projected_device_mb,
                split_param_bytes)
            rep_bytes, sh_bytes = split_param_bytes(self._variables)
            pool_bytes = 0
            if self.ragged:
                per_row = int(np.dtype(warm_dtype).itemsize)
                for extent in self._steady_shape[1:]:
                    per_row *= int(extent)
                pool_bytes = int(self.pool_rows) * per_row
            projected = projected_device_mb(rep_bytes, sh_bytes,
                                            pool_bytes,
                                            self.shard_degree)
            floor = 1
            if self.shard_hbm_budget_mb is not None:
                floor = min_feasible_degree(
                    rep_bytes, sh_bytes, pool_bytes,
                    self.shard_hbm_budget_mb)
            self.shard_stats = {
                "degree": self.shard_degree,
                "axis": self.shard_axis,
                "gathers": 0,
                "collective_ms": 0.0,
                "rows": 0,
                "budget_mb": self.shard_hbm_budget_mb,
                "projected_mb": projected,
                "replicated_bytes": int(rep_bytes),
                "sharded_bytes": int(sh_bytes),
                "pool_bytes": int(pool_bytes),
                "min_degree": floor if floor is not None else 0,
            }
            if self.shard_hbm_budget_mb is not None \
                    and projected > self.shard_hbm_budget_mb:
                feasible = min_feasible_degree(
                    rep_bytes, sh_bytes, pool_bytes,
                    self.shard_hbm_budget_mb)
                raise ValueError(
                    "shard launch rejected: projected per-device HBM "
                    "%.1f MiB at shard degree %d exceeds "
                    "hbm_budget_mb=%.1f for layers [%d..%d] "
                    "(replicated %.1f MiB + sharded %.1f MiB / degree "
                    "+ pool %.1f MiB); smallest feasible degree of "
                    "(1, 2, 4, 8): %s"
                    % (projected, self.shard_degree,
                       self.shard_hbm_budget_mb, self.start_index,
                       self.end_index, rep_bytes / 2**20,
                       sh_bytes / 2**20, pool_bytes / 2**20,
                       feasible if feasible is not None else "none"))
        #: set by the executor's bind_shard_step() so the merge
        #: collective's trace span carries the step index even on
        #: trace-disabled runs
        self._tr_collective = None
        #: jit-entry signature accounting (rnb_tpu.compilestats):
        #: distinct applier input signatures == executables this stage
        #: requires; frozen by the executor at measured-window start
        self.compiles = SignatureTracker()
        #: the executables warm-up compiled, one a warmed row count, of
        #: a stage that ends the network: their text is the scope table
        #: the stage writes when it has drained (rnb_tpu.hloscopes)
        self._warmed_programs = []
        self._log_dir = None
        # set-up's spans of a bucket's warm-up (the launcher's Tracer
        # collects them until the start barrier)
        step = trace.building_step()
        tr_program = trace.name("setup.s%d.program", step)
        tr_scopes = trace.name("setup.s%d.scopes", step)
        tr_first_call = trace.name("setup.s%d.first_call", step)
        for rows in warm_rows:
            host = np.zeros((rows,) + self._steady_shape[1:],
                            warm_dtype)
            # the declared shape vocabulary is observed even under
            # num_warmups=0 (warmup explicitly opted out): the
            # steady_new accounting flags OUT-OF-VOCABULARY
            # signatures — drift — not the expected first-call
            # compile of an unwarmed run
            self.compiles.observe(host)
            if num_warmups > 0:
                with trace.span(tr_program, rows=rows):
                    self._warm_bucket(rows, host, num_warmups,
                                      tr_first_call, tr_scopes)

    def _warm_bucket(self, rows: int, host, num_warmups: int,
                     tr_first_call: str, tr_scopes: str) -> None:
        """One row count's executable, from nothing to warmed."""
        import jax
        if self._input_sharding is not None:
            dummy = jax.device_put(host, self._input_sharding)
        else:
            dummy = jax.device_put(host, self._jax_device)
        args = (self._variables, dummy) + (
            (np.int32(rows),) if self.ragged else ())
        with trace.span(tr_first_call):
            for _ in range(num_warmups):
                out = self._apply(*args)
                jax.block_until_ready(out)
                if self._merge is not None:
                    # warm the merge collective too: its compile
                    # must not land inside the measured window
                    jax.block_until_ready(self._merge(out))
        if self.end_index == NUM_LAYERS:
            # not a second compile: the same arguments find the
            # executable the call above made in jit's cache
            # (tests/test_r2p1d_scopes.py counts the backend's
            # compilations)
            with trace.span(tr_scopes):
                self._warmed_programs.append(
                    self._apply.lower(*args).compile())

    def input_shape(self):
        return (self._steady_shape,)

    def bind_log_dir(self, log_dir: str) -> None:
        self._log_dir = log_dir

    def scope_table(self) -> dict:
        """{"<instruction> <result shape>": op_name} over the programs
        warm-up compiled: which named scope (``ingest``, ``stem``,
        ``stage2`` ... ``stage5``, ``head``) each instruction of each
        row bucket's program came from."""
        table = {}
        for program in self._warmed_programs:
            table.update(hloscopes.scopes_of_hlo(program.as_text()))
        return table

    def finalize(self) -> None:
        """The stage has drained: a stage that ends the network writes
        the scope table of its programs beside the run's logs."""
        if self._log_dir is not None and self._warmed_programs:
            hloscopes.write_table(self._log_dir, self.scope_table())

    def bind_shard_step(self, step_idx: int) -> None:
        """Executor protocol (rnb_tpu.runner): hand the stage its step
        index so the merge collective can be host-timed under the
        ``exec{i}.collective`` trace span. Called unconditionally
        (unlike enable_trace) because the collective tax must reach
        the span and the Shard: accounting even on trace-disabled
        runs; a no-op for unsharded stages."""
        if self._merge is None:
            return
        self._tr_collective = trace.name("exec%d.collective",
                                         int(step_idx))

    def enable_pager(self, pager) -> None:
        """Executor protocol (rnb_tpu.runner): attach this stage as
        the feature-page consumer. Its config fingerprint keys every
        entry (two configs can never alias), its ``features`` arena
        holds output logit rows written strictly after each
        successful forward, and a feature hit gathers those exact
        rows back over a preallocated zero pool — bit-identical to
        re-running the forward, because they ARE the original
        forward's rows."""
        import jax
        self.pager = pager
        if pager.feature is None:
            return
        if self.shard_degree > 1:
            raise ValueError(
                "pager.feature_cache cannot attach to a shard-sharded "
                "stage (shard_degree=%d): the feature arena is a "
                "single-device gather pool, while sharded logits live "
                "on a %d-device mesh" % (self.shard_degree,
                                         self.shard_degree))
        if not self.ragged:
            raise ValueError(
                "pager.feature_cache requires ragged dispatch on the "
                "consuming stage: feature rows gather into the ONE "
                "pool shape")
        num_classes = int(self._flops_args["num_classes"])
        if self.end_index != NUM_LAYERS:
            raise ValueError(
                "pager.feature_cache requires the consuming stage to "
                "end the network (end_index=%d): cached rows must be "
                "final outputs, not mid-pipeline activations another "
                "stage still transforms" % (self.end_index,))
        fingerprint = (
            "r2p1d-logits", self.start_index, self.end_index,
            num_classes, self._flops_args["layer_sizes"],
            self._flops_args["factored_shortcut"],
            self._flops_args["consecutive_frames"],
            self.pixel_path, self.dct_coeffs_per_frame)
        self._feature_arena = pager.create_arena(
            "features", (num_classes,), np.float32,
            device=self._jax_device,
            gather_keys=("feature_gathers", "feature_gather_rows"))
        pager.feature.attach(self._feature_arena, fingerprint)
        zeros = np.zeros((self.pool_rows, num_classes), np.float32)
        self._logit_pool = jax.device_put(zeros, self._jax_device)

    def _take_feature_plan(self, time_card):
        """The pinned feature-page plan riding this dispatch's card,
        if any (stamped by the loader's feature-hit emission), removed
        from the card so downstream consumers never see it."""
        if self.pager is None or self.pager.feature is None:
            return None
        cards = (time_card.time_cards
                 if isinstance(time_card, TimeCardList)
                 else (time_card,))
        for tc in cards:
            plan = getattr(tc, "feature_plan", None)
            if plan is not None:
                tc.feature_plan = None
                return plan
        return None

    def _insert_features(self, out, time_card) -> None:
        """Publish this forward's output rows for every constituent
        request the loader stamped (insert-after-success: this runs
        only once ``_apply`` returned; contained failures and sheds
        never reach it)."""
        feature = None if self.pager is None else self.pager.feature
        if feature is None or not feature.ready:
            return
        cards = (time_card.time_cards
                 if isinstance(time_card, TimeCardList)
                 else (time_card,))
        for tc in cards:
            job = getattr(tc, "feature_insert", None)
            if job is not None:
                tc.feature_insert = None
                key, row0, n = job
                feature.insert(key, out, row0, n)

    @classmethod
    def input_shape_for(cls, start_index: int = 1,
                        max_rows: int = MAX_CLIPS,
                        consecutive_frames: int = CONSECUTIVE_FRAMES,
                        pixel_path: str = "rgb",
                        dct_coeffs_per_frame=None, **_kwargs):
        # the exact steady-state input shape warm-up compiles. The
        # temporal extent follows the pipeline's consecutive_frames
        # everywhere: at layer 1 it IS consecutive_frames; mid-pipeline
        # it is whatever the upstream range [1..start-1] downsampled
        # those frames to (the static LAYER_INPUT_SHAPES table only
        # covers the default 8)
        from rnb_tpu.models.r2p1d.network import range_output_shape
        if pixel_path == "yuv420":
            shape = (int(consecutive_frames),
                     packed_frame_bytes(FRAME_HW, FRAME_HW))
        elif pixel_path == "dct":
            shape = (int(consecutive_frames),
                     dct_frame_elems(FRAME_HW, FRAME_HW,
                                     dct_coeffs_per_frame))
        elif int(start_index) == 1:
            shape = ((int(consecutive_frames),)
                     + tuple(LAYER_INPUT_SHAPES[1][1:]))
        else:
            shape = range_output_shape(1, int(start_index) - 1,
                                       int(consecutive_frames))
        return ((int(max_rows),) + tuple(shape),)

    @classmethod
    def input_dtype_for(cls, start_index: int = 1,
                        pixel_path: str = "rgb", **_kwargs):
        # the dtype the pipeline actually flows: packed uint8 planes
        # under pixel_path='yuv420', packed int16 coefficient rows
        # under 'dct'; the loader's preprocess emits bfloat16 into
        # layer 1; an upstream network stage emits float32 activations
        # (R2Plus1DClassifier casts its output)
        if pixel_path == "yuv420":
            return "uint8"
        if pixel_path == "dct":
            return "int16"
        return "bfloat16" if int(start_index) == 1 else "float32"

    @classmethod
    def output_dtype_for(cls, **_kwargs):
        return "float32"

    @staticmethod
    def output_shape():
        # full-range default; partial ranges declare their exact
        # feature-map shape via output_shape_for below
        return ((MAX_CLIPS, KINETICS_CLASSES),)

    @classmethod
    def output_shape_for(cls, start_index: int = 1,
                         end_index: int = NUM_LAYERS,
                         num_classes: int = KINETICS_CLASSES,
                         max_rows: int = MAX_CLIPS,
                         consecutive_frames: int = CONSECUTIVE_FRAMES,
                         **_kwargs):
        # exact per-range shape — fixes the restriction the reference
        # shipped broken (hardcoded (10, 400) for every range, its TODO
        # #69 at models/r2p1d/model.py:76-80): a conv1-4 stage declares
        # its feature map, so the runtime can size rings for a
        # mid-pipeline layer split
        from rnb_tpu.models.r2p1d.network import range_output_shape
        per_row = range_output_shape(int(start_index), int(end_index),
                                     int(consecutive_frames),
                                     int(num_classes))
        return ((int(max_rows),) + per_row,)

    def __call__(self, tensors, non_tensors, time_card):
        jax, _ = _jax_numpy()
        pb = tensors[0]
        fplan = self._take_feature_plan(time_card)
        if fplan is not None:
            # feature-page hit: the loader shipped a stub pool and
            # skipped decode + transfer; this stage skips the whole
            # forward and gathers the exact logit rows the original
            # request computed over a preallocated zero pool
            src = np.full((int(self._logit_pool.shape[0]),), -1,
                          np.int32)
            src[:fplan.valid] = fplan.src_rows
            out = self._feature_arena.gather(self._logit_pool, src)
            fplan.release()
            offsets = getattr(pb, "segment_offsets",
                              (0, int(pb.valid)))
            return (RaggedBatch(out, pb.valid, offsets),), \
                non_tensors, time_card
        if self._input_sharding is not None:
            x = jax.device_put(pb.data, self._input_sharding)
        else:
            x = jax.device_put(pb.data, self._jax_device)
        self.compiles.observe(x)
        if self.ragged:
            out = self._apply(self._variables, x, np.int32(pb.valid))
        else:
            out = self._apply(self._variables, x)
        if self._merge is not None:
            # the forward leaves logits channel-sharded; the merge
            # gather is the stage-level collective, host-timed as its
            # own span so the collective tax is a measured number —
            # block on the forward first so the timing brackets ONLY
            # the collective
            jax.block_until_ready(out)
            rid = getattr(time_card, "id", None)
            t0 = time.perf_counter()
            if self._tr_collective is not None:
                with trace.span(self._tr_collective, rid):
                    out = self._merge(out)
                    jax.block_until_ready(out)
            else:
                out = self._merge(out)
                jax.block_until_ready(out)
            stats = self.shard_stats
            stats["gathers"] += 1
            stats["collective_ms"] += (time.perf_counter() - t0) * 1e3
        if self.shard_stats is not None:
            self.shard_stats["rows"] += int(pb.valid)
        self._insert_features(out, time_card)
        if self.ragged:
            # the pool shape rides through: downstream consumers (and
            # the executor's payload validation) see the same segment
            # table the loader filled
            offsets = getattr(pb, "segment_offsets",
                              (0, int(pb.valid)))
            return (RaggedBatch(out, pb.valid, offsets),), \
                non_tensors, time_card
        return (PaddedBatch(out, pb.valid),), non_tensors, time_card


class R2P1DSingleStep(StageModel):
    """Fused decode + full network in one stage — the no-pipelining
    baseline (reference models/r2p1d/model.py:161-235). Emits the
    predicted class id as the non-tensor payload; declares no tensor
    outputs, so the runtime allocates no rings for it."""

    # open config kwargs (row_buckets, pixel_path, cache_mb, ...) are
    # forwarded to the embedded loader/runner pair — the static
    # unconsumed-key check (rnb_tpu.analysis.graph) honors their
    # constructor signatures through this declaration
    FORWARDS_CONFIG_TO = (R2P1DLoader, R2P1DRunner)

    def __init__(self, device, num_classes: int = KINETICS_CLASSES,
                 layer_sizes=R18_LAYER_SIZES, max_clips: int = MAX_CLIPS,
                 consecutive_frames: int = CONSECUTIVE_FRAMES,
                 num_warmups: int = NUM_WARMUPS,
                 ckpt_path: Optional[str] = None, **kwargs):
        super().__init__(device)
        self.loader = R2P1DLoader(device, max_clips=max_clips,
                                  consecutive_frames=consecutive_frames,
                                  num_warmups=num_warmups, **kwargs)
        # surface the embedded loader's clip cache (if configured) and
        # staging pool so the executor's stats sinks see them
        # (rnb_tpu.runner)
        self.cache = self.loader.cache
        self.staging = self.loader.staging
        # the inner runner must warm the same bucket shapes the loader
        # emits, or the first occurrence of each bucket would pay a
        # silent XLA recompile inside the measured window
        self.net = R2P1DRunner(device, start_index=1, end_index=NUM_LAYERS,
                               num_classes=num_classes,
                               layer_sizes=layer_sizes,
                               max_rows=max_clips,
                               consecutive_frames=consecutive_frames,
                               num_warmups=num_warmups,
                               ckpt_path=ckpt_path,
                               row_buckets=kwargs.get("row_buckets"),
                               factored_shortcut=kwargs.get(
                                   "factored_shortcut", False),
                               pixel_path=kwargs.get("pixel_path",
                                                     "rgb"),
                               dct_coeffs_per_frame=kwargs.get(
                                   "dct_coeffs_per_frame"))

    def bind_step(self, step_idx: int) -> None:
        """Forward to the embedded loader: its phase-refinement
        stamps carry this fused step's index (rnb_tpu.runner
        executor protocol)."""
        self.loader.bind_step(step_idx)

    def enable_trace(self, tracer, step_idx: int) -> None:
        """Forward to the embedded loader: its occupancy sources
        apply to this fused step's index."""
        self.loader.enable_trace(tracer, step_idx)

    def input_shape(self):
        return None

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        _, jnp = _jax_numpy()
        (pb,), _, time_card = self.loader(None, non_tensors, time_card)
        (logits,), _, time_card = self.net((pb,), None, time_card)
        # sum+argmax on device; only the class id crosses to the host
        # (a full logits D2H per video would serialize on transfer
        # latency)
        pred = int(jnp.argmax(
            jnp.sum(logits.data[: logits.valid], axis=0)))
        return None, pred, time_card


class R2P1DMeshRunner(StageModel):
    """Clip-sharded inference stage over a device sub-mesh.

    The TPU-native successor to the reference's segment-parallel
    topology (config/r2p1d-segment.json: loader fans each video out as
    ``num_segments`` row-splits to replica processes, a host aggregator
    re-sums the logits — reference runner.py:138-173,
    models/r2p1d/model.py:238-285). Here the split, the compute and the
    merge are ONE compiled program over an ``sp`` mesh axis: every core
    computes logits for its clip shard and a ``psum`` over ICI reduces
    them on-device — no queue fan-out, no TimeCard forks, no host
    aggregator hop.

    Config: home the stage on one device (its executor thread) and pass
    ``mesh_devices`` = the logical device indices forming the sub-mesh
    (the home device should be among them), factored as ``dp`` x
    ``sp = len(mesh_devices)/dp``. ``sp`` need not divide ``max_clips``
    — the sharded step pads the clip axis to the next multiple inside
    the compiled program (masked rows), so e.g. 8 cores serve 15-clip
    batches with none idle. Consumes the loader's ``raw_output`` uint8
    batches and emits predicted class ids (final-stage contract, no
    tensor outputs).

    Pipeline-friendliness (round-3 verdict weak#5): with ``dp > 1`` the
    stage accumulates ``dp`` queued videos and dispatches them as ONE
    sharded step (videos over ``dp``, clips over ``sp``). With
    ``sync_preds=False`` the emitted predictions are **device values**
    — no per-video host sync blocks the executor thread; in-flight
    dispatches are bounded, ``flush()`` pads and runs a partial video
    batch at end-of-stream, and ``finalize()`` drains outstanding
    device work before the finish barrier so the measured *window*
    still covers all compute. Caveat (same as the executor's
    ``async_dispatch``): per-record ``inference{i}`` spans then measure
    dispatch, not device compute, so latency percentiles from async
    runs under-report — the default ``sync_preds=True`` blocks per
    dispatch and keeps them honest.
    """

    def __init__(self, device, mesh_devices, dp: int = 1,
                 max_clips: int = MAX_CLIPS,
                 consecutive_frames: int = CONSECUTIVE_FRAMES,
                 num_classes: int = KINETICS_CLASSES,
                 layer_sizes=R18_LAYER_SIZES,
                 num_warmups: int = NUM_WARMUPS,
                 ckpt_path: Optional[str] = None,
                 max_inflight: int = 4, sync_preds: bool = True,
                 factored_shortcut: bool = False,
                 pixel_path: str = "rgb", **kwargs):
        super().__init__(device)
        from collections import deque

        import numpy as _np
        import jax
        from jax.sharding import Mesh

        from rnb_tpu.devices import DeviceSpec
        from rnb_tpu.parallel.sharded import ShardedInference

        self.dp = int(dp)
        if len(mesh_devices) % self.dp != 0:
            raise ValueError("dp=%d must divide len(mesh_devices)=%d"
                             % (self.dp, len(mesh_devices)))
        devs = [DeviceSpec(int(d)).resolve() for d in mesh_devices]
        mesh = Mesh(_np.array(devs).reshape(
            self.dp, len(devs) // self.dp), ("dp", "sp"))
        self.max_clips = int(max_clips)
        self.consecutive_frames = int(consecutive_frames)
        self.max_inflight = int(max_inflight)
        self.sync_preds = bool(sync_preds)
        self._si = ShardedInference(
            mesh, max_clips=self.max_clips,
            consecutive_frames=self.consecutive_frames,
            num_classes=num_classes, layer_sizes=tuple(layer_sizes),
            ckpt_path=ckpt_path, factored_shortcut=factored_shortcut,
            pixel_path=pixel_path)
        self.pixel_path = pixel_path
        self._acc = []            # (PaddedBatch, TimeCard) awaiting dp fill
        self._inflight = deque()  # unretired device prediction arrays
        dummy = np.zeros(self._si.batch_shape(self.dp), np.uint8)
        for _ in range(num_warmups):
            vids, mask = self._si.place(dummy, [self.max_clips] * self.dp)
            jax.block_until_ready(self._si.run(vids, mask))

    def input_shape(self):
        # one source of truth for the per-video shape in either pixel
        # path: the sharded step's own batch geometry
        return (self._si.batch_shape(1)[1:],)

    def input_sharding(self):
        """Edge-contract target (rnb_tpu.handoff, root ``handoff``
        key): per-item payloads land mesh-replicated, so the
        ``dp``-stacked dispatch reshards purely on-device — the
        sharded program's clip padding happens inside the jit, so the
        raw per-video clip axis cannot be pre-split over ``sp``
        (max_clips need not divide), but a replicated placement
        already puts the bytes on every core the shard_map will
        read from."""
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self._si.mesh, PartitionSpec())

    @classmethod
    def input_shape_for(cls, max_clips: int = MAX_CLIPS,
                        consecutive_frames: int = CONSECUTIVE_FRAMES,
                        pixel_path: str = "rgb", **_kwargs):
        # mirrors ShardedInference.batch_shape(1)[1:] without building
        # the mesh: one raw loader video batch per dispatch row
        if pixel_path == "yuv420":
            return ((int(max_clips), int(consecutive_frames),
                     packed_frame_bytes(FRAME_HW, FRAME_HW)),)
        return ((int(max_clips), int(consecutive_frames),
                 FRAME_HW, FRAME_HW, 3),)

    @classmethod
    def input_dtype_for(cls, **_kwargs):
        # consumes the loader's raw_output uint8 batches in either
        # pixel path (the sharded program owns normalize/ingest)
        return "uint8"

    @staticmethod
    def output_shape():
        return None

    def _dispatch(self, pbs, cards):
        """One sharded step over len(pbs)==dp videos; async device
        preds out, bounded in-flight window."""
        jax, jnp = _jax_numpy()

        # re-home the loader's device batches straight onto the mesh
        # sharding (device-to-device, ICI on hardware — no host bounce)
        batch = jnp.stack([pb.data for pb in pbs])
        vids = jax.device_put(batch, self._si.batch_sharding)
        mask = self._si.place_mask([pb.valid for pb in pbs])
        logits = self._si.run(vids, mask)
        preds = jnp.argmax(logits, axis=-1)  # computed on-device
        if self.sync_preds:
            # honest latency spans: the executor stamps
            # inference_finish right after we return
            jax.block_until_ready(preds)
        else:
            self._inflight.append(preds)
            while len(self._inflight) > self.max_inflight:
                # bound the async queue: retire the oldest dispatch
                jax.block_until_ready(self._inflight.popleft())
        out_card = (TimeCardList(list(cards)) if len(cards) > 1
                    else cards[0])
        return None, preds, out_card

    def __call__(self, tensors, non_tensors, time_card):
        pb = tensors[0]
        want = self.input_shape()[0]
        if tuple(pb.data.shape) != tuple(want):
            # fail fast with the likely cause: the loader and this
            # stage must agree on pixel_path (a mismatch would
            # otherwise surface as a cryptic shape error deep inside
            # shard_map tracing)
            raise ValueError(
                "mesh stage received batch shape %r but expects %r — "
                "do the loader's and this stage's pixel_path settings "
                "agree? (this stage: %r)"
                % (tuple(pb.data.shape), tuple(want), self.pixel_path))
        self._acc.append((pb, time_card))
        if len(self._acc) < self.dp:
            return None, None, None  # swallow until the dp axis fills
        pbs, cards = zip(*self._acc)
        self._acc = []
        return self._dispatch(list(pbs), list(cards))

    def flush(self):
        """End-of-stream: run the partial video batch, padding the dp
        axis with zero videos (mask 0 — dead rows, no result rows)."""
        if not self._acc:
            return None
        _, jnp = _jax_numpy()
        pbs, cards = zip(*self._acc)
        self._acc = []
        pbs = list(pbs)
        while len(pbs) < self.dp:
            pbs.append(PaddedBatch(jnp.zeros_like(pbs[0].data), 0))
        return self._dispatch(pbs, list(cards))

    def finalize(self):
        """Drain outstanding device work (called by the executor before
        the finish barrier, keeping the measured window honest)."""
        jax, _ = _jax_numpy()
        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())


class R2P1DAggregator(StageModel):
    """Host-side merge of segment logits (reference
    models/r2p1d/model.py:238-285): accumulates summed logits per
    request id until ``aggregate`` segments arrived, merges the forked
    TimeCards, and emits the argmax class. Declares no tensor outputs.
    """

    def __init__(self, device, aggregate: int, **kwargs):
        super().__init__(device)
        self.aggregate = int(aggregate)
        if self.aggregate < 1:
            raise ValueError("aggregate must be >= 1")
        # request id -> [summed logits, [TimeCard, ...]]
        self._pending: Dict[Any, list] = {}

    def input_shape(self):
        return ((MAX_CLIPS, KINETICS_CLASSES),)

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        logits = np.asarray(tensors[0].data,
                            np.float32)[: tensors[0].valid]
        contribution = logits.sum(axis=0)
        entry = self._pending.setdefault(time_card.id,
                                         [np.zeros_like(contribution), []])
        entry[0] = entry[0] + contribution
        entry[1].append(time_card)
        if len(entry[1]) < self.aggregate:
            return None, None, None  # swallow until all segments arrive
        del self._pending[time_card.id]
        merged = (TimeCard.merge(entry[1]) if self.aggregate > 1
                  else entry[1][0])
        pred = int(entry[0].argmax())
        return None, pred, merged


class R2P1DVideoPathIterator(VideoPathIterator):
    """Cycles a video dataset forever (reference
    models/r2p1d/model.py:86-113 scanned a root/label/video tree).
    Scans ``root`` (or $RNB_TPU_DATA_ROOT) for video files (.y4m
    uncompressed, .mjpg/.mjpeg compressed); without a dataset it cycles
    a fixed population of synthetic video ids, which the decode layer
    resolves procedurally.
    """

    EXTENSIONS = video_path_provider.VIDEO_EXTENSIONS

    @classmethod
    def scan_tree(cls, root: str) -> list:
        """Sorted video paths from a root/label/video tree; delegates
        to the jax-free scan in rnb_tpu.video_path_provider."""
        return video_path_provider.scan_video_tree(root, cls.EXTENSIONS)

    def __init__(self, root: Optional[str] = None,
                 num_synthetic: int = 200):
        super().__init__()
        import itertools
        import os
        root = root or os.environ.get("RNB_TPU_DATA_ROOT")
        videos = (self.scan_tree(root)
                  if root and os.path.isdir(root) else [])
        if not videos:
            videos = ["synth://kinetics/video-%04d" % i
                      for i in range(num_synthetic)]
        self._videos = videos
        self._cycle = itertools.cycle(videos)

    def dataset(self):
        """Finite universe for popularity wrappers (ZipfPathIterator)."""
        return list(self._videos)

    def __iter__(self):
        return self._cycle


class LargeSmallSelector(QueueSelector):
    """Content-aware router: rare large (max-clip) videos go to queue 1,
    everything else to queue 0, so small videos can be batched without
    head-of-line blocking — the Replicate & Batch placement policy
    (reference models/r2p1d/model.py:288-296). Keyed off the
    ``num_clips`` the loader stamped on the TimeCard.

    The "large" threshold binds to the producing loader's configured
    clip population (``bind_stage``): a config sampling
    ``num_clips_population`` != the default [1, 15] still routes its
    own largest class to the dedicated lane. Falls back to the module
    default when the stage exposes no sampler."""

    def __init__(self, num_queues: int):
        super().__init__(num_queues)
        if num_queues != 2:
            raise ValueError("LargeSmallSelector routes over exactly two "
                             "queues (got %d)" % num_queues)
        self._threshold = MAX_CLIPS

    def bind_stage(self, model) -> None:
        sampler = getattr(model, "sampler", None)
        threshold = getattr(sampler, "max_clips", None)
        if threshold:
            # the loader truncates every request at its own max_clips
            # cap (submit/__call__ starts[:max_clips]), so a population
            # max above the cap would be an unreachable threshold and
            # the large lane would starve
            cap = getattr(model, "max_clips", None)
            if cap:
                threshold = min(int(threshold), int(cap))
            self._threshold = int(threshold)

    def select(self, tensors, non_tensors, time_card) -> int:
        return (1 if getattr(time_card, "num_clips", 0) >= self._threshold
                else 0)
