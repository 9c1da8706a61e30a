"""Device-resident decoded-clip cache + in-flight request coalescing.

Real video-serving traffic is popularity-skewed: a small fraction of
videos receives most of the requests (the Zipf workload
``rnb_tpu.video_path_provider.ZipfPathIterator`` models). Round 5
measured the host core at 98% saturation with the two dominant terms
being ``device_put`` staging (49.3%) and decode-output assembly +
decode wait (22.1%) — both of which a cache hit skips entirely: the
cached value is the *already-padded on-device uint8 clip batch*
(post-``device_put``, pre-preprocess) plus its valid-row count, so a
hit feeds the existing jitted preprocess/network path unchanged and
produces bit-identical logits to a miss.

Design:

* **Content-addressed keys** (:func:`content_key`): (video path,
  file mtime_ns + size, decode-config fingerprint). The fingerprint
  covers everything that changes decoded bytes — sampler population/
  weights (clip starts are deterministic per video id given these),
  ``consecutive_frames``, frame geometry, pixel format, ``max_clips``
  and the row-bucket set (the padded shape is part of the value). A
  file replaced on disk gets a new key; a config change can never
  alias another config's entries.
* **Byte-accounted LRU** bounded by ``cache_mb``: every entry is
  charged its device-array ``nbytes``; inserts evict from the
  least-recently-used end until the new entry fits. An entry larger
  than the whole budget is skipped (counted ``oversize``), never
  inserted.
* **Insert-after-success only**: the loaders insert a value only once
  decode + transfer completed; failed or contained requests
  (rnb_tpu.faults taxonomy, including ``take_failed()`` inside fused
  assembly) never reach the insert path, so a corrupt video cannot
  poison later requests.
* **In-flight coalescing** (:class:`InflightTable`): concurrent
  requests for the same key share one decode. The loaders register
  the leader's in-flight record; followers park on it — in the fusing
  loader they ride the leader's fused emission through the existing
  TimeCardList fan-out, in the prefetching loader they share the
  leader's decoded host buffer. Either way the duplicate decode never
  happens, which is where the win is under Poisson+Zipf arrivals.

The cache is per loader-stage instance (all access happens on the one
executor thread that owns the stage), but every mutator takes the lock
anyway so a future shared deployment stays correct. Stats are exact
counters surfaced end-to-end: BenchmarkResult, ``log-meta.txt``
(``Cache:`` line), the ``# cache`` trailer on per-instance tables, and
``scripts/parse_utils.py``.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rnb_tpu import lockwitness
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy

#: stat signature for ids that are not files on disk (synth:// ids):
#: their content is deterministic per id, so a constant signature is
#: content-correct
_NO_STAT = (-1, -1)


def content_key(video: str, cfg_key: Any) -> tuple:
    """Content-addressed cache key for one request.

    ``cfg_key`` is the loader's decode-config fingerprint (hashable).
    For real files the file's (mtime_ns, size) joins the key so a
    video replaced on disk mid-run invalidates instead of serving
    stale clips; ids without a backing file (synthetic, vanished
    files — the decode layer resolves those deterministically) use a
    constant signature.
    """
    try:
        st = os.stat(video)
        sig = (st.st_mtime_ns, st.st_size)
    except (OSError, ValueError):
        sig = _NO_STAT
    return (video, sig, cfg_key)


class CacheEntry:
    """One cached clip batch: device-resident uint8, padded to its
    row bucket, plus the valid-row count."""

    __slots__ = ("batch", "valid", "nbytes")

    def __init__(self, batch, valid: int, nbytes: int):
        self.batch = batch      # jax.Array uint8, shape = bucket shape
        self.valid = int(valid)  # meaningful leading rows
        self.nbytes = int(nbytes)


class PagedEntry:
    """One paged cache entry: a page *reference list* into the clip
    arena's device slab (rnb_tpu.pager) instead of a contiguous blob —
    any free pages serve any entry (no fragmentation, no oversize
    skip) and eviction frees pages, not bytes."""

    __slots__ = ("pages", "valid", "nbytes")

    def __init__(self, pages: Tuple[int, ...], valid: int, nbytes: int):
        self.pages = pages
        self.valid = int(valid)
        self.nbytes = int(nbytes)


class ClipCache:
    """Bounded, byte-accounted LRU of device-resident clip batches."""

    #: declared concurrency contract (rnb-lint RNB-C001/C003): which
    #: lock guards which cross-thread attribute
    GUARDED_BY = {
        "_entries": "_lock",
        "_arena": "_lock",
        "capacity_bytes": "_lock",
        "resident_bytes": "_lock",
        "num_hits": "_lock",
        "num_misses": "_lock",
        "num_inserts": "_lock",
        "num_evictions": "_lock",
        "num_coalesced": "_lock",
        "num_oversize": "_lock",
    }

    def __init__(self, cache_mb: float, device=None):
        if cache_mb <= 0:
            raise ValueError("cache_mb must be > 0 to build a ClipCache "
                             "(got %r); omit the key to disable caching"
                             % (cache_mb,))
        self.capacity_bytes = int(float(cache_mb) * (1 << 20))
        self.device = device
        self._lock = lockwitness.lock("ClipCache._lock")
        self._entries: "OrderedDict[tuple, CacheEntry]" = OrderedDict()
        self.resident_bytes = 0
        # exact counters, surfaced end-to-end (benchmark/log-meta/parse)
        self.num_hits = 0
        self.num_misses = 0
        self.num_inserts = 0
        self.num_evictions = 0
        self.num_coalesced = 0
        self.num_oversize = 0
        #: paged mode (rnb_tpu.pager): entries become page reference
        #: lists in this arena's slab; None = blob mode (the seed
        #: semantics, byte-stable)
        self._arena = None

    def attach_arena(self, arena) -> None:
        """Switch this cache to paged mode: entries are page reference
        lists allocated from ``arena``; the arena budget replaces
        ``capacity_bytes`` as the byte bound (still reported, for the
        Cache: line's footing)."""
        with self._lock:
            if self._entries:
                raise RuntimeError("attach_arena on a non-empty cache: "
                                   "blob and paged entries must never "
                                   "coexist")
            self._arena = arena
            self.capacity_bytes = int(arena.nbytes)

    @property
    def paged(self) -> bool:
        with self._lock:
            return self._arena is not None

    def acquire(self, key: tuple):
        """Paged-mode hit path: counted lookup -> pinned
        ``rnb_tpu.pager.GatherPlan`` (flat slab rows for the entry's
        valid rows) or None. The caller overlays the rows on device at
        the consumption seam and releases the plan once its gather
        dispatched; pages evicted in between park in limbo, so the
        plan's rows can never be recycled under it."""
        from rnb_tpu.pager import GatherPlan
        with self._lock:
            arena = self._arena
            assert arena is not None, "acquire() is the paged hit path"
            entry = self._entries.get(key)
            if entry is None:
                self.num_misses += 1
                return None
            self._entries.move_to_end(key)
            self.num_hits += 1
            with arena.pager.lock:
                arena.pin_locked(entry.pages)
            return GatherPlan(arena, entry.pages,
                              arena.flat_rows(entry.pages, entry.valid),
                              entry.valid)

    def insert_pages(self, key: tuple, src_pool, row0: int,
                     valid: int) -> bool:
        """Paged-mode insert: allocate pages, publish ``valid`` rows of
        the already-transferred device pool (rows ``[row0, row0 +
        valid)``) into the arena slab via the donated page writer, and
        record the reference list. First writer wins; evicts LRU
        entries (freeing their pages) until the allocation fits; an
        entry needing more pages than the whole arena holds is counted
        ``oversize`` and skipped — the only size an entry can still
        exceed, since pages need not be contiguous."""
        valid = int(valid)
        if valid < 1:
            return False
        with self._lock:
            arena = self._arena
            assert arena is not None, \
                "insert_pages() is the paged insert"
            if key in self._entries:
                return False
            needed = arena.pages_needed(valid)
            if needed > arena.num_pages:
                self.num_oversize += 1
                return False
            with arena.pager.lock:
                pages = None
                while True:
                    pages = arena.alloc_locked(needed)
                    if pages is not None or not self._entries:
                        break
                    _, evicted = self._entries.popitem(last=False)
                    self.resident_bytes -= evicted.nbytes
                    self.num_evictions += 1
                    arena.free_locked(evicted.pages)
                if pages is None:
                    # every evictable page is out and the rest are
                    # pinned/limbo under live plans — skip, never block
                    return False
                arena.write_entry_locked(pages, src_pool, row0, valid)
            entry = PagedEntry(pages, valid, needed * arena.page_bytes)
            self._entries[key] = entry
            self.resident_bytes += entry.nbytes
            self.num_inserts += 1
            return True

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup(self, key: tuple) -> Optional[CacheEntry]:
        """Counted hit/miss lookup; a hit refreshes LRU recency."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.num_misses += 1
                return None
            self._entries.move_to_end(key)
            self.num_hits += 1
            return entry

    def contains(self, key: tuple) -> bool:
        """Uncounted membership probe (insert-path dedup)."""
        with self._lock:
            return key in self._entries

    def note_coalesced(self, n: int = 1) -> None:
        with self._lock:
            self.num_coalesced += n

    def _insert(self, key: tuple, batch, valid: int,
                nbytes: int) -> bool:
        """The one locked insert body every flavor shares: first
        writer wins, oversize skipped (counted), LRU-evict until the
        entry fits."""
        with self._lock:
            if key in self._entries:
                return False
            if nbytes > self.capacity_bytes:
                self.num_oversize += 1
                return False
            while (self.resident_bytes + nbytes > self.capacity_bytes
                   and self._entries):
                _, evicted = self._entries.popitem(last=False)
                self.resident_bytes -= evicted.nbytes
                self.num_evictions += 1
            self._entries[key] = CacheEntry(batch, valid, nbytes)
            self.resident_bytes += nbytes
            self.num_inserts += 1
            return True

    def insert_device(self, key: tuple, device_batch, valid: int) -> bool:
        """Insert an already-transferred padded device batch.

        Returns False when the entry was skipped (oversize, or the key
        is already resident — first writer wins, the bytes are
        identical by content-addressing).
        """
        return self._insert(key, device_batch, valid,
                            int(device_batch.nbytes))

    def insert_host(self, key: tuple, clips, valid: int,
                    target_shape: Tuple[int, ...],
                    dtype=np.uint8) -> bool:
        """Pad host clips to ``target_shape`` and transfer, then insert.

        Used by the fusing loader, whose misses cross the wire inside a
        fused batch — there is no standalone padded device array to
        reuse, so the insert pays one extra transfer the first time a
        video is seen (amortized away by every later hit).

        Staging contract (rnb_tpu.staging): ``clips`` may be a view
        into a staging slot whose buffer is recycled after the fused
        emission's transfer confirms. This method COPIES the rows into
        its own freshly padded array before any transfer, so it must
        be called while the slot rows are still live (the fusing
        loader inserts during ``_emit``, strictly before the slot's
        transfer handoff) — after that, the cached device array owns
        independent bytes and can never observe a slot reuse.
        """
        dtype = np.dtype(dtype)
        with self._lock:
            # capacity_bytes is rebound by attach_arena — read it
            # under the same lock that guards the switch
            if int(np.prod(target_shape)) * dtype.itemsize \
                    > self.capacity_bytes:
                self.num_oversize += 1
                return False
        if self.contains(key):
            return False
        jax, _ = _jax_numpy()
        padded = np.zeros(target_shape, dtype=dtype)
        padded[:valid] = clips[:valid]
        device_batch = jax.device_put(padded, self.device)
        return self.insert_device(key, device_batch, valid)

    def insert_rows(self, key: tuple, clips, valid: int) -> bool:
        """Insert a **host row extent**: exactly ``valid`` decoded rows,
        no bucket padding, no device transfer (ragged dispatch mode,
        rnb_tpu.ops.ragged).

        Under ragged row-pool dispatch there is no per-request padded
        device batch to reuse — hit rows are *filled into the pool*
        alongside fresh decodes and ride the pool's single transfer —
        so the cached value is the minimal thing that skips the decode:
        the raw rows. Copies out of the caller's buffer (which may be a
        staging-slot view about to recycle, same contract as
        :meth:`insert_host`), and charges exactly ``valid`` rows of
        bytes — a 1-clip entry costs 1/15th of its bucket-padded
        equivalent. The rows keep the loader's wire dtype (uint8
        pixels/planes, int16 packed dct coefficients).
        """
        valid = int(valid)
        rows = np.array(np.asarray(clips)[:valid])
        return self._insert(key, rows, valid, int(rows.nbytes))

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time counter copy for reports."""
        with self._lock:
            return {
                "hits": self.num_hits,
                "misses": self.num_misses,
                "inserts": self.num_inserts,
                "evictions": self.num_evictions,
                "coalesced": self.num_coalesced,
                "oversize": self.num_oversize,
                "bytes_resident": self.resident_bytes,
                "entries": len(self._entries),
                "capacity_bytes": self.capacity_bytes,
            }


def aggregate_snapshots(snapshots: List[Dict[str, int]]) -> Dict[str, int]:
    """Sum per-instance cache snapshots into one job-wide record
    (every counter is additive, including bytes_resident — each
    instance owns its own budget)."""
    total = {"hits": 0, "misses": 0, "inserts": 0, "evictions": 0,
             "coalesced": 0, "oversize": 0, "bytes_resident": 0,
             "entries": 0, "capacity_bytes": 0}
    for snap in snapshots:
        for k in total:
            total[k] += int(snap.get(k, 0))
    return total


class InflightTable:
    """Key -> opaque in-flight record, for request coalescing.

    The loaders register the record of a decode they just kicked off;
    a later request for the same key finds it and parks on it instead
    of re-decoding. Records are removed when the decode is finalized
    (emitted, failed, or discarded) — a removed key simply means the
    next request consults the cache (where a successful decode has
    landed by then) or decodes afresh.
    """

    GUARDED_BY = {"_records": "_lock"}

    def __init__(self):
        self._lock = lockwitness.lock("InflightTable._lock")
        self._records: Dict[tuple, Any] = {}

    def get(self, key: tuple) -> Optional[Any]:
        with self._lock:
            return self._records.get(key)

    def put(self, key: tuple, record: Any) -> None:
        with self._lock:
            self._records[key] = record

    def pop(self, key: Optional[tuple]) -> None:
        if key is None:
            return
        with self._lock:
            self._records.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)
