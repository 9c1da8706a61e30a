"""One HBM page allocator: paged clip-cache entries + feature pages.

Until this module, three subsystems owned device memory separately —
the whole-blob LRU clip cache (rnb_tpu.cache: every hit memcpys rows
into the open staging slot; entries larger than the budget are skipped
outright), the per-(loader, shape) staging slabs (rnb_tpu.staging),
and handoff adoptions (rnb_tpu.handoff) — which fragments HBM and
makes a cache hit cost a host copy. Following Ragged Paged Attention
(PAPERS.md) applied to video rows, this module provides the unifying
layer:

* **One slab, fixed-size row pages** (:class:`Arena`): each arena owns
  a single device allocation ``(num_pages * page_rows,) + row_shape``
  — the only legal pool-shaped device allocation outside stage init
  (rnb-lint RNB-H010 enforces this) — carved into pages on a free
  list. Entries hold page *reference lists*: no fragmentation (any
  free pages serve any entry), no oversize skips (an entry needs
  pages, not a contiguous extent), and eviction frees pages, not
  blobs.
* **Zero-copy hits**: a hit pins its entry's pages and returns a
  :class:`GatherPlan` — flat slab row indices the consumption seam
  hands to the gather-from-pages kernel (rnb_tpu.ops.pages) AFTER the
  pool's device transfer. The hit rows never exist as host bytes.
* **Pin/limbo discipline**: pages freed (evicted) while a plan still
  pins them move to a limbo list and only re-enter the free list at
  unpin — an insert can therefore never recycle a page an in-flight
  gather has planned but not yet dispatched. (Once dispatched, jax's
  functional arrays make the read safe regardless: the gather captured
  the slab value; later donated writes produce a new one.)
* **Feature pages** (:class:`FeatureCache`, config-gated by
  ``pager.feature_cache``): post-stage activation rows keyed by
  (content key, stage fingerprint). The consuming stage registers its
  fingerprint; the loader probes at admission and, on a hit, the
  request skips decode, transfer AND the whole stage-0..N forward —
  the runner gathers the exact logit rows the original request
  computed (bit-identical by construction). Insert-after-success only:
  the runner inserts strictly after its forward returned, so contained
  failures and deadline sheds never populate feature pages.
* **Accounting**: exact counters (allocs/frees/live pages, gathers,
  gather rows, feature lookups/hits/bytes saved) surfaced end-to-end —
  the ``Pages:`` log-meta line and the ``parse_utils --check``
  invariants (pages allocated == freed + live at teardown; feature
  hits <= lookups; gather rows foot with cache hit rows).

Sizing: ``pager.pool_mb`` is the explicit per-arena page budget; when
absent, the arena is sized from the clip cache — the loader passes
its clip-cache byte budget (the bytes the blob cache would have
owned), and the feature arena inherits the same figure via
:meth:`Pager.size_hint` (its rows are orders of magnitude smaller, so
this is a generous ceiling, and bounded either way).
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from rnb_tpu import lockwitness

#: fallback arena budget when neither ``pool_mb`` nor a cache-derived
#: size hint exists (a bare pager on a cache-less config)
DEFAULT_ARENA_MB = 64


@dataclasses.dataclass(frozen=True)
class PagerSettings:
    """Validated, defaulted view of the ``pager`` root config key."""

    page_rows: int = 4
    pool_mb: Optional[float] = None
    feature_cache: bool = False

    @staticmethod
    def from_config(raw: Optional[dict]) -> Optional["PagerSettings"]:
        """Settings from the (schema-validated) config dict, or None
        when the key is absent or ``enabled`` is false."""
        if not raw or not raw.get("enabled", True):
            return None
        page_rows = int(raw.get("page_rows", 4))
        if page_rows < 1:
            raise ValueError("pager.page_rows must be >= 1, got %r"
                             % (raw.get("page_rows"),))
        pool_mb = raw.get("pool_mb")
        if pool_mb is not None:
            pool_mb = float(pool_mb)
            if pool_mb <= 0:
                raise ValueError("pager.pool_mb must be > 0, got %r"
                                 % (raw.get("pool_mb"),))
        return PagerSettings(page_rows=page_rows, pool_mb=pool_mb,
                             feature_cache=bool(
                                 raw.get("feature_cache", False)))


class GatherPlan:
    """One pinned hit: flat slab row per valid entry row, released
    after the consumption seam dispatched its gather."""

    __slots__ = ("arena", "pages", "src_rows", "valid", "_released")

    def __init__(self, arena: "Arena", pages: Tuple[int, ...],
                 src_rows: np.ndarray, valid: int):
        self.arena = arena
        self.pages = pages
        self.src_rows = src_rows  # int32 (valid,) flat slab rows
        self.valid = int(valid)
        self._released = False

    def release(self) -> None:
        """Unpin the plan's pages (idempotent — drop paths and the
        post-dispatch path may both reach it)."""
        if not self._released:
            self._released = True
            self.arena.unpin(self.pages)


class Arena:
    """One device slab carved into fixed-size row pages.

    All mutation runs under the owning :class:`Pager`'s lock (hit
    plans are built on executor threads while inserts run on transfer
    workers). The slab itself is updated through the donated writer in
    rnb_tpu.ops.pages — in place, never copied — and read through
    functional gathers, so readers always observe a consistent value.
    """

    #: declared concurrency contract (rnb-lint RNB-C001): the arena
    #: has no lock of its own — every mutable field is guarded by the
    #: owning pager's shared lock (hit plans build on executor threads
    #: while inserts run on transfer workers)
    GUARDED_BY = {
        "_free": "pager.lock",
        "_pins": "pager.lock",
        "_limbo": "pager.lock",
        "_slab": "pager.lock",
    }

    def __init__(self, pager: "Pager", name: str,
                 row_shape: Tuple[int, ...], dtype,
                 budget_bytes: int, device=None,
                 gather_keys: Tuple[str, str] = ("gathers",
                                                 "gather_rows")):
        import jax
        import jax.numpy as jnp
        self.pager = pager
        self.name = str(name)
        # which counter pair this arena's gathers increment: the clip
        # arena foots gather_rows against the clip cache's hit rows,
        # the feature arena keeps its own pair so the --check footing
        # never mixes the two planes
        self.gather_keys = tuple(gather_keys)
        self.row_shape = tuple(int(d) for d in row_shape)
        self.dtype = np.dtype(dtype)
        self.page_rows = int(pager.settings.page_rows)
        row_bytes = int(np.prod(self.row_shape)) * self.dtype.itemsize
        self.row_bytes = row_bytes
        self.page_bytes = row_bytes * self.page_rows
        self.num_pages = max(1, int(budget_bytes) // self.page_bytes)
        slab = jnp.zeros((self.num_pages * self.page_rows,)
                         + self.row_shape, self.dtype)
        if device is not None:
            slab = jax.device_put(slab, device)
        self._slab = slab
        #: LIFO free list: recently-freed pages are re-alloc'd first
        #: (their slab rows are warm)
        self._free: List[int] = list(range(self.num_pages))
        self._pins: Dict[int, int] = {}
        self._limbo: set = set()

    @property
    def nbytes(self) -> int:
        return self.num_pages * self.page_bytes

    # -- page lifecycle (call under the pager lock) -------------------

    def pages_needed(self, valid: int) -> int:
        return (int(valid) + self.page_rows - 1) // self.page_rows

    def alloc_locked(self, n_pages: int) -> Optional[Tuple[int, ...]]:
        """Pop ``n_pages`` from the free list, or None (the caller
        evicts and retries, or skips the insert — counted either
        way)."""
        if n_pages > len(self._free):
            self.pager.counters["alloc_fails"] += 1
            return None
        pages = tuple(self._free.pop() for _ in range(n_pages))
        self.pager.counters["allocs"] += n_pages
        return pages

    def free_locked(self, pages: Tuple[int, ...]) -> None:
        """Return pages to the free list; pages a live plan still pins
        park in limbo until their unpin (the eviction-under-gather
        safety rule)."""
        for page in pages:
            if self._pins.get(page, 0) > 0:
                self._limbo.add(page)
            else:
                self._free.append(page)
                self.pager.counters["frees"] += 1

    def pin_locked(self, pages: Tuple[int, ...]) -> None:
        for page in pages:
            self._pins[page] = self._pins.get(page, 0) + 1

    def unpin(self, pages: Tuple[int, ...]) -> None:
        with self.pager.lock:
            for page in pages:
                left = self._pins.get(page, 0) - 1
                if left > 0:
                    self._pins[page] = left
                    continue
                self._pins.pop(page, None)
                if page in self._limbo:
                    # the eviction already happened; the page only now
                    # becomes reusable
                    self._limbo.discard(page)
                    self._free.append(page)
                    self.pager.counters["frees"] += 1

    def live_pages_locked(self) -> int:
        """Pages not on the free list: entry-held + limbo."""
        return self.num_pages - len(self._free)

    # -- row addressing ------------------------------------------------

    def flat_rows(self, pages: Tuple[int, ...],
                  valid: int) -> np.ndarray:
        """int32 (valid,) flat slab row of each entry row: row ``r``
        lives at ``pages[r // page_rows] * page_rows + r % page_rows``."""
        r = np.arange(int(valid))
        return (np.asarray(pages, np.int64)[r // self.page_rows]
                * self.page_rows + r % self.page_rows).astype(np.int32)

    # -- slab IO ------------------------------------------------------

    def write_entry_locked(self, pages: Tuple[int, ...], src_pool,
                           src_row0: int, valid: int) -> None:
        """Publish ``valid`` device-pool rows starting at ``src_row0``
        into ``pages``: one donated write per page (fixed page_rows
        index vector — clamp-padded tails land in page rows no gather
        references), swapping the slab value atomically under the
        pager lock."""
        from rnb_tpu.ops.pages import write_rows_page
        slab = self._slab
        for pi, page in enumerate(pages):
            base = pi * self.page_rows
            idx = np.minimum(src_row0 + base + np.arange(self.page_rows),
                             src_row0 + valid - 1).astype(np.int32)
            slab = write_rows_page(slab, src_pool, idx,
                                   page * self.page_rows)
        self._slab = slab

    def gather(self, dest_pool, src_rows, interpret: bool = False):
        """Overlay slab rows onto ``dest_pool`` on device (counted);
        ``src_rows`` is the emission-level int32 table (``-1`` keeps
        the pool row)."""
        from rnb_tpu.ops.pages import gather_rows
        src = np.asarray(src_rows, np.int32)
        with self.pager.lock:
            slab = self._slab
            self.pager.counters[self.gather_keys[0]] += 1
            self.pager.counters[self.gather_keys[1]] += \
                int((src >= 0).sum())
        return gather_rows(dest_pool, slab, src, interpret=interpret)

    def snapshot_locked(self) -> Dict[str, int]:
        return {
            "name": self.name,
            "pages": self.num_pages,
            "page_rows": self.page_rows,
            "page_bytes": self.page_bytes,
            "free": len(self._free),
            "limbo": len(self._limbo),
            "bytes": self.nbytes,
        }


class _FeatureEntry:
    __slots__ = ("pages", "valid", "nbytes")

    def __init__(self, pages: Tuple[int, ...], valid: int,
                 nbytes: int):
        self.pages = pages
        self.valid = int(valid)
        self.nbytes = int(nbytes)


class FeatureCache:
    """Post-stage activation rows on feature pages, keyed by
    (content key, stage fingerprint).

    The consuming stage owns the value semantics: it registers its
    fingerprint + row shape via :meth:`attach` (before the run
    barrier), inserts rows strictly AFTER its forward succeeded, and
    gathers hits from the arena. The loader only probes
    (:meth:`acquire`) and stamps the plan onto the request's time
    card. First writer wins; LRU eviction frees pages until an insert
    fits.
    """

    GUARDED_BY = {
        "_arena": "pager.lock",
        "_fingerprint": "pager.lock",
        "_entries": "pager.lock",
    }

    def __init__(self, pager: "Pager"):
        self.pager = pager
        self._arena: Optional[Arena] = None
        self._fingerprint = None
        self._entries: "OrderedDict[tuple, _FeatureEntry]" = \
            OrderedDict()

    def attach(self, arena: Arena, fingerprint) -> None:
        """Register the consuming stage's arena + fingerprint. Keys
        from other fingerprints (a config change, a different stage)
        can never alias: the fingerprint is part of every entry key."""
        with self.pager.lock:
            self._arena = arena
            self._fingerprint = fingerprint

    @property
    def ready(self) -> bool:
        # _arena is published by attach() under the pager lock; the
        # loader probes from its own threads, so the read pairs with it
        with self.pager.lock:
            return self._arena is not None

    def __len__(self) -> int:
        with self.pager.lock:
            return len(self._entries)

    def acquire(self, content_key) -> Optional[GatherPlan]:
        """Counted feature lookup -> pinned plan on a hit (the caller
        releases after its gather dispatched), None on a miss or
        before any stage attached."""
        with self.pager.lock:
            self.pager.counters["feature_lookups"] += 1
            arena = self._arena
            if arena is None:
                return None
            key = (content_key, self._fingerprint)
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            self.pager.counters["feature_hits"] += 1
            arena.pin_locked(entry.pages)
            return GatherPlan(arena, entry.pages,
                              arena.flat_rows(entry.pages, entry.valid),
                              entry.valid)

    def contains(self, content_key) -> bool:
        with self.pager.lock:
            if self._arena is None:
                return False
            return (content_key, self._fingerprint) in self._entries

    def insert(self, content_key, src_pool, row0: int,
               valid: int) -> bool:
        """Insert ``valid`` output rows (device pool rows
        ``[row0, row0 + valid)``) under ``content_key``. First writer
        wins; evicts LRU entries until the pages fit; skips (False)
        when even a fully-evicted arena cannot hold the entry."""
        valid = int(valid)
        if valid < 1:
            return False
        with self.pager.lock:
            arena = self._arena
            if arena is None:
                return False
            key = (content_key, self._fingerprint)
            if key in self._entries:
                return False
            needed = arena.pages_needed(valid)
            pages = None
            while True:
                pages = arena.alloc_locked(needed)
                if pages is not None or not self._entries:
                    break
                _, evicted = self._entries.popitem(last=False)
                arena.free_locked(evicted.pages)
                self.pager.counters["feature_evictions"] += 1
            if pages is None:
                return False
            arena.write_entry_locked(pages, src_pool, row0, valid)
            self._entries[key] = _FeatureEntry(
                pages, valid, needed * arena.page_bytes)
            self.pager.counters["feature_inserts"] += 1
            return True


class Pager:
    """The per-job page-allocator root: arena registry, shared lock,
    exact counters, and the feature cache. Created by the launcher
    from the ``pager`` root config key and handed to every
    ``SUPPORTS_PAGER`` stage via ``enable_pager``."""

    COUNTER_KEYS = ("allocs", "frees", "alloc_fails", "gathers",
                    "gather_rows", "feature_lookups", "feature_hits",
                    "feature_inserts", "feature_evictions",
                    "feature_gathers", "feature_gather_rows",
                    "feature_bytes_saved")

    GUARDED_BY = {
        "counters": "lock",
        "_arenas": "lock",
        "_size_hint_bytes": "lock",
    }

    def __init__(self, settings: PagerSettings):
        self.settings = settings
        self.lock = lockwitness.lock("Pager.lock", threading.RLock)
        self.counters: Dict[str, int] = {k: 0
                                         for k in self.COUNTER_KEYS}
        self._arenas: List[Arena] = []
        self._size_hint_bytes: Optional[int] = None
        self.feature: Optional[FeatureCache] = \
            FeatureCache(self) if settings.feature_cache else None

    # -- sizing --------------------------------------------------------

    def size_hint(self, nbytes: int) -> None:
        """Feed the sizing figure (the loader's clip cache budget);
        later arenas without an explicit ``pool_mb`` inherit it."""
        with self.lock:
            if nbytes and nbytes > 0:
                self._size_hint_bytes = int(nbytes)

    def resolve_budget(self, requested: Optional[int] = None) -> int:
        """Arena byte budget: explicit ``pool_mb`` wins; else the
        caller's own figure; else the size hint; else the default."""
        if self.settings.pool_mb is not None:
            return int(self.settings.pool_mb * (1 << 20))
        if requested and requested > 0:
            return int(requested)
        with self.lock:
            if self._size_hint_bytes:
                return self._size_hint_bytes
        return DEFAULT_ARENA_MB << 20

    # -- arenas --------------------------------------------------------

    def create_arena(self, name: str, row_shape, dtype,
                     budget_bytes: Optional[int] = None,
                     device=None,
                     gather_keys: Tuple[str, str] = ("gathers",
                                                     "gather_rows")
                     ) -> Arena:
        arena = Arena(self, name, row_shape, dtype,
                      self.resolve_budget(budget_bytes), device=device,
                      gather_keys=gather_keys)
        with self.lock:
            self._arenas.append(arena)
        return arena

    # -- counters ------------------------------------------------------

    def note_feature_saved(self, nbytes: int) -> None:
        """Wire bytes a feature hit did NOT ship host->device (the
        decode+transfer the hit skipped; the skipped forward is time,
        not bytes, and shows up in throughput instead)."""
        with self.lock:
            self.counters["feature_bytes_saved"] += int(nbytes)

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time counter + occupancy copy for the ``Pages:``
        log-meta line and the ``pages.*`` metric polls."""
        with self.lock:
            snap = dict(self.counters)
            snap["arenas"] = len(self._arenas)
            snap["pages"] = sum(a.num_pages for a in self._arenas)
            snap["page_rows"] = int(self.settings.page_rows)
            snap["live"] = sum(a.live_pages_locked()
                               for a in self._arenas)
            snap["limbo"] = sum(len(a._limbo) for a in self._arenas)
            snap["bytes"] = sum(a.nbytes for a in self._arenas)
            snap["feature_entries"] = (len(self.feature._entries)
                                       if self.feature is not None
                                       else 0)
            return snap
