"""HBM footprint ledger: per-device byte accounting with declared owners.

ROADMAP item 3 (paged device memory) needs a page allocator sized from
what actually lives in HBM, and item 7 (push MFU past 55%) needs to
know when activation/cache growth starts stealing the bandwidth the
roofline assumes — but until this PR every byte-owning subsystem kept
its own private count (the clip cache's ``resident_bytes``, the
staging pool's slot slabs, the ragged pool's one dispatch shape, the
shared network parameters, the handoff edge's adopted payloads) and
nothing summed them, tracked a peak, or compared the claim against the
backend's own live-buffer list. This module is that unifying layer:

* **Declared owners** (:data:`MEM_OWNER_REGISTRY`): every byte source
  registers under one of the declared owner names — an undeclared
  owner raises at registration, the runtime twin of the metrics-plane
  rule (rnb_tpu.metrics) that undeclared series fail loudly.
* **Sources, not re-measurement**: each subsystem already tracks its
  own bytes; the ledger holds ``(owner, device, key) -> probe`` entries
  (a callable or a fixed byte count) and sums them on each
  :meth:`MemLedger.sample`. The ``key`` dedupes shared objects —
  replicas share one device parameter copy (``_shared_params``), so
  two stage instances registering the same variables count it once.
* **Peak high-water tracking** per owner and for the total, sampled by
  the devobs worker (rnb_tpu.devobs) and by every metrics flusher tick
  — the ``Memory:`` log-meta line's ``peak_bytes >= total_bytes``
  invariant (``parse_utils --check``) holds by construction.
* **Watermark**: a configurable byte threshold; crossing it (below ->
  at-or-above) warns once per episode, counts a ``watermark_hit``, and
  arms the PR 11 flight recorder (``metrics.trigger``) plus — through
  the registry's trigger hooks — a bounded devobs capture window, so
  the black box records what the device was doing when memory ran hot.
* **Reconciliation** (:meth:`reconcile`): on backends exposing
  ``jax.live_arrays()`` / ``jax.live_buffers()``, the ledger's
  *live-backed* claims (sources registered ``live=True`` — the device
  parameter copies, whose arrays provably persist) must not exceed the
  backend's own byte total. Checked, not trusted: a ledger claiming
  more live device bytes than the backend holds is lying.

Cost discipline: module-level hooks follow the house rule — the
disabled path (no ``devobs`` root config key) is one module-global
``None`` test, no registration happens, and every artifact stays
byte-identical to the pre-devobs schema.
"""

from __future__ import annotations

import sys
import threading
from collections import namedtuple
from typing import Callable, Dict, List, Optional, Tuple, Union

#: the active per-job ledger, installed/cleared by rnb_tpu.benchmark
#: around the measured run (module-global like trace.ACTIVE /
#: metrics.ACTIVE: jobs run one at a time per process)
ACTIVE: Optional["MemLedger"] = None

#: one declared footprint owner — same shape as the telemetry
#: registries (rnb_tpu.telemetry.StampSpec), surfaced by
#: ``parse_utils --stamps``
OwnerSpec = namedtuple("OwnerSpec", ("name", "producer", "description"))

#: every owner name a byte source may register under; the ``Memory
#: owners:`` log-meta line's keys are always a subset of these
MEM_OWNER_REGISTRY = (
    OwnerSpec("params", "rnb_tpu/models/r2p1d/model.py",
              "device-resident network parameter copies (deduped: "
              "replicas sharing one _shared_params copy count once)"),
    OwnerSpec("cache", "rnb_tpu/cache.py",
              "clip-cache resident bytes (padded device batches, or "
              "host row extents under ragged dispatch)"),
    OwnerSpec("staging", "rnb_tpu/staging.py",
              "pre-allocated host staging-slot slabs (the zero-copy "
              "decode targets)"),
    OwnerSpec("ragged_pool", "rnb_tpu/models/r2p1d/model.py",
              "one pool-shaped dispatch input per ragged stage (the "
              "stage's single compiled shape's footprint)"),
    OwnerSpec("handoff", "rnb_tpu/handoff.py",
              "payload bytes resident from the consumer's most recent "
              "edge adoption/reshard (rnb_tpu.handoff)"),
    OwnerSpec("page_pool", "rnb_tpu/pager.py",
              "page-allocator arena slabs (paged clip-cache rows and "
              "feature pages) plus the shared zero pools feature hits "
              "dispatch with (rnb_tpu.pager)"),
)

MEM_OWNERS = tuple(spec.name for spec in MEM_OWNER_REGISTRY)


def register(owner: str, device_label: str, key,
             source: Union[int, Callable[[], int]],
             live: bool = False) -> None:
    """Module-level registration hook: one ``None`` test when the
    ledger is off (no ``devobs`` config key), otherwise
    :meth:`MemLedger.register`."""
    ledger = ACTIVE
    if ledger is None:
        return
    ledger.register(owner, device_label, key, source, live=live)


class _Source:
    __slots__ = ("owner", "device", "fn", "live")

    def __init__(self, owner: str, device: str,
                 fn: Callable[[], int], live: bool):
        self.owner = owner
        self.device = device
        self.fn = fn
        self.live = live


class MemLedger:
    """Bounded, thread-safe per-device byte registry with peaks and a
    watermark. One instance per job, owned by the devobs plane
    (rnb_tpu.devobs); sampled by the devobs worker and by metrics
    flusher polls."""

    GUARDED_BY = {
        "_sources": "_lock",
        "_above_watermark": "_lock",
        "_last": "_lock",
        "_peak_by_owner": "_lock",
        "num_samples": "_lock",
        "peak_total": "_lock",
        "watermark_hits": "_lock",
    }

    def __init__(self, watermark_bytes: Optional[int] = None):
        self._lock = threading.Lock()
        #: (owner, key) -> _Source; the key dedupes shared objects
        self._sources: Dict[Tuple[str, object], _Source] = {}
        self.watermark_bytes = (int(watermark_bytes)
                                if watermark_bytes else 0)
        self.watermark_hits = 0
        self._above_watermark = False
        self.peak_total = 0
        self._peak_by_owner: Dict[str, int] = {}
        self._last: Optional[dict] = None
        self.num_samples = 0
        #: direct watermark observer (the devobs plane's capture
        #: arming) for runs WITHOUT a metrics registry — with metrics
        #: on, the flight-trigger hook path delivers the same event,
        #: and the observer is expected to dedupe (rnb_tpu.devobs
        #: checks metrics.ACTIVE)
        self.on_watermark: Optional[Callable[[int], None]] = None

    # -- registration --------------------------------------------------

    def register(self, owner: str, device_label: str, key,
                 source: Union[int, Callable[[], int]],
                 live: bool = False) -> None:
        """Register one byte source under a declared owner.

        ``key`` identifies the underlying object — a second
        registration with the same ``(owner, key)`` replaces rather
        than double-counts (replicas sharing one parameter copy).
        ``source`` is a fixed byte count or a zero-arg probe returning
        the current bytes; ``live=True`` marks sources whose bytes are
        provably backed by persistent device arrays (they enter the
        :meth:`reconcile` comparison).
        """
        if owner not in MEM_OWNERS:
            # runtime twin of the metrics-plane rule: an undeclared
            # owner fails loudly at registration, not as silent drift
            # in the Memory: footing
            raise ValueError(
                "memory owner %r is not declared in "
                "memledger.MEM_OWNER_REGISTRY — declare it or fix the "
                "registration site" % (owner,))
        if callable(source):
            fn = source
        else:
            nbytes = int(source)
            fn = lambda: nbytes  # noqa: E731 — fixed-count probe
        with self._lock:
            self._sources[(owner, key)] = _Source(
                owner, str(device_label), fn, bool(live))

    # -- sampling ------------------------------------------------------

    def sample(self) -> dict:
        """Probe every source, update peaks, evaluate the watermark.

        Returns ``{"total": int, "owners": {owner: bytes}, "devices":
        {device: bytes}}``. Crossing the watermark (below ->
        at-or-above) warns on stderr once per episode, counts one
        ``watermark_hit`` and arms the flight recorder
        (``metrics.trigger``) — trigger hooks then also arm a devobs
        capture window."""
        with self._lock:
            sources = list(self._sources.values())
        owners: Dict[str, int] = {}
        devices: Dict[str, int] = {}
        total = 0
        for src in sources:
            try:
                nbytes = int(src.fn())
            except Exception:
                continue  # a dying probe must not kill the sampler
            owners[src.owner] = owners.get(src.owner, 0) + nbytes
            devices[src.device] = devices.get(src.device, 0) + nbytes
            total += nbytes
        crossed = False
        with self._lock:
            self.num_samples += 1
            self.peak_total = max(self.peak_total, total)
            for owner, nbytes in owners.items():
                self._peak_by_owner[owner] = max(
                    self._peak_by_owner.get(owner, 0), nbytes)
            if self.watermark_bytes > 0:
                above = total >= self.watermark_bytes
                if above and not self._above_watermark:
                    crossed = True
                    self.watermark_hits += 1
                self._above_watermark = above
            record = {"total": total, "owners": owners,
                      "devices": devices}
            self._last = record
        if crossed:
            print("[rnb-tpu] WARNING: memory ledger total %d B crossed "
                  "the %d B watermark" % (total, self.watermark_bytes),
                  file=sys.stderr)
            from rnb_tpu import metrics
            metrics.trigger(metrics.TRIGGER_MEMORY_WATERMARK,
                            {"total_bytes": total,
                             "watermark_bytes": self.watermark_bytes})
            hook = self.on_watermark
            if hook is not None:
                try:
                    hook(total)
                except Exception:
                    pass  # an observer must not break the sampler
        return record

    def peek(self) -> "Optional[dict]":
        """The most recent :meth:`sample` record WITHOUT probing —
        no peak/num_samples updates, no watermark evaluation, no
        trigger side effects. The operator plane's read
        (rnb_tpu.statusz /statusz): an ungated GET must never mutate
        ledger state or fire actuation hooks. None until the devobs
        worker has sampled once."""
        with self._lock:
            return self._last

    # -- reconciliation ------------------------------------------------

    @staticmethod
    def _live_backend_bytes() -> int:
        """Total bytes of the backend's own live array list."""
        import jax
        return sum(int(arr.nbytes) for arr in jax.live_arrays())

    def reconcile(self) -> Tuple[int, bool]:
        """-> ``(live_bytes, ok)``: the backend's live-buffer byte
        total and whether the ledger's live-backed claims fit inside
        it. ``live_bytes == 0`` means the backend exposes no live list
        (``ok`` is then vacuously False — "not reconciled", distinct
        from "reconciled and violated")."""
        live_bytes = self._live_backend_bytes()
        if live_bytes <= 0:
            return 0, False
        with self._lock:
            sources = list(self._sources.values())
        claimed = 0
        for src in sources:
            if not src.live:
                continue
            try:
                claimed += int(src.fn())
            except Exception:
                continue
        return live_bytes, claimed <= live_bytes

    # -- reporting -----------------------------------------------------

    def snapshot(self) -> dict:
        """Final footing record for the ``Memory:`` / ``Memory
        owners:`` log-meta lines: re-samples so the totals reflect the
        settled end-of-run state, then attaches peaks."""
        record = self.sample()
        with self._lock:
            owners_detail = {
                owner: {"bytes": record["owners"].get(owner, 0),
                        "peak_bytes": self._peak_by_owner.get(owner, 0)}
                for owner in sorted(set(record["owners"])
                                    | set(self._peak_by_owner))}
            return {
                "total_bytes": record["total"],
                "peak_bytes": self.peak_total,
                "owners": owners_detail,
                "devices": dict(record["devices"]),
                "watermark_bytes": self.watermark_bytes,
                "watermark_hits": self.watermark_hits,
            }
