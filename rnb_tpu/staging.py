"""Zero-copy fused decode staging + pipelined host->device transfer.

Round 5 measured the single bench-host core at 98% saturation with the
two dominant terms being raw byte movement: ``device_put`` staging
(49.3% of the window) and decode-output assembly + decode wait (22.1%)
— 2026-07, previous transport, not reproduced; see the motivation in
``rnb_tpu/cache.py``. The clip cache removes those terms for popularity-skewed *hits*; this
module removes them for the miss/uniform hot path itself:

* **StagingPool** — per-(loader, bucket-shape) sets of pre-allocated
  C-contiguous host slots with an explicit lifecycle
  (``free -> decoding -> transferring -> free``). The fusing loader
  plans row placement at submit time, so the native
  ``DecodePool.submit_into`` decodes each request **directly into its
  disjoint row-slice of a slot** — the fused batch is assembled by the
  decoder itself and the per-emission ``np.empty`` + per-row memcpy
  (``loader.emit_alloc`` / ``loader.emit_copy``) vanish on the native
  path. A slot is recycled only after every planned decode retired its
  reference AND every transfer from it is confirmed complete; slot
  exhaustion backpressures the submitter (counted ``acquire_waits``,
  never silently dropped).

* **TransferWorker** — a dedicated per-stage thread that issues
  ``device_put`` for fused batch N while batch N+1 decodes into the
  next slot (double/triple buffering via the ``staging_slots`` config
  knob; opt-in per step via ``transfer_async``). The executor thread
  hands a finished assembly off and immediately returns to
  submitting/harvesting; completed transfers surface back through the
  stage's ``take_ready()`` hook, which the executor drains ahead of
  new input (rnb_tpu.runner publish handoff).

Alias safety (the subtle part): on some backends — notably the CPU
backend tier-1 runs on — ``jax.device_put`` of a host array may
*alias* the host buffer instead of copying (alignment-dependent).
Recycling an aliased slot would corrupt a live in-flight batch, so
transfer confirmation probes the produced array's buffer pointer
against the slot's memory range; an aliased slot gets a **fresh
backing buffer** before reuse (counted ``reallocs``) and the old
buffer's ownership rides with the device array. On real TPUs the
transfer is a genuine host->HBM copy, the probe never fires, and slots
recycle with zero allocation.

Padding bytes stay zeroed exactly as on the seed copy path, so staged
and copied emissions are byte-identical end to end (golden-logit
parity, ``tests/test_staging.py``).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from rnb_tpu import lockwitness, trace
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy

#: slot lifecycle states (kept as strings for cheap introspection)
FREE, DECODING, TRANSFERRING = "free", "decoding", "transferring"


def _aliases(device_array, buf: np.ndarray) -> bool:
    """Does ``device_array``'s backing buffer live inside ``buf``'s
    memory range? Conservative: an unprobeable array is treated as
    aliased (the slot gets a fresh buffer — one allocation, never a
    corruption)."""
    try:
        ptr = int(device_array.unsafe_buffer_pointer())
    except Exception:
        return True
    base = int(buf.ctypes.data)
    return base <= ptr < base + int(buf.nbytes)


class StagingSlot:
    """One pre-allocated C-contiguous host buffer plus its lifecycle
    accounting. ``refs`` counts planned decodes whose rows are still
    live in the buffer; ``transfers`` counts handed-off-but-unconfirmed
    device transfers; ``pending_confirm`` holds device arrays whose
    transfer completion is confirmed lazily at the next acquire (the
    double-buffering gate). ``dtype`` follows the owning loader's wire
    dtype (uint8 pixel/plane rows; int16 packed dct coefficient
    rows)."""

    __slots__ = ("buf", "shape", "dtype", "state", "refs", "transfers",
                 "pending_confirm", "tainted")

    def __init__(self, shape: Tuple[int, ...], dtype=np.uint8):
        self.dtype = np.dtype(dtype)
        self.buf = np.empty(shape, dtype=self.dtype)
        self.shape = tuple(shape)
        self.state = FREE
        self.refs = 0
        self.transfers = 0
        self.pending_confirm: List[Any] = []
        #: a confirmed transfer aliased this buffer: replace it before
        #: the slot is handed out again
        self.tainted = False

    @property
    def nbytes(self) -> int:
        return int(self.buf.nbytes)


class StagingPool:
    """Bounded per-shape pools of staging slots with counted
    backpressure.

    All mutators take the pool lock; ``acquire`` blocks (counted) when
    every slot of the requested shape is busy — exhaustion
    backpressures the submitter, it never drops work. A worker-thread
    failure recorded via :meth:`fail` re-raises out of ``acquire`` and
    :meth:`raise_if_failed` so a dead transfer pipeline can never
    silently hang the executor.
    """

    #: declared concurrency contract (rnb-lint RNB-C001/C003); the
    #: ``_available`` Condition is built ON ``_lock``, so holding
    #: either is the same critical section
    GUARDED_BY = {
        "_slots": "_lock",
        "_error": "_lock",
        "num_acquires": "_lock",
        "num_acquire_waits": "_lock",
        "num_staged_batches": "_lock",
        "num_copied_batches": "_lock",
        "num_bypassed_batches": "_lock",
        "num_reallocs": "_lock",
    }

    def __init__(self, shapes: Sequence[Tuple[int, ...]],
                 slots_per_shape: int, dtype=np.uint8):
        if slots_per_shape < 1:
            raise ValueError("slots_per_shape must be >= 1, got %r"
                             % (slots_per_shape,))
        self.dtype = np.dtype(dtype)
        self._lock = lockwitness.lock("StagingPool._lock")
        self._available = threading.Condition(self._lock)
        self._slots: Dict[Tuple[int, ...], List[StagingSlot]] = {}
        for shape in shapes:
            shape = tuple(int(d) for d in shape)
            if shape not in self._slots:
                self._slots[shape] = [StagingSlot(shape, self.dtype)
                                      for _ in range(slots_per_shape)]
        self.slots_per_shape = int(slots_per_shape)
        self._error: Optional[BaseException] = None
        # exact counters, surfaced end-to-end (BenchmarkResult /
        # log-meta `Staging:` line / parse_utils)
        self.num_acquires = 0
        self.num_acquire_waits = 0
        self.num_staged_batches = 0
        self.num_copied_batches = 0
        self.num_bypassed_batches = 0
        self.num_reallocs = 0

    # -- lifecycle ----------------------------------------------------

    def _claim_pending_locked(self, slot: StagingSlot) -> List[Any]:
        """Detach a just-claimed slot's lazily-pending transfers for
        confirmation OUTSIDE the lock: the slot's state is already
        DECODING, so no other acquirer can reach it, and the device
        sync the confirmation blocks on must never run under the pool
        lock (rnb-lint RNB-C005 — it would stall every producer and
        worker behind one device round-trip)."""
        lockwitness.require("StagingPool._lock")
        pending, slot.pending_confirm = slot.pending_confirm, []
        return pending

    def _confirm_claimed(self, slot: StagingSlot,
                         pending: List[Any]) -> None:
        """Retire the detached pending transfers of a slot this caller
        claimed: wait for the device copies, probe for host-buffer
        aliasing, and swap in a fresh buffer when a device array took
        ownership of this one. Runs WITHOUT the pool lock — the slot
        is owner-private (state DECODING) until the caller hands it
        on, so ``buf``/``tainted`` cannot race."""
        if pending:
            jax, _ = _jax_numpy()
            for arr in pending:
                jax.block_until_ready(arr)
                if _aliases(arr, slot.buf):
                    slot.tainted = True
        if slot.tainted:
            # the device array owns (aliases) the old buffer — replace
            # it rather than corrupt the live batch. One np.empty, no
            # copy: still cheaper than the seed alloc+memcpy path.
            slot.buf = np.empty(slot.shape, dtype=slot.dtype)
            slot.tainted = False
            with self._lock:
                self.num_reallocs += 1

    def _acquirable_locked(self, shape) -> Optional[StagingSlot]:
        for slot in self._slots[shape]:
            if slot.state == FREE and slot.refs == 0 \
                    and slot.transfers == 0:
                return slot
        return None

    def try_acquire(self, shape) -> Optional[StagingSlot]:
        """A free slot of ``shape`` (confirm-processed), or None."""
        shape = tuple(int(d) for d in shape)
        with self._lock:
            self.raise_if_failed_locked()
            if shape not in self._slots:
                # shapes are pre-registered at construction; an unseen
                # shape (e.g. a config change) gets its own sub-pool
                self._slots[shape] = [StagingSlot(shape, self.dtype)
                                      for _ in range(self.slots_per_shape)]
            slot = self._acquirable_locked(shape)
            if slot is None:
                return None
            slot.state = DECODING
            self.num_acquires += 1
            pending = self._claim_pending_locked(slot)
        self._confirm_claimed(slot, pending)
        return slot

    def acquire(self, shape) -> StagingSlot:
        """Blocking acquire: counted backpressure on exhaustion."""
        slot = self.try_acquire(shape)
        if slot is not None:
            return slot
        shape = tuple(int(d) for d in shape)
        with self._lock:
            self.num_acquire_waits += 1
        with trace.span("staging.acquire_wait"):
            while True:
                pending = None
                with self._available:
                    self.raise_if_failed_locked()
                    slot = self._acquirable_locked(shape)
                    if slot is None:
                        self._available.wait(timeout=0.05)
                        slot = self._acquirable_locked(shape)
                    if slot is not None:
                        slot.state = DECODING
                        self.num_acquires += 1
                        pending = self._claim_pending_locked(slot)
                if slot is not None:
                    self._confirm_claimed(slot, pending)
                    return slot

    def add_ref(self, slot: StagingSlot) -> None:
        """One more planned decode targets rows of this slot."""
        with self._lock:
            slot.refs += 1

    def retire_ref(self, slot: StagingSlot) -> None:
        """A planned decode is done with its rows (emitted, failed,
        discarded, or re-decoded elsewhere)."""
        with self._available:
            slot.refs -= 1
            assert slot.refs >= 0, "staging ref underflow"
            self._maybe_free_locked(slot)

    def begin_transfer(self, slot: StagingSlot) -> None:
        """The slot's bytes are being handed to a device transfer."""
        with self._lock:
            slot.state = TRANSFERRING
            slot.transfers += 1

    def finish_transfer(self, slot: StagingSlot, device_array=None
                        ) -> None:
        """A transfer was issued. With ``device_array`` given, its
        completion is confirmed lazily at the slot's next acquire (the
        executor never blocks); pass None when the caller already
        confirmed (:meth:`confirm_now`, the transfer worker)."""
        with self._available:
            if device_array is not None:
                slot.pending_confirm.append(device_array)
            slot.transfers -= 1
            assert slot.transfers >= 0, "staging transfer underflow"
            self._maybe_free_locked(slot)

    def confirm_now(self, slot: StagingSlot, device_array) -> None:
        """Synchronously confirm one transfer (off-executor callers:
        the TransferWorker). Blocks until the device copy is done,
        probes for aliasing, then releases the transfer hold."""
        jax, _ = _jax_numpy()
        jax.block_until_ready(device_array)
        with self._available:
            if _aliases(device_array, slot.buf):
                slot.tainted = True
            slot.transfers -= 1
            assert slot.transfers >= 0, "staging transfer underflow"
            self._maybe_free_locked(slot)

    def _maybe_free_locked(self, slot: StagingSlot) -> None:
        if slot.refs == 0 and slot.transfers == 0:
            slot.state = FREE
            self._available.notify_all()

    # -- accounting ---------------------------------------------------

    def note_staged(self) -> None:
        with self._lock:
            self.num_staged_batches += 1

    def note_copied(self) -> None:
        with self._lock:
            self.num_copied_batches += 1

    def note_bypassed(self) -> None:
        """An emission shipped with **zero** host->device bytes — every
        row was gathered on-device from the page allocator (full
        cache-hit or feature-page hit, rnb_tpu.pager). No slot was
        acquired and no transfer issued; counted separately so the
        staged/copied split still foots against transfer-carrying
        emissions only."""
        with self._lock:
            self.num_bypassed_batches += 1

    def fail(self, exc: BaseException) -> None:
        """Record a transfer-pipeline failure; every later acquire /
        raise_if_failed re-raises it (no silent hang)."""
        with self._available:
            if self._error is None:
                self._error = exc
            self._available.notify_all()

    def raise_if_failed_locked(self) -> None:
        if self._error is not None:
            raise self._error

    def raise_if_failed(self) -> None:
        with self._lock:
            self.raise_if_failed_locked()

    def available(self, shape=None) -> int:
        """Free-slot count (one shape, or all) — test/introspection."""
        with self._lock:
            pools = ([self._slots[tuple(int(d) for d in shape)]]
                     if shape is not None else self._slots.values())
            return sum(1 for slots in pools for s in slots
                       if s.state == FREE and s.refs == 0
                       and s.transfers == 0)

    def total_slots(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._slots.values())

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time counter copy for reports (additive across
        stage instances, like rnb_tpu.cache snapshots)."""
        with self._lock:
            return {
                "slots": sum(len(s) for s in self._slots.values()),
                "slot_bytes": sum(slot.nbytes
                                  for slots in self._slots.values()
                                  for slot in slots),
                "acquires": self.num_acquires,
                "acquire_waits": self.num_acquire_waits,
                "staged_batches": self.num_staged_batches,
                "copied_batches": self.num_copied_batches,
                "bypassed_batches": self.num_bypassed_batches,
                "reallocs": self.num_reallocs,
            }


def aggregate_snapshots(snapshots: List[Dict[str, int]]) -> Dict[str, int]:
    """Sum per-instance staging snapshots into one job-wide record
    (every counter is additive; slots/slot_bytes sum because each
    instance owns its own pool)."""
    total = {"slots": 0, "slot_bytes": 0, "acquires": 0,
             "acquire_waits": 0, "staged_batches": 0,
             "copied_batches": 0, "bypassed_batches": 0,
             "reallocs": 0}
    for snap in snapshots:
        for k in total:
            total[k] += int(snap.get(k, 0))
    return total


class TransferWorker:
    """A single dedicated thread running host->device transfer jobs.

    The executor thread enqueues a finished fused assembly and returns
    to submitting/harvesting immediately; the worker issues the
    ``device_put`` (batch N transferring while batch N+1 decodes into
    the next slot). Job errors are captured — not swallowed — and
    re-raised on the executor thread via :meth:`raise_if_failed`
    (wired through the stage's ``take_ready()``).
    """

    GUARDED_BY = {
        "_jobs": "_lock",
        "_outstanding": "_lock",
        "_error": "_lock",
        "_closed": "_lock",
    }

    def __init__(self, name: str = "rnb-transfer",
                 pool: Optional[StagingPool] = None):
        self._jobs: "deque[Optional[Callable[[], None]]]" = deque()
        self._lock = lockwitness.lock("TransferWorker._lock")
        self._wake = threading.Condition(self._lock)
        self._outstanding = 0
        self._error: Optional[BaseException] = None
        self._pool = pool
        self._closed = False
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def submit(self, job: Callable[[], None]) -> None:
        with self._wake:
            if self._closed:
                raise RuntimeError("TransferWorker is closed")
            self.raise_if_failed_locked()
            self._jobs.append(job)
            self._outstanding += 1
            self._wake.notify_all()

    def outstanding(self) -> int:
        with self._lock:
            return self._outstanding

    def raise_if_failed_locked(self) -> None:
        if self._error is not None:
            raise self._error

    def raise_if_failed(self) -> None:
        with self._lock:
            self.raise_if_failed_locked()

    def _run(self) -> None:
        while True:
            with self._wake:
                while not self._jobs and not self._closed:
                    self._wake.wait(timeout=0.1)
                if not self._jobs and self._closed:
                    return
                job = self._jobs.popleft()
            try:
                with trace.span("transfer.job"):
                    job()
            except BaseException as exc:  # noqa: BLE001 — surfaced
                with self._wake:
                    if self._error is None:
                        self._error = exc
                if self._pool is not None:
                    self._pool.fail(exc)
            finally:
                with self._wake:
                    self._outstanding -= 1
                    self._wake.notify_all()

    def close(self, timeout: float = 30.0) -> None:
        """Drain remaining jobs (transfers keep slot accounting
        balanced even on the abort path), then stop the thread."""
        with self._wake:
            self._closed = True
            self._wake.notify_all()
        self._thread.join(timeout=timeout)
