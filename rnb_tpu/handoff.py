"""Device-resident inter-stage handoff: the edge contract.

Until PR 9 every inter-stage tensor edge had ONE implicit shape: the
producer synced its device output (``sync_outputs``), parked the
arrays in a ring slot, and the consumer's stage model re-homed them
with its own ``jax.device_put`` — correct, but invisible: nothing
said whether a given edge actually moved bytes device-to-device or
bounced them through host memory, and nothing *enforced* either. This
module makes the edge an explicit, accounted contract the executor
applies when the config's root ``handoff`` key is present:

* ``mode: "device"`` — **device-resident**: the queue/ring carries
  committed on-device ``jax.Array`` values by reference. A payload
  already homed on the consumer's device is adopted as-is (zero-copy
  take, no transfer, no host bounce); a payload on a *different*
  device of the host's mesh is re-homed with an on-device resharding
  (``jax.device_put`` onto the consumer's device or — for stages that
  declare a :meth:`StageModel` ``input_sharding()`` — its
  ``NamedSharding``), with a Pallas ``make_async_remote_copy`` fast
  path gated to real TPU hardware and a ``shard_map``/``ppermute``
  CPU-testable twin (:mod:`rnb_tpu.ops.handoff_dma`). The host is
  never materialized; rnb-lint RNB-H008 rejects any
  ``device_get``/``np.asarray`` creeping into this path statically.
* ``mode: "host"`` — the explicit host round trip (device →
  ``np.asarray`` → ``device_put``), kept as the measurable A/B
  baseline arm and for backends whose D2D path is broken. Every byte
  it moves is counted, so "the device-resident edge moved zero host
  bytes" is a provable log statement, not an assertion.
* no ``handoff`` key — exactly the pre-PR behavior: the stage model's
  own ``device_put`` re-homes, no accounting, logs stay byte-stable.

Ownership (donation safety, mirroring the staging-slot lifecycle in
:mod:`rnb_tpu.staging`): the producer *commits* a payload by writing
it to the ring slot — from that instant it must neither mutate nor
donate the arrays (``jax.Array`` immutability gives the former; the
publish path never passes arrays to a donating jit, which gives the
latter). The consumer's take is the ownership transfer: an adopted
same-device array is owned jointly (both sides may read, neither may
donate it to a jit — exactly like a cached ClipCache value), while a
resharded take produces a fresh consumer-owned array and the
producer's copy dies with the ring-slot release. A stage that wants
to donate its input into its jit must therefore run under
``mode: "host"`` or make its own defensive copy — the contract trades
that freedom for the removed transfer.

Accounting (the ``Handoff:`` log-meta line, ``handoff_*``
BenchmarkResult fields, ``parse_utils --check`` invariants): every
consumer-side take of a tensor payload is one *edge event*, classified
``d2d`` (adopted or device-to-device resharded) or ``host`` (bounced
through numpy), with the payload bytes attributed to the class that
moved them — adopted same-device takes move zero bytes and count 0.
``d2d_edges + host_edges == edges`` always; a device-resident config
must report ``host_bytes == 0``.

Precisely: ``host`` counts takes where the edge *materialized a
device payload on the host* — the avoidable bounce this contract
exists to delete. A payload a producer publishes as host memory in
the first place (a numpy-emitting stage) has no host hop for the
edge to add or avoid; its one unavoidable upload counts under
``d2d_bytes`` (bytes the edge moved onto the device), so the
``host_bytes == 0`` promise reads "this edge added zero host
round-trips", not "no producer ever touched host memory".
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rnb_tpu.ops.handoff_dma import reshard
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy

#: modes the root ``handoff`` config key accepts
HANDOFF_MODES = ("device", "host")


class HandoffSettings:
    """Validated, defaulted view of the ``handoff`` root config key."""

    def __init__(self, mode: str):
        if mode not in HANDOFF_MODES:
            raise ValueError("handoff mode must be one of %s, got %r"
                             % (list(HANDOFF_MODES), mode))
        self.mode = mode

    @staticmethod
    def from_config(raw: Optional[dict]) -> Optional["HandoffSettings"]:
        """Settings from the (schema-validated) config dict, or None
        when the key is absent or ``enabled`` is false — absent means
        the pre-handoff edge semantics, byte-stable logs included."""
        if not raw or not raw.get("enabled", True):
            return None
        return HandoffSettings(raw.get("mode", "device"))


class EdgeHandoff:
    """One consumer stage instance's side of the edge contract.

    Built by the stage executor (rnb_tpu.runner) after stage
    construction — the stage may refine the re-home target via an
    ``input_sharding()`` method returning a ``NamedSharding`` (the
    mesh runner's clip-axis sharding) — and consulted once per ring
    payload take. Single-threaded like the stage itself; the snapshot
    is read after the stage drained.
    """

    def __init__(self, settings: HandoffSettings, device,
                 edge: str, model=None):
        self.mode = settings.mode
        self.edge = str(edge)
        self._device = (device.resolve() if hasattr(device, "resolve")
                        else device)
        # stages homed on a mesh declare the sharding their inputs
        # should land on; everything else re-homes to the home device
        self._target = self._device
        sharding_fn = getattr(model, "input_sharding", None)
        if sharding_fn is not None:
            target = sharding_fn()
            if target is not None:
                self._target = target
        # -- accounting (snapshot/log-meta schema) --------------------
        self.d2d_edges = 0
        self.host_edges = 0
        self.d2d_bytes = 0
        self.host_bytes = 0

    # -- the take -----------------------------------------------------

    def take(self, payload: Tuple) -> Tuple:
        """Apply the edge contract to one ring payload (a tuple of
        PaddedBatch/RaggedBatch): returns the consumer-resident
        payload and records the edge event. The batch wrappers are
        re-built around the re-homed arrays with their valid counts
        (and segment tables) intact."""
        if self.mode == "host":
            return self._take_host(payload)
        return self._take_device(payload)

    def _rewrap(self, pb, data):
        """A new batch wrapper of pb's kind around re-homed data."""
        offsets = getattr(pb, "segment_offsets", None)
        if offsets is not None:
            return type(pb)(data, pb.valid, offsets)
        return type(pb)(data, pb.valid)

    def _take_device(self, payload: Tuple) -> Tuple:
        """Device-resident take: adopt same-device arrays by
        reference; reshard cross-device arrays on-device (DMA fast
        path on real TPU, plain device_put otherwise). No host
        materialization on this path — rnb-lint RNB-H008 enforces it
        statically."""
        jax, _ = _jax_numpy()
        out: List[Any] = []
        moved = 0
        for pb in payload:
            data = pb.data
            if isinstance(data, jax.Array) \
                    and self._is_resident(data):
                out.append(pb)  # committed array adopted by reference
                continue
            rehomed = reshard(data, self._target)
            moved += int(getattr(data, "nbytes", 0))
            out.append(self._rewrap(pb, rehomed))
        self.d2d_edges += 1
        self.d2d_bytes += moved
        return tuple(out)

    def _is_resident(self, data) -> bool:
        """Is this committed array already where the consumer wants
        it? (Single-device home: exactly this device. Sharding home:
        identical sharding.)"""
        try:
            if hasattr(self._target, "device_set"):  # a Sharding
                return data.sharding == self._target
            devices = data.devices()
        except Exception:
            return False
        return devices == {self._target}

    def _take_host(self, payload: Tuple) -> Tuple:
        """The explicit host round trip (the A/B baseline arm): every
        payload byte bounces through a numpy buffer before the
        consumer-side upload — the cost the device-resident mode
        exists to delete, here so it stays measurable."""
        jax, _ = _jax_numpy()
        out: List[Any] = []
        moved = 0
        for pb in payload:
            host = np.asarray(pb.data)
            moved += int(host.nbytes)
            out.append(self._rewrap(
                pb, jax.device_put(host, self._device)))
        self.host_edges += 1
        self.host_bytes += moved
        return tuple(out)

    # -- reporting ----------------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """Final per-edge counters for the job-wide aggregation
        (BenchmarkResult ``handoff_*`` fields / log-meta ``Handoff:``
        + ``Handoff edges:`` lines)."""
        return {
            "edge": self.edge,
            "mode": self.mode,
            "d2d_edges": self.d2d_edges,
            "host_edges": self.host_edges,
            "d2d_bytes": self.d2d_bytes,
            "host_bytes": self.host_bytes,
        }


def aggregate_snapshots(snapshots: List[Dict[str, object]]
                        ) -> Dict[str, object]:
    """Sum per-instance edge snapshots into the job-wide view plus the
    per-edge detail dict (edge label -> summed counters) the
    ``Handoff edges:`` JSON line carries."""
    out: Dict[str, object] = {"edges": 0, "d2d_edges": 0,
                              "host_edges": 0, "d2d_bytes": 0,
                              "host_bytes": 0}
    detail: Dict[str, Dict[str, int]] = {}
    for snap in snapshots:
        per = detail.setdefault(str(snap.get("edge", "?")),
                                {"d2d_edges": 0, "host_edges": 0,
                                 "d2d_bytes": 0, "host_bytes": 0})
        for key in ("d2d_edges", "host_edges", "d2d_bytes",
                    "host_bytes"):
            n = int(snap.get(key, 0))
            out[key] += n
            per[key] += n
    out["edges"] = out["d2d_edges"] + out["host_edges"]
    out["edge_detail"] = detail
    return out


class InflightDepths:
    """Per-replica in-flight depth counters for least-loaded routing.

    One instance per replica-expanded step, shared by the upstream
    producers' :class:`rnb_tpu.selector.ReplicaSelector` (reads +
    increments at enqueue) and the replica executors (decrement once
    the popped item's processing completes). Depth therefore counts
    queued *plus* in-service dispatches — a replica wedged on a slow
    batch keeps its depth high and stops receiving work, which a bare
    ``queue.qsize()`` poll would miss.
    """

    GUARDED_BY = {"_depths": "_lock"}

    def __init__(self, queue_indices):
        self._lock = threading.Lock()
        self._depths: Dict[int, int] = {int(q): 0
                                        for q in queue_indices}

    def inc(self, queue_idx: int, n: int = 1) -> None:
        with self._lock:
            if queue_idx in self._depths:
                self._depths[queue_idx] += n

    def dec(self, queue_idx: int, n: int = 1) -> None:
        with self._lock:
            if queue_idx in self._depths:
                self._depths[queue_idx] -= n

    def depth(self, queue_idx: int) -> int:
        with self._lock:
            return self._depths.get(queue_idx, 0)

    def snapshot(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._depths)
