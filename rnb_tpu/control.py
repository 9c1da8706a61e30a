"""The channel runtime: control queues, device buffer rings, termination.

The pipeline's communication fabric, re-designed for a single-controller
TPU runtime (capability parity with the reference's control.py:1-209):

* **Control messages** travel through bounded ``queue.Queue`` channels as
  ``(Signal|None, non_tensors, TimeCard)`` tuples — never bulk tensors.
  Queue overflow is a *failure signal*, not backpressure: the run aborts
  with a reason code (reference semantics, README/runner.py:230-234).
* **Bulk data** lives in per-instance :class:`BufferRing` s — a bounded
  pool of slots, each holding a tuple of immutable device arrays plus
  their valid-row counts. A slot's ``free`` event provides the
  producer/consumer ownership handoff the reference implemented with
  ``mp.Event`` over shared CUDA tensors (control.py:19-46). Because JAX
  arrays are immutable there is no data race to guard — the ring's job
  here is *backpressure*: a producer blocks when all its slots hold
  unconsumed outputs, bounding device memory exactly like the
  reference's pre-allocated tensor pool.
* **Coordination**: a :class:`TerminationState` any stage may raise
  (first writer wins), inspected at every loop top; threading barriers
  fence start/finish so init and teardown stay out of timing windows.

Stage hand-off across devices happens when the *consumer* re-homes the
arrays with ``jax.device_put`` onto its own device — on TPU hardware an
ICI transfer, the analog of the reference's cross-GPU ``copy_``
(runner.py:104-114).
"""

from __future__ import annotations

import enum
import math
import queue
import threading
from collections import namedtuple
from typing import Dict, List, Optional, Tuple

from rnb_tpu.config import (  # DEFAULT_... re-exported for back-compat
    DEFAULT_NUM_SHARED_TENSORS, ConfigError, PipelineConfig)
from rnb_tpu.devices import DeviceSpec
from rnb_tpu.utils.class_utils import load_class

#: sentinel count marking end-of-stream on every edge (reference
#: client.py:9, runner.py:3)
NUM_EXIT_MARKERS = 10


class TerminationFlag(enum.IntEnum):
    """Job-wide termination reason codes (reference control.py:11-16;
    INTERNAL_ERROR is ours — the reference had no code for a crashed
    stage and could hang on one)."""

    UNSET = -1
    TARGET_NUM_VIDEOS_REACHED = 0
    FILENAME_QUEUE_FULL = 1
    FRAME_QUEUE_FULL = 2
    INTERNAL_ERROR = 3


class TerminationState:
    """A raise-once job termination flag shared by every stage thread.

    Any thread may raise it with a reason code; the first raise wins.
    Replaces the reference's lock-free shared ``Value`` write
    (runner.py:193) with an explicit first-writer-wins rule so the
    recorded reason is deterministic.
    """

    UNGUARDED_OK = {
        "_value": "first-writer-wins under _lock; bare reads observe "
                  "a monotone raise-once flag",
    }

    def __init__(self):
        self._value = TerminationFlag.UNSET
        self._lock = threading.Lock()

    @property
    def value(self) -> TerminationFlag:
        return self._value

    def raise_flag(self, code: TerminationFlag) -> None:
        with self._lock:
            if self._value == TerminationFlag.UNSET:
                self._value = TerminationFlag(code)

    @property
    def terminated(self) -> bool:
        return self._value != TerminationFlag.UNSET


class FaultStats:
    """Job-wide fault accounting shared by the client and every stage
    executor (rnb_tpu.runner containment layer).

    Counts contained permanent failures (with per-reason totals and a
    bounded dead-letter record of ``(request_id, step_idx, reason)``),
    shed requests per site, transient retries, and per-edge queue
    overflows (the abort-policy full-queue events that used to be an
    unparseable stdout warning — now a counter surfaced in
    BenchmarkResult and the log-meta ``Queue overflows:`` line). All
    exact counts; only the dead-letter *detail* list is capped so a
    pathological run cannot grow controller memory without bound.
    """

    MAX_DEAD_LETTERS = 1000

    GUARDED_BY = {
        "num_failed": "_lock",
        "num_shed": "_lock",
        "num_retries": "_lock",
        "failure_reasons": "_lock",
        "shed_sites": "_lock",
        "overflow_sites": "_lock",
        "dead_letters": "_lock",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self.num_failed = 0
        self.num_shed = 0
        self.num_retries = 0
        self.failure_reasons: Dict[str, int] = {}
        self.shed_sites: Dict[str, int] = {}
        self.overflow_sites: Dict[str, int] = {}
        self.dead_letters: List[tuple] = []

    def record_failure(self, request_ids, step_idx: int,
                       reason: str) -> None:
        """Dead-letter one or more requests (a fused batch fails as a
        unit) with one reason at one step."""
        with self._lock:
            self.num_failed += len(request_ids)
            self.failure_reasons[reason] = \
                self.failure_reasons.get(reason, 0) + len(request_ids)
            for rid in request_ids:
                if len(self.dead_letters) < self.MAX_DEAD_LETTERS:
                    self.dead_letters.append((rid, step_idx, reason))

    def record_shed(self, site: str, n: int = 1) -> None:
        with self._lock:
            self.num_shed += n
            self.shed_sites[site] = self.shed_sites.get(site, 0) + n

    def record_retries(self, n: int = 1) -> None:
        with self._lock:
            self.num_retries += n

    def record_overflow(self, edge: str, n: int = 1) -> None:
        """One inter-stage (or filename) queue hit capacity under the
        "abort" overload policy — counted per edge so the telemetry
        names WHERE the pipeline backed up, not just that it died."""
        with self._lock:
            self.overflow_sites[edge] = \
                self.overflow_sites.get(edge, 0) + n

    def snapshot(self) -> Dict[str, object]:
        """Point-in-time copy for reports (dead-letter detail included)."""
        with self._lock:
            return {
                "num_failed": self.num_failed,
                "num_shed": self.num_shed,
                "num_retries": self.num_retries,
                "failure_reasons": dict(self.failure_reasons),
                "shed_sites": dict(self.shed_sites),
                "overflow_sites": dict(self.overflow_sites),
                "dead_letters": list(self.dead_letters),
            }


class InferenceCounter:
    """Locked global disposed-request counter driving the progress
    display and the target-reached check (reference benchmark.py:199-205,
    runner.py:176-196). With the containment layer, *disposed* means
    completed, contained-failed, or shed — every request the pipeline
    will never owe further work on counts toward the target, so a run
    with contained failures still terminates instead of waiting forever
    for completions that cannot come."""

    UNGUARDED_OK = {
        "_value": "add() is atomic under _lock; bare value reads are "
                  "a progress gauge",
    }

    def __init__(self):
        self._value = 0
        self._lock = threading.Lock()

    @property
    def value(self) -> int:
        return self._value

    def add(self, n: int) -> Tuple[int, int]:
        """Add n; return (old, new) atomically."""
        with self._lock:
            old = self._value
            self._value = old + n
            return old, self._value


def dispose_requests(counter: InferenceCounter, num_videos: int,
                     termination: TerminationState,
                     n: int = 1) -> Tuple[int, int]:
    """Count n requests as disposed (failed/shed) and raise the
    target-reached flag when the count crosses the job target.

    The final step's success path keeps its own inline version (it also
    breaks its hot loop on the crossing); every *other* disposal site —
    a contained failure at any step, a shed at the client or between
    stages — funnels through here so the job still terminates when the
    last outstanding request dies instead of completing.
    """
    old, new = counter.add(n)
    if old < num_videos <= new:
        termination.raise_flag(TerminationFlag.TARGET_NUM_VIDEOS_REACHED)
    return old, new


def send_exit_markers(target_queue: "queue.Queue",
                      num_markers: int = NUM_EXIT_MARKERS,
                      termination: Optional["TerminationState"] = None,
                      timeout_s: float = 60.0) -> None:
    """Enqueue ``num_markers`` end-of-stream ``None`` markers.

    Markers must not be silently dropped: with the last-producer drain
    protocol each edge gets exactly one marker attempt, so a transiently
    full queue would otherwise lose the end-of-stream signal and hang
    every downstream consumer until the barrier timeout. Retries with a
    short blocking put until the queue drains, the job terminates, or a
    generous deadline passes (a dead pipeline with no consumers left).
    """
    import time as _time
    deadline = _time.monotonic() + timeout_s
    for _ in range(num_markers):
        while True:
            try:
                target_queue.put(None, timeout=0.05)
                break
            except queue.Full:
                if termination is not None and termination.terminated:
                    return
                if _time.monotonic() > deadline:
                    # markers could not be delivered — abort the job
                    # rather than leave downstream consumers polling an
                    # edge that will never see end-of-stream
                    print("[WARNING] end-of-stream markers undeliverable "
                          "for %.0fs; aborting" % timeout_s)
                    if termination is not None:
                        termination.raise_flag(TerminationFlag.INTERNAL_ERROR)
                    return


class EdgeTracker:
    """Producer countdown for one queue edge.

    Exit markers (``None``) must never overtake real items: with
    competing producer replicas feeding one queue, a fast replica that
    finished and enqueued its markers could starve a downstream consumer
    of a slower sibling's still-in-flight items (the consumer breaks on
    the first ``None`` it pops). The fix over the reference's
    fixed-10-markers heuristic (reference runner.py:238-245): every
    producer on the edge decrements this tracker when it is done, and
    only the *last* one enqueues the markers — by then every real item
    is already in the queue ahead of them.
    """

    GUARDED_BY = {"_remaining": "_lock"}

    def __init__(self, num_producers: int, num_markers: int):
        self._remaining = num_producers
        self._lock = threading.Lock()
        self.num_markers = num_markers

    def producer_finished(self) -> bool:
        """Record one producer's completion; True for the last one."""
        with self._lock:
            self._remaining -= 1
            return self._remaining == 0


#: Pointer passed through control queues instead of tensor payloads:
#: names the producer (group, instance) and the ring slot index
#: (reference control.py:209).
Signal = namedtuple("Signal", ("group_idx", "instance_idx", "tensor_idx"))


def get_segmented_shapes(shapes: Tuple[Tuple[int, ...], ...],
                         num_segments: int) -> Tuple[Tuple[int, ...], ...]:
    """Shrink per-output max shapes to one segment's worth of rows.

    A step with ``num_segments=k`` splits each output batch row-wise into
    k segments, so downstream buffers only ever hold ``ceil(rows/k)``
    rows (reference control.py:49-69).
    """
    if num_segments <= 1:
        return shapes
    out = []
    for shape in shapes:
        if not shape:
            raise ValueError(
                "cannot segment a scalar output shape %r" % (shape,))
        out.append((math.ceil(shape[0] / num_segments),) + tuple(shape[1:]))
    return tuple(out)


class RingSlot:
    """One credit of a BufferRing: free-event + the parked payload."""

    __slots__ = ("free", "payload")

    def __init__(self):
        self.free = threading.Event()
        self.free.set()  # set == free for reuse (reference control.py:23-33)
        self.payload: Optional[tuple] = None

    def write(self, payload: tuple) -> None:
        """Park a payload (tuple of PaddedBatch) and mark occupied."""
        self.payload = payload
        self.free.clear()

    def read(self) -> tuple:
        return self.payload

    def release(self) -> None:
        """Consumer is done with the slot; producer may reuse it."""
        self.payload = None
        self.free.set()


class BufferRing:
    """A bounded slot pool owned by one producer instance.

    The producer writes outputs round-robin into slots, blocking while
    the next slot is still held by a consumer — the same backpressure
    point as the reference's ``tensor_event.event.wait()``
    (runner.py:161-163). ``wait_free`` polls the termination flag so a
    dying pipeline can't deadlock a producer forever.
    """

    POLL_INTERVAL_S = 0.05

    def __init__(self, num_slots: int, device: DeviceSpec,
                 shapes: Tuple[Tuple[int, ...], ...]):
        if num_slots < 1:
            raise ValueError("BufferRing needs at least one slot")
        self.slots = [RingSlot() for _ in range(num_slots)]
        self.device = device
        self.shapes = shapes

    def __len__(self) -> int:
        return len(self.slots)

    def wait_free(self, slot_idx: int,
                  termination: TerminationState) -> bool:
        """Block until slot is free; False if the job died meanwhile."""
        slot = self.slots[slot_idx]
        while not slot.free.wait(timeout=self.POLL_INTERVAL_S):
            if termination.terminated:
                return False
        return True

    def would_block(self, slot_idx: int, count: int = 1) -> bool:
        """Whether a publish writing ``count`` slots from ``slot_idx``
        on would have to wait for a consumer right now (a peek for the
        producer's emission policy; it takes nothing)."""
        n = len(self.slots)
        return count > n or any(
            not self.slots[(slot_idx + k) % n].free.is_set()
            for k in range(count))

    def release_all(self) -> None:
        """Free every slot so blocked producers wake during teardown
        (reference runner.py:247-253)."""
        for slot in self.slots:
            slot.release()


class ChannelFabric:
    """Builds and wires every queue and buffer ring of one pipeline.

    Equivalent of the reference's ``SharedQueuesAndTensors``
    (control.py:72-205): a filename queue feeding step 0, one bounded
    queue per declared out-queue index per step, and a
    [step][group][instance] ring pool for every non-final step whose
    stage model declares tensor outputs (``output_shape() is not None``;
    None means no ring is allocated — distinct from an empty tuple,
    reference runner_model.py:31-46). Ring shapes come from the stage
    class's config-aware ``output_shape_for(**model_kwargs)`` —
    evaluated per group, since group extras may override step extras —
    shrunk by the step's ``num_segments``.
    """

    def __init__(self, pipeline: PipelineConfig, queue_size: int):
        self.pipeline = pipeline
        self.queue_size = queue_size
        self.filename_queue: "queue.Queue" = queue.Queue(maxsize=queue_size)

        # queues[step_idx][queue_idx] -> Queue shared by that step's
        # producers and the next step's consumers
        self.queues: List[Dict[int, "queue.Queue"]] = []
        # trackers[step_idx][queue_idx] -> EdgeTracker for that edge
        self.trackers: List[Dict[int, EdgeTracker]] = []
        # rings[step_idx][group_idx][instance_idx] -> BufferRing | None
        self.rings: List[List[List[Optional[BufferRing]]]] = []

        #: the filename queue has exactly one producer (the client), so
        #: it needs no countdown — just enough markers for step 0
        self.filename_num_markers = max(
            NUM_EXIT_MARKERS,
            sum(len(g.devices) for g in pipeline.steps[0].groups))

        for step_idx, step in enumerate(pipeline.steps):
            is_final = step_idx == pipeline.num_steps - 1

            step_queues: Dict[int, "queue.Queue"] = {}
            step_trackers: Dict[int, EdgeTracker] = {}
            if not is_final:
                for group in step.groups:
                    for q_idx in group.out_queues:
                        if q_idx not in step_queues:
                            step_queues[q_idx] = queue.Queue(
                                maxsize=queue_size)
                for q_idx in step_queues:
                    num_producers = sum(
                        len(g.devices) for g in step.groups
                        if q_idx in g.out_queues)
                    num_consumers = sum(
                        len(g.devices)
                        for g in pipeline.steps[step_idx + 1].groups
                        if g.in_queue == q_idx)
                    step_trackers[q_idx] = EdgeTracker(
                        num_producers,
                        max(NUM_EXIT_MARKERS, num_consumers))
            self.queues.append(step_queues)
            self.trackers.append(step_trackers)

            step_rings: List[List[Optional[BufferRing]]] = []
            model_class = load_class(step.model) if not is_final else None
            num_slots = step.effective_shared_tensors
            for group_idx, group in enumerate(step.groups):
                shapes = None
                if model_class is not None:
                    shapes = model_class.output_shape_for(
                        **step.kwargs_for_group(group_idx))
                    if shapes is not None:
                        # authoritative deadlock guard (parse_config
                        # repeats it conservatively for configs that
                        # never reach fabric construction): a producer
                        # fills one slot per segment before publishing
                        # any Signal, so slots < segments hangs forever
                        if num_slots < step.num_segments:
                            raise ConfigError(
                                "step %d: ring of %d slots cannot hold "
                                "%d segments — the producer would "
                                "deadlock" % (step_idx, num_slots,
                                              step.num_segments))
                        shapes = get_segmented_shapes(
                            tuple(map(tuple, shapes)), step.num_segments)
                group_rings: List[Optional[BufferRing]] = []
                for device in group.devices:
                    if shapes is None:
                        group_rings.append(None)
                    else:
                        group_rings.append(
                            BufferRing(num_slots, device, shapes))
                step_rings.append(group_rings)
            self.rings.append(step_rings)

    # -- accessors ---------------------------------------------------

    def get_filename_queue(self) -> "queue.Queue":
        return self.filename_queue

    def get_queues(self, step_idx: int, group_idx: int):
        """(in_queue, out_queues) for one group's runner instances.

        Step 0 reads the filename queue; the final step has no out
        queues (None). Reference: control.py:167-180.
        """
        group = self.pipeline.steps[step_idx].groups[group_idx]
        if step_idx == 0:
            in_queue = self.filename_queue
        else:
            in_queue = self.queues[step_idx - 1][group.in_queue]
        if step_idx == self.pipeline.num_steps - 1:
            out_queues = None
        else:
            out_queues = [self.queues[step_idx][q] for q in group.out_queues]
        return in_queue, out_queues

    def get_out_trackers(self, step_idx: int,
                         group_idx: int) -> Optional[List[EdgeTracker]]:
        """EdgeTrackers parallel to ``get_queues()[1]`` (None for the
        final step)."""
        if step_idx == self.pipeline.num_steps - 1:
            return None
        group = self.pipeline.steps[step_idx].groups[group_idx]
        return [self.trackers[step_idx][q] for q in group.out_queues]

    def get_input_rings(self, step_idx: int,
                        group_idx: int) -> Optional[Dict[int, List[Optional[BufferRing]]]]:
        """Upstream rings a consumer may receive Signals into.

        For a consumer group at ``step_idx``, returns
        ``{upstream_group_idx: [ring per instance]}`` restricted to the
        previous step's groups whose out-queues include this group's
        in-queue; None for step 0 or when the upstream step allocates no
        rings (reference control.py:182-205).
        """
        if step_idx == 0:
            return None
        group = self.pipeline.steps[step_idx].groups[group_idx]
        upstream = self.pipeline.steps[step_idx - 1]
        result: Dict[int, List[Optional[BufferRing]]] = {}
        any_ring = False
        for up_idx, up_group in enumerate(upstream.groups):
            if group.in_queue in up_group.out_queues:
                rings = self.rings[step_idx - 1][up_idx]
                result[up_idx] = rings
                if any(r is not None for r in rings):
                    any_ring = True
        return result if any_ring else None

    def get_output_ring(self, step_idx: int, group_idx: int,
                        instance_idx: int) -> Optional[BufferRing]:
        return self.rings[step_idx][group_idx][instance_idx]

    def all_rings(self) -> List[BufferRing]:
        return [r for step in self.rings for group in step for r in group
                if r is not None]

