"""The stage executor: one thread per (step, group, device instance).

Capability parity with the reference's per-process hot loop
(runner.py:5-271), re-designed for a single-controller TPU runtime:

* stages are **threads**, not OS processes — JAX async dispatch plays
  the role the private per-process CUDA stream played (reference
  runner.py:41-44); device work from different stages overlaps while
  threads block on queues;
* the tensor hand-off is by reference: a Signal names a ring slot whose
  payload is a tuple of immutable device arrays; "copy-out" is the
  consuming stage's ``jax.device_put`` onto its own device (ICI on real
  hardware), after which the slot is released for reuse;
* segmentation splits the *valid* rows of each output row-wise
  (remainder spread from the front: 11 rows over 3 segments -> 4/4/3,
  reference runner.py:140-154), pads each segment back to the ring's
  static segment shape, and forks the TimeCard per segment;
* a crashed stage raises ``INTERNAL_ERROR`` instead of hanging the job
  (the reference had no failure path for this);
* **request-level fault containment** (rnb_tpu.faults taxonomy): an
  error escaping the model call is classified — *transient* errors are
  retried up to the step's ``max_retries`` with ``retry_backoff_ms``
  of sleep between attempts, *permanent* errors (and exhausted retry
  budgets) stamp the request's TimeCard ``failed`` and dead-letter it
  on the controller while the stream keeps flowing, and everything
  unclassified stays **fatal** exactly as before (stage-init failures
  and ring-protocol violations abort the job). Under the config's
  ``overload_policy: "shed"`` a full downstream queue drops the *new*
  request with a counted ``shed`` outcome instead of aborting with
  ``FRAME_QUEUE_FULL``. A configured :class:`rnb_tpu.faults.FaultPlan`
  is consulted at two hook points (stage stall before the inference
  span; raise/latency per model-call attempt) so chaos behavior is
  deterministic and reproducible.

Synchronization fidelity: by default the executor blocks until a
stage's device output is ready before stamping ``inference_finish`` and
publishing downstream — the analog of the reference's
``stream.synchronize()`` (runner.py:127-128), keeping latency
decompositions honest. Setting ``async_dispatch=True`` on a step
publishes as soon as XLA has the work queued; dataflow stays correct
(consumers wait on the arrays' futures) and throughput improves, but
``inference{i}`` spans then measure dispatch, not device compute.
"""

from __future__ import annotations

import math
import queue
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from rnb_tpu import trace
from rnb_tpu.control import (NUM_EXIT_MARKERS, BufferRing, EdgeTracker,
                             FaultStats, InferenceCounter, Signal,
                             TerminationFlag, TerminationState,
                             dispose_requests, send_exit_markers)
from rnb_tpu.devices import DeviceSpec
from rnb_tpu.faults import (FATAL, TRANSIENT, LaneDeathError,
                            classify_error, fault_reason)
from rnb_tpu.health import (EVICTED, HEALTHY, LOSER, SUSPECT, WINNER,
                            DirectPayload, deadline_site)
from rnb_tpu.health import cards_of as health_cards_of
from rnb_tpu.health import expired as _deadline_expired
from rnb_tpu.ops.ragged import check_segment_offsets
from rnb_tpu.placement import CostRecord
from rnb_tpu.stage import PaddedBatch, RaggedBatch
from rnb_tpu.telemetry import (TimeCardList, TimeCardSummary, logname,
                               logroot)
from rnb_tpu.utils.class_utils import load_class
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy

NUM_SUMMARY_SKIPS = 10  # steady-state summaries skip warm records
QUEUE_POLL_S = 0.05
#: floor for deadline-driven poll timeouts: a zero/near-zero deadline
#: must still yield the GIL briefly instead of spinning
MIN_POLL_S = 0.001


def poll_plan(model):
    """``(timeout_s, holding)`` for an accumulator stage's queue poll:
    the stage's own next deadline (hold-timeout expiry / harvest tick
    — under autotune, the controller's next deadline), clamped to
    [MIN_POLL_S, QUEUE_POLL_S], plus whether the stage is actually
    holding work (drives the exec*.hold_wait/queue_get trace
    split: waiting to fill a batch is not starvation). Stages without
    deadlines poll at the coarse default. The round-5 frontier
    measured the fixed 50 ms poll as the light-load p99 floor
    (57-61 ms tails against a 5-8 ms configured hold) — emissions
    could only fire on a poll tick."""
    deadline = None
    next_deadline = getattr(model, "next_deadline_s", None)
    if next_deadline is not None:
        deadline = next_deadline()
    if deadline is None:
        return QUEUE_POLL_S, False
    return min(QUEUE_POLL_S, max(MIN_POLL_S, deadline)), True


def poll_timeout(model) -> float:
    """The timeout half of :func:`poll_plan` (kept as the stable
    public face the deadline tests exercise)."""
    return poll_plan(model)[0]
#: sentinel for "an idle poll produced an emission" in the hot loop
_IDLE_EMIT = object()


@dataclass
class RunnerContext:
    """Everything one stage-executor thread needs."""

    in_queue: "queue.Queue"
    out_queues: Optional[List["queue.Queue"]]
    queue_selector_path: str
    print_progress: bool
    job_id: str
    device: DeviceSpec
    group_idx: int
    instance_idx: int
    counter: InferenceCounter
    num_videos: int
    termination: TerminationState
    step_idx: int
    sta_bar: threading.Barrier
    fin_bar: threading.Barrier
    model_class_path: str
    num_segments: int
    input_rings: Optional[Dict[int, List[Optional[BufferRing]]]]
    output_ring: Optional[BufferRing]
    out_trackers: Optional[List[EdgeTracker]] = None
    sync_outputs: bool = True
    log_base: str = "logs"
    model_kwargs: Dict[str, Any] = field(default_factory=dict)
    # final-step instances append their TimeCardSummary here so the
    # controller can report aggregate latency percentiles
    summary_sink: Optional[List] = None
    # -- fault-containment knobs (rnb_tpu.faults / config schema) -----
    #: False = strict reference semantics: even classified errors abort
    containment: bool = True
    #: "abort" (full queue kills the job) | "shed" (drop new requests)
    overload_policy: str = "abort"
    #: transient-error retry budget for this step's model call
    max_retries: int = 0
    retry_backoff_ms: float = 10.0
    #: deterministic injection schedule (FaultPlan), or None
    fault_plan: Optional[Any] = None
    #: job-wide failed/shed/retry accounting shared with the controller
    fault_stats: Optional[FaultStats] = None
    #: stages owning a clip cache (rnb_tpu.cache: `cache_mb` on a
    #: loader step) append their final cache snapshot here so the
    #: controller can report job-wide hit/miss/eviction/coalesced
    #: counts (BenchmarkResult + log-meta `Cache:` line)
    cache_sink: Optional[List] = None
    #: stages owning a staging pool (rnb_tpu.staging: zero-copy decode
    #: staging on a loader step) append their final pool snapshot here
    #: (BenchmarkResult + log-meta `Staging:` line)
    staging_sink: Optional[List] = None
    #: load-adaptive batching (rnb_tpu.autotune): the job's
    #: AutotuneSettings when this step participates (root 'autotune'
    #: config key, per-step opt-out), or None. The executor calls
    #: model.enable_autotune() on supporting stages and feeds the
    #: controller's estimators from the hot loop.
    autotune: Optional[Any] = None
    #: controller-owning stages append their final decision/deadline
    #: counters here (BenchmarkResult + log-meta `Autotune:` line)
    autotune_sink: Optional[List] = None
    #: paged device memory (root 'pager' config key, rnb_tpu.pager):
    #: the job's one Pager when enabled, else None. The executor
    #: calls model.enable_pager() on SUPPORTS_PAGER stages before the
    #: start barrier — the loader switches its clip cache to page
    #: tables, the consuming stage attaches the feature-page arena
    pager: Optional[Any] = None
    #: every stage appends ``(step_idx, warmup_s, sigs-or-None)`` here:
    #: construction wall time plus — for stages owning a jit applier —
    #: the SignatureTracker snapshot (rnb_tpu.compilestats), feeding
    #: the `Compiles:`/`Warmup:` log-meta lines
    compile_sink: Optional[List] = None
    #: batching stages append their PadCounter snapshot here
    #: (BenchmarkResult pad_rows/total_rows + log-meta `Padding:` line)
    pad_sink: Optional[List] = None
    #: stages with counters of their own (``stage_counters()``: valid
    #: and shipped tokens, assignments served by each held expert)
    #: append them here at teardown (the `Tokens:` / `Experts:` lines)
    stage_counter_sink: Optional[List] = None
    #: ragged stages (root 'ragged' config key) append their
    #: ragged_stats here (BenchmarkResult ragged_* + `Ragged:` line)
    ragged_sink: Optional[List] = None
    #: shard-declared stages (step `shard` key,
    #: rnb_tpu.parallel.shardplan) append ``(step_idx, shard_stats)``
    #: here (BenchmarkResult shard_* + `Shard:`/`Shard steps:` lines)
    shard_sink: Optional[List] = None
    #: per-job rnb_tpu.trace.Tracer when the config's `trace` key
    #: enabled tracing, else None. The executor's hot-loop spans go
    #: through the module-level trace hooks either way (profiler
    #: annotations under a profiler session; the Tracer's buffer as
    #: well when one is set); with
    #: a Tracer it also calls model.enable_trace(tracer, step_idx) on
    #: stages that register occupancy sources, and opts the
    #: final-step summary into `# phases` trailers.
    tracer: Optional[Any] = None
    #: device-resident handoff (root 'handoff' config key,
    #: rnb_tpu.handoff): the job's HandoffSettings for consumer
    #: stages (input_rings present), else None. The executor builds
    #: one EdgeHandoff per instance and applies it to every ring
    #: payload take; snapshots land in handoff_sink.
    handoff_settings: Optional[Any] = None
    #: edge label for this consumer's handoff accounting
    #: ("step{i-1}->step{i}")
    handoff_edge: str = ""
    handoff_sink: Optional[List] = None
    #: measured-cost placement (root 'placement' config key,
    #: rnb_tpu.placement): when set, the executor accumulates its
    #: dispatch busy seconds (fault-plan latency + model call +
    #: device sync — the same work the trace timeline records) and
    #: appends a CostRecord here at teardown
    placement_sink: Optional[List] = None
    #: replica-lane depth counters (rnb_tpu.handoff.InflightDepths)
    #: when the NEXT step is replica-expanded: the producer increments
    #: its chosen lane per successful enqueue and hands the counters
    #: to its ReplicaSelector (least-loaded routing)
    out_depths: Optional[Any] = None
    #: config queue indices parallel to out_queues (lane addressing
    #: for out_depths; None when out_depths is None)
    out_queue_indices: Optional[List[int]] = None
    #: this consumer's side of the replica-lane depth counters: the
    #: executor decrements its lane once a popped item's processing
    #: completed (loop-top settlement), closing the in-flight window
    #: the producer's selector routes on
    in_depths: Optional[Any] = None
    in_queue_idx: Optional[int] = None
    # -- self-healing layer (rnb_tpu.health) --------------------------
    #: this consumer's replica step's LaneHealthBoard (root 'health'
    #: config key): the executor publishes a liveness beat per loop
    #: iteration, settles in-flight age windows, feeds dead-letter
    #: counts, and — on an injected lane death — evicts its lane
    health_board: Optional[Any] = None
    #: the NEXT step's board, handed to this producer's
    #: ReplicaSelector (bind_health) for circuit-gated routing
    out_health_board: Optional[Any] = None
    #: every lane queue of this consumer's replica step (queue idx ->
    #: Queue): the evicted-lane drain re-enqueues
    #: queued-but-undispatched work onto healthy siblings through
    #: these
    sibling_queues: Optional[Dict[int, "queue.Queue"]] = None
    #: deadline propagation (root 'deadline' key): settings + the
    #: job-wide expiry-shed ledger (both None = checks inert)
    deadline: Optional[Any] = None
    deadline_stats: Optional[Any] = None
    #: hedged re-dispatch governors (step key 'hedge_ms' on a
    #: replica step): out_hedges tracks/fires on the producer side of
    #: the edge; in_hedges claims exactly-once resolutions on the
    #: replica step itself
    out_hedges: Optional[Any] = None
    in_hedges: Optional[Any] = None


def _dispatch_counts(tensors, device: DeviceSpec, card=None) -> dict:
    """What a batched dispatch's ``model_call`` span carries: the rows
    shipped (the bucket or pool), the valid ones among them, and the
    id of the device they run on; for a batch that carries its segment
    table, the requests packed into it and, where their cards say how
    many tokens each holds, the valid tokens. Empty for a stage that
    takes no batch (a loader)."""
    if tensors and isinstance(tensors[0], PaddedBatch):
        head = tensors[0]
        counts = {"rows": head.max_rows, "rows_valid": int(head.valid),
                  "device": int(device.resolve().id)}
        if isinstance(head, RaggedBatch):
            counts["segments"] = head.num_segments
            tokens = [getattr(tc, "num_tokens", None)
                      for tc in _cards_of(card)] if card is not None else []
            if tokens and None not in tokens:
                counts["tokens_valid"] = int(sum(tokens))
        return counts
    return {}


def split_segments(payload, num_segments: int):
    """Row-split each PaddedBatch's valid rows into ``num_segments``
    per-segment PaddedBatches padded to the segment max shape.

    Segment row counts follow the reference rule (runner.py:140-154):
    ``divmod`` quotient everywhere, remainder spread from the front
    (11 rows, 3 segments -> 4, 4, 3). Segments may be empty when the
    batch has fewer valid rows than segments.
    """
    _, jnp = _jax_numpy()

    if num_segments <= 1:
        return [payload]
    segments = []
    for seg_idx in range(num_segments):
        seg_payload = []
        for pb in payload:
            q, r = divmod(pb.valid, num_segments)
            start = q * seg_idx + min(seg_idx, r)
            end = q * (seg_idx + 1) + min(seg_idx + 1, r)
            seg_rows = end - start
            seg_max = math.ceil(pb.max_rows / num_segments)
            chunk = pb.data[start:start + seg_max]
            pad = seg_max - chunk.shape[0]
            if pad > 0:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((pad,) + tuple(chunk.shape[1:]),
                                      chunk.dtype)], axis=0)
            seg_payload.append(PaddedBatch(chunk, seg_rows))
        segments.append(tuple(seg_payload))
    return segments


def _block_on(payload) -> None:
    # deliberate host sync: the executor's stream.synchronize() analog
    # (sync_outputs honesty) — baselined under RNB-H006
    jax, _ = _jax_numpy()
    jax.block_until_ready([pb.data for pb in payload])


def _eos_flush(model):
    """End-of-stream marker seen: the stage's pending partial batch (if
    it accumulates one) becomes the stream's last item. Returns the
    (tensors, non_tensors, time_card) to publish, or None."""
    flushed = model.flush() if hasattr(model, "flush") else None
    if flushed is None or flushed[2] is None:
        return None
    return flushed


def validate_payload(declared, payload, where: str) -> None:
    """Assert a stage's produced payload matches its declared
    ``output_shape_for``: same tensor count, same trailing dims, row
    axis no larger than the declared max (smaller is legal under row
    bucketing). Keeps shape metadata honest — a declaration nothing
    checks is dead metadata that silently rots.
    """
    payload = tuple(payload) if payload else ()
    if declared is None:
        if payload:
            raise ValueError(
                "%s declares no tensor outputs (output_shape None) but "
                "produced %d tensor(s)" % (where, len(payload)))
        return
    declared = tuple(map(tuple, declared))
    if len(payload) != len(declared):
        raise ValueError(
            "%s declares %d output tensor(s) %r but produced %d"
            % (where, len(declared), declared, len(payload)))
    for idx, (pb, want) in enumerate(zip(payload, declared)):
        got = tuple(int(d) for d in pb.data.shape)
        if (len(got) != len(want) or got[1:] != want[1:]
                or got[0] > want[0]):
            raise ValueError(
                "%s output %d has shape %r but declares %r (row axis may "
                "be smaller under bucketing, never larger; trailing dims "
                "must match exactly)" % (where, idx, got, want))
        if isinstance(pb, RaggedBatch):
            # ragged payloads additionally carry a per-request segment
            # table that must partition the valid rows — a broken pool
            # fill fails here, at the producing step, not as garbage
            # logits downstream
            try:
                check_segment_offsets(pb.segment_offsets, pb.valid)
            except ValueError as e:
                raise ValueError("%s output %d: %s" % (where, idx, e))


# the ONE fused-card unwrap rule, shared with the hedge governor's
# claim/key identity (rnb_tpu.health.cards_of) — two copies could
# silently diverge on what "the cards behind one item" means
_cards_of = health_cards_of


def _hedge_lost(ctx: RunnerContext, time_card) -> bool:
    """Exactly-once resolution at a hedged replica step: the FIRST
    disposal/completion event of a hedged dispatch claims the request
    id(s); the second copy's event is the loser — its result (or
    failure) is discarded with its burned service time counted as
    hedge waste, and the caller must drop the item without touching
    the counters (the rid already terminated through the winner).

    One COPY claims at most once: a copy that already claimed WINNER
    (marked ``hedge_resolved`` on its cards) owns the rid's terminal
    outcome — a later disposal of the same copy in the same iteration
    (e.g. its deadline expired between completion and publish)
    proceeds normally instead of consuming the sibling's LOSER slot,
    which would let the real sibling copy claim UNTRACKED and publish
    the rid a second time."""
    if ctx.in_hedges is None:
        return False
    cards = _cards_of(time_card)
    if any(getattr(tc, "hedge_resolved", False) for tc in cards):
        return False
    verdict = ctx.in_hedges.claim(time_card)
    if verdict == LOSER:
        ctx.in_hedges.discard(time_card)
        return True
    if verdict == WINNER:
        for tc in cards:
            tc.hedge_resolved = True
    return False


def _contain_failure(ctx: RunnerContext, time_card, reason: str,
                     summary) -> None:
    """Dead-letter one item's request(s): stamp the card(s) failed,
    record job-wide accounting, and count the disposal toward the run
    target so the job still terminates (a failed request will never
    produce the completion the target otherwise waits for)."""
    if _hedge_lost(ctx, time_card):
        return
    cards = _cards_of(time_card)
    for tc in cards:
        if tc.status == "ok":
            tc.mark_failed(reason)
    if ctx.fault_stats is not None:
        ctx.fault_stats.record_failure([tc.id for tc in cards],
                                       ctx.step_idx, reason)
    if ctx.health_board is not None:
        # the lane's dead-letter signal (one of the three circuit
        # inputs next to in-flight age and the liveness beat)
        ctx.health_board.note_failure(ctx.in_queue_idx)
    if summary is not None:
        summary.note_failure(reason, len(cards))
    dispose_requests(ctx.counter, ctx.num_videos, ctx.termination,
                     len(cards))


def _shed_item(ctx: RunnerContext, time_card, summary,
               lane: Optional[int] = None) -> None:
    """Drop one item under ``overload_policy: "shed"`` (downstream
    queue full): counted, stamped, disposed — never aborts the job.
    ``lane`` names the chosen replica lane queue when the full edge is
    replica-expanded, so shed-site accounting is per-lane."""
    if _hedge_lost(ctx, time_card):
        return
    site = ("step%d_out_queue.lane%d" % (ctx.step_idx, lane)
            if lane is not None
            else "step%d_out_queue" % ctx.step_idx)
    cards = _cards_of(time_card)
    for tc in cards:
        tc.mark_shed(site)
    if ctx.fault_stats is not None:
        ctx.fault_stats.record_shed(site, len(cards))
    if summary is not None:
        summary.note_shed(len(cards))
    dispose_requests(ctx.counter, ctx.num_videos, ctx.termination,
                     len(cards))


def _shed_deadline(ctx: RunnerContext, time_card, where: str,
                   summary) -> None:
    """Shed an item whose every constituent blew its absolute deadline
    (rnb_tpu.health, root 'deadline' key): the expiry rides the PR 1
    shed machinery — counted in FaultStats per site AND in the
    deadline ledger, which parse_utils --check cross-foots."""
    if _hedge_lost(ctx, time_card):
        return
    site = deadline_site(where)
    cards = _cards_of(time_card)
    for tc in cards:
        tc.mark_shed(site)
    if ctx.fault_stats is not None:
        ctx.fault_stats.record_shed(site, len(cards))
    if ctx.deadline_stats is not None:
        ctx.deadline_stats.record(site, len(cards))
    if summary is not None:
        summary.note_shed(len(cards))
    dispose_requests(ctx.counter, ctx.num_videos, ctx.termination,
                     len(cards))


def _sheddable_expired(ctx: RunnerContext, time_card) -> bool:
    """Deadline boundary check: expired AND legal to shed (forked
    segment cards never shed — dropping one segment would strand its
    aggregator siblings, same rule as the overload shed path)."""
    return (ctx.deadline is not None
            and getattr(time_card, "sub_id", None) is None
            and _deadline_expired(time_card))


def _pick_lane(depths, board, queue_indices,
               exclude: Optional[int] = None) -> Optional[int]:
    """Deterministic healthy-sibling choice for hedges and evicted-
    lane redispatch: healthy/suspect lanes first, non-evicted as the
    fallback, least-loaded wins with the lowest queue index as the
    stable tie-break. None when no candidate lane exists."""
    candidates = [q for q in queue_indices if q != exclude]
    if board is not None:
        live = [q for q in candidates
                if board.state(q) in (HEALTHY, SUSPECT)]
        if not live:
            live = [q for q in candidates
                    if board.state(q) != EVICTED]
        candidates = live
    if not candidates:
        return None
    if depths is None:
        return candidates[0]
    return min(candidates, key=lambda q: (depths.depth(q), q))


def _fire_hedges(ctx: RunnerContext) -> None:
    """Producer-side hedge tick: re-issue every dispatch outstanding
    past the governor's threshold onto the best healthy sibling lane.
    The hedge item carries its payload directly (DirectPayload) — the
    original still owns its ring slot — and a stamp-complete card
    clone, so whichever copy resolves first produces an identical
    summary row. A full sibling queue just defers the hedge to a
    later tick (hedging must never add backpressure)."""
    gov = ctx.out_hedges
    if gov is None or ctx.out_queues is None:
        return
    for entry in gov.poll():
        lane = _pick_lane(ctx.out_depths, ctx.out_health_board,
                          ctx.out_queue_indices, exclude=entry.lane)
        if lane is None:
            continue
        # commit BEFORE the enqueue: begin_fire re-checks under the
        # governor lock that the dispatch is still unresolved, so a
        # copy can never be fired for a request that already claimed
        # (the late copy would win a second time and double-publish)
        if not gov.begin_fire(entry):
            continue
        item = (DirectPayload(entry.payload), entry.non_tensors,
                entry.card)
        try:
            ctx.out_queues[ctx.out_queue_indices.index(lane)] \
                .put_nowait(item)
        except queue.Full:
            gov.cancel_fire(entry)
            continue
        if ctx.out_depths is not None:
            ctx.out_depths.inc(lane)
        if ctx.out_health_board is not None:
            ctx.out_health_board.note_enqueue(lane)


def _linger_for_hedges(ctx: RunnerContext) -> None:
    """Producer end-of-stream hook: the stream may end long before a
    wedged downstream dispatch exceeds its hedge threshold — exiting
    then would orphan exactly the tail dispatches hedging exists for.
    Keep ticking the governor until every tracked dispatch settled
    (consumers settle at their loop top, so this drains naturally) or
    the job terminates; the caller sends exit markers only after, so
    a late hedge can never arrive behind an end-of-stream marker."""
    gov = ctx.out_hedges
    if gov is None:
        return
    while not ctx.termination.terminated and gov.num_outstanding():
        _fire_hedges(ctx)
        time.sleep(QUEUE_POLL_S / 5.0)


def _die_lane(ctx: RunnerContext, exc: LaneDeathError,
              summary) -> None:
    """This replica lane's executor is dead (injected replica_crash /
    replica_stall): once the lane's LAST instance died, evict the
    lane so the upstream selector stops feeding it, then run a
    drain-and-redispatch pump until end-of-stream: every
    queued-but-undispatched item moves to a healthy sibling lane
    (``redispatched`` content stamp, in-flight windows reconciled on
    both lanes), so no request is ever stranded behind a dead lane.
    No model call happens after the death; the in-service dispatch
    was already dead-lettered by the caller."""
    if ctx.health_board is None:
        # no board: siblings have no end-of-stream linger, so a late
        # redispatch could land in a queue whose executor already
        # exited, and instance deaths cannot be coordinated — the
        # launcher rejects lane-death fault plans without the root
        # 'health' key, so this is only the defensive backstop
        return
    if ctx.health_board.instance_died(ctx.in_queue_idx) > 0:
        # a live sibling instance still consumes this lane's
        # queue — the lane serves on at reduced capacity, and
        # draining it would steal live work, not rescue it. (A
        # lane-addressed fault will kill that instance too on
        # its next matching dispatch; the LAST death drains.)
        return
    ctx.health_board.evict(ctx.in_queue_idx,
                           "replica-%s" % exc.fate)
    if ctx.sibling_queues is None:
        return
    targets = {q: sq for q, sq in ctx.sibling_queues.items()
               if q != ctx.in_queue_idx}
    if not targets:
        return
    tr_redispatch = trace.name("exec%d.redispatch", ctx.step_idx)
    try:
        _pump_dead_lane(ctx, targets, tr_redispatch)
    finally:
        # the dead lane's stream is over (its queue remainder moved to
        # siblings): release any sibling lingering on the drained
        # latch (rnb_tpu.health end-of-stream protocol)
        if ctx.health_board is not None:
            ctx.health_board.note_drained(ctx.in_queue_idx)


def _pump_dead_lane(ctx: RunnerContext, targets, tr_redispatch) -> None:
    while not ctx.termination.terminated:
        try:
            item = ctx.in_queue.get(timeout=QUEUE_POLL_S)
        except queue.Empty:
            continue
        if item is None:
            return  # end-of-stream: nothing more can strand here
        lane = _pick_lane(ctx.in_depths, ctx.health_board,
                          sorted(targets))
        if lane is None:
            lane = sorted(targets)[0]
        _sig, _nt, tc = item
        with trace.span(tr_redispatch):
            for c in _cards_of(tc):
                c.redispatched = getattr(c, "redispatched", 0) + 1
            # bounded put + liveness re-check: a dying pipeline must
            # not wedge the drain pump forever (RNB-H009 discipline)
            while not ctx.termination.terminated:
                try:
                    targets[lane].put(item, timeout=QUEUE_POLL_S)
                    break
                except queue.Full:
                    continue
            else:
                return
        if ctx.in_depths is not None:
            # reconcile the in-flight windows: the item leaves this
            # lane's count and joins the target's, so the selector's
            # depth view (and --check's settlement) still closes
            ctx.in_depths.dec(ctx.in_queue_idx)
            ctx.in_depths.inc(lane)
        if ctx.health_board is not None:
            ctx.health_board.note_settle(ctx.in_queue_idx)
            ctx.health_board.note_enqueue(lane)
            ctx.health_board.note_redispatch(ctx.in_queue_idx)
        # (a moved dispatch is still the ORIGINAL hedge copy, if one
        # was fired for it: its claim window keeps running and
        # resolves wherever it lands)


def _drain_stage_failures(ctx: RunnerContext, take_failed, take_retries,
                          summary) -> None:
    """Collect requests a stage contained *internally* (e.g. the fusing
    loader excluding a corrupt video from a fused batch): stages with
    intra-stage batching expose ``take_failed() -> [(card, reason)]``
    and the executor turns each entry into a normal dead-letter —
    unless containment is disabled, in which case a stage-contained
    failure still aborts the job (strict reference semantics must not
    depend on which code path an error took)."""
    if take_retries is not None:
        n = take_retries()
        if n:
            if ctx.fault_stats is not None:
                ctx.fault_stats.record_retries(n)
            if summary is not None:
                summary.note_retries(n)
    if take_failed is None:
        return
    for tc, reason in take_failed():
        if not ctx.containment:
            raise RuntimeError(
                "request %s failed in-stage (%s) with fault_containment "
                "disabled" % (getattr(tc, "id", "?"), reason))
        _contain_failure(ctx, tc, reason, summary)


def runner(ctx: RunnerContext) -> None:
    """Thread entry: init the stage, run the hot loop, drain cleanly."""
    summary = TimeCardSummary() if ctx.out_queues is None else None
    if summary is not None and ctx.tracer is not None:
        # trace-enabled runs opt the per-instance report into the
        # `# phases` trailer (same steady-state skip as the job-wide
        # Phases: line); trace-off reports stay byte-stable
        summary.track_phases = True
        summary.phase_num_skips = NUM_SUMMARY_SKIPS
    progress_bar = None
    declared_shapes = None
    controller = None
    handoff = None
    warmup_s = 0.0
    # measured-cost placement accounting (rnb_tpu.placement): busy =
    # this executor's dispatch spans (fault-plan latency + model call
    # + device sync), the same work the trace timeline records — the
    # planner's occupancy prediction is checked against the traced
    # busy fraction, so the two MUST measure the same thing
    stage_busy_s = 0.0
    stage_dispatches = 0
    # replica-lane in-flight settlement: items popped whose depth
    # decrement is owed at the next loop top (after their processing
    # completed) — rnb_tpu.handoff.InflightDepths
    depth_owed = 0
    try:
        model_class = load_class(ctx.model_class_path)
        # warmup wall time: weights + warmup compiles all happen in the
        # stage constructor, before the start barrier — the launch cost
        # the `Warmup:` accounting surfaces (ragged collapses the
        # per-bucket compile matrix here). It is the construct span's
        # duration: the launcher's Tracer of set-up collects it, and
        # the stage's own setup.s{step}.* spans nest under it
        trace.building_step(ctx.step_idx)
        t_construct = time.time()
        with trace.span(trace.name("setup.s%d.construct", ctx.step_idx),
                        instance=ctx.instance_idx,
                        device=ctx.device.label) as built:
            model = model_class(ctx.device, **ctx.model_kwargs)
        warmup_s = getattr(built, "dur", time.time() - t_construct)
        declared_shapes = model_class.output_shape_for(**ctx.model_kwargs)

        selector = None
        if ctx.out_queues is not None:
            selector_class = load_class(ctx.queue_selector_path)
            selector = selector_class(len(ctx.out_queues))
            selector.bind_stage(model)
            if ctx.out_depths is not None \
                    and hasattr(selector, "bind_depths"):
                # replica-lane routing (rnb_tpu.selector
                # .ReplicaSelector): share the downstream step's
                # in-flight depth counters so routing is least-loaded
                selector.bind_depths(ctx.out_depths,
                                     ctx.out_queue_indices)
                if ctx.out_health_board is not None \
                        and hasattr(selector, "bind_health"):
                    # circuit-gated routing (rnb_tpu.health): open/
                    # evicted lanes leave the candidate set; half-open
                    # lanes get their single recovery probe
                    selector.bind_health(ctx.out_health_board)
        if ctx.health_board is not None:
            # lane-instance census (pre-barrier, so deaths can never
            # race registration): the LAST instance to die is the one
            # that drains the lane
            ctx.health_board.register_instance(ctx.in_queue_idx)
        if ctx.handoff_settings is not None \
                and ctx.input_rings is not None:
            # device-resident handoff (rnb_tpu.handoff): this
            # consumer's side of the edge contract, re-home target
            # refined by the stage's input_sharding() when declared
            from rnb_tpu.handoff import EdgeHandoff
            handoff = EdgeHandoff(
                ctx.handoff_settings, ctx.device, ctx.handoff_edge,
                model)
        if ctx.autotune is not None \
                and getattr(model, "SUPPORTS_AUTOTUNE", False):
            # load-adaptive batching (rnb_tpu.autotune): the stage
            # builds its controller over its OWN warmed bucket set —
            # a bucket restriction it never warms is rejected here
            # (and statically by rnb-lint RNB-G006)
            controller = model.enable_autotune(ctx.autotune)
        if ctx.pager is not None \
                and getattr(model, "SUPPORTS_PAGER", False):
            # paged device memory (rnb_tpu.pager): arenas allocate and
            # register with the memory ledger here, pre-barrier, so
            # every Memory:/Pages: sample covers the full page pool
            model.enable_pager(ctx.pager)
        if hasattr(model, "bind_step"):
            # the stage learns its step index: loaders name the
            # per-request phase stamps (decode/hold/transfer) by it,
            # on every run
            model.bind_step(ctx.step_idx)
        if hasattr(model, "bind_log_dir"):
            # a stage that keeps samples of what it served (what a
            # run's check compares) writes them beside the job's logs
            model.bind_log_dir(logroot(ctx.job_id, ctx.log_base))
        if ctx.tracer is not None and hasattr(model, "enable_trace"):
            # the `trace` config key's Tracer (rnb_tpu.trace): stages
            # that own sampled occupancy sources register them here;
            # the executor's own spans need no stage support
            model.enable_trace(ctx.tracer, ctx.step_idx)
        if hasattr(model, "bind_shard_step"):
            # intra-stage sharding (rnb_tpu.parallel.shardplan): the
            # stage host-times its merge collective as
            # exec{i}.collective — unconditional (unlike enable_trace)
            # because the span and the Shard: accounting need the
            # step index even on trace-disabled runs
            model.bind_shard_step(ctx.step_idx)
    except Exception:
        traceback.print_exc()
        ctx.termination.raise_flag(TerminationFlag.INTERNAL_ERROR)
        model = None

    try:
        ctx.sta_bar.wait()
    except threading.BrokenBarrierError:
        pass
    # the measured window opens here: any jit-entry signature the
    # stage's applier first sees from now on is a mid-run recompile
    # (surfaced as steady_new in the Compiles: accounting; parse_utils
    # --check fails on nonzero)
    compile_tracker = getattr(model, "compiles", None)
    if compile_tracker is not None:
        compile_tracker.freeze()

    if ctx.print_progress:
        try:
            from tqdm import tqdm
            progress_bar = tqdm(total=ctx.num_videos)
        except ImportError:
            progress_bar = None

    ring_counter = 0  # next output slot (reference runner.py:60-61)
    if ctx.output_ring is not None \
            and hasattr(model, "bind_publish_probe"):
        # downstream back-pressure, observed: "would publishing one
        # more emission, behind `ahead` emitted and not yet published
        # ones, block now?" — a peek at the slots the publish path
        # below writes next. A stage with no probe bound reads "free"
        model.bind_publish_probe(
            lambda ahead=0: ctx.output_ring.would_block(
                ring_counter, (ahead + 1) * ctx.num_segments))
    # accumulator stages expose poll() for the idle tick; resolve once
    idle_poll = getattr(model, "poll", None)
    # stages with intra-stage batching surface internally-contained
    # request failures through take_failed(); resolve once
    take_failed = getattr(model, "take_failed", None)
    take_retries = getattr(model, "take_retries", None)
    # stages with a pipelined transfer handoff (rnb_tpu.staging:
    # transfer_async on a fusing loader) surface completed emissions
    # through take_ready(); resolve once
    take_ready = getattr(model, "take_ready", None)
    # stages that hold work internally (loader accumulator, Batcher)
    # surface deadline-expired requests they shed at admission through
    # take_shed() -> [(card, where)]; resolve once
    take_shed = getattr(model, "take_shed", None)
    if model is not None and take_failed is not None and ctx.containment:
        # stages with internal containment retry transients themselves;
        # hand them the step's schema retry knobs (never model kwargs).
        # In strict mode the budget stays (0, 0): the stage parks the
        # failure unretried and the drain below aborts the job, matching
        # the executor path's first-attempt abort.
        model.fault_retry_budget = (ctx.max_retries, ctx.retry_backoff_ms)
    old_counter_value = 0
    # loop-invariant stamp keys the autotune service feed reads (these
    # are lookups of stamps the record() sites below write, not new
    # stamp sites)
    key_inf_start = "inference%d_start" % ctx.step_idx
    key_inf_finish = "inference%d_finish" % ctx.step_idx
    # loop-invariant trace event names (rnb_tpu.trace): formatted once
    # here so the hot loop pays no formatting per event (the
    # trace.name literals are what RNB-T008 checks)
    tr_queue_get = trace.name("exec%d.queue_get", ctx.step_idx)
    tr_hold_wait = trace.name("exec%d.hold_wait", ctx.step_idx)
    tr_swallow = trace.name("exec%d.swallow", ctx.step_idx)
    tr_model_call = trace.name("exec%d.model_call", ctx.step_idx)
    tr_device_sync = trace.name("exec%d.device_sync", ctx.step_idx)
    tr_finish = trace.name("exec%d.finish", ctx.step_idx)
    tr_publish = trace.name("exec%d.publish", ctx.step_idx)
    tr_handoff = trace.name("exec%d.handoff", ctx.step_idx)

    # Prefetch (NVVL parity, reference README.md:46-110): a signal-free
    # first stage exposing submit()/complete() gets its next requests'
    # host work (decode) kicked off while the head request's device work
    # runs. Depth 0 (or any tensor-input stage) keeps the classic loop.
    prefetch_depth = 0
    if (model is not None and ctx.input_rings is None
            and hasattr(model, "submit") and hasattr(model, "complete")):
        prefetch_depth = int(getattr(model, "prefetch_depth", 0) or 0)
    pending = deque()  # (handle, non_tensors, time_card) submitted
    saw_marker = False
    # end-of-stream linger (health-enabled replica lanes): this lane
    # saw its exit marker but siblings may still redispatch stranded
    # work here — keep polling until the whole step drained
    marker_noted = False
    # all_drained was observed True once: one final timed sweep of the
    # queue runs before exiting (a pump's last put happens-before its
    # drained note, so one more poll after the observation closes the
    # Empty-then-put-then-drained ordering race)
    linger_final_sweep = False

    try:
        if model is not None:
            while not ctx.termination.terminated:
                if ctx.health_board is not None:
                    # explicit liveness beat: a wedged executor stops
                    # publishing these while its queue keeps aging —
                    # the circuit's missing-liveness signal
                    ctx.health_board.beat(ctx.in_queue_idx)
                if ctx.out_hedges is not None:
                    # producer-side hedge tick: re-issue dispatches
                    # outstanding past the threshold onto healthy
                    # siblings (rnb_tpu.health)
                    _fire_hedges(ctx)
                if depth_owed:
                    # the previous iteration's popped item(s) have
                    # fully processed: close their in-flight window so
                    # the upstream ReplicaSelector stops counting them
                    # against this lane
                    if ctx.in_depths is not None:
                        ctx.in_depths.dec(ctx.in_queue_idx, depth_owed)
                    if ctx.health_board is not None:
                        ctx.health_board.note_settle(ctx.in_queue_idx,
                                                     depth_owed)
                    depth_owed = 0
                # dead-letter requests the stage contained internally
                # during the previous iteration (fused-batch members
                # whose decode failed)
                _drain_stage_failures(ctx, take_failed, take_retries,
                                      summary)
                if take_shed is not None:
                    # requests the stage shed at admission because
                    # their deadline expired while it held work
                    for tc_shed, where in take_shed():
                        _shed_deadline(ctx, tc_shed,
                                       "step%d_%s" % (ctx.step_idx,
                                                      where), summary)
                handle = None
                # end-of-stream flush: a marker with an accumulating
                # stage (batcher) still holding a partial batch emits
                # that batch as one last item before draining, so the
                # final ``num_videos mod batch`` requests complete
                # instead of stranding the run
                flushed = None
                if take_ready is not None:
                    # publish handoff: a fused batch whose (possibly
                    # worker-side) transfer completed publishes BEFORE
                    # new input is admitted — bounded completion
                    # latency, and natural backpressure toward the
                    # input queue while transfers are behind
                    flushed = take_ready()
                if flushed is not None:
                    pass  # fall through to the publish path below
                elif saw_marker and prefetch_depth == 0:
                    # draining: the stage may hold MORE than one pending
                    # batch (e.g. a fusing loader's accumulator), so
                    # keep calling flush() until it runs dry instead of
                    # consuming one exit marker per flushed batch —
                    # markers are finite (NUM_EXIT_MARKERS) and running
                    # out would silently strand the tail requests
                    flushed = _eos_flush(model)
                    if flushed is None:
                        break
                elif prefetch_depth > 0:
                    while (not saw_marker
                           and len(pending) < prefetch_depth + 1):
                        try:
                            item = ctx.in_queue.get(block=not pending,
                                                    timeout=QUEUE_POLL_S)
                        except queue.Empty:
                            break
                        if item is None:
                            saw_marker = True
                            break
                        _sig, nt, tc = item
                        tc.add_device(ctx.device.label)
                        tc.record("runner%d_start" % ctx.step_idx)
                        if ctx.tracer is not None:
                            trace.instant(tr_swallow, rid=tc.id)
                        if _sheddable_expired(ctx, tc):
                            # expiry shed before the decode is even
                            # submitted — the whole point of deadline
                            # propagation is never decoding doomed work
                            _shed_deadline(ctx, tc,
                                           "step%d_take" % ctx.step_idx,
                                           summary)
                            continue
                        try:
                            pending.append((model.submit(nt, tc), nt, tc))
                        except Exception as exc:
                            # a submit-time decode error (corrupt
                            # header, vanished file) fails only this
                            # request; unclassified errors stay fatal
                            if classify_error(exc) is FATAL \
                                    or not ctx.containment:
                                raise
                            _contain_failure(ctx, tc, fault_reason(exc),
                                             summary)
                    if pending:
                        handle, non_tensors, time_card = pending.popleft()
                        signal, tensors = None, None
                    elif saw_marker:
                        flushed = _eos_flush(model)
                        if flushed is None:
                            break  # end-of-stream, all work drained
                    else:
                        continue
                else:
                    try:
                        if idle_poll is None:
                            with trace.span(tr_queue_get):
                                item = ctx.in_queue.get(
                                    timeout=QUEUE_POLL_S)
                        else:
                            # accumulator stages: the poll window
                            # shrinks to the stage's next deadline
                            # (under autotune, the controller's), and
                            # time spent blocked while the stage HOLDS
                            # work is batch-fill wait, not queue
                            # starvation — two span names split them
                            timeout, holding = poll_plan(model)
                            with trace.span(tr_hold_wait if holding
                                            else tr_queue_get):
                                item = ctx.in_queue.get(timeout=timeout)
                    except queue.Empty:
                        if marker_noted \
                                and ctx.health_board.all_drained():
                            # lingering past our own end-of-stream and
                            # every sibling lane has now drained too.
                            # A pump's final put may have landed
                            # BETWEEN our Empty and this check (puts
                            # happen-before the drained note), so run
                            # exactly one more timed sweep before
                            # exiting — after all_drained, no NEW put
                            # can occur, so the second Empty is proof
                            if linger_final_sweep:
                                break
                            linger_final_sweep = True
                            continue
                        # idle tick: give accumulator stages (fusing
                        # loader) a chance to emit on hold-timeout —
                        # without this, a decoded request would wait
                        # for the NEXT arrival, paying a full
                        # inter-arrival gap instead of max_hold_ms
                        # (+<= QUEUE_POLL_S of poll granularity)
                        if idle_poll is None:
                            continue
                        flushed = idle_poll()
                        if flushed is None or flushed[2] is None:
                            continue
                        item = _IDLE_EMIT
                    if item is _IDLE_EMIT:
                        pass  # flushed already holds the emission
                    elif item is None:
                        if ctx.health_board is not None:
                            # end-of-stream LINGER (rnb_tpu.health):
                            # a lane evicted after this one finished
                            # redispatches its queue here — exiting
                            # on our own marker would strand that
                            # work in a queue nobody reads. Note our
                            # drain, keep polling, and exit only once
                            # every sibling lane drained too.
                            if not marker_noted:
                                ctx.health_board.note_drained(
                                    ctx.in_queue_idx)
                                marker_noted = True
                            flushed = _eos_flush(model)
                            if flushed is None:
                                if ctx.health_board.all_drained():
                                    # same one-more-sweep rule as the
                                    # Empty branch: a pump's final put
                                    # can precede its drained note
                                    if linger_final_sweep:
                                        saw_marker = True
                                        break
                                    linger_final_sweep = True
                                continue
                        else:
                            saw_marker = True
                            flushed = _eos_flush(model)
                            if flushed is None:
                                break  # end-of-stream marker
                    else:
                        signal, non_tensors, time_card = item
                        if ctx.in_depths is not None:
                            # settle at the NEXT loop top (processing
                            # complete), not here — depth must cover
                            # in-service time or the router's view
                            # collapses to queue length
                            depth_owed += 1
                        time_card.add_device(ctx.device.label)
                        time_card.record("runner%d_start" % ctx.step_idx)
                        if ctx.tracer is not None:
                            # request-id flow anchors: one admitted
                            # item may carry many cards (an upstream
                            # fused batch)
                            for _tc in _cards_of(time_card):
                                trace.instant(tr_swallow, rid=_tc.id)
                        if controller is not None:
                            # arrival-rate estimator: the client's
                            # enqueue stamps (pure host arithmetic,
                            # no clock call)
                            for tc in _cards_of(time_card):
                                t_enq = tc.timings.get("enqueue_filename")
                                if t_enq is not None:
                                    controller.observe_enqueue(t_enq)

                        if isinstance(signal, DirectPayload):
                            # a hedged re-dispatch (rnb_tpu.health):
                            # the payload rides inside the item — the
                            # ORIGINAL copy still owns its ring slot,
                            # so there is no slot to read or release
                            tensors = signal.payload
                            signal = None
                        elif signal is not None:
                            ring = ctx.input_rings[signal.group_idx][
                                signal.instance_idx]
                            slot = ring.slots[signal.tensor_idx]
                            tensors = slot.read()
                            if tensors is None:
                                # an abort-path release_all() cleared the
                                # slot between our queue pop and this
                                # read — exit (reference runner.py:96-100)
                                break
                            slot.release()
                        else:
                            tensors = None
                        if _sheddable_expired(ctx, time_card):
                            # queue-take expiry shed (root 'deadline'
                            # key): the request's budget is already
                            # blown — drop it HERE, before decode /
                            # reshard / model work burns anything on
                            # it (the ring slot above is released, so
                            # nothing upstream blocks)
                            _shed_deadline(ctx, time_card,
                                           "step%d_take" % ctx.step_idx,
                                           summary)
                            continue
                        if handoff is not None and tensors:
                            # the edge contract (rnb_tpu.handoff):
                            # adopt/reshard the committed payload
                            # onto this consumer — and account the
                            # move, so "zero host-hop bytes" is a
                            # log fact, not a claim
                            with trace.span(tr_handoff):
                                tensors = handoff.take(tensors)

                if flushed is not None:
                    # constituents carry their own runner/inference start
                    # stamps from when the batcher swallowed them
                    tensors_out, non_tensors_out, time_card = flushed
                else:
                    in_card = time_card
                    rids = None
                    if ctx.fault_plan is not None:
                        # injection key: every constituent id (a fault
                        # matching ANY member of a fused batch affects
                        # the whole dispatch)
                        rids = [tc.id for tc in _cards_of(in_card)]
                        # 'stall' injection wedges the stage BEFORE the
                        # inference span: the delay surfaces downstream
                        # as queue wait while this stage's input queue
                        # backs up — a reproducible overload window
                        stall = ctx.fault_plan.stall_ms(
                            ctx.step_idx, rids, lane=ctx.in_queue_idx)
                        if stall > 0:
                            time.sleep(stall / 1000.0)
                    time_card.record("inference%d_start" % ctx.step_idx)
                    call_counts = _dispatch_counts(tensors, ctx.device,
                                                   in_card)
                    attempt = 0
                    failed_reason = None
                    lane_death = None
                    t_busy0 = (time.monotonic()
                               if ctx.placement_sink is not None
                               else None)
                    while True:
                        try:
                            with trace.span(tr_model_call,
                                            getattr(in_card, "id",
                                                    None),
                                            **call_counts):
                                if ctx.fault_plan is not None:
                                    # inside the model_call span:
                                    # injected 'latency' is emulated
                                    # stage service, and the trace
                                    # timeline / placement busy
                                    # accounting must agree on what
                                    # service means; the lane address
                                    # lets replica_crash/replica_stall
                                    # faults target ONE lane
                                    ctx.fault_plan.fire(
                                        ctx.step_idx, rids, attempt,
                                        lane=ctx.in_queue_idx)
                                if handle is not None and attempt == 0:
                                    tensors_out, non_tensors_out, \
                                        time_card = model.complete(
                                            handle, non_tensors, in_card)
                                else:
                                    # retries re-run the synchronous
                                    # path even for prefetched work: the
                                    # failed handle's decode cannot be
                                    # re-waited, only redone
                                    tensors_out, non_tensors_out, \
                                        time_card = model(
                                            tensors, non_tensors, in_card)
                            break
                        except Exception as exc:
                            if handle is not None:
                                # this request will never complete() the
                                # prefetched decode again (retries
                                # re-decode synchronously; injected
                                # errors may fire before complete ever
                                # ran): retire its pool tickets now or
                                # the decode buffers stay pinned in the
                                # native pool for the process's life
                                if hasattr(model, "discard"):
                                    model.discard(handle, non_tensors)
                                handle = None
                            if isinstance(exc, LaneDeathError) \
                                    and ctx.containment \
                                    and ctx.in_depths is not None:
                                # lane-scale death (chaos
                                # replica_crash/replica_stall), not a
                                # request fault: dead-letter the
                                # in-service dispatch below, then hand
                                # the lane to the eviction drain. On
                                # non-replica steps the error falls
                                # through to classify_error -> FATAL
                                # (a chaos plan aimed at a lane-less
                                # step is a config bug, not a
                                # containable fault).
                                lane_death = exc
                                failed_reason = fault_reason(exc)
                                break
                            kind = classify_error(exc)
                            if kind is FATAL or not ctx.containment:
                                raise  # job-fatal, exactly as before
                            if getattr(in_card, "sub_id", None) \
                                    is not None and not (
                                        kind is TRANSIENT
                                        and attempt < ctx.max_retries):
                                # a forked SEGMENT card: dead-lettering
                                # one segment would strand its siblings
                                # in the aggregator forever and count
                                # the request toward the target once
                                # per segment — segment-parallel steps
                                # stay fail-fast past the retry budget
                                raise
                            if kind is TRANSIENT \
                                    and attempt < ctx.max_retries:
                                attempt += 1
                                if ctx.fault_stats is not None:
                                    ctx.fault_stats.record_retries(1)
                                if summary is not None:
                                    summary.note_retries(1)
                                if ctx.retry_backoff_ms > 0:
                                    # backoff is idle wait, not
                                    # service: pause the placement
                                    # busy clock so the planner's
                                    # busy window keeps matching the
                                    # trace spans (which never see
                                    # the sleep) under chaos runs
                                    if t_busy0 is not None:
                                        stage_busy_s += \
                                            time.monotonic() - t_busy0
                                    time.sleep(
                                        ctx.retry_backoff_ms / 1000.0)
                                    if t_busy0 is not None:
                                        t_busy0 = time.monotonic()
                                continue
                            failed_reason = fault_reason(exc)
                            if kind is TRANSIENT:
                                failed_reason = ("retries-exhausted:"
                                                 + failed_reason)
                            break
                    if t_busy0 is not None:
                        stage_busy_s += time.monotonic() - t_busy0
                    if failed_reason is not None:
                        # permanent failure: dead-letter the request(s)
                        # and keep the stream flowing
                        _contain_failure(ctx, in_card, failed_reason,
                                         summary)
                        if lane_death is not None:
                            # this lane is dead: evict it, drain its
                            # queued work onto healthy siblings, then
                            # exit the hot loop for good (no model
                            # call ever runs here again)
                            _die_lane(ctx, lane_death, summary)
                            break
                        continue
                    if time_card is None:
                        # stage swallowed the item (accumulating batcher
                        # / aggregator) — nothing moves downstream
                        continue
                validate_payload(declared_shapes, tensors_out,
                                 "step %d %s" % (ctx.step_idx,
                                                 ctx.model_class_path))
                if ctx.sync_outputs and tensors_out:
                    t_sync0 = (time.monotonic()
                               if ctx.placement_sink is not None
                               else None)
                    with trace.span(tr_device_sync):
                        _block_on(tensors_out)
                    if t_sync0 is not None:
                        stage_busy_s += time.monotonic() - t_sync0
                with trace.span(tr_finish):
                    time_card.record("inference%d_finish" % ctx.step_idx)
                    if ctx.placement_sink is not None:
                        stage_dispatches += 1
                    if ctx.in_hedges is not None \
                            and _hedge_lost(ctx, time_card):
                        # first completion wins: a sibling copy already
                        # resolved this hedged dispatch — discard this
                        # result (service time lands in hedges_wasted_ms,
                        # nothing publishes, nothing double-counts)
                        continue
                    if controller is not None and tensors_out \
                            and flushed is None \
                            and not getattr(model, "AUTOTUNE_SELF_SERVICE",
                                            False):
                        # service-time estimator, per emitted row bucket:
                        # the LAST-swallowed constituent's start -> the
                        # emission finish. Accurate for stages where
                        # swallow and emit happen in the same call (the
                        # Batcher — earlier constituents' spans include
                        # their accumulate hold, which must not read as
                        # service). Stages whose emissions complete
                        # asynchronously (the fusing loader under
                        # transfer_async, where every emission surfaces
                        # via take_ready and `flushed` is never None)
                        # self-report their close->ready span instead and
                        # opt out via AUTOTUNE_SELF_SERVICE.
                        # Arrival-triggered dispatches only: on `flushed`
                        # emissions (idle-tick hold expiry, EOS flush,
                        # async-transfer drains) the last start predates
                        # the dispatch by up to the hold/poll gap, and
                        # feeding that span would inflate the EWMA until
                        # the controller stopped holding at all
                        cards = _cards_of(time_card)
                        t_fin = cards[0].timings.get(key_inf_finish)
                        if t_fin is not None:
                            t_sta = max(tc.timings.get(key_inf_start, t_fin)
                                        for tc in cards)
                            out_pb = tensors_out[0]
                            # ragged emissions always ship the pool shape;
                            # the controller's continuous candidates are
                            # keyed by the VALID rows the dispatch carried
                            rows_key = (out_pb.valid
                                        if isinstance(out_pb, RaggedBatch)
                                        else int(out_pb.data.shape[0]))
                            controller.observe_service(
                                rows_key, max(0.0, t_fin - t_sta))

                    out_queue = None
                    if ctx.out_queues is not None:
                        if _sheddable_expired(ctx, time_card):
                            # pre-ring-write expiry shed: the computed
                            # output is already too late — drop it before
                            # it occupies a ring slot or downstream queue
                            _shed_deadline(ctx, time_card,
                                           "step%d_publish" % ctx.step_idx,
                                           summary)
                            continue
                        # route BEFORE the ring publish so a shed decision
                        # can drop the item while no ring slot holds it (a
                        # written-but-never-signalled slot would deadlock
                        # the producer on the next wrap-around)
                        out_idx = selector.select(tensors_out,
                                                  non_tensors_out,
                                                  time_card)
                        out_queue = ctx.out_queues[out_idx]
                        # forked segment cards are never shed (dropping one
                        # segment would strand its siblings in the
                        # aggregator and double-count the request): they
                        # fall through to the blocking-put backpressure path
                        if (ctx.overload_policy == "shed"
                                and out_queue.maxsize > 0
                                and getattr(time_card, "sub_id", None) is None
                                and out_queue.qsize() + ctx.num_segments
                                > out_queue.maxsize):
                            # on a replica-expanded edge the shed site is
                            # per-LANE: which lane's queue filled up is
                            # the signal (satellite of the health layer)
                            _shed_item(ctx, time_card, summary,
                                       lane=(ctx.out_queue_indices[out_idx]
                                             if ctx.out_depths is not None
                                             else None))
                            continue

                if ctx.output_ring is not None:
                    with trace.span(tr_publish):
                        segments = split_segments(tensors_out,
                                                  ctx.num_segments)
                        for seg_idx, seg_payload in enumerate(segments):
                            slot_idx = (ring_counter + seg_idx) \
                                % len(ctx.output_ring)
                            if not ctx.output_ring.wait_free(
                                    slot_idx, ctx.termination):
                                break
                            ctx.output_ring.slots[slot_idx].write(
                                seg_payload)
                    if ctx.termination.terminated:
                        break

                if ctx.out_queues is None:
                    # final step: count completions, detect the target.
                    # Register BEFORE any target-reached break: a
                    # completion added to the counter must appear in some
                    # timing table even when a sibling instance raised
                    # the flag while this one was mid-inference — the
                    # reference registered every completed record
                    # (reference runner.py:176-202)
                    with trace.span(tr_finish):
                        n = len(time_card) if isinstance(time_card,
                                                         TimeCardList) \
                            else 1
                        old, new = ctx.counter.add(n)
                        if progress_bar is not None \
                                and new > old_counter_value:
                            progress_bar.update(new - old_counter_value)
                            old_counter_value = new
                        cards = time_card.time_cards if isinstance(
                            time_card, TimeCardList) else [time_card]
                        for tc in cards:
                            summary.register(tc)
                    if new >= ctx.num_videos:
                        if old < ctx.num_videos:
                            ctx.termination.raise_flag(
                                TerminationFlag.TARGET_NUM_VIDEOS_REACHED)
                        else:
                            break  # someone else already hit the target
                else:
                    try:
                        with trace.span(tr_publish):
                            for seg_idx in range(ctx.num_segments):
                                forked = time_card.fork(seg_idx) \
                                    if ctx.num_segments > 1 else time_card
                                if ctx.output_ring is not None:
                                    sig = Signal(ctx.group_idx,
                                                 ctx.instance_idx,
                                                 ring_counter)
                                    ring_counter = (ring_counter + 1) \
                                        % len(ctx.output_ring)
                                else:
                                    sig = None
                                item = (sig, non_tensors_out, forked)
                                if ctx.out_hedges is not None:
                                    # snapshot the hedge template
                                    # BEFORE the put: the card clone
                                    # must never race the consumer's
                                    # stamps, and the payload refs
                                    # (immutable arrays) outlive the
                                    # ring slot's reuse
                                    ctx.out_hedges.track(
                                        forked,
                                        ctx.out_queue_indices[out_idx],
                                        tensors_out, non_tensors_out)
                                enqueued = False
                                if ctx.overload_policy == "shed":
                                    # capacity raced away since the
                                    # pre-check (competing producer):
                                    # the ring slot is already written,
                                    # so block with termination polling
                                    # — bounded backpressure, not abort
                                    while not ctx.termination.terminated:
                                        try:
                                            out_queue.put(
                                                item,
                                                timeout=QUEUE_POLL_S)
                                            enqueued = True
                                            break
                                        except queue.Full:
                                            continue
                                else:
                                    out_queue.put_nowait(item)
                                    enqueued = True
                                if enqueued \
                                        and ctx.out_depths is not None:
                                    # open the item's in-flight window
                                    # on its chosen replica lane
                                    ctx.out_depths.inc(
                                        ctx.out_queue_indices[out_idx])
                                    if ctx.out_health_board is not None:
                                        ctx.out_health_board \
                                            .note_enqueue(
                                                ctx.out_queue_indices[
                                                    out_idx])
                    except queue.Full:
                        # counted telemetry, not a stray stdout line:
                        # the per-edge overflow count lands in
                        # BenchmarkResult.queue_overflows and the
                        # log-meta 'Queue overflows:' line; the
                        # termination flag still says the job aborted
                        if ctx.fault_stats is not None:
                            ctx.fault_stats.record_overflow(
                                "step%d->step%d"
                                % (ctx.step_idx, ctx.step_idx + 1))
                        ctx.termination.raise_flag(
                            TerminationFlag.FRAME_QUEUE_FULL)
                        break
                # a flushed item does NOT end the loop: the stage may
                # hold more (fusing loaders flush one batch per call);
                # the loop re-enters the drain branch until flush()
                # returns None
            # the final flush may have contained failures (or parked
            # deadline sheds) after the last loop-top drain ran
            _drain_stage_failures(ctx, take_failed, take_retries,
                                  summary)
            if take_shed is not None:
                for tc_shed, where in take_shed():
                    _shed_deadline(ctx, tc_shed,
                                   "step%d_%s" % (ctx.step_idx, where),
                                   summary)
    except Exception:
        traceback.print_exc()
        ctx.termination.raise_flag(TerminationFlag.INTERNAL_ERROR)
    finally:
        # abort/drain path: retire any prefetched decodes whose results
        # will never be used so native-pool tickets don't pin buffers
        if pending and hasattr(model, "discard"):
            for handle, nt, _tc in pending:
                model.discard(handle, nt)
        pending.clear()
        # same for a stage-internal accumulator (fusing loader): its
        # submitted decodes must be retired or the shared pool pins
        # their buffers for the process's life
        if model is not None and hasattr(model, "discard_pending"):
            try:
                model.discard_pending()
            except Exception:
                traceback.print_exc()
        # hedged edges: keep the governor ticking until every
        # outstanding downstream dispatch settled — hedges fired after
        # this producer's exit markers would strand behind them
        if ctx.out_hedges is not None:
            try:
                _linger_for_hedges(ctx)
            except Exception:
                traceback.print_exc()
        # drain: the LAST producer on each edge marks end-of-stream, so
        # markers can never overtake a slower sibling replica's real
        # items (improves on reference runner.py:238-245 which let any
        # replica enqueue markers immediately)
        if ctx.out_queues is not None:
            for q_idx, out_queue in enumerate(ctx.out_queues):
                tracker = (ctx.out_trackers[q_idx]
                           if ctx.out_trackers is not None else None)
                if tracker is None or tracker.producer_finished():
                    markers = (tracker.num_markers if tracker is not None
                               else NUM_EXIT_MARKERS)
                    send_exit_markers(out_queue, markers, ctx.termination)
        # on abort only: wake any upstream producer blocked on our input
        # rings (reference runner.py:247-253). On a clean end-of-stream
        # drain every upstream producer has already finished (markers
        # come only after the last one), and a sibling replica may still
        # hold an unread Signal — releasing here would clear its slot
        # under it.
        if ctx.input_rings is not None and ctx.termination.terminated:
            for rings in ctx.input_rings.values():
                for ring in rings:
                    if ring is not None:
                        ring.release_all()
        # async stages (mesh runner) drain outstanding device work
        # BEFORE the finish barrier so the measured window covers every
        # dispatched inference (the analog of the reference's final
        # stream.synchronize discipline)
        if model is not None and hasattr(model, "finalize"):
            try:
                model.finalize()
            except Exception:
                traceback.print_exc()
        # cache-owning stages report their final counters before the
        # finish barrier (all stage work is done by here), so the
        # controller's aggregation never races a live counter
        if (ctx.cache_sink is not None
                and getattr(model, "cache", None) is not None):
            try:
                ctx.cache_sink.append(model.cache.snapshot())
            except Exception:
                traceback.print_exc()
        # staging-owning stages likewise report their final pool
        # counters (discard_pending above already stopped any transfer
        # worker, so the snapshot is stable)
        if (ctx.staging_sink is not None
                and getattr(model, "staging", None) is not None):
            try:
                ctx.staging_sink.append(model.staging.snapshot())
            except Exception:
                traceback.print_exc()
        # controller-owning stages report their final decision counters
        # the same way (the stage is drained; counters are stable)
        if ctx.autotune_sink is not None and controller is not None:
            try:
                ctx.autotune_sink.append(controller.snapshot())
            except Exception:
                traceback.print_exc()
        # compile/warmup accounting: every stage reports construction
        # time; jit-owning stages add their signature snapshot
        if ctx.compile_sink is not None and model is not None:
            try:
                tracker = getattr(model, "compiles", None)
                ctx.compile_sink.append(
                    (ctx.step_idx, warmup_s,
                     tracker.snapshot() if tracker is not None
                     else None))
            except Exception:
                traceback.print_exc()
        # padding-waste counters (bucketed) / ragged pool counters
        if (ctx.pad_sink is not None
                and getattr(model, "padding", None) is not None):
            try:
                ctx.pad_sink.append(model.padding.snapshot())
            except Exception:
                traceback.print_exc()
        # a stage's own counters (tokens, expert assignments)
        if (ctx.stage_counter_sink is not None
                and hasattr(model, "stage_counters")):
            try:
                ctx.stage_counter_sink.append(model.stage_counters())
            except Exception:
                traceback.print_exc()
        if (ctx.ragged_sink is not None
                and getattr(model, "ragged_stats", None) is not None):
            try:
                ctx.ragged_sink.append(dict(model.ragged_stats))
            except Exception:
                traceback.print_exc()
        # intra-stage shard accounting (rnb_tpu.parallel.shardplan):
        # stages with a declared `shard` key report degree, projected
        # footprint and the host-timed collective tax
        if (ctx.shard_sink is not None
                and getattr(model, "shard_stats", None) is not None):
            try:
                ctx.shard_sink.append((ctx.step_idx,
                                       dict(model.shard_stats)))
            except Exception:
                traceback.print_exc()
        # replica-lane settlement for an item still in service when
        # the loop exited (abort / target-reached break); the hedge
        # governor needs no twin here — claim() settles on every
        # resolution path, and unresolved abort-path dispatches are
        # released by the producer's termination-gated linger
        if depth_owed:
            if ctx.in_depths is not None:
                ctx.in_depths.dec(ctx.in_queue_idx, depth_owed)
            if ctx.health_board is not None:
                ctx.health_board.note_settle(ctx.in_queue_idx,
                                             depth_owed)
            depth_owed = 0
        # device-resident handoff accounting (rnb_tpu.handoff): the
        # stage is drained, counters are stable
        if ctx.handoff_sink is not None and handoff is not None:
            try:
                ctx.handoff_sink.append(handoff.snapshot())
            except Exception:
                traceback.print_exc()
        # measured dispatch costs for the placement planner
        # (rnb_tpu.placement) — every executor reports, planner-on runs
        # only (the sink gates it)
        if ctx.placement_sink is not None and model is not None:
            try:
                sstats = getattr(model, "shard_stats", None)
                if sstats is not None:
                    # sharded steps carry their degree, the host-timed
                    # collective slice and the feasibility floor so
                    # the planner's joint (replicas x degree) model
                    # calibrates from measurement, never assumption
                    ctx.placement_sink.append(
                        CostRecord(ctx.step_idx, stage_busy_s,
                                   stage_dispatches,
                                   shard_degree=int(sstats["degree"]),
                                   collective_s=float(
                                       sstats["collective_ms"]) / 1e3,
                                   min_degree=max(
                                       1, int(sstats["min_degree"]))))
                else:
                    ctx.placement_sink.append(
                        CostRecord(ctx.step_idx, stage_busy_s,
                                   stage_dispatches))
            except Exception:
                traceback.print_exc()
        try:
            ctx.fin_bar.wait()
        except threading.BrokenBarrierError:
            pass

        if summary is not None:
            if ctx.summary_sink is not None:
                ctx.summary_sink.append(summary)
            with open(logname(ctx.job_id, ctx.device.label, ctx.group_idx,
                              ctx.instance_idx, base=ctx.log_base),
                      "w") as f:
                summary.save_full_report(f)
            if ctx.print_progress:
                summary.print_summary(NUM_SUMMARY_SKIPS)
                if progress_bar is not None:
                    progress_bar.close()
