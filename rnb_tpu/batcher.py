"""Dynamic batching stage.

Accumulates ``batch`` incoming requests and fuses them into one larger
batch so a downstream network stage amortizes its launch/compile cost —
the "Batch" half of Replicate & Batch. While accumulating, the stage
returns a None time_card, which tells the executor to propagate nothing
downstream (reference batcher.py:17-34, runner.py:130-134).

The fused output is one PaddedBatch holding the concatenated *valid*
rows of the constituents, re-padded to the stage's max shape, plus a
TimeCardList so one fused inference still stamps every constituent
request's card.
"""

from __future__ import annotations

import time

import numpy as np

from rnb_tpu import trace
from rnb_tpu.autotune import BatchController
from rnb_tpu.health import cards_of as _cards_of
from rnb_tpu.health import expired as _deadline_expired
from rnb_tpu.ops.ragged import resolve_pool_rows, segment_offsets_of
from rnb_tpu.stage import (PadCounter, PaddedBatch, RaggedBatch,
                           StageModel, normalize_row_buckets,
                           note_emission_accounting)
from rnb_tpu.telemetry import TimeCardList
from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy

MAX_ROWS = 15  # max clips per fused batch, matches the loader's max


class Batcher(StageModel):
    """Accumulate `batch` requests, then emit one fused PaddedBatch.

    ``row_buckets`` (optional) pads the fused batch to the smallest
    bucket holding its valid rows instead of all the way to the ring's
    max shape — e.g. 6 fused 1-clip videos dispatch as a 6-row batch,
    not a 15-row one — so the downstream network stage (warmed on the
    same buckets) spends MXU cycles on mostly-valid rows. ``flush()``
    emits any partial batch at end-of-stream so the last
    ``num_videos mod batch`` requests still complete (the reference's
    batcher simply stranded them, reference batcher.py:17-34).

    ``segments`` (optional) is for a consumer whose rows are not
    independent: consecutive rows of one request are one sequence
    (packed token rows, rnb_tpu.models.token_stages). The bucketed
    emission then carries its segment table (a RaggedBatch at the
    bucket's shape, validated on publish), and fusing runs up to the
    row cap: a batch that has reached the declared max rows is emitted
    at once instead of waiting for ``batch`` arrivals.

    A request that no longer fits such a batch waits aside instead of
    closing it, while requests behind it that still fit fill the
    batch; once more than ``LOOK_AHEAD`` wait aside the batch is
    emitted, and those aside open the next one in their order (so a
    request is overtaken for at most one emission). Without
    ``segments`` the request that does not fit closes the batch.
    """

    # any upstream bucket set is acceptable: the batcher concatenates
    # valid rows and re-pads to its OWN bucket set / max shape
    REPACKS_ROWS = True

    #: the accumulate/emit decision and the pad bucket can be driven
    #: by the load-adaptive controller (rnb_tpu.autotune): under
    #: autotune the static `batch` count becomes a ceiling and the
    #: controller emits as soon as growing the window cannot meet the
    #: latency budget — with a hold deadline, which the static batcher
    #: never had (it waited for `batch` arrivals or end-of-stream)
    SUPPORTS_AUTOTUNE = True

    #: fused emissions can ship as a flat row pool at ONE shape with a
    #: rows_valid count + per-request segment offsets instead of
    #: padding to a bucket (root 'ragged' config key)
    SUPPORTS_RAGGED = True

    #: requests that may wait aside of a packed batch (``segments``).
    #: On the prompts of nemotron3-nano.bulk (PR 28: the run replayed
    #: from its schedule, which gave the chip's counts to the request)
    #: 0 leaves 8.4% of the rows shipped as padding, 2 2.9%, 4 1.5%,
    #: 8 0.8%
    LOOK_AHEAD = 4

    def __init__(self, device, batch=1, shapes=None, max_rows=MAX_ROWS,
                 consecutive_frames=8, frame_hw=112, row_buckets=None,
                 ragged=False, ragged_pool_rows=None, segments=False,
                 **kwargs):
        super().__init__(device)
        self.batch = int(batch)
        # the fuse capacity comes from this stage's DECLARED output
        # shape, not from incoming payloads: under upstream row
        # bucketing an incoming batch's max_rows is its (small) bucket,
        # while the fused batch may legally grow to the ring shape
        self._declared_shapes = self.output_shape_for(
            shapes=shapes, max_rows=max_rows,
            consecutive_frames=consecutive_frames, frame_hw=frame_hw)
        self._declared_max = [int(s[0]) for s in self._declared_shapes]
        # same validation as the loader's bucketing: typo'd buckets
        # fail fast instead of silently padding to un-warmed shapes
        self.row_buckets = (normalize_row_buckets(
            row_buckets, self._declared_max[0], "stage max rows")
            if row_buckets else None)
        # ragged row-pool dispatch (rnb_tpu.ops.ragged): emissions ship
        # the full declared shape (the pool) with a rows_valid count +
        # segment offsets; row_buckets, if configured, become the
        # COUNTERFACTUAL pad rule the pad_rows_eliminated counter is
        # measured against, never a shipped shape
        self.ragged = bool(ragged)
        self.pool_rows = (resolve_pool_rows(
            ragged_pool_rows, self._declared_max[0], "stage max rows")
            if self.ragged else None)
        # packed sequences: a bucketed emission carries its segment
        # table too (a RaggedBatch at the bucket's shape), for a
        # consumer whose rows are not independent — consecutive rows
        # of one request are one sequence — and a batch that has
        # reached the row cap is emitted at once
        self.segments = bool(segments)
        self.look_ahead = self.LOOK_AHEAD if self.segments else 0
        #: [(tensors, time_card)] waiting aside for the next batch
        self._aside = []
        #: padding-waste accounting (always on; 0-pad under ragged)
        self.padding = PadCounter()
        #: ragged accounting, drained via the executor's ragged sink
        self.ragged_stats = ({"pool_rows": self.pool_rows,
                              "emissions": 0, "rows": 0,
                              "pad_rows_eliminated": 0,
                              "cache_hit_rows": 0}
                             if self.ragged else None)
        self._tensors = []      # list of tuples of PaddedBatch
        self._time_cards = []
        #: load-adaptive batching controller (rnb_tpu.autotune), set
        #: by the executor via enable_autotune(); None = static
        #: accumulate-to-`batch` semantics exactly as configured
        self.autotune = None
        #: deadline-expired requests dropped from the accumulator at
        #: emission time (rnb_tpu.health), parked for the executor's
        #: take_shed() drain — inert unless requests carry deadlines
        self._shed = []
        #: monotonic instant the oldest pending request joined the
        #: accumulator (None when empty) — the hold-deadline anchor
        self._t_oldest = None

    def enable_autotune(self, settings) -> BatchController:
        """Executor protocol (rnb_tpu.runner): drive this stage's
        accumulate/emit decision and pad bucket with a BatchController
        over the stage's own warmed bucket set — decisions can only
        name shapes the downstream stage warmed. Under ragged dispatch
        every row count is one dispatch of the same executable, so the
        candidate set is continuous (1..pool_rows) and decisions stop
        being bucket-quantized."""
        if self.segments:
            raise ValueError("segments: the requests waiting aside have "
                             "no hold deadline, so not under autotune")
        if self.ragged:
            self.autotune = BatchController.for_stage(
                settings, tuple(range(1, self.pool_rows + 1)),
                self.pool_rows)
            return self.autotune
        self.autotune = BatchController.for_stage(
            settings, self.row_buckets or (self._declared_max[0],),
            self._declared_max[0])
        return self.autotune

    def input_shape(self):
        # the batcher re-packs whatever it receives, so its input max
        # shapes ARE its declared output shapes — derived from the
        # constructor's shapes/max_rows/consecutive_frames/frame_hw,
        # never the flagship globals (a non-default topology's
        # declared-vs-actual payload validation depends on this)
        return self._declared_shapes

    @staticmethod
    def output_shape():
        return ((MAX_ROWS, 8, 112, 112, 3),)

    @classmethod
    def output_shape_for(cls, shapes=None, max_rows: int = MAX_ROWS,
                         consecutive_frames: int = 8,
                         frame_hw: int = 112, **_kwargs):
        # the batcher is payload-agnostic — it re-packs whatever its
        # upstream emits — so non-flagship topologies declare the wire
        # shapes explicitly via a `shapes` config key
        if shapes:
            return tuple(tuple(int(d) for d in s) for s in shapes)
        return ((int(max_rows), int(consecutive_frames),
                 frame_hw, frame_hw, 3),)

    @classmethod
    def input_shape_for(cls, **model_kwargs):
        # static counterpart of input_shape(): the batcher re-packs
        # whatever it receives, so its input max shapes ARE its
        # declared output shapes (same constructor-args derivation)
        return cls.output_shape_for(**model_kwargs)

    def __call__(self, tensors, non_tensors, time_card):
        if self.batch <= 1:
            return tensors, non_tensors, time_card

        # A single request bigger than the fuse capacity can never be
        # emitted — that is a topology error, fail fast and leave the
        # accumulator intact.
        for pos, pb in enumerate(tensors):
            if pb.valid > self._declared_max[pos]:
                raise ValueError(
                    "request carries %d rows, exceeding the stage max "
                    "shape %d; raise the stage max shape"
                    % (pb.valid, self._declared_max[pos]))

        # A request that no longer FITS with the pending ones closes
        # the window early: emit what is pending and start the next
        # batch with this request. Load-dependent early emission is
        # ordinary dynamic-batching behavior — aborting the run here
        # would let one mid-sized video kill the benchmark.
        early = None
        if self._tensors and not self._fits(tensors):
            if self.look_ahead:
                # it waits aside: smaller requests behind it may still
                # fill the batch, and it opens the next one
                self._aside.append((tensors, time_card))
                if len(self._aside) > self.look_ahead:
                    return self._emit_fused()
                return None, None, None
            early = self._emit_fused()

        self._tensors.append(tensors)
        self._time_cards.append(time_card)
        if self._t_oldest is None:
            self._t_oldest = time.monotonic()
        if self.autotune is not None:
            # rows per CLIENT request, not per upstream emission: a
            # fused upstream delivers many requests' rows in one call,
            # and the runner feeds the inter-arrival EWMA per
            # constituent card — mixing per-emission rows with
            # per-request gaps would understate residual-fill time by
            # the upstream fuse factor and hold when growth cannot
            # meet the budget
            n_req = len(getattr(time_card, "time_cards", None) or (1,))
            self.autotune.observe_rows(tensors[0].valid / n_req)
        if early is not None:
            return early
        if self._full():
            # the static fuse count stays a hard ceiling under
            # autotune; a packed batch that is full has nothing to
            # wait for
            return self._emit_fused()
        if self.autotune is not None:
            # controller-driven early emission: dispatch now when
            # growing the window cannot meet the latency budget (the
            # static batcher would wait for `batch` arrivals — at low
            # rate that wait is unbounded until end-of-stream)
            rows, waited, dec = self._decide()
            if rows >= dec.target_rows or waited >= dec.hold_s:
                return self._emit_fused()
        return None, None, None

    def _fits(self, tensors) -> bool:
        """Whether one more request's rows fit the pending batch."""
        return len(self._time_cards) < self.batch and not any(
            sum(parts[pos].valid for parts in self._tensors) + pb.valid
            > self._declared_max[pos] for pos, pb in enumerate(tensors))

    def _full(self) -> bool:
        return len(self._time_cards) >= self.batch or (
            self.segments and sum(parts[0].valid for parts in self._tensors)
            >= self._declared_max[0])

    def take_ready(self):
        """Executor hook, ahead of new input: the batch the requests
        from aside opened is emitted at once where they filled it (or
        where more than ``LOOK_AHEAD`` still wait aside)."""
        if self._time_cards and (self._full()
                                 or len(self._aside) > self.look_ahead):
            fused = self._emit_fused()
            return fused if fused[2] is not None else None
        return None

    def _refill(self) -> None:
        """Open the next batch with the requests waiting aside, in
        their order, each that fits; the first always does, so nothing
        waits aside of an empty batch."""
        waiting, self._aside = self._aside, []
        for tensors, card in waiting:
            if self._fits(tensors):
                self._tensors.append(tensors)
                self._time_cards.append(card)
            else:
                self._aside.append((tensors, card))
        if self._time_cards:
            self._t_oldest = time.monotonic()

    def _decide(self, peek=False):
        """``(rows_ready, oldest_wait_s, Decision)`` for the current
        accumulator state — the single place the controller's inputs
        are derived, so the emit check (__call__/poll) and the
        deadline the executor polls on (next_deadline_s) can never
        diverge. ``peek`` skips the controller's decision accounting
        (deadline queries happen every executor poll tick)."""
        rows = sum(parts[0].valid for parts in self._tensors)
        waited = time.monotonic() - self._t_oldest
        ask = self.autotune.peek if peek else self.autotune.decide
        return rows, waited, ask(len(self._time_cards), rows, waited)

    def next_deadline_s(self):
        """Seconds until the controller's hold deadline for the oldest
        pending request, or None when nothing is held (or autotune is
        off — the static batcher has no deadline: it waits for
        arrivals). The executor shrinks its queue-poll timeout to
        this (rnb_tpu.runner.poll_plan)."""
        if self.autotune is None or self._t_oldest is None:
            return None
        _, waited, dec = self._decide(peek=True)
        return max(0.0, dec.hold_s - waited)

    def poll(self):
        """Idle tick from the executor (no arrival within its queue
        poll window): emit the held partial batch once its controller
        hold deadline expired. Without this, a held batch could only
        emit on the NEXT arrival — exactly the unbounded low-rate wait
        autotune exists to remove. Static mode (autotune off) keeps
        the accumulate-to-`batch` semantics: always None."""
        if self.autotune is None or self._t_oldest is None:
            return None
        rows, waited, dec = self._decide()
        if rows >= dec.target_rows or waited >= dec.hold_s:
            return self._emit_fused()
        return None

    def _bucket_for(self, rows: int, max_rows: int) -> int:
        if self.autotune is not None:
            # restrict the pad bucket to the controller's candidate
            # set (warmed buckets, optionally narrowed by
            # autotune.buckets) so emissions land on the shapes the
            # decisions reason about; rows exceeding every candidate
            # fall back to the static rule (never pad short)
            bucket = self.autotune.bucket_for(rows)
            if rows <= bucket <= max_rows:
                return bucket
        if self.row_buckets:
            for bucket in self.row_buckets:
                if rows <= bucket <= max_rows:
                    return bucket
        return max_rows

    def _counterfactual_bucket(self, rows: int) -> int:
        """The rows the bucketed pad rule WOULD have shipped for this
        emission — what pad_rows_eliminated is measured against under
        ragged (max-shape padding when no row_buckets are named)."""
        if self.row_buckets:
            for bucket in self.row_buckets:
                if rows <= bucket:
                    return bucket
        return self._declared_max[0]

    def take_shed(self):
        """Executor hook (rnb_tpu.runner): requests this stage shed
        internally because their deadline expired while the batch
        accumulated -> [(card, where)] (drained each loop top)."""
        out, self._shed = self._shed, []
        return out

    def _drop_expired(self) -> None:
        """The 'Batcher emit' deadline boundary (rnb_tpu.health): a
        request whose absolute deadline passed while it waited in the
        accumulator is dropped BEFORE fusing — its rows never pad a
        dispatch, never burn downstream service. Inert when no card
        carries a deadline stamp."""
        if not any(getattr(tc, "deadline_s", None) is not None
                   for item in self._time_cards
                   for tc in _cards_of(item)):
            # no constituent card anywhere carries a deadline (the
            # unwrap matters: an upstream fusing loader delivers
            # TimeCardLists whose deadline stamps live on the
            # constituents, not the wrapper)
            return
        live_tensors, live_cards = [], []
        for tensors, card in zip(self._tensors, self._time_cards):
            # forked segment cards are never shed — same rule as every
            # other shed boundary (runner take/publish): dropping one
            # segment would strand its aggregator siblings forever and
            # count the request toward the target a second time
            forked = any(getattr(tc, "sub_id", None) is not None
                         for tc in _cards_of(card))
            if not forked and _deadline_expired(card):
                self._shed.append((card, "hold"))
            else:
                live_tensors.append(tensors)
                live_cards.append(card)
        self._tensors = live_tensors
        self._time_cards = live_cards

    def _emit_fused(self):
        self._drop_expired()
        if not self._time_cards:
            # every pending request expired: nothing to emit — the
            # executor's take_shed() drain disposes the parked cards
            self._tensors = []
            self._t_oldest = None
            self._refill()
            return None, None, None
        if trace.ACTIVE is not None:
            # timeline marker per fused dispatch (args allocated only
            # while tracing): how many requests/rows this batch fused
            trace.instant("batcher.emit", args={
                "requests": len(self._time_cards),
                "rows": sum(parts[0].valid for parts in self._tensors)})
        with trace.span("batcher.fuse", **self._fuse_counts()):
            fused = self._fuse_all()
        cards = TimeCardList(self._time_cards)
        self._tensors = []
        self._time_cards = []
        self._t_oldest = None
        self._refill()
        # Per-request metadata cannot be attributed to a fused batch; emit
        # None rather than one arbitrary constituent's non_tensors
        # (reference batcher.py:34 does the same).
        return tuple(fused), None, cards

    def _fuse_counts(self) -> dict:
        """What the fuse span carries: valid rows, requests and, where
        the requests' cards say how many tokens each holds, tokens."""
        counts = {"rows": sum(parts[0].valid for parts in self._tensors),
                  "segments": len(self._time_cards)}
        tokens = [getattr(tc, "num_tokens", None)
                  for item in self._time_cards for tc in _cards_of(item)]
        if tokens and None not in tokens:
            counts["tokens_valid"] = int(sum(tokens))
        return counts

    def _fuse_all(self):
        fused = []
        for pos, parts in enumerate(zip(*self._tensors)):
            valid = sum(pb.valid for pb in parts)
            if self.ragged:
                # one compiled shape: the pool is the declared max;
                # the segment table partitions the valid rows per
                # constituent request
                bucket = self._declared_max[pos]
            else:
                bucket = self._bucket_for(valid, self._declared_max[pos])
            if pos == 0 and self.autotune is not None:
                self.autotune.note_emission(valid if self.ragged
                                            else bucket)
            if pos == 0:
                # the shared padding/ragged accounting rule
                # (rnb_tpu.stage.note_emission_accounting): pad count
                # stamped on the first constituent card; under ragged
                # the counterfactual bucket feeds pad_rows_eliminated
                note_emission_accounting(
                    self.padding, self.ragged_stats, self._time_cards,
                    valid, bucket,
                    self._counterfactual_bucket(valid) if self.ragged
                    else 0)
            pb = self._fuse_parts(parts, valid, bucket)
            if (self.ragged or self.segments) and pos == 0:
                pb = RaggedBatch(pb.data, valid, segment_offsets_of(
                    part.valid for part in parts))
            fused.append(pb)
        return fused

    @staticmethod
    def _fuse_parts(parts, valid: int, bucket: int) -> PaddedBatch:
        """Concatenate the valid rows of ``parts`` padded to ``bucket``.

        Device arrays fuse ON DEVICE (lazy jnp slice+concat): the fused
        batch never round-trips through the host, which matters doubly
        on TPU — device_put/asarray bounces would serialize on transfer
        latency, and the async concat lets the executor thread move on.
        Host numpy payloads keep the numpy path.
        """
        jax, jnp = _jax_numpy()

        # "fusable on device" = identical placement: the seed rule
        # (every part on the SAME single device) OR — under the
        # device-resident edge contract (rnb_tpu.handoff), where
        # payloads may arrive mesh-sharded — equal shardings. Both
        # alternatives are needed: a NamedSharding over a 1-device
        # mesh and a SingleDeviceSharding on that device compare
        # unequal as objects yet fuse on device identically, and
        # falling to the host-numpy path for them would be the host
        # bounce the handoff exists to delete.
        all_jax = all(isinstance(pb.data, jax.Array) for pb in parts)
        same_placement = all_jax and (
            len({d for pb in parts for d in pb.data.devices()}) == 1
            or len({pb.data.sharding for pb in parts}) == 1)
        if same_placement:
            segments = [pb.data[: pb.valid] for pb in parts]
            pad = bucket - valid
            if pad > 0:
                segments.append(jnp.zeros(
                    (pad,) + tuple(parts[0].data.shape[1:]),
                    parts[0].data.dtype))
            return PaddedBatch(jnp.concatenate(segments, axis=0), valid)
        rows = np.concatenate(
            [np.asarray(pb.data)[: pb.valid] for pb in parts], axis=0)
        return PaddedBatch.from_rows(rows, bucket)

    def flush(self):
        """End-of-stream hook (called by the executor on the exit
        marker): emit whatever partial batch is pending, or None."""
        if not self._time_cards:
            return None
        return self._emit_fused()
