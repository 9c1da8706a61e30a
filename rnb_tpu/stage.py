"""The pipeline-stage plugin contract.

A *stage model* is one step of the inference pipeline — decode, a
(possibly partial) neural network, a batcher, an aggregator. Stage
classes are named by string in JSON configs and loaded dynamically;
the executor instantiates one per (step, group, device instance).

Capability parity with the reference's RunnerModel (runner_model.py:1-81)
with one deliberate TPU-first change: tensors move through the pipeline
as fixed max-shape arrays with an explicit valid-row count
(:class:`PaddedBatch`), never as dynamically-sized slices. XLA compiles
a jitted stage exactly once per static shape; the reference instead
sliced shared CUDA tensors to the valid batch size before each call
(reference runner.py:109-114), which on TPU would trigger a
recompilation per distinct clip count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class PadCounter:
    """Padding-waste accounting for one batching stage instance.

    Every emission notes its valid rows and the rows it actually
    shipped; the difference is pad work the downstream stage burns
    FLOPs (and the wire burns bytes) on. Surfaced end-to-end —
    BenchmarkResult ``pad_rows``/``total_rows``, the log-meta
    ``Padding:`` line, the ``# padding`` table trailer — so the
    bucketed path quantifies the waste the ragged path removes (and
    the ragged path proves its computed-pad count is ~0).
    """

    pad_rows: int = 0
    total_rows: int = 0
    emissions: int = 0

    def note(self, valid: int, shipped: int) -> int:
        """Record one emission; returns its pad-row count."""
        pad = max(0, int(shipped) - int(valid))
        self.pad_rows += pad
        self.total_rows += int(shipped)
        self.emissions += 1
        return pad

    def snapshot(self) -> dict:
        return {"pad_rows": self.pad_rows, "total_rows": self.total_rows,
                "emissions": self.emissions}


def note_emission_accounting(padding: "PadCounter", ragged_stats,
                             cards, valid: int, shipped: int,
                             counterfactual_rows: int) -> None:
    """The ONE padding/ragged accounting rule every batching stage
    (loaders, Batcher) applies per emission — parse_utils --check
    asserts invariants over these counters, so two hand-maintained
    copies would be exactly the drift the checker exists to stop.

    Bucketed (``ragged_stats is None``): count ``shipped - valid`` pad
    rows. Ragged: the consumer's kernel computes no pad rows, so the
    counted shipped rows ARE the valid rows and ``counterfactual_rows
    - valid`` — what the bucketed pad rule would have shipped — lands
    in ``pad_rows_eliminated`` (equal to a same-seed bucketed arm's
    ``pad_rows`` by construction). Either way the emission's pad count
    is stamped on the FIRST constituent card (0 on the rest) so table
    sums stay exact.
    """
    if ragged_stats is not None:
        pad = padding.note(valid, valid)
        ragged_stats["emissions"] += 1
        ragged_stats["rows"] += valid
        ragged_stats["pad_rows_eliminated"] += \
            int(counterfactual_rows) - int(valid)
    else:
        pad = padding.note(valid, shipped)
    for idx, tc in enumerate(cards):
        tc.pad_rows = (getattr(tc, "pad_rows", 0) + pad if idx == 0
                       else getattr(tc, "pad_rows", 0))


@dataclasses.dataclass
class PaddedBatch:
    """A static-shape array plus the number of leading valid rows.

    ``data``'s row axis (axis 0, the batch/clip axis) is the stage's
    declared max shape — or, under opt-in row bucketing, a smaller
    bucket from a fixed per-config set (still static per bucket, one jit
    executable each). Consumers must use ``valid``/``max_rows``, never
    assume axis 0 equals the declared maximum. Rows ``valid:`` are
    padding and must be ignored. This is the TPU-idiomatic encoding of
    the reference's max-shape shared tensors + ``valid_batch_sizes``
    side array (reference control.py:34-39).
    """

    data: Any          # numpy or jax.Array, shape = (max_rows, ...)
    valid: int         # number of meaningful leading rows

    @property
    def max_rows(self) -> int:
        return int(self.data.shape[0])

    def valid_data(self):
        """Host-side view of the meaningful rows (do not use inside jit)."""
        return self.data[: self.valid]

    @staticmethod
    def from_rows(rows, max_rows: int, dtype=None) -> "PaddedBatch":
        """Pad a (n, ...) host array up to (max_rows, ...) with zeros."""
        rows = np.asarray(rows, dtype=dtype)
        n = rows.shape[0]
        if n > max_rows:
            raise ValueError("batch of %d rows exceeds max_rows=%d"
                             % (n, max_rows))
        if n == max_rows:
            return PaddedBatch(rows, n)
        pad = np.zeros((max_rows - n,) + rows.shape[1:], dtype=rows.dtype)
        return PaddedBatch(np.concatenate([rows, pad], axis=0), n)


@dataclasses.dataclass
class RaggedBatch(PaddedBatch):
    """A :class:`PaddedBatch` whose row axis is a **flat row pool** at
    the stage's one compiled shape, plus the per-request segment table
    (rnb_tpu.ops.ragged).

    Under the ``ragged`` root key ``data`` always has exactly the
    pool shape — never a bucket — so every dispatch hits the same XLA
    executable (a ``Batcher`` with ``segments`` ships a bucket with its
    segment table instead: packed sequences); ``valid`` is the
    scalar ``rows_valid`` the ragged forward primitive masks against;
    ``segment_offsets`` partitions ``[0, valid)`` per constituent
    request (request i owns rows ``[offsets[i], offsets[i+1])``),
    validated by the executor on every publish.
    """

    segment_offsets: Tuple[int, ...] = (0, 0)

    def __post_init__(self):
        self.segment_offsets = tuple(int(o)
                                     for o in self.segment_offsets)

    @property
    def num_segments(self) -> int:
        """Constituent requests packed into the pool."""
        return len(self.segment_offsets) - 1


def normalize_row_buckets(row_buckets, max_rows: int, what: str
                          ) -> Tuple[int, ...]:
    """Sorted, validated bucket tuple; ``(max_rows,)`` when disabled.

    The one validation every bucketing stage (loader, batcher) shares:
    buckets are distinct positive row counts ending exactly at the
    stage's max shape — a typo'd set must fail fast, not silently pad
    to an un-warmed shape.
    """
    if not row_buckets:
        return (int(max_rows),)
    buckets = sorted(int(b) for b in row_buckets)
    if buckets[0] < 1 or len(set(buckets)) != len(buckets):
        raise ValueError("row_buckets %r must be distinct positive row "
                         "counts" % (row_buckets,))
    if buckets[-1] != max_rows:
        raise ValueError("row_buckets %r must end at %s=%d"
                         % (row_buckets, what, max_rows))
    return tuple(buckets)


class StageModel:
    """Abstract contract every pipeline stage implements.

    Besides the instance lifecycle below, stages expose a *static*
    face — ``output_shape_for`` / ``input_shape_for`` and the dtype
    variants — classmethods that derive wire metadata from the step's
    JSON kwargs without constructing the stage (no device, no
    checkpoint, no warm-up). The runtime sizes buffer rings from the
    output side; the static pipeline checker (rnb_tpu.analysis.graph)
    walks both sides step-to-step to reject shape/dtype-incompatible
    wiring before any device is touched.

    Lifecycle (all in the executor thread that owns the stage's devices):

    * ``__init__(device, **kwargs)`` — build the stage, load weights, and
      *warm up* (jit-compile with dummy inputs) so steady-state requests
      never pay compilation latency. Extra JSON config keys arrive as
      kwargs (reference runner_model.py:3-14, benchmark.py:241-246).
    * ``input_shape()`` — nested tuple of expected per-tensor shapes, or
      None if the stage takes no tensor inputs (reference
      runner_model.py:16-29).
    * ``output_shape()`` — static; tuple of max shapes of the produced
      tensors, or None meaning "this stage emits no tensors", in which
      case the runtime allocates no device ring for it (reference
      runner_model.py:31-46 — note None differs from ``()``).
    * ``output_shape_for(**model_kwargs)`` — classmethod refinement of
      ``output_shape()``: receives the step's model kwargs (the same
      dict the constructor gets) so config-dependent stages — a partial
      layer range, a non-default row count — can declare their *exact*
      output shapes. The runtime sizes buffer rings with this and
      validates every produced payload against it, so shape metadata
      can never silently rot (the reference's hardcoded (10, 400) was
      wrong for partial ranges — its TODO #69,
      models/r2p1d/model.py:76-80). Default: the static shape.
    * ``__call__(tensors, non_tensors, time_card)`` — run one request.
      ``tensors`` is a tuple of :class:`PaddedBatch` (or None for the
      first stage); returns ``(tensors, non_tensors, time_card)`` where a
      None time_card means the stage swallowed the item (e.g. a batcher
      still accumulating) and nothing propagates downstream (reference
      runner_model.py:48-81, runner.py:130-134).
    """

    #: True for stages that re-pack incoming rows into their own
    #: batches (Batcher): any upstream row-bucket set is acceptable on
    #: their input, so bucket-compatibility checks skip them. Stages
    #: that jit-compile per incoming bucket shape (network runners)
    #: leave this False — their warmed bucket set must cover every
    #: bucket the producer can emit.
    REPACKS_ROWS = False

    #: Classes this stage forwards its open config kwargs to (composed
    #: stages, e.g. R2P1DSingleStep embedding a loader + runner). The
    #: static unconsumed-config-key check unions their named
    #: constructor parameters with this class's own.
    FORWARDS_CONFIG_TO: Tuple[type, ...] = ()

    #: True for stages whose batching knobs the load-adaptive
    #: controller (rnb_tpu.autotune, root 'autotune' config key) can
    #: drive — they implement ``enable_autotune(settings)`` and route
    #: their accumulate/emit decisions through the controller. The
    #: executor and the static graph checker both key off this.
    SUPPORTS_AUTOTUNE = False

    #: True for stages that implement the ragged row-pool dispatch
    #: contract (root 'ragged' config key, rnb_tpu.ops.ragged): they
    #: accept ``ragged``/``ragged_pool_rows`` constructor kwargs, warm
    #: exactly ONE shape (the pool), and move RaggedBatch payloads.
    #: The launcher injects the kwargs only for supporting classes.
    SUPPORTS_RAGGED = False

    #: True for stages that implement the page-allocator contract
    #: (root 'pager' config key, rnb_tpu.pager): they implement
    #: ``enable_pager(pager)`` — loaders switch the clip cache to
    #: paged entries and gather hits on device; consumers attach a
    #: feature-page arena and serve repeat requests from cached
    #: post-stage rows. The executor wires the shared Pager only for
    #: supporting classes.
    SUPPORTS_PAGER = False

    def __init__(self, device, **kwargs):
        self.device = device

    def input_shape(self) -> Optional[Sequence]:
        return None

    def input_sharding(self):
        """The ``jax.sharding.Sharding`` this stage wants its input
        payloads homed on, or None for the instance's home device.

        Consulted by the device-resident edge contract
        (rnb_tpu.handoff.EdgeHandoff) under the root ``handoff``
        config key: a mesh-resident stage (R2P1DMeshRunner) declares
        its mesh placement here so the inter-stage edge re-homes
        payloads as ONE on-device resharding — ICI on real hardware,
        with the remote-DMA fast path when the move matches the ring
        pattern (rnb_tpu.ops.handoff_dma) — instead of the stage
        re-placing them inside its dispatch path."""
        return None

    @staticmethod
    def output_shape() -> Optional[Tuple[Tuple[int, ...], ...]]:
        return None

    @classmethod
    def input_shape_for(cls, **model_kwargs) -> Optional[
            Tuple[Tuple[int, ...], ...]]:
        """Config-aware *expected input* max shapes, or None when the
        stage takes no tensor inputs (first-stage loaders) or accepts
        anything. The static counterpart of ``input_shape()`` —
        derivable from the step's JSON kwargs alone, so the pipeline
        checker can match it against the upstream step's declared
        output shapes without constructing the stage."""
        del model_kwargs
        return None

    @classmethod
    def input_dtype_for(cls, **model_kwargs) -> Optional[str]:
        """Expected input dtype name ("uint8", "bfloat16", "float32"),
        or None when any dtype is acceptable / unknown."""
        del model_kwargs
        return None

    @classmethod
    def output_dtype_for(cls, **model_kwargs) -> Optional[str]:
        """Produced output dtype name, or None when unknown (e.g. a
        pass-through stage that emits whatever it receives)."""
        del model_kwargs
        return None

    @classmethod
    def output_shape_for(cls, **model_kwargs) -> Optional[
            Tuple[Tuple[int, ...], ...]]:
        """Config-aware output shapes; defaults to ``output_shape()``.

        Overrides must accept (and ignore) arbitrary kwargs — the
        runtime passes the step's full model-kwargs dict.
        """
        del model_kwargs
        return cls.output_shape()

    def __call__(self, tensors, non_tensors, time_card):
        raise NotImplementedError
