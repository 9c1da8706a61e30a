"""Request-scoped software tracing: per-request event timelines.

Every request travelling through the pipeline carries a TimeCard; each
stage stamps named events on it (``runner{i}_start``, ``inference{i}_start``,
``inference{i}_finish``) together with a trail of the devices it visited.
Segment-parallel execution forks a card per segment and the aggregation
stage merges the siblings back into one card whose post-fork events carry
``-{sub_id}`` suffixes.

Capability parity with the reference's rnb_logging.py (TimeCard
rnb_logging.py:22-123, TimeCardList :126-142, TimeCardSummary :145-214,
log path helpers :6-19), re-designed for the TPU runtime: device trails
are arbitrary string labels ("tpu:3", "cpu:0", "host") instead of GPU
integers, and log filenames use a device-label scheme.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict, namedtuple
from typing import (IO, Callable, Iterable, List, NamedTuple, Optional,
                    Sequence)


def logroot(job_id: str, base: str = "logs") -> str:
    """Directory holding every artifact of one benchmark job."""
    path = os.path.join(base, str(job_id))
    os.makedirs(path, exist_ok=True)
    return path


def logmeta(job_id: str, base: str = "logs") -> str:
    """Path of the job metadata file (args, wall time, termination code)."""
    return os.path.join(logroot(job_id, base), "log-meta.txt")


def logname(job_id: str, device_label: str, group_idx: int, instance_idx: int,
            base: str = "logs") -> str:
    """Path of the per-final-instance timing table.

    Mirrors the reference's ``g{gpu}-group{group}-{instance}.txt`` scheme
    (rnb_logging.py:17-19) with a device label usable for TPU cores.
    """
    safe = str(device_label).replace(":", "").replace("/", "-")
    return os.path.join(
        logroot(job_id, base),
        "%s-group%d-%d.txt" % (safe, group_idx, instance_idx))


def latency_percentiles(latencies_ms: Sequence[float],
                        percentiles=(50.0, 99.0)):
    """{percentile: value_ms} over a latency sample; {} when empty.

    The one percentile convention shared by per-instance summaries and
    the controller's cross-instance aggregation (rnb_tpu.benchmark).
    """
    import numpy as np
    if not latencies_ms:
        return {}
    return {p: float(np.percentile(latencies_ms, p)) for p in percentiles}


#: per-request content stamps set by the loader that must survive
#: fork/merge: clip count (routing, MFU accounting) and the cache
#: outcome (rnb_tpu.cache: True=hit, False=miss; cache_coalesced marks
#: a request that shared another request's in-flight decode)
CONTENT_STAMPS = ("num_clips", "cache_hit", "cache_coalesced",
                  # True when the request was answered from feature
                  # pages (rnb_tpu.pager): the stage forward never ran,
                  # so MFU accounting counts its rows 0 — the honesty
                  # policy twin of cache_coalesced. (The feature_plan /
                  # feature_insert carriers live in TRANSIENT_STAMPS
                  # below instead: they hold live page pins / insert
                  # obligations a fork would double-own.)
                  "feature_hit",
                  # pad rows the emission carrying this request shipped
                  # (attributed to the emission's first constituent so
                  # sums stay exact; 0 on every other card and on every
                  # ragged emission — the ragged kernel computes no pad
                  # rows)
                  "pad_rows",
                  # absolute wall-clock deadline (rnb_tpu.health,
                  # root 'deadline' config key): stamped by the client
                  # at enqueue; every stage boundary sheds the request
                  # once it passes — absent on deadline-off runs
                  "deadline_s",
                  # times this request was drained off an evicted
                  # replica lane and re-enqueued onto a healthy
                  # sibling (rnb_tpu.health lane eviction)
                  "redispatched",
                  # True on the CLONE card of a hedged re-dispatch
                  # (rnb_tpu.health.HedgeGovernor) — the claim site
                  # reads it to attribute the win to the hedge or the
                  # original copy
                  "hedge_copy",
                  # set once a copy claimed WINNER: later disposal of
                  # the SAME copy must not claim again (it owns the
                  # rid's terminal outcome; a re-claim would consume
                  # the sibling copy's LOSER slot)
                  "hedge_resolved",
                  # valid tokens of a request whose rows are blocks of
                  # tokens (rnb_tpu.models.token_stages): the fuse and
                  # model_call spans sum it over a packed dispatch
                  "num_tokens")

#: card-riding carriers that are DELIBERATELY not content stamps: they
#: must NOT survive fork/merge. Each holds single-owner live state —
#: copying it onto a hedge clone would double-own it. The schema
#: checker (RNB-T007) accepts stamp sites for these names but the
#: fork/merge copy loop above never touches them; both plans are
#: released idempotently by the loader's failure/shed sweeps so a
#: dropped card cannot strand a page pin.
TRANSIENT_STAMPS = (
    # rnb_tpu.pager.GatherPlan for a feature-page hit: pins live pages
    # until the runner's logit gather releases them — exactly-once
    # consumption, popped (set back to None) by the consuming stage
    "feature_plan",
    # (content_key, row_start, rows) insert obligation: must fire
    # exactly once AFTER the forward succeeds; surviving a fork would
    # double-insert the same rows
    "feature_insert")


# -- the declared telemetry schema ------------------------------------
#
# PRs 1-2 each extended the TimeCard/report schema by hand in three
# places (stamp sites, scripts/parse_utils.py, README) — exactly the
# silent drift a stamp registry exists to stop. Every timing-stamp
# pattern, log-meta line and report trailer the tree may write is
# DECLARED here; the static schema checker
# (rnb_tpu.analysis.schema, gated in tier-1) cross-checks these
# declarations against the actual stamp/write sites AND against what
# scripts/parse_utils.py parses, so a stamp can neither appear
# unregistered nor silently vanish from reports.
# ``python scripts/parse_utils.py --stamps`` prints the generated
# reference.

#: one declared telemetry element: ``pattern`` uses ``{step}`` for the
#: pipeline-step index (stamp sites format it with ``%d``); merged
#: segment cards additionally suffix post-fork stamps with
#: ``-{sub_id}`` (TimeCard.merge)
StampSpec = namedtuple("StampSpec", ("pattern", "producer", "description"))

#: every TimeCard timing-stamp pattern any code path may record
STAMP_REGISTRY = (
    StampSpec("enqueue_filename", "rnb_tpu/client.py",
              "client created the request and enqueued its video path"),
    StampSpec("runner{step}_start", "rnb_tpu/runner.py",
              "stage executor popped the request off its input queue"),
    StampSpec("inference{step}_start", "rnb_tpu/runner.py",
              "model call (or prefetched-decode completion) began"),
    StampSpec("inference{step}_finish", "rnb_tpu/runner.py",
              "stage output ready (device-synced unless async_dispatch)"),
    # -- phase-refinement stamps (rnb_tpu.trace): recorded for every
    # request a loader serves under an executor (PR 24; before, only
    # under the `trace` config key). They split the loader's
    # inference{step} span into decode/hold/transfer/drain for
    # per-request attribution (parse_utils --attribute, the
    # benchmark's phase_*_ms metrics).
    StampSpec("decode{step}_done", "rnb_tpu/models/r2p1d/model.py",
              "this request's clip decode completed (a cache hit "
              "records a zero-length decode phase)"),
    StampSpec("transfer{step}_start", "rnb_tpu/models/r2p1d/model.py",
              "the emission holding this request closed and its "
              "host->device transfer began"),
    StampSpec("transfer{step}_done", "rnb_tpu/models/r2p1d/model.py",
              "host->device transfer dispatched/confirmed; the gap to "
              "inference{step}_finish is publish drain"),
)

#: every ``<Prefix>:``-keyed line rnb_tpu/benchmark.py may write into
#: ``logs/<job>/log-meta.txt`` (plus one bare ``<start> <end>``
#: timestamp line carrying no prefix)
META_LINE_REGISTRY = (
    StampSpec("Args:", "rnb_tpu/benchmark.py",
              "argparse-style repr of the launch arguments"),
    StampSpec("Termination flag:", "rnb_tpu/benchmark.py",
              "job termination reason code (TerminationFlag)"),
    StampSpec("Faults:", "rnb_tpu/benchmark.py",
              "job-wide num_failed/num_shed/num_retries counters"),
    StampSpec("Failure reasons:", "rnb_tpu/benchmark.py",
              "JSON per-reason contained-failure counts"),
    StampSpec("Shed sites:", "rnb_tpu/benchmark.py",
              "JSON per-site shed counts"),
    StampSpec("Queue overflows:", "rnb_tpu/benchmark.py",
              "JSON per-edge abort-policy queue-overflow counts"),
    StampSpec("Cache:", "rnb_tpu/benchmark.py",
              "clip-cache counters (cache-enabled runs only)"),
    StampSpec("Staging:", "rnb_tpu/benchmark.py",
              "zero-copy decode-staging pool counters "
              "(staging-enabled runs only)"),
    StampSpec("Pages:", "rnb_tpu/benchmark.py",
              "paged device-memory counters (rnb_tpu.pager): arena/"
              "page occupancy (live/limbo/bytes), page allocs/frees/"
              "alloc_fails, gather dispatches + rows split clip vs "
              "feature plane, feature-cache lookups/hits/inserts/"
              "evictions/bytes_saved, and emissions that shipped "
              "zero host->device bytes (pager-enabled runs only; "
              "--check holds allocs == frees + live at teardown, "
              "feature_hits <= feature_lookups, and gather_rows <= "
              "the ragged cache_hit_rows they serve)"),
    StampSpec("Autotune:", "rnb_tpu/benchmark.py",
              "load-adaptive batching controller counters "
              "(autotune-enabled runs only)"),
    StampSpec("Autotune buckets:", "rnb_tpu/benchmark.py",
              "JSON per-chosen-bucket emission counts "
              "(autotune-enabled runs only)"),
    StampSpec("Ragged:", "rnb_tpu/benchmark.py",
              "ragged row-pool dispatch counters: pool capacity, "
              "emissions, valid rows, pad rows the bucketed rule "
              "would have shipped (ragged-enabled runs only)"),
    StampSpec("Shard:", "rnb_tpu/benchmark.py",
              "intra-stage shard counters: declared-degree steps, max "
              "degree, logits-path merge gathers, their summed "
              "host-timed microseconds, valid rows crossing sharded "
              "stages (declared-shard runs only; --check holds "
              "degree x replicas <= the device budget and "
              "collective_us <= the inference span sum)"),
    StampSpec("Shard steps:", "rnb_tpu/benchmark.py",
              "JSON per-step shard detail: degree/axis, merge-gather "
              "counters, projected vs budget per-device MiB, min "
              "feasible degree (declared-shard runs only)"),
    StampSpec("Padding:", "rnb_tpu/benchmark.py",
              "bucketed-path padding waste: pad rows / total shipped "
              "rows / emissions summed over batching stages"),
    # -- a stage's own counters: STAGE_COUNTERS (below) says which
    # counter stands on which of these four, under which keys
    StampSpec("Tokens:", "rnb_tpu/telemetry.py",
              "token accounting of stages whose rows are blocks of "
              "tokens (such stages only)"),
    StampSpec("Experts:", "rnb_tpu/telemetry.py",
              "sparse-expert accounting of a stage holding a share of "
              "each layer's experts (such stages only)"),
    StampSpec("Sparse:", "rnb_tpu/telemetry.py",
              "sparse attention accounting of a stage whose stack "
              "chooses key blocks or, by a learned indexer, keys (such "
              "stages only)"),
    StampSpec("Attention:", "rnb_tpu/telemetry.py",
              "packed flash attention accounting of a stage whose "
              "stack runs it (such stages only)"),
    StampSpec("Compiles:", "rnb_tpu/benchmark.py",
              "JSON per-step jit-entry signature counts "
              "{step: {warmup, steady_new, steady_calls}} — "
              "steady_new > 0 means a mid-run recompile"),
    StampSpec("Warmup:", "rnb_tpu/benchmark.py",
              "JSON per-step stage-construction wall seconds "
              "(weights + warmup compiles): the setup.s{step}."
              "construct spans' durations"),
    StampSpec("Setup:", "rnb_tpu/benchmark.py",
              "JSON seconds by phase from run_benchmark's first line "
              "to the start barrier's release, along the stage "
              "instance whose constructor ended last "
              "(rnb_tpu.trace.setup_account)"),
    StampSpec("Handoff:", "rnb_tpu/benchmark.py",
              "device-resident handoff counters: edge takes split "
              "d2d vs host with bytes each class moved "
              "(handoff-enabled runs only; d2d+host == edges, "
              "host_bytes == 0 on device-resident edges)"),
    StampSpec("Handoff edges:", "rnb_tpu/benchmark.py",
              "JSON per-edge-label handoff counters "
              "(handoff-enabled runs only)"),
    StampSpec("Placement:", "rnb_tpu/benchmark.py",
              "JSON measured-cost placement report: per-step dispatch "
              "costs, predicted occupancy, recommended replica plan "
              "(placement-enabled runs only; --check holds the "
              "prediction to the traced busy fraction)"),
    StampSpec("Health:", "rnb_tpu/benchmark.py",
              "lane health/circuit-breaker counters: lanes, state "
              "transitions, circuit opens, evictions, half-open "
              "probes, redispatched items, routes to open lanes "
              "(health-enabled replica runs only; --check holds "
              "routes_after_open to 0 and replays every lane's "
              "transition path against the legal automaton)"),
    StampSpec("Health lanes:", "rnb_tpu/benchmark.py",
              "JSON per-lane health detail: final state, transition "
              "path, redispatched-from count "
              "(health-enabled replica runs only)"),
    StampSpec("Deadline:", "rnb_tpu/benchmark.py",
              "deadline-propagation counters: configured budget_ms "
              "and requests shed as deadline_expired "
              "(deadline-enabled runs only; per-site sheds must sum "
              "to the total)"),
    StampSpec("Deadline sites:", "rnb_tpu/benchmark.py",
              "JSON per-check-site deadline_expired shed counts "
              "(deadline-enabled runs only)"),
    StampSpec("Hedge:", "rnb_tpu/benchmark.py",
              "hedged re-dispatch counters: hedges fired, won by the "
              "hedge copy, lost (original resolved first), and the "
              "losers' wasted service milliseconds (hedge_ms runs "
              "only; won + lost == fired always — hedge compute is "
              "overhead, never throughput)"),
    StampSpec("Trace:", "rnb_tpu/benchmark.py",
              "trace-export counters: events written to trace.json, "
              "events dropped at the max_events cap "
              "(trace-enabled runs only)"),
    StampSpec("Phases:", "rnb_tpu/benchmark.py",
              "JSON per-phase latency attribution "
              "{phase: {mean_ms, p99_ms, count}} over steady-state "
              "completions (trace-enabled runs only)"),
    StampSpec("Locks:", "rnb_tpu/benchmark.py",
              "lock-order witness ledger (rnb_tpu.lockwitness, root "
              "`lint.lock_witness` config key): witnessed locks, "
              "total acquisitions, distinct acquisition-order edges, "
              "discipline violations (order inversions + non-LIFO "
              "releases + require() failures) — witness-enabled runs "
              "only; --check holds violations to zero and the "
              "Lock edges: detail to edges/violations counts"),
    StampSpec("Lock edges:", "rnb_tpu/benchmark.py",
              "JSON detail for the Locks: line: the observed "
              "acquisition-order edges and any violation records; "
              "--check holds every observed edge to the static "
              "RNB-C lock-order graph (observed subset-of declared, "
              "so a runtime order the analyzer never blessed fails "
              "offline)"),
)

#: every ``# <kind> ...`` trailer a per-instance timing table may carry
#: (TimeCardSummary.save_full_report)
TABLE_TRAILER_REGISTRY = (
    StampSpec("faults", "rnb_tpu/telemetry.py",
              "per-instance failed/shed/retry counts + reasons"),
    StampSpec("cache", "rnb_tpu/telemetry.py",
              "per-instance completed-request cache attribution"),
    StampSpec("phases", "rnb_tpu/telemetry.py",
              "per-instance per-phase latency attribution "
              "(mean/p99 microseconds; trace-enabled runs only)"),
    StampSpec("padding", "rnb_tpu/telemetry.py",
              "per-instance pad rows shipped with completed requests "
              "(0 under ragged dispatch)"),
)


#: every span/instant/counter name the tracing layer (rnb_tpu.trace)
#: may emit into logs/<job>/trace.json — ``{step}`` stands for the
#: pipeline-step or queue index, formatted at the ``trace.name`` call
#: site. The static schema checker (rnb_tpu.analysis.schema,
#: RNB-T008) cross-checks these declarations against the actual
#: instrumentation sites, so a trace event can neither appear
#: unregistered nor linger registered after its site is deleted.
TRACE_EVENT_REGISTRY = (
    StampSpec("client.enqueue", "rnb_tpu/client.py",
              "instant: client created + enqueued one request (flow "
              "anchor for the request id)"),
    StampSpec("client.enqueued", "rnb_tpu/client.py",
              "counter: cumulative requests the client has emitted"),
    StampSpec("client.shed", "rnb_tpu/client.py",
              "instant: client dropped a request at the full filename "
              "queue (overload_policy shed)"),
    StampSpec("exec{step}.queue_get", "rnb_tpu/runner.py",
              "span: executor blocked on its input queue (starvation)"),
    StampSpec("exec{step}.hold_wait", "rnb_tpu/runner.py",
              "span: executor blocked while its stage holds work "
              "(batch-fill wait, not starvation)"),
    StampSpec("exec{step}.swallow", "rnb_tpu/runner.py",
              "instant: one request admitted into the stage"),
    StampSpec("exec{step}.model_call", "rnb_tpu/runner.py",
              "span: the stage model call for one dispatch (a batched "
              "dispatch carries rows = rows shipped, rows_valid, "
              "device = the device's id; a packed one also segments "
              "and tokens_valid)"),
    StampSpec("exec{step}.device_sync", "rnb_tpu/runner.py",
              "span: blocking on device output readiness "
              "(sync_outputs)"),
    StampSpec("exec{step}.finish", "rnb_tpu/runner.py",
              "span: the executor's own work on a finished dispatch, "
              "from the end of device_sync to the start of publish: "
              "finish stamp, meters, routing; on the final step also "
              "the completion bookkeeping (a second span)"),
    StampSpec("exec{step}.publish", "rnb_tpu/runner.py",
              "span: route + ring write + downstream enqueue"),
    StampSpec("exec{step}.handoff", "rnb_tpu/runner.py",
              "span: the edge contract's payload take — adopt or "
              "reshard the committed upstream arrays onto this "
              "consumer (handoff-enabled runs only)"),
    StampSpec("exec{step}.redispatch", "rnb_tpu/runner.py",
              "span: an evicted replica lane's executor re-enqueues "
              "one queued-but-undispatched item onto a healthy "
              "sibling lane (health-enabled chaos runs only)"),
    StampSpec("exec{step}.collective", "rnb_tpu/models/r2p1d/model.py",
              "span: the sharded stage's cross-shard logits merge "
              "gather, host-timed around the separate merge jit "
              "(declared shard_degree > 1 only; nested inside the "
              "step's model_call span — the collective tax, never "
              "extra wall)"),
    StampSpec("health.lane_state", "rnb_tpu/health.py",
              "instant: a replica lane's health state transition "
              "(args: lane, from, to, why) — the timeline face of "
              "the Health lanes: path log"),
    StampSpec("loader.decode_submit", "rnb_tpu/models/r2p1d/model.py",
              "instant: one request's decode submitted to the pool"),
    StampSpec("loader.decode", "rnb_tpu/models/r2p1d/model.py",
              "span: fallback-pool decode body (rnb-decode threads; "
              "native-pool decodes run in C++ and are delimited by "
              "the submit/ready instants instead)"),
    StampSpec("loader.decode_ready", "rnb_tpu/models/r2p1d/model.py",
              "instant: one request's decode observed complete"),
    StampSpec("loader.emit", "rnb_tpu/models/r2p1d/model.py",
              "span: fused-batch take/assemble/handoff (reason = the "
              "rule that fired: full, hold, idle, or drain for a "
              "forced emission; rows and bucket of the take; left = "
              "ready rows it left behind)"),
    StampSpec("loader.emit_deferred", "rnb_tpu/models/r2p1d/model.py",
              "instant: a latency rule (hold expired, nothing in "
              "flight) was held back because the output ring is full "
              "— once per batch, the first time"),
    StampSpec("loader.emit_wait", "rnb_tpu/models/r2p1d/model.py",
              "span: the emission blocked on decodes of its take that "
              "were not done yet (inside loader.emit)"),
    StampSpec("loader.transfer", "rnb_tpu/models/r2p1d/model.py",
              "span: the host->device device_put of one batch — "
              "executor thread or transfer worker"),
    StampSpec("loader.s{step}.inflight", "rnb_tpu/models/r2p1d/model.py",
              "counter (sampled): decodes in flight + decoded-but-"
              "unemitted requests held by the loader"),
    StampSpec("staging.s{step}.free", "rnb_tpu/models/r2p1d/model.py",
              "counter (sampled): free staging slots in the loader's "
              "pool"),
    StampSpec("staging.acquire_wait", "rnb_tpu/staging.py",
              "span: blocked acquiring a staging slot (exhaustion "
              "backpressure)"),
    StampSpec("transfer.job", "rnb_tpu/staging.py",
              "span: one queued job on the transfer worker thread"),
    StampSpec("batcher.fuse", "rnb_tpu/batcher.py",
              "span: concatenating the pending requests' valid rows "
              "into one batch (rows, segments = requests; tokens_valid "
              "where the cards carry num_tokens)"),
    StampSpec("tokens.read", "rnb_tpu/models/token_stages.py",
              "span: reading one request's prompt file"),
    StampSpec("tokens.pack", "rnb_tpu/models/token_stages.py",
              "span: one prompt into its rows of tokens (rows, "
              "tokens_valid, segments = 1)"),
    StampSpec("batcher.emit", "rnb_tpu/batcher.py",
              "instant: the Batcher fused + emitted one batch "
              "(args: requests, rows)"),
    StampSpec("autotune.decision", "rnb_tpu/autotune.py",
              "instant: one BatchController decision (args: verdict, "
              "target_rows, hold_ms)"),
    StampSpec("compile.steady", "rnb_tpu/compilestats.py",
              "instant: a jitted applier met an entry signature its "
              "warm-up never saw — a compilation inside the run "
              "(signature = the shapes and dtypes), on the thread "
              "that dispatched it"),
    StampSpec("queue.filename.depth", "rnb_tpu/benchmark.py",
              "counter (sampled): client filename queue depth"),
    StampSpec("queue.e{step}.depth", "rnb_tpu/benchmark.py",
              "counter (sampled): inter-stage queue depth, keyed by "
              "queue index"),
    # -- set-up, from the process's start to the start barrier: kept by
    # a Tracer the launcher holds until the barrier whatever the
    # `trace` key says (BenchmarkResult.setup, setup-trace.json, the
    # Setup: line), on time.time(); each names the benchmark metric
    # that reads it (benchmarks/setup_account.py)
    StampSpec("setup.entered", "rnb_tpu/benchmark.py",
              "instant: enable_compilation_cache()'s first call, once "
              "JAX and the accelerator runtime are up; setup_runtime_s "
              "ends and setup_inputs_s starts here"),
    StampSpec("setup.run", "rnb_tpu/benchmark.py",
              "span: run_benchmark's first line to the start barrier's "
              "release; setup_inputs_s ends where it opens, "
              "setup_unnamed_s is what of it no span below names"),
    StampSpec("setup.launch", "rnb_tpu/benchmark.py",
              "span: run_benchmark's first line to the last runner "
              "thread's start (configuration, queues, rings); "
              "setup_unnamed_s"),
    StampSpec("setup.s{step}.construct", "rnb_tpu/runner.py",
              "span: one stage instance's constructor whole (instance, "
              "device), on its runner thread: the Warmup: line's "
              "seconds; the one that ends last is the instance every "
              "setup_*_s metric follows, its self time setup_unnamed_s"),
    StampSpec("setup.s{step}.weights", "rnb_tpu/models/token_stages.py",
              "span: recipe or checkpoint to parameters handed to the "
              "device; the draw's device work runs on behind it and "
              "the first call's span carries that; self time "
              "setup_weights_s"),
    StampSpec("setup.s{step}.program", "rnb_tpu/models/token_stages.py",
              "span: one row bucket's program on the constructor's "
              "thread (rows): in a token stage its tracing and lowering "
              "and the hand to the worker, in R(2+1)D's from nothing to "
              "warmed; self time setup_unnamed_s"),
    StampSpec("setup.s{step}.load", "rnb_tpu/models/token_stages.py",
              "span: a token stage's worker thread (prefill-load) on "
              "one lowered program (rows): the compiler or the cache's "
              "read and the executable's load, its scope table, its "
              "first calls, while the constructor lowers the next "
              "bucket; the sum less load_wait's ran behind a lowering; "
              "no metric reads another thread than the constructor's"),
    StampSpec("setup.s{step}.load_wait", "rnb_tpu/models/token_stages.py",
              "span: the token stage's constructor waits for its worker "
              "to end, behind the last bucket's lowering: the tail of "
              "the pipeline; self time setup_unnamed_s"),
    StampSpec("setup.s{step}.scopes", "rnb_tpu/models/token_stages.py",
              "span: the executable's text and its scope table, inside "
              "the bucket's load span (R(2+1)D: program span); self "
              "time setup_unnamed_s where the constructor's thread runs "
              "it"),
    StampSpec("setup.s{step}.first_call", "rnb_tpu/models/token_stages.py",
              "span: the bucket's warm-up calls to block_until_ready, "
              "in a token stage on the worker inside the load span; "
              "self time setup_first_call_s where the constructor's "
              "thread runs it"),
    StampSpec("setup.jax.trace", "rnb_tpu/benchmark.py",
              "span: JAX's /jax/core/compile/jaxpr_trace_duration "
              "(fun_name), on the tracing thread; setup_lower_s"),
    StampSpec("setup.jax.lower", "rnb_tpu/benchmark.py",
              "span: JAX's /jax/core/compile/"
              "jaxpr_to_mlir_module_duration (fun_name); setup_lower_s"),
    StampSpec("setup.jax.compile", "rnb_tpu/benchmark.py",
              "span: JAX's /jax/core/compile/backend_compile_duration "
              "(fun_name; cache_hit 1 where the persistent cache "
              "answered, retrieval_s its read): the cache key, then "
              "the cache's read and the executable's load or the "
              "compiler; setup_compile_s, and setup_compiled_programs "
              "counts those with cache_hit 0"),
)


class TimeCard:
    """An ordered event->timestamp record that rides along with a request.

    Reference behavior: rnb_logging.py:22-123. Supports single-level
    fork (one child per parallel segment) and merge (recombine siblings:
    pre-fork events kept once, post-fork events suffixed ``-{sub_id}``,
    device trails merged positionally).
    """

    def __init__(self, id: int):
        self.timings: "OrderedDict[str, float]" = OrderedDict()
        self.id = id
        self.sub_id: Optional[int] = None
        self.num_parent_timings: Optional[int] = None
        # One entry per pipeline step traversed; each entry is a tuple of
        # device labels (singleton until a merge combines segments that ran
        # on different devices).
        self.devices: List[tuple] = []
        # request outcome: "ok" until the containment layer stamps the
        # card "failed" (dead-lettered) or "shed" (dropped under the
        # "shed" overload policy) — rnb_tpu.runner / rnb_tpu.client
        self.status: str = "ok"
        self.failure_reason: Optional[str] = None

    def mark_failed(self, reason: str) -> None:
        """Stamp this request permanently failed (dead-letter path)."""
        self.status = "failed"
        self.failure_reason = str(reason)

    def mark_shed(self, site: str) -> None:
        """Stamp this request dropped by the overload policy."""
        self.status = "shed"
        self.failure_reason = str(site)

    def record(self, key: str, at: Optional[float] = None) -> None:
        """Stamp event ``key`` with the current wall-clock time (or a
        caller-supplied instant, for events shared across cards)."""
        self.timings[key] = time.time() if at is None else at

    def add_device(self, device_label: str) -> None:
        """Append a pipeline-step device visit to the trail."""
        self.devices.append((device_label,))

    def fork(self, sub_id: int) -> "TimeCard":
        """Clone this card for one parallel segment.

        The clone keeps the same id and a copy of all timings; the fork
        point is remembered so merge() knows which events are shared.
        Two-level forking is rejected — merge before forking again
        (reference invariant, rnb_logging.py:56-62).
        """
        if self.sub_id is not None:
            raise RuntimeError(
                "cannot fork TimeCard(id=%s) twice: it is already a fork "
                "with sub_id=%s; merge first" % (self.id, self.sub_id))
        child = TimeCard(self.id)
        child.timings = OrderedDict(self.timings)
        child.sub_id = sub_id
        child.num_parent_timings = len(self.timings)
        for attr in CONTENT_STAMPS:
            # content stamps (loader's num_clips / cache outcome) ride
            # along with every segment so routing, clip accounting and
            # cache attribution survive the fork
            if hasattr(self, attr):
                setattr(child, attr, getattr(self, attr))
        child.devices = list(self.devices)
        child.status = self.status
        child.failure_reason = self.failure_reason
        return child

    @staticmethod
    def merge(time_cards: Sequence["TimeCard"]) -> "TimeCard":
        """Recombine sibling forks into one card.

        All inputs must share id-independent structure: identical timing
        keys and identical fork points. Events recorded before the fork
        are emitted once; events after the fork are emitted per sibling
        with a ``-{sub_id}`` suffix, ordered by sub_id. Device trails are
        zipped positionally: a step where every sibling used the same
        device collapses to a singleton, otherwise the full tuple is kept
        (reference behavior, rnb_logging.py:72-123).
        """
        if not time_cards:
            raise ValueError("merge() needs at least one TimeCard")
        first = time_cards[0]
        keys = list(first.timings.keys())
        fork_point = first.num_parent_timings
        seen_sub_ids = set()
        for tc in time_cards:
            if tc.sub_id is None:
                raise RuntimeError(
                    "cannot merge TimeCard(id=%s): not a fork (sub_id is "
                    "None); only sibling forks can be merged" % tc.id)
            if tc.sub_id in seen_sub_ids:
                raise RuntimeError(
                    "cannot merge TimeCards with duplicate sub_id=%s"
                    % tc.sub_id)
            seen_sub_ids.add(tc.sub_id)
        for tc in time_cards[1:]:
            if list(tc.timings.keys()) != keys:
                raise RuntimeError(
                    "cannot merge TimeCards with different timing keys: "
                    "%s != %s" % (keys, list(tc.timings.keys())))
            if tc.num_parent_timings != fork_point:
                raise RuntimeError(
                    "cannot merge TimeCards forked at different points: "
                    "%s != %s" % (fork_point, tc.num_parent_timings))
        ordered = sorted(time_cards, key=lambda tc: tc.sub_id)

        merged = TimeCard(first.id)
        for key_idx, key in enumerate(keys):
            if fork_point is not None and key_idx < fork_point:
                merged.timings[key] = ordered[0].timings[key]
            else:
                for tc in ordered:
                    merged.timings["%s-%s" % (key, tc.sub_id)] = tc.timings[key]

        for step_devices in zip(*[tc.devices for tc in ordered]):
            flat = tuple(d for tpl in step_devices for d in tpl)
            if len(set(flat)) == 1:
                merged.devices.append((flat[0],))
            else:
                merged.devices.append(flat)
        for attr in CONTENT_STAMPS:
            # content stamps are per-request, identical on every
            # sibling fork — keep them once
            if hasattr(ordered[0], attr):
                setattr(merged, attr, getattr(ordered[0], attr))
        for tc in ordered:
            # one failed segment fails the merged request
            if tc.status != "ok":
                merged.status = tc.status
                merged.failure_reason = tc.failure_reason
                break
        return merged


class TimeCardList:
    """Broadcast wrapper over the cards of a dynamically-batched request.

    Produced by the Batcher stage so that one fused inference still stamps
    events on every constituent request's card (reference
    rnb_logging.py:126-142). Forking a batched card is not meaningful.
    """

    def __init__(self, time_cards: List[TimeCard]):
        self.time_cards = time_cards

    def record(self, key: str, at: Optional[float] = None) -> None:
        # one event, one instant: every constituent of a fused batch
        # gets the SAME stamp (per-card time.time() calls would drift
        # by microseconds, breaking offline dispatch-grouping — one
        # fused jit call IS one event for all its constituents)
        at = time.time() if at is None else at
        for tc in self.time_cards:
            tc.record(key, at=at)

    def add_device(self, device_label: str) -> None:
        for tc in self.time_cards:
            tc.add_device(device_label)

    def fork(self, sub_id: int) -> "TimeCard":
        raise NotImplementedError("TimeCardLists cannot be forked")

    def __len__(self) -> int:
        return len(self.time_cards)


class TimeCardSummary:
    """Columnar accumulator over completed requests' TimeCards.

    Assumes every registered card carries the identical event-key sequence
    (true per final-step instance because the pipeline topology is fixed);
    prints mean inter-event gaps and persists a whitespace table with one
    row per request plus per-step device columns (split per segment when a
    step ran on several devices). Reference: rnb_logging.py:145-214.
    """

    def __init__(self):
        self.summary: "OrderedDict[str, List[float]]" = OrderedDict()
        self.keys: List[str] = []
        self.devices_per_inference: List[List[tuple]] = []
        # per-record clip counts (0 when the pipeline never stamped
        # num_clips) — feeds clips/sec and MFU accounting
        self.clip_counts: List[int] = []
        # fault accounting (rnb_tpu.runner containment): failed/shed
        # requests never enter the columnar timing data, so latency
        # percentiles stay success-only; the counters keep the summary
        # honest about what the instance dropped along the way.
        # num_shed is part of the schema for symmetry with the
        # controller's FaultStats but is structurally 0 in current
        # topologies: sheds happen at the client and at producing
        # (non-final) stages, while a summary exists only on final-step
        # instances — job-level shed counts live in FaultStats/log-meta
        self.num_failed: int = 0
        self.num_shed: int = 0
        self.num_retries: int = 0
        self.failure_reasons: "OrderedDict[str, int]" = OrderedDict()
        # decoded-clip cache attribution (rnb_tpu.cache): registered
        # completions whose card carries a cache_hit stamp. tracked=0
        # means the pipeline ran cacheless and the report stays
        # byte-stable with the pre-cache schema.
        self.num_cache_hits: int = 0
        self.num_cache_coalesced: int = 0
        self.num_cache_tracked: int = 0
        # padding-waste attribution: pad rows the emissions carrying
        # the registered completions shipped (stamped on each
        # emission's first constituent card by the batching stages;
        # tracked=0 keeps pre-padding-era reports byte-stable)
        self.num_pad_rows: int = 0
        self.num_pad_tracked: int = 0
        # per-request phase attribution (rnb_tpu.trace): surfaced as a
        # `# phases` trailer + the job-wide `Phases:` line ONLY when
        # the executor opts this summary in (trace-enabled runs) —
        # trace-off reports stay byte-stable with the earlier schema
        self.track_phases: bool = False
        self.phase_num_skips: int = 0

    def note_failure(self, reason: str, n: int = 1) -> None:
        """Count a contained permanent failure (excluded from timings)."""
        self.num_failed += n
        self.failure_reasons[reason] = \
            self.failure_reasons.get(reason, 0) + n

    def note_shed(self, n: int = 1) -> None:
        self.num_shed += n

    def note_retries(self, n: int = 1) -> None:
        self.num_retries += n

    def register(self, time_card: TimeCard) -> None:
        if not self.summary:
            self.keys = list(time_card.timings.keys())
            for key in self.keys:
                self.summary[key] = []
        if self.keys != list(time_card.timings.keys()):
            raise AssertionError(
                "TimeCard key sequence changed mid-run: %s != %s"
                % (self.keys, list(time_card.timings.keys())))
        for key, ts in time_card.timings.items():
            self.summary[key].append(ts)
        self.devices_per_inference.append(time_card.devices)
        # clip_counts feeds clips/sec and MFU — DEVICE-WORK accounting.
        # A coalesced follower's rows were computed once, on the
        # leader's card; counting them again would inflate the device
        # utilization the honesty policy protects, so followers
        # contribute 0 here (their num_clips stamp remains on the card
        # for routing/request-level analysis).
        coalesced = getattr(time_card, "cache_coalesced", False)
        self.clip_counts.append(
            0 if coalesced else int(getattr(time_card, "num_clips", 0)))
        hit = getattr(time_card, "cache_hit", None)
        if hit is not None:
            self.num_cache_tracked += 1
            if hit:
                self.num_cache_hits += 1
        if getattr(time_card, "cache_coalesced", False):
            self.num_cache_coalesced += 1
        pad = getattr(time_card, "pad_rows", None)
        if pad is not None:
            self.num_pad_tracked += 1
            self.num_pad_rows += int(pad)

    def total_clips(self) -> int:
        """Sum of registered records' ``num_clips`` stamps."""
        return sum(self.clip_counts)

    def num_records(self) -> int:
        return len(self.summary[self.keys[0]]) if self.keys else 0

    def mean_gaps_ms(self, num_skips: int = 0):
        """[(prev_key, next_key, mean_ms)] over records after `num_skips`."""
        import numpy as np
        out = []
        for prv, nxt in zip(self.keys[:-1], self.keys[1:]):
            if len(self.summary[prv]) <= num_skips:
                return out
            gap = np.mean(
                (np.asarray(self.summary[nxt][num_skips:])
                 - np.asarray(self.summary[prv][num_skips:])) * 1000.0)
            out.append((prv, nxt, float(gap)))
        return out

    def latencies_ms(self, num_skips: int = 0):
        """Per-record end-to-end latency (first event -> last event) in
        ms over records after ``num_skips``."""
        import numpy as np
        if not self.keys or len(self.keys) < 2:
            return []
        first = np.asarray(self.summary[self.keys[0]][num_skips:])
        last = np.asarray(self.summary[self.keys[-1]][num_skips:])
        return ((last - first) * 1000.0).tolist()

    def latency_percentiles_ms(self, num_skips: int = 0,
                               percentiles=(50.0, 99.0)):
        """End-to-end latency percentiles in ms; {} when there are not
        enough records."""
        return latency_percentiles(self.latencies_ms(num_skips),
                                   percentiles)

    def print_summary(self, num_skips: int) -> None:
        gaps = self.mean_gaps_ms(num_skips)
        if not gaps and self.keys:
            print("Not enough log entries (%d records) to print summary!"
                  % self.num_records())
        for prv, nxt, ms in gaps:
            print("Average time between %s and %s: %f ms" % (prv, nxt, ms))
        if self.num_failed or self.num_shed or self.num_retries:
            print("Contained faults: %d failed, %d shed, %d retries (%s)"
                  % (self.num_failed, self.num_shed, self.num_retries,
                     ", ".join("%s=%d" % kv
                               for kv in self.failure_reasons.items())
                     or "no failures"))
        if self.num_cache_tracked:
            print("Clip cache: %d/%d completions were hits, %d coalesced"
                  % (self.num_cache_hits, self.num_cache_tracked,
                     self.num_cache_coalesced))

    def faults_line(self) -> Optional[str]:
        """The ``# faults ...`` trailer of the full report, or None when
        every request succeeded (keeping fault-free reports byte-stable
        with the pre-containment schema)."""
        if not (self.num_failed or self.num_shed or self.num_retries):
            return None
        parts = ["# faults num_failed=%d num_shed=%d num_retries=%d"
                 % (self.num_failed, self.num_shed, self.num_retries)]
        parts.extend("reason:%s=%d" % kv
                     for kv in self.failure_reasons.items())
        return " ".join(parts)

    def cache_line(self) -> Optional[str]:
        """The ``# cache ...`` trailer, or None for cacheless runs
        (keeping their reports byte-stable with the pre-cache schema).
        Written even when hits=0 on a cache-enabled run — a zero
        hit-rate is a result, not an absence of data."""
        if not self.num_cache_tracked:
            return None
        return ("# cache num_hits=%d num_coalesced=%d num_tracked=%d"
                % (self.num_cache_hits, self.num_cache_coalesced,
                   self.num_cache_tracked))

    def padding_line(self) -> Optional[str]:
        """The ``# padding ...`` trailer, or None when no registered
        card carried a ``pad_rows`` stamp (pre-padding-era pipelines
        keep their byte-stable reports). pad_rows=0 on a tracked run
        is a result — exactly what a ragged arm should show."""
        if not self.num_pad_tracked:
            return None
        return ("# padding pad_rows=%d num_tracked=%d"
                % (self.num_pad_rows, self.num_pad_tracked))

    def phase_samples(self, num_skips: int = 0):
        """{phase: [per-request milliseconds]} over records after
        ``num_skips`` — the deterministic stamp-only decomposition
        (rnb_tpu.trace.attribute_phases) applied to this instance's
        columnar data. Phases partition each request's end-to-end
        span, so per-request sums equal latencies_ms() exactly."""
        from rnb_tpu.trace import attribute_phases
        samples: "OrderedDict[str, List[float]]" = OrderedDict()
        if not self.keys or len(self.keys) < 2:
            return samples
        columns = [self.summary[key][num_skips:] for key in self.keys]
        for row in zip(*columns):
            for phase, ms in attribute_phases(
                    dict(zip(self.keys, row))).items():
                samples.setdefault(phase, []).append(ms)
        return samples

    def phases_line(self) -> Optional[str]:
        """The ``# phases ...`` trailer, or None when phase tracking
        is off (trace-disabled runs keep the earlier byte-stable
        schema) or too few records exist. Microsecond integers so the
        generic ``key=value`` trailer parser reads it unchanged."""
        if not self.track_phases:
            return None
        from rnb_tpu.trace import phase_stats, sorted_phases
        stats = phase_stats(self.phase_samples(self.phase_num_skips))
        if not stats:
            return None
        count = max(s["count"] for s in stats.values())
        parts = ["# phases n=%d" % count]
        for phase in sorted_phases(stats):
            parts.append("%s_mean_us=%d"
                         % (phase, round(stats[phase]["mean_ms"] * 1000)))
            parts.append("%s_p99_us=%d"
                         % (phase, round(stats[phase]["p99_ms"] * 1000)))
        return " ".join(parts)

    def save_full_report(self, fp: IO[str]) -> None:
        # Per-step device-column widths can differ across records (a merge
        # collapses segments that happened to share a device); size each
        # step's columns to the widest record and pad narrower rows with
        # '-' so the whitespace table stays rectangular.
        num_steps = max((len(d) for d in self.devices_per_inference),
                        default=0)
        widths = [0] * num_steps
        for devices_per_step in self.devices_per_inference:
            for step_idx, step_devices in enumerate(devices_per_step):
                widths[step_idx] = max(widths[step_idx], len(step_devices))

        fp.write(" ".join(self.keys))
        for step_idx, width in enumerate(widths):
            if width > 1:
                for sub_id in range(width):
                    fp.write(" device%d-%d" % (step_idx, sub_id))
            else:
                fp.write(" device%d" % step_idx)
        fp.write("\n")
        for row, devices_per_step in zip(zip(*self.summary.values()),
                                         self.devices_per_inference):
            fp.write(" ".join(map(str, row)))
            for step_idx, width in enumerate(widths):
                step_devices = (devices_per_step[step_idx]
                                if step_idx < len(devices_per_step) else ())
                for col in range(width):
                    fp.write(" %s" % (step_devices[col]
                                      if col < len(step_devices) else "-"))
            fp.write("\n")
        faults = self.faults_line()
        if faults is not None:
            fp.write(faults + "\n")
        cache = self.cache_line()
        if cache is not None:
            fp.write(cache + "\n")
        padding = self.padding_line()
        if padding is not None:
            fp.write(padding + "\n")
        phases = self.phases_line()
        if phases is not None:
            fp.write(phases + "\n")


def _token_totals(snapshots, row):
    """The ``tokens`` row: a stage reports its own tokens beside the
    family's counters, a number a key (``tokens_valid``, ...)."""
    counted = [snap for snap in snapshots if "tokens_valid" in snap]
    if not counted:
        return None
    return [sum(int(snap[field]) for snap in counted)
            for field in row.fields]


def _expert_loads(snapshots, row):
    """The ``expert_served`` row: (valid token, chosen expert) pairs
    routed — tokens x experts a token x expert layers — then, of the
    (expert layers, held) counts summed over the instances (replicas of
    one stage hold the same experts), the pairs served here and the
    most and the mean that one held expert of one layer served."""
    import numpy as np
    served, assignments = None, 0
    for snap in snapshots:
        if snap.get(row.counter) is not None:
            part = np.asarray(snap[row.counter], np.int64)
            served = part if served is None or served.shape != part.shape \
                else served + part
            assignments += int(snap["tokens_valid"]) \
                * int(snap["experts_per_token"]) * part.shape[0]
    if served is None:
        return None
    return [assignments, int(served.sum()), int(served.max()),
            float(served.mean())]


class StageCounter(NamedTuple):
    """What one counter of a stage is: its name in the family's
    ``network.COUNTERS`` (``tokens`` is the stage's own), the log-meta
    line its numbers stand on, the ``key=`` names in the order they
    stand there, and ``prefix`` + key the ``BenchmarkResult`` field of
    each. ``reduce`` None: a vector of ``len(keys)`` a snapshot, summed
    over the snapshots; else the row's own ``reduce(snapshots, row)``
    over the counter as the stage counted it, whole. ``maxima``: the
    keys that are a largest value and no count: over dispatches and
    snapshots their maximum is taken (:meth:`merge`)."""
    counter: str
    line: str
    prefix: str
    keys: Sequence[str]
    doc: str
    reduce: Optional[Callable] = None
    maxima: Sequence[str] = ()

    @property
    def fields(self):
        return tuple(self.prefix + key for key in self.keys)

    def merge(self, counted, count):
        """``counted`` (None before the first) and one more ``count`` of
        this row, arrays of one shape with the keys last -> both as
        one."""
        import numpy as np
        count = np.asarray(count, np.int64)
        if counted is None:
            return count
        if not self.maxima:
            return counted + count
        return np.where([key in self.maxima for key in self.keys],
                        np.maximum(counted, count), counted + count)


#: every counter a stage may hand the launcher through
#: ``stage_counters()``, in the order of the lines and of the keys on a
#: line. A line is written where a stage counted one of its rows, a row's
#: keys only where a stage counted that row. A new counter is its
#: family's ``network.py``, a row here and its ``BenchmarkResult``
#: field(s): the stage (``models/token_stages.py``) reduces by this and
#: the launcher writes what :func:`stage_counter_report` hands it;
#: ``scripts/parse_utils.py`` reads any of the four lines back by one
#: rule (a key behind the line's name in lower case).
STAGE_COUNTERS = (
    StageCounter(
        "tokens", "Tokens:", "tokens_", ("valid", "shipped"),
        "valid tokens / tokens shipped (rows x tokens a row) over every "
        "dispatch a stage of token rows served", _token_totals),
    StageCounter(
        "scan_resets", "Tokens:", "tokens_", ("scan_resets",),
        "(1,): the rows of a dispatch that open a request, where a "
        "state-space scan zeroes its state and the convolution in front "
        "of it its history (``ops/ssd.py``'s ``row_first``), pad rows "
        "not counted: the requests a dispatch packed"),
    StageCounter(
        "cross_lines", "Tokens:", "tokens_", ("cross_lines",),
        "(1,): the lines a dispatch sends through a cross-decoder, of a "
        "stack whose second half reads the first half's keys, values and "
        "scan memory (``models/phi4_flash``): one a request with the "
        "prefill exit, one a valid token without it"),
    StageCounter(
        "stream_mix", "Tokens:", "tokens_", ("mixes", "res_defect_e9"),
        "(2,), a residual stream several wide under per-token mappings "
        "(``ops/hyper.py``): the (valid token, sublayer) mixings a "
        "dispatch made, and the largest distance of a row or column sum "
        "of any valid token's ``H_res`` from 1, in units of 1e-9 (the "
        "largest of the run, not a sum): fewer Sinkhorn steps show here",
        maxima=("res_defect_e9",)),
    StageCounter(
        "expert_served", "Experts:", "experts_",
        ("assignments", "held", "max_per_expert", "mean_per_expert"),
        "(expert layers, held): the assignments each held expert "
        "served, of a stage holding a share of each layer's experts "
        "(``network.held_slots`` makes its slots, ``experts_per_token`` "
        "stands beside it in the snapshot)", _expert_loads),
    StageCounter(
        "group_tokens", "Experts:", "experts_", ("group_tokens",),
        "(expert layers,): the valid tokens that sent the held experts "
        "anything, where the router chooses among groups of experts"),
    StageCounter(
        "pair_rows", "Experts:", "experts_",
        ("pair_rows_moved", "pair_rows_all"),
        "(expert layers, 2): the pair rows the held experts' buffers "
        "held, where a stack sizes them by the share held "
        "(``ops/moe.pair_capacity``), and the tokens x k they would "
        "hold unsized"),
    StageCounter(
        "gmm_rows", "Experts:", "experts_", ("gmm_rows",),
        "(expert layers,): the rows the first grouped product's grid "
        "steps multiplied for the pairs the held experts served "
        "(``ops/moe.gmm_visits`` times the row tile in use)"),
    StageCounter(
        "sparse", "Sparse:", "sparse_",
        ("queries", "selecting", "causal_keys", "chosen_keys"),
        "(sparse layers, 4), block-selected attention "
        "(``ops/blocksparse.py``): (valid query, key-value head) pairs, "
        "those of requests that select key blocks, the causal keys "
        "those could read, the keys of the blocks they chose; under a "
        "learned indexer (``ops/indexed.py``) the same four of valid "
        "queries, one set a query for all heads: those with more keys "
        "to read than ``topk``, the keys those could read, the keys "
        "they chose"),
    StageCounter(
        "index_tiles", "Sparse:", "sparse_",
        ("tiles_chosen", "tiles_causal"),
        "(sparse layers, 2), attention under an indexer's sets "
        "(``ops/indexed.py``): the attention kernel's (query tile, key "
        "tile) pairs in which any query chose any key, and those on or "
        "under the diagonal, at its own tile sizes"),
    StageCounter(
        "index_chunks", "Sparse:", "sparse_",
        ("chunks_walked", "chunks_to_diagonal"),
        "(sparse layers, 2), the thresholds under an indexer's scores "
        "(``ops/indexed.chunk_visits``): the (query step, key chunk) "
        "visits a count's walk makes, from the chunk of the step's "
        "first request's first key to the diagonal's and none in a "
        "step no query of which has ``topk`` keys to read, and those "
        "of a walk from key 0 to every step's diagonal"),
    StageCounter(
        "attn_tiles", "Attention:", "attention_",
        ("tiles_visited", "tiles_causal"),
        "(attention layers, 2), the packed flash kernel "
        "(``ops/segattn.py``): the (query block, key block) tiles the "
        "dispatch's block table let run, and those on or under the "
        "diagonal; the full layers' alone where a stack has layers "
        "with a window"),
    StageCounter(
        "window_tiles", "Attention:", "",
        ("window_tiles_visited", "window_tiles_causal"),
        "(window layers, 2), a stack's layers with a window, whose "
        "kernel walks a band and no table (``ops/banded.py``): the "
        "steps it ran a key-value head, and the tiles on or under the "
        "diagonal at their own tile sizes (a step's queries by twice "
        "as many keys)"),
    StageCounter(
        "window_keys", "Attention:", "",
        ("window_keys_kept", "window_keys_causal"),
        "(window layers, 2), a stack's layers with a window: the (valid "
        "query, key) pairs the window keeps, ``min(position + 1, "
        "window)`` a query, and the causal pairs a layer without one "
        "would read"),
)


def stage_counter_report(snapshots):
    """(lines, fields) of the ``stage_counters()`` snapshots of a run's
    stage instances, by :data:`STAGE_COUNTERS`: the log-meta lines to
    write, whole and in order, and ``BenchmarkResult``'s fields by
    name. Nothing of a counter that no stage counted."""
    import numpy as np
    parts, fields = {}, {}
    for row in STAGE_COUNTERS:
        if row.reduce is not None:
            values = row.reduce(snapshots, row)
        else:
            values = None
            for snap in snapshots:
                if snap.get(row.counter) is not None:
                    values = row.merge(values, np.asarray(
                        snap[row.counter], np.int64).reshape(-1))
            if values is not None:
                values = [int(v) for v in values]
        if values is None:
            continue
        fields.update(zip(row.fields, values))
        parts.setdefault(row.line, []).extend(
            ("%s=%.3f" if isinstance(value, float) else "%s=%d")
            % (key, value) for key, value in zip(row.keys, values))
    return (["%s %s" % (line, " ".join(counts))
             for line, counts in parts.items()], fields)
