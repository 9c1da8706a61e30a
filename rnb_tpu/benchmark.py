"""Benchmark orchestrator: launch a pipeline job end-to-end.

CLI parity with the reference launcher (benchmark.py:127-305):
``python -m rnb_tpu.benchmark -mi <ms> -b <batch> -v <videos>
-qs <queue-size> -c <config.json> [--check]`` — plus TPU-runtime
extras: ``--platform cpu`` forces the virtual-CPU backend (useful with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; without it the
CLI insists on a TPU), and ``--log-base`` relocates the log directory.

One controller process owns everything: it validates the config against
the visible JAX devices (replacing the reference's NVML free-GPU probe,
benchmark.py:97-125), builds the channel fabric, spawns the client and
one executor thread per (step, group, device instance), fences them all
with start/finish barriers so model compile/warm-up stays out of the
measured window (benchmark.py:276-288), and writes ``log-meta.txt``
plus a copy of the pipeline config into ``logs/<job_id>/``.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from datetime import datetime
from typing import Any, Dict, Optional

from rnb_tpu import trace as trace_mod
from rnb_tpu.arg_utils import nonnegative_int, positive_int

BARRIER_TIMEOUT_S = 1800.0  # generous: first TPU compile can be slow


#: the checkout root: <root>/rnb_tpu/benchmark.py
REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: when an entry point first called enable_compilation_cache(): JAX and
#: the accelerator runtime are up by then (benchmarks/run.py calls it
#: right behind jax.devices()). The next run_benchmark takes the stamp
#: into its record of set-up as the instant ``setup.entered``.
_ENTERED: Optional[float] = None


def enable_compilation_cache() -> str:
    """Persist XLA executables across processes so repeat runs skip
    the first compile; -> the cache directory in effect.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there
    and this function sets no directory: a cache placed from outside
    (a chip host that keeps one between runs) must be the one used.
    Otherwise it is ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of the cache key and a directory that moves never
    hits. The one function every process of a job calls: the launcher
    and chip_smoke.py.

    An executable's metadata is part of its key here. JAX leaves it
    out by default, and a program then gets back whatever executable
    was cached first for the same arithmetic, with *that* program's
    ``op_name``s: the final stages write their scope table from the
    executable's text (rnb_tpu.hloscopes), so a cache shared with a
    checkout that names its scopes otherwise would hand them its
    names.

    Its first call also stamps the end of "imports and the runtime's
    start" for the record of set-up (``_ENTERED``)."""
    import jax
    global _ENTERED
    if _ENTERED is None:
        _ENTERED = time.time()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(REPO_DIR, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir


#: JAX's own time spans of a program's way to an executable, by the
#: names they take in the record of set-up (jax/_src/dispatch.py)
_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_JAX_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


def _listen_to_jax(tracer):
    """Record ``jax.monitoring``'s time spans of tracing, lowering and
    compiling into ``tracer`` as ``setup.jax.*`` spans, on the thread
    that compiles (JAX stamps them with ``time.time()`` there, so they
    nest under that thread's ``setup.s{step}.*`` spans); -> the
    function that unregisters every listener again. A compile span
    carries ``cache_hit`` and ``retrieval_s`` from the cache's event
    and duration, which JAX records inside it on the same thread. A
    ``jit`` traced inside another's trace or lowering (thousands a
    program; the metrics add the two up) is in the outer one's span
    and gets none of its own: JAX records a span's start as a scalar,
    which is what tells the depth."""
    from jax import monitoring
    names = {
        _JAX_TRACE: trace_mod.name("setup.jax.trace"),
        _JAX_LOWER: trace_mod.name("setup.jax.lower"),
        _JAX_COMPILE: trace_mod.name("setup.jax.compile"),
    }
    mine = threading.local()  # the compiling thread's open spans

    def on_event(event, **_kwargs):
        if event == _JAX_CACHE_HIT:
            mine.hit = 1

    def on_duration(event, duration, **_kwargs):
        if event == _JAX_CACHE_READ:
            mine.retrieval_s = duration

    def on_scalar(event, _value, **_kwargs):
        if event in (_JAX_TRACE, _JAX_LOWER):
            mine.lowering = getattr(mine, "lowering", 0) + 1

    def on_span(event, start, end, **kwargs):
        event_name = names.get(event)
        if event_name is None:
            return
        counts = {"fun_name": str(kwargs.get("fun_name", ""))}
        if event == _JAX_COMPILE:
            counts["cache_hit"] = mine.__dict__.pop("hit", 0)
            if "retrieval_s" in mine.__dict__:
                counts["retrieval_s"] = mine.__dict__.pop("retrieval_s")
        else:
            mine.lowering = getattr(mine, "lowering", 1) - 1
            if mine.lowering > 0:
                return
        tracer.add_event(event_name, "X", start, end - start, None, counts)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_scalar_listener(on_scalar)
    monitoring.register_event_time_span_listener(on_span)

    def stop():
        monitoring.unregister_event_listener(on_event)
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_scalar_listener(on_scalar)
        monitoring.unregister_event_time_span_listener(on_span)
    return stop


class _Setup:
    """One job's record of set-up: a Tracer that collects from
    run_benchmark's first line to the start barrier whatever the
    ``trace`` key says, and JAX's compile listeners for as long. At
    the barrier's release (its action: every party has arrived, none
    runs yet) ``trace.ACTIVE`` becomes what the configuration asked
    for and the listeners go, so the served window is traced, or not,
    exactly as without this."""

    def __init__(self):
        self.run_start = time.time()
        #: where no entry point stamped it, set-up enters here
        self.entered = self.run_start if _ENTERED is None else _ENTERED
        self.released: Optional[float] = None
        self.tracer = None
        self._configured = None
        self._stop_listening = None

    def open(self, configured) -> None:
        """Start collecting: into the ``trace`` key's Tracer where
        there is one, else into one of set-up's own (no sampler)."""
        global _ENTERED
        _ENTERED = None  # a later job of this process stamps its own
        self._configured = configured
        self.tracer = configured or trace_mod.Tracer(
            trace_mod.TraceSettings(sample_hz=0.0))
        trace_mod.ACTIVE = self.tracer
        self._stop_listening = _listen_to_jax(self.tracer)
        self.tracer.add_event(trace_mod.name("setup.entered"), "i",
                              self.entered, 0.0, None, None)

    def launched(self) -> None:
        """Every thread of the job is started."""
        self.tracer.add_event(trace_mod.name("setup.launch"), "X",
                              self.run_start,
                              time.time() - self.run_start, None, None)

    def release(self) -> None:
        """The start barrier's action; also run_benchmark's way out
        where the barrier was never reached."""
        if self._stop_listening is None:
            return
        self.released = time.time()
        trace_mod.ACTIVE = self._configured
        self._stop_listening()
        self._stop_listening = None

    def events(self):
        """The Tracer's events up to the release."""
        return [e for e in self.tracer.snapshot_events()
                if e[2] <= self.released]


@dataclass
class BenchmarkResult:
    job_id: str
    total_time_s: float
    num_videos: int
    termination_flag: int
    throughput_vps: float
    log_dir: str
    #: end-to-end per-request latency percentiles (ms) over every
    #: final-step instance, steady-state records only; None when the
    #: run produced too few records
    p50_latency_ms: Optional[float] = None
    p99_latency_ms: Optional[float] = None
    #: total clips across every registered completion (0 when the
    #: pipeline never stamps num_clips) — clips/sec and MFU accounting
    clips_completed: int = 0
    #: process CPU seconds (utime+stime, all threads incl. the decode
    #: pool) over the measured window; / total_time_s ~ host-core
    #: saturation on a 1-core host
    host_cpu_s: float = 0.0
    #: the shared native decode pool over the measured window
    #: (rnb_tpu.decode.native.DecodePool.stats): seconds its workers
    #: spent inside the decoder summed over the workers, and frames
    #: decoded. Both zero when the native pool decoded nothing.
    decode_busy_s: float = 0.0
    decode_frames: int = 0
    #: fault-containment accounting (rnb_tpu.faults): requests
    #: dead-lettered with a permanent failure, dropped by the "shed"
    #: overload policy, and transient retry attempts. Successfully
    #: completed requests = num_completed; throughput_vps and the
    #: latency percentiles cover successes only.
    num_completed: int = 0
    num_failed: int = 0
    num_shed: int = 0
    num_retries: int = 0
    failure_reasons: Dict[str, int] = field(default_factory=dict)
    shed_sites: Dict[str, int] = field(default_factory=dict)
    #: decoded-clip cache accounting (rnb_tpu.cache), summed over every
    #: cache-owning stage instance; all zero when no step configures
    #: `cache_mb`. hits+misses = loader-side lookups (including for
    #: requests that later failed/shed); coalesced = requests that
    #: shared an in-flight decode instead of re-decoding.
    cache_hits: int = 0
    cache_misses: int = 0
    cache_inserts: int = 0
    cache_evictions: int = 0
    cache_coalesced: int = 0
    #: entries skipped because a single batch exceeded the whole
    #: cache_mb budget (was written to log-meta but missing here until
    #: the schema checker's BenchmarkResult cross-check caught it)
    cache_oversize: int = 0
    cache_bytes_resident: int = 0
    #: zero-copy decode-staging accounting (rnb_tpu.staging), summed
    #: over every staging-owning stage instance; all zero when no
    #: loader built a pool (staging_slots=0 / non-native backend).
    #: staged vs copied batches split the emissions between the
    #: zero-copy slot path and the seed copy fallback; acquire_waits
    #: counts backpressure blocks on slot exhaustion (never drops);
    #: reallocs counts alias-forced slot-buffer replacements.
    staging_slots: int = 0
    staging_slot_bytes: int = 0
    staging_acquires: int = 0
    staging_acquire_waits: int = 0
    staging_staged_batches: int = 0
    staging_copied_batches: int = 0
    staging_reallocs: int = 0
    #: load-adaptive batching accounting (rnb_tpu.autotune), summed
    #: over every controller-owning stage instance; all zero when the
    #: config carries no enabled `autotune` root key. decisions =
    #: controller consultations (every emission is covered by one, so
    #: decisions >= emissions); immediate/held split them by verdict;
    #: the deadline_us_* triple summarizes the held-decision deadline
    #: histogram (min/max/sum microseconds).
    autotune_decisions: int = 0
    autotune_immediate: int = 0
    autotune_held: int = 0
    autotune_emissions: int = 0
    autotune_deadline_us_min: int = 0
    autotune_deadline_us_max: int = 0
    autotune_deadline_us_sum: int = 0
    #: emissions per chosen row bucket (keys are stringified row
    #: counts; always a subset of the configured warmed buckets)
    autotune_bucket_counts: Dict[str, int] = field(default_factory=dict)
    #: per-edge queue-overflow counts under the "abort" overload
    #: policy (rnb_tpu.control.FaultStats.record_overflow) — the
    #: events that used to be an unparseable stdout warning
    queue_overflows: Dict[str, int] = field(default_factory=dict)
    #: per-request phase attribution (rnb_tpu.trace): {phase:
    #: {mean_ms, p99_ms, count}} over steady-state completions,
    #: phases summing to end-to-end latency per request. Empty unless
    #: the config's `trace` key enabled tracing (the same gating as
    #: the log-meta `Phases:` line, keeping trace-off runs byte-
    #: stable).
    phases: Dict[str, Dict[str, float]] = field(default_factory=dict)
    #: trace export accounting: events written to logs/<job>/
    #: trace.json and events dropped at the max_events cap (both 0 on
    #: trace-off runs)
    trace_events: int = 0
    trace_dropped: int = 0
    #: padding-waste accounting (rnb_tpu.stage.PadCounter), summed
    #: over every batching stage instance: pad rows shipped /
    #: total rows shipped / emissions. Under ragged dispatch the
    #: consumer's kernel computes no pad rows, so pad_rows stays ~0
    #: and the waste the bucketed rule would have burned lands in
    #: ragged_pad_rows_eliminated instead.
    pad_rows: int = 0
    total_rows: int = 0
    pad_emissions: int = 0
    #: a stage's own counters (``stage_counters()``; the token
    #: families' final stage, rnb_tpu.models.token_stages), summed over
    #: the run's stage instances: rnb_tpu.telemetry.STAGE_COUNTERS says
    #: what each counts and which log-meta line and key carries it
    #: (Tokens: / Experts: / Sparse: / Attention:); 0 where no stage
    #: counts it
    tokens_valid: int = 0
    tokens_shipped: int = 0
    tokens_scan_resets: int = 0
    tokens_cross_lines: int = 0
    tokens_mixes: int = 0
    tokens_res_defect_e9: int = 0
    experts_assignments: int = 0
    experts_held: int = 0
    experts_max_per_expert: int = 0
    experts_mean_per_expert: float = 0.0
    experts_group_tokens: int = 0
    experts_pair_rows_moved: int = 0
    experts_pair_rows_all: int = 0
    experts_gmm_rows: int = 0
    sparse_queries: int = 0
    sparse_selecting: int = 0
    sparse_causal_keys: int = 0
    sparse_chosen_keys: int = 0
    sparse_tiles_chosen: int = 0
    sparse_tiles_causal: int = 0
    sparse_chunks_walked: int = 0
    sparse_chunks_to_diagonal: int = 0
    attention_tiles_visited: int = 0
    attention_tiles_causal: int = 0
    window_tiles_visited: int = 0
    window_tiles_causal: int = 0
    window_keys_kept: int = 0
    window_keys_causal: int = 0
    #: ragged row-pool dispatch accounting (rnb_tpu.ops.ragged),
    #: summed over every ragged stage instance; all zero without the
    #: `ragged` root config key. rows = valid rows shipped across all
    #: pool emissions; pad_rows_eliminated = what the bucketed pad
    #: rule would have shipped on top; cache_hit_rows = rows served
    #: into pools from the row-extent clip cache.
    ragged_pool_rows: int = 0
    ragged_emissions: int = 0
    ragged_rows: int = 0
    ragged_pad_rows_eliminated: int = 0
    ragged_cache_hit_rows: int = 0
    #: intra-stage shard accounting (rnb_tpu.parallel.shardplan, step
    #: `shard` config key), summed over every declared-degree stage
    #: instance; all zero without the key. Degree buys per-device HBM
    #: feasibility, never speed: gathers counts logits-path merge
    #: collectives, collective_us their summed host-timed wall
    #: (nested inside the model_call span, so it never adds to
    #: inference time), rows the valid rows that crossed a sharded
    #: stage.
    shard_steps: int = 0
    shard_max_degree: int = 0
    shard_gathers: int = 0
    shard_collective_us: int = 0
    shard_rows: int = 0
    #: per-step shard detail (the `Shard steps:` JSON meta line):
    #: degree/axis, merge-gather counters, projected vs budget MiB,
    #: and the projected min feasible degree
    shard_step_detail: Dict[str, Any] = field(default_factory=dict)
    #: paged device-memory accounting (rnb_tpu.pager, root `pager`
    #: config key) — the `Pages:` meta line verbatim: page
    #: alloc/free/live occupancy, gather dispatches split by plane
    #: (clip arena vs feature arena), feature-cache
    #: lookup/hit/insert/evict counters, and bypassed_batches =
    #: emissions that shipped ZERO host->device bytes because every
    #: row gathered from pages. Empty without the key.
    pages: Dict[str, int] = field(default_factory=dict)
    #: per-step jit-entry signature accounting
    #: (rnb_tpu.compilestats): {step: {warmup, steady_new,
    #: steady_calls}} — steady_new > 0 means a mid-run recompile; a
    #: ragged stage's warmup is exactly 1
    compile_signatures: Dict[str, Dict[str, int]] = \
        field(default_factory=dict)
    #: per-step stage-construction wall seconds (weights + warmup
    #: compiles), summed over the step's instances
    warmup_s: Dict[str, float] = field(default_factory=dict)
    #: set-up's record (rnb_tpu.benchmark._Setup): ``entered``,
    #: ``run_start``, ``released`` in epoch seconds and the ``setup.*``
    #: events ``(name, t0, dur, thread, counts)`` of every thread from
    #: run_benchmark's first line to the start barrier's release —
    #: telemetry.TRACE_EVENT_REGISTRY says what each spans; the same
    #: events are logs/<job>/setup-trace.json
    setup: Dict[str, Any] = field(default_factory=dict)
    #: device-resident handoff accounting (rnb_tpu.handoff), summed
    #: over every consumer executor; all zero without the root
    #: `handoff` config key. Every ring-payload take is one edge
    #: event, classified d2d (adopted / resharded on-device) or host
    #: (the explicit host round trip), with the bytes each class
    #: moved — d2d_edges + host_edges == edges always, and a
    #: device-resident config must show host_bytes == 0.
    handoff_edges: int = 0
    handoff_d2d_edges: int = 0
    handoff_host_edges: int = 0
    handoff_d2d_bytes: int = 0
    handoff_host_bytes: int = 0
    #: per-edge-label handoff counters (the `Handoff edges:` JSON
    #: meta line)
    handoff_edge_detail: Dict[str, Dict[str, int]] = \
        field(default_factory=dict)
    #: measured-cost placement report (rnb_tpu.placement): per-step
    #: measured dispatch costs, the executed plan's predicted
    #: occupancy, and the recommendation over the device budget —
    #: the `Placement:` JSON meta line verbatim. Empty without the
    #: root `placement` config key.
    placement: Dict[str, Any] = field(default_factory=dict)
    #: lane health / circuit-breaker accounting (rnb_tpu.health,
    #: root `health` config key), summed over every replica step's
    #: board; all zero without the key. transitions counts every
    #: state-machine hop; evictions counts permanently dead lanes;
    #: redispatches counts items drained off evicted lanes onto
    #: healthy siblings; routes_after_open counts containment
    #: violations (routes to an open/evicted lane while a routable
    #: sibling existed) and must be 0 on a healthy run.
    health_lanes: int = 0
    health_transitions: int = 0
    health_opens: int = 0
    health_evictions: int = 0
    health_probes: int = 0
    health_redispatches: int = 0
    health_routes_after_open: int = 0
    #: per-lane health detail (the `Health lanes:` JSON meta line):
    #: final state, full transition path, redispatched-from count
    health_lane_detail: Dict[str, Any] = field(default_factory=dict)
    #: deadline-propagation accounting (rnb_tpu.health, root
    #: `deadline` config key): the configured budget and requests
    #: shed as deadline_expired across every check site; zero/empty
    #: without the key
    deadline_budget_ms: int = 0
    deadline_expired: int = 0
    deadline_sites: Dict[str, int] = field(default_factory=dict)
    #: hedged re-dispatch accounting (rnb_tpu.health, step key
    #: `hedge_ms`): fired re-issues, wins by the hedge copy, losses
    #: (original resolved first), and the losers' burned service
    #: milliseconds — won + lost == fired always; hedge work is
    #: counted here as overhead, never in throughput_vps
    hedges_fired: int = 0
    hedges_won: int = 0
    hedges_lost: int = 0
    hedges_wasted_ms: int = 0
    #: lock-order witness ledger (rnb_tpu.lockwitness, root `lint`
    #: config key with lock_witness true): witnessed locks, total
    #: acquisitions, distinct acquisition-order edges, discipline
    #: violations — all zero without the key. --check holds
    #: locks_violations to zero and the Lock edges: JSON detail to
    #: the static RNB-C lock-order graph (observed subset-of
    #: declared).
    locks_tracked: int = 0
    locks_acquires: int = 0
    locks_edges: int = 0
    locks_violations: int = 0


def run_benchmark(config_path: str,
                  mean_interval_ms: int = 3,
                  batch_size: int = 1,
                  num_videos: int = 2000,
                  queue_size: int = 50000,
                  log_base: str = "logs",
                  print_progress: bool = True,
                  seed: Optional[int] = None,
                  job_id: Optional[str] = None) -> BenchmarkResult:
    """Programmatic entry used by the CLI, the tests and the benchmark
    (``benchmarks/run.py``)."""
    setup = _Setup()
    try:
        return _run_benchmark(setup, config_path, mean_interval_ms,
                              batch_size, num_videos, queue_size,
                              log_base, print_progress, seed, job_id)
    finally:
        setup.release()


def _run_benchmark(setup: _Setup, config_path: str, mean_interval_ms: int,
                   batch_size: int, num_videos: int, queue_size: int,
                   log_base: str, print_progress: bool,
                   seed: Optional[int],
                   job_id: Optional[str]) -> BenchmarkResult:
    enable_compilation_cache()
    # multi-host: honor RNB_TPU_COORDINATOR/_NUM_PROCESSES/_PROCESS_ID
    # before the first backend touch — jax.distributed must initialize
    # ahead of jax.devices() for DCN-attached devices to be visible
    # (SURVEY.md §2.4 TPU mapping; no-op for single-host runs)
    from rnb_tpu.devices import keep_host_backend
    from rnb_tpu.parallel.distributed import maybe_initialize
    keep_host_backend()
    maybe_initialize()
    from rnb_tpu.client import bulk_client, poisson_client
    from rnb_tpu.config import load_config
    from rnb_tpu.control import (ChannelFabric, FaultStats,
                                 InferenceCounter, TerminationState)
    from rnb_tpu.faults import FaultPlan
    from rnb_tpu.runner import NUM_SUMMARY_SKIPS, RunnerContext, runner
    from rnb_tpu.telemetry import logmeta, logroot

    # defensive: a previous run that died mid-trace must not leave its
    # tracer active — this run's instrumentation would otherwise write
    # into a dead collector (and un-traced runs would stop being
    # byte-stable)
    trace_mod.ACTIVE = None

    config = load_config(config_path)
    # unified pipeline tracing (rnb_tpu.trace, root 'trace' config
    # key): one per-job collector every thread role records spans
    # into. Until the start barrier a Tracer collects in any case —
    # this one, or one of set-up's own (_Setup)
    trace_settings = trace_mod.TraceSettings.from_config(config.trace)
    tracer = trace_mod.Tracer(trace_settings) \
        if trace_settings is not None else None
    setup.open(tracer)
    config.check_devices()
    # best-effort contention probe (reference benchmark.py:97-125
    # aborted here; we warn — see rnb_tpu.devices.probe_busy_devices)
    from rnb_tpu.devices import probe_busy_devices
    for warning in probe_busy_devices(config.all_devices()):
        print("[rnb-tpu] WARNING: %s" % warning, file=sys.stderr)

    # lock-order witness (rnb_tpu.lockwitness, root `lint` config
    # key): armed BEFORE pipeline construction — the witness wraps
    # locks at lockwitness.lock() creation time, so enabling it after
    # the cache/pager/staging/health objects exist would observe
    # nothing
    from rnb_tpu import lockwitness
    witness_armed = bool(config.lint
                         and config.lint.get("lock_witness", False))
    if witness_armed:
        lockwitness.enable()
        lockwitness.reset()

    if job_id is None:
        job_id = "%s-mi%d-b%d-v%d-qs%d" % (
            datetime.today().strftime("%y%m%d_%H%M%S"), mean_interval_ms,
            batch_size, num_videos, queue_size)

    num_runners = config.num_runners
    bar_total = num_runners + 2  # runners + client + this controller
    sta_bar = threading.Barrier(bar_total, action=setup.release,
                                timeout=BARRIER_TIMEOUT_S)
    fin_bar = threading.Barrier(bar_total, timeout=BARRIER_TIMEOUT_S)
    counter = InferenceCounter()
    termination = TerminationState()
    summary_sink: list = []
    cache_sink: list = []
    staging_sink: list = []
    autotune_sink: list = []
    compile_sink: list = []
    pad_sink: list = []
    ragged_sink: list = []
    stage_counter_sink: list = []
    shard_sink: list = []
    fault_stats = FaultStats()
    # load-adaptive batching (rnb_tpu.autotune): one validated settings
    # object shared by every participating stage; per-step opt-out via
    # "autotune": false on the step
    from rnb_tpu.autotune import AutotuneSettings
    autotune_settings = AutotuneSettings.from_config(config.autotune)
    if autotune_settings is not None:
        # enabled-but-inert is a measurement confound: an operator
        # A/B-ing against a static baseline must be able to tell a
        # pipeline where no stage participates (every step opted out,
        # or none SUPPORTS_AUTOTUNE) from an adaptive run. Class-load
        # failures are deferred to the runner thread, which owns that
        # error path.
        from rnb_tpu.utils.class_utils import load_class

        def _may_participate(step):
            try:
                return getattr(load_class(step.model),
                               "SUPPORTS_AUTOTUNE", False)
            except Exception:
                return True
        if not any(step.autotune and _may_participate(step)
                   for step in config.steps):
            print("[rnb-tpu] WARNING: autotune is enabled but no "
                  "pipeline stage participates (every step opted out "
                  "or unsupported) — batching stays static and no "
                  "Autotune: telemetry will be emitted",
                  file=sys.stderr)
    # ragged row-pool dispatch (root 'ragged' config key,
    # rnb_tpu.ops.ragged): supporting stages get the kwargs injected —
    # the keys are runtime wiring, not user config, so the static
    # unconsumed-key check never sees them and a non-supporting stage
    # (mesh runner, single-step baseline) simply stays bucketed
    from rnb_tpu.ops.ragged import RaggedSettings
    ragged_settings = RaggedSettings.from_config(config.ragged)
    ragged_kwargs_by_step: Dict[int, Dict[str, Any]] = {}
    if ragged_settings is not None:
        from rnb_tpu.utils.class_utils import load_class as _load_cls
        any_ragged = False
        for step_idx, step in enumerate(config.steps):
            try:
                supports = getattr(_load_cls(step.model),
                                   "SUPPORTS_RAGGED", False)
            except Exception:
                supports = False
            if supports:
                any_ragged = True
                ragged_kwargs_by_step[step_idx] = {
                    "ragged": True,
                    "ragged_pool_rows": ragged_settings.pool_rows}
        if not any_ragged:
            print("[rnb-tpu] WARNING: ragged is enabled but no "
                  "pipeline stage supports it — every emission stays "
                  "bucketed and no Ragged: telemetry will be emitted",
                  file=sys.stderr)

    # paged device memory (root 'pager' config key, rnb_tpu.pager):
    # ONE page allocator per job — executors hand it to every
    # SUPPORTS_PAGER stage before the start barrier (the loader's
    # clip cache switches to page tables, the consuming stage
    # attaches the feature-page arena). Absent => None, byte-stable
    # logs, no arenas allocated.
    from rnb_tpu.pager import Pager, PagerSettings
    pager_settings = PagerSettings.from_config(config.pager)
    pager = Pager(pager_settings) if pager_settings is not None \
        else None

    # device-resident handoff (root 'handoff' key, rnb_tpu.handoff):
    # consumer executors apply the edge contract to every ring payload
    # take and account d2d vs host-hop moves; absent => the stage
    # models' own re-homing, no accounting, byte-stable logs
    from rnb_tpu.handoff import HandoffSettings, InflightDepths
    handoff_settings = HandoffSettings.from_config(config.handoff)
    handoff_sink: list = []
    # measured-cost placement (root 'placement' key,
    # rnb_tpu.placement): every executor measures its dispatch busy
    # spans; the launcher turns them into the Placement: plan line.
    # (Apply-mode replica counts were already expanded at parse time.)
    from rnb_tpu.placement import PlacementSettings
    placement_settings = PlacementSettings.from_config(config.placement)
    placement_sink = [] if placement_settings is not None else None
    # replica-lane depth counters: one shared InflightDepths per
    # replica-expanded step, feeding the upstream ReplicaSelector's
    # least-loaded routing and settled by the replica executors
    depths_by_step = {
        step_idx: InflightDepths(step.replica_queues)
        for step_idx, step in enumerate(config.steps)
        if step.replica_queues}
    # self-healing layer (rnb_tpu.health): lane health boards per
    # replica step (root 'health' key), the job-wide deadline ledger
    # (root 'deadline' key; budget seeded from autotune.slo_ms), and
    # hedge governors per replicated edge ('hedge_ms' step key)
    from rnb_tpu.health import (DeadlineSettings, DeadlineStats,
                                HealthSettings, HedgeGovernor,
                                LaneHealthBoard)
    health_settings = HealthSettings.from_config(config.health)
    boards_by_step: Dict[int, LaneHealthBoard] = {}
    if health_settings is not None:
        boards_by_step = {
            step_idx: LaneHealthBoard(step.replica_queues,
                                      health_settings)
            for step_idx, step in enumerate(config.steps)
            if step.replica_queues}
        if not boards_by_step:
            print("[rnb-tpu] WARNING: health is enabled but no step "
                  "declares replica lanes — there is nothing to "
                  "circuit-break and no Health: telemetry will be "
                  "emitted", file=sys.stderr)
    deadline_settings = DeadlineSettings.from_config(config.deadline,
                                                     config.autotune)
    deadline_stats = (DeadlineStats()
                      if deadline_settings is not None else None)
    governors_by_step = {
        step_idx: HedgeGovernor(step.hedge_ms)
        for step_idx, step in enumerate(config.steps)
        if step.replica_queues and step.hedge_ms is not None}

    fault_plan = FaultPlan.resolve(config.fault_plan)
    if fault_plan is not None:
        # env-provided plans bypass config parsing — re-check their
        # step indices against this pipeline before launching
        fault_plan.check_steps(config.num_steps)
        from rnb_tpu.faults import LANE_KINDS
        if not boards_by_step and any(f["kind"] in LANE_KINDS
                                      for f in fault_plan.faults):
            # a lane death without the health layer cannot be
            # contained: there is no eviction, no drain pump, and no
            # sibling linger — the queued work would strand and the
            # run would hang to the barrier timeout. Fail at launch
            # with the fix, not 30 minutes in.
            raise ValueError(
                "the fault plan injects replica_crash/replica_stall "
                "but the config has no enabled root 'health' key (or "
                "no replica lanes) — lane deaths need the health "
                "layer's eviction/drain machinery to stay contained")
    if fault_plan is not None and print_progress:
        print("[rnb-tpu] fault plan active: %s" % fault_plan.describe())

    # bulk mode pre-enqueues everything; size the queues accordingly
    # (reference benchmark.py:209 — but unlike the reference, account
    # for segmentation fan-out: a step with num_segments=k multiplies
    # the messages in flight downstream of it — and for the exit
    # markers that share the queue with the payload items: a slow
    # consumer must never leave a producer's end-of-stream markers
    # undeliverable past the send deadline)
    if mean_interval_ms > 0:
        effective_queue_size = queue_size
    else:
        from rnb_tpu.control import NUM_EXIT_MARKERS
        seg_factor = 1
        for step in config.steps:
            seg_factor *= step.num_segments
        effective_queue_size = (num_videos * seg_factor + num_runners
                                + max(NUM_EXIT_MARKERS, num_runners) + 1)
    fabric = ChannelFabric(config, effective_queue_size)
    # the `trace` key's Tracer also runs a low-rate background sampler
    # over the inter-stage queue depths (stage-owned sources — staging
    # occupancy, in-flight decode counts — register in the runner via
    # enable_trace)
    if tracer is not None:
        tracer.add_counter_source(
            trace_mod.name("queue.filename.depth"),
            fabric.get_filename_queue().qsize)
        edge_idx = 0
        for step_queues in fabric.queues:
            # edge ordinal in step-major enumeration order (queue
            # indices may legally repeat across steps, so the ordinal
            # — not the config's queue index — keys the counter track)
            for q_idx in sorted(step_queues):
                tracer.add_counter_source(
                    trace_mod.name("queue.e%d.depth", edge_idx),
                    step_queues[q_idx].qsize)
                edge_idx += 1

    threads = []
    client_kwargs = dict(overload_policy=config.overload_policy,
                         fault_stats=fault_stats, counter=counter,
                         target_num_videos=num_videos,
                         popularity=config.popularity,
                         deadline_budget_s=(
                             deadline_settings.budget_ms / 1000.0
                             if deadline_settings is not None
                             else None))
    if mean_interval_ms > 0:
        client_args = (config.video_path_iterator,
                       fabric.get_filename_queue(), mean_interval_ms,
                       termination, sta_bar, fin_bar, seed,
                       fabric.filename_num_markers)
        client_impl = poisson_client
    else:
        client_args = (config.video_path_iterator,
                       fabric.get_filename_queue(), num_videos,
                       termination, sta_bar, fin_bar, seed,
                       fabric.filename_num_markers)
        client_impl = bulk_client
    threads.append(threading.Thread(target=client_impl, args=client_args,
                                    kwargs=client_kwargs,
                                    name="client", daemon=True))

    for step_idx, step in enumerate(config.steps):
        is_final = step_idx == config.num_steps - 1
        for group_idx, group in enumerate(step.groups):
            model_kwargs = step.kwargs_for_group(group_idx)
            if step_idx in ragged_kwargs_by_step:
                model_kwargs = dict(model_kwargs,
                                    **ragged_kwargs_by_step[step_idx])
            for instance_idx, device in enumerate(group.devices):
                in_queue, out_queues = fabric.get_queues(step_idx,
                                                         group_idx)
                ctx = RunnerContext(
                    in_queue=in_queue,
                    out_queues=out_queues,
                    queue_selector_path=group.queue_selector,
                    print_progress=(is_final and group_idx == 0
                                    and instance_idx == 0
                                    and print_progress),
                    job_id=job_id,
                    device=device,
                    group_idx=group_idx,
                    instance_idx=instance_idx,
                    counter=counter,
                    num_videos=num_videos,
                    termination=termination,
                    step_idx=step_idx,
                    sta_bar=sta_bar,
                    fin_bar=fin_bar,
                    model_class_path=step.model,
                    num_segments=step.num_segments,
                    input_rings=fabric.get_input_rings(step_idx, group_idx),
                    output_ring=fabric.get_output_ring(step_idx, group_idx,
                                                       instance_idx),
                    out_trackers=fabric.get_out_trackers(step_idx,
                                                         group_idx),
                    sync_outputs=not step.async_dispatch,
                    log_base=log_base,
                    model_kwargs=model_kwargs,
                    summary_sink=summary_sink if is_final else None,
                    containment=config.fault_containment,
                    overload_policy=config.overload_policy,
                    max_retries=step.max_retries,
                    retry_backoff_ms=step.retry_backoff_ms,
                    fault_plan=fault_plan,
                    fault_stats=fault_stats,
                    cache_sink=cache_sink,
                    staging_sink=staging_sink,
                    autotune=(autotune_settings if step.autotune
                              else None),
                    autotune_sink=autotune_sink,
                    pager=pager,
                    compile_sink=compile_sink,
                    pad_sink=pad_sink,
                    ragged_sink=ragged_sink,
                    stage_counter_sink=stage_counter_sink,
                    shard_sink=shard_sink,
                    tracer=tracer,
                    handoff_settings=handoff_settings,
                    handoff_edge=("step%d->step%d"
                                  % (step_idx - 1, step_idx)
                                  if step_idx > 0 else ""),
                    handoff_sink=handoff_sink,
                    placement_sink=placement_sink,
                    out_depths=depths_by_step.get(step_idx + 1),
                    out_queue_indices=(list(group.out_queues)
                                       if group.out_queues else None),
                    in_depths=(depths_by_step.get(step_idx)
                               if step.replica_queues
                               and group.in_queue
                               in step.replica_queues else None),
                    in_queue_idx=group.in_queue,
                    health_board=(boards_by_step.get(step_idx)
                                  if step.replica_queues
                                  and group.in_queue
                                  in step.replica_queues else None),
                    out_health_board=boards_by_step.get(step_idx + 1),
                    sibling_queues=(
                        {q: fabric.queues[step_idx - 1][q]
                         for q in step.replica_queues}
                        if step_idx > 0 and step.replica_queues
                        and group.in_queue in step.replica_queues
                        else None),
                    deadline=deadline_settings,
                    deadline_stats=deadline_stats,
                    out_hedges=governors_by_step.get(step_idx + 1),
                    in_hedges=(governors_by_step.get(step_idx)
                               if step.replica_queues
                               and group.in_queue
                               in step.replica_queues else None),
                )
                threads.append(threading.Thread(
                    target=runner, args=(ctx,),
                    name="runner-s%d-g%d-i%d" % (step_idx, group_idx,
                                                 instance_idx),
                    daemon=True))

    for t in threads:
        t.start()
    setup.launched()

    import resource

    from rnb_tpu.decode.native import DecodePool
    if tracer is not None:
        # occupancy sampling covers the measured window (plus the
        # short drain): the sampler starts here, so its counter tracks
        # hold nothing of warm-up; set-up's spans are in the timeline
        tracer.start_sampler()
    sta_bar.wait()
    setup.tracer.add_event(trace_mod.name("setup.run"), "X",
                           setup.run_start,
                           setup.released - setup.run_start, None, None)
    ru_start = resource.getrusage(resource.RUSAGE_SELF)
    decode_start = DecodePool.shared_stats()
    time_start = time.time()
    if print_progress:
        print("START! %f" % time_start)

    fin_bar.wait()
    time_end = time.time()
    # host-core accounting over the measured window: on the 1-core
    # bench host, (utime+stime)/wall ~ 1.0 means the host core is the
    # ceiling — the quantitative side of any "host-bound" claim. Taken
    # between the same barriers as the wall clock. Decode-pool threads
    # are in-process, so their CPU time is included.
    ru_end = resource.getrusage(resource.RUSAGE_SELF)
    host_cpu_s = ((ru_end.ru_utime + ru_end.ru_stime)
                  - (ru_start.ru_utime + ru_start.ru_stime))
    decode_end = DecodePool.shared_stats()
    total_time = time_end - time_start
    if print_progress:
        print("FINISH! %f" % time_end)
        print("Time: %f sec" % total_time)
        print("Number of videos: %d videos" % num_videos)

    for t in threads:
        t.join(timeout=60)

    # trace export: every thread is drained, so the event set is
    # final; clear the module hook BEFORE exporting so a later run in
    # this process can never write into this job's collector
    trace_events = trace_dropped = 0
    if tracer is not None:
        trace_mod.ACTIVE = None
        tracer.stop_sampler()
        trace_path = os.path.join(logroot(job_id, base=log_base),
                                  "trace.json")
        trace_events = tracer.export(trace_path, job_id)
        trace_dropped = tracer.dropped
        if print_progress:
            print("Trace: %d event(s) -> %s (%d dropped at the "
                  "max_events cap)"
                  % (trace_events, trace_path, trace_dropped))

    # set-up's record: what the Tracer held when the barrier released
    # (with the `trace` key, the same events are in trace.json too)
    setup_events = setup.events()
    trace_mod.export_events(
        setup_events, 0,
        os.path.join(logroot(job_id, base=log_base), "setup-trace.json"),
        job_id)
    setup_account = trace_mod.setup_account(
        setup_events, setup.run_start, setup.released)

    # per-request phase attribution (rnb_tpu.trace): aggregated over
    # every final-step instance's steady-state records — surfaced only
    # on trace-enabled runs so earlier logs stay byte-stable
    phases_stats = None
    if tracer is not None and summary_sink:
        from rnb_tpu.trace import phase_stats, sorted_phases
        merged: Dict[str, list] = {}
        for s in summary_sink:
            for phase, vals in s.phase_samples(
                    NUM_SUMMARY_SKIPS).items():
                merged.setdefault(phase, []).extend(vals)
        phases_stats = phase_stats(merged) or None

    # decoded-clip cache accounting: cache-owning stages appended
    # their final snapshots before the finish barrier (rnb_tpu.runner)
    cache_stats = None
    if cache_sink:
        from rnb_tpu.cache import aggregate_snapshots
        cache_stats = aggregate_snapshots(cache_sink)
    staging_stats = None
    if staging_sink:
        from rnb_tpu.staging import aggregate_snapshots as \
            aggregate_staging
        staging_stats = aggregate_staging(staging_sink)
    autotune_stats = None
    if autotune_sink:
        from rnb_tpu.autotune import aggregate_snapshots as \
            aggregate_autotune
        autotune_stats = aggregate_autotune(autotune_sink)
    # compile/warmup + padding + ragged accounting (every stage reports
    # warmup; jit-owning stages report signatures; batching stages
    # report pad counters; ragged stages report pool counters)
    from rnb_tpu.compilestats import aggregate_compile_records
    compile_stats, warmup_stats = aggregate_compile_records(compile_sink)
    pad_stats = None
    if pad_sink:
        pad_stats = {"pad_rows": 0, "total_rows": 0, "emissions": 0}
        for snap in pad_sink:
            for key in pad_stats:
                pad_stats[key] += int(snap.get(key, 0))
    ragged_stats = None
    if ragged_sink:
        ragged_stats = {"pool_rows": 0, "emissions": 0, "rows": 0,
                        "pad_rows_eliminated": 0, "cache_hit_rows": 0}
        for snap in ragged_sink:
            ragged_stats["pool_rows"] = max(
                ragged_stats["pool_rows"],
                int(snap.get("pool_rows") or 0))
            for key in ("emissions", "rows", "pad_rows_eliminated",
                        "cache_hit_rows"):
                ragged_stats[key] += int(snap.get(key, 0))

    # the stages' own counters (tokens, experts, attention tiles): the
    # lines to write and the result's fields, by telemetry's table
    from rnb_tpu.telemetry import stage_counter_report
    counter_lines, counter_fields = stage_counter_report(
        stage_counter_sink)

    # intra-stage shard accounting (rnb_tpu.parallel.shardplan):
    # declared-degree stages snapshot their merge-collective counters
    # at teardown; replica lanes of the same step sum, the static
    # facts (degree/axis/budgets) are per-step constants
    shard_stats = None
    if shard_sink:
        per_step: Dict[int, Dict[str, Any]] = {}
        for shard_step_idx, snap in shard_sink:
            row = per_step.setdefault(shard_step_idx, {
                "degree": int(snap.get("degree", 1)),
                "axis": str(snap.get("axis", "")),
                "gathers": 0, "collective_us": 0, "rows": 0,
                "budget_mb": round(float(snap.get("budget_mb") or 0.0),
                                   3),
                "projected_mb": round(
                    float(snap.get("projected_mb", 0.0)), 3),
                "min_degree": int(snap.get("min_degree", 0)),
            })
            row["gathers"] += int(snap.get("gathers", 0))
            row["collective_us"] += int(round(
                float(snap.get("collective_ms", 0.0)) * 1e3))
            row["rows"] += int(snap.get("rows", 0))
        shard_stats = {
            "steps": len(per_step),
            "max_degree": max(r["degree"] for r in per_step.values()),
            "gathers": sum(r["gathers"] for r in per_step.values()),
            "collective_us": sum(r["collective_us"]
                                 for r in per_step.values()),
            "rows": sum(r["rows"] for r in per_step.values()),
            "step_detail": {str(k): per_step[k]
                            for k in sorted(per_step)},
        }

    handoff_stats = None
    if handoff_sink:
        from rnb_tpu.handoff import aggregate_snapshots as \
            aggregate_handoff
        handoff_stats = aggregate_handoff(handoff_sink)
    # self-healing accounting (rnb_tpu.health): boards/governors are
    # shared objects, stable once every thread joined above
    health_stats = None
    if boards_by_step:
        from rnb_tpu.health import aggregate_board_snapshots
        health_stats = aggregate_board_snapshots(
            [b.snapshot() for b in boards_by_step.values()])
    deadline_snap = (deadline_stats.snapshot()
                     if deadline_stats is not None else None)
    # final witness ledger: every pipeline thread joined above, so the
    # edge set and violation list are settled (config-armed runs only
    # — an externally enabled witness, e.g. the test harness, keeps
    # un-armed runs' logs byte-stable)
    lock_snap = lockwitness.summary() if witness_armed else None
    hedge_stats = None
    if governors_by_step:
        from rnb_tpu.health import aggregate_hedge_snapshots
        hedge_stats = aggregate_hedge_snapshots(
            [g.snapshot() for g in governors_by_step.values()])
    placement_report = None
    if placement_sink is not None:
        import jax
        from rnb_tpu.placement import build_report
        placement_report = build_report(placement_sink, total_time,
                                        len(jax.devices()),
                                        placement_settings.mode)

    # paged-memory ledger (rnb_tpu.pager): every pipeline thread
    # joined, so live/limbo occupancy is settled and the teardown
    # invariant (allocs == frees + live-held pages) is checkable from
    # the line alone; bypassed_batches rides along from the staging
    # plane (the zero-transfer emissions only the pager can produce)
    pages_summary = None
    if pager is not None:
        pages_summary = pager.snapshot()
        pages_summary["bypassed_batches"] = int(
            staging_stats.get("bypassed_batches", 0)
            if staging_stats else 0)

    faults = fault_stats.snapshot()
    num_failed = faults["num_failed"]
    num_shed = faults["num_shed"]
    num_retries = faults["num_retries"]
    # every disposal (success, contained failure, shed) lands in the
    # shared counter; successes are what remains
    num_completed = max(0, counter.value - num_failed - num_shed)

    args_repr = ("Namespace(mean_interval_ms=%d, batch_size=%d, videos=%d, "
                 "queue_size=%d, config_file_path=%r)"
                 % (mean_interval_ms, batch_size, num_videos, queue_size,
                    config_path))
    with open(logmeta(job_id, base=log_base), "w") as f:
        f.write("Args: %s\n" % args_repr)
        f.write("%f %f\n" % (time_start, time_end))
        f.write("Termination flag: %d\n" % termination.value)
        f.write("Faults: num_failed=%d num_shed=%d num_retries=%d\n"
                % (num_failed, num_shed, num_retries))
        if faults["failure_reasons"]:
            f.write("Failure reasons: %s\n"
                    % json.dumps(faults["failure_reasons"],
                                 sort_keys=True))
        if faults["shed_sites"]:
            f.write("Shed sites: %s\n"
                    % json.dumps(faults["shed_sites"], sort_keys=True))
        if faults["overflow_sites"]:
            # abort-policy full-queue events, counted per edge — the
            # parseable replacement for the old stdout warning
            f.write("Queue overflows: %s\n"
                    % json.dumps(faults["overflow_sites"],
                                 sort_keys=True))
        if cache_stats is not None:
            # only cache-enabled runs carry the line, keeping cacheless
            # logs byte-stable with the pre-cache schema
            f.write("Cache: hits=%d misses=%d inserts=%d evictions=%d "
                    "coalesced=%d oversize=%d bytes_resident=%d\n"
                    % (cache_stats["hits"], cache_stats["misses"],
                       cache_stats["inserts"], cache_stats["evictions"],
                       cache_stats["coalesced"], cache_stats["oversize"],
                       cache_stats["bytes_resident"]))
        if staging_stats is not None:
            # only staging-enabled runs carry the line, keeping
            # staging-free logs byte-stable with the earlier schema
            f.write("Staging: slots=%d slot_bytes=%d acquires=%d "
                    "acquire_waits=%d staged_batches=%d "
                    "copied_batches=%d reallocs=%d\n"
                    % (staging_stats["slots"],
                       staging_stats["slot_bytes"],
                       staging_stats["acquires"],
                       staging_stats["acquire_waits"],
                       staging_stats["staged_batches"],
                       staging_stats["copied_batches"],
                       staging_stats["reallocs"]))
        if pages_summary is not None:
            # only pager-enabled runs carry the line, keeping pager-off
            # logs (including the Staging: line above) byte-stable with
            # the earlier schema; --check holds allocs == frees + live
            # at teardown, feature_hits <= feature_lookups, and
            # gather_rows <= the ragged cache_hit_rows it serves
            f.write("Pages: arenas=%d pages=%d page_rows=%d live=%d "
                    "limbo=%d bytes=%d allocs=%d frees=%d "
                    "alloc_fails=%d gathers=%d gather_rows=%d "
                    "feature_lookups=%d feature_hits=%d "
                    "feature_inserts=%d feature_evictions=%d "
                    "feature_gathers=%d feature_gather_rows=%d "
                    "feature_bytes_saved=%d feature_entries=%d "
                    "bypassed_batches=%d\n"
                    % (pages_summary["arenas"], pages_summary["pages"],
                       pages_summary["page_rows"],
                       pages_summary["live"], pages_summary["limbo"],
                       pages_summary["bytes"],
                       pages_summary["allocs"], pages_summary["frees"],
                       pages_summary["alloc_fails"],
                       pages_summary["gathers"],
                       pages_summary["gather_rows"],
                       pages_summary["feature_lookups"],
                       pages_summary["feature_hits"],
                       pages_summary["feature_inserts"],
                       pages_summary["feature_evictions"],
                       pages_summary["feature_gathers"],
                       pages_summary["feature_gather_rows"],
                       pages_summary["feature_bytes_saved"],
                       pages_summary["feature_entries"],
                       pages_summary["bypassed_batches"]))
        if autotune_stats is not None:
            # only autotune-enabled runs carry the lines, keeping
            # static-batching logs byte-stable with the earlier schema
            f.write("Autotune: decisions=%d immediate=%d held=%d "
                    "emissions=%d deadline_us_min=%d "
                    "deadline_us_max=%d deadline_us_sum=%d\n"
                    % (autotune_stats["decisions"],
                       autotune_stats["immediate"],
                       autotune_stats["held"],
                       autotune_stats["emissions"],
                       autotune_stats["deadline_us_min"],
                       autotune_stats["deadline_us_max"],
                       autotune_stats["deadline_us_sum"]))
            if autotune_stats["bucket_counts"]:
                f.write("Autotune buckets: %s\n"
                        % json.dumps(autotune_stats["bucket_counts"],
                                     sort_keys=True))
        if pad_stats is not None:
            # padding-waste accounting over every batching stage: the
            # bucketed path quantifies its pad work; a ragged run shows
            # ~0 here (pad FLOPs land in Ragged: pad_rows_eliminated)
            f.write("Padding: pad_rows=%d total_rows=%d "
                    "pad_emissions=%d\n"
                    % (pad_stats["pad_rows"], pad_stats["total_rows"],
                       pad_stats["emissions"]))
        for line in counter_lines:
            f.write(line + "\n")
        if ragged_stats is not None:
            # only ragged-enabled runs carry the line, keeping bucketed
            # logs byte-stable with the earlier schema
            f.write("Ragged: pool_rows=%d emissions=%d rows=%d "
                    "pad_rows_eliminated=%d cache_hit_rows=%d\n"
                    % (ragged_stats["pool_rows"],
                       ragged_stats["emissions"], ragged_stats["rows"],
                       ragged_stats["pad_rows_eliminated"],
                       ragged_stats["cache_hit_rows"]))
        if shard_stats is not None:
            # only declared-shard runs carry the lines, keeping
            # unsharded logs byte-stable with the earlier schema;
            # --check holds degree x replicas <= the device budget,
            # collective_us <= the inference span sum (the merge is
            # nested inside model_call), and per-step rows footing
            f.write("Shard: steps=%d max_degree=%d gathers=%d "
                    "collective_us=%d rows=%d\n"
                    % (shard_stats["steps"],
                       shard_stats["max_degree"],
                       shard_stats["gathers"],
                       shard_stats["collective_us"],
                       shard_stats["rows"]))
            f.write("Shard steps: %s\n"
                    % json.dumps(shard_stats["step_detail"],
                                 sort_keys=True))
        if handoff_stats is not None:
            # only handoff-enabled runs carry the lines, keeping
            # pre-handoff logs byte-stable with the earlier schema;
            # d2d_edges + host_edges == edges and host_bytes == 0 on
            # device-resident edges are --check invariants
            f.write("Handoff: edges=%d d2d_edges=%d host_edges=%d "
                    "d2d_bytes=%d host_bytes=%d\n"
                    % (handoff_stats["edges"],
                       handoff_stats["d2d_edges"],
                       handoff_stats["host_edges"],
                       handoff_stats["d2d_bytes"],
                       handoff_stats["host_bytes"]))
            if handoff_stats["edge_detail"]:
                f.write("Handoff edges: %s\n"
                        % json.dumps(handoff_stats["edge_detail"],
                                     sort_keys=True))
        if placement_report is not None:
            # the measured-cost plan: per-step dispatch costs, the
            # executed plan's predicted occupancy (parse_utils --check
            # holds it to the traced busy fraction), and the
            # recommendation over the device budget
            f.write("Placement: %s\n"
                    % json.dumps(placement_report, sort_keys=True))
        if health_stats is not None:
            # only health-enabled replica runs carry the lines (logs
            # stay byte-stable otherwise); --check replays every
            # lane's path against the legal automaton and holds
            # routes_after_open to 0
            f.write("Health: lanes=%d transitions=%d opens=%d "
                    "evictions=%d probes=%d redispatches=%d "
                    "routes_after_open=%d\n"
                    % (health_stats["lanes"],
                       health_stats["transitions"],
                       health_stats["opens"],
                       health_stats["evictions"],
                       health_stats["probes"],
                       health_stats["redispatches"],
                       health_stats["routes_after_open"]))
            if health_stats["lane_detail"]:
                f.write("Health lanes: %s\n"
                        % json.dumps(health_stats["lane_detail"],
                                     sort_keys=True))
        if deadline_snap is not None:
            # only deadline-enabled runs carry the lines; --check
            # cross-foots the per-site counts against the
            # deadline-suffixed entries of the Shed sites: ledger
            f.write("Deadline: budget_ms=%d expired=%d\n"
                    % (round(deadline_settings.budget_ms),
                       deadline_snap["expired"]))
            if deadline_snap["sites"]:
                f.write("Deadline sites: %s\n"
                        % json.dumps(deadline_snap["sites"],
                                     sort_keys=True))
        if hedge_stats is not None:
            # only hedge_ms runs carry the line; won + lost == fired
            # is a --check invariant (every fired hedge resolves
            # exactly once), and wasted_ms is the honesty counter —
            # hedge compute is overhead, never throughput
            f.write("Hedge: fired=%d won=%d lost=%d wasted_ms=%d\n"
                    % (hedge_stats["fired"], hedge_stats["won"],
                       hedge_stats["lost"], hedge_stats["wasted_ms"]))
        if compile_stats:
            # per-step jit-entry signatures: warmup vocabulary size +
            # signatures first seen inside the measured window
            # (steady_new > 0 = mid-run recompile; --check fails it)
            f.write("Compiles: %s\n"
                    % json.dumps(compile_stats, sort_keys=True))
        if warmup_stats:
            f.write("Warmup: %s\n"
                    % json.dumps(warmup_stats, sort_keys=True))
        if setup_account:
            f.write("Setup: %s\n"
                    % json.dumps(setup_account, sort_keys=True))
        if tracer is not None:
            # trace-export accounting: events written to trace.json
            # and events dropped at the max_events cap — parse_utils
            # --check cross-checks the count against the artifact
            f.write("Trace: events=%d dropped=%d\n"
                    % (trace_events, trace_dropped))
        if phases_stats is not None:
            # only trace-enabled runs carry the line: per-phase
            # mean/p99/count, phases summing to end-to-end latency
            # per request (parse_utils --check asserts it)
            f.write("Phases: %s\n"
                    % json.dumps(phases_stats, sort_keys=True))
        if lock_snap is not None:
            # witness-armed runs only; --check holds violations to
            # zero, the Lock edges: detail to these counts, and every
            # observed edge to the static RNB-C lock-order graph
            f.write("Locks: tracked=%d acquires=%d edges=%d "
                    "violations=%d\n"
                    % (lock_snap["locks"], lock_snap["acquires"],
                       len(lock_snap["edges"]),
                       len(lock_snap["violations"])))
            f.write("Lock edges: %s\n"
                    % lockwitness.format_edges(lock_snap))
    if faults["dead_letters"]:
        # the controller's dead-letter record: one line per contained
        # failure (detail capped at FaultStats.MAX_DEAD_LETTERS; the
        # counters above stay exact regardless)
        with open(os.path.join(logroot(job_id, base=log_base),
                               "failed-requests.txt"), "w") as f:
            f.write("# request_id step reason\n")
            for rid, step_idx, reason in faults["dead_letters"]:
                f.write("%s %d %s\n" % (rid, step_idx, reason))
    shutil.copyfile(config_path,
                    os.path.join(logroot(job_id, base=log_base),
                                 os.path.basename(config_path)))

    # aggregate end-to-end latency percentiles over every final-step
    # instance, skipping warm records per the summary convention
    from rnb_tpu.telemetry import latency_percentiles
    latencies = []
    clips_completed = 0
    for s in summary_sink:
        latencies.extend(s.latencies_ms(NUM_SUMMARY_SKIPS))
        clips_completed += s.total_clips()
    pct = latency_percentiles(latencies)
    p50, p99 = pct.get(50.0), pct.get(99.0)
    if pct and print_progress:
        print("Latency p50: %.3f ms  p99: %.3f ms (%d steady-state "
              "records, successes only)" % (p50, p99, len(latencies)))
    if (num_failed or num_shed or num_retries) and print_progress:
        print("Faults: %d failed, %d shed, %d retries (%s)"
              % (num_failed, num_shed, num_retries,
                 ", ".join("%s=%d" % kv for kv in sorted(
                     faults["failure_reasons"].items())) or "-"))
    if cache_stats is not None and print_progress:
        lookups = cache_stats["hits"] + cache_stats["misses"]
        print("Cache: %d hits / %d lookups (%.1f%% hit-rate), "
              "%d coalesced, %d evictions, %.1f MiB resident"
              % (cache_stats["hits"], lookups,
                 100.0 * cache_stats["hits"] / lookups if lookups else 0.0,
                 cache_stats["coalesced"], cache_stats["evictions"],
                 cache_stats["bytes_resident"] / (1 << 20)))
    if staging_stats is not None and print_progress:
        emissions = (staging_stats["staged_batches"]
                     + staging_stats["copied_batches"])
        print("Staging: %d/%d emissions zero-copy, %d slot(s) "
              "(%.1f MiB), %d acquire wait(s), %d realloc(s)"
              % (staging_stats["staged_batches"], emissions,
                 staging_stats["slots"],
                 staging_stats["slot_bytes"] / (1 << 20),
                 staging_stats["acquire_waits"],
                 staging_stats["reallocs"]))
    if pages_summary is not None and print_progress:
        print("Pages: %d/%d pages live (%.1f MiB slab), %d gathers "
              "(%d rows), feature %d/%d hits, %d emission(s) with "
              "zero transfer bytes"
              % (pages_summary["live"], pages_summary["pages"],
                 pages_summary["bytes"] / (1 << 20),
                 pages_summary["gathers"] + pages_summary["feature_gathers"],
                 pages_summary["gather_rows"]
                 + pages_summary["feature_gather_rows"],
                 pages_summary["feature_hits"],
                 pages_summary["feature_lookups"],
                 pages_summary["bypassed_batches"]))
    if autotune_stats is not None and print_progress:
        print("Autotune: %d decision(s) (%d immediate / %d held), "
              "%d emission(s), buckets %s"
              % (autotune_stats["decisions"],
                 autotune_stats["immediate"], autotune_stats["held"],
                 autotune_stats["emissions"],
                 json.dumps(autotune_stats["bucket_counts"],
                            sort_keys=True)))
    if handoff_stats is not None and print_progress:
        print("Handoff: %d edge take(s) — %d d2d (%.1f MiB on-device) "
              "/ %d host (%.1f MiB through host memory)"
              % (handoff_stats["edges"], handoff_stats["d2d_edges"],
                 handoff_stats["d2d_bytes"] / (1 << 20),
                 handoff_stats["host_edges"],
                 handoff_stats["host_bytes"] / (1 << 20)))
    if placement_report is not None and print_progress:
        print("Placement plan (predicted occupancy over %d devices): %s"
              % (placement_report["device_budget"],
                 json.dumps(placement_report["plan"], sort_keys=True)))
    if health_stats is not None and print_progress:
        print("Health: %d lane(s), %d transition(s), %d open(s), "
              "%d eviction(s), %d probe(s), %d redispatch(es)"
              % (health_stats["lanes"], health_stats["transitions"],
                 health_stats["opens"], health_stats["evictions"],
                 health_stats["probes"],
                 health_stats["redispatches"]))
    if deadline_snap is not None and print_progress:
        print("Deadline: budget %d ms, %d expired request(s) shed (%s)"
              % (round(deadline_settings.budget_ms),
                 deadline_snap["expired"],
                 ", ".join("%s=%d" % kv for kv in sorted(
                     deadline_snap["sites"].items())) or "-"))
    if hedge_stats is not None and print_progress:
        print("Hedge: %d fired, %d won by the hedge / %d by the "
              "original, %d ms of loser service wasted"
              % (hedge_stats["fired"], hedge_stats["won"],
                 hedge_stats["lost"], hedge_stats["wasted_ms"]))
    if lock_snap is not None and print_progress:
        print("Locks: %d witnessed lock(s), %d acquisition(s), "
              "%d order edge(s), %d violation(s)"
              % (lock_snap["locks"], lock_snap["acquires"],
                 len(lock_snap["edges"]),
                 len(lock_snap["violations"])))
    if ragged_stats is not None and print_progress:
        print("Ragged: %d emission(s), %d valid row(s) at pool_rows=%d"
              ", %d pad row(s) eliminated vs the bucketed rule, "
              "%d cache-hit row(s)"
              % (ragged_stats["emissions"], ragged_stats["rows"],
                 ragged_stats["pool_rows"],
                 ragged_stats["pad_rows_eliminated"],
                 ragged_stats["cache_hit_rows"]))
    recompiled = sorted(step for step, sigs in compile_stats.items()
                        if sigs.get("steady_new", 0) > 0)
    if recompiled:
        # a signature first seen inside the measured window is a
        # silent XLA compile on the hot path — exactly what warmup
        # (and the ragged one-shape contract) exists to prevent
        print("[rnb-tpu] WARNING: mid-run recompile signature(s) on %s "
              "(Compiles: steady_new > 0)" % ", ".join(recompiled),
              file=sys.stderr)
    if phases_stats is not None and print_progress:
        print("Phases (per-request attribution, mean/p99 ms):")
        for phase in sorted_phases(phases_stats):
            s = phases_stats[phase]
            print("  %-18s %8.3f / %8.3f  (n=%d)"
                  % (phase, s["mean_ms"], s["p99_ms"], s["count"]))

    return BenchmarkResult(
        job_id=job_id,
        total_time_s=total_time,
        num_videos=num_videos,
        termination_flag=int(termination.value),
        # successes only: shed/failed requests must not inflate the
        # headline rate (success-rate and shed-rate are first-class
        # metrics next to it)
        throughput_vps=(num_completed / total_time if total_time > 0
                        else 0.0),
        log_dir=logroot(job_id, base=log_base),
        p50_latency_ms=p50,
        p99_latency_ms=p99,
        clips_completed=clips_completed,
        host_cpu_s=host_cpu_s,
        decode_busy_s=decode_end["busy_s"] - decode_start["busy_s"],
        decode_frames=decode_end["frames"] - decode_start["frames"],
        num_completed=num_completed,
        num_failed=num_failed,
        num_shed=num_shed,
        num_retries=num_retries,
        failure_reasons=dict(faults["failure_reasons"]),
        shed_sites=dict(faults["shed_sites"]),
        cache_hits=cache_stats["hits"] if cache_stats else 0,
        cache_misses=cache_stats["misses"] if cache_stats else 0,
        cache_inserts=cache_stats["inserts"] if cache_stats else 0,
        cache_evictions=cache_stats["evictions"] if cache_stats else 0,
        cache_coalesced=cache_stats["coalesced"] if cache_stats else 0,
        cache_oversize=cache_stats["oversize"] if cache_stats else 0,
        cache_bytes_resident=(cache_stats["bytes_resident"]
                              if cache_stats else 0),
        staging_slots=staging_stats["slots"] if staging_stats else 0,
        staging_slot_bytes=(staging_stats["slot_bytes"]
                            if staging_stats else 0),
        staging_acquires=(staging_stats["acquires"]
                          if staging_stats else 0),
        staging_acquire_waits=(staging_stats["acquire_waits"]
                               if staging_stats else 0),
        staging_staged_batches=(staging_stats["staged_batches"]
                                if staging_stats else 0),
        staging_copied_batches=(staging_stats["copied_batches"]
                                if staging_stats else 0),
        staging_reallocs=(staging_stats["reallocs"]
                          if staging_stats else 0),
        autotune_decisions=(autotune_stats["decisions"]
                            if autotune_stats else 0),
        autotune_immediate=(autotune_stats["immediate"]
                            if autotune_stats else 0),
        autotune_held=autotune_stats["held"] if autotune_stats else 0,
        autotune_emissions=(autotune_stats["emissions"]
                            if autotune_stats else 0),
        autotune_deadline_us_min=(autotune_stats["deadline_us_min"]
                                  if autotune_stats else 0),
        autotune_deadline_us_max=(autotune_stats["deadline_us_max"]
                                  if autotune_stats else 0),
        autotune_deadline_us_sum=(autotune_stats["deadline_us_sum"]
                                  if autotune_stats else 0),
        autotune_bucket_counts=(dict(autotune_stats["bucket_counts"])
                                if autotune_stats else {}),
        queue_overflows=dict(faults["overflow_sites"]),
        phases=dict(phases_stats) if phases_stats else {},
        trace_events=trace_events,
        trace_dropped=trace_dropped,
        pad_rows=pad_stats["pad_rows"] if pad_stats else 0,
        total_rows=pad_stats["total_rows"] if pad_stats else 0,
        pad_emissions=pad_stats["emissions"] if pad_stats else 0,
        **counter_fields,
        ragged_pool_rows=(ragged_stats["pool_rows"]
                          if ragged_stats else 0),
        ragged_emissions=(ragged_stats["emissions"]
                          if ragged_stats else 0),
        ragged_rows=ragged_stats["rows"] if ragged_stats else 0,
        ragged_pad_rows_eliminated=(
            ragged_stats["pad_rows_eliminated"] if ragged_stats else 0),
        ragged_cache_hit_rows=(ragged_stats["cache_hit_rows"]
                               if ragged_stats else 0),
        shard_steps=shard_stats["steps"] if shard_stats else 0,
        shard_max_degree=(shard_stats["max_degree"]
                          if shard_stats else 0),
        shard_gathers=shard_stats["gathers"] if shard_stats else 0,
        shard_collective_us=(shard_stats["collective_us"]
                             if shard_stats else 0),
        shard_rows=shard_stats["rows"] if shard_stats else 0,
        shard_step_detail=(dict(shard_stats["step_detail"])
                           if shard_stats else {}),
        pages=dict(pages_summary) if pages_summary else {},
        compile_signatures=compile_stats,
        warmup_s=warmup_stats,
        setup={"entered": setup.entered, "run_start": setup.run_start,
               "released": setup.released,
               "events": [(e[0], e[2], e[3], e[4], e[6] or {})
                          for e in setup_events
                          if e[0].startswith("setup.")]},
        handoff_edges=handoff_stats["edges"] if handoff_stats else 0,
        handoff_d2d_edges=(handoff_stats["d2d_edges"]
                           if handoff_stats else 0),
        handoff_host_edges=(handoff_stats["host_edges"]
                            if handoff_stats else 0),
        handoff_d2d_bytes=(handoff_stats["d2d_bytes"]
                           if handoff_stats else 0),
        handoff_host_bytes=(handoff_stats["host_bytes"]
                            if handoff_stats else 0),
        handoff_edge_detail=(dict(handoff_stats["edge_detail"])
                             if handoff_stats else {}),
        placement=placement_report or {},
        health_lanes=health_stats["lanes"] if health_stats else 0,
        health_transitions=(health_stats["transitions"]
                            if health_stats else 0),
        health_opens=health_stats["opens"] if health_stats else 0,
        health_evictions=(health_stats["evictions"]
                          if health_stats else 0),
        health_probes=health_stats["probes"] if health_stats else 0,
        health_redispatches=(health_stats["redispatches"]
                             if health_stats else 0),
        health_routes_after_open=(health_stats["routes_after_open"]
                                  if health_stats else 0),
        health_lane_detail=(dict(health_stats["lane_detail"])
                            if health_stats else {}),
        deadline_budget_ms=(int(round(deadline_settings.budget_ms))
                            if deadline_settings is not None else 0),
        deadline_expired=(deadline_snap["expired"]
                          if deadline_snap else 0),
        deadline_sites=(dict(deadline_snap["sites"])
                        if deadline_snap else {}),
        hedges_fired=hedge_stats["fired"] if hedge_stats else 0,
        hedges_won=hedge_stats["won"] if hedge_stats else 0,
        hedges_lost=hedge_stats["lost"] if hedge_stats else 0,
        hedges_wasted_ms=(hedge_stats["wasted_ms"]
                          if hedge_stats else 0),
        locks_tracked=(lock_snap["locks"] if lock_snap else 0),
        locks_acquires=(lock_snap["acquires"] if lock_snap else 0),
        locks_edges=(len(lock_snap["edges"]) if lock_snap else 0),
        locks_violations=(len(lock_snap["violations"])
                          if lock_snap else 0),
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="TPU-native streaming video-analytics benchmark")
    parser.add_argument("-mi", "--mean_interval_ms",
                        help="Mean request interval (Poisson), ms; "
                             "0 = bulk max-throughput mode",
                        type=nonnegative_int, default=3)
    parser.add_argument("-b", "--batch_size",
                        help="Video batch size per replica",
                        type=positive_int, default=1)
    parser.add_argument("-v", "--videos",
                        help="Total number of videos to run",
                        type=positive_int, default=2000)
    parser.add_argument("-qs", "--queue_size",
                        help="Max size of inter-stage queues",
                        type=positive_int, default=50000)
    parser.add_argument("-c", "--config_file_path",
                        help="Pipeline configuration JSON",
                        type=str, default="configs/r2p1d-whole.json")
    parser.add_argument("--check", action="store_true",
                        help="Quick import smoke test, then exit")
    parser.add_argument("--platform", choices=["auto", "cpu"],
                        default="auto",
                        help="'auto' runs on the TPU and fails if JAX "
                             "finds none; the (virtual) CPU backend has "
                             "to be asked for: 'cpu'")
    parser.add_argument("--log-base", type=str, default="logs")
    parser.add_argument("--seed", type=int, default=None)
    args = parser.parse_args(argv)

    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    if args.check:
        import jax  # noqa: F401
        import flax  # noqa: F401
        from rnb_tpu import control, runner, client  # noqa: F401
        from rnb_tpu.models.r2p1d import model  # noqa: F401
        # validate the named config against the full (extended) schema
        # and surface its robustness posture — the knobs an operator
        # needs to know before pointing traffic at the pipeline
        from rnb_tpu.config import load_config
        from rnb_tpu.faults import FaultPlan
        cfg = load_config(args.config_file_path)
        retries = ", ".join(
            "step%d: %d@%gms" % (i, s.max_retries, s.retry_backoff_ms)
            for i, s in enumerate(cfg.steps) if s.max_retries) or "none"
        print("config %s: %d step(s), overload_policy=%s, "
              "fault_containment=%s, retries: %s"
              % (args.config_file_path, cfg.num_steps,
                 cfg.overload_policy, cfg.fault_containment, retries))
        plan = FaultPlan.resolve(cfg.fault_plan)
        if plan is not None:
            plan.check_steps(cfg.num_steps)
        print("fault plan: %s"
              % (plan.describe() if plan is not None else "none"))
        caches = ", ".join(
            "step%d: %g MB" % (i, s.extras["cache_mb"])
            for i, s in enumerate(cfg.steps)
            if s.extras.get("cache_mb")) or "none"
        print("clip cache: %s; popularity: %s"
              % (caches, json.dumps(cfg.popularity, sort_keys=True)
                 if cfg.popularity else "none"))
        opted_out = [i for i, s in enumerate(cfg.steps)
                     if not s.autotune]
        print("autotune: %s%s"
              % (json.dumps(cfg.autotune, sort_keys=True)
                 if cfg.autotune else "none",
                 "; opted-out steps: %s" % opted_out
                 if opted_out else ""))
        print("ragged: %s"
              % (json.dumps(cfg.ragged, sort_keys=True)
                 if cfg.ragged else "none"))
        print("handoff: %s"
              % (json.dumps(cfg.handoff, sort_keys=True)
                 if cfg.handoff else "none"))
        replicated = {"step%d" % i: len(s.replica_queues)
                      for i, s in enumerate(cfg.steps)
                      if s.replica_queues}
        print("placement: %s%s"
              % (json.dumps(cfg.placement, sort_keys=True)
                 if cfg.placement else "none",
                 "; replica lanes: %s" % json.dumps(replicated,
                                                    sort_keys=True)
                 if replicated else ""))
        print("trace: %s"
              % (json.dumps(cfg.trace, sort_keys=True)
                 if cfg.trace else "none"))
        hedged = {"step%d" % i: s.hedge_ms
                  for i, s in enumerate(cfg.steps)
                  if s.hedge_ms is not None}
        print("health: %s; deadline: %s; hedging: %s"
              % (json.dumps(cfg.health, sort_keys=True)
                 if cfg.health else "none",
                 json.dumps(cfg.deadline, sort_keys=True)
                 if cfg.deadline else "none",
                 json.dumps(hedged, sort_keys=True)
                 if hedged else "none"))
        print("rnb_tpu is ready to go!")
        return 0

    print("Args:", args)
    if args.platform == "auto":
        # JAX falls back to the CPU when no accelerator initializes; a
        # run that nobody asked to put there must not look like one
        from rnb_tpu.devices import require_platform
        require_platform("tpu")
    result = run_benchmark(
        config_path=args.config_file_path,
        mean_interval_ms=args.mean_interval_ms,
        batch_size=args.batch_size,
        num_videos=args.videos,
        queue_size=args.queue_size,
        log_base=args.log_base,
        seed=args.seed,
    )
    print("Throughput: %.3f videos/s" % result.throughput_vps)
    print("Logs: %s" % result.log_dir)
    return 0 if result.termination_flag == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
