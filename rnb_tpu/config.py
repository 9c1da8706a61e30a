"""Pipeline configuration: JSON schema, parsing and validation.

A pipeline config names a video-path iterator plus an ordered list of
*steps*; each step names a stage-model class and a list of *queue
groups* placing replicas on devices and wiring them to numbered
inter-stage queues. Any step/group key outside the reserved schema is
forwarded verbatim to the stage constructor — the open kwargs
passthrough that makes every model parameter configurable from JSON.

Schema and validation parity with the reference (benchmark.py:23-125):
same step/group structure, same queue-wiring rule (the out-queue set of
step i must equal the in-queue set of step i+1), same last-step
constraints (no multi-segment, no shared output tensors), same reserved
keyword handling. TPU-first changes: the placement key is ``devices``
(``gpus`` accepted as an alias for drop-in use of reference configs),
-1 places a group on the host, and the availability probe inspects
`jax.devices()` instead of NVML.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

from rnb_tpu.devices import DeviceSpec

RESERVED_KEYWORDS = [
    "model", "queue_groups", "num_shared_tensors", "num_segments",
    "in_queue", "out_queues", "devices", "gpus", "queue_selector",
    "async_dispatch", "max_retries", "retry_backoff_ms", "autotune",
    "replicas", "hedge_ms", "shard",
]

#: keys a step-level 'shard' object may carry
#: (rnb_tpu.parallel.shardplan)
SHARD_KEYWORDS = ["degree", "axis", "hbm_budget_mb"]

#: root-level keys with meaning to the runtime (everything else at the
#: root is rejected to catch typos like "overload_polcy")
ROOT_KEYWORDS = [
    "video_path_iterator", "pipeline", "overload_policy",
    "fault_containment", "fault_plan", "popularity", "autotune",
    "trace", "ragged", "pager", "handoff", "placement", "health",
    "deadline", "lint",
    "_comment",
]

#: keys a root 'popularity' object may carry
POPULARITY_KEYWORDS = ["dist", "s", "universe"]

#: keys a root 'autotune' object may carry (rnb_tpu.autotune)
AUTOTUNE_KEYWORDS = ["enabled", "slo_ms", "ewma_alpha", "min_hold_ms",
                     "max_hold_ms", "buckets"]

#: keys a root 'trace' object may carry (rnb_tpu.trace)
TRACE_KEYWORDS = ["enabled", "sample_hz", "max_events"]

#: keys a root 'ragged' object may carry (rnb_tpu.ops.ragged)
RAGGED_KEYWORDS = ["enabled", "pool_rows"]

#: keys a root 'pager' object may carry (rnb_tpu.pager)
PAGER_KEYWORDS = ["enabled", "page_rows", "pool_mb", "feature_cache"]

#: keys a root 'handoff' object may carry (rnb_tpu.handoff)
HANDOFF_KEYWORDS = ["enabled", "mode"]

#: keys a root 'placement' object may carry (rnb_tpu.placement)
PLACEMENT_KEYWORDS = ["enabled", "mode", "plan"]

#: keys a root 'health' object may carry (rnb_tpu.health)
HEALTH_KEYWORDS = ["enabled", "suspect_after_ms", "open_after_ms",
                   "probe_interval_ms"]

#: keys a root 'deadline' object may carry (rnb_tpu.health)
DEADLINE_KEYWORDS = ["enabled", "budget_ms"]

#: keys a root 'lint' object may carry (runtime arms of the
#: rnb-lint analyzers; today just the RNB-C lock-order witness)
LINT_KEYWORDS = ["lock_witness"]

#: Ring slots per stage instance when a step omits 'num_shared_tensors'
#: (reference control.py:8). Lives here (not control.py) so validation
#: can check the effective slot count at parse time.
DEFAULT_NUM_SHARED_TENSORS = 10


def _effective_shared_tensors(num_shared_tensors: Optional[int]) -> int:
    """The one defaulting rule for ring depth — used by parse-time
    validation and by StepConfig.effective_shared_tensors (which
    ChannelFabric allocation reads)."""
    return (num_shared_tensors if num_shared_tensors is not None
            else DEFAULT_NUM_SHARED_TENSORS)

DEFAULT_QUEUE_SELECTOR = "rnb_tpu.selector.RoundRobinSelector"

#: the selector replica expansion swaps in for the default on the
#: producer side of a replica-expanded edge (least-loaded routing)
REPLICA_QUEUE_SELECTOR = "rnb_tpu.selector.ReplicaSelector"


class ConfigError(ValueError):
    """Malformed pipeline configuration."""


def _expect(cond: bool, message: str) -> None:
    if not cond:
        raise ConfigError(message)


@dataclasses.dataclass
class GroupConfig:
    """One queue group: replicas on `devices` sharing one in-queue and a
    selector-routed set of out-queues."""

    devices: List[DeviceSpec]
    in_queue: Optional[int]
    out_queues: List[int]
    queue_selector: str
    extras: Dict[str, Any]

    @property
    def num_instances(self) -> int:
        return len(self.devices)


@dataclasses.dataclass
class StepConfig:
    """One pipeline step: a stage-model class fanned out over groups."""

    model: str
    groups: List[GroupConfig]
    num_segments: int
    num_shared_tensors: Optional[int]
    extras: Dict[str, Any]
    #: publish outputs without blocking on device completion (timing
    #: then measures dispatch, not compute — see rnb_tpu.runner)
    async_dispatch: bool = False
    #: containment retry budget for *transient* errors escaping this
    #: step's model call (rnb_tpu.faults taxonomy): up to max_retries
    #: re-attempts with retry_backoff_ms of sleep between them; an
    #: exhausted budget degrades the request to a contained permanent
    #: failure. Default 0 = fail on first transient.
    max_retries: int = 0
    retry_backoff_ms: float = 10.0
    #: False opts this step out of the job's load-adaptive batching
    #: controller (root 'autotune' key, rnb_tpu.autotune); the step
    #: then keeps its static batching knobs exactly as configured
    autotune: bool = True
    #: set on replica-expanded steps (step key ``replicas: N`` or a
    #: placement-apply plan): the per-replica lane queue indices, in
    #: replica order. The launcher builds the shared
    #: rnb_tpu.handoff.InflightDepths over these so the upstream
    #: ReplicaSelector routes least-loaded (rnb_tpu.selector).
    replica_queues: Optional[tuple] = None
    #: hedged re-dispatch threshold for dispatches INTO this
    #: replica-expanded step (rnb_tpu.health.HedgeGovernor): a
    #: positive millisecond count, or "p95x" for the governor's own
    #: settle-latency p95 estimate. None = no hedging.
    hedge_ms: Optional[object] = None

    @property
    def effective_shared_tensors(self) -> int:
        """Ring slots per producer instance after defaulting."""
        return _effective_shared_tensors(self.num_shared_tensors)

    def kwargs_for_group(self, group_idx: int) -> Dict[str, Any]:
        """Model-constructor kwargs: step extras overridden by group extras
        (reference benchmark.py:241-246)."""
        merged = dict(self.extras)
        merged.update(self.groups[group_idx].extras)
        return merged


@dataclasses.dataclass
class PipelineConfig:
    video_path_iterator: str
    steps: List[StepConfig]
    raw: Dict[str, Any]
    #: "abort" (reference parity: a full queue kills the job) or
    #: "shed" (a full queue drops the NEW request with a counted shed
    #: outcome and the pipeline keeps serving)
    overload_policy: str = "abort"
    #: when False, even *classified* transient/permanent errors abort
    #: the job like any other exception — strict reference semantics
    fault_containment: bool = True
    #: validated fault-injection plan dict (rnb_tpu.faults), or None;
    #: the RNB_FAULT_PLAN env JSON overrides it at launch
    fault_plan: Optional[Dict[str, Any]] = None
    #: validated request-popularity spec ({"dist": "zipf", "s": ...,
    #: "universe": ...}), or None for the base iterator's own order;
    #: the client wraps the video-path iterator with
    #: rnb_tpu.video_path_provider.ZipfPathIterator when set
    popularity: Optional[Dict[str, Any]] = None
    #: validated load-adaptive batching spec ({"enabled": ..,
    #: "slo_ms": .., "ewma_alpha": .., "min_hold_ms": ..,
    #: "max_hold_ms": .., "buckets": [..]}), or None; the launcher
    #: builds rnb_tpu.autotune.AutotuneSettings from it and every
    #: batching stage not opted out gets a BatchController
    autotune: Optional[Dict[str, Any]] = None
    #: validated ragged row-pool dispatch spec ({"enabled": ..,
    #: "pool_rows": ..}), or None; when enabled the launcher injects
    #: ``ragged``/``ragged_pool_rows`` kwargs into every
    #: ``SUPPORTS_RAGGED`` stage (rnb_tpu.ops.ragged): stages dispatch
    #: a flat row pool at ONE compiled shape with a rows_valid scalar
    #: and per-request segment offsets instead of padding to buckets
    ragged: Optional[Dict[str, Any]] = None
    #: validated page-allocator spec ({"enabled": .., "page_rows": ..,
    #: "pool_mb": .., "feature_cache": ..}), or None; when enabled the
    #: launcher builds one rnb_tpu.pager.Pager (fixed-size device row
    #: pages under one slab per arena) shared by every
    #: ``SUPPORTS_PAGER`` stage: clip-cache entries become page
    #: reference lists gathered on device at the consumption seam
    #: (zero host memcpy on hits), and — with ``feature_cache`` true —
    #: post-stage activation rows are cached on feature pages so a
    #: repeat request skips the backbone. Requires ``ragged`` (the
    #: gather seam is the one pool shape). Absent => byte-stable logs.
    pager: Optional[Dict[str, Any]] = None
    #: validated device-resident handoff spec ({"enabled": ..,
    #: "mode": "device"|"host"}), or None for the pre-handoff edge
    #: semantics (stage models re-home their own inputs, no
    #: accounting, byte-stable logs) — rnb_tpu.handoff
    handoff: Optional[Dict[str, Any]] = None
    #: validated placement-planner spec ({"enabled": .., "mode":
    #: "plan"|"apply", "plan": {"step<i>": replicas}}), or None; when
    #: set the launcher measures per-stage dispatch costs and writes
    #: the Placement: log-meta plan line (rnb_tpu.placement); "apply"
    #: additionally expands the named steps' replica counts at parse
    #: time exactly like a hand-written ``replicas`` key
    placement: Optional[Dict[str, Any]] = None
    #: validated lane-health / circuit-breaker spec ({"enabled": ..,
    #: "suspect_after_ms": .., "open_after_ms": ..,
    #: "probe_interval_ms": ..}), or None; when set the launcher
    #: builds one rnb_tpu.health.LaneHealthBoard per replica-expanded
    #: step — the upstream ReplicaSelector stops routing to open
    #: lanes, evicted lanes drain onto siblings, and log-meta gains
    #: the Health:/Health lanes: lines
    health: Optional[Dict[str, Any]] = None
    #: validated deadline-propagation spec ({"enabled": ..,
    #: "budget_ms": ..}), or None; when set the client stamps every
    #: request with an absolute deadline (budget seeded from
    #: autotune.slo_ms when unset) and every stage boundary sheds
    #: expired requests (shed reason deadline_expired) instead of
    #: computing doomed work — rnb_tpu.health
    deadline: Optional[Dict[str, Any]] = None
    #: validated lint-runtime spec ({"lock_witness": ..}), or None;
    #: with lock_witness true the launcher enables the
    #: rnb_tpu.lockwitness lock-order witness BEFORE pipeline
    #: construction (the witness wraps locks at creation), log-meta
    #: gains the Locks:/Lock edges: lines, and parse --check holds
    #: observed acquisition-order edges to a subset of the static
    #: RNB-C lock-order graph with zero violations. Absent or false
    #: => plain threading locks, byte-stable logs.
    lint: Optional[Dict[str, Any]] = None
    #: validated tracing spec ({"enabled": .., "sample_hz": ..,
    #: "max_events": ..}), or None; when enabled the launcher builds
    #: an rnb_tpu.trace.Tracer, every thread role emits named spans,
    #: a background sampler records queue/slot occupancy, and the job
    #: dir gains a Perfetto-loadable trace.json plus per-request
    #: phase attribution (Phases: line, `# phases` trailers). Absent
    #: => logs are byte-stable with the pre-trace schema.
    trace: Optional[Dict[str, Any]] = None

    @property
    def num_steps(self) -> int:
        return len(self.steps)

    @property
    def num_runners(self) -> int:
        return sum(g.num_instances for s in self.steps for g in s.groups)

    def all_devices(self) -> List[DeviceSpec]:
        return [d for s in self.steps for g in s.groups for d in g.devices]

    def check_devices(self) -> None:
        """Resolve every placement against the visible JAX devices."""
        from rnb_tpu.devices import check_devices
        check_devices(self.all_devices())


def _expand_replicas(pipeline: list, placement: Optional[Dict[str, Any]]
                     ) -> tuple:
    """Replica-sharded serving (PR 9): expand every step declaring
    ``replicas: N`` (or named by an apply-mode placement plan) into N
    queue groups — one per replica, each with its own fresh lane queue
    and an equal slice of the step's device list (the per-replica
    sub-mesh) — and rewire the upstream producers onto the lanes with
    the least-loaded ReplicaSelector swapped in for the default.

    Returns ``(expanded_pipeline, {step_idx: (lane queue indices)})``;
    the input list is never mutated (``config.raw`` keeps the
    as-written form). Expansion happens at parse time so everything
    downstream — fabric wiring, the static graph checker, the job-dir
    config copy — sees one canonical multi-group form.
    """
    import copy

    plan: Dict[int, int] = {}
    if placement is not None and placement.get("enabled", True) \
            and placement.get("mode", "plan") == "apply":
        for key, val in (placement.get("plan") or {}).items():
            plan[int(key[4:])] = int(val)

    wants: Dict[int, Any] = {}
    for step_idx, step in enumerate(pipeline):
        if not isinstance(step, dict):
            continue
        n = step.get("replicas")
        if n is None:
            # an explicit per-step ``replicas`` wins over the plan —
            # the plan is advice, the step key is the operator's word
            n = plan.get(step_idx)
        if n is not None:
            wants[step_idx] = n

    for step_idx, n in wants.items():
        _expect(isinstance(n, int) and not isinstance(n, bool)
                and n >= 1,
                "pipeline step %d: 'replicas' must be a positive "
                "integer, got %r" % (step_idx, n))
    if not wants:
        return pipeline, {}

    pipeline = copy.deepcopy(pipeline)
    used = set()
    for step in pipeline:
        if not isinstance(step, dict):
            continue
        for g in step.get("queue_groups") or []:
            if not isinstance(g, dict):
                continue
            if isinstance(g.get("in_queue"), int):
                used.add(g["in_queue"])
            for q in g.get("out_queues") or []:
                if isinstance(q, int):
                    used.add(q)
    next_q = max(used) + 1 if used else 0

    replica_queues: Dict[int, tuple] = {}
    for step_idx in sorted(wants):
        n = wants[step_idx]
        step = pipeline[step_idx]
        step.pop("replicas", None)
        # the structural constraints hold for EVERY declared replicas
        # key, n == 1 included — otherwise an operator iterating
        # replica counts would hit a "regression" at n=2 for a
        # topology that was invalid (but silently accepted) at n=1
        where = "pipeline step %d" % step_idx
        _expect(step_idx > 0,
                "%s: 'replicas' needs a routable in_queue; the first "
                "step reads the shared filename queue — replicate it "
                "by listing more devices instead" % where)
        _expect(step.get("num_segments", 1) == 1,
                "%s: 'replicas' cannot be combined with "
                "'num_segments' > 1 (segment siblings must reach one "
                "aggregator, which per-replica lanes cannot "
                "guarantee)" % where)
        groups = step.get("queue_groups")
        _expect(isinstance(groups, list) and len(groups) == 1
                and isinstance(groups[0], dict),
                "%s: 'replicas' requires exactly one queue group to "
                "expand" % where)
        g = groups[0]
        dev_key = ("devices" if "devices" in g
                   else "gpus" if "gpus" in g else None)
        _expect(dev_key is not None,
                "%s, queue group 0 needs a 'devices' list" % where)
        devices = g[dev_key]
        _expect(isinstance(devices, list) and devices
                and len(devices) % n == 0,
                "%s: 'replicas'=%d must evenly divide the %d-entry "
                "device list — each replica owns an equal sub-mesh"
                % (where, n, len(devices) if isinstance(devices, list)
                   else 0))
        orig_in = g.get("in_queue")
        _expect(isinstance(orig_in, int),
                "%s, queue group 0 needs an integer 'in_queue'" % where)
        if n == 1:
            # validated but structurally a no-op: the existing queue
            # IS the single lane, so no rewiring (and no selector
            # swap) happens
            continue

        lanes = list(range(next_q, next_q + n))
        next_q += n
        # the per-replica sub-mesh rule lives with the mesh factoring
        # (rnb_tpu.parallel.mesh): contiguous equal device slices
        from rnb_tpu.parallel.mesh import carve_replicas
        new_groups = []
        for lane, sub_mesh in zip(lanes, carve_replicas(devices, n)):
            ng = copy.deepcopy(g)
            ng[dev_key] = sub_mesh
            ng["in_queue"] = lane
            new_groups.append(ng)
        step["queue_groups"] = new_groups

        rewired = False
        for ug in pipeline[step_idx - 1].get("queue_groups") or []:
            if not isinstance(ug, dict):
                continue
            outs = list(ug.get("out_queues") or [])
            if orig_in not in outs:
                continue
            pos = outs.index(orig_in)
            ug["out_queues"] = outs[:pos] + lanes + outs[pos + 1:]
            if ug.get("queue_selector",
                      DEFAULT_QUEUE_SELECTOR) == DEFAULT_QUEUE_SELECTOR:
                ug["queue_selector"] = REPLICA_QUEUE_SELECTOR
            rewired = True
        _expect(rewired,
                "%s: no upstream queue group names out-queue %d, so "
                "the replica lanes cannot be wired" % (where, orig_in))
        replica_queues[step_idx] = tuple(lanes)
    return pipeline, replica_queues


def _expand_shard(pipeline: list) -> list:
    """Intra-stage tensor parallelism (rnb_tpu.parallel.shardplan):
    translate every step's ``shard: {degree, axis, hbm_budget_mb}``
    key into per-group constructor kwargs. Runs AFTER replica
    expansion, so the two compose replica-major: ``replicas: N``
    first carves the step's device list into N equal lane sub-meshes,
    then each lane's sub-mesh must be exactly ``degree`` devices —
    its shard ring. The group keeps ONE primary device (the executor
    spawns one instance per listed device; a shard ring is one
    executable over k devices, not k executors) and the full ring
    travels to the stage as ``shard_devices``.

    Returns the (possibly copied) pipeline; the input list is never
    mutated when a shard key is present (``config.raw`` keeps the
    as-written form).
    """
    import copy

    if not any(isinstance(step, dict) and step.get("shard") is not None
               for step in pipeline):
        return pipeline
    pipeline = copy.deepcopy(pipeline)
    for step_idx, step in enumerate(pipeline):
        if not isinstance(step, dict):
            continue
        shard = step.get("shard")
        if shard is None:
            continue
        where = "pipeline step %d" % step_idx
        _expect(isinstance(shard, dict),
                "%s: 'shard' must be an object" % where)
        unknown = sorted(set(shard) - set(SHARD_KEYWORDS))
        _expect(not unknown,
                "%s: 'shard' has unknown key(s) %s — keys are %s"
                % (where, unknown, SHARD_KEYWORDS))
        degree = shard.get("degree")
        _expect(isinstance(degree, int) and not isinstance(degree, bool)
                and degree >= 1,
                "%s: 'shard.degree' must be a positive integer, got %r"
                % (where, degree))
        axis = shard.get("axis", "tp")
        _expect(isinstance(axis, str) and axis,
                "%s: 'shard.axis' must be a non-empty string, got %r"
                % (where, axis))
        budget = shard.get("hbm_budget_mb")
        _expect(budget is None
                or (isinstance(budget, (int, float))
                    and not isinstance(budget, bool) and budget > 0),
                "%s: 'shard.hbm_budget_mb' must be a positive number, "
                "got %r" % (where, budget))
        _expect(step.get("num_segments", 1) == 1,
                "%s: 'shard' cannot be combined with 'num_segments' "
                "> 1 (segment siblings would each need their own "
                "ring)" % where)
        for group_idx, group in enumerate(step.get("queue_groups")
                                          or []):
            gwhere = "%s, queue group %d" % (where, group_idx)
            _expect(isinstance(group, dict),
                    "%s must be an object" % gwhere)
            dev_key = ("devices" if "devices" in group
                       else "gpus" if "gpus" in group else None)
            _expect(dev_key is not None,
                    "%s needs a 'devices' list" % gwhere)
            devices = group[dev_key]
            _expect(isinstance(devices, list)
                    and len(devices) == degree,
                    "%s: 'shard.degree'=%d needs exactly that many "
                    "devices per lane (got %d) — with 'replicas' the "
                    "step's device list must total replicas x degree"
                    % (gwhere, degree,
                       len(devices) if isinstance(devices, list)
                       else 0))
            _expect(all(d != -1 for d in devices),
                    "%s: 'shard' rings cannot include the host "
                    "(-1)" % gwhere)
            # one primary device -> one executor instance; the ring
            # rides the open kwargs passthrough to the stage
            group[dev_key] = devices[:1]
            group["shard_devices"] = list(devices)
            group["shard_degree"] = degree
            group["shard_axis"] = axis
            if budget is not None:
                group["shard_hbm_budget_mb"] = budget
    return pipeline


def load_config(path: str) -> PipelineConfig:
    with open(path, "r") as f:
        try:
            raw = json.load(f)
        except json.JSONDecodeError as e:
            raise ConfigError("config file %s is not valid JSON: %s"
                              % (path, e)) from e
    return parse_config(raw)


def parse_config(raw: Dict[str, Any]) -> PipelineConfig:
    _expect(isinstance(raw, dict), "config root must be a JSON object")
    _expect("video_path_iterator" in raw,
            "config is missing 'video_path_iterator'")
    _expect(isinstance(raw["video_path_iterator"], str),
            "'video_path_iterator' must be a class-path string")
    _expect("pipeline" in raw, "config is missing 'pipeline'")
    pipeline = raw["pipeline"]
    _expect(isinstance(pipeline, list) and pipeline,
            "'pipeline' must be a non-empty list of steps")

    unknown_root = sorted(set(raw) - set(ROOT_KEYWORDS))
    _expect(not unknown_root,
            "config has unknown root key(s) %s — root keys are %s"
            % (unknown_root, sorted(k for k in ROOT_KEYWORDS
                                    if k != "_comment")))

    overload_policy = raw.get("overload_policy", "abort")
    _expect(overload_policy in ("abort", "shed"),
            "'overload_policy' must be \"abort\" or \"shed\", got %r"
            % (overload_policy,))
    fault_containment = raw.get("fault_containment", True)
    _expect(isinstance(fault_containment, bool),
            "'fault_containment' must be a boolean")
    popularity = raw.get("popularity")
    if popularity is not None:
        _expect(isinstance(popularity, dict),
                "'popularity' must be an object")
        unknown_pop = sorted(set(popularity) - set(POPULARITY_KEYWORDS))
        _expect(not unknown_pop,
                "'popularity' has unknown key(s) %s — keys are %s"
                % (unknown_pop, POPULARITY_KEYWORDS))
        _expect(popularity.get("dist", "zipf") == "zipf",
                "'popularity.dist' must be \"zipf\" (the one supported "
                "distribution), got %r" % (popularity.get("dist"),))
        s = popularity.get("s", 1.0)
        _expect(isinstance(s, (int, float)) and not isinstance(s, bool)
                and s >= 0,
                "'popularity.s' must be a non-negative number, got %r"
                % (s,))
        universe = popularity.get("universe")
        _expect(universe is None
                or (isinstance(universe, int)
                    and not isinstance(universe, bool) and universe >= 1),
                "'popularity.universe' must be a positive integer, got %r"
                % (universe,))

    autotune = raw.get("autotune")
    if autotune is not None:
        _expect(isinstance(autotune, dict), "'autotune' must be an object")
        unknown_at = sorted(set(autotune) - set(AUTOTUNE_KEYWORDS))
        _expect(not unknown_at,
                "'autotune' has unknown key(s) %s — keys are %s"
                % (unknown_at, AUTOTUNE_KEYWORDS))
        _expect(isinstance(autotune.get("enabled", True), bool),
                "'autotune.enabled' must be a boolean")

        def _number(key, default, minimum, strict=False):
            val = autotune.get(key, default)
            ok = (isinstance(val, (int, float))
                  and not isinstance(val, bool)
                  and (val > minimum if strict else val >= minimum))
            _expect(ok, "'autotune.%s' must be a number %s %g, got %r"
                    % (key, ">" if strict else ">=", minimum, val))
            return float(val)

        _number("slo_ms", 50.0, 0, strict=True)
        alpha = _number("ewma_alpha", 0.2, 0, strict=True)
        _expect(alpha <= 1.0,
                "'autotune.ewma_alpha' must be in (0, 1], got %r"
                % (alpha,))
        min_hold = _number("min_hold_ms", 0.5, 0)
        max_hold = _number("max_hold_ms", max(min_hold, 50.0), 0)
        _expect(max_hold >= min_hold,
                "'autotune.max_hold_ms' (%g) must be >= "
                "'autotune.min_hold_ms' (%g)" % (max_hold, min_hold))
        buckets = autotune.get("buckets")
        if buckets is not None:
            _expect(isinstance(buckets, list) and buckets
                    and all(isinstance(b, int) and not isinstance(b, bool)
                            and b >= 1 for b in buckets)
                    and len(set(buckets)) == len(buckets),
                    "'autotune.buckets' must be a non-empty list of "
                    "distinct positive row counts, got %r" % (buckets,))

    trace = raw.get("trace")
    if trace is not None:
        _expect(isinstance(trace, dict), "'trace' must be an object")
        unknown_tr = sorted(set(trace) - set(TRACE_KEYWORDS))
        _expect(not unknown_tr,
                "'trace' has unknown key(s) %s — keys are %s"
                % (unknown_tr, TRACE_KEYWORDS))
        _expect(isinstance(trace.get("enabled", True), bool),
                "'trace.enabled' must be a boolean")
        sample_hz = trace.get("sample_hz", 20.0)
        _expect(isinstance(sample_hz, (int, float))
                and not isinstance(sample_hz, bool) and sample_hz >= 0,
                "'trace.sample_hz' must be a non-negative number "
                "(0 disables the occupancy sampler), got %r"
                % (sample_hz,))
        max_events = trace.get("max_events", 200000)
        _expect(isinstance(max_events, int)
                and not isinstance(max_events, bool) and max_events >= 1,
                "'trace.max_events' must be a positive integer, got %r"
                % (max_events,))

    ragged = raw.get("ragged")
    if ragged is not None:
        _expect(isinstance(ragged, dict), "'ragged' must be an object")
        unknown_rg = sorted(set(ragged) - set(RAGGED_KEYWORDS))
        _expect(not unknown_rg,
                "'ragged' has unknown key(s) %s — keys are %s"
                % (unknown_rg, RAGGED_KEYWORDS))
        _expect(isinstance(ragged.get("enabled", True), bool),
                "'ragged.enabled' must be a boolean")
        pool_rows = ragged.get("pool_rows")
        _expect(pool_rows is None
                or (isinstance(pool_rows, int)
                    and not isinstance(pool_rows, bool)
                    and pool_rows >= 1),
                "'ragged.pool_rows' must be a positive integer "
                "(the flat row pool's capacity), got %r" % (pool_rows,))
        if ragged.get("enabled", True):
            # the pool is ONE fixed shape; a row-split into segments
            # would need per-segment pool shapes — reject like the
            # row_buckets/segments combination above
            _expect(all(step.get("num_segments", 1) == 1
                        for step in pipeline if isinstance(step, dict)),
                    "'ragged' cannot be combined with 'num_segments' "
                    "> 1: the pool is one fixed dispatch shape")

    pager = raw.get("pager")
    if pager is not None:
        _expect(isinstance(pager, dict), "'pager' must be an object")
        unknown_pg = sorted(set(pager) - set(PAGER_KEYWORDS))
        _expect(not unknown_pg,
                "'pager' has unknown key(s) %s — keys are %s"
                % (unknown_pg, PAGER_KEYWORDS))
        _expect(isinstance(pager.get("enabled", True), bool),
                "'pager.enabled' must be a boolean")
        page_rows = pager.get("page_rows")
        _expect(page_rows is None
                or (isinstance(page_rows, int)
                    and not isinstance(page_rows, bool)
                    and page_rows >= 1),
                "'pager.page_rows' must be a positive integer (rows "
                "per fixed-size page), got %r" % (page_rows,))
        pool_mb = pager.get("pool_mb")
        _expect(pool_mb is None
                or (isinstance(pool_mb, (int, float))
                    and not isinstance(pool_mb, bool)
                    and pool_mb > 0),
                "'pager.pool_mb' must be a positive number (per-arena "
                "page budget; omit to size from the cache budget), "
                "got %r" % (pool_mb,))
        _expect(isinstance(pager.get("feature_cache", False), bool),
                "'pager.feature_cache' must be a boolean")
        if pager.get("enabled", True):
            # the gather-from-pages seam overlays rows onto the ONE
            # ragged pool shape after its transfer; bucketed emissions
            # have no single dispatch pool to gather into
            _expect(isinstance(ragged, dict)
                    and ragged.get("enabled", True),
                    "'pager' requires 'ragged': paged cache hits "
                    "gather into the ragged row pool at its one "
                    "compiled shape")

    handoff = raw.get("handoff")
    if handoff is not None:
        _expect(isinstance(handoff, dict), "'handoff' must be an object")
        unknown_ho = sorted(set(handoff) - set(HANDOFF_KEYWORDS))
        _expect(not unknown_ho,
                "'handoff' has unknown key(s) %s — keys are %s"
                % (unknown_ho, HANDOFF_KEYWORDS))
        _expect(isinstance(handoff.get("enabled", True), bool),
                "'handoff.enabled' must be a boolean")
        mode = handoff.get("mode", "device")
        _expect(mode in ("device", "host"),
                "'handoff.mode' must be \"device\" (device-resident "
                "edges) or \"host\" (the explicit host round-trip "
                "baseline arm), got %r" % (mode,))

    placement = raw.get("placement")
    if placement is not None:
        _expect(isinstance(placement, dict),
                "'placement' must be an object")
        unknown_pl = sorted(set(placement) - set(PLACEMENT_KEYWORDS))
        _expect(not unknown_pl,
                "'placement' has unknown key(s) %s — keys are %s"
                % (unknown_pl, PLACEMENT_KEYWORDS))
        _expect(isinstance(placement.get("enabled", True), bool),
                "'placement.enabled' must be a boolean")
        pl_mode = placement.get("mode", "plan")
        _expect(pl_mode in ("plan", "apply"),
                "'placement.mode' must be \"plan\" (report the "
                "measured-cost plan) or \"apply\" (apply 'plan' replica "
                "counts at launch), got %r" % (pl_mode,))
        plan = placement.get("plan")
        if pl_mode == "apply":
            _expect(isinstance(plan, dict) and plan,
                    "'placement.mode' \"apply\" needs a non-empty "
                    "'plan' object ({\"step<i>\": replicas})")
        if plan is not None:
            _expect(isinstance(plan, dict), "'placement.plan' must be "
                    "an object")
            for key, val in plan.items():
                ok_key = (isinstance(key, str) and key.startswith("step")
                          and key[4:].isdigit()
                          and int(key[4:]) < len(pipeline))
                _expect(ok_key,
                        "'placement.plan' keys must be \"step<i>\" with "
                        "i inside the pipeline (0..%d), got %r"
                        % (len(pipeline) - 1, key))
                _expect(isinstance(val, int)
                        and not isinstance(val, bool) and val >= 1,
                        "'placement.plan.%s' must be a positive integer "
                        "replica count, got %r" % (key, val))

    health = raw.get("health")
    if health is not None:
        _expect(isinstance(health, dict), "'health' must be an object")
        unknown_h = sorted(set(health) - set(HEALTH_KEYWORDS))
        _expect(not unknown_h,
                "'health' has unknown key(s) %s — keys are %s"
                % (unknown_h, HEALTH_KEYWORDS))
        _expect(isinstance(health.get("enabled", True), bool),
                "'health.enabled' must be a boolean")
        for key in ("suspect_after_ms", "open_after_ms",
                    "probe_interval_ms"):
            val = health.get(key)
            _expect(val is None
                    or (isinstance(val, (int, float))
                        and not isinstance(val, bool) and val > 0),
                    "'health.%s' must be a positive number, got %r"
                    % (key, val))
        if health.get("enabled", True):
            # the same defaulting the runtime applies — a config whose
            # thresholds invert must fail at parse time, not at launch
            try:
                from rnb_tpu.health import HealthSettings
                HealthSettings.from_config(health)
            except ValueError as e:
                raise ConfigError("invalid 'health': %s" % e) from e

    deadline = raw.get("deadline")
    if deadline is not None:
        _expect(isinstance(deadline, dict),
                "'deadline' must be an object")
        unknown_d = sorted(set(deadline) - set(DEADLINE_KEYWORDS))
        _expect(not unknown_d,
                "'deadline' has unknown key(s) %s — keys are %s"
                % (unknown_d, DEADLINE_KEYWORDS))
        _expect(isinstance(deadline.get("enabled", True), bool),
                "'deadline.enabled' must be a boolean")
        budget = deadline.get("budget_ms")
        _expect(budget is None
                or (isinstance(budget, (int, float))
                    and not isinstance(budget, bool) and budget > 0),
                "'deadline.budget_ms' must be a positive number "
                "(defaults to autotune.slo_ms when autotune is "
                "configured), got %r" % (budget,))

    lint = raw.get("lint")
    if lint is not None:
        _expect(isinstance(lint, dict), "'lint' must be an object")
        unknown_lint = sorted(set(lint) - set(LINT_KEYWORDS))
        _expect(not unknown_lint,
                "'lint' has unknown key(s) %s — keys are %s"
                % (unknown_lint, LINT_KEYWORDS))
        _expect(isinstance(lint.get("lock_witness", False), bool),
                "'lint.lock_witness' must be a boolean")

    fault_plan = raw.get("fault_plan")
    if fault_plan is not None:
        from rnb_tpu.faults import FaultPlan
        try:
            # structural validation + step indices against THIS
            # pipeline (a typo'd step would silently never fire)
            FaultPlan(fault_plan).check_steps(len(pipeline))
        except ValueError as e:
            raise ConfigError("invalid 'fault_plan': %s" % e) from e

    # replica-sharded serving: expand `replicas` steps (and an
    # apply-mode placement plan) into per-replica lane groups BEFORE
    # any wiring validation, so the expanded form is the one canonical
    # topology everything checks and builds
    pipeline, replica_queues = _expand_replicas(pipeline, placement)
    # intra-stage sharding composes replica-major: each replica lane's
    # equal device slice becomes that lane's shard ring
    pipeline = _expand_shard(pipeline)

    steps: List[StepConfig] = []
    prev_out_queues: Optional[set] = None
    for step_idx, step_raw in enumerate(pipeline):
        first = step_idx == 0
        final = step_idx == len(pipeline) - 1
        where = "pipeline step %d" % step_idx
        _expect(isinstance(step_raw, dict), "%s must be an object" % where)
        _expect(isinstance(step_raw.get("model"), str),
                "%s needs a 'model' class-path string" % where)
        groups_raw = step_raw.get("queue_groups")
        _expect(isinstance(groups_raw, list) and groups_raw,
                "%s needs a non-empty 'queue_groups' list" % where)

        num_segments = step_raw.get("num_segments", 1)
        _expect(isinstance(num_segments, int) and num_segments >= 1,
                "%s: 'num_segments' must be a positive integer" % where)
        _expect(not (final and num_segments != 1),
                "the last step may not have multiple segments")
        # variable bucketed row counts would make the per-segment split
        # shapes unpredictable — every first-seen shape is a silent XLA
        # recompile inside the measured window
        _expect(not (num_segments > 1 and "row_buckets" in step_raw),
                "%s: 'row_buckets' cannot be combined with "
                "'num_segments' > 1" % where)

        # transfer-pipeline knobs (rnb_tpu.staging) are open kwargs —
        # they flow to the stage constructor like any extra — but
        # their types are validated here so a typo'd value fails at
        # parse time, not as a mid-run constructor error
        staging_slots = step_raw.get("staging_slots")
        _expect(staging_slots is None
                or (isinstance(staging_slots, int)
                    and not isinstance(staging_slots, bool)
                    and staging_slots >= 0),
                "%s: 'staging_slots' must be a non-negative integer "
                "(0 disables zero-copy staging), got %r"
                % (where, staging_slots))
        transfer_async = step_raw.get("transfer_async")
        _expect(transfer_async is None or isinstance(transfer_async, bool),
                "%s: 'transfer_async' must be a boolean, got %r"
                % (where, transfer_async))
        fallback_threads = step_raw.get("fallback_decode_threads")
        _expect(fallback_threads is None
                or (isinstance(fallback_threads, int)
                    and not isinstance(fallback_threads, bool)
                    and fallback_threads >= 1),
                "%s: 'fallback_decode_threads' must be a positive "
                "integer, got %r" % (where, fallback_threads))

        num_shared_tensors = step_raw.get("num_shared_tensors")
        if num_shared_tensors is not None:
            _expect(isinstance(num_shared_tensors, int)
                    and num_shared_tensors >= 1,
                    "%s: 'num_shared_tensors' must be a positive integer"
                    % where)
            _expect(not final,
                    "the last step does not need shared output tensors")

        # A producer writes every segment of a batch into its own ring
        # slot before publishing any Signal (runner.py), so a ring with
        # fewer slots than segments blocks forever on a slot whose
        # consumer was never told about it — a silent self-deadlock the
        # 1800 s barrier timeout would otherwise be the first sign of.
        # Deliberately conservative: a ring-less step (output_shape None,
        # knowable only after loading the model class — which parse-time
        # validation must not do) cannot deadlock, but is still rejected
        # here; declare num_shared_tensors >= num_segments to get past
        # (harmless when no ring is allocated).
        effective_slots = _effective_shared_tensors(num_shared_tensors)
        _expect(num_segments <= effective_slots,
                "%s: 'num_segments' (%d) exceeds the shared-tensor ring "
                "size (%d%s) — the producer would deadlock waiting on a "
                "slot it has not yet published; raise 'num_shared_tensors'"
                % (where, num_segments, effective_slots,
                   "" if num_shared_tensors is not None
                   else ", the default"))

        groups: List[GroupConfig] = []
        for group_idx, group_raw in enumerate(groups_raw):
            gwhere = "%s, queue group %d" % (where, group_idx)
            _expect(isinstance(group_raw, dict),
                    "%s must be an object" % gwhere)
            dev_key = ("devices" if "devices" in group_raw
                       else "gpus" if "gpus" in group_raw else None)
            _expect(dev_key is not None,
                    "%s needs a 'devices' list" % gwhere)
            devices_raw = group_raw[dev_key]
            _expect(isinstance(devices_raw, list) and devices_raw,
                    "%s: '%s' must be a non-empty list" % (gwhere, dev_key))
            devices = [DeviceSpec(d) for d in devices_raw]

            in_queue = group_raw.get("in_queue")
            if first:
                _expect(in_queue is None,
                        "%s: the first step reads the filename queue and "
                        "may not declare 'in_queue'" % gwhere)
            else:
                _expect(isinstance(in_queue, int),
                        "%s needs an integer 'in_queue'" % gwhere)

            out_queues = group_raw.get("out_queues", [])
            if final:
                _expect(not out_queues,
                        "%s: the last step may not declare 'out_queues'"
                        % gwhere)
            else:
                _expect(isinstance(out_queues, list) and out_queues
                        and all(isinstance(q, int) for q in out_queues),
                        "%s needs a non-empty integer 'out_queues' list"
                        % gwhere)

            selector = group_raw.get("queue_selector",
                                     DEFAULT_QUEUE_SELECTOR)
            _expect(isinstance(selector, str),
                    "%s: 'queue_selector' must be a class-path string"
                    % gwhere)

            extras = {k: v for k, v in group_raw.items()
                      if k not in RESERVED_KEYWORDS}
            groups.append(GroupConfig(devices=devices, in_queue=in_queue,
                                      out_queues=list(out_queues),
                                      queue_selector=selector,
                                      extras=extras))

        # queue wiring: this step's in-queues must be exactly the previous
        # step's out-queues (reference benchmark.py:79-87)
        if not first:
            in_queues = {g.in_queue for g in groups}
            if in_queues != prev_out_queues:
                raise ConfigError(
                    "output queues of step %d %s do not match input queues "
                    "of step %d %s"
                    % (step_idx - 1, sorted(prev_out_queues),
                       step_idx, sorted(in_queues)))
        prev_out_queues = {q for g in groups for q in g.out_queues}

        async_dispatch = step_raw.get("async_dispatch", False)
        _expect(isinstance(async_dispatch, bool),
                "%s: 'async_dispatch' must be a boolean" % where)

        max_retries = step_raw.get("max_retries", 0)
        _expect(isinstance(max_retries, int) and max_retries >= 0,
                "%s: 'max_retries' must be a non-negative integer" % where)
        retry_backoff_ms = step_raw.get("retry_backoff_ms", 10.0)
        _expect(isinstance(retry_backoff_ms, (int, float))
                and retry_backoff_ms >= 0,
                "%s: 'retry_backoff_ms' must be a non-negative number"
                % where)

        step_autotune = step_raw.get("autotune", True)
        _expect(isinstance(step_autotune, bool),
                "%s: 'autotune' must be a boolean (false opts the step "
                "out of the root autotune controller)" % where)

        hedge_ms = step_raw.get("hedge_ms")
        if hedge_ms is not None:
            _expect(hedge_ms == "p95x"
                    or (isinstance(hedge_ms, (int, float))
                        and not isinstance(hedge_ms, bool)
                        and hedge_ms > 0),
                    "%s: 'hedge_ms' must be a positive millisecond "
                    "count or \"p95x\", got %r" % (where, hedge_ms))
            _expect(replica_queues.get(step_idx) is not None,
                    "%s: 'hedge_ms' needs replica lanes to re-dispatch "
                    "onto — declare 'replicas' >= 2 on this step"
                    % where)

        step_extras = {k: v for k, v in step_raw.items()
                       if k not in RESERVED_KEYWORDS}
        steps.append(StepConfig(model=step_raw["model"], groups=groups,
                                num_segments=num_segments,
                                num_shared_tensors=num_shared_tensors,
                                extras=step_extras,
                                async_dispatch=async_dispatch,
                                max_retries=max_retries,
                                retry_backoff_ms=float(retry_backoff_ms),
                                autotune=step_autotune,
                                replica_queues=replica_queues.get(
                                    step_idx),
                                hedge_ms=hedge_ms))

    return PipelineConfig(video_path_iterator=raw["video_path_iterator"],
                          steps=steps, raw=raw,
                          overload_policy=overload_policy,
                          fault_containment=fault_containment,
                          fault_plan=fault_plan,
                          popularity=popularity,
                          autotune=autotune,
                          ragged=ragged,
                          pager=pager,
                          handoff=handoff,
                          placement=placement,
                          health=health,
                          deadline=deadline,
                          lint=lint,
                          trace=trace)
