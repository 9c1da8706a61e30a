"""Offline log parsing: benchmark log directories -> pandas DataFrames.

Capability parity with the reference's ``scripts/parse_utils.py``
(reference scripts/parse_utils.py:5-163) — but parsing the *current*
log schema, fixing the staleness the reference shipped with (its parser
expected an older arg set and the retired ``g%d-r%d.txt`` filename
scheme; see SURVEY.md §2.1 #15):

* ``logs/<job_id>/log-meta.txt`` — written by rnb_tpu/benchmark.py: an
  ``Args: Namespace(...)`` repr, start/end wall-clock timestamps, the
  termination flag, a ``Faults: num_failed=K num_shed=S num_retries=R``
  accounting line, (when any request failed) a ``Failure reasons:``
  JSON line with per-reason counts, (when a queue overflowed under the
  abort policy) a ``Queue overflows:`` JSON per-edge line, and — on
  cache-/staging-/autotune-enabled runs only — the ``Cache:``,
  ``Staging:``, ``Autotune:`` and ``Autotune buckets:`` counter lines.
* ``logs/<job_id>/<device>-group<g>-<i>.txt`` — one whitespace table
  per final-step instance (rnb_tpu/telemetry.py TimeCardSummary
  .save_full_report): a header of event keys followed by per-step
  device columns, then one row per completed request. Runs with
  contained faults append a ``# faults ...`` trailer line (skipped by
  the table parser; counters land in the meta dict instead).
* ``logs/<job_id>/failed-requests.txt`` — the controller's dead-letter
  record, one ``request_id step reason`` line per contained failure.

Public API mirrors the reference: ``parse_meta``, ``get_data`` (one
job), ``get_data_from_all_logs`` (every job under a log root, returning
a job-level and a request-level DataFrame).
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

import pandas as pd

#: ``Args: Namespace(mean_interval_ms=3, ..., config_file_path='x.json')``
_ARGS_RE = re.compile(r"(\w+)=('[^']*'|\"[^\"]*\"|[^,)]+)")
#: ``<device-label>-group<g>-<i>.txt`` (telemetry.logname)
_TABLE_RE = re.compile(r"^(?P<device>.+)-group(?P<group>\d+)-"
                       r"(?P<instance>\d+)\.txt$")


def parse_meta(job_dir: str) -> Dict[str, object]:
    """Parse one job's ``log-meta.txt`` into a flat dict.

    Returns arg values (ints where possible), ``time_start``/``time_end``,
    ``wall_time_s``, ``termination_flag``, and ``throughput_vps`` derived
    from the job's video count and wall time.
    """
    meta: Dict[str, object] = {"job_id": os.path.basename(job_dir.rstrip("/"))}
    with open(os.path.join(job_dir, "log-meta.txt")) as f:
        lines = f.read().splitlines()
    for line in lines:
        if line.startswith("Faults:"):
            # "Faults: num_failed=K num_shed=S num_retries=R"
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta[key] = int(val)
        elif line.startswith("Cache:"):
            # "Cache: hits=H misses=M inserts=I evictions=E
            #  coalesced=C oversize=O bytes_resident=B" — written only
            # by cache-enabled runs (rnb_tpu.cache)
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["cache_" + key] = int(val)
        elif line.startswith("Staging:"):
            # "Staging: slots=S slot_bytes=B acquires=A
            #  acquire_waits=W staged_batches=Z copied_batches=C
            #  reallocs=R" — written only by runs whose loader built a
            # zero-copy staging pool (rnb_tpu.staging)
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["staging_" + key] = int(val)
        elif line.startswith("Pages:"):
            # "Pages: arenas=A pages=P page_rows=R live=L limbo=M
            #  bytes=B allocs=.. frees=.. alloc_fails=.. gathers=..
            #  gather_rows=.. feature_lookups=.. feature_hits=..
            #  feature_inserts=.. feature_evictions=..
            #  feature_gathers=.. feature_gather_rows=..
            #  feature_bytes_saved=.. feature_entries=..
            #  bypassed_batches=.." — paged device-memory ledger
            # (rnb_tpu.pager), pager-enabled runs only; --check holds
            # allocs == frees + live at teardown, feature_hits <=
            # feature_lookups, gather_rows <= ragged cache_hit_rows
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["pages_" + key] = int(val)
        elif line.startswith("Autotune buckets:"):
            # JSON {row-bucket: emission count} — must be matched
            # before the "Autotune:" prefix below
            import json
            meta["autotune_bucket_counts"] = {
                key: int(val) for key, val
                in json.loads(line.split(":", 1)[1]).items()}
        elif line.startswith("Autotune:"):
            # "Autotune: decisions=D immediate=I held=H emissions=E
            #  deadline_us_min=N deadline_us_max=X deadline_us_sum=S"
            # — written only by autotune-enabled runs (rnb_tpu.autotune)
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["autotune_" + key] = int(val)
        elif line.startswith("Ragged:"):
            # "Ragged: pool_rows=P emissions=E rows=R
            #  pad_rows_eliminated=K cache_hit_rows=H" — written only
            # by ragged-enabled runs (rnb_tpu.ops.ragged)
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["ragged_" + key] = int(val)
        elif line.startswith("Shard steps:"):
            # JSON per-step shard detail {step: {degree, axis,
            # gathers, collective_us, rows, projected_mb, budget_mb,
            # min_degree}} — must be matched before the "Shard:"
            # prefix below; declared-shard runs only
            import json
            meta["shard_step_detail"] = json.loads(
                line.split(":", 1)[1])
        elif line.startswith("Shard:"):
            # "Shard: steps=S max_degree=D gathers=G collective_us=C
            #  rows=R" — intra-stage shard accounting
            # (rnb_tpu.parallel.shardplan), declared-shard runs only;
            # --check holds degree x replicas to the device budget and
            # collective_us under the inference span sum
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["shard_" + key] = int(val)
        elif line.startswith("Padding:"):
            # "Padding: pad_rows=P total_rows=T pad_emissions=E" —
            # padding-waste counters over every batching stage
            # (rnb_tpu.stage.PadCounter); ~0 pad_rows under ragged
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta[key] = int(val)
        elif line.startswith(("Tokens:", "Experts:", "Sparse:",
                              "Attention:")):
            # a stage's own counters, "<Line>: key=N ..." under the
            # keys of rnb_tpu.telemetry.STAGE_COUNTERS (which says what
            # each counts, and which are there only where a stage
            # counts them): the key behind the line's name in lower
            # case; a value with a point is a float
            name, counts = line.split(":", 1)
            for part in counts.split():
                key, _, val = part.partition("=")
                meta["%s_%s" % (name.lower(), key)] = float(val) \
                    if "." in val else int(val)
        elif line.startswith("Compiles:"):
            # JSON {step: {warmup, steady_new, steady_calls}} —
            # jit-entry signature accounting (rnb_tpu.compilestats);
            # steady_new > 0 is a mid-run recompile (--check fails it)
            import json
            meta["compile_signatures"] = json.loads(
                line.split(":", 1)[1])
        elif line.startswith("Warmup:"):
            # JSON {step: seconds} — per-step stage-construction wall
            # time (weights + warmup compiles)
            import json
            meta["warmup_s"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Setup:"):
            # JSON {phase: seconds} from run_benchmark's first line to
            # the start barrier, along the stage instance built last
            import json
            meta["setup_account"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Trace:"):
            # "Trace: events=N dropped=M" — written only by
            # trace-enabled runs (rnb_tpu.trace); counts events
            # exported to logs/<job>/trace.json and events dropped at
            # the max_events cap
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["trace_" + key] = int(val)
        elif line.startswith("Lock edges:"):
            # JSON {"edges": [[a, b], ...], "violations": [...]} —
            # the lock-order witness's observed acquisition-order
            # graph (rnb_tpu.lockwitness), witness-armed runs only;
            # --check holds every observed edge to the static RNB-C
            # lock-order graph
            import json
            meta["lock_edge_detail"] = json.loads(
                line.split(":", 1)[1])
        elif line.startswith("Locks:"):
            # "Locks: tracked=L acquires=A edges=E violations=V" —
            # the lock-order witness ledger (rnb_tpu.lockwitness),
            # witness-armed runs only; --check holds violations to
            # zero and the counts to the Lock edges: detail
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["locks_" + key] = int(val)
        elif line.startswith("Phases:"):
            # JSON {phase: {mean_ms, p99_ms, count}} — the per-request
            # latency attribution over steady-state completions,
            # written only by trace-enabled runs (rnb_tpu.trace)
            import json
            meta["phases"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Handoff edges:"):
            # JSON per-edge-label handoff counters — written only by
            # handoff-enabled runs (rnb_tpu.handoff)
            import json
            meta["handoff_edge_detail"] = json.loads(
                line.split(":", 1)[1])
        elif line.startswith("Handoff:"):
            # "Handoff: edges=E d2d_edges=D host_edges=H d2d_bytes=B
            #  host_bytes=C" — device-resident handoff accounting,
            # written only by handoff-enabled runs (rnb_tpu.handoff)
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["handoff_" + key] = int(val)
        elif line.startswith("Health lanes:"):
            # JSON per-lane health detail (state, transition path,
            # redispatched-from) — must be matched before the
            # "Health:" prefix below; health-enabled replica runs only
            import json
            meta["health_lane_detail"] = json.loads(
                line.split(":", 1)[1])
        elif line.startswith("Health:"):
            # "Health: lanes=L transitions=T opens=O evictions=E
            #  probes=P redispatches=R routes_after_open=X" — lane
            # health/circuit accounting (rnb_tpu.health), written only
            # by health-enabled replica runs
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["health_" + key] = int(val)
        elif line.startswith("Deadline sites:"):
            # JSON per-check-site deadline_expired shed counts — must
            # be matched before the "Deadline:" prefix below
            import json
            meta["deadline_sites"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Deadline:"):
            # "Deadline: budget_ms=B expired=K" — deadline-propagation
            # accounting (rnb_tpu.health), deadline-enabled runs only
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["deadline_" + key] = int(val)
        elif line.startswith("Hedge:"):
            # "Hedge: fired=F won=W lost=L wasted_ms=M" — hedged
            # re-dispatch accounting (rnb_tpu.health), hedge_ms runs
            # only; won + lost == fired is a --check invariant
            for part in line.split(":", 1)[1].split():
                key, _, val = part.partition("=")
                meta["hedges_" + key] = int(val)
        elif line.startswith("Placement:"):
            # JSON measured-cost placement report (rnb_tpu.placement):
            # per-step dispatch costs + predicted occupancy + the
            # recommended replica plan — placement-enabled runs only
            import json
            meta["placement"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Failure reasons:"):
            import json
            meta["failure_reasons"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Shed sites:"):
            import json
            meta["shed_sites"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Queue overflows:"):
            import json
            meta["queue_overflows"] = json.loads(line.split(":", 1)[1])
        elif line.startswith("Args:"):
            for key, raw in _ARGS_RE.findall(line):
                raw = raw.strip()
                if raw[:1] in "'\"":
                    meta[key] = raw[1:-1]
                else:
                    try:
                        meta[key] = int(raw)
                    except ValueError:
                        try:
                            meta[key] = float(raw)
                        except ValueError:
                            meta[key] = raw
        elif line.startswith("Termination flag:"):
            meta["termination_flag"] = int(line.split(":")[1])
        else:
            parts = line.split()
            if len(parts) == 2:
                meta["time_start"], meta["time_end"] = map(float, parts)
    if "time_start" in meta and "time_end" in meta:
        meta["wall_time_s"] = meta["time_end"] - meta["time_start"]
        videos = meta.get("videos")
        if videos and meta["wall_time_s"] > 0:
            meta["throughput_vps"] = videos / meta["wall_time_s"]
    return meta


def parse_timing_table(path: str) -> pd.DataFrame:
    """Parse one final-instance timing table.

    Timestamp columns stay float; ``device*`` columns stay string. The
    producing replica's identity (from the filename) is attached as
    ``final_device`` / ``final_group`` / ``final_instance`` columns.
    ``#``-prefixed lines (the ``# faults ...`` trailer of runs with
    contained failures) are not table rows and are skipped.
    """
    with open(path) as f:
        header = f.readline().split()
        rows = [line.split() for line in f
                if line.strip() and not line.startswith("#")]
    df = pd.DataFrame(rows, columns=header)
    for col in df.columns:
        if not col.startswith("device"):
            df[col] = df[col].astype(float)
    m = _TABLE_RE.match(os.path.basename(path))
    if m:
        df["final_device"] = m.group("device")
        df["final_group"] = int(m.group("group"))
        df["final_instance"] = int(m.group("instance"))
    return df


def parse_table_trailers(path: str) -> Dict[str, Dict[str, int]]:
    """``#``-prefixed trailer lines of one timing table, keyed by
    trailer kind: ``{"faults": {...}, "cache": {...}}`` with integer
    ``key=value`` fields (non-integer fields like ``reason:x=3`` keep
    their full token as key). Absent trailers are absent keys."""
    trailers: Dict[str, Dict[str, int]] = {}
    with open(path) as f:
        for line in f:
            if not line.startswith("#"):
                continue
            tokens = line[1:].split()
            if not tokens:
                continue
            fields: Dict[str, int] = {}
            for token in tokens[1:]:
                key, sep, val = token.partition("=")
                if sep:
                    try:
                        fields[key] = int(val)
                    except ValueError:
                        fields[token] = 0
            trailers[tokens[0]] = fields
    return trailers


def parse_dead_letters(job_dir: str) -> pd.DataFrame:
    """One job's dead-letter record -> DataFrame with ``request_id``,
    ``step`` and ``reason`` columns; empty when the run contained no
    failures (the file is only written when there were any)."""
    path = os.path.join(job_dir, "failed-requests.txt")
    if not os.path.isfile(path):
        return pd.DataFrame(columns=["request_id", "step", "reason"])
    rows = []
    with open(path) as f:
        for line in f:
            if not line.strip() or line.startswith("#"):
                continue
            rid, step, reason = line.split(None, 2)
            rows.append((int(rid), int(step), reason.strip()))
    return pd.DataFrame(rows, columns=["request_id", "step", "reason"])


def _timing_tables(job_dir: str) -> List[str]:
    return sorted(
        os.path.join(job_dir, name) for name in os.listdir(job_dir)
        if _TABLE_RE.match(name))


def get_data(job_dir: str) -> Tuple[Dict[str, object], pd.DataFrame]:
    """One job -> (meta dict, request-level DataFrame).

    The request DataFrame concatenates every final instance's table and
    carries the job's meta columns so per-request rows are self-describing
    (reference get_data, scripts/parse_utils.py:32-69).
    """
    meta = parse_meta(job_dir)
    tables = [parse_timing_table(p) for p in _timing_tables(job_dir)]
    if tables:
        df = pd.concat(tables, ignore_index=True)
    else:
        df = pd.DataFrame()
    for key in ("job_id", "mean_interval_ms", "batch_size", "videos",
                "queue_size"):
        if key in meta:
            df[key] = meta[key]
    return meta, df


def get_data_from_all_logs(log_base: str = "logs") \
        -> Tuple[pd.DataFrame, pd.DataFrame]:
    """Every job under ``log_base`` -> (jobs DataFrame, requests DataFrame).

    Mirrors the reference's two-frame contract
    (scripts/parse_utils.py:72-163): the first frame has one row per job
    (args + wall time + throughput), the second one row per request.
    Jobs whose meta file is missing or unparsable are skipped.
    """
    metas: List[Dict[str, object]] = []
    request_frames: List[pd.DataFrame] = []
    for name in sorted(os.listdir(log_base)):
        job_dir = os.path.join(log_base, name)
        if not os.path.isfile(os.path.join(job_dir, "log-meta.txt")):
            continue
        try:
            meta, df = get_data(job_dir)
        except (OSError, ValueError):
            continue
        metas.append(meta)
        if not df.empty:
            request_frames.append(df)
    jobs = pd.DataFrame(metas)
    requests = (pd.concat(request_frames, ignore_index=True)
                if request_frames else pd.DataFrame())
    return jobs, requests


#: Semantic names for the standard 2-stage (decode -> network) schema's
#: inter-event gaps — the decomposition the reference plots
#: (scripts/latency_summary.py:29-33).
STANDARD_COMPONENTS = [
    ("enqueue_filename", "runner0_start", "filename_queue_wait"),
    ("runner0_start", "inference0_start", "runner0_dispatch"),
    ("inference0_start", "inference0_finish", "decode"),
    ("inference0_finish", "runner1_start", "frame_queue_wait"),
    ("runner1_start", "inference1_start", "device_comm"),
    ("inference1_start", "inference1_finish", "neural_net"),
]

#: trace-mode refinement of the loader span (rnb_tpu.trace): runs with
#: the `trace` config key enabled additionally stamp decode{step}_done
#: / transfer{step}_start / transfer{step}_done, splitting the step-0
#: "decode" component into decode / hold / transfer / drain. Absent
#: columns are simply skipped, so pre-trace logs decompose unchanged.
REFINED_COMPONENTS = [
    ("inference0_start", "decode0_done", "decode_only"),
    ("decode0_done", "transfer0_start", "batch_hold"),
    ("transfer0_start", "transfer0_done", "transfer"),
    ("transfer0_done", "inference0_finish", "publish_drain"),
]


def dispatch_batch_sizes(df: pd.DataFrame,
                         step: Optional[int] = None) -> pd.Series:
    """Batch-size distribution of the network dispatches.

    Constituents of one fused dispatch (Batcher / R2P1DFusingLoader:
    one jit call stamps every constituent card) share their
    ``inference{step}_finish`` timestamp exactly, so grouping requests
    by that stamp recovers how many requests each device dispatch
    carried — the evidence for whether the batching strategy actually
    fills dispatches under the measured load. ``step`` defaults to the
    last inference step present. Returns size -> dispatch count.
    """
    # numeric sort (lexicographic would rank step 9 above step 10), and
    # only columns with data — a union-schema frame carries all-NaN
    # finish columns for jobs with shallower pipelines
    finish_cols = sorted(
        (c for c in df.columns
         if re.fullmatch(r"inference\d+_finish", c)
         and df[c].notna().any()),
        key=lambda c: int(re.search(r"\d+", c).group()))
    if step is not None:
        col = "inference%d_finish" % step
        if col not in df.columns or not df[col].notna().any():
            raise ValueError("no data for %r; columns with data: %r"
                             % (col, finish_cols))
    else:
        if not finish_cols:
            return pd.Series(dtype=int)
        last_plain = int(re.search(r"\d+", finish_cols[-1]).group())
        # segment-parallel jobs carry SUFFIXED merged keys
        # ('inference1_finish-0', telemetry merge) for their deeper
        # steps; grouping a pre-fork stage's stamps would mislabel
        # per-request loader stamps as 'dispatch sizes', so refuse the
        # default rather than mislead
        if any(re.fullmatch(r"inference(\d+)_finish-\d+", c)
               and int(re.search(r"\d+", c).group()) > last_plain
               for c in df.columns):
            return pd.Series(dtype=int)
        col = finish_cols[-1]
    sizes = df.groupby(df[col]).size()
    return sizes.value_counts().sort_index()


def decompose_latency(df: pd.DataFrame) -> pd.DataFrame:
    """Add per-request latency-component columns (milliseconds).

    Standard-schema gaps get their semantic names; any remaining adjacent
    event pairs get ``gap:<prev>-><next>`` columns so segmented/merged
    schemas still decompose fully.
    """
    time_cols = [c for c in df.columns
                 if df[c].dtype == float and not c.startswith("device")
                 and c not in ("final_group", "final_instance")]
    named = set()
    out = df.copy()
    for prv, nxt, name in STANDARD_COMPONENTS + REFINED_COMPONENTS:
        if prv in time_cols and nxt in time_cols:
            out[name] = (df[nxt] - df[prv]) * 1000.0
            named.update((prv, nxt))
    for prv, nxt in zip(time_cols[:-1], time_cols[1:]):
        if prv in named and nxt in named:
            continue
        out["gap:%s->%s" % (prv, nxt)] = (df[nxt] - df[prv]) * 1000.0
    return out


# -- per-request phase attribution (CLI: --attribute <job_dir>) --------

def _rnb_trace():
    """Import :mod:`rnb_tpu.trace` (the attribution rules live next to
    the tracer so the online ``Phases:`` line and this offline path can
    never diverge) from the repo checkout this script sits in."""
    import sys as _sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from rnb_tpu import trace
    return trace


def _summary_skips() -> int:
    """The per-instance warm-record skip the job-wide summaries apply
    (rnb_tpu.runner.NUM_SUMMARY_SKIPS)."""
    _rnb_trace()
    from rnb_tpu.runner import NUM_SUMMARY_SKIPS
    return NUM_SUMMARY_SKIPS


#: columns of a timing table that are identity, not timestamps
_NON_TIME_COLS = ("final_device", "final_group", "final_instance")


def _table_time_cols(df: pd.DataFrame) -> List[str]:
    return [c for c in df.columns
            if not c.startswith("device") and c not in _NON_TIME_COLS]


def _df_phase_rows(df: pd.DataFrame, num_skips: int = 0):
    """Yield ``(phases, e2e_ms)`` per row after ``num_skips`` — the
    single-pass primitive under ``--attribute``/``--check``: each row's
    stamp-only decomposition (rnb_tpu.trace.attribute_phases) together
    with its end-to-end latency, so samples and the partition residual
    come out of one iteration. Rows with fewer than two recorded
    stamps (nothing to decompose) are skipped."""
    trace = _rnb_trace()
    time_cols = _table_time_cols(df)
    for row in df.iloc[num_skips:][time_cols].itertuples(index=False):
        timings = {k: t for k, t in zip(time_cols, row) if t == t}
        if len(timings) < 2:
            continue
        e2e_ms = (max(timings.values()) - min(timings.values())) * 1e3
        yield trace.attribute_phases(timings), e2e_ms


def table_phase_samples(path: str, num_skips: int = 0
                        ) -> Dict[str, List[float]]:
    """{phase: [per-request milliseconds]} over one timing table's
    rows after ``num_skips`` — the deterministic stamp-only
    decomposition (rnb_tpu.trace.attribute_phases), so it works on any
    past log: without the trace-mode refinement stamps
    (decode0_done / transfer0_start / transfer0_done) the whole loader
    span reports as one ``decode`` phase."""
    samples: Dict[str, List[float]] = {}
    for phases, _e2e_ms in _df_phase_rows(parse_timing_table(path),
                                          num_skips):
        for phase, ms in phases.items():
            samples.setdefault(phase, []).append(ms)
    return samples


def attribute_job(job_dir: str, num_skips: Optional[int] = None
                  ) -> Dict[str, Dict[str, float]]:
    """Job-wide per-phase attribution {phase: {mean_ms, p99_ms,
    count}} over every final instance's steady-state rows — the same
    aggregation rule as the log-meta ``Phases:`` line, recomputed from
    the tables alone. ``num_skips`` defaults to the summary convention
    (rnb_tpu.runner.NUM_SUMMARY_SKIPS per instance)."""
    trace = _rnb_trace()
    if num_skips is None:
        num_skips = _summary_skips()
    merged: Dict[str, List[float]] = {}
    for path in _timing_tables(job_dir):
        for phase, vals in table_phase_samples(path, num_skips).items():
            merged.setdefault(phase, []).extend(vals)
    return trace.phase_stats(merged)


def print_attribution(job_dir: str, out=None) -> int:
    """``--attribute``: print the per-phase mean/p99 table for one job
    and verify the partition invariant (phases sum to each request's
    end-to-end latency). Returns 0 on success, 1 when the invariant
    fails or the job has no rows."""
    import sys as _sys
    trace = _rnb_trace()
    out = out or _sys.stdout
    # one pass over the tables: phase samples and the partition
    # residual (1 ms tolerance, same bound --check applies) come from
    # the same parsed rows
    merged: Dict[str, List[float]] = {}
    worst = 0.0
    latencies: List[float] = []
    num_skips = _summary_skips()
    for path in _timing_tables(job_dir):
        df = parse_timing_table(path)
        for phases, e2e_ms in _df_phase_rows(df, num_skips):
            for phase, ms in phases.items():
                merged.setdefault(phase, []).append(ms)
            worst = max(worst, abs(sum(phases.values()) - e2e_ms))
            latencies.append(e2e_ms)
    stats = trace.phase_stats(merged)
    if not stats:
        out.write("%s: no steady-state rows to attribute\n" % job_dir)
        return 1
    out.write("%s: per-request phase attribution "
              "(steady-state, mean/p99 ms)\n" % job_dir)
    mean_sum = 0.0
    for phase in trace.sorted_phases(stats):
        s = stats[phase]
        mean_sum += s["mean_ms"]
        out.write("  %-18s %9.3f / %9.3f  (n=%d)\n"
                  % (phase, s["mean_ms"], s["p99_ms"], s["count"]))
    mean_e2e = sum(latencies) / len(latencies) if latencies else 0.0
    out.write("  %-18s %9.3f  (end-to-end mean %0.3f, worst "
              "per-request residual %.6f ms)\n"
              % ("sum", mean_sum, mean_e2e, worst))
    return 0 if worst <= 1.0 else 1


# -- consistency checking (CLI: parse_utils.py --check <job_dir>) ------

def check_job(job_dir: str) -> List[str]:
    """Cross-artifact consistency check of one job's log directory:
    log-meta vs timing tables vs trailers vs dead letters. Returns a
    list of human-readable problems (empty = consistent)."""
    return check_job_detail(job_dir)[0]


def check_job_detail(job_dir: str) -> Tuple[List[str], bool]:
    """:func:`check_job` plus a parse-failure verdict: ``(problems,
    parse_failed)`` where ``parse_failed`` marks schema-level
    unreadability (missing/corrupt log-meta, unparsable timing table)
    as opposed to an invariant violation over parsable artifacts —
    the CLI exits 2 for the former and 1 for the latter, matching the
    rnb-lint convention (2 = the checker could not run, 1 =
    findings)."""
    problems: List[str] = []
    parse_failed = False
    try:
        meta = parse_meta(job_dir)
    except (OSError, ValueError) as e:
        return ["log-meta.txt unreadable: %s" % e], True
    if "termination_flag" not in meta:
        problems.append("log-meta.txt carries no 'Termination flag:'")
    if "wall_time_s" not in meta:
        problems.append("log-meta.txt carries no start/end timestamps")

    tables = _timing_tables(job_dir)
    num_rows = 0
    table_faults = {"num_failed": 0, "num_shed": 0, "num_retries": 0}
    cache_hits = cache_tracked = 0
    saw_cache_trailer = False
    trailer_pads = 0
    saw_pad_trailer = False
    for path in tables:
        try:
            num_rows += len(parse_timing_table(path))
        except (OSError, ValueError) as e:
            problems.append("%s unparsable: %s"
                            % (os.path.basename(path), e))
            parse_failed = True
            continue
        trailers = parse_table_trailers(path)
        for key in table_faults:
            table_faults[key] += trailers.get("faults", {}).get(key, 0)
        if "cache" in trailers:
            saw_cache_trailer = True
            cache_hits += trailers["cache"].get("num_hits", 0)
            cache_tracked += trailers["cache"].get("num_tracked", 0)
        if "padding" in trailers:
            saw_pad_trailer = True
            trailer_pads += trailers["padding"].get("pad_rows", 0)
    if not tables:
        problems.append("no timing tables (<device>-group<g>-<i>.txt)")

    # fault accounting: table trailers count only failures observed AT
    # final-step instances; the meta line is job-wide, so tables can
    # never exceed it
    for key in ("num_failed", "num_shed"):
        if key in meta and table_faults[key] > meta[key]:
            problems.append(
                "tables count %s=%d but log-meta says %d"
                % (key, table_faults[key], meta[key]))
    letters = parse_dead_letters(job_dir)
    if "num_failed" in meta and len(letters) > meta["num_failed"]:
        problems.append("failed-requests.txt has %d rows but log-meta "
                        "says num_failed=%d"
                        % (len(letters), meta["num_failed"]))

    # cache accounting: a '# cache' trailer requires the job-wide
    # 'Cache:' line; completed hits can never exceed loader-side hits
    if saw_cache_trailer and "cache_hits" not in meta:
        problems.append("tables carry a '# cache' trailer but log-meta "
                        "has no 'Cache:' line")
    if "cache_hits" in meta:
        # hits recorded on completed cards at the final step are a
        # subset of the loader's lookup hits (some hit requests may
        # still be shed/failed downstream)
        if cache_hits > meta["cache_hits"] + meta.get("cache_coalesced",
                                                      0):
            problems.append(
                "tables count %d completed cache hits but log-meta "
                "records only %d lookup hits (+%d coalesced)"
                % (cache_hits, meta["cache_hits"],
                   meta.get("cache_coalesced", 0)))
        if cache_tracked > num_rows:
            problems.append("cache trailer tracks %d completions but "
                            "tables hold %d rows"
                            % (cache_tracked, num_rows))
        if meta.get("cache_inserts", 0) > meta.get("cache_misses", 0):
            problems.append("cache_inserts=%d exceeds cache_misses=%d "
                            "(inserts happen only after a miss decoded)"
                            % (meta["cache_inserts"],
                               meta["cache_misses"]))
        if meta.get("cache_bytes_resident", 0) < 0:
            problems.append("negative cache_bytes_resident")

    # staging accounting (rnb_tpu.staging): a wait happens inside an
    # acquire, and an alias-forced realloc happens at most once per
    # confirmed staged transfer — violations mean counter drift
    if "staging_acquires" in meta:
        for key in ("staging_slots", "staging_slot_bytes",
                    "staging_acquires", "staging_acquire_waits",
                    "staging_staged_batches", "staging_copied_batches",
                    "staging_reallocs"):
            if meta.get(key, 0) < 0:
                problems.append("negative %s" % key)
        if meta.get("staging_acquire_waits", 0) \
                > meta.get("staging_acquires", 0):
            problems.append(
                "staging_acquire_waits=%d exceeds staging_acquires=%d "
                "(every wait is part of an acquire)"
                % (meta["staging_acquire_waits"],
                   meta["staging_acquires"]))
        if meta.get("staging_reallocs", 0) \
                > meta.get("staging_staged_batches", 0):
            problems.append(
                "staging_reallocs=%d exceeds staging_staged_batches=%d "
                "(a realloc needs a confirmed staged transfer)"
                % (meta["staging_reallocs"],
                   meta["staging_staged_batches"]))

    # paged device-memory accounting (rnb_tpu.pager): the teardown
    # page ledger must foot exactly — every allocated page is either
    # freed or still live (entry-held/limbo) when the job ends; the
    # feature cache can never hit more than it looked up, inserts
    # split exactly into resident entries + evictions, a feature
    # gather needs a feature hit that survived to the runner, and the
    # clip-plane gather rows are a subset of the ragged cache hit
    # rows (a shed hit releases its plan before any gather dispatch)
    if "pages_allocs" in meta:
        for key in ("pages_arenas", "pages_pages", "pages_page_rows",
                    "pages_live", "pages_limbo", "pages_bytes",
                    "pages_allocs", "pages_frees", "pages_alloc_fails",
                    "pages_gathers", "pages_gather_rows",
                    "pages_feature_lookups", "pages_feature_hits",
                    "pages_feature_inserts", "pages_feature_evictions",
                    "pages_feature_gathers",
                    "pages_feature_gather_rows",
                    "pages_feature_bytes_saved",
                    "pages_feature_entries",
                    "pages_bypassed_batches"):
            if meta.get(key, 0) < 0:
                problems.append("negative %s" % key)
        allocs = meta.get("pages_allocs", 0)
        frees = meta.get("pages_frees", 0)
        live = meta.get("pages_live", 0)
        if allocs != frees + live:
            problems.append(
                "pages_allocs=%d != pages_frees=%d + pages_live=%d "
                "(a page leaked or was double-freed)"
                % (allocs, frees, live))
        if meta.get("pages_limbo", 0) > live:
            problems.append(
                "pages_limbo=%d exceeds pages_live=%d (limbo pages "
                "are off the free list)"
                % (meta["pages_limbo"], live))
        if meta.get("pages_feature_hits", 0) \
                > meta.get("pages_feature_lookups", 0):
            problems.append(
                "pages_feature_hits=%d exceeds "
                "pages_feature_lookups=%d (every hit is a lookup)"
                % (meta["pages_feature_hits"],
                   meta["pages_feature_lookups"]))
        if meta.get("pages_feature_inserts", 0) \
                != meta.get("pages_feature_entries", 0) \
                + meta.get("pages_feature_evictions", 0):
            problems.append(
                "pages_feature_inserts=%d != pages_feature_entries=%d "
                "+ pages_feature_evictions=%d (entries leave only by "
                "eviction)"
                % (meta["pages_feature_inserts"],
                   meta["pages_feature_entries"],
                   meta["pages_feature_evictions"]))
        if meta.get("pages_feature_gathers", 0) \
                > meta.get("pages_feature_hits", 0):
            problems.append(
                "pages_feature_gathers=%d exceeds "
                "pages_feature_hits=%d (a gather needs a hit plan; "
                "shed hits release without gathering)"
                % (meta["pages_feature_gathers"],
                   meta["pages_feature_hits"]))
        if "ragged_cache_hit_rows" in meta \
                and meta.get("pages_gather_rows", 0) \
                > meta.get("ragged_cache_hit_rows", 0):
            problems.append(
                "pages_gather_rows=%d exceeds ragged "
                "cache_hit_rows=%d (gathered rows are the cache hit "
                "rows that survived to dispatch)"
                % (meta["pages_gather_rows"],
                   meta["ragged_cache_hit_rows"]))

    # autotune accounting (rnb_tpu.autotune): every batched emission
    # under autotune is covered by a controller decision (forced drains
    # are back-filled as immediate decisions), decisions split exactly
    # into immediate/held verdicts, the held-deadline histogram must be
    # internally consistent, and chosen buckets must be a subset of
    # the buckets the config warms — a chosen un-warmed bucket would
    # have been a silent mid-run recompile
    if "autotune_decisions" in meta:
        for key in ("autotune_decisions", "autotune_immediate",
                    "autotune_held", "autotune_emissions",
                    "autotune_deadline_us_min",
                    "autotune_deadline_us_max",
                    "autotune_deadline_us_sum"):
            if meta.get(key, 0) < 0:
                problems.append("negative %s" % key)
        decisions = meta.get("autotune_decisions", 0)
        immediate = meta.get("autotune_immediate", 0)
        held = meta.get("autotune_held", 0)
        emissions = meta.get("autotune_emissions", 0)
        if immediate + held != decisions:
            problems.append(
                "autotune_immediate=%d + autotune_held=%d != "
                "autotune_decisions=%d (every decision has exactly one "
                "verdict)" % (immediate, held, decisions))
        if emissions > decisions:
            problems.append(
                "autotune_emissions=%d exceeds autotune_decisions=%d "
                "(every emission under autotune is covered by a "
                "decision)" % (emissions, decisions))
        buckets = meta.get("autotune_bucket_counts", {})
        if sum(buckets.values()) != emissions:
            problems.append(
                "autotune bucket counts sum to %d but "
                "autotune_emissions=%d (every emission is attributed "
                "to its chosen bucket)"
                % (sum(buckets.values()), emissions))
        d_min = meta.get("autotune_deadline_us_min", 0)
        d_max = meta.get("autotune_deadline_us_max", 0)
        d_sum = meta.get("autotune_deadline_us_sum", 0)
        if held > 0:
            if d_min > d_max:
                problems.append(
                    "autotune_deadline_us_min=%d exceeds "
                    "autotune_deadline_us_max=%d" % (d_min, d_max))
            if not held * d_min <= d_sum <= held * d_max:
                problems.append(
                    "autotune_deadline_us_sum=%d outside "
                    "[held*min, held*max]=[%d, %d]"
                    % (d_sum, held * d_min, held * d_max))
        elif d_sum != 0:
            problems.append(
                "autotune_deadline_us_sum=%d with autotune_held=0 "
                "(only held decisions enter the deadline histogram)"
                % d_sum)
        if "ragged_pool_rows" in meta:
            # ragged dispatch: every row count <= pool_rows hits the
            # same executable, so the warmed-set subset rule relaxes
            # to the pool capacity (decisions are continuous)
            pool = meta["ragged_pool_rows"]
            rogue = sorted(int(b) for b in buckets if int(b) > pool)
            if rogue:
                problems.append(
                    "autotune chose row count(s) %s above the ragged "
                    "pool capacity %d" % (rogue, pool))
        else:
            configured = _configured_buckets(job_dir)
            if buckets and configured:
                rogue = sorted(int(b) for b in buckets
                               if int(b) not in configured)
                if rogue:
                    problems.append(
                        "autotune chose row bucket(s) %s the config "
                        "never warms (configured: %s) — each would "
                        "have been a silent mid-run recompile"
                        % (rogue, sorted(configured)))

    # padding-waste accounting (rnb_tpu.stage.PadCounter): pads are a
    # subset of shipped rows, and the per-instance trailers (final-step
    # completions only) can never exceed the job-wide meta counters
    if "pad_rows" in meta:
        if meta["pad_rows"] > meta.get("total_rows", 0):
            problems.append(
                "pad_rows=%d exceeds total_rows=%d (pads are part of "
                "the shipped rows)" % (meta["pad_rows"],
                                       meta.get("total_rows", 0)))
        if saw_pad_trailer and trailer_pads > meta["pad_rows"]:
            problems.append(
                "tables count pad_rows=%d but log-meta says %d "
                "(the job-wide counter covers every emission)"
                % (trailer_pads, meta["pad_rows"]))

    # ragged row-pool accounting (rnb_tpu.ops.ragged): every emission
    # ships the one pool shape, so valid rows are bounded by
    # emissions * pool_rows; counters never go negative
    if "ragged_emissions" in meta:
        for key in ("ragged_pool_rows", "ragged_emissions",
                    "ragged_rows", "ragged_pad_rows_eliminated",
                    "ragged_cache_hit_rows"):
            if meta.get(key, 0) < 0:
                problems.append("negative %s" % key)
        if meta.get("ragged_rows", 0) > (meta.get("ragged_emissions", 0)
                                         * meta.get("ragged_pool_rows",
                                                    0)):
            problems.append(
                "ragged_rows=%d exceeds emissions*pool_rows=%d — an "
                "emission carried more valid rows than the pool holds"
                % (meta.get("ragged_rows", 0),
                   meta.get("ragged_emissions", 0)
                   * meta.get("ragged_pool_rows", 0)))
        if meta.get("ragged_cache_hit_rows", 0) \
                > meta.get("ragged_rows", 0):
            problems.append(
                "ragged_cache_hit_rows=%d exceeds ragged_rows=%d "
                "(hit rows ship inside pool emissions)"
                % (meta["ragged_cache_hit_rows"], meta["ragged_rows"]))
        # ragged emissions compute no pad rows: the Padding: counter
        # must stay 0 for a ragged-only pipeline (mixed pipelines may
        # carry bucketed stages, so only flag when every batching
        # stage is ragged — emissions counts agree exactly then)
        if meta.get("pad_emissions") == meta.get("ragged_emissions") \
                and meta.get("pad_rows", 0) > 0:
            problems.append(
                "pad_rows=%d on a fully ragged run (every emission "
                "ragged) — the ragged path must compute no pad rows"
                % meta["pad_rows"])

    # compile/warmup accounting (rnb_tpu.compilestats): a jit-entry
    # signature first seen inside the measured window is a silent
    # mid-run XLA recompile — the dynamic twin of rnb-lint RNB-G006
    for step, sigs in sorted(dict(meta.get("compile_signatures",
                                           {})).items()):
        if int(sigs.get("steady_new", 0)) > 0:
            problems.append(
                "%s compiled %d new signature(s) inside the measured "
                "window (Compiles: steady_new) — warmup must cover "
                "the full shape vocabulary"
                % (step, int(sigs["steady_new"])))

    # set-up's account (rnb_tpu.trace.setup_account): the phases
    # along the instance built last partition run_benchmark's first
    # line to the start barrier's release
    account = meta.get("setup_account")
    if account:
        parts = sum(v for k, v in account.items()
                    if k not in ("total", "instance"))
        if abs(parts - account["total"]) > 1e-3:
            problems.append(
                "Setup: the phases give %.6f s of total %.6f"
                % (parts, account["total"]))

    # self-healing accounting (rnb_tpu.health): lane transition paths
    # must be legal automaton walks, routing must never feed an open
    # lane while siblings lived, deadline sheds must cross-foot
    # between their two ledgers, and every fired hedge must resolve
    # exactly once
    problems.extend(_check_health(meta, num_rows))
    problems.extend(_check_deadline(meta))
    problems.extend(_check_hedge(meta))
    # device-resident handoff accounting (rnb_tpu.handoff): every
    # edge take has exactly one class, the per-edge detail must sum
    # to the totals, and a device-resident config must have moved
    # zero bytes through host memory
    problems.extend(_check_handoff(job_dir, meta))
    # measured-cost placement (rnb_tpu.placement): the executed
    # plan's predicted occupancy must agree with the busy fraction
    # the trace timeline actually recorded
    problems.extend(_check_placement(job_dir, meta))
    # intra-stage sharding (rnb_tpu.parallel.shardplan): totals foot
    # the per-step detail, rings fit the config's device budget, and
    # the collective tax nests inside the inference spans it rides
    problems.extend(_check_shard(job_dir, meta))
    # phase attribution (rnb_tpu.trace): the stamp-only decomposition
    # must partition every request's end-to-end span, cover every
    # steady row once per phase, and agree across its three surfaced
    # forms (per-instance '# phases' trailers, the job-wide 'Phases:'
    # line, a recomputation from the raw tables)
    problems.extend(_check_phases(job_dir, meta, tables))
    # trace export accounting: the Trace: line must match what
    # trace.json actually holds, and the artifact must be structurally
    # valid (every event stamped, every flow resolving)
    problems.extend(_check_trace_artifact(job_dir, meta))
    problems.extend(_check_locks(meta))
    return problems, parse_failed


def _check_health(meta: Dict[str, object],
                  num_rows: int) -> List[str]:
    """Lane health/circuit invariants (rnb_tpu.health): the per-lane
    transition paths must replay as legal automaton walks consistent
    with the aggregate counters, no route may have landed on an
    open/evicted lane while a routable sibling existed, and — with
    the termination target reached — no request may be stranded."""
    problems: List[str] = []
    detail = meta.get("health_lane_detail")
    if "health_lanes" not in meta:
        if detail is not None:
            problems.append("log-meta carries a 'Health lanes:' line "
                            "but no 'Health:' totals line")
        return problems
    for key in ("health_lanes", "health_transitions", "health_opens",
                "health_evictions", "health_probes",
                "health_redispatches", "health_routes_after_open"):
        if meta.get(key, 0) < 0:
            problems.append("negative %s" % key)
    if meta.get("health_routes_after_open", 0) != 0:
        problems.append(
            "health_routes_after_open=%d — the selector routed to an "
            "open/evicted lane while a routable sibling existed "
            "(circuit containment violated)"
            % meta["health_routes_after_open"])
    if detail is None:
        if meta.get("health_lanes", 0) != 0:
            problems.append("'Health:' counts %d lane(s) but no "
                            "'Health lanes:' detail line exists"
                            % meta["health_lanes"])
        return problems
    _rnb_trace()  # side effect: puts the repo checkout on sys.path
    from rnb_tpu import health as health_mod
    detail = {k: dict(v) for k, v in dict(detail).items()}
    if len(detail) != meta.get("health_lanes", 0):
        problems.append("'Health lanes:' names %d lane(s) but the "
                        "'Health:' line says lanes=%d"
                        % (len(detail), meta.get("health_lanes", 0)))
    transitions = evictions = opens = redispatches = routes = 0
    for lane, entry in sorted(detail.items()):
        path = list(entry.get("path", []))
        if not health_mod.legal_path(path):
            problems.append(
                "lane %s transition path %s is not a legal walk of "
                "the health automaton (healthy start, declared edges "
                "only)" % (lane, path))
        if path and entry.get("state") != path[-1]:
            problems.append(
                "lane %s final state %r disagrees with its path %s"
                % (lane, entry.get("state"), path))
        transitions += max(0, len(path) - 1)
        opens += sum(1 for s in path if s == health_mod.OPEN)
        evictions += sum(1 for s in path if s == health_mod.EVICTED)
        redispatches += int(entry.get("redispatched_from", 0))
        routes += int(entry.get("routes_after_open", 0))
        if int(entry.get("redispatched_from", 0)) \
                and entry.get("state") != health_mod.EVICTED:
            problems.append(
                "lane %s reports %d redispatched item(s) but was "
                "never evicted — only an evicted lane's drain moves "
                "work" % (lane, entry.get("redispatched_from")))
    for want, key in ((transitions, "health_transitions"),
                      (opens, "health_opens"),
                      (evictions, "health_evictions"),
                      (redispatches, "health_redispatches"),
                      (routes, "health_routes_after_open")):
        if meta.get(key, 0) != want:
            problems.append(
                "'Health lanes:' detail recomputes %s=%d but the "
                "'Health:' line says %d" % (key, want,
                                            meta.get(key, 0)))
    # no stranded requests: with the target reached (flag 0) on a
    # bulk run, every one of the `videos` requests must have
    # terminated — completed (a table row), dead-lettered, or shed.
    # (A final fused dispatch may legally overshoot the target, so
    # only a SHORTFALL is a violation: work stranded behind a lane.)
    if meta.get("termination_flag") == 0 \
            and meta.get("mean_interval_ms") == 0 \
            and isinstance(meta.get("videos"), int):
        terminated = (num_rows + meta.get("num_failed", 0)
                      + meta.get("num_shed", 0))
        if terminated < meta["videos"]:
            problems.append(
                "only %d of %d requests terminated (completed + "
                "failed + shed) on a target-reached chaos run — the "
                "rest are stranded" % (terminated, meta["videos"]))
    return problems


def _check_locks(meta: Dict[str, object]) -> List[str]:
    """Lock-order witness invariants (rnb_tpu.lockwitness): the
    'Locks:' counters must foot against the 'Lock edges:' detail,
    the run must record ZERO discipline violations, and every
    observed acquisition-order edge must appear in the static RNB-C
    lock-order graph — a runtime order the analyzer never blessed is
    an undeclared lock dependency, offline-checkable."""
    problems: List[str] = []
    if "locks_tracked" not in meta:
        if "lock_edge_detail" in meta:
            problems.append("log-meta carries a 'Lock edges:' line "
                            "but no 'Locks:' totals line")
        return problems
    if "lock_edge_detail" not in meta:
        problems.append("log-meta carries a 'Locks:' line but no "
                        "'Lock edges:' detail line")
        return problems
    detail = meta["lock_edge_detail"]
    edges = [tuple(e) for e in detail.get("edges", [])]
    violations = detail.get("violations", [])
    for key in ("locks_tracked", "locks_acquires", "locks_edges",
                "locks_violations"):
        if meta.get(key, 0) < 0:
            problems.append("negative %s" % key)
    if len(edges) != meta.get("locks_edges", 0):
        problems.append(
            "'Lock edges:' lists %d edge(s) but the Locks: line says "
            "edges=%d" % (len(edges), meta.get("locks_edges", 0)))
    if len(violations) != meta.get("locks_violations", 0):
        problems.append(
            "'Lock edges:' lists %d violation(s) but the Locks: line "
            "says violations=%d"
            % (len(violations), meta.get("locks_violations", 0)))
    if violations:
        problems.append(
            "lock-order witness recorded %d discipline violation(s): "
            "%s" % (len(violations), "; ".join(
                str(v) for v in violations[:5])))
    if meta.get("locks_edges", 0) > meta.get("locks_acquires", 0):
        problems.append(
            "locks_edges=%d exceeds locks_acquires=%d — an order "
            "edge with no acquisition behind it"
            % (meta.get("locks_edges", 0),
               meta.get("locks_acquires", 0)))
    named = {name for edge in edges for name in edge}
    if len(named) > meta.get("locks_tracked", 0):
        problems.append(
            "%d distinct lock name(s) appear in edges but only "
            "locks_tracked=%d were witnessed"
            % (len(named), meta.get("locks_tracked", 0)))
    if edges:
        try:
            from rnb_tpu.analysis.concurrency import \
                static_lock_order_edges
            declared = static_lock_order_edges()
        except Exception as e:
            problems.append("static lock-order graph unavailable "
                            "(%s) — observed edges unverified" % e)
        else:
            for a, b in edges:
                if (a, b) not in declared:
                    problems.append(
                        "observed lock-order edge %s -> %s is not in "
                        "the static RNB-C lock-order graph — an "
                        "undeclared runtime lock dependency" % (a, b))
    return problems


def _check_deadline(meta: Dict[str, object]) -> List[str]:
    """Deadline-expiry invariants (rnb_tpu.health): the per-site
    counts must sum to the total, and the deadline ledger must
    cross-foot exactly with the deadline-suffixed entries of the shed
    ledger (two independent code paths counted every shed)."""
    problems: List[str] = []
    sites = meta.get("deadline_sites")
    if "deadline_expired" not in meta:
        if sites is not None:
            problems.append("log-meta carries a 'Deadline sites:' "
                            "line but no 'Deadline:' totals line")
        return problems
    if meta.get("deadline_budget_ms", 0) <= 0:
        problems.append("deadline_budget_ms=%s must be positive"
                        % meta.get("deadline_budget_ms"))
    expired = meta.get("deadline_expired", 0)
    if expired < 0:
        problems.append("negative deadline_expired")
    sites = dict(sites or {})
    if sum(sites.values()) != expired:
        problems.append(
            "'Deadline sites:' counts sum to %d but "
            "deadline_expired=%d (per-site sheds must sum to the "
            "total)" % (sum(sites.values()), expired))
    shed_sites = dict(meta.get("shed_sites", {}))
    suffix = ":deadline_expired"
    shed_deadline = {k: int(v) for k, v in shed_sites.items()
                     if k.endswith(suffix)}
    if shed_deadline != {k: int(v) for k, v in sites.items()}:
        problems.append(
            "deadline ledger %s disagrees with the shed ledger's "
            "deadline-suffixed sites %s (every expiry shed must be "
            "counted in both)" % (
                {k: int(v) for k, v in sorted(sites.items())},
                dict(sorted(shed_deadline.items()))))
    if expired > meta.get("num_shed", 0):
        problems.append(
            "deadline_expired=%d exceeds num_shed=%d (expiry sheds "
            "are a subset of all sheds)"
            % (expired, meta.get("num_shed", 0)))
    return problems


def _check_hedge(meta: Dict[str, object]) -> List[str]:
    """Hedged re-dispatch invariants (rnb_tpu.health): every fired
    hedge resolves exactly once — the hedge copy wins or the original
    does — and the loser's burned service is non-negative."""
    problems: List[str] = []
    if "hedges_fired" not in meta:
        return problems
    for key in ("hedges_fired", "hedges_won", "hedges_lost",
                "hedges_wasted_ms"):
        if meta.get(key, 0) < 0:
            problems.append("negative %s" % key)
    fired = meta.get("hedges_fired", 0)
    won = meta.get("hedges_won", 0)
    lost = meta.get("hedges_lost", 0)
    if won + lost != fired:
        problems.append(
            "hedges_won=%d + hedges_lost=%d != hedges_fired=%d "
            "(every fired hedge resolves exactly once)"
            % (won, lost, fired))
    if fired == 0 and meta.get("hedges_wasted_ms", 0) > 0:
        problems.append(
            "hedges_wasted_ms=%d with no hedge fired"
            % meta["hedges_wasted_ms"])
    return problems


def _check_handoff(job_dir: str, meta: Dict[str, object]) -> List[str]:
    problems: List[str] = []
    if "handoff_edges" not in meta:
        if "handoff_edge_detail" in meta:
            problems.append("log-meta carries a 'Handoff edges:' line "
                            "but no 'Handoff:' totals line")
        return problems
    for key in ("handoff_edges", "handoff_d2d_edges",
                "handoff_host_edges", "handoff_d2d_bytes",
                "handoff_host_bytes"):
        if meta.get(key, 0) < 0:
            problems.append("negative %s" % key)
    d2d = meta.get("handoff_d2d_edges", 0)
    host = meta.get("handoff_host_edges", 0)
    edges = meta.get("handoff_edges", 0)
    if d2d + host != edges:
        problems.append(
            "handoff_d2d_edges=%d + handoff_host_edges=%d != "
            "handoff_edges=%d (every edge take has exactly one class)"
            % (d2d, host, edges))
    detail = meta.get("handoff_edge_detail", {})
    if detail:
        for total_key, field in (("handoff_d2d_edges", "d2d_edges"),
                                 ("handoff_host_edges", "host_edges"),
                                 ("handoff_d2d_bytes", "d2d_bytes"),
                                 ("handoff_host_bytes", "host_bytes")):
            summed = sum(int(dict(e).get(field, 0))
                         for e in detail.values())
            if summed != meta.get(total_key, 0):
                problems.append(
                    "'Handoff edges:' %s sums to %d but the 'Handoff:' "
                    "line says %d" % (field, summed,
                                      meta.get(total_key, 0)))
    if _config_handoff_mode(job_dir) == "device" \
            and meta.get("handoff_host_bytes", 0) != 0:
        problems.append(
            "handoff_host_bytes=%d on a device-resident config "
            "(handoff.mode \"device\" promises zero host-hop bytes on "
            "every edge)" % meta["handoff_host_bytes"])
    return problems


def _config_handoff_mode(job_dir: str) -> Optional[str]:
    """The job's declared handoff mode from the config copy
    benchmark.py drops into the job dir, or None when no config copy
    declares an enabled ``handoff`` key."""
    import json
    for name in sorted(os.listdir(job_dir)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(job_dir, name)) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(raw, dict) or "pipeline" not in raw:
            continue
        handoff = raw.get("handoff")
        if isinstance(handoff, dict) and handoff.get("enabled", True):
            return handoff.get("mode", "device")
        return None
    return None


#: relative tolerance of the predicted-vs-traced occupancy check,
#: with an absolute floor so near-idle stages (where scheduling noise
#: dominates) don't flap
_OCCUPANCY_REL_TOL = 0.25
_OCCUPANCY_ABS_TOL = 0.05


def _check_placement(job_dir: str,
                     meta: Dict[str, object]) -> List[str]:
    problems: List[str] = []
    report = meta.get("placement")
    if not report:
        return problems
    steps = dict(report).get("steps", {})
    plan = dict(report).get("plan", {})
    for key, entry in sorted(dict(plan).items()):
        if int(dict(entry).get("replicas", 0)) < 1:
            problems.append("'Placement:' plan for %s names %r "
                            "replicas (must be >= 1)"
                            % (key, dict(entry).get("replicas")))
    # prediction vs trace: only checkable on trace-enabled runs whose
    # artifact is complete (a dropped-events trace undercounts busy)
    trace_path = os.path.join(job_dir, "trace.json")
    if not os.path.isfile(trace_path) or "wall_time_s" not in meta \
            or meta.get("trace_dropped", 0):
        return problems
    import json
    try:
        with open(trace_path) as f:
            doc = json.load(f)
    except ValueError:
        return problems  # _check_trace_artifact reports unreadability
    busy_us: Dict[int, float] = {}
    span_re = re.compile(r"exec(\d+)\.(model_call|device_sync)$")
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        m = span_re.match(str(ev.get("name", "")))
        if m:
            step = int(m.group(1))
            busy_us[step] = busy_us.get(step, 0.0) \
                + float(ev.get("dur", 0.0))
    wall = float(meta["wall_time_s"])
    for key, entry in sorted(dict(steps).items()):
        entry = dict(entry)
        step_idx = int(key[4:])
        if step_idx not in busy_us or wall <= 0.0:
            continue
        pred = float(entry.get("occupancy", 0.0))
        instances = max(1, int(entry.get("instances", 1)))
        traced = busy_us[step_idx] / 1e6 / wall / instances
        tol = max(_OCCUPANCY_REL_TOL * traced, _OCCUPANCY_ABS_TOL)
        if abs(pred - traced) > tol:
            problems.append(
                "'Placement:' %s predicts occupancy %.4f but the "
                "trace records a %.4f busy fraction (tolerance "
                "max(%d%%, %.2f)) — the planner's cost model drifted "
                "from what the executors measured"
                % (key, pred, traced,
                   int(_OCCUPANCY_REL_TOL * 100), _OCCUPANCY_ABS_TOL))
    return problems


def _check_shard(job_dir: str, meta: Dict[str, object]) -> List[str]:
    """'Shard:' ledger invariants: the totals must foot the per-step
    detail, every declared ring must fit the step's written device
    budget (degree x replicas <= listed devices), a running stage must
    sit inside its declared HBM budget (over-budget configs are
    launch-rejected, so a line showing one is a contradiction), and
    the merge collective must nest inside the inference spans it
    rides (traced collective wall <= traced model_call wall)."""
    problems: List[str] = []
    if "shard_steps" not in meta:
        return problems
    detail = {str(k): dict(v) for k, v
              in dict(meta.get("shard_step_detail") or {}).items()}
    if len(detail) != meta.get("shard_steps", 0):
        problems.append(
            "'Shard:' says steps=%s but 'Shard steps:' details %d "
            "step(s)" % (meta.get("shard_steps"), len(detail)))
    for key, total_key in (("gathers", "shard_gathers"),
                           ("collective_us", "shard_collective_us"),
                           ("rows", "shard_rows")):
        want = sum(int(d.get(key, 0)) for d in detail.values())
        if int(meta.get(total_key, 0)) != want:
            problems.append(
                "'Shard:' %s=%s but the per-step details sum to %d"
                % (key, meta.get(total_key), want))
    if detail:
        want = max(int(d.get("degree", 0)) for d in detail.values())
        if int(meta.get("shard_max_degree", 0)) != want:
            problems.append(
                "'Shard:' max_degree=%s but the per-step details max "
                "to %d" % (meta.get("shard_max_degree"), want))
    for step_key, d in sorted(detail.items()):
        for key in ("gathers", "collective_us", "rows"):
            if int(d.get(key, 0)) < 0:
                problems.append("negative shard %s on step %s"
                                % (key, step_key))
        if int(d.get("degree", 0)) < 1:
            problems.append(
                "'Shard steps:' step %s shows degree %s (a declared "
                "stage runs at least degree 1)"
                % (step_key, d.get("degree")))
        budget = float(d.get("budget_mb") or 0.0)
        projected = float(d.get("projected_mb") or 0.0)
        if budget and projected > budget:
            problems.append(
                "'Shard steps:' step %s projects %.1f MiB over its "
                "%.1f MiB budget — an over-budget stage is "
                "launch-rejected, so this line cannot come from a "
                "completed run" % (step_key, projected, budget))
    # ring vs the written device budget (the config copy benchmark.py
    # drops into the job dir keeps the as-written, pre-expansion form)
    import json
    for name in sorted(os.listdir(job_dir)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(job_dir, name)) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(raw, dict) or "pipeline" not in raw:
            continue
        for step_idx, step in enumerate(raw["pipeline"]):
            shard = (step.get("shard")
                     if isinstance(step, dict) else None)
            if not isinstance(shard, dict):
                continue
            degree = int(shard.get("degree", 1))
            replicas = int(step.get("replicas") or 1)
            devs = 0
            for group in step.get("queue_groups") or []:
                if isinstance(group, dict):
                    listed = group.get("devices",
                                       group.get("gpus")) or []
                    devs += (len(listed) if isinstance(listed, list)
                             else 0)
            if devs and degree * replicas > devs:
                problems.append(
                    "pipeline step %d declares shard degree %d x %d "
                    "replica(s) but lists only %d device(s) — the "
                    "ring exceeds the step's device budget"
                    % (step_idx, degree, replicas, devs))
            d = detail.get(str(step_idx))
            if d is not None and int(d.get("degree", 0)) != degree:
                problems.append(
                    "'Shard steps:' says step %d ran degree %s but "
                    "the config declares %d"
                    % (step_idx, d.get("degree"), degree))
        break
    # collective-tax nesting: only checkable on trace-enabled runs
    # whose artifact is complete (dropped events undercount both sides)
    trace_path = os.path.join(job_dir, "trace.json")
    if not os.path.isfile(trace_path) or meta.get("trace_dropped", 0):
        return problems
    try:
        with open(trace_path) as f:
            doc = json.load(f)
    except ValueError:
        return problems  # _check_trace_artifact reports unreadability
    coll_us: Dict[int, float] = {}
    call_us: Dict[int, float] = {}
    span_re = re.compile(r"exec(\d+)\.(collective|model_call)$")
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        m = span_re.match(str(ev.get("name", "")))
        if not m:
            continue
        step = int(m.group(1))
        bucket = coll_us if m.group(2) == "collective" else call_us
        bucket[step] = bucket.get(step, 0.0) + float(ev.get("dur", 0.0))
    for step_idx, us in sorted(coll_us.items()):
        if us > call_us.get(step_idx, 0.0) + 1.0:
            problems.append(
                "step %d traced %.0f us of exec.collective spans but "
                "only %.0f us of model_call spans — the merge must "
                "nest inside the inference span it rides"
                % (step_idx, us, call_us.get(step_idx, 0.0)))
    return problems


def _check_phases(job_dir: str, meta: Dict[str, object],
                  tables: List[str]) -> List[str]:
    problems: List[str] = []
    try:
        trace = _rnb_trace()
        num_skips = _summary_skips()
    except Exception as e:  # noqa: BLE001 — surfaced, not hidden
        return ["phase check unavailable (rnb_tpu unimportable): %s" % e]
    merged: Dict[str, List[float]] = {}
    saw_phase_trailer = False
    for path in tables:
        base = os.path.basename(path)
        try:
            df = parse_timing_table(path)
        except (OSError, ValueError):
            continue  # already reported by the table loop above
        # partition invariant over EVERY row (warm records included):
        # per-request phases must sum to the end-to-end latency
        for phases, e2e_ms in _df_phase_rows(df):
            total = sum(phases.values())
            if abs(total - e2e_ms) > 1.0:
                problems.append(
                    "%s: a request's phases sum to %.3f ms but its "
                    "end-to-end latency is %.3f ms (attribution must "
                    "partition the span)" % (base, total, e2e_ms))
                break  # one report per table is enough
        samples: Dict[str, List[float]] = {}
        for phases, _e2e_ms in _df_phase_rows(df, num_skips):
            for phase, ms in phases.items():
                samples.setdefault(phase, []).append(ms)
        steady = max(0, len(df) - num_skips)
        if samples:
            counts = {len(vals) for vals in samples.values()}
            if counts != {steady}:
                problems.append(
                    "%s: phase sample counts %s != steady row count %d "
                    "(every completed request contributes exactly one "
                    "sample per phase)"
                    % (base, sorted(counts), steady))
            for phase, vals in samples.items():
                merged.setdefault(phase, []).extend(vals)
        trailer = parse_table_trailers(path).get("phases")
        if trailer is not None:
            saw_phase_trailer = True
            stats = trace.phase_stats(samples)
            n = max((s["count"] for s in stats.values()), default=0)
            if trailer.get("n") != n:
                problems.append(
                    "%s: '# phases' trailer says n=%s but the table "
                    "holds %d steady rows" % (base, trailer.get("n"),
                                              n))
            for phase, s in sorted(stats.items()):
                for stat_key, fmt in (("mean_ms", "%s_mean_us"),
                                      ("p99_ms", "%s_p99_us")):
                    want = round(s[stat_key] * 1000)
                    got = trailer.get(fmt % phase)
                    if got is None or abs(got - want) > 1:
                        problems.append(
                            "%s: '# phases' trailer %s=%s but the "
                            "table's rows recompute to %d"
                            % (base, fmt % phase, got, want))
    if "phases" in meta:
        if not saw_phase_trailer and tables:
            problems.append("log-meta carries a 'Phases:' line but no "
                            "table carries a '# phases' trailer")
        stats = trace.phase_stats(merged)
        line = meta["phases"]
        if set(line) != set(stats):
            problems.append(
                "'Phases:' line names phases %s but the tables "
                "recompute %s" % (sorted(line), sorted(stats)))
        else:
            for phase, s in sorted(stats.items()):
                if line[phase].get("count") != s["count"]:
                    problems.append(
                        "'Phases:' %s count=%s but tables hold %d "
                        "steady samples" % (phase,
                                            line[phase].get("count"),
                                            s["count"]))
                for stat_key in ("mean_ms", "p99_ms"):
                    got = line[phase].get(stat_key)
                    if got is None or abs(got - s[stat_key]) > 0.005:
                        problems.append(
                            "'Phases:' %s %s=%s but tables recompute "
                            "%.6f" % (phase, stat_key, got,
                                      s[stat_key]))
    elif saw_phase_trailer:
        problems.append("tables carry a '# phases' trailer but "
                        "log-meta has no 'Phases:' line")
    return problems


def _check_trace_artifact(job_dir: str,
                          meta: Dict[str, object]) -> List[str]:
    problems: List[str] = []
    path = os.path.join(job_dir, "trace.json")
    if "trace_events" in meta:
        if not os.path.isfile(path):
            return ["log-meta carries a 'Trace:' line but trace.json "
                    "is missing"]
        trace = _rnb_trace()
        import json
        try:
            with open(path) as f:
                doc = json.load(f)
        except ValueError as e:
            return ["trace.json unreadable: %s" % e]
        recorded = doc.get("otherData", {}).get("num_events")
        if recorded != meta["trace_events"]:
            problems.append(
                "'Trace:' line says events=%s but trace.json records "
                "num_events=%s" % (meta["trace_events"], recorded))
        dropped = doc.get("otherData", {}).get("dropped_events")
        if dropped != meta.get("trace_dropped"):
            problems.append(
                "'Trace:' line says dropped=%s but trace.json records "
                "dropped_events=%s" % (meta.get("trace_dropped"),
                                       dropped))
        for issue in trace.validate_trace(path)[:5]:
            problems.append("trace.json: %s" % issue)
    elif os.path.isfile(path):
        problems.append("trace.json present but log-meta has no "
                        "'Trace:' line")
    return problems


def _configured_buckets(job_dir: str) -> set:
    """Every row count the job's config could legally warm: the union
    of ``row_buckets`` / ``max_clips`` / ``max_rows`` values across
    steps and groups of the config copy benchmark.py drops into the
    job dir, plus ``autotune.buckets``. Empty when no config copy is
    found, or when a step that could participate (not opted out via
    ``"autotune": false``) declares none of those knobs — its warmed
    set then comes from constructor defaults the JSON never names, so
    the vocabulary is incomplete and the subset check is skipped
    rather than flagging a healthy run."""
    import json
    out: set = set()
    for name in sorted(os.listdir(job_dir)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(job_dir, name)) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(raw, dict) or "pipeline" not in raw:
            continue
        autotune = raw.get("autotune")
        if isinstance(autotune, dict):
            out.update(int(b) for b in autotune.get("buckets") or [])
        for step in raw["pipeline"]:
            if not isinstance(step, dict):
                continue
            scopes = [step] + [g for g in step.get("queue_groups", [])
                               if isinstance(g, dict)]
            declared: set = set()
            for scope in scopes:
                declared.update(int(b) for b
                                in scope.get("row_buckets") or [])
                for key in ("max_clips", "max_rows"):
                    if isinstance(scope.get(key), int):
                        declared.add(scope[key])
            if declared:
                out.update(declared)
            elif step.get("autotune") is not False:
                return set()  # default-shaped stage: vocab unknown
        break
    return out


def print_stamp_registry(out=None) -> None:
    """Emit the generated telemetry-schema reference (``--stamps``):
    the declared stamp patterns, log-meta lines and table trailers
    from rnb_tpu.telemetry — the registries the static schema checker
    (rnb_tpu.analysis.schema) holds this parser to."""
    import sys as _sys
    out = out or _sys.stdout
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from rnb_tpu.telemetry import (META_LINE_REGISTRY, STAMP_REGISTRY,
                                   TABLE_TRAILER_REGISTRY,
                                   TRACE_EVENT_REGISTRY, CONTENT_STAMPS)
    out.write("# Telemetry schema reference (generated by "
              "parse_utils.py --stamps)\n")
    out.write("# Source of truth: rnb_tpu/telemetry.py registries; "
              "cross-checked in tier-1 by scripts/rnb_lint.py.\n\n")
    out.write("## TimeCard stamps ({step} = pipeline step index; "
              "merged segment\n## cards suffix post-fork stamps with "
              "-{sub_id})\n")
    for spec in STAMP_REGISTRY:
        out.write("%-26s %-22s %s\n" % (spec.pattern, spec.producer,
                                        spec.description))
    out.write("\n## Content stamps (TimeCard attributes that survive "
              "fork/merge)\n")
    out.write("%s\n" % " ".join(CONTENT_STAMPS))
    out.write("\n## log-meta.txt lines (plus one bare '<start> <end>' "
              "timestamp line)\n")
    for spec in META_LINE_REGISTRY:
        out.write("%-26s %-22s %s\n" % (spec.pattern, spec.producer,
                                        spec.description))
    out.write("\n## Timing-table trailers ('# <kind> ...')\n")
    for spec in TABLE_TRAILER_REGISTRY:
        out.write("%-26s %-22s %s\n" % (spec.pattern, spec.producer,
                                        spec.description))
    out.write("\n## Trace events (logs/<job>/trace.json, trace-enabled "
              "runs only;\n## {step} = pipeline-step or queue index)\n")
    for spec in TRACE_EVENT_REGISTRY:
        out.write("%-26s %-22s %s\n" % (spec.pattern, spec.producer,
                                        spec.description))


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="Benchmark log parsing and consistency checking")
    parser.add_argument("job_dirs", nargs="*",
                        help="logs/<job_id> directories to inspect")
    parser.add_argument("--check", action="store_true",
                        help="cross-check log-meta vs timing tables vs "
                             "trailers; non-zero exit on inconsistency")
    parser.add_argument("--stamps", action="store_true",
                        help="print the generated telemetry-schema "
                             "reference (stamp registry) and exit")
    parser.add_argument("--attribute", action="store_true",
                        help="per-request phase attribution: print the "
                             "per-phase mean/p99 table derived from "
                             "TimeCard stamps alone and verify phases "
                             "sum to end-to-end latency")
    args = parser.parse_args(argv)
    if args.stamps:
        print_stamp_registry()
        return 0
    if not args.job_dirs:
        parser.error("job_dirs required unless --stamps is given")
    status = 0
    for job_dir in args.job_dirs:
        # --attribute/--check compose: both run, worst status wins
        if args.attribute:
            status = max(status, print_attribution(job_dir))
        if args.check:
            # exit discipline matches the rnb-lint CLI: 2 = the
            # artifacts could not be parsed (the check never ran), 1 =
            # parsable artifacts violating an invariant, 0 = clean
            problems, parse_failed = check_job_detail(job_dir)
            if problems:
                status = max(status, 2 if parse_failed else 1)
                print("%s: INCONSISTENT" % job_dir)
                for problem in problems:
                    print("  - %s" % problem)
            else:
                print("%s: OK" % job_dir)
        if not args.attribute and not args.check:
            meta, df = get_data(job_dir)
            print("%s: %d requests" % (job_dir, len(df)))
            for key in sorted(meta):
                print("  %s = %r" % (key, meta[key]))
    return status


if __name__ == "__main__":
    import sys
    sys.exit(main())
