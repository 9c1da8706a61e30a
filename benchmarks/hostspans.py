"""The program's own spans, read from the profiler's host plane.

Since PR 24 a ``rnb_tpu.trace.span/instant`` under a profiler session
opens a ``jax.profiler.TraceAnnotation``: in a traced run the program's
spans lie in the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
chip's operations, one line per OS thread, with their counts (``rows``,
``rows_valid``, ``device``, ``rid``) as event stats. Kept here, with
the benchmark, is what is made of them:

* **idle by the executor's state**: the final stage waits for its
  outputs before it takes the next batch, so whenever step K's executor
  thread is outside ``model_call`` and ``device_sync`` the chip has
  nothing of its to run. Over the stretch from the first recorded
  ``exec{K}.model_call`` to the last recorded ``exec{K}.device_sync``
  the thread's time is ``starved`` (``queue_get``, ``hold_wait``: no
  batch to take), ``launch`` (``model_call``, ``device_sync``) or
  ``host_loop`` (everything else: handoff, finish, publish, unspanned).
  All of ``starved`` and ``host_loop`` is idle time of the chip; of the
  ``launch`` time the chip was idle for what is left once its busy time
  is taken off (a program handed over and not started, or ended and
  the host not yet awake). The three are shares of that stretch, which
  a capture's cut dispatches at both edges make a little shorter than
  the capture, and add up to the chip's idle share of it. They need
  the device's clock only to cut the operations at the stretch's two
  ends, so an offset between the planes moves them by offset /
  stretch: 0.01 points for a millisecond in ten seconds;
* **the clock check**, which is what says that the thread read is the
  one that drives this chip and that the chip is idle outside its
  ``launch`` time: each ``exec{K}.model_call`` is paired with the
  program of the chip's ``XLA Modules`` line that starts nearest to it
  (within 5 ms; the capture's two edges cut off); the program's first
  operation has to start after the span opened and its last one end
  before the following ``exec{K}.device_sync`` closed (0.1 ms of
  tolerance). On the v5e the profiler lays the two planes up to 1.2 ms
  apart, by another amount in every capture (PERF.md, PR 24), so the
  rule is held to after one constant shift of the device's times: the
  smallest in size under which every pair keeps both halves. Where the
  lists do not pair, where that shift is over :data:`MAX_SHIFT_NS`
  (2 ms: more than the planes were ever seen apart, less than the gap
  between two dispatches), or where more than 1% of the pairs break
  the rule under it, the spans are not trusted and the idle shares read
  ``None``. The shift and the room left beside it are written to
  ``hostspans.json``;
* **counts inside the traced window**: rows shipped and valid rows of
  the dispatches, durations of ``loader.transfer``;
* **a request's phases** from the stamp tables of the run, with a copy
  of ``rnb_tpu.trace.phase_of``'s rules (the benchmark stays
  independent of the program's arithmetic, as ``stamps.percentile``
  does): every gap between two stamps falls in one class, so the
  classes sum to finish - ``enqueue_filename``.

On a program from before PR 24 the host plane holds none of these
names and the tables lack the refinement columns: everything here reads
``None`` and raises nothing. ``python -m benchmarks.hostspans
<file.xplane.pb>`` prints seconds, calls and mean by span name.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
TOLERANCE_NS = 1e5  # 0.1 ms
MAX_VIOLATION_SHARE = 0.01
#: a program starts within this of its span's start, or is not its pair
PAIR_RADIUS_NS = 5e6
#: planes farther apart than this are a clock fault, not a capture's
#: constant (seen on the v5e: 0 to 1.2 ms)
MAX_SHIFT_NS = 2e6

STARVED, LAUNCH, HOST_LOOP = "starved", "launch", "host_loop"
STATE_OF_SPAN = {"queue_get": STARVED, "hold_wait": STARVED,
                 "model_call": LAUNCH, "device_sync": LAUNCH}

#: the six classes the phase metrics report, by phase_of's names
PHASE_CLASSES = {
    "client_queue": ("client_queue",),
    "decode": ("decode",),
    "hold": ("hold",),
    "transfer": ("transfer",),
    "ring_wait": ("drain", "inter_stage_queue"),
    "device": ("inference",),
}

Interval = Tuple[float, float]


class Span:
    __slots__ = ("start", "end", "name", "stats", "line")

    def __init__(self, start, end, name, stats=None, line=0):
        self.start = float(start)
        self.end = float(end)
        self.name = name
        self.stats = stats or {}
        self.line = line


def registered_names():
    """A predicate for the program's declared event names
    (``telemetry.TRACE_EVENT_REGISTRY``, ``{step}`` a number)."""
    from rnb_tpu.telemetry import TRACE_EVENT_REGISTRY
    pattern = re.compile("^(?:%s)$" % "|".join(
        re.escape(spec.pattern).replace(re.escape("{step}"), r"\d+")
        for spec in TRACE_EVENT_REGISTRY))
    return lambda name: pattern.match(name) is not None


def read_trace(path: str, device_plane: str):
    """-> (spans, modules): the registered events of the host plane by
    start, and the programs the chip ran by start (None where the
    device's plane has no ``XLA Modules`` line: the CPU stand-in of a
    dry run)."""
    from jax.profiler import ProfileData
    known = registered_names()
    spans: List[Span] = []
    modules: Optional[List[Interval]] = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for idx, line in enumerate(plane.lines):
                spans += [Span(e.start_ns, e.start_ns + e.duration_ns,
                               e.name, dict(e.stats), idx)
                          for e in line.events if known(e.name)]
        if plane.name == device_plane:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = sorted((float(e.start_ns),
                                      float(e.start_ns + e.duration_ns))
                                     for e in line.events)
    spans.sort(key=lambda s: s.start)
    return spans, modules


def last_step(spans: Sequence[Span]) -> Optional[int]:
    steps = [int(m.group(1)) for m in (
        re.match(r"^exec(\d+)\.model_call$", s.name) for s in spans) if m]
    return max(steps) if steps else None


def executor_spans(spans: Sequence[Span], step: int,
                   device_index: Optional[int]) -> List[Span]:
    """The spans ``exec{step}.*`` of the one thread that dispatched to
    this device: the line with the most of its ``model_call`` spans."""
    prefix = "exec%d." % step
    calls: Dict[int, int] = {}
    for s in spans:
        if s.name == prefix + "model_call" and (
                device_index is None or "device" not in s.stats
                or int(s.stats["device"]) == device_index):
            calls[s.line] = calls.get(s.line, 0) + 1
    if not calls:
        return []
    line = max(calls, key=calls.get)
    return [s for s in spans if s.line == line
            and s.name.startswith(prefix)]


def pair_dispatches(calls: Sequence[Span], modules: Sequence[Interval]):
    """Each ``model_call`` with the module that starts nearest to it,
    within :data:`PAIR_RADIUS_NS` (dispatches of a stage that waits
    for its outputs lie tens of ms apart), each module taken once. The
    capture's two edges are cut: a program that ran when the capture
    began has no span in it, and the program of the last span may be
    cut short or missing. -> ([(call, module)], loose,
    note): ``loose`` counts what, between the edges, has no partner (a
    span without a program, a program without a span); it is held to
    the same 1% as the clock rule, beyond which there are no pairs."""
    starts = [m[0] for m in modules]
    pairs, loose_calls, used = [], [], set()
    for k, call in enumerate(calls):
        near = [i for i in range(
            bisect.bisect_left(starts, call.start - PAIR_RADIUS_NS),
            bisect.bisect_right(starts, call.start + PAIR_RADIUS_NS))
            if i not in used]
        if near:
            i = min(near, key=lambda i: abs(starts[i] - call.start))
            used.add(i)
            pairs.append((call, modules[i]))
        else:
            loose_calls.append(k)
    loose_modules = [i for i in range(len(modules)) if i not in used]
    if not pairs:
        return [], 0, ("%d model_call spans against %d module events: "
                       "none pair" % (len(calls), len(modules)))
    first, last = calls.index(pairs[0][0]), calls.index(pairs[-1][0])
    first_m, last_m = (modules.index(pairs[0][1]),
                       modules.index(pairs[-1][1]))
    inner = [k for k in loose_calls if first < k < last] \
        + [i for i in loose_modules if first_m < i < last_m]
    if not inner:
        return pairs, 0, None
    note = ("%d model_call spans against %d module events: %d without a "
            "partner between the capture's edges"
            % (len(calls), len(modules), len(inner)))
    if len(inner) > MAX_VIOLATION_SHARE * len(pairs):
        return [], len(inner), note + ": they do not pair one to one"
    return pairs, len(inner), note + ", left out"


def _first_last_op(module: Interval, ops, starts) -> Interval:
    """Start of the first and end of the last operation inside a
    program's event (the event's own bounds where it holds none)."""
    inside = ops[bisect.bisect_left(starts, module[0]):
                 bisect.bisect_right(starts, module[1])]
    return (min((o[0] for o in inside), default=module[0]),
            max((o[1] for o in inside), default=module[1]))


def check_clock(pairs, syncs: Sequence[Span], ops: Sequence[Interval]):
    """-> (checked, raw violations, shift_ns, slack_ns, violations
    after the shift). For each pair: the program's first operation
    starts after the span opened, its last one ends before the
    following ``device_sync`` closed. ``shift_ns``, added to the
    device's times, is the constant of smallest size under which both
    halves hold for every pair, and ``slack_ns`` the width of the range
    it was taken from; where no constant does (``slack_ns`` below 0),
    it is the smallest that lets every program start after its span
    opened, and the second half is counted again."""
    starts = [o[0] for o in ops]
    sync_starts = [s.start for s in syncs]
    rows = []
    for call, module in pairs:
        first, last = _first_last_op(module, ops, starts)
        i = bisect.bisect_left(sync_starts, call.end)
        rows.append((call.start, first, last,
                     syncs[i].end if i < len(syncs) else None))
    least = max(opened - first for opened, first, _, _ in rows)
    most = min([closed - last for _, _, last, closed in rows
                if closed is not None] or [least])
    shift = min(max(0.0, least), most) if least <= most else least

    def broken(by: float) -> int:
        return sum(first + by < opened - TOLERANCE_NS
                   or (closed is not None
                       and last + by > closed + TOLERANCE_NS)
                   for opened, first, last, closed in rows)

    return len(rows), broken(0.0), shift, most - least, broken(shift)


def classify(exec_spans: Sequence[Span], ops: Sequence[Interval]):
    """-> ((start, end), {state: ns of the chip's idle time}) over the
    stretch from the thread's first ``model_call`` to its last
    ``device_sync``, or (None, note). ``ops`` on the spans' clock."""
    from benchmarks.xplane import merge
    calls = [s for s in exec_spans if s.name.endswith(".model_call")]
    syncs = [s for s in exec_spans if s.name.endswith(".device_sync")
             and calls and s.end > calls[0].start]
    if not syncs:
        return None, "no dispatch with both its spans in the capture"
    lo, hi = calls[0].start, max(s.end for s in syncs)
    in_state = {STARVED: 0.0, LAUNCH: 0.0}
    cursor = lo
    for s in sorted(exec_spans, key=lambda s: s.start):
        state = STATE_OF_SPAN.get(s.name.split(".", 1)[1])
        if state is not None and min(s.end, hi) > max(s.start, cursor):
            in_state[state] += min(s.end, hi) - max(s.start, cursor)
            cursor = min(s.end, hi)
    busy = sum(min(b, hi) - max(a, lo)
               for a, b in merge([(a, b, "") for a, b in ops])
               if min(b, hi) > max(a, lo))
    idle = {STARVED: in_state[STARVED], LAUNCH: in_state[LAUNCH] - busy,
            HOST_LOOP: (hi - lo) - in_state[STARVED] - in_state[LAUNCH]}
    if idle[LAUNCH] < -TOLERANCE_NS * len(calls):
        return None, ("the chip was busy %.3f ms longer than step K's "
                      "executor was in model_call and device_sync: it "
                      "works outside them, and the host's spans alone do "
                      "not say when it is idle" % (-idle[LAUNCH] / 1e6))
    return (lo, hi), idle


# -- a request's phases: a copy of rnb_tpu.trace.phase_of's rules ---------

_STAMP = re.compile(r"^(runner|decode|transfer|inference)(\d+)_"
                    r"(start|done|finish)(?:-\d+)?$")


def phase_of(prev_key: str, next_key: str) -> str:
    m = _STAMP.match(next_key)
    if m is None:
        return "drain"
    kind, step, edge = m.group(1), int(m.group(2)), m.group(3)
    if (kind, edge) in (("runner", "start"), ("inference", "start")):
        return "client_queue" if step == 0 else "inter_stage_queue"
    if (kind, edge) == ("decode", "done"):
        return "decode"
    if (kind, edge) == ("transfer", "start"):
        return "hold"
    if (kind, edge) == ("transfer", "done"):
        return "transfer"
    if (kind, edge) == ("inference", "finish"):
        p = _STAMP.match(prev_key)
        if p and (p.group(1), p.group(3)) == ("transfer", "done") \
                and int(p.group(2)) == step:
            return "drain"
        return "decode" if step == 0 else "inference"
    return "drain"


def phases_ms(row: Dict[str, float]) -> Dict[str, float]:
    """One request's stamps -> ms in each of the six classes."""
    stamps = sorted((t, key) for key, t in row.items() if t == t)
    by_phase: Dict[str, float] = {}
    for (t0, k0), (t1, k1) in zip(stamps, stamps[1:]):
        phase = phase_of(k0, k1)
        by_phase[phase] = by_phase.get(phase, 0.0) + (t1 - t0) * 1e3
    return {name: sum(by_phase.get(p, 0.0) for p in parts)
            for name, parts in PHASE_CLASSES.items()}


def mean_phases(tables: Dict[str, List[dict]], sent, due_in_window
                ) -> Optional[Dict[str, float]]:
    """Mean ms by class over the finished requests due in the window,
    and ``total``: the mean of finish - ``enqueue_filename`` over the
    same requests. None where the tables lack the refinement stamps."""
    order = [float(s) for s in sent if s == s]
    sums: Dict[str, float] = {}
    count = 0
    for rows in tables.values():
        for row in rows:
            if not any(re.match(r"^decode\d+_done", k) for k in row):
                return None
            i = bisect.bisect_right(order, row["enqueue_filename"]) - 1
            if i < 0 or not due_in_window[i]:
                continue
            count += 1
            for name, ms in phases_ms(row).items():
                sums[name] = sums.get(name, 0.0) + ms
            stamps = [t for t in row.values() if t == t]
            sums["total"] = sums.get("total", 0.0) \
                + (max(stamps) - row["enqueue_filename"]) * 1e3
    if not count:
        return None
    return {name: total / count for name, total in sums.items()}


# -- one traced run ---------------------------------------------------------


class HostSpans:
    """What the host plane of one traced run says. ``ops`` are the
    operation intervals of one device on the same file's clock,
    ``modules`` its programs (None: no such line), ``window_s`` the
    traced window that ``device_idle_pct`` divides by."""

    def __init__(self, spans: Sequence[Span], ops: Sequence[Interval],
                 modules: Optional[Sequence[Interval]], window_s: float,
                 device_index: Optional[int] = None):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.window_s = float(window_s)
        self.notes: List[str] = []
        self.step = last_step(self.spans)
        self.checked = self.violations = self.raw_violations = 0
        self.shift_ns = self.slack_ns = 0.0
        self.unpaired = 0
        self.idle_ns: Optional[Dict[str, float]] = None
        self.classified_ns = self.capture_ns = 0.0
        self.exec_spans: List[Span] = []
        if self.step is None:
            self.notes.append("no exec*.model_call span in the host plane")
            return
        self.exec_spans = executor_spans(self.spans, self.step,
                                         device_index)
        ops = sorted((o[0], o[1]) for o in ops)
        if not ops or not self.exec_spans:
            self.notes.append("no operations or no executor spans")
            return
        self.capture_ns = max(o[1] for o in ops) - ops[0][0]
        if self.capture_ns < 0.99 * self.window_s * 1e9:
            self.notes.append(
                "the capture holds %.3f s of the %.3f s window: "
                "device_idle_pct of this run counts the rest as idle, the "
                "shares here are of what was captured"
                % (self.capture_ns / 1e9, self.window_s))
        if modules is None:
            self.notes.append("clock not checked: the device plane has no "
                              "%r line" % MODULES_LINE)
        elif not self._clock_ok(modules, ops):
            return
        stretch, found = classify(
            self.exec_spans, [(lo + self.shift_ns, hi + self.shift_ns)
                              for lo, hi in ops])
        if stretch is None:
            self.notes.append(found)
        else:
            self.classified_ns, self.idle_ns = stretch[1] - stretch[0], found

    def _clock_ok(self, modules, ops) -> bool:
        name = "exec%d." % self.step
        pairs, self.unpaired, note = pair_dispatches(
            [s for s in self.exec_spans if s.name == name + "model_call"],
            modules)
        if note:
            self.notes.append(note)
        if not pairs:
            return False
        (self.checked, self.raw_violations, self.shift_ns, self.slack_ns,
         self.violations) = check_clock(
            pairs, [s for s in self.exec_spans
                    if s.name == name + "device_sync"], ops)
        if self.raw_violations:
            self.notes.append(
                "%d of %d dispatches break the clock rule as recorded; "
                "device times shifted by %.3f ms"
                % (self.raw_violations, self.checked, self.shift_ns / 1e6))
        if abs(self.shift_ns) > MAX_SHIFT_NS:
            self.notes.append(
                "the planes lie %.3f ms apart, over the %.1f ms a capture's "
                "constant may be: host spans are not on the device's clock"
                % (self.shift_ns / 1e6, MAX_SHIFT_NS / 1e6))
            return False
        if self.violations > MAX_VIOLATION_SHARE * self.checked:
            self.notes.append(
                "%d of %d dispatches break the clock rule under any "
                "constant shift: host spans are not on the device's clock"
                % (self.violations, self.checked))
            return False
        return True

    def idle_pct(self, state: str) -> Optional[float]:
        """% of the classified stretch (not of ``window_s``: a capture
        is a little longer than the window, or much shorter where the
        profiler lost its start) the chip idled under ``state``."""
        if self.idle_ns is None or not self.classified_ns:
            return None
        return 100.0 * self.idle_ns[state] / self.classified_ns

    def dispatch_rows(self) -> List[Tuple[int, int]]:
        """(rows shipped, rows valid) of step K's traced dispatches."""
        return [(int(s.stats["rows"]), int(s.stats["rows_valid"]))
                for s in self.exec_spans
                if s.name.endswith(".model_call") and "rows" in s.stats]

    def pad_row_pct(self) -> Optional[float]:
        rows = self.dispatch_rows()
        shipped = sum(r for r, _ in rows)
        return 100.0 * sum(r - v for r, v in rows) / shipped \
            if shipped else None

    def rows_per_dispatch(self) -> Optional[float]:
        rows = self.dispatch_rows()
        return sum(r for r, _ in rows) / len(rows) if rows else None

    def put_ms(self) -> Optional[float]:
        """Mean ms from a batch's ``loader.transfer`` opening to its
        bytes being on the device. ``jax.device_put`` returns once the
        copy is handed over (0.3-0.5 ms for 29 MB on the v5e: PERF.md,
        PR 24); the copy is over when the same thread's next
        ``exec{i}.device_sync`` closes, which is the loader's executor
        waiting for exactly that array. On a transfer worker's thread
        no such span follows, and the ``transfer.job`` that holds the
        span (it confirms the copy) is taken."""
        by_line: Dict[int, List[Span]] = {}
        for s in self.spans:
            by_line.setdefault(s.line, []).append(s)
        total, count = 0.0, 0
        for line in by_line.values():
            for i, s in enumerate(line):
                if s.name != "loader.transfer":
                    continue
                jobs = [t.end for t in line[max(0, i - 4):i]
                        if t.name == "transfer.job" and t.end >= s.end]
                syncs = [t.end for t in line[i + 1:i + 8]
                         if re.match(r"^exec\d+\.device_sync$", t.name)]
                done = jobs[0] if jobs else syncs[0] if syncs else s.end
                total += done - s.start
                count += 1
        return total / count / 1e6 if count else None

    def table(self) -> List[Tuple[str, float, int, float]]:
        """(name, seconds, calls, mean_us), longest first."""
        acc: Dict[str, List[float]] = {}
        for s in self.spans:
            entry = acc.setdefault(s.name, [0.0, 0])
            entry[0] += s.end - s.start
            entry[1] += 1
        return sorted(((n, t / 1e9, c, t / c / 1e3)
                       for n, (t, c) in acc.items()), key=lambda r: -r[1])

    def summary(self) -> dict:
        return {"step": self.step, "dispatches_checked": self.checked,
                "clock_violations_as_recorded": self.raw_violations,
                "device_shift_ms": self.shift_ns / 1e6,
                "shift_slack_ms": self.slack_ns / 1e6,
                "max_shift_ms": MAX_SHIFT_NS / 1e6,
                "clock_violations": self.violations,
                "unpaired_between_edges": self.unpaired, "notes": self.notes,
                "idle_s": {k: v / 1e9 for k, v in self.idle_ns.items()}
                if self.idle_ns else None,
                "classified_s": self.classified_ns / 1e9,
                "capture_s": self.capture_ns / 1e9,
                "window_s": self.window_s,
                "spans": [[n, s, c] for n, s, c, _ in self.table()]}


def describe(spans: "HostSpans") -> str:
    lines = ["%-28s %10s %8s %10s" % ("span", "seconds", "calls",
                                      "mean_us")]
    lines += ["%-28s %10.4f %8d %10.1f" % row for row in spans.table()]
    return "\n".join(lines)


def from_trace(trace) -> HostSpans:
    """From a :class:`benchmarks.xplane.TraceFacts`: the idlest device,
    the one ``device_idle_pct`` is read on."""
    plane = min(trace.busy_s, key=trace.busy_s.get)
    index = re.search(r"(\d+)$", plane)
    spans, modules = read_trace(trace.path, plane)
    return HostSpans(spans, trace.by_device[plane], modules,
                     trace.window_s, int(index.group(1)) if index else None)


def of(facts) -> Optional[HostSpans]:
    """The run's host spans, reduced once; None without a trace. What
    the reduction has to say goes to standard error and to
    ``hostspans.json`` beside the run's stamp tables."""
    if facts.trace is None:
        return None
    cached = getattr(facts, "_hostspans", None)
    if cached is None:
        cached = facts._hostspans = from_trace(facts.trace)
        print("[bench] hostspans: step %s, %d dispatches checked, %d off "
              "the clock as recorded, %d after a shift of %.3f ms (%.3f ms "
              "of room); %.3f s classified; %s"
              % (cached.step, cached.checked, cached.raw_violations,
                 cached.violations, cached.shift_ns / 1e6,
                 cached.slack_ns / 1e6, cached.classified_ns / 1e9,
                 "; ".join(cached.notes) or "no notes"),
              file=sys.stderr, flush=True)
        log_dir = getattr(facts.result, "log_dir", None)
        if log_dir and os.path.isdir(log_dir):
            with open(os.path.join(log_dir, "hostspans.json"), "w") as f:
                json.dump(cached.summary(), f, indent=1)
    return cached


def notes_of(facts) -> Optional[dict]:
    """What the reduction said of this run (its summary without the
    span table: the notes, the clock check's counts and constant), for
    the result line's ``notes``; None where no reader asked for one."""
    cached = getattr(facts, "_hostspans", None)
    if cached is None:
        return None
    return {k: v for k, v in cached.summary().items() if k != "spans"}


def idle_pct(facts, state: str) -> Optional[float]:
    spans = of(facts)
    return spans.idle_pct(state) if spans is not None else None


def phase_ms(facts, name: str) -> Optional[float]:
    """Mean ms of one class of :data:`PHASE_CLASSES` over the finished
    requests due in the window."""
    cached = getattr(facts, "_phases", False)
    if cached is False:
        from benchmarks import stamps
        cached = facts._phases = mean_phases(
            stamps.read_tables(facts.result.log_dir), facts.schedule.sent,
            facts.due_in_window)
        if cached is not None:
            print("[bench] hostspans: mean ms by phase %s"
                  % json.dumps({k: round(v, 3) for k, v in cached.items()}),
                  file=sys.stderr, flush=True)
    return cached[name] if cached is not None else None


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import xplane
    facts_ = xplane.TraceFacts(sys.argv[1], window_s=1.0)
    ops_ = facts_.by_device[min(facts_.busy_s, key=facts_.busy_s.get)]
    facts_.window_s = (max(o[1] for o in ops_) - ops_[0][0]) / 1e9
    reduced = from_trace(facts_)
    print(describe(reduced))
    print(json.dumps({k: v for k, v in reduced.summary().items()
                      if k != "spans"}))
