"""Device time by named scope and by row bucket, inside the dispatches
that the program's own spans name.

Three things the run leaves are put together:

* the final stage's ``hlo-scopes.json`` (``rnb_tpu/hloscopes.py``):
  ``"<instruction> <result shape>" -> op_name``, the path of
  ``jax.named_scope``s an instruction was traced under. An ``XLA Ops``
  event opens with the same two words, so an operation's self time
  falls to the leftmost scope of :data:`SCOPES` on its path, or to
  ``other`` (a copy of a parameter carries none). It is the join
  ``benchmarks/subscopes.py`` makes;
* the ``exec{K}.model_call`` spans of the final step's executor thread,
  each with ``rows`` (the bucket it shipped) and ``rows_valid``;
* the chip's ``XLA Modules`` line, one event a program, which
  ``hostspans.pair_dispatches`` pairs with those spans under the
  capture's clock shift. Operations and programs are on one clock: the
  shift is needed for the pairing alone.

Only operations that start inside a paired program are counted, and
the divisor is the sum of those spans' ``rows_valid``: ms a row (a
clip, for R(2+1)D) is device time of exactly the rows it is divided by,
whatever finished or did not finish inside the capture. By construction
the scopes' seconds and ``other`` add up to the paired programs'
operation time.

A run with no table (a program from before the stage wrote one), no
device plane, no ``XLA Modules`` line, or a clock check that failed
reads ``None`` everywhere and raises nothing.

**A stage's share of its roofline** needs the stage's work, which is
its family's to count: a file ``families/<family>_stages.py`` beside
the family file brings ``stage_work(model)`` (operations and bytes of
each scope from the configuration's ``model`` block), and a family that
brings none reads ``None``.

``python -m benchmarks.stages <run directory>`` prints scope x row
bucket -> calls, ms a dispatch, ms a row, share of the paired
programs' time, for any family whose final stage wrote a table.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import hostspans, scopes, xplane

#: the top scopes the families' programs open
#: (``rnb_tpu/models/*/network.py``), in the order they run
SCOPES = ("ingest", "stem", "stage2", "stage3", "stage4", "stage5",
          "embed", "ssd", "attn", "experts", "mlp", "head")
OTHER = "other"
TABLE_FILE = "hlo-scopes.json"

Op = Tuple[float, float, str]  # start_ns, end_ns, "<instruction> <shape>"
#: rows shipped, rows valid, the program's (start_ns, end_ns)
Dispatch = Tuple[int, int, Tuple[float, float]]


def scope_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return OTHER


class Account:
    """{rows shipped: calls, valid rows, {scope: seconds}} over the
    paired dispatches of one traced run."""

    def __init__(self):
        self.calls: Dict[int, int] = {}
        self.valid: Dict[int, int] = {}
        self.spent: Dict[int, Dict[str, float]] = {}

    def add(self, rows: int, rows_valid: int,
            by_scope: Dict[str, float]) -> None:
        self.calls[rows] = self.calls.get(rows, 0) + 1
        self.valid[rows] = self.valid.get(rows, 0) + rows_valid
        into = self.spent.setdefault(rows, {})
        for scope, seconds in by_scope.items():
            into[scope] = into.get(scope, 0.0) + seconds

    def seconds(self, scope: Optional[str] = None,
                rows: Optional[int] = None) -> float:
        """Of one scope (None: of all) in one bucket (None: in all)."""
        return sum(s for bucket, by_scope in self.spent.items()
                   if rows in (None, bucket)
                   for name, s in by_scope.items()
                   if scope in (None, name))

    def rows_valid(self, rows: Optional[int] = None) -> int:
        return sum(n for bucket, n in self.valid.items()
                   if rows in (None, bucket))

    def dispatches(self, rows: Optional[int] = None) -> int:
        return sum(n for bucket, n in self.calls.items()
                   if rows in (None, bucket))


def reduce(ops: Sequence[Op], dispatches: Sequence[Dispatch],
           op_names: Dict[str, str]) -> Account:
    """``ops`` by start. Self times (a ``while`` holds its body's
    operations) of the operations that start inside each dispatch's
    program, by the scope of ``op_names``."""
    starts = [op[0] for op in ops]
    account = Account()
    for rows, rows_valid, (lo, hi) in dispatches:
        inside = ops[bisect.bisect_left(starts, lo):
                     bisect.bisect_right(starts, hi)]
        by_scope: Dict[str, float] = {}
        for key, ns in xplane.self_times(inside).items():
            scope = scope_of(op_names.get(key, ""))
            by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
        account.add(rows, rows_valid, by_scope)
    return account


def read_ops(path: str, plane_name: str) -> List[Op]:
    from jax.profiler import ProfileData
    ops: List[Op] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for e in line.events:
                head = scopes._INSTRUCTION.match(e.name)
                ops.append((float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            "%s %s" % head.groups() if head else ""))
    return sorted(ops)


def clock_held(spans: "hostspans.HostSpans") -> bool:
    """The verdict of ``hostspans``' clock check: dispatches paired,
    the planes no farther apart than a capture's constant, and under
    that constant every program inside its spans but 1%."""
    return spans.checked > 0 \
        and abs(spans.shift_ns) <= hostspans.MAX_SHIFT_NS \
        and spans.violations <= hostspans.MAX_VIOLATION_SHARE * spans.checked


def paired_dispatches(trace) -> Tuple[Optional[List[Dispatch]], str, str]:
    """-> (the final step's dispatches that have both a span with its
    row counts and a program, the device plane read, a note) from a
    :class:`benchmarks.xplane.TraceFacts`; None where the planes do not
    pair or the clock check fails."""
    plane = min(trace.busy_s, key=trace.busy_s.get)
    index = re.search(r"(\d+)$", plane)
    spans, modules = hostspans.read_trace(trace.path, plane)
    if modules is None:
        return None, plane, "no %r line" % hostspans.MODULES_LINE
    reduced = hostspans.HostSpans(
        spans, trace.by_device[plane], modules, trace.window_s,
        int(index.group(1)) if index else None)
    if not clock_held(reduced):
        return None, plane, "; ".join(reduced.notes) or "no dispatch pairs"
    name = "exec%d.model_call" % reduced.step
    pairs, _, _ = hostspans.pair_dispatches(
        [s for s in reduced.exec_spans if s.name == name], modules)
    found = [(int(call.stats["rows"]), int(call.stats["rows_valid"]),
              module) for call, module in pairs if "rows" in call.stats]
    return found or None, plane, "%d dispatches paired" % len(found)


def read_table(log_dir: str) -> Optional[Dict[str, str]]:
    path = os.path.join(log_dir, TABLE_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def of_trace(trace, op_names: Optional[Dict[str, str]]
             ) -> Tuple[Optional[Account], str]:
    if not op_names:
        return None, "no %s" % TABLE_FILE
    dispatches, plane, note = paired_dispatches(trace)
    if dispatches is None:
        return None, note
    return reduce(read_ops(trace.path, plane), dispatches, op_names), note


def of(facts) -> Optional[Account]:
    """The run's account, reduced once; None without a trace, a table,
    or pairs under a clock that held."""
    if facts.trace is None:
        return None
    cached = getattr(facts, "_stages", False)
    if cached is False:
        cached, note = of_trace(facts.trace,
                                read_table(facts.result.log_dir))
        facts._stages = cached
        if cached is not None and cached.seconds():
            note += "; %.3f s of operations inside them, %.2f%% under a scope" \
                % (cached.seconds(), 100.0 * (
                    1.0 - cached.seconds(OTHER) / cached.seconds()))
        print("[bench] stages: %s" % note, file=sys.stderr, flush=True)
    return cached


def ms_per_row(facts, scope: str) -> Optional[float]:
    """Device milliseconds under ``scope`` a valid row of the paired
    dispatches (a row of R(2+1)D is a clip)."""
    account = of(facts)
    if account is None:
        return None
    spent, rows = account.seconds(scope), account.rows_valid()
    if not spent or not rows:
        return None
    return 1e3 * spent / rows


# -- a stage's share of its roofline ---------------------------------------


def stage_work(config: dict):
    """{scope: work} of one row through the configuration's model, from
    the file ``families/<family>_stages.py`` beside the configuration's
    family file (its ``stage_work(model)``; a work has ``flops`` and
    ``activation_bytes`` a row and ``weight_bytes`` a dispatch), or None
    for a family that brings no such file."""
    from benchmarks import manifest
    path = os.path.join(manifest.FAMILIES_DIR,
                        "%s_stages.py" % config["family"])
    if not os.path.exists(path):
        return None
    return manifest._load_file(path, "families", config["family"]
                               + "_stages").stage_work(config["model"])


def roofline_pct(facts, scope: str) -> Optional[float]:
    """The least time the chip could take for one stage over the valid
    rows of the paired dispatches (the larger of operations over the
    bf16 peak and bytes over the HBM bandwidth) over the device time
    under the stage's scope in those dispatches. Pad rows cost time and
    count no work: they can only lower the share."""
    account = of(facts)
    if account is None or facts.peak_flops_per_s is None:
        return None
    spent, rows = account.seconds(scope), account.rows_valid()
    work = (stage_work(facts.config) or {}).get(scope)
    if not spent or not rows or work is None:
        return None
    from benchmarks import peaks
    nbytes = work.activation_bytes * rows \
        + work.weight_bytes * account.dispatches()
    least_s = max(work.flops * rows / facts.peak_flops_per_s,
                  nbytes / peaks.peak_for(facts.device_kind)[
                      "hbm_bytes_per_s"])
    return 100.0 * least_s / spent


# -- the operator's view --------------------------------------------------


def describe(account: Account) -> str:
    """scope x rows shipped -> calls, ms a dispatch, ms a valid row,
    % of the paired programs' operation time; ``all`` over the buckets."""
    whole = account.seconds()
    lines = ["%-8s %5s %6s %12s %10s %8s"
             % ("scope", "rows", "calls", "ms/dispatch", "ms/row", "share%")]
    found = {name for by_scope in account.spent.values() for name in by_scope}
    for scope in [s for s in SCOPES + (OTHER,) if s in found] + [None]:
        for rows in sorted(account.calls) + [None]:
            spent = account.seconds(scope, rows)
            calls = account.dispatches(rows)
            valid = account.rows_valid(rows)
            lines.append("%-8s %5s %6d %12.3f %10.4f %8.2f" % (
                scope or "total", "all" if rows is None else rows, calls,
                1e3 * spent / calls, 1e3 * spent / valid if valid else 0.0,
                100.0 * spent / whole if whole else 0.0))
    return "\n".join(lines)


def main(argv: Sequence[str]) -> int:
    run = argv[1]
    tables = [root for root, _, files in os.walk(run) if TABLE_FILE in files]
    if not tables:
        print("no %s under %s: the final stage wrote no table"
              % (TABLE_FILE, run), file=sys.stderr)
        return 1
    path = xplane.find_xplane(os.path.join(run, "xplane"))
    trace = xplane.TraceFacts(path, window_s=1.0)
    ops = trace.by_device[min(trace.busy_s, key=trace.busy_s.get)]
    trace.window_s = (max(o[1] for o in ops) - ops[0][0]) / 1e9
    account, note = of_trace(trace, read_table(sorted(tables)[-1]))
    if account is None:
        print("no account: %s" % note, file=sys.stderr)
        return 1
    print(describe(account))
    print("%s; %.4f s of operations inside them of %.4f s in the capture"
          % (note, account.seconds(), sum(trace.self_s.values())))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
