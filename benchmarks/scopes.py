"""Device time by the program's named scopes, from the ``.xplane.pb``.

The program wraps each mechanism in a ``jax.named_scope`` (``ssd``,
``experts``, ``attn``, ``head``, ``embed``). XLA keeps the scope in
each instruction's ``op_name``, but the profiler names a device
operation by its instruction alone (no event field carries the scope:
read on the v5e, PR 28). The join is the program's: a stage that
compiles its programs ahead writes ``hlo-scopes.json`` beside the
run's logs, ``{"<instruction> <result shape>": op_name}``, and an
operation's event, whose name opens with the same two words, is looked
up there. An instruction fused from several scopes carries its root's.
Self time (an event's duration less what its nested events cover) is
summed by scope; what carries none is ``other``.

A run with no such table, or a trace with no device plane, gives None:
a reader then reports nothing rather than a guess.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Optional

from benchmarks import xplane

SCOPES = ("ssd", "experts", "attn", "head", "embed")
_SCOPE = re.compile(r"/(%s)/" % "|".join(SCOPES))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])")
_CACHE: Dict[str, tuple] = {}


def scope_table(log_dir: str) -> Optional[Dict[str, str]]:
    """{"<instruction> <result shape>": scope} of the run's programs."""
    path = os.path.join(log_dir, "hlo-scopes.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        op_names = json.load(f)
    table = {}
    for key, op_name in op_names.items():
        found = _SCOPE.search(op_name + "/")
        if found:
            table[key] = found.group(1)
    return table


def scope_of(event_name: str, table: Dict[str, str]) -> str:
    head = _INSTRUCTION.match(event_name)
    return table.get("%s %s" % head.groups(), "other") if head else "other"


def _reduce(facts):
    """({scope: s}, {instruction name: s}) of self time over the device
    planes, or (None, None)."""
    if facts.trace is None:
        return None, None
    path = facts.trace.path
    if path in _CACHE:
        return _CACHE[path]
    table = scope_table(facts.result.log_dir)
    totals = by_op = None
    if table:
        from jax.profiler import ProfileData
        totals, by_op = {}, {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
                continue
            for line in plane.lines:
                if line.name != xplane.OPS_LINE:
                    continue
                intervals = [(float(e.start_ns),
                              float(e.start_ns) + float(e.duration_ns),
                              e.name.split(" = ", 1)[0].strip()
                              + "|" + scope_of(e.name, table))
                             for e in line.events]
                for key, ns in xplane.self_times(intervals).items():
                    op, scope = key.rsplit("|", 1)
                    totals[scope] = totals.get(scope, 0.0) + ns / 1e9
                    by_op[op] = by_op.get(op, 0.0) + ns / 1e9
        if not any(scope in totals for scope in SCOPES):
            totals = by_op = None
    _CACHE[path] = (totals, by_op)
    return _CACHE[path]


def scope_seconds(facts) -> Optional[Dict[str, float]]:
    """{scope: seconds of self time, summed over the device planes}."""
    return _reduce(facts)[0]


def kernel_seconds(facts, kernel: str) -> Optional[float]:
    """Device seconds of the custom calls named ``%<kernel>`` or
    ``%<kernel>.<n>`` (a Pallas kernel's instruction carries its
    function's name)."""
    by_op = _reduce(facts)[1]
    if not by_op:
        return None
    found = [s for op, s in by_op.items()
             if re.fullmatch(r"%%%s(\.\d+)?" % re.escape(kernel), op)]
    return sum(found) if found else None


def busy_pct(facts, scope: str) -> Optional[float]:
    """A scope's share of the device's operation time."""
    totals = scope_seconds(facts)
    if not totals:
        return None
    whole = sum(totals.values())
    return 100.0 * totals.get(scope, 0.0) / whole if whole else None


def traced_tokens(facts) -> Optional[float]:
    """Valid tokens of the requests that finished while the trace ran
    (a family whose requests are prompts says how long each is)."""
    lengths_of = getattr(facts.family, "prompt_lengths", None)
    if facts.trace is None or lengths_of is None:
        return None
    import os

    from benchmarks import stamps
    lengths = lengths_of(facts.config)
    done = stamps.in_window(facts.finish, facts.trace.host_span)
    return float(sum(lengths[os.path.basename(p)]
                     for p, d in zip(facts.schedule.paths, done) if d))


def roofline_pct(facts, mechanism: str,
                 kernel: Optional[str] = None) -> Optional[float]:
    """The larger of operations / bf16 peak and bytes / HBM bandwidth
    of one mechanism's blocks (or of one kernel's calls), over the
    device time of its scope (of the kernel's custom calls). Counted
    over the valid tokens of the requests that finished inside the
    traced window, with the run's share of assignments that fell to
    held experts and its mean rows a dispatch: padding, and whatever
    else the device did under the scope, can only lower the share."""
    tokens = traced_tokens(facts)
    work = getattr(facts.family, "mechanism_work", None)
    result = facts.result
    if tokens is None or work is None or facts.peak_flops_per_s is None \
            or not getattr(result, "experts_assignments", 0) \
            or not result.tokens_valid or not result.pad_emissions:
        return None
    if kernel is not None:
        spent = kernel_seconds(facts, kernel)
    else:
        spent = (scope_seconds(facts) or {}).get(mechanism)
    if not spent:
        return None
    from benchmarks import peaks
    held = tokens * result.experts_held / result.tokens_valid
    dispatches = tokens * result.pad_emissions / result.tokens_valid
    ops, nbytes = work(facts.config, mechanism, tokens, held, dispatches)
    least_s = max(ops / facts.peak_flops_per_s,
                  nbytes / peaks.peak_for(facts.device_kind)[
                      "hbm_bytes_per_s"])
    return 100.0 * least_s / spent
