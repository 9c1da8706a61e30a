"""Set-up's account: where the seconds from this process's start to
the window's start went, from the program's own record of set-up.

The program (``rnb_tpu.benchmark``) keeps ``setup.*`` spans from
``run_benchmark``'s first line to the release of its start barrier,
JAX's own time spans of tracing, lowering and compiling among them, on
``time.time()``: the clock of ``benchmarks/run.py``'s ``T_PROCESS`` and
of ``schedule.window``, so the account subtracts them without a fit.
``BenchmarkResult.setup`` hands the record out: ``entered``,
``run_start``, ``released`` and the events ``(name, t0, dur, thread,
counts)``. A program without the record (a parent of PR 51) reads None
everywhere.

**The critical path.** Stages are built in parallel threads; the
account follows the one instance whose ``setup.s{step}.construct``
ended last: it released the barrier. A span's self time is its
duration less its children's on the same thread. The seven metrics in
seconds and the mix's ramp add up to the run's ``notes.setup_s``:

====================  ==============================================
``setup_runtime_s``    ``T_PROCESS`` -> ``setup.entered``
``setup_inputs_s``     ``setup.entered`` -> ``setup.run`` opens
``setup_weights_s``    self time of the instance's ``.weights``
``setup_lower_s``      its thread's ``setup.jax.trace`` + ``.lower``
``setup_compile_s``    its thread's ``setup.jax.compile``
``setup_first_call_s`` self time of its ``.first_call``
``setup_unnamed_s``    ``setup_s`` less the six less the ramp
====================  ==============================================

and ``setup_compiled_programs`` counts the whole process's
``setup.jax.compile`` spans with ``cache_hit`` 0. The other instances'
spans stay in ``setup.json`` beside the run's tables, with the
critical instance's programs by row bucket. ``python -m
benchmarks.setup_account <job's log directory>`` prints the account
of a run from that file, or from the program's ``setup-trace.json``
(then without ``T_PROCESS`` and the window: the program's part alone).
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

#: the metrics in seconds, in the order of the account
SECONDS = ("setup_runtime_s", "setup_inputs_s", "setup_weights_s",
           "setup_lower_s", "setup_compile_s", "setup_first_call_s",
           "setup_unnamed_s")
COUNT = "setup_compiled_programs"
_CONSTRUCT = re.compile(r"^setup\.s\d+\.construct$")
_JAX = ("setup.jax.trace", "setup.jax.lower", "setup.jax.compile")
#: what the parts may miss of ``setup_s``
CLOSES_TO_S = 1e-3


def t_process() -> Optional[float]:
    """``T_PROCESS`` of the running ``benchmarks/run.py``."""
    for name in ("__main__", "benchmarks.run"):
        value = getattr(sys.modules.get(name), "T_PROCESS", None)
        if value is not None:
            return float(value)
    return None


def self_times(spans: List[Tuple[float, float]]) -> List[float]:
    """Each ``(t0, dur)``'s duration less its children's, for spans of
    one thread (they nest or follow each other), in the order given."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    own = [dur for _t0, dur in spans]
    open_: List[int] = []
    for i in order:
        t0, dur = spans[i]
        while open_ and spans[open_[-1]][0] + spans[open_[-1]][1] <= t0:
            open_.pop()
        if open_:
            own[open_[-1]] -= dur
        open_.append(i)
    return own


def _kind(name: str) -> str:
    """``weights`` of ``setup.s2.weights``, ``jax.compile`` of
    ``setup.jax.compile``."""
    return name.split(".", 2)[2] if name.startswith("setup.s") \
        else name.split(".", 1)[1]


def reduce(record: Optional[dict], process_start: Optional[float] = None,
           window_start: Optional[float] = None) -> Optional[dict]:
    """The account of one record, or None where there is none (or no
    constructor in it). Without ``process_start`` and ``window_start``
    the program's part alone: ``metrics`` then holds None for what
    needs them."""
    if not record or not record.get("events"):
        return None
    events = [tuple(e) for e in record["events"]]
    spans = [e for e in events if e[0] != "setup.entered"]
    built = [e for e in spans if _CONSTRUCT.match(e[0])]
    if not built:
        return None
    last = max(built, key=lambda e: e[1] + e[2])
    thread = last[3]
    mine = [e for e in spans if e[3] == thread]
    own = dict.fromkeys(("weights", "jax.trace", "jax.lower", "jax.compile",
                         "first_call"), 0.0)
    for event, seconds in zip(mine, self_times([e[1:3] for e in mine])):
        if _kind(event[0]) in own:
            own[_kind(event[0])] += seconds
    has_jax = any(e[0] in _JAX for e in spans)
    metrics: Dict[str, Optional[float]] = dict.fromkeys(SECONDS + (COUNT,))
    metrics["setup_weights_s"] = own["weights"]
    metrics["setup_first_call_s"] = own["first_call"]
    if has_jax:
        metrics["setup_lower_s"] = own["jax.trace"] + own["jax.lower"]
        metrics["setup_compile_s"] = own["jax.compile"]
        metrics[COUNT] = float(sum(
            1 for e in spans if e[0] == "setup.jax.compile"
            and not (e[4] or {}).get("cache_hit")))
    entered, run_start = record["entered"], record["run_start"]
    released = record["released"]
    account = {"critical": {"thread": thread, "construct": last[0],
                            "counts": last[4] or {},
                            "t0": last[1], "dur": last[2]},
               "entered": entered, "run_start": run_start,
               "released": released, "metrics": metrics,
               "programs": _programs(mine),
               "instances": [{"thread": e[3], "construct": e[0],
                              "t0": e[1], "dur": e[2]} for e in built],
               "events": len(events)}
    if process_start is None or window_start is None:
        return account
    setup_s = window_start - process_start
    ramp_s = window_start - released
    metrics["setup_runtime_s"] = entered - process_start
    metrics["setup_inputs_s"] = run_start - entered
    named = sum(metrics[name] or 0.0 for name in SECONDS[:-1])
    metrics["setup_unnamed_s"] = setup_s - named - ramp_s
    account.update(setup_s=setup_s, ramp_s=ramp_s,
                   process_start=process_start, window_start=window_start)
    total = sum(metrics[name] or 0.0 for name in SECONDS) + ramp_s
    ordered = process_start <= entered <= run_start <= released \
        <= window_start
    if not ordered or not abs(total - setup_s) <= CLOSES_TO_S:
        account["problem"] = (
            "the parts give %.6f s of %.6f (T_PROCESS %.3f, entered %.3f, "
            "run %.3f, released %.3f, window %.3f)"
            % (total, setup_s, process_start, entered, run_start,
               released, window_start))
        account["metrics"] = dict.fromkeys(SECONDS + (COUNT,))
    return account


def _programs(mine: list) -> List[dict]:
    """The critical instance's ``program`` spans, a row bucket each,
    with the seconds of what lies inside each by kind (self times) and
    how many of its compilations the cache answered."""
    own = self_times([e[1:3] for e in mine])
    rows = []
    for program in (e for e in mine if _kind(e[0]) == "program"):
        t0, t1 = program[1], program[1] + program[2]
        row = {"rows": (program[4] or {}).get("rows"), "dur": program[2],
               "trace": 0.0, "lower": 0.0, "compile": 0.0, "scopes": 0.0,
               "first_call": 0.0, "self": 0.0, "retrieval_s": 0.0,
               "compiled": 0, "cache_hits": 0}
        for event, seconds in zip(mine, own):
            if not (t0 <= event[1] and event[1] + event[2] <= t1):
                continue
            kind = "self" if event is program else _kind(event[0])
            kind = kind[4:] if kind.startswith("jax.") else kind
            if kind in row:
                row[kind] += seconds
            if event[0] == "setup.jax.compile":
                counts = event[4] or {}
                row["cache_hits" if counts.get("cache_hit")
                    else "compiled"] += 1
                row["retrieval_s"] += float(counts.get("retrieval_s", 0.0))
        rows.append(row)
    return rows


def of(facts) -> Optional[dict]:
    """The run's account, reduced once; None where the program kept no
    record. What it says goes to standard error and to ``setup.json``
    beside the run's stamp tables."""
    cached = getattr(facts, "_setup_account", False)
    if cached is False:
        record = getattr(facts.result, "setup", None)
        cached = facts._setup_account = reduce(
            record, t_process(), facts.schedule.window[0])
        if cached is not None:
            print("[bench] setup: " + describe(cached).replace("\n", "; "),
                  file=sys.stderr, flush=True)
            log_dir = getattr(facts.result, "log_dir", None)
            if log_dir and os.path.isdir(log_dir):
                with open(os.path.join(log_dir, "setup.json"), "w") as f:
                    json.dump(dict(cached, record=record), f, indent=1)
    return cached


def read(facts, name: str) -> Optional[float]:
    """One metric of the run's account, by its name in the manifest."""
    account = of(facts)
    return account["metrics"][name] if account is not None else None


def describe(account: dict) -> str:
    """The account as lines of text."""
    metrics = account["metrics"]
    lines = ["critical instance %s (%s), %d events"
             % (account["critical"]["thread"],
                account["critical"]["construct"], account["events"])]
    if "problem" in account:
        lines.append("DOES NOT CLOSE: " + account["problem"])
    if "setup_s" in account:
        lines.append("setup_s %.3f = %s + ramp %.3f"
                     % (account["setup_s"],
                        " + ".join("%s %s" % (name[6:-2], _s(metrics[name]))
                                   for name in SECONDS),
                        account["ramp_s"]))
    else:
        lines.append("run_start -> released %.3f: %s"
                     % (account["released"] - account["run_start"],
                        ", ".join("%s %s" % (name[6:-2], _s(metrics[name]))
                                  for name in SECONDS[2:-1])))
    lines.append("programs compiled (cache_hit 0), whole process: %s"
                 % _s(metrics[COUNT], "%d"))
    for row in account["programs"]:
        lines.append(
            "rows %s: %.3f s = trace %.3f + lower %.3f + compile %.3f "
            "(%d compiled, %d from the cache in %.3f) + scopes %.3f + "
            "first call %.3f + self %.3f"
            % (row["rows"], row["dur"], row["trace"], row["lower"],
               row["compile"], row["compiled"], row["cache_hits"],
               row["retrieval_s"], row["scopes"], row["first_call"],
               row["self"]))
    for instance in account["instances"]:
        lines.append("%s %s: %.3f s, ended %.3f s before the release"
                     % (instance["thread"], instance["construct"],
                        instance["dur"], account["released"]
                        - instance["t0"] - instance["dur"]))
    return "\n".join(lines)


def _s(value, form: str = "%.3f") -> str:
    return "None" if value is None else form % value


def record_of_trace(path: str) -> dict:
    """The record in the program's ``setup-trace.json`` (a Chrome trace
    of the events up to the barrier's release)."""
    with open(path) as f:
        doc = json.load(f)
    base = float(doc["otherData"]["t_base_epoch_s"])
    threads = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
               if e["ph"] == "M" and e["name"] == "thread_name"}
    events = [(e["name"], base + e["ts"] / 1e6, e.get("dur", 0.0) / 1e6,
               threads.get(e["tid"], str(e["tid"])), e.get("args") or {})
              for e in doc["traceEvents"]
              if e["ph"] in ("X", "i") and e["name"].startswith("setup.")]
    at = {e[0]: e for e in events}
    run = at["setup.run"]
    return {"entered": at["setup.entered"][1], "run_start": run[1],
            "released": run[1] + run[2], "events": events}


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print("usage: python -m benchmarks.setup_account <job's log "
              "directory>", file=sys.stderr)
        return 2
    reduced = os.path.join(argv[0], "setup.json")
    exported = os.path.join(argv[0], "setup-trace.json")
    account = None
    if os.path.exists(reduced):
        with open(reduced) as f:
            kept = json.load(f)
        account = reduce(kept["record"], kept.get("process_start"),
                         kept.get("window_start"))
    elif os.path.exists(exported):
        account = reduce(record_of_trace(exported))
    if account is None:
        print("no record of set-up in %s" % argv[0], file=sys.stderr)
        return 1
    print(describe(account))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
