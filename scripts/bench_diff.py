#!/usr/bin/env python
"""``make benchdiff``: CI-able perf-trajectory check over the matrix.

The per-config throughput matrix (``MULTICHIP_CONFIGS.json``, written
by ``scripts/multichip_demo.py`` / ``scripts/run_shipped_configs.py`` runs)
has so far been eyeballed across ``BENCH_*.json`` snapshots — a
regression in one cell is invisible until someone reads the numbers.
This script makes the trajectory a checked artifact: it diffs the
current matrix row-by-row against a COMMITTED baseline
(``MULTICHIP_BASELINE.json``) with a per-cell relative tolerance and
exits non-zero on any regression, so the perf floor rides CI like the
correctness gates.

Rules (per config row, joined on the ``config`` key):

* a row that was ``ok`` in the baseline but failed now (``ok`` false
  or a nonzero ``termination_flag``) is a REGRESSION;
* ``videos_per_sec`` more than ``--tolerance`` (default 30% — the
  1-core CPU harness is noisy; tighten on hardware) below the
  baseline cell is a REGRESSION;
* a baseline row missing from the current matrix is a REGRESSION
  (coverage loss is a failure, not a skip);
* new rows and improvements are reported, never failed.

``--update`` rewrites the baseline from the current matrix (the
reviewed way to ratify a new floor). Exit: 0 clean, 1 regression(s),
2 unreadable inputs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_CURRENT = os.path.join(REPO, "MULTICHIP_CONFIGS.json")
DEFAULT_BASELINE = os.path.join(REPO, "MULTICHIP_BASELINE.json")
DEFAULT_TOLERANCE = 0.30


def load_rows(path: str):
    """-> {config: row} from one matrix artifact."""
    with open(path) as f:
        doc = json.load(f)
    rows = {}
    for row in doc.get("configs", []):
        key = row.get("config")
        if key:
            rows[str(key)] = dict(row)
    return rows


def row_ok(row: dict) -> bool:
    return bool(row.get("ok")) and int(row.get(
        "termination_flag", 0) or 0) == 0


def diff(baseline: dict, current: dict, tolerance: float):
    """-> (report lines, regression count). Pure so tests drive it."""
    lines = []
    regressions = 0
    for key in sorted(set(baseline) | set(current)):
        base = baseline.get(key)
        cur = current.get(key)
        if base is None:
            lines.append("  NEW        %-44s %.3f v/s"
                         % (key, float(cur.get("videos_per_sec") or 0)))
            continue
        if cur is None:
            regressions += 1
            lines.append("  MISSING    %-44s baseline %.3f v/s — row "
                         "vanished from the matrix"
                         % (key, float(base.get("videos_per_sec")
                                       or 0)))
            continue
        base_vps = float(base.get("videos_per_sec") or 0.0)
        cur_vps = float(cur.get("videos_per_sec") or 0.0)
        if row_ok(base) and not row_ok(cur):
            regressions += 1
            lines.append("  REGRESSION %-44s was ok, now failed "
                         "(ok=%s flag=%s)"
                         % (key, cur.get("ok"),
                            cur.get("termination_flag")))
            continue
        floor = base_vps * (1.0 - tolerance)
        if row_ok(base) and cur_vps < floor:
            regressions += 1
            lines.append("  REGRESSION %-44s %.3f v/s < floor %.3f "
                         "(baseline %.3f, tolerance %d%%)"
                         % (key, cur_vps, floor, base_vps,
                            round(tolerance * 100)))
        elif base_vps > 0:
            lines.append("  ok         %-44s %.3f v/s vs baseline "
                         "%.3f (%+.0f%%)"
                         % (key, cur_vps, base_vps,
                            100.0 * (cur_vps - base_vps) / base_vps))
        else:
            lines.append("  ok         %-44s %.3f v/s" % (key,
                                                          cur_vps))
    return lines, regressions


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Diff the throughput matrix against the committed "
                    "baseline; non-zero exit on regression")
    parser.add_argument("--current", default=DEFAULT_CURRENT,
                        help="matrix artifact to check (default: "
                             "MULTICHIP_CONFIGS.json)")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="committed floor (default: "
                             "MULTICHIP_BASELINE.json)")
    parser.add_argument("--tolerance", type=float,
                        default=DEFAULT_TOLERANCE,
                        help="per-cell relative throughput tolerance "
                             "(default %.2f)" % DEFAULT_TOLERANCE)
    parser.add_argument("--update", action="store_true",
                        help="ratify the current matrix as the new "
                             "baseline instead of checking")
    args = parser.parse_args(argv)

    try:
        current = load_rows(args.current)
    except (OSError, ValueError) as e:
        print("bench_diff: cannot read current matrix %s: %s"
              % (args.current, e))
        return 2
    if args.update:
        with open(args.current) as f:
            doc = json.load(f)
        doc["_baseline_note"] = (
            "committed perf floor for scripts/bench_diff.py "
            "(make benchdiff); regenerate with --update after a "
            "reviewed perf change")
        with open(args.baseline, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=False)
            f.write("\n")
        print("bench_diff: baseline %s updated from %s (%d row(s))"
              % (args.baseline, args.current, len(current)))
        return 0
    try:
        baseline = load_rows(args.baseline)
    except (OSError, ValueError) as e:
        print("bench_diff: cannot read baseline %s: %s "
              "(run --update once to ratify a floor)"
              % (args.baseline, e))
        return 2
    lines, regressions = diff(baseline, current, args.tolerance)
    print("bench_diff: %s vs %s (tolerance %d%%)"
          % (os.path.relpath(args.current, REPO),
             os.path.relpath(args.baseline, REPO),
             round(args.tolerance * 100)))
    for line in lines:
        print(line)
    print("bench_diff: %d regression(s) over %d baseline row(s) — %s"
          % (regressions, len(baseline),
             "FAIL" if regressions else "OK"))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
