"""Device-busy analysis of an ``--xprof`` capture.

Cross-checks the analytic MFU published by bench.py against what the
device trace says: reads a job's ``xprof-ops.txt`` (one ``t0_ns t1_ns
op_name`` line per device-op interval, written by
``rnb_tpu.benchmark --xprof``), merges overlapping intervals, and
reports the busy fraction of the measured window plus the top ops by
accumulated time.

Usage::

    python -m rnb_tpu.benchmark -c configs/r2p1d-whole.json -mi 0 \
        -v 2000 --xprof
    python scripts/device_busy.py logs/<job_id>/xprof-ops.txt

An analytic MFU of X% with a device-busy fraction well above X% means
the gap is kernel inefficiency (small batches, layout); busy fraction
near X% means the chip is compute-bound and X% is the honest ceiling
for this topology.

Clocks, as read from a trace taken on a locally attached TPU v5e
(PR 21): a device plane's timestamps are host nanoseconds counted from
the start of the capture, in chronological order, and cover exactly
the span between start_trace and stop_trace. So the window markers
delimit the measured window directly (preferred), and where a trace
has none — the devobs capture windows — the host-epoch header maps
onto the device timeline by anchoring the flush instant to the last
device timestamp.
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import defaultdict

#: matches rnb_tpu.profiler.DEVICE_PLANE_MARKER (kept local: this script
#: must run without importing jax)
DEVICE_PLANE_MARKER = "/device:"


def is_device_op(name: str) -> bool:
    """Heuristic: keep XLA/TPU op intervals, drop host-side trace rows
    (python frames like ``$threading.py:323 wait``, thread bootstrap
    spans) that the xplane capture interleaves on CPU backends —
    counting those as 'busy' would claim 100% trivially."""
    return not (name.startswith("$") or ".py" in name
                or name.startswith("Thread "))


def _sniff_four_col(line: str) -> bool:
    """Does a header-less data row look like the 4-column format?

    4+ whitespace-separated fields, two leading integers, and a plane
    token (``/device:`` or ``/host:``) third — without this sniff a
    4-column file whose header was stripped would silently fold the
    plane token into the op name under ``"(all)"``.
    """
    parts = line.split()
    if len(parts) < 4:
        return False
    try:
        int(parts[0]), int(parts[1])
    except ValueError:
        return False
    return DEVICE_PLANE_MARKER in parts[2] or "/host:" in parts[2]


def load_intervals(path: str, device_only: bool = True):
    """-> {plane: [(t0_ns, t1_ns, name)]} from an xprof-ops.txt file.

    Two formats: the current 4-column ``t0 t1 plane name`` (marked by
    a ``# t0_ns t1_ns plane op_name`` header, or sniffed from the first
    data row when the header is missing) and the legacy 3-column
    ``t0 t1 name``, which lands under the single plane ``"(all)"``.
    Per-plane grouping matters: XLine clock bases differ across planes,
    so a busy-time union across planes conflates clocks (observed as a
    54 s "span" for a 6 s capture before the format carried the plane).
    """
    out = {}
    four_col = None  # decided by the header, else sniffed from data
    with open(path) as f:
        for line in f:
            if line.startswith("#"):
                if four_col is None and "plane" in line.split():
                    four_col = True  # the '# t0_ns t1_ns plane op_name' header
                continue
            if four_col is None:
                four_col = _sniff_four_col(line)
            if four_col:
                parts = line.rstrip("\n").split(" ", 3)
                if len(parts) != 4:
                    continue
                t0, t1, plane, name = parts
            else:
                parts = line.rstrip("\n").split(" ", 2)
                if len(parts) != 3:
                    continue
                t0, t1, name = parts
                plane = "(all)"
            if device_only and not is_device_op(name):
                continue
            out.setdefault(plane, []).append((int(t0), int(t1), name))
    return out


def load_window(path: str):
    """-> (window_t0_epoch, window_t1_epoch, flush_epoch) or None.

    Written by ``rnb_tpu.benchmark --xprof`` as a header comment. The
    trace's device clock counts from the start of the capture, not
    from the host epoch — so the measured window travels as host
    epochs plus the flush time, and :func:`clip_to_window` maps it
    onto the device timeline by anchoring flush_epoch to the last
    device timestamp.
    """
    with open(path) as f:
        for line in f:
            if not line.startswith("#"):
                return None
            parts = line.split()
            if "window_epoch" in parts and "flush_epoch" in parts:
                i = parts.index("window_epoch")
                j = parts.index("flush_epoch")
                return (float(parts[i + 1]), float(parts[i + 2]),
                        float(parts[j + 1]))
    return None


MARKER = "rnb_window_marker"


def marker_events(intervals):
    """Sorted [(t0, t1)] of the window-marker ops in one plane."""
    return sorted((t0, t1) for t0, t1, n in intervals if MARKER in n)


def marker_window(intervals):
    """-> (w0_ns, w1_ns) from the window-marker ops, or None when
    there are fewer than two.

    ``rnb_tpu.benchmark --xprof`` dispatches a jitted no-op named
    ``rnb_window_marker`` right before releasing the start barrier and
    right after the finish barrier. Those events sit on the device's
    own timeline, so the window needs no host-epoch mapping. Window =
    end of the first marker to start of the last.
    """
    marks = marker_events(intervals)
    if len(marks) < 2:
        return None
    return marks[0][1], marks[-1][0]


def clip_to_window(intervals, window, anchor_t1_ns: int):
    """Clip one plane's intervals to the measured window.

    ``anchor_t1_ns`` (the plane's max t1) is assumed to coincide with
    ``flush_epoch``; under bulk load the device is busy until moments
    before the controller stops the clock, so the alignment error is
    the drain+flush time (tens of ms), small against multi-second
    windows. Returns (clipped_intervals, (w0_ns, w1_ns)).
    """
    t0_epoch, t1_epoch, flush_epoch = window
    w0 = anchor_t1_ns - int((flush_epoch - t0_epoch) * 1e9)
    w1 = anchor_t1_ns - int((flush_epoch - t1_epoch) * 1e9)
    out = []
    for t0, t1, name in intervals:
        if t1 <= w0 or t0 >= w1:
            continue
        out.append((max(t0, w0), min(t1, w1), name))
    return out, (w0, w1)


def merged_busy_ns(intervals) -> int:
    """Union length of [t0, t1) intervals (overlaps counted once)."""
    busy = 0
    end = None
    start = None
    for t0, t1, _name in sorted(intervals):
        if start is None:
            start, end = t0, t1
        elif t0 <= end:
            end = max(end, t1)
        else:
            busy += end - start
            start, end = t0, t1
    if start is not None:
        busy += end - start
    return busy


def summarize(intervals, top: int = 15, span_bounds=None):
    """``span_bounds`` (t_min, t_max) should come from the UNFILTERED
    trace: device idle at the window's edges must stay in the
    denominator, or the busy fraction overstates utilization."""
    if not intervals:
        return {"ops": 0}
    if span_bounds is not None:
        t_min, t_max = span_bounds
    else:
        t_min = min(t0 for t0, _t1, _n in intervals)
        t_max = max(t1 for _t0, t1, _n in intervals)
    span = t_max - t_min
    busy = merged_busy_ns(intervals)
    per_op = defaultdict(int)
    for t0, t1, name in intervals:
        per_op[name] += t1 - t0
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "ops": len(intervals),
        "span_ms": span / 1e6,
        "busy_ms": busy / 1e6,
        "busy_fraction": busy / span if span else 0.0,
        "top_ops": ranked,
    }


#: log-meta lines of the devobs ledger this script surfaces when
#: pointed at a job directory (rnb_tpu.devobs / rnb_tpu.memledger)
LEDGER_PREFIXES = ("Compute:", "Compute stages:", "Memory:",
                   "Memory owners:")


def ledger_lines(job_dir: str):
    """The job's Compute:/Memory: ledger lines (devobs-enabled runs),
    read straight from log-meta.txt — the device-accounting context
    every busy-fraction report below should be read against."""
    path = os.path.join(job_dir, "log-meta.txt")
    out = []
    if os.path.isfile(path):
        with open(path) as f:
            for line in f:
                if line.startswith(LEDGER_PREFIXES):
                    out.append(line.rstrip("\n"))
    return out


def job_trace_files(job_dir: str):
    """Every device-op interval artifact a job dir may hold: the
    ``--xprof`` capture plus the devobs plane's bounded capture
    windows (same 4-column format)."""
    names = sorted(os.listdir(job_dir))
    out = [os.path.join(job_dir, n) for n in names
           if n == "xprof-ops.txt"
           or (n.startswith("devobs-capture-") and n.endswith(".txt"))]
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace",
                        help="path to xprof-ops.txt / a devobs "
                             "capture, or a logs/<job> directory "
                             "(reads the devobs ledger lines plus "
                             "every capture artifact)")
    parser.add_argument("--top", type=int, default=15)
    parser.add_argument("--include-host", action="store_true",
                        help="keep host-side python/thread trace rows")
    args = parser.parse_args(argv)

    if os.path.isdir(args.trace):
        # job-dir mode: the devobs ledger is the accounting of record
        # — print it first, then analyze every capture artifact
        lines = ledger_lines(args.trace)
        for line in lines:
            print(line)
        files = job_trace_files(args.trace)
        if not files:
            print("no capture artifacts under %s" % args.trace)
            return 0 if lines else 1
        status = 0
        for path in files:
            print("== %s" % os.path.basename(path))
            status = max(status, analyze(path, args.top,
                                         args.include_host))
        return status
    return analyze(args.trace, args.top, args.include_host)


def analyze(trace_path: str, top: int = 15,
            include_host: bool = False) -> int:
    everything = load_intervals(trace_path, device_only=False)
    if not everything:
        # a bounded devobs capture can legitimately hold zero ops
        # (idle window); an empty file with the header is not an error
        if os.path.basename(trace_path).startswith("devobs-capture-"):
            print("no intervals in %s (idle capture window)"
                  % trace_path)
            return 0
        print("no intervals in %s" % trace_path)
        return 1
    args = argparse.Namespace(trace=trace_path, top=top,
                              include_host=include_host)
    # plane-aware device selection: when the trace names /device:
    # planes, those ARE the device ops — the name heuristic only has
    # to carry legacy 3-column traces (one anonymous "(all)" plane)
    device_planes = {p for p in everything if DEVICE_PLANE_MARKER in p}
    kept = {}
    for plane, ivals in everything.items():
        if not args.include_host:
            if device_planes:
                if plane not in device_planes:
                    continue
            else:
                ivals = [iv for iv in ivals if is_device_op(iv[2])]
        if ivals:
            kept[plane] = ivals
    if not kept:
        if os.path.basename(trace_path).startswith("devobs-capture-"):
            # a bounded trigger capture can land on an idle/host-only
            # window — nothing to aggregate is a report, not an error
            print("no device-op intervals in %s (host-only capture)"
                  % trace_path)
            return 0
        print("no device-op intervals in %s" % args.trace)
        return 1
    # one block per plane, busiest first; spans NEVER cross planes
    # (clock bases differ), so each block is internally consistent
    blocks = []
    for plane, intervals in kept.items():
        allp = everything[plane]
        bounds = (min(t0 for t0, _t1, _n in allp),
                  max(t1 for _t0, t1, _n in allp))
        blocks.append((plane, summarize(intervals, args.top,
                                        span_bounds=bounds)))
    blocks.sort(key=lambda b: -b[1]["busy_ms"])
    window = load_window(args.trace)
    for plane, stats in blocks:
        print("plane               : %s" % plane)
        print("device-op intervals : %d" % stats["ops"])
        print("trace span          : %.3f ms" % stats["span_ms"])
        print("device busy (union) : %.3f ms  (%.1f%% of span)"
              % (stats["busy_ms"], 100.0 * stats["busy_fraction"]))
        # the honest MFU cross-check: busy fraction of the MEASURED
        # window only. Preferred: the in-trace window markers (device
        # timeline, no mapping); fallback: the host-epoch header
        # anchored at the capture stop.
        mwin = marker_window(everything[plane])
        if mwin is not None:
            rows = [iv for iv in kept[plane] if MARKER not in iv[2]]
            clipped = [(max(t0, mwin[0]), min(t1, mwin[1]), n)
                       for t0, t1, n in rows
                       if t1 > mwin[0] and t0 < mwin[1]]
            wstats = summarize(clipped, 0, span_bounds=mwin)
            if wstats["ops"]:
                print("measured window     : busy %.3f ms of the "
                      "marker-delimited window (%.1f%%)"
                      % (wstats["busy_ms"],
                         100.0 * wstats["busy_fraction"]))
            else:
                print("measured window     : no device ops between "
                      "the markers")
        elif window is not None:
            anchor = max(t1 for _t0, t1, _n in everything[plane])
            clipped, (w0, w1) = clip_to_window(kept[plane], window,
                                               anchor)
            wstats = summarize(clipped, 0, span_bounds=(w0, w1))
            if wstats["ops"]:
                print("measured window     : %.3f ms  busy %.3f ms "
                      "(%.1f%% of window)"
                      % (wstats["span_ms"], wstats["busy_ms"],
                         100.0 * wstats["busy_fraction"]))
            else:
                print("measured window     : no device ops in window")
        print("top ops by accumulated device time:")
        for name, ns in stats["top_ops"]:
            print("  %10.3f ms  %s" % (ns / 1e6, name[:90]))
        print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
