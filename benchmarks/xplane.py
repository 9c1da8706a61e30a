"""The benchmark's reduction from the profiler's ``.xplane.pb``.

Read with ``jax.profiler.ProfileData`` alone. A device plane is one
whose name starts with ``/device:TPU:``; its line ``XLA Ops`` holds
one event per operation the chip ran, with a start and a duration in
nanoseconds on a clock that counts from the start of the capture
(PERF.md, PR 21). Reduced here:

* **busy**: the union of the operation intervals of a device — not the
  time a program was resident (the ``XLA Modules`` line), which counts
  the bubbles inside a program as work;
* **self time by operation**: an event's duration less the part its
  nested events cover (a ``while`` holds its body's operations);
* **idle gaps**: the spaces between the merged intervals, longest
  first, each labelled by the caller's host span that covers it and by
  the operation that ended it.

On a CPU dry run there is no device plane; the host plane's
``tf_XLAPjRtCpuClient`` lines stand in so that the control flow can be
rehearsed, and the result's device says ``cpu``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
CPU_LINE_PREFIX = "tf_XLAPjRtCpuClient"

Interval = Tuple[float, float, str]  # start_ns, end_ns, name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def device_ops(path: str) -> Dict[str, List[Interval]]:
    """{device plane name: its operation intervals, sorted by start}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name] = sorted(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns),
                         short_name(e.name))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith(CPU_LINE_PREFIX):
                    host.extend(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns), e.name)
                        for e in line.events if e.duration_ns > 0)
    if not out and host:
        out["/host:CPU"] = sorted(host)
    return out


def merge(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals as disjoint (start, end), ascending."""
    merged: List[Tuple[float, float]] = []
    for start, end, _ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_ns(intervals: Sequence[Interval]) -> float:
    return sum(end - start for start, end in merge(intervals))


def self_times(intervals: Sequence[Interval]) -> Dict[str, float]:
    """{operation name: ns} with nested events' time taken out of the
    event that holds them."""
    totals: Dict[str, float] = {}
    stack: List[list] = []  # [start, end, name, covered]

    def close(item):
        totals[item[2]] = totals.get(item[2], 0.0) \
            + (item[1] - item[0]) - item[3]

    for start, end, name in sorted(intervals,
                                   key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            # a child: its whole length is covered time of the parent
            stack[-1][3] += min(end, stack[-1][1]) - start
        stack.append([start, end, name, 0.0])
    while stack:
        close(stack.pop())
    return totals


_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\]).*? ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def short_name(text: str) -> str:
    """An event of the ``XLA Ops`` line is named by its whole HLO
    instruction; keep the instruction's name, what it is and the shape
    it makes: ``%fusion.67 fusion/kOutput bf16[48,32,56,56,64]``.
    The same instruction name recurs in each bucket's program with
    another shape, so the shape keeps them apart."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    kind = _KIND.search(text)
    return "%s %s%s %s" % (m.group(1), m.group(3),
                           "/" + kind.group(1) if kind else "", m.group(2))


def is_convolution(name: str) -> bool:
    """Whether a device operation is a convolution. On a TPU XLA puts
    each convolution (or matrix product) at the head of an *output
    fusion* with the elementwise work that consumes it — ``fusion``
    with ``kind=kOutput`` — or leaves it bare (``convolution``);
    reshapes, copies, loop and input fusions (elementwise, reductions)
    and custom calls (the Pallas kernels) are everything else."""
    return " convolution" in name or "/kOutput" in name \
        or "/kConv" in name


def idle_gaps(intervals: Sequence[Interval], window: Tuple[float, float],
              label=None, top: int = 10) -> List[Tuple[str, float]]:
    """The longest spaces inside ``window`` (ns on the plane's clock) in
    which no operation ran: [(label, seconds)], longest first.
    ``label(start_ns, end_ns)`` names what the host was doing; the
    operation that ended the gap is appended."""
    ordered = sorted(intervals)
    merged = merge(ordered)
    starts = [iv[0] for iv in ordered]
    gaps = []
    cursor = window[0]
    for start, end in merged + [(window[1], window[1])]:
        lo, hi = max(cursor, window[0]), min(start, window[1])
        if hi > lo:
            gaps.append((lo, hi))
        cursor = max(cursor, end)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for lo, hi in gaps[:top]:
        i = bisect.bisect_left(starts, hi)
        then = ordered[i][2] if i < len(ordered) else "end_of_window"
        what = label(lo, hi) if label is not None else "window"
        out.append(("%s|before:%s" % (what, then), (hi - lo) / 1e9))
    return out


class TraceFacts:
    """What one traced window says, per device and over the devices."""

    def __init__(self, path: str, window_s: float,
                 label=None):
        self.path = path
        self.window_s = float(window_s)
        self.by_device = device_ops(path)
        if not self.by_device:
            raise ValueError("%s holds no device operations" % path)
        self.busy_s = {name: busy_ns(ivs) / 1e9
                       for name, ivs in self.by_device.items()}
        self.self_s: Dict[str, float] = {}
        for ivs in self.by_device.values():
            for name, ns in self_times(ivs).items():
                self.self_s[name] = self.self_s.get(name, 0.0) + ns / 1e9
        # gaps of the idlest device, inside the span its events cover
        idlest = min(self.busy_s, key=self.busy_s.get)
        ivs = self.by_device[idlest]
        first, last = ivs[0][0], max(iv[1] for iv in ivs)
        self.gaps = idle_gaps(ivs, (first, last), label=label)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def min_busy_s(self) -> float:
        return min(self.busy_s.values())

    def op_seconds(self, predicate) -> float:
        return sum(s for name, s in self.self_s.items() if predicate(name))

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        ranked = sorted(self.self_s.items(), key=lambda kv: -kv[1])
        return [(name, s) for name, s in ranked[:top]]


def describe(path: str, limit: int = 40) -> str:
    """Planes, lines and the first events with their stats: what a
    builder reads before writing code against a new trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append("PLANE %s" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines.append("  LINE %s events=%d" % (line.name, len(events)))
            for e in events[:limit if plane.name.startswith(
                    DEVICE_PLANE_PREFIX) else 2]:
                try:
                    stats = dict(e.stats)
                except Exception:
                    stats = {}
                lines.append("    %s start=%d dur=%d %s"
                             % (e.name, e.start_ns, e.duration_ns,
                                {k: stats[k] for k in list(stats)[:6]}))
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
