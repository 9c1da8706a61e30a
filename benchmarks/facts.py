"""What one run established: the readers' one argument.

A :class:`RunFacts` holds the schedule with its stamps, each request's
finish, the program's counters (``BenchmarkResult``), the process CPU
seconds over the window, the device's peak memory, the configuration
as run with its family's module (``config``, ``family``: a reader that
wants a kernel's operations or bytes asks the family for them) and, in
a traced run, the trace's reduction. End-to-end metrics and the shared
arithmetic of the per-layer readers are methods here, so that a reader
file is a description and a line or two.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import stamps


class RunFacts:
    def __init__(self, *, schedule, finish, instance, result, chips: int,
                 device_kind: str, platform: str, config: dict, family,
                 flops_per_row: int, peak_flops_per_s: Optional[float],
                 window_cpu_s: float, memory_peak_bytes: int,
                 wire_bytes_per_row: int, trace=None):
        self.schedule = schedule
        self.finish = finish
        self.instance = instance
        self.result = result
        self.chips = int(chips)
        self.device_kind = device_kind
        self.platform = platform
        self.config = config
        self.family = family
        self.flops_per_row = int(flops_per_row)
        self.peak_flops_per_s = peak_flops_per_s
        self.window_cpu_s = float(window_cpu_s)
        self.memory_peak_bytes = int(memory_peak_bytes)
        self.wire_bytes_per_row = int(wire_bytes_per_row)
        self.trace = trace
        self.window = schedule.window
        self.seconds = schedule.seconds
        self.finished_in_window = stamps.in_window(finish, self.window)
        due_epoch = schedule.t0 + schedule.due
        self.due_in_window = stamps.in_window(due_epoch, self.window)
        self.due_epoch = due_epoch

    # -- end to end ----------------------------------------------------

    def videos_per_s(self) -> float:
        return float(self.finished_in_window.sum()) / self.seconds

    def latencies_ms(self) -> np.ndarray:
        """From due to last-stage finish, requests due inside the
        window that finished."""
        mask = self.due_in_window & ~np.isnan(self.finish)
        return (self.finish[mask] - self.due_epoch[mask]) * 1e3

    def latency_ms(self, p: float) -> Optional[float]:
        values = self.latencies_ms()
        return stamps.percentile(values, p) if len(values) else None

    # -- attempted and failed -------------------------------------------

    def attempted(self) -> int:
        """Open loop: the requests due inside the window. Backlog: those
        finished inside it plus those the program failed or shed (what
        still waits when the window closes was not yet attempted)."""
        if self.schedule.process == "backlog":
            return int(self.finished_in_window.sum()) + self.failed()
        return int(self.due_in_window.sum())

    def failed(self) -> int:
        """Failed, shed, or unfinished when the run ended."""
        if self.schedule.process == "backlog":
            return int(self.result.num_failed + self.result.num_shed)
        return int((self.due_in_window & np.isnan(self.finish)).sum())

    # -- shared arithmetic of the per-layer readers ----------------------

    def clips_per_s(self) -> float:
        return float(self.schedule.clips[self.finished_in_window].sum()) \
            / self.seconds

    def gen_late_ms(self) -> np.ndarray:
        mask = self.due_in_window & ~np.isnan(self.schedule.sent)
        return (self.schedule.sent[mask] - self.due_epoch[mask]) * 1e3

    def host_cores_busy(self) -> float:
        return self.window_cpu_s / self.seconds

    def rows_per_dispatch(self) -> Optional[float]:
        r = self.result
        return r.total_rows / r.pad_emissions if r.pad_emissions else None

    def pad_row_pct(self) -> Optional[float]:
        r = self.result
        return 100.0 * r.pad_rows / r.total_rows if r.total_rows else None

    def net_flops_util_pct(self) -> Optional[float]:
        if self.peak_flops_per_s is None:
            return None  # a CPU dry run has no peak to stand against
        return 100.0 * self.clips_per_s() * self.flops_per_row \
            / (self.chips * self.peak_flops_per_s)

    def per_instance_in_window(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for done, name in zip(self.finished_in_window, self.instance):
            if done:
                counts[name] = counts.get(name, 0) + 1
        return counts

    def replica_imbalance_pct(self) -> Optional[float]:
        counts = list(self.per_instance_in_window().values())
        if len(counts) < 2:
            return None
        mean = sum(counts) / len(counts)
        return 100.0 * (max(counts) - min(counts)) / mean

    def rehomed_byte_pct(self) -> Optional[float]:
        r = self.result
        put = r.total_rows * self.wire_bytes_per_row
        if not getattr(r, "handoff_edges", 0) or not put:
            return None
        return 100.0 * r.handoff_d2d_bytes / put

    def hbm_peak_gib(self) -> Optional[float]:
        if not self.memory_peak_bytes:
            return None
        return self.memory_peak_bytes / 2 ** 30

    # -- from the trace --------------------------------------------------

    def device_idle_pct(self) -> Optional[float]:
        """1 - union of operation intervals / traced window, on the
        idlest chip."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.min_busy_s / self.trace.window_s)

    def nonconv_busy_pct(self) -> Optional[float]:
        if self.trace is None:
            return None
        from benchmarks.xplane import is_convolution
        total = sum(self.trace.self_s.values())
        other = self.trace.op_seconds(lambda n: not is_convolution(n))
        return 100.0 * other / total if total else None

    def net_roofline_pct(self) -> Optional[float]:
        """Analytic FLOPs of the rows dispatched while the trace ran,
        over the summed device time of the operations times the peak.
        Rows dispatched are counted from finish stamps inside the
        traced window, pad rows left out: the share can only be
        under-stated by padding, never pushed past 100%."""
        if self.trace is None or self.peak_flops_per_s is None:
            return None
        span = self.trace.host_span
        done = stamps.in_window(self.finish, span)
        flops = float(self.schedule.clips[done].sum()) * self.flops_per_row
        op_s = sum(self.trace.self_s.values())  # over every chip used
        return 100.0 * flops / (op_s * self.peak_flops_per_s) \
            if op_s else None
