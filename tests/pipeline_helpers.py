"""Tiny stage models + path iterators used by runtime tests.

These play the role of the reference's CPU fallback (`gpus: [-1]`) as a
poor man's fake backend (SURVEY.md §4): minimal stages that exercise the
pipeline machinery without heavyweight models.
"""

from __future__ import annotations

import numpy as np

from rnb_tpu.stage import PaddedBatch, StageModel
from rnb_tpu.video_path_provider import VideoPathIterator

SHAPE = (4, 2)  # (max_rows, feature)


class TinyLoader(StageModel):
    """First stage: turns a request id string into a small batch."""

    def __init__(self, device, rows_per_video=2, **kwargs):
        super().__init__(device)
        self.rows_per_video = int(rows_per_video)

    @staticmethod
    def output_shape():
        return (SHAPE,)

    def __call__(self, tensors, non_tensors, time_card):
        vid = int(str(non_tensors).rsplit("-", 1)[-1])
        rows = np.full((self.rows_per_video, SHAPE[1]), float(vid),
                       dtype=np.float32)
        return (PaddedBatch.from_rows(rows, SHAPE[0]),), vid, time_card


class TinyDouble(StageModel):
    """Middle stage: doubles the payload."""

    def input_shape(self):
        return (SHAPE,)

    @staticmethod
    def output_shape():
        return (SHAPE,)

    def __call__(self, tensors, non_tensors, time_card):
        pb = tensors[0]
        return (PaddedBatch(np.asarray(pb.data) * 2.0, pb.valid),), \
            non_tensors, time_card


class TinySink(StageModel):
    """Final stage: no tensor outputs (output_shape None => no rings)."""

    def __init__(self, device, **kwargs):
        super().__init__(device)
        self.seen = []

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        if tensors is not None:
            self.seen.append(np.asarray(tensors[0].data).copy())
        return None, non_tensors, time_card


class TinyRoutedLoader(TinyLoader):
    """Loader stamping num_clips: every 4th video 'large' (15 clips)."""

    def __call__(self, tensors, non_tensors, time_card):
        out = super().__call__(tensors, non_tensors, time_card)
        vid = int(str(non_tensors).rsplit("-", 1)[-1])
        time_card.num_clips = 15 if vid % 4 == 3 else 1
        return out


class TinySlowSink(StageModel):
    """Final stage that sleeps per item — forces upstream overflow."""

    def __init__(self, device, delay_s=0.2, **kwargs):
        super().__init__(device)
        self.delay_s = float(delay_s)

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        import time
        time.sleep(self.delay_s)
        return None, non_tensors, time_card


class BackpressureSink(StageModel):
    """Final stage that holds each dispatch until the loader upstream
    has closed ``ahead`` further batches — enough to fill the ring
    behind it and have one more assembled and waiting for a slot — or
    has emitted its last row. Back-pressure by condition, not by the
    clock: however slow the host decodes, a slot never frees before a
    whole batch waits for it. Reads the active tracer's ``loader.emit``
    spans (the run needs ``trace`` enabled)."""

    def __init__(self, device, ahead=3, total_rows=0, timeout_s=120.0,
                 **kwargs):
        super().__init__(device)
        self.ahead, self.total_rows = int(ahead), int(total_rows)
        self.timeout_s = float(timeout_s)
        self._taken = 0

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        import time

        from rnb_tpu import trace
        self._taken += 1
        deadline = time.monotonic() + self.timeout_s
        while time.monotonic() < deadline:
            rows = [e[6]["rows"] for e in trace.ACTIVE.snapshot_events()
                    if e[0] == "loader.emit"]
            if len(rows) >= self._taken + self.ahead \
                    or sum(rows) >= self.total_rows:
                break
            time.sleep(0.002)
        return None, non_tensors, time_card


class HoardingSink(StageModel):
    """Final stage that swallows EVERY item and releases them only at
    end-of-stream, one per flush() call — a deterministic stand-in for
    accumulator stages holding many pending batches at drain time."""

    def __init__(self, device, **kwargs):
        super().__init__(device)
        self._held = []

    @staticmethod
    def output_shape():
        return None

    def __call__(self, tensors, non_tensors, time_card):
        time_card.num_clips = 1  # completions show in clips_completed
        self._held.append((non_tensors, time_card))
        return None, None, None

    def flush(self):
        if not self._held:
            return None
        non_tensors, time_card = self._held.pop(0)
        return None, non_tensors, time_card


class CountingPathIterator(VideoPathIterator):
    """Yields synthetic request ids forever: video-0, video-1, ..."""

    def __iter__(self):
        i = 0
        while True:
            yield "video-%d" % i
            i += 1


#: the paths ProbingPathIterator hands out, and what it saw at each
PROBE_PATHS: list = []
PROBED: list = []


def listener_counts() -> tuple:
    """How many listeners of each kind ``jax.monitoring`` holds."""
    from jax._src import monitoring
    return (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_time_span_listeners()),
            len(monitoring.get_scalar_listeners()))


class ProbingPathIterator(VideoPathIterator):
    """Cycles ``PROBE_PATHS`` and keeps in ``PROBED`` what the client's
    thread saw of the tracing at each request. The client asks for its
    first path behind the start barrier, so every entry is the state
    the served window runs under."""

    def __iter__(self):
        import itertools

        from rnb_tpu import trace
        for path in itertools.cycle(list(PROBE_PATHS)):
            PROBED.append({"active": trace.ACTIVE,
                           "span": trace.span("client.enqueue"),
                           "listeners": listener_counts()})
            yield path
