"""Load generators: the request streams that drive a pipeline.

``poisson_client`` emits one video request per draw of an exponential
inter-arrival time (mean ``mean_interval_ms``) — the open-loop streaming
workload. ``bulk_client`` enqueues ``num_videos`` requests as fast as
possible — the max-throughput mode selected by ``-mi 0``. Both stamp a
fresh TimeCard (``enqueue_filename``) per request.

A full filename queue is handled per the config's overload policy:
``"abort"`` (default, reference parity) treats it as a fatal
configuration failure; ``"shed"`` drops the *new* request with a
counted ``shed`` outcome — disposed toward the run target through the
shared counter — and keeps streaming, so a load spike degrades
success-rate instead of killing the job.

Capability parity with the reference clients (client.py:11-106), as
threads in the controller process instead of a separate OS process.
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from typing import Optional

import numpy as np

from rnb_tpu import trace
from rnb_tpu.control import NUM_EXIT_MARKERS, FaultStats, \
    InferenceCounter, TerminationFlag, TerminationState, \
    dispose_requests, send_exit_markers
from rnb_tpu.telemetry import TimeCard
from rnb_tpu.utils.class_utils import load_class

SHED_SITE = "filename_queue"


def _client(video_path_iterator_path: str, filename_queue: "queue.Queue",
            termination: TerminationState, sta_bar: threading.Barrier,
            fin_bar: threading.Barrier, *, mean_interval_ms: int,
            num_videos: Optional[int], seed: Optional[int],
            num_markers: int = NUM_EXIT_MARKERS,
            overload_policy: str = "abort",
            fault_stats: Optional[FaultStats] = None,
            counter: Optional[InferenceCounter] = None,
            target_num_videos: Optional[int] = None,
            popularity: Optional[dict] = None,
            deadline_budget_s: Optional[float] = None) -> None:
    try:
        source = load_class(video_path_iterator_path)()
        if popularity is not None:
            # popularity-skewed replay (config root key "popularity"):
            # wrap the configured iterator with the seeded Zipf sampler
            # so the request stream models head-heavy real traffic —
            # the workload shape the decoded-clip cache (rnb_tpu.cache)
            # is benchmarked under. Seeded with the job seed: same
            # seed => identical request sequence.
            from rnb_tpu.video_path_provider import ZipfPathIterator
            # derive a CHILD seed for the popularity draws: seeding the
            # video stream and the Poisson interarrival rng below with
            # the identical value would hand both generators the same
            # PCG64 state, deterministically coupling video rank with
            # the following gap length — a correlation the Poisson+Zipf
            # workload must not carry
            zipf_seed = (None if seed is None
                         else np.random.SeedSequence([seed, 1]))
            source = ZipfPathIterator(source,
                                      s=popularity.get("s", 1.0),
                                      universe=popularity.get("universe"),
                                      seed=zipf_seed)
        iterator = iter(source)
        rng = np.random.default_rng(seed)
    except Exception:
        traceback.print_exc()
        termination.raise_flag(TerminationFlag.INTERNAL_ERROR)
        iterator = None

    try:
        sta_bar.wait()
    except threading.BrokenBarrierError:
        pass

    try:
        if iterator is not None:
            video_count = 0
            while not termination.terminated:
                if num_videos is not None and video_count >= num_videos:
                    break
                video_path = next(iterator)
                time_card = TimeCard(video_count)
                time_card.record("enqueue_filename")
                if deadline_budget_s is not None:
                    # absolute per-request deadline (rnb_tpu.health,
                    # root 'deadline' config key): every stage
                    # boundary downstream sheds the request once this
                    # wall-clock instant passes, instead of computing
                    # doomed work
                    time_card.deadline_s = \
                        time_card.timings["enqueue_filename"] \
                        + deadline_budget_s
                # flow anchor for the request's cross-stage trace
                # chain + an event-driven arrival-rate counter track
                # (rnb_tpu.trace)
                trace.instant("client.enqueue", rid=video_count)
                trace.counter("client.enqueued", video_count + 1)
                try:
                    filename_queue.put_nowait((None, video_path, time_card))
                except queue.Full:
                    if overload_policy == "shed":
                        # overload: drop the NEW request, count it, and
                        # keep the stream alive (it still consumes an
                        # id and counts toward the run target — the
                        # pipeline owes it no further work)
                        trace.instant("client.shed", rid=video_count)
                        time_card.mark_shed(SHED_SITE)
                        if fault_stats is not None:
                            fault_stats.record_shed(SHED_SITE)
                        if counter is not None \
                                and target_num_videos is not None:
                            dispose_requests(counter, target_num_videos,
                                             termination)
                    else:
                        # counted telemetry (log-meta 'Queue
                        # overflows:' / BenchmarkResult
                        # .queue_overflows) instead of a stray
                        # stdout warning; the termination flag
                        # still records the abort
                        if fault_stats is not None:
                            fault_stats.record_overflow(SHED_SITE)
                        termination.raise_flag(
                            TerminationFlag.FILENAME_QUEUE_FULL)
                        break
                video_count += 1
                if mean_interval_ms > 0:
                    time.sleep(rng.exponential(mean_interval_ms / 1000.0))
    except Exception:
        traceback.print_exc()
        termination.raise_flag(TerminationFlag.INTERNAL_ERROR)
    finally:
        send_exit_markers(filename_queue, num_markers, termination)
        try:
            fin_bar.wait()
        except threading.BrokenBarrierError:
            pass


def poisson_client(video_path_iterator_path, filename_queue,
                   mean_interval_ms, termination, sta_bar, fin_bar,
                   seed: Optional[int] = None,
                   num_markers: int = NUM_EXIT_MARKERS,
                   **fault_kwargs) -> None:
    """Open-loop Poisson stream until the job terminates
    (reference client.py:11-59)."""
    _client(video_path_iterator_path, filename_queue, termination, sta_bar,
            fin_bar, mean_interval_ms=mean_interval_ms, num_videos=None,
            seed=seed, num_markers=num_markers, **fault_kwargs)


def bulk_client(video_path_iterator_path, filename_queue, num_videos,
                termination, sta_bar, fin_bar,
                seed: Optional[int] = None,
                num_markers: int = NUM_EXIT_MARKERS,
                **fault_kwargs) -> None:
    """Enqueue num_videos requests immediately — max-throughput mode
    (reference client.py:61-106)."""
    _client(video_path_iterator_path, filename_queue, termination, sta_bar,
            fin_bar, mean_interval_ms=0, num_videos=num_videos, seed=seed,
            num_markers=num_markers, **fault_kwargs)
