"""Compile/warmup accounting: count the shapes a stage's jit sees.

The pipeline's one structural promise about XLA is *bounded
compilation*: every jitted stage applier is warmed on its full shape
vocabulary before the measured window opens, and no new signature —
i.e. no compile — may appear mid-run. rnb-lint's RNB-G006 enforces
that statically from config declarations; this module verifies it
**dynamically**, per stage instance, against what the hot loop
actually dispatched — which is also how the ragged path's headline
claim ("exactly one compiled shape per stage") is asserted at runtime
rather than taken on faith.

Counting is deliberately signature-based, not XLA-event-based: the
persistent compilation cache (rnb_tpu.benchmark) turns repeat-run
compiles into cache hits, so backend compile events undercount on
warm caches — while the number of *distinct (shape, dtype) entry
signatures* a jitted applier is fed equals the number of executables
the run requires, cache or no cache. One tracker per stage instance;
the executor freezes it when the measured window opens
(rnb_tpu.runner), so any signature first seen after the freeze is a
mid-run recompile and is surfaced as ``steady_new`` in the
``Compiles:`` accounting (parse_utils --check fails on nonzero).

Warm-up wall time rides the signatures' sink, but is not measured
here: it is the duration of the executor's ``setup.s{step}.construct``
span around each stage's construction (weights + warmup compiles
happen in ``__init__``; rnb_tpu.runner, rnb_tpu.trace), summed a step
into the ``Warmup:`` log-meta line — under ragged, collapsing the
per-bucket warmup matrix to one compile is a measurable launch-latency
win, and the set-up spans under that one say where it went.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Tuple

from rnb_tpu import trace


def signature_of(*arrays) -> tuple:
    """The jit-entry signature of a positional array argument list:
    per-argument (shape, dtype-name). Scalars and non-array leaves
    hash by type (a traced scalar never forks an executable)."""
    sig = []
    for a in arrays:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None:
            sig.append((type(a).__name__,))
        else:
            sig.append((tuple(int(d) for d in shape), str(dtype)))
    return tuple(sig)


def _signature_text(sig: tuple) -> str:
    """``8x32x18816:uint8 scalar:float32``-style text of a signature:
    no comma, ``#`` or ``=``, which delimit a profiler annotation's
    stats."""
    return " ".join(("x".join(map(str, part[0])) or "scalar") + ":" + part[1]
                    if len(part) == 2 else part[0] for part in sig)


class SignatureTracker:
    """Distinct jit-entry signatures of one stage applier, split at
    the measured-window freeze. Locked: under ``transfer_async`` the
    fusing loader's preprocess dispatch (and so its observe) runs on
    the transfer-worker thread while cache hits dispatch on the
    executor thread — the lock costs nanoseconds per *emission* and
    keeps the counters exact."""

    __slots__ = ("_warmup", "_steady_new", "_steady_calls", "_frozen",
                 "_lock")

    GUARDED_BY = {
        "_warmup": "_lock",
        "_steady_new": "_lock",
        "_steady_calls": "_lock",
        "_frozen": "_lock",
    }

    def __init__(self):
        self._warmup: set = set()
        self._steady_new: set = set()
        self._steady_calls = 0
        self._frozen = False
        self._lock = threading.Lock()

    def observe(self, *arrays) -> None:
        """Note one dispatch's entry signature."""
        sig = signature_of(*arrays)
        with self._lock:
            if not self._frozen:
                self._warmup.add(sig)
                return
            self._steady_calls += 1
            if sig in self._warmup or sig in self._steady_new:
                return
            # a signature warmup never saw: this dispatch is (or
            # would be, modulo the persistent cache) a mid-run
            # compile
            self._steady_new.add(sig)
        # a traced run shows which step recompiled and when: the
        # instant lies inside that step's model_call span
        trace.instant("compile.steady", signature=_signature_text(sig))

    def freeze(self) -> None:
        """The measured window opened: signatures from here on must
        already be warmed."""
        with self._lock:
            self._frozen = True

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "warmup": len(self._warmup),
                "steady_new": len(self._steady_new),
                "steady_calls": self._steady_calls,
            }


def aggregate_compile_records(records: List[Tuple[int, float, dict]]
                              ) -> Tuple[Dict[str, dict],
                                         Dict[str, float]]:
    """Per-instance ``(step_idx, warmup_s, sigs-or-None)`` records ->
    (``{step: {warmup, steady_new, steady_calls}}`` summed over the
    step's instances for tracker-owning stages,
    ``{step: warmup_seconds}`` summed over every instance)."""
    compiles: Dict[str, dict] = {}
    warmup: Dict[str, float] = {}
    for step_idx, warmup_s, sigs in records:
        key = "step%d" % int(step_idx)
        warmup[key] = round(warmup.get(key, 0.0) + float(warmup_s), 3)
        if sigs is None:
            continue
        agg = compiles.setdefault(
            key, {"warmup": 0, "steady_new": 0, "steady_calls": 0})
        for field in agg:
            agg[field] += int(sigs.get(field, 0))
    return compiles, warmup
