"""The network edge: a crash-tolerant cross-host ingest transport.

Ingest (decode + staging) split from inference behind a real
transport (ROADMAP D8 holds the case against keeping it): the main
process keeps the client, the local step-0 fallback path and every
downstream inference stage, while a *peer* process (``python -m
rnb_tpu.netedge --serve``) runs a second copy of the step-0 stage and
serves requests over the length-prefixed, checksummed TCP frame
protocol in :mod:`rnb_tpu.ops.wire`.

The robustness contract — every signal the PR 10 health machinery
consumes exists on the wire:

* liveness beats are heartbeat frames (``BEAT`` every ``beat_ms``),
* the peer's in-flight depth rides the header of EVERY frame,
* ``deadline_s`` rides the REQ header so expiry shedding fires on
  both sides of the edge without decoding the payload,
* the sender reconnects with capped exponential backoff + jitter and
  keeps a bounded sequence-numbered resend window,
* both sides keep dedup ledgers so a resend after an ack-loss can
  never double-dispatch (the peer re-serves its cached response; the
  main side drops response frames for already-settled sequences),
* the receiver side is bound to a :class:`~rnb_tpu.health.LaneHealthBoard`
  (one lane, :data:`NET_LANE`), so a dead or wedged peer trips
  healthy -> suspect -> open, surviving requests drain to the local
  fallback path, and every request still terminates exactly once.

Exactly-once honesty policy: a window entry is removed ONLY on a
terminal event — its DATA injected downstream, its DISPOSE processed,
a receive-boundary deadline shed, a corrupt-frame dead-letter, or a
local reroute. Acks merely suppress resends. ``frames_sent`` counts
unique sequences, so ``frames_sent == frames_acked + resent_pending``
holds at teardown by construction, and ``--check`` cross-foots the
whole ledger (rnb_tpu/scripts/parse_utils.py).

Clocks: ``deadline_s`` stamps are wall-clock (``time.time()``), which
is comparable across processes on one host (the loopback cell) and
across NTP-disciplined hosts; the health board's staleness math stays
monotonic and purely local to the main process.
"""

from __future__ import annotations

import argparse
import os
import queue
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rnb_tpu import lockwitness
from rnb_tpu.control import dispose_requests
from rnb_tpu.faults import (NetCorruptFrameError, NetPartialFrameError,
                            NetRefusedError, NetResetError,
                            NetTimeoutError, PermanentError,
                            TransientError, fault_reason)
from rnb_tpu.health import (DirectPayload, deadline_site, expired)
from rnb_tpu.ops import wire

#: the edge's lane index on its dedicated LaneHealthBoard — there is
#: exactly one remote peer, so one lane (index 0 keeps lane_detail
#: keys disjoint from per-step replica boards only because netedge
#: excludes replicas entirely; see config.py guards)
NET_LANE = 0

#: reconnect backoff: exponential from ``backoff_ms``, capped here
BACKOFF_CAP_MS = 2000.0
#: uniform jitter fraction added on top of each capped base delay
JITTER_FRAC = 0.25

#: dispatcher wait-loop tick — every blocking wait in this module
#: polls at this period so the health board keeps evaluating (and the
#: circuit can open) even while the peer is wedged and nothing else
#: is making progress
_TICK_S = 0.05

#: peer: exit when connected once, then idle with no connection this long
_PEER_IDLE_S = 60.0
#: peer: dedup ledger size (seq -> cached response); far beyond any
#: legal resend_window so a resend always finds its cached response
_PEER_LEDGER_MAX = 4096


def parse_addr(addr: str) -> Tuple[str, int]:
    """``"host:port"`` -> ``(host, port)`` (IPv4/hostname only)."""
    host, sep, port = str(addr).rpartition(":")
    if not sep or not host:
        raise ValueError("netedge address %r is not host:port" % (addr,))
    return host, int(port)


def backoff_schedule_ms(backoff_ms: float, max_retries: int,
                        seed: int) -> List[float]:
    """The deterministic per-cycle reconnect delay schedule.

    Attempt ``i`` sleeps ``min(backoff_ms * 2**i, BACKOFF_CAP_MS)``
    plus uniform jitter up to ``JITTER_FRAC`` of that base — seeded,
    so a chaos run's dial storm is replayable byte-for-byte. The
    attempt counter resets after every successful connect; the same
    schedule is reused per cycle (re-drawing jitter per cycle would
    make reconnect timing depend on how many cycles ran before).
    """
    rng = np.random.default_rng(int(seed) if seed else 0)
    schedule = []
    for attempt in range(int(max_retries)):
        base = min(float(backoff_ms) * (2.0 ** attempt), BACKOFF_CAP_MS)
        schedule.append(base + float(rng.uniform(0.0, base * JITTER_FRAC)))
    return schedule


class NetEdgeSettings:
    """Validated, defaulted view of the root ``netedge`` config key."""

    __slots__ = ("listen", "connect", "beat_ms", "io_timeout_ms",
                 "max_retries", "backoff_ms", "resend_window", "spawn")

    def __init__(self, listen: Optional[str] = None,
                 connect: Optional[str] = None,
                 beat_ms: float = 200.0,
                 io_timeout_ms: float = 2000.0,
                 max_retries: int = 5,
                 backoff_ms: float = 50.0,
                 resend_window: int = 8,
                 spawn: bool = False):
        if not beat_ms > 0:
            raise ValueError("netedge beat_ms must be > 0")
        if not io_timeout_ms > beat_ms:
            raise ValueError(
                "netedge io_timeout_ms (%g) must be > beat_ms (%g): "
                "a receive timeout shorter than the heartbeat period "
                "would classify a healthy peer as silent"
                % (io_timeout_ms, beat_ms))
        if int(max_retries) < 1:
            raise ValueError("netedge max_retries must be >= 1")
        if backoff_ms < 0:
            raise ValueError("netedge backoff_ms must be >= 0")
        if int(resend_window) < 1:
            raise ValueError("netedge resend_window must be >= 1")
        if connect is None and not spawn:
            raise ValueError(
                "netedge needs 'connect' (host:port of a running "
                "peer) or 'spawn: true' (launch the peer locally)")
        if connect is not None:
            parse_addr(connect)
        if listen is not None:
            parse_addr(listen)
        self.listen = listen
        self.connect = connect
        self.beat_ms = float(beat_ms)
        self.io_timeout_ms = float(io_timeout_ms)
        self.max_retries = int(max_retries)
        self.backoff_ms = float(backoff_ms)
        self.resend_window = int(resend_window)
        self.spawn = bool(spawn)

    @staticmethod
    def from_config(raw: Optional[dict]) -> Optional["NetEdgeSettings"]:
        """Settings from the (schema-validated) config dict, or None
        when the key is absent or ``enabled`` is false — absent means
        no edge, no Net: lines, byte-stable logs (the PR 6/11/15
        inertness pattern)."""
        if raw is None or not raw.get("enabled", True):
            return None
        return NetEdgeSettings(
            listen=raw.get("listen"),
            connect=raw.get("connect"),
            beat_ms=raw.get("beat_ms", 200.0),
            io_timeout_ms=raw.get("io_timeout_ms", 2000.0),
            max_retries=raw.get("max_retries", 5),
            backoff_ms=raw.get("backoff_ms", 50.0),
            resend_window=raw.get("resend_window", 8),
            spawn=raw.get("spawn", False))


class NetStats:
    """Thread-safe edge counters — the ``Net:`` / ``Net errors:``
    log-meta lines and the BenchmarkResult ``net_*`` fields both read
    one :meth:`snapshot`."""

    COUNTERS = ("frames_sent", "frames_acked", "resent_pending",
                "resends", "beats", "reconnects", "remote", "local",
                "dedup_drops", "dup_arrivals", "wire_bytes",
                "frame_bytes", "window_stranded",
                "open_before_timeout", "err_total", "err_refused",
                "err_reset", "err_timeout", "err_partial_frame",
                "err_corrupt")

    _ERR_FIELD = {"net_refused": "err_refused",
                  "net_reset": "err_reset",
                  "net_timeout": "err_timeout",
                  "net_partial_frame": "err_partial_frame",
                  "net_corrupt": "err_corrupt"}

    #: declared concurrency contract (rnb-lint RNB-C001/C003)
    GUARDED_BY = {
        "_c": "_lock",
        "peer_depth": "_lock",
        "_t_first_open": "_lock",
        "_t_first_timeout": "_lock",
    }

    def __init__(self):
        self._lock = lockwitness.lock("NetStats._lock")
        self._c: Dict[str, int] = {k: 0 for k in self.COUNTERS}
        self.peer_depth = 0.0
        self._t_first_open: Optional[float] = None
        self._t_first_timeout: Optional[float] = None

    def inc(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._c[key] += n

    def err(self, reason: str, n: int = 1) -> None:
        """Count one classified net error (``fault_reason`` string)."""
        with self._lock:
            self._c["err_total"] += n
            self._c[self._ERR_FIELD[reason]] += n
            if reason == "net_timeout" and self._t_first_timeout is None:
                self._t_first_timeout = time.monotonic()

    def gauge_depth(self, depth: float) -> None:
        with self._lock:
            self.peer_depth = float(depth)

    def note_open(self) -> None:
        """The dispatcher observed the lane circuit OPEN (or worse)."""
        with self._lock:
            if self._t_first_open is None:
                self._t_first_open = time.monotonic()

    def finalize(self, stranded: int) -> None:
        """Teardown bookkeeping: the resend-window remainder and the
        did-the-circuit-beat-the-io-timeout verdict (the netchaos
        gate's headline assertion)."""
        with self._lock:
            self._c["window_stranded"] = int(stranded)
            self._c["resent_pending"] = (self._c["frames_sent"]
                                         - self._c["frames_acked"])
            self._c["open_before_timeout"] = int(
                self._t_first_open is not None
                and (self._t_first_timeout is None
                     or self._t_first_open < self._t_first_timeout))

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            snap: Dict[str, object] = dict(self._c)
            snap["peer_depth"] = self.peer_depth
            return snap


class _WindowEntry:
    """One in-flight remote request (window-lock protected)."""

    __slots__ = ("seq", "path", "card", "frame", "acked")

    def __init__(self, seq: int, path, card, frame: bytes):
        self.seq = seq
        self.path = path
        self.card = card
        self.frame = frame   # cached wire bytes, ready to resend
        self.acked = False


class NetEdgeClient:
    """Main-process side of the edge: a dispatcher thread
    (``netedge-tx``) routing filename-queue items remote-or-local, and
    a receiver thread (``netedge-rx``) turning response frames back
    into step-0 output-queue items. Neither joins the pipeline
    barriers — the edge is a transport, not a stage."""

    #: declared concurrency contract (rnb-lint RNB-C001/C003): three
    #: locks, three planes — socket handoff, resend window, receiver
    #: pad re-count
    GUARDED_BY = {
        "_sock": "_send_lock",
        "_window": "_wlock",
        "_seq_next": "_wlock",
        "_finalizing": "_wlock",
        "_pad": "_pad_lock",
    }
    UNGUARDED_OK = {
        "_dial_count": "tx thread is the sole dialer",
        "_ever_connected": "tx-thread confined (dial path only)",
        "_fired": "tx-thread confined (dial path only)",
        "_eos_sent": "tx-thread confined (EOS drain runs on tx)",
        "_evicted": "written only by the tx dial path; other "
                    "threads' bare bool reads are monotone "
                    "(evicted never un-evicts)",
    }

    def __init__(self, settings: NetEdgeSettings, *, board, stats,
                 fault_plan, fault_stats, deadline_stats, counter,
                 num_videos, termination, filename_queue, local_queue,
                 inject_queue, num_markers, seed: int = 0):
        self.settings = settings
        self.board = board
        self.stats = stats
        self.fault_plan = fault_plan
        self.fault_stats = fault_stats
        self.deadline_stats = deadline_stats
        self.counter = counter
        self.num_videos = num_videos
        self.termination = termination
        self.filename_queue = filename_queue
        self.local_queue = local_queue
        self.inject_queue = inject_queue
        self.num_markers = int(num_markers)
        self._io_s = settings.io_timeout_ms / 1000.0
        self._schedule = backoff_schedule_ms(
            settings.backoff_ms, settings.max_retries, seed)
        self._addr = parse_addr(settings.connect)
        # -- connection (tx thread is the sole dialer) ----------------
        self._sock: Optional[socket.socket] = None
        self._send_lock = lockwitness.lock("NetEdgeClient._send_lock")
        self._connected = threading.Event()
        self._ever_connected = False
        self._dial_count = 0
        self._fired: set = set()   # (fault_idx, id) net-fault ledger
        self._evicted = False
        #: EOS shipped — the peer closing its end after that is the
        #: protocol's clean goodbye, not a net_reset to count
        self._eos_sent = False
        # -- resend window --------------------------------------------
        self._wlock = lockwitness.lock("NetEdgeClient._wlock")
        self._window: "OrderedDict[int, _WindowEntry]" = OrderedDict()
        self._seq_next = 1
        self._resend_due = threading.Event()
        #: entries popped by the receiver but not yet fully settled —
        #: the EOS drain must not release end-of-stream markers while
        #: an injection is mid-flight (pop happens first for dedup)
        self._finalizing = 0
        # -- receiver-side pad accounting: remote cards carry the
        # loader's pad_rows stamps but the peer's PadCounter dies with
        # the peer, so the receiver re-counts shipped emissions here
        # and the launcher appends it to the job's pad sink
        self._pad_lock = lockwitness.lock("NetEdgeClient._pad_lock")
        self._pad = {"pad_rows": 0, "total_rows": 0, "emissions": 0}
        self._stop = threading.Event()
        self._tx = threading.Thread(target=self._tx_loop,
                                    name="netedge-tx", daemon=True)
        self._rx = threading.Thread(target=self._rx_loop,
                                    name="netedge-rx", daemon=True)

    # -- lifecycle ----------------------------------------------------

    def start(self) -> None:
        self._rx.start()
        self._tx.start()

    def stop(self, timeout_s: float = 30.0) -> None:
        """Join both threads (the tx thread ends itself after the EOS
        drain protocol) and finalize the teardown counters."""
        self._tx.join(timeout=timeout_s)
        self._stop.set()
        self._close_sock()
        self._rx.join(timeout=5.0)
        with self._wlock:
            stranded = len(self._window)
        self.stats.finalize(stranded)

    def pad_snapshot(self) -> Dict[str, int]:
        with self._pad_lock:
            return dict(self._pad)

    # -- connection management (tx thread only) -----------------------

    def _close_sock(self) -> None:
        with self._send_lock:
            sock, self._sock = self._sock, None
            self._connected.clear()
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _mark_dead(self, sock) -> None:
        """Receiver saw the connection die; the dispatcher redials."""
        with self._send_lock:
            if self._sock is sock:
                self._sock = None
                self._connected.clear()
        try:
            sock.close()
        except OSError:
            pass

    def _dial_once(self) -> socket.socket:
        """One dial attempt — consults the fault plan's ``net_refused``
        draws first (dial counter as the request id, PR 1 contract)."""
        self._dial_count += 1
        if self.fault_plan is not None:
            hit = self.fault_plan.net_fault("net_refused",
                                            self._dial_count)
            if hit is not None:
                key = (hit[0], self._dial_count)
                if key not in self._fired:
                    self._fired.add(key)
                    raise NetRefusedError(
                        "injected dial refusal (fault %d, dial %d)"
                        % (hit[0], self._dial_count))
        try:
            sock = socket.create_connection(self._addr,
                                            timeout=self._io_s)
        except Exception as exc:  # noqa: BLE001 - classified below
            net = wire.classify_io_error(exc)
            if net is None and isinstance(exc, OSError):
                # dialing a dead host surfaces as assorted OSErrors
                # (EHOSTUNREACH, ENETUNREACH...) — all "refused" for
                # the edge's purposes: nobody answered
                net = NetRefusedError(str(exc))
            if net is not None:
                raise net from exc
            raise
        sock.settimeout(self._io_s)
        return sock

    def _ensure_connected(self) -> bool:
        """Live connection or bust: dial with the seeded backoff
        schedule; an exhausted cycle (``max_retries`` failed dials)
        evicts the lane and reroutes the whole window locally."""
        if self._connected.is_set():
            return True
        if self._evicted:
            return False
        last_reason = "net_refused"
        for attempt in range(self.settings.max_retries):
            if self._stop.is_set() or self._aborted():
                return False
            try:
                sock = self._dial_once()
            except (NetRefusedError, NetResetError,
                    NetTimeoutError) as exc:
                last_reason = fault_reason(exc)
                self.stats.err(last_reason)
                if attempt < len(self._schedule):
                    self._sleep_ticking(
                        self._schedule[attempt] / 1000.0)
                continue
            with self._send_lock:
                self._sock = sock
                self._connected.set()
            if self._ever_connected:
                self.stats.inc("reconnects")
            self._ever_connected = True
            self._resend_all()
            return True
        self._evict("netedge peer unreachable (%s after %d dials)"
                    % (last_reason, self.settings.max_retries))
        return False

    def _evict(self, reason: str) -> None:
        self._evicted = True
        self.board.evict(NET_LANE, reason)
        self.stats.note_open()   # evicted is as open as it gets
        self._close_sock()
        self._reroute_window()

    # -- resend window ------------------------------------------------

    def _resend_all(self) -> None:
        """After a reconnect: resend every non-terminal entry in
        sequence order. The peer's dedup ledger re-acks and re-serves
        processed ones; the rest are genuinely lost and re-enter."""
        with self._wlock:
            frames = [e.frame for e in self._window.values()]
        for frame in frames:
            if not self._send_raw(frame):
                return
            self.stats.inc("resends")

    def _maybe_resend(self) -> None:
        """Receive-timeout recovery: the receiver heard nothing for a
        full io_timeout, so nudge the oldest unacked entry (an ack
        lost to a reset would otherwise strand it until reconnect)."""
        if not self._resend_due.is_set():
            return
        self._resend_due.clear()
        if not self._connected.is_set():
            return
        with self._wlock:
            frame = next((e.frame for e in self._window.values()
                          if not e.acked), None)
        if frame is not None and self._send_raw(frame):
            self.stats.inc("resends")

    def _reroute_window(self) -> None:
        """Move every non-terminal window entry onto the local fallback
        path — each atomically popped, so a response frame racing in
        for it hits the dedup ledger instead of double-dispatching."""
        while True:
            with self._wlock:
                if not self._window:
                    return
                _, entry = self._window.popitem(last=False)
            card = entry.card
            card.redispatched = getattr(card, "redispatched", 0) + 1
            self.board.note_redispatch(NET_LANE)
            self.board.note_settle(NET_LANE)
            self.stats.inc("local")
            self._put_local((None, entry.path, card))

    # -- dispatcher (netedge-tx) --------------------------------------

    def _aborted(self) -> bool:
        """Abnormal termination only — target-reached keeps the edge
        draining so already-produced requests still terminate."""
        return (self.termination.terminated
                and int(self.termination.value) != 0)

    def _tick(self) -> None:
        """The idle-path health tick: evaluate the board's clock-driven
        transitions (an empty consult sets no probes) and track the
        first OPEN sighting. board.beat() would be WRONG here — it
        refreshes last_beat and would mask exactly the staleness this
        tick exists to let the board see."""
        self.board.route_filter(())
        state = self.board.state(NET_LANE)
        if state in ("open", "evicted"):
            self.stats.note_open()

    def _sleep_ticking(self, seconds: float) -> None:
        deadline = time.monotonic() + seconds
        while not self._stop.is_set() and not self._aborted():
            self._tick()
            left = deadline - time.monotonic()
            if left <= 0:
                return
            time.sleep(min(_TICK_S, left))

    def _put_local(self, item) -> None:
        while not self._stop.is_set():
            try:
                self.local_queue.put(item, timeout=_TICK_S)
                return
            except queue.Full:
                if self._aborted():
                    return
                self._tick()

    def _route_remote(self) -> bool:
        """Cheap pre-filter for the next dispatch: evaluate the board
        and rule out an open/evicted lane early. Deliberately
        CLAIM-FREE — ``consult_and_route`` inside ``_send_request`` is
        the one routing arbiter (it claims half-open probes and
        accounts the route atomically); claiming the probe here via
        ``route_filter((NET_LANE,))`` would make the arbiter refuse
        it and strand the lane half-open until the probe ages out.
        Never forced: the local fallback always exists, so
        ``routes_after_open`` stays an invariant, not an apology."""
        if self._evicted:
            return False
        self.board.route_filter(())   # pure evaluation tick
        state = self.board.state(NET_LANE)
        if state in ("open", "evicted"):
            self.stats.note_open()
            return False
        return state in ("healthy", "suspect", "half_open")

    def _send_raw(self, frame: bytes) -> bool:
        with self._send_lock:
            sock = self._sock
            if sock is None:
                return False
            try:
                wire.send_frame(sock, frame)
            except (NetResetError, NetPartialFrameError,
                    NetTimeoutError) as exc:
                if not self._eos_sent:
                    self.stats.err(fault_reason(exc))
                self._sock = None
                self._connected.clear()
                try:
                    sock.close()
                except OSError:
                    pass
                return False
        self.stats.inc("wire_bytes", len(frame))
        return True

    def _send_request(self, path, card) -> bool:
        """Own this dispatch remotely: window slot, sequence number,
        REQ frame. True = the window owns it now (even if the send
        itself failed — reconnect will resend it); False = route it
        locally instead."""
        if not self._ensure_connected():
            return False
        # block for a window slot, re-consulting the route so a
        # wedged peer (full window, circuit opening) releases new
        # arrivals to the local path instead of convoying behind it
        while True:
            with self._wlock:
                if len(self._window) < self.settings.resend_window:
                    # the routing claim and the slot are taken under
                    # one window lock hold: consult_and_route decides
                    # and accounts atomically on the board (a route
                    # claimed here can never be a routes_after_open
                    # violation), and the entry enters the window only
                    # on a claimed route — never before, so a
                    # concurrent reconnect's _resend_all cannot ship
                    # an entry whose route was about to be refused
                    if not self.board.consult_and_route(NET_LANE):
                        return False
                    seq = self._seq_next
                    self._seq_next += 1
                    frame = wire.encode_frame(
                        wire.REQ, wire.encode_req(path, card), seq=seq,
                        deadline=float(getattr(card, "deadline_s", 0.0)
                                       or 0.0),
                        depth=len(self._window))
                    self._window[seq] = _WindowEntry(seq, path, card,
                                                     frame)
                    break
            if self._stop.is_set() or self._aborted():
                return False
            self._tick()
            self._maybe_resend()
            if not self._connected.is_set() \
                    and not self._ensure_connected():
                return False
            state = self.board.state(NET_LANE)
            if state not in ("healthy", "suspect", "half_open"):
                return False
            time.sleep(_TICK_S)
        self.board.note_enqueue(NET_LANE)
        self.stats.inc("frames_sent")
        self.stats.inc("remote")
        self._send_raw(frame)   # failure is fine: reconnect resends
        return True

    def _tx_loop(self) -> None:
        markers = 0
        while not self._stop.is_set():
            if self._aborted():
                return
            try:
                item = self.filename_queue.get(timeout=_TICK_S)
            except queue.Empty:
                self._tick()
                self._maybe_resend()
                if not self._connected.is_set() and not self._evicted \
                        and self._window_nonempty():
                    self._ensure_connected()
                continue
            if item is None:
                markers += 1
                if markers >= self.num_markers:
                    break
                continue
            _, path, card = item
            if self._route_remote() and self._send_request(path, card):
                continue
            self.stats.inc("local")
            self._put_local((None, path, card))
        self._drain_window()
        # markers ONLY after the drain: every remote injection into
        # the step-0 output queues precedes end-of-stream downstream,
        # and every leftover reroute precedes the markers locally
        for _ in range(markers):
            self._put_local(None)
        self._send_eos()

    def _window_nonempty(self) -> bool:
        with self._wlock:
            return bool(self._window) or self._finalizing > 0

    def _drain_window(self) -> None:
        """Wait (bounded) for in-flight responses, then reroute the
        leftovers locally — nothing strands."""
        budget = (self._io_s * (self.settings.max_retries + 2)
                  + sum(self._schedule) / 1000.0 + 1.0)
        deadline = time.monotonic() + budget
        while self._window_nonempty() and not self._evicted \
                and not self._aborted() \
                and time.monotonic() < deadline:
            self._tick()
            self._maybe_resend()
            if not self._connected.is_set():
                self._ensure_connected()
            time.sleep(_TICK_S)
        self._reroute_window()

    def _send_eos(self) -> None:
        self._eos_sent = True
        if self._connected.is_set():
            self._send_raw(wire.encode_frame(wire.EOS))

    # -- receiver (netedge-rx) ----------------------------------------

    def _rx_loop(self) -> None:
        while not self._stop.is_set():
            # the tx thread swaps _sock on every reconnect — take the
            # same lock that guards the swap, or this loop can read a
            # half-published reference mid-redial
            with self._send_lock:
                sock = self._sock
            if sock is None:
                if self._evicted:
                    return
                self._connected.wait(_TICK_S)
                continue
            try:
                (ftype, _flags, depth, seq, _deadline,
                 payload) = wire.read_frame(sock)
            except NetTimeoutError:
                self.stats.err("net_timeout")
                self._resend_due.set()
                continue
            except NetCorruptFrameError as exc:
                self.stats.err("net_corrupt")
                self._dead_letter(getattr(exc, "seq", 0))
                continue
            except (NetResetError, NetPartialFrameError) as exc:
                if not self._stop.is_set() and not self._eos_sent:
                    self.stats.err(fault_reason(exc))
                self._mark_dead(sock)
                continue
            except OSError:
                self._mark_dead(sock)
                continue
            self.stats.inc("wire_bytes",
                           wire.HEADER_SIZE + len(payload))
            self.board.beat(NET_LANE)
            self.stats.gauge_depth(depth)
            if ftype == wire.BEAT:
                self.stats.inc("beats")
            elif ftype == wire.ACK:
                self._on_ack(seq)
            elif ftype == wire.DATA:
                self._on_data(seq, payload)
            elif ftype == wire.DISPOSE:
                self._on_dispose(seq, payload)

    def _on_ack(self, seq: int) -> None:
        with self._wlock:
            entry = self._window.get(seq)
            if entry is not None and not entry.acked:
                entry.acked = True
                self.stats.inc("frames_acked")

    def _pop_entry(self, seq: int) -> Optional[_WindowEntry]:
        """Terminal-event pop, or the dedup verdict: a response for a
        sequence no longer in the window already terminated — a
        resend's twin, dropped here and never dispatched twice."""
        with self._wlock:
            entry = self._window.pop(seq, None)
            if entry is not None:
                self._finalizing += 1
        if entry is None:
            # classification site: this arrival is a duplicate
            self.stats.inc("dup_arrivals")
        return entry

    def _finalized(self) -> None:
        with self._wlock:
            self._finalizing -= 1

    def _on_data(self, seq: int, payload: bytes) -> None:
        entry = self._pop_entry(seq)
        if entry is None:
            # drop-action site (--check: dedup_drops == dup_arrivals)
            self.stats.inc("dedup_drops")
            return
        batch, non_tensors, card, row_bytes = wire.decode_data(payload)
        self.stats.inc("frame_bytes", row_bytes)
        with self._pad_lock:
            self._pad["pad_rows"] += batch.max_rows - batch.valid
            self._pad["total_rows"] += batch.max_rows
            self._pad["emissions"] += 1
        if self.deadline_stats is not None and expired(card):
            site = deadline_site("netedge")
            card.mark_shed(site)
            self.fault_stats.record_shed(site)
            self.deadline_stats.record(site)
            dispose_requests(self.counter, self.num_videos,
                             self.termination)
        else:
            self._inject((DirectPayload((batch,)), non_tensors, card))
        self.board.note_settle(NET_LANE)
        self._finalized()

    def _on_dispose(self, seq: int, payload: bytes) -> None:
        entry = self._pop_entry(seq)
        if entry is None:
            self.stats.inc("dedup_drops")
            return
        outcome, reason, card = wire.decode_dispose(payload)
        if outcome == "failed":
            self.fault_stats.record_failure([card.id], 0, reason)
            self.board.note_failure(NET_LANE)
        else:
            self.fault_stats.record_shed(reason)
            if self.deadline_stats is not None \
                    and reason.endswith(":deadline_expired"):
                self.deadline_stats.record(reason)
        dispose_requests(self.counter, self.num_videos,
                         self.termination)
        self.board.note_settle(NET_LANE)
        self._finalized()

    def _dead_letter(self, seq: int) -> None:
        """A corrupt frame consumed in full: framing survived, the
        request it carried did not (permanent per the taxonomy)."""
        with self._wlock:
            entry = self._window.pop(seq, None)
            if entry is not None:
                self._finalizing += 1
        if entry is None:
            return
        card = entry.card
        card.mark_failed("net_corrupt")
        self.fault_stats.record_failure([card.id], 0, "net_corrupt")
        self.board.note_failure(NET_LANE)
        self.board.note_settle(NET_LANE)
        dispose_requests(self.counter, self.num_videos,
                         self.termination)
        self._finalized()

    def _inject(self, item) -> None:
        while not self._stop.is_set():
            try:
                self.inject_queue.put(item, timeout=_TICK_S)
                return
            except queue.Full:
                if self.termination.terminated:
                    return


# -- the peer process -------------------------------------------------

class _PeerConnGone(Exception):
    """Internal: this connection is over; back to accept()."""


class NetEdgePeer:
    """The ingest peer: step 0 of the same config, served over the
    wire. One connection at a time (the edge has one sender); a beat
    thread keeps liveness flowing while the model runs."""

    GUARDED_BY = {"_conn": "_send_lock"}
    UNGUARDED_OK = {
        "_ledger": "serve-thread confined",
        "_fired": "serve-thread confined",
        "_depth": "written by the serve thread; the beat thread's "
                  "bare int read is a depth gauge (staleness shows "
                  "up as one conservative beat)",
        "_wedge_until": "written by the serve thread; the beat "
                        "thread reads a float gate (worst case one "
                        "extra beat before wedging)",
        "model": "published by build_model before the listener binds "
                 "and the beat thread starts",
    }

    def __init__(self, config, listen: str, seed: int = 0):
        from rnb_tpu.faults import FaultPlan
        self.config = config
        self.listen_addr = parse_addr(listen)
        self.step = config.steps[0]
        self.settings = (NetEdgeSettings.from_config(config.netedge)
                         or NetEdgeSettings(connect="127.0.0.1:1"))
        self._io_s = self.settings.io_timeout_ms / 1000.0
        self.plan = FaultPlan.resolve(config.fault_plan)
        self.device = self.step.groups[0].devices[0]
        self._fired: set = set()
        self._ledger: "OrderedDict[int, tuple]" = OrderedDict()
        self._depth = 0
        self._wedge_until = 0.0
        self._send_lock = lockwitness.lock("NetEdgePeer._send_lock")
        self._conn: Optional[socket.socket] = None
        self._beat_stop = threading.Event()
        self.model = None

    def build_model(self) -> None:
        """Construct (and warm up) the stage BEFORE binding the
        listener, so the advertised port means 'ready to serve'."""
        from rnb_tpu.utils.class_utils import load_class
        model_class = load_class(self.step.model)
        self.model = model_class(self.device,
                                 **self.step.kwargs_for_group(0))
        if hasattr(self.model, "bind_step"):
            # the executor protocol's step binding: remote emissions
            # carry the same phase-refinement stamps as local ones, so
            # the timing tables keep one schema
            self.model.bind_step(0)

    # -- framing helpers ----------------------------------------------

    def _send(self, frame: bytes) -> None:
        with self._send_lock:
            conn = self._conn
            if conn is None:
                raise _PeerConnGone()
            try:
                wire.send_frame(conn, frame)
            except (NetResetError, NetPartialFrameError,
                    NetTimeoutError) as exc:
                raise _PeerConnGone() from exc

    def _beat_loop(self) -> None:
        period = self.settings.beat_ms / 1000.0
        while not self._beat_stop.wait(period):
            if time.monotonic() < self._wedge_until:
                continue   # a wedged peer is SILENT — that is the point
            try:
                self._send(wire.encode_frame(wire.BEAT,
                                             depth=self._depth))
            except _PeerConnGone:
                return

    # -- request serving ----------------------------------------------

    def _net_hit(self, kind: str, rid: int):
        """One-shot fault draw: re-matches on resends (the plan is
        stateless) but fires once per (fault, request)."""
        if self.plan is None:
            return None
        hit = self.plan.net_fault(kind, rid)
        if hit is None:
            return None
        key = (hit[0], rid)
        if key in self._fired:
            return None
        self._fired.add(key)
        return hit[1]

    def _run_model(self, path, card):
        """The executor containment recipe, single-request edition:
        transient retries per the step budget, permanent degrade."""
        card.add_device(self.device.label)
        card.record("runner%d_start" % 0)
        attempt = 0
        while True:
            card.record("inference%d_start" % 0)
            try:
                tensors, non_tensors, out_card = self.model(
                    None, path, card)
                break
            except TransientError as exc:
                if attempt >= self.step.max_retries:
                    card.mark_failed(fault_reason(exc))
                    return None, fault_reason(exc)
                attempt += 1
                time.sleep(self.step.retry_backoff_ms / 1000.0)
            except PermanentError as exc:
                card.mark_failed(fault_reason(exc))
                return None, fault_reason(exc)
        out_card.record("inference%d_finish" % 0)
        if tensors is None or len(tensors) != 1:
            out_card.mark_failed("net_bad_emission")
            return None, "net_bad_emission"
        return (tensors[0], non_tensors, out_card), None

    def _serve_req(self, seq: int, deadline: float,
                   payload: bytes) -> None:
        if seq in self._ledger:
            # dedup ledger: a resend after ack-loss re-serves the
            # cached outcome — never a second model call
            ack, response = self._ledger[seq]
            self._send(ack)
            self._send(response)
            return
        path, card = wire.decode_req(payload)
        rid = int(card.id)
        hit = self._net_hit("net_reset", rid)
        if hit is not None:
            if hit.get("fatal"):
                os._exit(1)   # the chaos peer kill: no goodbye
            with self._send_lock:
                conn, self._conn = self._conn, None
            if conn is not None:
                conn.setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))   # RST, not FIN
                conn.close()
            raise _PeerConnGone()
        self._depth += 1
        ack = wire.encode_frame(wire.ACK, seq=seq, depth=self._depth)
        self._send(ack)
        hit = self._net_hit("net_timeout", rid)
        if hit is not None:
            # the wedge: acked, then silent — beats pause too, so the
            # main side's distress is inflight age + beat staleness
            stall_s = float(hit.get("ms", 0.0)) / 1000.0
            self._wedge_until = time.monotonic() + stall_s
            time.sleep(stall_s)
        if deadline > 0 and time.time() > deadline:
            site = deadline_site("netedge")
            card.mark_shed(site)
            response = wire.encode_frame(
                wire.DISPOSE, wire.encode_dispose("shed", site, card),
                seq=seq, depth=self._depth)
        else:
            served, reason = self._run_model(path, card)
            if served is None:
                response = wire.encode_frame(
                    wire.DISPOSE,
                    wire.encode_dispose("failed", reason, card),
                    seq=seq, depth=self._depth)
            else:
                batch, non_tensors, out_card = served
                response = wire.encode_frame(
                    wire.DATA,
                    wire.encode_data(batch, non_tensors, out_card),
                    seq=seq, depth=self._depth)
        self._depth -= 1
        self._ledger[seq] = (ack, response)
        while len(self._ledger) > _PEER_LEDGER_MAX:
            self._ledger.popitem(last=False)
        if self._net_hit("net_corrupt", rid) is not None:
            # flip one payload byte AFTER the crc was computed
            corrupt = bytearray(response)
            corrupt[-1] ^= 0xff
            self._send(bytes(corrupt))
            return
        if self._net_hit("net_partial_frame", rid) is not None:
            half = response[:max(1, len(response) // 2)]
            self._send(half)
            with self._send_lock:
                conn, self._conn = self._conn, None
            if conn is not None:
                conn.close()
            raise _PeerConnGone()
        self._send(response)

    # -- accept loop --------------------------------------------------

    def serve_forever(self, port_file: Optional[str] = None) -> int:
        lsock = socket.create_server(self.listen_addr)
        lsock.settimeout(1.0)
        port = lsock.getsockname()[1]
        if port_file:
            tmp = port_file + ".tmp"
            with open(tmp, "w") as f:
                f.write("%d\n" % port)
            os.replace(tmp, port_file)   # atomic: readers never see ""
        served_any = False
        idle_since = time.monotonic()
        try:
            while True:
                try:
                    conn, _ = lsock.accept()
                except socket.timeout:
                    if served_any and (time.monotonic() - idle_since
                                       > _PEER_IDLE_S):
                        return 3   # orphaned: main died without EOS
                    continue
                served_any = True
                conn.settimeout(self._io_s)
                if self._serve_conn(conn):
                    return 0       # EOS: clean end of stream
                idle_since = time.monotonic()
        finally:
            lsock.close()

    def _serve_conn(self, conn) -> bool:
        """One connection until EOS (-> True) or it dies (-> False)."""
        # published under the send lock: a previous connection's beat
        # thread may still be draining through _send — it must observe
        # either the old (dead) socket or the new one, never a torn
        # handoff
        with self._send_lock:
            self._conn = conn
        self._beat_stop.clear()
        beat = threading.Thread(target=self._beat_loop,
                                name="netedge-beat", daemon=True)
        beat.start()
        try:
            while True:
                try:
                    (ftype, _flags, _depth, seq, deadline,
                     payload) = wire.read_frame(conn)
                except NetTimeoutError:
                    continue   # idle sender; beats still flowing
                except (NetResetError, NetPartialFrameError,
                        NetCorruptFrameError):
                    return False
                if ftype == wire.EOS:
                    return True
                if ftype == wire.REQ:
                    try:
                        self._serve_req(seq, deadline, payload)
                    except _PeerConnGone:
                        return False
        finally:
            self._beat_stop.set()
            beat.join(timeout=2.0)
            with self._send_lock:
                conn, self._conn = self._conn, None
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass


def peer_env() -> dict:
    """The environment the spawned peer runs in: the parent's
    (XLA_FLAGS, RNB_FAULT_PLAN — both sides must resolve the same
    fault plan) with JAX pinned to the CPU platform. The peer is a
    host decode process; an accelerator belongs to one process at a
    time, and the parent that spawns it already holds the chip, so a
    peer that opened the default backend would fail or hang there.
    PYTHONPATH leads with this package's checkout, so the peer runs
    the parent's code whatever directory the parent was started in."""
    from rnb_tpu.benchmark import REPO_DIR
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO_DIR, env.get("PYTHONPATH")) if p)
    return env


def spawn_peer(config_path: str, settings: NetEdgeSettings,
               seed: int = 0, timeout_s: float = 60.0):
    """Launch the ingest peer as a real second process (same config
    file the main process runs) and wait for its bound port. Returns
    ``(proc, "host:port")``; the caller owns termination."""
    listen = settings.listen or "127.0.0.1:0"
    host, _ = parse_addr(listen)
    tmpdir = tempfile.mkdtemp(prefix="rnb-netedge-")
    port_file = os.path.join(tmpdir, "port")
    cmd = [sys.executable, "-m", "rnb_tpu.netedge", "--serve",
           "--config", config_path, "--listen", listen,
           "--port-file", port_file, "--seed", str(int(seed))]
    proc = subprocess.Popen(cmd, env=peer_env())
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                "netedge peer exited rc=%d before binding its port"
                % proc.returncode)
        if os.path.exists(port_file):
            with open(port_file) as f:
                port = int(f.read().strip())
            return proc, "%s:%d" % (host, port)
        time.sleep(0.05)
    proc.terminate()
    raise RuntimeError("netedge peer did not bind within %.0fs"
                       % timeout_s)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m rnb_tpu.netedge",
        description="RnB-TPU ingest peer: serve step 0 of a pipeline "
                    "config over the netedge wire protocol.")
    parser.add_argument("--serve", action="store_true", required=True,
                        help="run the ingest peer (the only mode)")
    parser.add_argument("--config", required=True,
                        help="pipeline config JSON (same file the "
                             "main process runs)")
    parser.add_argument("--listen", default="127.0.0.1:0",
                        help="host:port to bind (port 0 = ephemeral)")
    parser.add_argument("--port-file", default=None,
                        help="write the bound port here once serving")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    from rnb_tpu.benchmark import enable_compilation_cache
    from rnb_tpu.config import load_config
    enable_compilation_cache()
    config = load_config(args.config)
    peer = NetEdgePeer(config, args.listen, seed=args.seed)
    peer.build_model()
    return peer.serve_forever(port_file=args.port_file)


if __name__ == "__main__":
    sys.exit(main())
