"""The pipeline stages of the token families (the packages under
``rnb_tpu/models/`` that bring ``checkpoint`` and ``network``;
``tests/family_contract.py``'s ``FAMILIES`` lists them): a first stage whose
request is a prompt file, and a final stage that runs a family's stack
over a packed pool of rows. Between them stands ``rnb_tpu.batcher.Batcher``
(``segments: true``), which fuses requests into row buckets up to the
row cap and carries the segment table.

A family is a package ``rnb_tpu.models.<family>`` that brings
``checkpoint.load_recipe(path) -> (cfg, seed, held)``,
``checkpoint.make_params(cfg, seed, held, device)`` and
``network.forward(cfg, params, slots, tokens, row_tokens, row_start,
last_idx, interpret=...) -> (logits, chosen, *counts)``; its ``cfg``
says ``chunk_size``. ``chosen`` is what the stack chose for each token
and the run's check hands its reference (a router's experts, an
attention's key blocks), token axis second; ``network.COUNTERS`` names
the ``counts``, device counters of one dispatch with the layers that
count first, which the stage sums over the dispatches it serves and
hands on by those names (``stage_counters()``).
``rnb_tpu.telemetry.STAGE_COUNTERS`` is where a counter is described:
what it counts, its shape, the log-meta line and the keys it is written
under. A family with ``expert_served`` among them holds a share of each
layer's experts: ``network.held_slots(cfg, held)`` makes its ``slots``
and ``cfg.num_experts_per_tok`` is reported beside the counter; one
without has ``slots`` None. A family whose choices are not one row a
token, or of more kinds than one (a router's experts and an attention's
keys: ``chosen`` is then a tuple), brings ``network.request_choices(cfg,
chosen, first, count)``: what a sample keeps of ``chosen`` for the
request of ``count`` tokens from flat token ``first``, an array (the
sample's ``chosen``) or a dict of them, a name each. The recipe the final stage is pointed at
names the family. ``MAX_ROWS`` is the default row cap; a
configuration's pipeline states its own.

The batch unit is the *row*: ``chunk_size`` tokens. A prompt of L
tokens is ceil(L / chunk) consecutive rows, the last one's tail
padded; two tensors travel: the token ids ``(rows, chunk)`` and the
valid tokens of each row ``(rows,)``.
"""

from __future__ import annotations

import importlib
import os
import queue
import threading
from typing import Optional

import numpy as np

from rnb_tpu import hloscopes, trace
from rnb_tpu.compilestats import SignatureTracker
from rnb_tpu.health import cards_of
from rnb_tpu.stage import (PaddedBatch, StageModel,
                           normalize_row_buckets)
from rnb_tpu.telemetry import STAGE_COUNTERS

MAX_ROWS = 64
CHUNK = 128
_COUNTER_ROWS = {row.counter: row for row in STAGE_COUNTERS}


def rows_of_tokens(num_tokens: int, chunk: int = CHUNK) -> int:
    return -(-int(num_tokens) // int(chunk))


class TokenLoader(StageModel):
    """First stage: reads a request's prompt (a ``.npy`` of int32 token
    ids) and emits its rows."""

    def __init__(self, device, max_rows: int = MAX_ROWS,
                 chunk: int = CHUNK, **kwargs):
        super().__init__(device)
        self.max_rows = int(max_rows)
        self.chunk = int(chunk)

    @classmethod
    def output_shape_for(cls, max_rows: int = MAX_ROWS,
                         chunk: int = CHUNK, **_kwargs):
        return ((int(max_rows), int(chunk)), (int(max_rows),))

    @staticmethod
    def output_shape():
        return ((MAX_ROWS, CHUNK), (MAX_ROWS,))

    @classmethod
    def output_dtype_for(cls, **_kwargs):
        return "int32"

    def __call__(self, tensors, non_tensors, time_card):
        rid = getattr(time_card, "id", None)
        with trace.span("tokens.read", rid):
            ids = np.load(str(non_tensors)).astype(np.int32, copy=False)
        count = int(ids.shape[0])
        rows = rows_of_tokens(count, self.chunk)
        if not 0 < rows <= self.max_rows:
            raise ValueError("%s holds %d tokens: a request is 1 to %d "
                             "rows of %d" % (non_tensors, count,
                                             self.max_rows, self.chunk))
        with trace.span("tokens.pack", rid, rows=rows, tokens_valid=count,
                        segments=1):
            packed = np.zeros((rows, self.chunk), np.int32)
            packed.reshape(-1)[:count] = ids
            row_tokens = np.full((rows,), self.chunk, np.int32)
            row_tokens[-1] = count - (rows - 1) * self.chunk
        time_card.num_clips = rows
        time_card.num_tokens = count
        return (PaddedBatch(packed, rows), PaddedBatch(row_tokens, rows)), \
            non_tensors, time_card


def dispatch_meta(offsets, row_tokens, rows: int, chunk: int):
    """The (3, rows) int32 table one packed dispatch carries beside its
    tokens: each row's valid tokens, the first row of its request (its
    own index on a pad row), and the flat index of request i's last
    valid token."""
    offsets = np.asarray(offsets, np.int64)
    valid = int(offsets[-1])
    meta = np.zeros((3, rows), np.int32)
    meta[0, :valid] = np.asarray(row_tokens[:valid], np.int32)
    meta[1] = np.arange(rows)
    spans = np.diff(offsets)
    meta[1, :valid] = np.repeat(offsets[:-1], spans)
    last_rows = offsets[1:] - 1
    keep = spans > 0
    meta[2, :len(spans)][keep] = \
        last_rows[keep] * chunk + meta[0, last_rows[keep]] - 1
    return meta


def pack_prompts(prompts, rows: int, chunk: int):
    """-> (tokens (rows, chunk), meta, offsets): prompts (arrays of
    ids) packed in order into one dispatch of ``rows`` rows, as the
    loader and the Batcher pack them (tests and
    ``scripts/prefill_control.py``)."""
    tokens = np.zeros((rows, chunk), np.int32)
    per_row = np.zeros(rows, np.int32)
    offsets = [0]
    for prompt in prompts:
        row, count = offsets[-1], rows_of_tokens(len(prompt), chunk)
        tokens.reshape(-1)[row * chunk:row * chunk + len(prompt)] = prompt
        per_row[row:row + count] = chunk
        per_row[row + count - 1] = len(prompt) - (count - 1) * chunk
        offsets.append(row + count)
    return tokens, dispatch_meta(offsets, per_row, rows, chunk), offsets


class PackedPrefill(StageModel):
    """Final stage: embedding -> the blocks -> final norm -> head on
    each request's last valid token, one jitted program a row bucket.
    Weights are made on the device from the recipe at ``ckpt_path``,
    which names the family (``family``, where a pipeline gives it, has
    to agree).

    It emits one value a request (the executor waits on it); the
    logits stay on the device. While it serves, it keeps what the run's
    check compares: the logits (and the stack's choices: a router's
    experts, an attention's key blocks or keys) of ``samples`` requests,
    every ``sample_every``-th it serves, written under the run's log
    directory when the stage ends. What it reads of a dispatch on the
    host it reads behind a later launch, so that the device does not
    idle for the host's reading: its counters once the *next* dispatch
    is launched; a sampled one's arrays are fetched from then on by a
    thread of their own."""

    #: rnb-lint's contract (rnb_tpu.analysis.concurrency, RNB-C002)
    READ_ONLY_ROLES = {
        "prefill-load": "the constructor's worker reads the stage's "
                        "weights and hands what it makes back through "
                        "its arguments; the constructor alone, behind "
                        "its join, writes the stage"}

    def __init__(self, device, ckpt_path: Optional[str] = None,
                 max_rows: int = MAX_ROWS, chunk: int = CHUNK,
                 row_buckets=None, num_warmups: int = 1,
                 sample_every: int = 50, samples: int = 8,
                 family: Optional[str] = None, **kwargs):
        super().__init__(device)
        import jax

        from rnb_tpu.models import seeded
        if ckpt_path is None:
            raise ValueError("PackedPrefill needs ckpt_path: the "
                             "recipe its weights are made from")
        self.family = seeded.read_recipe(ckpt_path)["family"]
        if family not in (None, self.family):
            raise ValueError("the pipeline names family %r, the recipe %s "
                             "%r" % (family, ckpt_path, self.family))
        checkpoint, network = (
            importlib.import_module("rnb_tpu.models.%s.%s"
                                    % (self.family, part))
            for part in ("checkpoint", "network"))
        self.cfg, seed, held = checkpoint.load_recipe(ckpt_path)
        self.max_rows, self.chunk = int(max_rows), int(chunk)
        if self.chunk != self.cfg.chunk_size:
            raise ValueError("a row is chunk_size=%d tokens, not %d"
                             % (self.cfg.chunk_size, self.chunk))
        self.row_buckets = normalize_row_buckets(row_buckets,
                                                 self.max_rows, "max_rows")
        self._jax_device = device.resolve()
        # set-up's spans (the launcher's Tracer collects them until the
        # start barrier): the step is the one the executor bound, to
        # this thread, so the worker's names are made here too
        step = trace.building_step()
        tr_program = trace.name("setup.s%d.program", step)
        tr_load_wait = trace.name("setup.s%d.load_wait", step)
        tr_worker = (trace.name("setup.s%d.load", step),
                     trace.name("setup.s%d.scopes", step),
                     trace.name("setup.s%d.first_call", step))
        self._slots = None
        with trace.span(trace.name("setup.s%d.weights", step)):
            self._params = checkpoint.make_params(self.cfg, seed, held,
                                                  self._jax_device)
            if "expert_served" in network.COUNTERS:
                self._slots = jax.device_put(
                    network.held_slots(self.cfg, held), self._jax_device)
        self._request_choices = getattr(network, "request_choices", None)
        cfg = self.cfg
        interpret = self._jax_device.platform != "tpu"

        def apply(params, slots, tokens, meta):
            logits, chosen, *counts = network.forward(
                cfg, params, slots, tokens, meta[0], meta[1], meta[2],
                interpret=interpret)
            # one value a request goes back to the executor, which
            # waits on it as on any stage's output
            return logits[:, 0], logits, chosen, tuple(counts)
        # one program a row bucket, made ahead, two buckets at a time:
        # this thread traces and lowers them in turn (Python, the GIL
        # held) while one worker turns each lowered program into its
        # loaded executable, reads its text, which says which named scope
        # each instruction came from (hlo_scopes), and makes its first
        # calls (the compiler or the cache's read and the load onto the
        # chip: C++ that lets the GIL go)
        self._programs = {}
        self.hlo_scopes = {}
        self.compiles = SignatureTracker()
        lowered, loaded, failed = queue.SimpleQueue(), [], []
        worker = threading.Thread(
            target=self._load_programs, name="prefill-load", daemon=True,
            args=(lowered, loaded, failed, tr_worker, int(num_warmups)))
        worker.start()
        try:
            for rows in self.row_buckets:
                if failed:
                    break
                tokens = np.zeros((rows, self.chunk), np.int32)
                meta = dispatch_meta((0, rows), np.full(rows, self.chunk),
                                     rows, self.chunk)
                self.compiles.observe(tokens)
                with trace.span(tr_program, rows=rows):
                    lowered.put((rows, tokens, meta, jax.jit(apply).lower(
                        self._params, self._slots, tokens, meta)))
        except BaseException as e:
            failed.append(e)    # the worker drops what is still queued
            raise
        finally:
            lowered.put(None)
            with trace.span(tr_load_wait):
                worker.join()
        if failed:
            raise failed[0]
        # in the buckets' order: a later bucket's scopes overwrite an
        # earlier one's under the same key
        for rows, program, scopes in loaded:
            self._programs[rows] = program
            self.hlo_scopes.update(scopes)
        #: counters of the dispatches served: valid and shipped tokens
        #: (the Tokens: line) and the family's own, by the names of
        #: ``network.COUNTERS``, summed as they come back
        self.tokens_valid = 0
        self.tokens_shipped = 0
        self._counter_names = tuple(network.COUNTERS)
        self._counted = {}
        self._sample_every = max(1, int(sample_every))
        self._samples_wanted = int(samples)
        self._samples = []
        #: samples taken from the last dispatch, the threads that fetch
        #: the earlier ones' arrays, and what one of them raised
        self._sampled = []
        self._fetching = []
        self._sample_errors = []
        self._samples_taken = 0
        self._served = 0
        self._log_dir = None
        #: (counts, valid tokens, rows) of the last dispatch: its counters
        #: are read once the next dispatch is launched (the executor has
        #: waited for this one by then)
        self._pending = None

    def _load_programs(self, lowered, loaded, failed, names,
                       num_warmups: int) -> None:
        """The constructor's worker: each ``(rows, tokens, meta, lowered
        program)`` of ``lowered``, up to the None that ends it, becomes
        ``(rows, executable, its scope table)`` in ``loaded``, warmed by
        ``num_warmups`` calls. What it raises goes to ``failed`` for the
        constructor to raise, and what is still queued is dropped."""
        import jax
        tr_load, tr_scopes, tr_first_call = names
        for rows, tokens, meta, program in iter(lowered.get, None):
            if failed:
                continue
            try:
                with trace.span(tr_load, rows=rows):
                    program = program.compile()
                    with trace.span(tr_scopes):
                        scopes = hloscopes.scopes_of_hlo(program.as_text())
                    with trace.span(tr_first_call):
                        for _ in range(num_warmups):
                            jax.block_until_ready(program(
                                self._params, self._slots, tokens, meta))
                loaded.append((rows, program, scopes))
            except BaseException as e:     # raised by the constructor
                failed.append(e)

    def bind_log_dir(self, log_dir: str) -> None:
        self._log_dir = log_dir

    @classmethod
    def input_shape_for(cls, max_rows: int = MAX_ROWS, chunk: int = CHUNK,
                        **_kwargs):
        return ((int(max_rows), int(chunk)), (int(max_rows),))

    def input_shape(self):
        return self.input_shape_for(max_rows=self.max_rows,
                                    chunk=self.chunk)

    @classmethod
    def input_dtype_for(cls, **_kwargs):
        return "int32"

    @classmethod
    def output_shape_for(cls, max_rows: int = MAX_ROWS, **_kwargs):
        return ((int(max_rows),),)

    @staticmethod
    def output_shape():
        return ((MAX_ROWS,),)

    @classmethod
    def output_dtype_for(cls, **_kwargs):
        return "float32"

    def stage_counters(self) -> dict:
        """What ``telemetry.stage_counter_report`` reads of a run's
        stages: the tokens, and the family's counters under their
        names, each summed over its layers to the keys of its row in
        ``telemetry.STAGE_COUNTERS`` (a row with a reduction of its own
        whole); only what the family counts, and none before the first
        dispatch."""
        self._count_pending()
        counters = {"tokens_valid": int(self.tokens_valid),
                    "tokens_shipped": int(self.tokens_shipped)}
        for row in STAGE_COUNTERS:
            count = self._counted.get(row.counter)
            if count is None:
                continue
            if row.reduce or row.maxima:
                counters[row.counter] = count.copy()
            else:
                counters[row.counter] = count.reshape(
                    -1, len(row.keys)).sum(axis=0)
        if "expert_served" in counters:
            counters["experts_per_token"] = int(
                self.cfg.num_experts_per_tok)
        return counters

    def _count_pending(self) -> None:
        """The counters the last dispatch brought back with its logits
        (the executor has waited for it: no extra synchronisation)."""
        pending, self._pending = self._pending, None
        if pending is not None:
            self._count(pending)

    def _count(self, pending) -> None:
        counts, valid, rows = pending
        for name, count in zip(self._counter_names, counts):
            self._counted[name] = _COUNTER_ROWS[name].merge(
                self._counted.get(name), np.asarray(count, np.int64))
        self.tokens_valid += valid
        self.tokens_shipped += rows * self.chunk

    def __call__(self, tensors, non_tensors, time_card):
        pb, per_row = tensors
        rows = pb.max_rows
        offsets = getattr(pb, "segment_offsets", (0, int(pb.valid)))
        tokens = np.asarray(pb.data, np.int32)
        meta = dispatch_meta(offsets, np.asarray(per_row.data), rows,
                             self.chunk)
        self.compiles.observe(tokens)
        done, logits, chosen, counts = self._programs[rows](
            self._params, self._slots, tokens, meta)
        # what the dispatch before this one left to read — its counters
        # and, where it was sampled, its arrays, which have reached the
        # host while the device ran — is read now that this one is under
        # way: the device does not wait for the host's reading
        before, self._pending = self._pending, \
            (counts, int(meta[0].sum()), rows)
        if before is not None:
            self._count(before)
        self._send_samples()
        cards = cards_of(time_card)
        for seg, card in enumerate(cards):
            self._served += 1
            if self._served % self._sample_every == 0 \
                    and self._samples_taken < self._samples_wanted \
                    and len(cards) == len(offsets) - 1:
                self._start_sample(seg, card, offsets, tokens, meta, logits,
                                   chosen, rows)
        return (PaddedBatch(done, len(offsets) - 1),), non_tensors, \
            time_card

    def _start_sample(self, seg, card, offsets, tokens, meta, logits,
                      chosen, rows) -> None:
        """A sample of request ``seg`` of the dispatch just launched:
        its arrays are held on the device until the next dispatch is
        launched (:meth:`_send_samples`)."""
        first = int(offsets[seg]) * self.chunk
        count = int(meta[2, seg]) + 1 - first
        self._samples_taken += 1
        self._sampled.append(({
            "rid": int(card.id), "rows": rows,
            "segments": len(offsets) - 1,
            "tokens": tokens.reshape(-1)[first:first + count].copy()},
            seg, first, count, logits, chosen))

    def _send_samples(self) -> None:
        """The samples taken from the dispatch before the one just
        launched go to a thread each, which fetches their arrays and
        keeps the request's part. Not sooner, and not on this thread:
        behind its own launch such a copy (hundreds of MB where a sample
        keeps every query's keys) stood in front of the *next*
        dispatch's inputs on the way to the device, and read on the
        stage's thread it held the launch after that back: the chip
        idled 0.25 to 0.55 s a sampled dispatch (my chip runs, PR 46)."""
        for entry in self._sampled:
            reader = threading.Thread(target=self._fetch_sample, args=entry,
                                      name="prefill-sample", daemon=True)
            reader.start()
            self._fetching.append(reader)
        self._sampled = []

    def _fetch_sample(self, sample, seg, first, count, logits, chosen):
        """What a sample keeps: the request's line of the logits and
        its own slice of the stack's choices (``chosen``, or what the
        family's ``request_choices`` names)."""
        try:
            sample["logits"] = np.asarray(logits)[seg].astype(np.float32)
            if self._request_choices is None:
                kept = np.asarray(chosen)[:, first:first + count].copy()
            else:
                kept = self._request_choices(self.cfg, chosen, first, count)
            sample.update(kept if isinstance(kept, dict)
                          else {"chosen": kept})
            self._samples.append(sample)
        except BaseException as e:     # raised where the stage ends
            self._sample_errors.append(e)

    def _collect_samples(self) -> None:
        """Waits for the samples under way (the stage's end; the
        tests)."""
        for reader in self._fetching:
            reader.join()
        self._fetching = []
        if self._sample_errors:
            raise self._sample_errors[0]
        self._samples.sort(key=lambda sample: sample["rid"])

    def finalize(self) -> None:
        """The stage has drained: write the samples and the scopes of
        its programs' instructions, free the weights."""
        self._count_pending()
        self._send_samples()
        self._collect_samples()
        if self._log_dir is not None:
            for k, sample in enumerate(self._samples):
                np.savez(os.path.join(self._log_dir,
                                      "prefill-sample-%d.npz" % k),
                         **sample)
            hloscopes.write_table(self._log_dir, self.hlo_scopes)
        self._params = None
        self._programs = None

