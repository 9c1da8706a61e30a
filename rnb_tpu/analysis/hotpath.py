"""Hot-path AST lint: host-sync and import hygiene, statically.

The runtime's throughput ceiling in round 5 was the single host core
(2026-07, previous transport, not reproduced), so the per-request
code paths have hard rules the tree learned the expensive way — PR 2 measured per-emission ``import`` machinery and
``np.zeros`` staging as whole percentage points of the core. This
module encodes those rules over the AST so they hold by construction
instead of by review.

What counts as *hot*: the executor's thread entry
(``rnb_tpu/runner.py::runner``) and every stage-contract entry point —
``__call__``, ``submit``, ``complete``, ``poll``, ``select`` — plus
everything reachable from them through same-module ``self.method()`` /
bare-function calls (an intra-module call graph; cross-module calls
are out of scope and covered by linting the callee's own module).

Rules
-----
* ``RNB-H001`` jit-host-sync: a host-sync/host-data call
  (``np.asarray``, ``.block_until_ready()``, ``float()``/``int()``,
  ``.valid_data()``, ``time.time``, ``print``, ``device_put``) inside
  a function handed to ``jax.jit`` in the same module — under jit
  these either break tracing or silently force a device round-trip.
* ``RNB-H002`` hot-import: an ``import`` statement inside a hot
  function — per-request interpreter import machinery; hoist to the
  module top or use :mod:`rnb_tpu.utils.lazy_jax`.
* ``RNB-H003`` device-put-in-loop: ``device_put`` inside a ``for`` /
  ``while`` loop of a hot function — per-item transfers serialize on
  transfer latency; batch first, transfer once.
* ``RNB-H004`` fault-nondeterminism: wall-clock (``time.time``) or
  unseeded RNG (``random.*``, ``np.random.*``, ``datetime.now``) in
  deterministic fault-injection code (``rnb_tpu/faults.py`` and any
  ``*FaultPlan*`` class) — injection schedules must be reproducible.
* ``RNB-H005`` ring-write-before-shed: within one function, a write
  into an ``output_ring`` slot at a line preceding the shed decision
  (``_shed_item``) — a written-but-never-signalled slot deadlocks the
  producer on the next wrap-around.
* ``RNB-H006`` host-sync-in-hot-path: ``.block_until_ready()``,
  ``np.asarray``, ``.valid_data()``, or ``float()``/``int()`` over a
  ``jax``/``jnp`` expression in a hot function — a deliberate sync
  belongs in the baseline with its justification, everything else is
  a stall of the executor thread.
* ``RNB-H007`` bucket-alloc-per-emission: ``np.empty``/``np.zeros``
  of a bucket/batch shape (an argument referencing a
  ``_batch_shape``-style helper) in a hot function — a fresh
  bucket-shaped host allocation per request/emission is the staging
  anti-pattern PR 4 removed; decode into a ``rnb_tpu.staging``
  StagingPool slot instead, and baseline the copy fallback with its
  justification.
* ``RNB-H009`` unbounded-blocking-wait: a no-argument ``.get()`` /
  ``.wait()`` / ``.acquire()`` / ``.result()`` call without a
  ``timeout`` keyword in an executor/stage hot path (or any ``wait``
  method, the blocking leaves hot paths call through) — a consumer
  blocked forever on a dead producer's queue/event hangs the drain
  path past every containment mechanism. Bound the wait and re-check
  liveness (termination flag, pool failure, deadline) each lap, or
  baseline the site with the justification for why it cannot hang
  (e.g. a Barrier carrying a construction-time timeout).
* ``RNB-H010`` device-alloc-per-emission: a pool/bucket-shaped
  DEVICE allocation (``jnp.zeros``/``jnp.empty``/``jnp.ones`` of a
  stage-declared shape, or a ``device_put`` whose payload expression
  derives from one) in a hot function outside the page allocator —
  the device twin of RNB-H007. A fresh pool-shaped device array per
  emission fragments HBM and defeats the single-slab page allocator
  (rnb_tpu.pager) that exists to own exactly these bytes; allocate
  once at stage init (an arena, a preallocated zero pool) and reuse,
  or baseline a deliberate staged fallback with its justification.
  ``rnb_tpu/pager.py`` itself is exempt: its arena slab is the one
  legal pool-shaped device allocation.
* ``RNB-H008`` host-materialization-on-device-edge: a host
  materialization call (``device_get``, ``np.asarray``/``np.array``,
  ``.copy_to_host_async``, ``.tolist``) inside a device-resident
  handoff path — a ``*Handoff*`` class method (or a module-level
  function of a ``handoff*.py`` module) whose name does not mark it
  as the host-mode path with a ``host`` component. The device-
  resident edge contract (rnb_tpu.handoff) promises zero host-hop
  bytes; a host bounce creeping into its take path would silently
  void the contract while the ``Handoff:`` accounting kept claiming
  d2d. Route the call through a ``*host*``-named method (the
  explicit host-mode arm) or fix it.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from rnb_tpu.analysis.findings import (Finding, package_py_files,
                                       parse_py)

#: stage-contract entry points — hot by definition
HOT_ROOT_METHODS = {"__call__", "submit", "complete", "poll", "select"}

#: module-level functions that are hot loops, keyed by path suffix
EXTRA_HOT_ROOTS = {"rnb_tpu/runner.py": {"runner"}}

#: receivers recognized as the numpy module
_NP_NAMES = {"np", "numpy"}


def _qual(owner: Optional[str], name: str) -> str:
    return "%s.%s" % (owner, name) if owner else name


class _ModuleIndex(ast.NodeVisitor):
    """Collect defs, class structure and jitted-function names."""

    def __init__(self):
        self.functions: Dict[str, ast.AST] = {}   # qualname -> def node
        self.by_name: Dict[str, List[str]] = {}   # bare name -> qualnames
        self.class_bases: Dict[str, List[str]] = {}
        self.class_methods: Dict[str, Set[str]] = {}
        self.jit_names: Set[str] = set()
        self._class: Optional[str] = None
        self._stack: List[str] = []  # enclosing function names

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        prev, self._class = self._class, node.name
        self.class_bases[node.name] = [
            b.id if isinstance(b, ast.Name) else
            b.attr if isinstance(b, ast.Attribute) else ""
            for b in node.bases]
        self.class_methods[node.name] = set()
        self.generic_visit(node)
        self._class = prev

    def _visit_def(self, node) -> None:
        qual = _qual(self._class,
                     ".".join(self._stack + [node.name]))
        if qual in self.functions:
            # same-name defs (e.g. per-branch closures): keep each
            # registered so every jitted variant gets linted. The
            # suffix is an occurrence ordinal — stable for baselining
            # (no line numbers, no '#' which baseline syntax reserves
            # for justifications)
            ordinal = 2
            while "%s~%d" % (qual, ordinal) in self.functions:
                ordinal += 1
            qual = "%s~%d" % (qual, ordinal)
        self.functions[qual] = node
        self.by_name.setdefault(node.name, []).append(qual)
        if self._class is not None and not self._stack:
            self.class_methods[self._class].add(node.name)
        for deco in node.decorator_list:
            if _is_jit(deco) or (isinstance(deco, ast.Call)
                                 and _is_jit(deco.func)):
                self.jit_names.add(node.name)
        # recurse: the real jit sites live INSIDE function bodies
        # (`fn = jax.jit(apply)` in a factory), and nested defs need
        # their own registration so by_name can resolve them
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def

    def visit_Call(self, node: ast.Call) -> None:
        if _is_jit(node.func) and node.args \
                and isinstance(node.args[0], ast.Name):
            self.jit_names.add(node.args[0].id)
        self.generic_visit(node)


def _is_jit(node) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "jit"
    if isinstance(node, ast.Attribute):
        return node.attr == "jit"
    return False


def _own_walk(node):
    """ast.walk over a function's OWN statements, not descending into
    nested function defs — nested defs are registered under their own
    qualname and linted there, so one call site yields one finding
    with one stable anchor (never a parent+closure duplicate)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        sub = stack.pop()
        yield sub
        if not isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(sub))


def _attr_chain_has(node, names: Set[str]) -> bool:
    """Does any Name/attr component of an expression match ``names``?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in names:
            return True
    return False


def _method_owner(index: _ModuleIndex, cls: str, method: str
                  ) -> Optional[str]:
    """Resolve ``self.method`` against a class and its in-module
    ancestors; -> owning class name or None."""
    seen = set()
    stack = [cls]
    while stack:
        c = stack.pop()
        if c in seen or c not in index.class_methods:
            continue
        seen.add(c)
        if method in index.class_methods[c]:
            return c
        stack.extend(index.class_bases.get(c, ()))
    return None


def _hot_set(index: _ModuleIndex, rel: str) -> Set[str]:
    """Qualnames reachable from the hot roots via the intra-module
    call graph."""
    roots: List[str] = []
    for cls, methods in index.class_methods.items():
        for m in methods & HOT_ROOT_METHODS:
            roots.append(_qual(cls, m))
    for suffix, names in EXTRA_HOT_ROOTS.items():
        if rel.endswith(suffix):
            roots.extend(n for n in names if n in index.functions)
    hot: Set[str] = set()
    stack = list(roots)
    while stack:
        qual = stack.pop()
        if qual in hot or qual not in index.functions:
            continue
        hot.add(qual)
        # closures of a hot function run on the same hot path; they
        # are linted under their own qualname (one finding per site)
        prefix = qual + "."
        stack.extend(q for q in index.functions
                     if q.startswith(prefix))
        cls = qual.rsplit(".", 1)[0] if "." in qual else None
        for node in ast.walk(index.functions[qual]):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "self" and cls is not None):
                owner = _method_owner(index, cls, f.attr)
                if owner is not None:
                    stack.append(_qual(owner, f.attr))
            elif isinstance(f, ast.Name) and f.id in index.functions:
                stack.append(f.id)
    return hot


def _host_sync_kind(node: ast.Call) -> Optional[str]:
    """Classify one call as a host-sync pattern, or None."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "block_until_ready":
            return ".block_until_ready()"
        if f.attr == "valid_data":
            return ".valid_data()"
        if f.attr == "asarray" and isinstance(f.value, ast.Name) \
                and f.value.id in _NP_NAMES:
            return "np.asarray()"
    if isinstance(f, ast.Name) and f.id in ("float", "int") and node.args:
        if any(_attr_chain_has(a, {"jax", "jnp"}) for a in node.args):
            return "%s() on a device value" % f.id
    return None


#: attribute accesses that make an int()/float() argument static
#: metadata (legal under jit) rather than a traced value
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}


def _lint_jit_body(rel: str, qual: str, node, findings: List[Finding]
                   ) -> None:
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        kind = _host_sync_kind(sub)
        f = sub.func
        if kind is None and isinstance(f, ast.Name) \
                and f.id in ("float", "int", "print") and sub.args:
            # int(x.shape[0]) & friends are static shape arithmetic,
            # idiomatic and legal under jit — only traced values sync
            if f.id == "print" or not all(
                    _attr_chain_has(a, _STATIC_ATTRS)
                    for a in sub.args):
                kind = "%s()" % f.id
        if kind is None and isinstance(f, ast.Attribute) \
                and f.attr == "device_put":
            kind = "device_put()"
        if kind is None and isinstance(f, ast.Attribute) \
                and f.attr == "time" and isinstance(f.value, ast.Name) \
                and f.value.id == "time":
            kind = "time.time()"
        if kind is not None:
            findings.append(Finding(
                "RNB-H001", rel, sub.lineno, qual,
                "%s inside a jit-compiled function — breaks tracing or "
                "forces a device round-trip" % kind))


#: every looping construct a per-item device_put can hide in —
#: comprehensions are the idiomatic JAX spelling of the same bug
_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp,
               ast.SetComp, ast.DictComp, ast.GeneratorExp)

#: helper names whose result is a bucket/batch shape — an np.empty/
#: np.zeros over one of these on a hot path is a per-emission staging
#: allocation (RNB-H007)
_BATCH_SHAPE_HELPERS = {"_batch_shape", "batch_shape", "bucket_shape"}


def _bucket_alloc_kind(node: ast.Call) -> Optional[str]:
    """Classify one call as a bucket-shaped host allocation, or None."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in ("empty", "zeros") \
            and isinstance(f.value, ast.Name) \
            and f.value.id in _NP_NAMES and node.args:
        if _attr_chain_has(node.args[0], _BATCH_SHAPE_HELPERS):
            return "np.%s() of a bucket shape" % f.attr
    return None


#: receivers recognized as the jax.numpy module (RNB-H010)
_JNP_NAMES = {"jnp"}

#: the one module whose pool-shaped device allocation IS the design —
#: the page allocator's arena slab (rnb_tpu.pager); everything else
#: must draw from it or preallocate at stage init
_H010_EXEMPT_BASENAMES = {"pager.py"}


def _device_alloc_kind(node: ast.Call) -> Optional[str]:
    """Classify one call as a pool/bucket-shaped DEVICE allocation
    (RNB-H010), or None: a jnp zeros/empty/ones whose shape comes
    from a stage-declared shape helper, or a device_put whose payload
    expression derives from one (``device_put(np.zeros(
    self._batch_shape(n)))`` is the canonical spelling)."""
    f = node.func
    if isinstance(f, ast.Attribute) \
            and f.attr in ("empty", "zeros", "ones") \
            and isinstance(f.value, ast.Name) \
            and f.value.id in _JNP_NAMES and node.args:
        if _attr_chain_has(node.args[0], _BATCH_SHAPE_HELPERS):
            return "jnp.%s() of a stage-declared shape" % f.attr
    if isinstance(f, ast.Attribute) and f.attr == "device_put" \
            and node.args:
        if any(_attr_chain_has(a, _BATCH_SHAPE_HELPERS)
               for a in node.args):
            return "device_put() of a stage-declared shape"
    return None


def _lint_hot_body(rel: str, qual: str, node,
                   findings: List[Finding]) -> None:
    loop_spans: List[Tuple[int, int]] = []
    for sub in _own_walk(node):
        if isinstance(sub, _LOOP_NODES):
            loop_spans.append((sub.lineno,
                               max(getattr(sub, "end_lineno", sub.lineno),
                                   sub.lineno)))

    def in_loop(lineno: int) -> bool:
        # inclusive bounds: one-line `for ...: device_put(...)` bodies
        # and comprehension headers are still per-item transfers
        return any(lo <= lineno <= hi for lo, hi in loop_spans)

    for sub in _own_walk(node):
        if isinstance(sub, (ast.Import, ast.ImportFrom)):
            findings.append(Finding(
                "RNB-H002", rel, sub.lineno, qual,
                "import inside a per-request hot path — hoist to the "
                "module top or use rnb_tpu.utils.lazy_jax"))
        elif isinstance(sub, ast.Call):
            f = sub.func
            if isinstance(f, ast.Attribute) and f.attr == "device_put" \
                    and in_loop(sub.lineno):
                findings.append(Finding(
                    "RNB-H003", rel, sub.lineno, qual,
                    "device_put inside a loop on a hot path — per-item "
                    "transfers serialize; batch first, transfer once"))
            kind = _host_sync_kind(sub)
            if kind is not None:
                findings.append(Finding(
                    "RNB-H006", rel, sub.lineno, qual,
                    "%s on a hot path stalls the executor thread — fix "
                    "it, or baseline it with the justification"
                    % kind))
            alloc = _bucket_alloc_kind(sub)
            if alloc is not None:
                findings.append(Finding(
                    "RNB-H007", rel, sub.lineno, qual,
                    "%s on a per-emission loader path — decode into a "
                    "staging slot (rnb_tpu.staging) instead, or "
                    "baseline the copy fallback with its justification"
                    % alloc))
            if os.path.basename(rel) not in _H010_EXEMPT_BASENAMES:
                dev_alloc = _device_alloc_kind(sub)
                if dev_alloc is not None:
                    findings.append(Finding(
                        "RNB-H010", rel, sub.lineno, qual,
                        "%s on a hot path — a fresh pool-shaped device "
                        "array per emission fragments HBM; draw from "
                        "the page allocator (rnb_tpu.pager) or a "
                        "stage-init preallocation, or baseline the "
                        "deliberate fallback with its justification"
                        % dev_alloc))


#: attribute names whose NO-ARGUMENT call blocks until someone else
#: acts — with no timeout, forever (dict.get and Queue.get(key-ish)
#: take positional args, so zero-arg calls are the queue/event/lock/
#: future shapes)
_H009_BLOCKING_ATTRS = {"get", "wait", "acquire", "result"}


def _unbounded_wait_kind(node: ast.Call) -> Optional[str]:
    """Classify one call as an unbounded blocking wait, or None."""
    f = node.func
    if not isinstance(f, ast.Attribute) \
            or f.attr not in _H009_BLOCKING_ATTRS:
        return None
    if node.args:
        return None  # positional args: dict.get(key), pool.wait(t)
    if any(kw.arg == "timeout" for kw in node.keywords):
        return None
    return ".%s()" % f.attr


#: socket verbs whose blocking is bounded only by the SOCKET's
#: configured timeout — unlike queue/event waits there is no per-call
#: ``timeout=`` to demand, so the rule instead demands VISIBLE timeout
#: discipline in the enclosing function: a ``settimeout(...)`` call
#: (configuring the socket before/around the blocking verb) or a
#: ``gettimeout()`` consult (guarding against an unconfigured one,
#: the rnb_tpu.ops.wire.recv_exact idiom)
_H009_SOCKET_ATTRS = {"recv", "recv_into", "accept", "connect"}

#: the in-function evidence that a socket's blocking is bounded
_H009_SOCKET_MARKERS = {"settimeout", "gettimeout"}


def _socket_wait_kind(node: ast.Call) -> Optional[str]:
    """Classify one call as a timeout-governed socket verb, or None."""
    f = node.func
    if not isinstance(f, ast.Attribute) \
            or f.attr not in _H009_SOCKET_ATTRS:
        return None
    return ".%s()" % f.attr


def _lint_unbounded_waits(rel: str, index: _ModuleIndex,
                          findings: List[Finding],
                          hot: Set[str]) -> None:
    """RNB-H009 over the hot set plus every ``wait`` method — the
    blocking leaf hot paths call through cross-object (the intra-
    module call graph cannot follow ``handle.wait()``), so the leaves
    are linted under their own anchors.

    Socket verbs (recv/recv_into/accept/connect) are linted over
    EVERY function for the same leaf reason — receiver loops are
    thread targets the hot-root graph cannot reach — and their
    compliance evidence is per-function: the socket's timeout cannot
    ride the call, so the function that blocks must be the one seen
    configuring (``settimeout``) or guarding (``gettimeout``) it.
    """
    scope = set(hot)
    for qual in index.functions:
        name = qual.rsplit(".", 1)[-1]
        if name == "wait":
            scope.add(qual)
    for qual in sorted(scope):
        node = index.functions.get(qual)
        if node is None:
            continue
        for sub in _own_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            kind = _unbounded_wait_kind(sub)
            if kind is not None:
                findings.append(Finding(
                    "RNB-H009", rel, sub.lineno, qual,
                    "%s without a timeout on a hot/blocking path — a "
                    "dead counterpart hangs this thread forever; "
                    "bound the wait and re-check liveness each lap, "
                    "or baseline it with the justification" % kind))
    for qual in sorted(index.functions):
        node = index.functions[qual]
        bounded = any(
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr in _H009_SOCKET_MARKERS
            for sub in _own_walk(node))
        if bounded:
            continue
        for sub in _own_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            kind = _socket_wait_kind(sub)
            if kind is not None:
                findings.append(Finding(
                    "RNB-H009", rel, sub.lineno, qual,
                    "socket%s with no configured timeout in sight — "
                    "a silently dead peer blocks this thread forever "
                    "instead of classifying as net_timeout; settimeout "
                    "the socket (or gettimeout-guard it) in this "
                    "function, or baseline it with the justification"
                    % kind))


def _lint_fault_determinism(rel: str, index: _ModuleIndex,
                            findings: List[Finding]) -> None:
    is_faults_module = os.path.basename(rel) == "faults.py"
    for qual, node in index.functions.items():
        cls = qual.rsplit(".", 1)[0] if "." in qual else ""
        if not (is_faults_module or "FaultPlan" in cls
                or "fault_plan" in node.name):
            continue
        for sub in _own_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            bad = None
            if isinstance(f, ast.Attribute):
                if f.attr == "time" and isinstance(f.value, ast.Name) \
                        and f.value.id == "time":
                    bad = "time.time()"
                elif isinstance(f.value, ast.Name) \
                        and f.value.id == "random":
                    bad = "random.%s()" % f.attr
                elif isinstance(f.value, ast.Attribute) \
                        and f.value.attr == "random" \
                        and isinstance(f.value.value, ast.Name) \
                        and f.value.value.id in _NP_NAMES:
                    bad = "np.random.%s()" % f.attr
                elif f.attr in ("now", "utcnow") \
                        and _attr_chain_has(f, {"datetime"}):
                    bad = "datetime.%s()" % f.attr
            if bad is not None:
                findings.append(Finding(
                    "RNB-H004", rel, sub.lineno, qual,
                    "%s in deterministic fault-injection code — "
                    "schedules must be reproducible (use seeded, "
                    "stateless draws like faults._hash_draw)" % bad))


#: host-materialization calls RNB-H008 rejects on device-resident
#: handoff paths (attribute names; np-receiver checked for asarray/
#: array)
_H008_NP_CALLS = {"asarray", "array"}
_H008_ATTR_CALLS = {"device_get", "copy_to_host_async", "tolist"}


def _lint_handoff_device_paths(rel: str, index: _ModuleIndex,
                               findings: List[Finding]) -> None:
    """RNB-H008: no host materialization inside a device-resident
    handoff path. Scope: methods of ``*Handoff*`` classes and
    module-level functions of ``handoff*.py`` modules; a ``host``
    component in the function name marks the designated host-mode
    path and exempts it (that arm exists to bounce, measurably)."""
    is_handoff_module = os.path.basename(rel).startswith("handoff")
    for qual, node in index.functions.items():
        cls, _, meth = qual.rpartition(".")
        name = meth or qual
        in_scope = "Handoff" in cls or (is_handoff_module and not cls)
        if not in_scope or "host" in name.lower():
            continue
        for sub in _own_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            bad = None
            if isinstance(f, ast.Attribute):
                if f.attr in _H008_ATTR_CALLS:
                    bad = ".%s()" % f.attr
                elif f.attr in _H008_NP_CALLS \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id in _NP_NAMES:
                    bad = "np.%s()" % f.attr
            if bad is not None:
                findings.append(Finding(
                    "RNB-H008", rel, sub.lineno, qual,
                    "%s on a device-resident handoff path — the edge "
                    "contract promises zero host-hop bytes; move the "
                    "call into the '*host*'-named host-mode path or "
                    "fix it" % bad))


def _lint_shed_ordering(rel: str, index: _ModuleIndex,
                        findings: List[Finding]) -> None:
    for qual, node in index.functions.items():
        write_line = shed_line = None
        for sub in _own_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if isinstance(f, ast.Attribute) and f.attr == "write" \
                    and _attr_chain_has(f.value, {"output_ring"}):
                if write_line is None or sub.lineno < write_line:
                    write_line = sub.lineno
            if isinstance(f, ast.Name) and f.id == "_shed_item":
                if shed_line is None or sub.lineno < shed_line:
                    shed_line = sub.lineno
        if write_line is not None and shed_line is not None \
                and write_line < shed_line:
            findings.append(Finding(
                "RNB-H005", rel, write_line, qual,
                "ring-slot write at line %d precedes the shed decision "
                "at line %d — a written-but-never-signalled slot "
                "deadlocks the producer on wrap-around; decide shed "
                "first" % (write_line, shed_line)))


def check_file(path: str, root: str = ".") -> List[Finding]:
    rel = os.path.relpath(path, root).replace(os.sep, "/")
    try:
        tree = parse_py(path)
    except SyntaxError as e:
        return [Finding("RNB-H000", rel, e.lineno or 0, "parse",
                        "file does not parse: %s" % e)]
    index = _ModuleIndex()
    index.visit(tree)
    findings: List[Finding] = []

    def is_direct_method(qual: str) -> bool:
        # "Class.method" (exactly one dot, class known): methods are
        # never handed to jax.jit by bare name — a same-named method
        # elsewhere in the module must not be linted as a jit body
        head, _, tail = qual.partition(".")
        return bool(tail) and "." not in tail \
            and head in index.class_methods

    jit_quals = {q for n in index.jit_names
                 for q in index.by_name.get(n, ())
                 if not is_direct_method(q)}
    for qual in sorted(jit_quals):
        _lint_jit_body(rel, qual, index.functions[qual], findings)

    hot = _hot_set(index, rel)
    for qual in sorted(hot - jit_quals):
        _lint_hot_body(rel, qual, index.functions[qual], findings)

    _lint_unbounded_waits(rel, index, findings, hot)
    _lint_fault_determinism(rel, index, findings)
    _lint_shed_ordering(rel, index, findings)
    _lint_handoff_device_paths(rel, index, findings)
    return findings


def check_package(package_dir: str, root: str = ".") -> List[Finding]:
    findings: List[Finding] = []
    for path in package_py_files(package_dir):
        findings.extend(check_file(path, root))
    return findings
