"""Device time under a *path* of named scopes, and the roofline share of
a mechanism of a family without experts.

``benchmarks/scopes.py`` sums device time by the five top scopes it
knows and gives a roofline share only to a run that counted expert
assignments. A family with other scopes (``mlp``), with a scope inside
a scope (``attn/select``) and with no experts reads through this file:
the same join (the stage's ``hlo-scopes.json``: ``"<instruction>
<result shape>" -> op_name``; an ``XLA Ops`` event opens with the same
two words), the same self times, summed over the instructions whose
``op_name`` holds a given path. A run with no such table, no device
plane, or no instruction under the path gives None: a reader then
reports nothing rather than a guess (the parent of the PR that brought
a scope has none of it).
"""

from __future__ import annotations

import functools
import json
import os
import re
from typing import Dict, Optional

from benchmarks import scopes, xplane

_CACHE: Dict[str, Optional[Dict[str, float]]] = {}


def _by_instruction(facts) -> Optional[Dict[str, float]]:
    """{"<instruction> <result shape>": seconds of self time} over the
    device planes of the run's trace."""
    if facts.trace is None:
        return None
    path = facts.trace.path
    if path not in _CACHE:
        from jax.profiler import ProfileData
        out: Dict[str, float] = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
                continue
            for line in plane.lines:
                if line.name != xplane.OPS_LINE:
                    continue
                intervals = []
                for e in line.events:
                    head = scopes._INSTRUCTION.match(e.name)
                    intervals.append((
                        float(e.start_ns),
                        float(e.start_ns) + float(e.duration_ns),
                        "%s %s" % head.groups() if head else ""))
                for key, ns in xplane.self_times(intervals).items():
                    out[key] = out.get(key, 0.0) + ns / 1e9
        _CACHE[path] = out or None
    return _CACHE[path]


@functools.lru_cache(maxsize=None)
def _op_names(log_dir: str) -> Optional[Dict[str, str]]:
    """The run's ``hlo-scopes.json``, or None."""
    table = os.path.join(log_dir, "hlo-scopes.json")
    if not os.path.exists(table):
        return None
    with open(table) as f:
        return json.load(f)


def seconds_under(facts, path: str) -> Optional[float]:
    """Device seconds of the instructions traced under the scope path
    ``path`` (``"mlp"``, ``"attn/select"``)."""
    spent = _by_instruction(facts)
    op_names = _op_names(facts.result.log_dir)
    if spent is None or op_names is None:
        return None
    under = re.compile(r"/%s/" % re.escape(path))
    found = [spent[key] for key, op_name in op_names.items()
             if key in spent and under.search(op_name + "/")]
    return sum(found) if found else None


def device_seconds(facts) -> Optional[float]:
    """Device seconds of every operation of the trace."""
    spent = _by_instruction(facts)
    return sum(spent.values()) if spent else None


def traced_dispatches(facts) -> Optional[float]:
    """Dispatches of the traced window, as the accepted roofline shares
    count them: the valid tokens of the requests that finished inside it
    over the run's mean valid tokens a dispatch."""
    tokens = scopes.traced_tokens(facts)
    result = facts.result
    if not tokens or not result.tokens_valid or not result.pad_emissions:
        return None
    return tokens * result.pad_emissions / result.tokens_valid


def roofline_pct(facts, mechanism: str, path: Optional[str] = None,
                 kernel: Optional[str] = None) -> Optional[float]:
    """The larger of operations / bf16 peak and bytes / HBM bandwidth of
    one mechanism (the family file's ``mechanism_work(config, mechanism,
    tokens, dispatches)``), over the device time under the scope
    ``path`` or of the custom calls named ``kernel``. Counted over the
    valid tokens of the requests that finished inside the traced window:
    padding, and whatever else the device did there, can only lower the
    share."""
    tokens = scopes.traced_tokens(facts)
    dispatches = traced_dispatches(facts)
    work = getattr(facts.family, "mechanism_work", None)
    if not tokens or not dispatches or work is None \
            or facts.peak_flops_per_s is None:
        return None
    spent = scopes.kernel_seconds(facts, kernel) if kernel is not None \
        else seconds_under(facts, path)
    if not spent:
        return None
    from benchmarks import peaks
    ops, nbytes = work(facts.config, mechanism, tokens, dispatches)
    least_s = max(ops / facts.peak_flops_per_s,
                  nbytes / peaks.peak_for(facts.device_kind)[
                      "hbm_bytes_per_s"])
    return 100.0 * least_s / spent
