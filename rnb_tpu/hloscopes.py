"""The table that joins a compiled program's instructions to the
``jax.named_scope``s they were traced under.

A stage wraps each mechanism of its network in a named scope, and XLA
keeps the scope's path in every instruction's ``op_name``. The profiler
names a device operation by its instruction alone, so a final stage
writes the join itself: ``hlo-scopes.json`` beside the run's logs,
``{"<instruction> <result shape>": op_name}`` over the programs it
serves with, one a row bucket (the same instruction name recurs in each
bucket's program with another shape). The text is the executable's that
the stage calls: no program is compiled for the table's sake. The
trace's readers (``benchmarks/scopes.py``, ``subscopes.py``,
``stages.py``) look an operation's event up there.

Both families' final stages call this: ``models/token_stages.py``
(``PackedPrefill``) and ``models/r2p1d/model.py`` (``R2P1DRunner``).
"""

from __future__ import annotations

import json
import os
import re
import threading

TABLE_FILE = "hlo-scopes.json"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_of_hlo(text: str) -> dict:
    """{"<instruction> <result shape>": op_name} for every instruction
    of a compiled module's text that carries an ``op_name``: the path
    of ``jax.named_scope``s it was traced under. The profiler names a
    device operation by its instruction and not by its scope; this is
    the table that joins the two (the same instruction name recurs in
    each bucket's program with another shape)."""
    out = {}
    open_head = None
    for line in text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            open_head = "%s %s" % head.groups()
        # a Pallas kernel's attributes hold line breaks: its op_name
        # follows on a later line of the same instruction
        found = _OP_NAME.search(line)
        if found and open_head is not None:
            out[open_head] = found.group(1)
            open_head = None
    return out


def write_table(log_dir: str, table: dict) -> None:
    """``table`` as ``<log_dir>/hlo-scopes.json``, whole or not at all:
    replicas of one stage write the same table to the same name, and a
    reader never sees one of them half-way."""
    path = os.path.join(log_dir, TABLE_FILE)
    partial = "%s.%d.%d" % (path, os.getpid(), threading.get_ident())
    try:
        with open(partial, "w") as f:
            json.dump(table, f)
        os.replace(partial, path)
    finally:
        if os.path.exists(partial):
            os.unlink(partial)
