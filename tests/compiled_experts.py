"""What the two token families' tests read out of one expert block's
compiled program (``compiled.as_text()`` for a described v5e): the
pair buffers of ``rnb_tpu.ops.moe.held_experts`` by instruction."""

from __future__ import annotations

import re

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])\S* ([a-z\-]+)\(")


def instructions(text):
    """-> [(name, result shape, opcode, op_name, top-level, line)] of
    every instruction; top-level: it is the entry computation's own,
    one operation on the device, and not a line inside a fusion."""
    from rnb_tpu.hloscopes import scopes_of_hlo
    scopes = scopes_of_hlo(text)
    found, entry = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif line.startswith("}"):
            entry = False
        match = _INSTRUCTION.match(line)
        if match:
            name, shape, opcode = match.groups()
            found.append((name, shape, opcode,
                          scopes.get("%s %s" % (name, shape), ""), entry,
                          line[:200]))
    return found


def check_pair_buffers(text, tokens, k, hidden, stacks):
    """The grouped product reads the expert stacks where they lie (no
    instruction but a parameter has a stack's shape: no relayout in
    front of the kernel); nothing scatters a [tokens x k, hidden]
    float32 array; no array carries the k chosen experts on its
    sublane axis (``f32[tokens, k, hidden]``: the device pads k to 8
    and the reshape into it is a copy); and the second product's rows
    are written twice, by the kernel and by the gather back, and reach
    the sum through bitcasts alone. -> the result shapes of the
    ``%gmm`` kernels, each found under the scope ``experts``."""
    pairs = "f32[%d,%d]" % (tokens * k, hidden)
    by_choice = "f32[%d,%d,%d]" % (k, tokens, hidden)
    by_token = "f32[%d,%d,%d]" % (tokens, k, hidden)
    kernels, written = [], []
    for name, shape, opcode, op_name, top, line in instructions(text):
        if shape in stacks:
            assert opcode == "parameter", line
        if shape == pairs:
            assert not op_name.endswith("scatter"), line
        assert shape != by_token, line
        if top and shape in (pairs, by_choice) and opcode != "bitcast":
            written.append(opcode)      # a copy, a transpose: a third
        if re.fullmatch(r"%gmm(\.\d+)?", name):
            assert opcode == "custom-call" and "/experts/" in op_name, line
            kernels.append(shape)
    assert sorted(written) == ["custom-call", "fusion"], written
    return sorted(kernels)


def gather_in_sources(text, tokens, k, hidden):
    """-> for each gather into expert order of the whole stage program
    (a top-level fusion ``bf16[tokens x k, hidden]`` of a
    ``bf16[tokens, hidden]`` operand), whether the compiler's memory
    space assignment put the tokens' rows in its fast memory
    (``S(1)`` in the operand's layout) or left them in HBM."""
    entry = text[text.index("\nENTRY "):]
    layouts = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", entry,
                              re.M))
    gather = re.compile(
        r"^\s*%%[\w.\-]+ = bf16\[%d,%d\]\S* fusion\((%%[\w.\-]+)[,)]"
        % (tokens * k, hidden), re.M)
    return ["S(1)" in layouts[source] for source in gather.findall(entry)
            if layouts[source].startswith("bf16[%d,%d]" % (tokens, hidden))]
