"""Device milliseconds per round in which a chip ran a collective
operation (the opcodes in ``collective_ms.json``, with their ``-start``
and ``-done`` halves): per chip the union of those operations' intervals,
the largest over the chips.  Left out when no chip ran one."""
import re

import tracefile
from _common import data


def is_collective(text: str, opcodes) -> bool:
    """Whether the trace's operation text (``%name = type opcode(...)``)
    is one of ``opcodes``: by its instruction name or its opcode, never
    by an operand it reads."""
    name = tracefile.op_name(text)
    words = "|".join(re.escape(o) for o in opcodes)
    return bool(re.match(rf"({words})(-start|-done)?([.\-]|$)", name)
                or re.search(rf"\s({words})(-start|-done)?\(", text))


def read(ctx):
    ops = data("collective_ms")["opcodes"]
    per_chip = [sum(b - a for a, b in tracefile.union(
        (o[2], o[3]) for o in d["ops"] if is_collective(o[1], ops)))
        for d in ctx["trace"]["devices"].values()]
    if not any(per_chip):
        return None
    return max(per_chip) / 1e6 / ctx["rounds"]
