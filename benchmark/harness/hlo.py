"""Counts taken from the compiled step's HLO text. They repeat exactly
from run to run, so they are counts and never speeds."""

from __future__ import annotations

import re
from typing import List, Tuple

from benchmark.harness.xtrace import SHAPE_RE, shape_bytes

# One collective instruction: "%id = <shape> <op>(<operands>), attrs".
# "-done" halves of async pairs carry no payload of their own.
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>\([^=]*?\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\((?P<operands>[^)]*)\)(?P<attrs>.*)$")
# XLA numbers the elements of a tuple shape of more than five ("/*index=5*/");
# the "=" inside would end the shape group early.
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _bytes_in(text: str) -> int:
    return sum(shape_bytes(dt, dims)
               for dt, dims, _ in SHAPE_RE.findall(text))


def collectives(hlo_text: str) -> List[Tuple[str, int, int]]:
    """(op, operand bytes, largest replica group) for every collective
    instruction of the module. Operand bytes are those of the shapes
    printed with the operands; where the text prints none, the result's.
    A variadic instruction counts all its operands."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(_COMMENT_RE.sub("", line))
        if not m:
            continue
        nbytes = _bytes_in(m.group("operands")) or _bytes_in(m.group("shape"))
        out.append((m.group("op"), nbytes, _group_size(m.group("attrs"))))
    return out


def _group_size(attrs: str) -> int:
    """Size of the replica groups in either spelling:
    ``replica_groups={{0,1,2,3}}`` or the iota form ``[1,4]<=[4]``
    (groups x group size). 0 where the instruction names none."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{(\{[^=]*?\})\}", attrs)
    if m:
        return max(len(g.split(",")) for g in
                   re.findall(r"\{([\d,]+)\}", m.group(1)))
    return 0


def wire_bytes(hlo_text: str) -> int:
    return sum(nbytes for _, nbytes, _ in collectives(hlo_text))


def all_reduce_group(hlo_text: str) -> int:
    """The largest replica group any all-reduce of the module spans."""
    return max((g for op, _, g in collectives(hlo_text)
                if op == "all-reduce"), default=0)


def has_tpu_custom_call(hlo_text: str) -> bool:
    """Whether a Mosaic (pallas) kernel is in the compiled module."""
    return "tpu_custom_call" in hlo_text
