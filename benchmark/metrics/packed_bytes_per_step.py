"""``packed_bytes_per_step`` (layer: frontend): a count, not a speed:
bytes a step copies into flat buffers before it can use them. From the
compiled step's HLO text, the result bytes of the program's
``concatenate`` calls under ``hvd_pack`` (``op_name`` ends in
``concatenate`` below a ``hvd_pack``). The TPU compiler keeps a large
one as a ``concatenate`` and builds a small one from a chain of
dynamic-update-slice fusions that write one buffer in place, as many of
which keep the call's ``op_name`` as the compiler sees fit; so the
reader goes by the name and not by the opcode, counts a call (one
``op_name``, one ``stack_frame_id``, one result shape) once, and leaves
the instructions inside a fused computation to their fusion. It repeats
exactly. Divided by ``wire_bytes_per_step`` it is the share of the
exchange that pays for a copy; on one chip it is the fused update's own
small buffers. 0 for a step that holds no such name, ``None`` for a
scan-fused step, whose HLO holds an unrolled body and not a step."""

import re

from benchmark.harness.xtrace import SHAPE_RE, shape_bytes

_PACKED_RE = re.compile(
    r'op_name="([^"]*\bhvd_pack/(?:[^"]*/)?concatenate)"'
    r"(?: stack_frame_id=(\d+))?")
_FUSED_RE = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
_COMPUTATION_RE = re.compile(r"(?:ENTRY\s+)?%?([\w.\-]+)\s+\(")


def packed_bytes(hlo_text: str) -> int:
    lines = hlo_text.splitlines()
    fused = {m.group(1) for m in map(_FUSED_RE.search, lines) if m}
    calls, counted = {}, True
    for line in lines:
        if not line.startswith(" "):  # a computation opens or closes
            m = _COMPUTATION_RE.match(line)
            counted = not (m and m.group(1) in fused)
            continue
        call = counted and _PACKED_RE.search(line)
        result = call and SHAPE_RE.match(line.partition(" = ")[2])
        if result:
            dtype, dims, _ = result.groups()
            calls[(*call.groups(), dtype, dims)] = shape_bytes(dtype, dims)
    return sum(calls.values())


def read(context):
    if context.system.steps_per_call != 1:
        return None
    return float(packed_bytes(context.system.hlo_text))
