"""``wire_bytes_per_step`` (layer: compiled collectives): a count, not a
speed: operand bytes of the collective instructions in the compiled
step's HLO. It repeats exactly; 0 on one chip. ``None`` for a scan-fused
step, whose HLO holds an unrolled body and not a step."""

from benchmark.harness import hlo


def read(context):
    if context.system.steps_per_call != 1:
        return None
    return float(hlo.wire_bytes(context.system.hlo_text))
