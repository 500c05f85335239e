"""``flash_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the three pallas flash-attention kernels, mean over the
devices. Zero in a cell whose traffic uses stock attention.

``PATTERNS`` are the kernels' names as the trace prints them on
``XLA Ops`` (read by hand, PR 22): a pallas call without a ``name=`` is a
``custom-call`` named after the jitted function round it, so the forward
kernel is ``%_fwd_bhsd.N`` and the dq and dkv kernels are both
``%_bwd_bhsd.N`` (12 and 24 events a step for 12 layers). Telling the two
backward kernels apart needs ``name=`` on the pallas calls of
``ops/flash_attention.py``: the tracing issue."""

from benchmark.harness import xtrace

PATTERNS = ("_fwd_bhsd", "_bwd_bhsd")


def kernel_seconds(context):
    """Per device, the seconds of the traced window in the kernels."""
    pattern = "|".join(PATTERNS)
    return [xtrace.op_seconds(d, pattern, context.window)
            for d in context.capture.devices]


def read(context):
    return context.per_step_ms(kernel_seconds(context))
