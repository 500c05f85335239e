"""``flash_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the pallas flash-attention kernels, mean over the devices: the
forward and the fused backward (``flash_fwd_bhsd``,
``fused_flash_dkv_bwd_bhsd``) and, where a layer fell back to them, the
dq and dK/dV kernels of the two-kernel backward. Zero in a cell whose
traffic uses stock attention.

``PATTERNS`` match the kernels' names as the trace prints them on
``XLA Ops``, with or without ``name=`` on the pallas calls (without it a
call is a ``custom-call`` named after the jitted function round it:
``%_fwd_bhsd.N``, ``%_bwd_bhsd.N``; read by hand, PR 22). Every backward
kernel's name ends in ``_bwd_bhsd``, the fused one's too."""

from benchmark.harness import xtrace

PATTERNS = ("_fwd_bhsd", "_bwd_bhsd")


def kernel_seconds(context):
    """Per device, the seconds of the traced window in the kernels."""
    pattern = "|".join(PATTERNS)
    return [xtrace.op_seconds(d, pattern, context.window)
            for d in context.capture.devices]


def read(context):
    return context.per_step_ms(kernel_seconds(context))
