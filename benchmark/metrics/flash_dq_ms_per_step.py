"""``flash_dq_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the flash-attention backward kernel for dq (pallas name
``flash_dq_bwd_bhsd``; in a program whose pallas calls have no names, the
``_bwd_bhsd`` call that returns one array). ``None`` where no flash
kernel ran."""

from benchmark.harness import phases


def read(context):
    return phases.flash_ms(context, "flash_dq")
