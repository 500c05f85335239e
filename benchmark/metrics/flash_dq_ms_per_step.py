"""``flash_dq_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the flash-attention backward kernel for dq alone (pallas name
``flash_dq_bwd_bhsd``; in a program whose pallas calls have no names, the
``_bwd_bhsd`` call that returns one array). It runs only where a layer
fell back to the two-kernel backward, so this is the fused backward's
engagement counter: 0.0 = every layer took the fused kernel, whose time
is under ``flash_dkv_ms_per_step``. ``None`` where no flash kernel ran."""

from benchmark.harness import phases


def read(context):
    return phases.flash_ms(context, "flash_dq")
