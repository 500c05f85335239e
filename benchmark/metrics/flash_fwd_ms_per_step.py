"""``flash_fwd_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the flash-attention forward kernel (pallas name
``flash_fwd_bhsd``). With ``flash_dq_ms_per_step`` and
``flash_dkv_ms_per_step`` it sums to ``flash_ms_per_step``. ``None``
where no flash kernel ran."""

from benchmark.harness import phases


def read(context):
    return phases.flash_ms(context, "flash_fwd")
