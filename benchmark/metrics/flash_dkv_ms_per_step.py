"""``flash_dkv_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the fused flash-attention backward kernel, which makes dq, dk
and dv in the dK/dV grid's one pass (pallas name
``fused_flash_dkv_bwd_bhsd``), plus that of any dK/dV kernel of the
two-kernel backward that a layer fell back to (``flash_dkv_bwd_bhsd``;
in a program whose pallas calls have no names, the ``_bwd_bhsd`` call
that returns a pair). ``None`` where no flash kernel ran."""

from benchmark.harness import phases


def read(context):
    return phases.flash_ms(context, "flash_dkv")
