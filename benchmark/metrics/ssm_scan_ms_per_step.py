"""``ssm_scan_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the state-space scan of ``ops/ssd.py`` (scope ``ssm_scan``):
the chunks' decay and score tiles, the products inside the chunks, the
chunks' end states, the carry between chunks and the entering states'
part of the output, forward, recomputed and backward. ``None`` for a
program without the name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("ssm_scan",))
