"""``moe_experts_ms_per_step`` (layer: parallel): device milliseconds a
step spends in the grouped products over the experts held (scope
``moe_experts``: the three products of each block forward, their
recompute and the five of the backward pass, with the weight gradients'
accumulation). ``None`` for a program without the name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("moe_experts",))
