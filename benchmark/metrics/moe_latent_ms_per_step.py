"""``moe_latent_ms_per_step`` (layer: parallel): device milliseconds a
step spends in the two projections round the routed experts of a latent
expert block (scope ``moe_latent`` of ``models/hybrid.py``: hidden to
latent before the experts, latent to hidden after them), forward and
backward, recompute included. ``None`` for a program without the name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("moe_latent",))
