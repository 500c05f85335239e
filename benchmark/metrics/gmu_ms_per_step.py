"""``gmu_ms_per_step`` (layer: models): device milliseconds a step spends
in the gated memory units (scope ``gmu`` of ``models/sambay.py``: the
unit's two projections and the gate ``silu(in_proj(u)) * memory`` between
them, which the compiler fuses into the products), forward and backward,
recompute included. ``None`` for a program without the name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("gmu",))
