"""``moe_ms_per_step`` (layer: parallel): device milliseconds a step
spends in the expert layers of ``parallel/moe.py``, forward and backward,
recompute included: the scopes ``moe_route`` (router, softmax, top-k),
``moe_dispatch`` (counting and laying out the assignments, gathering the
rows), ``moe_experts`` (the grouped products), ``moe_combine`` (weighting
and adding back) and ``moe_shared`` (the shared expert). ``None`` for a
program without those names."""

from benchmark.harness import scopes

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared")


def read(context):
    return scopes.per_step_ms(context, SCOPES)
