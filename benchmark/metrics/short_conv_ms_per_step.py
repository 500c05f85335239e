"""``short_conv_ms_per_step`` (layer: models): device milliseconds a step
spends in the gated short convolutions of ``models/decoder.py`` (scope
``short_conv``): what lies between each operator's two projections, the
product ``B * x``, the causal sum of the taps and the product with ``C``,
forward, recomputed and backward. The projections are not under the
name. ``None`` for a program without it."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("short_conv",))
