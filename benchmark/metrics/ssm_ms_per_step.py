"""``ssm_ms_per_step`` (layer: models): device milliseconds a step spends
in the state-space mixers of ``models/hybrid.py`` outside their two
projections, forward and backward, recompute included: the scopes
``ssm_conv`` (the causal depthwise convolution and its SiLU),
``ssm_scan`` (everything of ``ops/ssd.py``: the chunked scan and its
backward pass) and ``ssm_norm`` (the gated group norm). ``None`` for a
program without those names."""

from benchmark.harness import scopes

SCOPES = ("ssm_conv", "ssm_scan", "ssm_norm")


def read(context):
    return scopes.per_step_ms(context, SCOPES)
