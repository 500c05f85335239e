"""``attn_diff_ms_per_step`` (layer: models): device milliseconds a step
spends in what differential attention does outside its kernels and its
projections (scope ``attn_diff`` of ``models/sambay.py``): pairing the
heads, joining a pair's two outputs, lambda, the difference of the two
maps' outputs and the RMSNorm over a pair, forward and backward,
recompute included. ``None`` for a program without the name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("attn_diff",))
