"""``lm_head_ms_per_step`` (layer: models): device milliseconds a step
spends in the flax module ``lm_head`` of ``models/transformer.py``,
forward and backward: the logits matmul, its two gradients (the weight
gradient with whatever update XLA fused into it) and the copies round
them. The loss over the logits is the benchmark family's code and is not
under this name."""

from benchmark.harness import phases


def read(context):
    return phases.per_step_ms(context, ("lm_head",))
