"""The plain optax transform a configuration's file names. The system
wraps it in ``DistributedOptimizer``; the reference uses it as it is."""

from __future__ import annotations


def make_optimizer(spec: dict):
    import optax

    kind = spec["name"]
    if kind == "sgd":
        return optax.sgd(spec["learning_rate"], momentum=spec["momentum"])
    if kind == "adamw":
        return optax.adamw(spec["learning_rate"],
                           weight_decay=spec["weight_decay"])
    raise ValueError(f"optimizer {kind!r}: want 'sgd' or 'adamw'")
