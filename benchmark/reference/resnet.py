"""Plain reference of family ``resnet``: the bottleneck ResNet's forward
pass and loss in ``jax.numpy`` / ``jax.lax``, float32, reading the
parameter tree the system's model makes and importing nothing of the
system.

Per ``horovod_tpu/models/resnet.py``: 7x7/2 stem -> BN -> relu -> 3x3/2
max pool -> bottleneck blocks (1x1 -> 3x3 strided -> 1x1 x4, projection
shortcut where the shape changes, relu after the join) -> global mean ->
classifier; all convolutions 'SAME' without bias. Batch norm is in
training mode: it normalises with the statistics of the batch it is
given (biased variance, eps 1e-5), so the loss does not depend on the
running statistics and the reference does not carry them. Each block
is under ``jax.checkpoint``, and the blocks of a stage after its first
(which all have one shape) run as a ``lax.scan`` over their stacked
parameters: the arithmetic is the same, a chip's batch in float32 then
fits one chip, and the program is half the size (it has to stay in a
compile cache that the machine caps).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv(x, p, stride=1):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps=1e-5):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]), p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"], stride),
                                p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"], stride), p["norm_proj"])
    return jax.nn.relu(x + y)


def loss(params, extra, batch, config):
    """Mean cross-entropy of one micro-batch (one chip's images: batch
    norm is per chip in the system), float32."""
    images, labels = batch
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = images.astype(jnp.float32)
    x = jax.nn.relu(_batch_norm(_conv(x, params["conv_init"], 2),
                                params["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    block = jax.checkpoint(_bottleneck, static_argnums=2)
    first = 0
    for i, count in enumerate(config["stage_sizes"]):
        x = block(x, params[f"BottleneckBlock_{first}"], 2 if i > 0 else 1)
        rest = [params[f"BottleneckBlock_{first + j}"]
                for j in range(1, count)]
        if rest:
            stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *rest)
            x, _ = jax.lax.scan(lambda x, p: (block(x, p, 1), None),
                                x, stacked)
        first += count
    logits = x.mean((1, 2)) @ params["Dense_0"]["kernel"] \
        + params["Dense_0"]["bias"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
