"""Family ``resnet``: ``horovod_tpu.models.ResNet`` with bottleneck
blocks, trained on softmax cross-entropy, as ``chip_smoke.resnet_phase``
and ``bench.py`` build it. Batch-norm statistics are per chip (the
model's data-parallel semantics), and they are the family's extra state.

An item is an image.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ITEM = "images"


def items_per_sample(config: dict, traffic: dict) -> int:
    return 1


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    return ResNet(stage_sizes=list(config["stage_sizes"]),
                  block_cls=BottleneckBlock,
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"],
                  dtype=jnp.dtype(config["compute_dtype"]))


def _image_shape(n, traffic):
    size = int(traffic["image_size"])
    return (n, size, size, 3)


def init_variables(model, key, config: dict, traffic: dict):
    """(params, batch statistics) from ``key``."""
    variables = model.init(
        key, jnp.zeros(_image_shape(1, traffic), model.dtype), False)
    return variables["params"], variables["batch_stats"]


def make_batch(key, n_samples: int, config: dict, traffic: dict):
    k_img, k_lab = jax.random.split(key)
    images = jax.random.uniform(
        k_img, _image_shape(n_samples, traffic), jnp.float32
    ).astype(jnp.dtype(config["compute_dtype"]))
    labels = jax.random.randint(k_lab, (n_samples,), 0,
                                config["num_classes"], jnp.int32)
    return images, labels


def loss_fn(model, params, extra, batch):
    """(loss, new batch statistics) of one per-chip batch."""
    images, labels = batch
    logits, mutated = model.apply(
        {"params": params, "batch_stats": extra}, images, True,
        mutable=["batch_stats"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
    return loss, mutated["batch_stats"]


def conv_shapes(config: dict, traffic: dict):
    """Every convolution of the network as (output height, output width,
    kernel size, channels in, channels out), in forward order: the stem,
    then per bottleneck block 1x1 -> 3x3 (strided) -> 1x1 (x4) and the
    1x1 projection where the shape changes. 'SAME' padding: a stride-2
    layer halves the size, rounding up."""
    size = int(traffic["image_size"])
    f = config["num_filters"]
    half = lambda n: -(-n // 2)  # noqa: E731
    size = half(size)
    convs = [(size, size, 7, 3, f)]
    size = half(size)  # 3x3/2 max pool
    c_in = f
    for i, blocks in enumerate(config["stage_sizes"]):
        width = f * 2 ** i
        for j in range(blocks):
            stride2 = i > 0 and j == 0
            out = half(size) if stride2 else size
            convs.append((size, size, 1, c_in, width))
            convs.append((out, out, 3, width, width))
            convs.append((out, out, 1, width, 4 * width))
            if c_in != 4 * width or stride2:
                convs.append((out, out, 1, c_in, 4 * width))
            c_in, size = 4 * width, out
    return convs


def _conv_flops(config: dict, traffic: dict):
    """Forward FLOPs of each convolution for one image: a multiply and an
    add per kernel tap and output element. Taps that fall on the padding
    count, as in the customary 4.1 GMAC figure for ResNet-50."""
    return [2.0 * oh * ow * k * k * ci * co
            for oh, ow, k, ci, co in conv_shapes(config, traffic)]


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Convolution and classifier FLOPs of the forward pass for one
    image, from shapes: no batch-norm or pooling arithmetic."""
    width = conv_shapes(config, traffic)[-1][4]
    return (sum(_conv_flops(config, traffic))
            + 2.0 * width * config["num_classes"])


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one image: no
    optimizer, no recompute. Backward is the input gradient plus the
    weight gradient, each as large as forward; the stem needs no input
    gradient."""
    return (3.0 * forward_flops_per_item(config, traffic)
            - _conv_flops(config, traffic)[0])
