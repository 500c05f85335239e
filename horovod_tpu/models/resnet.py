"""ResNet v1.5 family in flax, laid out for the MXU.

The reference benchmarks ResNet-50/101 from ``tf.keras.applications`` /
``tf_cnn_benchmarks`` (reference: examples/tensorflow_synthetic_benchmark.py:
44-45, docs/benchmarks.md:33-38). This is a native implementation, not a
port: NHWC layout (XLA's preferred TPU conv layout), bfloat16 compute with
float32 parameters and batch-norm statistics, and static shapes throughout so
XLA can tile convs onto the systolic array.

v1.5 = stride-2 in the 3x3 of the bottleneck (not the 1x1), the variant every
modern benchmark reports.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3(stride) -> 1x1(x4) with projection shortcut."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.act(self.norm()(self.conv(self.filters, (1, 1))(x)))
        y = self.act(self.norm()(
            self.conv(self.filters, (3, 3), self.strides)(y)))
        # Zero-init of the last BN scale: each block starts as identity,
        # which is what lets large-batch distributed training (the regime
        # this framework exists for) hold accuracy at high learning rates.
        y = self.norm(scale_init=nn.initializers.zeros)(
            self.conv(self.filters * 4, (1, 1))(y))
        if residual.shape != y.shape:
            residual = self.norm(name="norm_proj")(
                self.conv(self.filters * 4, (1, 1), self.strides,
                          name="conv_proj")(residual))
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 (ResNet-18/34)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.act(self.norm()(
            self.conv(self.filters, (3, 3), self.strides)(x)))
        y = self.norm(scale_init=nn.initializers.zeros)(
            self.conv(self.filters, (3, 3))(y))
        if residual.shape != y.shape:
            residual = self.norm(name="norm_proj")(
                self.conv(self.filters, (1, 1), self.strides,
                          name="conv_proj")(residual))
        return self.act(residual + y)


def space_to_depth_2x2(x):
    """NHWC [N,H,W,C] → [N,H/2,W/2,4C]: each output channel block is one
    subpixel of the 2x2 macro-pixel (row-major: (row_sub, col_sub, c))."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // 2, w // 2, 4 * c)


def conv7_kernel_to_s2d(k7):
    """Exact reparameterization of a 7x7/s2 'SAME' conv kernel [7,7,C,O]
    as the equivalent 4x4/s1 kernel [4,4,4C,O] over space-to-depth input
    (zero-pad 7→8 taps, fold each tap's parity into the subpixel
    channels). Used by the equivalence test; training uses the 4x4 form
    directly."""
    c, o = k7.shape[2], k7.shape[3]
    k8 = jnp.pad(k7, ((0, 1), (0, 1), (0, 0), (0, 0)))
    # [8,8,C,O] -> [4,2,4,2,C,O] -> [4,4,2,2,C,O] -> [4,4,4C,O]
    k = k8.reshape(4, 2, 4, 2, c, o).transpose(0, 2, 1, 3, 4, 5)
    return k.reshape(4, 4, 4 * c, o)


class ResNet(nn.Module):
    """ResNet over NHWC images.

    Batch-norm statistics are per-replica (each chip normalizes its local
    batch), matching the reference's data-parallel semantics where BN state
    is never allreduced — only initially broadcast (reference:
    horovod/tensorflow/__init__.py:96-115).

    ``stem``: ``"conv7"`` is the classic 7x7/s2 convolution; contracting
    over only 3 input channels it wastes most of the MXU's 128 lanes.
    ``"space_to_depth"`` reshapes the image to [H/2, W/2, 12] and trains
    the mathematically equivalent 4x4/s1 kernel instead (exactness:
    :func:`conv7_kernel_to_s2d`; the standard TPU ResNet stem). Same
    function class, different parameterization — checkpoints are not
    interchangeable between stems.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu
    stem: str = "conv7"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       padding="SAME")
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        x = jnp.asarray(x, self.dtype)
        if self.stem not in ("conv7", "space_to_depth"):
            # Silent fallback would train a different parameterization
            # than the user asked for (checkpoints are not
            # interchangeable between stems).
            raise ValueError(f"unknown stem {self.stem!r}; expected "
                             "'conv7' or 'space_to_depth'")
        if self.stem == "space_to_depth":
            x = space_to_depth_2x2(x)
            # Pad (1,2): macro-row span of the 7x7/s2 taps (see
            # conv7_kernel_to_s2d) — NOT flax 'SAME', which would center
            # the 4x4 window differently and break equivalence.
            x = nn.Conv(self.num_filters, (4, 4), use_bias=False,
                        dtype=self.dtype, padding=((1, 2), (1, 2)),
                        name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        x = self.act(norm(name="bn_init")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    filters=self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm, act=self.act,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        # Logits in f32: the loss/softmax wants full precision.
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
