#!/usr/bin/env python
"""ResNet-50 ImageNet-style training through the Trainer (reference:
examples/keras_imagenet_resnet50.py): warmup over 5 epochs, 30/60/80
stepwise decay, checkpoint/resume, metric averaging. Synthetic data by
default (no egress).

Run: PYTHONPATH=. python examples/keras_imagenet_resnet50.py --epochs 1 \
         --steps 4 --image-size 64
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import optax

import horovod_tpu as hvd
import horovod_tpu.keras as hvd_keras
from horovod_tpu.keras.callbacks import (
    BroadcastGlobalVariablesCallback,
    LearningRateScheduleCallback,
    LearningRateWarmupCallback,
    MetricAverageCallback,
)
from horovod_tpu.models import ResNet50

from common import synthetic_imagenet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=90)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--base-lr", type=float, default=0.0125)
    ap.add_argument("--warmup-epochs", type=int, default=5)
    ap.add_argument("--steps", type=int, default=8,
                    help="train steps per epoch (synthetic)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--checkpoint-dir", default="")
    args = ap.parse_args()

    hvd.init()
    n = args.steps * args.batch_size * hvd.local_size()
    x, y = synthetic_imagenet(n=n, size=args.image_size)
    import jax.numpy as jnp

    # Feed bf16: the model computes in bf16, and halving the host->device
    # bytes matters wherever the feed link is the bottleneck.
    x = x.astype(jnp.bfloat16)

    trainer = hvd_keras.Trainer(
        ResNet50(),
        # Reference: base_lr scaled by size, SGD momentum 0.9
        # (keras_imagenet_resnet50.py:117-120).
        optax.sgd(args.base_lr * hvd.size(), momentum=0.9))

    callbacks = [
        BroadcastGlobalVariablesCallback(0),
        MetricAverageCallback(),
        LearningRateWarmupCallback(warmup_epochs=args.warmup_epochs,
                                   verbose=1),
        # Reference decay schedule: 30/60/80 (keras_imagenet_resnet50.py:
        # 124-127).
        LearningRateScheduleCallback(1.0, start_epoch=args.warmup_epochs,
                                     end_epoch=30),
        LearningRateScheduleCallback(1e-1, start_epoch=30, end_epoch=60),
        LearningRateScheduleCallback(1e-2, start_epoch=60, end_epoch=80),
        LearningRateScheduleCallback(1e-3, start_epoch=80),
    ]
    import time

    hist = trainer.fit(x, y, batch_size=args.batch_size, epochs=1,
                       callbacks=callbacks, verbose=1)  # compile warmup
    t0 = time.perf_counter()
    hist = trainer.fit(x, y, batch_size=args.batch_size, epochs=args.epochs,
                       callbacks=callbacks, verbose=1)
    dt = time.perf_counter() - t0
    images = args.steps * args.batch_size * args.epochs
    print(f"images/sec/chip: {images / dt:.1f} "
          f"(keras trainer path, {hvd.size()} chip(s))")
    if args.checkpoint_dir:
        trainer.save(args.checkpoint_dir)
    assert "loss" in hist


if __name__ == "__main__":
    main()
