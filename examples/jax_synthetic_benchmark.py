#!/usr/bin/env python
"""Synthetic model benchmark — img/sec per chip, mean ± 1.96σ (reference:
examples/tensorflow_synthetic_benchmark.py). ResNet-50 by default; any
model in horovod_tpu.models via --model.

Random data, a ``DistributedOptimizer`` training step compiled by
``hvd.jax.jit``, one dispatch a step, and a device->host fetch of the loss
at the end of each timed window of ``--num-batches-per-iter`` steps. This
is an example of the user's loop; what the repo measures is measured by
``benchmark/run.py`` (cells in BENCHMARK.json, account in PERF.md).

Run: PYTHONPATH=. python examples/jax_synthetic_benchmark.py --model resnet50
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu import models
from horovod_tpu.common.compile_cache import enable_compile_cache

from common import shard_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=32,
                    help="per-chip batch size")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()

    model = models.get_model(args.model)
    # fused_update: the per-parameter update fusions collapse into
    # per-dtype flat buffers (horovod_tpu/jax/fused.py).
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                       fused_update=True)

    # Every chip gets its own --batch-size images; bf16 on the host
    # halves the feed bytes (the models compute in bf16).
    n_local = args.batch_size * hvd.local_size()
    shape = (n_local, args.image_size, args.image_size, 3)
    images_host = np.random.uniform(size=shape).astype(jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.asarray(images_host[:args.batch_size]), False)
    # Label range from the model's own head width (10 for the mnist models).
    labels_host = np.random.randint(0, model.num_classes, size=(n_local,))
    # Startup sync, as every reference example does before training
    # (reference: BroadcastGlobalVariablesHook).
    params = hvd_jax.broadcast_parameters(variables["params"], root_rank=0)
    batch_stats = variables.get("batch_stats", {})
    opt_state = opt.init(params)

    def loss_fn(params, batch_stats, images, labels, dropout_key):
        # vgg16 / inceptionv3 train with dropout and need the stream; the
        # models without dropout ignore the unused collection.
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, True,
            mutable=["batch_stats"], rngs={"dropout": dropout_key})
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    @hvd_jax.jit(
        in_specs=(P(), P(), P(), P(),
                  P(hvd_jax.HVD_AXIS), P(hvd_jax.HVD_AXIS)),
        out_specs=(P(), P(), P(), P(), P()),
        donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, key, images, labels):
        key, sub = jax.random.split(key)
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels, sub)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, batch_stats, opt_state, key, hvd_jax.allreduce(loss)

    mesh = hvd.mesh()
    images = shard_batch(images_host, mesh, hvd_jax.HVD_AXIS)
    labels = shard_batch(labels_host, mesh, hvd_jax.HVD_AXIS)
    key = jax.random.PRNGKey(hvd.rank())  # dropout stream

    def run_batches(n):
        nonlocal params, batch_stats, opt_state, key
        for _ in range(n):
            params, batch_stats, opt_state, key, loss = train_step(
                params, batch_stats, opt_state, key, images, labels)
        # Fetching the last loss returns only once every step has run.
        return float(loss)

    def log(line):
        if hvd.rank() == 0:
            print(line, flush=True)

    log(f"Model: {args.model}, batch size {args.batch_size} per chip, "
        f"{hvd.size()} chip(s)")
    loss = run_batches(args.num_warmup_batches)
    if not np.isfinite(loss):
        raise SystemExit(f"diverged in warmup: loss {loss}")

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        img_secs.append(args.batch_size * args.num_batches_per_iter / dt)
        log(f"Iter #{i}: {img_secs[-1]:.1f} img/sec per chip")

    mean, conf = float(np.mean(img_secs)), float(1.96 * np.std(img_secs))
    log(f"Img/sec per chip: {mean:.1f} +-{conf:.1f}")
    log(f"Total img/sec on {hvd.size()} chip(s): "
        f"{hvd.size() * mean:.1f} +-{hvd.size() * conf:.1f}")
    dev0 = hvd.devices()[0]
    log(json.dumps({
        "value": mean, "unit": "images/sec/chip",
        "platform": dev0.platform, "device_kind": dev0.device_kind,
        "n_devices": hvd.size()}))


if __name__ == "__main__":
    main()
