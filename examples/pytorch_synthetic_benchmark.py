#!/usr/bin/env python
"""Torch synthetic benchmark — img/sec through the async-engine allreduce
path (reference: examples/pytorch_synthetic_benchmark.py). This measures
the *host* engine (enqueue → fuse → XLA collective), the path torch
training uses; compiled-in JAX training is measured by benchmark/run.py.

Run: PYTHONPATH=. python examples/pytorch_synthetic_benchmark.py \
         --num-iters 3 --model resnet18
"""

import argparse
import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import torch
import torchvision_stub  # noqa: F401  (torchvision is absent; stub below)

import horovod_tpu.torch as hvd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--num-warmup-batches", type=int, default=2)
    ap.add_argument("--num-batches-per-iter", type=int, default=2)
    ap.add_argument("--num-iters", type=int, default=3)
    ap.add_argument("--fp16-allreduce", action="store_true")
    args = ap.parse_args()

    hvd.init()
    model = torchvision_stub.get_model(args.model)
    optimizer = torch.optim.SGD(model.parameters(), lr=0.01)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters(),
        compression=compression)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    data = torch.randn(args.batch_size, 3, 64, 64)
    target = torch.randint(0, 1000, (args.batch_size,))

    def benchmark_step():
        optimizer.zero_grad()
        loss = torch.nn.functional.cross_entropy(model(data), target)
        loss.backward()
        optimizer.step()

    for _ in range(args.num_warmup_batches):
        benchmark_step()

    img_secs = []
    for _ in range(args.num_iters):
        t = timeit.timeit(benchmark_step,
                          number=args.num_batches_per_iter)
        img_sec = args.batch_size * args.num_batches_per_iter / t
        img_secs.append(img_sec)
        print(f"Iter: {img_sec:.1f} img/sec per chip")
    print(f"Img/sec per chip: {np.mean(img_secs):.1f} "
          f"+-{1.96 * np.std(img_secs):.1f} "
          f"(total over {hvd.size()} ranks: "
          f"{hvd.size() * np.mean(img_secs):.1f})")


if __name__ == "__main__":
    main()
