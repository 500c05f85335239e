#!/usr/bin/env python
"""Scaling-efficiency harness — the reference's headline claim, measured.

The reference's banner numbers are scaling efficiencies (90% for
ResNet-101/Inception V3, 68% for VGG-16 at 512 GPUs — reference:
docs/benchmarks.md:1-7); BASELINE.json's north star is >=85% allreduce
scaling 8->256 v5e chips. This script produces those two curves on
whatever world it is started in:

  PYTHONPATH=. python examples/scaling_benchmark.py            # full sweep
  PYTHONPATH=. python examples/scaling_benchmark.py --chips 1 4 8
  PYTHONPATH=. python examples/scaling_benchmark.py --model resnet50

For each chip count n (powers of two up to the world, by default) it
re-forms the world from the first n chips (``hvd.init(ranks=...)`` — the
reference's ``init(comm=...)`` subset form) and measures:

- **allreduce bus bandwidth**: ring-equivalent ``2*(n-1)/n * bytes / t``
  for each ``--sizes-mb``, the metric NCCL tests report — how close the
  collective rides the ICI links.
- **end-to-end scaling efficiency** (with ``--model``): synthetic
  training images/sec at n chips vs n * (images/sec at 1 chip) — the
  reference's definition.

With one chip the sweep degenerates to n=1 (still useful as the per-chip
baseline). The multi-chip mechanics — subset meshes, re-init, per-n
compiled programs — are exercised on the 8-device virtual CPU mesh in
tests/test_examples_smoke.py; this script has not been run on a
multi-chip host.
"""

import argparse
import time

import numpy as np


def _timeit(fn, barrier, warmup=2, iters=8):
    """Timed window ending in ``barrier(out)`` — a real device->host
    fetch. The one timing convention for both the allreduce and
    training measurements in this file."""
    for _ in range(warmup):
        out = fn()
    barrier(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    barrier(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, nargs="+", default=None,
                    help="chip counts to sweep (default: powers of 2 up "
                         "to the full world)")
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1.0, 16.0, 64.0])
    ap.add_argument("--model", default=None,
                    help="also measure end-to-end training scaling "
                         "efficiency for this model (e.g. resnet50)")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--hierarchical-sweep", action="store_true",
                    help="instead of the chip-count sweep: on the full "
                         "world, trend flat vs hierarchical vs "
                         "hierarchical+int8-DCN allreduce per size — the "
                         "two-tier route's cross-tier byte win, measured "
                         "(simulates a multi-host mesh via "
                         "--two-tier-shape on one host)")
    ap.add_argument("--two-tier-shape", default=None,
                    help="o,i (dcn,ici) split for --hierarchical-sweep "
                         "(default: 2,<world/2> — two simulated hosts)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    world = hvd.size()
    hvd.shutdown()
    if args.hierarchical_sweep:
        return _hier_sweep(args, world)
    chips = args.chips or [n for n in (2 ** i for i in range(20))
                           if n <= world]
    skipped = [n for n in chips if n > world]
    if skipped:
        print(f"# skipping {skipped}: world has only {world} chip(s)")
        chips = [n for n in chips if n <= world]
    if not chips:
        raise SystemExit(f"no requested chip count fits the {world}-chip "
                         "world; nothing to sweep")

    e2e_base = None  # per-chip throughput at the SMALLEST swept n
    print(f"# world: {world} chip(s); sweeping {chips}")
    print("chips | " + " | ".join(f"allreduce {s:g}MB GB/s(bus)"
                                  for s in args.sizes_mb)
          + (f" | img/s | efficiency vs n={chips[0]}" if args.model
             else ""))
    for n in chips:
        hvd.init(ranks=list(range(n)))
        assert hvd.size() == n
        row = [f"{n:5d}"]
        for size_mb in args.sizes_mb:
            if n == 1:
                row.append("     n/a")  # no wire to measure
                continue
            # Compiled in-SPMD allreduce (allreduce_benchmark.py's
            # default mode): the eager path would re-stage the buffer
            # host->device inside the timed window and bill staging, not
            # the ICI collective, to the scaling number.
            from jax.sharding import NamedSharding, PartitionSpec

            from horovod_tpu.ops.collectives import ranked_allreduce

            elems = int(size_mb * 1024 * 1024 / 4)
            x = jax.device_put(
                jnp.ones((n, elems), jnp.float32),
                NamedSharding(hvd.mesh(), PartitionSpec("hvd")))
            fn = lambda: ranked_allreduce(x)  # noqa: E731
            # Sliced-scalar fetch: a whole-buffer fetch would bill a
            # multi-MB host transfer to the collective.
            t = _timeit(fn, lambda o: float(np.asarray(o[0])))
            bus = (2 * (n - 1) / n) * elems * 4 / t / 1e9
            row.append(f"{bus:8.2f}")
        if args.model:
            img_s = _train_throughput(args, n)
            # The reference defines efficiency against the 1-chip rate;
            # when a --chips list omits 1, the smallest swept n stands in
            # (and the column header says so).
            eff = (img_s / (n * e2e_base)) if e2e_base else 1.0
            if e2e_base is None:
                e2e_base = img_s / n
            row.append(f"{img_s:8.1f}")
            row.append(f"{100 * eff:5.1f}%")
        print(" | ".join(row), flush=True)
        hvd.shutdown()


def _hier_sweep(args, world):
    """Flat vs hierarchical vs hierarchical+int8-DCN allreduce on the
    full world: the two-tier composition's trend line. On one host the
    (dcn, ici) split is SIMULATED (HVD_TWO_TIER_SHAPE), so the timing
    columns share one interconnect — the structural number to watch is
    the cross-tier byte column: int8-DCN ships bytes/(L*~4) across the
    slow tier, the term that dominates once 'dcn' is a real network."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import horovod_tpu as hvd
    from horovod_tpu.ops.collectives import ranked_allreduce

    if world < 4:
        raise SystemExit(f"--hierarchical-sweep needs >=4 chips to "
                         f"split into two tiers; world has {world}")
    shape = args.two_tier_shape or f"2,{world // 2}"
    outer, inner = (int(v) for v in shape.split(","))
    modes = (("flat", {}, "none"),
             ("hier", {"HVD_TWO_TIER_SHAPE": shape,
                       "HVD_HIERARCHICAL_ALLREDUCE": "1"}, "none"),
             ("hier+int8dcn", {"HVD_TWO_TIER_SHAPE": shape,
                               "HVD_HIERARCHICAL_ALLREDUCE": "1"}, "int8"))
    print(f"# world: {world} chip(s); two-tier shape dcn={outer} x "
          f"ici={inner} (simulated on one host)")
    print(f"# {'size':>8s} | " + " | ".join(f"{m:>14s} ms" for m, _, _
                                            in modes)
          + " | cross-tier bytes flat vs int8-dcn")
    for size_mb in args.sizes_mb:
        elems = int(size_mb * 1024 * 1024 / 4)
        times = []
        for _, env, dcn_wire in modes:
            for k, v in env.items():
                os.environ[k] = v
            hvd.init()
            try:
                x = jax.device_put(
                    jnp.ones((world, elems), jnp.float32),
                    NamedSharding(hvd.mesh(), PartitionSpec("hvd")))
                fn = lambda: ranked_allreduce(x, dcn_wire=dcn_wire)  # noqa: E731
                times.append(_timeit(
                    fn, lambda o: float(np.asarray(o[0]))))
            finally:
                hvd.shutdown()
                for k in env:
                    os.environ.pop(k, None)
        # Cross-tier byte model (per chip, one allreduce): flat ships
        # the full ring volume across every hop; the two-phase route
        # ships only the quantized 1/L shard (+ f32 scales per 512
        # block) across the slow tier.
        from horovod_tpu.jax import quantize as Q
        from horovod_tpu.jax.compression import Compression

        pol = Compression.int8
        flat_bytes = elems * 4
        n_ici = Q.padded_len(elems, inner) // inner
        npad = Q.padded_len(n_ici, outer * pol.block)
        dcn_bytes = npad + (npad // pol.block) * 4  # i8 payload + scales
        print(f"# {size_mb:6.1f}MB | "
              + " | ".join(f"{t * 1e3:14.3f}   " for t in times)
              + f" | {flat_bytes / 1e6:.2f}MB vs {dcn_bytes / 1e6:.3f}MB "
                f"({flat_bytes / dcn_bytes:.1f}x fewer)", flush=True)


def _train_throughput(args, n):
    """Synthetic training images/sec on the current n-chip world
    (examples/jax_synthetic_benchmark.py's loop at sweep-friendly step
    counts)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import models

    model = models.get_model(args.model)
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    x = np.random.uniform(size=(args.batch_size, args.image_size,
                                args.image_size, 3)).astype(jnp.bfloat16)
    y = np.random.randint(0, model.num_classes, size=(args.batch_size,))
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x), False)
    params, bstats = variables["params"], variables.get("batch_stats", {})
    opt_state = opt.init(params)

    def loss_fn(p, bs, xx, yy, dk):
        # Dropout models (vgg16/inceptionv3) need an rng; others ignore it.
        logits, mut = model.apply({"params": p, "batch_stats": bs}, xx,
                                  True, mutable=["batch_stats"],
                                  rngs={"dropout": dk})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yy).mean(), mut["batch_stats"]

    @hvd_jax.jit(in_specs=(P(), P(), P(), P(), P(hvd_jax.HVD_AXIS),
                           P(hvd_jax.HVD_AXIS)),
                 out_specs=(P(), P(), P(), P(), P()),
                 donate_argnums=(0, 1, 2))
    def step(p, bs, s, key, xx, yy):
        key, dk = jax.random.split(key)
        (loss, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, xx, yy, dk)
        up, s = opt.update(g, s, p)
        return (optax.apply_updates(p, up), bs, s, key,
                hvd_jax.allreduce(loss))

    mesh = hvd.mesh()
    from jax.sharding import NamedSharding

    def shard(a):
        shards = [jax.device_put(a, d) for d in jax.local_devices()
                  if d in mesh.devices.flat]
        return jax.make_array_from_single_device_arrays(
            (a.shape[0] * hvd.size(),) + a.shape[1:],
            NamedSharding(mesh, P(hvd_jax.HVD_AXIS)), shards)

    xx, yy = shard(x), shard(np.asarray(y))

    key = jax.random.PRNGKey(0)

    def run():
        nonlocal params, bstats, opt_state, key
        for _ in range(args.steps):
            params, bstats, opt_state, key, loss = step(
                params, bstats, opt_state, key, xx, yy)
        return loss

    dt = _timeit(run, lambda loss: float(np.asarray(loss)),
                 warmup=1, iters=1)
    return args.batch_size * hvd.size() * args.steps / dt


if __name__ == "__main__":
    main()
