#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of two models the repo supports, then the host engine:

1. ``resnet50``: ``hvd.init()`` -> ``DistributedOptimizer(sgd+momentum,
   fused_update=True)`` -> ``broadcast_parameters`` -> ``@hvd_jax.jit``
   training steps, 224x224, bf16, 32 images per chip, one step per
   dispatch on one fixed synthetic batch; loss finite at every step and
   lower at the end.
2. ``bert_base``: the same path, 12 layers / hidden 768 / 12 heads /
   vocab 30,522, seq 512, 8 sequences per chip, with the pallas flash
   attention kernel inside ``shard_map`` + ``jit`` + ``grad``.
3. ``engine``: ``horovod_tpu.jax.mpi_ops.allreduce_async`` /
   ``synchronize`` round trips (1 MB, 16 MB, one fused group of
   32 x 64 kB) through the native engine, exact expected values.

It takes no arguments, uses every chip the process sees, and exits 0 only
if every phase ran on a TPU whose ``device_kind`` is in
``utils/hardware.py``, no pallas kernel fell to interpret mode and the
engine is the C++ one. A phase that raises ends the run: nothing here
catches it. On more than one chip the same run also checks where arrays
land and that the collectives reduce across all of them.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed per phase are a smoke record, not a benchmark.
"""

import json
import os
import sys
import time

FULL = {
    "resnet": dict(overrides={}, image=224, per_chip=32, steps=30),
    "bert": dict(layers=12, hidden=768, heads=12, vocab=30522, seq=512,
                 per_chip=8, steps=8),
    "engine": dict(sizes_kb=(1024, 16384), group=(32, 64)),
}
# Seconds-scale shapes for the CPU test tier (tests/test_chip_smoke.py);
# reachable only through main(tiny=True), never from the command line.
TINY = {
    "resnet": dict(overrides=dict(stage_sizes=[1, 1], num_filters=8,
                                  num_classes=10),
                   image=16, per_chip=2, steps=4),
    "bert": dict(layers=1, hidden=32, heads=2, vocab=64, seq=16,
                 per_chip=2, steps=3),
    "engine": dict(sizes_kb=(4, 64), group=(4, 1)),
}


def _device_tag(devs):
    return (f"platform={devs[0].platform} "
            f"device_kind={devs[0].device_kind!r} n_devices={len(devs)}")


def _memory(devs, key):
    """``memory_stats()[key]`` per device (None where the backend keeps
    no statistics, as XLA:CPU)."""
    stats = [d.memory_stats() for d in devs]
    return [s[key] if s else None for s in stats]


def _shard(host_array, mesh):
    """The global batch, dim 0 split over the 'hvd' axis: one shard per
    chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd_jax

    return jax.device_put(host_array,
                          NamedSharding(mesh, P(hvd_jax.HVD_AXIS)))


def _train(name, step, state, batch, steps, devs):
    """Compile ``step`` ahead of time, run ``steps`` dispatches of one
    step each on the fixed ``batch``, print the phase line. Odd steps end
    behind ``jax.block_until_ready``, even steps behind a one-scalar
    device->host fetch of the loss, so the two barriers are timed side by
    side on the same program. ``step`` returns the new state, then the
    loss, then any extras. Returns (extras of the last step, compiled
    HLO text)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    compiled = step.lower(*state, *batch).compile()
    compile_s = time.perf_counter() - t0

    n_state = len(state)
    losses, t_ready, t_fetch = [], [], []
    extras = ()
    for i in range(steps):
        t0 = time.perf_counter()
        out = compiled(*state, *batch)
        state, loss, extras = out[:n_state], out[n_state], out[n_state + 1:]
        if i % 2:
            jax.block_until_ready((state, loss))
            t_ready.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(loss)))
        else:
            losses.append(float(np.asarray(loss)))
            t_fetch.append(time.perf_counter() - t0)
        if not np.isfinite(losses[-1]):
            raise AssertionError(f"{name}: loss {losses[-1]} at step {i}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{name}: loss did not fall: {losses[0]} -> {losses[-1]}")
    # The first dispatch of each kind carries one-off warm-up.
    print(f"phase={name} {_device_tag(devs)} compile_s={compile_s:.2f} "
          f"steps={steps} "
          f"step_s_block_until_ready={np.median(t_ready[1:] or t_ready):.5f} "
          f"step_s_fetch={np.median(t_fetch[1:] or t_fetch):.5f} "
          f"loss_first={losses[0]:.4f} loss_last={losses[-1]:.4f} "
          f"peak_bytes_in_use={_memory(devs, 'peak_bytes_in_use')}",
          flush=True)
    return extras, compiled.as_text()


def resnet_phase(cfg, devs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import models

    n = hvd.size()
    model = models.ResNet50(**cfg["overrides"])
    rng = np.random.RandomState(0)
    images_host = rng.uniform(
        size=(cfg["per_chip"] * n, cfg["image"], cfg["image"], 3)
    ).astype(jnp.bfloat16)
    labels_host = rng.randint(0, model.num_classes,
                              size=(cfg["per_chip"] * n,))

    variables = jax.jit(lambda key, x: model.init(key, x, False))(
        jax.random.PRNGKey(0), jnp.asarray(images_host[:cfg["per_chip"]]))
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                       fused_update=True)
    opt_state = opt.init(params)
    params = hvd_jax.broadcast_parameters(params, root_rank=0)

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    @hvd_jax.jit(
        in_specs=(P(), P(), P(), P(hvd_jax.HVD_AXIS), P(hvd_jax.HVD_AXIS)),
        out_specs=(P(), P(), P(), P(), P()),
        donate_argnums=(0, 1, 2),
    )
    def train_step(params, batch_stats, opt_state, images, labels):
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # Mean of the per-chip ranks: (n - 1) / 2 only if the in-step
        # collective really spans every chip.
        mean_rank = hvd_jax.allreduce(
            hvd_jax.axis_rank().astype(jnp.float32))
        return params, new_bs, opt_state, hvd_jax.allreduce(loss), mean_rank

    mesh = hvd.mesh()
    images, labels = _shard(images_host, mesh), _shard(labels_host, mesh)
    (mean_rank,), hlo = _train(
        "resnet50", train_step, (params, batch_stats, opt_state),
        (images, labels), cfg["steps"], devs)

    # Where things landed, and whether the exchange spans the world. At
    # one chip these hold trivially (and every collective is elided).
    if not hvd.size() == jax.device_count() == len(devs):
        raise AssertionError(
            f"world {hvd.size()} != devices {jax.device_count()}")
    shard_devs = {s.device for s in images.addressable_shards}
    if len(shard_devs) != n:
        raise AssertionError(
            f"batch shards sit on {len(shard_devs)} device(s), not {n}")
    in_use = _memory(devs, "bytes_in_use")
    if any(b == 0 for b in in_use):
        raise AssertionError(f"a device holds nothing after a step: {in_use}")
    if n > 1 and "all-reduce" not in hlo:
        raise AssertionError("no all-reduce in the compiled step's HLO")
    if float(mean_rank) != (n - 1) / 2:
        raise AssertionError(
            f"in-step allreduce(axis_rank) = {float(mean_rank)}, "
            f"want {(n - 1) / 2}")
    print(f"check=resnet50 world={n} shard_devices={len(shard_devs)} "
          f"bytes_in_use={in_use} all_reduce_in_hlo={'all-reduce' in hlo} "
          f"mean_rank={float(mean_rank)}", flush=True)


def bert_phase(cfg, devs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.ops.flash_attention import flash_attention

    n = hvd.size()
    model = TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], hidden_dim=cfg["hidden"],
        mlp_dim=4 * cfg["hidden"], max_len=cfg["seq"], dtype=jnp.bfloat16,
        attention_fn=flash_attention))
    tokens_host = np.random.RandomState(0).randint(
        0, cfg["vocab"], size=(cfg["per_chip"] * n, cfg["seq"])
    ).astype(np.int32)

    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(tokens_host[:1]))
    opt = hvd_jax.DistributedOptimizer(
        optax.adamw(1e-4, weight_decay=0.01), fused_update=True)
    params = hvd_jax.broadcast_parameters(variables["params"], root_rank=0)
    opt_state = opt.init(params)

    def loss_fn(params, toks):
        logits = model.apply({"params": params}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(toks, -1, axis=1)).mean()

    @hvd_jax.jit(in_specs=(P(), P(), P(hvd_jax.HVD_AXIS)),
                 out_specs=(P(), P(), P()), donate_argnums=(0, 1))
    def train_step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(loss_fn)(params, toks)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd_jax.allreduce(loss))

    _train("bert_base", train_step, (params, opt_state),
           (_shard(tokens_host, hvd.mesh()),), cfg["steps"], devs)


def engine_phase(cfg, devs):
    """Host path: numpy buffers through the async engine and back. Every
    chip contributes this controller's buffer, so a sum is ``size`` times
    the input — exact in f32 for the small integers used here."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.core import get_engine, native
    from horovod_tpu.jax import mpi_ops

    lib = native.library_path()
    reused = os.path.exists(lib)
    engine = get_engine()
    if type(engine).__name__ != "NativeEngine":
        raise AssertionError(
            f"engine in use is {type(engine).__name__}, not NativeEngine "
            "(the C++ build failed and the python engine took over)")
    n = hvd.size()

    def pattern(n_elems, seed):
        return ((np.arange(n_elems) + seed) % 251).astype(np.float32)

    # Each shape goes twice and the second trip is the one printed: the
    # first compiles the eager collective program for that length.
    trips = []
    for kb in cfg["sizes_kb"]:
        x = pattern(kb * 256, kb)
        for _ in range(2):
            t0 = time.perf_counter()
            out = mpi_ops.synchronize(mpi_ops.allreduce_async(
                x, average=False, name=f"smoke.{kb}kb"))
            dt = time.perf_counter() - t0
            np.testing.assert_array_equal(out, x * n)
        trips.append(f"{kb}kB:{dt:.4f}s")

    count, kb = cfg["group"]
    xs = [pattern(kb * 256, i) for i in range(count)]
    names = [f"smoke.group.{i}" for i in range(count)]
    for _ in range(2):
        t0 = time.perf_counter()
        outs = [mpi_ops.synchronize(h) for h in
                mpi_ops.allreduce_n_async(xs, average=False, names=names)]
        dt = time.perf_counter() - t0
        for x, out in zip(xs, outs):
            np.testing.assert_array_equal(out, x * n)
    trips.append(f"{count}x{kb}kB:{dt:.4f}s")

    ones = mpi_ops.synchronize(mpi_ops.allreduce_async(
        np.ones((8,), np.float32), average=False, name="smoke.ones"))
    np.testing.assert_array_equal(ones, np.full((8,), float(n), np.float32))
    print(f"phase=engine {_device_tag(devs)} engine={type(engine).__name__} "
          f"library={'reused' if reused else 'built'} "
          f"round_trips=[{' '.join(trips)}] ones_sum={ones[0]:g} "
          f"peak_bytes_in_use={_memory(devs, 'peak_bytes_in_use')}",
          flush=True)


def main(tiny: bool = False):
    cfg = TINY if tiny else FULL

    from horovod_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    import horovod_tpu as hvd
    from horovod_tpu.ops import pallas_mode
    from horovod_tpu.utils import hardware

    hvd.init()
    devs = hvd.devices()
    if devs[0].platform != "tpu" and not tiny:
        print(f"chip_smoke: FAIL: jax found no TPU ({_device_tag(devs)}); "
              "this script proves the chip path and never runs elsewhere",
              file=sys.stderr)
        return 1
    hardware.peak_flops(devs[0])  # raises for a device_kind not in the table
    print(f"chip_smoke: size={'tiny' if tiny else 'full'} "
          f"{_device_tag(devs)} compile_cache={cache_dir} "
          f"cache_entries_at_start={cached}", flush=True)

    resnet_phase(cfg["resnet"], devs)
    bert_phase(cfg["bert"], devs)
    if pallas_mode.INTERPRETED and not tiny:
        raise AssertionError("pallas kernels ran interpreted: "
                             f"{sorted(pallas_mode.INTERPRETED)}")
    engine_phase(cfg["engine"], devs)
    hvd.shutdown()

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
