#!/usr/bin/env python
"""Synthetic training benchmark — the driver's headline metric.

Methodology mirrors the reference's synthetic benchmark (reference:
examples/tensorflow_synthetic_benchmark.py:17-28,77-106): random data,
``DistributedOptimizer`` training step, N warmup batches, then
``num_iters x num_batches_per_iter`` timed steps, reporting images/sec per
chip.

Timing is honest: each timed window ends with a real device->host fetch of
the loss (``float(np.asarray(loss))``), which cannot return before the
window's last step has run.  The JSON line also reports per-step FLOPs
from XLA's own cost analysis and the implied MFU against the chip's peak,
so a physically impossible number is self-evident, and it names the
device it ran on (``platform``, ``device_kind``, ``n_devices``).

The benchmark measures the chip: it refuses to run on anything but a TPU
unless ``JAX_PLATFORMS=cpu`` is set explicitly (the CPU smoke tier), and
on a TPU a failed AOT compile or a failed profile capture fails the run
instead of printing nulls.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "step_time_ms": ..., "gflops_per_step": ..., "mfu": ...,
   "platform": ..., "device_kind": ..., "n_devices": N}

vs_baseline compares against the only absolute throughput figure published in
the reference tree: 1656.82 images/sec on 16 GPUs (ResNet-101,
docs/benchmarks.md:33-38) -> 103.55 images/sec per device.
"""

import argparse
import json
import os
import sys
import time

BASELINE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16  # reference docs/benchmarks.md:33-38


def main():
    p = argparse.ArgumentParser(description="horovod_tpu synthetic benchmark")
    p.add_argument("--model", default="resnet50")
    p.add_argument("--stem", default=None,
                   choices=["conv7", "space_to_depth"],
                   help="ResNet stem: classic 7x7/s2 conv, or the exact "
                        "space-to-depth reparameterization (MXU-friendly; "
                        "see models/resnet.py)")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-chip batch size (reference default 32)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-warmup-batches", type=int, default=150)
    p.add_argument("--num-batches-per-iter", type=int, default=800,
                   help="batches per timed window; each window ends in one "
                        "device->host fetch (the honesty barrier), so the "
                        "window must be long enough to amortize the "
                        "fetch+dispatch round-trip")
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--steps-per-call", type=int, default=800,
                   help="training steps fused into one dispatch via "
                        "lax.scan; amortizes per-call host latency "
                        "(each scanned step is a full real SGD update). "
                        "The default is one dispatch per timed window: "
                        "fewer dispatches measured faster at every size "
                        "and one call removes multi-call wobble from "
                        "the headline")
    p.add_argument("--unroll", type=int, default=5,
                   help="lax.scan unroll factor: >1 lets XLA software-"
                        "pipeline across step boundaries (prefetch next "
                        "step's weights during this step's compute) at "
                        "the cost of code size (measured on ResNet-50 "
                        "bs32: 2 is +4%%, 4-5 are +6%%)")
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="bf16 gradient compression on the wire")
    p.add_argument("--compression", default=None,
                   choices=["none", "bf16", "fp16", "int8", "int8_ef",
                            "fp8"],
                   help="wire-compression policy for the gradient "
                        "collectives (jax/quantize.py): 'int8'/'fp8' are "
                        "block-scaled quantized formats (~4x fewer bytes "
                        "on the wire, scales included); 'int8_ef' adds "
                        "the error-feedback residual (needs "
                        "--sharded-update: the residual rides the "
                        "sharded optimizer state). Overrides "
                        "--fp16-allreduce when given")
    p.add_argument("--sharded-update", action="store_true",
                   help="cross-replica sharded weight update (arxiv "
                        "2004.13336): reduce-scatter the gradient "
                        "buckets, update a 1/N shard of params + "
                        "optimizer state, all-gather the result. Cuts "
                        "per-chip optimizer HBM traffic ~(N-1)/N on a "
                        "multi-chip world; at N=1 it degrades to whole-"
                        "tree packing (a measured NEGATIVE — see "
                        "docs/benchmarks.md 'HBM diet')")
    p.add_argument("--state-dtype", default="f32", choices=["f32", "bf16"],
                   help="resident-state precision policy (HBM diet round "
                        "2): 'bf16' keeps parameters and optimizer state "
                        "in bf16 HBM with the update math in f32; with "
                        "--sharded-update, f32 master weights ride the "
                        "sharded optimizer state as each chip's 1/N "
                        "shard (arxiv 2004.13336 §4) — full-width f32 "
                        "state never touches HBM. Without sharding there "
                        "are no masters (docs/troubleshooting.md on "
                        "bf16 drift)")
    p.add_argument("--remat-blocks", nargs="?", const="act_drop",
                   default=None, choices=["act_drop", "conv_saves"],
                   help="ResNet traffic-removal remat: 'act_drop' "
                        "(default) drops the tagged post-BN/ReLU/join "
                        "activations from the saved set and recomputes "
                        "them in backward from saved conv outputs + BN "
                        "stats; 'conv_saves' saves ONLY conv outputs "
                        "(measured negative — see docs/benchmarks.md). "
                        "Numerics identical either way")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture an XLA profiler trace of one timed "
                        "window into DIR (view: tensorboard --logdir DIR)")
    p.add_argument("--xla-option", action="append", default=[],
                   metavar="KEY=VAL",
                   help="extra XLA compiler option(s) for the step "
                        "executable (repeatable), e.g. "
                        "--xla-option xla_tpu_scoped_vmem_limit_kib=65536")
    p.add_argument("--check", action="store_true",
                   help="perf regression gate (utils/perfwatch): compare "
                        "this run against the newest same-metric "
                        "BENCH_r*.json history record with noise-aware "
                        "bounds from the recorded iteration spread; the "
                        "JSON line gains a \"gate\" object and the exit "
                        "code is nonzero on an img/s drop or "
                        "hbm_gb_per_step creep")
    p.add_argument("--dry", action="store_true",
                   help="parse args and print the one-JSON-line contract "
                        "with null values, without importing jax or "
                        "touching a device — the CI guard "
                        "(tests/test_bench_contract.py) pins that this "
                        "stays import-free and one line")
    args = p.parse_args()

    if args.dry:
        # The exact key set of the real result line below (minus the
        # best-effort "telemetry"/"trace" extras); values null. MUST stay
        # reachable without importing jax/the framework: `bench.py
        # --help` and this guard are how CI proves argparse errors never
        # pay the framework import.
        print(json.dumps({
            "metric": f"{args.model}_train_images_per_sec_per_chip"
                      f"_bs{args.batch_size}",
            "value": None, "unit": "images/sec/chip", "vs_baseline": None,
            "step_time_ms": None, "gflops_per_step": None, "mfu": None,
            "hbm_gb_per_step": None, "hbm_source": None,
            "membw_util": None, "spread_pct": None, "gate": None,
            "state_dtype": None, "compression": None, "numerics": None,
            "platform": None, "device_kind": None, "n_devices": None,
            "dry": True,
        }))
        return

    # Numerics observatory (core/numerics.py): default the in-step
    # gradient-health policy OFF for the bench — the headline hot loop
    # must compile to the identical HLO as the recorded BENCH_r* history
    # (the off-policy pin in tests/test_numerics.py). setdefault: an
    # operator explicitly exporting HVD_NUMERICS=warn|halt gets an
    # instrumented (and honestly slower) run.
    os.environ.setdefault("HVD_NUMERICS", "off")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import models
    # Deliberately imported here, not at module top: `bench.py --help`
    # and argparse errors must not pay the framework+jax import.
    from horovod_tpu.common.compile_cache import enable_compile_cache
    from horovod_tpu.utils import hardware as hw

    enable_compile_cache()
    hvd.init()
    nchips = hvd.size()
    dev0 = hvd.devices()[0]
    on_tpu = dev0.platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        # With libtpu installed and no chip reachable jax warns and hands
        # back CPU devices; a benchmark that carried on would print a
        # host number under the chip's metric name.
        raise SystemExit(
            f"bench.py: jax found platform={dev0.platform!r} "
            f"({dev0.device_kind!r}), not a TPU. The benchmark measures "
            "the chip; set JAX_PLATFORMS=cpu to run the CPU smoke "
            "explicitly.")

    model_kw = {"stem": args.stem} if args.stem else {}
    model = models.get_model(args.model, **model_kw)
    # --compression (the quantized-collectives subsystem) wins over the
    # legacy --fp16-allreduce spelling; argparse already vetted the
    # name, resolve() threads the policy object through.
    compression_name = (args.compression
                        or ("fp16" if args.fp16_allreduce else "none"))
    compression = hvd_jax.Compression.resolve(compression_name)
    # fused_update: the ~160 per-parameter update fusions collapse into
    # per-dtype flat buffers (horovod_tpu/jax/fused.py) — profiling shows
    # per-tensor updates + their HBM<->VMEM copies costing ~2.5 ms of an
    # 11.4 ms step at bs32.
    # state_dtype (HBM diet round 2): resident params + optimizer state
    # in bf16 HBM, update math in f32; with --sharded-update the f32
    # masters ride the sharded state as 1/N shards.
    state_dtype = None if args.state_dtype == "f32" else args.state_dtype
    opt = hvd_jax.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=compression,
        fused_update=True, sharded_update=args.sharded_update,
        state_dtype=state_dtype)

    rng = jax.random.PRNGKey(0)
    # bf16 host feed: the model computes in bf16; feeding bf16 halves the
    # host->device bytes and skips the on-device upcast-downcast.
    images_host = np.random.uniform(
        size=(args.batch_size, args.image_size, args.image_size, 3)
    ).astype(jnp.bfloat16)

    variables = model.init(rng, jnp.asarray(images_host), False)
    # Label range from the model's own head width: a hardcoded 1000
    # NaNs the loss for the 10-class mnist_* models.
    labels_host = np.random.randint(0, model.num_classes,
                                    size=(args.batch_size,))
    params, batch_stats = variables["params"], variables.get("batch_stats", {})
    # Resident params at the policy width (identity under f32; the
    # masters — when sharded — derive from these in opt.init, so cast
    # FIRST). BN statistics stay f32: running moments accumulate badly
    # in bf16.
    params = hvd_jax.cast_resident_params(params, state_dtype)
    opt_state = opt.init(params)
    # Startup sync, as every reference example does before training
    # (reference: BroadcastGlobalVariablesHook).
    params = hvd_jax.broadcast_parameters(params, root_rank=0)

    def loss_fn(params, batch_stats, images, labels, dropout_rng):
        # Unused rng collections are ignored by models without dropout
        # (resnet/mnist); vgg16/inceptionv3 train with 0.5 dropout and
        # need it — a benchmark that silently disabled dropout would
        # overstate them.
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, True,
            mutable=["batch_stats"], rngs={"dropout": dropout_rng})
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    if args.remat_blocks:
        from horovod_tpu.models import resnet as _resnet

        # Traffic-removal remat (see models/resnet.py policy docstrings).
        policy = (_resnet.act_drop_policy() if args.remat_blocks == "act_drop"
                  else _resnet.conv_saves_policy())
        loss_fn = jax.checkpoint(loss_fn, policy=policy)

    def one_step(params, batch_stats, opt_state, key, images, labels):
        key, sub = jax.random.split(key)
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels,
                                   sub)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_bs, opt_state, key, hvd_jax.allreduce(loss)

    spc = max(1, args.steps_per_call)

    # Sharded update: each chip carries only its 1/N block of the
    # momentum/param flat buffers, so the optimizer state rides the mesh
    # as P('hvd') instead of replicated.
    ospec = (hvd_jax.sharded_state_specs(opt_state)
             if args.sharded_update else P())

    @hvd_jax.jit(
        in_specs=(P(), P(), ospec, P(),
                  P(hvd_jax.HVD_AXIS), P(hvd_jax.HVD_AXIS)),
        out_specs=(P(), P(), ospec, P(), P()),
        donate_argnums=(0, 1, 2),
    )
    def train_step(params, batch_stats, opt_state, key, images, labels):
        if spc == 1:
            return one_step(params, batch_stats, opt_state, key, images,
                            labels)

        def body(carry, _):
            params, batch_stats, opt_state, key = carry
            params, batch_stats, opt_state, key, loss = one_step(
                params, batch_stats, opt_state, key, images, labels)
            return (params, batch_stats, opt_state, key), loss

        (params, batch_stats, opt_state, key), losses = jax.lax.scan(
            body, (params, batch_stats, opt_state, key), None, length=spc,
            unroll=max(1, args.unroll))
        return params, batch_stats, opt_state, key, losses[-1]

    # Each chip sees the full per-chip batch: global batch = B * size.
    mesh = hvd.mesh()
    from jax.sharding import NamedSharding

    def chip_batch(x):
        shards = [jax.device_put(x, d) for d in jax.local_devices()
                  if d in mesh.devices.flat]
        global_shape = (x.shape[0] * nchips,) + x.shape[1:]
        return jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(mesh, P(hvd_jax.HVD_AXIS)), shards)

    images = chip_batch(images_host)
    labels = chip_batch(labels_host)
    step_key = jax.random.PRNGKey(hvd.rank())  # dropout stream (vgg/inception)

    # XLA's own FLOP count for the compiled step (reference methodology
    # anchor: tensorflow_synthetic_benchmark.py:96-106 reports img/sec; we
    # additionally pin it to hardware truth).
    # NB: XLA:TPU cost analysis counts a while-loop (lax.scan) body ONCE,
    # so for any steps-per-call this is the per-STEP figure (verified on
    # chip: spc=1 and spc=10 both report 765.2 GFLOP for ResNet-50 bs32).
    # The AOT executable is reused for the run itself — the traced-call jit
    # cache is separate, so falling back to train_step() would compile the
    # same program a second time.
    step_fn = train_step
    flops_per_step = 0.0
    counted = 1  # scan steps cost_analysis holds (set with flops below)
    bytes_per_step = None  # None = unavailable (cost analysis failed
    # or the body is unrolled — see below); never a fake measured zero.
    copts = {}
    for kv in args.xla_option:
        if "=" not in kv:
            p.error(f"--xla-option expects KEY=VAL, got {kv!r}")
        k, v = kv.split("=", 1)
        copts[k] = v
    try:
        compiled = train_step.lower(
            params, batch_stats, opt_state, step_key, images,
            labels).compile(compiler_options=copts or None)
        step_fn = compiled
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        # The scan BODY is counted once (verified on chip, note above);
        # unrolling multiplies the steps it holds (verified on chip:
        # unroll=4, spc=50 reports exactly 6x the one-step FLOPs —
        # 4-step body + 2-step peeled remainder).
        unroll = max(1, args.unroll) if spc > 1 else 1
        counted = hw.scan_cost_analysis_steps(spc, args.unroll)
        flops_per_step = float(ca.get("flops", 0.0)) / counted
        # "bytes accessed" does NOT follow the same rule under unrolling
        # (observed 0.66 GB/step at unroll=2 vs 16.95 at unroll=1 for the
        # same program) — only trust it on the un-unrolled body; report
        # null otherwise (0.0 would read as a measured zero).
        bytes_per_step = (float(ca.get("bytes accessed", 0.0))
                          if unroll == 1 else None)
    except Exception as e:  # pragma: no cover - CPU tier only
        if copts or on_tpu:
            # On the chip a step that does not AOT-compile is a broken
            # benchmark, not a degraded one; and silently benchmarking
            # WITHOUT requested compiler options would attribute a
            # default-config number to the flag. Fail loudly.
            print(f"# AOT compile (options {copts}) failed: {e}",
                  file=sys.stderr)
            raise
        print(f"# cost_analysis unavailable: {e}", file=sys.stderr)

    def run_batches(ncalls):
        nonlocal params, batch_stats, opt_state, step_key
        loss = None
        for _ in range(ncalls):
            params, batch_stats, opt_state, step_key, loss = step_fn(
                params, batch_stats, opt_state, step_key, images, labels)
        # Real device->host fetch of the last loss: returns only once
        # every step of the window has run.
        return float(np.asarray(loss))

    ncalls_warm = max(1, args.num_warmup_batches // spc)
    if ncalls_warm * spc != args.num_warmup_batches:
        print(f"# note: warmup rounded to {ncalls_warm * spc} batches "
              f"(multiple of --steps-per-call {spc})", file=sys.stderr)
    ncalls_iter = max(1, args.num_batches_per_iter // spc)
    batches_per_iter = ncalls_iter * spc
    if batches_per_iter != args.num_batches_per_iter:
        print(f"# note: window rounded to {batches_per_iter} batches "
              f"(multiple of --steps-per-call {spc})", file=sys.stderr)

    loss = run_batches(ncalls_warm)
    assert np.isfinite(loss), f"diverged in warmup: {loss}"

    # One profiled window ALWAYS runs (into --profile DIR when given,
    # else a tempdir): the capture is where the measured HBM-traffic
    # fields of the JSON line come from (docs/benchmarks.md "The
    # ceiling, measured") — async-DMA payload + fusion direct streams,
    # not XLA's bytes-accessed estimate.
    measured_gb_per_step = None

    def _measure_from_profile(prof_dir, new_files):
        from horovod_tpu.utils import xplane

        # Only THIS run's capture: a reused --profile dir still holds
        # earlier xplane files, which would double every byte count.
        spaces = xplane._load_spaces(prof_dir, files=new_files)
        dma = xplane.dma_bytes(prof_dir, spaces=spaces)
        direct = xplane.fusion_direct_bytes(prof_dir, spaces=spaces)
        window_steps = ncalls_iter * spc
        if dma["bytes"] or direct:
            return (dma["bytes"] + direct) / 1e9 / window_steps
        return None

    if args.profile:
        # User-requested capture: failures stay LOUD (a silent missing
        # trace is worse than a crashed bench); only the derived HBM
        # numbers are best-effort, and only off the chip.
        from horovod_tpu.utils import profiler

        before = set(profiler.trace_files(args.profile))
        with profiler.profile(args.profile):
            run_batches(ncalls_iter)
        new_files = [f for f in profiler.trace_files(args.profile)
                     if f not in before]
        if not new_files:
            # A capture that lands nothing is a broken measurement, not
            # a degraded one: every derived HBM figure would silently
            # read as "no traffic". Fail loudly (profiler.capture raises
            # the same way).
            print(f"# ERROR: --profile {args.profile} produced no "
                  "*.xplane.pb (is another trace active? is the "
                  "profiler plugin available?)", file=sys.stderr)
            raise SystemExit(3)
        print(f"# profile: {len(new_files)} new xplane file(s) in "
              f"{args.profile}", file=sys.stderr)
        try:
            measured_gb_per_step = _measure_from_profile(args.profile,
                                                         new_files)
        except Exception as e:  # pragma: no cover - CPU tier only
            if on_tpu:
                raise
            print(f"# profile-based HBM measurement unavailable: {e}",
                  file=sys.stderr)
    else:
        # Implicit capture into a tempdir purely for the measured HBM
        # fields. On a TPU a capture that fails is a failed run (the
        # line would carry null HBM fields under exit 0); off the chip
        # there is no HBM to measure and it stays best-effort.
        try:
            import tempfile

            from horovod_tpu.utils import profiler

            with tempfile.TemporaryDirectory(prefix="bench_prof_") as td:
                with profiler.profile(td):
                    run_batches(ncalls_iter)
                measured_gb_per_step = _measure_from_profile(
                    td, profiler.trace_files(td))
        except Exception as e:  # pragma: no cover - CPU tier only
            if on_tpu:
                raise
            print(f"# profile-based HBM measurement unavailable: {e}",
                  file=sys.stderr)

    rates = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(ncalls_iter)
        dt = time.perf_counter() - t0
        rates.append(args.batch_size * batches_per_iter / dt)

    per_chip = float(np.median(rates))
    step_time = args.batch_size / per_chip
    peak = hw.peak_flops(dev0)
    peak_bw = hw.peak_hbm_bw(dev0)
    if peak and flops_per_step / step_time > peak:
        # Guard against a cost-analysis that counted the full scan (all
        # spc steps, would make MFU read > 1 on a sane measurement): the
        # value was already divided by `counted`, so recover one step's
        # FLOPs as raw/spc = flops_per_step * counted / spc.
        flops_per_step *= counted / spc
        print("# note: cost_analysis FLOPs exceeded chip peak; assuming it "
              f"counted all {spc} scan steps and rescaling", file=sys.stderr)
    if (bytes_per_step and peak_bw
            and bytes_per_step / step_time > 2 * peak_bw):
        bytes_per_step /= spc  # same scan-body pitfall as FLOPs
        print("# note: cost_analysis bytes exceeded 2x chip HBM peak; "
              f"assuming scan body counted {spc}x and dividing",
              file=sys.stderr)
    mfu = (flops_per_step / step_time / peak
           ) if peak and flops_per_step else None
    # Preferred: the MEASURED per-step HBM traffic from the profiled
    # window (async-DMA payload + fusion direct streams — see
    # docs/benchmarks.md "The ceiling, measured"). Fallback: XLA's
    # "bytes accessed", which counts each op's operands+results and so
    # over-states true HBM traffic (measured discount ~0.46); the
    # hbm_source field says which one the line carries. MFU + a high
    # membw_util together locate the step on the roofline.
    if measured_gb_per_step is not None:
        hbm_bytes_step = measured_gb_per_step * 1e9
        hbm_source = "measured"
    else:
        hbm_bytes_step = bytes_per_step
        hbm_source = "cost_analysis" if bytes_per_step is not None else None
    membw = (hbm_bytes_step / step_time / peak_bw
             ) if peak_bw and hbm_bytes_step else None
    result = {
        "metric": f"{args.model}_train_images_per_sec_per_chip"
                  f"_bs{args.batch_size}",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_DEVICE, 3),
        "step_time_ms": round(step_time * 1e3, 3),
        # None (not 0.0) when cost analysis failed — same no-fake-zero
        # rule as hbm_gb_per_step.
        "gflops_per_step": (round(flops_per_step / 1e9, 1)
                            if flops_per_step else None),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "hbm_gb_per_step": (round(hbm_bytes_step / 1e9, 2)
                            if hbm_bytes_step is not None else None),
        "hbm_source": hbm_source,
        "membw_util": round(membw, 3) if membw is not None else None,
        # Iteration spread as a percentage of the median — the noise
        # bound the perfwatch gate derives its pass/fail margin from.
        "spread_pct": round((max(rates) - min(rates)) / per_chip * 100, 2)
        if per_chip else None,
        "gate": None,  # filled by --check below; present-but-null else
        "state_dtype": args.state_dtype,
        "compression": compression_name,
        "numerics": None,  # filled post-window below; null under --dry
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "n_devices": nchips,
    }
    # Numerics summary (core/numerics.py): policy + anything the run
    # observed (eager-path health, verdicts, consistency). Collected
    # AFTER the timed windows like telemetry; with the default bench
    # policy (off) it reports {"policy": "off", ...nulls} — the honest
    # "nothing was watched" record.
    try:
        from horovod_tpu.core import numerics as _numerics

        result["numerics"] = _numerics.compact()
    except Exception as e:  # pragma: no cover - never fail the bench
        print(f"# numerics summary unavailable: {e}", file=sys.stderr)
    # Unified telemetry (core/telemetry.py): eager-collective counts, the
    # startup broadcast, engine activity if any — read AFTER the timed
    # windows so collecting it can never perturb the headline. The hot
    # path itself is the AOT executable, which carries no instrumentation.
    try:
        from horovod_tpu.core import telemetry as _telemetry

        result["telemetry"] = _telemetry.compact()
    except Exception as e:  # pragma: no cover - never fail the bench line
        print(f"# telemetry unavailable: {e}", file=sys.stderr)
    # Distributed tracing: with HVD_TIMELINE set, report the merged
    # per-rank trace path. Collected POST-window (the AOT hot path
    # carries no timeline instrumentation — only the engines' host-side
    # spans land in it), and strictly best-effort.
    import os as _os

    tl_env = (_os.environ.get("HVD_TIMELINE")
              or _os.environ.get("HOROVOD_TIMELINE"))
    if tl_env:
        try:
            from horovod_tpu.core import engine as _eng

            if _eng._engine is not None:
                _eng.shutdown_engine()  # close per-rank files for merge
            from horovod_tpu.core import timeline as _tl

            if _tl.is_dir_mode(tl_env):
                from horovod_tpu.utils import trace as _trace

                result["trace"] = _trace.merge(tl_env)["path"]
            elif _os.path.exists(tl_env):
                result["trace"] = tl_env  # single-file spelling
        except Exception as e:  # pragma: no cover - never fail the bench
            print(f"# trace merge unavailable: {e}", file=sys.stderr)
    gate_failed = False
    if args.check:
        # Regression gate (ROADMAP item 2: img/s and HBM traffic must
        # not silently creep back). perfwatch is stdlib-only; the
        # history lives next to this script (BENCH_r*.json). Guarded:
        # whatever the gate does, the one-JSON-line contract holds — a
        # gating error is reported as status "error" on stderr, never a
        # traceback that eats the measured run.
        try:
            from horovod_tpu.utils import perfwatch as _pw

            repo = _os.path.dirname(_os.path.abspath(__file__))
            # The noise bound comes from result["spread_pct"] — ONE
            # definition of the iteration spread for both the JSON line
            # and the gate.
            cur = _pw.record_from_bench(result)
            gate = _pw.gate(cur, _pw.pick_reference(
                _pw.load_history(repo), cur))
            result["gate"] = gate
            gate_failed = gate["status"] == "fail"
            print("# " + _pw.gate_line(gate), file=sys.stderr)
        except Exception as e:  # pragma: no cover - defensive
            result["gate"] = {"status": "error", "note": str(e)[:300]}
            print(f"# perfwatch: gate errored: {e}", file=sys.stderr)
    print(json.dumps(result))
    print(f"# {nchips} chip(s), spread {min(rates):.0f}-{max(rates):.0f} "
          f"img/sec over {args.num_iters} iters, "
          f"platform={dev0.platform} ({dev0.device_kind})",
          file=sys.stderr)
    if gate_failed:
        # The one JSON line above already carries the verdict; the
        # nonzero exit is what CI keys on (docs/benchmarks.md
        # "Regression gate").
        raise SystemExit(4)


if __name__ == "__main__":
    main()
