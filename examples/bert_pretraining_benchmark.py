#!/usr/bin/env python
"""BERT-base pretraining benchmark — the tensor-fusion stress config of
BASELINE.json (many large gradient buckets). Measures tokens/sec/chip for
the compiled data-parallel training step with fused per-dtype gradient
allreduce.

Run: PYTHONPATH=. python examples/bert_pretraining_benchmark.py \
         --layers 2 --hidden 128 --seq-len 128 --steps 4
"""

import argparse
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
from horovod_tpu.common.compile_cache import enable_compile_cache
from horovod_tpu.models import TransformerConfig, TransformerLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--batch-size", type=int, default=8,
                    help="per-chip batch")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--steps-per-call", type=int, default=5,
                    help="steps fused into one dispatch via lax.scan "
                         "(amortizes per-call host latency)")
    ap.add_argument("--unroll", type=int, default=5,
                    help="scan unroll factor: lets XLA software-pipeline "
                         "across step boundaries")
    ap.add_argument("--bf16", action="store_true", default=True)
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each layer (HBM for FLOPs)")
    ap.add_argument("--flash", action="store_true",
                    help="use the pallas flash-attention kernel "
                         "(forward + backward) instead of stock attention")
    ap.add_argument("--dropout", action="store_true",
                    help="train with the model's dropout active (0.1): "
                         "the pretraining-realistic configuration; "
                         "default off isolates compute throughput")
    ap.add_argument("--fused-loss", action="store_true",
                    help="chunked LM-head cross-entropy: never "
                         "materializes the [tokens, vocab] logits "
                         "(ops/chunked_loss.py)")
    ap.add_argument("--loss-chunk", type=int, default=1024,
                    help="vocab tile width for --fused-loss (1024 is the "
                         "largest that fits the 16 MB scoped-VMEM stack)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture an XLA profiler trace of one timed "
                         "window (summarize: python -m "
                         "horovod_tpu.utils.xplane DIR)")
    args = ap.parse_args()

    if args.dropout and "JAX_DEFAULT_PRNG_IMPL" not in os.environ:
        # Counter-based rbg keys: threefry key derivation/mask generation
        # costs ~17% of the BERT-base step (measured, docs/benchmarks.md);
        # rbg brings active dropout to ~5%. Env var overrides.
        jax.config.update("jax_default_prng_impl", "rbg")

    enable_compile_cache()
    hvd.init()
    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.flash_attention import flash_attention

        attention_fn = flash_attention  # BERT is bidirectional
    cfg = TransformerConfig(
        vocab_size=args.vocab, num_layers=args.layers,
        num_heads=args.heads, hidden_dim=args.hidden,
        mlp_dim=4 * args.hidden, max_len=args.seq_len,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=args.remat, attention_fn=attention_fn)
    model = TransformerLM(cfg)
    # fused_update: tiny layernorm/bias tensors update through per-dtype
    # buffers (horovod_tpu/jax/fused.py) — adamw is elementwise.
    opt = hvd_jax.DistributedOptimizer(
        optax.adamw(1e-4, weight_decay=0.01), fused_update=True)

    rng = np.random.RandomState(0)
    tokens = rng.randint(
        0, args.vocab,
        size=(args.batch_size * hvd.local_size(), args.seq_len)
    ).astype(np.int32)

    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:1]))
    params = hvd_jax.broadcast_parameters(variables["params"])
    opt_state = opt.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(params))
    print(f"# params: {n_params/1e6:.1f}M, {hvd.size()} chip(s)")

    # deterministic=False + a per-step rng = the pretraining-realistic
    # dropout configuration (--dropout); the default isolates compute.
    det = not args.dropout

    def _apply(params, toks, dk, **kw):
        rngs = {"dropout": dk} if args.dropout else None
        return model.apply({"params": params}, toks, deterministic=det,
                           rngs=rngs, **kw)

    if args.fused_loss:
        from horovod_tpu.ops.chunked_loss import fused_softmax_cross_entropy

        def loss_fn(params, toks, dk):
            hidden = _apply(params, toks, dk, return_hidden=True)
            tgt = jnp.roll(toks, -1, axis=1)
            head = params["lm_head"]
            return fused_softmax_cross_entropy(
                hidden, head["kernel"], head["bias"], tgt,
                block_v=args.loss_chunk).mean()
    else:
        def loss_fn(params, toks, dk):
            logits = _apply(params, toks, dk)
            tgt = jnp.roll(toks, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

    def one_step(params, opt_state, key, toks):
        if args.dropout:
            key, dk = jax.random.split(key)
        else:
            dk = key  # unused (rngs=None): the stock program keeps its
            # published shape — no live split in the scan body
        loss, g = jax.value_and_grad(loss_fn)(params, toks, dk)
        updates, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, key, \
            hvd_jax.allreduce(loss)

    spc = max(1, args.steps_per_call)

    @hvd_jax.jit(in_specs=(P(), P(), P(), P(hvd_jax.HVD_AXIS)),
                 out_specs=(P(), P(), P(), P()), donate_argnums=(0, 1))
    def step(params, opt_state, key, toks):
        if spc == 1:
            return one_step(params, opt_state, key, toks)

        def body(carry, _):
            params, opt_state, key = carry
            params, opt_state, key, loss = one_step(params, opt_state,
                                                    key, toks)
            return (params, opt_state, key), loss

        (params, opt_state, key), losses = jax.lax.scan(
            body, (params, opt_state, key), None, length=spc,
            unroll=max(1, args.unroll))
        return params, opt_state, key, losses[-1]

    toks = jnp.asarray(tokens)
    # Per-PROCESS dropout stream: data-parallel replicas must not apply
    # correlated masks (chips within one controller still share a mask —
    # acceptable for a benchmark; per-chip streams would fold in
    # ops.axis_rank() inside the step).
    step_key = jax.random.fold_in(jax.random.PRNGKey(1), hvd.rank())
    # AOT compile: reuse the executable AND read XLA's own FLOP count so
    # the printout carries MFU (cost analysis counts a scan body once).
    flops_per_step = 0.0
    counted = 1  # scan steps cost_analysis holds (set with flops below)
    step_fn = step
    try:
        compiled = step.lower(params, opt_state, step_key,
                              toks).compile()
        step_fn = compiled
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        from horovod_tpu.utils.hardware import scan_cost_analysis_steps

        # Scan body + peeled remainder each counted once.
        counted = scan_cost_analysis_steps(spc, args.unroll)
        flops_per_step = float(ca.get("flops", 0.0)) / counted
    except Exception as exc:  # pragma: no cover
        print(f"# cost_analysis unavailable: {exc}", file=sys.stderr)

    ncalls_warm = max(1, args.warmup // spc)
    ncalls = max(1, args.steps // spc)
    nsteps = ncalls * spc
    for _ in range(ncalls_warm):
        params, opt_state, step_key, loss = step_fn(params, opt_state,
                                                    step_key, toks)
    # Real device->host fetch: returns only once every step has run.
    float(np.asarray(loss))

    if args.profile:
        from horovod_tpu.utils import profiler

        with profiler.profile(args.profile):
            for _ in range(ncalls):
                params, opt_state, step_key, loss = step_fn(
                    params, opt_state, step_key, toks)
            float(np.asarray(loss))  # fetch barrier INSIDE the trace
        print(f"# profile: {len(profiler.trace_files(args.profile))} "
              f"xplane file(s) in {args.profile}", file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(ncalls):
        params, opt_state, step_key, loss = step_fn(params, opt_state,
                                                    step_key, toks)
    float(np.asarray(loss))
    dt = time.perf_counter() - t0
    step_time = dt / nsteps
    tok_per_sec = args.batch_size * args.seq_len / step_time
    seq_per_sec = args.batch_size / step_time
    from horovod_tpu.utils.hardware import peak_flops

    peak = peak_flops(jax.devices()[0])
    if peak and flops_per_step / step_time > peak:
        # Value was pre-divided by `counted`: recover one step's FLOPs as
        # raw/spc, where the count came out over the chip's peak.
        flops_per_step *= counted / spc
    mfu = flops_per_step / step_time / peak if peak and flops_per_step \
        else float("nan")
    print(f"tokens/sec/chip: {tok_per_sec:.0f}  "
          f"sequences/sec/chip: {seq_per_sec:.2f}  "
          f"step_ms: {step_time*1e3:.2f}  mfu: {mfu:.3f}  "
          f"loss={float(loss):.3f}")


if __name__ == "__main__":
    main()
