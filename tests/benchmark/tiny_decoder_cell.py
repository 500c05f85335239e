"""The ``decoder_lm`` family's tiny cell, which only the tests can reach:
the harness end to end on the CPU as ``tiny_cells.py`` drives it (flash
kernels interpreted, the grouped expert products as they are). Run as
``python tiny_decoder_cell.py [fault]``. The one fault of its own,
``no_routed``, leaves the routed experts' output out of the timed path
(the shared expert and everything else stay): the harness has to say
``correct: false``. Not a benchmark: a time from here is never a device
metric.

Given a workload of ``BENCHMARK.json`` and a control (``tiny_decoder_cell.
py laguna_s_ep32_s8192 <control> <seed> <seconds>``) it plants the control
under that cell at its own size, on the chip only: how ``PERF.md``'s
readings of what ``correct`` can see were taken. Controls of the timed
path: ``no_routed``; ``no_window`` (the banded layers attend over every
earlier position); ``bf16_scores`` (the router wholly in bfloat16: its
input, weights, logits, softmax, top-k and the weights it hands on);
``fp8_params`` (the loss computed on parameters rounded to float8_e4m3fn,
gradients straight through: the precision below the stated one, which the
loss cannot see and the parameter change has to).
Controls of the reference, which the sound timed path is then compared
with: ``bf16_reference`` (its products in one bfloat16 pass, as the
configuration's compute type) and ``fp8_reference`` (besides, every
parameter rounded to float8_e4m3fn: the precision below the stated one)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny_cells  # noqa: E402  (puts the repo on the path)

DECODER = dict(
    family="decoder_lm", hidden_size=32, head_dim=8, num_key_value_heads=2,
    num_hidden_layers=3, vocab_size=64, intermediate_size=64,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[4, 6, 4],
    mlp_layer_types=["dense", "sparse", "sparse"],
    sliding_window=16, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    num_experts=8, published={"num_experts": 16}, first_expert=0,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, moe_routed_scaling_factor=2.5,
    compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    loss_tolerance=dict(abs=0.02),
    # a sound tiny run reads 0.29 by the worst leaf and 0.15 over all
    # leaves, ``fp8_params`` 0.77 and 0.60, ``no_routed`` 1.32 and 1.02
    update_tolerance=dict(rel=0.5, pooled_rel=0.3))
tiny_cells.CELLS["decoder"] = (DECODER, dict(
    tiny_cells.TRAFFIC, per_chip_batch=2, seq_len=64, attention="flash",
    remat=True))

CONTROLS = ("no_routed", "no_window", "bf16_scores", "fp8_params",
            "bf16_reference", "fp8_reference")


def _bf16_route(x, router_w, top_k, scaling):
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    logits = jnp.dot(x.astype(bf16), router_w.astype(bf16))
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weight = scaling * top_p / top_p.sum(-1, keepdims=True)
    assert weight.dtype == bf16, weight.dtype
    return top_e, weight.astype(jnp.float32)


def to_e4m3(x):
    """``x`` rounded to the nearest value of float8_e4m3fn (4 bits of
    exponent, 3 of mantissa, subnormals below 2**-6, largest 448), in
    ``x``'s own type and by arithmetic. Not ``x.astype(float8_e4m3fn)
    .astype(x.dtype)``: the TPU's compiler takes a cast down and back up
    for excess precision it may keep, and elides the pair (my chip runs,
    PR 31: with the casts, the reference's first loss on "float8"
    parameters equalled the unrounded one to the bit)."""
    import jax.numpy as jnp

    _, exponent = jnp.frexp(x)  # |x| = m 2**exponent, m in [0.5, 1)
    step = jnp.ldexp(jnp.ones_like(x), jnp.maximum(exponent - 1, -6) - 3)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def _rounded(params):
    """``params`` rounded to float8_e4m3fn; gradients pass straight
    through the rounding."""
    import jax

    return jax.tree.map(
        lambda p: p + jax.lax.stop_gradient(to_e4m3(p) - p), params)


def _lowered(loss, float8_weights: bool):
    """The reference's loss with its products in one bfloat16 pass and,
    asked so, its parameters rounded to float8_e4m3fn."""
    import jax

    def lowered(params, extra, batch, config):
        if float8_weights:
            params = _rounded(params)
        with jax.default_matmul_precision("bfloat16"):
            return loss(params, extra, batch, config)

    return lowered


def plant(control: str):
    """Break the timed path underneath the harness and leave the
    reference, which imports nothing of the system, whole; or lower the
    reference's precision and leave the timed path sound."""
    import jax.numpy as jnp

    from benchmark.harness import spec
    from horovod_tpu.models import decoder
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.parallel import moe

    if control == "no_routed":
        whole = decoder.expert_share_layer

        def shared_only(x, *args, **kwargs):
            y, counts = whole(x, *args, **kwargs)
            return jnp.zeros_like(y), counts

        decoder.expert_share_layer = shared_only
    elif control == "no_window":
        banded = fa.flash_attention
        fa.flash_attention = lambda q, k, v, causal, window: banded(
            q, k, v, causal=causal)
    elif control == "bf16_scores":
        moe._route = _bf16_route
    elif control == "fp8_params":
        load_module = spec.load_module

        def load_rounded(kind, name):
            module = load_module(kind, name)
            if kind == "families":
                loss_fn = module.loss_fn
                module.loss_fn = lambda model, params, *rest: loss_fn(
                    model, _rounded(params), *rest)
            return module

        spec.load_module = load_rounded
    elif control in ("bf16_reference", "fp8_reference"):
        load_module = spec.load_module

        def load_lowered(kind, name):
            module = load_module(kind, name)
            if kind == "reference":
                module.loss = _lowered(module.loss,
                                       control == "fp8_reference")
            return module

        spec.load_module = load_lowered
    else:
        raise SystemExit(f"control {control!r}: want one of {CONTROLS}")


def main(argv) -> int:
    if argv and argv[0] not in CONTROLS:  # a cell of BENCHMARK.json
        workload, control, seed, seconds = argv
        plant(control)
        return tiny_cells.main(workload, 1, "", int(seed), float(seconds))
    if argv:
        plant(argv[0])
    return tiny_cells.main("decoder", 1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
