"""The ``sambay_lm`` family's tiny cell, which only the tests can reach:
the harness end to end on the CPU as ``tiny_cells.py`` drives it (flash
kernels interpreted; the selective scan as it is). Run as ``python
tiny_sambay_cell.py [control]``. Not a benchmark: a time from here is
never a device metric.

Given a workload of ``BENCHMARK.json`` and a control (``tiny_sambay_cell.
py phi4_mini_flash_v8_s8192 <control> <seed> <seconds>``) it plants the
control under that cell at its own size, on the chip only: how
``PERF.md``'s readings of what ``correct`` can see were taken. Controls
of the timed path, each of which the harness has to read ``correct:
false``: ``no_carry`` (the carry between the scan's chunks left out, in
both passes: every chunk starts from a zero state) and ``no_lambda``
(differential attention without its subtracted map: the timed path reads
lambda vectors that make lambda 0, so ``a1`` alone goes into the norm;
the reference reads the seeded ones). Control of the reference, which the sound timed path is
then compared with: ``fp8_reference`` (``tiny_decoder_cell``'s: its
products in one bfloat16 pass on parameters rounded to float8_e4m3fn,
the precision below the stated one)."""

import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny_cells  # noqa: E402  (puts the repo on the path)
import tiny_decoder_cell  # noqa: E402

SAMBAY = dict(
    family="sambay_lm", hidden_size=32, intermediate_size=64,
    layer_norm_eps=1e-5, mb_per_layer=2, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=6, sliding_window=16,
    vocab_size=64, published=dict(num_hidden_layers=32, vocab_size=512),
    layers_held=[0, 1, 16, 17, 18, 19], tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, hidden_act="silu", embd_pdrop=0,
    resid_pdrop=0, mamba_d_state=4, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=2, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, scan_chunk=8, compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    # a sound tiny run reads 0.007 by the loss (adamw at 1e-3 moves it by
    # 0.1 a step) and 0.14 over all leaves (0.59 by the worst, a bias of
    # the window layer: said, not compared); ``no_carry`` 0.011 and 0.63,
    # ``no_lambda`` 0.067 and 0.98, ``fp8_reference`` 0.049 and 0.58
    loss_tolerance=dict(abs=0.03),
    update_tolerance=dict(rel=None, pooled_rel=0.3))
tiny_cells.CELLS["sambay"] = (SAMBAY, dict(
    tiny_cells.TRAFFIC, per_chip_batch=2, seq_len=64, attention="flash",
    remat=True))

CONTROLS = ("no_carry", "no_lambda", "fp8_reference")


def lambda_zero(params):
    """``params`` with every differential mixer's lambda vectors set so
    that lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init is 0:
    lq1 = 0 (the first term is 1), lk2 = 1 and lq2 = log(1 +
    lambda_init) / d (the second is 1 + lambda_init). The layer's
    lambda_init, and with it the norm's factor 1 - lambda_init, stay."""
    import jax.numpy as jnp

    from horovod_tpu.models.sambay import lambda_init

    out = dict(params)
    for name, layer in params.items():
        mixer = layer.get("mixer", {}) if name.startswith("layer_") else {}
        if "lambda_q1" not in mixer:
            continue
        like = mixer["lambda_q1"]
        rate = math.log1p(lambda_init(int(name.split("_")[1])))
        out[name] = dict(layer, mixer=dict(
            mixer, lambda_q1=jnp.zeros_like(like),
            lambda_k2=jnp.ones_like(like),
            lambda_q2=jnp.full_like(like, rate / like.shape[0])))
    return out


def plant(control: str):
    """Break the timed path underneath the harness and leave the
    reference, which imports nothing of the system, whole; or lower the
    reference's precision and leave the timed path sound."""
    import jax.numpy as jnp

    from benchmark.harness import spec
    from horovod_tpu.ops import selective_scan

    if control == "no_carry":
        selective_scan._carry = (
            lambda decay, ends, start, reverse=False:
            (jnp.zeros_like(ends), start))
    elif control == "no_lambda":
        load_module = spec.load_module

        def load_without(kind, name):
            module = load_module(kind, name)
            if kind == "families":
                loss_fn = module.loss_fn
                module.loss_fn = lambda model, params, *rest: loss_fn(
                    model, lambda_zero(params), *rest)
            return module

        spec.load_module = load_without
    elif control == "fp8_reference":
        tiny_decoder_cell.plant(control)
    else:
        raise SystemExit(f"control {control!r}: want one of {CONTROLS}")


def main(argv) -> int:
    if argv and argv[0] not in CONTROLS:  # a cell of BENCHMARK.json
        workload, control, seed, seconds = argv
        plant(control)
        return tiny_cells.main(workload, 1, "", int(seed), float(seconds))
    if argv:
        plant(argv[0])
    return tiny_cells.main("sambay", 1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
