"""The ``lfm2_lm`` family's tiny cell, which only the tests can reach: the
harness end to end on the CPU as ``tiny_cells.py`` drives it (flash
kernels interpreted, the grouped expert products as they are). Run as
``python tiny_lfm2_cell.py [control]``. Not a benchmark: a time from here
is never a device metric.

Given a workload of ``BENCHMARK.json`` and a control (``tiny_lfm2_cell.py
lfm2_8b_a1b_ep4_s8192x2 <control> <seed> <seconds>``) it plants the
control under that cell at its own size, on the chip only: how
``PERF.md``'s readings of what ``correct`` can see were taken. Controls
of the timed path, each of which the harness has to read ``correct:
false``: ``no_conv`` (the gated short convolution's two earlier taps left
out: each position reads its own ``B * x`` alone), ``no_routed``
(``tiny_decoder_cell``'s: the routed experts' output left out) and
``no_qk_norm`` (the queries and keys scaled and not normed). Control of
the reference, which the sound timed path is then compared with:
``fp8_reference`` (``tiny_decoder_cell``'s: its products in one bfloat16
pass on parameters rounded to float8_e4m3fn, the precision below the
stated one). Not a control: ``f32_program`` (the timed path as it is, in
float32 with every product at ``highest``, the reference's own precision),
which shows how much of a sound run's distance from the reference is the
rounding of bfloat16."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny_cells  # noqa: E402  (puts the repo on the path)
import tiny_decoder_cell  # noqa: E402

LFM2 = dict(
    family="lfm2_lm", model_type="lfm2_moe", conv_L_cache=3,
    conv_bias=False, hidden_size=32, intermediate_size=64,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    moe_intermediate_size=16, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=4, num_dense_layers=2, num_experts=4,
    num_experts_per_tok=4, num_hidden_layers=5, num_key_value_heads=2,
    rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
    vocab_size=64,
    published=dict(num_hidden_layers=6, num_experts=16, vocab_size=256),
    layers_held=[0, 2, 3, 4, 5], first_expert=0, vocab_first_row=0,
    router_eps=1e-6, compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    # a sound tiny run reads 0.0072 by the loss and 0.27 over all leaves
    # (0.42 by the worst, a norm's scale: said, not compared); the four
    # controls 0.031 to 0.25 and 0.68 to 1.37
    loss_tolerance=dict(abs=0.02),
    update_tolerance=dict(rel=None, pooled_rel=0.4))
tiny_cells.CELLS["lfm2"] = (LFM2, dict(
    tiny_cells.TRAFFIC, per_chip_batch=2, seq_len=64, attention="flash",
    remat=True))

CONTROLS = ("no_conv", "no_routed", "no_qk_norm", "fp8_reference")
#: Not a control: the timed path in the reference's precision.
PLANTS = CONTROLS + ("f32_program",)


def _in_f32():
    """The timed path in float32 with every product at ``highest``: the
    tiny cell's configuration and any cell that ``BENCHMARK.json`` names."""
    import jax

    from benchmark.harness import spec

    LFM2["compute_dtype"] = "float32"
    load_cell = spec.load_cell

    def load_f32(name):
        cell = load_cell(name)
        cell.config["compute_dtype"] = "float32"
        return cell

    spec.load_cell = load_f32
    jax.config.update("jax_default_matmul_precision", "highest")


def plant(control: str):
    """Break the timed path underneath the harness and leave the
    reference, which imports nothing of the system, whole; or lower the
    reference's precision and leave the timed path sound; or raise the
    timed path's precision to the reference's."""
    import jax.numpy as jnp

    from horovod_tpu.models import decoder

    if control == "f32_program":
        _in_f32()
    elif control == "no_conv":
        decoder.causal_conv = lambda x, taps: x.astype(jnp.float32) * taps[-1]
    elif control == "no_qk_norm":
        decoder.head_rms = lambda x, scale, eps: (
            x.astype(jnp.float32) * scale).astype(x.dtype)
    elif control in ("no_routed", "fp8_reference"):
        tiny_decoder_cell.plant(control)
    else:
        raise SystemExit(f"control {control!r}: want one of {PLANTS}")


def main(argv) -> int:
    if argv and argv[0] not in PLANTS:  # a cell of BENCHMARK.json
        workload, control, seed, seconds = argv
        plant(control)
        return tiny_cells.main(workload, 1, "", int(seed), float(seconds))
    if argv:
        plant(argv[0])
    return tiny_cells.main("lfm2", 1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
