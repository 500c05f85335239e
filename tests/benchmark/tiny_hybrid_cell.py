"""The ``hybrid_lm`` family's tiny cell, which only the tests can reach:
the harness end to end on the CPU as ``tiny_cells.py`` drives it (flash
kernels interpreted; the chunked scan and the grouped expert products as
they are). Run as ``python tiny_hybrid_cell.py [control]``. Not a
benchmark: a time from here is never a device metric.

Given a workload of ``BENCHMARK.json`` and a control (``tiny_hybrid_cell.
py nemotron3_super_ep64_s8192 <control> <seed> <seconds>``) it plants the
control under that cell at its own size, on the chip only: how
``PERF.md``'s readings of what ``correct`` can see were taken. Controls
of the timed path, each of which the harness has to read ``correct:
false``: ``no_carry`` (the carry between the scan's chunks left out:
every chunk starts from a zero state) and ``no_routed`` (the routed
experts' output left out; the shared expert and everything else stay).
Control of the reference, which the sound timed path is then compared
with: ``fp8_reference`` (``tiny_decoder_cell``'s: its products in one
bfloat16 pass on parameters rounded to float8_e4m3fn, the precision
below the stated one)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny_cells  # noqa: E402  (puts the repo on the path)
import tiny_decoder_cell  # noqa: E402

HYBRID = dict(
    family="hybrid_lm", hidden_size=32, num_hidden_layers=5,
    hybrid_override_pattern="MEM*EMEME", vocab_size=64,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=16, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=0.0001, num_attention_heads=4, num_key_value_heads=1,
    head_dim=8, n_routed_experts=8, published={"n_routed_experts": 16},
    first_expert=0, num_experts_per_tok=4, n_group=1, topk_group=1,
    norm_topk_prob=True, mlp_hidden_act="relu2", moe_latent_size=16,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    routed_scaling_factor=5, layer_norm_epsilon=1e-5,
    compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    # a sound tiny run reads 0.019 by the loss (adamw at 1e-3 moves it by
    # 0.19 a step) and 0.21 over all leaves (0.74 by the worst, a block's
    # four ``D``: said, not compared); ``no_carry`` 0.020 and 0.40,
    # ``no_routed`` 0.031 and 1.15, ``fp8_reference`` 0.014 and 0.67
    loss_tolerance=dict(abs=0.03),
    update_tolerance=dict(rel=None, pooled_rel=0.3))
tiny_cells.CELLS["hybrid"] = (HYBRID, dict(
    tiny_cells.TRAFFIC, per_chip_batch=2, seq_len=128, attention="flash",
    remat=True))

CONTROLS = ("no_carry", "no_routed", "fp8_reference")


def plant(control: str):
    """Break the timed path underneath the harness and leave the
    reference, which imports nothing of the system, whole; or lower the
    reference's precision and leave the timed path sound."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid
    from horovod_tpu.ops import ssd

    if control == "no_carry":
        ssd._carry = (lambda decay, states, reverse=False:
                      jnp.zeros_like(states))
    elif control == "no_routed":
        whole = hybrid.expert_share_layer

        def shared_only(x, *args, **kwargs):
            y, counts = whole(x, *args, **kwargs)
            return jnp.zeros_like(y), counts

        hybrid.expert_share_layer = shared_only
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
    return tiny_cells.main("hybrid", 1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
