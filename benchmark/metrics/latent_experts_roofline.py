"""``latent_experts_roofline`` (layer: parallel), in percent: the least
time the chip could take for the routed experts' products of one step in
a latent expert block, over the time under ``moe_experts``. Built as
``moe_experts_roofline`` is, for experts of two matrices at latent x
width: per block the least time is the larger of FLOPs over the published
bf16 peak and bytes over the published HBM bandwidth. FLOPs are what the
algorithm needs: three passes (forward, and the two of the backward pass)
of the two products of a relu^2 expert, 2 x latent x width each, for
every assignment kept (the program's own counter, of the last step); not
the recompute. Bytes are one read of the held experts' weights in the
compute dtype. It counts the same work whatever implements it. ``None``
without the counters, the name or a latent."""

from benchmark.harness import scopes


def experts_flops(assignments, latent, width):
    return 3 * 2 * 2.0 * assignments * latent * width


def experts_bytes(held, latent, width, itemsize=2):
    return 2.0 * held * latent * width * itemsize


def floor_seconds(kept_per_block, config, peaks):
    latent, width = config["moe_latent_size"], config["moe_intermediate_size"]
    return sum(
        max(experts_flops(int(kept.sum()), latent, width)
            / peaks["bf16_flops_per_s"],
            experts_bytes(len(kept), latent, width)
            / peaks["hbm_bytes_per_s"])
        for kept in kept_per_block)


def read(context):
    if "moe_latent_size" not in context.cell.config:
        return None
    ms = scopes.per_step_ms(context, ("moe_experts",))
    counters = scopes.routing_counters(context)
    if not ms or counters is None:
        return None
    floor = floor_seconds(counters["expert_kept"], context.cell.config,
                          context.peaks)
    return 100.0 * floor / (ms / 1e3)
