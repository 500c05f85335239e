"""``moe_experts_roofline`` (layer: parallel), in percent: the least time
the chip could take for the routed experts' products of one step, over
the time under ``moe_experts``. Per sparse layer the least time is the
larger of FLOPs over the published bf16 peak and bytes over the published
HBM bandwidth. FLOPs are what the algorithm needs: three passes (forward,
and the two of the backward pass) of the three products of a SwiGLU
expert, 2 x hidden x width each, for every assignment kept (the
program's own counter, of the last step); not the recompute. Bytes are
one read of the held experts' weights in the compute dtype. It counts
the same work whatever implements it. ``None`` without the counters or
the name."""

from benchmark.harness import scopes


def experts_flops(assignments, hidden, width):
    return 3 * 3 * 2.0 * assignments * hidden * width


def experts_bytes(held, hidden, width, itemsize=2):
    return 3.0 * held * hidden * width * itemsize


def floor_seconds(kept_per_layer, config, peaks):
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    return sum(
        max(experts_flops(int(kept.sum()), hidden, width)
            / peaks["bf16_flops_per_s"],
            experts_bytes(len(kept), hidden, width)
            / peaks["hbm_bytes_per_s"])
        for kept in kept_per_layer)


def read(context):
    ms = scopes.per_step_ms(context, ("moe_experts",))
    counters = scopes.routing_counters(context)
    if not ms or counters is None:
        return None
    floor = floor_seconds(counters["expert_kept"], context.cell.config,
                          context.peaks)
    return 100.0 * floor / (ms / 1e3)
