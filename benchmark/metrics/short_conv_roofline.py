"""``short_conv_roofline`` (layer: models), in percent: the least time the
chip could take for the gated short convolutions of one step, over the
time under ``short_conv``. Per ``C`` layer the least time is the larger of
FLOPs over the published bf16 peak and bytes over the published HBM
bandwidth.

Bytes, a token, in the compute dtype: forward reads B, C and x (3 d) and
writes y (d); backward reads dy (d) and B, C and x again (3 d) and writes
their gradients (3 d): 11 d. Not the recompute, and none of the float32
temporaries, which an implementation that fuses the operator keeps on the
chip. FLOPs, a token: 3 passes x (the two products and 2 x kernel for the
taps) x d: bytes set the floor. It counts the same work whatever
implements the operator. ``None`` without the name or for a
configuration with no such layer."""

from benchmark.harness import scopes


def conv_flops(tokens, channels, kernel):
    return 3.0 * tokens * (2 + 2 * kernel) * channels


def conv_bytes(tokens, channels, itemsize=2):
    return float(itemsize * tokens * 11 * channels)


def floor_seconds(layers, config, traffic, peaks):
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    channels, kernel = config["hidden_size"], config["conv_L_cache"]
    return layers * max(
        conv_flops(tokens, channels, kernel) / peaks["bf16_flops_per_s"],
        conv_bytes(tokens, channels) / peaks["hbm_bytes_per_s"])


def read(context):
    config = context.cell.config
    if "conv_L_cache" not in config:
        return None
    ms = scopes.per_step_ms(context, ("short_conv",))
    if not ms:
        return None
    layers = context.family.kinds(config).count("C")
    return 100.0 * floor_seconds(layers, config, context.cell.traffic,
                                 context.peaks) / (ms / 1e3)
