"""``sel_scan_roofline`` (layer: kernels), in percent: the least time the
chip could take for the selective scans of one step, over the time under
``sel_scan``. Per ``M`` layer the least time is the larger of FLOPs over
the published bf16 peak and bytes over the published HBM bandwidth.

The work is T x C x N state updates (no matrix product exists: the decay
differs by channel and by state), ``UPDATE_FLOPS`` each (dt A, its exp,
the decay times the state, the input's product with B and its sum, the
product with C and its sum), in three passes: forward, and the two of the
backward pass (the states again, the cotangents). Bytes, a token, in the
compute dtype: x and dt (C each) and B and C (N each) read forward and
again backward, y written and dy read (C each), the four gradients
written: 8 C + 6 N. Not the recompute, and nothing of the states: an
implementation that keeps them on the chip moves none of them. It counts
the same work whatever implements the scan. ``None`` without the name or
for a configuration with no such layer."""

from benchmark.harness import scopes

UPDATE_FLOPS = 7


def scan_flops(tokens, channels, states):
    return 3.0 * tokens * channels * states * UPDATE_FLOPS


def scan_bytes(tokens, channels, states, itemsize=2):
    return float(itemsize * tokens * (8 * channels + 6 * states))


def floor_seconds(layers, config, traffic, peaks):
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    channels = config["mamba_expand"] * config["hidden_size"]
    states = config["mamba_d_state"]
    return layers * max(
        scan_flops(tokens, channels, states) / peaks["bf16_flops_per_s"],
        scan_bytes(tokens, channels, states) / peaks["hbm_bytes_per_s"])


def read(context):
    config = context.cell.config
    if "mamba_d_state" not in config:
        return None
    ms = scopes.per_step_ms(context, ("sel_scan",))
    if not ms:
        return None
    layers = context.family.kinds(config).count("M")
    return 100.0 * floor_seconds(layers, config, context.cell.traffic,
                                 context.peaks) / (ms / 1e3)
