"""``ssm_scan_roofline`` (layer: kernels), in percent: the least time the
chip could take for the state-space scans of one step, over the time
under ``ssm_scan``. Per ``M`` block the least time is the larger of FLOPs
over the published bf16 peak and bytes over the published HBM bandwidth.

FLOPs are those of the chunked form at the published chunk Q, three
passes (forward, and the two of the backward pass) of, a token: the
scores C.B^T of each of G groups (2 Q N), the scores against x of each
of H heads (2 Q P), a chunk's end state and the entering state's part of
the output (2 N P each a head). Bytes, a token, in the compute dtype:
x (H P), B and C (G N each) and dt (H) read forward and again backward,
y written and dy read (H P each), the four gradients written: 5 H P +
6 G N + 3 H. Not the recompute, and nothing of the (Q, Q) tiles or the
states: an implementation that keeps them on the chip moves none of
them. It counts the same work whatever implements the scan. ``None``
without the name or for a configuration with no state-space block."""

from benchmark.harness import scopes


def scan_flops(tokens, q, n, groups, p, heads):
    return 3.0 * tokens * (2 * q * n * groups + 2 * q * p * heads
                           + 4 * n * p * heads)


def scan_bytes(tokens, n, groups, p, heads, itemsize=2):
    return float(itemsize * tokens * (5 * heads * p + 6 * groups * n
                                      + 3 * heads))


def floor_seconds(config, traffic, peaks):
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    blocks = config["hybrid_override_pattern"][
        :config["num_hidden_layers"]].count("M")
    n, groups = config["ssm_state_size"], config["n_groups"]
    p, heads = config["mamba_head_dim"], config["mamba_num_heads"]
    return blocks * max(
        scan_flops(tokens, config["chunk_size"], n, groups, p, heads)
        / peaks["bf16_flops_per_s"],
        scan_bytes(tokens, n, groups, p, heads) / peaks["hbm_bytes_per_s"])


def read(context):
    config = context.cell.config
    if "hybrid_override_pattern" not in config:
        return None
    ms = scopes.per_step_ms(context, ("ssm_scan",))
    if not ms:
        return None
    return 100.0 * floor_seconds(config, context.cell.traffic,
                                 context.peaks) / (ms / 1e3)
