"""``attn_band_roofline`` (layer: kernels), in percent: the least time
the chip could take for the causal and banded attention of one step,
over the time the flash kernels took (``flash_ms_per_step``'s
seconds, the recomputed forward included). Per layer the least time is
the larger of FLOPs over the published bf16 peak and bytes over the
published HBM bandwidth, by the visible pairs only: s(s+1)/2 in a full
causal layer, sum_i min(i+1, window) in a banded one.

FLOPs are what the algorithm needs: seven products (q.k^T and p.v
forward; q.k^T again, dp, dv, dq, dk backward) of 2 x pairs x head size
for each query head. Bytes: q, o, do and dq at the layer's query heads
(q and o read twice: forward and backward), k, v, dk and dv at the
key-value heads (k and v read twice); the row statistics are left out.
It counts the same work whatever implements it. ``None`` where no kernel
ran or the configuration has no layer kinds."""

from benchmark.harness import spec


def visible_pairs(s, window=None):
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def layer_flops(b, s, heads, d, window=None):
    return 7 * 2.0 * b * heads * visible_pairs(s, window) * d


def layer_bytes(b, s, heads, kv_heads, d, itemsize=2):
    return float(b * s * d * itemsize * (6 * heads + 6 * kv_heads))


def floor_seconds(config, traffic, peaks):
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    d, kv = config["head_dim"], config["num_key_value_heads"]
    n = config["num_hidden_layers"]
    total = 0.0
    for kind, heads in zip(config["layer_types"][:n],
                           config["num_attention_heads_per_layer"][:n]):
        window = (config["sliding_window"] if kind == "sliding_attention"
                  else None)
        total += max(
            layer_flops(b, s, heads, d, window) / peaks["bf16_flops_per_s"],
            layer_bytes(b, s, heads, kv, d) / peaks["hbm_bytes_per_s"])
    return total


def read(context):
    config = context.cell.config
    if "layer_types" not in config:
        return None
    seconds = spec.load_module(
        "metrics", "flash_ms_per_step").kernel_seconds(context)
    if not any(seconds):
        return None
    per_step = sum(seconds) / len(seconds) / context.traced_steps
    return 100.0 * floor_seconds(config, context.cell.traffic,
                                 context.peaks) / per_step
