"""``diff_attn_roofline`` (layer: kernels), in percent: the least time
the chip could take for the differential attention of one step, over the
time the flash kernels took (``flash_ms_per_step``'s seconds, the
recomputed forward included). Per attention layer the least time is the
larger of FLOPs over the published bf16 peak and bytes over the published
HBM bandwidth, by the visible pairs only: s(s+1)/2 in a full or cross
layer, sum_i min(i+1, window) in a banded one.

FLOPs are what the mathematics needs, not what four kernel calls a layer
run: a pair of query heads makes two score maps at the head size d, each
against a value 2 d wide. A map takes seven products (q.k^T and p.v
forward; q.k^T again, dp, dv, dq, dk backward), of which the three on the
value's side (p.v, dp, dv) are 2 x 2 d a visible pair and the other four
2 x d: 20 d a map. Visible pairs and bytes are ``attn_band_roofline``'s
(q, o, do and dq at the query heads, k, v, dk and dv at the key-value
heads; a pair's joined output is as wide as its two heads). It counts the
same work whatever implements it. ``None`` where no kernel ran or the
configuration has no such layer."""

from benchmark.harness import spec

MAP_FLOPS_PER_D = 4 * 2 + 3 * 2 * 2   # four products at d, three at 2 d


def layer_flops(b, s, heads, d, window=None):
    """Two maps for each of ``heads`` / 2 pairs of query heads."""
    return float(b * (heads // 2) * 2 * MAP_FLOPS_PER_D * d
                 * _band().visible_pairs(s, window))


def _band():
    """``attn_band_roofline``: the visible pairs and a layer's bytes are
    counted as it counts them."""
    return spec.load_module("metrics", "attn_band_roofline")


def floor_seconds(kinds, config, traffic, peaks):
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    total = 0.0
    for kind in kinds:
        if kind not in "SFX":
            continue
        window = config["sliding_window"] if kind == "S" else None
        total += max(
            layer_flops(b, s, heads, d, window) / peaks["bf16_flops_per_s"],
            _band().layer_bytes(b, s, heads, kv, d)
            / peaks["hbm_bytes_per_s"])
    return total


def read(context):
    config = context.cell.config
    if "layers_held" not in config:
        return None
    seconds = spec.load_module(
        "metrics", "flash_ms_per_step").kernel_seconds(context)
    if not any(seconds):
        return None
    per_step = sum(seconds) / len(seconds) / context.traced_steps
    return 100.0 * floor_seconds(
        context.family.kinds(config), config, context.cell.traffic,
        context.peaks) / per_step
