"""``flash_roofline`` (layer: kernels), in percent: the least time the
chip could take for the attention of one step, over the time the flash
kernels took (``flash_ms_per_step``'s). The least time is the larger of
FLOPs over the published bf16 peak and bytes over the published HBM
bandwidth; at d = 64 and s in the thousands the FLOPs bound it.

FLOPs are what the algorithm needs: two s x s x d matmuls forward
(q.k^T, p.v) and five backward (recomputing q.k^T once, dp, dv, dq, dk),
each 2.s.s.d per head, which is what the fused backward runs; the
two-kernel fallback runs seven, each kernel recomputing p, and is held
to the same five. A causal mask halves them. Bytes are one read of q,
k, v and one write of o forward; backward reads q, k, v, o, do and
writes dq, dk, dv; the row statistics are s floats a head and are left
out. ``None`` where no kernel ran."""

from benchmark.harness import spec


def attention_flops(b, s, heads, d, causal=False):
    matmul = 2.0 * b * heads * s * s * d
    return (2 + 5) * matmul * (0.5 if causal else 1.0)


def attention_bytes(b, s, heads, d, itemsize=2):
    tensor = b * s * heads * d * itemsize
    return (4 + 8) * tensor


def read(context):
    seconds = spec.load_module(
        "metrics", "flash_ms_per_step").kernel_seconds(context)
    if not any(seconds):
        return None
    config, traffic = context.cell.config, context.cell.traffic
    heads = config["num_attention_heads"]
    shape = (int(traffic["per_chip_batch"]), int(traffic["seq_len"]), heads,
             config["hidden_size"] // heads)
    layers = config["num_hidden_layers"]
    floor = layers * max(
        attention_flops(*shape) / context.peaks["bf16_flops_per_s"],
        attention_bytes(*shape) / context.peaks["hbm_bytes_per_s"])
    per_step = sum(seconds) / len(seconds) / context.traced_steps
    return 100.0 * floor / per_step
