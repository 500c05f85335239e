"""``mfu`` (layer: models), in percent: the FLOPs the forward and
backward passes need for one item, from shapes (the family's
``model_flops_per_item``: no optimizer, no recompute), times the items a
chip completed per second in the untraced stretch before the capture,
over the chip's published peak. It is throughput scaled by a constant,
and says how far from the chip a cell is. Never from ``cost_analysis``."""


def read(context):
    flops = context.family.model_flops_per_item(
        context.cell.config, context.cell.traffic)
    return (100.0 * flops * context.items_per_s_per_chip
            / context.peaks["bf16_flops_per_s"])
