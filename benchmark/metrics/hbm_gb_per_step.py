"""``hbm_gb_per_step`` (layer: frontend): bytes moved to and from HBM in
a traced step, mean over the devices: the async copies' payload
(``Async XLA Ops``: first shape of each copy's HLO text) plus what the
compute fusions stream themselves (``XLA Ops``: their operand and result
shapes outside VMEM). It is what the fused update and the resident-state
work moved. Not counted: ``concatenate``, ``copy`` and slices that
stand alone as instructions, so it is no measure of a step whose traffic
sits in them (a flat buffer that is packed and sliced back). ``None``
where the op names carry no shapes."""

from benchmark.harness import xtrace


def read(context):
    window = context.window
    per_device = [xtrace.hbm_bytes(d, window)
                  for d in context.capture.devices]
    if not any(per_device):
        return None
    return sum(per_device) / len(per_device) / 1e9 / context.traced_steps
