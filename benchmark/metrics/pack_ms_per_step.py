"""``pack_ms_per_step`` (layer: frontend): device milliseconds a step
spends between the gradient tree and the collective's operand and back:
the ops the program issues under ``hvd_pack`` (ravel, per-dtype
concatenate, pad, compress, quantize) and ``hvd_unpack`` (slices and
reshapes back to leaves, decompress, the average), and the copies the
compiler puts in for them (``benchmark/harness/phases.py``). Near zero
where the exchange is elided: the fused update's own ravel of the small
tensors is all that is left. ``None`` where the compiled step holds none
of the program's phase names."""

from benchmark.harness import phases


def read(context):
    return phases.per_step_ms(context, ("hvd_pack", "hvd_unpack"),
                              program_names=True)
