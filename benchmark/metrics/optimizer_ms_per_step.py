"""``optimizer_ms_per_step`` (layer: frontend): device milliseconds a
step spends in ops of their own under ``hvd_optimizer``, the inner optax
update that ``DistributedOptimizer.update`` calls. An update that XLA
fused into a weight gradient's matmul is not here but with the matmul
(its time is the matmul's); ``mixed_ms`` on the ``phases`` log line says
how much time such fusions take. ``None`` where the compiled step holds
none of the program's phase names."""

from benchmark.harness import phases


def read(context):
    return phases.per_step_ms(context, ("hvd_optimizer",),
                              program_names=True)
