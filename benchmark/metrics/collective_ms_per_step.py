"""``collective_ms_per_step`` (layer: compiled collectives): device
milliseconds a step has an all-reduce, all-gather, reduce-scatter,
all-to-all or collective-permute running or in flight, mean over the
devices. Zero on one chip, where every collective is elided."""

from benchmark.harness import xtrace


def read(context):
    return context.per_step_ms(
        [xtrace.collective_seconds(d, context.window)[0]
         for d in context.capture.devices])
