"""``collective_exposed_ms_per_step`` (layer: compiled collectives): the
part of ``collective_ms_per_step`` during which no other op runs on the
device's sequencer: the exchange that compute did not hide."""

from benchmark.harness import xtrace


def read(context):
    return context.per_step_ms(
        [xtrace.collective_seconds(d, context.window)[1]
         for d in context.capture.devices])
