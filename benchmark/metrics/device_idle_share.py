"""``device_idle_share`` (layer: device), in percent: 1 minus the share
of the traced window in which any op runs on the device, on the worst
device. The window is the harness's own host span round one loop window
(dispatches, barrier, loss fetch), on the profiler's clock. It says
whether the host holds the chip back now that dispatch is per step."""

from benchmark.harness import xtrace


def read(context):
    window = context.window
    busy = xtrace.busy_seconds(context.capture, window)
    return 100.0 * (1.0 - min(busy) / ((window[1] - window[0]) / 1e9))
