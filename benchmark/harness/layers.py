"""The traced run: what a per-layer metric's reader is given, and the
device times and breakdown of the result line.

A per-layer metric is ``benchmark/metrics/<name>.py`` with one function
``read(context)`` that returns a number, or ``None`` where it finds
nothing to read; the harness then leaves it out of the line. Its layer,
unit and the metric it moves are its entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import functools
import statistics
from typing import Any, Dict, Optional, Sequence, Tuple

from benchmark.harness import spec, xtrace


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    family: Any                  # benchmark/families/<family>.py
    system: Any                  # harness.step.System
    peaks: Optional[dict]        # this device's entry of peaks.json
    capture: xtrace.Capture
    window_span: str
    traced_steps: int
    items_per_s_per_chip: float  # of the untraced stretch before the capture

    @functools.cached_property
    def window(self) -> Tuple[float, float]:
        return xtrace.window_of(self.capture, self.window_span)

    def per_step_ms(self, seconds_per_device: Sequence[float]) -> float:
        """Mean over the devices of a per-device total, per traced step."""
        return (statistics.fmean(seconds_per_device) * 1e3
                / self.traced_steps)


def read_metrics(context: Context) -> Dict[str, Optional[float]]:
    return {m["name"]: spec.load_module("metrics", m["name"]).read(context)
            for m in context.cell.per_layer}


def device_times(context: Context) -> Dict[str, float]:
    """``busy_s`` averaged over the chips used, and ``window_s``."""
    window = context.window
    busy = xtrace.busy_seconds(context.capture, window)
    if not busy or min(busy) <= 0:
        raise RuntimeError("the capture shows a device on which no "
                           f"operation ran: busy seconds {busy}")
    return {"busy_s": statistics.fmean(busy),
            "window_s": (window[1] - window[0]) / 1e9}


def breakdown(context: Context, host_spans) -> Dict[str, list]:
    """Of the first device: its time by op category and its idle time by
    what the host loop was doing, in seconds of the traced window."""
    window = context.window
    dev = context.capture.devices[0]
    return {
        "device_ops": xtrace.device_ops_breakdown(dev, window),
        "idle_gaps": xtrace.idle_gaps_breakdown(
            context.capture, dev, window, host_spans),
    }
