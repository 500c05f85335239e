"""``sel_scan_ms_per_step`` (layer: kernels): device milliseconds a step
spends in the selective scan of ``ops/selective_scan.py`` (scope
``sel_scan``): every chunk's walk from a zero state, the carry between
chunks and the entering states' part of the output, forward and
recomputed; and the backward pass, the states again a group of chunks at
a time and the cotangents' walk. ``None`` for a program without the
name."""

from benchmark.harness import scopes


def read(context):
    return scopes.per_step_ms(context, ("sel_scan",))
