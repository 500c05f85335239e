"""``step_backend_s`` (layer: entry): seconds of the backend's part of the
step's compile (``backend_s`` of the step's record in the program's
compile log): XLA's compile on a cache miss, the read of the executable
on a hit. ``None`` from a program without the log."""

from benchmark.harness import setup_log


def read(context):
    return setup_log.step_stage("backend_s")
