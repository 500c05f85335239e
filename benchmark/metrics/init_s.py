"""``init_s`` (layer: frontend): seconds of ``hvd.init``, from the span the
program opens round it (devices, mesh, the host split). ``None`` from a
program without the log."""

from benchmark.harness import setup_log


def read(context):
    return setup_log.span_seconds("hvd.init")
