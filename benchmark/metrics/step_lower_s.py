"""``step_lower_s`` (layer: entry): seconds jax spent lowering the step's
jaxpr to an MLIR module (``lower_s`` of the step's record in the
program's compile log); like the tracing, paid on a warm cache too.
``None`` from a program without the log."""

from benchmark.harness import setup_log


def read(context):
    return setup_log.step_stage("lower_s")
