"""``step_trace_s`` (layer: entry): seconds jax spent tracing the step in
Python, from the program's own compile log: ``trace_s`` of the record that
the span ``hvd.jax.jit:train_step`` caused (inner jitted functions and a
rematerialised block's second trace are in it; a program compiled
meanwhile is not). The part of ``compile_s`` that no cache takes away.
``None`` from a program without the log."""

from benchmark.harness import setup_log


def read(context):
    return setup_log.step_stage("trace_s")
