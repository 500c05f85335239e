"""``setup_compile_s`` (layer: entry): seconds of tracing, lowering and
backend compile over every program the process has compiled when the
metrics are read: the step, the weights, the batch, the broadcast, the
rank check, the digest and every small eager program (the records of the
program's compile log; a trace nested in another is counted once). The
reference compiles later and is not in it. ``None`` from a program
without the log."""

from benchmark.harness import setup_log


def read(context):
    records = setup_log.records()
    if records is None:
        return None
    return float(sum(r[stage] for r in records
                     for stage in setup_log.STAGES))
