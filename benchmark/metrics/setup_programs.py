"""``setup_programs`` (layer: entry): a count: programs the process has
compiled when the metrics are read (records of the program's compile
log; the log keeps the first 256 whole). ``None`` from a program without
the log."""

from benchmark.harness import setup_log


def read(context):
    records = setup_log.records()
    return None if records is None else float(len(records))
