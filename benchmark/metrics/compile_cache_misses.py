"""``compile_cache_misses`` (layer: entry): a count: programs of
``setup_programs`` that the persistent compile cache did not hold
(``cache == "miss"`` in the program's compile log): every cacheable
program on a tree's first run, none on its second. ``None`` from a
program without the log."""

from benchmark.harness import setup_log


def read(context):
    records = setup_log.records()
    if records is None:
        return None
    return float(sum(r["cache"] == "miss" for r in records))
