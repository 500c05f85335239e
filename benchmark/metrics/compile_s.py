"""``compile_s`` (layer: entry): the host's seconds round the step's
``lower().compile()``. Cold on a cell's first run in a checkout; after
that the executable comes from the persistent cache and what is left is
tracing and lowering in Python."""


def read(context):
    return context.system.build_s["compile"]
