"""``broadcast_s`` (layer: frontend): the host's seconds in
``broadcast_parameters``, from the span the program opens round it: on
one chip what the world-size-1 path costs, on four the packing of the
529 MB buffer, the collective's compile and its dispatch (the device
finishes behind it, inside the harness's ``broadcast`` mark). ``None``
from a program without the log."""

from benchmark.harness import setup_log


def read(context):
    return setup_log.span_seconds("hvd.broadcast_parameters")
