"""Compiled-or-interpreted choice for the pallas kernels in this package.

Off a TPU the kernels run in pallas interpret mode: the same code path
with scalar semantics, right for tests and CPU debugging and never a
device timing. The choice is reported, not silent: the first time a
kernel falls to interpret mode a warning is logged, and the kernel's
name is kept in :data:`INTERPRETED` so a run that must be on the chip
(``chip_smoke.py``) can assert that none did.
"""

from __future__ import annotations

import logging

import jax

LOG = logging.getLogger("horovod_tpu")

#: Names of the kernels that chose interpret mode in this process.
INTERPRETED: set = set()


def resolve_interpret(interpret, kernel: str) -> bool:
    """``interpret`` as given, or — for ``None`` — interpret mode exactly
    when the default backend is not a TPU."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if kernel not in INTERPRETED:
        INTERPRETED.add(kernel)
        LOG.warning(
            "pallas kernel %s runs in interpret mode (default backend is "
            "%r, not a TPU): results are right, timings mean nothing",
            kernel, backend)
    return True
