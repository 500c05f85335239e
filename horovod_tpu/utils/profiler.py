"""XLA profile capture for the compiled hot path.

The reference times device-side work with CUDA events feeding the
timeline (reference: horovod/common/operations.cc:671-695 RECORD_EVENT /
WAIT_FOR_EVENTS); on TPU the compiled step is one fused XLA program, so
device-side spans come from the XLA profiler instead. This module makes
that a one-liner:

    from horovod_tpu.utils import profiler
    with profiler.profile("/tmp/prof"):
        for _ in range(3):
            loss = train_step(...)
        float(np.asarray(loss))   # real barrier INSIDE the trace

View with ``tensorboard --logdir /tmp/prof`` (profile plugin / xprof) or
convert the contained ``*.xplane.pb`` with Perfetto tooling. Collective
time appears inside the fused step program — on the hot path
communication is compiler-scheduled and overlapped with compute, which is
exactly what the trace shows.
"""

from __future__ import annotations

import contextlib
import glob
import os
from typing import Callable, Optional


class CaptureError(RuntimeError):
    """A profiler capture completed but produced no ``*.xplane.pb`` —
    raised loudly instead of letting callers iterate a silently empty
    ``trace_files()`` list (a missing trace read as "zero traffic" is
    worse than a crashed capture)."""


@contextlib.contextmanager
def profile(logdir: str):
    """Context manager capturing an XLA profiler trace into ``logdir``."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(str(logdir)):
        yield


def capture(fn: Callable, *args, logdir: str, iters: int = 3,
            barrier: Optional[Callable] = None) -> str:
    """Run ``fn(*args)`` ``iters`` times under the profiler and return the
    logdir. ``barrier`` (default: numpy-fetch the last output's first
    leaf) forces execution to finish inside the trace window (a
    device->host fetch cannot return before the last step has run).

    Raises :class:`CaptureError` when the capture lands no new
    ``*.xplane.pb`` under ``logdir`` (profiler plugin missing, a
    concurrent trace already active, or the runtime wrote nothing):
    every downstream consumer (xplane attribution, perf.jsonl records)
    would otherwise silently report an empty profile."""
    import jax
    import numpy as np

    before = set(trace_files(logdir)) if os.path.isdir(logdir) else set()
    out = None
    with profile(logdir):
        for _ in range(max(1, iters)):
            out = fn(*args)
        if barrier is not None:
            barrier(out)
        elif out is not None:
            leaf = jax.tree_util.tree_leaves(out)
            if leaf:
                # Slice ON DEVICE, then fetch: pulling a whole weight
                # array to the host inside the trace window would
                # pollute the captured profile.
                first = leaf[0]
                if hasattr(first, "ravel"):
                    first = first.ravel()[:1]
                np.asarray(first)
    new = [f for f in trace_files(logdir) if f not in before]
    if not new:
        raise CaptureError(
            f"profiler capture produced no *.xplane.pb under {logdir!r} "
            "(is another trace already active? is the profiler plugin "
            "available on this platform?) — refusing to return an empty "
            "capture")
    return logdir


def trace_files(logdir: str) -> list:
    """The captured xplane protobufs (empty list = capture failed)."""
    return sorted(glob.glob(os.path.join(
        logdir, "**", "*.xplane.pb"), recursive=True))
