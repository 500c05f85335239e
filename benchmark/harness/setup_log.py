"""What the program recorded of its own set-up, for the per-layer metrics
that move ``setup_s``: ``hvd.telemetry()["compile_log"]`` (the program's
``core/compile_log.py``: one record a compiled program with its stages,
cache result and the span that caused it, and the spans of ``hvd.init``
and ``broadcast_parameters``). Read inside a metric's ``read(context)``,
which runs before the system is released, before the reference compiles
anything and before ``hvd.shutdown``. A program without the log (the
parent of the PR that brought it) reads ``None`` everywhere, never an
error.
"""

from __future__ import annotations

from typing import Optional

#: The harness's step (``harness/step.py``) and the span ``hvd.jax.jit``
#: opens round it; its rank check is ``hvd.jax.jit:mean_rank``.
STEP_NAME = "train_step"
STEP_CAUSE = "hvd.jax.jit:" + STEP_NAME
STAGES = ("trace_s", "lower_s", "backend_s")


def compile_log() -> Optional[dict]:
    import horovod_tpu as hvd

    return hvd.telemetry().get("compile_log")


def records() -> Optional[list]:
    log = compile_log()
    return None if log is None else log["records"]


def step_stage(stage: str) -> Optional[float]:
    """``stage`` of the step's record: the program called ``train_step``
    that the step's span caused (the span may also cause one that lays
    out an argument), with the largest ``backend_s`` should there ever be
    two."""
    mine = [r for r in records() or ()
            if r["name"] == STEP_NAME and r["cause"] == STEP_CAUSE]
    if not mine:
        return None
    return float(max(mine, key=lambda r: r["backend_s"])[stage])


def span_seconds(name: str) -> Optional[float]:
    """Seconds of the spans called ``name``, summed (each of the
    harness's is opened once)."""
    log = compile_log()
    spans = [s for s in (log or {}).get("spans", ()) if s["name"] == name]
    if not spans:
        return None
    return float(sum(s["end"] - s["start"] for s in spans))
