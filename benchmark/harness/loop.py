"""One run of one cell: set-up, warm-up, the measured window, the traced
window, the reference, the result line.

The window is the user's loop: one asynchronous dispatch per step and
``jax.block_until_ready`` on the state every ``window_steps`` steps,
where a user would log the loss. It ends on the first such barrier after
``--seconds``. Everything before the first measured dispatch is
``setup_s``; the reference runs after the window and after the memory
peak is read, so neither its buffers nor its seconds are in a metric.
Before it runs the system's state and executable are given back
(``release``): the check holds the reference beside the resident batch
and nothing else, so a cell may fill the chip in its window.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List

from benchmark.harness import check, layers, spec, step, xtrace

#: Host spans the harness's loop writes into the profiler's trace.
HOST_SPANS = ("dispatch", "barrier", "fetch_loss")
WINDOW_SPAN = "bench_window"


def log(**fields):
    """An earlier line of stdout: one JSON object, never the last."""
    print(json.dumps(fields), flush=True)


def _window(system, n_calls: int, annotate) -> float:
    """``n_calls`` asynchronous dispatches, a barrier, the loss, each
    inside ``annotate(<span name>)``."""
    import jax

    state, loss = system.state, None
    for _ in range(n_calls):
        with annotate("dispatch"):
            *state, loss = system.compiled(*state, *system.batch)
    with annotate("barrier"):
        jax.block_until_ready(state)
    system.state = tuple(state)
    with annotate("fetch_loss"):
        return float(loss)


def _calls(system, steps: int) -> int:
    """Dispatches that make ``steps`` steps (a scan-fused call is several)."""
    return max(1, steps // system.steps_per_call)


def measure(system, window_steps: int, seconds: float) -> Dict:
    """Whole windows until ``seconds`` have passed; stalls inside count."""
    calls_per_window = _calls(system, window_steps)
    losses, ends = [], [0.0]
    t0 = time.perf_counter()
    while ends[-1] < seconds:
        losses.append(_window(system, calls_per_window, contextlib.nullcontext))
        ends.append(time.perf_counter() - t0)
    calls, wall = calls_per_window * len(losses), ends[-1]
    failed = sum(calls_per_window for x in losses if not math.isfinite(x))
    return {"wall_s": wall, "calls": calls, "losses": losses,
            "window_s": [b - a for a, b in zip(ends, ends[1:])],
            "steps": calls * system.steps_per_call,
            "failed_steps": failed * system.steps_per_call,
            "items_per_s_per_chip":
                calls * system.items_per_call / wall / system.n_chips}


def capture(system, window_steps: int, logdir: str) -> str:
    """One window under the profiler, with the loop's host spans; returns
    the capture's ``.xplane.pb``."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir)
    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            _window(system, _calls(system, window_steps),
                    jax.profiler.TraceAnnotation)
    files = xtrace.trace_files(logdir)
    if len(files) != 1:
        raise RuntimeError(f"the capture left {len(files)} .xplane.pb "
                           f"files under {logdir}, not one")
    return files[0]


def peak_bytes(devices) -> List[int]:
    """Per device, the most the runtime ever held: ``peak_bytes_in_use``
    plus ``peak_bytes_reserved``. On this installation a program's
    temporaries are counted as reserved and not as in use (read on the
    chip, PR 22: in use 0.33 GB and reserved 4.47 GB for a step whose
    ``memory_analysis()`` has 0.24 GB of arguments and 4.51 GB of
    temporaries), so the first alone would leave out most of a step. A
    TPU that reports neither is an error (XLA:CPU, which the tests run
    on, keeps no statistics)."""
    stats = [d.memory_stats() or {} for d in devices]
    keys = ("peak_bytes_in_use", "peak_bytes_reserved")
    if devices[0].platform == "tpu" and any(
            k not in s for s in stats for k in keys):
        raise RuntimeError(f"a device reports no {keys}: {stats}")
    return [sum(int(s.get(k, 0)) for k in keys) for s in stats]


def release(system, device) -> Dict:
    """Delete the arrays of ``system.state`` and drop the executable; what
    ``device`` held before and after, where the runtime says (XLA:CPU
    keeps no statistics). The deleted arrays stay in ``system.state``:
    they hold nothing, and a test can ask each whether it went. The
    batch, the HLO text and ``remake_weights`` stay for the reference
    and the verdict."""
    import jax

    def in_use(key):
        stats = device.memory_stats() or {}
        return {key: int(stats["bytes_in_use"])} \
            if "bytes_in_use" in stats else {}

    held = in_use("bytes_in_use_before")
    for leaf in jax.tree.leaves(system.state):
        leaf.delete()
    system.compiled = None
    return {**held, **in_use("bytes_in_use_after")}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> Dict:
    """Run ``cell`` once and return the result line's object.
    ``require_tpu=False`` is for the tests' tiny cells on the CPU; the
    command never passes it."""
    import jax

    t_imported = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(
            f"benchmark: jax found platform={platform!r} "
            f"({devices[0].device_kind!r}), no TPU; the benchmark measures "
            "the chip and never runs elsewhere")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: cell {cell.name!r} asks for "
                         f"{cell.chips} chip(s), jax found {len(devices)}")
    devices = devices[:cell.chips]
    peaks = spec.load_peaks(devices[0].device_kind) if require_tpu else None

    family = spec.load_module("families", cell.family)
    reference = spec.load_module("reference", cell.family)
    system = step.build(cell, family, seed, devices)
    log(phase="built", import_s=t_imported - t_start,
        devices_s=t_devices - t_imported, build_s=system.build_s,
        mean_rank=system.mean_rank, item=family.ITEM,
        memory_analysis=str(system.compiled.memory_analysis()))

    # Warm-up: the first steps one by one (their losses are what the
    # reference is held against), then ``warmup_steps`` more behind one
    # barrier (a whole window's worth in the first cells, so that the
    # first measured window is nothing the runtime has not done).
    window_steps = int(cell.traffic["window_steps"])
    first_losses = [_window(system, 1, contextlib.nullcontext)
                    for _ in range(check.REFERENCE_STEPS)]
    # What those steps made of the parameters, at a few thousand seeded
    # coordinates a leaf: the reference's change is held against it.
    t_digest = time.perf_counter()
    after_first = check.digester(system.state[0], seed)(system.state[0])
    digest_s = time.perf_counter() - t_digest
    _window(system, _calls(system, int(cell.traffic["warmup_steps"])),
            contextlib.nullcontext)
    setup_s = time.perf_counter() - t_start

    window = measure(system, window_steps, seconds)
    peak = peak_bytes(devices)
    log(phase="measured", setup_s=setup_s, digest_s=digest_s,
        peak_bytes=peak,
        memory_stats=devices[0].memory_stats(),
        **{k: window[k] for k in ("wall_s", "window_s", "steps",
                                  "failed_steps", "items_per_s_per_chip")},
        loss_first=first_losses[0], loss_last=window["losses"][-1])

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(peak)}
    values = {"throughput_per_chip": window["items_per_s_per_chip"],
              "peak_hbm_gb": max(peak) / 1e9, "setup_s": setup_s}
    breakdown = {}

    if trace:
        pb = capture(system, window_steps, os.path.join(
            spec.ROOT, ".cache", "benchmark_trace"))
        context = layers.Context(
            cell=cell, family=family, system=system, peaks=peaks,
            capture=xtrace.load(pb), window_span=WINDOW_SPAN,
            traced_steps=(_calls(system, window_steps)
                          * system.steps_per_call),
            items_per_s_per_chip=window["items_per_s_per_chip"])
        values = layers.read_metrics(context)
        device.update(layers.device_times(context))
        breakdown = {"breakdown": layers.breakdown(context, HOST_SPANS)}
        log(phase="traced", top_ops=xtrace.top_ops(
            context.capture.devices[0], context.window))
        declared = cell.per_layer
    else:
        declared = cell.end_to_end

    # The reference last: its buffers must not enter the peak above, and
    # the system's must not stand beside it.
    import horovod_tpu as hvd
    from horovod_tpu.ops import pallas_mode

    log(phase="released", **release(system, devices[0]))
    ref = check.reference_losses(cell, reference, system, devices[0], seed)
    update = check.update_gaps(ref["start"], after_first, ref["end"],
                               ref["gradient_rms"])
    compared = check.verdict(cell, system, first_losses, window["losses"],
                             ref["losses"], update, pallas_mode.INTERPRETED,
                             on_tpu=platform == "tpu")
    hvd.shutdown()
    by_leaf = update.pop("by_leaf")  # a line of its own, before the last two
    log(phase="update_by_leaf", **by_leaf)
    log(phase="checked", reference_s=ref["seconds"],
        system_losses=first_losses, reference_losses=ref["losses"],
        update=update,
        checks={name: c["ok"] for name, c in compared.items()})
    # Each number compared beside its limit: the end of standard error
    # is what the driver keeps of a run that is not correct.
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)

    return {
        "correct": all(c["ok"] for c in compared.values()),
        "attempted": window["steps"], "failed": window["failed_steps"],
        "metrics": {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in declared if values.get(m["name"]) is not None},
        "device": device, **breakdown,
        "compared": {name: [c["value"], c["limit"]]
                     for name, c in compared.items()}}
