"""Process-wide telemetry: the one queryable answer to "how many
collectives ran, how big, how long, and who was late".

The framework has three execution paths — the compiled SPMD hot path
(hvd.jax.jit), the Python engine and the native C++ engine — and, before
this module, two disconnected lenses on them (chrome timeline, xplane
HBM tables). This registry is the common sink every
layer feeds (reference rationale: Horovod's production story leaned on
exactly this instrumentation — timeline + stall/straggler analysis,
arxiv 1802.05799 §5; step-time/traffic accounting is what turns a
one-chip benchmark into a scalable system, arxiv 1909.09756):

- :mod:`horovod_tpu.ops.collectives` counts per-op eager calls, bytes and
  world-size-1 elisions;
- :mod:`horovod_tpu.core.engine` (and the native engine through its stats
  C API) counts submissions, completions, errors, fusion-buffer batches
  and cycle time, and times negotiation rounds;
- :mod:`horovod_tpu.core.coordinator` distills per-process lateness from
  the negotiation round tables (the RANK_READY data) into the straggler
  report;
- :func:`horovod_tpu.jax.jit` and the keras Trainer record dispatch /
  step-time ring buffers for the compiled path;
- :mod:`horovod_tpu.core.compile_log` records every program jax
  compiles (by function, stage, cache result and the span that caused
  it), ``hvd.init`` and the parameter broadcast.

Four surfaces:

- ``hvd.telemetry()`` — nested dict snapshot (this module's
  :func:`telemetry`);
- ``hvd.telemetry_report()`` — human table (:func:`report`);
- ``HVD_TELEMETRY_FILE=<path>`` — Prometheus-style text exposition,
  flushed every ``HVD_TELEMETRY_INTERVAL`` seconds (default 15) and at
  exit;
- ``python -m horovod_tpu.utils.stats <file-or-live>`` — CLI over the
  exposition file (or an xplane capture dir / the live process).

No new dependencies; everything here is stdlib. All mutators are
thread-safe (engine background threads, framework threads and watchdogs
all feed the same registry).
"""

from __future__ import annotations

import atexit
import math
import os
import re
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Default bucket boundaries. Latencies span 100 µs (an engine cycle slice)
# to 30 s (a stalled negotiation); bytes span 256 B (a scalar metric) to
# 1 GiB (a fused gradient buffer).
LATENCY_BUCKETS_S = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3,
                     1.0, 3.0, 10.0, 30.0)
BYTES_BUCKETS = tuple(256 * 4 ** i for i in range(12))  # 256 B .. 1 GiB


def quantile_from_buckets(bounds: Sequence[float], counts: Sequence[int],
                          q: float) -> Optional[float]:
    """Quantile estimate from raw histogram bucket counts
    (``len(counts) == len(bounds) + 1``, overflow last). Log-interpolates
    inside the winning bucket — the latency buckets are log-spaced, so
    linear interpolation would bias every estimate toward the upper
    edge. The overflow bucket reports the last bound (a lower bound on
    the true value). None when the histogram is empty."""
    if len(counts) != len(bounds) + 1:
        raise ValueError(
            f"{len(counts)} counts for {len(bounds)} bounds")
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if c and cum >= target:
            if i >= len(bounds):
                return float(bounds[-1])
            hi = float(bounds[i])
            if i > 0:
                lo = float(bounds[i - 1])
            elif len(bounds) > 1:
                lo = hi * float(bounds[0]) / float(bounds[1])
            else:
                lo = hi / 2.0
            frac = (target - (cum - c)) / c
            return float(math.exp(
                math.log(lo) + frac * (math.log(hi) - math.log(lo))))
    return float(bounds[-1])  # pragma: no cover - cum >= target above


class Counter:
    """Monotonic counter (int or float increments)."""

    kind = "counter"
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self.value += n

    def snapshot(self):
        return self.value


class Gauge:
    """Last-write-wins instantaneous value (queue depth, occupancy)."""

    kind = "gauge"
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self.value = v

    def snapshot(self):
        return self.value


class Histogram:
    """Fixed-bucket histogram (no dynamic resizing — bounded memory, no
    allocation on the observe path)."""

    kind = "histogram"
    __slots__ = ("bounds", "counts", "sum", "count", "_lock")

    def __init__(self, bounds: Tuple[float, ...] = LATENCY_BUCKETS_S):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf overflow
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, v: float):
        i = 0
        for i, b in enumerate(self.bounds):
            if v <= b:
                break
        else:
            i = len(self.bounds)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def observe_many(self, vals):
        """Fold a whole batch of observations under ONE lock acquisition
        (the batched-submit telemetry path: per-value observe() would put
        N lock round-trips back on the submit fast path)."""
        if not vals:
            return
        n_bounds = len(self.bounds)
        idxs = []
        total = 0.0
        for v in vals:
            i = 0
            for i, b in enumerate(self.bounds):
                if v <= b:
                    break
            else:
                i = n_bounds
            idxs.append(i)
            total += v
        with self._lock:
            for i in idxs:
                self.counts[i] += 1
            self.sum += total
            self.count += len(idxs)

    def add_counts(self, deltas: Sequence[int], sum_delta: float = 0.0):
        """Fold per-bucket count deltas (``len(self.counts)`` entries,
        overflow last) plus the matching value-sum delta — the native
        engine's latency sync path: the C++ side observed into its own
        bucket array and hands over deltas, exactly like the stats
        counters, so the merged histogram stays exact (same buckets,
        summed counts)."""
        if len(deltas) != len(self.counts):
            raise ValueError(
                f"bucket-count mismatch: {len(deltas)} deltas for "
                f"{len(self.counts)} buckets")
        with self._lock:
            n = 0
            for i, d in enumerate(deltas):
                self.counts[i] += d
                n += d
            self.sum += sum_delta
            self.count += n

    def snapshot(self):
        with self._lock:
            buckets = {}
            cum = 0
            for b, c in zip(self.bounds, self.counts):
                cum += c
                if c:
                    buckets[b] = cum
            return {"count": self.count, "sum": self.sum,
                    "buckets": buckets}

    def cumulative(self):
        """(bounds, cumulative counts, total count, sum) read atomically —
        the exposition writer must not mix a locked snapshot with a
        second unlocked read of the live counts, or a concurrent observe
        lands a non-monotonic bucket series on a scraper."""
        with self._lock:
            cums, cum = [], 0
            for c in self.counts[:-1]:
                cum += c
                cums.append(cum)
            return self.bounds, cums, self.count, self.sum


class Ring:
    """Fixed-size ring buffer of recent observations (dispatch latencies,
    step times) — bounded memory, summarized at snapshot."""

    kind = "ring"
    __slots__ = ("_buf", "count", "total", "_lock")

    def __init__(self, size: int = 256):
        self._buf = deque(maxlen=size)
        self.count = 0
        self.total = 0.0
        self._lock = threading.Lock()

    def push(self, v: float):
        with self._lock:
            self._buf.append(v)
            self.count += 1
            self.total += v

    def values(self) -> List[float]:
        """The current window, oldest first (the fleet snapshot ships
        this for the console's step-time sparkline)."""
        with self._lock:
            return list(self._buf)

    def snapshot(self):
        with self._lock:
            window = list(self._buf)
        if not window:
            return {"count": 0}
        return {"count": self.count, "last": window[-1],
                "mean": sum(window) / len(window), "max": max(window),
                "window": len(window)}


class StragglerTracker:
    """Per-process cumulative imposed wait, distilled from the negotiation
    round tables (the same per-process readiness data the timeline's
    RANK_READY instants draw; reference: timeline.cc:106-130 +
    CheckForStalledTensors, operations.cc:1535-1581).

    For each tensor instance the coordinator hands us the time every
    process's announcement was first observed; process ``p`` is charged
    ``t_p - min(t)`` — the microseconds it kept the earliest-ready
    process waiting. Charges accumulate per process and per tensor
    *class* (the name with digits collapsed, so ``grad/17`` and
    ``grad/18`` aggregate)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.tensors = 0
        self.wait_us: Dict[int, int] = {}
        self.by_class: Dict[str, Dict[int, int]] = {}
        self._total_us = 0  # running sum of wait_us values (O(1) reads)

    def observe(self, name: str, announce_times: Dict[int, float]):
        if len(announce_times) < 2:
            return
        t0 = min(announce_times.values())
        cls = re.sub(r"\d+", "#", name)
        with self._lock:
            self.tensors += 1
            per_cls = self.by_class.setdefault(cls, {})
            for pid, t in announce_times.items():
                us = int((t - t0) * 1e6)
                self.wait_us[pid] = self.wait_us.get(pid, 0) + us
                per_cls[pid] = per_cls.get(pid, 0) + us
                self._total_us += us

    def total_wait_us(self) -> int:
        """Cumulative imposed wait across all processes — O(1), no map
        copies (the sentinel reads this on every observed step)."""
        with self._lock:
            return self._total_us

    def worst(self) -> Optional[Tuple[int, int]]:
        """(process, cumulative µs) of the rank that imposed the most
        wait, or None when nothing has been observed."""
        with self._lock:
            if not any(self.wait_us.values()):
                return None
            pid = max(self.wait_us, key=self.wait_us.get)
            return pid, self.wait_us[pid]

    def worst_line(self) -> str:
        """Stall-warning suffix naming the worst straggler (one phrasing
        shared by both engines' watchdogs and the coordinator), or ''."""
        worst = self.worst()
        if worst is None:
            return ""
        return (f"[historically slowest: process {worst[0]}, "
                f"{worst[1] / 1e3:.0f} ms cumulative imposed wait]")

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "tensors": self.tensors,
                "wait_us": dict(self.wait_us),
                "by_class": {c: dict(v) for c, v in self.by_class.items()},
            }

    def report_lines(self) -> List[str]:
        snap = self.snapshot()
        if not snap["tensors"]:
            return []
        out = [f"straggler report ({snap['tensors']} tensors observed):"]
        for pid, us in sorted(snap["wait_us"].items(),
                              key=lambda kv: -kv[1]):
            out.append(f"  process {pid}: kept the world waiting "
                       f"{us / 1e3:.1f} ms cumulative")
        for cls, per in sorted(snap["by_class"].items()):
            top = max(per, key=per.get)
            if per[top]:
                out.append(f"  {cls}: slowest process {top} "
                           f"(+{per[top] / 1e3:.1f} ms)")
        return out

    def reset(self):
        with self._lock:
            self.tensors = 0
            self.wait_us.clear()
            self.by_class.clear()
            self._total_us = 0


class Registry:
    """Name → metric store. Metric names are dotted paths
    (``engine.submitted.allreduce``); :meth:`snapshot` nests on the dots.
    ``sync`` callbacks let sources that cannot push per-event (the C++
    engine's counters) fold their state in right before a read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        self._syncs: List[Callable[[], None]] = []

    # -- metric accessors (get-or-create) -----------------------------------

    def _get(self, name: str, factory):
        # Any metric touch arms the HVD_TELEMETRY_FILE exporter: engine-
        # only or compiled-only workloads must produce the exposition
        # file too, not just paths that happen to call telemetry().
        # Cost once armed/absent: one global-flag check.
        _maybe_start_exporter()
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory()
            return m

    def counter(self, name: str) -> Counter:
        m = self._get(name, Counter)
        if not isinstance(m, Counter):
            raise TypeError(f"{name} is a {m.kind}, not a counter")
        return m

    def gauge(self, name: str) -> Gauge:
        m = self._get(name, Gauge)
        if not isinstance(m, Gauge):
            raise TypeError(f"{name} is a {m.kind}, not a gauge")
        return m

    def histogram(self, name: str,
                  bounds: Tuple[float, ...] = LATENCY_BUCKETS_S) -> Histogram:
        m = self._get(name, lambda: Histogram(bounds))
        if not isinstance(m, Histogram):
            raise TypeError(f"{name} is a {m.kind}, not a histogram")
        return m

    def ring(self, name: str, size: int = 256) -> Ring:
        m = self._get(name, lambda: Ring(size))
        if not isinstance(m, Ring):
            raise TypeError(f"{name} is a {m.kind}, not a ring")
        return m

    # -- sync hooks (pull-model sources: the native engine) ------------------

    def register_sync(self, fn: Callable[[], None]):
        with self._lock:
            if fn not in self._syncs:
                self._syncs.append(fn)

    def unregister_sync(self, fn: Callable[[], None]):
        with self._lock:
            if fn in self._syncs:
                self._syncs.remove(fn)

    def _run_syncs(self):
        with self._lock:
            syncs = list(self._syncs)
        for fn in syncs:
            try:
                fn()
            except Exception:
                pass  # a dying engine must not take a snapshot down

    # -- views ---------------------------------------------------------------

    def flat(self) -> Dict[str, object]:
        """{dotted name: snapshot value} for every metric (post-sync)."""
        self._run_syncs()
        with self._lock:
            items = list(self._metrics.items())
        return {name: m.snapshot() for name, m in items}

    def flat_counters(self) -> Dict[str, object]:
        """Counters only (post-sync) — the delta-comparable subset."""
        self._run_syncs()
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if isinstance(m, Counter)]
        return {name: m.snapshot() for name, m in items}

    def flat_gauges(self) -> Dict[str, object]:
        """Gauges only (post-sync) — the spread-comparable subset the
        fleet rollup reports min/max over (queue depth, pool bytes)."""
        self._run_syncs()
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if isinstance(m, Gauge)]
        return {name: m.snapshot() for name, m in items}

    def histogram_counts(self) -> Dict[str, dict]:
        """{name: {bounds, counts (raw, overflow last), sum, count}} for
        every histogram (post-sync) — the mergeable form the fleet
        snapshot publishes: same buckets on every rank, so the world
        rollup sums counts exactly."""
        self._run_syncs()
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if isinstance(m, Histogram)]
        out: Dict[str, dict] = {}
        for name, m in items:
            with m._lock:
                out[name] = {"bounds": list(m.bounds),
                             "counts": list(m.counts),
                             "sum": m.sum, "count": m.count}
        return out

    def ring_values(self) -> Dict[str, List[float]]:
        """{name: recent window} for every ring — the fleet snapshot's
        sparkline feed (step times, dispatch latencies)."""
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if isinstance(m, Ring)]
        return {name: m.values() for name, m in items}

    def snapshot(self) -> dict:
        """Nested dict of every metric (dots become nesting levels)."""
        flat = self.flat()
        out: dict = {}
        for name, val in flat.items():
            parts = name.split(".")
            d = out
            ok = True
            for p in parts[:-1]:
                nxt = d.setdefault(p, {})
                if not isinstance(nxt, dict):  # name-prefix collision
                    ok = False
                    break
                d = nxt
            if ok and not isinstance(d.get(parts[-1]), dict):
                d[parts[-1]] = val
            elif not isinstance(out.get(name), dict):
                out[name] = val  # keep the flat name instead
            # else: a single-segment name colliding with its own subtree
            # ('a' vs 'a.b') — drop the scalar rather than clobber the
            # subtree. Avoid prefix-colliding metric names.
        return out

    def report(self) -> str:
        """Human-readable table of this registry's metrics (the module
        level :func:`report` adds the process straggler lines)."""
        flat = self.flat()
        if not flat:
            return "telemetry: no metrics recorded"
        out = [f"{'metric':44s} {'value':>16s}"]
        for name in sorted(flat):
            m = flat[name]
            if isinstance(m, dict):
                if "buckets" in m:  # histogram
                    mean = m["sum"] / m["count"] if m["count"] else 0.0
                    val = f"n={m['count']} mean={mean:.6g}"
                elif "count" in m:  # ring
                    val = (f"n={m['count']} last={m.get('last', 0):.6g} "
                           f"mean={m.get('mean', 0):.6g}"
                           if m["count"] else "n=0")
                else:
                    val = str(m)
            elif isinstance(m, float):
                val = f"{m:.6g}"
            else:
                val = str(m)
            out.append(f"{name:44s} {val:>16s}")
        return "\n".join(out)

    def prometheus(self) -> str:
        """Prometheus-style text exposition of the registry (the format
        ``HVD_TELEMETRY_FILE`` writes and ``utils.stats`` parses)."""
        self._run_syncs()
        with self._lock:
            items = sorted(self._metrics.items())
        lines: List[str] = []
        for name, m in items:
            pname = "hvd_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)
            if isinstance(m, Counter):
                lines.append(f"# TYPE {pname} counter")
                lines.append(f"{pname} {m.snapshot()}")
            elif isinstance(m, Gauge):
                lines.append(f"# TYPE {pname} gauge")
                lines.append(f"{pname} {m.snapshot()}")
            elif isinstance(m, Histogram):
                bounds, cums, count, total = m.cumulative()
                lines.append(f"# TYPE {pname} histogram")
                for b, cum in zip(bounds, cums):
                    lines.append(f'{pname}_bucket{{le="{b:g}"}} {cum}')
                lines.append(f'{pname}_bucket{{le="+Inf"}} {count}')
                lines.append(f"{pname}_sum {total:.9g}")
                lines.append(f"{pname}_count {count}")
            elif isinstance(m, Ring):
                s = m.snapshot()
                lines.append(f"# TYPE {pname}_count counter")
                lines.append(f"{pname}_count {s['count']}")
                if s["count"]:
                    lines.append(f"# TYPE {pname}_last gauge")
                    lines.append(f"{pname}_last {s['last']:.9g}")
                    lines.append(f"{pname}_mean {s['mean']:.9g}")
        return "\n".join(lines) + "\n"

    def reset(self):
        """Drop every metric (tests only — sync hooks stay registered)."""
        with self._lock:
            self._metrics.clear()
        STRAGGLERS.reset()


REGISTRY = Registry()
STRAGGLERS = StragglerTracker()


def telemetry() -> dict:
    """Nested snapshot of every counter/gauge/histogram/ring plus the
    process straggler report — the ``hvd.telemetry()`` surface. (The
    straggler merge lives here, not in Registry: standalone Registry
    instances must not report the process-global tracker's data.)"""
    _maybe_start_exporter()
    out = REGISTRY.snapshot()
    strag = STRAGGLERS.snapshot()
    if strag["tensors"]:
        out["straggler"] = strag
    world = _world_lines(as_dict=True)
    if world:
        out["world"] = world
    out["compile_log"] = _compile_log().snapshot()
    return out


def _compile_log():
    """The process's compile log (core/compile_log.py feeds this
    registry, so it is imported from here only when read)."""
    from horovod_tpu.core import compile_log

    return compile_log.LOG


def _world_lines(as_dict: bool = False):
    """Elastic world state (core/elastic.py world.* gauges) for the
    report surfaces: None/[] when the process is not an elastic world
    member."""
    try:
        from horovod_tpu.core import elastic as _elastic

        world = _elastic.world_summary()
    except Exception:  # pragma: no cover - defensive
        world = None
    if as_dict:
        return world
    if world is None:
        return []
    line = (f"world: epoch {world['epoch']} "
            f"size {world['size']} "
            f"({world['processes']}/{world['initial_processes']} "
            f"process(es), generation {world['generation']})")
    if world.get("degraded"):
        line += " DEGRADED"
        if world.get("dead"):
            line += " — lost process(es) " + ", ".join(
                str(p) for p in sorted(world["dead"]))
    return [line]


def report() -> str:
    """Human-readable table — the ``hvd.telemetry_report()`` surface."""
    out = REGISTRY.report()
    lines = (_world_lines() + STRAGGLERS.report_lines()
             + _compile_log().report_lines())
    return out + ("\n" + "\n".join(lines) if lines else "")


def compact() -> dict:
    """Small flat summary for embedding in one JSON object (the flight
    recorder's dump): nonzero counters, ring counts, and per-process straggler waits."""
    out: Dict[str, object] = {}
    for name, val in REGISTRY.flat().items():
        if isinstance(val, (int, float)) and val:
            out[name] = val
        elif isinstance(val, dict) and val.get("count"):
            out[name + ".count"] = val["count"]
    strag = STRAGGLERS.snapshot()
    if strag["tensors"]:
        out["straggler.wait_us"] = strag["wait_us"]
    return out


# ---------------------------------------------------------------------------
# HVD_TELEMETRY_FILE exposition (interval + atexit)
# ---------------------------------------------------------------------------

_exporter_lock = threading.Lock()
_exporter_started = False
_http_started = False


def prometheus() -> str:
    """Process-wide exposition: the global registry plus the straggler
    tracker (what ``HVD_TELEMETRY_FILE`` holds)."""
    lines = [REGISTRY.prometheus().rstrip("\n")]
    strag = STRAGGLERS.snapshot()
    if strag["tensors"]:
        lines.append("# TYPE hvd_straggler_wait_microseconds counter")
        for pid, us in sorted(strag["wait_us"].items()):
            lines.append(
                f'hvd_straggler_wait_microseconds{{process="{pid}"}} {us}')
        lines.append(f"hvd_straggler_tensors {strag['tensors']}")
    return "\n".join(lines) + "\n"


def flush_to_file(path: Optional[str] = None):
    """Write the Prometheus exposition atomically (tmp + replace) so a
    concurrent reader never sees a torn file."""
    path = path or os.environ.get("HVD_TELEMETRY_FILE")
    if not path:
        return
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            fh.write(prometheus())
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _exporter_loop(path: str, interval_s: float):
    while True:
        time.sleep(interval_s)
        flush_to_file(path)


def _maybe_start_http():
    """Start the HVD_TELEMETRY_PORT localhost endpoint once, lazily
    (same activation rule as the file exporter): /metrics serves this
    exposition, /healthz the sentinel's watchdog state — see
    core/telemetry_http.py."""
    global _http_started
    if _http_started:
        return
    port = os.environ.get("HVD_TELEMETRY_PORT")
    if not port:
        return
    with _exporter_lock:
        if _http_started:
            return
        _http_started = True
    try:
        pnum = int(port)
        if pnum <= 0:
            return  # "0" means disabled, NOT an ephemeral port
        from horovod_tpu.core import telemetry_http

        telemetry_http.maybe_start(pnum)
    except Exception:
        pass  # a malformed port / bind failure must not break metrics


def _maybe_start_exporter():
    """Start the HVD_TELEMETRY_FILE flusher once, lazily (first telemetry
    touch) — no thread at import, nothing at all when the env is unset.
    The HTTP endpoint rides the same activation points."""
    global _exporter_started
    _maybe_start_http()
    if _exporter_started:
        return
    path = os.environ.get("HVD_TELEMETRY_FILE")
    if not path:
        return
    with _exporter_lock:
        if _exporter_started:
            return
        _exporter_started = True
        interval = float(os.environ.get("HVD_TELEMETRY_INTERVAL", "15"))
        atexit.register(flush_to_file, path)
        threading.Thread(target=_exporter_loop, args=(path, interval),
                         name="hvd-telemetry-export", daemon=True).start()


def record_eager(op: str, nbytes: int, elided: bool = False):
    """One eager collective call (ops/collectives.py feeds this; the jax
    frontend's size-1 short circuits too)."""
    _maybe_start_exporter()
    REGISTRY.counter(f"eager.{op}.count").inc()
    REGISTRY.counter(f"eager.{op}.bytes").inc(int(nbytes))
    if elided:
        REGISTRY.counter(f"eager.{op}.elided").inc()
