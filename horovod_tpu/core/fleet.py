"""Fleet observability plane: cross-rank telemetry rollups over the KV
plane, and the rank-0 world aggregator behind ``hvd.fleet_report()``.

Every observability surface before this one is per-process — N ranks
means N ``/metrics`` endpoints and no answer to "what is the world's
p99 allreduce latency right now?". This module closes that gap without
inventing a transport: each process periodically publishes a compact
telemetry snapshot to the existing KV plane (``FileKV`` under the fleet
directory — atomic rename, so readers never see a torn value; PR 11's
durability rule), and rank 0 merges the per-rank snapshots into world
rollups:

- global per-op latency quantiles (p50/p99/p999) — histograms merge
  EXACTLY because both engines feed identical bucket edges
  (``LATENCY_BUCKETS_S``, machine-checked by hvdcheck rule
  ``parity-latency``): merging is just summing count arrays;
- per-rank imbalance/straggler heatmap (queue depth, step time, beat
  age), world gauges (min/mean/max spreads);
- liveness: a rank whose snapshot sequence number stops advancing for
  ``HVD_FLEET_LEASE_S`` is marked STALE (judged by the READER's clock —
  same rule as the elastic heartbeat lease); a rank in the elastic
  death-note plane is DEAD. Neither ever blocks the aggregator — a dead
  peer must not wedge the rollup.

Surfaces: ``hvd.fleet_report()`` (dict), the ``/fleet`` arm on the
rank-0 telemetry endpoint, per-rank-labeled Prometheus series appended
to rank 0's ``/metrics``, and the live console
``python -m horovod_tpu.utils.stats --fleet <target> [--watch]``.

The publisher is OFF by default: it starts from ``topology.init`` only
when a fleet directory resolves (``HVD_FLEET_DIR``, or
``<HVD_ELASTIC_DIR>/fleet`` when the elastic plane is up) and
``HVD_FLEET`` is not ``0``. ``benchmark/run.py`` sets neither, so the
measured path never pays for this plane. The compiled/AOT hot path is
untouched either way — snapshots read the registry, they never
instrument the step.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

LOG = logging.getLogger("horovod_tpu.fleet")

# The histogram vocabulary that rides every snapshot (the cross-engine
# latency instruments; hvdcheck pins both engines to these names).
LATENCY_PREFIXES = ("engine.latency.", "engine.phase.", "engine.deadline.")

# The step-time ring for the console sparkline.
STEP_RING = "trainer.step_s"

_OPS = ("allreduce", "allgather", "broadcast")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def enabled() -> bool:
    return os.environ.get("HVD_FLEET", "1").lower() not in (
        "0", "false", "off")


def interval_s() -> float:
    """Publish cadence (seconds between snapshots)."""
    return max(0.1, _env_float("HVD_FLEET_INTERVAL_S", 2.0))


def fleet_lease_s() -> float:
    """Reader-clock lease: a rank whose snapshot seq is frozen this long
    is STALE. Defaults to three publish intervals so one missed tick
    (GC pause, loaded host) does not flap the marking."""
    return _env_float("HVD_FLEET_LEASE_S", 3.0 * interval_s())


def fleet_dir() -> Optional[str]:
    """Where snapshots live: ``HVD_FLEET_DIR``, or the elastic plane's
    shared directory when one exists (the supervisor already assumes
    shared storage there). None = plane off."""
    explicit = os.environ.get("HVD_FLEET_DIR")
    if explicit:
        return explicit
    try:
        from horovod_tpu.core import elastic

        d = elastic.elastic_dir()
    except Exception:  # pragma: no cover - defensive
        d = None
    return os.path.join(d, "fleet") if d else None


def _world_coords() -> Tuple[int, int]:
    """(generation, epoch) for key scoping — from the elastic world when
    it is up (epoch advances on every shrink/regrow, so a new world
    never merges against stale-epoch snapshots), else (HVD generation
    env, 0)."""
    try:
        from horovod_tpu.core import elastic

        summary = elastic.world_summary()
        if summary is not None:
            return int(summary["generation"]), int(summary["epoch"])
        return elastic.generation(), 0
    except Exception:  # pragma: no cover - defensive
        return 0, 0


def snapshot_key(generation: int, epoch: int, rank: int) -> str:
    return f"hvd/fleet/g{generation}/e{epoch}/p{rank}"


# ---------------------------------------------------------------------------
# Per-rank snapshot
# ---------------------------------------------------------------------------

def local_snapshot(rank: Optional[int] = None, seq: int = 0,
                   generation: Optional[int] = None,
                   epoch: Optional[int] = None) -> dict:
    """The compact per-rank telemetry snapshot the publisher ships:
    counters/gauges flat, the latency-vocabulary histograms as raw
    bucket counts (mergeable exactly), the step-time ring window, and
    the watchdog/numerics verdict summary."""
    from horovod_tpu.core import telemetry as tele

    if rank is None:
        try:
            from horovod_tpu.common import topology as topo

            rank = topo.process_index() if topo.is_initialized() else 0
        except Exception:  # pragma: no cover - defensive
            rank = 0
    if generation is None or epoch is None:
        g, e = _world_coords()
        generation = g if generation is None else generation
        epoch = e if epoch is None else epoch
    hists = {name: {"counts": h["counts"], "sum": h["sum"],
                    "count": h["count"]}
             for name, h in tele.REGISTRY.histogram_counts().items()
             if name.startswith(LATENCY_PREFIXES)}
    rings = {name: vals for name, vals
             in tele.REGISTRY.ring_values().items() if name == STEP_RING}
    health = None
    numerics = None
    try:
        from horovod_tpu.core import sentinel

        h = sentinel.health()
        health = h.get("status")
        numerics = (h.get("numerics") or {}).get("verdicts")
    except Exception:  # pragma: no cover - defensive
        pass
    # Serving-plane admission state (core/engine.py admission_summary,
    # one shape for both engines): queue depth + per-class in-flight vs
    # budget — the fleet console's saturation view rides the snapshot.
    admission = None
    try:
        from horovod_tpu.core import engine as _eng

        admission = _eng.admission_summary()
    except Exception:  # pragma: no cover - defensive
        pass
    # The hang doctor's latest attributed blame (core/doctor.py), in
    # compact form: the fleet console's blamed-tensor line rides the
    # ordinary snapshot plane — no extra keys, no extra reads.
    doctor = None
    try:
        from horovod_tpu.core import doctor as _doc

        v = _doc.last_verdict()
        if v and v.get("kind"):
            doctor = {"kind": v["kind"], "tensor": v.get("tensor"),
                      "ranks": v.get("ranks"),
                      "wall_us": v.get("wall_us")}
    except Exception:  # pragma: no cover - defensive
        pass
    return {
        "v": 1,
        "rank": int(rank),
        "seq": int(seq),
        "wall": time.time(),
        "generation": int(generation),
        "epoch": int(epoch),
        "counters": dict(tele.REGISTRY.flat_counters()),
        "gauges": dict(tele.REGISTRY.flat_gauges()),
        "hists": hists,
        "rings": rings,
        "health": health,
        "numerics": numerics,
        "admission": admission,
        "doctor": doctor,
    }


class FleetPublisher:
    """Background thread: one compact snapshot to the KV plane per
    interval, epoch-scoped keys, rename-only durability (durable=False —
    a beat lost to power failure is indistinguishable from a missed
    tick, and the control loop must not fsync per tick)."""

    def __init__(self, kv, rank: int,
                 interval: Optional[float] = None):
        self._kv = kv
        self._rank = rank
        self._interval = interval_s() if interval is None else interval
        self._seq = 0
        self._last_key: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def publish_once(self):
        """One snapshot to the current (generation, epoch) key. Epoch
        rollover (elastic shrink) retires the previous key so dead
        epochs do not accumulate in the plane."""
        g, e = _world_coords()
        self._seq += 1
        snap = local_snapshot(rank=self._rank, seq=self._seq,
                              generation=g, epoch=e)
        key = snapshot_key(g, e, self._rank)
        if self._last_key is not None and self._last_key != key:
            try:
                self._kv.delete(self._last_key)
            except Exception:  # pragma: no cover - defensive
                pass
        try:
            self._kv.set(key, json.dumps(snap), durable=False)
        except TypeError:
            # KV backends without the durability knob (LocalKV in unit
            # tests) take the plain two-argument form.
            self._kv.set(key, json.dumps(snap))
        self._last_key = key

    def _loop(self):
        while not self._stop.wait(self._interval):
            try:
                self.publish_once()
            except Exception:  # publishing must never kill the thread
                LOG.debug("fleet publish failed", exc_info=True)

    def start(self):
        if self._thread is not None:
            return
        try:
            self.publish_once()  # first beat now, not one interval late
        except Exception:  # pragma: no cover - defensive
            LOG.debug("fleet first publish failed", exc_info=True)
        self._thread = threading.Thread(
            target=self._loop, name="hvd-fleet-publish", daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None


# ---------------------------------------------------------------------------
# Rank-0 aggregation
# ---------------------------------------------------------------------------

def _quantiles_us(bounds: List[float], counts: List[int]) -> dict:
    from horovod_tpu.core import telemetry as tele

    out = {}
    for label, q in (("p50", 0.50), ("p99", 0.99), ("p999", 0.999)):
        v = tele.quantile_from_buckets(bounds, counts, q)
        out[f"{label}_us"] = None if v is None else round(v * 1e6, 1)
    return out


def merge_snapshots(snaps: List[dict],
                    states: Optional[Dict[int, str]] = None) -> dict:
    """Merge per-rank snapshots into the world rollup. Histograms merge
    exactly (identical bucket edges on every rank — summed counts);
    counters sum; gauges report min/mean/max spreads plus the per-rank
    heatmap. ``states`` overrides the liveness marking per rank (the
    aggregator's lease/death verdicts); ranks default to OK."""
    from horovod_tpu.core import telemetry as tele

    bounds = list(tele.LATENCY_BUCKETS_S)
    now = time.time()
    states = states or {}

    ranks: Dict[int, dict] = {}
    counters: Dict[str, float] = {}
    gauges_per_rank: Dict[str, Dict[int, float]] = {}
    hists: Dict[str, dict] = {}
    step_last: Dict[int, Optional[float]] = {}
    sparkline: List[float] = []
    doctor: Optional[dict] = None
    saturated_ranks: Dict[int, List[str]] = {}
    generation = epoch = 0
    for snap in snaps:
        rank = int(snap["rank"])
        generation = max(generation, int(snap.get("generation", 0)))
        epoch = max(epoch, int(snap.get("epoch", 0)))
        ring = (snap.get("rings") or {}).get(STEP_RING) or []
        step_last[rank] = ring[-1] if ring else None
        if ring and len(ring) > len(sparkline):
            sparkline = list(ring)
        ranks[rank] = {
            "seq": snap.get("seq"),
            "age_s": round(max(0.0, now - snap.get("wall", now)), 3),
            "state": states.get(rank, "OK"),
            "health": snap.get("health"),
            "numerics": snap.get("numerics"),
            "queue_depth": (snap.get("gauges") or {}).get(
                "engine.queue_depth"),
            "pool_bytes": (snap.get("gauges") or {}).get(
                "engine.pool.bytes_resident"),
            "step_s": step_last[rank],
            "saturated": sorted((snap.get("admission") or {}).get(
                "saturated") or []),
        }
        if ranks[rank]["saturated"]:
            saturated_ranks[rank] = ranks[rank]["saturated"]
        blame = snap.get("doctor")
        if blame and blame.get("kind") and (
                doctor is None
                or (blame.get("wall_us") or 0)
                > (doctor.get("wall_us") or 0)):
            doctor = blame  # newest attributed hang blame wins
        for name, v in (snap.get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + v
        for name, v in (snap.get("gauges") or {}).items():
            gauges_per_rank.setdefault(name, {})[rank] = v
        for name, h in (snap.get("hists") or {}).items():
            agg = hists.setdefault(
                name, {"counts": [0] * (len(bounds) + 1),
                       "sum": 0.0, "count": 0})
            counts = h.get("counts") or []
            if len(counts) != len(agg["counts"]):
                continue  # foreign bucket layout: never corrupt the merge
            agg["counts"] = [a + c for a, c in zip(agg["counts"], counts)]
            agg["sum"] += h.get("sum", 0.0)
            agg["count"] += h.get("count", 0)

    ops = {}
    for op in _OPS:
        h = hists.get(f"engine.latency.{op}")
        if h and h["count"]:
            ops[op] = dict(count=h["count"], **_quantiles_us(
                bounds, h["counts"]))
    phases = {}
    for name, h in sorted(hists.items()):
        if name.startswith("engine.phase.") and h["count"]:
            phases[name.split(".")[-1]] = dict(
                count=h["count"], **_quantiles_us(bounds, h["counts"]))
    # Per-priority-class completion latency (the serving-plane SLO
    # view): merged exactly like the per-op histograms above.
    classes = {}
    for cls in ("high", "normal", "low"):
        h = hists.get(f"engine.latency.class.{cls}")
        if h and h["count"]:
            classes[cls] = dict(count=h["count"], **_quantiles_us(
                bounds, h["counts"]))
    margin = hists.get("engine.deadline.margin")

    gauges = {}
    for name, per_rank in sorted(gauges_per_rank.items()):
        vals = list(per_rank.values())
        gauges[name] = {
            "min": min(vals), "max": max(vals),
            "mean": sum(vals) / len(vals),
            "per_rank": {str(r): v for r, v in sorted(per_rank.items())},
        }

    return {
        "v": 1,
        "wall": now,
        "generation": generation,
        "epoch": epoch,
        "size": len(ranks),
        "stale": sorted(r for r, s in states.items() if s == "STALE"),
        "dead": sorted(r for r, s in states.items() if s == "DEAD"),
        "ranks": {str(r): info for r, info in sorted(ranks.items())},
        "ops": ops,
        "phases": phases,
        "classes": classes,
        "deadline": {
            "margin_p50_s": (
                None if not (margin and margin["count"]) else round(
                    tele.quantile_from_buckets(
                        bounds, margin["counts"], 0.5), 6)),
            "exceeded": counters.get("engine.deadline_exceeded", 0),
            "cancelled": counters.get("engine.cancelled", 0),
            "ring_full": counters.get("engine.ring.full", 0),
        },
        # Serving-plane rollup: summed rejection/shed counters, the
        # world in-flight per class (summed gauges), and which ranks are
        # saturated right now (their classes at budget).
        "admission": {
            "rejected": counters.get("engine.admission.rejected", 0),
            "shed": counters.get("engine.admission.shed", 0),
            "inflight": {
                cls: sum((gauges_per_rank.get(
                    f"engine.admission.inflight.{cls}") or {}).values())
                for cls in ("high", "normal", "low")},
            "saturated_ranks": {str(r): cls for r, cls
                                in sorted(saturated_ranks.items())},
        },
        "counters": counters,
        "gauges": gauges,
        "step": {"sparkline": sparkline,
                 "per_rank_last": {str(r): v for r, v
                                   in sorted(step_last.items())}},
        "doctor": doctor,
    }


class FleetAggregator:
    """Rank 0's merged world view. Reads every rank's snapshot key for
    the CURRENT (generation, epoch) through any kv-like object exposing
    ``try_get`` (FileKV in production, LocalKV in unit tests), judges
    staleness by its OWN clock against the snapshot seq (a frozen seq
    past the lease = STALE; wall-clock skew between hosts never enters
    the verdict), folds the elastic death notes in as DEAD, and merges.
    Nothing here blocks: a missing or dead rank's key is simply absent
    or stale — the rollup always returns."""

    def __init__(self, kv, nproc: int,
                 lease: Optional[float] = None):
        self._kv = kv
        self._nproc = nproc
        self._lease = fleet_lease_s() if lease is None else lease
        # rank -> (seq, monotonic time the seq last ADVANCED)
        self._beats: Dict[int, Tuple[int, float]] = {}
        self._lock = threading.Lock()

    def collect(self, generation: Optional[int] = None,
                epoch: Optional[int] = None,
                now: Optional[float] = None,
                extra: Optional[List[dict]] = None) -> dict:
        """One rollup pass. ``extra`` prepends already-local snapshots
        (rank 0 includes its own registry directly — its view must not
        depend on reading back its own KV write)."""
        if generation is None or epoch is None:
            g, e = _world_coords()
            generation = g if generation is None else generation
            epoch = e if epoch is None else epoch
        now = time.monotonic() if now is None else now
        snaps: List[dict] = list(extra or [])
        # Ranks handed in directly are live by construction (rank 0's
        # own registry in fleet_report) — the seq lease only judges
        # ranks read back through the KV plane.
        live = {int(s["rank"]) for s in snaps}
        have = set(live)
        for rank in range(self._nproc):
            if rank in have:
                continue
            raw = None
            try:
                raw = self._kv.try_get(snapshot_key(generation, epoch,
                                                    rank))
            except Exception:  # a failing KV must not wedge the rollup
                LOG.debug("fleet collect failed for rank %d", rank,
                          exc_info=True)
            if raw is None:
                continue
            try:
                snap = json.loads(raw)
            except ValueError:
                continue  # torn/foreign value: skip, never raise
            snaps.append(snap)

        dead = set()
        try:
            from horovod_tpu.core import elastic

            summary = elastic.world_summary()
            if summary:
                dead = {int(r) for r in summary.get("dead", {})}
        except Exception:  # pragma: no cover - defensive
            pass

        states: Dict[int, str] = {}
        with self._lock:
            for snap in snaps:
                rank = int(snap["rank"])
                seq = int(snap.get("seq", 0))
                prev = self._beats.get(rank)
                if rank in live or prev is None or seq > prev[0]:
                    self._beats[rank] = (max(seq, prev[0] if prev else 0),
                                         now)
                    states[rank] = "OK"
                elif now - prev[1] > self._lease:
                    states[rank] = "STALE"
                else:
                    states[rank] = "OK"
                if rank in dead:
                    states[rank] = "DEAD"
        return merge_snapshots(snaps, states)


# ---------------------------------------------------------------------------
# Process-wide wiring (topology.init / telemetry endpoint / hvd API)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_publisher: Optional[FleetPublisher] = None
_aggregator: Optional[FleetAggregator] = None


def maybe_start(rank: int, nproc: int):
    """Start the per-rank publisher (every rank) and the aggregator
    (rank 0) when a fleet directory resolves. Called from
    ``topology.init``; idempotent; never raises."""
    global _publisher, _aggregator
    if not enabled():
        return
    d = fleet_dir()
    if not d:
        return
    try:
        from horovod_tpu.core.elastic import FileKV

        with _lock:
            if _publisher is None:
                _publisher = FleetPublisher(FileKV(d), rank)
                _publisher.start()
            if rank == 0 and _aggregator is None:
                _aggregator = FleetAggregator(FileKV(d), nproc)
    except Exception:  # observability must never break init
        LOG.warning("fleet plane failed to start", exc_info=True)


def stop():
    global _publisher, _aggregator
    with _lock:
        pub, _publisher = _publisher, None
        _aggregator = None
    if pub is not None:
        pub.stop()


def fleet_report() -> dict:
    """The merged world view. On rank 0 with the plane up this covers
    every publishing rank (STALE/DEAD marked, never blocking); without
    a KV plane (single process, plane off) it degrades to a one-rank
    rollup of the local registry — same shape either way."""
    try:
        from horovod_tpu.common import topology as topo

        rank = topo.process_index() if topo.is_initialized() else 0
    except Exception:  # pragma: no cover - defensive
        rank = 0
    with _lock:
        agg = _aggregator
    local = local_snapshot(rank=rank)
    if agg is None:
        return merge_snapshots([local])
    return agg.collect(extra=[local])


def report_from_dir(directory: str,
                    now: Optional[float] = None) -> dict:
    """Cold-scan rollup for the console: read every snapshot file in a
    fleet directory (FileKV flattens ``hvd/fleet/g{g}/e{e}/p{r}`` to
    ``hvd~fleet~...``), keep the newest (generation, epoch), and merge.
    A console has no seq history, so staleness is judged by snapshot
    wall age against the lease — good enough for eyes on a screen; the
    in-process aggregator keeps the clock-skew-proof seq rule."""
    import re as _re

    now = time.time() if now is None else now
    pat = _re.compile(r"^hvd~fleet~g(\d+)~e(\d+)~p(\d+)$")
    found: Dict[Tuple[int, int], List[dict]] = {}
    try:
        names = os.listdir(directory)
    except OSError:
        return merge_snapshots([])
    for fname in names:
        m = pat.match(fname)
        if not m:
            continue
        try:
            with open(os.path.join(directory, fname)) as fh:
                snap = json.loads(fh.read())
        except (OSError, ValueError):
            continue  # torn/retired key mid-scan: skip
        found.setdefault((int(m.group(1)), int(m.group(2))),
                         []).append(snap)
    if not found:
        return merge_snapshots([])
    snaps = found[max(found)]
    lease = fleet_lease_s()
    states = {int(s["rank"]): ("STALE" if now - s.get("wall", now) > lease
                               else "OK")
              for s in snaps}
    return merge_snapshots(snaps, states)


def prometheus_extra() -> str:
    """Per-rank-labeled Prometheus series appended to rank 0's
    ``/metrics`` (empty off rank 0 or with the plane down). Fleet series
    are labeled so one scrape of rank 0 carries the whole world."""
    with _lock:
        agg = _aggregator
    if agg is None:
        return ""
    # Same view as /fleet: the KV-merged world plus this rank's LIVE
    # registry (a scrape between beats must not lag a publish interval).
    report = fleet_report()
    lines: List[str] = []
    lines.append("# TYPE hvd_fleet_size gauge")
    lines.append(f"hvd_fleet_size {report['size']}")
    lines.append(f"hvd_fleet_epoch {report['epoch']}")
    for rank, info in report["ranks"].items():
        state = info.get("state", "OK")
        lines.append(
            f'hvd_fleet_rank_up{{rank="{rank}"}} '
            f"{1 if state == 'OK' else 0}")
        lines.append(
            f'hvd_fleet_rank_age_seconds{{rank="{rank}"}} '
            f"{info['age_s']:.3f}")
        if info.get("queue_depth") is not None:
            lines.append(
                f'hvd_fleet_queue_depth{{rank="{rank}"}} '
                f"{info['queue_depth']:g}")
        if info.get("pool_bytes") is not None:
            lines.append(
                f'hvd_fleet_pool_bytes_resident{{rank="{rank}"}} '
                f"{info['pool_bytes']:g}")
        if info.get("step_s") is not None:
            lines.append(
                f'hvd_fleet_step_seconds{{rank="{rank}"}} '
                f"{info['step_s']:.6g}")
    for op, q in report["ops"].items():
        for label in ("p50_us", "p99_us", "p999_us"):
            if q.get(label) is not None:
                lines.append(
                    f'hvd_fleet_latency_{label}{{op="{op}"}} '
                    f"{q[label]:g}")
    return "\n".join(lines) + "\n" if lines else ""
