"""Chrome-tracing timeline (reference: horovod/common/timeline.{h,cc} —
same phase vocabulary, same per-tensor lanes, same HOROVOD_TIMELINE
activation; device-side spans come from the XLA profiler instead of CUDA
events).

Distributed-tracing extensions beyond the reference:

- ``HVD_TIMELINE=<dir>`` writes ONE trace per controller process
  (``timeline.rank{N}.json``); each trace embeds an ``HVD_CLOCK``
  metadata event mapping its timeline clock onto a common time base
  (see :meth:`Timeline.clock_sync` and utils/trace.py ``merge``). The
  single-file spelling (``HVD_TIMELINE=/path/trace.json``) still works
  and records exactly the reference's rank-local view.
- An always-on **flight recorder**: a bounded in-memory ring of the most
  recent events, recorded whether or not a trace file is being written
  (the C++ engine keeps its own ring — hvdcore.cc — exported through
  ``hvd_engine_recent_events`` with the same event shape). The engines
  dump it (with a telemetry snapshot) on stalls, failed negotiations,
  shutdown-drained work and SIGUSR1, so a hung or dying run yields a
  post-mortem trace without any env var set.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import tempfile
import threading
import time
from collections import deque
from typing import Callable, List, Optional

# Activity names (reference: operations.h:29-50).
QUEUE = "QUEUE"
# Submit-time snapshot copy (nested at the head of the QUEUE span; its
# END args carry the zero-copy attribution: {"pooled": bool} for a
# pool-slab copy, {"donated": true} for an ownership handoff that
# skipped the copy entirely — utils/trace.py splits MEMCPY medians by
# these the way NEGOTIATE is split by `cached`).
MEMCPY = "MEMCPY"
NEGOTIATE_ALLREDUCE = "NEGOTIATE_ALLREDUCE"
NEGOTIATE_ALLGATHER = "NEGOTIATE_ALLGATHER"
NEGOTIATE_BROADCAST = "NEGOTIATE_BROADCAST"
WAIT_FOR_DATA = "WAIT_FOR_DATA"
MEMCPY_IN_FUSION_BUFFER = "MEMCPY_IN_FUSION_BUFFER"
ALLREDUCE = "ALLREDUCE"
ALLGATHER = "ALLGATHER"
BROADCAST = "BROADCAST"
MEMCPY_OUT_FUSION_BUFFER = "MEMCPY_OUT_FUSION_BUFFER"
# Instant mark inside a NEGOTIATE_* span: process N announced the tensor
# (reference: the per-rank readiness events timeline.cc:106-130 records
# while a tensor is NEGOTIATING — the trace then shows who was late).
RANK_READY = "RANK_READY"
# A cooperatively-cancelled collective: pre-announce entries retire
# locally under this span; post-agreement entries complete cross-rank
# (a fused batch cannot be torn) and the span marks the discarded
# result. Both engines' writers spell it (hvdcheck parity-spans).
CANCELLED = "CANCELLED"
# Instant stamped when a per-request deadline fires: args carry the
# phase the entry was stuck in (QUEUE/NEGOTIATE/ALLREDUCE/...) and its
# age — the attribution the CollectiveTimeout error repeats.
DEADLINE_EXCEEDED = "DEADLINE_EXCEEDED"
# Clock metadata event: maps this trace's timeline clock onto the common
# time base (utils/trace.py merge). args: rank, epoch_wall_us (wall-clock
# µs at trace ts 0), offset_us (subtract from epoch_wall_us+ts to land on
# the common base — the wall↔monotonic bridge, replaced by rank 0's
# bridge once the coordinator's anchor exchange completes), rtt_us (the
# measured KV round trip bounding the exchange's error).
CLOCK_SYNC = "HVD_CLOCK"

_FLUSH_INTERVAL_S = 1.0  # reference: timeline.h:32


def flight_recorder_size() -> int:
    try:
        return max(16, int(os.environ.get("HVD_FLIGHT_RECORDER_SIZE", "512")))
    except ValueError:
        return 512


def _process_index() -> int:
    """This controller's process index, resolvable before hvd.init():
    topology when initialized, else the launcher's HVD_PROCESS_ID."""
    try:
        from horovod_tpu.common import topology as topo

        if topo.is_initialized():
            return topo.process_index()
    except Exception:
        pass
    try:
        return int(os.environ.get("HVD_PROCESS_ID", "0"))
    except ValueError:
        return 0


class Timeline:
    """Per-process chrome://tracing JSON writer. One "pid" lane per tensor
    name (reference: timeline.cc:60-96 metadata events). The clock base
    and the flight-recorder ring are live even with no file (path=None):
    ``now_us`` always returns the real clock and ``recent()`` always holds
    the last-N events."""

    def __init__(self, path: Optional[str], rank: Optional[int] = None):
        self._path = path
        self._lock = threading.RLock()
        self._fh = None
        self._pids = {}
        self._last_flush = 0.0
        self._first = True
        # The clock base is captured unconditionally: a disabled timeline
        # must still answer now_us() with the real clock (callers compute
        # retro-span boundaries from it) and stamp ring events.
        wall = time.time()
        self._start = time.monotonic()
        self.rank = _process_index() if rank is None else rank
        # Wall-clock µs corresponding to trace ts 0, and the wall↔
        # monotonic bridge: epoch_wall_us + ts - offset_us lands every
        # same-host rank on the shared CLOCK_MONOTONIC base. clock_sync
        # replaces offset_us with rank 0's bridge (exchanged through the
        # KV store) so multi-host traces merge on rank 0's frame too.
        self.epoch_wall_us = int(wall * 1e6)
        self.offset_us = int((wall - self._start) * 1e6)
        self.rtt_us: Optional[int] = None
        self._ring: deque = deque(maxlen=flight_recorder_size())
        # Metadata (the HVD_CLOCK mapping) is pinned in its own tiny ring
        # so a busy run's span events can never evict it — every flight
        # dump must carry the clock mapping or cross-rank alignment of
        # dumps silently degrades to local time.
        self._meta_ring: deque = deque(maxlen=16)
        if path:
            self._fh = open(path, "w")
            self._fh.write("[\n")
            # Crash-safety: a killed run leaves a truncated file. Events
            # are separator-FIRST (no trailing comma after the last one),
            # which the chrome/Perfetto JSON-array reader accepts without
            # the closing ']'; a clean interpreter exit that never reached
            # close() (engine leaked, Ctrl-C mid-run) is closed here.
            atexit.register(self.close)
        # Recorded in the ring even with no file (the C++ twin does the
        # same), so flight-recorder dumps carry the clock mapping too.
        self._emit_clock_meta()

    @property
    def enabled(self) -> bool:
        return self._fh is not None

    def _ts_us(self) -> int:
        return int((time.monotonic() - self._start) * 1e6)

    def _pid(self, name: str) -> int:
        if name not in self._pids:
            pid = len(self._pids) + 1
            self._pids[name] = pid
            self._emit(
                {"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": name}}
            )
        return self._pids[name]

    def _emit(self, ev: dict):
        # Separator BEFORE each event (after the first): however the
        # process dies, the file never ends in a trailing comma, so it
        # stays loadable in Perfetto after truncation.
        sep = "" if self._first else ",\n"
        self._first = False
        self._fh.write(sep + json.dumps(ev))
        now = time.monotonic()
        if now - self._last_flush > _FLUSH_INTERVAL_S:
            self._fh.flush()
            self._last_flush = now

    def now_us(self) -> int:
        """Current timeline clock, for retro-emitted spans (a caller that
        learns a phase boundary only after the fact — e.g. WAIT_FOR_DATA
        split out of an executor round-trip — records explicit ts). Valid
        whether or not a file is being written: the base is captured at
        construction, so a timeline enabled mid-run never receives a
        zero/negative retro timestamp."""
        return self._ts_us()

    def _clock_args(self) -> dict:
        args = {"rank": self.rank, "epoch_wall_us": self.epoch_wall_us,
                "offset_us": self.offset_us}
        if self.rtt_us is not None:
            args["rtt_us"] = self.rtt_us
        return args

    def _emit_clock_meta(self):
        args = self._clock_args()
        with self._lock:
            self._meta_ring.append({"name": CLOCK_SYNC, "ph": "M",
                                    "ts": self._ts_us(), "args": args})
            if self._fh is not None:
                self._emit({"name": CLOCK_SYNC, "ph": "M", "pid": 0,
                            "args": args})

    def clock_sync(self, offset_us: int, rtt_us: Optional[int]):
        """Record the coordinator's clock-anchor exchange result: rank 0's
        wall↔monotonic bridge (the common-base offset every rank now
        shares) plus the measured KV round trip that bounds the estimate's
        error. Re-emits the HVD_CLOCK metadata; the merge tool uses the
        LAST one per trace."""
        self.offset_us = int(offset_us)
        self.rtt_us = None if rtt_us is None else int(rtt_us)
        self._emit_clock_meta()

    def _event(self, phase: str, tensor: str, activity: str,
               args: Optional[dict], ts_us: Optional[int] = None):
        ts = self._ts_us() if ts_us is None else ts_us
        rec = {"name": activity, "ph": phase, "ts": ts, "tensor": tensor}
        if args:
            rec["args"] = args
        with self._lock:
            # Flight recorder: always on, bounded, never touches disk.
            self._ring.append(rec)
            if self._fh is None:  # no file (disabled, or closed)
                return
            ev = {"name": activity, "ph": phase, "pid": self._pid(tensor),
                  "ts": ts}
            if phase == "i":
                ev["s"] = "p"  # instant scope: process
            if args:
                ev["args"] = args
            self._emit(ev)

    def start(self, tensor: str, activity: str, args: Optional[dict] = None,
              ts_us: Optional[int] = None):
        self._event("B", tensor, activity, args, ts_us)

    def end(self, tensor: str, activity: str, args: Optional[dict] = None,
            ts_us: Optional[int] = None):
        self._event("E", tensor, activity, args, ts_us)

    def instant(self, tensor: str, activity: str,
                args: Optional[dict] = None):
        """Zero-duration mark on the tensor's lane (chrome 'i' event) —
        e.g. RANK_READY instants inside a NEGOTIATE_* span."""
        self._event("i", tensor, activity, args)

    def recent(self) -> List[dict]:
        """The flight-recorder ring: the most recent events (bounded by
        HVD_FLIGHT_RECORDER_SIZE), each ``{"name", "ph", "ts", "tensor",
        "args"?}`` — the same shape the C++ engine's ring exports. The
        pinned metadata (HVD_CLOCK, newest last) leads the list so the
        clock mapping survives however many span events followed it."""
        with self._lock:
            return ([dict(ev) for ev in self._meta_ring]
                    + [dict(ev) for ev in self._ring])

    def close(self):
        if not self.enabled:
            return
        with self._lock:
            if self._fh is None:  # raced with another closer
                return
            self._fh.write("\n]\n")
            self._fh.close()
            self._fh = None
        # Drop the crash-safety hook: without this, every engine
        # generation's closed Timeline (and its per-tensor lane map)
        # stays pinned by the atexit registry for process lifetime.
        atexit.unregister(self.close)


def timeline_path_from_env() -> Optional[str]:
    """HOROVOD_TIMELINE=<file-or-dir> activation (reference:
    operations.cc:1732-1736); HVD_TIMELINE is the native spelling. A
    directory target (anything not ending in ``.json``, or an existing
    directory) resolves to one file per process inside it."""
    raw = os.environ.get("HVD_TIMELINE") or os.environ.get("HOROVOD_TIMELINE")
    if not raw:
        return None
    return resolve_timeline_path(raw)


def is_dir_mode(raw: str) -> bool:
    """True when an HVD_TIMELINE value means per-rank-traces-in-a-dir
    (an existing directory, or a not-yet-existing path without a
    ``.json`` suffix). An existing plain FILE is always file mode —
    the reference allowed arbitrary trace filenames, and treating a
    legacy ``HOROVOD_TIMELINE=/tmp/hvd.trace`` leftover as a directory
    would crash engine init on makedirs. The ONE definition of the
    rule — the launcher classifies through this too, so
    where children write always matches where the mergers look."""
    if os.path.isdir(raw):
        return True
    if os.path.isfile(raw):
        return False
    return not raw.endswith(".json")


def resolve_timeline_path(raw: str, rank: Optional[int] = None) -> str:
    """Map the HVD_TIMELINE value to this process's trace file. Dir mode
    (the distributed-tracing default) creates the directory and returns
    ``<dir>/timeline.rank{N}.json``; a ``.json`` path is used verbatim
    (the reference's single-file spelling)."""
    if not is_dir_mode(raw):
        return raw
    rank = _process_index() if rank is None else rank
    os.makedirs(raw, exist_ok=True)
    return os.path.join(raw, f"timeline.rank{rank}.json")


def from_env() -> Timeline:
    return Timeline(timeline_path_from_env())


# ---------------------------------------------------------------------------
# Flight-recorder dumps (post-mortem traces for hung or dying runs)
# ---------------------------------------------------------------------------


def flight_recorder_dir() -> str:
    return (os.environ.get("HVD_FLIGHT_DIR")
            or tempfile.gettempdir())


def flight_keep() -> int:
    """Retention cap: how many dump files to keep per rank in the flight
    dir (``HVD_FLIGHT_KEEP``, default 8). A long run with repeated
    stalls/anomalies must not fill the disk with post-mortems."""
    try:
        return max(1, int(os.environ.get("HVD_FLIGHT_KEEP", "8")))
    except ValueError:
        return 8


def _prune_flight_dumps(directory: str, rank: int, keep: int):
    """Drop the oldest of THIS PROCESS's dumps beyond ``keep``
    (newest-by-mtime survive). Keyed on (rank, pid), not rank alone:
    two unrelated runs sharing the default temp dir are both rank 0,
    and one run's dump churn must never destroy the other's
    post-mortems. Best-effort: pruning must never take the dumper
    down."""
    import glob

    try:
        files = glob.glob(os.path.join(
            directory, f"hvd_flight.rank{rank}.{os.getpid()}.*.json"))
        if len(files) <= keep:
            return
        files.sort(key=lambda f: (os.path.getmtime(f), f))
        for stale in files[:-keep]:
            try:
                os.unlink(stale)
            except OSError:
                pass
    except OSError:
        pass


def dump_flight_recorder(events: List[dict], reason: str,
                         rank: Optional[int] = None,
                         path: Optional[str] = None,
                         kind: Optional[str] = None,
                         inspect: Optional[List[dict]] = None,
                         verdict: Optional[dict] = None) -> Optional[str]:
    """Write a post-mortem dump: the flight-recorder events plus a
    telemetry snapshot (counters + the straggler report — the same data
    ``hvd.telemetry()`` serves). Hang-class dumps additionally carry the
    dump ``kind`` ("stall", "deadline", "negotiation", "sigusr1"), the
    engine's per-entry ``inspect`` table, and — when the hang doctor
    reached a diagnosis — its attributed ``doctor`` verdict, making each
    dump a self-contained offline-diagnosable artifact
    (``stats --doctor <dir>``). Written atomically (tmp + replace) so a
    concurrent reader never sees a torn file. Returns the path, or None
    when writing failed (dumping must never take the caller down)."""
    rank = _process_index() if rank is None else rank
    payload = {
        "reason": str(reason),
        "rank": rank,
        "pid": os.getpid(),
        "wall_us": int(time.time() * 1e6),
        "events": list(events),
    }
    if kind is not None:
        payload["kind"] = str(kind)
    if inspect is not None:
        payload["inspect"] = list(inspect)
    if verdict is not None:
        payload["doctor"] = verdict
    try:
        from horovod_tpu.core import telemetry as tele

        payload["telemetry"] = tele.compact()
        payload["straggler"] = tele.STRAGGLERS.snapshot()
        payload["report"] = tele.report()
    except Exception:
        pass  # telemetry is additive; the events are the dump's core
    try:
        # Injected faults (core/faultline.py): every post-mortem says
        # whether the failure it records was provoked — a chaos run's
        # dumps must never read as organic incidents.
        from horovod_tpu.core import faultline as _flt

        if _flt.armed() or _flt.snapshot():
            payload["faults"] = {"spec": _flt.active_spec(),
                                 "injected": _flt.snapshot()}
    except Exception:
        pass
    prune_dir = None
    if path is None:
        # Unique per dump (wall-µs suffix) so a run's post-mortem HISTORY
        # survives — the retention cap below keeps it bounded. The older
        # {rank}.{pid} two-part spelling is still matched by every
        # consumer (they glob rank{N}.*).
        prune_dir = flight_recorder_dir()
        path = os.path.join(
            prune_dir,
            f"hvd_flight.rank{rank}.{os.getpid()}."
            f"{payload['wall_us']}.json")
    tmp = f"{path}.tmp"
    try:
        if prune_dir is not None:
            # An operator-set HVD_FLIGHT_DIR need not pre-exist: a lost
            # post-mortem is far worse than a mkdir on the dump path.
            os.makedirs(prune_dir, exist_ok=True)
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)
        if prune_dir is not None:
            _prune_flight_dumps(prune_dir, rank, flight_keep())
        return path
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


_dump_rate_lock = threading.Lock()
_last_dump_at: dict = {}  # (rank, kind, reason head) -> monotonic s


def _dump_min_interval_s() -> float:
    try:
        return float(os.environ.get("HVD_FLIGHT_MIN_INTERVAL", "1.0"))
    except ValueError:
        return 1.0


def dump_and_warn(events: List[dict], reason: str, rank: Optional[int],
                  logger, kind: Optional[str] = None,
                  inspect: Optional[List[dict]] = None,
                  verdict: Optional[dict] = None) -> Optional[str]:
    """The engines' shared dump wrapper (their post-mortem semantics
    must stay twins): write the flight dump, warn with the path, never
    raise. Returns the path or None.

    Rate-limited per (rank, kind, reason): a poisoned negotiation
    re-raises the SAME failure every ~5 ms engine cycle — dumping each
    one is a 200 Hz dump storm that churns the retention cap out from
    under a concurrent reader. The dump ``kind`` is part of the key so a
    prior unrelated dump (say a shutdown drain whose reason head
    collides) can never suppress a hang post-mortem. The first dump of
    each distinct (kind, reason) always lands; repeats within
    ``HVD_FLIGHT_MIN_INTERVAL`` seconds (default 1.0; 0 disables the
    limit) are dropped."""
    try:
        min_s = _dump_min_interval_s()
        key = (rank, kind or "", str(reason).splitlines()[0][:80])
        now = time.monotonic()
        with _dump_rate_lock:
            last = _last_dump_at.get(key)
            if last is not None and min_s > 0 and now - last < min_s:
                return None
        path = dump_flight_recorder(events, reason, rank=rank, kind=kind,
                                    inspect=inspect, verdict=verdict)
        if path:
            # Stamp only on SUCCESS: a transiently unwritable flight dir
            # must not suppress the retries — "the first dump of each
            # distinct reason always lands" includes landing late.
            with _dump_rate_lock:
                while len(_last_dump_at) >= 256:  # bounded memory
                    _last_dump_at.pop(next(iter(_last_dump_at)))
                _last_dump_at[key] = now
            logger.warning("flight recorder dumped to %s (%s)", path,
                           str(reason).splitlines()[0][:200])
        return path
    except Exception:
        return None


_sigusr1_lock = threading.Lock()
_sigusr1_dump: Optional[Callable[[str], None]] = None
_sigusr1_installed = False
_sigusr1_prev = None  # the application's handler, chained after ours


def install_sigusr1(dump_fn: Callable[[str], None]):
    """Register ``dump_fn("SIGUSR1")`` to run on SIGUSR1 (the live-engine
    post-mortem hook: ``kill -USR1 <pid>`` dumps the flight recorder of a
    hung run with no env var set). The latest registrant wins — each
    engine generation re-registers its own dumper. A handler the
    application installed first is preserved and chained after the dump
    (e.g. SLURM preemption checkpointing must keep working). Installable
    only from the main thread (the signal module's rule); elsewhere the
    request is recorded but the handler of a previous main-thread install
    serves it."""
    global _sigusr1_dump, _sigusr1_installed, _sigusr1_prev
    with _sigusr1_lock:
        _sigusr1_dump = dump_fn
        if _sigusr1_installed:
            return
        try:
            _sigusr1_prev = signal.signal(signal.SIGUSR1, _on_sigusr1)
            _sigusr1_installed = True
        except (ValueError, AttributeError, OSError):
            pass  # non-main thread, or a platform without SIGUSR1


def uninstall_sigusr1(dump_fn: Callable[[str], None]):
    """Drop ``dump_fn`` if it is the current SIGUSR1 dumper (engine
    shutdown calls this): the module global must not keep a strong
    reference pinning a dead engine — and a later SIGUSR1 must not dump
    a shut-down engine's stale ring as if it were live state. A newer
    registrant is left untouched."""
    global _sigusr1_dump
    with _sigusr1_lock:
        # == not `is`: each `self._dump_flight` access builds a fresh
        # bound-method object; equality compares (__self__, __func__).
        if _sigusr1_dump == dump_fn:
            _sigusr1_dump = None


def _on_sigusr1(signum, frame):
    fn = _sigusr1_dump
    if fn is not None:
        try:
            # Hand off to a thread: the handler interrupts the main
            # thread at an arbitrary bytecode boundary, possibly INSIDE a
            # telemetry or timeline critical section — dumping inline
            # would deadlock on the non-reentrant lock the interrupted
            # frame still holds. A separate thread simply waits its turn.
            threading.Thread(target=_safe_dump, args=(fn,),
                             name="hvd-sigusr1-dump", daemon=True).start()
        except Exception:
            pass  # a signal handler must never raise into arbitrary frames
    if callable(_sigusr1_prev):
        # Chain the application's own handler (SIG_DFL/SIG_IGN are ints,
        # not callables) — the dump is additive, never a replacement.
        try:
            _sigusr1_prev(signum, frame)
        except Exception:
            pass


def _safe_dump(fn):
    try:
        fn("SIGUSR1")
    except Exception:
        pass
