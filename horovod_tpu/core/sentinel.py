"""Performance sentinel: in-loop anomaly watchdog + auto-capture profiling.

The observability stack before this module was *passive*: the telemetry
registry (core/telemetry.py) counts, the timelines (core/timeline.py)
record, the profiler (utils/profiler.py) captures — but only when a human
asks. This module watches the run while it trains (reference rationale:
Horovod's timeline made scaling problems *diagnosable*, arxiv 1802.05799
§5; the MLPerf TPU-pod work shows sustained-throughput claims only hold
when measurement is continuous, arxiv 1909.09756 §3):

- **Watchdog** (:class:`StepWatchdog`): a rolling step-time baseline
  (EWMA + p99 over the same observations the telemetry dispatch/step
  rings hold) per *origin* — the keras Trainer's wall step time, the
  ``hvd.jax.jit`` wrapper's dispatch latency. A step exceeding the
  anomaly threshold fires ONCE (cooldown, no re-trigger storm): flight
  recorder dump, a bounded profiler capture of the next few steps, and
  an attributed verdict — recompile (the compile log, core/compile_log.py,
  counted a backend compile during the step; where a ``hvd.jax.jit``
  call past its first compiled it, the verdict names the program and
  the dispatch) vs straggler rank (the telemetry straggler report gained
  imposed wait) vs engine stall (both engines' stall paths call
  :func:`note_stall`) vs HBM-traffic jump (the post-anomaly capture's
  measured bytes/step vs the previous capture).
- **Auto-capture** (:class:`AutoCapture`): with ``HVD_PROFILE_DIR`` set,
  ``HVD_PROFILE_EVERY=N`` takes a periodic capture of
  ``HVD_PROFILE_STEPS`` steps every N steps, and SIGUSR2 takes one on
  demand. Each capture folds through
  :func:`horovod_tpu.utils.xplane.hbm_json` into measured
  hbm_gb_per_step / membw_util (and MFU when
  :func:`set_flops_per_step` was told the program's cost) and appends
  one JSON record to ``$HVD_PROFILE_DIR/perf.jsonl``, the run's health
  log (one JSON object a line).
- **Health** (:func:`health`): the ``/healthz`` payload the
  ``HVD_TELEMETRY_PORT`` endpoint serves (core/telemetry_http.py) —
  watchdog verdicts + last-step age.

The AOT path (``lower().compile()``) stays uninstrumented: the sentinel
only sees the per-call dispatch boundary (``_InstrumentedJit``) and
post-window captures — never the inside of the compiled program.

Knobs (all env): ``HVD_WATCHDOG`` (default on; 0 disables),
``HVD_WATCHDOG_FACTOR`` (default 3.0 × EWMA), ``HVD_WATCHDOG_P99_MULT``
(default 2.0 × p99 — the threshold is the max of both),
``HVD_WATCHDOG_MIN_STEPS`` (warmup, default 32),
``HVD_WATCHDOG_COOLDOWN`` (steps between firings per origin, default
200), ``HVD_PROFILE_DIR``, ``HVD_PROFILE_EVERY``, ``HVD_PROFILE_STEPS``
(default 3). Stdlib-only on the observe path; jax/xplane are imported
only when a capture actually starts/folds.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import tempfile
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from horovod_tpu.core import compile_log
from horovod_tpu.core import telemetry as tele
from horovod_tpu.core import timeline as tl

LOG = logging.getLogger("horovod_tpu.sentinel")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------


class StepWatchdog:
    """Rolling step-time baseline for one origin (trainer / dispatch).

    ``observe`` returns an anomaly dict when the step exceeds the
    threshold — ``max(factor × EWMA, p99_mult × p99)`` — after
    ``min_steps`` of warmup. The FIRED sample is not folded into the
    baseline (one outlier must not drag the EWMA up and mask the next);
    a fired anomaly then opens a ``cooldown``-step window in which
    further excursions are counted as ``suppressed`` but do not re-fire
    — and those samples DO fold in, so a persistent regime shift
    becomes the new baseline (one dump per shift) instead of a dump
    storm when the cooldown expires."""

    def __init__(self, origin: str, factor: float = 3.0,
                 p99_mult: float = 2.0, min_steps: int = 32,
                 cooldown: int = 200, window: int = 256,
                 alpha: float = 0.1):
        self.origin = origin
        self.factor = factor
        self.p99_mult = p99_mult
        self.min_steps = max(2, min_steps)
        self.cooldown = max(1, cooldown)
        self.alpha = alpha
        self.ewma: Optional[float] = None
        self.steps = 0
        self.anomalies = 0
        self.suppressed = 0
        self._window: deque = deque(maxlen=window)
        self._cooldown_left = 0
        self._lock = threading.Lock()
        # p99 is refreshed every _P99_REFRESH inserts, not per observe:
        # sorting 256 samples on every dispatch would dominate the
        # claimed ~1-2 µs per-call overhead. A sixteen-step-stale p99
        # only delays threshold ADAPTATION, never detection (the EWMA
        # half of the threshold is always current).
        self._p99_cache: Optional[float] = None
        self._since_p99 = 0
        # Attribution context captured at the END of the previous step:
        # a delta over the anomalous step is evidence about THAT step.
        self._prev_compiles = compile_log.LOG.compiled
        self._prev_recompiles = compile_log.LOG.recompiles
        self._prev_strag_us = 0

    _P99_REFRESH = 16

    def _p99_locked(self) -> Optional[float]:
        if not self._window:
            return None
        w = sorted(self._window)
        return w[min(len(w) - 1, int(0.99 * (len(w) - 1) + 0.999))]

    def p99(self) -> Optional[float]:
        with self._lock:
            return self._p99_locked()

    def threshold(self) -> Optional[float]:
        """Current anomaly threshold in seconds, or None during warmup.
        Uses the cached p99 (refreshed every ``_P99_REFRESH`` inserts)."""
        if self.steps < self.min_steps or self.ewma is None:
            return None
        thr = self.factor * self.ewma
        if self._p99_cache is not None:
            thr = max(thr, self.p99_mult * self._p99_cache)
        return thr

    def _strag_total_us(self) -> int:
        try:
            return tele.STRAGGLERS.total_wait_us()
        except Exception:
            return 0

    def observe(self, step_s: float,
                allow_fire: bool = True) -> Optional[dict]:
        """Record one step; returns the anomaly context dict when this
        step fired (caller attributes/dumps), else None.
        ``allow_fire=False`` records an over-threshold sample as
        suppressed (the sentinel passes it when ANOTHER origin just
        fired on the same excursion — one slow compiled step must not
        dump twice through the trainer AND dispatch watchdogs)."""
        thr = self.threshold()
        anomalous = thr is not None and step_s > thr
        fired = None
        with self._lock:
            self.steps += 1
            if self._cooldown_left > 0 or (anomalous and not allow_fire):
                if self._cooldown_left > 0:
                    self._cooldown_left -= 1
                if anomalous:
                    self.suppressed += 1
                anomalous = False  # suppressed — baseline still protected
            elif anomalous:
                self.anomalies += 1
                self._cooldown_left = self.cooldown
                fired = {
                    "origin": self.origin,
                    "step_s": step_s,
                    "ewma_s": self.ewma,
                    "threshold_s": thr,
                }
            if fired is None:
                # Baseline update excludes the fired outlier.
                self._window.append(step_s)
                self.ewma = (step_s if self.ewma is None
                             else (1 - self.alpha) * self.ewma
                             + self.alpha * step_s)
                self._since_p99 += 1
                if (self._p99_cache is None
                        or self._since_p99 >= self._P99_REFRESH):
                    self._since_p99 = 0
                    self._p99_cache = self._p99_locked()
        # Attribution deltas over THIS step (read outside the lock; the
        # counters are process-global and monotonic).
        comp = compile_log.LOG.compiled
        recomp = compile_log.LOG.recompiles
        strag = self._strag_total_us()
        if fired is not None:
            fired["p99_s"] = self.p99()
            fired["compiles"] = comp - self._prev_compiles
            if recomp > self._prev_recompiles:
                # Which hvd.jax.jit function, at which of its dispatches,
                # how long the backend took and whether the cache hit.
                fired["recompile"] = compile_log.LOG.last_recompile()
            fired["straggler_wait_us"] = strag - self._prev_strag_us
        self._prev_compiles = comp
        self._prev_recompiles = recomp
        self._prev_strag_us = strag
        return fired

    def summary(self) -> dict:
        p99 = self.p99()
        thr = self.threshold()
        return {
            "steps": self.steps,
            "ewma_ms": round(self.ewma * 1e3, 3) if self.ewma else None,
            "p99_ms": round(p99 * 1e3, 3) if p99 else None,
            "threshold_ms": round(thr * 1e3, 3) if thr else None,
            "anomalies": self.anomalies,
            "suppressed": self.suppressed,
        }


# ---------------------------------------------------------------------------
# Auto-capture
# ---------------------------------------------------------------------------

_SIGUSR2_INSTALLED = False
_SIGUSR2_PREV = None


def _on_sigusr2(signum, frame):
    """Module-level handler: looks up the CURRENT sentinel at signal
    time (a closure over one AutoCapture would pin a replaced sentinel
    forever and arm an orphan nobody steps). Signal-safe: one attribute
    write, no allocation, no locks."""
    s = _sentinel
    if s is not None:
        # Through request(), not a raw _pending write: its guard keeps
        # an armed watchdog capture from being displaced (compare +
        # attribute writes — still signal-safe).
        s.capture.request("sigusr2")
    if callable(_SIGUSR2_PREV):
        try:
            _SIGUSR2_PREV(signum, frame)
        except Exception:
            pass


def _install_sigusr2_once():
    global _SIGUSR2_INSTALLED, _SIGUSR2_PREV
    if _SIGUSR2_INSTALLED:
        return
    try:
        _SIGUSR2_PREV = signal.signal(signal.SIGUSR2, _on_sigusr2)
        _SIGUSR2_INSTALLED = True
    except (ValueError, AttributeError, OSError):
        pass  # non-main thread, or a platform without SIGUSR2


class AutoCapture:
    """Bounded XLA-profiler captures of the live training loop.

    Periodic (``HVD_PROFILE_EVERY`` steps, needs ``HVD_PROFILE_DIR``),
    on-demand (SIGUSR2, or :meth:`request`), and watchdog-triggered.
    Each capture spans the next ``HVD_PROFILE_STEPS`` observed steps,
    then folds asynchronously (the xplane parse imports tensorflow —
    never paid inside the training loop) into one ``perf.jsonl``
    record."""

    def __init__(self, sentinel: "Sentinel"):
        self._sentinel = sentinel
        self.dir = os.environ.get("HVD_PROFILE_DIR") or None
        self.every = _env_int("HVD_PROFILE_EVERY", 0)
        self.steps_per_capture = max(1, _env_int("HVD_PROFILE_STEPS", 3))
        self._seq = 0
        self._step = 0
        # ONE attribute holds (kind, verdict): the SIGUSR2 handler and
        # the training thread race on this slot, and two separate
        # fields could interleave into a sigusr2 kind carrying a
        # clobbered watchdog verdict.
        self._pending_req: Optional[tuple] = None
        self._active: Optional[dict] = None
        self._lock = threading.Lock()
        self.last_record: Optional[dict] = None
        self._last_hbm_gb: Optional[float] = None
        if self.dir:
            _install_sigusr2_once()

    # -- triggers ------------------------------------------------------------

    def request(self, kind: str, verdict: Optional[dict] = None):
        """Arm a capture starting at the next observed step (signal-safe:
        attribute compare + writes). An armed WATCHDOG request is never
        displaced by a lesser trigger — a SIGUSR2 landing right after an
        anomaly (the operator reacting to the warning) must not leave
        the verdict's capture pending forever. ``verdict`` rides along
        on watchdog requests so the fold resolves THE verdict that armed
        the capture, not whatever ``last_verdict`` holds by then."""
        req = self._pending_req
        if req is not None and req[0] == "watchdog" and kind != "watchdog":
            return
        self._pending_req = (kind, verdict)  # single atomic store

    # -- the per-step state machine ------------------------------------------

    def observe_step(self, step_s: float):
        with self._lock:
            if self._active is not None:
                self._active["step_times"].append(step_s)
                if len(self._active["step_times"]) >= \
                        self._active["steps"]:
                    self._stop_locked()
                return
            self._step += 1
            req, self._pending_req = self._pending_req, None
            kind, verdict = req if req is not None else (None, None)
            if kind is None and self.dir and self.every > 0 \
                    and self._step % self.every == 0:
                kind = "periodic"
            if kind is not None:
                self._start_locked(kind, verdict)

    def _start_locked(self, kind: str, verdict: Optional[dict] = None):
        base = self.dir
        if base is None:
            # Watchdog-triggered capture with no HVD_PROFILE_DIR: the
            # evidence still gets captured, into a kept tempdir named in
            # the verdict (no perf.jsonl without a configured home).
            base = tempfile.mkdtemp(prefix="hvd_sentinel_")
        self._seq += 1
        capdir = os.path.join(base, f"capture_{self._seq:04d}_{kind}")
        try:
            import jax

            os.makedirs(capdir, exist_ok=True)
            jax.profiler.start_trace(capdir)
        except Exception as exc:
            # Another trace active (bench --profile, a user's tensorboard
            # capture) or no jax: skip, never break the training loop —
            # but RESOLVE a pending watchdog verdict (its deferred
            # counter and /healthz "pending" marker must not dangle on
            # a capture that never happened).
            LOG.debug("sentinel capture skipped: %s", exc)
            self._sentinel._note_capture(
                {"capture_dir": None, "kind": kind,
                 "error": f"capture failed to start: {exc}"}, None,
                verdict=verdict)
            return
        self._active = {"kind": kind, "dir": capdir,
                        "steps": self.steps_per_capture,
                        "t0": time.time(), "step_times": [],
                        "verdict": verdict}
        tele.REGISTRY.counter("sentinel.captures.started").inc()

    def _stop_locked(self):
        active, self._active = self._active, None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            LOG.debug("sentinel capture stop failed: %s", exc)
            active["error"] = f"stop_trace failed: {exc}"
        threading.Thread(target=self._fold, args=(active,),
                         name="hvd-sentinel-fold", daemon=True).start()

    # -- folding (background thread) -----------------------------------------

    def _fold(self, active: dict):
        record = {
            "ts": round(time.time(), 3),
            "rank": tl._process_index(),
            "kind": active["kind"],
            "steps": len(active["step_times"]),
            "capture_dir": active["dir"],
            "step_time_ms": None,
            "hbm_gb_per_step": None,
            "hbm_gb_by_dtype": None,
            "membw_util": None,
            "mfu": None,
            "gflops_per_step": None,
            # Latest host-visible training loss (Trainer epoch
            # boundaries): convergence beside throughput. None when
            # no loop reported one.
            "final_loss": self._sentinel.last_loss,
            "error": active.get("error"),
        }
        times = active["step_times"]
        step_s = sum(times) / len(times) if times else None
        if step_s:
            record["step_time_ms"] = round(step_s * 1e3, 3)
        try:
            from horovod_tpu.utils import profiler

            files = profiler.trace_files(active["dir"])
            if not files:
                raise profiler.CaptureError(
                    f"capture produced no *.xplane.pb under "
                    f"{active['dir']}")
            from horovod_tpu.utils import xplane

            data = xplane.hbm_json(active["dir"],
                                   steps=max(1, len(times)))
            hbm_bytes = data["true_hbm_bytes_per_step"]
            record["hbm_gb_per_step"] = round(hbm_bytes / 1e9, 3)
            by_dtype = data.get("bytes_by_dtype_per_step") or None
            if by_dtype:
                # bf16-vs-f32 byte split (HBM diet round 2): schedule-
                # derived, so it audits the state_dtype policy — f32
                # bytes creeping back show up per capture in perf.jsonl.
                record["hbm_gb_by_dtype"] = {
                    dt: round(b / 1e9, 3) for dt, b in by_dtype.items()}
            import jax

            from horovod_tpu.utils import hardware as hw

            dev = jax.devices()[0]
            peak_bw = hw.peak_hbm_bw(dev)
            peak = hw.peak_flops(dev)
            if step_s and peak_bw and hbm_bytes:
                record["membw_util"] = round(
                    hbm_bytes / step_s / peak_bw, 3)
            flops = self._sentinel.flops_per_step
            if flops:
                record["gflops_per_step"] = round(flops / 1e9, 1)
                if step_s and peak:
                    record["mfu"] = round(flops / step_s / peak, 4)
        except Exception as exc:
            if record["error"] is None:
                record["error"] = str(exc).splitlines()[0][:300]
        self.last_record = record
        tele.REGISTRY.counter("sentinel.captures.folded").inc()
        if self.dir:
            try:
                with open(os.path.join(self.dir, "perf.jsonl"), "a") as fh:
                    fh.write(json.dumps(record) + "\n")
            except OSError as exc:
                LOG.warning("cannot append perf.jsonl: %s", exc)
        # HBM-jump attribution: a watchdog capture's traffic vs the last
        # known-good capture. Update BEFORE publishing the baseline.
        self._sentinel._note_capture(record, self._last_hbm_gb,
                                     verdict=active.get("verdict"))
        if record["hbm_gb_per_step"] is not None \
                and record["kind"] != "watchdog":
            self._last_hbm_gb = record["hbm_gb_per_step"]

    def summary(self) -> dict:
        return {
            "dir": self.dir,
            "every": self.every,
            "captures": self._seq,
            "active": self._active is not None,
            "last": self.last_record,
        }


# ---------------------------------------------------------------------------
# Sentinel (process singleton)
# ---------------------------------------------------------------------------


class Sentinel:
    """Per-process sentinel: per-origin watchdogs + one AutoCapture."""

    #: HBM-traffic jump factor for the post-anomaly capture verdict.
    HBM_JUMP = 1.10

    def __init__(self):
        self.enabled = os.environ.get("HVD_WATCHDOG", "1") not in (
            "0", "false", "off")
        self.factor = _env_float("HVD_WATCHDOG_FACTOR", 3.0)
        self.p99_mult = _env_float("HVD_WATCHDOG_P99_MULT", 2.0)
        self.min_steps = _env_int("HVD_WATCHDOG_MIN_STEPS", 32)
        self.cooldown = _env_int("HVD_WATCHDOG_COOLDOWN", 200)
        self.capture_on_anomaly = os.environ.get(
            "HVD_WATCHDOG_CAPTURE",
            "1" if os.environ.get("HVD_PROFILE_DIR") else "0") not in (
            "0", "false", "off")
        self.flops_per_step: Optional[float] = None
        self.watchdogs: Dict[str, StepWatchdog] = {}
        self.capture = AutoCapture(self)
        self.last_verdict: Optional[dict] = None
        self.last_step_wall: Optional[float] = None
        self.last_stall: Optional[dict] = None
        self.last_loss: Optional[float] = None
        self._lock = threading.Lock()
        # One real training step can be observed through SEVERAL origins
        # (the keras Trainer's wall time wraps a jitted call that itself
        # reports its dispatch): exactly one origin — "trainer" when one
        # exists, else the first seen — drives the capture state machine,
        # and a fresh firing suppresses other origins' firings on the
        # same excursion for a short wall window.
        self._capture_origin: Optional[str] = None
        self._last_fire_wall: Optional[float] = None

    #: Wall seconds after a firing during which OTHER origins' anomalies
    #: are suppressed (the same slow step seen through two lenses).
    FIRE_SUPPRESS_S = 5.0

    # -- wiring --------------------------------------------------------------

    def watchdog(self, origin: str) -> StepWatchdog:
        with self._lock:
            wd = self.watchdogs.get(origin)
            if wd is None:
                wd = self.watchdogs[origin] = StepWatchdog(
                    origin, factor=self.factor, p99_mult=self.p99_mult,
                    min_steps=self.min_steps, cooldown=self.cooldown)
            return wd

    def observe_step(self, step_s: float, origin: str = "step"
                     ) -> Optional[dict]:
        """One observed step/dispatch. Cheap when nothing is armed: a
        deque append + a few compares. Returns the verdict when this
        step fired the watchdog."""
        now = time.time()
        self.last_step_wall = now
        # Capture stepping follows ONE origin ("trainer" preferred —
        # wall step time — else the first seen): a Trainer step would
        # otherwise be counted twice (its own observation + the wrapped
        # jit dispatch), halving the periodic cadence and folding
        # mixed-meaning step times into perf.jsonl.
        if self._capture_origin is None or origin == "trainer":
            self._capture_origin = origin
        if origin == self._capture_origin:
            self.capture.observe_step(step_s)
        if not self.enabled:
            return None
        allow = (self._last_fire_wall is None
                 or now - self._last_fire_wall > self.FIRE_SUPPRESS_S)
        fired = self.watchdog(origin).observe(step_s, allow_fire=allow)
        if fired is None:
            return None
        self._last_fire_wall = now
        return self._fire(fired)

    def note_stall(self, reason: str, rank: Optional[int] = None):
        """Both engines' stall paths land here: the stall becomes health
        state and attribution context for the next anomaly verdict."""
        self.last_stall = {"wall": time.time(),
                           "reason": str(reason).splitlines()[0][:300],
                           "rank": rank}
        tele.REGISTRY.counter("sentinel.stalls").inc()

    def note_loss(self, loss):
        """Latest host-visible training loss (the Trainer reports it at
        epoch boundaries, where it is already a host float): auto-capture
        perf.jsonl records carry it as ``final_loss``, so the log shows
        convergence next to throughput."""
        try:
            self.last_loss = float(loss)
        except (TypeError, ValueError):
            pass

    def note_numerics(self, kind: str, info: dict) -> dict:
        """A numerics verdict (``nonfinite`` / ``diverged`` — see
        core/numerics.py): same dump + health machinery as the watchdog
        verdicts, independent of ``HVD_WATCHDOG`` (a disabled step
        watchdog must not silence numerics events). The flight dump
        rides the existing rate-limit (``HVD_FLIGHT_MIN_INTERVAL``) and
        retention cap; ``last_verdict`` recency degrades ``/healthz`` to
        warn/503 exactly like a watchdog firing."""
        verdict = {"origin": info.get("origin", "numerics"),
                   "verdict": kind,
                   "wall_us": int(time.time() * 1e6)}
        verdict.update({k: v for k, v in info.items() if k != "origin"})
        tele.REGISTRY.counter(f"sentinel.verdict.{kind}").inc()
        events = self._flight_events()
        last_ts = events[-1].get("ts") if events else None
        events.append({"name": "NUMERICS_VERDICT", "ph": "i",
                       "ts": (int(last_ts) + 1
                              if isinstance(last_ts, (int, float))
                              else 0),
                       "args": {k: v for k, v in verdict.items()
                                if k != "dump"}})
        detail = (f"tensor {info['tensor']!r}" if info.get("tensor")
                  else f"step {info.get('step')}")
        who = info.get("ranks") or info.get("processes")
        verdict["dump"] = tl.dump_and_warn(
            events,
            f"numerics: {kind} at {detail}"
            + (f", bucket(s) {sorted(info['buckets'])}"
               if info.get("buckets") else "")
            + (f", rank(s)/process(es) {who}" if who else ""),
            None, LOG)
        self.last_verdict = verdict
        return verdict

    def note_hang(self, verdict: dict,
                  rank: Optional[int] = None) -> dict:
        """The hang doctor's attributed verdict (core/doctor.py) lands
        here as verdict kind ``hang``: counted under the existing
        ``sentinel.verdict.*`` vocabulary, recorded as ``last_verdict``
        (recency degrades ``/healthz`` to warn/503 exactly like a
        watchdog or numerics verdict). No flight dump of its own — the
        hang-class dump that triggered the diagnosis already embeds the
        doctor verdict, and a second dump here would only burn the rate
        limit."""
        v = {"origin": "doctor", "verdict": "hang",
             "wall_us": int(time.time() * 1e6)}
        if rank is not None:
            v["rank"] = rank
        v.update({k: val for k, val in verdict.items()
                  if k not in ("origin", "verdict", "wall_us")})
        tele.REGISTRY.counter("sentinel.verdict.hang").inc()
        self.last_verdict = v
        return v

    def set_flops_per_step(self, flops: Optional[float]):
        """Tell the sentinel the compiled step's FLOP cost so capture
        records can carry MFU (the training loop knows it from XLA cost
        analysis; the sentinel cannot derive it from a trace)."""
        self.flops_per_step = float(flops) if flops else None

    # -- anomaly pipeline ----------------------------------------------------

    def _fire(self, fired: dict) -> dict:
        verdict = dict(fired)
        verdict["wall_us"] = int(time.time() * 1e6)
        # Attribution priority: a recompile explains the whole excursion;
        # a straggler explains a collective-bound one; a fresh engine
        # stall explains a host-path one; otherwise the capture may still
        # attribute HBM traffic after it folds. The straggler delta must
        # be COMMENSURATE with the excursion (≥25% of step − baseline):
        # multi-process rounds accrue a few µs of skew every step, and
        # blaming a peer for an unrelated slow step would pre-empt the
        # stall/HBM attributions with an innocent name.
        excursion_us = max(
            0.0, fired["step_s"] - (fired.get("ewma_s") or 0.0)) * 1e6
        if fired.get("compiles"):
            verdict["verdict"] = "recompile"
        elif fired.get("straggler_wait_us", 0) > 0.25 * excursion_us:
            worst = tele.STRAGGLERS.worst()
            verdict["verdict"] = "straggler"
            if worst is not None:
                verdict["straggler"] = {"process": worst[0],
                                        "wait_us": worst[1]}
        elif self.last_stall and (time.time() - self.last_stall["wall"]
                                  < 10 * max(fired["step_s"], 1.0)):
            verdict["verdict"] = "engine_stall"
            verdict["stall"] = self.last_stall["reason"]
        else:
            verdict["verdict"] = "unattributed"
        tele.REGISTRY.counter("sentinel.anomalies").inc()
        # An "unattributed" verdict with a capture pending may still be
        # upgraded to "hbm_traffic" when the capture folds — defer its
        # per-verdict counter to _note_capture so the counters sum to
        # sentinel.anomalies instead of double-counting upgrades.
        defer_counter = (verdict["verdict"] == "unattributed"
                         and self.capture_on_anomaly)
        if not defer_counter:
            tele.REGISTRY.counter(
                f"sentinel.verdict.{verdict['verdict']}").inc()
        # Flight dump: engine ring if an engine is live, plus the verdict
        # itself as the trailing event (post-mortem readers see the
        # attribution next to the events that led to it).
        events = self._flight_events()
        # The verdict event must share the ring events' (timeline-
        # relative) clock, or ts-sorted readers (trace merge accepts
        # dump files) place it eons away from the events it explains.
        last_ts = events[-1].get("ts") if events else None
        events.append({"name": "WATCHDOG_VERDICT", "ph": "i",
                       "ts": (int(last_ts) + 1
                              if isinstance(last_ts, (int, float))
                              else 0),
                       "args": {k: v for k, v in verdict.items()
                                if k != "dump"}})
        verdict["dump"] = tl.dump_and_warn(
            events,
            f"watchdog: {verdict['origin']} step "
            f"{fired['step_s'] * 1e3:.1f} ms exceeded threshold "
            f"{fired['threshold_s'] * 1e3:.1f} ms "
            f"({verdict['verdict']})",
            None, LOG)
        # Bounded capture of the next few steps (opt-in by default only
        # when HVD_PROFILE_DIR is configured: an unsolicited
        # start_trace would collide with user captures).
        verdict["capture"] = None
        if self.capture_on_anomaly:
            verdict["capture"] = "pending"
            self.capture.request("watchdog", verdict)
        self.last_verdict = verdict
        return verdict

    def _flight_events(self) -> List[dict]:
        """The live engine's flight-recorder ring, when one exists (the
        compiled path has no engine — its dump carries telemetry + the
        verdict only)."""
        try:
            from horovod_tpu.core import engine as _eng

            e = _eng._engine
            if e is None:
                return []
            if hasattr(e, "recent_events"):  # native
                return list(e.recent_events())
            return list(e.timeline.recent())
        except Exception:
            return []

    def _note_capture(self, record: dict, prev_hbm_gb: Optional[float],
                      verdict: Optional[dict] = None):
        """Capture folded: finalize a pending HBM-jump attribution (and
        land the per-verdict counter _fire deferred). Only a WATCHDOG
        capture resolves a pending verdict — a periodic capture that was
        already running when the anomaly fired folds first and carries
        PRE-anomaly traffic; the armed watchdog request stays pending in
        AutoCapture and resolves the verdict when its own capture folds.
        ``verdict`` is the object that ARMED the capture (rode through
        AutoCapture) — never ``last_verdict``, which a second anomaly
        may have replaced by fold time."""
        v = verdict
        if record.get("kind") != "watchdog":
            return
        if v is not None and v.get("capture") == "pending":
            v["capture"] = record["capture_dir"]
            cur = record.get("hbm_gb_per_step")
            if (v.get("verdict") == "unattributed" and cur
                    and prev_hbm_gb
                    and cur > prev_hbm_gb * self.HBM_JUMP):
                v["verdict"] = "hbm_traffic"
                v["hbm_gb_per_step"] = cur
                v["hbm_gb_per_step_baseline"] = prev_hbm_gb
            if v.get("verdict") in ("unattributed", "hbm_traffic"):
                tele.REGISTRY.counter(
                    f"sentinel.verdict.{v['verdict']}").inc()

    # -- health --------------------------------------------------------------

    def health(self) -> dict:
        """The ``/healthz`` payload: watchdog verdict + last-step age.

        Degrades to ``warn`` (HTTP 503) on a recent verdict/stall AND on
        a **stale** loop — no observed step for longer than
        ``max(HVD_HEALTH_STALE_S (60), 20 × the largest origin EWMA)``.
        A rank hung inside a compiled-path collective stops calling
        observe_step entirely; without the staleness arm the endpoint
        would serve 200 forever through the one failure mode it most
        exists to catch. (A run that legitimately left its training
        loop — eval, checkpointing — also reads warn until steps
        resume: the endpoint measures training liveness.)"""
        draining = _draining_reason()
        now = time.time()
        age = (round(now - self.last_step_wall, 3)
               if self.last_step_wall else None)
        recent_verdict = (self.last_verdict is not None
                          and now - self.last_verdict["wall_us"] / 1e6
                          < 300)
        recent_stall = (self.last_stall is not None
                        and now - self.last_stall["wall"] < 300)
        stale_after = _env_float("HVD_HEALTH_STALE_S", 60.0)
        with self._lock:
            # Snapshot under the lock: the HTTP thread serves health()
            # while the training thread may be registering a new origin.
            wds = sorted(self.watchdogs.items())
            ewmas = [w.ewma for _, w in wds if w.ewma]
        if ewmas:
            stale_after = max(stale_after, 20.0 * max(ewmas))
        stale = age is not None and age > stale_after
        # Verdict recency is checked BEFORE the no-step-yet "init" arm:
        # a numerics verdict can fire from the engine path before any
        # training step is observed (core/numerics.py), and /healthz
        # must degrade on it regardless.
        # Serving-plane admission state (core/engine.py
        # admission_summary — covers both engines via the singleton):
        # queue depth, per-class in-flight vs budget, saturation.
        admission = None
        try:
            from horovod_tpu.core import engine as _eng

            admission = _eng.admission_summary()
        except Exception:  # pragma: no cover - defensive
            pass
        if draining is not None:
            # Deliberate drain (engine quiesce / graceful preemption):
            # load balancers must stop routing here NOW — the endpoint
            # serves non-200 for it (telemetry_http treats everything
            # outside ok/init as 503), and the payload says why.
            status = "draining"
        elif admission is not None and admission.get("saturated"):
            # Overload: at least one priority class is at its admission
            # budget RIGHT NOW — new submits in that class are being
            # rejected. Non-200 so load balancers route serving traffic
            # elsewhere until in-flight work drains below the budget.
            status = "saturated"
        elif recent_verdict or recent_stall:
            status = "warn"
        elif age is None:
            status = "init"
        elif stale:
            status = "warn"
        else:
            status = "ok"
        try:
            from horovod_tpu.core import numerics as _num

            numerics = _num.summary()
        except Exception:  # pragma: no cover - defensive
            numerics = None
        # Elastic world state (core/elastic.py): a shrunk world is a
        # DEGRADED deployment even when every surviving step is healthy
        # — /healthz must say so until the mesh regrows.
        world = None
        try:
            from horovod_tpu.core import elastic as _elastic

            world = _elastic.world_summary()
        except Exception:  # pragma: no cover - defensive
            pass
        if world is not None and world.get("degraded") \
                and status in ("ok", "init"):
            status = "warn"
        return {
            "status": status,
            "draining": draining,
            "admission": admission,
            "world": world,
            "rank": tl._process_index(),
            "pid": os.getpid(),
            "enabled": self.enabled,
            "last_step_age_s": age,
            "stale": stale,
            "stale_after_s": round(stale_after, 1),
            "watchdogs": {o: w.summary() for o, w in wds},
            "verdict": self.last_verdict,
            "stall": self.last_stall,
            "numerics": numerics,
            "capture": self.capture.summary(),
        }


_sentinel: Optional[Sentinel] = None
_sentinel_lock = threading.Lock()


def get_sentinel() -> Sentinel:
    global _sentinel
    with _sentinel_lock:
        if _sentinel is None:
            _sentinel = Sentinel()
        return _sentinel


def reset_sentinel():
    """Drop the singleton (tests only — the replacement re-reads env)."""
    global _sentinel
    with _sentinel_lock:
        _sentinel = None


def observe_step(step_s: float, origin: str = "step") -> Optional[dict]:
    """Module-level hook the Trainer / jit wrapper call per step. Never
    raises: the sentinel must not take the training loop down."""
    try:
        return get_sentinel().observe_step(step_s, origin)
    except Exception:  # pragma: no cover - defensive
        return None


def note_stall(reason: str, rank: Optional[int] = None):
    """Module-level hook the engines' stall paths call. Never raises."""
    try:
        get_sentinel().note_stall(reason, rank)
    except Exception:  # pragma: no cover - defensive
        pass


# Deliberate-drain marker (engine quiesce / graceful preemption): module
# state, not Sentinel state — a drain survives a sentinel reset and must
# be visible before any sentinel was ever built.
_draining: Optional[str] = None
_draining_lock = threading.Lock()


def note_draining(reason: Optional[str]):
    """Mark this process as draining (``/healthz`` answers ``draining``
    with a non-200 status until cleared with None). The engines' quiesce
    and the graceful-preemption ladder call it. Never raises."""
    global _draining
    with _draining_lock:
        _draining = str(reason) if reason is not None else None


def _draining_reason() -> Optional[str]:
    with _draining_lock:
        return _draining


def note_loss(loss):
    """Module-level hook the Trainer calls with the latest host-visible
    loss (epoch boundaries). Never raises."""
    try:
        get_sentinel().note_loss(loss)
    except Exception:  # pragma: no cover - defensive
        pass


def note_numerics(kind: str, info: dict) -> dict:
    """Module-level hook the numerics observatory calls. Unlike the
    other module hooks this RETURNS the verdict (the caller attributes
    and may raise under the halt policy) but still never raises
    itself."""
    try:
        return get_sentinel().note_numerics(kind, info)
    except Exception:  # pragma: no cover - defensive
        return {"verdict": kind, "dump": None}


def note_hang(verdict: dict, rank: Optional[int] = None):
    """Module-level hook the hang doctor calls with its attributed
    verdict. Never raises."""
    try:
        return get_sentinel().note_hang(verdict, rank)
    except Exception:  # pragma: no cover - defensive
        return None


def health() -> dict:
    return get_sentinel().health()


def set_flops_per_step(flops: Optional[float]):
    get_sentinel().set_flops_per_step(flops)
