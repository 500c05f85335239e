"""The performance sentinel (core/sentinel.py): watchdog anomaly semantics
(fire-once, cooldown, attribution), the periodic capture's ``perf.jsonl``
records, flight-dump retention, the /metrics + /healthz endpoint, and the
unified stats --json envelope."""

import contextlib
import io
import json
import os
import time

import numpy as np
import pytest

from horovod_tpu.core import sentinel as sen
from horovod_tpu.core import telemetry as tele


@pytest.fixture()
def fresh_sentinel(monkeypatch):
    """A sentinel rebuilt from THIS test's env (the suite default is
    HVD_WATCHDOG=0, see conftest) and torn down after, so one test's
    watchdog state never leaks into the next."""

    def make(**env):
        for k, v in env.items():
            if v is None:
                monkeypatch.delenv(k, raising=False)
            else:
                monkeypatch.setenv(k, str(v))
        sen.reset_sentinel()
        return sen.get_sentinel()

    yield make
    sen.reset_sentinel()
    tele.STRAGGLERS.reset()


# ---------------------------------------------------------------------------
# Watchdog semantics
# ---------------------------------------------------------------------------

def test_watchdog_warmup_fire_once_and_cooldown(fresh_sentinel, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=8,
                       HVD_WATCHDOG_COOLDOWN=5, HVD_PROFILE_DIR=None)
    # Warmup: nothing fires below min_steps, whatever the excursion.
    for _ in range(7):
        assert s.observe_step(0.010, origin="t") is None
    # Steady baseline, then one 20x step.
    for _ in range(10):
        assert s.observe_step(0.010, origin="t") is None
    v = s.observe_step(0.200, origin="t")
    assert v is not None and v["origin"] == "t"
    assert v["step_s"] == pytest.approx(0.2)
    assert v["threshold_s"] < 0.2
    assert v["verdict"] == "unattributed"
    assert v["dump"] and os.path.exists(v["dump"])
    dump = json.load(open(v["dump"]))
    assert dump["reason"].startswith("watchdog:")
    assert any(ev["name"] == "WATCHDOG_VERDICT" for ev in dump["events"])
    # Cooldown: repeated excursions are suppressed, not re-fired.
    for _ in range(5):
        assert s.observe_step(0.200, origin="t") is None
    wd = s.watchdog("t")
    assert wd.anomalies == 1 and wd.suppressed >= 1
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("hvd_flight")]
    assert len(dumps) == 1, dumps
    # Health reflects the verdict.
    h = s.health()
    assert h["status"] == "warn"
    assert h["verdict"]["verdict"] == "unattributed"
    assert h["watchdogs"]["t"]["anomalies"] == 1


def test_watchdog_recompile_attribution(fresh_sentinel, tmp_path,
                                        monkeypatch, hvd):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.core import compile_log

    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4,
                       HVD_PROFILE_DIR=None)

    @hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
    def watched_step(x):
        return x * 3

    watched_step(jnp.ones((hvd.size(), 2)))  # the first compile: no news
    for _ in range(10):
        s.observe_step(0.010, origin="d")
    # A new shape recompiles the function DURING the anomalous step: the
    # compile log notes it, and the watchdog asks the log.
    before = compile_log.LOG.compiled
    watched_step(jnp.ones((2 * hvd.size(), 2)))
    compiled = compile_log.LOG.compiled - before
    v = s.observe_step(0.300, origin="d")
    assert v is not None and v["verdict"] == "recompile"
    assert v["compiles"] == compiled >= 1
    assert v["recompile"]["name"] == "watched_step"
    assert v["recompile"]["dispatch"] == 1
    assert v["recompile"]["backend_s"] > 0
    assert v["recompile"]["cache"] in ("off", "miss", "hit")
    assert v["recompile"] == hvd.telemetry()["compile_log"]["recompiles"][-1]
    # A slow step during which nothing compiled carries neither.
    quiet = sen.StepWatchdog("quiet", min_steps=4)
    for _ in range(10):
        quiet.observe(0.010)
    fired = quiet.observe(0.300)
    assert fired is not None
    assert fired["compiles"] == 0 and "recompile" not in fired


def test_watchdog_counts_backend_compiles_not_lowerings(hvd):
    """A program lowered during a slow step and not compiled (a bare
    ``lower()``) is no recompile: the verdict's count is the backend's."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.core import compile_log

    @hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
    def only_lowered(x):
        return x - 2

    dog = sen.StepWatchdog("lowering", min_steps=4)
    for _ in range(10):
        dog.observe(0.010)
    x = jnp.ones((hvd.size(), 2))
    programs, compiled = compile_log.LOG.programs, compile_log.LOG.compiled
    lowered = only_lowered.lower(x)
    assert compile_log.LOG.programs == programs + 1
    fired = dog.observe(0.300)
    assert fired is not None and fired["compiles"] == 0
    lowered.compile()
    assert compile_log.LOG.compiled == compiled + 1


def test_watchdog_straggler_attribution(fresh_sentinel, tmp_path,
                                        monkeypatch):
    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4,
                       HVD_PROFILE_DIR=None)
    for _ in range(10):
        s.observe_step(0.010, origin="t")
    # The negotiation tables charged process 1 during the slow step —
    # the verdict cross-references the telemetry straggler report.
    tele.STRAGGLERS.observe("grad/7", {0: 100.0, 1: 100.5})
    v = s.observe_step(0.300, origin="t")
    assert v is not None and v["verdict"] == "straggler"
    assert v["straggler"]["process"] == 1
    assert v["straggler"]["wait_us"] == pytest.approx(5e5, rel=0.01)


def test_watchdog_stall_attribution(fresh_sentinel, tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4,
                       HVD_PROFILE_DIR=None)
    for _ in range(10):
        s.observe_step(0.010, origin="t")
    sen.note_stall("stalled tensors: grad/3 (61s)")
    v = s.observe_step(0.300, origin="t")
    assert v is not None and v["verdict"] == "engine_stall"
    assert "grad/3" in v["stall"]
    assert s.health()["stall"]["reason"].startswith("stalled tensors")


def test_one_step_observed_via_two_origins_counts_once(fresh_sentinel,
                                                       tmp_path,
                                                       monkeypatch):
    """A keras Trainer step is seen twice — the wrapped jit reports its
    dispatch, then the Trainer reports wall time. Capture stepping must
    follow ONE origin (trainer preferred), and one slow step must not
    dump through both watchdogs."""
    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4,
                       HVD_PROFILE_DIR=None)
    for _ in range(10):  # interleaved, like a real Trainer step
        s.observe_step(0.008, origin="jax.dispatch")
        s.observe_step(0.010, origin="trainer")
    # The capture state machine advanced once per REAL step (plus the
    # one pre-upgrade dispatch observation of the very first step).
    assert s.capture._step <= 11
    assert s._capture_origin == "trainer"
    # One slow step, seen through both lenses: exactly one firing.
    v1 = s.observe_step(0.400, origin="jax.dispatch")
    v2 = s.observe_step(0.402, origin="trainer")
    fired = [v for v in (v1, v2) if v is not None]
    assert len(fired) == 1, (v1, v2)
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("hvd_flight")]
    assert len(dumps) == 1, dumps
    total = (s.watchdogs["jax.dispatch"].anomalies
             + s.watchdogs["trainer"].anomalies)
    assert total == 1


def test_telemetry_port_zero_means_disabled(monkeypatch):
    from horovod_tpu.core import telemetry, telemetry_http

    telemetry_http.stop()
    monkeypatch.setattr(telemetry, "_http_started", False)
    monkeypatch.setenv("HVD_TELEMETRY_PORT", "0")
    telemetry._maybe_start_http()
    assert telemetry_http.current_port() is None
    # And a malformed value is ignored, not fatal.
    monkeypatch.setattr(telemetry, "_http_started", False)
    monkeypatch.setenv("HVD_TELEMETRY_PORT", "not-a-port")
    telemetry._maybe_start_http()
    assert telemetry_http.current_port() is None


def test_watchdog_disabled_still_tracks_health(fresh_sentinel):
    s = fresh_sentinel(HVD_WATCHDOG=0)
    assert s.observe_step(10.0, origin="t") is None
    h = s.health()
    assert h["enabled"] is False
    assert h["last_step_age_s"] is not None


def test_health_warns_on_stale_loop(fresh_sentinel):
    """A rank hung inside a compiled-path collective stops observing
    steps entirely — /healthz must degrade on staleness, not just on
    verdicts/stalls."""
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4)
    for _ in range(6):
        s.observe_step(0.010, origin="t")
    assert s.health()["status"] == "ok"
    s.last_step_wall = time.time() - 120  # 2 min of silence
    h = s.health()
    assert h["status"] == "warn" and h["stale"] is True
    assert h["stale_after_s"] >= 60.0


# ---------------------------------------------------------------------------
# Tier-1 integration: injected slow step on the 8-device mesh
# ---------------------------------------------------------------------------

def test_trainer_slow_step_dumps_and_attributes_once(hvd, tmp_path,
                                                     monkeypatch,
                                                     fresh_sentinel):
    """ISSUE 6 acceptance: one artificially slow training step on the
    8-device CPU mesh yields exactly one flight dump + one attributed
    watchdog verdict — no re-trigger storm."""
    import optax

    import horovod_tpu.keras as hvd_keras
    from horovod_tpu.models import MnistMLP

    rng = np.random.RandomState(0)
    x = rng.randn(256, 8, 8, 1).astype(np.float32)
    y = (rng.rand(256) * 10).astype(np.int32) % 10

    # Build + compile with the suite-default (disabled) sentinel: the
    # first-call compile must not pollute the baseline window.
    t = hvd_keras.Trainer(MnistMLP(hidden=16), optax.sgd(0.1))
    t.fit(x, y, batch_size=2, epochs=1, shuffle=False)

    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    # Wide margins (30× EWMA / 10× p99): ordinary one-core-host jitter
    # (GC pauses, a sibling process) must not fire before the injected
    # step — a spurious firing would open the cooldown and suppress the
    # real anomaly (observed flake: a 14 ms jitter step beat a 2×p99
    # threshold of ~10 ms).
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=8,
                       HVD_WATCHDOG_FACTOR=30, HVD_WATCHDOG_P99_MULT=10,
                       HVD_WATCHDOG_COOLDOWN=1000, HVD_PROFILE_DIR=None)

    # Bypass the _InstrumentedJit wrapper (call the inner jitted object)
    # so ONLY the trainer origin observes this fit: the dispatch origin's
    # µs-scale baseline would make it the jitter-flake magnet.
    real = getattr(t._train_step, "_jitted", t._train_step)
    calls = {"n": 0}

    def injected(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 12:  # past the 8-step warmup
            time.sleep(1.5)
        return real(*args, **kwargs)

    t._train_step = injected
    t.fit(x, y, batch_size=2, epochs=1, shuffle=False)  # 16 steps

    wd = s.watchdog("trainer")
    assert wd.steps == 16
    assert wd.anomalies == 1, wd.summary()
    v = s.last_verdict
    assert v is not None and v["origin"] == "trainer"
    assert v["step_s"] > 1.0
    assert v["verdict"] in ("unattributed", "recompile", "straggler",
                            "engine_stall")
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("hvd_flight")]
    assert len(dumps) == 1, dumps
    dump = json.load(open(tmp_path / dumps[0]))
    assert "watchdog: trainer step" in dump["reason"]
    assert s.health()["status"] == "warn"


# ---------------------------------------------------------------------------
# Auto-capture: bounded capture -> perf.jsonl record
# ---------------------------------------------------------------------------

def test_autocapture_periodic_appends_perf_jsonl(hvd, tmp_path,
                                                 fresh_sentinel):
    import jax
    import jax.numpy as jnp

    s = fresh_sentinel(HVD_WATCHDOG=0, HVD_PROFILE_DIR=str(tmp_path),
                       HVD_PROFILE_EVERY=6, HVD_PROFILE_STEPS=2)
    f = jax.jit(lambda a: a @ a)
    a = jnp.ones((32, 32))
    for _ in range(9):
        t0 = time.perf_counter()
        f(a).block_until_ready()
        s.observe_step(time.perf_counter() - t0, origin="cap")
    pj = os.path.join(str(tmp_path), "perf.jsonl")
    deadline = time.monotonic() + 60
    rec = None
    while time.monotonic() < deadline and rec is None:
        if os.path.exists(pj):
            lines = open(pj).read().splitlines()
            if lines:
                rec = json.loads(lines[-1])
                break
        time.sleep(0.2)
    assert rec is not None, "no perf.jsonl record appeared"
    assert rec["kind"] == "periodic" and rec["steps"] == 2
    assert rec["step_time_ms"] is not None
    assert os.path.isdir(rec["capture_dir"])


# ---------------------------------------------------------------------------
# Flight-dump retention cap
# ---------------------------------------------------------------------------

def test_flight_dump_retention_cap(tmp_path, monkeypatch):
    from horovod_tpu.core import timeline as tl

    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_FLIGHT_KEEP", "3")
    paths = []
    for i in range(7):
        p = tl.dump_flight_recorder([{"name": "X", "ph": "i", "ts": i}],
                                    f"r{i}")
        assert p is not None
        paths.append(p)
        time.sleep(0.002)  # distinct mtimes/wall_us across dumps
    kept = sorted(f for f in os.listdir(tmp_path)
                  if f.startswith("hvd_flight"))
    assert len(kept) == 3, kept
    # The newest dumps survive; the older ones are gone.
    for new in paths[-3:]:
        assert os.path.exists(new), kept
    for old in paths[:4]:
        assert not os.path.exists(old), kept
    # An explicit path (the engines' tests pass one) is never pruned.
    explicit = tmp_path / "explicit.json"
    tl.dump_flight_recorder([], "explicit", path=str(explicit))
    assert explicit.exists()


def test_flight_dump_same_reason_rate_limited(tmp_path, monkeypatch):
    """A poisoned negotiation re-raises the same failure every ~5 ms
    cycle: dump_and_warn must land the first dump and drop same-reason
    repeats inside HVD_FLIGHT_MIN_INTERVAL (distinct reasons still
    land immediately)."""
    import logging

    from horovod_tpu.core import timeline as tl

    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_FLIGHT_MIN_INTERVAL", "30")
    log = logging.getLogger("test.flight")
    first = tl.dump_and_warn([], "negotiation failed: peer died", 0, log)
    assert first is not None and os.path.exists(first)
    for _ in range(5):
        assert tl.dump_and_warn([], "negotiation failed: peer died",
                                0, log) is None
    other = tl.dump_and_warn([], "stalled tensors: grad/1", 0, log)
    assert other is not None and other != first
    files = [f for f in os.listdir(tmp_path) if f.startswith("hvd_flight")]
    assert len(files) == 2, files


def test_flight_keep_env_parsing(monkeypatch):
    from horovod_tpu.core import timeline as tl

    monkeypatch.delenv("HVD_FLIGHT_KEEP", raising=False)
    assert tl.flight_keep() == 8
    monkeypatch.setenv("HVD_FLIGHT_KEEP", "not-a-number")
    assert tl.flight_keep() == 8
    monkeypatch.setenv("HVD_FLIGHT_KEEP", "0")
    assert tl.flight_keep() == 1  # at least the newest dump survives


# ---------------------------------------------------------------------------
# Profiler: empty captures fail loudly
# ---------------------------------------------------------------------------

def test_profiler_capture_raises_on_empty_capture(tmp_path, monkeypatch):
    from horovod_tpu.utils import profiler

    # A "profiler" that records nothing (the plugin-missing /
    # concurrent-trace failure mode).
    monkeypatch.setattr(profiler, "profile",
                        lambda d: contextlib.nullcontext())
    with pytest.raises(profiler.CaptureError, match="no \\*.xplane.pb"):
        profiler.capture(lambda v: v, 1.0, logdir=str(tmp_path), iters=1)


# ---------------------------------------------------------------------------
# /metrics + /healthz endpoint and the unified stats --json envelope
# ---------------------------------------------------------------------------

@pytest.fixture()
def http_endpoint(fresh_sentinel):
    from horovod_tpu.core import telemetry_http

    fresh_sentinel(HVD_WATCHDOG=0)
    telemetry_http.stop()
    port = telemetry_http.maybe_start(0)  # ephemeral port
    assert port
    yield f"http://127.0.0.1:{port}"
    telemetry_http.stop()


def test_http_endpoint_serves_metrics_and_healthz(http_endpoint):
    import urllib.request

    tele.REGISTRY.counter("sentinel.test_counter").inc(3)
    text = urllib.request.urlopen(
        http_endpoint + "/metrics", timeout=5).read().decode()
    assert "hvd_sentinel_test_counter 3" in text
    resp = urllib.request.urlopen(http_endpoint + "/healthz", timeout=5)
    h = json.loads(resp.read())
    assert resp.status == 200  # no steps yet -> "init", still healthy
    assert h["status"] in ("init", "ok")
    assert "watchdogs" in h and "pid" in h
    # Unknown paths 404 with a hint.
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(http_endpoint + "/nope", timeout=5)
    assert ei.value.code == 404


def test_healthz_degrades_to_503_on_warn(http_endpoint, fresh_sentinel,
                                         tmp_path, monkeypatch):
    import urllib.request

    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    s = fresh_sentinel(HVD_WATCHDOG=1, HVD_WATCHDOG_MIN_STEPS=4,
                       HVD_PROFILE_DIR=None)
    for _ in range(8):
        s.observe_step(0.01, origin="t")
    s.observe_step(0.5, origin="t")  # anomaly -> warn
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(http_endpoint + "/healthz", timeout=5)
    assert ei.value.code == 503
    assert json.loads(ei.value.read())["status"] == "warn"
    # The stats CLI still shows the payload on 503 — the warn state is
    # exactly when the operator queries /healthz.
    from horovod_tpu.utils import stats

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert stats.main([http_endpoint + "/healthz"]) == 0
    assert json.loads(buf.getvalue())["status"] == "warn"
    # --json passes the health document through instead of burying it
    # in an empty-samples envelope.
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert stats.main([http_endpoint + "/healthz", "--json"]) == 0
    h = json.loads(buf.getvalue())
    assert h["status"] == "warn" and "watchdogs" in h


def _stats_json(argv):
    from horovod_tpu.utils import stats

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert stats.main(argv) == 0
    return json.loads(buf.getvalue())


def test_stats_json_shape_identical_across_sources(http_endpoint,
                                                   tmp_path):
    """ISSUE 6 satellite: one envelope shape — {source, target, samples}
    with {name, labels, value} samples — whatever the source."""
    tele.REGISTRY.counter("sentinel.shape_probe").inc()
    # file source
    path = str(tmp_path / "expo.prom")
    from horovod_tpu.core import telemetry

    open(path, "w").write(telemetry.prometheus())
    envs = {
        "file": _stats_json([path, "--json"]),
        "live": _stats_json(["live", "--json"]),
        "http": _stats_json([http_endpoint, "--json"]),
    }
    for src, env in envs.items():
        assert set(env) == {"source", "target", "samples"}, src
        assert env["source"] == src
        assert env["samples"], src
        assert all(set(s) == {"name", "labels", "value"}
                   for s in env["samples"]), src
    probe = "hvd_sentinel_shape_probe"
    for src, env in envs.items():
        assert any(s["name"] == probe for s in env["samples"]), src
    # file and http carry byte-identical sample lists (same exposition
    # text modulo the instant it was read) — compare the probe value.
    get = lambda env: [s["value"] for s in env["samples"]  # noqa: E731
                       if s["name"] == probe][0]
    assert get(envs["file"]) <= get(envs["http"])


def test_stats_watch_works_against_http(http_endpoint, monkeypatch,
                                        capsys):
    from horovod_tpu.utils import stats

    sleeps = []

    def fake_sleep(seconds):
        sleeps.append(seconds)
        if len(sleeps) >= 2:
            raise KeyboardInterrupt

    monkeypatch.setattr(stats.time, "sleep", fake_sleep)
    assert stats.main([http_endpoint, "--watch", "0.25"]) == 0
    out = capsys.readouterr().out
    assert sleeps == [0.25, 0.25]
    assert out.count("hvd_") >= 2  # redrawn at least twice


def test_launcher_exposes_telemetry_port_flag():
    import horovod_tpu.run as launcher

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), pytest.raises(SystemExit):
        launcher.main(["--help"])
    assert "--telemetry-port-base" in buf.getvalue()


# ---------------------------------------------------------------------------
# Numerics observatory satellites (ISSUE 8): the convergence column
# ---------------------------------------------------------------------------


def test_sentinel_note_loss_feeds_capture_records():
    from horovod_tpu.core import sentinel as sn

    sn.reset_sentinel()
    try:
        s = sn.get_sentinel()
        assert s.last_loss is None
        sn.note_loss(2.5)
        assert s.last_loss == 2.5
        sn.note_loss("not-a-number")  # ignored, never raises
        assert s.last_loss == 2.5
        sn.note_loss(np.float32(1.25))  # host scalars coerce
        assert s.last_loss == 1.25
    finally:
        sn.reset_sentinel()
