"""The unified telemetry core (core/telemetry.py): registry semantics,
eager-path counters, engine-path counters with native/python parity,
compiled-path rings, and the exposition/stats-CLI surfaces (reference
rationale: Horovod's production observability — timeline + stall/straggler
analysis, arxiv 1802.05799 §5)."""

import json
import threading
import time

import numpy as np
import pytest

from horovod_tpu.core import telemetry as tele


def _counters():
    return dict(tele.REGISTRY.flat_counters())


def _delta(before, after):
    """Counter deltas between two flat_counters() snapshots (the global
    registry is process-wide and monotonic, so tests compare deltas)."""
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d:
            out[k] = d
    return out


# ---------------------------------------------------------------------------
# Registry unit semantics
# ---------------------------------------------------------------------------

def test_registry_metric_kinds():
    r = tele.Registry()
    r.counter("a.count").inc()
    r.counter("a.count").inc(4)
    r.gauge("a.depth").set(7)
    h = r.histogram("a.lat", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)  # overflow bucket
    ring = r.ring("a.ring", size=2)
    for v in (1.0, 2.0, 3.0):
        ring.push(v)

    snap = r.snapshot()
    assert snap["a"]["count"] == 5
    assert snap["a"]["depth"] == 7
    assert snap["a"]["lat"]["count"] == 3
    assert snap["a"]["lat"]["sum"] == pytest.approx(5.55)
    # Ring keeps the window (2) but counts everything (3).
    assert snap["a"]["ring"]["count"] == 3
    assert snap["a"]["ring"]["last"] == 3.0
    assert snap["a"]["ring"]["window"] == 2
    # get-or-create returns the same object; kind mismatches are errors.
    assert r.counter("a.count").snapshot() == 5
    with pytest.raises(TypeError):
        r.gauge("a.count")


def test_registry_thread_safety():
    r = tele.Registry()
    c = r.counter("n")

    def spin():
        for _ in range(10000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.snapshot() == 40000


def test_straggler_tracker_blames_latest():
    s = tele.StragglerTracker()
    # grad/0 and grad/1 aggregate into one class; process 1 is late.
    s.observe("grad/0", {0: 10.0, 1: 10.5})
    s.observe("grad/1", {0: 20.0, 1: 20.25})
    s.observe("loss", {0: 30.1, 1: 30.0})
    pid, us = s.worst()
    assert pid == 1
    assert us == pytest.approx(750000, abs=2)
    snap = s.snapshot()
    assert snap["tensors"] == 3
    assert set(snap["by_class"]) == {"grad/#", "loss"}
    assert snap["by_class"]["grad/#"][1] == pytest.approx(750000, abs=2)
    assert snap["by_class"]["loss"][0] == pytest.approx(100000, abs=2)
    assert any("process 1" in ln for ln in s.report_lines())
    # Single-participant observations carry no blame.
    s2 = tele.StragglerTracker()
    s2.observe("x", {0: 1.0})
    assert s2.worst() is None


def test_prometheus_round_trip_through_stats_cli():
    from horovod_tpu.utils import stats

    r = tele.Registry()
    r.counter("engine.completed").inc(3)
    r.gauge("engine.queue_depth").set(2)
    h = r.histogram("engine.negotiation_s", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    r.ring("jax.dispatch_s").push(0.01)
    text = r.prometheus()
    samples = stats.parse_prometheus(text)
    by_name = {n: v for n, _, v in samples}
    assert by_name["hvd_engine_completed"] == 3
    assert by_name["hvd_engine_queue_depth"] == 2
    assert by_name["hvd_engine_negotiation_s_count"] == 2
    assert by_name["hvd_engine_negotiation_s_sum"] == pytest.approx(0.55)
    assert by_name["hvd_jax_dispatch_s_count"] == 1
    # Cumulative bucket counts parse with their labels.
    buckets = [(l, v) for n, l, v in samples
               if n == "hvd_engine_negotiation_s_bucket"]
    assert ({"le": "0.1"}, 1.0) in buckets
    assert ({"le": "+Inf"}, 2.0) in buckets
    table = stats.render(samples)
    assert "hvd_engine_completed" in table
    assert "hvd_engine_negotiation_s" in table


def test_telemetry_file_exposition(tmp_path):
    from horovod_tpu.utils import stats

    path = str(tmp_path / "telemetry.prom")
    tele.REGISTRY.counter("engine.completed").inc(0)  # ensure it exists
    tele.flush_to_file(path)
    samples = stats.parse_prometheus(open(path).read())
    assert any(n == "hvd_engine_completed" for n, _, _ in samples)
    # The stats CLI over the file prints a table.
    rc = stats.main([path])
    assert rc == 0


# ---------------------------------------------------------------------------
# (a) eager path
# ---------------------------------------------------------------------------

def test_eager_collectives_counted(hvd):
    import jax.numpy as jnp

    before = _counters()
    hvd.allreduce(jnp.ones((16,), jnp.float32), average=False)
    hvd.broadcast(jnp.ones((4,), jnp.float32), 0)
    hvd.allgather(jnp.ones((2, 3), jnp.float32))
    d = _delta(before, _counters())
    assert d["eager.allreduce.count"] == 1
    assert d["eager.allreduce.bytes"] == 64
    assert d["eager.broadcast.count"] == 1
    assert d["eager.allgather.count"] == 1
    # 8-rank world: nothing elided.
    assert "eager.allreduce.elided" not in d

    snap = hvd.telemetry()
    assert snap["eager"]["allreduce"]["count"] >= 1
    assert isinstance(hvd.telemetry_report(), str)
    assert "eager.allreduce.count" in hvd.telemetry_report()


# ---------------------------------------------------------------------------
# (b) engine path — python and native, real executor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["python", "native"])
def test_engine_async_counters_real_executor(hvd, impl):
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine

    before = _counters()
    e = (NativeEngine(timeline_path="") if impl == "native"
         else Engine(timeline=tl.Timeline(None)))
    try:
        h = e.allreduce_async("tele/x", np.ones((8,), np.float32), False)
        np.testing.assert_allclose(e.synchronize(h), np.full((8,), 8.0))
    finally:
        e.shutdown()
    d = _delta(before, _counters())
    assert d["engine.submitted.allreduce"] == 1
    assert d["engine.submitted.bytes"] == 32
    assert d["engine.completed"] == 1
    assert d.get("engine.cycles", 0) >= 1
    assert "engine.errors" not in d


class _EchoExecutor:
    """Deterministic local data plane (no mesh): identity results."""

    def allreduce(self, flat, average):
        return flat.copy()

    def allgather(self, t):
        return np.tile(t, (2,) + (1,) * (t.ndim - 1))

    def broadcast(self, t, root):
        return t.copy()


def _submit_sequence(engine):
    """Identical submit sequence for the parity contract: synchronize
    after each enqueue so batching is deterministic (one entry per
    cycle)."""
    engine.synchronize(
        engine.allreduce_async("p/a", np.ones((4,), np.float32), False))
    engine.synchronize(
        engine.allreduce_async("p/b", np.ones((4,), np.float32), False))
    engine.synchronize(
        engine.allgather_async("p/g", np.ones((2, 3), np.float32)))
    engine.synchronize(
        engine.broadcast_async("p/c", np.ones((5,), np.float32), 0))
    engine.shutdown()


def test_native_python_counter_parity(hvd):
    """Same counter names, same values, for an identical submit sequence
    on both engines (the ISSUE's parity criterion). Wall-clock-dependent
    counters (cycles, cycle_seconds) are compared by presence, not
    value."""
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine

    TIMING = ("engine.cycles", "engine.cycle_seconds_total")

    before = _counters()
    _submit_sequence(Engine(executor=_EchoExecutor(),
                            timeline=tl.Timeline(None)))
    d_py = _delta(before, _counters())

    before = _counters()
    _submit_sequence(NativeEngine(executor=_EchoExecutor(),
                                  timeline_path=""))
    d_nat = _delta(before, _counters())

    # Buffer-pool event counts are implementation-scoped (the C++ engine
    # pools its entry/fusion/result buffers, the python engine its
    # snapshot/fusion/output buffers), so engine.pool.* is compared by
    # presence, not value — both engines must FEED the same names.
    def _core(d):
        return {k: v for k, v in d.items()
                if not k.startswith("engine.pool.")}

    assert set(_core(d_py)) == set(_core(d_nat)), (d_py, d_nat)
    for k in set(_core(d_py)) - set(TIMING):
        if k.endswith("seconds_total"):
            continue
        assert d_py[k] == d_nat[k], (k, d_py[k], d_nat[k])
    for d in (d_py, d_nat):
        assert d.get("engine.pool.checkouts", 0) > 0, d
    expected = {
        "engine.submitted.allreduce": 2,
        "engine.submitted.allgather": 1,
        "engine.submitted.broadcast": 1,
        "engine.submitted.bytes": 16 + 16 + 24 + 20,
        "engine.completed": 4,
    }
    for k, v in expected.items():
        assert d_py[k] == v, (k, d_py[k])
    for d in (d_py, d_nat):
        assert d.get("engine.cycles", 0) >= 1
        assert "engine.errors" not in d


class _PlugExecutor:
    """First allreduce blocks until released — tensors enqueued meanwhile
    pile up and fuse on the next drain (the deterministic fusion driver
    from test_timeline_profiler.py)."""

    def __init__(self):
        self.gate = threading.Event()
        self.started = threading.Event()
        self.calls = 0

    def allreduce(self, flat, average):
        self.calls += 1
        if self.calls == 1:
            self.started.set()
            self.gate.wait(5.0)
        return flat.copy()


@pytest.mark.parametrize("impl", ["python", "native"])
def test_fusion_counters(hvd, impl):
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine

    ex = _PlugExecutor()
    before = _counters()
    if impl == "native":
        e = NativeEngine(executor=ex, timeline_path="")
    else:
        e = Engine(executor=ex, timeline=tl.Timeline(None))
    h0 = e.allreduce_async("f/plug", np.ones((2,), np.float32), False)
    assert ex.started.wait(5.0)
    ha = e.allreduce_async("f/a", np.ones((4,), np.float32), False)
    hb = e.allreduce_async("f/b", np.ones((4,), np.float32), False)
    ex.gate.set()
    for h in (h0, ha, hb):
        e.synchronize(h)
    e.shutdown()
    d = _delta(before, _counters())
    assert d["engine.fused.batches"] == 1
    assert d["engine.fused.tensors"] == 2
    assert d["engine.fused.bytes"] == 32
    assert d["engine.completed"] == 3


def test_error_counter(hvd):
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine, EngineError

    class Boom:
        def allreduce(self, flat, average):
            raise RuntimeError("boom")

    before = _counters()
    e = Engine(executor=Boom(), timeline=tl.Timeline(None))
    try:
        h = e.allreduce_async("err/x", np.ones((2,), np.float32), False)
        with pytest.raises(EngineError):
            e.synchronize(h)
    finally:
        e.shutdown()
    d = _delta(before, _counters())
    assert d["engine.errors"] == 1
    assert "engine.completed" not in d


# ---------------------------------------------------------------------------
# (c) compiled path — jit dispatch ring + Trainer step ring
# ---------------------------------------------------------------------------

def test_jit_dispatch_ring(hvd):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hj
    from horovod_tpu.ops import collectives as C

    @hj.jit(in_specs=(P(hj.HVD_AXIS),), out_specs=P())
    def step(x):
        return C.allreduce(x[0], average=False)

    x = C.make_ranked([jnp.full((3,), float(i)) for i in range(hvd.size())])
    before = _counters()
    n0 = tele.REGISTRY.ring("jax.dispatch_s").count
    np.testing.assert_allclose(np.asarray(step(x)),
                               np.full((3,), float(sum(range(8)))))
    d = _delta(before, _counters())
    assert d["jax.dispatches"] == 1
    assert tele.REGISTRY.ring("jax.dispatch_s").count == n0 + 1
    # AOT surface still reachable through the wrapper (benchmark/run.py
    # relies on .lower/.compile bypassing instrumentation).
    assert "all-reduce" in step.lower(x).compile().as_text()


def test_trainer_step_telemetry(hvd):
    import optax

    import horovod_tpu.keras as hvd_keras
    from horovod_tpu.models import MnistMLP

    rng = np.random.RandomState(0)
    x = rng.randn(32, 8, 8, 1).astype(np.float32)
    y = (rng.rand(32) * 10).astype(np.int32) % 10

    before = _counters()
    t = hvd_keras.Trainer(MnistMLP(hidden=8), optax.sgd(0.1))
    t.fit(x, y, batch_size=2, epochs=1)
    d = _delta(before, _counters())
    steps = 32 // (2 * hvd.local_size())
    assert d["trainer.steps"] == steps
    assert d["jax.dispatches"] >= steps
    ring = tele.REGISTRY.ring("trainer.step_s").snapshot()
    assert ring["count"] >= steps and ring["last"] > 0


# ---------------------------------------------------------------------------
# satellites: Metric.avg memoization + MetricAverage routing
# ---------------------------------------------------------------------------

def test_metric_avg_memoized(hvd, monkeypatch):
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.utils import metrics

    calls = {"n": 0}
    real = C.allreduce

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(C, "allreduce", counting)
    m = metrics.Metric("loss")
    m.update(2.0)
    m.update(4.0)
    assert m.avg == pytest.approx(3.0)
    assert m.avg == pytest.approx(3.0)  # memoized: no second collective
    assert calls["n"] == 1
    m.update(6.0)
    assert m.avg == pytest.approx(4.0)  # state advanced: one more
    assert calls["n"] == 2


def test_metric_average_routed_through_registry(hvd):
    from horovod_tpu.utils import metrics

    before = _counters()
    out = metrics.MetricAverage({"loss": 1.0, "acc": 0.5})
    d = _delta(before, _counters())
    assert out["loss"] == pytest.approx(1.0)
    assert d["metrics.averages"] == 1
    assert d["metrics.averaged_values"] == 2
    # The underlying collective is counted with every other eager op.
    assert d["eager.allreduce.count"] == 1
