"""The compile log (core/compile_log.py): one record a program jax
compiles, with its stages, its cache result and the framework span that
caused it; the spans of ``hvd.init`` and ``broadcast_parameters``; a
recompile by function and dispatch. Read where an operator reads it:
``hvd.telemetry()["compile_log"]`` and the ``jax.compile.*`` series."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring
from jax.sharding import PartitionSpec as P

from horovod_tpu.core import compile_log as clog
from horovod_tpu.core import telemetry as tele

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _listen(log, on: bool):
    """``log``'s three listeners on or off jax's lists."""
    if on:
        monitoring.register_event_time_span_listener(log.on_time_span)
        monitoring.register_event_duration_secs_listener(log.on_duration)
        monitoring.register_event_listener(log.on_event)
    else:
        monitoring.unregister_event_time_span_listener(log.on_time_span)
        monitoring.unregister_event_duration_listener(log.on_duration)
        monitoring.unregister_event_listener(log.on_event)


@pytest.fixture()
def fresh_log(hvd, monkeypatch):
    """A log of this test's own in the process's place: other tests of
    this worker have compiled hundreds of programs, and a log keeps the
    first 256."""
    process, fresh = clog.LOG, clog.CompileLog()
    monkeypatch.setattr(clog, "LOG", fresh)
    _listen(process, False)
    _listen(fresh, True)
    yield fresh
    _listen(fresh, False)
    _listen(process, True)


@pytest.fixture()
def log(fresh_log, hvd):
    return lambda: hvd.telemetry()["compile_log"]


def _named(records, name):
    return [r for r in records if r["name"] == name]


def _program(target, name, start, trace=0.0, lower=0.25, backend=0.5,
             cache=None):
    """One program's events as jax fires them, on this thread: each stage
    when it ends, with its start and its end."""
    if trace:
        target.on_time_span(clog.TRACE, start, start + trace, fun_name=name)
    jit_name = f"jit({name})"
    start += trace
    target.on_time_span(clog.LOWER, start, start + lower, fun_name=jit_name)
    if cache:
        target.on_event(f"/jax/compilation_cache/cache_{cache}")
    start += lower
    target.on_time_span(clog.BACKEND, start, start + backend,
                        fun_name=jit_name)


# ---------------------------------------------------------------------------
# Cache result: a miss in one process, a hit in the next
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
hvd.init()

@hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
def tiny_step(x):
    return jnp.sin(x) * 3

tiny_step(jnp.ones((hvd.size(), 2))).block_until_ready()
t = hvd.telemetry()
print(json.dumps({"records": [r for r in t["compile_log"]["records"]
                              if r["name"] == "tiny_step"],
                  "series": t["jax"]["compile"]}))
"""


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, str(cache)],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out.append(json.loads(proc.stdout.splitlines()[-1]))
    return out


@pytest.mark.parametrize("run,cache", [(0, "miss"), (1, "hit")])
def test_a_first_process_reads_miss_and_a_second_hit(two_processes, run,
                                                     cache):
    got = two_processes[run]
    (rec,) = got["records"]
    assert rec["cache"] == cache
    assert rec["cause"] == "hvd.jax.jit:tiny_step"
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert rec["start"] < rec["end"]
    series = got["series"]
    if cache == "miss":
        assert series["cache_misses"] >= 1
        assert rec["retrieval_s"] == 0.0
        assert "cache_hits" not in series
    else:
        # Every program of the first process is in the cache now.
        assert series["cache_hits"] >= 1 and "cache_misses" not in series
        assert rec["retrieval_s"] > 0
        assert series["cache_retrieval_s"] >= rec["retrieval_s"]
        assert rec["backend_s"] >= rec["retrieval_s"]


# ---------------------------------------------------------------------------
# Folding jax's events into records (hand-fed: exact seconds)
# ---------------------------------------------------------------------------

def test_an_inner_functions_trace_is_the_outer_records_time_once():
    fresh = clog.CompileLog()
    before = tele.REGISTRY.counter("jax.compile.trace_s").snapshot()
    fresh.on_time_span(clog.TRACE, 100.25, 100.75, fun_name="inner")
    fresh.on_time_span(clog.TRACE, 100.0, 101.0, fun_name="outer")
    _program(fresh, "outer", 101.0)
    # The inner one's trace is gone with the outer one's: a program of
    # that name lowered later (its jaxpr cached) does not take it.
    _program(fresh, "inner", 200.0)
    snap = fresh.snapshot()
    rec, later = snap["records"]
    assert (later["name"], later["trace_s"]) == ("inner", 0.0)
    assert (rec["name"], rec["trace_s"]) == ("outer", 1.0)
    assert (rec["lower_s"], rec["backend_s"], rec["cache"]) == (
        0.25, 0.5, "off")
    assert (rec["start"], rec["end"]) == (100.0, 101.75)
    assert snap["programs"] == 2
    after = tele.REGISTRY.counter("jax.compile.trace_s").snapshot()
    assert after - before == pytest.approx(1.0)


def test_a_trace_inside_a_lowering_is_the_lowerings_time():
    """A lowering rule's own jitted helper is traced while the outer
    program is lowered, after the outer one's trace and before the
    lowering's event: the record still finds its trace, and the helper's
    is in ``lower_s``."""
    fresh = clog.CompileLog()
    fresh.on_time_span(clog.TRACE, 10.0, 11.0, fun_name="outer")
    for i in range(100):  # more than the log keeps of traces
        fresh.on_time_span(clog.TRACE, 11.0 + i / 128, 11.0 + (i + 1) / 128,
                           fun_name="helper")
    _program(fresh, "outer", 11.0, lower=1.0)
    _program(fresh, "helper", 20.0)
    outer, helper = fresh.snapshot()["records"]
    assert (outer["trace_s"], outer["lower_s"]) == (1.0, 1.0)
    assert (outer["start"], outer["end"]) == (10.0, 12.5)
    assert helper["trace_s"] == 0.0


def test_the_real_inner_jit_leaves_no_record_of_its_own(log, hvd):
    import horovod_tpu.jax as hvd_jax

    @jax.jit
    def inner_of_log_test(a):
        return a * 2

    @hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
    def outer_of_log_test(a):
        return inner_of_log_test(a) + inner_of_log_test(a + 1)

    before = tele.REGISTRY.counter("jax.compile.trace_s").snapshot()
    outer_of_log_test(jnp.ones((hvd.size(), 2)))
    records = log()["records"]
    assert not _named(records, "inner_of_log_test")
    (rec,) = _named(records, "outer_of_log_test")
    span = [s for s in log()["spans"]
            if s["name"] == "hvd.jax.jit:outer_of_log_test"][-1]
    assert 0 < rec["trace_s"] < span["end"] - span["start"]
    after = tele.REGISTRY.counter("jax.compile.trace_s").snapshot()
    assert after - before == pytest.approx(
        sum(r["trace_s"] for r in records))


def test_cache_events_belong_to_the_program_being_compiled():
    fresh = clog.CompileLog()
    fresh.on_event(clog.CACHE_HIT)  # before any lowering: nobody's
    _program(fresh, "a", 1.0, cache="misses")
    # The listeners came after b was lowered: its compile alone.
    fresh.on_event(clog.CACHE_HIT)
    fresh.on_duration(clog.CACHE_SAVED, 2.0)
    fresh.on_duration(clog.CACHE_RETRIEVAL, 0.125)
    fresh.on_time_span(clog.BACKEND, 5.0, 5.25, fun_name="jit(b)")
    _program(fresh, "c", 6.0)  # no cache event: not b's hit again
    a, b, c = fresh.snapshot()["records"]
    assert (c["cache"], c["retrieval_s"], c["saved_s"]) == ("off", 0.0, 0.0)
    assert (a["cache"], a["retrieval_s"], a["saved_s"]) == ("miss", 0.0, 0.0)
    assert (b["cache"], b["retrieval_s"], b["saved_s"]) == ("hit", 0.125, 2.0)
    assert b["lower_s"] == 0.0 and b["backend_s"] == 0.25


def test_the_records_are_bounded_and_the_counts_go_on(monkeypatch):
    monkeypatch.setattr(clog.CompileLog, "MAX_RECORDS", 3)
    fresh = clog.CompileLog()
    before = tele.REGISTRY.counter("jax.compiles").snapshot()
    for i in range(5):
        _program(fresh, f"f{i}", float(i), trace=0.125)
    snap = fresh.snapshot()
    assert [r["name"] for r in snap["records"]] == ["f0", "f1", "f2"]
    assert snap["programs"] == fresh.programs == fresh.compiled == 5
    assert tele.REGISTRY.counter("jax.compiles").snapshot() - before == 5
    assert clog.CompileLog.MAX_RECORDS == 3
    monkeypatch.undo()
    assert clog.CompileLog.MAX_RECORDS == 256 == clog.LOG.MAX_RECORDS


# ---------------------------------------------------------------------------
# cause: the span that was open when the record's first event fired
# ---------------------------------------------------------------------------

def test_cause_through_lower_compile_and_through_the_first_call(log, hvd):
    import horovod_tpu.jax as hvd_jax

    def make(name):
        def fn(x):
            return jnp.cos(x) + 1

        fn.__name__ = name
        return hvd_jax.jit(fn, in_specs=(P("hvd"),), out_specs=P("hvd"))

    x = jnp.ones((hvd.size(), 3))
    aot, called = make("aot_of_log_test"), make("called_of_log_test")
    lowered = aot.lower(x)
    assert type(lowered) is jax.stages.Lowered  # jax's own, unwrapped
    # Traced and lowered inside the span; the backend's part comes later,
    # after the span has closed, and joins the same record.
    (rec,) = _named(log()["records"], "aot_of_log_test")
    assert rec["cause"] == "hvd.jax.jit:aot_of_log_test"
    assert rec["lower_s"] > 0 and rec["backend_s"] == 0.0
    assert clog.LOG.open_spans() == []
    compiled = lowered.compile()
    assert type(compiled) is jax.stages.Compiled
    (rec,) = _named(log()["records"], "aot_of_log_test")
    assert rec["cause"] == "hvd.jax.jit:aot_of_log_test"
    assert rec["backend_s"] > 0
    compiled(x)

    called(x)
    (rec,) = _named(log()["records"], "called_of_log_test")
    assert rec["cause"] == "hvd.jax.jit:called_of_log_test"
    assert rec["trace_s"] > 0 and rec["backend_s"] > 0
    spans = [s["name"] for s in log()["spans"]]
    assert spans.count("hvd.jax.jit:aot_of_log_test") == 1   # lower()
    assert spans.count("hvd.jax.jit:called_of_log_test") == 1
    called(x)  # compiles nothing: the per-step path leaves no span
    assert len(log()["spans"]) == len(spans)


def test_a_bare_jnp_op_has_no_cause(log):
    jnp.arange(7).reshape(7, 1) * jnp.float32(1.5)
    records = log()["records"]
    assert records and {r["cause"] for r in records} == {""}
    assert all(r["name"] and not r["name"].startswith("jit(")
               for r in records)


def test_what_init_compiles_names_init(monkeypatch, fresh_log, hvd):
    from horovod_tpu.common import topology

    build_mesh = topology._build_mesh

    def build_and_compile(devices):
        jnp.arange(11).sum()  # a world that needs a program to come up
        return build_mesh(devices)

    monkeypatch.setattr(topology, "_build_mesh", build_and_compile)
    hvd.shutdown()
    try:
        hvd.init()
        log = hvd.telemetry()["compile_log"]
        assert log["records"]
        assert {r["cause"] for r in log["records"]} == {"hvd.init"}
        (span,) = [s for s in log["spans"] if s["name"] == "hvd.init"]
        assert span["parent"] == ""
        assert all(span["start"] <= r["start"] and r["end"] <= span["end"]
                   for r in log["records"])
        assert span["end"] > span["start"]
        hvd.init()  # initialised: no second span
        assert len(hvd.telemetry()["compile_log"]["spans"]) == len(
            log["spans"])
    finally:
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()


def test_one_listener_set_however_often_the_world_comes_up(hvd):
    from jax._src import monitoring  # the lists themselves

    for _ in range(3):
        hvd.shutdown()
        hvd.init()
    clog.install()
    assert monitoring.get_event_listeners().count(clog.LOG.on_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        clog.LOG.on_duration) == 1
    assert monitoring.get_event_time_span_listeners().count(
        clog.LOG.on_time_span) == 1
    # A stage's start (jax's scalar event) has no listener: the end
    # event carries both times.
    assert monitoring.get_scalar_listeners() == []


# ---------------------------------------------------------------------------
# Recompiles, the broadcast, the surfaces
# ---------------------------------------------------------------------------

def test_a_new_shape_is_one_recompile_with_function_and_dispatch(log, hvd):
    import horovod_tpu.jax as hvd_jax

    @hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
    def reshaped_of_log_test(x):
        return x * 2 + 1

    counter = tele.REGISTRY.counter("jax.recompiles")
    before = counter.snapshot()
    n = hvd.size()
    reshaped_of_log_test(jnp.ones((n, 2)))  # dispatch 0: the first compile
    assert counter.snapshot() == before and log()["recompiles"] == []
    reshaped_of_log_test(jnp.ones((n, 2)))  # dispatch 1: nothing compiles
    assert counter.snapshot() == before
    reshaped_of_log_test(jnp.ones((2 * n, 2)))  # dispatch 2: a new shape
    assert counter.snapshot() == before + 1
    (note,) = log()["recompiles"]
    assert note["name"] == "reshaped_of_log_test" and note["dispatch"] == 2
    assert note["backend_s"] > 0 and note["cache"] in ("off", "miss", "hit")
    recs = _named(log()["records"], "reshaped_of_log_test")
    assert len(recs) == 2 and note["backend_s"] == recs[1]["backend_s"]
    assert clog.LOG.last_recompile() == note


def test_a_recompile_names_the_program_that_was_compiled():
    """A call may compile its function's own program and one that lays
    out an argument, or that one alone: the note names the function's
    own where there is one, else what was compiled."""
    fresh = clog.CompileLog()
    before = fresh.compiled
    _program(fresh, "step", 1.0, backend=0.25)
    _program(fresh, "_multi_slice", 2.0, backend=0.5)
    fresh.compiled_in_call("step", 2.0, before, 7)
    before = fresh.compiled
    _program(fresh, "_multi_slice", 3.0, backend=0.125)
    fresh.compiled_in_call("step", 1.0, before, 9)
    fresh.compiled_in_call("step", 1.0, fresh.compiled, 10)  # another thread's
    own, layout = fresh.snapshot()["recompiles"]
    assert own == {"name": "step", "dispatch": 7, "backend_s": 0.25,
                   "cache": "off"}
    assert layout == {"name": "_multi_slice", "dispatch": 9,
                      "backend_s": 0.125, "cache": "off"}
    assert fresh.recompiles == 2 and fresh.last_recompile() == layout


def test_the_broadcast_has_a_span_and_its_bytes_are_the_eager_series(log, hvd):
    import horovod_tpu.jax as hvd_jax

    nbytes = tele.REGISTRY.counter("eager.broadcast.bytes")
    before = nbytes.snapshot()
    tree = {"w": jnp.ones((5, 4), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32),
            "n": jnp.arange(6, dtype=jnp.int32)}
    out = hvd_jax.broadcast_parameters(tree, root_rank=0)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    # The leaves' bytes, as one buffer a dtype in a world of several.
    assert nbytes.snapshot() - before == (20 + 3 + 6) * 4
    (span,) = [s for s in log()["spans"]
               if s["name"] == "hvd.broadcast_parameters"]
    assert span["end"] > span["start"] and span["parent"] == ""
    caused = [r for r in log()["records"]
              if r["cause"] == "hvd.broadcast_parameters"]
    assert (len(caused) > 0) == (hvd.size() > 1)
    assert all(span["start"] <= r["start"] and r["end"] <= span["end"]
               for r in caused)


def test_the_report_and_the_exposition_carry_the_compiles(log, hvd):
    import horovod_tpu.jax as hvd_jax

    @hvd_jax.jit(in_specs=(P("hvd"),), out_specs=P("hvd"))
    def reported_fn(x):
        return x - 1

    reported_fn(jnp.ones((hvd.size(), 2)))
    report = hvd.telemetry_report()
    table = report[report.index("compiles ("):].splitlines()
    assert table[0].endswith("recorded):")
    assert table[1].split() == ["name", "cause", "trace_s", "lower_s",
                                "backend_s", "cache"]
    assert any(row.split()[:2] == ["reported_fn",
                                   "hvd.jax.jit:reported_fn"]
               for row in table[2:])
    assert len(table) <= 2 + 10 + 1 and table[-1].split()[0] == "total"
    text = tele.prometheus()
    for series in ("jax_compiles", "jax_compile_trace_s",
                   "jax_compile_lower_s", "jax_compile_backend_s"):
        assert f"# TYPE hvd_{series} counter" in text
    snap = hvd.telemetry()
    assert snap["jax"]["compiles"] >= 1
    assert set(snap["compile_log"]) == {"programs", "records", "spans",
                                        "recompiles"}
    assert set(snap["compile_log"]["records"][0]) == set(clog.RECORD_FIELDS)
