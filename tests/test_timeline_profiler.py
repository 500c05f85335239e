"""Timeline parity between the C++ and Python writers, and the XLA
profile-capture harness (reference: common/timeline.cc detail — dtype and
shape args on events — and the CUDA-event device timing that the XLA
profiler replaces, operations.cc:671-695)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest


def _run_ops(engine):
    # Synchronize after each enqueue: one entry per engine cycle, so the
    # event stream is deterministic (whether same-cycle allreduces fuse
    # depends on enqueue/drain timing; fusion-path events are covered by
    # the multi-process engine_fusion scenario).
    engine.synchronize(
        engine.allreduce_async("t/a", np.ones((4,), np.float32), False))
    engine.synchronize(
        engine.allreduce_async("t/b", np.ones((4,), np.float32), False))
    engine.synchronize(
        engine.allgather_async("t/g", np.ones((2, 3), np.float32)))
    engine.synchronize(
        engine.broadcast_async("t/c", np.ones((5,), np.float32), 0))
    engine.shutdown()


def _summarize(path):
    """Per-tensor set of (activity, phase, args) — the diff-comparable
    shape of a timeline, timestamps excluded."""
    lanes = {}
    events = {}
    for ev in json.load(open(path)):
        if not ev:
            continue
        if ev.get("ph") == "M":
            # Structural metadata: lane names feed the summary; the
            # HVD_CLOCK record (distributed tracing) is not a span.
            if ev.get("name") == "process_name":
                lanes[ev["pid"]] = ev["args"]["name"]
            continue
        pid = ev.get("pid")
        args = ev.get("args")
        events.setdefault(pid, set()).add(
            (ev["name"], ev["ph"],
             None if args is None else (args.get("dtype"),
                                        tuple(args.get("shape", ())))))
    return {lanes[pid]: evs for pid, evs in events.items()}


def test_cpp_timeline_diff_comparable_with_python_twin(hvd, tmp_path):
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine
    from horovod_tpu.core.timeline import Timeline

    cpp_path = str(tmp_path / "cpp.json")
    py_path = str(tmp_path / "py.json")
    _run_ops(NativeEngine(timeline_path=cpp_path))
    _run_ops(Engine(timeline=Timeline(py_path)))

    cpp, py = _summarize(cpp_path), _summarize(py_path)
    assert set(cpp) == set(py) == {"t/a", "t/b", "t/g", "t/c"}
    for name in cpp:
        # Same activities with the same phase types and the same
        # dtype/shape args on collective begins.
        assert cpp[name] == py[name], (name, cpp[name] ^ py[name])
    # Spot-check the detail the reference writer records
    # (timeline.cc:98-188): dtype + shape on the collective begin event.
    assert ("ALLGATHER", "B", ("float32", (2, 3))) in cpp["t/g"]
    # Both writers must cover the single-op vocabulary declared in
    # core/timeline.py — not merely agree with each other (the reference
    # emits WAIT_FOR_DATA before every executed op, operations.cc:783-807;
    # MEMCPY is the submit-time snapshot span of the zero-copy data
    # plane, nested at the head of QUEUE).
    for summary in (cpp, py):
        acts = {a for evs in summary.values() for a, _, _ in evs}
        assert acts == {tl.QUEUE, tl.MEMCPY, tl.WAIT_FOR_DATA,
                        tl.ALLREDUCE, tl.ALLGATHER, tl.BROADCAST}, acts


class _PluggedExecutor:
    """Echo executor whose FIRST call blocks until release(), so tensors
    enqueued meanwhile pile up in the queue and fuse on the next drain —
    a deterministic way to drive the fusion-buffer timeline path."""

    def __init__(self):
        import threading

        self.gate = threading.Event()
        self.started = threading.Event()
        self.calls = 0

    def allreduce(self, flat, average):
        self.calls += 1
        if self.calls == 1:
            self.started.set()
            self.gate.wait(5.0)
        return flat.copy()


def _run_fused(engine, ex):
    h0 = engine.allreduce_async("t/plug", np.ones((2,), np.float32), False)
    # Only once the plug is INSIDE the executor is the dispatch thread
    # provably busy; tensors enqueued now stack up and fuse next cycle.
    assert ex.started.wait(5.0)
    ha = engine.allreduce_async("t/fa", np.ones((4,), np.float32), False)
    hb = engine.allreduce_async("t/fb", np.ones((4,), np.float32), False)
    ex.gate.set()
    for h in (h0, ha, hb):
        engine.synchronize(h)
    engine.shutdown()


@pytest.mark.parametrize("impl", ["native", "python"])
def test_fused_timeline_covers_declared_vocabulary(hvd, tmp_path, impl):
    """Every activity constant declared in core/timeline.py is actually
    emitted by both writers (VERDICT r2 weak #5: WAIT_FOR_DATA and
    MEMCPY_OUT_FUSION_BUFFER were declared but never written; reference
    emits out-copy spans, operations.cc:1359-1374). NEGOTIATE_* phases are
    multi-controller-only and covered by tests/multiproc_worker.py."""
    from horovod_tpu.core import timeline as tl
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine
    from horovod_tpu.core.timeline import Timeline

    path = str(tmp_path / f"{impl}.json")
    ex = _PluggedExecutor()
    if impl == "native":
        engine = NativeEngine(executor=ex, timeline_path=path)
    else:
        engine = Engine(executor=ex, timeline=Timeline(path))
    _run_fused(engine, ex)

    summary = _summarize(path)
    acts = {a for evs in summary.values() for a, _, _ in evs}
    declared = {tl.QUEUE, tl.MEMCPY, tl.WAIT_FOR_DATA,
                tl.MEMCPY_IN_FUSION_BUFFER, tl.ALLREDUCE,
                tl.MEMCPY_OUT_FUSION_BUFFER}
    assert acts == declared, acts ^ declared
    # The fused tensors carry the fusion-buffer spans; the plug ran alone.
    for name in ("t/fa", "t/fb"):
        lane_acts = {a for a, _, _ in summary[name]}
        assert tl.MEMCPY_IN_FUSION_BUFFER in lane_acts, (name, lane_acts)
        assert tl.MEMCPY_OUT_FUSION_BUFFER in lane_acts, (name, lane_acts)
    assert tl.MEMCPY_IN_FUSION_BUFFER not in {
        a for a, _, _ in summary["t/plug"]}


def test_timeline_truncation_safe(hvd, tmp_path):
    """Crash-safety (ISSUE 2 satellite): a killed run leaves no closing
    ']' — the writer's separator-first style must leave no trailing comma
    either, so the file still loads after appending the bracket (what
    Perfetto's tolerant JSON-array reader does). Both writers."""
    from horovod_tpu.core.engine import Engine
    from horovod_tpu.core.native_engine import NativeEngine
    from horovod_tpu.core.timeline import Timeline

    py_path = str(tmp_path / "py_trunc.json")
    t = Timeline(py_path)
    t.start("t/x", "QUEUE")
    t.end("t/x", "QUEUE")
    t._fh.flush()
    # Simulate SIGKILL: read the file WITHOUT close().
    raw = open(py_path).read()
    assert not raw.rstrip().endswith(",")
    events = json.loads(raw + "]")
    assert any(ev.get("name") == "QUEUE" for ev in events)
    t.close()  # idempotent clean close still yields valid JSON
    events = json.load(open(py_path))
    assert any(ev.get("name") == "QUEUE" for ev in events)
    t.close()  # second close is a no-op

    # The C++ writer flushes on its 1 s horizon at event boundaries, so a
    # mid-run snapshot (the SIGKILL view) is a complete-event prefix with
    # no trailing comma and no ']'.
    import time

    cpp_path = str(tmp_path / "cpp_trunc.json")
    e = NativeEngine(timeline_path=cpp_path)
    try:
        e.synchronize(
            e.allreduce_async("t/c0", np.ones((4,), np.float32), False))
        time.sleep(1.2)  # cross the flush horizon on the next emit
        e.synchronize(
            e.allreduce_async("t/c1", np.ones((4,), np.float32), False))
        raw = open(cpp_path).read()
        assert raw.strip() != "[", "flush horizon not crossed"
        assert not raw.rstrip().endswith(",")
        assert json.loads(raw + "]")  # loadable after truncation
    finally:
        e.shutdown()
    events = json.load(open(cpp_path))
    assert any(ev.get("name") == "QUEUE" for ev in events)

    # Python Engine.shutdown closes the timeline it owns (no leak).
    leak_path = str(tmp_path / "owned.json")
    eng = Engine(timeline=Timeline(leak_path))
    eng.synchronize(
        eng.allreduce_async("t/p", np.ones((2,), np.float32), False))
    eng.shutdown()
    assert json.load(open(leak_path))


def test_profiler_capture_produces_trace(hvd, tmp_path):
    import jax

    from horovod_tpu.utils import profiler

    logdir = str(tmp_path / "prof")

    @jax.jit
    def step(x):
        return (x * 2.0).sum()

    out = profiler.capture(step, jnp.ones((8, 8)), logdir=logdir, iters=2)
    files = profiler.trace_files(out)
    assert files, f"no xplane files under {logdir}: {os.listdir(logdir)}"


def _synthetic_xspace(tmp_path):
    """A hand-built device plane exercising every xplane metric: two
    compute fusions (one HBM-direct, one VMEM-only), an async copy pair,
    a while wrapper, an XLA Modules span, plus one collective and one
    optimizer-update fusion for the per-op-class attribution."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    names = {
        1: "%convert_reduce_fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion("
           "bf16[8,128]{1,0:T(8,128)} %p0, f32[128]{0:T(128)S(1)} %p1)",
        2: "%fusion.9 = f32[64]{0:T(128)S(1)} fusion(f32[64]{0:T(128)S(1)} %x)",
        3: "%copy-start = (f32[256]{0:T(128)S(1)}, f32[256]{0:T(128)}, u32[]{:S(2)})"
           " copy-start(f32[256]{0:T(128)} %w)",
        4: "%copy-done = f32[256]{0:T(128)S(1)} copy-done(%copy-start)",
        5: "%while.2 = (s32[]{:T(128)}, f32[999999]{0:T(128)}) while(...)",
        6: "jit_step(123)",
        7: "%all-reduce.3 = f32[128]{0:T(128)} all-reduce("
           "f32[128]{0:T(128)} %x)",
        8: "%multiply_add_fusion.11 = f32[256]{0:T(128)} fusion("
           "f32[256]{0:T(128)} %g, f32[256]{0:T(128)S(1)} %m)",
    }
    for i, n in names.items():
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = n
    ops = plane.lines.add(name="XLA Ops")
    for mid, dur_ps in [(1, 4e9), (2, 1e9), (4, 2e9), (5, 8e9),
                        (7, 2e9), (8, 1.5e9)]:
        ev = ops.events.add(metadata_id=int(mid))
        ev.duration_ps = int(dur_ps)
    async_line = plane.lines.add(name="Async XLA Ops")
    ev = async_line.events.add(metadata_id=3)
    ev.duration_ps = int(3e9)
    mods = plane.lines.add(name="XLA Modules")
    ev = mods.events.add(metadata_id=6)
    ev.duration_ps = int(9e9)
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(tmp_path)


def test_xplane_hbm_accounting_on_synthetic_capture(tmp_path):
    """Pins the measured-roofline machinery (docs/benchmarks.md r4): DMA
    payload = destination shape of async copies; fusion direct bytes
    exclude S(n)-annotated (VMEM/SMEM) operands; while wrappers are
    excluded; module time sums the Modules line."""
    from horovod_tpu.utils import xplane as xp

    logdir = _synthetic_xspace(tmp_path)
    d = xp.dma_bytes(logdir)
    assert d["bytes"] == 256 * 4 and d["events"] == 1  # dest f32[256]
    assert d["busy_ms"] == pytest.approx(3.0)
    assert xp.module_ms(logdir) == pytest.approx(9.0)

    # fusion.7: bf16 out 8*128*2 + bf16 operand 8*128*2 (the S(1) f32
    # operand excluded); fusion.9 all-VMEM -> 0; copy-done + while
    # skipped; all-reduce.3 in+out 2*128*4; multiply_add_fusion.11 out +
    # one HBM operand 2*256*4 (the S(1) momentum operand excluded).
    hb = xp.hbm_bytes(logdir)
    assert hb["bytes"] == 2 * (8 * 128 * 2) + 2 * 128 * 4 + 2 * 256 * 4

    report = xp.hbm_report(logdir, steps=1)
    assert "conv+BN fusion" in report and "while" not in report
    assert "true HBM traffic" in report
    assert "per-op-class" in report
    # Per-dtype columns in the human table, heaviest dtype first (f32
    # carries 2*128*4 + 2*256*4 = 3072 B vs bf16's 2*8*128*2 = 4096 B
    # -> bf16 leads).
    header = next(ln for ln in report.splitlines()
                  if ln.strip().startswith("class"))
    assert "GB bf16" in header and "GB f32" in header
    assert header.index("GB bf16") < header.index("GB f32")

    # Per-op-class attribution (collective vs optimizer vs conv/matmul
    # bytes): the table that makes a traffic regression attributable.
    classes = xp.class_breakdown(logdir, steps=1)
    assert classes["collective"]["bytes"] == 2 * 128 * 4
    assert classes["collective"]["ms"] == pytest.approx(2.0)
    assert classes["optimizer"]["bytes"] == 2 * 256 * 4
    assert classes["optimizer"]["ms"] == pytest.approx(1.5)
    assert classes["conv/matmul"]["bytes"] == 2 * (8 * 128 * 2)
    # control (while + copy-done) carries time but never bytes.
    assert classes["control"]["bytes"] == 0
    assert classes["control"]["ms"] == pytest.approx(10.0)
    assert classes["elementwise fusion"]["bytes"] == 0
    # Per-dtype split inside each class (HBM diet round 2): the
    # bf16-vs-f32 audit — fusion.7 streams bf16 in+out, the collective
    # and the optimizer fusion are all-f32 here.
    assert classes["conv/matmul"]["by_dtype"] == {"bf16": 2 * (8 * 128 * 2)}
    assert classes["collective"]["by_dtype"] == {"f32": 2 * 128 * 4}
    assert classes["optimizer"]["by_dtype"] == {"f32": 2 * 256 * 4}
    assert classes["control"]["by_dtype"] == {}
    # steps divides evenly into per-step figures.
    half = xp.class_breakdown(logdir, steps=2)
    assert half["collective"]["bytes"] == 128 * 4
    assert half["collective"]["by_dtype"] == {"f32": 128 * 4}

    # Machine-readable attribution (ISSUE 2 satellite): --json carries
    # the same numbers as the human table, and the stats CLI consumes a
    # capture dir through the same helper instead of re-parsing text.
    data = xp.hbm_json(logdir, steps=1)
    assert data["classes"]["collective"]["bytes"] == 2 * 128 * 4
    # Capture-wide dtype totals ride the JSON (and perf.jsonl via the
    # sentinel fold): sum of the per-class splits.
    assert data["bytes_by_dtype_per_step"] == {
        "bf16": 2 * (8 * 128 * 2), "f32": 2 * 128 * 4 + 2 * 256 * 4}
    assert data["dma_bytes"] == 256 * 4
    assert data["true_hbm_bytes_per_step"] == \
        data["dma_bytes"] + data["fusion_direct_bytes"]
    assert data["module_ms"] == pytest.approx(9.0)
    import io
    import json as _json
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        xp.main([logdir, "--hbm", "--json"])
    assert _json.loads(buf.getvalue()) == _json.loads(_json.dumps(data))

    from horovod_tpu.utils import stats

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert stats.main([logdir, "--json"]) == 0
    env = _json.loads(buf.getvalue())
    # The unified envelope shape (ISSUE 6 satellite): same schema as the
    # file/live/http sources, xplane figures flattened into samples.
    assert set(env) == {"source", "target", "samples"}
    assert env["source"] == "xplane"
    by_name = {(s["name"], s["labels"].get("class")): s["value"]
               for s in env["samples"]}
    assert by_name[("xplane_dma_bytes", None)] == 256 * 4
    assert by_name[("xplane_class_bytes", "collective")] == 2 * 128 * 4
    # The dtype split flattens into labeled samples too (the stats CLI's
    # bf16-vs-f32 view of a capture).
    by_dt = {(s["name"], s["labels"].get("class"), s["labels"].get("dtype")):
             s["value"] for s in env["samples"]}
    assert by_dt[("xplane_bytes_per_step", None, "bf16")] == 2 * (8 * 128 * 2)
    assert by_dt[("xplane_class_dtype_bytes", "collective", "f32")] == \
        2 * 128 * 4

    # Shape parsing corner cases.
    assert xp._first_shape_bytes("%x = pred[3]{0} y(pred[3] %a)") == 3
    assert xp._first_shape_bytes("no shapes") == 0
    assert xp._hbm_shape_bytes(
        "f32[2,2]{1,0:T(8,128)} f32[4]{0:T(128)S(1)} bf16[8]{0}") == 32
    assert xp._op_root("%get-tuple-element.991 = ...") == "get-tuple-element"
    assert xp._op_root("%while.2 = (...) while(...)") == "while"
