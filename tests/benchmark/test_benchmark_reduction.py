"""The reduction from a capture to metrics, on a small hand-built capture
whose every number can be worked out on paper, and the HLO counts on
hand-written instructions. The capture is spelt as this installation's
profiler spells one (read by hand, PR 22): a ``/device:TPU:n`` plane with
``XLA Ops`` and ``Async XLA Ops`` lines whose event names are scheduled
HLO text, and the harness's host spans on a ``/host:CPU`` thread."""

import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import check, hlo, layers, spec, xtrace  # noqa: E402

HBM = "bf16[8,128]{1,0:T(8,128)}"          # 2,048 bytes in HBM
VMEM = "f32[128]{0:T(128)S(1)}"            # scoped memory: not counted
NAMES = {
    "fusion_hbm": f"%convert_reduce_fusion.7 = {HBM} fusion({HBM} %p0, "
                  f"{VMEM} %p1), kind=kOutput, calls=%fused_computation.1",
    "fusion_vmem": f"%fusion.9 = {VMEM} fusion({VMEM} %x), kind=kLoop",
    "ar_start": "%all-reduce-start.1 = f32[1024]{0:T(1024)} "
                "all-reduce-start(f32[1024]{0:T(1024)} %g), channel_id=1, "
                "replica_groups={{0,1,2,3}}",
    "ar_done": "%all-reduce-done.1 = f32[1024]{0:T(1024)} "
               "all-reduce-done(f32[1024]{0:T(1024)} %all-reduce-start.1)",
    # As the chip names it: after the jax primitive, not the opcode.
    "ar_sync": "%psum.5 = f32[]{:T(128)} all-reduce(f32[]{:T(128)S(6)} "
               "%div.1), channel_id=1, replica_groups={{0,1,2,3}}, "
               "use_global_device_ids=true, to_apply=%region_1.2",
    "flash_fwd": "%_fwd_bhsd.3 = (bf16[48,128,64]{2,1,0:T(8,128)(2,1)}, "
                 "f32[48,128,1]{2,1,0:T(8,128)}) custom-call(%q, %k, %v), "
                 "custom_call_target=\"tpu_custom_call\"",
    "flash_bwd": "%_bwd_bhsd.4 = (bf16[48,128,64]{2,1,0:T(8,128)(2,1)}) "
                 "custom-call(%q), custom_call_target=\"tpu_custom_call\"",
    "copy_start": "%copy-start.2 = (f32[256]{0:T(128)S(1)}, "
                  "f32[256]{0:T(128)}, u32[]{:S(2)}) copy-start("
                  "f32[256]{0:T(128)} %w)",
    "copy_done": "%copy-done.2 = f32[256]{0:T(128)S(1)} copy-done("
                 "%copy-start.2)",
    # An async slice lists its operands first: 512 of 2,048 rows move.
    "slice_start": "%slice-start.7 = ((f32[2048,768]{1,0:T(8,128)}), "
                   "f32[512,768]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) "
                   "async-start(f32[2048,768]{1,0:T(8,128)} %pos_embed)",
    "while": "%while.9 = (s32[]{:T(128)}, f32[999999]{0:T(128)}) "
             "while(%tuple), body=%b",
}
# (name, start ns, end ns) on the sequencer of device 0: the step runs
# from 100 to 850 inside a window of 0 to 1000.
OPS_0 = [("fusion_hbm", 100, 300), ("ar_start", 300, 310),
         ("fusion_vmem", 310, 500), ("ar_done", 500, 600),
         ("flash_fwd", 600, 700), ("flash_bwd", 700, 760),
         ("copy_start", 760, 761), ("copy_done", 761, 800),
         ("ar_sync", 800, 850), ("while", 100, 800)]
ASYNC_0 = [("ar_start", 300, 600), ("copy_start", 760, 800),
           ("slice_start", 100, 200)]
OPS_1 = [("fusion_hbm", 100, 300)]          # device 1 does less: idle 80%
HOST = [("bench_window", 0, 1000), ("dispatch", 0, 10),
        ("dispatch", 10, 20), ("barrier", 20, 990),
        ("fetch_loss", 990, 1000), ("$threading.py:1 run", 0, 5000)]
WINDOW = (0.0, 1000.0)


def _text_proto(planes) -> str:
    ids = {name: i + 1 for i, name in enumerate(
        sorted(NAMES) + [h[0] for h in HOST])}
    texts = dict(NAMES, **{h[0]: h[0] for h in HOST})
    out = []
    for plane, lines in planes:
        out.append(f'planes {{ name: "{plane}"')
        for line, events in lines:
            out.append(f'  lines {{ name: "{line}" timestamp_ns: 0')
            out += [f"    events {{ metadata_id: {ids[n]} offset_ps: "
                    f"{start * 1000} duration_ps: {(end - start) * 1000} }}"
                    for n, start, end in events]
            out.append("  }")
        for name, i in ids.items():
            text = texts[name].replace("\\", "\\\\").replace('"', '\\"')
            out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                       f'name: "{text}" }} }}')
        out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def capture():
    from jax.profiler import ProfileData

    text = _text_proto([
        ("/device:TPU:0", [("XLA Ops", OPS_0), ("Async XLA Ops", ASYNC_0),
                           ("Steps", [("while", 100, 850)])]),
        ("/device:TPU:1", [("XLA Ops", OPS_1)]),
        ("#Chip0 Misc", []),
        ("/host:CPU", [("main/1", HOST[:5]), ("python3", HOST[5:])]),
    ])
    return xtrace.from_profile_data(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def test_capture_has_two_devices_and_the_hosts_spans(capture):
    assert [d.name for d in capture.devices] == ["/device:TPU:0",
                                                 "/device:TPU:1"]
    assert xtrace.window_of(capture, "bench_window") == WINDOW
    # The wrapper that spans its children is not an op of its own.
    assert len(capture.devices[0].ops()) == len(OPS_0) - 1
    with pytest.raises(ValueError, match="no host span"):
        xtrace.window_of(capture, "never_written")


def test_busy_and_idle(capture):
    # Device 0 runs ops from 100 to 850 without a gap; device 1 for 200.
    assert xtrace.busy_seconds(capture, WINDOW) == [750e-9, 200e-9]
    # A narrower window clips what it cuts.
    assert xtrace.busy_seconds(capture, (0.0, 200.0)) == [100e-9, 100e-9]


def test_kernel_time_is_the_sum_of_the_matching_ops(capture):
    dev = capture.devices[0]
    assert xtrace.op_seconds(dev, "_fwd_bhsd|_bwd_bhsd", WINDOW) == \
        pytest.approx(160e-9)
    assert xtrace.op_seconds(capture.devices[1], "_fwd_bhsd", WINDOW) == 0


def test_collective_total_and_exposed_part(capture):
    total, exposed = xtrace.collective_seconds(capture.devices[0], WINDOW)
    # In flight 300-600 (async line) and the synchronous one 800-850.
    assert total == pytest.approx(350e-9)
    # Not hidden: the start (300-310), the wait in -done (500-600) and
    # the synchronous all-reduce; 310-500 ran under a fusion.
    assert exposed == pytest.approx(160e-9)
    assert xtrace.collective_seconds(capture.devices[1], WINDOW) == (0, 0)


def test_hbm_bytes_from_the_op_names(capture):
    dev = capture.devices[0]
    # Async copy: its destination f32[256]; async slice: its result
    # f32[512,768], not the table it reads from; the all-reduce in
    # flight on the same line is not a copy.
    assert xtrace.dma_bytes(dev, WINDOW) == 256 * 4 + 512 * 768 * 4
    # fusion.7: result and one operand in HBM, the S(1) operand left
    # out; fusion.9 lives in VMEM; kernels, copies and the while
    # wrapper are not direct streams.
    assert xtrace.fusion_direct_bytes(dev, WINDOW) == 2 * 8 * 128 * 2
    assert xtrace.hbm_bytes(dev, WINDOW) == 1024 + 1572864 + 4096


def test_ops_are_told_apart_by_opcode_not_by_name():
    assert xtrace.opcode(NAMES["ar_sync"]) == "all-reduce"
    assert xtrace.opcode(NAMES["ar_start"]) == "all-reduce-start"
    assert xtrace.opcode(NAMES["while"]) == "while"        # tuple shape
    assert xtrace.opcode(NAMES["slice_start"]) == "async-start"
    assert xtrace.opcode(NAMES["flash_fwd"]) == "custom-call"
    assert xtrace.opcode("bench_window") == ""             # a host span
    assert xtrace.is_collective(NAMES["ar_sync"])
    assert xtrace.is_collective(NAMES["ar_done"])
    # An operand called after a collective does not make one.
    assert not xtrace.is_collective(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop")
    assert xtrace.identifier(NAMES["ar_sync"]) == "psum.5"


def test_breakdown_of_ops_and_idle_gaps(capture):
    dev = capture.devices[0]
    assert xtrace.device_ops_breakdown(dev, WINDOW) == [
        ["convert/reduce fusion", pytest.approx(200e-9)],
        ["elementwise fusion (kLoop)", pytest.approx(190e-9)],
        ["collective", pytest.approx(160e-9)],
        ["pallas kernel", pytest.approx(160e-9)],
        ["copy/layout", pytest.approx(40e-9)]]
    assert xtrace.top_ops(dev, WINDOW, top=2) == [
        ["convert/reduce fusion: convert_reduce_fusion.7",
         pytest.approx(200e-9)],
        ["elementwise fusion (kLoop): fusion.9", pytest.approx(190e-9)]]
    assert xtrace.categorize("%fusion.3 = f32[8]{0} fusion(%x), "
                             "kind=kOutput, calls=%c") == \
        "matmul/convolution fusion (kOutput)"
    assert xtrace.categorize("%select_and_scatter.9 = f32[8]{0} "
                             "select-and-scatter(%x)") == "select-and-scatter"
    # Idle 0-100 (20 under dispatch, 80 under barrier) and 850-1000 (140
    # under barrier, 10 under fetch_loss): both go to the barrier.
    gaps = xtrace.idle_gaps_breakdown(
        capture, dev, WINDOW, ("dispatch", "barrier", "fetch_loss"))
    assert gaps == [["barrier", pytest.approx(250e-9)]]
    assert xtrace.idle_gaps_breakdown(capture, dev, WINDOW, ()) == [
        ["none", pytest.approx(250e-9)]]


def test_interval_arithmetic():
    cover = xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert cover == [[0, 3], [5, 8]]
    assert xtrace.length(cover) == 6
    assert xtrace.subtract(cover, [[2, 6], [7, 20]]) == [[0, 2], [6, 7]]
    assert xtrace.subtract(cover, []) == cover
    assert xtrace.clip(cover, 1, 6) == [[1, 3], [5, 6]]


def _context(capture, cell_name, steps_per_call=1):
    cell = spec.load_cell(cell_name)
    hlo_text = "\n".join([
        "  %all-reduce.1 = f32[132361531]{0} all-reduce(f32[132361531]{0} "
        "%concatenate), channel_id=1, replica_groups={{0,1,2,3}}, "
        "use_global_device_ids=true, to_apply=%add",
        "  %all-reduce.2 = f32[] all-reduce(f32[] %loss), channel_id=2, "
        "replica_groups=[1,4]<=[4], to_apply=%add"])
    system = types.SimpleNamespace(hlo_text=hlo_text,
                                   build_s={"compile": 6.5},
                                   steps_per_call=steps_per_call)
    return layers.Context(
        cell=cell, family=spec.load_module("families", cell.family),
        system=system, peaks=spec.load_peaks("TPU v5 lite"),
        capture=capture, window_span="bench_window", traced_steps=2,
        items_per_s_per_chip=90_000.0)


def test_every_reader_on_the_hand_built_capture(capture):
    context = _context(capture, "bert_base_s2048_flash")
    got = layers.read_metrics(context)
    assert set(got) == {m["name"] for m in context.cell.per_layer}
    assert got["compile_s"] == 6.5
    flops = context.family.model_flops_per_item(
        context.cell.config, context.cell.traffic)
    assert got["mfu"] == pytest.approx(100 * flops * 90_000 / 197e12)
    # Means over the two devices, per traced step (two of them).
    assert got["flash_ms_per_step"] == pytest.approx(160e-6 / 2 / 2)
    assert got["collective_ms_per_step"] == pytest.approx(350e-6 / 2 / 2)
    assert got["collective_exposed_ms_per_step"] == pytest.approx(
        160e-6 / 2 / 2)
    assert got["hbm_gb_per_step"] == pytest.approx(
        (5120 + 1572864 + 4096) / 2 / 1e9 / 2)
    assert got["wire_bytes_per_step"] == 132361531 * 4 + 4
    assert got["device_idle_share"] == pytest.approx(80.0)  # worst device
    roofline = spec.load_module("metrics", "flash_roofline")
    floor = 12 * roofline.attention_flops(4, 2048, 12, 64) / 197e12
    assert got["flash_roofline"] == pytest.approx(
        100 * floor / (160e-9 / 2 / 2))

    times = layers.device_times(context)
    assert times == {"busy_s": pytest.approx(475e-9), "window_s": 1e-6}
    shown = layers.breakdown(context, ("dispatch", "barrier", "fetch_loss"))
    assert set(shown) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in shown.values())


def test_readers_that_find_nothing_return_nothing(capture):
    idle_only = xtrace.Capture(devices=capture.devices[1:],
                               host=capture.host)
    context = _context(idle_only, "bert_base_s512", steps_per_call=5)
    got = layers.read_metrics(context)
    assert "flash_roofline" not in got      # not declared for this cell
    assert got["flash_ms_per_step"] == 0.0  # the kernel is bypassed
    assert got["collective_ms_per_step"] == 0.0
    assert got["wire_bytes_per_step"] is None  # a scan-fused step
    roofline = spec.load_module("metrics", "flash_roofline")
    assert roofline.read(context) is None   # no kernel ran


def test_a_device_on_which_nothing_ran_is_an_error(capture):
    context = _context(capture, "bert_base_s512")
    context.window_span = "fetch_loss"      # 990-1000: no op at all
    with pytest.raises(RuntimeError, match="no\\s+operation ran"):
        layers.device_times(context)


def test_hlo_counts():
    text = "\n".join([
        "  %all-reduce-start.1 = f32[1024]{0} all-reduce-start(f32[1024]{0}"
        " %x), channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add",
        "  %all-reduce-done.1 = f32[1024]{0} all-reduce-done(f32[1024]{0} "
        "%all-reduce-start.1)",
        "  %ag = (bf16[8,16]{1,0}, bf16[32,16]{1,0}) all-gather-start("
        "bf16[8,16]{1,0} %y), replica_groups=[1,4]<=[4], dimensions={0}",
        "  %rs.3 = f32[256]{0} reduce-scatter(%z), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
        "  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.1)",
    ])
    assert hlo.collectives(text) == [
        ("all-reduce", 4096, 2), ("all-gather", 256, 4),
        # operands printed without shapes: the result's bytes stand in
        ("reduce-scatter", 1024, 4)]
    assert hlo.wire_bytes(text) == 4096 + 256 + 1024
    assert hlo.all_reduce_group(text) == 2
    assert hlo.all_reduce_group("%f = f32[8] fusion(%x)") == 0
    assert not hlo.has_tpu_custom_call(text)
    assert hlo.has_tpu_custom_call(
        '%k = f32[8] custom-call(%x), custom_call_target="tpu_custom_call"')


# A variadic all-reduce as the TPU compiler prints one (PR 25's step has
# three, of 21 to 29 operands): a tuple shape whose elements XLA numbers
# in comments from the sixth on, tiled layouts, operands without shapes.
VARIADIC = (
    "  %all-reduce.1 = (f32[3072,768]{1,0:T(8,128)}, "
    "f32[12,64,768]{2,1,0:T(8,128)}, f32[768,12,64]{0,2,1:T(8,128)}, "
    "f32[768]{0:T(1024)S(1)}, f32[768,3072]{1,0:T(8,128)}, "
    "/*index=5*/f32[3072,768]{1,0:T(8,128)S(1)}, f32[]{:T(128)}) "
    "all-reduce(%fusion.1, %fusion.2, %fusion.3, %fusion.4, %fusion.5, "
    "/*index=5*/%fusion.6, %div.7), channel_id=3, "
    "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
    "to_apply=%region_1.2, frontend_attributes={hvd_phases=\"1\"}")
VARIADIC_BYTES = 4 * (3 * 3072 * 768 + 2 * 12 * 64 * 768 + 768 + 1)


def test_a_variadic_all_reduce_is_counted_whole():
    assert hlo.collectives(VARIADIC) == [("all-reduce", VARIADIC_BYTES, 4)]
    assert hlo.wire_bytes(VARIADIC) == VARIADIC_BYTES
    assert hlo.all_reduce_group(VARIADIC) == 4
    # Beside instructions that stand alone, each is counted once.
    text = "\n".join([
        VARIADIC,
        "  %psum.9 = f32[768,30522]{0,1:T(8,128)} all-reduce(%fusion.200), "
        "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add",
        "  %get-tuple-element.4 = f32[768]{0:T(1024)S(1)} "
        "get-tuple-element(%all-reduce.1), index=3"])
    assert hlo.wire_bytes(text) == VARIADIC_BYTES + 4 * 768 * 30522


@pytest.mark.parametrize("replica_groups,spans", [
    ("{{0,1,2,3}}", True), ("[1,4]<=[4]", True),
    ("{{0,1},{2,3}}", False), ("[2,2]<=[4]", False)])
def test_a_step_whose_all_reduces_are_all_variadic(replica_groups, spans):
    """``all_reduce_spans_world`` rests on every all-reduce of the step,
    the variadic ones too: a correct step that has no other passes, and
    one that reduces over half the world does not."""
    import jax

    text = VARIADIC.replace("{{0,1,2,3}}", replica_groups)
    batch = jax.numpy.zeros((4, 2))
    system = types.SimpleNamespace(n_chips=4, mean_rank=1.5, hlo_text=text,
                                   batch=(batch,))
    cell = types.SimpleNamespace(config={
        "loss_tolerance": {"abs": 0.002},
        "update_tolerance": {"rel": 0.3, "pooled_rel": 0.2}})
    got = check.verdict(cell, system, [3.0, 2.9, 2.8], [2.0], [3.0] * 3,
                        {"update_gap": 0.1, "update_pooled_gap": 0.05}, set(),
                        on_tpu=False)
    assert got["all_reduce_spans_world"] == {
        "value": 0 if spans else 2, "limit": 0, "ok": spans}
