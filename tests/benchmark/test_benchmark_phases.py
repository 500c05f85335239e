"""The phase reader (``benchmark/harness/phases.py``) on a hand-written
HLO text and a hand-built capture whose every number can be worked out on
paper: the join of trace events to ``op_name`` by identifier, the rules
in their order, the tiling, and what a compiled step without the
program's names reads."""

import json
import os
import re
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import layers, phases, spec, xtrace  # noqa: E402

F32 = "f32[8,8]{1,0}"
STEP = "jit(train_step)/shard_map"
BWD = f"{STEP}/transpose(jvp(TransformerLM))"
FWD = f"{STEP}/jvp(TransformerLM)"
UPDATE = f"{STEP}/hvd_update/hvd_optimizer"


def _meta(op_name):
    return f'metadata={{op_name="{op_name}" stack_frame_id=7}}'


# The compiled step as ``compiled.as_text()`` spells one: fused
# computations first, operands by identifier, metadata behind the
# attributes and in front of a long backend_config.
HLO = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    # A weight gradient fused with its adamw update: the dot is the
    # model's backward, the root the user's apply_updates.
    f"%fused_computation.1 (param_0: {F32}, param_1: {F32}) -> {F32} {{",
    f"  %param_0 = {F32} parameter(0)",
    f"  %param_1 = {F32} parameter(1)",
    f"  %dot.1 = {F32} dot(%param_0, %param_1), lhs_contracting_dims={{1}}, "
    f"rhs_contracting_dims={{0}}, "
    + _meta(f"{BWD}/layer_3/Dense_0/dot_general"),
    f"  %multiply.1 = {F32} multiply(%dot.1, %param_1), "
    + _meta(f"{UPDATE}/mul"),
    f"  ROOT %add.1 = {F32} add(%multiply.1, %param_0), "
    + _meta(f"{STEP}/hvd_update/add"),
    "}",
    "",
    # adamw on its own: the root again apply_updates' add, the rest the
    # optimizer's.
    f"%fused_computation.2 (param_0.1: {F32}) -> {F32} {{",
    f"  %param_0.1 = {F32} parameter(0)",
    f"  %sqrt.1 = {F32} sqrt(%param_0.1), " + _meta(f"{UPDATE}/sqrt"),
    f"  %divide.1 = {F32} divide(%param_0.1, %sqrt.1), "
    + _meta(f"{UPDATE}/div"),
    f"  ROOT %add.2 = {F32} add(%divide.1, %param_0.1), "
    + _meta(f"{STEP}/hvd_update/add"),
    "}",
    "",
    f"%fused_computation.3 (param_0.2: {F32}) -> {F32} {{",
    f"  %param_0.2 = {F32} parameter(0)",
    f"  ROOT %dot.2 = {F32} dot(%param_0.2, %param_0.2), "
    + _meta(f"{FWD}/lm_head/dot_general"),
    "}",
    "",
    f"ENTRY %main.9 (p.1: {F32}, p.2: {F32}) -> {F32} {{",
    f"  %p.1 = {F32} parameter(0), " + _meta("params['w']"),
    f"  %p.2 = {F32} parameter(1)",
    f"  %fusion.1 = {F32} fusion(%p.1, %p.2), kind=kOutput, "
    f"calls=%fused_computation.1, " + _meta(f"{STEP}/hvd_update/add")
    + ', backend_config={"estimated_cycles":"9"}',
    f"  %multiply_add_fusion.2 = {F32} fusion(%p.2), kind=kLoop, "
    f"calls=%fused_computation.2, " + _meta(f"{STEP}/hvd_update/add"),
    f"  %fusion.3 = {F32} fusion(%p.1), kind=kOutput, "
    "calls=%fused_computation.3, " + _meta(f"{FWD}/lm_head/dot_general"),
    # A copy the compiler put in: no name, its consumer packs.
    f"  %copy.4 = {F32} copy(%fusion.1)",
    f"  %concatenate.5 = f32[128]{{0}} concatenate(%copy.4, %p.2), "
    "dimensions={0}, " + _meta(f"{UPDATE}/hvd_pack/concatenate"),
    f"  %psum.6 = f32[128]{{0}} all-reduce(%concatenate.5), channel_id=1, "
    "replica_groups={{0,1,2,3}}, to_apply=%add, "
    + _meta(f"{STEP}/hvd_update/hvd_allreduce/psum"),
    f"  %slice.7 = {F32} slice(%psum.6), slice={{[0:64]}}, "
    + _meta(f"{STEP}/hvd_update/hvd_unpack/slice"),
    "  %flash_fwd_bhsd.8 = (bf16[48,128,64]{2,1,0}, f32[48,128,1]{2,1,0}) "
    "custom-call(%p.1), custom_call_target=\"tpu_custom_call\", "
    + _meta(f"{FWD}/layer_3/MultiHeadAttention_0/jit(_fwd_bhsd)/pallas_call"),
    "  %flash_dq_bwd_bhsd.9 = bf16[48,128,64]{2,1,0} custom-call(%p.1), "
    "custom_call_target=\"tpu_custom_call\", "
    + _meta(f"{BWD}/layer_3/MultiHeadAttention_0/jit(_bwd_bhsd)/pallas_call"),
    "  %flash_dkv_bwd_bhsd.10 = (bf16[48,128,64]{2,1,0}, "
    "bf16[48,128,64]{2,1,0}) custom-call(%p.1), "
    "custom_call_target=\"tpu_custom_call\", "
    + _meta(f"{BWD}/layer_3/MultiHeadAttention_0/jit(_bwd_bhsd)/pallas_call"),
    # Left over: no name, and neither consumer nor producer has one.
    f"  %iota.11 = {F32} iota(), iota_dimension=0",
    f"  ROOT %tuple.12 = ({F32}) tuple(%slice.7)",
    "}",
])

# Event names as the trace prints them: the same instructions with shapes
# on the operands and no metadata. (identifier, start ns, end ns); the
# window is 0-1000 and holds two steps' worth on each of two devices.
EVENTS = [
    ("fusion.1", f"= {F32} fusion({F32} %p.1, {F32} %p.2), kind=kOutput, "
     "calls=%fused_computation.1", 0, 100),
    ("multiply_add_fusion.2", f"= {F32} fusion({F32} %p.2), kind=kLoop, "
     "calls=%fused_computation.2", 100, 130),
    ("fusion.3", f"= {F32} fusion({F32} %p.1), kind=kOutput, "
     "calls=%fused_computation.3", 130, 200),
    ("copy.4", f"= {F32} copy({F32} %fusion.1)", 200, 220),
    ("concatenate.5", f"= f32[128]{{0}} concatenate({F32} %copy.4, {F32} "
     "%p.2), dimensions={0}", 220, 250),
    ("psum.6", "= f32[128]{0} all-reduce(f32[128]{0} %concatenate.5), "
     "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add", 250, 350),
    ("slice.7", f"= {F32} slice(f32[128]{{0}} %psum.6), slice={{[0:64]}}",
     350, 360),
    ("flash_fwd_bhsd.8", "= (bf16[48,128,64]{2,1,0}, f32[48,128,1]{2,1,0}) "
     "custom-call(%p.1), custom_call_target=\"tpu_custom_call\"", 360, 400),
    ("flash_dq_bwd_bhsd.9", "= bf16[48,128,64]{2,1,0} custom-call(%p.1), "
     "custom_call_target=\"tpu_custom_call\"", 400, 430),
    ("flash_dkv_bwd_bhsd.10", "= (bf16[48,128,64]{2,1,0}, "
     "bf16[48,128,64]{2,1,0}) custom-call(%p.1), "
     "custom_call_target=\"tpu_custom_call\"", 430, 480),
    ("iota.11", f"= {F32} iota(), iota_dimension=0", 480, 485),
    # A wrapper spans its children and is no op of its own; an op that
    # starts before the window counts with the part inside it.
    ("while.13", "= (s32[]) while(%tuple), body=%b", 0, 485),
]
EXPECTED_NS = {  # device 0
    "layers": 100, "hvd_optimizer": 30, "lm_head": 70,
    "hvd_pack": 20 + 30, "hvd_allreduce": 100, "hvd_unpack": 10,
    "flash_fwd": 40, "flash_dq": 30, "flash_dkv": 50, "unnamed": 5}
WINDOW = (0.0, 1000.0)


def _capture(events=EVENTS, second_device=True):
    ops = [(f"%{key} {text}", s, e) for key, text, s, e in events]
    devices = [xtrace.DevicePlane("/device:TPU:0", {"XLA Ops": ops})]
    if second_device:  # the same step, shifted so that 15 ns fall outside
        devices.append(xtrace.DevicePlane(
            "/device:TPU:1",
            {"XLA Ops": [(n, s - 15, e - 15) for n, s, e in ops]}))
    return xtrace.Capture(devices=devices,
                          host=[("bench_window", *WINDOW)])


def _context(hlo_text, capture=None, cell="bert_base_s2048_flash"):
    cell = spec.load_cell(cell)
    return layers.Context(
        cell=cell, family=None, peaks=spec.load_peaks("TPU v5 lite"),
        system=types.SimpleNamespace(hlo_text=hlo_text,
                                     build_s={"compile": 1.0},
                                     steps_per_call=1),
        capture=capture or _capture(), window_span="bench_window",
        traced_steps=2, items_per_s_per_chip=1.0)


def test_text_is_parsed_into_instructions_computations_and_operands():
    module = phases.parse(HLO)
    assert module.names == "fresh"
    assert set(module.computations) == {
        "fused_computation.1", "fused_computation.2", "fused_computation.3",
        "main.9"}
    assert module.computations["fused_computation.1"] == [
        "param_0", "param_1", "dot.1", "multiply.1", "add.1"]
    fusion = module.instructions["fusion.1"]
    assert (fusion.opcode, fusion.calls, fusion.operands) == (
        "fusion", "fused_computation.1", ("p.1", "p.2"))
    assert fusion.op_name.endswith("hvd_update/add")
    assert module.instructions["copy.4"].op_name == ""
    assert module.instructions["psum.6"].opcode == "all-reduce"
    assert module.users["fusion.1"] == ["copy.4"]


@pytest.mark.parametrize("op_name, phase, direction", [
    (f"{UPDATE}/hvd_pack/concatenate", "hvd_pack", ""),   # the innermost
    (f"{UPDATE}/mul", "hvd_optimizer", ""),
    (f"{STEP}/hvd_update/add", "unnamed", ""),             # the user's own
    (f"{FWD}/lm_head/dot_general", "lm_head", "forward"),
    (f"{BWD}/layer_11/Dense_1/dot_general", "layers", "backward"),
    (f"{FWD}/tok_embed/jit(_take)/gather", "embed", "forward"),
    (f"{BWD}/final_norm/mul", "final_norm", "backward"),
    ("jit(train_step)/jvp(ResNet)/BottleneckBlock_3/Conv_0/"
     "conv_general_dilated", "model", "forward"),
    ("reduce_sum", "unnamed", ""),
    ("", "unnamed", ""),
])
def test_a_name_stack_gives_phase_and_direction(op_name, phase, direction):
    assert phases.name_phase(op_name) == phase
    assert phases.direction(op_name) == direction


def test_each_event_goes_by_the_first_rule_that_applies():
    module = phases.parse(HLO)
    events = {key: f"%{key} {text}" for key, text, _, _ in EVENTS}

    def verdict(key):
        return phases.classify(events[key], module)

    # Rule 2: the fusion's dot is the model's backward, though the root
    # is the update's; its instructions span two phases.
    assert verdict("fusion.1") == ("layers", "backward", True, False)
    # Rule 3: the root says nothing, most of the instructions do.
    assert verdict("multiply_add_fusion.2") == (
        "hvd_optimizer", "", False, False)
    assert verdict("fusion.3") == ("lm_head", "forward", False, False)
    # Rule 4: the nameless copy takes its consumer's phase, not its
    # producer's.
    assert verdict("copy.4") == ("hvd_pack", "", False, True)
    assert verdict("concatenate.5") == ("hvd_pack", "", False, False)
    # Rule 1: a collective by opcode, whatever it is called; kernels by
    # their names.
    assert verdict("psum.6")[0] == "hvd_allreduce"
    assert verdict("slice.7")[0] == "hvd_unpack"
    assert verdict("flash_fwd_bhsd.8")[:2] == ("flash_fwd", "forward")
    assert verdict("flash_dq_bwd_bhsd.9")[:2] == ("flash_dq", "backward")
    assert verdict("flash_dkv_bwd_bhsd.10")[:2] == ("flash_dkv", "backward")
    assert verdict("iota.11") == ("unnamed", "", False, True)
    # An event of another program's is not silently a phase.
    assert phases.classify(f"%fusion.99 = {F32} fusion(%x), kind=kLoop",
                           module)[0] == "unjoined"


def test_dq_and_dkv_of_a_program_without_kernel_names_go_by_arity():
    one = ("%_bwd_bhsd.4 = bf16[48,128,64]{2,1,0:T(8,128)(2,1)} "
           "custom-call(%q), custom_call_target=\"tpu_custom_call\"")
    pair = ("%_bwd_bhsd.5 = (bf16[48,128,64]{2,1,0:T(8,128)(2,1)}, "
            "bf16[48,128,64]{2,1,0:T(8,128)(2,1)}) custom-call(%q), "
            "custom_call_target=\"tpu_custom_call\"")
    assert phases.kernel_phase(one) == "flash_dq"
    assert phases.kernel_phase(pair) == "flash_dkv"
    assert phases.kernel_phase(
        "%_fwd_bhsd.3 = (bf16[8]{0}, f32[8]{0}) custom-call(%q)") == \
        "flash_fwd"
    assert phases.kernel_phase("%xent_dw.1 = (f32[8]{0}, f32[8]{0}) "
                               "custom-call(%x)") == "xent_dw"
    assert phases.kernel_phase("%mystery.1 = f32[8]{0} custom-call(%x)") \
        == "other_kernel"


def test_phases_tile_the_device_time_of_the_window():
    capture = _capture()
    got = phases.read(HLO, capture, WINDOW)
    assert got.names == "fresh"
    first, second = got.phases
    assert first == {k: pytest.approx(v * 1e-9)
                     for k, v in EXPECTED_NS.items()}
    # Device 1 runs the same step 15 ns earlier: the window cuts 15 ns
    # off its first op.
    assert second["layers"] == pytest.approx(85e-9)
    for dev, phase_s, ops_s in zip(capture.devices, got.phases, got.ops_s):
        in_window = sum(max(0.0, min(e, WINDOW[1]) - max(s, WINDOW[0]))
                        for _, s, e in dev.ops()) / 1e9
        assert sum(phase_s.values()) == pytest.approx(in_window, rel=1e-3)
        assert ops_s == pytest.approx(in_window, rel=1e-3)
    assert got.mixed_s == [pytest.approx(100e-9), pytest.approx(85e-9)]
    assert got.borrowed == [{"hvd_pack": pytest.approx(20e-9),
                             "unnamed": pytest.approx(5e-9)}] * 2
    assert got.forward_s[0] == pytest.approx((70 + 40) * 1e-9)
    assert got.backward_s[0] == pytest.approx((100 + 30 + 50) * 1e-9)


def test_the_six_metrics_and_the_log_line(capsys):
    context = _context(HLO)
    read = {name: spec.load_module("metrics", name).read(context)
            for name in ("pack_ms_per_step", "optimizer_ms_per_step",
                         "lm_head_ms_per_step", "flash_fwd_ms_per_step",
                         "flash_dq_ms_per_step", "flash_dkv_ms_per_step")}
    # Two traced steps; means over the two devices (which differ only
    # in the first op, a layer's).
    assert read == {
        "pack_ms_per_step": pytest.approx(60e-6 / 2),
        "optimizer_ms_per_step": pytest.approx(30e-6 / 2),
        "lm_head_ms_per_step": pytest.approx(70e-6 / 2),
        "flash_fwd_ms_per_step": pytest.approx(40e-6 / 2),
        "flash_dq_ms_per_step": pytest.approx(30e-6 / 2),
        "flash_dkv_ms_per_step": pytest.approx(50e-6 / 2)}
    # The three kernels are what flash_ms_per_step sums; the exchange is
    # what collective_ms_per_step times from outside.
    whole = spec.load_module("metrics", "flash_ms_per_step").read(context)
    assert sum(read[f"flash_{k}_ms_per_step"]
               for k in ("fwd", "dq", "dkv")) == pytest.approx(whole)
    outside = spec.load_module("metrics",
                               "collective_ms_per_step").read(context)
    assert phases.per_step_ms(context, ("hvd_allreduce",)) == \
        pytest.approx(outside)
    # One reading for the six, logged once as an earlier line.
    lines = [line for line in capsys.readouterr().out.splitlines()
             if '"phases"' in line]
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert line["phase"] == "phases" and line["names"] == "fresh"
    assert set(line["ms_per_step"]) == set(EXPECTED_NS)
    assert sum(line["ms_per_step"].values()) == pytest.approx(
        line["ops_ms"], rel=1e-3)
    assert line["unnamed_ms"] == pytest.approx(5e-6 / 2)
    assert line["mixed_ms"] == pytest.approx(92.5e-6 / 2)
    assert line["borrowed_ms"] == {"hvd_pack": pytest.approx(20e-6 / 2),
                                   "unnamed": pytest.approx(5e-6 / 2)}


def test_a_step_without_the_programs_names_is_stale_not_zero(capsys):
    """The parent's program, or an executable that a compile cache kept
    from before the names: the flax names and the kernels still read,
    what exists only by the program's names reads nothing."""
    old = re.sub(r"hvd_(pack|allreduce|unpack|optimizer|numerics)/", "", HLO)
    old = old.replace("flash_fwd_bhsd", "_fwd_bhsd").replace(
        "flash_dq_bwd_bhsd", "_bwd_bhsd").replace(
        "flash_dkv_bwd_bhsd", "_bwd_bhsd")
    events = [(key.replace("flash_fwd_bhsd", "_fwd_bhsd")
               .replace("flash_dq_bwd_bhsd", "_bwd_bhsd")
               .replace("flash_dkv_bwd_bhsd", "_bwd_bhsd"), *rest)
              for key, *rest in EVENTS]
    assert phases.parse(old).names == "stale"
    context = _context(old, _capture(events))

    def read(name):
        return spec.load_module("metrics", name).read(context)

    assert read("pack_ms_per_step") is None
    assert read("optimizer_ms_per_step") is None
    assert read("lm_head_ms_per_step") == pytest.approx(70e-6 / 2)
    assert read("flash_fwd_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("flash_dq_ms_per_step") == pytest.approx(30e-6 / 2)
    assert read("flash_dkv_ms_per_step") == pytest.approx(50e-6 / 2)
    assert '"names": "stale"' in capsys.readouterr().out


def test_cells_without_the_kernel_or_without_a_device_read_nothing():
    no_kernels = [e for e in EVENTS if "bhsd" not in e[0]]
    context = _context(HLO, _capture(no_kernels), cell="bert_base_s512")
    assert phases.flash_ms(context, "flash_fwd") is None
    assert phases.per_step_ms(context, ("lm_head",)) == pytest.approx(
        70e-6 / 2)
    empty = _context(HLO, xtrace.Capture(devices=[], host=[
        ("bench_window", *WINDOW)]))
    assert phases.per_step_ms(empty, ("lm_head",)) is None


def test_the_readers_vocabulary_is_the_programs():
    from horovod_tpu.common import phases as program

    assert phases.VOCABULARY == program.PHASES
    patterns = [p for p, _ in phases.KERNELS]
    for name in program.KERNELS:
        assert any(re.search(p, name) for p in patterns), name
    # The accepted flash_ms_per_step goes by these two substrings.
    accepted = spec.load_module("metrics", "flash_ms_per_step").PATTERNS
    for name in program.KERNELS[:4]:
        assert any(p in name for p in accepted), name


@pytest.mark.parametrize("name,phase", [
    ("flash_fwd_bhsd", "flash_fwd"),
    ("flash_dq_bwd_bhsd", "flash_dq"),
    ("flash_dkv_bwd_bhsd", "flash_dkv"),
    # PR 30's whole backward in one kernel: under its own name, read with
    # the dK/dV kernel it took over.
    ("fused_flash_dkv_bwd_bhsd", "flash_dkv"),
    ("xent_fwd", "xent_fwd"), ("xent_dx", "xent_dx"), ("xent_dw", "xent_dw"),
])
def test_every_kernel_of_the_program_has_a_pattern_of_its_own(name, phase):
    from horovod_tpu.common import phases as program

    assert name in program.KERNELS
    event = (f"%{name}.7 = (bf16[48,128,64]{{2,1,0}}, bf16[48,128,64]"
             f"{{2,1,0}}) custom-call(%q), custom_call_target="
             "\"tpu_custom_call\"")
    assert phases.kernel_phase(event) == phase
    # The fused kernel's name holds the dK/dV kernel's, so its own pattern
    # comes first; no other kernel's pattern matches two of the program's.
    first = next(p for p, _ in phases.KERNELS if re.search(p, name))
    matched = {n for n in program.KERNELS if re.search(first, n)}
    assert matched == ({name, "fused_flash_dkv_bwd_bhsd"}
                       if name == "flash_dkv_bwd_bhsd" else {name})
