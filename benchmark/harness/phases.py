"""Device time by what the program asked for, not by what XLA made of it.

An event on ``XLA Ops`` carries the scheduled HLO text of one instruction
but not its ``metadata`` (PERF.md section 3). The compiled step's own
text (``System.hlo_text``) does: ``op_name`` holds the program's name
stack, which is where ``horovod_tpu.common.phases`` puts the framework's
phase names, flax its module names and autodiff its ``transpose(...)``.
This reader joins the two by the instruction's identifier and gives every
event one phase, first rule that applies:

1. a collective by opcode is ``hvd_allreduce``; a ``tpu_custom_call`` is
   its kernel (``KERNELS``);
2. an op, or a fusion holding an op, that is a ``dot`` or ``convolution``
   goes where *that* instruction goes: a weight gradient fused with its
   adamw update is the model's backward, since the matmul sets its time;
3. otherwise the innermost name of ``VOCABULARY`` in its ``op_name``,
   else the flax module on its path (``MODULES``), else, for a fusion,
   what most of its instructions say;
4. an op that has no name of its own by these rules (a copy, slice or
   layout change the compiler put in) borrows from the nearest op that
   consumes its result and has one, else from the nearest that produces
   its operands: the copy exists because its consumer wants that layout.
   What is left is ``unnamed``.

Independently an event is ``backward`` if its ``op_name`` holds
``transpose(``, ``forward`` if it is inside the differentiated function
(``jvp(``) and not transposed, and neither otherwise (update, exchange).
Every event gets exactly one phase, so the phases tile the device time.
``mixed`` is the time in fusions whose instructions belong to more than
one phase (what rule 2 and the majority hide), ``borrowed`` the part of
each phase that rule 4 placed there. An event whose identifier the text
does not hold is ``unjoined``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import json
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.harness import xtrace

#: The program's phase names: a copy of ``horovod_tpu.common.phases.
#: PHASES`` (a test holds the two equal), kept here because the reader
#: also runs on a program that has none.
VOCABULARY = ("hvd_pack", "hvd_allreduce", "hvd_unpack", "hvd_numerics",
              "hvd_optimizer")

#: (pattern on a ``tpu_custom_call``'s identifier, phase), first match.
KERNELS = (
    (r"flash_dq_bwd_bhsd", "flash_dq"),
    # The whole backward in the dK/dV grid's pass (PR 30), where a head's
    # accumulators fit VMEM; read with the dK/dV kernel it took over, so
    # ``flash_dq`` then reads 0: the engagement counter.
    (r"fused_flash_dkv_bwd_bhsd", "flash_dkv"),
    (r"flash_dkv_bwd_bhsd", "flash_dkv"),
    (r"_fwd_bhsd", "flash_fwd"),
    (r"xent_fwd", "xent_fwd"), (r"xent_dx", "xent_dx"),
    (r"xent_dw", "xent_dw"),
)
#: A backward flash kernel of a program without ``name=`` on its pallas
#: calls: dq returns one array, dkv a pair.
UNNAMED_FLASH_BWD = r"_bwd_bhsd"

#: (pattern on one component of the name stack, phase), first component
#: that matches any. ``model`` is the rest of the differentiated
#: function (a model without these modules, the family's loss).
MODULES = (
    (r"lm_head", "lm_head"),
    (r"layer_\d+", "layers"),
    (r"\w+_embed", "embed"),
    (r"final_norm", "final_norm"),
)
MODEL_OTHER, UNNAMED, UNJOINED = "model", "unnamed", "unjoined"

_MATMULS = frozenset({"dot", "convolution"})
#: Instructions of a fused computation that say nothing about its phase.
_PLUMBING = frozenset({"parameter", "constant", "tuple", "bitcast",
                       "get-tuple-element"})

_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = 'metadata={op_name="'


@dataclasses.dataclass(frozen=True)
class Instruction:
    opcode: str
    op_name: str
    calls: str                 # the fused computation of a fusion, else ""
    operands: Tuple[str, ...]  # identifiers of the instructions it reads


@dataclasses.dataclass
class Module:
    """What the reader needs of a compiled module's text."""

    instructions: Dict[str, Instruction]
    computations: Dict[str, List[str]]  # name -> its instructions' names
    names: str                          # "fresh" or "stale"
    # rule 4 asks for the same neighbours' verdicts again and again
    _verdicts: Dict[str, tuple] = dataclasses.field(default_factory=dict)

    @functools.cached_property
    def users(self) -> Dict[str, List[str]]:
        out: Dict[str, List[str]] = collections.defaultdict(list)
        for name, instr in self.instructions.items():
            for operand in instr.operands:
                out[operand].append(name)
        return out


def _operands(line: str, opcode: str, start: int) -> Tuple[str, ...]:
    """Identifiers inside the parentheses behind ``opcode``."""
    at = line.find(f" {opcode}(", start)
    if at < 0:
        return ()
    at += len(opcode) + 1
    depth = 0
    for end in range(at, len(line)):
        depth += (line[end] == "(") - (line[end] == ")")
        if depth == 0:
            return tuple(_OPERAND_RE.findall(line, at, end))
    return ()


def parse(hlo_text: str) -> Module:
    instructions: Dict[str, Instruction] = {}
    computations: Dict[str, List[str]] = {}
    body: Optional[List[str]] = None
    seen = set()
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMP_RE.match(line)
            body = computations.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        at = line.find(_OP_NAME)
        op_name = ""
        if at >= 0:
            at += len(_OP_NAME)
            op_name = line[at:line.index('"', at)]
            seen.update(p for p in op_name.split("/") if p in VOCABULARY)
        calls = _CALLS_RE.search(line, m.end())
        opcode = xtrace.opcode(line.strip())
        instructions[m.group(2)] = Instruction(
            opcode, op_name, calls.group(1) if calls else "",
            _operands(line, opcode, m.end()))
        if body is not None:
            body.append(m.group(2))
    return Module(instructions, computations,
                  "fresh" if seen else "stale")


def name_phase(op_name: str) -> str:
    """Rule 3 on one name stack."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in VOCABULARY:
            return part
    for part in parts:
        for pattern, label in MODULES:
            if re.fullmatch(pattern, part):
                return label
    return MODEL_OTHER if "jvp(" in op_name else UNNAMED


def direction(op_name: str) -> str:
    if "transpose(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else ""


def kernel_phase(event_name: str) -> str:
    key = xtrace.identifier(event_name)
    for pattern, label in KERNELS:
        if re.search(pattern, key):
            return label
    if re.search(UNNAMED_FLASH_BWD, key):
        result = event_name.partition(" = ")[2].partition(" custom-call(")[0]
        return "flash_dkv" if result.count("[") > 1 else "flash_dq"
    return "other_kernel"


def _own(key: str, module: Module) -> Tuple[str, str, bool]:
    """(phase, direction, mixed) of instruction ``key`` by rules 2 and 3."""
    if key not in module._verdicts:
        module._verdicts[key] = _rules_2_and_3(module.instructions[key],
                                               module)
    return module._verdicts[key]


def _rules_2_and_3(instr: Instruction, module: Module):
    inner = [module.instructions[n]
             for n in module.computations.get(instr.calls, ())]
    inner = [i for i in inner if i.opcode not in _PLUMBING and i.op_name]
    votes = collections.Counter(name_phase(i.op_name) for i in inner)
    votes.pop(UNNAMED, None)
    matmul = next((i for i in [instr] + inner if i.opcode in _MATMULS), None)
    if matmul is not None:
        return (name_phase(matmul.op_name), direction(matmul.op_name),
                len(votes) > 1)
    phase = name_phase(instr.op_name)
    if phase == UNNAMED and votes:
        phase = votes.most_common(1)[0][0]
    facing = direction(instr.op_name) or next(
        (d for d in map(direction, (i.op_name for i in inner)) if d), "")
    return phase, facing, len(votes) > 1


def _borrowed(key: str, module: Module) -> Tuple[str, str]:
    """Rule 4: (phase, direction) of the nearest named consumer of
    ``key``, breadth first through unnamed ones, else producer."""
    for neighbours in (lambda k: module.users.get(k, ()),
                       lambda k: module.instructions[k].operands):
        seen, frontier = {key}, [key]
        while frontier:
            reached = [n for k in frontier for n in neighbours(k)
                       if n in module.instructions]
            frontier = []
            for n in reached:
                if n in seen:
                    continue
                seen.add(n)
                phase, facing, _ = _own(n, module)
                if phase != UNNAMED:
                    return phase, facing
                frontier.append(n)
    return UNNAMED, ""


def classify(event_name: str, module: Module) -> Tuple[str, str, bool, bool]:
    """(phase, direction, mixed, borrowed) of one event of ``XLA Ops``."""
    key = xtrace.identifier(event_name)
    instr = module.instructions.get(key)
    if xtrace.is_collective(event_name):
        return "hvd_allreduce", "", False, False
    if "tpu_custom_call" in event_name:
        return (kernel_phase(event_name),
                direction(instr.op_name) if instr else "", False, False)
    if instr is None:  # not an instruction of this text
        return UNJOINED, "", False, False
    phase, facing, mixed = _own(key, module)
    if phase != UNNAMED:
        return phase, facing, mixed, False
    return (*_borrowed(key, module), mixed, True)


@dataclasses.dataclass
class Reading:
    names: str                      # "fresh" or "stale"
    phases: List[Dict[str, float]]  # per device: phase -> seconds
    mixed_s: List[float]
    borrowed: List[Dict[str, float]]  # per device: the part rule 4 placed
    forward_s: List[float]
    backward_s: List[float]
    ops_s: List[float]              # all of dev.ops() inside the window


def read(hlo_text: str, capture: xtrace.Capture, window) -> Reading:
    module = parse(hlo_text)
    verdicts: Dict[str, Tuple[str, str, bool, bool]] = {}
    out = Reading(module.names, [], [], [], [], [], [])
    lo, hi = window
    for dev in capture.devices:
        phases: Dict[str, float] = collections.defaultdict(float)
        borrowed: Dict[str, float] = collections.defaultdict(float)
        sides = {"forward": 0.0, "backward": 0.0, "": 0.0}
        mixed = total = 0.0
        for name, start, end in dev.ops():
            seconds = (min(end, hi) - max(start, lo)) / 1e9
            if seconds <= 0:
                continue
            if name not in verdicts:
                verdicts[name] = classify(name, module)
            phase, facing, is_mixed, is_borrowed = verdicts[name]
            phases[phase] += seconds
            sides[facing] += seconds
            mixed += seconds * is_mixed
            if is_borrowed:
                borrowed[phase] += seconds
            total += seconds
        out.phases.append(dict(phases))
        out.mixed_s.append(mixed)
        out.borrowed.append(dict(borrowed))
        out.forward_s.append(sides["forward"])
        out.backward_s.append(sides["backward"])
        out.ops_s.append(total)
    return out


def reading(context) -> Reading:
    """The reading of ``context``'s capture. The harness gives a metric's
    reader the context and nothing else, so the first of this module's
    metrics to be read makes the reading, keeps it on the context and
    logs it as an earlier line of stdout; the others find it there."""
    got = vars(context).get("_phases_reading")
    if got is not None:
        return got
    t0 = time.perf_counter()
    got = read(context.system.hlo_text, context.capture, context.window)
    reader_s = time.perf_counter() - t0  # host time, in no metric
    vars(context)["_phases_reading"] = got
    if got.phases:  # else a capture without a device plane
        ms = context.per_step_ms

        def by_phase(per_device):
            return {p: ms([d.get(p, 0.0) for d in per_device])
                    for p in sorted({p for d in per_device for p in d})}

        print(json.dumps({
            "phase": "phases", "names": got.names,
            "ms_per_step": by_phase(got.phases),
            "mixed_ms": ms(got.mixed_s),
            "borrowed_ms": by_phase(got.borrowed),
            "unnamed_ms": ms([d.get(UNNAMED, 0.0) for d in got.phases]),
            "forward_ms": ms(got.forward_s),
            "backward_ms": ms(got.backward_s),
            "ops_ms": ms(got.ops_s), "reader_s": reader_s}), flush=True)
    return got


def per_step_ms(context, phases: Sequence[str],
                program_names: bool = False) -> Optional[float]:
    """Device milliseconds a step spends in ``phases``, mean over the
    devices. ``program_names``: the phases exist only by the program's
    own names, so a compiled step that holds none of them (a program
    from before the names, or an executable that a compile cache served
    from then) reads ``None`` and not 0."""
    got = reading(context)
    if not got.phases or (program_names and got.names == "stale"):
        return None
    return context.per_step_ms(
        [sum(d.get(p, 0.0) for p in phases) for d in got.phases])


FLASH = ("flash_fwd", "flash_dq", "flash_dkv")


def flash_ms(context, kernel: str) -> Optional[float]:
    """Device milliseconds a step spends under one of the three flash
    phases (``FLASH``: the forward, the dq kernel of the two-kernel
    backward, and the fused or the dK/dV backward); ``None`` where no
    flash kernel ran."""
    got = reading(context)
    if not any(d.get(k) for d in got.phases for k in FLASH):
        return None
    return per_step_ms(context, (kernel,))
