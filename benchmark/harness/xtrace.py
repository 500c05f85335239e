"""From a profiler capture to numbers: the benchmark's own reduction.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` with jax alone. A
capture becomes plain lists of ``(name, start_ns, end_ns)`` per device
line, and every metric is interval arithmetic over them, so the same
functions run on a hand-built capture in the tests.

What the lines of a TPU device plane are on this installation (read by
hand, PR 22; see ``PERF.md`` section 3):

- ``XLA Ops`` is the sequencer's occupancy: its events tile a step back
  to back (1,813.0 ms of ops in 1,813.5 ms of steps) and do not overlap,
  apart from the ``while`` / ``conditional`` wrappers that span their
  children. An op's name is its whole scheduled HLO text (``%fusion.7 =
  bf16[..]{..} fusion(...)``), shapes and memory spaces included, which
  is where the byte counts come from. The identifier need not say what
  the op is: the gradient all-reduce is ``%psum.14 = f32[132361530]{..}
  all-reduce(...)``, so ops are told apart by their opcode.
- ``Async XLA Ops`` carries one span per copy (``copy-start``) or slice
  (``async-start``) in flight; these overlap compute, and on ``XLA Ops``
  a ``-done`` is the wait that was not hidden. The all-reduce of the
  step is not there: it is one synchronous op on the sequencer.
- ``XLA Modules`` and ``Steps`` span whole executions; host and device
  events are on one clock.

The HBM accounting (``dma_bytes``, ``fusion_direct_bytes``) and the op
categories are copies of ``horovod_tpu/utils/xplane.py`` (listed in
``PERF.md`` for deletion there), with one correction: an async slice's
name lists its operands first, so its payload is the shape after them
and not the first one (the original counts the whole source buffer).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, end_ns

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"

#: Ops on the sequencer line that span their children; their time is
#: their children's, so no reduction counts them.
_WRAPPERS = frozenset({"while", "conditional", "call"})

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


@dataclasses.dataclass
class DevicePlane:
    name: str
    lines: Dict[str, List[Event]]

    @functools.cached_property
    def _ops(self) -> List[Event]:
        return [e for e in self.lines.get(OPS_LINE, [])
                if opcode(e[0]) not in _WRAPPERS]

    def ops(self) -> List[Event]:
        """The sequencer line without its wrapper ops."""
        return self._ops

    @functools.cached_property
    def busy(self) -> List[List[float]]:
        """Intervals in which any op runs on the device: the sequencer
        line together with whatever is in flight on the async line."""
        return union(_spans(self.ops())
                     + _spans(self.lines.get(ASYNC_LINE, [])))


@dataclasses.dataclass
class Capture:
    devices: List[DevicePlane]
    host: List[Event]  # every event of every host thread


def trace_files(logdir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))


def load(path: str) -> Capture:
    from jax.profiler import ProfileData

    return from_profile_data(ProfileData.from_file(path))


def from_profile_data(data) -> Capture:
    devices, host = [], []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        lines = {}
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events]
            if is_device:
                lines.setdefault(line.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host.extend(events)
        if is_device and lines.get(OPS_LINE):
            devices.append(DevicePlane(plane.name, lines))
    devices.sort(key=lambda d: d.name)
    return Capture(devices, host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Disjoint, sorted cover of ``intervals``."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def length(cover: Sequence[Sequence[float]]) -> float:
    return sum(end - start for start, end in cover)


def subtract(cover, other) -> List[List[float]]:
    """The part of ``cover`` that ``other`` does not touch (both disjoint
    and sorted)."""
    out, j = [], 0
    for start, end in cover:
        while j < len(other) and other[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(other) and other[k][0] < end:
            if other[k][0] > at:
                out.append([at, other[k][0]])
            at = max(at, other[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def clip(cover, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in cover
            if min(e, hi) > max(s, lo)]


def _spans(events: Iterable[Event]):
    return [(s, e) for _, s, e in events]


# ---------------------------------------------------------------------------
# device busy and idle
# ---------------------------------------------------------------------------

def window_of(capture: Capture, span_name: str) -> Tuple[float, float]:
    """The traced window on the profiler's clock: the harness's own host
    span ``span_name`` (the last one, if the capture holds several)."""
    spans = [(s, e) for name, s, e in capture.host if name == span_name]
    if not spans:
        raise ValueError(f"the capture holds no host span {span_name!r}")
    return max(spans)


def busy_seconds(capture: Capture, window) -> List[float]:
    """Per device, the seconds of ``window`` in which an op ran."""
    return [length(clip(d.busy, *window)) / 1e9
            for d in capture.devices]


# ---------------------------------------------------------------------------
# kernels and collectives
# ---------------------------------------------------------------------------

def _after_tuple(text: str) -> str:
    """``text`` behind the parenthesised group it starts with."""
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return text[i + 1:]
    return ""


@functools.lru_cache(maxsize=1 << 16)  # a step's ops repeat every step
def opcode(name: str) -> str:
    """The opcode of an HLO text: ``%psum.14 = f32[8]{0} all-reduce(...)``
    -> ``all-reduce``; ``%while.2 = (s32[], f32[9]) while(...)`` ->
    ``while``. Empty where ``name`` is not HLO text."""
    _, found, rest = name.partition(" = ")
    if not found:
        return ""
    rest = (_after_tuple(rest) if rest.startswith("(")
            else rest.partition(" ")[2])
    m = re.match(r"\s*([\w-]+)\(", rest)
    return m.group(1) if m else ""


def identifier(name: str) -> str:
    return name.partition(" = ")[0].lstrip("%")


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE_RE.match(opcode(name)))


def op_seconds(dev: DevicePlane, pattern: str, window) -> float:
    """Summed device time, inside ``window``, of the sequencer-line ops
    whose identifier matches ``pattern``."""
    rx = re.compile(pattern)
    spans = [(s, e) for name, s, e in dev.ops()
             if rx.search(identifier(name))]
    return length(clip(union(spans), *window)) / 1e9


def collective_seconds(dev: DevicePlane, window) -> Tuple[float, float]:
    """(total, exposed) collective seconds on one device. Total is the
    cover of every collective event, on the sequencer line and in
    flight on the async line; exposed is the part of it during which no
    other op runs on the sequencer."""
    ops = dev.ops()
    coll = union(_spans(
        e for e in ops + dev.lines.get(ASYNC_LINE, [])
        if is_collective(e[0])))
    compute = union(_spans(e for e in ops if not is_collective(e[0])))
    coll = clip(coll, *window)
    return length(coll) / 1e9, length(subtract(coll, compute)) / 1e9


# ---------------------------------------------------------------------------
# HBM bytes, from the scheduled HLO text in the op names
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}
SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\](?:\{([^}]*)\})?")

_NO_TRAFFIC_OPS = frozenset({
    "while", "conditional", "call", "tuple", "get-tuple-element",
    "parameter", "bitcast", "constant", "copy-done", "after-all",
    "optimization-barrier",
})


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _payload_bytes(name: str) -> int:
    """Bytes an async copy or slice moves: the destination is the first
    shape of ``%copy-start = (dest, source, context)`` and the shape
    behind the operands of ``%slice-start = ((operands), result,
    context)``."""
    body = name.partition(" = ")[2]
    if body.startswith("(("):
        body = _after_tuple(body[1:])
    m = SHAPE_RE.search(body)
    return shape_bytes(m.group(1), m.group(2)) if m else 0


def _hbm_shape_bytes(text: str) -> int:
    """Bytes of every shape literal whose layout does not place it in a
    scoped memory space (``S(n)`` is VMEM or SMEM; none is HBM)."""
    return sum(shape_bytes(dt, dims)
               for dt, dims, layout in SHAPE_RE.findall(text)
               if not (layout and "S(" in layout))


def dma_bytes(dev: DevicePlane, window) -> float:
    """Payload of the async copies and slices that start in ``window``.
    Collectives in flight are not copies and are left out."""
    return float(sum(
        _payload_bytes(name)
        for name, start, _ in dev.lines.get(ASYNC_LINE, [])
        if window[0] <= start < window[1] and not is_collective(name)))


def _direct(name: str) -> bool:
    """Single-pass compute fusions, whose HBM operands and results are
    exact at the name level (slice and copy ops over-count their source
    buffers; async copies are counted by ``dma_bytes``)."""
    key = identifier(name)
    return bool("convert_reduce_fusion" in key
                or "multiply_add_fusion" in key
                or "select-and-scatter" in key
                or re.match(r"(loop_)?fusion", key))


def fusion_direct_bytes(dev: DevicePlane, window) -> float:
    """Bytes the compute fusions stream to and from HBM themselves."""
    cache: Dict[str, int] = {}
    total = 0
    for name, start, _ in dev.ops():
        if not window[0] <= start < window[1]:
            continue
        if name not in cache:
            cache[name] = (_hbm_shape_bytes(name)
                           if _direct(name)
                           and opcode(name) not in _NO_TRAFFIC_OPS else 0)
        total += cache[name]
    return float(total)


def hbm_bytes(dev: DevicePlane, window) -> float:
    return dma_bytes(dev, window) + fusion_direct_bytes(dev, window)


# ---------------------------------------------------------------------------
# breakdown: top device ops by category, idle gaps by host span
# ---------------------------------------------------------------------------

_CATEGORIES = [
    # On the identifier, first match wins; no bare "conv": it would
    # swallow "%convert_*". Collectives and pallas kernels are told by
    # their opcode before these are tried.
    (r"convolution|conv\d", "convolution"),
    (r"dot|einsum|matmul|gemm", "matmul"),
    (r"convert.*fusion|fusion.*convert", "convert/reduce fusion"),
    (r"multiply.*add.*fusion|scatter.*fusion", "multiply-add fusion"),
    (r"fusion", "other fusion"),
    (r"copy|slice|bitcast|transpose|reshape|concatenate", "copy/layout"),
    (r"select.and.scatter", "select-and-scatter"),
    (r"rng|random", "rng"),
    (r"infeed|outfeed|send|recv", "host transfer"),
]


_FUSION_KINDS = {"kOutput": "matmul/convolution fusion (kOutput)",
                 "kLoop": "elementwise fusion (kLoop)",
                 "kInput": "reduction fusion (kInput)"}


def categorize(name: str) -> str:
    """The category of an op on the sequencer line. A fusion whose
    identifier says nothing (``%fusion.12``) goes by its ``kind=``: on a
    TPU the matmuls and convolutions are the ``kOutput`` fusions."""
    if "tpu_custom_call" in name:
        return "pallas kernel"
    if is_collective(name):
        return "collective"
    key = identifier(name).lower()
    for pattern, label in _CATEGORIES:
        if re.search(pattern, key):
            if label == "other fusion":
                kind = re.search(r"kind=(k\w+)", name)
                return _FUSION_KINDS.get(kind.group(1) if kind else "",
                                         label)
            return label
    return "other"


def _op_totals(dev: DevicePlane, window, label) -> Dict[str, float]:
    totals: Dict[str, float] = collections.defaultdict(float)
    for name, start, end in dev.ops():
        if window[0] <= start < window[1]:
            totals[label(name)] += (end - start) / 1e9
    return totals


def _top(totals: Dict[str, float], top: int):
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def device_ops_breakdown(dev: DevicePlane, window, top: int = 10):
    """[[category, seconds], ...]: the device time of ``window`` by op
    category, largest first."""
    return _top(_op_totals(dev, window, categorize), top)


def top_ops(dev: DevicePlane, window, top: int = 10):
    """[[``<category>: <identifier>``, seconds], ...]: the single ops
    that took most device time in ``window``."""
    return _top(_op_totals(
        dev, window,
        lambda name: f"{categorize(name)}: {identifier(name)}"), top)


def idle_gaps_breakdown(capture: Capture, dev: DevicePlane, window,
                        span_names: Sequence[str], top: int = 10):
    """[[host span, seconds], ...]: the device's idle time in ``window``
    by what the harness's host loop was doing, a gap going to the named
    span that covers most of it (``none`` where no span does)."""
    idle = subtract([list(window)], dev.busy)
    spans = [(n, s, e) for n, s, e in capture.host if n in span_names]
    totals: Dict[str, float] = collections.defaultdict(float)
    for g0, g1 in idle:
        cover: Dict[str, float] = collections.defaultdict(float)
        for n, s, e in spans:
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                cover[n] += overlap
        owner = max(cover, key=cover.get) if cover else "none"
        totals[owner] += (g1 - g0) / 1e9
    return _top(totals, top)
