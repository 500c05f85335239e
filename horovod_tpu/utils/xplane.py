"""XLA profile (xplane) summarizer — where does the step time go?

The reference answers "where did the time go" with its chrome-tracing
timeline of host-side engine phases (horovod/common/timeline.cc); on TPU
the compiled step is one fused XLA program, so the equivalent question is
answered from the XLA profiler's device plane. This module turns a
``jax.profiler.trace`` capture
(``examples/bert_pretraining_benchmark.py --profile DIR``) into the
per-op-category breakdown used in docs/benchmarks.md:

    python -m horovod_tpu.utils.xplane /tmp/prof [--top 30]

It parses the ``*.xplane.pb`` protobuf with the proto bindings TF ships
(tensorflow.tsl.profiler.protobuf) — no tensorboard needed.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple


def _load_spaces(logdir: str, files=None):
    """Parse the capture's xplane protobufs. ``files`` restricts the
    parse to an explicit list — callers measuring ONE capture window in
    a reused logdir must pass the files that window produced, or prior
    captures in the same tree silently inflate every byte count."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from horovod_tpu.utils.profiler import trace_files

    spaces = []
    for path in (trace_files(logdir) if files is None else files):
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        spaces.append(space)
    return spaces


def _device_lines(spaces, line_name):
    """Yield (plane, line) for every device-plane line named
    ``line_name`` — the one place the device-plane selection idiom
    lives (three metrics must not disagree over the same capture)."""
    for space in spaces:
        for plane in space.planes:
            if "/device:" not in plane.name and "TPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name == line_name:
                    yield plane, line


def device_op_times(logdir: str, line_name: str = "XLA Ops") -> Dict[str, float]:
    """Sum device-plane event durations (ms) by op/fusion name across all
    captured cores, from the ``line_name`` line only.

    The TPU device plane carries hierarchical lines — "Steps" and
    "XLA Modules" span whole steps, "Async XLA Ops" are DMA spans that
    overlap compute — so summing everything would double-count wildly.
    "XLA Ops" is the sequencer's occupancy: its events tile the step
    back-to-back (a copy-done there is the WAIT the scheduler failed to
    hide, not the copy itself), which is the decomposition
    docs/benchmarks.md's tables use."""
    totals: Dict[str, float] = collections.defaultdict(float)
    for plane, line in _device_lines(_load_spaces(logdir), line_name):
        meta = {i: m.name for i, m in plane.event_metadata.items()}
        for ev in line.events:
            name = meta.get(ev.metadata_id, str(ev.metadata_id))
            totals[name] += ev.duration_ps / 1e9  # ps -> ms
    return dict(totals)


_CATEGORIES: List[Tuple[str, str]] = [
    # (regex on op name, category label) — first match wins. The matmul
    # pattern sits BEFORE the generic fusion buckets because on TPU
    # nearly every matmul surfaces as a fusion op; when the fusion's
    # name carries its root ("%fusion.7 dot.42" / "loop_dot_fusion") it
    # is classified as matmul here. Anonymous "fusion.N" names give no
    # such signal and still land in the fusion buckets, so the matmul
    # row is a LOWER bound on MXU share — docs/benchmarks.md's MFU
    # numbers come from analytic FLOPs, not this table.
    # NB: no bare "conv" — it would swallow "%convert_*" names.
    (r"convolution|conv\d", "convolution"),
    (r"dot|einsum|matmul|gemm", "matmul"),
    (r"convert.*fusion|fusion.*convert", "convert/reduce fusion"),
    (r"multiply.*add.*fusion|scatter.*fusion", "multiply-add fusion"),
    (r"fusion", "other fusion"),
    (r"copy|slice|bitcast|transpose|reshape", "copy/layout"),
    (r"all-reduce|all-gather|reduce-scatter|collective|permute",
     "collective"),
    (r"select-and-scatter", "select-and-scatter"),
    (r"rng|random", "rng"),
    (r"infeed|outfeed|send|recv", "host transfer"),
]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_LAYOUT_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\](?:\{([^}]*)\})?")


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _first_shape_bytes(name: str) -> int:
    """Payload bytes of the FIRST shape literal in an HLO op string.

    Async-copy events are named with their full HLO text, e.g.
    ``%copy-start = (f32[16777216]{0:T(1024)S(1)}, ...)`` — the first
    shape is the destination buffer, i.e. the DMA payload. Returns 0
    when no shape is present (e.g. tuple-only or token ops).
    """
    m = _SHAPE_LAYOUT_RE.search(name)
    if not m:
        return 0
    dt, dims, _ = m.groups()
    return _shape_bytes(dt, dims)


def dma_bytes(logdir: str, line_name: str = "Async XLA Ops",
              spaces=None) -> Dict[str, float]:
    """Sum the DMA payload bytes moved by the async-copy engine.

    The TPU device plane's "Async XLA Ops" line carries one span per
    in-flight async copy (HBM<->VMEM staging; the copies the scheduler
    issues ahead of compute). Event stats hold no byte counts, but the
    event NAME is the HLO text whose first shape literal is the payload
    — that is what this sums. This measures the *prefetch-engine*
    traffic only: bytes a fusion loads/stores directly from HBM in its
    own loop never appear here, so the result is a LOWER bound on true
    HBM traffic for the capture window.

    Returns {"bytes": total payload bytes, "events": count,
    "busy_ms": summed span duration}.
    """
    total = 0.0
    nev = 0
    busy = 0.0
    if spaces is None:
        spaces = _load_spaces(logdir)
    for plane, line in _device_lines(spaces, line_name):
        meta = {i: m.name for i, m in plane.event_metadata.items()}
        for ev in line.events:
            name = meta.get(ev.metadata_id, "")
            b = _first_shape_bytes(name)
            if b:
                total += b
                nev += 1
                busy += ev.duration_ps / 1e9
    return {"bytes": total, "events": nev, "busy_ms": busy}


# Ops whose name-level operand lists alias or re-list buffers that other
# events already account for (while re-lists its whole carry tuple; GTEs
# are views; copy-done is the wait for a copy-start counted already).
_NO_TRAFFIC_OPS = frozenset({
    "while", "conditional", "call", "tuple", "get-tuple-element",
    "parameter", "bitcast", "constant", "copy-done", "after-all",
    "optimization-barrier",
})

_ID_ROOT_RE = re.compile(r"^%?([A-Za-z][\w.-]*?)(?:\.\d+)?(?:\s|=|$)")


def _op_root(name: str) -> str:
    """Op identifier root of an HLO text: "%while.2 = (...) while(...)"
    -> "while"; "%convert_reduce_fusion.1215 = ..." ->
    "convert_reduce_fusion". HLO ids default to the op type, so this is
    robust where an op-type regex is not (tuple output shapes contain
    nested parens that defeat simple matching)."""
    m = _ID_ROOT_RE.match(name)
    return m.group(1) if m else ""


def _hbm_shape_bytes_by_dtype(text: str) -> Dict[str, int]:
    """Bytes of every shape literal in ``text`` whose layout does NOT
    place it in a scoped memory space (``S(n)`` = VMEM/SMEM; unannotated
    layouts are HBM, space 0), split by element dtype — the bf16-vs-f32
    byte attribution the ``state_dtype`` policy (HBM diet round 2) is
    judged by: a mixed-precision regression shows up as f32 bytes
    creeping back into a class that should stream bf16."""
    out: Dict[str, int] = {}
    for dt, dims, layout in _SHAPE_LAYOUT_RE.findall(text):
        if layout and "S(" in layout:
            continue
        out[dt] = out.get(dt, 0) + _shape_bytes(dt, dims)
    return out


def _hbm_shape_bytes(text: str) -> int:
    """Total over :func:`_hbm_shape_bytes_by_dtype` — one accounting
    rule, so the per-dtype split can never desynchronize from the
    totals."""
    return sum(_hbm_shape_bytes_by_dtype(text).values())


def hbm_bytes(logdir: str, spaces=None) -> Dict[str, float]:
    """Per-capture HBM traffic derived from the COMPILED schedule.

    For every executed op on the sequencer's "XLA Ops" line, the event
    name is the scheduled HLO text: output + operand shape literals,
    each carrying its assigned memory space (``S(1)`` = VMEM; no ``S``
    = HBM). Summing the HBM-resident shapes over all executions counts
    the bytes each op moves to/from HBM — fusions' direct loads/stores
    included, which the async-DMA accounting (:func:`dma_bytes`) cannot
    see. Control-flow/aliasing ops (while, get-tuple-element, ...) are
    skipped — their names re-list buffers the real ops already count —
    and async copies are counted once at copy-start (copy-done is the
    wait). Known over-count: an in-place dynamic-update-slice is
    charged its full buffer. Returns {"bytes", "events"}.
    """
    total = 0.0
    nev = 0
    if spaces is None:
        spaces = _load_spaces(logdir)
    for plane, line in _device_lines(spaces, "XLA Ops"):
        meta = {i: m.name for i, m in plane.event_metadata.items()}
        # Per-op-name bytes memoized: 14k unique names, millions of events.
        cache: Dict[int, int] = {}
        for ev in line.events:
            b = cache.get(ev.metadata_id)
            if b is None:
                name = meta.get(ev.metadata_id, "")
                b = (0 if _op_root(name) in _NO_TRAFFIC_OPS
                     else _hbm_shape_bytes(name))
                cache[ev.metadata_id] = b
            if b:
                total += b
                nev += 1
    return {"bytes": total, "events": nev}


# Categories whose HBM byte counts are DIRECT streams (single-pass
# compute fusions — exact at the name level, unlike slice/copy ops
# whose names over-count their source buffers).
_DIRECT_CATS = ("conv+BN fusion", "wgrad+update fusion", "maxpool bwd",
                "elementwise fusion")


def _category_totals(spaces):
    """Per-category (sequencer ms, direct HBM bytes) over "XLA Ops"."""
    cat_ms: Dict[str, float] = collections.defaultdict(float)
    cat_b: Dict[str, float] = collections.defaultdict(float)
    for plane, line in _device_lines(spaces, "XLA Ops"):
        meta = {i: m.name for i, m in plane.event_metadata.items()}
        info: Dict[int, Tuple[str, int]] = {}
        for ev in line.events:
            mid = ev.metadata_id
            if mid not in info:
                name = meta.get(mid, "")
                op = _op_root(name)
                key = name.split(" = ")[0]
                if op in ("while", "conditional"):
                    cat = "while wrapper"
                elif "convert_reduce_fusion" in key:
                    cat = "conv+BN fusion"
                elif "multiply_add_fusion" in key:
                    cat = "wgrad+update fusion"
                elif "select-and-scatter" in key:
                    cat = "maxpool bwd"
                elif re.match(r"%(loop_)?fusion", key):
                    cat = "elementwise fusion"
                elif "start" in op or "done" in op or "copy" in key:
                    cat = "async copy waits"
                else:
                    cat = "other"
                b = (_hbm_shape_bytes(name)
                     if cat in _DIRECT_CATS and op not in _NO_TRAFFIC_OPS
                     else 0)
                info[mid] = (cat, b)
            cat, b = info[mid]
            cat_ms[cat] += ev.duration_ps / 1e9
            cat_b[cat] += b
    return cat_ms, cat_b


# Per-op-CLASS attribution (coarser than _category_totals' fusion-name
# buckets): where do the HBM bytes go — the wire, the optimizer, or the
# math? First match wins; roots in _NO_TRAFFIC_OPS are classed
# "control" with zero bytes (their names re-list buffers real ops own).
_OP_CLASSES: List[Tuple[str, str]] = [
    (r"all-reduce|all-gather|reduce-scatter|all-to-all|"
     r"collective-permute|collective", "collective"),
    # wgrad+momentum+param-apply fusions (TPU names them multiply_add /
    # scatter fusions; see _category_totals) — the traffic the sharded
    # weight update divides by N.
    (r"multiply[._-]?add.*fusion|scatter.*fusion", "optimizer"),
    (r"convolution|conv\d|dot|einsum|matmul|gemm|convert_reduce_fusion",
     "conv/matmul"),
    (r"copy|slice|bitcast|transpose|reshape|dynamic-update", "copy/layout"),
    (r"rng|random", "rng"),
    (r"infeed|outfeed|send|recv", "host transfer"),
    (r"fusion", "elementwise fusion"),
]


def _op_class(name: str) -> str:
    if _op_root(name) in _NO_TRAFFIC_OPS:
        return "control"
    low = name.lower()
    for pat, label in _OP_CLASSES:
        if re.search(pat, low):
            return label
    return "other"


def class_breakdown(logdir: str, steps: int = 1,
                    spaces=None) -> Dict[str, Dict[str, float]]:
    """Per-op-class sequencer time and schedule-derived HBM bytes over
    the "XLA Ops" line: ``{class: {"ms": .., "bytes": ..,
    "by_dtype": {dtype: bytes}}}`` (per step).

    This is the attribution table for traffic regressions: a jump in
    "collective" bytes means the wire (or a size-1 world failing to
    elide its collectives), "optimizer" the update fusions the sharded
    weight update divides by N, "conv/matmul" the math itself; the
    per-dtype split inside each class is the ``state_dtype`` policy's
    audit trail (f32 bytes reappearing in "optimizer" or "collective"
    means a full-width master/gradient buffer crept back). Bytes are
    name-level (each op's non-VMEM operand/result shapes — same
    accounting as :func:`hbm_bytes`), so copy/layout ops over-count
    their source buffers; "control" ops contribute time but no bytes.
    """
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"ms": 0.0, "bytes": 0.0,
                 "by_dtype": collections.defaultdict(float)})
    if spaces is None:
        spaces = _load_spaces(logdir)
    for plane, line in _device_lines(spaces, "XLA Ops"):
        meta = {i: m.name for i, m in plane.event_metadata.items()}
        info: Dict[int, Tuple[str, int, dict]] = {}
        for ev in line.events:
            mid = ev.metadata_id
            if mid not in info:
                name = meta.get(mid, "")
                cls = _op_class(name)
                if cls == "control":
                    info[mid] = (cls, 0, {})
                else:
                    bd = _hbm_shape_bytes_by_dtype(name)
                    info[mid] = (cls, sum(bd.values()), bd)
            cls, b, bd = info[mid]
            out[cls]["ms"] += ev.duration_ps / 1e9
            out[cls]["bytes"] += b
            for dt, db in bd.items():
                out[cls]["by_dtype"][dt] += db
    steps = max(steps, 1)
    return {c: {"ms": v["ms"] / steps, "bytes": v["bytes"] / steps,
                "by_dtype": {dt: db / steps
                             for dt, db in sorted(v["by_dtype"].items())}}
            for c, v in out.items()}


def _dtype_totals(classes: Dict[str, dict]) -> Dict[str, float]:
    """Capture-wide per-dtype byte totals summed over a
    :func:`class_breakdown` result — the one accounting rule behind both
    ``hbm_json``'s ``bytes_by_dtype_per_step`` and the CLI table's
    per-dtype columns, so the two can never disagree."""
    totals: Dict[str, float] = collections.defaultdict(float)
    for v in classes.values():
        for dt, db in v["by_dtype"].items():
            totals[dt] += db
    return dict(totals)


def fusion_direct_bytes(logdir: str, spaces=None) -> float:
    """Total bytes the compute fusions stream to/from HBM directly
    (their non-VMEM operand/output shapes) — the component of true HBM
    traffic the async-DMA accounting (:func:`dma_bytes`) cannot see.
    ``dma_bytes()["bytes"] + fusion_direct_bytes()`` is the measured
    true-traffic figure docs/benchmarks.md's roofline uses."""
    if spaces is None:
        spaces = _load_spaces(logdir)
    _, cat_b = _category_totals(spaces)
    return float(sum(cat_b.values()))


def hbm_json(logdir: str, steps: int = 1, spaces=None) -> dict:
    """Machine-readable form of the ``--hbm`` attribution (what
    ``--json`` prints and what bench tooling / the stats CLI consume
    instead of re-parsing the human table): per-op-class ms + bytes per
    step, the async-DMA payload, the fusion direct streams, and the
    true-traffic sum."""
    if spaces is None:
        spaces = _load_spaces(logdir)
    steps = max(steps, 1)
    dma = dma_bytes(logdir, spaces=spaces)
    direct = fusion_direct_bytes(logdir, spaces=spaces)
    classes = class_breakdown(logdir, steps=steps, spaces=spaces)
    by_dtype = _dtype_totals(classes)
    return {
        "steps": steps,
        "classes": classes,
        # Schedule-derived (name-level) bytes split by element dtype —
        # the bf16-vs-f32 audit column for the state_dtype policy.
        "bytes_by_dtype_per_step": dict(sorted(by_dtype.items())),
        "dma_bytes": dma["bytes"],
        "dma_events": dma["events"],
        "dma_busy_ms": dma["busy_ms"],
        "fusion_direct_bytes": direct,
        "true_hbm_bytes_per_step": (dma["bytes"] + direct) / steps,
        "module_ms": module_ms(logdir, spaces=spaces),
    }


def hbm_report(logdir: str, steps: int = 1, spaces=None) -> str:
    """The measured-roofline table (docs/benchmarks.md "The ceiling,
    measured"): per-category sequencer time, schedule-derived HBM bytes
    and achieved GB/s, plus the async-DMA payload and the true-traffic
    sum (DMA + fusion direct streams — disjoint by construction: a
    VMEM-resident operand is excluded from the fusion term).

    The scan's ``while`` wrapper is excluded — it spans the whole loop
    the inner ops already tile. Slice/copy -start/-done bytes are
    excluded from the direct-stream sum (their payloads are what the
    Async line counts; their name-level source shapes over-count)."""
    if spaces is None:
        spaces = _load_spaces(logdir)
    cat_ms, cat_b = _category_totals(spaces)
    dma = dma_bytes(logdir, spaces=spaces)
    inner = sum(ms for c, ms in cat_ms.items() if c != "while wrapper")
    if not inner:
        return (f"no device 'XLA Ops' events found under {logdir} "
                f"(empty or failed capture)")
    direct_gb = sum(cat_b.values()) / 1e9
    dma_gb = dma["bytes"] / 1e9
    out = [f"inner-op device time: {inner / steps:.2f} ms/step "
           f"({steps} steps)",
           f"{'category':22s} {'ms/step':>8s} {'share':>6s} "
           f"{'GB/step':>8s} {'GB/s':>6s}"]
    for c, ms in sorted(cat_ms.items(), key=lambda kv: -kv[1]):
        if c == "while wrapper":
            continue
        gbs = cat_b[c] / 1e9 / (ms / 1e3) if ms and cat_b[c] else 0
        out.append(f"{c:22s} {ms / steps:8.3f} {100 * ms / inner:5.1f}% "
                   f"{cat_b[c] / 1e9 / steps:8.2f} "
                   f"{gbs:6.0f}" if gbs else
                   f"{c:22s} {ms / steps:8.3f} {100 * ms / inner:5.1f}% "
                   f"{cat_b[c] / 1e9 / steps:8.2f} {'—':>6s}")
    out.append(f"async-DMA payload: {dma_gb / steps:.2f} GB/step "
               f"({dma['events'] // max(steps, 1)} copies/step)")
    total = (dma_gb + direct_gb) / steps
    out.append(f"true HBM traffic (DMA + direct streams): {total:.2f} "
               f"GB/step -> {total / (inner / steps / 1e3):.0f} GB/s "
               f"achieved over the device step")
    # Attribution: which op CLASS owns the bytes (collective wire vs
    # optimizer update vs the math) — the table that makes a traffic
    # regression attributable. Name-level accounting; "control" ops
    # (incl. the while wrapper, whose span covers the whole loop)
    # carry time but no bytes.
    classes = class_breakdown(logdir, steps=steps, spaces=spaces)
    # Per-dtype columns (bf16-vs-f32 split, HBM diet round 2): one
    # column per dtype carrying bytes anywhere in the capture, heaviest
    # first, so a full-width f32 buffer creeping back under a bf16
    # state policy is visible per class.
    dtotals = _dtype_totals(classes)
    dts = [d for d, _ in sorted(dtotals.items(), key=lambda kv: -kv[1])]
    out.append("per-op-class (schedule-derived bytes, name-level):")
    out.append(f"  {'class':20s} {'ms/step':>8s} {'GB/step':>8s}"
               + "".join(f" {('GB ' + d):>8s}" for d in dts))
    for c, v in sorted(classes.items(), key=lambda kv: -kv[1]["bytes"]):
        row = f"  {c:20s} {v['ms']:8.3f} {v['bytes'] / 1e9:8.2f}"
        for d in dts:
            row += f" {v['by_dtype'].get(d, 0.0) / 1e9:8.2f}"
        out.append(row)
    return "\n".join(out)


def categorize(name: str) -> str:
    low = name.lower()
    for pat, label in _CATEGORIES:
        if re.search(pat, low):
            return label
    return "other"


def module_ms(logdir: str, spaces=None) -> float:
    """Total device-occupancy of compiled modules (ms): the "XLA
    Modules" line spans whole executions, so this is the denominator for
    achieved-bandwidth numbers over a capture window."""
    if spaces is None:
        spaces = _load_spaces(logdir)
    return sum(ev.duration_ps / 1e9
               for _, line in _device_lines(spaces, "XLA Modules")
               for ev in line.events)


def summarize(logdir: str, top: int = 25, line_name: str = "XLA Ops") -> str:
    """Human-readable breakdown: per-category totals plus the `top`
    heaviest individual ops."""
    times = device_op_times(logdir, line_name=line_name)
    if not times:
        return f"no device-plane events found under {logdir}"
    total = sum(times.values())
    by_cat: Dict[str, float] = collections.defaultdict(float)
    by_cat_n: Dict[str, int] = collections.defaultdict(int)
    for name, ms in times.items():
        c = categorize(name)
        by_cat[c] += ms
        by_cat_n[c] += 1
    out = [f"device op time total: {total:.2f} ms (all cores, whole trace)",
           "", "by category:"]
    for cat, ms in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        out.append(f"  {ms:10.2f} ms  {100 * ms / total:5.1f}%  "
                   f"{cat}  (x{by_cat_n[cat]})")
    out.append("")
    out.append(f"top {top} ops:")
    for name, ms in sorted(times.items(), key=lambda kv: -kv[1])[:top]:
        out.append(f"  {ms:10.2f} ms  {100 * ms / total:5.1f}%  {name[:90]}")
    return "\n".join(out)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="Summarize a jax.profiler.trace capture by device op")
    ap.add_argument("logdir")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--line", default="XLA Ops",
                    help="device-plane line to sum (e.g. 'Async XLA Ops' "
                         "for the overlapped DMA spans)")
    ap.add_argument("--dma", action="store_true",
                    help="report async-DMA payload bytes (a lower bound "
                         "on HBM traffic) and achieved GB/s over the "
                         "captured device time")
    ap.add_argument("--steps", type=int, default=None,
                    help="training steps in the capture window (with "
                         "--dma/--hbm: per-step figures)")
    ap.add_argument("--hbm", action="store_true",
                    help="measured-roofline table: per-category time + "
                         "HBM bytes + achieved GB/s, async-DMA payload, "
                         "true-traffic sum, and the per-op-class "
                         "attribution (collective vs optimizer vs "
                         "conv/matmul bytes) (docs/benchmarks.md)")
    ap.add_argument("--json", action="store_true",
                    help="with --hbm: machine-readable attribution "
                         "(what bench tooling and utils.stats consume)")
    args = ap.parse_args(argv)
    if args.hbm:
        import json as _json

        if args.json:
            print(_json.dumps(hbm_json(args.logdir, steps=args.steps or 1)))
        else:
            print(hbm_report(args.logdir, steps=args.steps or 1))
    elif args.dma:
        spaces = _load_spaces(args.logdir)  # parse the (large) pbs once
        d = dma_bytes(args.logdir, spaces=spaces)
        dev_ms = module_ms(args.logdir, spaces=spaces)
        if not dev_ms:
            print(f"no device module events found under {args.logdir} "
                  f"(empty or failed capture)")
            return
        out = [f"async-DMA payload: {d['bytes'] / 1e9:.2f} GB over "
               f"{d['events']} copies (engine busy {d['busy_ms']:.1f} ms)",
               f"device module time: {dev_ms:.1f} ms -> achieved "
               f"{d['bytes'] / 1e9 / (dev_ms / 1e3):.0f} GB/s "
               f"(prefetch engine only; lower bound on HBM traffic)"]
        if args.steps:
            out.append(f"per step ({args.steps}): "
                       f"{d['bytes'] / 1e9 / args.steps:.2f} GB")
        print("\n".join(out))
    else:
        print(summarize(args.logdir, top=args.top, line_name=args.line))


if __name__ == "__main__":
    main()
