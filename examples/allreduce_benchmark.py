#!/usr/bin/env python
"""Allreduce microbenchmark: bus bandwidth and scaling efficiency.

The driver's north-star metric is allreduce scaling efficiency at 8→256
chips (BASELINE.md). This harness measures, for a sweep of buffer sizes:

- achieved allreduce algorithmic bandwidth (2·N·(size-1)/size bytes moved
  per chip per ring allreduce — the standard bus-bandwidth formula), and
- weak-scaling efficiency = t(1 chip) / t(N chips) for fixed per-chip
  payload (1.0 = perfect).

Runs on whatever mesh is visible: one real chip today, a pod slice
unmodified. On a single chip the collective is a self-reduction, so the
numbers are an upper bound / plumbing check.

Modes:
- default: compiled in-SPMD collective (the hot path).
- ``--engine``: the background-engine path — host numpy buffers through
  enqueue→fuse→stage→collective→host, the reference's CudaOnCPU staging
  shape (torch/mpi_ops_v2.cc:78-110). Scored in bytes/µs, the autotuner's
  objective (reference: parameter_manager.h:34-43).
- ``--engine --tensors K``: K equal tensors submitted together per
  iteration — the tensor-fusion stress (reference: docs/tensor-fusion.md);
  compare HVD_FUSION_THRESHOLD=0 vs default 64 MB.

Run: PYTHONPATH=. python examples/allreduce_benchmark.py --sizes-mb 1 16 64
     PYTHONPATH=. python examples/allreduce_benchmark.py --engine \
         --sizes-kb 1 64 1024 65536 --tensors 16

Multi-process (the engine control plane under negotiation — the
``--decompose`` table then carries the NEGOTIATE phase, split cached vs
full by the response cache; compare against HVD_CACHE_CAPACITY=0 run
sequentially for the measured win, docs/running.md "Negotiation cache"):
     python -m horovod_tpu.run -np 2 --cpu -- python \
         examples/allreduce_benchmark.py --engine --tensors 8 \
         --sizes-kb 64 --iters 30 --decompose --json
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common.compile_cache import enable_compile_cache
from horovod_tpu.ops.collectives import (
    HVD_AXIS,
    ranked_allgather,
    ranked_allreduce,
    ranked_reducescatter,
)


def _decompose_timeline(path, n_ops):
    """Phase decomposition of the engine round trip from the engine's
    own timeline (VERDICT r3 #6 — enqueue→cycle→stage→collective→fetch).
    Sums B→E durations per activity over every op in the run (warmup
    included) and reports the per-op average: QUEUE is time on the
    submission queue before a cycle drained it (queue spans of tensors
    submitted together OVERLAP — per-op queue time is what a caller
    experiences, not a wall-clock component), WAIT_FOR_DATA the
    host→device staging leg, ALLREDUCE the eager collective incl. the
    device→host fetch, MEMCPY_* the fusion-buffer pack/unpack.

    Multi-controller runs additionally carry NEGOTIATE_* spans; those
    are split by the ``cached`` arg the engines stamp on the span end —
    the negotiate-phase column comparing response-cache fast rounds vs
    full-table rounds (run once with the default cache and once with
    HVD_CACHE_CAPACITY=0 for the measured win). Returns the data for
    ``--json``."""
    import collections
    import json

    stack = {}
    totals = collections.defaultdict(float)
    spans = collections.defaultdict(list)  # activity -> [duration_s]
    neg_durs = {"cached": [], "full": []}
    for ev in json.load(open(path)):
        if not ev or ev.get("ph") not in ("B", "E"):
            continue
        key = (ev.get("pid"), ev.get("tid"))
        if ev["ph"] == "B":
            stack.setdefault(key, []).append((ev.get("name"), ev["ts"]))
        elif stack.get(key):
            name, ts0 = stack[key].pop()
            dur_s = (ev["ts"] - ts0) / 1e6
            totals[name] += dur_s
            spans[name].append(dur_s)
            if str(name).startswith("NEGOTIATE_"):
                cached = ev.get("args", {}).get("cached")
                if cached is not None:
                    neg_durs["cached" if cached else "full"].append(dur_s)
    accounted = sum(totals.values())
    print(f"# per-op phase decomposition ({n_ops} ops):")
    for name, s in sorted(totals.items(), key=lambda kv: -kv[1]):
        print(f"#   {s / n_ops * 1e3:10.2f} ms/op "
              f"{100 * s / accounted:5.1f}%  {name}")
    negotiate = {}
    for kind, durs in neg_durs.items():
        if durs:
            durs.sort()
            negotiate[kind] = {
                "n": len(durs),
                "median_ms": round(durs[len(durs) // 2] * 1e3, 3),
                "total_ms": round(sum(durs) * 1e3, 2),
            }
    if negotiate:
        import os

        parts = [f"{k} n={v['n']} median={v['median_ms']:.3f} ms"
                 for k, v in sorted(negotiate.items())]
        print(f"#   negotiate rounds (HVD_CACHE_CAPACITY="
              f"{os.environ.get('HVD_CACHE_CAPACITY', 'default')}): "
              + " | ".join(parts))

    # Per-SPAN medians over the canonical engine-path phases
    # (QUEUE/NEGOTIATE/MEMCPY/ALLREDUCE/MEMCPY_OUT — MEMCPY folds the submit snapshot and the fusion
    # copy-in together: the copy-in cost a tensor pays on the way to the
    # wire; the zero-copy pool/donation work moves exactly these two).
    def _median(names):
        durs = sorted(d for n in names for d in spans.get(n, ()))
        return round(durs[len(durs) // 2] * 1e3, 4) if durs else None

    phase_medians = {
        "QUEUE": _median(["QUEUE"]),
        "NEGOTIATE": _median([n for n in spans
                              if str(n).startswith("NEGOTIATE_")]),
        "MEMCPY": _median(["MEMCPY", "MEMCPY_IN_FUSION_BUFFER"]),
        "ALLREDUCE": _median(["ALLREDUCE"]),
        "MEMCPY_OUT": _median(["MEMCPY_OUT_FUSION_BUFFER"]),
    }
    parts = [f"{k}={v:.4f}" for k, v in phase_medians.items()
             if v is not None]
    print("#   phase medians (ms/span): " + " ".join(parts))
    return {
        "phases_ms_per_op": {k: round(v / n_ops * 1e3, 4)
                             for k, v in totals.items()},
        "phase_medians": phase_medians,
        "negotiate": negotiate or None,
    }


def _latency_fields(before, decompose=False):
    """Per-op submit→complete latency quantiles over the run, from the
    engine latency histograms (``engine.latency.*`` — the same
    instruments the fleet rollup merges world-wide, so a benchmark
    number is directly comparable to a production ``/fleet`` p99).
    ``before`` is a ``histogram_counts()`` snapshot from the start of
    the run; quantiles are computed on the bucket-count DELTAS so a
    warm registry doesn't pollute the window."""
    from horovod_tpu.core import telemetry as _tele

    out = {}
    for name, h in sorted(_tele.REGISTRY.histogram_counts().items()):
        if not name.startswith("engine.latency."):
            continue
        prev = before.get(name)
        counts = (h["counts"] if prev is None else
                  [c - p for c, p in zip(h["counts"], prev["counts"])])
        if not sum(counts):
            continue
        op = name.rsplit(".", 1)[1]
        q = {}
        for label, frac in (("latency_p50_us", 0.5),
                            ("latency_p99_us", 0.99)):
            v = _tele.quantile_from_buckets(h["bounds"], counts, frac)
            q[label] = None if v is None else round(v * 1e6, 1)
        out[op] = q
    if decompose and out:
        parts = [f"{op} p50={q['latency_p50_us']:g}us "
                 f"p99={q['latency_p99_us']:g}us"
                 for op, q in sorted(out.items())]
        print("#   submit->complete latency: " + " | ".join(parts))
    return out


def _wire_split(compressed_bytes, policy_name):
    """Decompose the MEASURED ``engine.wire_bytes.compressed`` counter
    into (payload_bytes, scale_bytes). Exact regardless of how fusion
    and chunk bucketing sliced the buffers: every scale block ships
    ``block`` one-byte payload elements + one 4-byte f32 scale (int8
    and fp8 payloads are both 1 byte), so the payload:scales ratio is
    block:4 for every chunk uniformly."""
    from horovod_tpu.jax.compression import Compression

    pol = Compression.resolve(policy_name)
    payload = compressed_bytes * pol.block // (pol.block + 4)
    return payload, compressed_bytes - payload


def run_engine(args, tl_path):
    """Engine-path sweep: bytes/µs through the async host engine.
    Tensor names are STABLE across iterations (``bench/{i}`` — the
    per-step-gradient pattern a training loop exhibits), so on a
    multi-process world steady-state negotiation rides the response
    cache's bitvector fast path; compare against HVD_CACHE_CAPACITY=0
    for the measured control-plane win.

    With ``--compression int8|fp8`` the engine wire policy is active and
    ``--decompose`` additionally prints the bytes-on-wire split:
    full-width submitted bytes vs what the mesh collectives actually
    shipped (int8 payload + f32 scales, from the engine.wire_bytes
    telemetry counters both engines feed identically), plus a sha256
    digest of the reduced result — run once with HVD_ENGINE=python and
    once with the default native engine to verify the reductions are
    bit-identical under the same policy."""
    import hashlib

    from horovod_tpu.core import engine as eng
    from horovod_tpu.core import telemetry as _tele

    import os as _os

    e = eng.get_engine()
    kind = type(e).__name__
    lat_before = _tele.REGISTRY.histogram_counts()
    policy = args.compression or "none"
    policy_dcn = args.compression_dcn or "none"
    print(f"# engine path ({kind}), fusion_threshold="
          f"{e.fusion_threshold}, tensors/iter={args.tensors}, "
          f"compression={policy}, compression_dcn={policy_dcn}, "
          f"donate={args.donate}, "
          f"HVD_POOL_MAX_BYTES="
          f"{_os.environ.get('HVD_POOL_MAX_BYTES', 'default')}")
    print(f"# {'size/tensor':>12s} {'total':>10s} {'time':>10s} "
          f"{'bytes/us':>9s} {'host_bw':>9s}")
    rows = []
    for kb in args.sizes_kb:
        # --decompose shuts the engine down after each size to flush its
        # timeline; a fresh singleton picks up cleanly.
        e = eng.get_engine()
        elems = max(1, int(kb * 1024 / 4))
        tensors = [np.ones((elems,), np.float32) for _ in range(args.tensors)]
        total = sum(t.nbytes for t in tensors)

        def one_iter(collect=False, bufs=None):
            # --donate: ownership handoff — the engine references the
            # buffers in place (read-only) instead of snapshotting,
            # the MEMCPY phase the pool already cheapened goes to ~0.
            handles = [
                e.allreduce_async(f"bench/{i}", t, average=False,
                                  donate=args.donate)
                for i, t in enumerate(bufs if bufs is not None else tensors)
            ]
            outs = [e.synchronize(h) for h in handles]
            return outs if collect else None

        wire_before = _tele.REGISTRY.flat_counters()
        for _ in range(args.warmup):
            one_iter()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            one_iter()
        wall = time.perf_counter() - t0
        dt = wall / args.iters
        # One extra (untimed) iteration for the reduction digest — the
        # cross-engine bit-identity check the quantized wire format is
        # pinned by. Fresh buffers: under --donate the timed tensors were
        # handed to the engine, and the digest must stay comparable
        # across engines and modes.
        outs = one_iter(collect=True,
                        bufs=[np.ones((elems,), np.float32)
                              for _ in range(args.tensors)])
        digest = hashlib.sha256(
            b"".join(np.ascontiguousarray(o).tobytes()
                     for o in outs)).hexdigest()
        wire_after = _tele.REGISTRY.flat_counters()
        print(f"  {kb:10.1f}kB {total/1e6:8.2f}MB {dt*1e3:8.3f}ms "
              f"{total/dt/1e6:9.1f} {total/dt/1e9:7.2f}GB/s")
        row = {"size_kb": kb, "total_mb": round(total / 1e6, 3),
               "ms_per_iter": round(dt * 1e3, 4),
               "bytes_per_us": round(total / dt / 1e6, 2),
               "digest": digest}
        niters = args.warmup + args.iters + 1

        def _delta(key):
            return wire_after.get(key, 0) - wire_before.get(key, 0)

        wire = {"submitted": _delta("engine.submitted.bytes"),
                "wire": _delta("engine.wire_bytes"),
                "compressed": _delta("engine.wire_bytes.compressed"),
                "dcn": _delta("engine.wire_bytes.dcn"),
                "ici": _delta("engine.wire_bytes.ici")}
        if policy != "none":
            wire["payload"], wire["scales"] = _wire_split(
                wire["compressed"], policy)
        elif policy_dcn != "none" and wire["dcn"]:
            # Two-phase route: the compressed counter IS the DCN tier.
            wire["payload"], wire["scales"] = _wire_split(
                wire["dcn"], policy_dcn)
        if wire["wire"]:
            wire["ratio"] = round(wire["submitted"] / wire["wire"], 3)
        row["wire_bytes"] = wire
        if args.decompose and wire["wire"]:
            pol = policy if policy != "none" else policy_dcn
            parts = (f"payload={wire['payload']/1e6:.2f}MB "
                     f"scales={wire['scales']/1e6:.3f}MB "
                     if "payload" in wire else "")
            print(f"#   bytes on the wire ({pol}): "
                  f"submitted={wire['submitted']/1e6:.2f}MB "
                  f"shipped={wire['wire']/1e6:.2f}MB {parts}"
                  f"-> {wire.get('ratio', 1.0):.2f}x fewer; "
                  f"digest={digest[:16]}")
            if wire["dcn"] or wire["ici"]:
                # Per-tier split of the hierarchical two-phase route:
                # ICI ships full-width 1/L chunks, DCN only the
                # quantized 1/L shard (+scales) — the cross-tier ratio
                # is the number that scales with host count.
                dcn_ratio = (wire["submitted"] / wire["dcn"]
                             if wire["dcn"] else float("inf"))
                print(f"#   per tier: ici={wire['ici']/1e6:.2f}MB "
                      f"dcn={wire['dcn']/1e6:.3f}MB "
                      f"-> {dcn_ratio:.1f}x fewer bytes cross-tier")
        if tl_path:
            from horovod_tpu.core import engine as _e

            # Flush the timeline for parsing; the next size's fresh
            # engine reopens the path with mode "w" and truncates it.
            _e.shutdown_engine()
            row["decompose"] = _decompose_timeline(
                tl_path, niters * args.tensors)
        rows.append(row)
    return {"mode": "engine", "engine": kind, "tensors": args.tensors,
            "iters": args.iters, "compression": policy,
            "compression_dcn": policy_dcn,
            "donate": args.donate,
            "pool_max_bytes": _os.environ.get("HVD_POOL_MAX_BYTES",
                                              "default"),
            "latency": _latency_fields(lat_before,
                                       decompose=args.decompose),
            "rows": rows}


def run_small(args, tl_path):
    """Small-tensor submit→complete throughput (tensors/s): ``--tensors
    N --bytes B`` — N stable names x B bytes per iteration, submitted
    through ONE batched engine call (``submit_n`` /
    ``hvd_engine_enqueue_n``) by default, or per-tensor with
    ``--per-tensor`` for the baseline this PR's acceptance compares
    against. The metric is what a gradient bucket of hundreds of small
    tensors experiences: per-tensor submit OVERHEAD, not bandwidth.

    Two phases keep the timed window honest: throughput is measured with
    the timeline OFF, then (for ``--json``) a short timeline'd rerun on
    a fresh engine supplies ``phase_medians`` — with batching working,
    QUEUE (not MEMCPY) is the residual phase."""
    import hashlib
    import os as _os

    from horovod_tpu.core import engine as eng

    from horovod_tpu.core import telemetry as _tele

    e = eng.get_engine()
    kind = type(e).__name__
    lat_before = _tele.REGISTRY.histogram_counts()
    n = args.tensors
    elems = max(1, args.bytes // 4)
    names = [f"bench/{i}" for i in range(n)]
    tensors = [np.full((elems,), 1.0, np.float32) for _ in range(n)]
    submit_mode = "per-tensor" if args.per_tensor else "batched"
    print(f"# small-tensor mode ({kind}, {submit_mode}): "
          f"{n} x {args.bytes}B per iteration, stable names")

    def one_iter(engine):
        t_sub0 = time.perf_counter()
        if args.per_tensor:
            handles = [engine.allreduce_async(nm, t, average=False)
                       for nm, t in zip(names, tensors)]
        else:
            handles = engine.submit_n("allreduce", [
                eng.SubmitRequest(nm, t, average=False)
                for nm, t in zip(names, tensors)])
        t_sub = time.perf_counter() - t_sub0
        return [engine.synchronize(h) for h in handles], t_sub

    for _ in range(args.warmup):
        one_iter(e)
    submit_s = 0.0
    t0 = time.perf_counter()
    for _ in range(args.iters):
        submit_s += one_iter(e)[1]
    wall = time.perf_counter() - t0
    per_iter = wall / args.iters
    tps = n / per_iter
    # The submit PLANE alone (handles-in-hand rate): what this PR's
    # batched ABI actually changes — the backend (negotiate + execute +
    # drain) is a floor both submit modes share.
    submit_per_iter = submit_s / args.iters
    submit_tps = n / submit_per_iter if submit_per_iter > 0 else 0.0
    # Untimed extra iteration for the reduction digest — the
    # batch-vs-singles / python-vs-C++ bit-identity check.
    outs = one_iter(e)[0]
    digest = hashlib.sha256(
        b"".join(np.ascontiguousarray(o).tobytes()
                 for o in outs)).hexdigest()
    print(f"#   {tps:12,.0f} tensors/s  "
          f"({per_iter * 1e3:.2f} ms per {n}-tensor iteration)")
    print(f"#   {submit_tps:12,.0f} tensors/s submit-plane  "
          f"({submit_per_iter * 1e3:.2f} ms to handles-in-hand)")
    result = {"mode": "engine-small", "engine": kind,
              "submit": submit_mode, "tensors": n, "bytes": args.bytes,
              "iters": args.iters, "tensors_per_s": round(tps, 1),
              "ms_per_iter": round(per_iter * 1e3, 3),
              "submit_tensors_per_s": round(submit_tps, 1),
              "submit_ms_per_iter": round(submit_per_iter * 1e3, 3),
              "latency": _latency_fields(lat_before,
                                         decompose=args.decompose),
              "digest": digest}
    if tl_path:
        # Timeline'd rerun on a fresh engine (2 iterations: one binds
        # the names, one steady-state) — phase medians only; the timed
        # numbers above never paid for timeline writes.
        _os.environ["HVD_TIMELINE"] = tl_path
        eng.shutdown_engine()
        e2 = eng.get_engine()
        for _ in range(2):
            one_iter(e2)
        eng.shutdown_engine()  # flush for parsing
        _os.environ.pop("HVD_TIMELINE", None)
        result["decompose"] = _decompose_timeline(tl_path, 2 * n)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 4, 16, 64])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--engine", action="store_true",
                    help="measure the background-engine (host/async) path "
                         "instead of the compiled in-SPMD path")
    ap.add_argument("--sizes-kb", type=float, nargs="+",
                    default=[1, 16, 64, 256, 1024, 16384, 65536, 262144],
                    help="per-tensor sizes for --engine (kB)")
    ap.add_argument("--tensors", type=int, default=None,
                    help="tensors submitted together per iteration "
                         "(--engine; exercises runtime fusion; default 1, "
                         "or 10000 in --bytes small-tensor mode)")
    ap.add_argument("--bytes", type=int, default=None,
                    help="with --engine: small-tensor mode — --tensors N "
                         "stable names x this many bytes each per "
                         "iteration (default 10000 x 4096), submitted "
                         "through ONE batched engine call; reports "
                         "submit→complete throughput in tensors/s and "
                         "(with --json) phase_medians")
    ap.add_argument("--per-tensor", action="store_true",
                    help="with --bytes: submit per-tensor (loop of "
                         "*_async) instead of batched — the baseline the "
                         "batched-submit speedup is measured against")
    ap.add_argument("--donate", action="store_true",
                    help="with --engine: submit with donate=True — the "
                         "zero-copy ownership handoff that skips the "
                         "submit snapshot entirely (compare the MEMCPY "
                         "phase median against a run without it, and "
                         "against HVD_POOL_MAX_BYTES=0 for the pooled "
                         "vs unpooled copy split)")
    ap.add_argument("--decompose", action="store_true",
                    help="with --engine: print the per-phase share table "
                         "of the round trip (queue / stage / collective "
                         "/ fusion memcpys) from the engine timeline. "
                         "Without --engine: additionally time the "
                         "reduce_scatter and all_gather phases an "
                         "allreduce decomposes into — the collective "
                         "shape of the sharded weight update "
                         "(DistributedOptimizer(sharded_update=True))")
    ap.add_argument("--compression", default=None,
                    choices=["none", "int8", "fp8"],
                    help="engine wire-compression policy (block-scaled "
                         "quantization, jax/quantize.py): sets "
                         "HVD_COMPRESSION for the run; with --decompose "
                         "the per-size output gains the bytes-on-wire "
                         "split (full-width vs int8 payload + f32 "
                         "scales) and a reduction digest for the "
                         "python-vs-C++ engine bit-identity check")
    ap.add_argument("--hierarchical", action="store_true",
                    help="route through reduce-scatter(ICI) -> psum(DCN) "
                         "-> all-gather(ICI) (reference: "
                         "HOROVOD_HIERARCHICAL_ALLREDUCE). Needs a "
                         "two-tier world: multi-process, or "
                         "HVD_TWO_TIER_SHAPE=o,i to split one host.")
    ap.add_argument("--compression-dcn", default=None,
                    choices=["none", "int8", "fp8"],
                    help="per-TIER engine wire policy: quantize ONLY the "
                         "cross-tier (DCN) phase of the hierarchical "
                         "two-phase route — ICI reduces at full width "
                         "(sets HVD_COMPRESSION_DCN; implies "
                         "--hierarchical; needs a two-tier world). With "
                         "--decompose the per-size output gains the "
                         "per-tier byte split from the "
                         "engine.wire_bytes.dcn/.ici counters")
    ap.add_argument("--json", action="store_true",
                    help="additionally print ONE machine-readable JSON "
                         "line with the sweep results (and, with "
                         "--decompose, the per-phase + negotiate "
                         "cached/full split), for tracking round-trip "
                         "latency across rounds")
    args = ap.parse_args()

    import os

    if args.engine and args.bytes:
        # Small-tensor mode defaults (10k x 4KB) — and the steady state
        # needs every name to fit the control/data-plane working sets:
        # a pre-bound pool slab per name, and a response-cache entry per
        # name (a cache smaller than the working set thrashes — all
        # misses, every round full-table — and the run measures cache
        # churn, not submit cost). Explicit env values still win.
        args.tensors = args.tensors or 10000
        os.environ.setdefault("HVD_POOL_BIND_MAX", str(args.tensors))
        os.environ.setdefault("HVD_CACHE_CAPACITY",
                              str(max(2 * args.tensors, 1024)))
    else:
        args.tensors = args.tensors or 1
    if args.compression_dcn and args.compression_dcn != "none":
        args.hierarchical = True
        os.environ["HVD_COMPRESSION_DCN"] = args.compression_dcn
    if args.hierarchical:
        os.environ["HVD_HIERARCHICAL_ALLREDUCE"] = "1"
    if args.compression and args.compression != "none":
        # Before hvd.init(): multi-controller init eagerly creates the
        # engine, which reads the wire policy at construction.
        os.environ["HVD_COMPRESSION"] = args.compression
    tl_path = None
    small = args.engine and bool(args.bytes)
    if args.engine and (args.decompose or (small and args.json)):
        # Must be in the env BEFORE hvd.init(): multi-controller init
        # eagerly creates the engine (negotiation liveness), and only
        # engine construction reads HVD_TIMELINE. Small-tensor mode
        # instead enables it AFTER the timed window, on a fresh engine
        # (run_small) — timeline writes must not distort tensors/s.
        import tempfile

        tl_path = os.path.join(tempfile.mkdtemp(prefix="hvd_tl_"),
                               "timeline.json")
        if not small:
            os.environ["HVD_TIMELINE"] = tl_path
    enable_compile_cache()
    hvd.init()
    if args.engine:
        result = (run_small(args, tl_path) if small
                  else run_engine(args, tl_path))
        if args.json:
            import json as _json

            result["nproc"] = hvd.num_processes()
            result["cache_capacity"] = os.environ.get(
                "HVD_CACHE_CAPACITY", "default")
            try:
                from horovod_tpu.core import telemetry as _tele

                flat = _tele.REGISTRY.flat()
                result["negotiation_cache"] = {
                    k.rsplit(".", 1)[1]: v for k, v in flat.items()
                    if k.startswith("engine.negotiation.cache_")}
            except Exception:
                pass
            print(_json.dumps(result))
        return
    if args.compression and args.compression != "none":
        print("# note: --compression measures the ENGINE wire format "
              "(use --engine); the compiled-path policy rides "
              "DistributedOptimizer(compression=...)")
    n = hvd.size()
    mesh = hvd.mesh()
    from horovod_tpu.ops.collectives import _hier_allreduce_active

    mode = "hierarchical" if _hier_allreduce_active() else "flat"
    if args.hierarchical and mode == "flat":
        print("# WARNING: --hierarchical requested but the world has no "
              "two-tier mesh; falling back to flat "
              "(set HVD_TWO_TIER_SHAPE or run multi-process)")
    print(f"# world: {n} chip(s), platform="
          f"{jax.devices()[0].platform}, mode={mode}")

    rows = []
    for mb in args.sizes_mb:
        elems = int(mb * 1024 * 1024 / 4)
        # Per-chip payload of `elems` f32, stacked over the mesh.
        x = jax.device_put(
            np.ones((n, elems), np.float32),
            NamedSharding(mesh, P(HVD_AXIS)))
        for _ in range(args.warmup):
            float(np.asarray(ranked_allreduce(x)[0]))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = ranked_allreduce(x)
        # Real device->host fetch of a SLICED scalar: fetching the whole buffer would bill a multi-MB
        # host transfer to the collective being measured.
        float(np.asarray(out[0]))
        dt = (time.perf_counter() - t0) / args.iters
        payload = elems * 4
        bus_bytes = 2 * payload * (n - 1) / max(n, 1)
        print(f"size={mb:8.1f} MB/chip  time={dt*1e3:8.3f} ms  "
              f"busbw={bus_bytes/dt/1e9:8.2f} GB/s  "
              f"alg_bw={payload/dt/1e9:8.2f} GB/s")
        rows.append({"size_mb": mb, "ms": round(dt * 1e3, 4),
                     "busbw_gbs": round(bus_bytes / dt / 1e9, 3),
                     "alg_bw_gbs": round(payload / dt / 1e9, 3)})

        if not args.decompose:
            continue
        # Phase decomposition of the same payload into the two halves an
        # allreduce is built from — reduce_scatter (each rank keeps the
        # sum of one 1/n chunk) then all_gather of the chunks. This is
        # the collective shape of the sharded weight update
        # (horovod_tpu/jax/sharded.py), so the engine-vs-in-step
        # comparison covers it directly. rs+ag ≈ allreduce is the
        # expected signature on a ring; a large gap means one phase's
        # schedule is mis-tuned.
        def timed(fn, arg, sync):
            for _ in range(args.warmup):
                sync(fn(arg))
            t0 = time.perf_counter()
            out = None
            for _ in range(args.iters):
                out = fn(arg)
            sync(out)
            return (time.perf_counter() - t0) / args.iters

        # Sliced-scalar fetch: a barrier that does not bill a multi-MB
        # host transfer (see above).
        def sync(out):
            return float(np.asarray(out.ravel()[0]))

        t_rs = timed(ranked_reducescatter, x, sync)
        scattered = ranked_reducescatter(x)  # (n, elems/n) per-rank chunks
        t_ag = timed(ranked_allgather, scattered, sync)
        print(f"  phases: reduce_scatter={t_rs*1e3:8.3f} ms  "
              f"all_gather={t_ag*1e3:8.3f} ms  "
              f"rs+ag={(t_rs+t_ag)*1e3:8.3f} ms  "
              f"(allreduce {dt*1e3:8.3f} ms)")
        rows[-1]["phases_ms"] = {
            "reduce_scatter": round(t_rs * 1e3, 4),
            "all_gather": round(t_ag * 1e3, 4)}
    if args.json:
        import json as _json

        print(_json.dumps({"mode": "spmd", "world": n,
                           "collective_mode": mode, "rows": rows}))


if __name__ == "__main__":
    main()
