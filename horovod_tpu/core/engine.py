"""Background dispatch engine for async host-side collectives.

Architecture mirrors the reference core (SURVEY.md §2.1 C1-C6): framework
threads *enqueue* named tensors and get an integer handle; one background
thread drains the queue each cycle, fuses compatible requests into flat
buffers, executes them on the data plane, and completes handles
(reference: operations.cc BackgroundThreadLoop/RunLoopOnce:1921-2172,
EnqueueTensorAllreduce:2264-2300, HandleManager: torch/handle_manager.cc).

TPU-native differences:
- No rank-0 negotiation: within one controller, request order is the
  program order; consistency checks (dtype/shape/op agreement for a name)
  still run and surface the reference's ERROR semantics
  (operations.cc:315-517).
- The data plane is the XLA collective module (:mod:`horovod_tpu.ops`),
  so "execute" stages host tensors onto the mesh — the same staging shape
  as the reference's CudaOnCPU path (torch/mpi_ops_v2.cc:78-110).

This Python engine is the semantic reference; the C++ `libhvdcore` engine
(horovod_tpu/core/native) replaces the scheduler/table/fusion loop with the
same observable behavior.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from horovod_tpu.core import bufferpool as bpool
from horovod_tpu.core import faultline as flt
from horovod_tpu.core import numerics as numx
from horovod_tpu.core import telemetry as tele
from horovod_tpu.core import timeline as tl

LOG = logging.getLogger("horovod_tpu.engine")

DEFAULT_CYCLE_TIME_S = 0.005  # reference: 5 ms, operations.cc:1747
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024  # reference: 64 MB, operations.cc:1739
STALL_WARNING_TIME_S = 60.0  # reference: operations.cc:253

# Engine-side wire formats (the quantized-collectives subsystem,
# jax/quantize.py): applied per execution CHUNK in the shared data plane
# below, so the python and C++ engines produce bit-identical reductions
# under the same policy by construction. Cast policies (bf16/fp16) stay
# frontend-side — they ride compress()/decompress() around the submit.
# Codes are the `wire` field of the C ABI (hvdcore.cc hvd_request).
ENGINE_WIRE_POLICIES = ("none", "int8", "fp8")
WIRE_CODES = {name: i for i, name in enumerate(ENGINE_WIRE_POLICIES)}
WIRE_NAMES = {i: name for name, i in WIRE_CODES.items()}

# Priority classes on the submit plane (the serving-plane subsystem):
# codes are the `priority` field of the C ABI (hvdcore.cc hvd_request) —
# a LOWER code drains first, so the tuple order below IS the drain
# order. The cycle loop composes fused batches and drains ready work in
# (priority, deadline-margin, name) order; admission budgets
# (HVD_ADMISSION_MAX_*) are accounted per class.
PRIORITY_CLASSES = ("high", "normal", "low")
PRIORITY_CODES = {name: i for i, name in enumerate(PRIORITY_CLASSES)}
PRIORITY_NAMES = {i: name for name, i in PRIORITY_CODES.items()}

# Per-entry introspection record shape (``Engine.inspect`` /
# ``NativeEngine.inspect`` / the ``hvd_engine_inspect`` C ABI): key names
# AND their order are machine-diffed against the C++ Inspect writer by
# hvdcheck rule ``parity-doctor`` — the hang doctor (core/doctor.py)
# correlates these records across ranks, so the two engines must export
# the identical shape. Records are built with ``dict(keyword=...)`` on
# purpose: dict literals in this module are swept by the span-args
# vocabulary lint (hvdcheck parity-span-args).
ENGINE_INSPECT_KEYS = (
    "name", "op", "phase", "phase_age_us", "bytes", "dtype", "wire",
    "batch_n", "priority", "deadline_remaining_us", "round",
)


def _process_str() -> str:
    try:
        from horovod_tpu.common import topology as _topo

        if _topo.is_initialized():
            return f"process {_topo.process_index()}"
    except Exception:
        pass
    return f"pid {os.getpid()}"


def resolve_wire_policy(name: Optional[str]) -> str:
    """Normalize an engine wire-policy spelling, failing FAST with rank
    attribution on unknown names (the same contract the frontend
    Compression surfaces enforce)."""
    if name is None:
        return "none"
    val = str(name).lower()
    if val in ("", "0", "false", "off"):
        return "none"
    if val not in ENGINE_WIRE_POLICIES:
        raise EngineError(
            f"unknown engine wire policy {name!r} on {_process_str()}: "
            f"expected one of {list(ENGINE_WIRE_POLICIES)} (cast "
            "policies bf16/fp16 are applied frontend-side)")
    return val


def wire_policy_from_env() -> str:
    """HVD_COMPRESSION: the engine-wide default wire format for the
    execution chunks (per-request policies override it). Misspellings
    fail fast at engine construction."""
    return resolve_wire_policy(os.environ.get("HVD_COMPRESSION")
                               or os.environ.get("HOROVOD_COMPRESSION"))


def wire_dcn_policy_from_env() -> str:
    """HVD_COMPRESSION_DCN: the engine-wide default DCN-tier wire format
    for the hierarchical two-phase route (per-request
    ``compression_dcn`` overrides it). Inert unless the world has
    two-tier structure AND HVD_HIERARCHICAL_ALLREDUCE is on — a flat
    world never quantizes through it."""
    return resolve_wire_policy(os.environ.get("HVD_COMPRESSION_DCN")
                               or os.environ.get("HOROVOD_COMPRESSION_DCN"))


def check_wire_exclusive(wire: str, wire_dcn: str, name: str):
    """A request's uniform wire policy and its per-tier DCN policy are
    mutually exclusive: `wire` quantizes the WHOLE exchange (the flat
    PR-12 route), `wire_dcn` quantizes only the 1/L cross-tier shard of
    the hierarchical route — asking for both is ambiguous about which
    pipeline runs, so the submit fails fast (shared by both engines)."""
    if wire not in ("", "none") and wire_dcn not in ("", "none"):
        raise EngineError(
            f"request '{name}' on {_process_str()} sets both the uniform "
            f"wire policy ({wire!r}) and the per-tier DCN policy "
            f"({wire_dcn!r}): they are mutually exclusive — the uniform "
            "policy quantizes the whole exchange, the DCN policy "
            "quantizes only the 1/L cross-tier shard of the "
            "hierarchical route. Pick one.")


def _poison_result(fault, out: np.ndarray, private: bool = False) -> np.ndarray:
    """engine.exec 'poison' fault: NaN-fill a float result AFTER the real
    collective ran — the reduced value every rank hands back is poisoned,
    which is what drives the numerics engine_check_result attribution
    (non-float results pass through; there is no NaN to poison with).

    ``private=True`` says the reduction already produced a buffer nothing
    else can alias (the executor's pool-checked-out output), so the
    defensive copy is the double copy on the result path — poison in
    place instead."""
    if fault is None or fault.mode != "poison" or out.dtype.kind not in "fc":
        return out
    if not private:
        out = np.array(out)  # never scribble on a caller-shared buffer
    out[...] = np.nan
    return out


# Placeholder a completed entry's tensor is swapped to (releases the
# snapshot slab's last engine-side reference before the waiter wakes).
_RETIRED = np.empty((0,), np.uint8)


def _freeze_donated(a: np.ndarray) -> bool:
    """Flag a donated buffer unwriteable so a donate-then-mutate raises
    (runtime-owned buffers — jax/TF — are read-only already). Returns
    whether the flag was actually flipped: a REJECTED donated submit
    (duplicate name, shutdown, injected fault) must flip it back — the
    engine never took ownership, and the caller's buffer must not stay
    read-only forever."""
    if not a.flags.writeable:
        return False
    try:
        a.flags.writeable = False
        return True
    except ValueError:  # pragma: no cover — writeable arrays flip fine
        return False


class EngineError(RuntimeError):
    """Collective failed; surfaced at synchronize() like the reference's
    ERROR response → exception path (test_torch.py:265-349)."""


class DuplicateNameError(EngineError):
    """Same tensor name enqueued twice before completion (reference:
    operations.cc:265-268, 2293-2296)."""


class ShutdownError(EngineError):
    """Engine shut down with requests outstanding (reference:
    SHUT_DOWN_ERROR, operations.cc:1833-1848)."""


class AdmissionRejected(EngineError):
    """The serving-plane admission controller rejected this submit
    SYNCHRONOUSLY at the boundary: the request's priority class is at
    its in-flight budget (HVD_ADMISSION_MAX_INFLIGHT /
    HVD_ADMISSION_MAX_BYTES), or the deadline-aware fast-fail shed it
    because its remaining deadline is provably smaller than the current
    p50 queue+negotiate latency. Nothing was admitted — no handle, no
    queue state, no peer announcement — so the caller may retry,
    degrade, or drop; in-flight work is NEVER rejected mid-flight and a
    fused batch is never torn (the cancel doctrine)."""


class CollectiveTimeout(EngineError):
    """A per-request deadline fired before the collective completed. The
    message names the PHASE the entry was stuck in (QUEUE / NEGOTIATE /
    ALLREDUCE / ...) and its age — fail fast with attribution instead of
    waiting out the global negotiation timeout. The entry itself may
    still be in flight (a wedged executor call cannot be interrupted);
    only the waiter is released, and an eventual late completion is
    discarded."""


class CancelledError(EngineError):
    """The collective was cooperatively cancelled (``cancel(handle)``).
    Pre-announce entries retire locally without executing; entries
    already announced to peers (or already executing) complete
    cross-rank — a fused/negotiated batch cannot be torn — and their
    result is discarded, so negotiation coherence is preserved by
    construction."""


def collective_deadline_from_env() -> Optional[float]:
    """HVD_COLLECTIVE_DEADLINE_S: the engine-wide default per-request
    deadline (seconds); per-request ``deadline_ms`` overrides it. Unset,
    empty or <= 0 means no default — and the deadline plane then adds
    ZERO hot-path work (the sweep short-circuits on a zero count)."""
    raw = (os.environ.get("HVD_COLLECTIVE_DEADLINE_S") or "").strip()
    if not raw:
        return None
    try:
        val = float(raw)
    except ValueError:
        raise EngineError(
            f"bad HVD_COLLECTIVE_DEADLINE_S {raw!r} on {_process_str()}: "
            "want seconds (a float)") from None
    return val if val > 0 else None


def resolve_priority(priority, name: str = "") -> int:
    """Normalize a priority-class spelling (or its integer code) to the
    code, failing FAST with rank attribution on unknown values — the
    same contract as :func:`resolve_wire_policy`. ``None`` means
    'normal' (callers that defer to the engine default resolve
    HVD_PRIORITY themselves via :func:`priority_from_env`)."""
    if priority is None:
        return PRIORITY_CODES["normal"]
    if isinstance(priority, (int, np.integer)) \
            and int(priority) in PRIORITY_NAMES:
        return int(priority)
    val = str(priority).lower()
    if val in PRIORITY_CODES:
        return PRIORITY_CODES[val]
    raise EngineError(
        f"unknown priority class {priority!r}"
        + (f" for '{name}'" if name else "")
        + f" on {_process_str()}: expected one of "
        f"{list(PRIORITY_CLASSES)} (or a code 0/1/2)")


def priority_from_env() -> int:
    """HVD_PRIORITY: the engine-wide default priority class for submits
    that name none ('normal' when unset). Misspellings fail fast at
    engine construction."""
    raw = (os.environ.get("HVD_PRIORITY")
           or os.environ.get("HOROVOD_PRIORITY") or "").strip()
    return resolve_priority(raw or None)


def _admission_limit(env: str, cls: str) -> int:
    """One per-class admission budget: ``{env}_{CLS}`` overrides the
    class-wide ``{env}``; unset/empty/0 means unlimited."""
    key = f"{env}_{cls.upper()}"
    raw = (os.environ.get(key) or os.environ.get(env) or "").strip()
    if not raw:
        return 0
    try:
        val = int(raw)
    except ValueError:
        raise EngineError(
            f"bad {key if os.environ.get(key) else env} {raw!r} on "
            f"{_process_str()}: want an integer (0 = unlimited)"
        ) from None
    return max(val, 0)


def admission_from_env():
    """HVD_ADMISSION_MAX_INFLIGHT / HVD_ADMISSION_MAX_BYTES: bounded
    per-class queue budgets for the serving plane (admission control).
    Each knob is the default for EVERY class; ``_HIGH`` / ``_NORMAL`` /
    ``_LOW`` suffixes override one class. 0/unset = unlimited (the
    historical behavior). Returns (max_inflight, max_bytes) as lists
    ordered like PRIORITY_CLASSES — shared by both engines; the native
    engine pushes the arrays through ``hvd_engine_set_admission`` at
    construction so its lock-free submit path enforces the same
    budgets."""
    mi = [_admission_limit("HVD_ADMISSION_MAX_INFLIGHT", c)
          for c in PRIORITY_CLASSES]
    mb = [_admission_limit("HVD_ADMISSION_MAX_BYTES", c)
          for c in PRIORITY_CLASSES]
    return mi, mb


# Deadline-aware shedding engages only once the phase histograms hold a
# meaningful sample (a cold engine must not shed on startup noise).
SHED_MIN_SAMPLES = 8


def queue_latency_estimate() -> Optional[float]:
    """Current p50 queue (+ negotiate, when that phase has samples)
    residency in seconds, read from the engine.phase.* histograms — the
    deadline-aware fast-fail's shedding threshold. None until
    SHED_MIN_SAMPLES observations exist, so a cold engine never
    sheds."""
    h = tele.REGISTRY.histogram("engine.phase.queue")
    if h.count < SHED_MIN_SAMPLES:
        return None
    est = tele.quantile_from_buckets(h.bounds, h.counts, 0.5)
    if est is None:
        return None
    hn = tele.REGISTRY.histogram("engine.phase.negotiate")
    if hn.count >= SHED_MIN_SAMPLES:
        neg = tele.quantile_from_buckets(hn.bounds, hn.counts, 0.5)
        if neg is not None:
            est += neg
    return est


@dataclass
class _Entry:
    handle: int
    name: str
    op: str  # 'allreduce' | 'allgather' | 'broadcast'
    tensor: np.ndarray
    average: bool = False
    root_rank: int = 0
    prescale: float = 1.0
    compression: str = "none"  # engine wire policy for this request
    # Per-tier DCN wire policy (hierarchical two-phase route): quantizes
    # ONLY the 1/L cross-tier shard; mutually exclusive with
    # `compression` (check_wire_exclusive at submit).
    compression_dcn: str = "none"
    # Ownership-handoff submit (allreduce_async(..., donate=True)): the
    # entry references the caller's buffer in place — no snapshot copy
    # was taken, and the engine only ever READS it (results land in
    # separate pool buffers), so frontends may donate runtime-owned
    # immutable buffers (jax arrays, TF eager tensors).
    donated: bool = False
    enqueued_at: float = field(default_factory=time.monotonic)
    # Processes whose announcement of this tensor has been marked on the
    # timeline (RANK_READY instants inside the NEGOTIATE_* span).
    ready_marked: set = field(default_factory=set)
    # Deadline/cancel plane: absolute monotonic deadline (None = none),
    # the phase the entry is currently stuck in (QUEUE -> NEGOTIATE ->
    # ALLREDUCE/ALLGATHER/BROADCAST — the CollectiveTimeout attribution),
    # whether the deadline already failed the waiter, and whether a
    # cooperative cancel is pending.
    deadline: Optional[float] = None
    phase: str = tl.QUEUE
    # Monotonic time of the last phase transition: the per-phase
    # residency histograms (engine.phase.*) observe the elapsed span at
    # every transition and once more at completion.
    phase_since: float = field(default_factory=time.monotonic)
    fired: bool = False
    cancelled: bool = False
    # Size of the batched submit this entry rode in on (submit_n /
    # hvd_engine_enqueue_n); 1 for a per-tensor submit. Carried onto the
    # QUEUE/MEMCPY span args so the trace critical path can attribute a
    # batch's queue share per member, not N x.
    batch_n: int = 1
    # Priority class code (PRIORITY_CODES; lower drains first). Joins
    # the drain sort key, the fusion key and — in negotiated worlds —
    # the request fingerprint, so batches stay priority-uniform and
    # mixed-priority worlds for one tensor fail fast by name.
    priority: int = 1


class _Handle:
    __slots__ = ("event", "result", "error", "name")

    def __init__(self, name: str = ""):
        self.event = threading.Event()
        self.result = None
        self.error: Optional[Exception] = None
        self.name = name  # numerics attribution at synchronize


class SubmitRequest:
    """One request of a batched submit (``Engine.submit_n`` /
    ``NativeEngine.submit_n``): the per-tensor arguments of the
    ``*_async`` verbs as one value, so a frontend holding a whole
    gradient bucket can hand it over in ONE engine call. Fields that a
    given op ignores (``root_rank`` for allreduce, ``average`` for
    broadcast, ...) are simply unused — exactly as the per-tensor verbs
    treat them. A plain-slots class, not a dict: the span-args
    vocabulary lint (hvdcheck span parity) sweeps dict literals in this
    module."""

    __slots__ = ("name", "tensor", "average", "root_rank", "prescale",
                 "compression", "compression_dcn", "donate", "deadline_ms",
                 "priority")

    def __init__(self, name: str, tensor, *, average: bool = False,
                 root_rank: int = 0, prescale: float = 1.0,
                 compression: Optional[str] = None,
                 compression_dcn: Optional[str] = None, donate: bool = False,
                 deadline_ms: Optional[float] = None,
                 priority: Optional[str] = None):
        self.name = name
        self.tensor = tensor
        self.average = average
        self.root_rank = root_rank
        self.prescale = prescale
        self.compression = compression
        self.compression_dcn = compression_dcn
        self.donate = donate
        self.deadline_ms = deadline_ms
        self.priority = priority


class JaxExecutor:
    """Data plane: host numpy buffers → eager XLA collectives over the mesh
    (reference analogue: PerformOperation's MPI/NCCL calls,
    operations.cc:1401-1531).

    When ``measure_staging`` is on (set by the engines while a timeline is
    being recorded), each call times the host→device staging step and
    leaves it in ``last_stage_s`` — the engines turn it into the
    ``WAIT_FOR_DATA`` span the reference records while waiting for input
    data to become available (operations.cc:783-807)."""

    measure_staging = False
    last_stage_s = 0.0
    # Buffer pool for output/staging buffers (engines hand their own pool
    # over at construction; a standalone executor rides the process-wide
    # default). Output buffers are checked out per call and recycle when
    # the caller drops the result views — the allocation-free
    # steady-state contract of core/bufferpool.py.
    pool = None
    # Wire policy of the CURRENT allreduce call (set by the engine from
    # the request's `compression`/`wire` just before the call — an
    # attribute, not a parameter, so test doubles with the historical
    # allreduce(flat, average) signature keep working) and the bytes the
    # call actually shipped (payload + scales under a quantized policy,
    # full width otherwise). Both engines read these into the
    # engine.wire_bytes{,.compressed} telemetry counters.
    wire_policy = "none"
    last_wire_bytes = 0
    last_wire_compressed = 0
    # Per-tier DCN wire policy of the current call (the hierarchical
    # two-phase route: ICI reduce-scatter at the resident dtype, ONLY
    # the 1/L shard crosses the DCN tier quantized) and the per-tier
    # byte split of the last call. Both stay 0 on every non-hierarchical
    # route — the engines feed them into engine.wire_bytes.dcn/.ici.
    wire_policy_dcn = "none"
    last_wire_bytes_dcn = 0
    last_wire_bytes_ici = 0

    @staticmethod
    def _ctx(arr: np.ndarray):
        # jax downcasts 64-bit dtypes unless x64 is enabled; host tensors
        # (e.g. torch float64 hyperparameters) must round-trip exactly.
        import contextlib

        if arr.dtype.itemsize == 8 and arr.dtype.kind in "fiuc":
            import jax

            return jax.enable_x64()
        return contextlib.nullcontext()

    def _stage(self, arr: np.ndarray):
        """Host→device transfer (the WAIT_FOR_DATA phase)."""
        import jax.numpy as jnp

        if not self.measure_staging:
            self.last_stage_s = 0.0
            return jnp.asarray(arr)
        t0 = time.perf_counter()
        staged = jnp.asarray(arr)
        try:
            staged.block_until_ready()
        except Exception:
            pass
        self.last_stage_s = time.perf_counter() - t0
        return staged

    # Fused-buffer execution granularity. Runtime fusion concatenates
    # whatever happened to share a cycle, so raw lengths are effectively
    # unique — every length would recompile the eager collective program.
    # Executing in fixed CHUNK-sized slices plus one pow2-bucketed tail
    # bounds the program count to ~12 per dtype (and chunking large
    # buffers also keeps any one staging transfer bounded).
    CHUNK_ELEMS = 1 << 22  # 16 MB of f32 — ~the reference's fusion scale

    @staticmethod
    def _bucket(n: int) -> int:
        """Round a tail length up to the next power of two (≥1 KiB of
        elements): ≤11 distinct tail programs below CHUNK_ELEMS."""
        return max(1024, 1 << (n - 1).bit_length())

    def _checkout(self, count: int, dtype) -> np.ndarray:
        pool = self.pool
        if pool is None:
            pool = self.pool = bpool.get_default()
        return pool.checkout(count, dtype)

    def _quantized_chunk(self, chunk: np.ndarray, pol, average: bool):
        """One execution chunk under a quantized wire policy: quantize
        HOST-side (the staged device buffers — the wire — already carry
        the int8 payload + f32 scales), allgather both across the world
        (each rank's hop ships the quantized bytes, the quantized
        reduce-scatter's per-rank traffic), dequantize-accumulate in
        f32. The quantize step stages into pool-checked-out wire slabs
        (payload, scales, f32 scratch) — no fresh arrays in the
        steady-state wire path. Returns (reduced chunk (f32), wire bytes
        shipped)."""
        from horovod_tpu.jax import quantize as Q
        from horovod_tpu.ops import collectives as C

        npad = Q.padded_len(max(chunk.shape[0], 1), pol.block)
        payload = self._checkout(npad, Q.np_wire_dtype(pol))
        scales = self._checkout(npad // pol.block, np.float32)
        work = self._checkout(npad, np.float32)
        Q.np_quantize_into(chunk, pol, payload, scales, work)
        gp = np.asarray(C.allgather(self._stage(payload)))
        stage_s = self.last_stage_s
        gs = np.asarray(C.allgather(self._stage(scales)))
        self.last_stage_s += stage_s
        world = gp.shape[0] // npad
        out = Q.np_dequantize_sum(gp.reshape(world, npad),
                                  gs.reshape(world, -1), pol)
        if average:
            out /= world
        return out[:chunk.shape[0]], payload.nbytes + scales.nbytes

    def _wire_quantizer(self, flat: np.ndarray):
        """The quantized-policy object for this call, or None (policy
        off, non-float payload, or a 1-rank world — where the compiled
        path elides quantization too, so the engines match)."""
        if self.wire_policy in ("", "none") or flat.dtype.kind not in "f":
            return None
        try:
            from horovod_tpu.common import topology as _topo

            if _topo._require_init().size <= 1:
                return None
        except Exception:
            return None
        from horovod_tpu.jax.compression import Compression

        return Compression.resolve(self.wire_policy, where="engine wire")

    def _dcn_quantizer(self, flat: np.ndarray):
        """The quantized DCN-tier policy for this call, or None. Gated
        exactly like the compiled hierarchical route: float payload, a
        multi-chip world with two-tier structure, the hierarchical knob
        on, AND a cross tier of more than one group — a single-tier
        outer axis elides the quantization (no wire hop to shrink), so
        the digest stays on the unquantized path on both planes."""
        if (self.wire_policy_dcn in ("", "none")
                or flat.dtype.kind not in "f"):
            return None
        try:
            from horovod_tpu.common import topology as _topo
            from horovod_tpu.ops import collectives as C

            st = _topo._require_init()
            if (st.size <= 1 or st.two_tier is None
                    or not C.hierarchical_allreduce_enabled()):
                return None
            # Tier dims come from the two-tier MESH, not the host
            # split: a simulated topology (HVD_TWO_TIER_SHAPE) has
            # several mesh groups inside one host/process.
            if dict(st.two_tier.shape).get("dcn", 1) <= 1:
                return None
        except Exception:
            return None
        from horovod_tpu.jax.compression import Compression

        return Compression.resolve(self.wire_policy_dcn,
                                   where="engine dcn wire")

    @staticmethod
    def _two_tier_chunk_bytes(n: int, dpol) -> int:
        """DCN-tier bytes one execution chunk of ``n`` elements ships on
        the hierarchical route: the 1/L ICI-reduced shard, block-padded
        and quantized (payload + f32 scales) — mirroring
        spmd_allreduce's padding (outer_size * block) so the counter is
        the TRUE cross-tier payload, not an estimate."""
        from horovod_tpu.common import topology as _topo
        from horovod_tpu.jax import quantize as Q

        shape = dict(_topo._require_init().two_tier.shape)
        local = shape["ici"]
        cross = shape["dcn"]
        n_ici = Q.padded_len(max(n, 1), local) // local
        npad = Q.padded_len(n_ici, cross * dpol.block)
        wire_itemsize = np.dtype(Q.np_wire_dtype(dpol)).itemsize
        return npad * wire_itemsize + (npad // dpol.block) * 4

    def allreduce(self, flat: np.ndarray, average: bool) -> np.ndarray:
        from horovod_tpu.ops import collectives as C

        fault = flt.engine_exec("allreduce")  # stall sleeps, error raises
        pol = self._wire_quantizer(flat)
        dpol = self._dcn_quantizer(flat) if pol is None else None
        n = flat.shape[0]
        # Pool-checked-out result buffer: private by construction (nothing
        # else holds a view), handed to callers as slices and recycled by
        # the pool once they drop it.
        out = self._checkout(n, flat.dtype)
        stage_s = 0.0
        wire = 0
        wire_dcn = 0
        wire_ici = 0
        with self._ctx(flat):
            off = 0
            while off < n:
                take = min(self.CHUNK_ELEMS, n - off)
                chunk = flat[off: off + take]
                bucket = (take if take == self.CHUNK_ELEMS
                          else self._bucket(take))
                if bucket != take:
                    # Zero padding is reduction-neutral (sum of zeros;
                    # average divides by world size only — and zero
                    # blocks quantize to zero payload). Padded into a
                    # pooled slab, not a fresh concatenation.
                    padded = self._checkout(bucket, flat.dtype)
                    padded[:take] = chunk
                    padded[take:] = 0
                    chunk = padded
                if pol is not None:
                    res, chunk_wire = self._quantized_chunk(chunk, pol,
                                                            average)
                    wire += chunk_wire
                elif dpol is not None:
                    # Hierarchical two-phase route: the eager ranked
                    # program reduce-scatters over ICI at the resident
                    # dtype and ships ONLY the quantized 1/L shard
                    # across the DCN tier — both engines execute it
                    # through this shared call, so their digests are
                    # bit-identical by construction.
                    res = np.asarray(
                        C.allreduce(self._stage(chunk), average=average,
                                    dcn_wire=self.wire_policy_dcn))
                    ici_b = chunk.nbytes
                    dcn_b = self._two_tier_chunk_bytes(chunk.shape[0],
                                                       dpol)
                    wire += ici_b + dcn_b
                    wire_ici += ici_b
                    wire_dcn += dcn_b
                else:
                    res = np.asarray(
                        C.allreduce(self._stage(chunk), average=average))
                    wire += chunk.nbytes
                stage_s += self.last_stage_s
                out[off: off + take] = res[:take]
                off += take
        self.last_stage_s = stage_s
        self.last_wire_bytes = wire
        self.last_wire_compressed = (wire if pol is not None else wire_dcn)
        self.last_wire_bytes_dcn = wire_dcn
        self.last_wire_bytes_ici = wire_ici
        return _poison_result(fault, out, private=True)

    def allgather(self, tensor: np.ndarray) -> np.ndarray:
        from horovod_tpu.ops import collectives as C

        fault = flt.engine_exec("allgather")
        self.last_wire_bytes = tensor.nbytes
        self.last_wire_compressed = 0
        self.last_wire_bytes_dcn = 0
        self.last_wire_bytes_ici = 0
        with self._ctx(tensor):
            return _poison_result(
                fault, np.asarray(C.allgather(self._stage(tensor))))

    def broadcast(self, tensor: np.ndarray, root_rank: int) -> np.ndarray:
        from horovod_tpu.ops import collectives as C

        fault = flt.engine_exec("broadcast")
        self.last_wire_bytes = tensor.nbytes
        self.last_wire_compressed = 0
        self.last_wire_bytes_dcn = 0
        self.last_wire_bytes_ici = 0
        with self._ctx(tensor):
            return _poison_result(
                fault,
                np.asarray(C.broadcast(self._stage(tensor), root_rank)))


def _multi_controller() -> bool:
    """True when more than one controller process is active. Fusion
    decisions are local to a controller; with several controllers, local
    drain timing could fuse different batches on different processes and
    launch mismatched collective programs — the failure the reference's
    rank-0 negotiation exists to prevent (operations.cc:279-517). The
    negotiated path (core/coordinator.py) makes batch composition agreed;
    without it, multi-process runs execute one name-ordered collective
    per tensor."""
    try:
        from horovod_tpu.common import topology as _topo

        return _topo.is_initialized() and _topo.num_processes() > 1
    except Exception:
        return False


def _negotiated() -> bool:
    """True when multi-controller runs will coordinate batches through the
    KV-store negotiation protocol (so fusion/autotune may stay enabled)."""
    if not _multi_controller():
        return False
    from horovod_tpu.core import coordinator as _coord

    if not _coord.negotiation_enabled():
        return False
    try:
        _coord.JaxKV()
        return True
    except _coord.KVError:
        return False


def record_cache_config(capacity: int, forced_off: bool = False):
    """Surface the EFFECTIVE negotiation-cache capacity in telemetry
    (`hvd.telemetry_report()` then says whether the cache is on, and
    whether the negotiation-fallback rule forced it off along with
    fusion)."""
    tele.REGISTRY.gauge("engine.negotiation.cache_capacity").set(
        int(capacity))
    # Always written (not only when 1): a later engine generation with
    # negotiation available must clear a stale forced-off marker, or the
    # report would say "capacity 1024" and "forced off" at once.
    tele.REGISTRY.gauge("engine.negotiation.cache_forced_off").set(
        1 if forced_off else 0)


def config_from_env(cycle_time_s: Optional[float],
                    fusion_threshold: Optional[int],
                    stall_warning_s: float):
    """Shared env-knob parsing for both engine implementations (reference:
    operations.cc:1732-1804). Returns (cycle_time_s, fusion_threshold,
    stall_warning_s, cache_capacity).

    The negotiation response cache follows the same fallback rule as
    fusion: HVD_NEGOTIATION=0 or no usable KV store forces it off —
    without negotiated rounds there is no control plane to cache."""
    if cycle_time_s is None:
        ms = os.environ.get("HVD_CYCLE_TIME") or os.environ.get(
            "HOROVOD_CYCLE_TIME")
        cycle_time_s = float(ms) / 1000.0 if ms else DEFAULT_CYCLE_TIME_S
    if fusion_threshold is None:
        b = os.environ.get("HVD_FUSION_THRESHOLD") or os.environ.get(
            "HOROVOD_FUSION_THRESHOLD")
        fusion_threshold = int(b) if b else DEFAULT_FUSION_THRESHOLD
    from horovod_tpu.core import coordinator as _coord

    cache_capacity = _coord.cache_capacity_from_env()
    if _multi_controller():
        if not _negotiated():
            fusion_threshold = 0
            forced = cache_capacity > 0
            cache_capacity = 0
            record_cache_config(0, forced_off=forced)
        else:
            if _coord.aggregation_enabled():
                # Gather-tree rounds republish full tables through p0's
                # digest by design — the Coordinator keeps the cache off,
                # and telemetry must say 0, not pretend it is on.
                cache_capacity = 0
            record_cache_config(cache_capacity)
    st = os.environ.get("HVD_STALL_CHECK_TIME") or os.environ.get(
        "HOROVOD_STALL_CHECK_TIME")
    if st:  # seconds; reference hardcodes 60 (operations.cc:253)
        stall_warning_s = float(st)
    if os.environ.get("HVD_STALL_CHECK_DISABLE") or os.environ.get(
            "HOROVOD_STALL_CHECK_DISABLE"):
        stall_warning_s = 0.0
    return cycle_time_s, fusion_threshold, stall_warning_s, cache_capacity


def record_submit(op: str, nbytes: int, queue_depth: int):
    """Submit-side telemetry shared by both engine implementations (the
    native engine enqueues through Python too; only execution-side
    counters need its stats C API). Counter names are the parity contract
    tests/test_telemetry.py pins across the two engines."""
    tele.REGISTRY.counter(f"engine.submitted.{op}").inc()
    tele.REGISTRY.counter("engine.submitted.bytes").inc(int(nbytes))
    tele.REGISTRY.histogram(
        "engine.tensor_bytes", tele.BYTES_BUCKETS).observe(int(nbytes))
    tele.REGISTRY.gauge("engine.queue_depth").set(queue_depth)


def record_submit_batch(op: str, sizes, queue_depth: Optional[int],
                        ring_full: int = 0, ring_spins: int = 0):
    """Submit-side telemetry for ONE batched submit of ``len(sizes)``
    requests — the whole batch folds into one pass over the registry
    (one ``inc(n)`` per counter, one :meth:`Histogram.observe_many`)
    instead of N per-tensor ``record_submit`` calls, so instrumentation
    does not hand back the lock round-trips the batched ABI removed.
    Shared by both engines (the native engine's ring pressure counters
    arrive through its stats sync instead — it passes no ring args; the
    python twin has no ring, so the pair stays 0 and merely pins the
    counter names into existence for cross-engine parity).
    ``queue_depth=None`` skips the gauge: the native engine's batched
    path must NOT read its pending count here — that takes the engine
    mutex (and folds the submit ring), re-locking the very fast path the
    ring exists to unlock; its periodic stats sync owns the gauge."""
    n = len(sizes)
    total = int(sum(sizes))
    tele.REGISTRY.counter(f"engine.submitted.{op}").inc(n)
    tele.REGISTRY.counter("engine.submitted.bytes").inc(total)
    tele.REGISTRY.counter("engine.submit.batched").inc(n)
    tele.REGISTRY.counter("engine.ring.full").inc(ring_full)
    tele.REGISTRY.counter("engine.ring.spins").inc(ring_spins)
    tele.REGISTRY.histogram(
        "engine.tensor_bytes",
        tele.BYTES_BUCKETS).observe_many([int(s) for s in sizes])
    if queue_depth is not None:
        tele.REGISTRY.gauge("engine.queue_depth").set(queue_depth)


def record_wire(executor):
    """Wire-byte telemetry after one executor call: engine.wire_bytes =
    bytes the mesh collective actually shipped (int8 payload + f32
    scales under a quantized policy, full width otherwise);
    engine.wire_bytes.compressed = the subset shipped under a policy.
    The native engine feeds the SAME counters through its stats C API
    (hvd_result.wire_bytes/wire_compressed -> hvd_engine_stats)."""
    wire = int(getattr(executor, "last_wire_bytes", 0))
    comp = int(getattr(executor, "last_wire_compressed", 0))
    if wire:
        tele.REGISTRY.counter("engine.wire_bytes").inc(wire)
    if comp:
        tele.REGISTRY.counter("engine.wire_bytes.compressed").inc(comp)
    # Per-tier split of the hierarchical two-phase route (zero on every
    # flat route): engine.wire_bytes.dcn is the quantized 1/L cross-tier
    # payload, engine.wire_bytes.ici the full-width intra-tier share.
    # The native engine feeds the SAME counters through its stats C API
    # (hvd_result.wire_dcn/wire_ici -> hvd_engine_stats).
    dcn = int(getattr(executor, "last_wire_bytes_dcn", 0))
    ici = int(getattr(executor, "last_wire_bytes_ici", 0))
    if dcn:
        tele.REGISTRY.counter("engine.wire_bytes.dcn").inc(dcn)
    if ici:
        tele.REGISTRY.counter("engine.wire_bytes.ici").inc(ici)


def record_cycle(elapsed_s: float):
    """One engine cycle that executed work (idle ticks are not counted —
    both engines apply the same rule, so the counts are comparable)."""
    tele.REGISTRY.counter("engine.cycles").inc()
    tele.REGISTRY.counter("engine.cycle_seconds_total").inc(elapsed_s)


def _phase_class(phase: str) -> str:
    """Collapse a deadline-attribution phase (QUEUE / NEGOTIATE_* /
    ALLREDUCE / ALLGATHER / BROADCAST) to its residency class."""
    if phase == tl.QUEUE:
        return "queue"
    if phase.startswith("NEGOTIATE"):
        return "negotiate"
    return "exec"


def record_phase(cls: str, seconds: float):
    """One phase-residency observation (queue / negotiate / memcpy /
    exec). Instrument names and bucket boundaries are the cross-engine
    parity contract: the C++ engine feeds the SAME histograms through
    ``hvd_engine_latency`` (hvdcheck rule ``parity-latency``). The
    memcpy class counts one observation per fusion-buffer copy pass
    that performs a real copy (pack on both engines; the native staging
    copy-out too — the python twin unpacks by view and observes no
    copy-out)."""
    tele.REGISTRY.histogram(
        "engine.phase.queue" if cls == "queue" else
        "engine.phase.negotiate" if cls == "negotiate" else
        "engine.phase.memcpy" if cls == "memcpy" else
        "engine.phase.exec").observe(seconds)


def record_complete_latency(op: str, latency_s: float,
                            margin_s: Optional[float] = None,
                            priority: Optional[int] = None):
    """End-to-end submit→complete latency of ONE engine collective, per
    op class, plus — when the request carried a deadline — the margin
    remaining at completion (clipped at 0: a deadline-fired entry that
    completes late reports zero margin), plus — when a priority class
    is given — the per-class serving-plane split
    (engine.latency.class.*) the overload acceptance gate reads. Same
    parity contract as :func:`record_phase`. The compiled/AOT hot path
    feeds nothing here (hvd.jax.jit collectives stay uninstrumented —
    the bench headline's standing rule)."""
    tele.REGISTRY.histogram(
        "engine.latency.allreduce" if op == "allreduce" else
        "engine.latency.allgather" if op == "allgather" else
        "engine.latency.broadcast").observe(latency_s)
    if priority is not None:
        tele.REGISTRY.histogram(
            "engine.latency.class.high" if priority == 0 else
            "engine.latency.class.low" if priority == 2 else
            "engine.latency.class.normal").observe(latency_s)
    if margin_s is not None:
        tele.REGISTRY.histogram("engine.deadline.margin").observe(
            max(float(margin_s), 0.0))


def record_admission_rejected(shed: bool = False):
    """One admission-plane rejection. ``shed`` says the deadline-aware
    fast-fail (remaining deadline < current p50 queue+negotiate
    latency) rejected the request, rather than a class budget. Counter
    names are the cross-engine parity contract — the native engine
    feeds the SAME counters through its stats C API
    (hvd_engine_stats.admission_rejected / admission_shed)."""
    tele.REGISTRY.counter(
        "engine.admission.shed" if shed
        else "engine.admission.rejected").inc()


def record_admission(inflight):
    """Per-class in-flight gauges (ordered like PRIORITY_CLASSES) — the
    saturation view /healthz, the doctor and the fleet console read.
    The native engine calls this from its stats sync with its
    ``admission_inflight_*`` stats fields."""
    tele.REGISTRY.gauge("engine.admission.inflight.high").set(
        int(inflight[0]))
    tele.REGISTRY.gauge("engine.admission.inflight.normal").set(
        int(inflight[1]))
    tele.REGISTRY.gauge("engine.admission.inflight.low").set(
        int(inflight[2]))


# Reserved name prefix of the synthetic submits the engine.admit burst
# fault injects — the injector skips its own names, so a burst can
# never recurse.
ADMIT_BURST_PREFIX = "_hvd.admit.burst."
_admit_burst_seq = 0


def admission_burst_inject(engine, name: str):
    """Fault site ``engine.admit`` (mode ``burst``, core/faultline.py):
    deterministically inject N synthetic LOW-priority 1-element
    allreduces ahead of this submit, so admission/shedding behavior is
    chaos-testable without the full load harness. Rejected synthetic
    submits are swallowed (saturation rejecting the burst IS the
    scenario under test); survivors carry a short deadline and are
    retired by a daemon waiter, so they cannot wedge a negotiated world
    where peers never announce them. Shared by both engines — called at
    the top of the single-submit path (batched submits bypass it, like
    the per-request shed check)."""
    global _admit_burst_seq
    if name.startswith(ADMIT_BURST_PREFIX):
        return
    burst = flt.engine_admit_burst()
    if not burst:
        return
    handles = []
    for _ in range(int(burst)):
        _admit_burst_seq += 1
        try:
            handles.append(engine.allreduce_async(
                f"{ADMIT_BURST_PREFIX}{os.getpid()}.{_admit_burst_seq}",
                np.zeros(1, np.float32), False, deadline_ms=10000.0,
                priority="low"))
        except EngineError:
            continue
    if handles:
        def _retire():
            for h in handles:
                try:
                    engine.synchronize(h)
                except EngineError:
                    pass

        threading.Thread(target=_retire, name="hvd-admit-burst",
                         daemon=True).start()


def build_admission_summary(queue_depth, inflight, inflight_bytes,
                            max_inflight, max_bytes):
    """The admission-state body BOTH engines hand to /healthz, the
    doctor snapshot and the fleet console: queue depth, per-class
    in-flight counts/bytes against their budgets, and which
    class+budget is tripped (saturated). Built with ``dict(keyword=...)``
    on purpose — dict literals in this module are swept by the
    span-args vocabulary lint (hvdcheck parity-span-args)."""
    classes = {}
    saturated = []
    tripped_first = None
    for i, cls in enumerate(PRIORITY_CLASSES):
        tripped = []
        if max_inflight[i] > 0 and inflight[i] >= max_inflight[i]:
            tripped.append("max_inflight")
        if max_bytes[i] > 0 and inflight_bytes[i] >= max_bytes[i]:
            tripped.append("max_bytes")
        classes[cls] = dict(inflight=int(inflight[i]),
                            inflight_bytes=int(inflight_bytes[i]),
                            max_inflight=int(max_inflight[i]),
                            max_bytes=int(max_bytes[i]),
                            tripped=tripped)
        if tripped:
            saturated.append(cls)
            if tripped_first is None:
                tripped_first = dict(cls=cls, budget=tripped[0])
    return dict(queue_depth=int(queue_depth), classes=classes,
                saturated=saturated, tripped=tripped_first)


def doctor_on_hang(reason, kind, table, rank):
    """Engage the cross-rank hang doctor (core/doctor.py) on a
    hang-class flight dump: publish this rank's inspect table on the
    fleet/KV plane and attempt an attributed verdict. Shared by both
    engine implementations; never raises — post-mortem reporting must
    not take the engine down. Returns the verdict dict or None."""
    try:
        from horovod_tpu.core import doctor as _doctor

        return _doctor.on_hang(reason, kind, table, rank)
    except Exception:
        LOG.debug("hang doctor failed", exc_info=True)
        return None


def make_autotuner(engine):
    """Shared autotuner construction (reference: HOROVOD_AUTOTUNE,
    operations.cc:1797-1804). Returns a ParameterManager or None. In
    multi-controller worlds tuning runs on process 0 only and propagates
    through the negotiation round params, mirroring the reference where
    rank 0 tunes and broadcasts (parameter_manager.cc:63-77,203-236);
    without negotiation it stays off. Failures are reported, not silently
    swallowed, and never take the engine down."""
    from horovod_tpu.tune import ParameterManager, autotune_enabled

    if not autotune_enabled():
        return None
    if _multi_controller():
        from horovod_tpu.common import topology as _topo

        if not _negotiated() or _topo.process_index() != 0:
            return None
    try:
        return ParameterManager(engine)
    except Exception as exc:
        LOG.warning("HVD_AUTOTUNE requested but the autotuner failed to "
                    "start (%s); continuing without autotuning", exc)
        return None


class Engine:
    def __init__(
        self,
        executor=None,
        cycle_time_s: Optional[float] = None,
        fusion_threshold: Optional[int] = None,
        stall_warning_s: float = STALL_WARNING_TIME_S,
        timeline: Optional[tl.Timeline] = None,
    ):
        (self.cycle_time_s, self.fusion_threshold, stall_warning_s,
         self.cache_capacity) = config_from_env(
            cycle_time_s, fusion_threshold, stall_warning_s)
        self.stall_warning_s = stall_warning_s or STALL_WARNING_TIME_S
        self.stall_check_disabled = stall_warning_s == 0.0
        self.executor = executor or JaxExecutor()
        # Per-engine buffer pool (core/bufferpool.py): submit snapshots,
        # fusion buffers and executor outputs ride reused slabs. Per
        # ENGINE, not process-wide, so elastic teardown can poison
        # exactly the dying engine's pool (abandon below).
        self.pool = bpool.BufferPool()
        if getattr(self.executor, "pool", None) is None:
            self.executor.pool = self.pool
        # Engine-wide default wire format (HVD_COMPRESSION); per-request
        # policies override it at submit. Fails fast on misspellings.
        self.wire_default = wire_policy_from_env()
        # Per-tier DCN default (HVD_COMPRESSION_DCN) for the
        # hierarchical two-phase route; inert without two-tier
        # structure. Mutually exclusive with a uniform wire policy on
        # any one request (check_wire_exclusive).
        self.wire_dcn_default = wire_dcn_policy_from_env()
        # Deadline/cancel/drain plane: the engine-wide default deadline
        # (HVD_COLLECTIVE_DEADLINE_S), the count of in-flight entries
        # carrying a deadline (the sweep's zero-cost short circuit), and
        # the quiesce reason once admission is closed.
        self.default_deadline_s = collective_deadline_from_env()
        self._deadline_count = 0
        self._quiesced: Optional[str] = None
        # Serving-plane admission control: the default priority class
        # (HVD_PRIORITY) and the per-class in-flight budgets
        # (HVD_ADMISSION_MAX_INFLIGHT / _MAX_BYTES with per-class
        # overrides; 0 = unlimited), plus the per-class accounting the
        # budgets are enforced against (guarded by self._lock).
        self.priority_default = priority_from_env()
        self.adm_max_inflight, self.adm_max_bytes = admission_from_env()
        self._adm_inflight = [0] * len(PRIORITY_CLASSES)
        self._adm_bytes = [0] * len(PRIORITY_CLASSES)
        self.timeline = timeline if timeline is not None else tl.from_env()
        if self.timeline.enabled:
            # Staging time feeds the WAIT_FOR_DATA spans; only measured
            # (it costs a device sync) while a timeline is recording.
            self.executor.measure_staging = True
        self._param_manager = make_autotuner(self)
        self._queue: "queue.Queue[_Entry]" = queue.Queue()
        self._handles: Dict[int, _Handle] = {}
        self._pending_names: Dict[str, _Entry] = {}
        self._lock = threading.Lock()
        self._next_handle = 0
        self._shutdown = threading.Event()
        self._wake = threading.Event()  # enqueue cuts idle sleeps short
        # Submitting a deadline'd entry breaks the watchdog's (possibly
        # 12 s) idle sleep immediately — the tightened sweep tick alone
        # would only take effect on the NEXT wait. Shutdown sets it too.
        self._stall_kick = threading.Event()
        self._last_stall_warn = 0.0
        # Negotiated multi-controller path (core/coordinator.py): entries
        # drained but not yet agreed with the peer processes.
        self._coordinator = None
        self._coord_unavailable = False
        self._negotiating: list = []
        self._extra_wait = 0.0
        # Clock-anchor sync emitted into the timeline once the
        # coordinator's exchange completes (distributed tracing).
        self._clock_synced = False
        # Post-mortem hook: SIGUSR1 dumps the flight recorder of a live
        # (possibly hung) run — no env var needed.
        tl.install_sigusr1(self._dump_sigusr1)
        self._thread = threading.Thread(
            target=self._loop, name="hvd-background", daemon=True
        )
        self._thread.start()
        # Stall detection runs on its own watchdog thread: the dispatch
        # thread may itself be blocked inside a hung collective — exactly
        # the condition to report (reference rationale: operations.cc:
        # 1535-1581; there the check rides the coordinator tick).
        self._stall_thread = threading.Thread(
            target=self._stall_loop, name="hvd-stall-watchdog", daemon=True
        )
        self._stall_thread.start()

    # -- enqueue API (reference: EnqueueTensorAllreduce/Allgather/Broadcast,
    # operations.cc:2264-2380) ------------------------------------------------

    def _enqueue(self, entry: _Entry, mem_span=None) -> int:
        # Fault site engine.admit (burst mode): synthetic low-priority
        # submits land ahead of this one (a no-op without the fault).
        admission_burst_inject(self, entry.name)
        # Fault site engine.submit (core/faultline.py): a failed submit
        # raises before any handle/queue state exists — same observable
        # shape as an organic enqueue rejection.
        injected = flt.engine_submit(entry.name)
        if injected is not None:
            raise EngineError(injected)
        with self._lock:
            if self._shutdown.is_set():
                raise ShutdownError("engine is shut down")
            if self._quiesced is not None:
                # Admission closed (quiesce): fail FAST with a
                # descriptive error — new work must not ride into a
                # draining engine (graceful preemption, elastic shrink).
                raise EngineError(
                    f"engine is draining ({self._quiesced}): submissions "
                    "are closed — the engine is completing in-flight "
                    "work before shutdown (quiesce)")
            if entry.name in self._pending_names:
                raise DuplicateNameError(
                    f"a collective named '{entry.name}' is already pending; "
                    "names must be unique among in-flight tensors"
                )
            self._check_admission_locked(entry)
            h = _Handle(entry.name)
            entry.handle = self._next_handle
            self._next_handle += 1
            self._handles[entry.handle] = h
            self._pending_names[entry.name] = entry
            self._adm_inflight[entry.priority] += 1
            self._adm_bytes[entry.priority] += int(entry.tensor.nbytes)
            adm = list(self._adm_inflight)
            if entry.deadline is not None:
                self._deadline_count += 1
                self._stall_kick.set()
            depth = len(self._pending_names)
        record_admission(adm)
        record_submit(entry.op, entry.tensor.nbytes, depth)
        # Numerics (core/numerics.py): the local nonfinite count of the
        # SNAPSHOT is the attribution side of the synchronize-time check
        # — a poisoned reduced result names the submitting process.
        numx.engine_note_submit(entry.name, entry.tensor)
        if mem_span is not None:
            # The submit-time snapshot as a retro MEMCPY span at the head
            # of the QUEUE span; the END args carry the zero-copy
            # attribution ({"pooled": bool} / {"donated": true}) the
            # trace CLI splits copy-phase medians by.
            t0, t1, args = mem_span
            self.timeline.start(entry.name, tl.QUEUE, ts_us=t0)
            self.timeline.start(entry.name, tl.MEMCPY, ts_us=t0)
            self.timeline.end(entry.name, tl.MEMCPY, args, ts_us=t1)
        else:
            self.timeline.start(entry.name, tl.QUEUE)
        self._queue.put(entry)
        self._wake.set()
        return entry.handle

    def _check_admission_locked(self, entry: _Entry):
        """Admission control (the serving-plane subsystem): reject a
        submit SYNCHRONOUSLY when its priority class is at budget, and
        shed a deadline'd submit whose remaining margin is provably
        smaller than the current p50 queue+negotiate latency — instead
        of letting it rot in QUEUE past its deadline. Rejection happens
        at the submit boundary ONLY: never mid-flight, never tearing a
        fused batch (the cancel doctrine). Runs under the engine lock;
        raises :class:`AdmissionRejected`."""
        cls = entry.priority
        limit = self.adm_max_inflight[cls]
        blimit = self.adm_max_bytes[cls]
        nbytes = int(entry.tensor.nbytes)
        if limit > 0 and self._adm_inflight[cls] + 1 > limit:
            record_admission_rejected()
            raise AdmissionRejected(
                f"admission rejected for '{entry.name}' on "
                f"{_process_str()}: priority class "
                f"'{PRIORITY_NAMES[cls]}' is at its in-flight budget "
                f"({self._adm_inflight[cls]}/{limit} requests, "
                "HVD_ADMISSION_MAX_INFLIGHT); resubmit after in-flight "
                "work completes, or raise the budget")
        if blimit > 0 and self._adm_bytes[cls] + nbytes > blimit:
            record_admission_rejected()
            raise AdmissionRejected(
                f"admission rejected for '{entry.name}' on "
                f"{_process_str()}: priority class "
                f"'{PRIORITY_NAMES[cls]}' is at its bytes budget "
                f"({self._adm_bytes[cls]} in flight + {nbytes} > "
                f"{blimit} bytes, HVD_ADMISSION_MAX_BYTES); resubmit "
                "after in-flight work completes, or raise the budget")
        if entry.deadline is not None:
            est = queue_latency_estimate()
            if (est is not None
                    and entry.deadline - time.monotonic() < est):
                record_admission_rejected(shed=True)
                raise AdmissionRejected(
                    f"shed '{entry.name}' on {_process_str()}: its "
                    "remaining deadline is smaller than the current "
                    f"p50 queue+negotiate latency ({est * 1e3:.1f} ms) "
                    "— it would expire in QUEUE (deadline-aware "
                    "fast-fail; counted in engine.admission.shed)")

    # Submit-time SNAPSHOT (pool-slab copy — np.array before the pool):
    # the C++ engine memcpys at enqueue (hvdcore.cc), so a caller
    # mutating its buffer after an *_async call must not change what gets
    # reduced — the python twin owes the same observable semantics, and
    # frontends hand over zero-copy views (torch .numpy()/bf16
    # reinterpret). ``donate=True`` skips the copy: the engine takes
    # ownership and references the buffer in place (read-only — results
    # land in separate pool buffers), so the caller must not touch it
    # again; the numpy view is flagged unwriteable so an in-process
    # mutation raises rather than corrupting the reduction.
    def _snapshot(self, tensor, donate: bool):
        """(array, donated, flipped-read-only, (t0, t1, span_args))."""
        t0 = self.timeline.now_us()
        a = np.asarray(tensor)
        if donate and a.flags["C_CONTIGUOUS"]:
            flipped = _freeze_donated(a)
            return a, True, flipped, (t0, self.timeline.now_us(),
                                      {"donated": True})
        snap, tracked = self.pool.snapshot(a)
        return snap, False, False, (t0, self.timeline.now_us(),
                                    {"pooled": tracked})

    def _submit(self, entry: _Entry, span, flipped: bool) -> int:
        try:
            return self._enqueue(entry, span)
        except Exception:
            # Rejected submit: the engine never took ownership — a
            # donated buffer we froze must become writable again.
            if flipped:
                entry.tensor.flags.writeable = True
            raise

    def _abs_deadline(self, deadline_ms: Optional[float]) -> Optional[float]:
        """Per-request ``deadline_ms`` (overrides the engine-wide
        HVD_COLLECTIVE_DEADLINE_S default; <= 0 disables for this
        request) as an absolute monotonic instant, or None."""
        if deadline_ms is not None:
            return (time.monotonic() + deadline_ms / 1000.0
                    if deadline_ms > 0 else None)
        if self.default_deadline_s is not None:
            return time.monotonic() + self.default_deadline_s
        return None

    def _priority(self, priority, name: str) -> int:
        """Per-request priority class (None defers to HVD_PRIORITY)."""
        return (resolve_priority(priority, name)
                if priority is not None else self.priority_default)

    def allreduce_async(self, name: str, tensor: np.ndarray, average: bool,
                        prescale: float = 1.0,
                        compression: Optional[str] = None,
                        compression_dcn: Optional[str] = None,
                        donate: bool = False,
                        deadline_ms: Optional[float] = None,
                        priority: Optional[str] = None) -> int:
        # `compression` is the per-request engine wire policy (frontend
        # Compression objects carry it as .engine_wire); None defers to
        # the HVD_COMPRESSION default. `compression_dcn` is the per-TIER
        # policy of the hierarchical route (HVD_COMPRESSION_DCN default)
        # — mutually exclusive with a uniform wire policy.
        wire = (resolve_wire_policy(compression)
                if compression is not None else self.wire_default)
        wire_dcn = (resolve_wire_policy(compression_dcn)
                    if compression_dcn is not None
                    else self.wire_dcn_default)
        check_wire_exclusive(wire, wire_dcn, name)
        prio = self._priority(priority, name)
        snap, donated, flipped, span = self._snapshot(tensor, donate)
        return self._submit(
            _Entry(-1, name, "allreduce", snap, average=average,
                   prescale=prescale, compression=wire,
                   compression_dcn=wire_dcn, donated=donated,
                   deadline=self._abs_deadline(deadline_ms),
                   priority=prio),
            span, flipped)

    def allgather_async(self, name: str, tensor: np.ndarray,
                        donate: bool = False,
                        deadline_ms: Optional[float] = None,
                        priority: Optional[str] = None) -> int:
        prio = self._priority(priority, name)
        snap, donated, flipped, span = self._snapshot(tensor, donate)
        return self._submit(
            _Entry(-1, name, "allgather", snap, donated=donated,
                   deadline=self._abs_deadline(deadline_ms),
                   priority=prio),
            span, flipped)

    def broadcast_async(self, name: str, tensor: np.ndarray, root_rank: int,
                        donate: bool = False,
                        deadline_ms: Optional[float] = None,
                        priority: Optional[str] = None) -> int:
        prio = self._priority(priority, name)
        snap, donated, flipped, span = self._snapshot(tensor, donate)
        return self._submit(
            _Entry(-1, name, "broadcast", snap, root_rank=root_rank,
                   donated=donated,
                   deadline=self._abs_deadline(deadline_ms),
                   priority=prio),
            span, flipped)

    def submit_n(self, op: str, requests) -> List[int]:
        """Batched submit — the python twin of ``hvd_engine_enqueue_n``:
        one validation pass, one snapshot pass (name-bound pool slabs,
        :meth:`BufferPool.snapshot_bound`), ONE lock acquisition and one
        wakeup for N :class:`SubmitRequest` of a single collective op.
        Returns N handles in request order; per-request ``deadline_ms``
        / ``compression`` / ``donate`` are preserved.

        The duplicate-name contract is DEFERRED: a request whose name is
        already in flight does not fail the batch — that handle alone
        fails, and its ``synchronize`` raises
        :class:`DuplicateNameError`. (The C++ engine admits
        ring-published batches asynchronously on the loop thread, where
        a synchronous per-request verdict no longer exists; the python
        twin owes the same observable semantics.) Mixed-op batches,
        empty batches and intra-batch duplicate names are rejected
        synchronously — those are caller bugs, not races."""
        if op not in ("allreduce", "allgather", "broadcast"):
            raise EngineError(f"batched submit: unsupported op {op!r}")
        reqs = list(requests)
        n = len(reqs)
        if n == 0:
            raise EngineError("batched submit needs at least one request")
        seen = set()
        for r in reqs:
            if r.name in seen:
                raise DuplicateNameError(
                    f"a collective named '{r.name}' appears twice in one "
                    "batched submit; names must be unique among in-flight "
                    "tensors")
            seen.add(r.name)
        # Fault site engine.submit: checked ONCE per batch, before any
        # buffer is frozen or snapshotted — same observable shape as a
        # synchronous enqueue rejection.
        injected = flt.engine_submit(reqs[0].name)
        if injected is not None:
            raise EngineError(injected)
        # Wire-policy validation BEFORE any buffer is frozen or
        # snapshotted: a bad spelling (or a uniform+per-tier conflict)
        # must reject the batch while the engine still owns nothing —
        # donated buffers frozen mid-loop would otherwise stay
        # read-only after the raise.
        wires: List[tuple] = []
        for r in reqs:
            wire = ("none" if op != "allreduce"
                    else (resolve_wire_policy(r.compression)
                          if r.compression is not None
                          else self.wire_default))
            wire_dcn = ("none" if op != "allreduce"
                        else (resolve_wire_policy(r.compression_dcn)
                              if r.compression_dcn is not None
                              else self.wire_dcn_default))
            check_wire_exclusive(wire, wire_dcn, r.name)
            # Priority resolves here too — a bad spelling must reject
            # the batch before any buffer is frozen.
            wires.append((wire, wire_dcn,
                          self._priority(getattr(r, "priority", None),
                                         r.name)))
        entries: List[_Entry] = []
        spans = []
        flipped: List[np.ndarray] = []
        for r, (wire, wire_dcn, prio) in zip(reqs, wires):
            t0 = self.timeline.now_us()
            a = np.asarray(r.tensor)
            if r.donate and a.flags["C_CONTIGUOUS"]:
                if _freeze_donated(a):
                    flipped.append(a)
                snap, donated = a, True
                args = {"donated": True}
            else:
                snap, tracked = self.pool.snapshot_bound(r.name, a)
                donated = False
                args = {"pooled": tracked}
            args["batch_n"] = n
            spans.append((t0, self.timeline.now_us(), args))
            entries.append(_Entry(
                -1, r.name, op, snap, average=r.average,
                root_rank=r.root_rank, prescale=r.prescale,
                compression=wire, compression_dcn=wire_dcn, donated=donated,
                deadline=self._abs_deadline(r.deadline_ms), batch_n=n,
                priority=prio))
        dup_failed = []
        handles: List[int] = []
        with self._lock:
            if self._shutdown.is_set() or self._quiesced is not None:
                # Whole-batch rejection: the engine never took
                # ownership, so every buffer frozen above flips back.
                for a in flipped:
                    a.flags.writeable = True
                if self._shutdown.is_set():
                    raise ShutdownError("engine is shut down")
                raise EngineError(
                    f"engine is draining ({self._quiesced}): submissions "
                    "are closed — the engine is completing in-flight "
                    "work before shutdown (quiesce)")
            # Whole-batch admission pre-check, all-or-nothing: a
            # batched submit over budget rejects synchronously BEFORE
            # any handle exists — admission never tears a batch (the
            # per-request shed fast-fail stays single-submit-only; same
            # rule as the C++ EnqueueN pre-check).
            need_n = [0] * len(PRIORITY_CLASSES)
            need_b = [0] * len(PRIORITY_CLASSES)
            for e in entries:
                need_n[e.priority] += 1
                need_b[e.priority] += int(e.tensor.nbytes)
            for cls in range(len(PRIORITY_CLASSES)):
                limit = self.adm_max_inflight[cls]
                blimit = self.adm_max_bytes[cls]
                if ((limit > 0
                     and self._adm_inflight[cls] + need_n[cls] > limit)
                        or (blimit > 0
                            and self._adm_bytes[cls] + need_b[cls]
                            > blimit)):
                    for a in flipped:
                        a.flags.writeable = True
                    record_admission_rejected()
                    raise AdmissionRejected(
                        f"admission rejected for a batched submit of "
                        f"{n} on {_process_str()}: priority class "
                        f"'{PRIORITY_NAMES[cls]}' is over budget "
                        f"({self._adm_inflight[cls]} in flight + "
                        f"{need_n[cls]} requested, "
                        "HVD_ADMISSION_MAX_INFLIGHT / "
                        "HVD_ADMISSION_MAX_BYTES); the batch is "
                        "rejected whole — admission never tears a "
                        "fused batch")
            for e in entries:
                h = _Handle(e.name)
                e.handle = self._next_handle
                self._next_handle += 1
                self._handles[e.handle] = h
                handles.append(e.handle)
                if e.name in self._pending_names:
                    # Deferred duplicate: registered but never queued —
                    # completed inline below, after the lock.
                    dup_failed.append((e, h))
                    continue
                self._pending_names[e.name] = e
                self._adm_inflight[e.priority] += 1
                self._adm_bytes[e.priority] += int(e.tensor.nbytes)
                if e.deadline is not None:
                    self._deadline_count += 1
                    self._stall_kick.set()
            adm = list(self._adm_inflight)
            depth = len(self._pending_names)
        record_admission(adm)
        # All N requests count as submitted — the native engine cannot
        # know at submit which will dup-fail at its async fold, so the
        # python twin counts identically to keep the counters parable.
        record_submit_batch(op, [e.tensor.nbytes for e in entries], depth)
        for e, (t0, t1, args) in zip(entries, spans):
            self.timeline.start(e.name, tl.QUEUE, ts_us=t0)
            self.timeline.start(e.name, tl.MEMCPY, ts_us=t0)
            self.timeline.end(e.name, tl.MEMCPY, args, ts_us=t1)
        dup_names = {e.name for e, _ in dup_failed}
        queued = [e for e in entries if e.name not in dup_names]
        numx.engine_note_submit_batch([e.name for e in queued],
                                      [e.tensor for e in queued])
        for e in queued:
            self._queue.put(e)
        for e, h in dup_failed:
            self.timeline.end(e.name, tl.QUEUE,
                              {"batch_n": e.batch_n} if e.batch_n > 1
                              else None)
            tele.REGISTRY.counter("engine.errors").inc()
            e.tensor = _RETIRED
            h.error = DuplicateNameError(
                f"a collective named '{e.name}' is already pending; "
                "names must be unique among in-flight tensors")
            h.event.set()
        self._wake.set()
        return handles

    # -- deadline / cancel / drain plane --------------------------------------

    def cancel(self, handle: int) -> bool:
        """Cooperative cancel. Pre-announce entries retire locally at the
        next cycle without executing; entries already announced to peers
        (or executing) complete cross-rank and DISCARD their result —
        either way ``synchronize`` raises :class:`CancelledError`.
        Returns False when the handle is unknown or already complete."""
        with self._lock:
            h = self._handles.get(handle)
            if h is None or h.event.is_set():
                return False
            for e in self._pending_names.values():
                if e.handle == handle:
                    e.cancelled = True
                    break
            else:
                return False
        self._wake.set()  # retire promptly even on an idle engine
        return True

    def _sweep_deadlines(self):
        """Fail the waiter of every overdue entry with an attributed
        :class:`CollectiveTimeout` naming the phase it is stuck in, plus
        ONE flight dump per sweep. Runs on the loop thread each cycle
        (QUEUE/NEGOTIATE phases) and on the stall watchdog thread (an
        executor call the loop is wedged inside). Zero work when no
        in-flight entry carries a deadline."""
        if not self._deadline_count:
            return
        now = time.monotonic()
        expired = []
        with self._lock:
            for e in self._pending_names.values():
                if (e.deadline is not None and not e.fired
                        and now > e.deadline):
                    e.fired = True
                    expired.append(e)
        if not expired:
            return
        lines = []
        for e in expired:
            age = now - e.enqueued_at
            err = CollectiveTimeout(
                f"collective '{e.name}' exceeded its deadline after "
                f"{age:.2f}s stuck in phase {e.phase} on {_process_str()}"
                " (the request is abandoned; a late completion will be "
                "discarded)")
            tele.REGISTRY.counter("engine.deadline_exceeded").inc()
            self.timeline.instant(e.name, tl.DEADLINE_EXCEEDED,
                                  {"phase": e.phase,
                                   "age_s": round(age, 3)})
            with self._lock:
                h = self._handles.get(e.handle)
            if h is not None and not h.event.is_set():
                h.error = err
                h.event.set()
            lines.append(f"{e.name} (phase {e.phase}, {age:.2f}s)")
        self._dump_flight("collective deadline exceeded: "
                          + ", ".join(lines), kind="deadline")

    def _cull(self, entries):
        """Retire cancelled / deadline-fired entries that have NOT been
        announced to peers yet (local retirement is safe — no peer lists
        them); returns the survivors in order. Announced entries keep
        negotiating/executing and discard their result at completion."""
        live = []
        for e in entries:
            if e.cancelled:
                self._complete(e, None, None)  # -> CancelledError path
            elif e.fired:
                self._complete(e, None, CollectiveTimeout(
                    f"collective '{e.name}' exceeded its deadline in "
                    f"phase {e.phase}"))
            else:
                live.append(e)
        return live

    def quiesce(self, deadline_s: float,
                reason: str = "quiesce requested"):
        """Drain for a graceful exit: close admission (new submits fail
        fast; ``/healthz`` reports ``draining``), complete negotiated
        in-flight work, and report what was drained. Bounded by
        ``deadline_s`` — work wedged behind a dead peer cannot be
        completed, only reported. Reused by elastic shrink and the
        graceful-preemption ladder."""
        with self._lock:
            already = self._quiesced is not None
            if not already:
                self._quiesced = reason

        def _names():
            with self._lock:
                return list(self._pending_names)

        return quiesce_drain(reason, deadline_s, already, _names,
                             self._wake.set,
                             min(self.cycle_time_s, 0.01))

    # -- completion API (reference: handle_manager.cc + mpi_ops_v2.cc poll/
    # wait_and_clear:228-338) -------------------------------------------------

    def poll(self, handle: int) -> bool:
        with self._lock:
            h = self._handles.get(handle)
        if h is None:
            raise EngineError(f"unknown handle {handle}")
        return h.event.is_set()

    def synchronize(self, handle: int) -> np.ndarray:
        with self._lock:
            h = self._handles.get(handle)
        if h is None:
            raise EngineError(f"unknown handle {handle}")
        h.event.wait()
        with self._lock:
            self._handles.pop(handle, None)
        if h.error is not None:
            raise h.error
        # Numerics: a nonfinite reduced result fires the attributed
        # `nonfinite` verdict (and raises under HVD_NUMERICS=halt) —
        # same hook, counters and verdict shape as the native engine's.
        numx.engine_check_result(h.name, h.result)
        return h.result

    # -- background loop (reference: RunLoopOnce, operations.cc:1921-2172) ----

    def _loop(self):
        while not self._shutdown.is_set():
            start = time.monotonic()
            self._run_cycle()
            elapsed = time.monotonic() - start
            # idle-round backoff keeps all-quiet negotiation rounds from
            # hammering the coordination service (identical on every
            # process, so rounds stay in lockstep).
            sleep = self.cycle_time_s - elapsed + self._extra_wait
            self._extra_wait = 0.0
            if sleep > 0:
                self._wake.wait(sleep)
            self._wake.clear()
        # The loop may have built the coordinator after shutdown() checked
        # for one — publish the tombstone here too so peers never wait out
        # the full negotiation timeout on a cleanly exiting process.
        if self._coordinator is not None:
            self._coordinator.close()
        # Fail whatever is left (reference: operations.cc:1833-1848).
        self._drain_with_error(ShutdownError("Horovod engine has been shut down"))

    def _drain(self):
        out = []
        while True:
            try:
                out.append(self._queue.get_nowait())
            except queue.Empty:
                return out

    def _drain_with_error(self, err: Exception):
        entries = self._drain()
        if entries:
            # Work died in the queue (shutdown with requests outstanding,
            # poisoned engine): leave a post-mortem trace of the last N
            # events alongside the error the callers will see.
            self._dump_flight(
                f"drained {len(entries)} pending entr"
                f"{'y' if len(entries) == 1 else 'ies'} with error: {err}")
        for e in entries:
            self._complete(e, None, err)

    def _dump_flight(self, reason: str, kind: Optional[str] = None):
        """Dump the flight recorder (+ telemetry snapshot) — called on
        stalls, failed negotiations, deadline expiries, shutdown-drained
        work and SIGUSR1. ``kind`` tags hang-class dumps ("stall",
        "deadline", "negotiation", "sigusr1"): those embed the per-entry
        inspect table, engage the cross-rank hang doctor
        (core/doctor.py) for an attributed verdict, and key the dump
        rate limit separately so a prior unrelated dump cannot suppress
        a hang post-mortem. Never raises: post-mortem reporting must not
        take the engine down."""
        table = None
        verdict = None
        if kind is not None:
            try:
                table = self.inspect()
            except Exception:
                table = None
            verdict = doctor_on_hang(reason, kind, table,
                                     self.timeline.rank)
        tl.dump_and_warn(self.timeline.recent(), reason,
                         self.timeline.rank, LOG, kind=kind,
                         inspect=table, verdict=verdict)

    def _dump_sigusr1(self, reason: str):
        """SIGUSR1 entry point: an on-demand live-hang post-mortem —
        the dump embeds the inspect table and engages the doctor."""
        self._dump_flight(reason, kind="sigusr1")

    # -- introspection (the hang doctor's raw table) --------------------------

    def inspect(self) -> List[dict]:
        """Full per-entry state of every in-flight tensor — the hang
        doctor's raw table, superseding the bare pending-name list.
        Record shape (``ENGINE_INSPECT_KEYS``) is the cross-engine
        parity contract with ``hvd_engine_inspect``; hvdcheck rule
        ``parity-doctor`` machine-diffs the two writers."""
        c = self._coordinator
        rnd = int(getattr(c, "round", 0)) if c is not None else 0
        now = time.monotonic()
        out = []
        with self._lock:
            for e in self._pending_names.values():
                out.append(dict(
                    name=e.name,
                    op=e.op,
                    phase=e.phase,
                    phase_age_us=int((now - e.phase_since) * 1e6),
                    bytes=int(e.tensor.nbytes),
                    dtype=str(e.tensor.dtype),
                    wire=e.compression,
                    batch_n=int(e.batch_n),
                    priority=PRIORITY_NAMES.get(e.priority, "normal"),
                    deadline_remaining_us=(
                        None if e.deadline is None
                        else int((e.deadline - now) * 1e6)),
                    round=rnd))
        return out

    def admission_summary(self) -> dict:
        """Queue depth + per-class admission state for /healthz, the
        doctor snapshot and the fleet console (shared shape with the
        native engine via :func:`build_admission_summary`)."""
        with self._lock:
            inflight = list(self._adm_inflight)
            nbytes = list(self._adm_bytes)
            depth = len(self._pending_names)
        return build_admission_summary(depth, inflight, nbytes,
                                       self.adm_max_inflight,
                                       self.adm_max_bytes)

    def set_params(self, cycle_time_s: Optional[float] = None,
                   fusion_threshold: Optional[int] = None):
        """Live parameter updates (the autotuner drives this). In a
        negotiated multi-controller world, process 0's values propagate to
        every process through the round params (coordinator.negotiate)."""
        if cycle_time_s is not None and cycle_time_s > 0:
            self.cycle_time_s = cycle_time_s
        if fusion_threshold is not None and fusion_threshold >= 0:
            # Without negotiation, the multi-controller invariant holds
            # even if topology came up after engine construction: fusion
            # stays off.
            self.fusion_threshold = 0 if (
                _multi_controller() and not _negotiated()
            ) else fusion_threshold
        if (self.cache_capacity and _multi_controller()
                and not _negotiated()):
            # The response cache follows fusion's fallback rule: no
            # negotiated rounds, nothing to cache.
            self.cache_capacity = 0
            record_cache_config(0, forced_off=True)
        if self._coordinator is not None:
            self._coordinator.cycle_time_s = self.cycle_time_s
            self._coordinator.fusion_threshold = self.fusion_threshold

    def current_params(self):
        """(cycle_time_s, fusion_threshold) — same surface as the native
        engine's readback."""
        return self.cycle_time_s, self.fusion_threshold

    def _maybe_build_coordinator(self):
        """Lazily stand up negotiation once topology is known (the engine
        may be constructed before hvd.init())."""
        if self._coordinator is not None or self._coord_unavailable:
            return
        if not _multi_controller():
            return
        from horovod_tpu.core import coordinator as coord

        # warn_stalls=False: this engine's own watchdog thread already
        # attributes stalls via coordinator.missing_processes — a second
        # warning from inside negotiate() would be a duplicate.
        self._coordinator = coord.make_coordinator(
            self.cycle_time_s, self.fusion_threshold,
            0.0 if self.stall_check_disabled else self.stall_warning_s,
            warn_stalls=False, cache_capacity=self.cache_capacity)
        if self._coordinator is None:
            # Fall back to the unfused, name-ordered local path for good
            # (the response cache rides the same rule: no rounds to
            # compress).
            self._coord_unavailable = True
            self.fusion_threshold = 0
            if self.cache_capacity:
                self.cache_capacity = 0
                record_cache_config(0, forced_off=True)

    def _negotiated_cycle(self, entries):
        """One negotiation round: agree on batch composition with every
        peer process, then execute exactly the agreed groups (the role of
        the reference's RunLoopOnce negotiation half,
        operations.cc:1921-2172)."""
        from horovod_tpu.core import coordinator as coord

        t_cycle = time.monotonic()
        entries = self._cull(entries)  # cancel/deadline BEFORE announce
        for e in entries:
            # Phase attribution reuses the span vocabulary (the C++
            # sweep spells the same literals — hvdcheck parity-spans).
            record_phase("queue", t_cycle - e.phase_since)
            e.phase = f"NEGOTIATE_{e.op.upper()}"
            e.phase_since = t_cycle
            self.timeline.start(e.name, f"NEGOTIATE_{e.op.upper()}")
        self._negotiating.extend(entries)
        c = self._coordinator
        now = time.monotonic()
        metas = [
            coord.RequestMeta(
                name=e.name, op=e.op, dtype=str(e.tensor.dtype),
                itemsize=e.tensor.dtype.itemsize,
                shape=tuple(e.tensor.shape), average=e.average,
                root_rank=e.root_rank, prescale=e.prescale,
                age_s=now - e.enqueued_at, nbytes=e.tensor.nbytes,
                compression=e.compression,
                compression_dcn=e.compression_dcn,
                priority=e.priority)
            for e in self._negotiating
        ]
        t_neg = time.monotonic()
        try:
            decision = c.negotiate(metas)
            tele.REGISTRY.histogram("engine.negotiation_s").observe(
                time.monotonic() - t_neg)
        except Exception as exc:
            # Both twins raise ShutdownError for every completion after a
            # peer shut down, not just the first batch (the shared
            # predicate rates post-poison re-raises by message text).
            msg = str(exc)
            shutdownish = coord.is_shutdownish(exc)
            err = ShutdownError(msg) if shutdownish else EngineError(msg)
            if not shutdownish:
                # A hung negotiation (timeout, KV failure) is exactly the
                # post-mortem the flight recorder exists for; a clean
                # peer/local shutdown is not. Dump BEFORE failing the
                # round's entries: the doctor diagnoses off the inspect
                # table, so the victims must still be in it (the native
                # twin dumps from the negotiator trampoline before the
                # C++ loop culls — same order).
                self._dump_flight(f"negotiation failed: {msg}",
                                  kind="negotiation")
            for e in self._negotiating:
                self.timeline.end(e.name, f"NEGOTIATE_{e.op.upper()}")
                self._complete(e, None, err)
            self._negotiating.clear()
            return
        if c.clock_ready and not self._clock_synced:
            # The anchor exchange completed: embed rank 0's clock bridge
            # (+ the measured KV round trip) in this rank's trace so the
            # merge tool can align every rank on one time base.
            self._clock_synced = True
            self.timeline.clock_sync(c.clock_offset_us, c.clock_rtt_us)
        self.cycle_time_s = decision.cycle_time_s or self.cycle_time_s
        if decision.fusion_threshold is not None:
            self.fusion_threshold = decision.fusion_threshold
        self._extra_wait = decision.idle_backoff_s
        if c.last_tables:
            # Per-process readiness instants inside the NEGOTIATE_* span
            # (reference: timeline.cc:106-130) — the trace names who was
            # late, not just that negotiation was long.
            for e in self._negotiating:
                for p, names in c.last_tables.items():
                    if p not in e.ready_marked and e.name in names:
                        e.ready_marked.add(p)
                        self.timeline.instant(e.name, tl.RANK_READY,
                                              {"process": p})
        done = set()
        executed_bytes = 0
        # `cached` on the span end: whether the round that RESOLVED this
        # tensor took the response-cache bitvector fast path — the trace
        # CLI attributes fast vs full rounds from it.
        neg_args = {"cached": decision.cached}
        for g in decision.groups:
            ents = [self._negotiating[i] for i in g.indices]
            done.update(g.indices)
            for e in ents:
                self.timeline.end(e.name, f"NEGOTIATE_{e.op.upper()}",
                                  neg_args)
            if g.error:
                for e in ents:
                    self._complete(e, None, EngineError(g.error))
                continue
            executed_bytes += sum(e.tensor.nbytes for e in ents)
            if ents[0].op == "allreduce":
                self._exec_allreduce_batch(ents)
            else:
                for e in ents:
                    self._exec_single(e)
        if done:
            self._negotiating = [e for i, e in enumerate(self._negotiating)
                                 if i not in done]
            record_cycle(time.monotonic() - t_cycle)
        if executed_bytes and self._param_manager is not None:
            self._param_manager.update(executed_bytes)

    def _run_cycle(self):
        t_cycle = time.monotonic()
        self._sweep_deadlines()
        entries = self._drain()
        self._maybe_build_coordinator()
        if self._coordinator is not None:
            self._negotiated_cycle(entries)
            return
        entries = self._cull(entries)  # cancelled/overdue: retire locally
        if len(entries) > 1:
            if _multi_controller():
                # Fallback (negotiation disabled/unavailable): sort each
                # drained cycle by (priority, name) so thread-racy
                # enqueue order within a cycle cannot diverge across
                # processes. Deadline margin is deliberately NOT in this
                # key — it is clock-local and would diverge. This is
                # per-cycle only — drain-boundary skew can still split a
                # batch differently on different processes, so this mode
                # requires a single enqueue thread with identical
                # program order (the negotiated path has no such
                # requirement).
                entries.sort(key=lambda e: (e.priority, e.name))
            else:
                # Single controller: drain in (priority, deadline
                # margin, name) order, so latency-sensitive serving
                # work overtakes bulk training traffic sharing the
                # cycle and tight deadlines run first within a class.
                now = time.monotonic()
                entries.sort(key=lambda e: (
                    e.priority,
                    e.deadline - now if e.deadline is not None
                    else float("inf"),
                    e.name))
        if entries and self._param_manager is not None:
            # One update per engine cycle with that cycle's traffic — the
            # manager's scoring window contract (parameter_manager.cc
            # scores bytes per cycle tick).
            self._param_manager.update(sum(e.tensor.nbytes for e in entries))
        if entries:
            # Fuse allreduces per (priority, dtype, average) in drain
            # order up to the threshold (reference: operations.cc:
            # 2035-2074); other ops run singly in order. Priority joins
            # the key so fused batches stay priority-uniform — a batch
            # is scheduled at its own class, never dragging high-class
            # work behind bulk traffic (or vice versa).
            batch: list[_Entry] = []
            batch_key = None
            batch_bytes = 0
            for e in entries:
                if e.op == "allreduce":
                    key = (e.priority, e.tensor.dtype, e.average,
                           e.compression, e.compression_dcn)
                    if batch and (key != batch_key or
                                  batch_bytes + e.tensor.nbytes > self.fusion_threshold):
                        self._exec_allreduce_batch(batch)
                        batch, batch_bytes = [], 0
                    batch_key = key
                    batch.append(e)
                    batch_bytes += e.tensor.nbytes
                else:
                    if batch:
                        self._exec_allreduce_batch(batch)
                        batch, batch_bytes = [], 0
                    self._exec_single(e)
            if batch:
                self._exec_allreduce_batch(batch)
            record_cycle(time.monotonic() - t_cycle)

    def _emit_exec_spans(self, entries, activity, t0_us):
        """Retro-emit WAIT_FOR_DATA (host→device staging, reference:
        operations.cc:783-807) + the op activity for one executor call.
        The executor measured its own staging time; the split point lands
        between the two spans."""
        t1 = self.timeline.now_us()
        stage_us = int(getattr(self.executor, "last_stage_s", 0.0) * 1e6)
        split = min(t0_us + stage_us, t1)
        for e in entries:
            args = {"dtype": str(e.tensor.dtype),
                    "shape": list(e.tensor.shape)}
            if e.compression not in ("", "none"):
                # Wire-policy attribution, matching the C++ writer's
                # TensorArgs (no arg at full width) — hvdcheck
                # parity-span-args pins the two vocabularies together.
                args["wire"] = e.compression
            if e.compression_dcn not in ("", "none"):
                # Per-tier DCN policy of the hierarchical route; same
                # parity contract as `wire` above.
                args["wire_dcn"] = e.compression_dcn
            if e.priority != PRIORITY_CODES["normal"]:
                # Serving-plane class attribution (no arg for the
                # default class, like the wire policies above).
                args["priority"] = PRIORITY_CLASSES[e.priority]
            self.timeline.start(e.name, tl.WAIT_FOR_DATA, ts_us=t0_us)
            self.timeline.end(e.name, tl.WAIT_FOR_DATA, ts_us=split)
            self.timeline.start(e.name, activity, args, ts_us=split)
            self.timeline.end(e.name, activity, ts_us=t1)

    def _exec_allreduce_batch(self, batch):
        names = [e.name for e in batch]
        fused = len(batch) > 1
        if fused:
            # Fusion-buffer occupancy accounting (reference analogue: the
            # 64 MB fusion buffer, operations.cc:2035-2074).
            tele.REGISTRY.counter("engine.fused.batches").inc()
            tele.REGISTRY.counter("engine.fused.tensors").inc(len(batch))
            tele.REGISTRY.counter("engine.fused.bytes").inc(
                sum(e.tensor.nbytes for e in batch))
        try:
            if fused:
                t_pack = time.monotonic()
                for n in names:
                    self.timeline.start(n, tl.MEMCPY_IN_FUSION_BUFFER)
                dtype = batch[0].tensor.dtype
                if any(e.prescale != 1.0 for e in batch) \
                        and dtype.kind not in "fc":
                    # Degenerate corner (non-unit prescale on an integer
                    # batch): preserve the historical float-promoting
                    # concatenation semantics instead of pooling.
                    flat = np.concatenate(
                        [(e.tensor.reshape(-1) * e.prescale
                          if e.prescale != 1.0 else e.tensor.reshape(-1))
                         for e in batch])
                    pooled_fusion = False
                else:
                    # Pool-checked-out fusion buffer, reused across
                    # cycles (the reference's persistent fusion buffer,
                    # operations.cc:2035-2074).
                    flat, pooled_fusion = self.pool.checkout_tracked(
                        sum(e.tensor.size for e in batch), dtype)
                    off = 0
                    for e in batch:
                        n_ = e.tensor.size
                        src = e.tensor.reshape(-1)
                        if e.prescale != 1.0:
                            np.multiply(src, e.prescale,
                                        out=flat[off: off + n_])
                        else:
                            flat[off: off + n_] = src
                        off += n_
                record_phase("memcpy", time.monotonic() - t_pack)
                pool_args = {"pooled": pooled_fusion}
                for n in names:
                    self.timeline.end(n, tl.MEMCPY_IN_FUSION_BUFFER,
                                      pool_args)
            else:
                flat = batch[0].tensor.reshape(-1)
                if batch[0].prescale != 1.0:
                    flat = flat * batch[0].prescale
            t0 = self.timeline.now_us()
            t_exec = time.monotonic()
            for e in batch:
                record_phase(_phase_class(e.phase), t_exec - e.phase_since)
                e.phase = tl.ALLREDUCE  # deadline attribution: executing
                e.phase_since = t_exec
            # Wire policy rides an executor attribute, not a parameter,
            # so custom test executors with the historical two-arg
            # signature keep working (batches are policy-uniform — the
            # fusion key and the coordinator's grouping include it).
            self.executor.wire_policy = batch[0].compression
            self.executor.wire_policy_dcn = batch[0].compression_dcn
            out = self.executor.allreduce(flat, batch[0].average)
            # Release the fusion input before any completion wakes a
            # waiter: the caller's next cycle must find the slab free
            # (unless a test executor returned the input aliased as
            # output, in which case `out` legitimately pins it).
            flat = None
            record_wire(self.executor)
            self._emit_exec_spans(batch, tl.ALLREDUCE, t0)
            off = 0
            for e in batch:
                n = e.tensor.size
                if fused:
                    self.timeline.start(e.name, tl.MEMCPY_OUT_FUSION_BUFFER)
                result = out[off: off + n].reshape(e.tensor.shape)
                if fused:
                    self.timeline.end(e.name, tl.MEMCPY_OUT_FUSION_BUFFER,
                                      pool_args)
                self._complete(e, result, None)
                off += n
        except Exception as exc:  # surfaced at synchronize()
            for e in batch:
                self._complete(e, None, EngineError(str(exc)))

    def _exec_single(self, e: _Entry):
        try:
            t0 = self.timeline.now_us()
            t_exec = time.monotonic()
            record_phase(_phase_class(e.phase), t_exec - e.phase_since)
            e.phase = e.op.upper()  # deadline attribution: executing
            e.phase_since = t_exec
            if e.op == "allgather":
                out = self.executor.allgather(e.tensor)
                record_wire(self.executor)
                self._emit_exec_spans([e], tl.ALLGATHER, t0)
            elif e.op == "broadcast":
                out = self.executor.broadcast(e.tensor, e.root_rank)
                record_wire(self.executor)
                self._emit_exec_spans([e], tl.BROADCAST, t0)
            else:
                raise EngineError(f"unknown op {e.op}")
            self._complete(e, out, None)
        except Exception as exc:
            self._complete(e, None, EngineError(str(exc)))

    def _complete(self, e: _Entry, result, err: Optional[Exception]):
        now = time.monotonic()
        record_phase(_phase_class(e.phase), now - e.phase_since)
        record_complete_latency(
            e.op, now - e.enqueued_at,
            None if e.deadline is None else e.deadline - now,
            e.priority)
        if e.cancelled and err is None:
            # Cooperative cancel: the result (if the entry executed —
            # post-agreement cancels complete cross-rank) is DISCARDED
            # and the waiter sees CancelledError. Span + counter are the
            # cross-engine parity surface (CANCELLED / engine.cancelled).
            self.timeline.start(e.name, tl.CANCELLED)
            self.timeline.end(e.name, tl.CANCELLED)
            tele.REGISTRY.counter("engine.cancelled").inc()
            result, err = None, CancelledError(
                f"collective '{e.name}' was cancelled (cooperative "
                "cancel; result discarded)")
        self.timeline.end(
            e.name, tl.QUEUE,
            {"batch_n": e.batch_n} if e.batch_n > 1 else None)
        with self._lock:
            self._pending_names.pop(e.name, None)
            if e.deadline is not None and self._deadline_count > 0:
                self._deadline_count -= 1
            if self._adm_inflight[e.priority] > 0:
                self._adm_inflight[e.priority] -= 1
            self._adm_bytes[e.priority] = max(
                0, self._adm_bytes[e.priority] - int(e.tensor.nbytes))
            adm = list(self._adm_inflight)
            depth = len(self._pending_names)
            h = self._handles.get(e.handle)
        tele.REGISTRY.counter(
            "engine.errors" if err is not None else "engine.completed").inc()
        tele.REGISTRY.gauge("engine.queue_depth").set(depth)
        record_admission(adm)
        # Release the snapshot slab BEFORE waking the waiter: the cycle
        # loop's local batch list is the last engine-side reference, and
        # a submit-then-wait caller's next enqueue must find the slab
        # free, not race the loop thread for it.
        e.tensor = _RETIRED
        if h is not None and not h.event.is_set():
            # A deadline-fired handle was already released with its
            # attributed CollectiveTimeout — a late completion (the
            # wedged executor finally returning) must not clobber it.
            h.result = result
            h.error = err
            h.event.set()

    def _stall_loop(self):
        interval = max(self.stall_warning_s / 5.0, 0.01)
        while not self._shutdown.is_set():
            # Deadline enforcement for entries the LOOP thread cannot
            # reach (wedged inside an executor call): tighten the tick
            # while any in-flight entry carries a deadline, so an
            # exec-stuck collective fails its waiter promptly and not on
            # the (much coarser) stall-warning cadence. The kick breaks
            # an already-started coarse sleep the moment a deadline'd
            # entry is submitted.
            tick = min(interval, 0.05) if self._deadline_count else interval
            if self._stall_kick.wait(tick):
                self._stall_kick.clear()
            if self._shutdown.is_set():
                return
            self._sweep_deadlines()
            self._check_stalls()

    def _check_stalls(self):
        """Warn about tensors stuck in the table (reference:
        CheckForStalledTensors, operations.cc:1535-1581)."""
        if self.stall_check_disabled:
            return
        now = time.monotonic()
        if now - self._last_stall_warn < self.stall_warning_s:
            return
        with self._lock:
            stalled = [
                (n, now - e.enqueued_at)
                for n, e in self._pending_names.items()
                if now - e.enqueued_at > self.stall_warning_s
            ]
        if stalled:
            self._last_stall_warn = now
            c = self._coordinator

            def _fmt(n, age):
                # Name the processes holding this tensor up (reference:
                # CheckForStalledTensors, operations.cc:1535-1581).
                if c is not None and c.last_tables:
                    missing = c.missing_processes(n)
                    if missing:
                        from horovod_tpu.core import coordinator as coord

                        line = (f"{n} ({int(age)}s; missing from "
                                f"process(es): "
                                f"{', '.join(map(str, missing))})")
                        # Unresolvable-divergence diagnosis (same family,
                        # different sequence number on a peer).
                        return line + (coord.divergence_hint(c, n) or "")
                return f"{n} ({int(age)}s)"

            names = ", ".join(_fmt(n, age) for n, age in stalled)
            if c is not None and c.waiting_on is not None:
                names += (f" [negotiation is blocked waiting for process "
                          f"{c.waiting_on}]")
            # Same registry the straggler report reads: name the rank
            # with the largest cumulative imposed wait so far.
            worst = tele.STRAGGLERS.worst_line()
            if worst:
                names += " " + worst
            LOG.warning(
                "One or more tensors were submitted to be reduced/gathered/"
                "broadcast but have not completed for over %ds: %s",
                int(self.stall_warning_s), names,
            )
            # Post-mortem: the stalled world's last N events + telemetry,
            # dumped while the dispatch thread may itself be hung.
            self._dump_flight(f"stalled tensors: {names}", kind="stall")
            # The performance sentinel folds the stall into /healthz and
            # into the next watchdog verdict's attribution.
            try:
                from horovod_tpu.core import sentinel as _sentinel

                _sentinel.note_stall(f"stalled tensors: {names}",
                                     self.timeline.rank)
            except Exception:
                pass

    def abandon(self):
        """Elastic teardown of a WEDGED engine (core/elastic.py): the
        coordination KV host died and blocked KV RPCs never return, so
        :meth:`shutdown`'s thread join would hang forever. Fail the
        outstanding handles, poison the coordinator WITHOUT publishing
        (a tombstone set would wedge too), and leave the loop thread
        parked inside the dead service — the caller parks this object
        so nothing it references is ever destroyed."""
        c = self._coordinator
        if c is not None:
            c.dead = c.dead or "engine abandoned (elastic reconfiguration)"
            c._closed = True  # a blocked read aborts IF it ever returns
        # Pool hygiene: the parked loop thread may still hold checked-out
        # slabs (it is wedged inside the dead backend) — poison the pool
        # so none of them can ever be handed to a later checkout. The
        # successor engine builds a fresh pool.
        self.pool.poison()
        self._shutdown.set()
        self._wake.set()
        self._stall_kick.set()
        with self._lock:
            handles = list(self._handles.values())
            self._handles.clear()
            self._pending_names.clear()
            self._adm_inflight = [0] * len(PRIORITY_CLASSES)
            self._adm_bytes = [0] * len(PRIORITY_CLASSES)
        for h in handles:
            if not h.event.is_set():
                h.error = ShutdownError(
                    "engine abandoned: coordination KV plane lost")
                h.event.set()
        self.timeline.close()
        tl.uninstall_sigusr1(self._dump_sigusr1)

    def shutdown(self):
        # Publish the shutdown tombstone first: peers blocked mid-round on
        # our next message discover it and surface ShutdownError instead
        # of hanging (reference: shutdown propagation via the coordinator,
        # operations.cc:2008-2011).
        if self._coordinator is not None:
            self._coordinator.close()
        self._shutdown.set()
        self._wake.set()  # break an idle sleep immediately
        self._stall_kick.set()
        self._thread.join(timeout=5)
        # If the loop thread was inside _maybe_build_coordinator when the
        # check above ran, the coordinator exists only now. Close it again:
        # a blocked negotiate() aborts at its next poll slice once _closed
        # is set (close() is idempotent), and the tombstone is published.
        if self._coordinator is not None:
            self._coordinator.close()
        with self._lock:
            handles = list(self._handles.values())
            self._handles.clear()
            self._pending_names.clear()
            self._adm_inflight = [0] * len(PRIORITY_CLASSES)
            self._adm_bytes = [0] * len(PRIORITY_CLASSES)
        for h in handles:
            if not h.event.is_set():
                h.error = ShutdownError("Horovod engine has been shut down")
                h.event.set()
        self.timeline.close()
        # A later SIGUSR1 must dump a LIVE engine's ring, not this dead
        # one's — and the module-global handler state must not pin us.
        tl.uninstall_sigusr1(self._dump_sigusr1)


_engine: Optional[Engine] = None
_engine_lock = threading.Lock()


def _make_engine():
    """HVD_ENGINE selects the implementation: 'native' (default — the C++
    libhvdcore scheduler) or 'python' (this module's reference engine).
    Falls back to Python if the native build is unavailable."""
    choice = os.environ.get("HVD_ENGINE", "native").lower()
    if choice == "native":
        try:
            from horovod_tpu.core.native_engine import NativeEngine

            return NativeEngine()
        except Exception as exc:  # no toolchain — degrade, loudly
            LOG.warning("native engine unavailable (%s); "
                        "falling back to the python engine", exc)
    return Engine()


def get_engine():
    global _engine
    with _engine_lock:
        if _engine is None:
            _engine = _make_engine()
        return _engine


def shutdown_engine():
    global _engine
    with _engine_lock:
        if _engine is not None:
            _engine.shutdown()
            _engine = None


def quiesce_drain(reason: str, deadline_s: float, already: bool,
                  pending_names, wake, tick_s: float):
    """The quiesce policy BOTH engines share (core/native_engine.py
    calls this too): mark the process draining, bounded-drain until the
    in-flight table empties, and report NAMES. The report shape — name
    lists, not counts — and the draining marker/gauge/log wording are
    part of the engines' same-observable-semantics contract, so they
    live in exactly one place. ``pending_names`` is each engine's view
    of its in-flight table; ``wake`` nudges an idle loop (a no-op for
    the C++ engine, whose loop ticks on its own)."""

    def _shed_level() -> int:
        # Work leaving the table WITHOUT completing (deadline expiry,
        # cooperative cancel, admission shed) — sampled before/after
        # the drain window so the report splits shed from drained.
        # flat_counters() runs the registry syncs, so the native
        # engine's stats fold in before each sample.
        flat = tele.REGISTRY.flat_counters()
        return int(flat.get("engine.deadline_exceeded", 0)
                   + flat.get("engine.cancelled", 0)
                   + flat.get("engine.admission.shed", 0))

    shed0 = _shed_level()
    before = pending_names()
    tele.REGISTRY.gauge("engine.draining").set(1)
    try:
        from horovod_tpu.core import sentinel as _sentinel

        _sentinel.note_draining(reason)
    except Exception:
        pass
    deadline = time.monotonic() + max(0.0, deadline_s)
    pending = before
    while pending and time.monotonic() < deadline:
        wake()
        time.sleep(tick_s)
        pending = pending_names()
    drained = [n for n in before if n not in pending]
    report = dict(reason=reason, drained=drained,
                  still_pending=pending,
                  deadline_hit=bool(pending), already=already,
                  shed=max(0, _shed_level() - shed0))
    if pending:
        LOG.warning(
            "engine quiesce: drained %d of %d in-flight collective(s)"
            " within %.1fs; still pending: %s", len(drained),
            len(before), deadline_s, ", ".join(pending))
    else:
        LOG.info("engine quiesce: drained %d in-flight collective(s);"
                 " admission closed (%s)", len(drained), reason)
    return report


def quiesce_engine(deadline_s: float,
                   reason: str = "quiesce requested"):
    """Quiesce the engine singleton if one exists: close admission,
    drain in-flight work within ``deadline_s``, report what drained.
    Returns the report dict, or None when no engine was ever built.
    Reused by elastic shrink (a bounded politeness drain before the
    teardown) and the graceful-preemption ladder."""
    with _engine_lock:
        e = _engine
    if e is None:
        return None
    try:
        return e.quiesce(deadline_s, reason=reason)
    except Exception:
        LOG.warning("engine quiesce failed", exc_info=True)
        return None


def admission_summary():
    """Admission/saturation snapshot of the engine singleton, or None
    when no engine was ever built — the /healthz serving-plane body
    (queue depth, per-class in-flight vs budgets, tripped class)."""
    with _engine_lock:
        e = _engine
    if e is None:
        return None
    try:
        return e.admission_summary()
    except Exception:
        LOG.debug("admission summary failed", exc_info=True)
        return None


def abandon_engine():
    """Drop the engine singleton WITHOUT joining its threads — for
    elastic reconfiguration after the coordination KV plane died, where
    a blocked negotiation RPC never returns and a normal shutdown would
    hang on the join. Returns the abandoned engine so the caller can
    PARK it (its trampolines/threads must outlive the abandonment), or
    None when no engine existed."""
    global _engine
    with _engine_lock:
        e, _engine = _engine, None
    if e is None:
        return None
    try:
        e.abandon()
    except Exception:
        LOG.warning("engine abandon failed", exc_info=True)
    return e
