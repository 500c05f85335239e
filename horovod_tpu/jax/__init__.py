"""JAX frontend — the flagship (reference: horovod/tensorflow/__init__.py).

The reference wraps TF optimizers so each gradient is allreduced through the
background engine at session-run time. On TPU the idiomatic design compiles
gradient reduction *into* the training step: :func:`DistributedOptimizer`
wraps an optax transform whose ``update`` fuses all gradients into per-dtype
buffers and allreduces them with one XLA collective each, and
:func:`jit` compiles the user's step over the world mesh so those collectives
ride ICI. All verbs also work eagerly for host-side code.
"""

from __future__ import annotations

import pickle
import time as _time
from typing import Any, Callable, Optional

import jax as _jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map as _shard_map

from horovod_tpu.common import phases as _phases
from horovod_tpu.common.topology import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    size,
    rank,
    local_size,
    local_rank,
    cross_size,
    cross_rank,
    num_processes,
    process_index,
    mesh,
    devices,
    mpi_threads_supported,
)
from horovod_tpu.ops import collectives as _C
from horovod_tpu.ops.collectives import (  # noqa: F401
    HVD_AXIS,
    axis_rank,
    allgather,
    broadcast,
    reducescatter,
    alltoall,
    broadcast_pytree,
    fetch,
    grouped_allreduce,
)
from horovod_tpu.jax import compression as _compression
from horovod_tpu.jax import quantize as _quantize
from horovod_tpu.jax.compression import Compression, Compressor  # noqa: F401
from horovod_tpu.jax.fused import (  # noqa: F401
    canonical_state_dtype,
    cast_resident_params,
    fuse,
    state_storage,
)
from horovod_tpu.jax.sharded import (  # noqa: F401
    has_master_shards,
    resident_from_masters,
    shard_update,
    sharded_state_specs,
    unwrap_error_feedback,
)
from horovod_tpu.jax import mpi_ops  # noqa: F401  — engine-path async
# verbs (allreduce_async/synchronize/... with zero-copy donate=True)
from horovod_tpu.core import compile_log as _clog
from horovod_tpu.core import numerics as _num
from horovod_tpu.core import sentinel as _sentinel
from horovod_tpu.core import telemetry as _tele
from horovod_tpu.jax import numerics as _jnum

try:
    from jax.experimental import sparse as _jsparse

    _BCOO = _jsparse.BCOO
except Exception:  # pragma: no cover
    _jsparse = None
    _BCOO = ()


# ---------------------------------------------------------------------------
# allreduce with compression + sparse path
# ---------------------------------------------------------------------------

def _is_sparse(x) -> bool:
    return _jsparse is not None and isinstance(x, _BCOO)


def allreduce(
    tensor,
    average: bool = True,
    name: Optional[str] = None,
    compression=Compression.none,
    sparse_as_dense: bool = False,
):
    """Allreduce with optional wire compression and a sparse path.

    Sparse (BCOO) tensors are summed by allgathering values+indices —
    duplicate indices sum implicitly, exactly the reference's
    IndexedSlices→allgather strategy (reference:
    horovod/tensorflow/__init__.py:73-84). ``sparse_as_dense`` densifies
    first (reference: :184-203).

    ``compression`` accepts cast compressors (wrap the psum), quantized
    block-scaled policies (``Compression.int8``/``fp8`` — the collective
    itself changes shape: quantize → int8 reduce-scatter phase →
    dequantize-accumulate → requantize → int8 all-gather, see
    :mod:`horovod_tpu.jax.quantize`; this stateless surface carries no
    error-feedback residual), and ``Compression.select(...)`` per-tensor
    containers resolved by ``name``.
    """
    compression = _compression.for_tensor(compression, name)
    if _is_sparse(tensor):
        if sparse_as_dense:
            return allreduce(tensor.todense(), average, name, compression)
        data = allgather(tensor.data)
        indices = allgather(tensor.indices)
        if average:
            with _phases.phase("hvd_unpack"):
                data = data / _world_size_like(data)
        return _BCOO((data, indices), shape=tensor.shape)
    if _C._topo._require_init().size == 1:
        # Single-rank world: the reduction is identity; skip the wire
        # compression round trip too (it would be a lossy cast — or a
        # lossy quantize/dequantize — for nothing; the reference
        # likewise short-circuits size 1).
        out = jnp.asarray(tensor)
        if not _C.in_spmd(out):  # tracers: trace-time, not per-step
            _C._record_eager("allreduce", out, elided=True)
        return out
    if getattr(compression, "quantized", False):
        if jnp.issubdtype(jnp.result_type(tensor), jnp.floating):
            if _C.in_spmd(tensor):
                ax = _C.rank_axes()
                if ax is None:
                    _C._require_axis("allreduce")
                if (isinstance(ax, tuple)
                        and _C.hierarchical_allreduce_enabled()):
                    # Two-tier composition: the ICI phase reduce-scatters
                    # at the resident dtype and ONLY the 1/L shard
                    # crosses the DCN tier block-scaled (payload+scales)
                    # — the quantized wire applied where the bytes hurt.
                    from horovod_tpu.parallel.hierarchical import (
                        hierarchical_allreduce as _hier_ar,
                    )

                    return _hier_ar(tensor, average=average,
                                    dcn_policy=compression)
                return _quantize.spmd_allreduce(tensor, ax, average,
                                                compression)
            _C._record_eager("allreduce", jnp.asarray(tensor))
            return _quantize.eager_allreduce(tensor, average, compression)
        # Non-float payloads have no quantized form: ship full width
        # (the engine data plane makes the same call) instead of
        # tripping the quantized compressor's deliberate
        # NotImplementedError.
        return _C.allreduce(tensor, average=average, name=name)
    with _phases.phase("hvd_pack"):
        tensor, ctx = compression.compress(tensor)
    out = _C.allreduce(tensor, average=average, name=name)
    with _phases.phase("hvd_unpack"):
        return compression.decompress(out, ctx)


def _world_size_like(x):
    st = _C._topo._require_init()
    return jnp.asarray(st.size, x.dtype) if not isinstance(x, _jax.core.Tracer) else st.size


def allreduce_pytree(tree, average: bool = True, compression=Compression.none,
                     sparse_as_dense: bool = False):
    """Allreduce over a pytree with per-leaf compression. Dense leaves go
    through :func:`horovod_tpu.ops.collectives.grouped_allreduce`: inside a
    traced step the small ones (under ``FUSION_THRESHOLD_ELEMS``) share a
    per-dtype flat buffer — the compile-time analogue of the reference's
    64 MB fusion buffer (reference: operations.cc:2035-2074) — and every
    larger leaf is compressed, reduced and decompressed as itself; eager
    calls pack every leaf. A quantized policy is a pipeline over a flat
    buffer and still packs the whole tree per dtype."""
    if _C._topo._require_init().size == 1:
        # Identity at world size 1 — per-leaf allreduce (which itself
        # short-circuits before the compression round trip) elides the
        # per-dtype concatenate -> all-reduce -> slice chain that XLA
        # does NOT simplify away (a full extra HBM round trip of the
        # gradient tree per step on a one-chip bench; docs/benchmarks.md
        # "HBM diet") while keeping the N>1 leaf semantics: dense leaves
        # become jax arrays, sparse leaves densify under sparse_as_dense.
        leaves, treedef = _jax.tree_util.tree_flatten(tree)
        return _jax.tree_util.tree_unflatten(
            treedef, [allreduce(l, average, None, compression,
                                sparse_as_dense) for l in leaves])
    leaves, treedef = _jax.tree_util.tree_flatten(tree)
    dense_idx, sparse_idx = [], []
    for i, l in enumerate(leaves):
        (sparse_idx if _is_sparse(l) else dense_idx).append(i)
    out = list(leaves)
    if dense_idx and getattr(compression, "quantized", False):
        # Quantized policy: fuse per dtype as usual, then run the
        # quantized collective pipeline on each flat buffer (the policy
        # replaces the collective, it does not wrap it).
        reduced = _C._grouped_apply(
            lambda flat: allreduce(flat, average, None, compression),
            [leaves[i] for i in dense_idx])
        for i, r in zip(dense_idx, reduced):
            out[i] = r
    elif dense_idx:
        with _phases.phase("hvd_pack"):
            comp = [compression.compress(leaves[i]) for i in dense_idx]
        reduced = _C.grouped_allreduce([c[0] for c in comp], average=average)
        with _phases.phase("hvd_unpack"):
            for i, r, (_, ctx) in zip(dense_idx, reduced, comp):
                out[i] = compression.decompress(r, ctx)
    for i in sparse_idx:
        out[i] = allreduce(leaves[i], average, None, compression, sparse_as_dense)
    return _jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Parameter/state sync (reference §3.4 startup broadcast)
# ---------------------------------------------------------------------------

def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a parameter pytree from ``root_rank`` (reference:
    horovod/tensorflow/__init__.py:96-115 broadcast_global_variables,
    horovod/torch/__init__.py:185-214)."""
    # The span is the host's seconds: packing, the collective's compile on
    # a first call and its dispatch. The device finishes behind it. The
    # bytes are ``eager.broadcast.bytes``.
    with _clog.LOG.span("hvd.broadcast_parameters"):
        return broadcast_pytree(params, root_rank=root_rank)


# TF-compat alias: in JAX variables are explicit, so this takes the pytree.
broadcast_global_variables = broadcast_parameters


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast optax optimizer state (reference:
    horovod/torch/__init__.py:217-333 — the reference must tensor-ize
    scalar hyperparameters; optax states are already pytrees of arrays, so
    this is the same fused broadcast)."""
    return broadcast_pytree(opt_state, root_rank=root_rank)


def broadcast_object(obj: Any, root_rank: int = 0) -> Any:
    """Broadcast an arbitrary picklable object (rank-0 config, epoch
    counters — the reference examples hand-roll this with scalar bcasts,
    e.g. examples/pytorch_imagenet_resnet50.py:70-80)."""
    st = _C._topo._require_init()
    if st.num_processes == 1:
        # Single controller: every rank already holds the same host object.
        _check = _C._check_root(root_rank)
        return obj
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    # Phase 1: root broadcasts the byte length (same shape on every rank).
    n = int(np.asarray(
        _C.broadcast(jnp.asarray([payload.size], jnp.int32), root_rank)
    )[0])
    # Phase 2: pad/crop to root's length and broadcast the bytes.
    buf = np.zeros((n,), np.uint8)
    buf[: min(n, payload.size)] = payload[:n]
    out = np.asarray(_C.broadcast(jnp.asarray(buf), root_rank))
    return pickle.loads(out.tobytes())


# ---------------------------------------------------------------------------
# DistributedOptimizer / gradient transforms
# ---------------------------------------------------------------------------

# One zero tree per (structure, shapes, dtypes, shardings): the
# accumulation skip path must not allocate-and-write a fresh param-sized
# zero tree every non-boundary microstep (it returns the SAME buffers
# each time — the updates contract only promises values, not fresh
# arrays). Bounded: param-sized device buffers must not outlive a shape
# sweep, so old structures are evicted FIFO.
_ZERO_TREES: dict = {}
_ZERO_TREES_MAX = 8


def _zeros_like_in(dtype):
    """``zeros_like`` honoring a ``state_dtype`` policy: float leaves
    get ``dtype`` zeros instead of their own width, so the gradient
    accumulator cannot silently park a full-width f32 buffer in HBM
    (``acc_init``; f32 grads can't promote it — ``acc_update`` casts
    the sum back)."""
    if dtype is None:
        return jnp.zeros_like

    def one(leaf):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            return jnp.zeros(jnp.shape(leaf), dtype)
        return jnp.zeros_like(leaf)

    return one


def _cached_zero_tree(tree):
    leaves, treedef = _jax.tree_util.tree_flatten(tree)
    if any(isinstance(l, _jax.core.Tracer) for l in leaves):
        # Traced (the lax.cond branch): zeros_like stays a broadcast-of-0
        # — XLA's cheapest form, fusable into the consuming add. A cached
        # concrete tree here would bake a param-sized CONSTANT into the
        # executable instead.
        return _jax.tree.map(jnp.zeros_like, tree)
    key = (treedef,
           tuple((jnp.shape(l), str(jnp.result_type(l)),
                  str(getattr(l, "sharding", None)))
                 for l in leaves))
    z = _ZERO_TREES.get(key)
    if z is None:
        while len(_ZERO_TREES) >= _ZERO_TREES_MAX:
            _ZERO_TREES.pop(next(iter(_ZERO_TREES)))
        z = _ZERO_TREES[key] = _jax.tree.map(jnp.zeros_like, tree)
    return z

def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    name: Optional[str] = None,
    average: bool = True,
    compression=Compression.none,
    sparse_as_dense: bool = False,
    backward_passes_per_step: int = 1,
    fused_update: bool = False,
    sharded_update: bool = False,
    state_dtype=None,
):
    """Wrap an optax transform so gradients are allreduced (fused, with
    compression) before the update (reference: horovod/tensorflow/
    __init__.py:152-250 DistributedOptimizer overriding compute_gradients;
    accumulation mirrors torch's backward_passes_per_step,
    horovod/torch/__init__.py:66-78).

    ``fused_update=True`` additionally runs the *update itself* on
    per-dtype fused buffers (:func:`horovod_tpu.jax.fuse`): ~N tiny
    per-parameter XLA fusions collapse into a couple of large ones —
    worth ~20% of a ResNet-50 step on TPU. Valid for elementwise
    transforms (sgd/momentum/adam/...); keep it off for shape-dependent
    ones (adafactor, LARS).

    ``sharded_update=True`` replaces allreduce + replicated update with
    reduce-scatter -> update a 1/N shard of params/state -> all-gather
    (:func:`horovod_tpu.jax.shard_update`; arxiv 2004.13336): per-chip
    optimizer-state HBM read/write drops by ~(N-1)/N. The optimizer
    state becomes per-dtype flat buffers padded to a world-size multiple
    — lay them out ``P('hvd')`` in the compiled step via
    :func:`sharded_state_specs`. Subsumes ``fused_update`` (the whole
    tree is packed); valid for per-coordinate transforms ONLY (a
    shard-local ``clip_by_global_norm`` would be wrong — see
    sharded.py).

    ``state_dtype='bf16'`` (HBM diet round 2, arxiv 2004.13336 §4 +
    1909.09756) keeps the resident state in the reduced dtype: with
    ``sharded_update`` the params/opt-state live in bf16 HBM and f32
    master weights exist only as each chip's 1/N shard
    (:func:`horovod_tpu.jax.shard_update`); on the fused/plain paths the
    optimizer state is *stored* reduced and *computed* f32
    (:func:`horovod_tpu.jax.state_storage` — no masters: see
    docs/troubleshooting.md on drift). Cast your resident params to the
    policy dtype before ``init`` (the Trainer and bench wiring do).

    ``compression`` accepts a registry name (``'int8'``, ``'int8_ef'``,
    ``'fp8'``, ``'bf16'``, ...) or a compressor; unknown spellings fail
    FAST here, naming the rank (a bad object used to surface as an
    attribute error mid-step). Quantized policies change the collective
    shape (quantize → int8 reduce-scatter phase → dequantize-accumulate
    → requantize → int8 all-gather); ``int8_ef``'s error-feedback
    residual needs the optimizer-state carrier, so it requires
    ``sharded_update=True`` (the stateless paths run ``int8``/``fp8``
    without a residual)."""
    compression = Compression.resolve(compression)
    _sdt = canonical_state_dtype(state_dtype)
    if (getattr(compression, "quantized", False)
            and compression.error_feedback and not sharded_update):
        raise ValueError(
            "Compression.int8_ef needs an optimizer-state carrier for "
            "its error-feedback residual: use sharded_update=True, or "
            "pick Compression.int8 (no residual) for the plain path")
    if sharded_update:
        if backward_passes_per_step > 1:
            # The accumulation wrapper's state ({'inner', 'acc', 'count'})
            # interleaves param-structured accumulators with the sharded
            # flat buffers — sharded_state_specs cannot tell them apart,
            # so a divisible-sized accumulator would silently ride
            # P('hvd') and shard a buffer every rank needs whole.
            raise ValueError(
                "sharded_update does not compose with "
                "backward_passes_per_step > 1: accumulate before the "
                "optimizer, or use fused_update")
        # Reduction happens inside the wrapper (reduce-scatter on the
        # packed buffers), so there is no separate allreduce here.
        optimizer = shard_update(optimizer, average=average,
                                 compression=compression,
                                 state_dtype=_sdt)
        update = optimizer.update
    else:
        if fused_update:
            optimizer = fuse(optimizer, state_dtype=_sdt)
        elif _sdt is not None:
            # Unfused path: no packing, but the state storage policy
            # still applies (m/v stored reduced, computed f32).
            optimizer = state_storage(optimizer, _sdt)

        # In-step gradient health (core/numerics.py) is computed on the
        # REDUCED gradients this closure already holds — but not under
        # the accumulation wrapper: its lax.cond would trap the stashed
        # tracers inside a branch (the Trainer falls back to local-grad
        # health there).
        in_acc = backward_passes_per_step > 1

        def update(grads, state, params=None, **kwargs):
            pol = "off" if in_acc else _num.policy()
            local = grads
            grads = allreduce_pytree(
                grads, average=average, compression=compression,
                sparse_as_dense=sparse_as_dense,
            )
            if pol == "off":
                with _phases.phase("hvd_optimizer"):
                    return optimizer.update(grads, state, params, **kwargs)
            leaves = _jax.tree_util.tree_leaves(grads)
            ax = (_C.rank_axes()
                  if leaves and _C.in_spmd(leaves[0]) else None)
            # Reduced grads are already global (identical on every
            # rank): their stats need no psum. NaN/Inf from ANY rank
            # survives the reduction, so the nonfinite counts see it;
            # the per-rank vector (pre-reduction local counts,
            # all_gathered) names the offender.
            with _phases.phase("hvd_numerics"):
                stats = _jnum.tree_stats(grads)
                per_rank = (_jnum.per_rank_nonfinite(local, ax)
                            if ax is not None else None)
            with _phases.phase("hvd_optimizer"):
                upd, new_state = optimizer.update(grads, state, params,
                                                  **kwargs)
            with _phases.phase("hvd_numerics"):
                if pol == "halt":
                    finite = _jnum.all_finite(stats)
                    upd = _jnum.guard_updates(finite, upd)
                    new_state = _jnum.guard_state(finite, new_state,
                                                  state)
                health = _jnum.health_of(stats, per_rank)
            if leaves and _C.in_spmd(leaves[0]):
                _jnum.stash_traced(health)
            else:
                _num.note_step_health(
                    _jax.device_get(health), origin="eager")
            return upd, new_state

    if backward_passes_per_step <= 1:
        return optax.GradientTransformationExtraArgs(optimizer.init, update)

    # Accumulate locally; the collective and inner update fire only on step
    # boundaries (reference: torch/__init__.py:66-78). Hand-rolled rather
    # than optax.MultiSteps: its lax.cond would trace our collective outside
    # the 'hvd' axis in eager use; here the branch is Python when eager and
    # lax.cond when traced (all ranks hold the same count, so the branch is
    # uniform across the mesh).
    k = backward_passes_per_step

    def acc_init(params):
        return {
            "inner": optimizer.init(params),
            # Accumulators honor state_dtype (a skipped microbatch must
            # not park a full-width f32 gradient tree in HBM).
            "acc": _jax.tree.map(_zeros_like_in(_sdt), params),
            "count": jnp.zeros((), jnp.int32),
        }

    def acc_update(grads, state, params=None, **kwargs):
        # Cast the sum back to the accumulator dtype: a wider grad leaf
        # (f32 grads under a bf16 policy) would otherwise promote the
        # accumulator and change the state structure mid-training.
        acc = _jax.tree.map(lambda a, g: (a + g).astype(a.dtype),
                            state["acc"], grads)
        count = state["count"] + 1

        def apply_fn(operand):
            acc_, inner_ = operand
            mean = _jax.tree.map(lambda a: a / k, acc_)
            upd, new_inner = update(mean, inner_, params, **kwargs)
            return upd, {
                "inner": new_inner,
                "acc": _jax.tree.map(jnp.zeros_like, acc_),
                "count": jnp.zeros((), jnp.int32),
            }

        def skip_fn(operand):
            acc_, inner_ = operand
            # The skip branch's zeros must type-match the apply branch's
            # updates. Under the policy those follow the PARAM width when
            # params ride along (state_storage casts them there) and the
            # ACCUMULATOR width otherwise (the mean state_storage's
            # grad-width rule sees IS the policy-dtype accumulator — raw
            # f32 grads would mismatch). Deriving from params (not
            # forcing the policy dtype) keeps the bf16 diet for
            # compliant callers — residents ARE the policy width — while
            # an uncast-f32-params caller still gets a working step
            # instead of a cryptic lax.cond branch-type error.
            if _sdt is not None:
                ref = params if params is not None else acc_
            else:
                ref = grads
            return _cached_zero_tree(ref), {
                "inner": inner_,
                "acc": acc_,
                "count": count,
            }

        if isinstance(count, _jax.core.Tracer):
            return _jax.lax.cond(
                count % k == 0, apply_fn, skip_fn, (acc, state["inner"])
            )
        boundary = int(count) % k == 0
        return (apply_fn if boundary else skip_fn)((acc, state["inner"]))

    return optax.GradientTransformationExtraArgs(acc_init, acc_update)


def grad(fun: Callable, argnums=0, average: bool = True,
         compression=Compression.none, **jax_kwargs) -> Callable:
    """``jax.grad`` with distributed reduction — the functional analogue of
    DistributedGradientTape (reference: horovod/tensorflow/__init__.py:
    253-328)."""
    gfun = _jax.grad(fun, argnums=argnums, **jax_kwargs)

    def wrapped(*args, **kwargs):
        return allreduce_pytree(gfun(*args, **kwargs), average=average,
                                compression=compression)

    return wrapped


def value_and_grad(fun: Callable, argnums=0, average: bool = True,
                   compression=Compression.none, **jax_kwargs) -> Callable:
    gfun = _jax.value_and_grad(fun, argnums=argnums, **jax_kwargs)

    def wrapped(*args, **kwargs):
        v, g = gfun(*args, **kwargs)
        return v, allreduce_pytree(g, average=average, compression=compression)

    return wrapped


# DistributedGradientTape parity name.
DistributedGradientTape = value_and_grad


# ---------------------------------------------------------------------------
# SPMD compilation helper
# ---------------------------------------------------------------------------

class _InstrumentedJit:
    """Thin wrapper around the jitted step: each ``__call__`` records the
    dispatch latency (time to hand the program to the runtime — execution
    itself is async) into the telemetry ring buffer for the compiled path,
    and ``lower`` and ``__call__`` run inside the compile log's host span
    ``hvd.jax.jit:<fn>`` (core/compile_log.py), so that what they compile
    names them as its cause. ``lower`` returns jax's own ``Lowered``:
    everything past it (``compile()``, the compiled call) and everything
    else (``trace``, ``eval_shape``, ...) is the wrapped ``jax.jit``
    object's, so the perf-critical AOT call path
    (``fn.lower(...).compile()(...)`` — ``benchmark/run.py``) bypasses
    instrumentation entirely. Overhead of ``__call__``: two clock reads,
    a push and a pop of the span's name, one integer compare and a few
    deque appends/compares per dispatch (ring + the sentinel watchdog):
    13-15 µs a call on this repository's sandbox host, with the span as
    without it (PERF.md, PR 36), against a ≥50 µs dispatch."""

    __slots__ = ("_jitted", "_name", "_span", "_calls")

    def __init__(self, jitted, name: str):
        self._jitted = jitted
        self._name = name
        self._span = _clog.JIT_SPAN + name
        self._calls = 0

    def lower(self, *args, **kwargs):
        with _clog.LOG.span(self._span):
            return self._jitted.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        log = _clog.LOG
        spans, compiled = log.open_spans(), log.compiled
        spans.append(self._span)
        t0 = _time.perf_counter()
        try:
            out = self._jitted(*args, **kwargs)
        finally:
            spans.pop()
        dt = _time.perf_counter() - t0
        if log.compiled != compiled:
            # Something compiled meanwhile: past the first call that is a
            # recompile, by function and dispatch (before the watchdog
            # looks, whose 'recompile' verdict reads it).
            log.compiled_in_call(self._name, dt, compiled, self._calls)
        self._calls += 1
        _tele.REGISTRY.counter("jax.dispatches").inc()
        _tele.REGISTRY.ring("jax.dispatch_s").push(dt)
        # Performance sentinel: the per-call dispatch boundary is the
        # compiled path's watchdog signal (a recompile shows up as one
        # giant dispatch). The AOT path (lower().compile()) bypasses
        # this wrapper entirely.
        _sentinel.observe_step(dt, origin="jax.dispatch")
        return out

    def __getattr__(self, item):
        return getattr(self._jitted, item)


def _two_tier_specs(specs):
    """Rewrite every ``'hvd'`` PartitionSpec entry to the ``('dcn','ici')``
    axis pair so user specs written for the flat world mesh map unchanged
    onto the two-tier mesh (same devices, same order — rank identity is
    preserved)."""
    from jax.sharding import PartitionSpec as P

    def one_entry(e):
        if e == HVD_AXIS:
            return (_C.DCN_AXIS, _C.ICI_AXIS)
        if isinstance(e, tuple):
            out = []
            for a in e:
                out.extend((_C.DCN_AXIS, _C.ICI_AXIS) if a == HVD_AXIS
                           else (a,))
            return tuple(out)
        return e

    def one_spec(p):
        return P(*(one_entry(e) for e in p)) if isinstance(p, P) else p

    return _jax.tree_util.tree_map(
        one_spec, specs, is_leaf=lambda x: isinstance(x, P))


def jit(fn: Callable = None, *, in_specs, out_specs, static_argnums=(),
        donate_argnums=()):
    """Compile ``fn`` over the world mesh: ``shard_map`` with the ``'hvd'``
    rank axis bound (so in-step collectives lower to ICI collectives) under
    ``jax.jit``. This replaces the reference's runtime enqueue→negotiate→
    execute pipeline (SURVEY.md §3.2) with one compiled program.

    With ``HVD_HIERARCHICAL_ALLREDUCE`` on and a two-tier world, the step
    maps over the (dcn, ici) mesh instead (specs spelled with ``'hvd'``
    are rewritten) and in-step ``hvd.allreduce`` lowers to
    reduce-scatter(ICI) → psum(DCN) → all-gather(ICI) — the reference's
    hierarchical hot path (operations.cc:1194-1346) at compile time."""

    def wrap(f):
        f = _phases.stamped(f)
        if _C._hier_allreduce_active():
            sm = _shard_map(
                f, mesh=_C._topo.two_tier(),
                in_specs=_two_tier_specs(in_specs),
                out_specs=_two_tier_specs(out_specs), check_vma=False,
            )
        else:
            sm = _shard_map(
                f, mesh=mesh(), in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        return _InstrumentedJit(
            _jax.jit(sm, static_argnums=static_argnums,
                     donate_argnums=donate_argnums),
            getattr(f, "__name__", "step"))

    return wrap if fn is None else wrap(fn)
