"""TPU-native collectives.

Re-design of the reference's collective execution engine
(reference: horovod/common/operations.cc:735-1531 ``PerformOperation``) as
compiled XLA collectives. There is no negotiation, no fusion buffer and no
background thread on this path: SPMD determinism makes the rank-0 coordinator
protocol (reference: operations.cc:279-517) unnecessary, and XLA fuses and
schedules collectives at compile time. The async host-side engine (for the
torch frontend) lives in :mod:`horovod_tpu.core` instead.

Two calling contexts:

1. **Inside SPMD code** (under ``shard_map``/``hvd.jit`` with the ``'hvd'``
   mesh axis bound): ``allreduce`` lowers to ``lax.psum`` over ICI — this is
   the hot path that replaces ``MPI_Allreduce``/``ncclAllReduce``.
2. **Eager host calls**: the value on this controller is the contribution of
   each of its local chips; a cached jitted ``shard_map`` program runs the
   collective across the whole mesh. Matches the reference's semantics where
   every rank contributes a tensor (reference: horovod/tensorflow/mpi_ops.py).

``ranked_*`` variants take an explicitly stacked per-rank array (leading axis
= world size, sharded over the mesh); they are the primitive everything else
is built on, and what tests use to express distinct per-rank values on one
controller.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.common import topology as _topo
from horovod_tpu.common import phases as _phases
from horovod_tpu.common.topology import HVD_AXIS
from horovod_tpu.core import numerics as _num
from horovod_tpu.core import telemetry as _tele


# Two-tier axis names, matching horovod_tpu.parallel.mesh (not imported:
# the parallel package pulls flax; these two literals are the contract).
DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def hierarchical_allreduce_enabled() -> bool:
    """HVD_HIERARCHICAL_ALLREDUCE routes rank-axis allreduces through
    reduce-scatter(ICI) -> psum(DCN) -> all-gather(ICI) whenever the world
    has a two-tier mesh (reference: HOROVOD_HIERARCHICAL_ALLREDUCE,
    operations.cc:1760-1778, composition :1194-1346)."""
    v = (os.environ.get("HVD_HIERARCHICAL_ALLREDUCE")
         or os.environ.get("HOROVOD_HIERARCHICAL_ALLREDUCE") or "")
    return v.lower() not in ("", "0", "false", "off")


def hierarchical_allgather_enabled() -> bool:
    """HVD_HIERARCHICAL_ALLGATHER: two-phase allgather (reference:
    HOROVOD_HIERARCHICAL_ALLGATHER shared-memory path,
    operations.cc:875-1010)."""
    v = (os.environ.get("HVD_HIERARCHICAL_ALLGATHER")
         or os.environ.get("HOROVOD_HIERARCHICAL_ALLGATHER") or "")
    return v.lower() not in ("", "0", "false", "off")


def _hier_allreduce_active() -> bool:
    st = _topo._require_init()
    return hierarchical_allreduce_enabled() and st.two_tier is not None


def _hier_allgather_active() -> bool:
    st = _topo._require_init()
    return hierarchical_allgather_enabled() and st.two_tier is not None


# ---------------------------------------------------------------------------
# SPMD-context helpers
# ---------------------------------------------------------------------------

def _name_bound(name: str) -> bool:
    try:
        lax.axis_index(name)
        return True
    except NameError:
        return False


def rank_axes():
    """The mesh axis name(s) enumerating ranks in the current SPMD context:
    ``'hvd'`` over the flat world mesh, ``('dcn', 'ici')`` over the
    two-tier mesh (hvd.jax.jit under HVD_HIERARCHICAL_ALLREDUCE). None
    outside any rank axis."""
    if _name_bound(HVD_AXIS):
        return HVD_AXIS
    if _name_bound(DCN_AXIS) and _name_bound(ICI_AXIS):
        return (DCN_AXIS, ICI_AXIS)
    return None


def axis_rank():
    """Per-chip rank inside SPMD code (the in-program analogue of
    ``hvd.rank()``; reference rank discovery: operations.cc:1664-1666)."""
    ax = rank_axes()
    if ax is None:
        _require_axis("axis_rank")
    return lax.axis_index(ax)


def in_spmd(x=None) -> bool:
    """True when called from inside a traced program (where collectives must
    lower to lax primitives rather than launch an eager program)."""
    if x is not None and isinstance(x, jax.core.Tracer):
        return True
    return False


def _require_axis(opname: str):
    """Raise a clear error when a collective is traced without a rank axis
    (e.g. plain ``jax.jit`` instead of ``hvd.jit``/``shard_map``)."""
    raise RuntimeError(
        f"horovod_tpu.{opname} was traced without the '{HVD_AXIS}' mesh axis "
        f"(or the '{DCN_AXIS}'/'{ICI_AXIS}' pair). Wrap your step with "
        "horovod_tpu.jax.jit(...) / shard_map over the world mesh, or call "
        "it eagerly on concrete arrays."
    )


# ---------------------------------------------------------------------------
# Ranked primitives: stacked per-rank arrays over the device mesh
# ---------------------------------------------------------------------------

def _psum_avg(x, world: int, average: bool, axis=HVD_AXIS):
    """psum, optionally averaged, preserving integer dtypes (floor-divide)
    so traced and eager calls agree."""
    with _phases.phase("hvd_allreduce"):
        r = lax.psum(x, axis)
    if average:
        with _phases.phase("hvd_unpack"):
            if jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(x.dtype, jnp.complexfloating):
                r = (r / world).astype(x.dtype)
            else:
                r = r // world
    return r


def _hier_allreduce(x, average: bool, dcn_policy=None):
    """reduce-scatter(ICI) -> psum(DCN) -> all-gather(ICI) over the bound
    two-tier axes; the lazy import keeps flax off the hot import path.
    ``dcn_policy`` (quantized compression policy) swaps the DCN psum for
    the block-scaled wire exchange of the 1/L shard."""
    from horovod_tpu.parallel.hierarchical import hierarchical_allreduce

    return hierarchical_allreduce(x, ICI_AXIS, DCN_AXIS, average=average,
                                  dcn_policy=dcn_policy)


def dcn_wire_policy(dcn_wire):
    """Resolve a per-tier DCN wire-policy NAME (the engine vocabulary:
    'none'/'int8'/'fp8') to the quantized compression policy object that
    drives the hierarchical DCN phase; 'none'/None -> None. Non-quantized
    spellings fail fast — the DCN tier-wire is the EQuARX block-scaled
    pipeline, not a cast."""
    if not dcn_wire or dcn_wire == "none":
        return None
    from horovod_tpu.jax.compression import Compression

    pol = Compression.resolve(dcn_wire, where="dcn_wire")
    if not getattr(pol, "quantized", False):
        raise ValueError(
            f"dcn_wire={dcn_wire!r} is not a quantized wire policy: the "
            "hierarchical DCN phase ships the block-scaled payload+scales "
            "format ('int8' or 'fp8')")
    return pol


def _spmd_allreduce(x, average: bool, ax):
    """In-SPMD allreduce over whatever rank axes are bound, hierarchical
    when the two-tier axes are available and the env knob is on."""
    if lax.psum(1, ax) == 1:
        return x  # single-rank axis: sum and mean are both identity
    if isinstance(ax, tuple) and hierarchical_allreduce_enabled():
        return _hier_allreduce(x, average)
    return _psum_avg(x, lax.psum(1, ax), average, axis=ax)


def _root_select_psum(x, root: int, axis=HVD_AXIS):
    """Broadcast-from-root as select + psum. The select (not a mask multiply)
    keeps NaN/Inf on non-root ranks from poisoning the sum; bools ride
    through an integer cast since psum is undefined for them."""
    idx = lax.axis_index(axis)
    asbool = x.dtype == jnp.bool_
    with _phases.phase("hvd_pack"):
        v = x.astype(jnp.int8) if asbool else x
        v = jnp.where(idx == root, v, jnp.zeros_like(v))
    with _phases.phase("hvd_allreduce"):
        r = lax.psum(v, axis)
    return r.astype(jnp.bool_) if asbool else r


def _mesh():
    return _topo._require_init().mesh


def _rank_sharding(mesh, ndim: int):
    return NamedSharding(mesh, P(HVD_AXIS, *([None] * (ndim - 1))))


@functools.lru_cache(maxsize=None)
def _ranked_program(op: str, mesh_key, root: int, average: bool,
                    hier: bool = False, dcn_wire: str = "none"):
    """Build + cache a jitted collective over the current mesh. jit itself
    caches per shape/dtype, so one program object serves all tensors.

    ``hier=True`` builds the program over the (dcn, ici) two-tier mesh
    with the hierarchical composition (reference: operations.cc:1194-1346,
    875-1010) instead of the flat world mesh — rank identity is unchanged
    because the two meshes hold the same devices in the same order
    (topology._build_two_tier enforces it). ``dcn_wire`` (hier allreduce
    only) quantizes the cross-tier phase: the ICI reduce-scatter stays at
    the resident dtype and only the 1/L shard crosses DCN block-scaled."""
    st = _topo._require_init()
    mesh = st.two_tier if hier else st.mesh
    world = mesh.devices.size
    rank_spec = (DCN_AXIS, ICI_AXIS) if hier else HVD_AXIS
    dcn_pol = dcn_wire_policy(dcn_wire) if hier else None

    def body(stacked):
        # stacked: local shard of the (size, *shape) array => (1, *shape);
        # x is this rank's tensor.
        x = stacked[0]
        if op == "allreduce":
            if hier:
                pol = (dcn_pol if jnp.issubdtype(x.dtype, jnp.floating)
                       else None)
                return _hier_allreduce(x, average, pol)
            return _psum_avg(x, world, average)
        if op == "allgather":
            if hier:
                from horovod_tpu.parallel.hierarchical import (
                    hierarchical_allgather,
                )

                return hierarchical_allgather(x, ICI_AXIS, DCN_AXIS)
            return lax.all_gather(x, HVD_AXIS, axis=0, tiled=True)
        if op == "broadcast":
            return _root_select_psum(x, root, axis=rank_spec)
        if op == "reducescatter":
            return lax.psum_scatter(_pad_dim0(x, world), rank_spec,
                                    scatter_dimension=0, tiled=True)[None]
        if op == "alltoall":
            return lax.all_to_all(x, rank_spec, split_axis=0, concat_axis=0, tiled=True)[None]
        raise ValueError(op)

    if op in ("allreduce", "allgather", "broadcast"):
        out_spec = P()  # replicated result on every rank
    else:
        out_spec = P(rank_spec)  # per-rank results, stacked

    def run(stacked):
        spec = P(rank_spec, *([None] * (stacked.ndim - 1)))
        # check_vma=False: all_gather/all_to_all results are replicated or
        # per-rank by construction; jax's static replication checker cannot
        # infer this for every primitive.
        return _shard_map(
            body, mesh=mesh, in_specs=spec, out_specs=out_spec, check_vma=False
        )(stacked)

    return jax.jit(run)


def _mesh_key():
    st = _topo._require_init()
    return (id(st.mesh), st.size)


def make_ranked(per_rank_values: Sequence[jnp.ndarray]):
    """Assemble a stacked (size, ...) array from one value per rank, sharded
    so rank r's value lives on chip r. Test/debug utility."""
    st = _topo._require_init()
    vals = [jnp.asarray(v) for v in per_rank_values]
    if len(vals) != st.size:
        raise ValueError(f"expected {st.size} values, got {len(vals)}")
    shape = (st.size,) + vals[0].shape
    sharding = _rank_sharding(st.mesh, len(shape))
    shards = [
        jax.device_put(v[None], d) for v, d in zip(vals, st.devices)
        if d in st.local_devices
    ]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def _local_row(stacked_out):
    """Fetch this process's first rank's row of a P('hvd')-sharded result.
    Plain indexing would fail on non-fully-addressable arrays in
    multi-process runs; the local shard is always addressable."""
    st = _topo._require_init()
    d0 = st.local_devices[0]
    for shard in stacked_out.addressable_shards:
        if shard.device == d0:
            return jnp.asarray(shard.data)[0]
    raise RuntimeError("no addressable shard on this process's first device")


def _replicated_stack(x):
    """Stack this controller's value as the contribution of each of its local
    chips (the eager-call data layout)."""
    st = _topo._require_init()
    x = jnp.asarray(x)
    shape = (st.size,) + x.shape
    sharding = _rank_sharding(st.mesh, len(shape))
    shards = [jax.device_put(x[None], d) for d in st.local_devices]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def ranked_allreduce(stacked, average: bool = False,
                     dcn_wire: str = "none"):
    """Sum (or mean) of per-rank tensors; result replicated to all ranks.
    Routed hierarchically (ICI/DCN split) when HVD_HIERARCHICAL_ALLREDUCE
    is on and the world has a two-tier mesh; ``dcn_wire`` then quantizes
    the cross-tier phase (ignored on the flat route — there is no DCN
    hop to shrink)."""
    hier = _hier_allreduce_active()
    return _ranked_program("allreduce", _mesh_key(), 0, average,
                           hier=hier,
                           dcn_wire=dcn_wire if hier else "none")(stacked)


def ranked_allgather(stacked):
    """Concatenate per-rank tensors along dim 0 (reference: MPI_Allgatherv
    path, operations.cc:810-857); result (size*n, ...) replicated."""
    return _ranked_program("allgather", _mesh_key(), 0, False,
                           hier=_hier_allgather_active())(stacked)


def _check_root(root_rank: int) -> int:
    """Validate root range like the coordinator's response validation
    (reference: operations.cc:315-517 surfaces ERROR for bad requests)."""
    st = _topo._require_init()
    root_rank = int(root_rank)
    if not 0 <= root_rank < st.size:
        raise ValueError(
            f"root_rank {root_rank} is out of range for world size {st.size}"
        )
    return root_rank


def ranked_broadcast(stacked, root_rank: int):
    """Every rank receives rank ``root_rank``'s tensor."""
    return _ranked_program("broadcast", _mesh_key(), _check_root(root_rank), False)(stacked)


def ranked_reducescatter(stacked):
    """Rank r receives the r-th 1/size chunk (dim 0) of the rank-sum.
    Result stacked: (size, n/size, ...)."""
    return _ranked_program("reducescatter", _mesh_key(), 0, False)(stacked)


def ranked_alltoall(stacked):
    """Rank r sends its j-th chunk to rank j. Result stacked (size, n, ...)
    where row r is the concat of chunks received by rank r."""
    return _ranked_program("alltoall", _mesh_key(), 0, False)(stacked)


# ---------------------------------------------------------------------------
# Consistency-check mode (debug): reproduce the reference coordinator's
# request validation (operations.cc:315-517). SPMD determinism makes this
# structurally unnecessary, but when hunting divergence bugs across
# controller processes, HVD_CONSISTENCY_CHECKS=1 cross-checks every eager
# collective's (op, dtype, shape, root) before executing it and surfaces
# mismatches as errors on EVERY process, like the broadcast ERROR response.
# ---------------------------------------------------------------------------

_FP_LEN = 16  # op, root, dtype-hash, ndim, dims[<=11], flags


def consistency_checks_enabled() -> bool:
    """NOTE: the flag must be set uniformly on EVERY controller process —
    the check itself is a collective, so partial enablement desynchronizes
    the launch order (a hang, not an error). '0'/'false'/'off' disable."""
    val = (os.environ.get("HVD_CONSISTENCY_CHECKS")
           or os.environ.get("HOROVOD_CONSISTENCY_CHECKS") or "")
    return val.lower() not in ("", "0", "false", "off")


def _maybe_consistency_check(op_code: int, tensor, root: int = -1,
                             flags: int = 0):
    st = _topo._require_init()
    if not consistency_checks_enabled() or st.num_processes == 1:
        return
    fp = np.zeros((_FP_LEN,), np.int32)
    fp[0] = op_code
    fp[1] = root
    import zlib

    # crc32, not hash(): Python string hashing is salted per process.
    fp[2] = zlib.crc32(str(jnp.asarray(tensor).dtype).encode()) % (2 ** 31)
    shape = jnp.asarray(tensor).shape
    fp[3] = len(shape)
    for i, d in enumerate(shape[:11]):
        fp[4 + i] = d % (2 ** 31)
    fp[15] = flags  # e.g. the allreduce average flag
    # Every local chip contributes this controller's fingerprint; the
    # gathered matrix is identical everywhere, so the error (or not) is
    # raised consistently on every process.
    gathered = np.asarray(ranked_allgather(_replicated_stack(jnp.asarray(fp))))
    gathered = gathered.reshape(st.size, _FP_LEN)
    if not (gathered == gathered[0]).all():
        bad = np.where((gathered != gathered[0]).any(axis=1))[0]
        raise _topo.HorovodInternalError(
            f"consistency check failed: ranks {bad.tolist()} submitted a "
            f"mismatched collective (op/dtype/shape/root fingerprints "
            f"differ; local fingerprint {fp.tolist()}). The reference "
            "coordinator would return an ERROR response here "
            "(operations.cc:315-517).")


# ---------------------------------------------------------------------------
# Public verbs — context-polymorphic (SPMD tracer or eager host value)
# ---------------------------------------------------------------------------

def _nbytes(tensor) -> int:
    """Host-visible byte size of an eager tensor (telemetry accounting)."""
    try:
        return int(np.prod(tensor.shape) if tensor.shape else 1) \
            * np.dtype(tensor.dtype).itemsize
    except Exception:
        return 0


def _record_eager(op: str, tensor, elided: bool = False):
    """Feed the telemetry registry for one eager collective. The compiled
    (SPMD) path deliberately records nothing here — tracing happens once,
    and its cost story lives in the xplane capture instead.

    Under the numerics policy (core/numerics.py) a HOST-resident eager
    input is also scanned for nonfinite values — eager collectives are
    control-plane traffic (metric averaging, state broadcasts), exactly
    where a NaN silently spreads to every rank. Device-resident inputs
    are deliberately NOT scanned: np.asarray on them would force a
    blocking device→host fetch per tensor inside the drain window
    CLAUDE.md flags as rendezvous-sensitive (the compiled-path health
    and the engine submit hooks cover those buffers without extra
    transfers). Counter only, no verdict: the collective itself may be
    the legitimate carrier (a broadcast of a diverged peer's state for
    inspection), and MetricAverage has its own masking."""
    _tele.record_eager(op, _nbytes(tensor), elided=elided)
    if _num.enabled() and isinstance(tensor, np.ndarray):
        _num.note_eager_nonfinite(op, _num.np_nonfinite(tensor))


def _localize(x):
    """Re-home an eager collective's replicated GLOBAL output as an
    ordinary process-local array. In a multi-controller world the raw
    output is committed to the whole device set; feeding it to any
    subsequent local eager op fails jax's addressability checks (a
    reference user never sees this — each mpirun rank only ever holds
    local tensors). The local shard of a replicated result holds the full
    value, so one host hop restores composability. Single-controller runs
    return the array untouched."""
    st = _topo._require_init()
    if st.num_processes == 1:
        return x
    return jnp.asarray(np.asarray(x))


def fetch(x) -> np.ndarray:
    """Device→host of a possibly multi-process-sharded global array: the
    full global value on every process. Replicated/addressable arrays
    fetch directly; cross-process-sharded ones go through an allgather
    (``multihost_utils.process_allgather``)."""
    try:
        return np.asarray(x)
    except RuntimeError:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def allreduce(tensor, average: bool = True, name: Optional[str] = None,
              dcn_wire: str = "none"):
    """Allreduce (reference API: horovod/tensorflow/mpi_ops.py:78-91 and
    horovod/common/operations.cc:1401-1496).

    Inside SPMD code this is ``lax.pmean``/``lax.psum`` over the chip mesh
    axis. Eagerly, every local chip contributes this controller's value.
    ``name`` is accepted for reference-API parity (negotiation needed names;
    SPMD ordering does not) and used by the timeline. ``dcn_wire`` (eager
    path) quantizes the cross-tier phase of a hierarchically-routed call —
    the engines' two-phase chunk route rides this.
    """
    if in_spmd(tensor):
        ax = rank_axes()
        if ax is None:
            _require_axis("allreduce")
        return _spmd_allreduce(tensor, average, ax)
    tensor = jnp.asarray(tensor)
    if _topo._require_init().size == 1:
        # identity — no program launch for a 1-rank world
        _record_eager("allreduce", tensor, elided=True)
        return tensor
    _record_eager("allreduce", tensor)
    _maybe_consistency_check(0, tensor, flags=int(average))
    return _localize(ranked_allreduce(_replicated_stack(tensor),
                                      average=average, dcn_wire=dcn_wire))


def allgather(tensor, name: Optional[str] = None):
    """Concatenation of every rank's tensor along dim 0 (reference:
    horovod/tensorflow/mpi_ops.py:108-126). Ranks may have different first
    dims; eagerly that can only differ across processes, handled by a size
    exchange + pad + strip (XLA collectives need static shapes)."""
    if in_spmd(tensor):
        ax = rank_axes()
        if ax is None:
            _require_axis("allgather")
        if lax.psum(1, ax) == 1:
            return tensor
        with _phases.phase("hvd_allreduce"):
            return lax.all_gather(tensor, ax, axis=0, tiled=True)
    tensor = jnp.asarray(tensor)
    if tensor.ndim == 0:
        raise ValueError("allgather requires a tensor with at least one dimension")
    if _topo._require_init().size == 1:
        _record_eager("allgather", tensor, elided=True)
        return tensor
    _record_eager("allgather", tensor)
    # Allgather legitimately permits differing first dims; check the rest.
    _maybe_consistency_check(1, tensor[:0])
    st = _topo._require_init()
    if st.num_processes == 1:
        return ranked_allgather(_replicated_stack(tensor))  # already local
    # Cross-process variable first dim: exchange per-rank sizes (each local
    # chip one-hots its own global rank), pad to the max, gather, strip.
    n = tensor.shape[0]
    shards = []
    for d in st.local_devices:
        # Use the device's true global rank: init(devices=...) permits
        # non-contiguous local blocks.
        onehot = jnp.zeros((st.size,), jnp.int32).at[st.devices.index(d)].set(n)
        shards.append(jax.device_put(onehot[None], d))
    stacked = jax.make_array_from_single_device_arrays(
        (st.size, st.size), _rank_sharding(st.mesh, 2), shards
    )
    sizes = np.asarray(ranked_allreduce(stacked))
    maxn = int(sizes.max())
    pad = [(0, maxn - n)] + [(0, 0)] * (tensor.ndim - 1)
    padded = jnp.pad(tensor, pad)
    gathered = np.asarray(ranked_allgather(_replicated_stack(padded)))
    gathered = gathered.reshape((st.size, maxn) + tensor.shape[1:])
    pieces = [gathered[r, : int(sizes[r])] for r in range(st.size)]
    return jnp.asarray(np.concatenate(pieces, axis=0))


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    """Every rank receives rank ``root_rank``'s value (reference:
    horovod/tensorflow/mpi_ops.py:151-167, operations.cc:1502-1522)."""
    root_rank = _check_root(root_rank)
    if in_spmd(tensor):
        ax = rank_axes()
        if ax is None:
            _require_axis("broadcast")
        if lax.psum(1, ax) == 1:
            return tensor
        return _root_select_psum(tensor, root_rank, axis=ax)
    tensor = jnp.asarray(tensor)
    if _topo._require_init().size == 1:
        _record_eager("broadcast", tensor, elided=True)
        return tensor
    _record_eager("broadcast", tensor)
    _maybe_consistency_check(2, tensor, root_rank)
    return _localize(ranked_broadcast(_replicated_stack(tensor), root_rank))


def _pad_dim0(tensor, multiple: int):
    """Zero-pad dim 0 up to the next multiple (the reducescatter padding
    contract); identity when already divisible."""
    rem = tensor.shape[0] % multiple
    if rem == 0:
        return tensor
    pad = [(0, multiple - rem)] + [(0, 0)] * (tensor.ndim - 1)
    return jnp.pad(tensor, pad)


def reducescatter(tensor, name: Optional[str] = None):
    """Sum over ranks, scattered: rank r keeps the r-th chunk of dim 0.
    (Beyond the reference's three verbs; native on TPU, and the building
    block of hierarchical allreduce — operations.cc:1194-1346.)

    Padding contract: a dim 0 not divisible by the world size is
    zero-padded to the next multiple, so rank r receives rows
    ``[r*c, (r+1)*c)`` of the padded sum where ``c = ceil(n/size)`` —
    the trailing ``size*c - n`` rows of rank ``size-1``'s chunk are
    zeros. A following tiled ``allgather`` returns the ``size*c``-row
    concatenation; slice ``[:n]`` to recover the original extent (this
    round trip is how the sharded weight update composes —
    horovod_tpu/jax/sharded.py)."""
    if in_spmd(tensor):
        ax = rank_axes()
        if ax is None:
            _require_axis("reducescatter")
        if tensor.ndim == 0:
            raise ValueError(
                "reducescatter requires a tensor with at least one dimension")
        world = lax.psum(1, ax)
        if world == 1:
            return tensor
        with _phases.phase("hvd_pack"):
            tensor = _pad_dim0(tensor, world)
        with _phases.phase("hvd_allreduce"):
            return lax.psum_scatter(tensor, ax, scatter_dimension=0,
                                    tiled=True)
    tensor = jnp.asarray(tensor)
    if tensor.ndim == 0:
        raise ValueError(
            "reducescatter requires a tensor with at least one dimension")
    if _topo._require_init().size == 1:
        _record_eager("reducescatter", tensor, elided=True)
        return tensor
    _record_eager("reducescatter", tensor)
    _maybe_consistency_check(3, tensor)
    # _local_row is already process-local — no _localize round trip.
    return _local_row(ranked_reducescatter(_replicated_stack(tensor)))


def alltoall(tensor, name: Optional[str] = None):
    """Each rank scatters equal chunks of dim 0 to all ranks and concatenates
    what it receives (beyond the reference's verbs; rides ICI natively)."""
    if in_spmd(tensor):
        ax = rank_axes()
        if ax is None:
            _require_axis("alltoall")
        if lax.psum(1, ax) == 1:
            return tensor
        with _phases.phase("hvd_allreduce"):
            return lax.all_to_all(tensor, ax, split_axis=0, concat_axis=0,
                                  tiled=True)
    tensor = jnp.asarray(tensor)
    if _topo._require_init().size == 1:
        _record_eager("alltoall", tensor, elided=True)
        return tensor
    _record_eager("alltoall", tensor)
    _maybe_consistency_check(4, tensor)
    return _local_row(ranked_alltoall(_replicated_stack(tensor)))


# ---------------------------------------------------------------------------
# Fusion: grouped collectives (reference: tensor fusion, C5 —
# fusion_buffer_manager.cc + operations.cc:2035-2074 — done at trace time)
# ---------------------------------------------------------------------------

#: Leaves with fewer elements than this share a per-dtype buffer inside a
#: traced step; a larger one is already bandwidth-bound and keeps its own
#: op. One rule for both sides of the exchange: :func:`grouped_allreduce`
#: here and the fused optimizer update (``jax/fused.py``, which has the
#: measurement behind the number).
FUSION_THRESHOLD_ELEMS = 4096


def _flatten_group(tensors):
    shapes = [t.shape for t in tensors]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([jnp.ravel(t) for t in tensors]) if tensors else jnp.zeros((0,))
    return flat, shapes, sizes


def _unflatten_group(flat, shapes, sizes):
    out, off = [], 0
    for shp, n in zip(shapes, sizes):
        out.append(jnp.reshape(flat[off : off + n], shp))
        off += n
    return out


def _grouped_apply(fn, tensors: Sequence):
    """Apply ``fn(flat_1d) -> flat_1d`` to tensors fused per dtype group —
    the fusion rule admits same-dtype responses only (reference:
    operations.cc:2049-2054), order preserved within each group."""
    tensors = [jnp.asarray(t) for t in tensors]
    if not tensors:
        return []
    by_dtype = {}
    for i, t in enumerate(tensors):
        by_dtype.setdefault(t.dtype, []).append(i)
    results = [None] * len(tensors)
    for idxs in by_dtype.values():
        group = [tensors[i] for i in idxs]
        with _phases.phase("hvd_pack"):
            flat, shapes, sizes = _flatten_group(group)
        out = fn(flat)  # the collective: names itself
        with _phases.phase("hvd_unpack"):
            leaves = _unflatten_group(out, shapes, sizes)
        for i, r in zip(idxs, leaves):
            results[i] = r
    return results


def grouped_allreduce(tensors: Sequence, average: bool = True):
    """Allreduce many tensors — the compile-time equivalent of the
    reference's 64 MB fusion buffer (reference: operations.cc:2035-2074,
    fusion_buffer_manager.cc), with the buffer's membership decided by
    what each tensor is.

    Inside a traced step only the leaves under
    :data:`FUSION_THRESHOLD_ELEMS` share a buffer (one collective per
    dtype group); every larger leaf is reduced as itself, and XLA's
    all-reduce combiner groups those collectives and its scheduler
    places them in the backward pass. Packing a large leaf buys nothing
    there and costs a concatenate, an average and a slice pass over the
    whole buffer and one all-reduce that waits for the last gradient
    (measured on four chips, PERF.md, PR 25: 14.9 ms of BERT-base's
    86.2 ms step). The small leaves keep their buffer because not every
    route's collectives are combined: under the hierarchical route each
    would pay an all-gather of its own. Eager calls on concrete arrays
    keep one buffer per dtype group: each collective there is a dispatch
    of its own.

    World size 1 short-circuits BEFORE the packing: the concatenate ->
    all-reduce -> slice chain survives XLA simplification even with one
    participant, costing a full extra HBM round trip of the tensor set
    per step (measured on the one-chip bench — docs/benchmarks.md)."""
    tensors = [jnp.asarray(t) for t in tensors]
    if _topo._require_init().size == 1:
        for t in tensors:
            if not in_spmd(t):  # tracers: trace-time, not a per-step event
                _record_eager("allreduce", t, elided=True)
        return tensors
    alone = [in_spmd(t) and t.size >= FUSION_THRESHOLD_ELEMS for t in tensors]
    packed = iter(_grouped_apply(
        lambda flat: allreduce(flat, average=average),
        [t for t, a in zip(tensors, alone) if not a]))
    return [allreduce(t, average=average) if a else next(packed)
            for t, a in zip(tensors, alone)]


def allreduce_pytree(tree, average: bool = True):
    """Allreduce every leaf of a pytree (grad pytrees, metrics), fused as
    :func:`grouped_allreduce` fuses."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, grouped_allreduce(leaves, average))


def broadcast_pytree(tree, root_rank: int = 0):
    """Broadcast every leaf from ``root_rank`` (reference:
    broadcast_global_variables / broadcast_parameters — §3.4). Fused into
    one collective per dtype."""
    if _topo._require_init().size == 1:
        _check_root(root_rank)
        for leaf in jax.tree_util.tree_leaves(tree):
            # No jnp.asarray here: counting bytes must not device-put the
            # whole host-side tree on the very path that elides the
            # transfer. _nbytes reads shape/dtype only (0 for plain
            # python scalars — an acceptable undercount).
            if not in_spmd(leaf):  # tracers: trace-time, not per-step
                _record_eager("broadcast", leaf, elided=True)
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = _grouped_apply(lambda flat: broadcast(flat, root_rank), leaves)
    return jax.tree_util.tree_unflatten(treedef, out)
