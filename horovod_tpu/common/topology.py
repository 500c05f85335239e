"""Device/process topology — the TPU-native replacement for MPI rank discovery.

The reference derives ``rank/size/local_rank/local_size/cross_rank/cross_size``
from ``MPI_COMM_WORLD`` plus a shared-memory split and a cross-node split
(reference: horovod/common/operations.cc:1638-1705 and the C getters at
operations.cc:2226-2262). On TPU there is no MPI: a *rank* is a TPU chip, the
world is a ``jax.sharding.Mesh`` over all chips, the "local" communicator is
the set of chips attached to one host process (ICI-connected within a slice),
and the "cross" communicator is the across-host tier (DCN).

Mapping (see SURVEY.md §2.3):

==================  ==========================================================
reference concept   TPU-native equivalent
==================  ==========================================================
MPI_COMM_WORLD      1-D ``Mesh(jax.devices(), ('hvd',))``
rank                global id of this process's first device (device-level
                    rank inside SPMD code comes from ``lax.axis_index``)
size                total number of chips in the mesh
local_comm          this process's ``jax.local_devices()``; co-hosted
                    controllers are split by ``local_rank()`` (hostname
                    exchange at init — the shared-memory split)
cross_comm          one representative per host (DCN tier):
                    ``cross_rank()``/``cross_size()`` enumerate hosts
==================  ==========================================================

Single-controller SPMD means one Python process may *speak for* several ranks
(its local chips); host-side code therefore sees the process-level view while
per-chip rank identity lives inside compiled programs.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional, Sequence

import numpy as np

HVD_AXIS = "hvd"


class HorovodInternalError(RuntimeError):
    """Engine-surfaced error (reference: coordinator ERROR responses,
    horovod/common/operations.cc:315-517)."""


class NotInitializedError(ValueError):
    """Raised by topology getters before init() (reference:
    horovod/common/__init__.py:90-139 raises ValueError)."""

    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init()."
        )


class _Topology:
    """Singleton world state (reference: HorovodGlobalState,
    horovod/common/operations.cc:108-247 — minus the comm thread, which on
    TPU lives in the native engine, see horovod_tpu/core)."""

    def __init__(self) -> None:
        self.initialized = False
        self.lock = threading.Lock()
        self.mesh = None
        self.devices: list = []
        self.local_devices: list = []
        self.size = 0
        self.rank0 = 0  # global rank of this process's first local device
        self.local_size = 0
        self.local_rank = 0  # this controller's index among co-hosted ones
        self.cross_size = 0
        self.cross_rank = 0
        self.host_num_processes = 1  # controllers sharing this host
        self.num_processes = 1
        self.process_index = 0
        self.homogeneous = True
        self.two_tier = None  # (dcn, ici) Mesh when the world has 2 tiers


_state = _Topology()


def _build_mesh(devs: Sequence) -> "object":
    from jax.sharding import Mesh

    return Mesh(np.asarray(devs), (HVD_AXIS,))


def _build_two_tier(devices: Sequence):
    """(dcn, ici) mesh over the SAME devices in the SAME order as the flat
    world mesh — the reference's local/cross communicator split
    (operations.cc:1668-1705). Axis names match
    :mod:`horovod_tpu.parallel.mesh`. Returns None when the world has no
    usable two-tier structure (single process without an override,
    heterogeneous chip counts, or process-interleaved device order —
    hierarchical collectives would silently permute ranks then).

    ``HVD_TWO_TIER_SHAPE=o,i`` overrides the process grouping — the test
    and simulation knob (e.g. treat a single 8-device process as 2 slices
    of 4), mirroring how the reference's hierarchical path is exercised
    by telling MPI there are multiple nodes.
    """
    from jax.sharding import Mesh

    shape_env = os.environ.get("HVD_TWO_TIER_SHAPE")
    if shape_env:
        # An explicit override must fail loudly — silently degrading to
        # flat collectives would invalidate whatever the user is measuring.
        try:
            outer, inner = (int(v) for v in shape_env.split(","))
        except ValueError:
            raise ValueError(
                f"HVD_TWO_TIER_SHAPE={shape_env!r} is not 'outer,inner' "
                "(e.g. '2,4')") from None
        if outer < 1 or inner < 1 or outer * inner != len(devices):
            raise ValueError(
                f"HVD_TWO_TIER_SHAPE={shape_env!r} does not factor the "
                f"{len(devices)}-device world")
        arr = np.empty((outer, inner), dtype=object)
        for idx, d in enumerate(devices):
            arr[idx // inner, idx % inner] = d
        return Mesh(arr, ("dcn", "ici"))
    by_proc: dict = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    if len(by_proc) < 2:
        return None
    if len({len(v) for v in by_proc.values()}) != 1:
        return None  # heterogeneous: reference gates hierarchical off too
    rows = [by_proc[p] for p in sorted(by_proc)]
    flat = [d for row in rows for d in row]
    if flat != list(devices):
        return None  # interleaved order would change rank identity
    arr = np.empty((len(rows), len(rows[0])), dtype=object)
    for r, row in enumerate(rows):
        for c, d in enumerate(row):
            arr[r, c] = d
    return Mesh(arr, ("dcn", "ici"))


# Count of successfully COMPLETED hostname exchanges; advances only on
# success, so it stays agreed across processes (see _host_split).
_host_split_completed = 0


def _host_split(num_processes: int, process_index: int):
    """Shared-host split (reference: the MPI_Comm_split_type(SHARED) local
    communicator + the cross split, operations.cc:1668-1705): every
    process publishes its hostname to the coordination service and reads
    its peers', yielding which controllers share a physical host.

    Returns ``(local_rank, host_num_processes, cross_rank, cross_size)``
    — controller index among co-hosted controllers, how many controllers
    share this host, this host's index, and the number of distinct hosts
    — or ``None`` when no coordination service is reachable (callers
    degrade to the one-controller-per-host view).

    ``HVD_HOSTNAME`` overrides the reported hostname — the simulation
    knob for exercising multi-host layouts on one machine (the same role
    mpirun's hostfile plays for the reference)."""
    import json as _json
    import socket

    host = os.environ.get("HVD_HOSTNAME") or socket.gethostname()
    if num_processes == 1:
        return 0, 1, 0, 1
    from horovod_tpu.core import coordinator as coord

    try:
        kv = coord.JaxKV()
    except Exception:
        # No coordination service is a PROPERTY OF THE WORLD (the jax
        # distributed client is either up everywhere or nowhere), so the
        # one-controller-per-host fallback stays consistent across it.
        return None
    global _host_split_completed
    # Keys are namespaced by the count of COMPLETED exchanges, which
    # agrees across processes (lifecycle is collective, and an exchange
    # completes either everywhere or nowhere — completion requires
    # every process's key, which requires every process to have
    # published). This closes both failure modes at once: a new
    # incarnation reads FRESH keys (never a peer's stale hostname from
    # the previous one), while a FAILED attempt does not advance the
    # count, so retriers and a late straggler converge on the same
    # namespace. Keys are immutable in the common case; the remaining
    # delete+set only fires for a hostname changed across a *failed*
    # attempt within one incarnation.
    inc = _host_split_completed
    try:
        key = f"hvd/host/i{inc}/p{process_index}"
        existing = kv.try_get(key)
        if existing is not None and _json.loads(existing) != host:
            kv.delete(key)
            existing = None
        if existing is None:
            kv.set(key, _json.dumps(host))
        deadline = coord.negotiation_timeout_s()
        peers = [_json.loads(kv.get(f"hvd/host/i{inc}/p{p}", deadline))
                 for p in range(num_processes)]
        if peers[process_index] != host:  # own delete/set failed
            raise KeyError("own hostname key is stale")
        _host_split_completed += 1
    except Exception as exc:
        # The service exists but a peer's hostname never arrived: a
        # silent per-process fallback here would leave the world
        # DISAGREEING on cross_size/local_rank ownership — fail loudly
        # instead (the same contract negotiation rounds have).
        raise HorovodInternalError(
            f"shared-host split failed: could not exchange hostnames "
            f"with all {num_processes} processes ({exc}); a peer may "
            "not have reached hvd.init()") from None
    by_host: dict = {}
    for p, h in enumerate(peers):
        by_host.setdefault(h, []).append(p)
    hosts = sorted(by_host, key=lambda h: by_host[h][0])  # first-pid order
    mine = by_host[host]
    return (mine.index(process_index), len(mine),
            hosts.index(host), len(hosts))


def init(ranks: Optional[Sequence[int]] = None, devices: Optional[Sequence] = None):
    """Initialize the world.

    Args:
      ranks: optional subset of global device indices to form the world from,
        mirroring the reference's ``init(comm=[ranks])`` rank-subset support
        (reference: horovod/common/__init__.py:58-84). Only valid
        single-process.
      devices: explicit device list (tests use this to shrink the world).

    Idempotent like the reference's ``InitializeHorovodOnce``
    (reference: horovod/common/operations.cc:2176-2194).

    Runs inside the host span ``hvd.init`` of the compile log
    (core/compile_log.py), whose listeners it installs, once a process:
    what the process compiles from here on has a record, and what this
    call compiles names it as its cause.
    """
    if _state.initialized:
        return
    from horovod_tpu.core import compile_log

    compile_log.install()
    with compile_log.LOG.span("hvd.init"):
        _init(ranks, devices)


def _init(ranks, devices):
    """:func:`init`, inside its span."""
    with _state.lock:
        if _state.initialized:
            return

        import jax
        from jax._src import distributed as _jax_dist

        # Multi-host: if the user (or launcher) provided coordination env,
        # bring up the JAX distributed client so jax.devices() is global.
        # The already-initialized probe must NOT touch the backend
        # (jax.process_count() would initialize it, after which
        # distributed.initialize refuses to run), hence the client check.
        coord = os.environ.get("HVD_COORDINATOR_ADDRESS")
        if coord and os.environ.get("HVD_NUM_PROCESSES"):
            if _jax_dist.global_state.client is None:
                from horovod_tpu.core import elastic as _elastic

                if _elastic.enabled():
                    # Elastic worlds own the bring-up: the stock client
                    # TERMINATES survivors when the coordination service
                    # notices a dead peer — detection must live in the
                    # elastic heartbeat lease instead (core/elastic.py).
                    _elastic.bring_up_distributed(
                        coord,
                        int(os.environ["HVD_NUM_PROCESSES"]),
                        int(os.environ.get("HVD_PROCESS_ID", "0")))
                else:
                    jax.distributed.initialize(
                        coordinator_address=coord,
                        num_processes=int(os.environ["HVD_NUM_PROCESSES"]),
                        process_id=int(os.environ.get("HVD_PROCESS_ID",
                                                      "0")),
                    )

        # Multi-controller on the CPU platform: jaxlib executes
        # cross-process CPU collectives only through a CPU collectives
        # backend — without one, the first collective dies with
        # "Multiprocess computations aren't implemented on the CPU
        # backend". Select gloo while the backend is still uninitialized.
        # No-op for single-process and for real TPU platforms.
        if (_jax_dist.global_state.client is not None
                and jax.config.jax_platforms == "cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

        if devices is None:
            devices = list(jax.devices())
        if ranks is not None:
            if jax.process_count() > 1:
                raise ValueError("ranks= subset is only supported single-process")
            devices = [devices[i] for i in ranks]

        local = [d for d in devices if d.process_index == jax.process_index()]
        if not local:
            raise ValueError("this process owns no devices in the requested world")

        _state.devices = list(devices)
        _state.local_devices = local
        _state.mesh = _build_mesh(devices)
        _state.size = len(devices)
        _state.local_size = len(local)
        _state.num_processes = jax.process_count()
        _state.process_index = jax.process_index()
        # Global rank of the first local device: devices are mesh-ordered, so
        # this is its index in the world list.
        _state.rank0 = _state.devices.index(local[0])
        # Shared-host split (reference: operations.cc:1668-1705). Without
        # a coordination service (or single-process) every controller is
        # assumed to own its host — the previous fixed behavior.
        split = _host_split(jax.process_count(), jax.process_index())
        if split is None:
            _state.local_rank = 0
            _state.host_num_processes = 1
            _state.cross_rank = jax.process_index()
            _state.cross_size = jax.process_count()
        else:
            (_state.local_rank, _state.host_num_processes,
             _state.cross_rank, _state.cross_size) = split
        counts = {}
        for d in devices:
            counts[d.process_index] = counts.get(d.process_index, 0) + 1
        _state.homogeneous = len(set(counts.values())) == 1
        _state.two_tier = _build_two_tier(devices)
        _state.initialized = True
    # If an engine was constructed before init() (legal: enqueue works
    # pre-init), re-apply its params so the multi-controller fusion guard
    # sees the now-known topology.
    try:
        from horovod_tpu.core import engine as _eng

        if _eng._engine is not None:
            _eng._engine.set_params(
                fusion_threshold=_eng._engine.fusion_threshold)
    except Exception:
        pass
    if _state.num_processes > 1:
        # Multi-controller liveness: negotiation rounds need EVERY
        # process's engine participating (peers block on our round
        # message even when we never use the engine path ourselves —
        # the reference equivalently gathers a possibly-empty request
        # list from every rank each tick, operations.cc:2117-2131).
        # A failure here MUST be loud: a silent non-participant stalls
        # every peer for the full negotiation timeout.
        try:
            from horovod_tpu.core import coordinator as _coord, engine as _eng

            if _coord.negotiation_enabled():
                _eng.get_engine()
        except Exception as exc:
            import logging

            logging.getLogger("horovod_tpu").error(
                "failed to start the collective engine for negotiation "
                "rounds (%s); peer processes' engine collectives will "
                "stall until HVD_NEGOTIATION_TIMEOUT", exc)
    # Elastic worlds (HVD_ELASTIC=1): start the heartbeat lease + adopt
    # the world-epoch journal. No-op when elastic is off.
    try:
        from horovod_tpu.core import elastic as _elastic

        if _elastic.enabled():
            _elastic.get_world().on_init(_state.num_processes,
                                         _state.process_index)
    except Exception:
        import logging

        logging.getLogger("horovod_tpu").warning(
            "elastic world bring-up failed", exc_info=True)
    # Fleet observability plane: per-rank snapshot publisher (+ rank-0
    # aggregator) over the KV plane. No-op unless a fleet directory
    # resolves (HVD_FLEET_DIR, or the elastic dir); must never break init.
    try:
        from horovod_tpu.core import fleet as _fleet

        _fleet.maybe_start(_state.process_index, _state.num_processes)
    except Exception:
        import logging

        logging.getLogger("horovod_tpu").warning(
            "fleet plane bring-up failed", exc_info=True)


def shutdown():
    """Tear down the world (reference: horovod_shutdown,
    horovod/common/operations.cc:2216-2224)."""
    with _state.lock:
        if not _state.initialized:
            return
        try:
            from horovod_tpu.core import engine as _engine

            _engine.shutdown_engine()
        except Exception:
            pass
        try:
            from horovod_tpu.ops import collectives as _coll

            _coll._ranked_program.cache_clear()
        except Exception:
            pass
        try:
            from horovod_tpu.core import fleet as _fleet

            _fleet.stop()
        except Exception:
            pass
        try:
            # Shutdown -> init re-entry (elastic reconfiguration rebuilds
            # the mesh in-process): cached concrete trees hold arrays of
            # the outgoing world — clear them with the mesh-keyed
            # programs so nothing pins the old Mesh/devices.
            from horovod_tpu import jax as _hjax

            _hjax._ZERO_TREES.clear()
        except Exception:
            pass
        _state.initialized = False
        _state.mesh = None
        _state.two_tier = None
        _state.devices = []
        _state.local_devices = []


atexit.register(shutdown)


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _Topology:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def size() -> int:
    """Total number of ranks (chips) in the world."""
    return _require_init().size


def rank() -> int:
    """Global rank of this process's first chip. Inside compiled SPMD code use
    ``horovod_tpu.ops.axis_rank()`` for the per-chip rank."""
    return _require_init().rank0


def local_size() -> int:
    """Number of chips THIS CONTROLLER drives (the mapping table above:
    local_comm = this process's ``jax.local_devices()``) — the
    per-process sizing knob examples use for their local batch."""
    return _require_init().local_size


def local_rank() -> int:
    """This controller's index among the controllers sharing its host
    (reference: the shared-memory-split local rank,
    operations.cc:1668-1705) — the owner key for per-host resources
    (cache dirs, log files, host-level data shards; see
    docs/running.md). 0 for the usual one controller per host; with two
    controllers on one machine they see 0 and 1."""
    return _require_init().local_rank


def local_num_processes() -> int:
    """Number of controller processes sharing this host."""
    return _require_init().host_num_processes


def cross_size() -> int:
    """Number of distinct hosts in the world (one controller per host —
    the common TPU layout — makes this equal to ``num_processes()``)."""
    return _require_init().cross_size


def cross_rank() -> int:
    """This host's index among the world's hosts. For a per-process id
    that is unique even with several controllers on one host, use
    :func:`process_index`."""
    return _require_init().cross_rank


def num_processes() -> int:
    return _require_init().num_processes


def process_index() -> int:
    return _require_init().process_index


def mesh():
    """The world ``jax.sharding.Mesh`` (1-D, axis name ``'hvd'``)."""
    return _require_init().mesh


def two_tier():
    """The (dcn, ici) world mesh, or None when the world has no two-tier
    structure (see :func:`_build_two_tier`)."""
    return _require_init().two_tier


def devices() -> list:
    return list(_require_init().devices)


def device_rank_axis() -> str:
    """Name of the mesh axis that enumerates ranks."""
    return HVD_AXIS


def is_homogeneous() -> bool:
    """Every process owns the same number of chips (reference:
    horovod/common/operations.cc:1686-1705 homogeneity check)."""
    return _require_init().homogeneous


def mpi_threads_supported() -> bool:
    """Reference API parity (horovod/common/operations.cc:2256-2262). There
    is no MPI on TPU; host threads may always call into the engine."""
    _require_init()
    return True
