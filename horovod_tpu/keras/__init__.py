"""High-level trainer frontend — the role horovod.keras plays in the
reference (reference: horovod/keras/__init__.py, horovod/_keras/__init__.py).

Keras itself is not the compute stack on TPU; the equivalent surface is a
compiled flax/optax ``Trainer`` with the same integration points the
reference patches into Keras: a distributed optimizer wrapping gradient
reduction (reference: _keras/__init__.py:20-70 create_distributed_optimizer),
the callback suite (:mod:`horovod_tpu.keras.callbacks`), and
``load_model``/``save_model`` that round-trip the *wrapped* optimizer state
(reference: _keras/__init__.py:93-109).
"""

from __future__ import annotations

import collections.abc
import math
import os
import queue as _queue
import threading
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.common.topology import (  # noqa: F401
    HorovodInternalError, init, shutdown, is_initialized, size, rank,
    local_size, local_rank, cross_size, cross_rank, mesh, num_processes,
)
from horovod_tpu.jax import (
    DistributedOptimizer,  # noqa: F401 — same wrapper (reference binds P9 to keras)
    Compression,  # noqa: F401
    allreduce_pytree,
    broadcast_pytree,
    canonical_state_dtype as _canonical_state_dtype,
    cast_resident_params as _cast_resident_params,
    jit as _hvd_jit,
    sharded_state_specs as _sharded_state_specs,
    state_storage as _state_storage,
)
from horovod_tpu.jax import allreduce as _allreduce
from horovod_tpu.jax import numerics as _jnumerics
from horovod_tpu.jax.sharded import (
    drift_ulp as _drift_ulp,
    has_master_shards as _has_master_shards,
)
from horovod_tpu.core import elastic as _elastic
from horovod_tpu.core import numerics as _numerics
from horovod_tpu.core import preempt as _preempt
from horovod_tpu.core import sentinel as _sentinel
from horovod_tpu.core import telemetry as _tele
from horovod_tpu.core import timeline as _tl
from horovod_tpu.keras import callbacks  # noqa: F401
from horovod_tpu.ops import collectives as _ops
from horovod_tpu.ops.collectives import HVD_AXIS
from horovod_tpu.utils import checkpoint as _ckpt

import logging as _logging

_ELASTIC_LOG = _logging.getLogger("horovod_tpu.elastic.trainer")


def _default_loss(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


class _LazyLogs(collections.abc.MutableMapping):
    """Per-batch logs whose values stay device-resident until read.

    ``fit`` hands this to ``on_batch_end`` instead of a plain float dict
    so the training loop never blocks on a device fetch it doesn't need
    (the fetch would serialize the pipelined step dispatch). Reading a
    value — ``logs["loss"]``, ``.get``, ``.items()``, ``dict(logs)``,
    ``{**logs}``, ``logs.copy()`` — yields Python floats, so callbacks
    that json-serialize, type-check, copy, or accumulate keep the
    classic Keras contract; each value read costs one host round trip.
    Writes (``logs["lr"] = ...``, ``.update``) land in a host-side
    overlay that shadows the device value and, for the epoch's last
    batch, flows into the epoch logs/history — the same visibility a
    plain dict gave. Deliberately NOT a dict subclass: CPython's
    ``dict(d)``/``{**d}`` fast path would bypass ``__getitem__`` and
    leak device arrays.
    """

    def __init__(self, raw):
        self._raw = raw      # device-resident step outputs
        self._host = {}      # callback-written values (host objects)

    def __getitem__(self, k):
        if k in self._host:
            return self._host[k]
        return float(self._raw[k])

    def __setitem__(self, k, v):
        self._host[k] = v

    def __delitem__(self, k):
        found = k in self._host or k in self._raw
        if not found:
            raise KeyError(k)
        # Remove from BOTH layers: deleting a shadowed key must not
        # resurrect the underlying device value (plain-dict contract).
        self._host.pop(k, None)
        self._raw.pop(k, None)

    def __iter__(self):
        yield from self._raw
        for k in self._host:
            if k not in self._raw:
                yield k

    def __contains__(self, k):
        # Mapping's default is `self[k]` — a blocking device fetch for a
        # mere membership guard (`if "loss" in logs:`). Keep it free.
        return k in self._host or k in self._raw

    def __len__(self):
        return sum(1 for _ in self)

    def copy(self) -> dict:
        # Best-effort float coercion of callback-written values too: the
        # pre-_LazyLogs epoch logs applied float() to every value, and
        # history/json consumers rely on host floats (values float()
        # rejects are kept as written).
        out = {}
        for k in self:
            v = self[k]
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                out[k] = v
        return out

    def __repr__(self):
        return repr(self.copy())


class _SacrificialDispatcher:
    """Runs closures on a worker thread so the caller can ABANDON a call
    that wedged (elastic worlds, core/elastic.py).

    A peer dying at the wrong instant can block the runtime's dispatch
    path itself, synchronously, inside C++ — past any point where
    Python-level recovery could run. Dispatching from a sacrificial
    thread keeps the main thread free to observe the death verdict and
    reconfigure; a wedged worker is simply leaked along with the
    poisoned backend (it blocks with the GIL released, so it costs a
    thread, not the process)."""

    def __init__(self):
        self._req: "_queue.Queue" = _queue.Queue()
        self._res: "_queue.Queue" = _queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, name="hvd-elastic-dispatch", daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            fn = self._req.get()
            try:
                self._res.put(("ok", fn()))
            except BaseException as exc:  # surfaced to the caller
                self._res.put(("exc", exc))

    def call(self, fn, poll: Callable[[], None]):
        """Run ``fn()`` on the worker; ``poll()`` runs every few ms and
        may raise (the death-verdict escape) — the in-flight call is
        then abandoned and this dispatcher must be discarded."""
        self._req.put(fn)
        while True:
            try:
                kind, val = self._res.get(timeout=0.005)
            except _queue.Empty:
                poll()
                continue
            if kind == "exc":
                raise val
            return val


class Trainer:
    """Compiled data-parallel fit/evaluate loop over the world mesh.

    The training step (forward, backward, fused gradient allreduce,
    optimizer update) is one XLA program; callbacks run host-side between
    steps, mirroring Keras's contract in the reference.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        loss_fn: Callable = _default_loss,
        metrics: Sequence[str] = ("accuracy",),
        distributed: bool = True,
        compression=Compression.none,
        rng: int = 0,
        fused_update: bool = False,
        sharded_update: bool = False,
        state_dtype=None,
    ):
        """``fused_update``/``sharded_update`` forward to
        :func:`horovod_tpu.jax.DistributedOptimizer` — ``sharded_update``
        runs the optimizer on a 1/N shard of params/state per chip
        (reduce-scatter + all-gather; per-coordinate transforms only) and
        lays the optimizer state out ``P('hvd')`` in the compiled step.

        ``state_dtype='bf16'`` (HBM diet round 2): resident parameters
        are cast to bf16 at :meth:`build` (batch-norm statistics stay
        f32), the optimizer state is stored reduced, and — with
        ``sharded_update`` — f32 master weights ride the sharded state
        as each chip's 1/N shard; :meth:`load` rebuilds the bf16
        residents bitwise from the persisted masters."""
        self.model = model
        self._sharded_update = bool(sharded_update and distributed)
        self._state_dtype = _canonical_state_dtype(state_dtype)
        if distributed:
            optimizer = DistributedOptimizer(optimizer,
                                             compression=compression,
                                             fused_update=fused_update,
                                             sharded_update=sharded_update,
                                             state_dtype=state_dtype)
        elif self._state_dtype is not None:
            # Non-distributed trainer: the storage policy still applies
            # (no masters — see docs/troubleshooting.md on drift).
            optimizer = _state_storage(optimizer, self._state_dtype)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.metrics = tuple(metrics)
        self.rng = jax.random.PRNGKey(rng)
        self.params = None
        self.batch_stats = {}
        self.opt_state = None
        self.lr_scale = 1.0
        self.steps_per_epoch: Optional[int] = None
        self._train_step = None
        self._eval_step = None
        self._epoch = 0
        self._gstep = 0  # global step counter (numerics attribution)
        self._elastic_dispatcher: Optional[_SacrificialDispatcher] = None
        # Elastic: the previous step's donated state, parked until the
        # NEXT dispatcher call releases it on the worker thread —
        # releasing donated buffers can block inside a dead runtime, so
        # the main thread must never hold their last reference.
        self._elastic_graveyard: list = []

    # -- state ---------------------------------------------------------------

    def build(self, x_sample):
        """Initialize parameters from one (host) batch sample."""
        if self.params is not None:
            return
        self.rng, key = jax.random.split(self.rng)
        variables = self.model.init(
            {"params": key, "dropout": key}, jnp.asarray(x_sample), False)
        self.params = variables["params"]
        # Resident params at the policy width (identity when off); the
        # f32 masters (sharded_update) derive from these in
        # optimizer.init, so cast BEFORE init. BN statistics are outside
        # the param tree and stay f32.
        self.params = _cast_resident_params(self.params, self._state_dtype)
        self.batch_stats = dict(variables.get("batch_stats", {}))
        self.opt_state = self.optimizer.init(self.params)

    def broadcast_state(self, root_rank: int = 0):
        """Reference: BroadcastGlobalVariablesCallback on_train_begin.

        Hardened (r4, found by the smoke tier): the state is pulled to
        HOST before broadcasting, and the result is drained before
        returning. A second fit() used to hand the broadcast mesh-
        sharded train-step outputs with async work still in flight —
        the eager broadcast programs then recompiled for the new input
        layouts and their 8-device all-reduce wedged with only 5
        executions launched (XLA:CPU aborts the rendezvous after 40 s).
        ``device_get`` is itself a hard sync, and host leaves make every
        fit take the identical first-fit program path — no layout-driven
        recompiles, nothing concurrent in flight. The broadcast runs
        once per fit, so the host round trip is startup cost, not step
        cost."""
        host = jax.device_get((self.params, self.batch_stats,
                               self.opt_state))
        params, batch_stats, opt_state = host
        self.params = broadcast_pytree(params, root_rank)
        if batch_stats:
            self.batch_stats = broadcast_pytree(batch_stats, root_rank)
        self.opt_state = broadcast_pytree(opt_state, root_rank)
        jax.block_until_ready((self.params, self.batch_stats,
                               self.opt_state))
        # Consistency anchor (core/numerics.py): right after the sync
        # broadcast every process MUST digest identically — an eager
        # drain point, so the allgather is safe, and a mismatch here is
        # attributed before training compounds it.
        if _numerics.enabled() and num_processes() > 1:
            self.check_consistency(tag="broadcast_state")

    def check_consistency(self, tag: str = "params"):
        """Cross-rank state-consistency digest (core/numerics.py): every
        process digests its parameter/batch-stats buckets (crc32 + sum +
        nonfinite count per dtype), the digests are allgathered, and a
        mismatch yields an attributed ``diverged`` verdict + flight dump
        on EVERY process naming the deviating rank and bucket. A
        collective — call in lockstep on every process (fit calls it at
        epoch boundaries and after :meth:`broadcast_state`)."""
        return _numerics.check_consistency(
            {"params": self.params, "batch_stats": self.batch_stats},
            tag=tag, step=self._gstep)

    def _note_numerics(self, health):
        """Per-step host intake on the HVD_NUMERICS_EVERY cadence (every
        step under halt — a delayed check could not raise before the
        next poisoned update). The device_get is the only forced fetch
        the numerics layer adds to the loop, and only on checked
        steps."""
        pol = _numerics.policy()
        if pol == "off":
            return
        every = _numerics.check_every()
        if pol == "halt" or self._gstep % every == 0:
            _numerics.note_step_health(jax.device_get(health),
                                       step=self._gstep,
                                       origin="trainer")
        if (self._gstep % every == 0
                and _has_master_shards(self.opt_state)):
            # bf16 drift gauge: master↔resident max ULP per bucket (the
            # automated troubleshooting-ladder audit). Globalizing the
            # master shards is a collective in multi-controller worlds —
            # the step cadence is lockstep across processes.
            _numerics.note_drift(
                _drift_ulp(self.opt_state, self.params),
                step=self._gstep)

    def set_lr_scale(self, scale: float, momentum_correction: bool = False):
        """Scale the effective learning rate (callbacks drive this). With
        ``momentum_correction``, SGD momentum buffers are rescaled by
        ``new/old`` (Goyal et al.; reference: _keras/callbacks.py:104-113)."""
        old, self.lr_scale = self.lr_scale, float(scale)
        if momentum_correction and old != self.lr_scale and old != 0:
            factor = self.lr_scale / old
            self.opt_state = jax.tree_util.tree_map(
                lambda s: (s._replace(
                    trace=jax.tree_util.tree_map(
                        lambda t: t * factor, s.trace))
                    if isinstance(s, optax.TraceState) else s),
                self.opt_state,
                is_leaf=lambda s: isinstance(s, optax.TraceState))

    # -- compiled steps ------------------------------------------------------

    def _build_steps(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        want_acc = "accuracy" in self.metrics

        def forward(params, batch_stats, x, y, train, dropout_key):
            variables = {"params": params}
            if batch_stats:
                variables["batch_stats"] = batch_stats
            kwargs = {"rngs": {"dropout": dropout_key}} if train else {}
            if batch_stats and train:
                logits, mutated = model.apply(variables, x, train,
                                              mutable=["batch_stats"],
                                              **kwargs)
                new_bs = mutated["batch_stats"]
            else:
                logits = model.apply(variables, x, train, **kwargs)
                new_bs = batch_stats
            return loss_fn(logits, y), (logits, new_bs)

        def metrics_of(loss, logits, y):
            logs = {"loss": _allreduce(loss)}
            if want_acc:
                acc = jnp.mean(jnp.argmax(logits, -1) == y)
                logs["accuracy"] = _allreduce(acc)
            return logs

        # Sharded update: each chip carries its 1/N block of the flat
        # optimizer-state buffers instead of a replicated copy.
        ospec = (_sharded_state_specs(self.opt_state)
                 if self._sharded_update else P())

        # donate_argnums: params/batch_stats/opt_state are rebound to the
        # step's outputs every batch, so XLA may update them in place —
        # without donation every param-sized buffer pays a copy-on-update
        # each step. Callbacks run AFTER the rebind and therefore always
        # see live buffers.
        # Master-shard layout (state_dtype + sharded_update): the f32
        # masters advance INSIDE opt.update and the returned tree is only
        # a re-anchored resident delta, so the post-hoc `updates *
        # lr_scale` below would be overwritten by the next step's
        # re-anchor — the scale must ride into the epilogue instead
        # (shard_update's reserved `lr_scale` extra arg).
        scale_inside = (self._state_dtype is not None
                        and self._sharded_update)

        # Numerics observatory (core/numerics.py): the optimizer wrapper
        # computes in-step gradient health and stashes it mid-trace;
        # collect it HERE (same trace) and return it device-resident in
        # the logs — the host fetches on the HVD_NUMERICS_EVERY cadence.
        # Read at build time: the compiled program either carries the
        # stats or (policy off) lowers to the identical pre-numerics HLO.
        num_on = _numerics.enabled()

        @_hvd_jit(in_specs=(P(), P(), ospec, P(HVD_AXIS), P(HVD_AXIS), P(),
                            P()),
                  out_specs=(P(), P(), ospec, P()),
                  donate_argnums=(0, 1, 2))
        def train_step(params, batch_stats, opt_state, x, y, lr_scale,
                       dropout_key):
            (loss, (logits, new_bs)), grads = jax.value_and_grad(
                forward, has_aux=True)(params, batch_stats, x, y, True,
                                       dropout_key)
            prev_state = opt_state
            if scale_inside:
                updates, opt_state = opt.update(grads, opt_state, params,
                                                lr_scale=lr_scale)
            else:
                updates, opt_state = opt.update(grads, opt_state, params)
                updates = jax.tree_util.tree_map(lambda u: u * lr_scale,
                                                 updates)
            logs = metrics_of(loss, logits, y)
            if num_on:
                health = _jnumerics.collect_traced()
                if health is None:
                    # Fallback (the optimizer wrapper did not run — e.g.
                    # distributed=False): gradient health straight from
                    # the local grads, psum'd over the rank axis when one
                    # is bound so a NaN on ANY rank is seen identically
                    # everywhere (host-side reads of a replicated output
                    # only ever see device 0's tile). Under halt the
                    # guard must run HERE — the wrapper's guard did not.
                    ax = (_ops.rank_axes()
                          if _ops.in_spmd(loss) else None)
                    stats = _jnumerics.tree_stats(grads, ax=ax)
                    per_rank = (_jnumerics.per_rank_nonfinite(grads, ax)
                                if ax is not None else None)
                    health = _jnumerics.health_of(stats, per_rank)
                    if _numerics.policy() == "halt":
                        finite = _jnumerics.all_finite(stats)
                        updates = _jnumerics.guard_updates(finite,
                                                           updates)
                        opt_state = _jnumerics.guard_state(
                            finite, opt_state, prev_state)
                new_params = optax.apply_updates(params, updates)
                # Masterless-drift gauge input (fused.state_storage
                # caveat): update/param norm ratio per step.
                health["update_norm"] = _jnumerics.norm(updates)
                health["param_norm"] = _jnumerics.norm(new_params)
                logs["_numerics"] = health
            else:
                new_params = optax.apply_updates(params, updates)
            return new_params, new_bs, opt_state, logs

        @_hvd_jit(in_specs=(P(), P(), P(HVD_AXIS), P(HVD_AXIS)),
                  out_specs=P())
        def eval_step(params, batch_stats, x, y):
            loss, (logits, _) = forward(params, batch_stats, x, y, False,
                                        jax.random.PRNGKey(0))
            return metrics_of(loss, logits, y)

        self._train_step, self._eval_step = train_step, eval_step

    # -- data plumbing -------------------------------------------------------

    def _shard(self, arr):
        """Place this controller's host batch over its local chips, forming
        the (global_batch, ...) mesh-sharded array."""
        m = mesh()
        nloc = local_size()
        per = arr.shape[0] // nloc
        shards = [
            jax.device_put(arr[i * per:(i + 1) * per], d)
            for i, d in enumerate(m.local_mesh.devices.flat)
        ]
        shape = (per * size(),) + arr.shape[1:]
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(m, P(HVD_AXIS)), shards)

    def _batches(self, x, y, batch_size, shuffle, seed):
        n_local = batch_size * local_size()
        steps = len(x) // n_local
        idx = np.arange(steps * n_local)
        if shuffle:
            np.random.default_rng(seed).shuffle(idx)
        for s in range(steps):
            sel = idx[s * n_local:(s + 1) * n_local]
            yield self._shard(x[sel]), self._shard(y[sel])

    # -- public API ----------------------------------------------------------

    def fit(self, x, y, batch_size: int = 32, epochs: int = 1,
            callbacks: Sequence = (), validation_data=None,
            initial_epoch: int = 0, shuffle: bool = True,
            verbose: int = 0) -> dict:
        """Train; returns a history dict of per-epoch logs. ``x``/``y`` are
        this process's host arrays; ``batch_size`` is per chip (global
        batch = batch_size * size), matching the reference examples'
        convention.

        ``on_batch_end`` receives a :class:`_LazyLogs` mapping — values
        are fetched from device only when read (reads yield Python
        floats; writes land in a host overlay that reaches the epoch
        history). ``on_epoch_end`` receives a plain float dict.

        With ``HVD_ELASTIC=1`` (core/elastic.py) the loop survives rank
        loss: a death verdict raises out of the epoch, the world is
        reconfigured (mesh over survivors, fresh compiled steps), the
        newest elastic checkpoint is restored and broadcast, and
        training continues at the restored epoch — a recompile, not a
        crash. Epoch boundaries write the elastic checkpoint and honor
        supervisor restart requests (rejoin/regrow)."""
        x, y = np.asarray(x), np.asarray(y)
        x_sample = x[:batch_size * max(local_size(), 1)]
        self.build(x_sample)
        if self._train_step is None:
            self._build_steps()
        self.steps_per_epoch = len(x) // (batch_size * local_size())
        for cb in callbacks:
            cb.set_trainer(self)
        history: dict = {}
        for cb in callbacks:
            cb.on_train_begin()
        # Graceful preemption intake (core/preempt.py): SIGTERM — the
        # TPU maintenance/eviction signal — is polled at every batch
        # boundary; when it lands, the epoch raises and the ladder below
        # drains the step, checkpoints, barriers, and exits 0.
        _preempt.install()
        elastic_on = _elastic.active()
        if elastic_on:
            # A new fit revokes any standing completion mark (peers
            # resume leasing us), and train end announces completion so
            # the last rank to finish is not verdicted dead.
            _elastic.get_world().announce_active()
        epoch = initial_epoch
        while epoch < epochs:
            try:
                self._run_epoch(epoch, x, y, batch_size, shuffle,
                                callbacks, validation_data, history,
                                verbose, elastic_on)
            except _preempt.PreemptRequested:
                self._graceful_preempt(epoch)  # exits 0; no return
            except _elastic.WorldChanged:
                if not elastic_on:
                    raise
                _ELASTIC_LOG.warning(
                    "elastic recovery: death verdict observed at epoch "
                    "%d; reconfiguring", epoch)
                epoch = self._elastic_recover(x_sample)
                # Recovery replays every epoch since the newest
                # checkpoint: drop the replayed epochs' history entries
                # so each index keeps exactly one record. (Epoch-indexed
                # callbacks still observe a replayed epoch twice — the
                # documented cost of checkpoint-granularity recovery.)
                for k in history:
                    del history[k][max(0, epoch - initial_epoch):]
                continue
            epoch += 1
        for cb in callbacks:
            cb.on_train_end()
        if elastic_on:
            _elastic.get_world().announce_done()
        return history

    def _run_epoch(self, epoch, x, y, batch_size, shuffle, callbacks,
                   validation_data, history, verbose, elastic_on):
        self._epoch = epoch
        for cb in callbacks:
            cb.on_epoch_begin(epoch)
        lazy = _LazyLogs({})
        batches = self._batches(x, y, batch_size, shuffle, seed=epoch)
        nxt, b = next(batches, None), 0
        prev_step = None  # elastic: last step's device loss (readiness)
        while nxt is not None:
            if _preempt.requested():
                # Batch boundary: the last dispatched step is the one
                # the ladder drains; no new work is dispatched into a
                # world about to be evicted.
                raise _preempt.PreemptRequested()
            xb, yb = nxt
            for cb in callbacks:
                cb.on_batch_begin(b)
            if elastic_on:
                # Never dispatch into a world with a death verdict (the
                # collective would wedge behind the dead peer), and keep
                # the in-flight window at ONE step: the runtime's
                # dispatch queue is finite, and a deeper backlog behind
                # a dead peer's collective blocks the dispatch call
                # itself — past any point where recovery could run. The
                # one-step lag keeps the device busy (step N executes
                # while the host preps N+1); only the await's poll
                # granularity is added latency.
                self._elastic_guard()
                self._elastic_await(prev_step)
            t_step = time.perf_counter()
            # The split stays on the main thread (tiny, non-donating —
            # cannot wedge) so the worker closure below never mutates
            # trainer state: an abandoned call that unwedges after
            # recovery must have nothing to clobber.
            self.rng, dk = jax.random.split(self.rng)

            # Everything the step touches is bound at CLOSURE CREATION
            # (default args), not call time: an abandoned worker that
            # unwedges after recovery then re-dispatches only into the
            # OLD world's objects — it can never reach the rebuilt step
            # or the recovered state.
            def _one_step(xb=xb, yb=yb, dk=dk, step_fn=self._train_step,
                          params=self.params, bs=self.batch_stats,
                          opt=self.opt_state,
                          grave=self._elastic_graveyard):
                if elastic_on:
                    # Release the PREVIOUS step's parked donated state
                    # here, on the abandonable worker: dropping buffers
                    # donated into an execution wedged behind a dead
                    # peer blocks inside the runtime.
                    grave.clear()
                return step_fn(params, bs, opt, xb, yb,
                               jnp.float32(self.lr_scale), dk)

            try:
                out = (self._elastic_call(_one_step) if elastic_on
                       else _one_step())
                if elastic_on:
                    # Park-then-rebind: the old references stay alive in
                    # the graveyard, so these assignments never run a
                    # (possibly blocking) destructor on the main thread
                    # — and the worker never mutates trainer state, so
                    # an abandoned call that completes later cannot
                    # clobber a recovered world.
                    self._elastic_graveyard.append(
                        (self.params, self.batch_stats, self.opt_state))
                self.params, self.batch_stats, self.opt_state, logs = out
            except Exception as exc:
                self._elastic_translate(exc, elastic_on)
                raise
            # Compiled-path telemetry: dispatch time of the whole step
            # program (execution is async — the ring records the host
            # cost of handing work to the runtime; wall step time
            # shows up in the inter-dispatch cadence).
            t_step = time.perf_counter() - t_step
            _tele.REGISTRY.counter("trainer.steps").inc()
            _tele.REGISTRY.ring("trainer.step_s").push(t_step)
            # Performance sentinel: the wall step time feeds the
            # trainer watchdog (anomaly -> flight dump + bounded
            # capture + attributed verdict) and drives periodic
            # auto-capture (HVD_PROFILE_DIR) — see core/sentinel.py.
            _sentinel.observe_step(t_step, origin="trainer")
            # Prefetch: the step above dispatched asynchronously;
            # pulling the next batch NOW overlaps its host->device
            # transfers with the running step (the role tf.data
            # prefetching plays for reference keras users — without
            # it, per-batch feed+fetch serializes with compute:
            # the device-resident logs below remove the other
            # per-batch sync).
            nxt = next(batches, None)
            # Numerics: pop the device-resident health dict BEFORE
            # the logs proxy (callbacks must not see — or float() —
            # the per-rank vector); checked on the numerics cadence.
            self._gstep += 1
            health = (logs.pop("_numerics", None)
                      if isinstance(logs, dict) else None)
            if health is not None:
                if elastic_on:
                    # The intake device_gets this step's health — a
                    # blocking fetch that wedges on a step the dead peer
                    # never joins; dispatcher-routed like the step.
                    self._elastic_call(
                        lambda h=health: self._note_numerics(h))
                else:
                    self._note_numerics(health)
            # Batch logs stay device-resident (fetching every batch
            # costs a full host round trip); the proxy converts any
            # value a callback actually reads to a Python float at
            # that moment, so float-expecting callbacks keep working
            # and pay only for what they read.
            if elastic_on and isinstance(logs, dict):
                prev_step = logs.get("loss")
            lazy = _LazyLogs(logs)
            if elastic_on and callbacks:
                # A callback reading lazy logs performs a blocking
                # device fetch of this step's outputs — dispatcher-
                # routed like every other fetch that could wedge behind
                # a dead peer.
                self._elastic_call(
                    lambda b=b, lazy=lazy: [cb.on_batch_end(b, lazy)
                                            for cb in callbacks])
            else:
                for cb in callbacks:
                    cb.on_batch_end(b, lazy)
            b += 1
        # Epoch logs come from the last batch's view INCLUDING any
        # callback writes (plain-dict behavior before _LazyLogs).
        try:
            logs = (self._elastic_epoch_logs(lazy) if elastic_on
                    else lazy.copy())
            # Epoch boundary = eager drain point: report the (already
            # host-visible) loss to the sentinel for perf.jsonl's
            # final_loss column, and run the cross-rank consistency
            # digest when there is more than one controller to diverge.
            if "loss" in logs:
                _sentinel.note_loss(logs["loss"])
            if _numerics.enabled() and num_processes() > 1:
                # A collective: in elastic mode it runs on the
                # sacrificial dispatcher so a peer dying mid-digest
                # cannot wedge the loop past recovery.
                if elastic_on:
                    self._elastic_call(
                        lambda: self.check_consistency(tag="epoch_end"))
                else:
                    self.check_consistency(tag="epoch_end")
            if validation_data is not None:
                # Collectives + blocking metric fetches: dispatcher-
                # routed in elastic mode for the same wedge-proofing as
                # the train step.
                if elastic_on:
                    val = self._elastic_call(
                        lambda: self.evaluate(*validation_data,
                                              batch_size=batch_size))
                else:
                    val = self.evaluate(*validation_data,
                                        batch_size=batch_size)
                logs.update({f"val_{k}": v for k, v in val.items()})
        except Exception as exc:
            self._elastic_translate(exc, elastic_on)
            raise
        for cb in callbacks:
            cb.on_epoch_end(epoch, logs)
        for k, v in logs.items():
            history.setdefault(k, []).append(v)
        if verbose:
            print(f"epoch {epoch}: " +
                  " ".join(f"{k}={v:.4f}" for k, v in logs.items()))
        if elastic_on:
            self._elastic_epoch_boundary(epoch)

    # -- elastic worlds (core/elastic.py) ------------------------------------

    def _elastic_guard(self):
        if _elastic.get_world().world_changed():
            raise _elastic.WorldChanged()

    def _elastic_call(self, fn):
        """Run ``fn`` on the sacrificial dispatcher, polling for a death
        verdict: the call is abandoned (and the dispatcher discarded for
        a fresh one) the moment the world changes under it."""
        if self._elastic_dispatcher is None:
            self._elastic_dispatcher = _SacrificialDispatcher()
        try:
            return self._elastic_dispatcher.call(fn, self._elastic_guard)
        except _elastic.WorldChanged:
            # The in-flight call may be wedged inside the dead world's
            # runtime forever — never reuse this worker.
            self._elastic_dispatcher = None
            raise

    def _elastic_await(self, arr):
        """Bounded-in-flight await: poll one device value's readiness,
        bailing to recovery the moment a death verdict lands. A plain
        blocking fetch would sit inside a collective the dead peer never
        joins; deeper dispatch queues wedge the dispatch call itself."""
        if arr is None:
            return
        is_ready = getattr(arr, "is_ready", None)
        if is_ready is None:
            return
        w = _elastic.get_world()
        while True:
            if w.world_changed():
                raise _elastic.WorldChanged()
            try:
                if is_ready():
                    return
            except Exception:
                return  # errored buffer: the step's own fetch surfaces it
            time.sleep(0.005)

    def _elastic_translate(self, exc: Exception, elastic_on: bool):
        """A step/fetch raised: when a death verdict explains it (or
        arrives within a couple of leases — the runtime error usually
        beats the heartbeat), convert to WorldChanged so fit recovers
        instead of crashing."""
        if isinstance(exc, _elastic.WorldChanged) or not elastic_on:
            return
        w = _elastic.get_world()
        if w.world_changed() or w.await_verdict(_elastic.verdict_wait_s()):
            raise _elastic.WorldChanged() from exc

    def _elastic_epoch_logs(self, lazy) -> dict:
        """Epoch-end fetch that cannot wedge on a dead world: poll the
        device values' readiness, bailing to recovery the moment a death
        verdict lands (a blocking fetch would sit inside a collective
        the dead peer never joins)."""
        w = _elastic.get_world()
        for v in list(lazy._raw.values()):
            is_ready = getattr(v, "is_ready", None)
            if is_ready is None:
                continue
            while True:
                if w.world_changed():
                    raise _elastic.WorldChanged()
                try:
                    if is_ready():
                        break
                except Exception:
                    break  # the copy below surfaces the real error
                time.sleep(0.05)
        return lazy.copy()

    def _elastic_epoch_boundary(self, epoch: int):
        """Elastic bookkeeping at the epoch drain point: write the
        checkpoint recovery resumes from, then honor a pending
        supervisor restart request (rejoin admission / regrow)."""
        d = _elastic.checkpoint_dir()
        if d:
            try:
                # save() globalizes sharded state (a collective) and
                # fetches device buffers — dispatcher-routed for the
                # same wedge-proofing as the step itself.
                self._elastic_call(lambda: self.save(d, step=epoch))
            except Exception as exc:
                self._elastic_translate(exc, True)
                raise
        req = _elastic.get_world().restart_requested()
        if req:
            _elastic.get_world().exit_for_restart(req)

    # -- graceful preemption (core/preempt.py) -------------------------------

    def _graceful_preempt(self, epoch: int):
        """The planned-eviction ladder: finish (or deadline-abort) the
        in-flight step, quiesce the engine (admission closed, /healthz
        ``draining``), write the crash-atomic emergency checkpoint,
        rendezvous with the peers at the drain barrier, journal a
        ``preempted`` note, and exit 0. Every rung is bounded — a rung
        wedged behind a dead peer is abandoned, never waited out (the
        launcher's ``--grace-s`` SIGKILL escalation is the backstop).
        Does not return."""
        why = _preempt.reason() or "preemption requested"
        deadline = _preempt.step_deadline_s()
        _ELASTIC_LOG.warning(
            "graceful preemption (%s): draining the current step, "
            "checkpointing, and exiting cleanly", why)
        state = (self.params, self.batch_stats, self.opt_state)
        drained, _ = _preempt.bounded(
            lambda: jax.block_until_ready(state), deadline,
            "in-flight step drain")
        from horovod_tpu.core import engine as _eng

        _eng.quiesce_engine(min(deadline, 5.0),
                            reason=f"graceful preemption ({why})")
        ckpt_dir = _elastic.checkpoint_dir()
        ckpt_path = None
        if ckpt_dir:
            # Crash-atomic by construction (utils/checkpoint.py: tmp +
            # fsync + rename): an escalated SIGKILL mid-save can never
            # corrupt the newest checkpoint a relaunch resumes from.
            ok, ckpt_path = _preempt.bounded(
                lambda: self.save(ckpt_dir, step=epoch), deadline,
                "emergency checkpoint")
            if not ok:
                _ELASTIC_LOG.error(
                    "graceful preemption: emergency checkpoint did not "
                    "complete; the relaunch resumes from the previous "
                    "one")
        else:
            _ELASTIC_LOG.warning(
                "graceful preemption: no checkpoint dir configured "
                "(HVD_CHECKPOINT_DIR / HVD_ELASTIC_DIR) — exiting "
                "without an emergency checkpoint")
        if _elastic.active():
            # A preempting rank going silent must read as a PLANNED
            # exit to its peers' lease, not a casualty.
            _elastic.get_world().announce_done()
        barriered = _preempt.drain_barrier()
        note = _preempt.journal_note(
            epoch=epoch, step=self._gstep,
            checkpoint=ckpt_path, step_drained=bool(drained),
            barrier_ok=bool(barriered))
        _ELASTIC_LOG.warning(
            "graceful preemption complete: step_drained=%s checkpoint=%s"
            " barrier_ok=%s note=%s — exiting 0", bool(drained),
            ckpt_path or "none", bool(barriered), note or "none")
        # The stdout marker the launcher/operator (and the chaos tier)
        # greps for; os._exit because interpreter teardown in a
        # multi-process world mid-eviction can hang in distributed-
        # client destructors (the exit_for_restart precedent).
        print(f"PREEMPTED rank={_tl._process_index()} epoch={epoch} "
              f"ckpt={'yes' if ckpt_path else 'no'} exiting=0",
              flush=True)
        try:
            import sys

            sys.stdout.flush()
            sys.stderr.flush()
        except Exception:
            pass
        os._exit(0)

    def _elastic_recover(self, x_sample) -> int:
        """Death-verdict recovery: reconfigure the world (in-place
        shrink, or exit for a supervisor-coordinated restart), rebuild
        the compiled steps over the new mesh, and resume from the newest
        checkpoint via the host-first broadcast pattern. Returns the
        epoch to resume at."""
        w = _elastic.get_world()
        self._elastic_dispatcher = None  # may be wedged in the old world
        try:
            w.reconfigure()
        except _elastic.ElasticRestartRequired as exc:
            w.exit_for_restart(str(exc))  # no return
        except Exception as exc:
            # A blown rebuild must DEGRADE to the coordinated restart,
            # never crash out of fit: an unhandled exception here would
            # reach interpreter exit, whose jax atexit hook calls
            # distributed.shutdown() — a barrier that wedges forever
            # against a dead/partial world (measured) — and the
            # supervisor would wait on the zombie instead of
            # relaunching.
            _ELASTIC_LOG.error("elastic reconfiguration failed",
                               exc_info=True)
            w.exit_for_restart(f"reconfiguration failed: {exc}")
        _ELASTIC_LOG.warning("elastic recovery: world reconfigured "
                             "(epoch %d); rebuilding steps and restoring "
                             "the newest checkpoint", w.epoch)
        # Fresh programs + fresh state on the new backend: everything
        # from the old world (including the RNG key, an old-backend
        # array) is unusable. The old references are PARKED, not
        # dropped — releasing state donated into a wedged execution can
        # block inside the dead runtime. The graveyard (previous step's
        # donated state awaiting worker-side release) is parked whole
        # for the same reason.
        w.park((self.params, self.batch_stats, self.opt_state,
                self.rng, self._elastic_graveyard))
        self._elastic_graveyard = []
        self._train_step = self._eval_step = None
        self.rng = jax.random.PRNGKey(997 + int(w.epoch))
        self.params = None
        self.batch_stats = {}
        self.opt_state = None
        self.build(x_sample)
        # Same restore-and-resume path the regrown world uses at
        # startup (newest checkpoint -> host-first broadcast -> resume
        # at the restored epoch + 1).
        resume = _elastic.maybe_restore(self, x_sample)
        self._build_steps()
        if resume:
            return resume
        # No checkpoint to resume from: reinitialize (the loss curve
        # restarts — elastic training should checkpoint every epoch,
        # which fit does automatically when a checkpoint dir is set).
        self.broadcast_state()
        return self._epoch

    def evaluate(self, x, y, batch_size: int = 32) -> dict:
        x, y = np.asarray(x), np.asarray(y)
        self.build(x[:batch_size * max(local_size(), 1)])
        if self._eval_step is None:
            self._build_steps()
        totals: dict = {}
        steps = 0
        for xb, yb in self._batches(x, y, batch_size, False, 0):
            logs = self._eval_step(self.params, self.batch_stats, xb, yb)
            for k, v in logs.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            steps += 1
        return {k: v / max(steps, 1) for k, v in totals.items()}

    def predict(self, x, batch_size: int = 32):
        x = np.asarray(x)
        outs = [np.asarray(self.model.apply(
            {"params": self.params, **({"batch_stats": self.batch_stats}
                                       if self.batch_stats else {})},
            jnp.asarray(x[i:i + batch_size]), False))
            for i in range(0, len(x), batch_size)]
        return np.concatenate(outs) if outs else np.zeros((0,))

    # -- persistence (reference: hvd.load_model, _keras/__init__.py:93-109) --

    def state_dict(self) -> dict:
        return {"params": self.params, "batch_stats": self.batch_stats,
                "opt_state": self.opt_state, "epoch": self._epoch,
                "lr_scale": self.lr_scale}

    def save(self, directory: str, step: Optional[int] = None):
        """Write a checkpoint (process 0 only; atomic)."""
        return _ckpt.save_checkpoint(
            directory, self.state_dict(),
            self._epoch if step is None else step)

    def load(self, path: str, x_sample, root_rank: int = 0):
        """Restore params + *wrapped* optimizer state and broadcast from
        root so all ranks resume identically.

        A checkpoint that does not match this Trainer's model/optimizer
        raises a ValueError naming the mismatched entries — flax's
        from_bytes restores wrong-SHAPED leaves silently (the error
        would otherwise surface steps later as a cryptic XLA shape
        failure), and a wrong STRUCTURE raises a flax KeyError with no
        model context (r4 verdict weak #4)."""
        self.build(x_sample)
        try:
            restored = _ckpt.load_checkpoint(path, self.state_dict(),
                                             root_rank=root_rank)
        except (OSError, HorovodInternalError):
            raise  # missing file / dead peer are NOT structure problems
        except Exception as exc:
            raise ValueError(
                f"checkpoint {path!r} does not match this Trainer's "
                f"model/optimizer structure: {exc}") from exc
        mism = _signature_mismatches(self.state_dict(), restored)
        if mism:
            shown = "; ".join(mism[:5])
            more = f" (+{len(mism) - 5} more)" if len(mism) > 5 else ""
            raise ValueError(
                f"checkpoint {path!r} does not match this Trainer's "
                f"model: {shown}{more}")
        # Mixed layout: the f32 master shards are the persisted source
        # of truth — rebuild the bf16 residents from them so resident ==
        # cast(master) bitwise after the restore (no-op without masters).
        restored = _ckpt.rebuild_resident_params(restored)
        self.params = restored["params"]
        self.batch_stats = restored["batch_stats"]
        self.opt_state = restored["opt_state"]
        self._epoch = int(restored["epoch"])
        self.lr_scale = float(restored["lr_scale"])
        return self


def _signature_mismatches(expected, restored) -> list:
    """Per-leaf (shape, dtype) comparison of two same-structure pytrees;
    returns human-readable mismatch descriptions (checkpoint vs model)."""
    import jax.tree_util as jtu

    out = []
    exp = {jtu.keystr(kp): v
           for kp, v in jtu.tree_flatten_with_path(expected)[0]}
    got = {jtu.keystr(kp): v
           for kp, v in jtu.tree_flatten_with_path(restored)[0]}
    for key in sorted(set(exp) | set(got)):
        if key not in got:
            out.append(f"{key}: missing from checkpoint")
        elif key not in exp:
            out.append(f"{key}: not in model")
        else:
            se, sg = np.shape(exp[key]), np.shape(got[key])
            if se != sg:
                out.append(f"{key}: checkpoint shape {sg} vs model {se}")
                continue
            # dtype only for real arrays: python-scalar metadata (epoch,
            # lr_scale) legitimately narrows through the msgpack round
            # trip (int64->int32), which is not a model mismatch.
            if se != ():
                de = np.asarray(exp[key]).dtype
                dg = np.asarray(got[key]).dtype
                if de != dg:
                    out.append(
                        f"{key}: checkpoint dtype {dg} vs model {de}")
    return out


def save_model(trainer: Trainer, directory: str,
               step: Optional[int] = None):
    return trainer.save(directory, step)


def load_model(path: str, model, optimizer, x_sample, **trainer_kwargs):
    """Reconstruct a Trainer with a distributed-wrapped optimizer from a
    checkpoint — the reference's ``hvd.load_model`` wraps the deserialized
    optimizer in the same way (reference: _keras/__init__.py:93-109)."""
    t = Trainer(model, optimizer, **trainer_kwargs)
    return t.load(path, x_sample)
