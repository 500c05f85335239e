"""The system under test, built the way a user builds it (and
``chip_smoke.py`` does): ``hvd.init`` -> ``DistributedOptimizer(
fused_update=True)`` -> ``broadcast_parameters`` -> ``hvd.jax.jit``,
compiled ahead of time so that nothing can compile in the window.

Weights, optimizer state and the one resident batch are made on the
device from the seed, each by one jitted call whose outputs are laid out
over the mesh directly: what one device then holds more than the others
is the framework's doing, not the harness's. The benchmark sets no
``HVD_*`` variable: a cell runs the defaults a user gets.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

from benchmark.harness.optim import make_optimizer

#: Keys every traffic file carries. The last three are the levers of
#: ``DistributedOptimizer``; a cell that pulls one is a new JSON file.
TRAFFIC_KEYS = ("per_chip_batch", "steps_per_call", "unroll",
                "window_steps", "warmup_steps", "feed", "compression",
                "sharded_update", "state_dtype")


@dataclasses.dataclass
class Program:
    """The step and the makers of its arguments, before anything is on a
    device: what ``build`` materialises and ``aot_rehearsal.py`` only
    compiles."""

    train_step: Any          # hvd.jax.jit'ed; .lower(*state, *batch)
    init_weights: Callable   # key -> (params, extra)
    init_state: Callable     # key -> (params, extra, opt_state)
    make_batch: Callable     # key -> the global batch
    state_shardings: tuple
    batch_sharding: Any
    n_chips: int
    steps_per_call: int
    items_per_call: int      # items of the global batch x steps_per_call


@dataclasses.dataclass
class System:
    compiled: Any            # the AOT-compiled step
    state: tuple             # (params, extra, opt_state), donated per call
    batch: tuple             # the resident global batch, split over chips
    n_chips: int
    items_per_call: int
    steps_per_call: int
    build_s: dict            # seconds of each part of the build, by name
    mean_rank: float         # in-step allreduce(axis_rank), run in set-up
    hlo_text: str
    remake_weights: Callable  # () -> (params, extra) again from the seed


def _shardings(mesh, specs):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def program(cell, family) -> Program:
    """Define the cell's step over the initialised world (``hvd.init``
    comes first)."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax

    config, traffic = cell.config, cell.traffic
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise ValueError(f"traffic {cell.traffic_name!r} lacks {missing}")
    if traffic["feed"] != "resident":
        raise ValueError(
            f"traffic feed {traffic['feed']!r}: this harness keeps one "
            "resident batch on the device; another feed is a benchmark PR")

    mesh, n = hvd.mesh(), hvd.size()
    model = family.make_model(config, traffic)
    state_dtype = (None if traffic["state_dtype"] == "f32"
                   else traffic["state_dtype"])
    opt = hvd_jax.DistributedOptimizer(
        make_optimizer(config["optimizer"]),
        compression=hvd_jax.Compression.resolve(traffic["compression"]),
        fused_update=True, sharded_update=bool(traffic["sharded_update"]),
        state_dtype=state_dtype)
    n_global = int(traffic["per_chip_batch"]) * n

    def init_weights(key):
        return family.init_variables(model, key, config, traffic)

    def init_state(key):
        params, extra = init_weights(key)
        params = hvd_jax.cast_resident_params(params, state_dtype)
        return params, extra, opt.init(params)

    def make_batch(key):
        return family.make_batch(key, n_global, config, traffic)

    o_spec = (hvd_jax.sharded_state_specs(
        jax.eval_shape(init_state, jax.random.PRNGKey(0))[2])
        if traffic["sharded_update"] else P())
    state_specs = (P(), P(), o_spec)
    batch_spec = P(hvd_jax.HVD_AXIS)
    n_batch = len(jax.eval_shape(make_batch, jax.random.PRNGKey(0)))

    def one_step(params, extra, opt_state, *batch):
        (loss, extra), grads = jax.value_and_grad(
            lambda p: family.loss_fn(model, p, extra, batch),
            has_aux=True)(params)
        # Gradient exchange, numerics statistics and optimizer epilogue
        # under one name in the HLO's metadata. The trace's events do not
        # carry it (PERF.md section 3), so no metric reads it yet.
        with jax.named_scope("hvd_update"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, extra, opt_state, hvd_jax.allreduce(loss)

    spc = int(traffic["steps_per_call"])

    @hvd_jax.jit(in_specs=(*state_specs, *[batch_spec] * n_batch),
                 out_specs=(*state_specs, P()), donate_argnums=(0, 1, 2))
    def train_step(params, extra, opt_state, *batch):
        if spc == 1:
            return one_step(params, extra, opt_state, *batch)

        def body(carry, _):
            *carry, loss = one_step(*carry, *batch)
            return tuple(carry), loss

        carry, losses = jax.lax.scan(
            body, (params, extra, opt_state), None, length=spc,
            unroll=int(traffic["unroll"]))
        return (*carry, losses[-1])

    return Program(
        train_step=train_step, init_weights=init_weights,
        init_state=init_state, make_batch=make_batch,
        state_shardings=_shardings(mesh, state_specs),
        batch_sharding=_shardings(mesh, batch_spec), n_chips=n,
        steps_per_call=spc,
        items_per_call=(n_global * spc
                        * family.items_per_sample(config, traffic)))


def build(cell, family, seed: int, devices) -> System:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax

    marks = [("start", time.perf_counter())]

    def mark(name, *arrays):
        jax.block_until_ready(arrays)
        marks.append((name, time.perf_counter()))

    hvd.init(devices=list(devices))
    prog = program(cell, family)
    mark("init_and_define")
    k_init, k_batch = jax.random.split(jax.random.PRNGKey(seed))
    params, extra, opt_state = jax.jit(
        prog.init_state, out_shardings=prog.state_shardings)(k_init)
    mark("weights", params, opt_state)
    batch = jax.jit(prog.make_batch,
                    out_shardings=prog.batch_sharding)(k_batch)
    mark("batch", batch)
    # Start-up sync, as every user of the framework does before training.
    params = hvd_jax.broadcast_parameters(params, root_rank=0)
    mark("broadcast", params)

    compiled = prog.train_step.lower(
        params, extra, opt_state, *batch).compile()
    mark("compile")

    # adamw is blind to a sum in place of a mean, so the exchange is also
    # checked by value: the mean of the chips' ranks, outside the step.
    @hvd_jax.jit(in_specs=(P(hvd_jax.HVD_AXIS),), out_specs=P())
    def mean_rank(ones):
        return hvd_jax.allreduce(
            hvd_jax.axis_rank().astype(jnp.float32) * ones[0])

    rank = float(mean_rank(jax.device_put(
        jnp.ones((prog.n_chips,), jnp.float32), prog.batch_sharding)))
    mark("rank_check")
    build_s = {name: t - marks[i][1]
               for i, (name, t) in enumerate(marks[1:])}

    return System(
        compiled=compiled, state=(params, extra, opt_state), batch=batch,
        n_chips=prog.n_chips, items_per_call=prog.items_per_call,
        steps_per_call=prog.steps_per_call, build_s=build_s, mean_rank=rank, hlo_text=compiled.as_text(),
        remake_weights=lambda: jax.jit(prog.init_weights)(k_init))
