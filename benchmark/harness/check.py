"""What decides ``correct``: the plain reference's losses and the checks
that the step ran where and how the cell says.

The reference is the same model, seed and global batch through plain
``jax.value_and_grad`` and the plain optax optimizer on one device, in
float32 at ``jax.default_matmul_precision("highest")``. It accumulates
the global batch's gradient over micro-batches of one chip's share,
which keeps batch norm per chip as the system has it and lets one chip
hold what four computed.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

from benchmark.harness import hlo
from benchmark.harness.optim import make_optimizer

REFERENCE_STEPS = 3


def reference_step(cell, reference, n_global: int):
    """(step, optimizer): ``step(params, extra, opt_state, batch)`` ->
    (params, opt_state, loss) over the global batch of ``n_global``
    samples, one micro-batch per chip's share."""
    import jax
    import jax.numpy as jnp
    import optax

    micro = int(cell.traffic["per_chip_batch"])
    n_micro = n_global // micro
    opt = make_optimizer(cell.config["optimizer"])

    def step(params, extra, opt_state, batch):
        parts = jax.tree.map(
            lambda a: a.reshape(n_micro, micro, *a.shape[1:]), batch)

        def body(acc, part):
            out = jax.value_and_grad(reference.loss)(
                params, extra, part, cell.config)
            return jax.tree.map(jnp.add, acc, out), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (loss, grads), _ = jax.lax.scan(body, zero, parts)
        loss, grads = jax.tree.map(lambda x: x / n_micro, (loss, grads))
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step, opt


def reference_losses(cell, reference, system, device) -> Dict:
    """The reference's losses at the steps whose loss the system's first
    ``REFERENCE_STEPS`` calls return (every step, or the last of each
    scan-fused call), on weights made again from the seed (the system's
    were donated)."""
    import jax

    t0 = time.perf_counter()
    params, extra = jax.device_put(system.remake_weights(), device)
    batch = jax.device_put(system.batch, device)
    step, opt = reference_step(cell, reference, batch[0].shape[0])
    with jax.default_matmul_precision("highest"):
        opt_state = jax.jit(opt.init)(params)
        compiled = jax.jit(step, donate_argnums=(0, 2)).lower(
            params, extra, opt_state, batch).compile()
        losses = []
        for _ in range(REFERENCE_STEPS * system.steps_per_call):
            params, opt_state, loss = compiled(params, extra, opt_state,
                                               batch)
            losses.append(float(loss))
    return {"losses": losses[system.steps_per_call - 1::system.steps_per_call],
            "seconds": time.perf_counter() - t0}


def verdict(cell, system, first_losses: List[float],
            window_losses: List[float], ref_losses: List[float],
            interpreted_kernels, on_tpu: bool) -> Dict[str, bool]:
    """Every check by name; ``correct`` is their conjunction. The one
    about pallas kernels holds on a TPU only: off it (the tests' tiny
    cells) the kernels are interpreted by design."""
    n = system.n_chips
    tolerance = cell.config["loss_tolerance"]["abs"]
    fetched = first_losses + window_losses
    checks = {
        "losses_finite": all(math.isfinite(x) for x in fetched),
        "loss_fell": fetched[-1] < fetched[0],
        "reference": all(abs(a - b) <= tolerance for a, b in
                         zip(first_losses[:REFERENCE_STEPS], ref_losses)),
        "batch_on_every_chip": len(
            {s.device for s in system.batch[0].addressable_shards}) == n,
        "mean_rank": system.mean_rank == (n - 1) / 2,
    }
    if n > 1:
        checks["all_reduce_spans_world"] = (
            hlo.all_reduce_group(system.hlo_text) == n)
    if on_tpu:
        checks["kernels_compiled"] = not interpreted_kernels
    return checks
