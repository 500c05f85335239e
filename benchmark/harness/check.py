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
            interpreted_kernels, on_tpu: bool) -> Dict[str, Dict]:
    """Every check by name, as the number compared beside its limit:
    ``{"value", "limit", "ok"}``, where ``ok`` is ``value <= limit``
    (the loss has to fall, so there ``<``). ``correct`` is the
    conjunction of the ``ok``. The one about pallas kernels holds on a
    TPU only: off it (the tests' tiny cells) the kernels are interpreted
    by design."""
    n = system.n_chips
    fetched = first_losses + window_losses
    gaps = [abs(a - b) if math.isfinite(a - b) else math.inf for a, b in
            zip(first_losses[:REFERENCE_STEPS], ref_losses)]
    numbers = {
        # counts of what must not be there
        "losses_finite": (sum(not math.isfinite(x) for x in fetched), 0),
        "loss_fell": (fetched[-1] - fetched[0], 0.0),
        # the widest of the first losses' gaps to the reference's
        "reference": (max(gaps), cell.config["loss_tolerance"]["abs"]),
        "batch_on_every_chip": (n - len(
            {s.device for s in system.batch[0].addressable_shards}), 0),
        "mean_rank": (abs(system.mean_rank - (n - 1) / 2), 0.0),
    }
    if n > 1:  # chips the widest all-reduce of the step leaves out
        numbers["all_reduce_spans_world"] = (
            n - hlo.all_reduce_group(system.hlo_text), 0)
    if on_tpu:
        numbers["kernels_compiled"] = (len(interpreted_kernels), 0)
    return {name: {"value": value, "limit": limit,
                   "ok": value < limit if name == "loss_fell"
                   else value <= limit}
            for name, (value, limit) in numbers.items()}
