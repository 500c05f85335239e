"""What decides ``correct``: the plain reference's losses and parameter
change, and the checks that the step ran where and how the cell says.

The reference is the same model, seed and global batch through plain
``jax.value_and_grad`` and the plain optax optimizer on one device, in
float32 at ``jax.default_matmul_precision("highest")``. It accumulates
the global batch's gradient over micro-batches of one chip's share,
which keeps batch norm per chip as the system has it and lets one chip
hold what four computed.

The parameter change is compared on a **digest** (``digester``): every
parameter leaf at ``DIGEST_K`` coordinates drawn from the run's seed,
the same draw for the system (after its first checked steps, by one
small gather on the device) and for the reference (of the remade
weights, and again after its steps). A loss at seeded weights is about
ln(vocabulary) whatever the weights are, and a norm is second-order in
an uncorrelated error; the difference of the two changes, coordinate by
coordinate, is not. Under momentum SGD it is linear in the gradient's
error. Under adamw a coordinate moves by about its gradient's sign, so
the two sides differ where a sign flips, in the share of coordinates
whose gradient is smaller than the error: the gap goes with the square
root of the error (PERF.md section 4 has the readings).
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

from benchmark.harness import hlo
from benchmark.harness.optim import make_optimizer

REFERENCE_STEPS = 3
#: Coordinates of a leaf that the digest holds (a smaller leaf whole).
DIGEST_K = 4096
#: A leaf whose gradient in the reference is under this share of the
#: median leaf's (root mean square by element, the largest of the checked
#: steps) moves by round-off alone under a normalising optimizer, as a
#: key's bias does under softmax: it is left out of the change.
DEAD_GRADIENT = 1e-3


def digester(like, seed: int):
    """``take(params) -> {leaf's path: its values at the digest's
    coordinates}`` on the host in float64, for any tree shaped as
    ``like``. A leaf of at most ``DIGEST_K`` elements is taken whole; of
    a larger one, ``DIGEST_K`` coordinates drawn on the device from
    ``seed`` (with replacement: a coordinate drawn twice counts twice on
    both sides), the same for every tree of these shapes, wherever it
    lies. One jitted gather over the leaves as they lie (no reshape of a
    large leaf, so no second copy of it), nothing donated; the key is an
    argument, so every seed runs one cached program, and one packed
    result comes back: its bytes are all that the device holds more
    while it runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    paths, shapes = zip(*(
        (jax.tree_util.keystr(path), leaf.shape) for path, leaf in
        jax.tree_util.tree_flatten_with_path(like)[0]))
    sizes = [math.prod(shape) for shape in shapes]
    drawn = sum(size > DIGEST_K for size in sizes)

    @jax.jit
    def gather(key, leaves):
        bits = jax.random.bits(key, (drawn, DIGEST_K), jnp.uint32)
        rows, taken = iter(bits), []
        for leaf, size in zip(leaves, sizes):
            if size <= DIGEST_K:
                taken.append(leaf.reshape(-1))
            else:
                flat = (next(rows) % size).astype(jnp.int32)
                taken.append(leaf[jnp.unravel_index(flat, leaf.shape)])
        return jnp.concatenate([t.astype(jnp.float32) for t in taken])

    key = jax.random.PRNGKey(seed)
    ends = np.cumsum([min(size, DIGEST_K) for size in sizes])[:-1]

    def take(params) -> Dict:
        leaves = jax.tree.leaves(params)
        if tuple(leaf.shape for leaf in leaves) != shapes:
            raise ValueError("a tree of other shapes than the digest's")
        packed = np.asarray(gather(key, leaves), np.float64)
        return dict(zip(paths, np.split(packed, ends)))

    return take


def update_gaps(start: Dict, system: Dict, reference: Dict,
                gradient_rms: Dict) -> Dict:
    """The system's parameter change against the reference's on the
    digest. By leaf, ``gap`` is the norm of the difference of the two
    changes over the norm of the reference's (absolute where the
    reference's is zero): 1 for a leaf left where it was or moved
    double, and never under the gap between the two norms, which it
    bounds. ``update_gap`` is the worst leaf's and ``update_pooled_gap``
    the same ratio over every coordinate held, which is steadier from seed
    to seed; both over the leaves whose reference gradient is not dead
    (``DEAD_GRADIENT``). With them the leaves left out, the median
    leaf's gap and the table by leaf."""
    import numpy as np

    median = float(np.median(list(gradient_rms.values())))
    alive = [k for k in start if gradient_rms[k] >= DEAD_GRADIENT * median]
    # sums of squares: of the reference's change, and of the difference
    squares = {k: (float(np.sum((reference[k] - start[k]) ** 2)),
                   float(np.sum((system[k] - reference[k]) ** 2)))
               for k in alive}

    def ratio(ref, diff):
        value = math.sqrt(diff / ref) if ref else math.sqrt(diff)
        return value if math.isfinite(value) else math.inf

    by_leaf = {k: {"gap": ratio(ref, diff),
                   "reference_rms": math.sqrt(ref / len(start[k])),
                   "gradient_rms": gradient_rms[k]}
               for k, (ref, diff) in squares.items()}
    worst = max(by_leaf, key=lambda k: by_leaf[k]["gap"])
    return {"update_gap": by_leaf[worst]["gap"], "update_gap_leaf": worst,
            "update_pooled_gap": ratio(
                sum(ref for ref, _ in squares.values()),
                sum(diff for _, diff in squares.values())),
            "median_leaf_gap": float(np.median(
                [v["gap"] for v in by_leaf.values()])),
            "leaves": len(start),
            "dead_leaves": sorted(set(start) - set(alive)),
            "by_leaf": by_leaf}


def reference_step(cell, reference, n_global: int):
    """(step, optimizer): ``step(params, extra, opt_state, batch)`` ->
    (params, opt_state, loss, the gradient's root mean square by leaf
    as one vector in the leaves' order) over the global batch of
    ``n_global`` samples, one micro-batch per chip's share."""
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
        rms = jnp.stack([jnp.sqrt(jnp.mean(g * g))
                         for g in jax.tree.leaves(grads)])
        return optax.apply_updates(params, updates), opt_state, loss, rms

    return step, opt


def reference_losses(cell, reference, system, device, seed: int) -> Dict:
    """The reference's losses at the steps whose loss the system's first
    ``REFERENCE_STEPS`` calls return (every step, or the last of each
    scan-fused call), on weights made again from the seed (the system's
    were donated); the digests of those weights and of the reference's
    parameters after the same steps; and by leaf the largest root mean
    square of its gradient over them."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    params, extra = jax.device_put(system.remake_weights(), device)
    batch = jax.device_put(system.batch, device)
    take = digester(params, seed)
    start = take(params)
    step, opt = reference_step(cell, reference, batch[0].shape[0])
    with jax.default_matmul_precision("highest"):
        opt_state = jax.jit(opt.init)(params)
        compiled = jax.jit(step, donate_argnums=(0, 2)).lower(
            params, extra, opt_state, batch).compile()
        losses, gradient_rms = [], []
        for _ in range(REFERENCE_STEPS * system.steps_per_call):
            params, opt_state, loss, rms = compiled(params, extra, opt_state,
                                                    batch)
            losses.append(float(loss))
            gradient_rms.append(np.asarray(rms, np.float64))
    return {"losses": losses[system.steps_per_call - 1::system.steps_per_call],
            "start": start, "end": take(params),
            "gradient_rms": dict(zip(start, np.max(gradient_rms, axis=0))),
            "seconds": time.perf_counter() - t0}


def verdict(cell, system, first_losses: List[float],
            window_losses: List[float], ref_losses: List[float],
            update: Dict, interpreted_kernels,
            on_tpu: bool) -> Dict[str, Dict]:
    """Every check by name, as the number compared beside its limit:
    ``{"value", "limit", "ok"}``, where ``ok`` is ``value <= limit``
    (the loss has to fall, so there ``<``). ``correct`` is the
    conjunction of the ``ok``. The one about pallas kernels holds on a
    TPU only: off it (the tests' tiny cells) the kernels are interpreted
    by design."""
    n = system.n_chips
    tolerance = cell.config["update_tolerance"]
    fetched = first_losses + window_losses
    gaps = [abs(a - b) if math.isfinite(a - b) else math.inf for a, b in
            zip(first_losses[:REFERENCE_STEPS], ref_losses)]
    numbers = {
        # counts of what must not be there
        "losses_finite": (sum(not math.isfinite(x) for x in fetched), 0),
        "loss_fell": (fetched[-1] - fetched[0], 0.0),
        # the widest of the first losses' gaps to the reference's
        "reference": (max(gaps), cell.config["loss_tolerance"]["abs"]),
        # the parameter change against the reference's (``update_gaps``),
        # by the worst leaf and over all leaves; a limit of ``null`` in
        # the file is a number that the chip could set no limit for: said
        # in the ``checked`` line, not compared
        **{name: (update[name], limit) for name, limit in (
            ("update_gap", tolerance["rel"]),
            ("update_pooled_gap", tolerance["pooled_rel"]))
           if limit is not None},
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
