"""Tiny cells that only the tests can reach: the harness end to end on
the CPU (pallas kernels interpreted, virtual devices for several chips).
Run as ``python tiny_cells.py <resnet|bert|flash> <chips> [fault]``;
prints what ``benchmark/run.py`` would, the result object last, and one
line of its own, ``reference_entered``, that says what was left of the
system when the reference began. A ``fault`` (``FAULTS``) breaks the
timed path underneath the harness, which has to say ``correct: false``.
Not a benchmark: a time from here is never a device metric.

Given a workload of ``BENCHMARK.json`` in place of a tiny cell's name
(``tiny_cells.py bert_base_s512 1 half_batch <seed> <seconds>``) it
plants the fault under that cell at its own size, on the chip only: how
``PERF.md``'s readings of the faults were taken."""

import json
import os
import sys
import time

T_START = time.perf_counter()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

TRAFFIC = dict(steps_per_call=1, unroll=1, window_steps=4, warmup_steps=2,
               feed="resident", compression="none", sharded_update=False,
               state_dtype="f32")
RESNET = dict(
    family="resnet", stage_sizes=[2, 2], num_filters=8, num_classes=10,
    compute_dtype="bfloat16",
    optimizer=dict(name="sgd", learning_rate=0.01, momentum=0.9),
    loss_tolerance=dict(abs=0.02),
    # bf16 convolutions under batch norm over 4 images of 16 x 16: the
    # worst leaf reads 0.56 here and all leaves together 0.33; a state
    # left unchanged reads 1 by both
    update_tolerance=dict(rel=0.9, pooled_rel=0.6))
BERT = dict(
    family="transformer_lm", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=2, intermediate_size=64, vocab_size=64,
    max_position_embeddings=16, compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    loss_tolerance=dict(abs=0.02),
    # sound tiny runs read at most 0.15 by the worst leaf and 0.073 over
    # all leaves; the three faults 1.0 to 1.43 and 0.98 to 1.10
    update_tolerance=dict(rel=0.4, pooled_rel=0.2))
CELLS = {
    "resnet": (RESNET, dict(TRAFFIC, per_chip_batch=4, image_size=16)),
    "bert": (BERT, dict(TRAFFIC, per_chip_batch=2, seq_len=16,
                        attention="stock", remat=False)),
    # seq 128: the least the kernel's 128-wide blocks take.
    "flash": (BERT, dict(TRAFFIC, per_chip_batch=2, seq_len=128,
                         attention="flash", remat=False)),
    "sharded_int8": (BERT, dict(TRAFFIC, per_chip_batch=2, seq_len=16,
                                attention="stock", remat=False,
                                sharded_update=True, compression="int8")),
    "scan": (BERT, dict(TRAFFIC, per_chip_batch=2, seq_len=16,
                        attention="stock", remat=False, steps_per_call=2,
                        unroll=2)),
}


class _StateUnchanged:
    """A step that returns its state as it got it, with the loss of the
    real step (which runs on copies, since it donates)."""

    def __init__(self, compiled):
        self.compiled = compiled
        self.memory_analysis = compiled.memory_analysis

    def __call__(self, *args):
        import jax

        state, batch = args[:3], args[3:]
        *_, loss = self.compiled(*jax.tree.map(lambda a: a.copy(), state),
                                 *batch)
        return (*state, loss)


def _half_batch(loss_fn):
    """Half of each chip's rows left out, the mean taken over the rest."""
    import jax

    def broken_loss(model, params, extra, batch):
        return loss_fn(model, params, extra, jax.tree.map(
            lambda a: a[:a.shape[0] // 2], batch))

    return broken_loss


FAULTS = ("state_unchanged", "half_batch", "no_exchange")


def main(which: str, chips: int, fault: str = "", seed: int = 0,
         seconds: float = 0.3) -> int:
    import jax

    from benchmark.harness import check, loop, spec, step

    reference_losses = check.reference_losses

    def spy(cell, reference, system, *rest):
        leaves = jax.tree.leaves(system.state)
        loop.log(phase="reference_entered", state_leaves=len(leaves),
                 state_deleted=all(x.is_deleted() for x in leaves),
                 compiled_dropped=system.compiled is None,
                 batch_deleted=any(x.is_deleted() for x in system.batch))
        return reference_losses(cell, reference, system, *rest)

    check.reference_losses = spy
    if fault == "state_unchanged":
        build = step.build

        def build_broken(*args):
            system = build(*args)
            system.compiled = _StateUnchanged(system.compiled)
            return system

        step.build = build_broken
    elif fault == "no_exchange":
        # Every chip updates with its own gradient; the loss's own
        # all-reduce stays, as it would in a step that lost the other.
        import horovod_tpu.jax as hvd_jax

        hvd_jax.DistributedOptimizer = lambda opt, **_: opt
    elif fault == "half_batch":
        load_module = spec.load_module

        def load_broken(kind, name):
            module = load_module(kind, name)
            if kind == "families":
                module.loss_fn = _half_batch(module.loss_fn)
            return module

        spec.load_module = load_broken
    elif fault:
        raise SystemExit(f"fault {fault!r}: want one of {FAULTS}")

    if which in CELLS:
        config, traffic = CELLS[which]
        bench = spec.load_benchmark()
        cell = spec.Cell(name="tiny", chips=chips, config_name="tiny",
                         config=config, traffic_name="tiny", traffic=traffic,
                         end_to_end=tuple(bench["end_to_end"]),
                         per_layer=tuple(bench["per_layer"]))
    else:
        from horovod_tpu.common.compile_cache import enable_compile_cache

        cell = spec.load_cell(which)
        enable_compile_cache()  # as run.py: the cell's step is there
    result = loop.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                           t_start=T_START, require_tpu=which not in CELLS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), *sys.argv[3:4],
                  *map(int, sys.argv[4:5]), *map(float, sys.argv[5:6])))
