#!/usr/bin/env python3
"""Compile each cell's step at its real size for a described ``v5e:2x2``,
without a chip (on-chip-measurement guide, section 2, rehearsal 3):

    JAX_PLATFORMS=cpu python3 benchmark/aot_rehearsal.py [workload ...]
    JAX_PLATFORMS=cpu python3 benchmark/aot_rehearsal.py \\
        --config <file> --traffic <file> --chips <n>

The second form takes a cell that ``BENCHMARK.json`` does not name yet,
so that whoever sizes the next configuration compiles it before it has
an entry and before any chip time.

Prints one JSON line per cell: ``memory_analysis()`` on one device (and
of the plain reference's step, which has to fit one chip too), whether
a Mosaic kernel (``tpu_custom_call``) is in the program, the
collectives' bytes and the largest all-reduce group; and what one chip
holds in each phase of a run, against its HBM in ``peaks.json``:
``resident_gb`` between steps (the state; ``batch_gb`` beside it),
``live_gb`` in the window, ``check_phase_gb`` while the reference runs
(its step and the resident batch: the harness has released the system by
then), and ``fits`` for the window and for the check. What the TPU's
compiler refuses here costs no chip time. Nothing runs, so this says
nothing about results or times, and a compile that passes is not a chip
run. With no argument it compiles every cell of ``BENCHMARK.json``.

Code that asks ``jax.default_backend()`` sees the CPU here, so the
script itself tells the pallas kernels not to interpret.
"""

import argparse
import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


#: The chip the topology describes, as ``peaks.json`` names it.
DEVICE_KIND = "TPU v5 lite"


def resolve(argv) -> list:
    """The cells the command line names: workloads of ``BENCHMARK.json``
    (all of them where none is given), or one cell from its files."""
    from benchmark.harness import spec

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workloads", nargs="*")
    parser.add_argument("--config")
    parser.add_argument("--traffic")
    parser.add_argument("--chips", type=int, choices=(1, 4))
    args = parser.parse_args(argv)
    files = (args.config, args.traffic, args.chips)
    if any(x is not None for x in files):
        if args.workloads or any(x is None for x in files):
            parser.error("--config, --traffic and --chips go together, "
                         "and in place of workload names")
        return [spec.cell_from_files(*files)]
    names = args.workloads or [w["name"] for w in
                               spec.load_benchmark()["workloads"]]
    return [spec.load_cell(name) for name in names]


def _bytes_on_one_device(described) -> int:
    """Bytes that one device holds of a tree of shapes with shardings."""
    import jax

    return sum(s.dtype.itemsize * math.prod(s.sharding.shard_shape(s.shape))
               for s in jax.tree.leaves(described))


def rehearse(cell, topology) -> dict:
    import jax

    import horovod_tpu as hvd
    from benchmark.harness import check, hlo, spec, step

    family = spec.load_module("families", cell.family)
    hvd.init(devices=list(topology.devices[:cell.chips]))
    try:
        prog = step.program(cell, family)
        key = jax.random.PRNGKey(0)

        def described(shapes, sharding):
            """Shapes with their place on the described devices; one
            sharding for the whole tree, or a tree of them."""
            if isinstance(sharding, jax.sharding.Sharding):
                sharding = jax.tree.map(lambda _: sharding, shapes)
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                shapes, sharding)

        state = tuple(
            described(part, sh) for part, sh in
            zip(jax.eval_shape(prog.init_state, key), prog.state_shardings))
        batch = described(jax.eval_shape(prog.make_batch, key),
                          prog.batch_sharding)
        compiled = prog.train_step.lower(*state, *batch).compile()

        # The plain reference of the same global batch, on one device.
        one = jax.sharding.SingleDeviceSharding(topology.devices[0])
        params, extra = described(jax.eval_shape(prog.init_weights, key), one)
        ref_step, opt = check.reference_step(
            cell, spec.load_module("reference", cell.family),
            batch[0].shape[0])
        with jax.default_matmul_precision("highest"):
            ref_mem = jax.jit(ref_step, donate_argnums=(0, 2)).lower(
                params, extra,
                described(jax.eval_shape(opt.init, params), one),
                described(batch, one)).compile().memory_analysis()
    finally:
        hvd.shutdown()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    colls = hlo.collectives(text)

    def live_gb(m):
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9

    hbm_gb = spec.load_peaks(DEVICE_KIND)["hbm_bytes"] / 1e9
    batch_gb = _bytes_on_one_device(batch) / 1e9
    check_phase_gb = live_gb(ref_mem) + batch_gb
    return {
        "workload": cell.name, "chips": cell.chips,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "resident_gb": _bytes_on_one_device(state) / 1e9,
        "batch_gb": batch_gb,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "live_gb": live_gb(mem), "code_mb": mem.generated_code_size_in_bytes / 1e6,
        "reference_live_gb": live_gb(ref_mem),
        "check_phase_gb": check_phase_gb, "hbm_gb": hbm_gb,
        "fits": {"window": live_gb(mem) <= hbm_gb,
                 "check": check_phase_gb <= hbm_gb},
        "reference_code_mb": ref_mem.generated_code_size_in_bytes / 1e6,
        "tpu_custom_call": hlo.has_tpu_custom_call(text),
        "collectives": len(colls), "wire_bytes": hlo.wire_bytes(text),
        "all_reduce_group": hlo.all_reduce_group(text),
    }


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from horovod_tpu.ops import flash_attention

    cells = resolve(argv)
    # The compile cache can hold nothing a chipless process reads back.
    jax.config.update("jax_enable_compilation_cache", False)
    flash_attention.resolve_interpret = lambda interpret, kernel: False
    topology = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    for cell in cells:
        print(json.dumps(rehearse(cell, topology)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
