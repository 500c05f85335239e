"""The tiny BERT cell's set-up alone (``step.build``: ``hvd.init``, the
weights, the batch, ``broadcast_parameters``, the step compiled ahead of
time, the rank check) under a compile cache at ``<cache>``, then every
per-layer metric that moves ``setup_s`` read as the harness reads it.
Run as ``python tiny_setup_cell.py <chips> <cache>``; prints one JSON
object: the metrics by name, the harness's own ``build_s`` and what the
parent's program would have read. Not a benchmark: a time from here is
never a device metric."""

import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(chips: int, cache: str) -> int:
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import horovod_tpu as hvd
    import tiny_cells
    from benchmark.harness import spec, step

    config, traffic = tiny_cells.CELLS["bert"]
    bench = spec.load_benchmark()
    moving = tuple(m for m in bench["per_layer"] if m["moves"] == "setup_s")
    cell = spec.Cell(name="tiny", chips=chips, config_name="tiny",
                     config=config, traffic_name="tiny", traffic=traffic,
                     end_to_end=tuple(bench["end_to_end"]), per_layer=moving)
    family = spec.load_module("families", cell.family)
    system = step.build(cell, family, 0, jax.devices()[:chips])
    context = types.SimpleNamespace(cell=cell, family=family, system=system)
    values = {m["name"]: spec.load_module("metrics", m["name"]).read(context)
              for m in moving}

    # A program without the log, as the parent's is: nothing to read.
    telemetry = hvd.telemetry
    hvd.telemetry = lambda: {
        k: v for k, v in telemetry().items() if k != "compile_log"}
    without = {m["name"]: spec.load_module("metrics", m["name"]).read(context)
               for m in moving if m["name"] != "compile_s"}
    hvd.telemetry = telemetry
    print(json.dumps({"metrics": values, "build_s": system.build_s,
                      "without_the_log": without}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), sys.argv[2]))
