#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints one JSON object as
the last line of stdout and exits. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a short
capture after an untraced stretch. Off a TPU it fails: there is no CPU
fallback and no switch for one. Cells, configurations, traffic mixes and
metrics are found by the names in ``BENCHMARK.json``.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from benchmark.harness import spec

    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    # The program's own rule for the compile cache: <checkout>/.cache/jax,
    # or JAX_COMPILATION_CACHE_DIR where that is set. Small programs (the
    # weights, the rank check) are kept too, so a cell's second run
    # compiles nothing.
    from horovod_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmark.harness import loop

    loop.log(phase="start", workload=cell.name, seed=args.seed,
             compile_cache=cache_dir)
    result = loop.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
