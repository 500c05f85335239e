"""Tiny cells that only the tests can reach: the harness end to end on
the CPU (pallas kernels interpreted, virtual devices for several chips).
Run as ``python tiny_cells.py <resnet|bert|flash> <chips>``; prints what
``benchmark/run.py`` would, the result object last. Not a benchmark: a
time from here is never a device metric."""

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
    loss_tolerance=dict(abs=0.02))
BERT = dict(
    family="transformer_lm", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=2, intermediate_size=64, vocab_size=64,
    max_position_embeddings=16, compute_dtype="bfloat16",
    optimizer=dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    loss_tolerance=dict(abs=0.02))
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


def main(which: str, chips: int) -> int:
    from benchmark.harness import loop, spec

    config, traffic = CELLS[which]
    bench = spec.load_benchmark()
    cell = spec.Cell(name="tiny", chips=chips, config_name="tiny",
                     config=config, traffic_name="tiny", traffic=traffic,
                     end_to_end=tuple(bench["end_to_end"]),
                     per_layer=tuple(bench["per_layer"]))
    result = loop.run_cell(cell, seed=0, seconds=0.3, trace=False,
                           t_start=T_START, require_tpu=False)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2])))
