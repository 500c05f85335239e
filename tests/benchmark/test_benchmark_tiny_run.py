"""The harness end to end at tiny sizes on the CPU (``tiny_cells.py``):
set-up, warm-up, the window, the reference and the last line's keys, on
one device and on four virtual ones, with the pallas kernel interpreted,
with the sharded int8 update and with a scan-fused step. Each runs in a
process of its own: the harness calls ``hvd.init`` for its own world."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _run(which, chips):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("HVD_NUMERICS", None)  # the default a user gets
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_cells.py"), which,
         str(chips)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines, proc.stderr


@pytest.mark.parametrize("which,chips", [
    ("resnet", 1), ("bert", 4), ("flash", 1), ("sharded_int8", 4),
    ("scan", 1)])
def test_tiny_cell_runs_and_prints_the_result_line_last(which, chips):
    lines, stderr = _run(which, chips)
    result = lines[-1]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput_per_chip", "peak_hbm_gb",
                                      "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    assert result["metrics"]["throughput_per_chip"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": chips, "memory_peak_bytes": 0}

    phases = {x["phase"]: x for x in lines[:-1]}
    assert list(phases) == ["built", "measured", "checked"]
    built, checked = phases["built"], phases["checked"]
    assert built["mean_rank"] == (chips - 1) / 2
    assert built["item"] == ("images" if which == "resnet" else "tokens")
    assert "temp_size_in_bytes" in built["memory_analysis"]
    assert set(built["build_s"]) == {"init_and_define", "weights", "batch",
                                     "broadcast", "compile", "rank_check"}
    assert checked["reference_s"] > 0
    assert len(checked["system_losses"]) == len(
        checked["reference_losses"]) == 3
    assert all(checked["checks"].values()), checked["checks"]
    assert ("all_reduce_spans_world" in checked["checks"]) == (chips > 1)
    # Whole windows of 4 steps were counted.
    assert result["attempted"] % 4 == 0
    if which == "flash":
        assert "flash_attention runs in interpret mode" in stderr
