"""The harness end to end on the ``sambay_lm`` family's tiny cell
(``tiny_sambay_cell.py``): a sound run reads ``correct: true`` against
the plain reference (first three losses and the parameter change); each
of the three planted controls reads ``correct: false`` by the parameter
change."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _run(*control):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("HVD_NUMERICS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_sambay_cell.py"), *control],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(x) for x in proc.stdout.splitlines()], proc.stderr


def test_a_sound_run_reads_correct():
    lines, stderr = _run()
    result = lines[-1]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput_per_chip", "peak_hbm_gb",
                                      "setup_s"}
    phases = {x["phase"]: x for x in lines[:-1]}
    assert list(phases) == ["built", "measured", "released",
                            "reference_entered", "update_by_leaf", "checked"]
    assert phases["built"]["item"] == "tokens"
    assert phases["reference_entered"]["state_deleted"]
    value, limit = result["compared"]["reference"]
    assert value <= limit == 0.03
    value, limit = result["compared"]["update_pooled_gap"]
    assert 0 < value <= limit == 0.3
    assert "update_gap" not in result["compared"]  # said, not compared
    update = phases["checked"]["update"]
    # all six kinds' leaves and the one tied vocabulary leaf, none dead
    assert update["update_gap"] > 0 and update["leaves"] == 92
    assert update["dead_leaves"] == []
    by_leaf = phases["update_by_leaf"]
    assert "['tok_embed']['embedding']" in by_leaf
    assert not [k for k in by_leaf if "lm_head" in k]
    assert "flash_attention runs in interpret mode" in stderr


@pytest.mark.parametrize("control,by_the_loss", [
    ("no_carry", False), ("no_lambda", True), ("fp8_reference", True)])
def test_a_planted_control_reads_not_correct(control, by_the_loss):
    """The carry between the scan's chunks left out, differential
    attention without its subtracted map, the reference in the precision
    below the stated one: each fails the parameter change over all leaves
    by a wide margin, the last two the loss too."""
    lines, stderr = _run(control)
    result = lines[-1]
    assert result["correct"] is False
    checks = lines[-2]["checks"]
    assert not checks["update_pooled_gap"]
    value, limit = result["compared"]["update_pooled_gap"]
    assert value > 1.3 * limit
    assert (f"compared update_pooled_gap: {value!r} limit {limit!r} FAILED"
            in stderr)
    assert checks["reference"] is not by_the_loss
    assert all(ok for name, ok in checks.items()
               if name not in ("reference", "update_pooled_gap"))
