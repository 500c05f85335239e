"""The harness end to end on the ``lfm2_lm`` family's tiny cell
(``tiny_lfm2_cell.py``): a sound run reads ``correct: true`` against the
plain reference (first three losses and the parameter change) and carries
the routing counters through the step; each of the four planted controls
reads ``correct: false`` by the loss and by the parameter change; the
timed path in float32 reads the reference's numbers to rounding."""

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
        [sys.executable, os.path.join(HERE, "tiny_lfm2_cell.py"), *control],
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
    assert value <= limit == 0.02
    value, limit = result["compared"]["update_pooled_gap"]
    assert 0 < value <= limit == 0.4
    assert "update_gap" not in result["compared"]  # said, not compared
    update = phases["checked"]["update"]
    # the four expert biases get no gradient by design, and nothing else
    # is dead; the tied embedding is the one vocabulary leaf
    assert update["dead_leaves"] == [
        f"['layer_{l}']['moe']['expert_bias']" for l in (2, 3, 4, 5)]
    by_leaf = phases["update_by_leaf"]
    assert "['layer_0']['conv']['taps']" in by_leaf
    assert "['layer_2']['attention']['query_norm']" in by_leaf
    assert not [k for k in by_leaf if "lm_head" in k]
    assert "flash_attention runs in interpret mode" in stderr


def test_the_program_in_float32_reads_the_reference():
    """The timed path in float32 with every product at ``highest`` (the
    reference's precision): a sound bf16 run's distance from the
    reference (0.27 over all leaves here) is then rounding, all of it: the
    losses agree to 1e-6 and the parameter change to 3e-5 over all leaves
    (1.4e-4 by the worst), so the router's choice, its bias and every
    other piece compute what the reference computes."""
    lines, _ = _run("f32_program")
    result = lines[-1]
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["reference"][0] < 1e-4
    update = lines[-2]["update"]
    assert update["update_pooled_gap"] < 1e-3
    assert update["update_gap"] < 1e-2


@pytest.mark.parametrize("control", ["no_conv", "no_routed", "no_qk_norm",
                                     "fp8_reference"])
def test_a_planted_control_reads_not_correct(control):
    """The convolution's earlier taps left out, the routed experts' output
    left out, the queries and keys not normed, the reference in the
    precision below the stated one: each fails the loss's limit and the
    parameter change over all leaves, the second by a wide margin."""
    lines, stderr = _run(control)
    result = lines[-1]
    assert result["correct"] is False
    checks = lines[-2]["checks"]
    assert not checks["reference"] and not checks["update_pooled_gap"]
    value, limit = result["compared"]["update_pooled_gap"]
    assert value > 1.5 * limit
    assert (f"compared update_pooled_gap: {value!r} limit {limit!r} FAILED"
            in stderr)
    assert all(ok for name, ok in checks.items()
               if name not in ("reference", "update_pooled_gap"))
