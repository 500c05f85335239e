"""The harness end to end at tiny sizes on the CPU (``tiny_cells.py``):
set-up, warm-up, the window, the reference and the last line's keys, on
one device and on four virtual ones, with the pallas kernel interpreted,
with the sharded int8 update and with a scan-fused step; then with the
timed path broken underneath the harness, which has to say so. Each runs
in a process of its own: the harness calls ``hvd.init`` for its own
world."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tiny_cells  # noqa: E402  (the tiny cells' configurations)


def _run(which, chips, *fault):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("HVD_NUMERICS", None)  # the default a user gets
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_cells.py"), which,
         str(chips), *fault],
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
    # The numbers compared, each beside its limit, come last.
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
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
    # The system is released before the reference begins: every array
    # of its state deleted, its executable dropped, the batch kept (the
    # reference reads it). XLA:CPU keeps no memory statistics, so the
    # ``released`` line carries no bytes here.
    assert list(phases) == ["built", "measured", "released",
                            "reference_entered", "update_by_leaf", "checked"]
    assert phases["released"] == {"phase": "released"}
    entered = phases["reference_entered"]
    assert entered["state_leaves"] > 0 and entered["state_deleted"]
    assert entered["compiled_dropped"] and not entered["batch_deleted"]
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
    assert list(result["compared"]) == list(checked["checks"])
    said = [x for x in stderr.splitlines() if x.startswith("compared ")]
    assert said == stderr.splitlines()[-len(said):]  # standard error's end
    for line, (name, (value, limit)) in zip(said,
                                            result["compared"].items()):
        assert line == f"compared {name}: {value!r} limit {limit!r} ok"
    assert result["compared"]["reference"][1] == 0.02  # the cell's own
    # The parameter change of the three checked steps (six, scan-fused)
    # against the reference's, on the digest: the worst leaf under the
    # cell's limits, by a leaf that the table by leaf holds; the digest
    # is taken in set-up and its seconds are said.
    limits = tiny_cells.CELLS[which][0]["update_tolerance"]
    update, by_leaf = checked["update"], phases["update_by_leaf"]
    assert result["compared"]["update_gap"] == [update["update_gap"],
                                                limits["rel"]]
    assert result["compared"]["update_pooled_gap"] == [
        update["update_pooled_gap"], limits["pooled_rel"]]
    assert 0 < update["update_gap"] == by_leaf[update["update_gap_leaf"]][
        "gap"]
    assert 0 < update["median_leaf_gap"] <= update["update_gap"]
    assert 0 < update["update_pooled_gap"] <= update["update_gap"]
    assert update["leaves"] == len(by_leaf) - 1 + len(update["dead_leaves"])
    # A key's bias under softmax has no gradient but rounding: left out
    # by the reference's gradient, in the transformer's cells only.
    assert all("['key']['bias']" in leaf for leaf in update["dead_leaves"])
    assert len(update["dead_leaves"]) == (0 if which == "resnet" else 2)
    assert 0 < phases["measured"]["digest_s"] < \
        result["metrics"]["setup_s"]["value"]
    # Whole windows of 4 steps were counted.
    assert result["attempted"] % 4 == 0
    if which == "flash":
        assert "flash_attention runs in interpret mode" in stderr


@pytest.mark.parametrize("fault,chips,failed", [
    # the losses repeat: none falls, and the reference's do; nothing
    # moved, which reads 1 by the worst leaf and over all leaves
    ("state_unchanged", 1, {"loss_fell", "reference", "update_gap",
                            "update_pooled_gap"}),
    # the mean over half of each chip's rows is another loss, and its
    # gradient moves the parameters another way
    ("half_batch", 4, {"reference", "update_gap", "update_pooled_gap"}),
    # every chip follows its own gradient: steps 2 and 3 fall too fast
    ("no_exchange", 4, {"reference", "update_gap", "update_pooled_gap"}),
])
def test_a_broken_timed_path_reads_not_correct(fault, chips, failed):
    """The harness's look for a chip skipped, the rest of a run driven
    with the fault planted underneath (``tiny_cells.FAULTS``): ``correct``
    comes out false by the checks named, and the last lines of standard
    error say which number passed which limit."""
    lines, stderr = _run("bert", chips, fault)
    result = lines[-1]
    assert result["correct"] is False
    checks = lines[-2]["checks"]
    assert {name for name, ok in checks.items() if not ok} == failed
    for name in failed:
        value, limit = result["compared"][name]
        assert f"compared {name}: {value!r} limit {limit!r} FAILED" \
            in stderr.splitlines()[-len(checks):]
    # By a wide margin at this size, not by rounding.
    for name, times in (("reference", 3), ("update_gap", 2.5),
                        ("update_pooled_gap", 4)):
        value, limit = result["compared"][name]
        assert value >= times * limit
