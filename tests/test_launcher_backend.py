"""One process for each chip: the launcher parent must never initialise
a jax backend (a parent that holds the chip starves its children), and
on a TPU host it hands each child exactly one chip."""

import os
import subprocess
import sys

import pytest

from horovod_tpu import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_importing_package_and_launcher_initialises_no_backend():
    code = ("import horovod_tpu, horovod_tpu.run\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('NO BACKEND')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NO BACKEND" in proc.stdout


def test_one_chip_per_child_on_a_tpu_host():
    envs = run._tpu_child_envs(
        4, 4, {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"})
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    addresses = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(set(addresses)) == 4
    assert all(e["TPU_PROCESS_ADDRESSES"] == envs[0]["TPU_PROCESS_ADDRESSES"]
               for e in envs)
    assert [f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs] == addresses


def test_nothing_to_divide_means_no_changes():
    assert run._tpu_child_envs(2, 0, {}) == [{}, {}]   # no TPU here
    assert run._tpu_child_envs(1, 4, {}) == [{}]       # one controller


@pytest.mark.parametrize("num_proc,chips", [(2, 4), (3, 4), (2, 1), (6, 6)])
def test_other_layouts_are_refused_at_launch(num_proc, chips):
    with pytest.raises(SystemExit) as exc:
        run._tpu_child_envs(num_proc, chips, {})
    msg = str(exc.value)
    assert f"-np {num_proc}" in msg and f"{chips} TPU chip" in msg
