"""chip_smoke.py off the chip: its three phases at tiny size on the CPU
mesh (through ``main(tiny=True)`` — a function argument, not an option
of the shipped script), its refusal to run full width anywhere but a
TPU, and the pieces it leans on that need no chip: the compile-cache
placement and the unknown-device error."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *code_or_script],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)


def test_phases_run_tiny_on_the_cpu_mesh():
    proc = _run(["-c", "import sys, chip_smoke; "
                       "sys.exit(chip_smoke.main(tiny=True))"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    phases = {ln.split()[0]: ln for ln in lines if ln.startswith("phase=")}
    assert set(phases) == {"phase=resnet50", "phase=bert_base",
                           "phase=engine"}, proc.stdout
    for ln in phases.values():
        assert "platform=cpu" in ln and "n_devices=8" in ln, ln
        assert "peak_bytes_in_use=" in ln, ln
    for name in ("phase=resnet50", "phase=bert_base"):
        for field in ("compile_s=", "steps=", "step_s_block_until_ready=",
                      "step_s_fetch=", "loss_first=", "loss_last="):
            assert field in phases[name], (field, phases[name])
    assert "engine=NativeEngine" in phases["phase=engine"]
    assert "ones_sum=8" in phases["phase=engine"]
    # The multi-device checks ran against the 8-device world.
    check = next(ln for ln in lines if ln.startswith("check=resnet50"))
    assert "world=8 shard_devices=8" in check, check
    assert "all_reduce_in_hlo=True mean_rank=3.5" in check, check
    # The interpret-mode fallback is reported, not silent.
    assert "flash_attention runs in interpret mode" in proc.stderr
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}


def test_full_width_refuses_anything_but_a_tpu():
    proc = _run([os.path.join(REPO, "chip_smoke.py")])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform=cpu" in proc.stderr
    assert "ok" not in proc.stdout  # no result line


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax

    from horovod_tpu.common import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        # Set from outside: returned as-is, and nothing is set in code.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # Unset: the one fixed path under the checkout.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == os.path.join(REPO, ".cache",
                                                         "jax")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unknown_accelerator_is_an_error_cpu_is_not():
    from horovod_tpu.utils import hardware as hw

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert hw.peak_flops(Dev("tpu", "TPU v5 lite")) == 197e12
    assert hw.peak_flops(Dev("cpu", "cpu")) == 0.0
    with pytest.raises(ValueError, match="TPU v9"):
        hw.peak_hbm_bw(Dev("tpu", "TPU v9"))
