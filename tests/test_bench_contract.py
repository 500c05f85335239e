"""CI guards for bench.py's external contract (CLAUDE.md architecture
invariants): `bench.py --help` / `--dry` stay import-free (no jax, no
framework — argparse errors must never pay the multi-second import), and
the one-JSON-line output shape survives refactors. Also pins the
machine-readable `--json` surface of examples/allreduce_benchmark.py at
the argparse level (its full run needs a device world — covered by the
examples smoke tier)."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture()
def poisoned_env(tmp_path):
    """Environment where importing jax (or the framework package, which
    imports jax) raises immediately — proves a subprocess never touched
    either. The poison dir goes first on PYTHONPATH."""
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax").mkdir()
    (poison / "jax" / "__init__.py").write_text(
        "raise ImportError('bench.py --help/--dry must not import jax')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(poison) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_bench_help_is_import_free(poisoned_env):
    proc = subprocess.run([sys.executable, BENCH, "--help"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "usage" in proc.stdout.lower()
    assert "must not import jax" not in proc.stderr


def test_bench_argparse_error_is_import_free(poisoned_env):
    proc = subprocess.run([sys.executable, BENCH, "--no-such-flag"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 2  # argparse usage error, not ImportError
    assert "must not import jax" not in proc.stderr


def test_bench_dry_one_json_line_contract(poisoned_env):
    proc = subprocess.run([sys.executable, BENCH, "--dry", "--model",
                           "resnet50", "--batch-size", "32"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # EXACTLY one stdout line, and it is a JSON object (the contract
    # bench.py's consumers — BENCH_r*.json collection — regex for).
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    assert re.match(r"^\{.*\}$", lines[0])
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "step_time_ms",
                "gflops_per_step", "mfu", "hbm_gb_per_step", "hbm_source",
                "membw_util", "spread_pct", "gate", "state_dtype",
                "compression", "numerics", "platform", "device_kind",
                "n_devices", "dry"):
        assert key in rec, (key, rec)
    # The line names the device it ran on; nothing ran under --dry.
    assert rec["platform"] is rec["device_kind"] is rec["n_devices"] is None
    assert rec["metric"] == "resnet50_train_images_per_sec_per_chip_bs32"
    assert rec["unit"] == "images/sec/chip"
    assert rec["dry"] is True
    # Numerics observatory (ISSUE 8): the field is present-but-null
    # under --dry (nothing ran, nothing was watched — and the import-free
    # contract above means the observatory was never even imported).
    assert rec["numerics"] is None


def test_bench_dry_check_keeps_contract_and_gate_fields_null(poisoned_env):
    """`--dry --check` (ISSUE 6 satellite): still import-free, still one
    JSON line, the regression-gate fields present-but-null (there is
    nothing to gate without a run), exit 0."""
    proc = subprocess.run([sys.executable, BENCH, "--dry", "--check"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "must not import jax" not in proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["gate"] is None
    assert rec["spread_pct"] is None
    assert rec["dry"] is True


def test_bench_dry_state_dtype_keeps_contract(poisoned_env):
    """`--state-dtype bf16 --dry` (HBM diet round 2): still import-free,
    still one JSON line, the state_dtype field present-but-null (the
    policy only means something on a real run)."""
    proc = subprocess.run([sys.executable, BENCH, "--dry",
                           "--state-dtype", "bf16"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "must not import jax" not in proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["state_dtype"] is None
    assert rec["dry"] is True
    # A bad spelling is an argparse error (exit 2), still import-free.
    proc = subprocess.run([sys.executable, BENCH, "--dry",
                           "--state-dtype", "int8"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 2
    assert "must not import jax" not in proc.stderr


def test_bench_dry_compression_keeps_contract(poisoned_env):
    """`--compression int8 --dry` (quantized collectives, ISSUE 12):
    still import-free, still one JSON line, the compression field
    present-but-null (the policy only means something on a real run).
    A bad spelling is an argparse error (exit 2), still import-free."""
    proc = subprocess.run([sys.executable, BENCH, "--dry",
                           "--compression", "int8"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "must not import jax" not in proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["compression"] is None
    assert rec["dry"] is True
    proc = subprocess.run([sys.executable, BENCH, "--dry",
                           "--compression", "int9"],
                          capture_output=True, text=True, timeout=60,
                          env=poisoned_env, cwd=REPO)
    assert proc.returncode == 2
    assert "must not import jax" not in proc.stderr


def test_bench_check_flag_documented():
    proc = subprocess.run([sys.executable, BENCH, "--help"],
                          capture_output=True, text=True, timeout=60,
                          cwd=REPO)
    assert proc.returncode == 0
    assert "--check" in proc.stdout
    assert "--profile" in proc.stdout
    assert "--state-dtype" in proc.stdout
    assert "--compression" in proc.stdout


def test_allreduce_benchmark_has_json_flag():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "allreduce_benchmark.py"), "--help"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "--json" in proc.stdout
    assert "--decompose" in proc.stdout
    assert "--compression" in proc.stdout
