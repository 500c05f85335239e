"""The eight per-layer metrics that read the program's own record of its
set-up (``benchmark/harness/setup_log.py``): each resolves to its file and
to its entry of ``BENCHMARK.json`` by name, reads a number on the tiny
BERT cell on the CPU, cold and then from the compile cache, and reads
nothing, without an error, from a program that keeps no log."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402

#: name -> (unit, layer), as ISSUE 36's table has them.
NEW = {
    "step_trace_s": ("s", "entry"),
    "step_lower_s": ("s", "entry"),
    "step_backend_s": ("s", "entry"),
    "setup_compile_s": ("s", "entry"),
    "setup_programs": ("programs", "entry"),
    "compile_cache_misses": ("programs", "entry"),
    "init_s": ("s", "frontend"),
    "broadcast_s": ("s", "frontend"),
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The tiny cell's set-up twice over one cache, on four virtual
    devices (a world of several: the broadcast packs and compiles)."""
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("HVD_NUMERICS", None)  # the default a user gets
    out = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "tiny_setup_cell.py"), "4",
             str(cache)],
            capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out.append(json.loads(proc.stdout.splitlines()[-1]))
    return out


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_resolves_to_its_file_and_its_entry(name):
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, layer = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "setup_s"}
    assert callable(spec.load_module("metrics", name).read)
    # No list of cells: every cell reports it, beside compile_s.
    for cell in (w["name"] for w in bench["workloads"]):
        assert name in [m["name"] for m in spec.load_cell(cell).per_layer]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_reads_a_number_on_the_tiny_cell(runs, name):
    for run in runs:
        value = run["metrics"][name]
        assert isinstance(value, float) and value >= 0
        assert run["without_the_log"][name] is None
    cold, warm = (run["metrics"][name] for run in runs)
    if name == "compile_cache_misses":
        assert cold > 0 and warm == 0
    elif name != "broadcast_s":  # a span's host seconds may read ~0
        assert cold > 0 and warm > 0


@pytest.mark.parametrize("run", (0, 1), ids=("cold", "warm"))
def test_the_steps_parts_add_up_to_the_harnesss_own_clock(runs, run):
    metrics, build_s = runs[run]["metrics"], runs[run]["build_s"]
    parts = sum(metrics[k] for k in ("step_trace_s", "step_lower_s",
                                     "step_backend_s"))
    outside = build_s["compile"]
    assert outside == metrics["compile_s"]
    assert abs(parts - outside) <= max(0.1 * outside, 0.3)
    assert parts <= outside  # the clock outside holds all three
    assert metrics["setup_compile_s"] >= parts
    assert metrics["setup_programs"] >= metrics["compile_cache_misses"]
    assert metrics["setup_programs"] > 1
    assert metrics["init_s"] < build_s["init_and_define"]
    assert metrics["broadcast_s"] <= build_s["broadcast"]
