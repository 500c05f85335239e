"""The harness end to end on the ``decoder_lm`` family's tiny cell
(``tiny_decoder_cell.py``): a sound run reads ``correct: true`` and
carries the routing counters through the step; with the routed experts'
output left out of the timed path it reads ``correct: false`` by the
loss's gap to the reference."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _run(*fault):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("HVD_NUMERICS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_decoder_cell.py"), *fault],
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
    value, limit = result["compared"]["update_gap"]
    assert 0 < value <= limit == 0.5
    assert not phases["checked"]["update"]["dead_leaves"]  # no bias here
    assert "flash_attention runs in interpret mode" in stderr


def test_without_the_routed_experts_output_it_reads_not_correct():
    lines, stderr = _run("no_routed")
    result = lines[-1]
    assert result["correct"] is False
    checks = lines[-2]["checks"]
    assert not checks["reference"]
    value, limit = result["compared"]["reference"]
    assert value > 3 * limit  # by a wide margin, not by rounding
    assert f"compared reference: {value!r} limit {limit!r} FAILED" in stderr


def test_parameters_in_float8_are_read_by_the_parameter_change_alone():
    """The precision below the configuration's, on the timed path: the
    loss computed on parameters rounded to float8_e4m3fn. The losses
    stay within their limit of the reference's (a loss at seeded weights
    is blind to them); the parameter change, coordinate by coordinate,
    is not."""
    lines, stderr = _run("fp8_params")
    result = lines[-1]
    assert result["correct"] is False
    checks = lines[-1 - 1]["checks"]
    assert {name for name, ok in checks.items() if not ok} == {
        "update_gap", "update_pooled_gap"}
    value, limit = result["compared"]["reference"]
    assert value <= limit
    value, limit = result["compared"]["update_gap"]
    assert value > 1.4 * limit
    assert f"compared update_gap: {value!r} limit {limit!r} FAILED" in stderr
    value, limit = result["compared"]["update_pooled_gap"]
    assert value > 1.8 * limit


def test_the_precision_controls_lower_what_they_say():
    """``bf16_scores`` routes in bfloat16 throughout and still hands on
    float32 weights that sum to the scaling; ``fp8_reference`` computes
    the loss on parameters rounded to float8 and passes gradients
    straight through the rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import tiny_decoder_cell as cell
    from horovod_tpu.parallel import moe

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (64, 32), jnp.bfloat16)
    router = jax.random.normal(kw, (32, 16), jnp.float32)
    top_e, weight = moe._route(x, router, 4, 2.5)
    low_e, low_w = cell._bf16_route(x, router, 4, 2.5)
    assert (low_e.shape, low_w.dtype) == (top_e.shape, jnp.float32)
    np.testing.assert_allclose(low_w.sum(-1), 2.5, rtol=2e-2)
    same = np.mean([len(set(a) & set(b)) for a, b in
                    zip(np.asarray(top_e), np.asarray(low_e))]) / 4
    assert 0.9 < same <= 1.0

    w = jnp.asarray([0.3, 1.7, -2.9], jnp.float32)
    lowered = cell._lowered(
        lambda p, extra, batch, config: (p["w"] * batch).sum(), True)
    value, grad = jax.value_and_grad(lowered)(
        {"w": w}, None, jnp.asarray([1.0, 2.0, 4.0]), None)
    rounded = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert not np.array_equal(rounded, w)
    assert float(value) == float((rounded * jnp.asarray([1., 2., 4.])).sum())
    np.testing.assert_array_equal(grad["w"], [1.0, 2.0, 4.0])
    # The rounding is by arithmetic (the TPU's compiler elides a cast
    # down and back up), and is the cast's to the bit: over normals,
    # subnormals (under 2**-6), ties, the largest value and beyond it.
    x = jnp.concatenate([
        jax.random.normal(kx, (4096,)) * 0.02,
        jax.random.normal(kw, (4096,)) * 30.0,
        jnp.asarray([0.0, 1.0, -1.0, 2.0 ** -6, 2.0 ** -9, 2.0 ** -10,
                     1.5 * 2.0 ** -9, 0.0146484375, 1.0625, 1.1875, 448.0,
                     -448.0, 464.0, 1e4, -1e4, 3e-5])])
    cast = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(
        cell.to_e4m3(x), jnp.where(jnp.abs(x) > 448, jnp.sign(x) * 448, cast))
    assert float(jnp.abs(cell.to_e4m3(x) - x).max()) > 0
    assert set(cell.CONTROLS) == {"no_routed", "no_window", "bf16_scores",
                                  "fp8_params", "bf16_reference",
                                  "fp8_reference"}
