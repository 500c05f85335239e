"""``ops/ssd.py``'s chunked scan and its hand-written backward pass
against the recurrence one position after the other (a ``lax.scan`` in
float32 with autodiff): values and every gradient, over one, two and
five chunks, one group and several, float32 and bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd
from horovod_tpu.ops.ssd import ssd_scan

CHUNK = 8
NAMES = ("x", "dt", "a", "b", "c", "d")


def sequential(x, dt, a, b, c, d):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t; y_t = C_t.h_t + D x_t,
    head h with group h // (heads / groups); float32."""
    x, dt, b, c = (t.astype(jnp.float32) for t in (x, dt, b, c))
    heads, p = x.shape[2:]
    per_group = heads // b.shape[2]
    b, c = (jnp.repeat(t, per_group, axis=2) for t in (b, c))

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * b_t)[..., None] * x_t[..., None, :])
        return h, (c_t[..., None] * h).sum(-2) + d[:, None] * x_t

    h0 = jnp.zeros((x.shape[0], heads, b.shape[3], p), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def arguments(seed, t, heads, groups, dtype, batch=2, p=4, n=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, t, heads, p), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, heads)) - 2),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (batch, t, groups, n), dtype),
            jax.random.normal(ks[4], (batch, t, groups, n), dtype),
            jax.random.normal(ks[5], (heads,)))


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5),
                                             (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("heads,groups", [(4, 1), (6, 2)])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_values_and_every_gradient_equal_the_recurrences(
        chunks, heads, groups, dtype, tolerance):
    """Each result within ``tolerance`` of its own largest entry: float32
    differs by the order of its sums alone; with bfloat16 inputs the
    products are fed eight bits of mantissa (the decay sums and states
    stay float32), against the recurrence on the same rounded inputs in
    float32."""
    args = arguments(chunks, CHUNK * chunks, heads, groups, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def total(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    chunked = jax.jit(jax.value_and_grad(
        total(lambda *a: ssd_scan(*a, chunk=CHUNK)), argnums=range(6)))
    plain = jax.jit(jax.value_and_grad(total(sequential), argnums=range(6)))
    y = ssd_scan(*args, chunk=CHUNK)
    assert (y.shape, y.dtype) == (args[0].shape, dtype)
    want = sequential(*args)
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) <= \
        tolerance * float(jnp.abs(want).max())
    (_, got), (_, ref) = chunked(*args), plain(*args)
    for name, g, r, arg in zip(NAMES, got, ref, args):
        assert (g.shape, g.dtype) == (arg.shape, arg.dtype), name
        worst = float(jnp.abs(g.astype(jnp.float32)
                              - r.astype(jnp.float32)).max())
        assert worst <= tolerance * float(jnp.abs(r).max()), (name, worst)


def test_a_ragged_length_and_uneven_groups_are_refused():
    args = arguments(0, CHUNK * 2 + 3, 4, 1, jnp.float32)
    with pytest.raises(ValueError, match="no whole number of chunks"):
        ssd_scan(*args, chunk=CHUNK)
    x, dt, a, b, c, d = arguments(0, CHUNK, 6, 2, jnp.float32)
    with pytest.raises(ValueError, match="6 heads over 4 groups"):
        ssd_scan(x, dt, a, jnp.tile(b, (1, 1, 2, 1)),
                 jnp.tile(c, (1, 1, 2, 1)), d, chunk=CHUNK)


def test_the_state_crosses_the_chunks(monkeypatch):
    """With the carry left out every chunk starts from a zero state: the
    first chunk's output stands, the later ones' does not (what the
    benchmark's ``no_carry`` control plants)."""
    args = arguments(3, CHUNK * 3, 4, 2, jnp.float32)
    whole = ssd_scan(*args, chunk=CHUNK)
    monkeypatch.setattr(ssd, "_carry", lambda decay, states, reverse=False:
                        jnp.zeros_like(states))
    cut = ssd_scan(*args, chunk=CHUNK)
    np.testing.assert_array_equal(cut[:, :CHUNK], whole[:, :CHUNK])
    assert float(jnp.abs(cut[:, CHUNK:] - whole[:, CHUNK:]).max()) > 1e-2
    # and it equals the recurrence restarted at every chunk
    restarted = jnp.concatenate([
        sequential(*(t[:, i:i + CHUNK] if t.ndim > 1 else t for t in args))
        for i in range(0, CHUNK * 3, CHUNK)], axis=1)
    np.testing.assert_allclose(cut, restarted, atol=2e-5)


def test_everything_traced_is_under_the_scans_scope():
    args = arguments(1, CHUNK * 2, 4, 2, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda *a: ssd_scan(*a, chunk=CHUNK).astype(jnp.float32).sum(),
        argnums=range(6))).lower(*args).as_text(debug_info=True)
    lines = text.splitlines()
    products = [line for line in lines if "stablehlo.dot_general" in line]
    assert len(products) >= 10  # the backward pass alone has ten
    # the text names an op's place by reference: #locN = loc("<name stack>"
    places = {line.split(" = ")[0]: line for line in lines
              if line.startswith("#loc")}
    for line in products:
        place = places[line[line.rindex("loc(") + 4:line.rindex(")")]]
        assert "ssm_scan" in place, place
        # fed the inputs' type, accumulated in float32
        assert "bf16>, tensor" in line and line.rstrip().split(
            "-> tensor<")[1].split(">")[0].endswith("xf32"), line


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_chip_check_runs_at_a_small_shape(dtype):
    """``python -m horovod_tpu.ops.kernel_check`` holds the scan to the
    sequential recurrence at the hybrid cell's shape on the chip; here
    its two functions at a small one, against this file's recurrence
    too."""
    from horovod_tpu.ops import kernel_check

    shares = kernel_check.check_ssd(2, 64, 4, 8, 2, 8, 16, dtype=dtype)
    assert set(shares) == {"y", "dx", "ddt", "da", "db", "dc", "dd"}
    args = arguments(5, 64, 6, 2, jnp.float32)
    np.testing.assert_allclose(kernel_check.sequential_scan(*args),
                               sequential(*args), rtol=1e-5, atol=1e-5)
    assert kernel_check.SSD_SHAPE == (1, 8192, 32, 64, 2, 128, 128)
