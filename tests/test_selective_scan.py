"""``ops/selective_scan.py``'s chunked scan and its hand-written backward
pass against the recurrence one position after the other (a ``lax.scan``
in float32 with autodiff): values and all six gradients, at a length that
is no multiple of the chunk's square, chunks of one position and of the
whole sequence included, float32 and bfloat16; what the residuals hold;
the carry; the scope."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import selective_scan as module
from horovod_tpu.ops.selective_scan import selective_scan

T = 24  # 6 chunks of 4, 3 of 8: no multiple of 16 or 64
NAMES = ("x", "dt", "a", "b", "c", "d")


def sequential(x, dt, a, b, c, d):
    """h_t = exp(dt_t (x) A) h_{t-1} + (dt_t x_t) (x) B_t; y_t = h_t . C_t
    + D x_t; float32, one position after the other."""
    x, dt, b, c = (t.astype(jnp.float32) for t in (x, dt, b, c))

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, (h * c_t[:, None, :]).sum(-1) + d * x_t

    h0 = jnp.zeros((x.shape[0], *a.shape), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def arguments(seed, t, dtype, batch=2, channels=6, states=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, t, channels), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, channels))
                            - 2),
            -jnp.exp(jax.random.uniform(ks[2], (channels, states),
                                        maxval=2.7)),
            jax.random.normal(ks[3], (batch, t, states), dtype),
            jax.random.normal(ks[4], (batch, t, states), dtype),
            jax.random.normal(ks[5], (channels,)))


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5),
                                             (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("chunk,group_tokens", [
    (1, 1024), (4, 1024), (8, 1024), (T, 1024), (4, 8), (4, 4), (2, 8)],
    ids=["chunk1", "chunk4", "chunk8", "whole", "groups_of_2",
         "groups_of_1", "groups_of_4_of_12"])
def test_values_and_every_gradient_equal_the_recurrences(
        chunk, group_tokens, dtype, tolerance, monkeypatch):
    """Each result within ``tolerance`` of its own largest entry: float32
    differs by the order of its sums alone; bfloat16 inputs are widened,
    so the only rounding is of the results (against the recurrence on the
    same rounded inputs in float32). The backward pass in one group of
    chunks and in several."""
    monkeypatch.setattr(module, "GROUP_TOKENS", group_tokens)
    args = arguments(chunk, T, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def total(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    chunked = jax.jit(jax.value_and_grad(
        total(lambda *a: selective_scan(*a, chunk=chunk)),
        argnums=range(6)))
    plain = jax.jit(jax.value_and_grad(total(sequential), argnums=range(6)))
    y = selective_scan(*args, chunk=chunk)
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


def test_groups_are_the_most_chunks_that_divide_and_fit():
    assert module._group_size(64, 128) == 8     # the cell's: 1,024 tokens
    assert module._group_size(6, 4) == 6        # everything fits
    assert module._group_size(6, 256) == 3      # 4 would fit, 3 divides
    assert module._group_size(7, 512) == 1
    assert module._group_size(5, 4096) == 1     # a chunk over the bound


def test_the_residuals_hold_no_state_by_token():
    """What the forward pass keeps for the backward: the six arguments and
    the states entering the chunks, (batch, chunks, N, C) float32."""
    args = arguments(0, T, jnp.bfloat16)
    y, residuals = module._scan_fwd(*args, 4)
    assert y.shape == args[0].shape
    assert len(residuals) == 7
    for kept, arg in zip(residuals, args):
        assert kept is arg
    entering = residuals[-1]
    assert (entering.shape, entering.dtype) == ((2, 6, 5, 6), jnp.float32)
    # by shapes alone at the cell's size: 21 MB a layer, not 2.7 GB
    shapes = jax.eval_shape(
        lambda *a: module._scan_fwd(*a, 128)[1],
        *(jax.ShapeDtypeStruct(s, t) for s, t in (
            ((1, 8192, 5120), jnp.bfloat16), ((1, 8192, 5120), jnp.float32),
            ((5120, 16), jnp.float32), ((1, 8192, 16), jnp.bfloat16),
            ((1, 8192, 16), jnp.bfloat16), ((5120,), jnp.float32))))
    assert shapes[-1].shape == (1, 64, 16, 5120)
    assert 8192 * 5120 * 16 not in [int(np.prod(s.shape)) for s in shapes]


def test_a_ragged_length_is_refused():
    args = arguments(0, T + 3, jnp.float32)
    with pytest.raises(ValueError, match="no whole number of chunks"):
        selective_scan(*args, chunk=4)


def test_the_state_crosses_the_chunks(monkeypatch):
    """With the carry left out every chunk starts from a zero state: the
    first chunk's output stands, the later ones' does not (what the
    benchmark's ``no_carry`` control plants)."""
    args = arguments(3, T, jnp.float32)
    whole = selective_scan(*args, chunk=8)
    monkeypatch.setattr(
        module, "_carry", lambda decay, ends, start, reverse=False:
        (jnp.zeros_like(ends), start))
    cut = selective_scan(*args, chunk=8)
    np.testing.assert_array_equal(cut[:, :8], whole[:, :8])
    assert float(jnp.abs(cut[:, 8:] - whole[:, 8:]).max()) > 1e-2
    # and it equals the recurrence restarted at every chunk
    restarted = jnp.concatenate([
        sequential(*(t[:, i:i + 8] if t.ndim == 3 else t for t in args))
        for i in range(0, T, 8)], axis=1)
    np.testing.assert_allclose(cut, restarted, atol=2e-5)


def test_everything_traced_is_under_the_scans_scope():
    args = arguments(1, T, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda *a: selective_scan(*a, chunk=4).astype(jnp.float32).sum(),
        argnums=range(6))).lower(*args).compile().as_text()
    exps = [line for line in text.splitlines() if " exponential(" in line]
    assert len(exps) >= 5  # two forward, three or more backward
    for line in exps:
        assert "sel_scan" in line.split('op_name="')[1].split('"')[0], line
        assert " f32[" in line.split(" exponential(")[0], line  # decays
    assert " dot(" not in text  # no product over the channels
