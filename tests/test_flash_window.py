"""The flash kernels' window and grouped key-value heads, in interpret
mode against plain masked softmax: the forward pass and the three
gradients; and that with neither the kernels still lower to the program
they lowered to before (``ops/flash_attention.py``)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import _Band, flash_attention

S, D, HK, BLOCK = 64, 8, 2, 16


def plain_attention(q, k, v, window):
    """Masked softmax attention, f32; k and v repeated over the group."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    scores = jnp.where(keep, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _inputs(group, seed=0):
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (2, S, HK * group, D), jnp.float32)
    k = jax.random.normal(kk, (2, S, HK, D), jnp.float32)
    v = jax.random.normal(kv, (2, S, HK, D), jnp.float32)
    w = jax.random.normal(kw, q.shape, jnp.float32)  # a cotangent
    return q, k, v, w


def _check(window, group, block_q, block_k, seed=0):
    """Forward and the three gradients against plain masked softmax."""
    q, k, v, w = _inputs(group, seed)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=True)

    want, want_vjp = jax.vjp(lambda *a: plain_attention(*a, window), q, k, v)
    got, got_vjp = jax.vjp(flash, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", got_vjp(w), want_vjp(w)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("group", [1, 6, 9])
@pytest.mark.parametrize("window", [None, 5, BLOCK, 40],
                         ids=["full", "under_a_block", "a_block",
                              "several_blocks"])
def test_window_and_grouped_heads_match_masked_softmax(window, group):
    _check(window, group, BLOCK, BLOCK)


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32)])
def test_window_with_unequal_blocks(block_q, block_k):
    _check(24, 6, block_q, block_k, seed=1)


def test_a_band_visits_only_the_blocks_it_touches():
    """At the benchmark's shape (8,192 positions, blocks of 512, window
    512) a q block visits 2 of the 16 k blocks and a k block 2 q blocks:
    the grid's innermost axis is that long, not 16."""
    band = _Band(512, 8192, 512, 512)
    assert (band.n_k, band.n_q) == (2, 2)
    assert [band.k_first(i) for i in (0, 1, 15)] == [0, 0, 14]
    assert [band.q_last(i) for i in (0, 14, 15)] == [1, 15, 15]
    # Every visible pair lies in a visited block, for blocks that differ.
    for bq, bk, window in ((32, 16, 24), (16, 32, 24), (16, 16, 1)):
        band = _Band(window, S, bq, bk)
        for i in range(S):
            for j in range(max(0, i - window + 1), i + 1):
                qb, kb = i // bq, j // bk
                assert band.k_first(qb) <= kb <= band.k_last(qb)
                assert kb - band.k_first(qb) < band.n_k
                assert band.q_first(kb) <= qb <= band.q_last(kb)
                assert qb - band.q_first(kb) < band.n_q
    full = _Band(None, S, 16, 16)
    assert (full.n_k, full.n_q) == (4, 4)


def test_refusals():
    q, k, v, _ = _inputs(6)
    with pytest.raises(ValueError, match="window goes with causal"):
        flash_attention(q, k, v, window=8, interpret=True)
    with pytest.raises(ValueError, match="divides the query's"):
        flash_attention(q[:, :, :5], k, v, causal=True, interpret=True)
    # A window that covers the sequence is plain causal attention.
    a = flash_attention(q, k, v, causal=True, window=S, interpret=True)
    b = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_array_equal(a, b)


#: sha256 of the lowering (forward and the three gradients, interpret
#: mode, bf16, (1, 256, 2, 64), blocks of 128) of the kernels as they were
#: before the window and the grouped heads, by ``causal``; taken from the
#: parent commit with this jax.
GOLDEN_JAX = "0.9.0"
GOLDEN = {
    False: "ddc1d3914241e3fd924da1215425d41fe6f5698c976d530d8d24fec6237a1f89",
    True: "2f6b7ad60470c8606e69a24f8b397b1adf395a18aa8db861bdcff92bbe46f389",
}


@pytest.mark.parametrize("causal", [False, True])
def test_without_window_and_groups_the_program_is_the_old_one(causal):
    if jax.__version__ != GOLDEN_JAX:
        pytest.skip(f"the recorded lowering is jax {GOLDEN_JAX}'s")

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, interpret=True).astype(
                                   jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(x, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GOLDEN[causal]
