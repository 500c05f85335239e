"""The flash kernels' window and grouped key-value heads, in interpret
mode against plain masked softmax: the forward pass (output and row
log-sum-exp, at the shapes of blocks the benchmark's cells run) and the
three gradients; and that the two backward kernels still lower to the
program they lowered to before the forward's row statistics changed
(``ops/flash_attention.py``)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    _Band,
    _bwd_bhsd,
    _fwd_bhsd,
    flash_attention,
)
from horovod_tpu.ops.kernel_check import plain_attention

S, D, HK, BLOCK = 64, 8, 2, 16


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

    want, want_vjp = jax.vjp(
        lambda *a: plain_attention(*a, True, window), q, k, v)
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


def plain_forward(q, k, v, causal, window):
    """(heads, s, d) masked softmax in f32: the output and each row's
    log-sum-exp, the forward kernel's two results."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = (jnp.repeat(t, q.shape[0] // k.shape[0], axis=0) for t in (k, v))
    scores = jnp.einsum("hqd,hkd->hqk", q, k,
                        precision="highest") * q.shape[-1] ** -0.5
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(q.shape[1])[None, :]
    keep = (j <= i) if causal else jnp.ones_like(j <= i)
    if window is not None:
        keep &= i - j < window
    scores = jnp.where(keep, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    out = jnp.einsum("hqk,hkd->hqd", jnp.exp(scores - lse), v,
                     precision="highest")
    return out, lse


def _case(s, d, heads, group=1, causal=True, window=None, blocks=(128, 128)):
    return dict(s=s, d=d, heads=heads, group=group, causal=causal,
                window=window, blocks=blocks)


#: What the benchmark's cells run, at sizes interpret mode affords: k
#: blocks of whole 128-lane tiles (the statistics' own layout) in one or
#: many steps a row, the forward's wider q block, both head sizes; and
#: blocks narrower than a tile, where the statistics take the block's
#: width. With window 40 and blocks of 128, rows 40 and up of a q block
#: see nothing of the first block they visit: their running maximum
#: starts, and stays, at NEG_INF for a whole step.
FORWARD_CASES = {
    "noncausal_d64_many_k_blocks": _case(512, 64, 2, causal=False),
    "noncausal_d64_one_k_block": _case(256, 64, 2, causal=False,
                                       blocks=(128, 256)),
    "noncausal_d64_wide_q_block": _case(512, 64, 2, causal=False,
                                        blocks=(256, 128)),
    "causal_d128_many_k_blocks": _case(384, 128, 2),
    "causal_d128_wide_q_block": _case(512, 128, 1, blocks=(256, 128)),
    "causal_d128_wide_k_block": _case(512, 128, 1, blocks=(128, 256)),
    "causal_d128_one_k_block": _case(128, 128, 2),
    "causal_d256": _case(256, 256, 1),
    **{f"window_{name}_group{group}": _case(384, 128, 1, group=group,
                                            window=window)
       for name, window in (("under_a_block_first_block_masked", 40),
                            ("a_block", 128), ("over_a_block", 200))
       for group in (1, 6, 9)},
    "full_group6": _case(256, 128, 2, group=6),
    "window_unequal_blocks": _case(512, 64, 1, group=6, window=150,
                                   blocks=(256, 128)),
    "narrow_blocks": _case(64, 8, 2, blocks=(16, 16)),
    "narrow_blocks_window_group6": _case(64, 8, 1, group=6, window=5,
                                         blocks=(32, 16)),
    "narrow_k_block_wide_head": _case(64, 64, 1, blocks=(16, 16)),
    "odd_block_wider_than_a_tile": _case(400, 128, 1, blocks=(200, 200)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(FORWARD_CASES))
def test_forward_output_and_lse_match_masked_softmax(case, dtype):
    """The forward kernel alone: ``out`` and ``lse`` (the backward's
    residuals). f32 at the tolerances of the tests above; bf16 inputs
    against the same f32 reference at bf16's rounding (the probabilities
    and the output round to eight bits of mantissa)."""
    c = FORWARD_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    shape_q = (c["heads"] * c["group"], c["s"], c["d"])
    shape_k = (c["heads"], c["s"], c["d"])
    q = jax.random.normal(keys[0], shape_q, jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], shape_k, jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], shape_k, jnp.float32).astype(dtype)
    out, lse = _fwd_bhsd(q, k, v, c["causal"], *c["blocks"], True,
                         c["window"])
    want, want_lse = plain_forward(q, k, v, c["causal"], c["window"])
    assert out.dtype == dtype and out.shape == shape_q
    assert lse.dtype == jnp.float32 and lse.shape == shape_q[:2] + (1,)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32), want,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(lse, want_lse, rtol=tol, atol=tol)


def test_forward_takes_a_wider_q_block_by_default():
    """Without a window and without blocks asked for, the forward's q
    block is up to 1,024 rows and the backward's stay at 512; a window
    or an explicit block keeps all three kernels on the same blocks."""
    def grids(window=None, s=2048, **blocks):
        x = jax.ShapeDtypeStruct((1, s, 2, 64), jnp.bfloat16)

        def f(q, k, v):
            return flash_attention(
                q, k, v, causal=True, window=window, interpret=True,
                **blocks).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(x, x, x)
        found = {}

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    name = eqn.params["name"]
                    found[name] = eqn.params["grid_mapping"].grid
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return found

    assert grids() == {"flash_fwd_bhsd": (2, 2, 4),
                       "flash_dq_bwd_bhsd": (2, 4, 4),
                       "flash_dkv_bwd_bhsd": (2, 4, 4)}
    assert grids(window=512)["flash_fwd_bhsd"] == (2, 4, 2)
    assert grids(block_q=512)["flash_fwd_bhsd"] == (2, 4, 4)
    assert grids(s=512)["flash_fwd_bhsd"] == (2, 1, 1)
    assert grids(s=1536)["flash_fwd_bhsd"] == (2, 2, 3)


#: sha256 of the lowering of ``_bwd_bhsd`` alone (the dQ and the dK/dV
#: kernel; interpret mode, bf16, (2, 256, 64), blocks of 128), by
#: ``causal``; taken from the commit before the forward's row statistics
#: changed (PR 27's) with this jax. The forward is free to change; the
#: two backward kernels, the control of that change, are pinned.
GOLDEN_JAX = "0.9.0"
GOLDEN = {
    False: "b79a18fb49eb057d6e0be374c5e20a90d55b409731fee01bbbd8c48ee834b65e",
    True: "101f71b32b58819993248512b6db2ff44a4d1be359604e21adc754947cf236ff",
}


@pytest.mark.parametrize("causal", [False, True])
def test_without_window_and_groups_the_program_is_the_old_one(causal):
    if jax.__version__ != GOLDEN_JAX:
        pytest.skip(f"the recorded lowering is jax {GOLDEN_JAX}'s")
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((2, 256, 1), jnp.float32)
    text = _bwd_bhsd.lower(x, x, x, row, x, x, causal, 128, 128,
                           True).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GOLDEN[causal]
