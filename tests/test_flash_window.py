"""The flash kernels' window and grouped key-value heads, in interpret
mode against plain masked softmax: the forward pass (output and row
log-sum-exp, at the shapes of blocks the benchmark's cells run) and the
three gradients; the fused backward kernel against the two it stands in
for, and the byte rule that chooses between them; and that the two
backward kernels still lower to the program they lowered to before the
forward's row statistics changed (``ops/flash_attention.py``)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as flash_module
from horovod_tpu.ops.flash_attention import (
    _Band,
    _delta,
    _fused_bwd,
    _fwd_bhsd,
    _two_kernel_bwd,
    flash_attention,
    fused_backward_fits,
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
    # differential attention's banded layers: head size 64, the window of
    # 512 over blocks of 512, two query heads a key-value head
    "window512_d64_group2": _case(1024, 64, 1, group=2, window=512,
                                  blocks=(512, 512)),
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


def _pallas_grids(dtype=jnp.bfloat16, s=2048, heads=2, d=64, kv_heads=None,
                  causal=True, window=None, **blocks):
    """``name=`` → grid of every pallas call in the gradient of
    ``flash_attention`` at a shape, from the jaxpr: nothing runs."""
    q = jax.ShapeDtypeStruct((1, s, heads, d), dtype)
    kv = jax.ShapeDtypeStruct((1, s, kv_heads or heads, d), dtype)

    def f(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, window=window, interpret=True,
            **blocks).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, kv, kv)
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


def test_forward_takes_a_wider_q_block_by_default():
    """Without a window and without blocks asked for, the forward's q
    block is up to 1,024 rows and the backward's stay at 512; a window
    or an explicit block keeps the kernels on the same blocks."""
    assert _pallas_grids() == {"flash_fwd_bhsd": (2, 2, 4),
                               "fused_flash_dkv_bwd_bhsd": (2, 1, 4, 4)}
    assert _pallas_grids(window=512)["flash_fwd_bhsd"] == (2, 4, 2)
    assert _pallas_grids(block_q=512)["flash_fwd_bhsd"] == (2, 4, 4)
    assert _pallas_grids(s=512)["flash_fwd_bhsd"] == (2, 1, 1)
    assert _pallas_grids(s=1536)["flash_fwd_bhsd"] == (2, 2, 3)


FUSED, TWO = ({"fused_flash_dkv_bwd_bhsd"},
              {"flash_dq_bwd_bhsd", "flash_dkv_bwd_bhsd"})

#: (sequence, head size, dtype) → the backward's kernels. A head's dq, dk
#: and dv as f32 accumulators and as output blocks in two buffers, lanes
#: padded to 128: 3 s max(d, 128) (4 + 2 itemsize) bytes against 32 MiB.
BYTE_RULE_CASES = {
    "bert_s2048_d64_bf16": (2048, 64, jnp.bfloat16, FUSED),       # 6 MiB
    "decoder_s8192_d128_bf16": (8192, 128, jnp.bfloat16, FUSED),  # 24 MiB
    "decoder_s8192_d128_f32": (8192, 128, jnp.float32, TWO),      # 36 MiB
    "s16384_d128_bf16": (16384, 128, jnp.bfloat16, TWO),          # 48 MiB
    "s16384_d64_bf16_lanes_padded": (16384, 64, jnp.bfloat16, TWO),
    "s8192_d256_bf16": (8192, 256, jnp.bfloat16, TWO),            # 48 MiB
    "s8192_d64_f32": (8192, 64, jnp.float32, TWO),                # 36 MiB
    "s4096_d128_f32": (4096, 128, jnp.float32, FUSED),            # 18 MiB
}


@pytest.mark.parametrize("case", list(BYTE_RULE_CASES))
def test_the_backward_is_fused_where_a_heads_accumulators_fit(case):
    """The byte rule, seen in the jaxpr's ``pallas_call`` names: a shape
    under the constant takes the fused kernel, one over it the two, and
    nothing but (s, d, dtype) decides."""
    s, d, dtype, want = BYTE_RULE_CASES[case]
    assert fused_backward_fits(s, d, dtype) == (want is FUSED)
    for kw in (dict(heads=1), dict(heads=6, kv_heads=1, window=512),
               dict(heads=1, causal=False)):
        found = set(_pallas_grids(dtype, s, d=d, **kw)) - {"flash_fwd_bhsd"}
        assert found == want, (kw, found)


def test_the_fused_grid_walks_a_groups_heads_outside_the_k_blocks():
    """Grid (key-value head, head of its group, k block, q block): the
    decoder cell's layers, and a band's two q blocks a k block."""
    def fused(**kw):
        return _pallas_grids(s=8192, d=128, **kw)["fused_flash_dkv_bwd_bhsd"]

    assert fused(heads=48, kv_heads=8) == (8, 6, 16, 16)
    assert fused(heads=72, kv_heads=8, window=512) == (8, 9, 16, 2)
    assert fused(heads=12, causal=False) == (12, 1, 16, 16)


def test_the_fused_calls_vmem_limit_follows_the_bytes(monkeypatch):
    """The call asks Mosaic for the head's bytes and the scoped default
    on top: 24 + 16 MiB at the decoder cell's shapes."""
    seen = []
    real = flash_module.pltpu.CompilerParams

    def params(**kw):
        seen.append(kw)
        return real(**kw)

    monkeypatch.setattr(flash_module.pltpu, "CompilerParams", params)
    _pallas_grids(s=8192, heads=6, kv_heads=1, d=128)
    _pallas_grids(s=1024, heads=1, d=64)
    assert seen == [{"vmem_limit_bytes": (24 + 16) << 20},
                    {"vmem_limit_bytes": (3 + 16) << 20}]


def _bwd_case(causal=True, window=None, group=1, d=64, s=64, blocks=(16, 16)):
    return dict(causal=causal, window=window, group=group, d=d, s=s,
                blocks=blocks)


#: The fused backward against the two kernels: non-causal, causal and
#: banded, each with one head a key-value head and with several, at both
#: head sizes, over a sequence of one block and of several (every pair of
#: those choices meets in some case); a band under, at and over a block;
#: blocks that differ; blocks of whole lane tiles.
BACKWARD_CASES = {
    "noncausal_group1_d64_one_block": _bwd_case(False, s=16),
    "noncausal_group3_d128_several_blocks": _bwd_case(False, group=3, d=128),
    "noncausal_group1_d128_whole_lane_tiles": _bwd_case(
        False, d=128, s=256, blocks=(128, 128)),
    "causal_group1_d128_one_block": _bwd_case(d=128, s=16),
    "causal_group3_d64_one_block": _bwd_case(group=3, s=16),
    "causal_group1_d64_several_blocks": _bwd_case(),
    "causal_group3_d128_several_blocks": _bwd_case(group=3, d=128),
    "causal_group2_unequal_blocks": _bwd_case(group=2, d=8, blocks=(32, 16)),
    "window_group1_d128_several_blocks": _bwd_case(window=24, d=128),
    "window_group3_d64_several_blocks": _bwd_case(window=24, group=3),
    "window_under_a_block_group9": _bwd_case(window=5, group=9, d=8),
    "window_a_block_group6": _bwd_case(window=16, group=6, d=8),
    "window_unequal_blocks_wide_q": _bwd_case(window=24, group=6, d=8,
                                              blocks=(32, 16)),
    "window_unequal_blocks_wide_k": _bwd_case(window=24, group=6, d=8,
                                              blocks=(16, 32)),
    # differential attention's banded layers, scaled to the window of a
    # block: head size 64, two query heads a key-value head
    "window_a_block_group2_d64": _bwd_case(window=16, group=2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(BACKWARD_CASES))
def test_fused_backward_equals_the_two_kernels(case, dtype):
    """Same operands, same f32 accumulators, same order of accumulation
    (dq over k blocks ascending; dk and dv over the group's heads, then q
    blocks ascending): dq, dk and dv equal the two kernels' to the bit."""
    c = BACKWARD_CASES[case]
    s, d, hk = c["s"], c["d"], 2
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, do = (jax.random.normal(key, (hk * c["group"], s, d),
                               jnp.float32).astype(dtype)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (hk, s, d), jnp.float32).astype(dtype)
            for key in keys[2:])
    out, lse = plain_forward(q, k, v, c["causal"], c["window"])
    args = (q, k, v, lse, _delta(do, out), do, c["causal"], *c["blocks"],
            True, _Band(c["window"], s, *c["blocks"]))
    fused, two = _fused_bwd(*args), _two_kernel_bwd(*args)
    for name, a, b in zip(("dq", "dk", "dv"), fused, two):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        assert np.abs(np.asarray(b, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=name)


#: sha256 of the lowering of the two-kernel backward alone (``delta``,
#: the dQ and the dK/dV kernel; interpret mode, bf16, (2, 256, 64),
#: blocks of 128), by ``causal``; taken from the commit before the
#: forward's row statistics changed (PR 27's) with this jax, when
#: ``_bwd_bhsd`` was these two kernels and nothing else. The forward is
#: free to change and small shapes now take the fused kernel; the two
#: kernels, the control of both changes, are pinned.
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

    # The recorded module carries its jitted function's name.
    def _bwd_bhsd(q, k, v, lse, do, out):
        return _two_kernel_bwd(q, k, v, lse, _delta(do, out), do, causal,
                               128, 128, True, _Band(None, 256, 128, 128))

    text = jax.jit(_bwd_bhsd).lower(x, x, x, row, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GOLDEN[causal]
