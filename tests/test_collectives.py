"""Collective correctness, mirroring the reference oracle pattern:
allreduce == tensor * size etc. (reference: test/test_tensorflow.py:56-119,
test/test_torch.py:68-224), plus ranked variants with distinct per-rank
values — the multi-rank case the reference needs mpirun for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import collectives as C


def test_eager_allreduce_sum(hvd):
    x = jnp.arange(12.0).reshape(3, 4)
    out = hvd.allreduce(x, average=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * hvd.size())


def test_eager_allreduce_average(hvd):
    x = jnp.arange(12.0).reshape(3, 4)
    out = hvd.allreduce(x, average=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


def test_eager_allreduce_int(hvd):
    x = jnp.arange(6, dtype=jnp.int32)
    out = hvd.allreduce(x, average=False)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * hvd.size())


def test_ranked_allreduce_distinct(hvd):
    vals = [jnp.full((2, 3), float(r)) for r in range(hvd.size())]
    stacked = C.make_ranked(vals)
    out = C.ranked_allreduce(stacked)
    expect = sum(range(hvd.size()))
    np.testing.assert_allclose(np.asarray(out), np.full((2, 3), float(expect)))


def test_eager_allgather(hvd):
    x = jnp.arange(6.0).reshape(2, 3)
    out = hvd.allgather(x)
    assert out.shape == (2 * hvd.size(), 3)
    np.testing.assert_allclose(
        np.asarray(out), np.tile(np.asarray(x), (hvd.size(), 1))
    )


def test_ranked_allgather_distinct(hvd):
    vals = [jnp.full((2,), float(r)) for r in range(hvd.size())]
    out = C.ranked_allgather(C.make_ranked(vals))
    expect = np.repeat(np.arange(hvd.size(), dtype=np.float32), 2)
    np.testing.assert_allclose(np.asarray(out), expect)


def test_eager_broadcast(hvd):
    x = jnp.arange(4.0)
    for root in (0, hvd.size() - 1):
        out = hvd.broadcast(x, root_rank=root)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_ranked_broadcast_distinct(hvd):
    vals = [jnp.full((3,), float(r)) for r in range(hvd.size())]
    stacked = C.make_ranked(vals)
    for root in (0, 3, hvd.size() - 1):
        out = C.ranked_broadcast(stacked, root)
        np.testing.assert_allclose(np.asarray(out), np.full((3,), float(root)))


def test_ranked_reducescatter(hvd):
    n = hvd.size()
    vals = [jnp.arange(n, dtype=jnp.float32) + r for r in range(n)]
    out = C.ranked_reducescatter(C.make_ranked(vals))
    # Sum over ranks of vals = n*arange(n) + sum(r) ; rank r keeps chunk r.
    total = n * np.arange(n) + sum(range(n))
    assert out.shape == (n, 1)
    np.testing.assert_allclose(np.asarray(out)[:, 0], total)


def test_reducescatter_non_divisible_padding_contract(hvd):
    """Dim 0 not divisible by size: zero-pad to the next multiple, rank r
    keeps rows [r*c, (r+1)*c) of the padded sum, c = ceil(n/size) — the
    contract the sharded weight update composes on (allgather then slice
    [:n] recovers the original extent)."""
    n = hvd.size()
    rows = n + 2  # 10 rows over 8 ranks -> c = 2, padded to 16
    x = jnp.arange(rows * 3, dtype=jnp.float32).reshape(rows, 3)
    out = hvd.reducescatter(x)
    c = -(-rows // n)
    assert out.shape == (c, 3)
    # Eager semantics: every local chip contributes this controller's x,
    # so the sum is x * size; this process sees its FIRST rank's chunk.
    np.testing.assert_allclose(np.asarray(out), np.asarray(x[:c]) * n)


def test_ranked_reducescatter_non_divisible(hvd):
    n = hvd.size()
    rows = n + 2
    vals = [jnp.arange(rows, dtype=jnp.float32) + r for r in range(n)]
    out = C.ranked_reducescatter(C.make_ranked(vals))
    c = -(-rows // n)
    assert out.shape == (n, c)
    total = n * np.arange(rows) + sum(range(n))
    padded = np.zeros(n * c, np.float32)
    padded[:rows] = total
    np.testing.assert_allclose(np.asarray(out).ravel(), padded)


def test_spmd_reducescatter_allgather_roundtrip_non_divisible(hvd):
    """In-SPMD: reducescatter -> allgather -> [:n] == allreduce sum, for
    a leading dim the world size does not divide (the sharded-update
    round trip)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n = hvd.size()
    rows = 2 * n + 3

    def step(x):
        x = x[0]  # this rank's (rows,) contribution
        rs = hvd.reducescatter(x)
        back = hvd.allgather(rs)[:rows]
        return (back - hvd.allreduce(x, average=False))[None]

    xs = jnp.arange(n * rows, dtype=jnp.float32).reshape(n, rows)
    f = jax.jit(shard_map(
        step, mesh=hvd.mesh(), in_specs=P(C.HVD_AXIS, None),
        out_specs=P(C.HVD_AXIS, None), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(xs)), np.zeros((n, rows)),
                               atol=1e-5)


def test_reducescatter_scalar_raises(hvd):
    with pytest.raises(ValueError, match="at least one dimension"):
        hvd.reducescatter(jnp.float32(1.0))


def test_ranked_alltoall(hvd):
    n = hvd.size()
    # rank r's tensor: [r*n, r*n+1, ..., r*n+n-1]; after alltoall rank r
    # holds column r: [r, n+r, 2n+r, ...].
    vals = [jnp.arange(n, dtype=jnp.float32) + r * n for r in range(n)]
    out = C.ranked_alltoall(C.make_ranked(vals))
    expect = np.arange(n * n, dtype=np.float32).reshape(n, n).T
    np.testing.assert_allclose(np.asarray(out), expect)


def test_grouped_allreduce_mixed_dtypes(hvd):
    ts = [
        jnp.ones((4,), jnp.float32),
        jnp.ones((2, 2), jnp.float32) * 2,
        jnp.ones((3,), jnp.int32),
    ]
    out = hvd.grouped_allreduce(ts, average=False)
    np.testing.assert_allclose(np.asarray(out[0]), np.full((4,), hvd.size()))
    np.testing.assert_allclose(np.asarray(out[1]), np.full((2, 2), 2 * hvd.size()))
    np.testing.assert_array_equal(np.asarray(out[2]), np.full((3,), hvd.size()))
    assert out[2].dtype == jnp.int32


def test_allreduce_pytree(hvd):
    tree = {"a": jnp.ones((2,)), "b": [jnp.zeros((3,)), jnp.full((1,), 2.0)]}
    out = hvd.allreduce_pytree(tree, average=False)
    np.testing.assert_allclose(np.asarray(out["a"]), np.full((2,), hvd.size()))
    np.testing.assert_allclose(np.asarray(out["b"][1]), np.full((1,), 2.0 * hvd.size()))


def test_broadcast_pytree(hvd):
    tree = {"w": jnp.arange(4.0), "b": jnp.ones((2,), jnp.int32)}
    out = hvd.broadcast_pytree(tree, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.ones((2,), np.int32))


def test_in_spmd_collectives(hvd):
    """Collectives inside shard_map over the world mesh — the hot path."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = hvd.mesh()
    n = hvd.size()

    def step(x):
        # x: this rank's shard (1, 4)
        r = hvd.allreduce(x, average=False)
        m = hvd.allreduce(x, average=True)
        g = hvd.allgather(x)
        b = hvd.broadcast(x, root_rank=2)
        return r, m, g, b

    xs = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)
    f = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=P(C.HVD_AXIS, None),
            out_specs=(P(C.HVD_AXIS, None),) * 2 + (P(C.HVD_AXIS, None), P(C.HVD_AXIS, None)),
        )
    )
    r, m, g, b = f(xs)
    expect_sum = np.asarray(xs).sum(0, keepdims=True)
    np.testing.assert_allclose(np.asarray(r), np.tile(expect_sum, (n, 1)))
    np.testing.assert_allclose(np.asarray(m), np.tile(expect_sum / n, (n, 1)), rtol=1e-6)
    assert g.shape == (n * n, 4)
    np.testing.assert_allclose(np.asarray(b), np.tile(np.asarray(xs)[2:3], (n, 1)))


def test_jit_without_axis_raises(hvd):
    def f(x):
        return hvd.allreduce(x)

    with pytest.raises(Exception, match="hvd"):
        jax.jit(f)(jnp.ones((2,)))


def test_broadcast_nan_on_nonroot_does_not_poison(hvd):
    """Non-root NaN/Inf must not leak into the broadcast result."""
    vals = [jnp.full((3,), jnp.nan) for _ in range(hvd.size())]
    vals[2] = jnp.arange(3.0)
    out = C.ranked_broadcast(C.make_ranked(vals), 2)
    np.testing.assert_allclose(np.asarray(out), np.arange(3.0))


def test_broadcast_bool(hvd):
    vals = [jnp.zeros((4,), bool) for _ in range(hvd.size())]
    vals[1] = jnp.array([True, False, True, True])
    out = C.ranked_broadcast(C.make_ranked(vals), 1)
    assert out.dtype == bool
    np.testing.assert_array_equal(np.asarray(out), np.array([True, False, True, True]))


def test_broadcast_root_out_of_range(hvd):
    with pytest.raises(ValueError, match="out of range"):
        hvd.broadcast(jnp.arange(4.0), root_rank=hvd.size())


def test_spmd_int_average_preserves_dtype(hvd):
    """Traced and eager integer averaging must agree (floor-div, same dtype)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n = hvd.size()
    xs = jnp.full((n, 4), 3, dtype=jnp.int32)
    f = jax.jit(
        shard_map(
            lambda x: hvd.allreduce(x[0], average=True)[None],
            mesh=hvd.mesh(),
            in_specs=P(C.HVD_AXIS, None),
            out_specs=P(C.HVD_AXIS, None),
            check_vma=False,
        )
    )
    out = f(xs)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.full((n, 4), 3))


# -- the exchange inside a traced step: small leaves share a buffer, a
# -- large one is reduced as itself (grouped_allreduce) ---------------------

_BIG = C.FUSION_THRESHOLD_ELEMS  # a leaf of this many elements is large


def _mixed_tree(n, dtype, other):
    """Per-rank values (leading axis ``n``): a large and two small leaves
    in ``dtype``, a large and a small one in ``other``; one leaf exactly
    at the threshold, one just under it."""
    rng = np.random.default_rng(25)

    def leaf(shape, dt):
        if jnp.issubdtype(dt, jnp.integer):
            return jnp.asarray(rng.integers(-999, 999, (n, *shape)), dt)
        return jnp.asarray(rng.standard_normal((n, *shape)), dt)

    return {"big": leaf((8, _BIG // 8), dtype), "small": leaf((7, 3), dtype),
            "under": leaf((_BIG - 1,), dtype), "bias": leaf((5,), other),
            "other_big": leaf((2 * _BIG + 3,), other)}


_VERBS = ["grouped_allreduce", "allreduce_pytree", "jax.allreduce_pytree"]


def _exchange(hvd, verb, tree, average):
    """``tree`` through one of the three public spellings of the dense
    exchange."""
    import horovod_tpu.jax as hvd_jax

    if verb == "grouped_allreduce":
        leaves, treedef = jax.tree.flatten(tree)
        return jax.tree.unflatten(
            treedef, hvd.grouped_allreduce(leaves, average=average))
    fn = (hvd_jax if verb.startswith("jax.") else hvd).allreduce_pytree
    return fn(tree, average=average)


@pytest.mark.parametrize("average", [False, True], ids=["sum", "average"])
@pytest.mark.parametrize("dtype,other", [
    (jnp.float32, jnp.int32), (jnp.bfloat16, jnp.float32),
    (jnp.int32, jnp.float32)], ids=["f32+s32", "bf16+f32", "s32+f32"])
@pytest.mark.parametrize("verb", _VERBS)
def test_traced_exchange_equals_per_leaf_psum_bit_for_bit(
        hvd, verb, dtype, other, average):
    """Whichever leaf shares a buffer and whichever goes alone, each
    result is that leaf's own ``psum`` (then ``/ n``, or ``// n`` for an
    integer), to the bit."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    n = hvd.size()
    tree = _mixed_tree(n, dtype, other)

    def reference(x):
        s = lax.psum(x, C.HVD_AXIS)
        if not average:
            return s
        if jnp.issubdtype(x.dtype, jnp.integer):
            return s // n
        return (s / n).astype(x.dtype)

    def run(fn):
        return jax.jit(shard_map(
            lambda t: fn(jax.tree.map(lambda x: x[0], t)), mesh=hvd.mesh(),
            in_specs=P(C.HVD_AXIS), out_specs=P(), check_vma=False))(tree)

    got = run(lambda t: _exchange(hvd, verb, t, average))
    want = run(lambda t: jax.tree.map(reference, t))
    for key in tree:
        assert got[key].dtype == tree[key].dtype, key
        assert got[key].shape == tree[key].shape[1:], key
        np.testing.assert_array_equal(
            np.asarray(got[key].astype(jnp.float32)),
            np.asarray(want[key].astype(jnp.float32)), err_msg=key)


@pytest.mark.parametrize("verb", _VERBS)
def test_eager_exchange_still_issues_one_collective_per_dtype(
        hvd, verb, monkeypatch):
    """On concrete arrays every collective is a dispatch of its own, so a
    large leaf still rides the per-dtype buffer."""
    issued = []
    ranked = C.ranked_allreduce
    monkeypatch.setattr(
        C, "ranked_allreduce",
        lambda stacked, **kw: issued.append(
            (stacked.dtype, stacked.shape[1:])) or ranked(stacked, **kw))
    leaves = [jnp.ones((2 * _BIG,)), jnp.ones((3,)),
              jnp.ones((_BIG + 1,), jnp.int32), jnp.ones((2, 2), jnp.int32)]
    out = _exchange(hvd, verb, leaves, average=False)
    assert sorted(issued, key=str) == sorted(
        [(jnp.dtype(jnp.float32), (2 * _BIG + 3,)),
         (jnp.dtype(jnp.int32), (_BIG + 5,))], key=str)
    for leaf, reduced in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(reduced),
                                      np.asarray(leaf) * hvd.size())
