"""JAX frontend tests (reference: test/test_tensorflow.py — allreduce
average/compression/grads — and the DistributedOptimizer train-step tests in
test/test_keras.py:41-108)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hj
from horovod_tpu.jax import Compression


@pytest.fixture(autouse=True)
def _init(hvd):
    pass


def test_allreduce_fp16_compression():
    x = jnp.linspace(-1, 1, 16, dtype=jnp.float32)
    out = hj.allreduce(x, average=True, compression=Compression.fp16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-3)


def test_allreduce_bf16_compression():
    x = jnp.linspace(-1, 1, 16, dtype=jnp.float32)
    out = hj.allreduce(x, average=False, compression=Compression.bf16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * hj.size(), atol=0.1)


def test_sparse_allreduce():
    sparse = pytest.importorskip("jax.experimental.sparse")
    dense = jnp.zeros((6, 3)).at[1].set(2.0).at[4].set(-1.0)
    x = sparse.BCOO.fromdense(dense, nse=6)
    out = hj.allreduce(x, average=False)
    np.testing.assert_allclose(np.asarray(out.todense()), np.asarray(dense) * hj.size())
    out_avg = hj.allreduce(x, average=True)
    np.testing.assert_allclose(np.asarray(out_avg.todense()), np.asarray(dense), rtol=1e-6)
    out_dense = hj.allreduce(x, average=False, sparse_as_dense=True)
    np.testing.assert_allclose(np.asarray(out_dense), np.asarray(dense) * hj.size())


def test_broadcast_parameters_and_optimizer_state():
    params = {"w": jnp.arange(4.0), "b": jnp.ones(())}
    out = hj.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0))
    opt = optax.adam(1e-3)
    state = opt.init(params)
    state2 = hj.broadcast_optimizer_state(state, root_rank=0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        state, state2,
    )


def test_broadcast_object():
    obj = {"epoch": 7, "name": "resnet"}
    assert hj.broadcast_object(obj, root_rank=0) == obj


def _toy_data(n=64):
    rng = np.random.RandomState(0)
    X = rng.randn(n, 2).astype(np.float32)
    Y = X @ np.array([3.0, -1.0], np.float32) + 0.7
    return jnp.asarray(X), jnp.asarray(Y)


def _loss_fn(p, x, y):
    pred = x @ p["w"] + p["b"]
    return jnp.mean((pred - y) ** 2)


def test_distributed_optimizer_spmd_matches_full_batch():
    """DP (per-rank shards + averaged grads) must equal full-batch SGD —
    the fundamental data-parallel correctness invariant."""
    X, Y = _toy_data()
    params0 = {"w": jnp.zeros(2), "b": jnp.zeros(())}
    opt = hj.DistributedOptimizer(optax.sgd(0.1))

    def step(p, s, x, y):
        g = jax.grad(_loss_fn)(p, x, y)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s

    sharded_step = hj.jit(
        step, in_specs=(P(), P(), P("hvd", None), P("hvd")), out_specs=(P(), P())
    )
    p, s = params0, opt.init(params0)
    for _ in range(50):
        p, s = sharded_step(p, s, X, Y)

    # Reference: plain optax on the full batch.
    ref_opt = optax.sgd(0.1)
    rp, rs = params0, ref_opt.init(params0)
    for _ in range(50):
        g = jax.grad(_loss_fn)(rp, X, Y)
        up, rs = ref_opt.update(g, rs, rp)
        rp = optax.apply_updates(rp, up)

    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(rp["w"]), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(p["b"]), np.asarray(rp["b"]), rtol=1e-4)


def test_distributed_optimizer_eager():
    X, Y = _toy_data()
    params = {"w": jnp.zeros(2), "b": jnp.zeros(())}
    opt = hj.DistributedOptimizer(optax.sgd(0.1))
    state = opt.init(params)
    for _ in range(100):
        g = jax.grad(_loss_fn)(params, X, Y)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
    assert float(_loss_fn(params, X, Y)) < 1e-3


def test_backward_passes_per_step_accumulates():
    params = {"w": jnp.ones(2)}
    opt = hj.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=2)
    state = opt.init(params)
    g = {"w": jnp.ones(2)}
    updates, state = opt.update(g, state, params)
    # First micro-step: no update applied yet.
    np.testing.assert_allclose(np.asarray(updates["w"]), np.zeros(2))
    updates, state = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(updates["w"]), -0.1 * np.ones(2), rtol=1e-6)


def test_grad_and_value_and_grad_wrappers():
    X, Y = _toy_data(16)
    params = {"w": jnp.zeros(2), "b": jnp.zeros(())}
    g1 = hj.grad(_loss_fn)(params, X, Y)
    v, g2 = hj.value_and_grad(_loss_fn)(params, X, Y)
    ref = jax.grad(_loss_fn)(params, X, Y)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(ref["w"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g2["w"]), np.asarray(ref["w"]), rtol=1e-5)
    assert float(v) == pytest.approx(float(_loss_fn(params, X, Y)))


def test_gradient_through_spmd_collective():
    """Autodiff through the in-step collective: d/dx sum(pmean(x)) == 1/size
    per element per rank, summed over ranks' outputs == 1 (reference
    gradient tests: test_tensorflow.py:321-346)."""
    n = hvd_size = hj.size()
    xs = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)

    def loss(x):
        r = hj.allreduce(x, average=True)
        return jnp.sum(r)

    f = hj.jit(
        lambda x: jax.grad(loss)(x), in_specs=P("hvd", None), out_specs=P("hvd", None)
    )
    g = f(xs)
    # pmean's VJP is psum(ct)/n (the reference registers allreduce's gradient
    # as allreduce — tensorflow/mpi_ops.py:94-105): every rank's unit
    # cotangent flows to every rank's x with weight 1/n, summed over n ranks.
    # hj.fetch: g is rank-sharded; in a multi-controller world plain
    # np.asarray cannot fetch non-addressable shards.
    np.testing.assert_allclose(hj.fetch(g), np.ones((n, 2)), rtol=1e-6)


def _mixed_tree(seed=0):
    """Pytree mixing dtypes/shapes, like a real model's params."""
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 5)
    return {
        "dense": {"kernel": jax.random.normal(ks[0], (8, 16), jnp.float32),
                  "bias": jax.random.normal(ks[1], (16,), jnp.float32)},
        "embed": jax.random.normal(ks[2], (32, 4), jnp.bfloat16),
        "scale": jax.random.normal(ks[3], (4,), jnp.float32),
        # Above the fuse() threshold: exercises the per-tensor passthrough
        # beside the packed buffers.
        "wide": jax.random.normal(ks[4], (512, 9), jnp.float32),
    }


@pytest.mark.parametrize("make_opt", [
    lambda: optax.sgd(0.05, momentum=0.9),
    lambda: optax.adam(1e-2),
    lambda: optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1)),
], ids=["sgd_momentum", "adam", "global_clip_sgd"])
def test_fused_update_matches_unfused(make_opt):
    """hj.fuse() collapses per-parameter updates into per-dtype buffers
    without changing the math for elementwise transforms (and global-norm
    clipping, which is global either way). 5 steps, mixed f32/bf16 tree."""
    params_f = _mixed_tree()
    params_u = _mixed_tree()
    fused, plain = hj.fuse(make_opt()), make_opt()
    sf, su = fused.init(params_f), plain.init(params_u)
    for step in range(5):
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                np.random.RandomState(step).normal(size=p.shape), p.dtype),
            params_u)
        uf, sf = fused.update(grads, sf, params_f)
        uu, su = plain.update(grads, su, params_u)
        params_f = optax.apply_updates(params_f, uf)
        params_u = optax.apply_updates(params_u, uu)
    for a, b in zip(jax.tree.leaves(params_f), jax.tree.leaves(params_u)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-6)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_state_storage_policy_on_unsharded_paths(fused):
    """state_dtype='bf16' on the fused/plain (master-less) paths
    (HBM diet round 2, satellite): the optimizer state is *stored* bf16
    between steps — every non-scalar float buffer — while the update
    math runs f32; updates come back at the PARAM width and the
    trajectory tracks the f32 oracle within bf16 storage rounding."""
    params_p = _mixed_tree()
    params_u = _mixed_tree()
    mk = lambda: optax.adam(1e-2)
    policy = (hj.fuse(mk(), state_dtype="bf16") if fused
              else hj.state_storage(mk(), "bf16"))
    plain = mk()
    sp, su = policy.init(params_p), plain.init(params_u)
    # Storage layout: non-scalar float state (m/v, packed or not) lives
    # in bf16; the count scalar stays exact.
    bufs = [l for l in jax.tree.leaves(sp)
            if hasattr(l, "dtype") and jnp.ndim(l) >= 1
            and jnp.issubdtype(l.dtype, jnp.floating)]
    assert bufs and all(b.dtype == jnp.bfloat16 for b in bufs), [
        b.dtype for b in bufs]
    for step in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                np.random.RandomState(step).normal(size=p.shape), p.dtype),
            params_u)
        up, sp = policy.update(grads, sp, params_p)
        uu, su = plain.update(grads, su, params_u)
        for a, b in zip(jax.tree.leaves(up), jax.tree.leaves(params_p)):
            assert a.dtype == b.dtype, "updates must arrive at param width"
        params_p = optax.apply_updates(params_p, up)
        params_u = optax.apply_updates(params_u, uu)
    for a, b in zip(jax.tree.leaves(params_p), jax.tree.leaves(params_u)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_state_storage_identity_when_off():
    """state_dtype=None/'f32' is the identity wrapper — same state
    dtypes, same trajectory object-for-object semantics."""
    opt = hj.state_storage(optax.adam(1e-2), None)
    params = _mixed_tree()
    s = opt.init(params)
    bufs = [l for l in jax.tree.leaves(s)
            if hasattr(l, "dtype") and jnp.ndim(l) >= 1]
    # No downcast: m/v mirror the param dtypes (f32 stays f32).
    assert any(b.dtype == jnp.float32 for b in bufs)
    assert hj.canonical_state_dtype("f32") is None
    assert hj.canonical_state_dtype("bf16") == jnp.bfloat16


def test_distributed_optimizer_fused_update_spmd(hvd):
    """fused_update=True inside the compiled SPMD step gives the same
    trajectory as the default path (the profile-driven fast path every
    benchmark cell takes; VERDICT r3 item 1)."""
    xs, ys = _toy_data()
    n = hj.size()

    def run(fused):
        opt = hj.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                      fused_update=fused)
        p = {"w": jnp.ones((2,)), "b": jnp.zeros(())}
        s = opt.init(p)

        @hj.jit(in_specs=(P(), P(), P("hvd", None), P("hvd")),
                out_specs=(P(), P()))
        def step(p, s, x, y):
            g = jax.grad(_loss_fn)(p, x, y)
            u, s2 = opt.update(g, s, p)
            return optax.apply_updates(p, u), s2

        for _ in range(3):
            p, s = step(p, s, xs, ys)
        return p

    pf, pu = run(True), run(False)
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
