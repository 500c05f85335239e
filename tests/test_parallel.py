"""Parallelism strategies on the virtual 8-device mesh: hierarchical
collectives vs flat equivalents, ring/Ulysses attention vs single-device
attention, TP layers vs dense reference, pipeline vs sequential stages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from horovod_tpu import parallel
from horovod_tpu.models.transformer import (
    causal_attention,
    dot_product_attention,
)


def _smap(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


@pytest.fixture(scope="module")
def devs():
    d = jax.devices()
    assert len(d) == 8, "conftest must provide 8 virtual devices"
    return d


# -- hierarchical collectives ------------------------------------------------

def test_hierarchical_allreduce_matches_flat(devs):
    mesh = parallel.hybrid_mesh({"dcn": 2, "ici": 4}, devs)
    x = np.random.RandomState(0).randn(8, 5, 3).astype(np.float32)

    def body(xs):
        return parallel.hierarchical_allreduce(xs[0], "ici", "dcn")[None]

    spec = P(("dcn", "ici"))
    out = _smap(body, mesh, spec, spec)(x)
    expect = x.sum(axis=0)
    for row in np.asarray(out).reshape(8, 5, 3):
        np.testing.assert_allclose(row, expect, rtol=1e-5)


def test_hierarchical_allreduce_average_and_padding(devs):
    mesh = parallel.hybrid_mesh({"dcn": 4, "ici": 2}, devs)
    # 7 elements: not divisible by ici=2, exercises the pad path
    # (reference analogue: FUSION_BUFFER_ATOMIC_UNIT, operations.h:52-54).
    x = np.random.RandomState(1).randn(8, 7).astype(np.float32)

    def body(xs):
        return parallel.hierarchical_allreduce(
            xs[0], "ici", "dcn", average=True)[None]

    spec = P(("dcn", "ici"))
    out = _smap(body, mesh, spec, spec)(x)
    for row in np.asarray(out).reshape(8, 7):
        np.testing.assert_allclose(row, x.mean(axis=0), rtol=1e-5)


def test_hierarchical_allgather_rank_order(devs):
    mesh = parallel.hybrid_mesh({"dcn": 2, "ici": 4}, devs)
    x = np.arange(16, dtype=np.float32).reshape(8, 2)  # rank r: [2r, 2r+1]

    def body(xs):
        return parallel.hierarchical_allgather(xs[0], "ici", "dcn")[None]

    spec = P(("dcn", "ici"))
    out = _smap(body, mesh, spec, spec)(x)
    got = np.asarray(out).reshape(8, 16)
    for row in got:
        np.testing.assert_array_equal(row, np.arange(16))


# -- ring attention ----------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(devs, causal):
    mesh = parallel.hybrid_mesh({"sp": 8}, devs)
    rng = np.random.RandomState(2)
    b, s, h, d = 2, 32, 2, 4
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    ref = (causal_attention if causal else dot_product_attention)(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    def body(q, k, v):
        return parallel.ring_attention(q, k, v, "sp", causal=causal)

    spec = P(None, "sp", None, None)
    out = _smap(body, mesh, (spec, spec, spec), spec)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_with_bias(devs):
    mesh = parallel.hybrid_mesh({"sp": 4}, devs[:4])
    rng = np.random.RandomState(3)
    b, s, h, d = 1, 16, 2, 4
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    bias = rng.randn(b, h, s, s).astype(np.float32)
    ref = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), jnp.asarray(bias))

    def body(q, k, v, bias):
        return parallel.ring_attention(q, k, v, "sp", bias=bias)

    spec = P(None, "sp", None, None)
    bspec = P(None, None, "sp", None)  # bias sharded on the *query* dim
    out = _smap(body, mesh, (spec, spec, spec, bspec), spec)(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


# -- Ulysses -----------------------------------------------------------------

def test_ulysses_attention_exact(devs):
    mesh = parallel.hybrid_mesh({"sp": 8}, devs)
    rng = np.random.RandomState(4)
    b, s, h, d = 2, 32, 8, 4
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    ref = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v))

    def body(q, k, v):
        return parallel.ulysses_attention(q, k, v, "sp")

    spec = P(None, "sp", None, None)
    out = _smap(body, mesh, (spec, spec, spec), spec)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_ulysses_attention_with_bias(devs):
    mesh = parallel.hybrid_mesh({"sp": 4}, devs[:4])
    rng = np.random.RandomState(7)
    b, s, h, d = 1, 16, 4, 4
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    bias = rng.randn(b, h, s, s).astype(np.float32)
    ref = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), jnp.asarray(bias))

    def body(q, k, v, bias):
        return parallel.ulysses_attention(q, k, v, "sp", bias=bias)

    spec = P(None, "sp", None, None)
    bspec = P(None, None, "sp", None)  # same layout as ring_attention's
    out = _smap(body, mesh, (spec, spec, spec, bspec), spec)(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_ulysses_rejects_indivisible_heads(devs):
    mesh = parallel.hybrid_mesh({"sp": 8}, devs)
    x = np.zeros((1, 8, 4, 2), np.float32)  # 4 heads, 8-way sp

    def body(q):
        return parallel.ulysses_attention(q, q, q, "sp")

    spec = P(None, "sp", None, None)
    with pytest.raises(ValueError, match="divisible"):
        _smap(body, mesh, spec, spec)(x)


# -- tensor parallel ---------------------------------------------------------

def test_parallel_mlp_matches_dense(devs):
    mesh = parallel.hybrid_mesh({"tp": 8}, devs)
    rng = np.random.RandomState(5)
    hid, mlp = 16, 32
    x = rng.randn(4, hid).astype(np.float32)
    w1 = rng.randn(hid, mlp).astype(np.float32)
    b1 = rng.randn(mlp).astype(np.float32)
    w2 = rng.randn(mlp, hid).astype(np.float32)
    b2 = rng.randn(hid).astype(np.float32)
    import flax.linen as nn

    ref = np.asarray(nn.gelu(jnp.asarray(x) @ w1 + b1) @ w2 + b2)

    mlp_mod = parallel.ParallelMLP(hidden_dim=hid, mlp_dim=mlp,
                                   dtype=jnp.float32)

    def body(x, w1, b1, w2, b2):
        params = {"wi": {"kernel": w1, "bias": b1},
                  "wo": {"kernel": w2, "bias": b2}}
        return mlp_mod.apply({"params": params}, x)

    out = _smap(
        body, mesh,
        (P(), P(None, "tp"), P("tp"), P("tp", None), P()),
        P(),
    )(x, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_column_parallel_rejects_indivisible(devs):
    mesh = parallel.hybrid_mesh({"tp": 8}, devs)
    mod = parallel.ColumnParallelDense(12, dtype=jnp.float32)  # 12 % 8 != 0

    def body(x):
        return mod.init(jax.random.PRNGKey(0), x)["params"]["kernel"]

    with pytest.raises(ValueError, match="divisible"):
        _smap(body, mesh, P(), P(None, "tp"))(np.zeros((2, 4), np.float32))


# -- pipeline ----------------------------------------------------------------

@pytest.mark.parametrize("m", [4, 8])
def test_pipeline_matches_sequential(devs, m):
    p = 4
    mesh = parallel.hybrid_mesh({"pp": p}, devs[:p])
    rng = np.random.RandomState(6)
    # Stage s: x -> tanh(x @ W_s + b_s)
    ws = rng.randn(p, 6, 6).astype(np.float32) * 0.5
    bs = rng.randn(p, 6).astype(np.float32) * 0.1
    x = rng.randn(m, 3, 6).astype(np.float32)  # m microbatches of (3, 6)

    expect = x.copy()
    for s in range(p):
        expect = np.tanh(expect @ ws[s] + bs[s])

    def stage_fn(params, a):
        w, b = params
        return jnp.tanh(a @ w + b)

    def body(ws, bs, x):
        return parallel.pipeline_apply(stage_fn, (ws[0], bs[0]), x, "pp")

    out = _smap(
        body, mesh, (P("pp"), P("pp"), P()), P()
    )(ws, bs, x)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


# -- MoE / expert parallelism ------------------------------------------------

def _moe_reference(x, router_w, wi, wo, capacity):
    """Per-token reference: gate * FFN_e(x) when within capacity, else 0."""
    import scipy.special

    logits = x @ router_w
    probs = scipy.special.softmax(logits, axis=-1)
    e_idx = np.argmax(probs, axis=-1)
    gate = np.max(probs, axis=-1)
    counts = {}
    out = np.zeros_like(x)
    for t in range(len(x)):
        e = int(e_idx[t])
        k = counts.get(e, 0)
        counts[e] = k + 1
        if k >= capacity:
            continue
        h = np.asarray(jax.nn.gelu(jnp.asarray(x[t] @ wi[e])))
        out[t] = gate[t] * (h @ wo[e])
    return out


def test_moe_layer_matches_reference(devs):
    ep = 4
    mesh = parallel.hybrid_mesh({"ep": ep}, devs[:ep])
    rng = np.random.RandomState(8)
    t_local, hidden, ff, e_local = 16, 8, 16, 2
    n_experts = ep * e_local
    x = rng.randn(ep * t_local, hidden).astype(np.float32)
    router = rng.randn(hidden, n_experts).astype(np.float32)
    wi = rng.randn(n_experts, hidden, ff).astype(np.float32) * 0.3
    wo = rng.randn(n_experts, ff, hidden).astype(np.float32) * 0.3
    cf = 4.0  # capacity ample: no drops
    capacity = max(1, int(t_local * cf / n_experts))

    def body(x, router, wi, wo):
        y, aux = parallel.moe_layer(x, router, wi, wo, "ep",
                                    capacity_factor=cf)
        return y, aux

    y, aux = _smap(
        body, mesh,
        (P("ep"), P(), P("ep"), P("ep")), (P("ep"), P()),
    )(x, router, wi, wo)
    # Reference per chip block (routing/capacity is per-chip).
    expect = np.concatenate([
        _moe_reference(x[c * t_local:(c + 1) * t_local], router, wi, wo,
                       capacity)
        for c in range(ep)
    ])
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0.0


def test_moe_capacity_drops_tokens(devs):
    ep = 2
    mesh = parallel.hybrid_mesh({"ep": ep}, devs[:ep])
    rng = np.random.RandomState(9)
    x = rng.randn(2 * 32, 8).astype(np.float32)
    # Router forcing every token to expert 0 -> most exceed capacity.
    router = np.zeros((8, 2), np.float32)
    router[:, 0] = 1.0
    x = np.abs(x)  # positive activations -> logits favor expert 0
    wi = rng.randn(2, 8, 8).astype(np.float32)
    wo = rng.randn(2, 8, 8).astype(np.float32)

    def body(x, router, wi, wo):
        y, aux = parallel.moe_layer(x, router, wi, wo, "ep",
                                    capacity_factor=0.25)
        return y, aux

    y, _ = _smap(body, mesh, (P("ep"), P(), P("ep"), P("ep")),
                 (P("ep"), P()))(x, router, wi, wo)
    zero_rows = np.sum(~np.any(np.asarray(y), axis=1))
    assert zero_rows > 0  # overflow tokens passed through as zeros


# -- hybrid 4D step ----------------------------------------------------------

def test_hybrid_4d_step_trains(devs):
    """One dp×pp×tp×sp step must run and reduce the loss."""
    from horovod_tpu.parallel import hybrid

    l0, l1 = hybrid.dryrun(8, devs)
    assert l1 < l0, (l0, l1)


def test_hybrid_stage_params_replicated_across_ep(devs):
    """Router/attention/MLP weights must be IDENTICAL across ep chips;
    only expert weights (wi/wo) may differ — divergent shared params would
    silently desynchronize the ep replicas."""
    import jax
    from horovod_tpu.parallel import hybrid

    mesh = parallel.hybrid_mesh(
        {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 2}, devs[:2])
    cfg = hybrid.HybridConfig()

    def body(key):
        import jax.numpy as jnp
        from jax import lax

        stage = hybrid.HybridStage(cfg)
        stage_key = jax.random.fold_in(
            jax.random.fold_in(key[0], lax.axis_index("pp")),
            lax.axis_index("tp"))
        dummy = jnp.zeros((2, cfg.seq_len, cfg.hidden_dim), cfg.dtype)
        p = stage.init(stage_key, dummy)["params"]
        return (p["moe_router_0"][None], p["moe_wi_0"][None],
                p["q_0"]["kernel"][None])

    router, wi, qk = _smap(
        body, mesh, P(), (P("ep"), P("ep"), P("ep"))
    )(jax.random.PRNGKey(0)[None])
    router, wi, qk = (np.asarray(t) for t in (router, wi, qk))
    np.testing.assert_array_equal(router[0], router[1])
    np.testing.assert_array_equal(qk[0], qk[1])
    assert not np.allclose(wi[0], wi[1]), "experts must be sharded"


def test_hybrid_without_ep_axis(devs):
    """use_moe=False must work on a mesh with NO ep axis (the 4-axis mesh
    documented in docs/parallelism.md)."""
    import jax
    from horovod_tpu.parallel import hybrid

    mesh = parallel.hybrid_mesh(
        {"dp": 1, "pp": 2, "tp": 2, "sp": 2}, devs)
    cfg = hybrid.HybridConfig(use_moe=False)
    step, _ = hybrid.build_train_step(mesh, cfg)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2 * cfg.microbatches, cfg.seq_len)
    ).astype(np.int32)
    l0, l1 = step(tokens, jax.random.PRNGKey(0))
    assert float(l1) < float(l0)


def test_hybrid_partition_axes():
    from horovod_tpu.parallel.hybrid import partition_axes

    assert partition_axes(8) == {"dp": 1, "pp": 2, "tp": 2, "sp": 2,
                                 "ep": 1}
    assert partition_axes(16) == {"dp": 1, "pp": 2, "tp": 2, "sp": 2,
                                  "ep": 2}
    assert partition_axes(1) == {"dp": 1, "pp": 1, "tp": 1, "sp": 1,
                                 "ep": 1}
    assert partition_axes(6) == {"dp": 3, "pp": 2, "tp": 1, "sp": 1,
                                 "ep": 1}


def test_mesh_validation(devs):
    with pytest.raises(ValueError, match="devices"):
        parallel.hybrid_mesh({"dp": 3}, devs)


def test_two_tier_mesh_single_host(devs):
    mesh = parallel.two_tier_mesh(devs)
    assert mesh.axis_names == ("dcn", "ici")
    assert mesh.devices.shape == (1, 8)
