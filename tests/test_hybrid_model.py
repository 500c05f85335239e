"""``models/hybrid.py``'s ``HybridLM`` against the benchmark's plain
reference (``benchmark/reference/hybrid_lm.py``) in float32: the loss and
every gradient leaf; the sigmoid router and the two-matrix experts of
``parallel/moe.py``; and the test that ties a chip's share to the model:
the head-shares of a state-space and of an attention block, and the
expert-shares of a latent expert block, add up to the uncut block."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

import tiny_hybrid_cell  # noqa: E402
from benchmark.harness import spec  # noqa: E402
from horovod_tpu.models import HybridConfig, HybridLM  # noqa: E402
from horovod_tpu.models import hybrid  # noqa: E402
from horovod_tpu.parallel.moe import (  # noqa: E402
    expert_share_layer, sigmoid_route)

family = spec.load_module("families", "hybrid_lm")
reference = spec.load_module("reference", "hybrid_lm")

#: The whole (uncut) model at a small size, what four chips share: the
#: benchmark's tiny cell with every count four times a share's.
WHOLE = dict(tiny_hybrid_cell.HYBRID, mamba_num_heads=8, mamba_head_dim=4,
             n_groups=4, num_attention_heads=8, num_key_value_heads=2,
             n_routed_experts=16, compute_dtype="float32")
TRAFFIC = dict(seq_len=64, attention="flash", remat=False)
SHARES = 4


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def whole():
    model = family.make_model(WHOLE, TRAFFIC)
    params, extra = family.init_variables(model, jax.random.PRNGKey(0),
                                          WHOLE, TRAFFIC)
    # a correction bias that is not zero, as a trained model's is
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    for block in ("block_1", "block_4"):
        params[block]["mixer"]["router_bias"] = bias
    batch = family.make_batch(jax.random.PRNGKey(1), 2, WHOLE, TRAFFIC)
    return model, params, extra, batch


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_equal_the_references(whole, remat):
    _, params, extra, batch = whole
    model = family.make_model(WHOLE, dict(TRAFFIC, remat=remat))
    with jax.default_matmul_precision("highest"):
        (got, counters), grads = jax.value_and_grad(
            lambda p: family.loss_fn(model, p, extra, batch),
            has_aux=True)(params)
        want, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, extra, batch, WHOLE))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (name, g), r in zip(_leaves(grads).items(),
                            jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-6, err_msg=name)
    # the correction bias moves the choice only: no gradient reaches it
    assert not float(jnp.abs(grads["block_1"]["mixer"]["router_bias"]).max())
    # holding every expert, every assignment is kept and none elsewhere
    assert counters["expert_kept"].shape == (2, 16)
    assert counters["expert_kept"].sum(1).tolist() == [2 * 64 * 4] * 2
    assert counters["expert_elsewhere"].tolist() == [0, 0]


def test_the_initial_values_are_the_configurations():
    model = family.make_model(WHOLE, TRAFFIC)
    params, _ = family.init_variables(model, jax.random.PRNGKey(3), WHOLE,
                                      TRAFFIC)
    mixer = params["block_0"]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))  # softplus
    assert dt.min() >= 0.001 * (1 - 1e-4) and dt.max() <= 0.1 * (1 + 1e-4)
    np.testing.assert_array_equal(mixer["D"], 1.0)
    np.testing.assert_array_equal(mixer["conv_bias"], 0.0)
    np.testing.assert_array_equal(
        params["block_1"]["mixer"]["router_bias"], 0.0)
    assert params["lm_head"]["kernel"].dtype == jnp.float32
    with pytest.raises(ValueError, match="names mixers"):
        HybridLM(HybridConfig(vocab_size=8, hidden_dim=8, pattern="MX")
                 ).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


# ---------------------------------------------------------------------------
# the router and the experts' form
# ---------------------------------------------------------------------------

def test_a_correction_bias_changes_who_is_chosen_and_not_the_weights():
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (96, 32))
    router = jax.random.normal(kw, (32, 16)) * 0.3
    bias = jax.random.normal(kb, (16,))
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)))
    plain_e, plain_w = sigmoid_route(jnp.zeros((16,)))(x, router, 4, 5.0)
    moved_e, moved_w = sigmoid_route(bias)(x, router, 4, 5.0)
    # who is chosen: the top-4 of score + bias
    want = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :4]
    assert [set(a) for a in np.asarray(moved_e)] == [set(a) for a in want]
    assert np.mean([set(a) != set(b) for a, b in
                    zip(np.asarray(plain_e), np.asarray(moved_e))]) > 0.5
    # the weights: the scores of the chosen WITHOUT the bias, summing to 5
    for top_e, weight in ((plain_e, plain_w), (moved_e, moved_w)):
        chosen = np.take_along_axis(scores, np.asarray(top_e), axis=1)
        np.testing.assert_allclose(
            weight, 5.0 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(weight.sum(1), 5.0, rtol=1e-5)
    # and no gradient reaches the bias
    grad = jax.grad(lambda b: sigmoid_route(b)(x, router, 4, 5.0)[1].sum()
                    )(bias)
    np.testing.assert_array_equal(grad, 0.0)


@pytest.mark.parametrize("block_rows", [8, 64])
def test_two_matrix_experts_equal_a_dense_loop_with_every_gradient(
        block_rows):
    """``w_gate`` None: ``Wd relu(Wu z)^2``, routed on another input than
    the experts read (the full-width one), by the sigmoid router."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    tokens, width, latent, ff, experts, held, first = 96, 32, 16, 24, 16, 4, 8
    h = jax.random.normal(ks[0], (tokens, width))
    z = jax.random.normal(ks[1], (tokens, latent))
    router = jax.random.normal(ks[2], (width, experts)) * 0.3
    bias = 0.2 * jax.random.normal(ks[3], (experts,))
    up = jax.random.normal(ks[4], (held, latent, ff)) * 0.3
    down = jax.random.normal(ks[5], (held, ff, latent)) * 0.3

    def layer(z, router, up, down):
        y, counts = expert_share_layer(
            z, router, None, up, down, first_expert=first, top_k=4,
            scaling=5.0, block_rows=block_rows, route=sigmoid_route(bias),
            router_x=h)
        return y, counts

    def dense(z, router, up, down):
        top_e, weight = sigmoid_route(bias)(h, router, 4, 5.0)
        y = 0.0
        for e in range(held):
            w_e = jnp.where(top_e == first + e, weight, 0.0).sum(-1)
            y += w_e[:, None] * (jnp.square(jax.nn.relu(z @ up[e]))
                                 @ down[e])
        return y

    with jax.default_matmul_precision("highest"):
        (y, (kept, elsewhere)) = layer(z, router, up, down)
        want = dense(z, router, up, down)
        mix = jax.random.normal(jax.random.PRNGKey(8), y.shape)
        got_g = jax.grad(lambda *a: (layer(*a)[0] * mix).sum(),
                         argnums=range(4))(z, router, up, down)
        want_g = jax.grad(lambda *a: (dense(*a) * mix).sum(),
                          argnums=range(4))(z, router, up, down)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    top_e = np.asarray(sigmoid_route(bias)(h, router, 4, 5.0)[0])
    np.testing.assert_array_equal(
        kept, [(top_e == first + e).sum() for e in range(held)])
    assert int(elsewhere) == tokens * 4 - int(kept.sum())
    for name, g, r in zip(("z", "router", "up", "down"), got_g, want_g):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5, err_msg=name)


# ---------------------------------------------------------------------------
# the share and the model: four chips' parts add up to the uncut block
# ---------------------------------------------------------------------------

def _columns(count, width, share):
    """The columns of ``count`` units of ``width`` that share ``share``
    of ``SHARES`` holds."""
    held = count // SHARES
    return np.arange(share * held * width, (share + 1) * held * width)


def _state_space_share(p, share):
    """The state-space mixer's parameters for the heads and groups of one
    share: columns of ``in_proj`` ([z | x | B | C | dt]) and channels of
    the convolution ([x | B | C]), the heads' scalars, the gated norm's
    channels and the rows of ``out_proj``."""
    heads, hp = WHOLE["mamba_num_heads"], WHOLE["mamba_head_dim"]
    groups, n = WHOLE["n_groups"], WHOLE["ssm_state_size"]
    inner, bc = heads * hp, groups * n
    of_heads, of_groups = _columns(heads, hp, share), _columns(groups, n,
                                                               share)
    channels = np.concatenate([of_heads, inner + of_groups,
                               inner + bc + of_groups])
    scalars = _columns(heads, 1, share)
    return {
        "in_proj": {"kernel": p["in_proj"]["kernel"][:, np.concatenate([
            of_heads, inner + channels, 2 * inner + 2 * bc + scalars])]},
        "conv_kernel": p["conv_kernel"][:, channels],
        "conv_bias": p["conv_bias"][channels],
        "dt_bias": p["dt_bias"][scalars], "A_log": p["A_log"][scalars],
        "D": p["D"][scalars], "norm_scale": p["norm_scale"][of_heads],
        "out_proj": {"kernel": p["out_proj"]["kernel"][of_heads]}}


def _attention_share(p, share):
    """Query heads 2 share .. 2 share + 1 and the key-value head they
    read: each of the two key-value heads is held by two shares."""
    q = _columns(WHOLE["num_attention_heads"], 1, share)
    kv = [share // (SHARES // WHOLE["num_key_value_heads"])]
    return {"query": {"kernel": p["query"]["kernel"][:, q]},
            "key": {"kernel": p["key"]["kernel"][:, kv]},
            "value": {"kernel": p["value"]["kernel"][:, kv]},
            "out": {"kernel": p["out"]["kernel"][q]}}


def _experts_share(p, share):
    held = _columns(WHOLE["n_routed_experts"], 1, share)
    return dict(p, experts_up=p["experts_up"][held],
                experts_down=p["experts_down"][held])


def _held(config):
    """The model configuration of one share of ``WHOLE``."""
    return family.make_model(dict(
        config, mamba_num_heads=config["mamba_num_heads"] // SHARES,
        n_groups=config["n_groups"] // SHARES,
        num_attention_heads=config["num_attention_heads"] // SHARES,
        num_key_value_heads=1,
        n_routed_experts=config["n_routed_experts"] // SHARES),
        TRAFFIC).cfg


@pytest.mark.parametrize("block,kind,cut", [
    ("block_0", "M", _state_space_share), ("block_3", "*", _attention_share),
    ("block_1", "E", _experts_share)])
def test_the_shares_of_a_block_add_up_to_the_uncut_reference(
        whole, block, kind, cut):
    """Each of four chips computes its heads' or its experts' part of the
    mixer from its slice of the parameters; the parts add up to the whole
    block's mixer as the plain reference computes it from all of them.
    What every chip computes alike, the shared expert, counts once; the
    projection out of the latent is applied to each share's routed part."""
    _, params, _, _ = whole
    p = params[block]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(6), (64, 32))
    cfg = _held(WHOLE)
    experts_held = WHOLE["n_routed_experts"] // SHARES

    def part(share):
        held = cut(p, share)
        if kind == "M":
            return hybrid.SSMMixer(cfg).apply({"params": held}, u[None])[0]
        if kind == "*":
            # the attention as the model builds it: the block with a unit
            # norm scale, less its residual, is the mixer of rms(u)
            block = hybrid.HybridBlock(cfg, "*").apply(
                {"params": {"norm": {"scale": jnp.ones(32)},
                            "mixer": held}}, u[None])
            return block[0][0] - u
        out, (kept, elsewhere) = hybrid.LatentExperts(dataclasses.replace(
            cfg, first_expert=share * experts_held)).apply(
                {"params": held}, u[None])
        assert int(kept.sum() + elsewhere) == 64 * 4
        return out[0]

    with jax.default_matmul_precision("highest"):
        parts = [part(share) for share in range(SHARES)]
        if kind == "*":
            want = reference._attention(
                reference._rms(u, jnp.ones(32), WHOLE["layer_norm_epsilon"]),
                p, WHOLE)
        else:
            want = reference._MIXERS[kind](u, p, WHOLE)
        if kind == "E":  # every share computed the shared expert: once
            shared = reference._relu2_mlp(u, p["shared_up"]["kernel"],
                                          p["shared_down"]["kernel"])
            parts = parts[:1] + [part - shared for part in parts[1:]]
    np.testing.assert_allclose(sum(parts), want, rtol=2e-4, atol=2e-5)
    # a share alone is not the block: each holds a real part of it
    for part in parts:
        assert float(jnp.abs(part).max()) > 1e-3
        assert float(jnp.abs(part - want).max()) > 1e-3
