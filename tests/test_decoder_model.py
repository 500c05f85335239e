"""The system's decoder (``models/decoder.py``, flash kernels interpreted,
the grouped expert products) against the benchmark's plain reference
(``benchmark/reference/decoder_lm.py``) on seeded weights at a small
size, in float32: the loss and every gradient leaf; the routing counters;
the rotary frequencies against the published recipe's numbers."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402

#: Six layers of both kinds (dense first, then sparse; full and sliding in
#: the published 1 : 3 pattern), 16 experts top-4 of which 4 are held, a
#: window of 8 at 64 positions, query heads 4 | 6 over 2 key-value heads.
TINY = dict(
    family="decoder_lm", hidden_size=32, head_dim=8, num_key_value_heads=2,
    num_hidden_layers=6, vocab_size=48, intermediate_size=64,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention", "sliding_attention"],
    num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6],
    mlp_layer_types=["dense"] + ["sparse"] * 5,
    sliding_window=8, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    num_experts=4, published={"num_experts": 16}, first_expert=4,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, moe_routed_scaling_factor=2.5,
    moe_router_logit_softcapping=0, moe_apply_router_weight_on_input=False,
    compute_dtype="float32")
TRAFFIC = dict(seq_len=64, attention="flash", remat=True)

family = spec.load_module("families", "decoder_lm")
reference = spec.load_module("reference", "decoder_lm")


@pytest.fixture(scope="module")
def made():
    model = family.make_model(TINY, TRAFFIC)
    params, extra = family.init_variables(model, jax.random.PRNGKey(3),
                                          TINY, TRAFFIC)
    batch = family.make_batch(jax.random.PRNGKey(4), 2, TINY, TRAFFIC)
    return model, params, extra, batch


def test_loss_and_every_gradient_leaf_match_the_reference(made):
    model, params, extra, batch = made
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: family.loss_fn(model, p, extra, batch),
            has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, extra, batch, TINY)))(params)
    assert abs(float(loss) - float(want)) < 2e-5, (loss, want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_want = jax.tree.leaves(want_grads)
    assert len(flat) == len(flat_want) > 40
    for (path, got), ref in zip(flat, flat_want):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, jax.tree_util.keystr(path)  # every leaf is used
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * scale, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))

    # The counters: five sparse layers, every assignment counted once.
    kept, elsewhere = counters["expert_kept"], counters["expert_elsewhere"]
    assert kept.shape == (5, 4) and elsewhere.shape == (5,)
    assert kept.dtype == elsewhere.dtype == jnp.int32
    np.testing.assert_array_equal(kept.sum(1) + elsewhere,
                                  2 * 64 * TINY["num_experts_per_tok"])
    assert int(kept.sum()) > 0


def test_parameter_tree_has_the_names_trace_readers_go_by(made):
    _, params, _, _ = made
    assert sorted(params) == sorted(
        ["final_norm", "lm_head", "tok_embed"]
        + [f"layer_{i}" for i in range(6)])
    assert sorted(params["layer_0"]) == [
        "attention", "attention_norm", "mlp_down", "mlp_gate", "mlp_norm",
        "mlp_up"]
    assert sorted(params["layer_1"]["moe"]) == [
        "experts_down", "experts_gate", "experts_up", "router",
        "shared_down", "shared_gate", "shared_up"]
    a0, a1 = params["layer_0"]["attention"], params["layer_1"]["attention"]
    assert a0["query"]["kernel"].shape == (32, 4, 8)
    assert a1["query"]["kernel"].shape == (32, 6, 8)
    assert a1["key"]["kernel"].shape == (32, 2, 8)
    assert a1["gate"]["kernel"].shape == (32, 6)
    assert params["layer_1"]["moe"]["router"].shape == (32, 16)
    assert params["layer_1"]["moe"]["experts_down"].shape == (4, 16, 32)
    assert "bias" not in str(jax.tree_util.tree_structure(params))


def test_yarn_frequencies_at_the_published_numbers():
    """Head 128, half rotated, theta 500,000, factor 128 over 8,192: the
    ramp runs from dim 9 to dim 18 of the 32 (worked by hand)."""
    rope = decoder.RopeSpec(
        theta=500000.0, rotary_dim=64, yarn_factor=128.0,
        original_max_len=8192, beta_fast=32.0, beta_slow=1.0,
        attention_factor=1.4852030263919618)
    inv = np.asarray(decoder.rope_inv_freq(rope))
    plain = 500000.0 ** (-np.arange(32) * 2.0 / 64)
    c = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    mid = (1 - 4 / 9) * plain[13] + (4 / 9) * plain[13] / 128
    np.testing.assert_allclose(inv[13], mid, rtol=1e-6)
    window = decoder.RopeSpec(theta=10000.0, rotary_dim=128)
    np.testing.assert_allclose(
        np.asarray(decoder.rope_inv_freq(window)),
        10000.0 ** (-np.arange(64) * 2.0 / 128), rtol=1e-6)
    # The reference's own frequencies are the same numbers.
    ref = np.asarray(reference._inv_freq(
        TINY["rope_parameters"]["full_attention"], 64))
    np.testing.assert_allclose(ref, inv, rtol=1e-6)
