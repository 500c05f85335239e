"""The FLOP and byte functions the MFU and roofline metrics rest on,
against counts that do not come from them: the matmul and convolution
FLOPs in the jaxpr of the family's own loss, XLA's ``cost_analysis`` of
the forward pass, and figures worked out by hand."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402

TINY = {
    "transformer_lm": (
        dict(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
             intermediate_size=1024, vocab_size=1024,
             max_position_embeddings=128, compute_dtype="float32"),
        dict(seq_len=128, attention="stock", remat=False)),
    "resnet": (
        dict(stage_sizes=[1, 2], num_filters=16, num_classes=10,
             compute_dtype="float32"),
        dict(image_size=32)),
}
SAMPLES = 2


def _matmul_flops(jaxpr) -> float:
    """2 x output elements x contracted length of every ``dot_general``
    and ``conv_general_dilated`` in ``jaxpr``, sub-jaxprs included."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * np.prod(eqn.outvars[0].aval.shape) * np.prod(
                [lhs[i] for i in contract])
        elif eqn.primitive.name == "conv_general_dilated":
            kernel = eqn.invars[1].aval.shape
            out_features = kernel[eqn.params["dimension_numbers"].rhs_spec[0]]
            total += (2.0 * np.prod(eqn.outvars[0].aval.shape)
                      * np.prod(kernel) / out_features)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _matmul_flops(inner)
    return total


def _tiny(name):
    import jax

    config, traffic = TINY[name]
    family = spec.load_module("families", name)
    model = family.make_model(config, traffic)
    key = jax.random.PRNGKey(0)
    params, extra = family.init_variables(model, key, config, traffic)
    batch = family.make_batch(key, SAMPLES, config, traffic)
    items = SAMPLES * family.items_per_sample(config, traffic)

    def loss(p):
        return family.loss_fn(model, p, extra, batch)[0]

    return family, config, traffic, loss, params, items


@pytest.mark.parametrize("name", sorted(TINY))
def test_forward_flops_equal_the_jaxprs_matmuls(name):
    import jax

    family, config, traffic, loss, params, items = _tiny(name)
    counted = _matmul_flops(jax.make_jaxpr(loss)(params).jaxpr)
    assert family.forward_flops_per_item(config, traffic) * items == counted


@pytest.mark.parametrize("name,rel", [("transformer_lm", 0.02),
                                      ("resnet", 0.05)])
def test_forward_flops_against_xla_cost_analysis(name, rel):
    """XLA also counts the elementwise arithmetic (softmax, gelu, norms)
    and leaves out a convolution's taps on the padding, which is a large
    share of a 32-pixel image and a small one of 224 pixels; so 2% for
    the matmul model, 5% for the convolutional one."""
    import jax

    family, config, traffic, loss, params, items = _tiny(name)
    cost = jax.jit(loss).lower(params).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    ours = family.forward_flops_per_item(config, traffic) * items
    assert cost["flops"] == pytest.approx(ours, rel=rel)


def test_backward_is_twice_forward_in_the_transformers_jaxpr():
    """Only the transformer: a strided convolution's gradient is a
    dilated convolution, whose jaxpr shape counts inserted zeros."""
    import jax

    family, config, traffic, loss, params, items = _tiny("transformer_lm")
    counted = _matmul_flops(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert family.model_flops_per_item(config, traffic) * items == counted


def _cell_files(config, traffic):
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           traffic + ".json")) as f:
        return c, json.load(f)


def test_resnet50_is_the_customary_4_1_gmac():
    family = spec.load_module("families", "resnet")
    config, traffic = _cell_files("resnet50", "img224_bs128")
    assert len(family.conv_shapes(config, traffic)) == 53
    forward = family.forward_flops_per_item(config, traffic)
    assert forward / 2 == pytest.approx(4.09e9, rel=0.005)
    stem = 2.0 * 112 * 112 * 7 * 7 * 3 * 64
    assert family.model_flops_per_item(config, traffic) == 3 * forward - stem


def test_bert_base_flops_per_token_by_hand():
    family = spec.load_module("families", "transformer_lm")
    config, traffic = _cell_files("bert_base", "seq512_bs16")
    layer = 8 * 768 * 768 + 4 * 768 * 3072 + 4 * 512 * 768
    assert family.model_flops_per_item(config, traffic) == 3.0 * (
        12 * layer + 2 * 768 * 30522)


def test_flash_flops_and_bytes_by_hand_at_one_shape():
    roofline = spec.load_module("metrics", "flash_roofline")
    b, s, heads, d = 4, 2048, 12, 64
    matmul = 2 * b * heads * s * s * d          # one s x s x d product
    assert matmul == 25_769_803_776
    assert roofline.attention_flops(b, s, heads, d) == 7 * matmul
    assert roofline.attention_flops(b, s, heads, d, causal=True) == \
        3.5 * matmul
    tensor = b * s * heads * d * 2              # one bf16 (b, s, h, d)
    assert tensor == 12_582_912
    # forward: q, k, v in, o out; backward: q, k, v, o, do in, dq, dk, dv out
    assert roofline.attention_bytes(b, s, heads, d) == 12 * tensor
    # compute-bound on a v5e: 0.92 ms of FLOPs against 0.18 ms of bytes
    assert 7 * matmul / 197e12 > 4 * (12 * tensor / 819e9)
