"""Model zoo shape/grad sanity (the reference has no model tests — its
examples are the coverage; here models are first-party so they get real
tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import models


def _init_and_apply(model, x, train=False):
    rng = jax.random.PRNGKey(0)
    variables = model.init({"params": rng, "dropout": rng}, x, train)
    out = model.apply(variables, x, train,
                      rngs={"dropout": rng} if train else None,
                      mutable=["batch_stats"] if train else False)
    return variables, out


def test_mnist_cnn_shapes():
    m = models.MnistConvNet()
    x = jnp.zeros((4, 784))
    _, out = _init_and_apply(m, x)
    assert out.shape == (4, 10)


def test_mnist_mlp_shapes():
    m = models.MnistMLP()
    _, out = _init_and_apply(m, jnp.zeros((2, 28, 28, 1)))
    assert out.shape == (2, 10)


@pytest.mark.parametrize("name,blocks", [("resnet18", 8), ("resnet50", 16)])
def test_resnet_shapes(name, blocks):
    m = models.get_model(name, num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables, out = _init_and_apply(m, x)
    assert out[0].shape == (2, 10) if isinstance(out, tuple) else out.shape == (2, 10)


def test_resnet50_param_count():
    """ResNet-50 ImageNet has ~25.6M params; a structural checksum."""
    m = models.ResNet50(num_classes=1000, dtype=jnp.float32)
    variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)),
                      False)
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 25.4e6 < n < 25.8e6, n


def test_resnet_train_updates_batch_stats():
    m = models.ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    rng = jax.random.PRNGKey(0)
    variables = m.init(rng, x, True)
    out, mutated = m.apply(variables, x, True, mutable=["batch_stats"])
    assert out.shape == (2, 10)
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_vgg16_param_count():
    m = models.VGG16(num_classes=1000, dtype=jnp.float32)
    variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)),
                      False)
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 138e6 < n < 139e6, n  # the communication-bound headline model


def test_inception_v3_param_count_and_shape():
    """Inception V3 ImageNet: ~23.8M params (torchvision: 23.83M w/o aux);
    299x299 input -> 8x8 final grid."""
    m = models.InceptionV3(num_classes=1000, dtype=jnp.float32)
    variables = m.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 299, 299, 3)), False)
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 23.0e6 < n < 24.5e6, n
    out = m.apply(variables, jnp.zeros((2, 299, 299, 3)), False)
    assert out.shape == (2, 1000)


def test_word2vec_loss_decreases():
    m = models.Word2Vec(vocab_size=100, embedding_dim=16)
    rng = jax.random.PRNGKey(0)
    center = jnp.array([1, 2, 3, 4])
    context = jnp.array([2, 3, 4, 5])
    negs = jax.random.randint(rng, (4, 5), 0, 100)
    variables = m.init(rng, center)

    def loss_fn(params):
        return m.apply({"params": params}, center, context, negs,
                       method=m.neg_loss)

    params = variables["params"]
    l0 = loss_fn(params)
    g = jax.grad(loss_fn)(params)
    params = jax.tree_util.tree_map(lambda p, gr: p - 0.5 * gr, params, g)
    l1 = loss_fn(params)
    assert l1 < l0


def test_transformer_lm_forward_and_grad():
    cfg = models.TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=2, hidden_dim=32,
        mlp_dim=64, max_len=16, dtype=jnp.float32, causal=True)
    m = models.TransformerLM(cfg)
    tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]])
    variables = m.init(jax.random.PRNGKey(0), tokens)
    logits = m.apply(variables, tokens)
    assert logits.shape == (1, 8, 128)

    def loss_fn(params):
        lg = m.apply({"params": params}, tokens)
        tgt = jnp.roll(tokens, -1, axis=1)
        return jnp.mean(
            -jax.nn.log_softmax(lg)[0, jnp.arange(8), tgt[0]])

    g = jax.grad(loss_fn)(variables["params"])
    assert all(np.all(np.isfinite(x)) for x in jax.tree_util.tree_leaves(g))


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, hidden_dim=16,
        mlp_dim=32, max_len=8, dtype=jnp.float32, causal=True,
        dropout_rate=0.0)
    m = models.TransformerLM(cfg)
    t1 = jnp.array([[1, 2, 3, 4]])
    t2 = jnp.array([[1, 2, 3, 9]])
    variables = m.init(jax.random.PRNGKey(0), t1)
    l1 = m.apply(variables, t1)
    l2 = m.apply(variables, t2)
    np.testing.assert_allclose(l1[0, :3], l2[0, :3], atol=1e-5)


def test_bert_base_param_count():
    """BERT-base ~110M params (within tolerance; untied LM head adds ~23M)."""
    m = models.BertBase(dtype=jnp.float32, num_layers=2)
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = m.init(jax.random.PRNGKey(0), tokens)
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    # 2 layers: embeddings ~23.8M + 2*7.1M + head ~23.5M
    assert 55e6 < n < 75e6, n


def test_transformer_rejects_overlong_sequence():
    cfg = models.TransformerConfig(
        vocab_size=32, num_layers=1, num_heads=2, hidden_dim=16,
        mlp_dim=32, max_len=8, dtype=jnp.float32)
    m = models.TransformerLM(cfg)
    with pytest.raises(ValueError, match="max_len"):
        m.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


def test_get_model_unknown():
    with pytest.raises(ValueError):
        models.get_model("alexnet")


def test_space_to_depth_stem_is_exact_reparameterization():
    """The s2d stem computes EXACTLY the classic 7x7/s2 'SAME' conv when
    its 4x4 kernel is derived from the 7x7 weights (the standard TPU
    ResNet stem transform) — same function class, MXU-friendly layout."""
    from jax import lax

    from horovod_tpu.models.resnet import (conv7_kernel_to_s2d,
                                           space_to_depth_2x2)

    rng = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (2, 16, 16, 3), jnp.float32)
    k7 = jax.random.normal(k2, (7, 7, 3, 8), jnp.float32)

    dn = ("NHWC", "HWIO", "NHWC")
    y_ref = lax.conv_general_dilated(
        x, k7, window_strides=(2, 2), padding=[(2, 3), (2, 3)],
        dimension_numbers=dn)
    y_s2d = lax.conv_general_dilated(
        space_to_depth_2x2(x), conv7_kernel_to_s2d(k7),
        window_strides=(1, 1), padding=[(1, 2), (1, 2)],
        dimension_numbers=dn)
    assert y_s2d.shape == y_ref.shape == (2, 8, 8, 8)
    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_inception_s2d_stem_is_exact_reparameterization():
    """The Inception stem's 3x3/s2 'VALID' conv computes EXACTLY as the
    2x2/s1 conv over space-to-depth input when the kernel is derived
    via conv3_kernel_to_s2d — the ResNet stem transform applied to the
    32-channel Inception stem (odd input sizes take one zero pad
    row/col, matching the mapped kernel's zero 4th taps)."""
    from jax import lax

    from horovod_tpu.models.inception import conv3_kernel_to_s2d
    from horovod_tpu.models.resnet import space_to_depth_2x2

    rng = jax.random.PRNGKey(1)
    k1, k2 = jax.random.split(rng)
    # Odd spatial size, like the real 299px input.
    x = jax.random.normal(k1, (2, 15, 15, 3), jnp.float32)
    k3 = jax.random.normal(k2, (3, 3, 3, 8), jnp.float32)

    dn = ("NHWC", "HWIO", "NHWC")
    y_ref = lax.conv_general_dilated(
        x, k3, window_strides=(2, 2), padding="VALID",
        dimension_numbers=dn)
    xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
    y_s2d = lax.conv_general_dilated(
        space_to_depth_2x2(xp), conv3_kernel_to_s2d(k3),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=dn)
    assert y_s2d.shape == y_ref.shape == (2, 7, 7, 8)
    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_inception_s2d_stem_trains():
    m = models.get_model("inceptionv3", num_classes=10,
                         dtype=jnp.float32, stem="space_to_depth")
    x = jnp.ones((1, 75, 75, 3), jnp.float32)
    v = m.init(jax.random.PRNGKey(0), x, False)
    out = m.apply(v, x, False)
    assert out.shape == (1, 10)
    with pytest.raises(ValueError):
        models.get_model("inceptionv3", stem="bogus").init(
            jax.random.PRNGKey(0), x, False)


def test_resnet_space_to_depth_stem_trains():
    m = models.get_model("resnet18", num_classes=10, dtype=jnp.float32,
                         stem="space_to_depth")
    x = jnp.zeros((2, 64, 64, 3))
    variables, out = _init_and_apply(m, x)
    logits = out[0] if isinstance(out, tuple) else out
    assert logits.shape == (2, 10)
    k = variables["params"]["conv_init"]["kernel"]
    assert k.shape == (4, 4, 12, 64), k.shape
