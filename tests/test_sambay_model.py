"""``models/sambay.py`` against the plain reference
(``benchmark/reference/sambay_lm.py``, which imports nothing of the
system) on seeded weights at tiny sizes: the loss and every gradient leaf
with all five kinds of mixer, with one consumer of the memory and of the
shared keys and values and with two; each consumer's part of their
gradient; differential attention against the masked-softmax formula;
remat; the tied leaf; the published rule at 32 layers; the vocabulary's
share."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

import tiny_sambay_cell  # noqa: E402
from benchmark.harness import spec  # noqa: E402
from horovod_tpu.models import SambaYConfig, SambaYLM, sambay  # noqa: E402

family = spec.load_module("families", "sambay_lm")
reference = spec.load_module("reference", "sambay_lm")

#: The tiny cell's configuration, in float32: the published depth and the
#: layers the benchmark's cell holds, at tiny widths: kinds M S M F G X.
CUT = dict(tiny_sambay_cell.SAMBAY, compute_dtype="float32")
#: Two gated memory units and two cross-attention layers: 12 published
#: layers, of which the last six and the first two (M S M F G X G X).
TWO_CONSUMERS = dict(CUT, published=dict(num_hidden_layers=12,
                                         vocab_size=512),
                     layers_held=[0, 1, 6, 7, 8, 9, 10, 11],
                     num_hidden_layers=8)
TRAFFIC = dict(seq_len=32, attention="flash", remat=True)


def seeded(config, traffic=TRAFFIC, seed=0):
    """(model, parameters with every leaf moved off its initial value,
    a batch of two sequences)."""
    model = family.make_model(config, traffic)
    params, _ = family.init_variables(model, jax.random.PRNGKey(seed),
                                      config, traffic)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 5), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])
    batch = family.make_batch(jax.random.PRNGKey(seed + 2), 2, config,
                              traffic)
    return model, params, batch


def assert_trees_close(got, want, tolerance=1e-4):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        worst = float(jnp.abs(g - w).max())
        assert worst <= tolerance * max(float(jnp.abs(w).max()), 1e-3), (
            jax.tree_util.keystr(path), worst)


@pytest.mark.parametrize("config", [CUT, TWO_CONSUMERS],
                         ids=["cut_of_32", "two_consumers"])
def test_loss_and_every_gradient_leaf_equal_the_references(config):
    model, params, batch = seeded(config)
    assert family.kinds(config) == {6: "MSMFGX", 8: "MSMFGXGX"}[
        config["num_hidden_layers"]]
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            lambda p: family.loss_fn(model, p, {}, batch)[0])(params)
        want, want_grads = jax.value_and_grad(
            lambda p: reference.loss(p, {}, batch, config))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    assert_trees_close(got_grads, want_grads)
    # every leaf gets gradient: nothing of the model is left unread
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(
        got_grads))


def by_hand(cfg, params, tokens, remat, lookup=None, head=None,
            read_memory=None, read_kv=None):
    """The stack composed layer by layer from the model's own modules, so
    that what crosses the layers is in the open: ``lookup`` and ``head``
    stand in for the tied embedding in its two places; ``read_memory`` /
    ``read_kv`` map a consumer's index to what it reads (another layer's
    gets the value behind ``stop_gradient``). Returns the logits."""
    embedding = params["tok_embed"]["embedding"]
    lookup = embedding if lookup is None else lookup
    head = embedding if head is None else head
    layer = jax.checkpoint if remat else (lambda f: f)
    x, memory, shared_kv = lookup[tokens], None, None
    made_memory = made_kv = None
    for l in cfg.layers:
        def apply(x, m, kv, p, l=l):
            return sambay.SambaYLayer(cfg, l).apply({"params": p}, x, m, kv)

        kind = sambay.layer_kind(l, cfg.num_layers, cfg.mb_per_layer)
        m, kv = memory, shared_kv
        if kind == "G" and read_memory is not None:
            m = read_memory(l, made_memory)
        if kind == "X" and read_kv is not None:
            kv = read_kv(l, made_kv)
        x, memory, shared_kv = layer(apply)(x, m, kv, params[f"layer_{l}"])
        if l == cfg.num_layers // 2:
            made_memory = memory
        if l == cfg.num_layers // 2 + 1:
            made_kv = shared_kv
    final = params["final_norm"]
    x = x - x.mean(-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                          + cfg.ln_eps) * final["scale"] + final["bias"]
    return x @ head.T


def test_by_hand_is_the_model():
    model, params, (tokens,) = seeded(TWO_CONSUMERS)
    with jax.default_matmul_precision("highest"):
        got = by_hand(model.cfg, params, tokens, remat=True)
        want = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_memory_and_shared_kv_get_gradient_from_every_consumer(remat=True):
    """The gradient of the loss in the layer that makes the memory (its
    mixer's parameters reach the later layers through the memory and
    through x) is the sum of what reaches it through x alone and through
    each gated memory unit's reading alone; the same for the layer whose
    keys and values are shared. Dropping one consumer's reading changes
    it."""
    model, params, (tokens,) = seeded(TWO_CONSUMERS)
    cfg = model.cfg
    targets = jnp.roll(tokens, -1, axis=1)

    def grads(read_memory=None, read_kv=None):
        def loss(p):
            logits = by_hand(cfg, p, tokens, remat, read_memory=read_memory,
                             read_kv=read_kv)
            return -jnp.take_along_axis(
                jax.nn.log_softmax(logits, -1), targets[..., None],
                -1).mean()

        with jax.default_matmul_precision("highest"):
            g = jax.grad(loss)(params)
        # parameters that reach the later layers only through what the
        # layer hands on: the scan's, and the key and value columns
        return (g["layer_6"]["mixer"]["A_log"],
                g["layer_7"]["mixer"]["qkv"]["kernel"][:, 4 * 8:])

    stop = jax.lax.stop_gradient
    whole = grads()
    only = lambda reader: (  # noqa: E731
        lambda l, value: value if l == reader else stop(value))
    none = lambda l, value: stop(value)  # noqa: E731
    own_memory = grads(read_memory=none)[0]
    own_kv = grads(read_kv=none)[1]
    parts_memory = [grads(read_memory=only(l))[0] - own_memory
                    for l in (8, 10)]
    parts_kv = [grads(read_kv=only(l))[1] - own_kv for l in (9, 11)]
    for part in parts_memory + parts_kv:
        assert float(jnp.abs(part).max()) > 1e-6  # each consumer counts
    np.testing.assert_allclose(own_memory + sum(parts_memory), whole[0],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(own_kv + sum(parts_kv), whole[1],
                               rtol=1e-4, atol=1e-7)
    # and the model's own stack (flax's remat over the three inputs)
    # gives the whole
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda p: family.loss_fn(
            family.make_model(TWO_CONSUMERS, dict(TRAFFIC, remat=remat)),
            p, {}, (tokens,))[0])(params)
    np.testing.assert_allclose(g["layer_6"]["mixer"]["A_log"], whole[0],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(
        g["layer_7"]["mixer"]["qkv"]["kernel"][:, 4 * 8:], whole[1],
        rtol=1e-4, atol=1e-7)


def test_with_and_without_remat_the_same_to_rounding():
    _, params, batch = seeded(CUT)
    results = []
    for remat in (False, True):
        model = family.make_model(CUT, dict(TRAFFIC, remat=remat))
        with jax.default_matmul_precision("highest"):
            results.append(jax.value_and_grad(
                lambda p: family.loss_fn(model, p, {}, batch)[0])(params))
    (plain, plain_grads), (remat, remat_grads) = results
    assert float(plain) == pytest.approx(float(remat), rel=1e-6)
    assert_trees_close(remat_grads, plain_grads, tolerance=1e-5)


def test_the_tied_leafs_gradient_is_the_lookups_plus_the_heads():
    model, params, (tokens,) = seeded(CUT)
    cfg = model.cfg
    targets = jnp.roll(tokens, -1, axis=1)
    embedding = params["tok_embed"]["embedding"]

    def loss(lookup, head):
        logits = by_hand(cfg, params, tokens, True, lookup=lookup, head=head)
        return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                    targets[..., None], -1).mean()

    with jax.default_matmul_precision("highest"):
        through_lookup, through_head = jax.grad(loss, argnums=(0, 1))(
            embedding, embedding)
        tied = jax.grad(lambda p: family.loss_fn(model, p, {}, (tokens,))[
            0])(params)["tok_embed"]["embedding"]
    assert float(jnp.abs(through_lookup).max()) > 1e-4
    assert float(jnp.abs(through_head).max()) > 1e-4
    np.testing.assert_allclose(tied, through_lookup + through_head,
                               rtol=1e-4, atol=1e-7)
    # no parameter of the head's own: the embedding is the only vocabulary
    # leaf, and the head's module holds nothing
    assert "lm_head" not in params


def masked_softmax_attention(q, k, v, window):
    """(s, d), (s, d), (s, dv) -> causal softmax(q k^T / sqrt d) v over
    the last ``window`` keys."""
    s, d = q.shape
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scores = jnp.where((j <= i) & (i - j < window), q @ k.T / d ** 0.5,
                       -jnp.inf)
    return jax.nn.softmax(scores, -1) @ v


@pytest.mark.parametrize("kind,l", [("S", 3), ("F", 17), ("X", 21)])
def test_differential_attention_equals_the_masked_softmax_formula(kind, l):
    """The published form, written out head by head: a1 = [attn(q1, k1,
    v1) | attn(q1, k1, v2)], a2 likewise from (q2, k2); o = rms(a1 -
    lambda a2) (1 - lambda_init); banded in S, full in F and X."""
    cfg = SambaYConfig(
        vocab_size=64, hidden_dim=48, num_layers=32, layers=(l,),
        mlp_dim=64, num_heads=6, num_kv_heads=2, window=8, dt_rank=3,
        dtype=jnp.float32)
    assert sambay.layer_kind(l, 32, 2) == kind
    s, d = 32, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(l), 4)
    u = jax.random.normal(keys[0], (1, s, 48))
    shared = tuple(jax.random.normal(key, (1, s, 2, d)) for key in keys[1:3])
    module = sambay.DifferentialAttention(cfg, l, kind)
    params = module.init(keys[3], u, shared)["params"]
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(keys[3], p.shape), params)
    with jax.default_matmul_precision("highest"):
        out, (k, v) = module.apply({"params": params}, u, shared)
        if kind == "X":
            q = u[0] @ params["query"]["kernel"] + params["query"]["bias"]
            np.testing.assert_array_equal(k, shared[0])
        else:
            qkv = u[0] @ params["qkv"]["kernel"] + params["qkv"]["bias"]
            q = qkv[:, :6 * d]
            np.testing.assert_allclose(
                k[0].reshape(s, 2 * d), qkv[:, 6 * d:8 * d], rtol=1e-6)
        q = q.reshape(s, 6, d)
        k, v = k[0], v[0]                                   # (s, 2, d)
        window = 8 if kind == "S" else s
        start = 0.8 - 0.6 * np.exp(-0.3 * l)
        lam = (jnp.exp(params["lambda_q1"] @ params["lambda_k1"])
               - jnp.exp(params["lambda_q2"] @ params["lambda_k2"]) + start)
        pairs = []
        for i in range(3):          # query pair i reads key-value pair 0
            a1, a2 = (jnp.concatenate([
                masked_softmax_attention(q[:, 2 * i + h], k[:, h], v[:, 0],
                                         window),
                masked_softmax_attention(q[:, 2 * i + h], k[:, h], v[:, 1],
                                         window)], -1) for h in (0, 1))
            o = a1 - lam * a2
            pairs.append(o * jax.lax.rsqrt(
                jnp.square(o).mean(-1, keepdims=True) + 1e-5)
                * params["norm_scale"] * (1 - start))
        want = (jnp.concatenate(pairs, -1) @ params["out"]["kernel"]
                + params["out"]["bias"])
    np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-5)


def test_the_published_rule_at_32_layers():
    """Kinds, lambda_init and what each layer reads, by the model's rule
    and by the reference's own copy of it."""
    kinds = "".join(sambay.layer_kind(l, 32, 2) for l in range(32))
    assert kinds == "MS" * 8 + "MF" + "GX" * 7
    config = dict(CUT)
    assert "".join(reference._kind(l, config) for l in range(32)) == kinds
    assert [kinds[l] for l in CUT["layers_held"]] == list("MSMFGX")
    assert sambay.lambda_init(0) == pytest.approx(0.2)
    assert [round(sambay.lambda_init(l), 4) for l in (1, 17, 19)] == [
        0.3555, 0.7963, 0.798]
    # a cut that keeps a consumer without its source is refused
    for layers, source in (((0, 1, 18), 16), ((0, 1, 16, 19), 17)):
        with pytest.raises(ValueError, match=f"not layer {source}"):
            sambay.check_layers(SambaYConfig(
                vocab_size=64, hidden_dim=32, num_layers=32, layers=layers,
                mlp_dim=64, num_heads=4, num_kv_heads=2, window=16, dt_rank=2))
    with pytest.raises(ValueError, match="divisible by 4"):
        sambay.check_layers(SambaYConfig(
            vocab_size=64, hidden_dim=32, num_layers=30, layers=(0,),
            mlp_dim=64, num_heads=4, num_kv_heads=2, window=16, dt_rank=2))
    # lambda_init goes by the published index, not the position held:
    # layer 17's output moves when it is told it is layer 3
    model, params, (tokens,) = seeded(CUT)
    renamed = dict(params, layer_3=params["layer_17"])
    as_17 = sambay.SambaYLayer(model.cfg, 17).apply(
        {"params": params["layer_17"]}, jnp.ones((1, 16, 32)), None, None)
    as_1 = sambay.SambaYLayer(model.cfg, 1).apply(
        {"params": renamed["layer_3"]}, jnp.ones((1, 16, 32)), None, None)
    assert float(jnp.abs(as_17[0] - as_1[0]).max()) > 1e-3


def test_eight_vocabulary_slices_side_by_side_are_the_uncut_logits():
    """The share and the model: one set of weights over the whole
    vocabulary of 8 x 16 rows; chip k holds rows 16 k .. 16 k + 15 of the
    tied embedding, its ids come from its slice, and its logits are the
    uncut reference's for the same tokens at its columns."""
    whole = dict(CUT, vocab_size=128, published=dict(num_hidden_layers=32,
                                                     vocab_size=128))
    share = dict(whole, vocab_size=16)
    _, params, _ = seeded(whole)
    embedding = params["tok_embed"]["embedding"]
    assert embedding.shape == (128, 32)
    model = family.make_model(share, TRAFFIC)
    by_slice, uncut = [], []
    for k in range(8):
        local = jax.random.randint(jax.random.PRNGKey(k), (1, 32), 0, 16)
        held = dict(params, tok_embed={
            "embedding": embedding[16 * k:16 * (k + 1)]})
        with jax.default_matmul_precision("highest"):
            by_slice.append(model.apply({"params": held}, local)[0])
            uncut.append(reference.logits(
                params, local[0] + 16 * k, whole)[:, 16 * k:16 * (k + 1)])
    np.testing.assert_allclose(jnp.concatenate(by_slice, -1),
                               jnp.concatenate(uncut, -1),
                               rtol=2e-4, atol=2e-5)
