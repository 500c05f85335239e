"""``models.Decoder`` as LFM2's sparse hybrid (``benchmark/families/
lfm2_lm.py``) against the benchmark's plain reference
(``benchmark/reference/lfm2_lm.py``) in float32: the logits, the loss and
every gradient leaf; the gated short convolution against a loop over
positions and its causality; the query-key norm against its definition;
the sigmoid router's normalisation with its epsilon; the test that ties a
chip's share of the experts to the model; the parameters the cell holds,
part by part; and the state-space convolution that now calls the shared
tap sum, value for value as before."""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

import tiny_lfm2_cell  # noqa: E402
from benchmark.harness import spec  # noqa: E402
from horovod_tpu.models import decoder, hybrid  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from horovod_tpu.parallel.moe import sigmoid_route  # noqa: E402

family = spec.load_module("families", "lfm2_lm")
reference = spec.load_module("reference", "lfm2_lm")

#: The uncut model at a small size, what four chips share: the benchmark's
#: tiny cell holding every one of its 16 experts, in float32.
WHOLE = dict(tiny_lfm2_cell.LFM2, num_experts=16, compute_dtype="float32")
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
    # an expert bias that is not zero, as a trained model's is
    for l in (2, 3, 4, 5):
        params[f"layer_{l}"]["moe"]["expert_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(l), (16,))
    batch = family.make_batch(jax.random.PRNGKey(1), 2, WHOLE, TRAFFIC)
    return model, params, extra, batch


def test_logits_loss_and_every_gradient_equal_the_reference(whole):
    """Without remat; the tiny cell of ``tiny_lfm2_cell.py`` runs it with
    remat against the same reference through the harness."""
    model, params, extra, batch = whole
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)({"params": params}, batch[0])
        want_logits = jax.jit(jax.vmap(
            lambda t: reference.logits(params, t, WHOLE)))(batch[0])
        (got, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: family.loss_fn(model, p, extra, batch),
            has_aux=True))(params)
        want, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, extra, batch, WHOLE)))(params)
    assert logits.shape == (2, 64, 64) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-4)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    flat, flat_ref = _leaves(grads), jax.tree.leaves(ref_grads)
    assert len(flat) == len(flat_ref) == 53
    for (name, g), r in zip(flat.items(), flat_ref):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-6, err_msg=name)
        if not name.endswith("['expert_bias']"):  # every other leaf is used
            assert float(jnp.abs(r).max()) > 0, name
    # the expert bias moves the choice only: no gradient reaches it
    for l in (2, 3, 4, 5):
        assert not float(jnp.abs(grads[f"layer_{l}"]["moe"]["expert_bias"])
                         .max())
    # holding every expert, every assignment is kept and none elsewhere
    assert counters["expert_kept"].shape == (4, 16)
    assert counters["expert_kept"].sum(1).tolist() == [2 * 64 * 4] * 4
    assert counters["expert_elsewhere"].tolist() == [0] * 4


def test_the_parameter_tree_is_named_by_published_index(whole):
    _, params, _, _ = whole
    assert sorted(params) == ["final_norm", "layer_0", "layer_2", "layer_3",
                              "layer_4", "layer_5", "tok_embed"]
    assert sorted(params["layer_0"]) == [
        "conv", "conv_norm", "mlp_down", "mlp_gate", "mlp_norm", "mlp_up"]
    assert sorted(params["layer_0"]["conv"]) == ["in_proj", "out_proj",
                                                 "taps"]
    assert params["layer_0"]["conv"]["taps"].shape == (3, 32)
    assert sorted(params["layer_2"]) == ["attention", "attention_norm",
                                         "mlp_norm", "moe"]
    assert sorted(params["layer_2"]["attention"]) == [
        "key", "key_norm", "out", "query", "query_norm", "value"]
    assert sorted(params["layer_3"]["moe"]) == [
        "expert_bias", "experts_down", "experts_gate", "experts_up",
        "router"]
    # no projection has a bias: the one bias is the router's correction
    assert [k for k in _leaves(params) if "bias" in k] == [
        f"['layer_{l}']['moe']['expert_bias']" for l in (2, 3, 4, 5)]


# ---------------------------------------------------------------------------
# the operator, the query-key norm and the router, each by its definition
# ---------------------------------------------------------------------------

def _conv_cfg(d=16, kernel=3):
    return decoder.DecoderConfig(
        vocab_size=8, hidden_dim=d, head_dim=4, num_kv_heads=1, layers=(),
        mlp_dim=8, window=0, rope_full=None, rope_window=None,
        dtype=jnp.float32, conv_kernel=kernel)


@pytest.mark.parametrize("kernel", [3, 4])
def test_the_short_conv_equals_a_loop_over_positions(kernel):
    cfg = _conv_cfg(kernel=kernel)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    op = decoder.ShortConv(cfg)
    params = op.init(jax.random.PRNGKey(1), u)["params"]
    assert params["taps"].shape == (kernel, 16)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(op.apply({"params": params}, u))
    w_in = np.asarray(params["in_proj"]["kernel"], np.float64)
    w_out = np.asarray(params["out_proj"]["kernel"], np.float64)
    taps = np.asarray(params["taps"], np.float64)
    want = np.zeros_like(got, np.float64)
    for b in range(2):
        proj = np.asarray(u[b], np.float64) @ w_in
        gate_b, gate_c, x = proj[:, :16], proj[:, 16:32], proj[:, 32:]
        v = gate_b * x
        for t in range(12):
            z = sum(taps[k] * v[t - kernel + 1 + k]
                    for k in range(kernel) if t - kernel + 1 + k >= 0)
            want[b, t] = (gate_c[t] * z) @ w_out
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_short_conv_is_causal():
    """An input changed at t moves no output before t, and moves t's."""
    cfg = _conv_cfg()
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 16))
    op = decoder.ShortConv(cfg)
    params = op.init(jax.random.PRNGKey(3), u)["params"]
    base = op.apply({"params": params}, u)
    for t in (0, 5, 11):
        moved = op.apply({"params": params}, u.at[:, t].add(1.0))
        np.testing.assert_array_equal(moved[:, :t], base[:, :t])
        assert float(jnp.abs(moved[:, t] - base[:, t]).min()) > 0


def test_the_short_conv_puts_what_lies_between_its_projections_in_scope():
    from horovod_tpu.common import phases

    assert {"short_conv", "attn_qk_norm"} <= set(phases.MODEL_SCOPES)
    cfg = _conv_cfg()
    u = jnp.zeros((1, 8, 16))
    op = decoder.ShortConv(cfg)
    params = op.init(jax.random.PRNGKey(0), u)["params"]
    text = jax.jit(lambda p: op.apply({"params": p}, u)).lower(
        params).compile().as_text()
    scoped = [line for line in text.splitlines() if "/short_conv/" in line]
    # B * x, the three taps and C * z; the projections are outside
    assert sum(" multiply(" in line for line in scoped) >= 5
    assert not any(" dot(" in line for line in scoped)
    assert sum(" dot(" in line for line in text.splitlines()) == 2


def test_the_query_key_norm_is_an_rms_norm_of_each_head():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 3, 8)) * 3.0
    scale = jax.random.normal(jax.random.PRNGKey(5), (8,))
    got = decoder.head_rms(x, scale, 1e-5)
    xs = np.asarray(x, np.float64)
    rms = np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, xs / rms * np.asarray(scale), rtol=1e-5,
                               atol=1e-6)
    unit = decoder.head_rms(x, jnp.ones(8), 0.0)
    np.testing.assert_allclose((unit ** 2).mean(-1), 1.0, rtol=1e-5)
    # bf16 in, bf16 out, float32 inside
    assert decoder.head_rms(x.astype(jnp.bfloat16), scale, 1e-5).dtype == \
        jnp.bfloat16


def test_the_sigmoid_router_divides_by_the_chosen_sum_plus_eps():
    """The published normalisation: ``s_i / (sum of the chosen s + 1e-6)``
    times the scaling; with ``eps`` 0 the program is the one-line form's
    that stood before, op for op, and ``eps`` adds one add."""
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(kx, (48, 16))
    router = jax.random.normal(kw, (16, 32)) * 2.0
    bias = jax.random.normal(kb, (32,))
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)), np.float64)
    top_e, weight = sigmoid_route(bias, 1e-6)(x, router, 4, 1.0)
    want_e = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :4]
    assert [set(a) for a in np.asarray(top_e)] == [set(a) for a in want_e]
    chosen = np.take_along_axis(scores, np.asarray(top_e), axis=1)
    np.testing.assert_allclose(
        weight, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    plain = sigmoid_route(bias)(x, router, 4, 1.0)[1]
    np.testing.assert_allclose(
        plain, chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    assert not np.array_equal(plain, weight)

    def jaxpr(route):
        return str(jax.make_jaxpr(lambda x: route(x, router, 4, 1.0))(x))

    def one_line(x, router_w, top_k, scaling):  # the form before eps
        scores = jax.nn.sigmoid(jnp.dot(
            x, router_w, precision=jax.lax.Precision.HIGHEST))
        _, top_e = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), top_k)
        top_s = moe._chosen(scores, top_e)
        return top_e, scaling * top_s / top_s.sum(-1, keepdims=True)

    assert jaxpr(sigmoid_route(bias)) == jaxpr(one_line)
    assert jaxpr(sigmoid_route(bias, 1e-6)).count(" add ") == \
        jaxpr(one_line).count(" add ") + 1


def test_the_state_space_convolution_is_what_it_was_value_for_value():
    """``causal_conv_silu`` over the shared tap sum: the same numbers as
    the sum of shifted taps it held before, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (2, 20, 24)).astype(jnp.bfloat16)
    taps = jax.random.normal(ks[1], (4, 24))
    bias = jax.random.normal(ks[2], (24,))

    def before(x, taps, bias, dtype):
        kernel, t = taps.shape[0], x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (kernel - 1, 0), (0, 0)))
        conv = bias + sum(taps[k] * padded[:, k:k + t]
                          for k in range(kernel))
        return nn.silu(conv).astype(dtype)

    for dtype in (jnp.bfloat16, jnp.float32):
        got = jax.jit(hybrid.causal_conv_silu, static_argnums=3)(
            x, taps, bias, dtype)
        want = jax.jit(before, static_argnums=3)(x, taps, bias, dtype)
        assert got.dtype == dtype
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the share and the model: four chips' experts add up to the uncut layer
# ---------------------------------------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_reference(whole):
    """Each of four chips computes its four experts' part of a sparse
    layer from its slice of them; the router is whole on every chip, and
    nothing else of the layer is computed alike on each (no shared
    expert). The parts add up to the whole layer as the plain reference
    computes it from all 16."""
    _, params, _, _ = whole
    p = params["layer_3"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    held = WHOLE["num_experts"] // SHARES
    cfg = family.make_model(dict(WHOLE, num_experts=held), TRAFFIC).cfg

    def part(share):
        cut = slice(share * held, (share + 1) * held)
        held_params = dict(p, **{k: p[k][cut] for k in (
            "experts_gate", "experts_up", "experts_down")})
        out, (kept, elsewhere) = decoder.SparseMLP(dataclasses.replace(
            cfg, first_expert=share * held)).apply(
                {"params": held_params}, h[None])
        assert int(kept.sum() + elsewhere) == 64 * 4
        return out[0]

    with jax.default_matmul_precision("highest"):
        parts = [part(share) for share in range(SHARES)]
        want = reference._experts_share(h, p, WHOLE)
    np.testing.assert_allclose(sum(parts), want, rtol=2e-4, atol=2e-5)
    for share in parts:  # a share alone is not the layer
        assert float(jnp.abs(share).max()) > 1e-3
        assert float(jnp.abs(share - want).max()) > 1e-3


# ---------------------------------------------------------------------------
# the cell's parameters, part by part, from the model's own shapes
# ---------------------------------------------------------------------------

def test_the_cell_holds_507_820_288_parameters_part_by_part():
    cell = spec.load_cell("lfm2_8b_a1b_ep4_s8192x2")
    model = family.make_model(cell.config, cell.traffic)
    shapes, extra = jax.eval_shape(
        lambda k: family.init_variables(model, k, cell.config, cell.traffic),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    held = cell.config["parameters_held"]
    conv = shapes["layer_0"]["conv"]
    assert count(conv) == held["conv_operator"] == 16_783_360
    assert (conv["in_proj"]["kernel"].shape, conv["taps"].shape,
            conv["out_proj"]["kernel"].shape) == (
        (2048, 6144), (3, 2048), (2048, 2048))
    attention = shapes["layer_2"]["attention"]
    assert count(attention) == held["attention"] == 10_485_888
    assert attention["query"]["kernel"].shape == (2048, 32, 64)
    assert attention["key"]["kernel"].shape == (2048, 8, 64)
    assert attention["query_norm"].shape == attention["key_norm"].shape == (
        64,)
    assert count([shapes["layer_0"][k] for k in (
        "mlp_gate", "mlp_up", "mlp_down")]) == held["dense_mlp"] == \
        44_040_192
    for l in (2, 3, 4, 5):
        moe = shapes[f"layer_{l}"]["moe"]
        assert count({k: v for k, v in moe.items() if k != "expert_bias"}) \
            == held["experts_held_8_and_router"] == 88_145_920
        assert moe["router"].shape == (2048, 32)       # all 32 outputs
        assert moe["experts_gate"].shape == (8, 2048, 1792)
        assert count(moe["expert_bias"]) == held["expert_bias"] == 32
    for l in (0, 2, 3, 4, 5):
        layer = shapes[f"layer_{l}"]
        norms = [v for k, v in layer.items() if k.endswith("_norm")]
        assert count(norms) == held["norms_a_layer"] == 4096
    assert count(shapes["layer_0"]) == held["layer_0"] == 60_827_648
    assert count(shapes["layer_2"]) == held["layer_2"] == 98_635_936
    for l in (3, 4, 5):
        assert count(shapes[f"layer_{l}"]) == held["layers_3_to_5_each"] \
            == 104_933_408
    assert count([shapes["tok_embed"], shapes["final_norm"]]) == \
        held["embedding_and_final_norm"] == 33_556_480
    assert "lm_head" not in shapes      # tied: the embedding is the head
    # 507,820,160 in the matrices and norms, and four expert biases of 32
    assert count(shapes) == held["total"] == 507_820_160 + 4 * 32
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(shapes))
    assert extra["expert_kept"].shape == (4, 8)
    assert extra["expert_elsewhere"].shape == (4,)
