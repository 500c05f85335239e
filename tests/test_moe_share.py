"""One chip's share of a top-k expert layer (``parallel/moe.py``
``expert_share_layer``): the shares add up to the uncut layer of the
benchmark's plain reference, nothing is dropped under a skewed router,
and the counters equal a plain count."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from horovod_tpu.parallel.moe import (  # noqa: E402
    expert_share_layer, sigmoid_route)

reference = spec.load_module("reference", "decoder_lm")

T, H, F, EXPERTS, HELD, TOP_K, SCALING = 96, 16, 8, 16, 4, 4, 2.5


@pytest.fixture(scope="module")
def weights():
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    normal = jax.random.normal
    return dict(
        x=normal(ks[0], (T, H)),
        router=normal(ks[1], (H, EXPERTS)),
        experts_gate=normal(ks[2], (EXPERTS, H, F)) * 0.3,
        experts_up=normal(ks[3], (EXPERTS, H, F)) * 0.3,
        experts_down=normal(ks[4], (EXPERTS, F, H)) * 0.3,
        shared_gate=normal(ks[5], (H, F)) * 0.3,
        shared_up=normal(ks[6], (H, F)) * 0.3,
        shared_down=normal(ks[7], (F, H)) * 0.3)


def _share(w, first, router=None, block_rows=8):
    held = slice(first, first + HELD)
    return expert_share_layer(
        w["x"], w["router"] if router is None else router,
        w["experts_gate"][held], w["experts_up"][held],
        w["experts_down"][held], first_expert=first, top_k=TOP_K,
        scaling=SCALING, block_rows=block_rows)


def _uncut(w, router=None):
    """The whole layer by the reference: the shared expert and every
    expert, the configuration one that holds all of them."""
    config = dict(num_experts=EXPERTS, published={"num_experts": EXPERTS},
                  first_expert=0, num_experts_per_tok=TOP_K,
                  moe_routed_scaling_factor=SCALING)
    p = dict(w, router=w["router"] if router is None else router)
    shared = reference._swiglu(w["x"], w["shared_gate"], w["shared_up"],
                               w["shared_down"])
    return shared + reference._experts_share(w["x"], p, config)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        weights):
    with jax.default_matmul_precision("highest"):
        parts = [_share(weights, first)[0]
                 for first in range(0, EXPERTS, HELD)]
        shared = reference._swiglu(
            weights["x"], weights["shared_gate"], weights["shared_up"],
            weights["shared_down"])
        want = _uncut(weights)
    np.testing.assert_allclose(shared + sum(parts), want, rtol=2e-5,
                               atol=2e-5)
    # A share alone is not the layer: each holds a real part of it.
    for part in parts:
        assert float(jnp.abs(part).max()) > 0.05


@pytest.mark.parametrize("block_rows", [8, 64])
def test_nothing_is_dropped_when_one_expert_gets_most_tokens(weights,
                                                             block_rows):
    """The router is skewed (a constant feature with a large weight) so
    that every token sends to expert 5, held by the share that starts at
    4: T assignments where a uniform router sends T x top-k / experts =
    24. A capacity would drop most of them."""
    w = dict(weights, x=weights["x"].at[:, -1].set(1.0),
             router=weights["router"].at[-1, 5].set(30.0))
    with jax.default_matmul_precision("highest"):
        y, (kept, elsewhere) = _share(w, 4, block_rows=block_rows)
        parts = [_share(w, first, block_rows=block_rows)[0]
                 for first in (0, 8, 12)]
        shared = reference._swiglu(
            w["x"], w["shared_gate"], w["shared_up"], w["shared_down"])
        want = _uncut(w)
    assert int(kept[1]) == T  # expert 5 is the share's second
    np.testing.assert_allclose(shared + y + sum(parts), want, rtol=2e-5,
                               atol=2e-5)
    assert int(kept.sum() + elsewhere) == T * TOP_K


@pytest.mark.parametrize("first", [0, 4, 12])
def test_counters_equal_a_plain_count(weights, first):
    _, (kept, elsewhere) = _share(weights, first)
    logits = np.asarray(weights["x"], np.float64) @ np.asarray(
        weights["router"], np.float64)
    chosen = np.argsort(-logits, axis=1)[:, :TOP_K]  # softmax keeps order
    count = np.bincount(chosen.reshape(-1), minlength=EXPERTS)
    np.testing.assert_array_equal(kept, count[first:first + HELD])
    assert kept.dtype == jnp.int32 and elsewhere.dtype == jnp.int32
    assert int(elsewhere) == T * TOP_K - count[first:first + HELD].sum()


def test_gradients_flow_to_every_argument(weights):
    """x, the router (through the weights) and the three expert matrices,
    against the uncut reference's gradient of the same share."""
    first = 4
    held = slice(first, first + HELD)
    cot = jax.random.normal(jax.random.PRNGKey(9), (T, H))

    def ours(x, router, gate, up, down):
        y, _ = expert_share_layer(x, router, gate, up, down,
                                  first_expert=first, top_k=TOP_K,
                                  scaling=SCALING, block_rows=8)
        return (y * cot).sum()

    def plain(x, router, gate, up, down):
        config = dict(num_experts=HELD, published={"num_experts": EXPERTS},
                      first_expert=first, num_experts_per_tok=TOP_K,
                      moe_routed_scaling_factor=SCALING)
        p = dict(router=router, experts_gate=gate, experts_up=up,
                 experts_down=down)
        return (reference._experts_share(x, p, config) * cot).sum()

    args = (weights["x"], weights["router"], weights["experts_gate"][held],
            weights["experts_up"][held], weights["experts_down"][held])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, argnums=range(5))(*args)
        want = jax.grad(plain, argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# The layout of the kept assignments: tokens x held, one sort
# ---------------------------------------------------------------------------

def _plain_share(x, router, gate, up, down, *, first, top_k, scaling,
                 bias=None, router_x=None):
    """Token by token the sum over its chosen experts held here of weight
    x expert, with no layout: every held expert on every token, times a
    dense (tokens, experts) table of weights that is zero off the top-k.
    ``bias`` None is the softmax router, else the sigmoid one."""
    logits = (x if router_x is None else router_x) @ router
    scores = (jax.nn.softmax(logits, axis=-1) if bias is None
              else jax.nn.sigmoid(logits))
    _, top_e = jax.lax.top_k(scores if bias is None else scores + bias,
                             top_k)
    chosen = jax.nn.one_hot(top_e, router.shape[1]).sum(1) * scores
    weight = scaling * chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        h = (jax.nn.relu(x @ up[e]) ** 2 if gate is None
             else jax.nn.silu(x @ gate[e]) * (x @ up[e]))
        y = y + weight[:, first + e, None] * (h @ down[e])
    return y, top_e


# tokens, block_rows, first_expert, and the router bent: (an expert, the
# weight of a constant feature on its column)
LAYOUTS = {
    "tokens_not_a_multiple_of_the_block": (100, 8, 4, None),
    "fewer_tokens_than_one_block": (50, 64, 0, None),
    "a_held_expert_nobody_chose": (96, 8, 4, (6, -30.0)),
    "every_token_on_one_held_expert": (96, 8, 4, (5, 30.0)),
    "the_last_share": (96, 16, 12, None),
}
FORMS = {"softmax_swiglu": (False, True), "sigmoid_relu2": (True, False),
         "sigmoid_swiglu": (True, True)}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_layout_gives_the_plain_sum_and_its_gradients(layout, form):
    t, block_rows, first, bent = LAYOUTS[layout]
    sigmoid, gated = FORMS[form]
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    width = 24 if sigmoid else H  # the sigmoid router scores another input
    x = jax.random.normal(ks[0], (t, H))
    router = jax.random.normal(ks[1], (width, EXPERTS))
    router_x = jax.random.normal(ks[2], (t, width)) if sigmoid else None
    bias = jax.random.normal(ks[3], (EXPERTS,)) * 0.1 if sigmoid else None
    if bent:
        if sigmoid:
            router_x = router_x.at[:, -1].set(1.0)
        else:
            x = x.at[:, -1].set(1.0)
        router = router.at[-1, bent[0]].set(bent[1])
    gate = (jax.random.normal(ks[4], (HELD, H, F)) * 0.3 if gated else None)
    up = jax.random.normal(ks[5], (HELD, H, F)) * 0.3
    down = jax.random.normal(ks[6], (HELD, F, H)) * 0.3
    cot = jax.random.normal(ks[7], (t, H))

    def ours(x, router, gate, up, down, router_x):
        y, counts = expert_share_layer(
            x, router, gate, up, down, first_expert=first, top_k=TOP_K,
            scaling=SCALING, block_rows=block_rows, router_x=router_x,
            route=sigmoid_route(bias) if sigmoid else None)
        return (y * cot).sum(), (y, counts)

    def plain(x, router, gate, up, down, router_x):
        y, top_e = _plain_share(x, router, gate, up, down, first=first,
                                top_k=TOP_K, scaling=SCALING, bias=bias,
                                router_x=router_x)
        return (y * cot).sum(), (y, top_e)

    args = (x, router, gate, up, down, router_x)
    wrt = [i for i, a in enumerate(args) if a is not None]
    with jax.default_matmul_precision("highest"):
        got, (y, (kept, elsewhere)) = jax.grad(
            ours, argnums=wrt, has_aux=True)(*args)
        want, (y_plain, top_e) = jax.grad(
            plain, argnums=wrt, has_aux=True)(*args)
    count = np.bincount(np.asarray(top_e).reshape(-1), minlength=EXPERTS)
    np.testing.assert_array_equal(kept, count[first:first + HELD])
    assert int(elsewhere) == t * TOP_K - int(kept.sum())
    if bent:
        assert int(kept[bent[0] - first]) == (t if bent[1] > 0 else 0)
    np.testing.assert_allclose(y, y_plain, rtol=1e-4, atol=1e-4)
    for i, a, b in zip(wrt, got, want):
        assert float(jnp.abs(b).max()) > 0, i  # every case keeps something
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def _index_counts(jaxpr, found):
    """(primitive, number of indices) of every scatter and gather of a
    jaxpr and the jaxprs under it, but a ``while``'s: the loop over the
    blocks is the one loop whose length the data sets."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            found.append((name, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
        if name == "while":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _index_counts(sub, found)
    return found


def test_no_scatter_or_gather_outside_the_block_loop_is_tokens_by_top_k():
    """The layout works on tokens x held: with the router's choice handed
    in (so that every indexed op left is the layout's) no scatter or
    gather of the gradient's program has tokens x top_k indices."""
    t, held, top_k, block_rows = 64, 2, 6, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (t, H))
    _, top_e = jax.lax.top_k(jax.random.normal(ks[1], (t, EXPERTS)), top_k)
    weight = jax.random.uniform(ks[2], (t, top_k))
    up = jax.random.normal(ks[3], (held, H, F))
    down = jax.random.normal(ks[4], (held, F, H))

    def loss(x, weight, up, down):
        y, _ = expert_share_layer(
            x, None, None, up, down, first_expert=2, top_k=top_k,
            block_rows=block_rows, route=lambda *_: (top_e, weight))
        return y.sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        x, weight, up, down)
    found = _index_counts(jaxpr.jaxpr, [])
    assert found, "the rows of a block are gathered somewhere"
    assert max(n for _, n in found) < t * held < t * top_k, found


# ---------------------------------------------------------------------------
# The routers: the chosen scores and their transpose by compares
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _router_inputs(t, h, experts, dtype, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (t, h), dtype),
            jax.random.normal(ks[1], (h, experts)) * h ** -0.5,
            jax.random.normal(ks[2], (experts,)) * 0.1)


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_a_routers_logits_are_one_float32_product_at_highest(router, dtype):
    """Whatever the input's dtype: it is widened, and the product is left
    to the compiler, which skips the passes over a bfloat16 input's zero
    low parts by itself on the chip (PERF.md, PR 35)."""
    x, w, bias = _router_inputs(32, 16, 8, dtype)
    route = moe._route if router == "softmax" else sigmoid_route(bias)
    eqns = _primitives(
        jax.make_jaxpr(lambda x, w: route(x, w, 3, SCALING))(x, w).jaxpr, [])
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert dots[0].params["precision"] == (HIGHEST, HIGHEST)
    assert [v.aval.dtype for v in dots[0].invars] == [jnp.float32] * 2
    assert [v.aval.shape for v in dots[0].invars] == [(32, 16), (16, 8)]


def _plain_route(x, router_w, bias, top_k, scaling):
    """Both routers as they are written down: a plain product at
    ``HIGHEST`` on the widened input, ``take_along_axis``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w, precision=HIGHEST)
    if bias is None:
        scores = jax.nn.softmax(logits, axis=-1)
        _, top_e = jax.lax.top_k(scores, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    return top_e, scaling * top_s / top_s.sum(-1, keepdims=True)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_a_router_equals_its_plain_formulation_and_so_do_its_gradients(
        router, dtype):
    t, h, experts, top_k = 96, 40, 32, 6
    x, w, bias = _router_inputs(t, h, experts, dtype)
    cot = jax.random.normal(jax.random.PRNGKey(8), (t, top_k))

    def ours(x, w, bias):
        route = moe._route if router == "softmax" else sigmoid_route(bias)
        top_e, weight = route(x, w, top_k, SCALING)
        return (weight * cot).sum(), (top_e, weight)

    def plain(x, w, bias):
        top_e, weight = _plain_route(
            x, w, None if router == "softmax" else bias, top_k, SCALING)
        return (weight * cot).sum(), (top_e, weight)

    # under remat, as the models run it
    (dx, dw, dbias), (top_e, weight) = jax.grad(
        jax.checkpoint(ours), argnums=(0, 1, 2), has_aux=True)(x, w, bias)
    (dx_p, dw_p, dbias_p), (top_e_p, weight_p) = jax.grad(
        plain, argnums=(0, 1, 2), has_aux=True)(x, w, bias)
    np.testing.assert_array_equal(top_e, top_e_p)
    assert top_e.dtype == jnp.int32 and weight.dtype == jnp.float32
    np.testing.assert_allclose(weight, weight_p, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(weight.sum(-1), SCALING, rtol=1e-6)
    assert dx.dtype == dtype and dw.dtype == jnp.float32
    assert float(jnp.abs(dw_p).max()) > 0 and float(
        jnp.abs(dx_p.astype(jnp.float32)).max()) > 0
    np.testing.assert_allclose(dw, dw_p, rtol=2e-5,
                               atol=2e-6 * float(jnp.abs(dw_p).max()))
    # dx leaves in x's dtype: in bfloat16 both sides round the same
    # float32 number, to one step of 2^-8 where they fall on two sides
    np.testing.assert_allclose(
        dx.astype(jnp.float32), dx_p.astype(jnp.float32),
        rtol=2e-5 if dtype == jnp.float32 else 2 ** -7,
        atol=2e-6 * float(jnp.abs(dx_p.astype(jnp.float32)).max()))
    assert not np.asarray(dbias).any() and not np.asarray(dbias_p).any()


@pytest.mark.parametrize("t,experts,top_k", [(70, 48, 9), (33, 7, 7),
                                             (64, 512, 22), (16, 16, 1)])
def test_the_chosen_scores_are_take_along_axis_bit_for_bit(t, experts, top_k):
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    scores = jax.nn.sigmoid(jax.random.normal(ks[0], (t, experts)) * 3)
    _, top_e = jax.lax.top_k(
        scores + jax.random.normal(ks[1], (experts,)), top_k)
    cot = jax.random.normal(ks[2], (t, top_k))
    got, vjp = jax.vjp(lambda s: moe._chosen(s, top_e), scores)
    want, vjp_plain = jax.vjp(
        lambda s: jnp.take_along_axis(s, top_e, axis=-1), scores)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(vjp(cot)[0], vjp_plain(cot)[0])


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_a_routers_gradient_has_no_gather_no_scatter_and_stores_nothing_of_tokens_by_top_k_by_experts(router):  # noqa: E501
    """In the style of the layout's test below ``_index_counts``: no
    indexed op of the gradient's program has tokens x top_k indices (none
    at all, here: autodiff's ``take_along_axis`` and ``top_k`` each
    scatter as many backward), and in the compiled program everything of
    tokens x top_k x experts elements lies inside a fusion with the
    reduction and no fusion writes it: the compare is fused into the
    sum."""
    t, h, experts, top_k = 64, 24, 16, 6
    x, w, bias = _router_inputs(t, h, experts, jnp.bfloat16)
    cot = jax.random.normal(jax.random.PRNGKey(8), (t, top_k))

    def loss(x, w, bias):
        route = moe._route if router == "softmax" else sigmoid_route(bias)
        _, weight = route(x, w, top_k, SCALING)
        return (weight * cot).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    found = _index_counts(jax.make_jaxpr(grad)(x, w, bias).jaxpr, [])
    assert not found, found

    text = jax.jit(grad).lower(x, w, bias).compile().as_text()
    computation, large, stored, reduces = None, set(), set(), set()
    for line in text.splitlines():
        if not line.startswith(" "):
            computation = line.split(" ")[0].lstrip("%")
            continue
        _, found_result, rest = line.partition(" = ")
        if not found_result:
            continue
        if " reduce(" in rest:
            reduces.add(computation)
        result = rest if rest.startswith("(") else rest.split("(")[0]
        for dims in re.findall(r"\w+\[([\d,]+)\]", result[:200]):
            if np.prod([int(d) for d in dims.split(",")]) >= (
                    t * top_k * experts):
                large.add(computation)
                if line.lstrip().startswith("ROOT"):  # what a fusion writes
                    stored.add(computation)
    assert large, "the compare against the experts' numbers is somewhere"
    assert not stored, stored
    for computation in large:
        assert computation.startswith("fused_computation"), computation
        assert computation in reduces, computation
