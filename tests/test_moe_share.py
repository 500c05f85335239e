"""One chip's share of a top-k expert layer (``parallel/moe.py``
``expert_share_layer``): the shares add up to the uncut layer of the
benchmark's plain reference, nothing is dropped under a skewed router,
and the counters equal a plain count."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from horovod_tpu.parallel.moe import expert_share_layer  # noqa: E402

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
