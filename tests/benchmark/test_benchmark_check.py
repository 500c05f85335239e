"""The digest that ``correct`` compares the parameter change on
(``benchmark/harness/check.py``): the same draw on both sides, a small
leaf whole, a run against itself at 0, the leaves that move by round-off
alone left out, and ``verdict``'s two entries beside their limits."""

import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import check  # noqa: E402

K = check.DIGEST_K


def _tree(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return {"embed": {"table": jax.random.normal(ks[0], (96, 128))},
            "layer_0": {"kernel": jax.random.normal(ks[1], (8, 40, 33)),
                        "bias": jax.random.normal(ks[2], (33,))},
            "scale": jax.random.normal(ks[3], ())}


def _coordinates(tree):
    """``tree`` with every element replaced by its own flat index: its
    digest is the coordinates that the digest holds."""
    return jax.tree.map(
        lambda leaf: jnp.arange(leaf.size, dtype=jnp.float32).reshape(
            leaf.shape), tree)


def test_the_same_seed_draws_the_same_coordinates_on_both_sides():
    """The system's tree of arrays and the reference's, of another
    process's making or only described by shapes, draw alike; another
    seed draws other coordinates, and two leaves of one shape differ."""
    tree = _tree()
    where = _coordinates(tree)
    ours = check.digester(tree, 2 ** 31 + 7)(where)
    theirs = check.digester(jax.eval_shape(lambda: tree), 2 ** 31 + 7)(where)
    assert list(ours) == list(theirs)
    for path in ours:
        np.testing.assert_array_equal(ours[path], theirs[path])
    table = ours["['embed']['table']"]
    assert table.shape == (K,) and table.dtype == np.float64
    assert 0 <= table.min() and table.max() < 96 * 128
    assert len(set(table)) > 0.8 * K  # drawn with replacement, over 12,288
    other = check.digester(tree, 2 ** 31 + 8)(where)["['embed']['table']"]
    assert not np.array_equal(other, table)
    twins = {"a": tree["embed"]["table"], "b": tree["embed"]["table"]}
    got = check.digester(twins, 5)(_coordinates(twins))
    assert not np.array_equal(got["['a']"], got["['b']"])


def test_a_leaf_smaller_than_k_is_taken_whole_and_read_where_it_lies():
    tree = _tree()
    take = check.digester(tree, 3)
    got, where = take(tree), take(_coordinates(tree))
    assert set(got) == {"['embed']['table']", "['layer_0']['bias']",
                        "['layer_0']['kernel']", "['scale']"}
    np.testing.assert_array_equal(got["['layer_0']['bias']"],
                                  np.asarray(tree["layer_0"]["bias"]))
    np.testing.assert_array_equal(got["['scale']"],
                                  [float(tree["scale"])])
    # 8 x 40 x 33 = 10,560 elements: K of them, each where its index says.
    kernel = np.asarray(tree["layer_0"]["kernel"]).reshape(-1)
    assert got["['layer_0']['kernel']"].shape == (K,)
    np.testing.assert_array_equal(
        got["['layer_0']['kernel']"],
        kernel[where["['layer_0']['kernel']"].astype(int)])
    # A tree of other shapes is another digest's.
    with pytest.raises(ValueError, match="other shapes"):
        take({"embed": {"table": jnp.zeros((96, 129))}})


def _moved(tree, by, key):
    return jax.tree.map(
        lambda p, k: p + by * jax.random.normal(k, p.shape),
        tree, dict(zip(tree, jax.random.split(jax.random.PRNGKey(key),
                                              len(tree)))))


def _digests(by_system=1e-3, by_reference=1e-3, key_system=1, key_reference=1):
    start = {"a": jnp.zeros((64, 80)), "b": jnp.ones((300,)),
             "c": jnp.ones((40, 40))}
    take = check.digester(start, 11)
    system = _moved(start, by_system, key_system)
    reference = _moved(start, by_reference, key_reference)
    return [take(t) for t in (start, system, reference)]


ALIVE = {"['a']": 1.0, "['b']": 2.0, "['c']": 0.5}


def test_the_gap_of_a_run_against_itself_is_nought():
    got = check.update_gaps(*_digests(), ALIVE)
    assert got["update_gap"] == got["update_pooled_gap"] == 0.0
    assert got["median_leaf_gap"] == 0.0
    assert (got["leaves"], got["dead_leaves"]) == (3, [])
    assert set(got["by_leaf"]) == set(ALIVE)


def test_the_gap_is_the_difference_of_the_changes_over_the_references():
    start, system, reference = _digests(key_system=2)
    got = check.update_gaps(start, system, reference, ALIVE)
    # Two independent changes of one size: the difference is sqrt(2) of
    # either, though their norms are nearly equal.
    for leaf in got["by_leaf"].values():
        assert leaf["gap"] == pytest.approx(math.sqrt(2), rel=0.15)
    assert got["update_gap"] == max(
        v["gap"] for v in got["by_leaf"].values())
    assert got["by_leaf"][got["update_gap_leaf"]]["gap"] == got["update_gap"]
    assert got["update_pooled_gap"] == pytest.approx(math.sqrt(2), rel=0.05)
    # A state left unchanged reads 1 by both; a state moved double too.
    start, still, reference = _digests(by_system=0.0)
    got = check.update_gaps(start, still, reference, ALIVE)
    assert got["update_gap"] == got["update_pooled_gap"] == 1.0
    start, double, reference = _digests(by_system=2e-3)
    got = check.update_gaps(start, double, reference, ALIVE)
    assert got["update_gap"] == pytest.approx(1.0, rel=1e-3)
    assert got["update_pooled_gap"] == pytest.approx(1.0, rel=1e-3)
    # One leaf of three left where it was: 1 by the worst leaf, and
    # diluted over all of them.
    still["['a']"] = reference["['a']"]
    still["['c']"] = reference["['c']"]
    got = check.update_gaps(start, still, reference, ALIVE)
    assert (got["update_gap"], got["update_gap_leaf"]) == (1.0, "['b']")
    assert 0.1 < got["update_pooled_gap"] < 0.35


def test_a_leaf_that_moves_by_round_off_alone_is_left_out():
    """A key's bias under softmax has no gradient but rounding, and a
    normalising optimizer moves it all the same, each side its own way:
    by the reference's gradient, under a thousandth of the median
    leaf's, it is left out, not by name."""
    start, system, reference = _digests(key_system=2)
    system["['b']"] = start["['b']"] - (reference["['b']"] - start["['b']"])
    dead = dict(ALIVE, **{"['b']": 1e-9})
    got = check.update_gaps(start, system, reference, dead)
    assert got["dead_leaves"] == ["['b']"] and "['b']" not in got["by_leaf"]
    assert got["update_gap"] < 1.7
    assert check.update_gaps(start, system, reference,
                             ALIVE)["update_gap"] == pytest.approx(2.0)
    # A leaf that the reference leaves where it was is compared
    # absolutely, and one that is not a number is the worst there is.
    start, system, reference = _digests()
    reference["['c']"] = start["['c']"]
    got = check.update_gaps(start, system, reference, ALIVE)
    assert got["by_leaf"]["['c']"]["gap"] == pytest.approx(
        np.linalg.norm(system["['c']"] - start["['c']"]))
    system["['a']"] = system["['a']"] * np.nan
    got = check.update_gaps(start, system, reference, ALIVE)
    assert got["update_gap"] == math.inf
    assert got["update_gap_leaf"] == "['a']"


def _verdict(gap, pooled, config=None):
    config = config or {"loss_tolerance": {"abs": 0.02},
                        "update_tolerance": {"rel": 0.3, "pooled_rel": 0.2}}
    batch = jnp.zeros((2, 4))
    system = types.SimpleNamespace(n_chips=1, mean_rank=0.0, batch=(batch,),
                                   hlo_text="")
    return check.verdict(
        types.SimpleNamespace(config=config), system, [3.0, 2.0, 1.0], [0.5],
        [3.0, 2.0, 1.0],
        {"update_gap": gap, "update_pooled_gap": pooled}, (),
        on_tpu=False)


@pytest.mark.parametrize("gap,pooled,failed", [
    (0.1, 0.01, set()), (0.3, 0.2, set()),
    (0.31, 0.01, {"update_gap"}), (0.1, 0.5, {"update_pooled_gap"}),
    (math.inf, math.inf, {"update_gap", "update_pooled_gap"})])
def test_verdict_holds_the_change_to_the_configurations_limits(
        gap, pooled, failed):
    compared = _verdict(gap, pooled)
    assert list(compared)[:5] == ["losses_finite", "loss_fell", "reference",
                                  "update_gap", "update_pooled_gap"]
    assert compared["update_gap"] == {"value": gap, "limit": 0.3,
                                      "ok": "update_gap" not in failed}
    assert compared["update_pooled_gap"]["limit"] == 0.2
    assert {k for k, c in compared.items() if not c["ok"]} == failed


def test_a_limit_of_null_is_a_number_said_and_not_compared():
    compared = _verdict(0.9, 0.9, {
        "loss_tolerance": {"abs": 0.02},
        "update_tolerance": {"rel": None, "pooled_rel": 0.2}})
    assert "update_gap" not in compared
    assert compared["update_pooled_gap"]["ok"] is False


def test_a_configuration_without_update_tolerance_is_an_error():
    with pytest.raises(KeyError, match="update_tolerance"):
        _verdict(0.0, 0.0, {"loss_tolerance": {"abs": 0.02}})


def test_the_reference_step_returns_the_gradients_size_by_leaf():
    """The rule on dead leaves reads the reference's own gradient: the
    step hands back its root mean square by leaf beside the loss."""
    cell = types.SimpleNamespace(
        config={"optimizer": {"name": "sgd", "learning_rate": 0.5,
                              "momentum": 0.0}},
        traffic={"per_chip_batch": 2})
    reference = types.SimpleNamespace(
        loss=lambda params, extra, batch, config:
        (params["w"] * batch[0].mean(0)).sum() + 0.0 * params["idle"].sum())
    step, opt = check.reference_step(cell, reference, 4)
    params = {"w": jnp.zeros((3,)), "idle": jnp.ones((5,))}
    batch = (jnp.tile(jnp.asarray([[1.0, 2.0, 2.0]]), (4, 1)),)
    new, _, loss, rms = step(params, {}, opt.init(params), batch)
    assert float(loss) == 0.0
    # in the leaves' order: ``idle`` before ``w``
    np.testing.assert_allclose(rms, [0.0, math.sqrt(3.0)], rtol=1e-6)
    np.testing.assert_allclose(new["w"], [-0.5, -1.0, -1.0])
