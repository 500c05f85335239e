"""Structural pins on the COMPILED collective schedule (VERDICT r3 #8).

Multi-chip hardware is absent on this rig, so the scaling-efficiency
design claims (docs/benchmarks.md "Scaling efficiency") are checkable
only in their compiled form: these tests lower the real programs and
assert on the optimized HLO —

1. hierarchical allreduce lowers to reduce-scatter + all-gather over the
   ICI groups with the cross-tier reduction over the DCN groups (the
   reference's NCCL-RS / MPI-allreduce / NCCL-AG split,
   /root/reference/horovod/common/operations.cc:1194-1346);
2. a fused gradient-pytree allreduce emits at most one collective per
   dtype group (the reference's 64 MB fusion buffer contract,
   operations.cc:2035-2074);
3. growing the world does not change the per-chip allreduce payload
   (the constant-per-chip-volume property ring/tree allreduce scaling
   rests on), and the DCN-crossing payload of the hierarchical form
   shrinks by exactly the ICI group size.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from horovod_tpu.parallel.hierarchical import hierarchical_allreduce

# Accept both HLO replica-group syntaxes: explicit {{0,1},{2,3}} and the
# iota form [2,2]<=[4] (+ optional transpose suffix).
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[\d,{} ]*\}\}|\[[\d,]+\]<=\[[\d,]+\][^,)\s]*)")


def _collectives(hlo: str, op: str):
    """[(groups_literal, result_shape_literal)] for every ``op`` line."""
    out = []
    for line in hlo.splitlines():
        ls = line.strip()
        # The result can be a bare shape or a tuple (XLA's combiner
        # merges same-group collectives into one variadic op); match the
        # op itself and its async -start form, not the -done wrapper.
        shape_m = re.search(rf"= (\([^)]*\)|\S+) {op}(?:-start)?\(", ls)
        if not shape_m:
            continue
        m = _GROUPS_RE.search(ls)
        out.append((m.group(1) if m else None, shape_m.group(1)))
    return out


def _group_sizes(groups: str):
    """Sizes of the replica groups in either HLO syntax."""
    if groups is None:
        return []
    if groups.startswith("{{"):
        return [len(g.split(",")) for g in re.findall(r"\{([\d, ]+)\}", groups)]
    m = re.match(r"\[(\d+),(\d+)\]<=", groups)
    assert m, groups
    ngroups, per = int(m.group(1)), int(m.group(2))
    return [per] * ngroups


def _mesh2d(outer, inner):
    devs = np.array(jax.devices()[: outer * inner]).reshape(outer, inner)
    return Mesh(devs, ("dcn", "ici"))


def _compile_hier(outer, inner, n=1024):
    mesh = _mesh2d(outer, inner)
    fn = shard_map(lambda x: hierarchical_allreduce(x, "ici", "dcn"),
                   mesh=mesh, in_specs=P(), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn).lower(jnp.ones((n,), jnp.float32)).compile().as_text()


def test_hierarchical_allreduce_lowers_to_rs_dcn_ar_ag():
    hlo = _compile_hier(2, 4)
    rs = _collectives(hlo, "reduce-scatter")
    ag = _collectives(hlo, "all-gather")
    ar = _collectives(hlo, "all-reduce")
    assert len(rs) == 1 and len(ag) == 1 and len(ar) == 1, hlo[-3000:]
    # RS + AG ride the inner tier: 2 groups of 4 (the ICI rows).
    assert sorted(_group_sizes(rs[0][0])) == [4, 4], rs
    assert sorted(_group_sizes(ag[0][0])) == [4, 4], ag
    # The reduction crossing tiers pairs one chip per ICI position over
    # DCN: 4 groups of 2.
    assert sorted(_group_sizes(ar[0][0])) == [2, 2, 2, 2], ar


def test_hierarchical_dcn_payload_is_shard_sized():
    """The DCN-crossing all-reduce must carry 1/inner of the tensor —
    the hierarchical design's entire point (2N/L bytes over the slow
    tier, parallel/hierarchical.py cost model)."""
    n = 1024
    for outer, inner in [(2, 4), (4, 2)]:
        hlo = _compile_hier(outer, inner, n=n)
        (groups, shape), = _collectives(hlo, "all-reduce")
        m = re.match(r"f32\[(\d+)\]", shape)
        assert m, shape
        assert int(m.group(1)) == n // inner, (outer, inner, shape)


def test_flat_allreduce_per_chip_payload_invariant_in_world_size():
    """Doubling the world must not change what each chip reduces: the
    all-reduce operand stays the full gradient shape at any size (the
    scaling table's constant-per-chip-volume premise)."""
    n = 4096
    shapes = {}
    for world in (2, 4, 8):
        mesh = Mesh(np.array(jax.devices()[:world]), ("hvd",))
        fn = shard_map(lambda x: lax.psum(x, "hvd"), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        hlo = jax.jit(fn).lower(jnp.ones((n,), jnp.float32)).compile().as_text()
        ars = _collectives(hlo, "all-reduce")
        assert len(ars) == 1, hlo[-2000:]
        groups, shape = ars[0]
        assert sum(_group_sizes(groups)) == world
        shapes[world] = shape
    assert len(set(shapes.values())) == 1, shapes
    assert "f32[4096]" in shapes[2], shapes


def test_fused_grad_allreduce_one_collective_per_dtype(hvd):
    """allreduce_pytree over a mixed-dtype gradient tree compiles to at
    most one all-reduce per dtype group — and, with XLA's combiner, at
    least not one per LEAF (8 leaves here)."""
    import horovod_tpu.jax as hvd_jax

    tree = {
        "f32": [jnp.ones((3, 5)), jnp.ones((7,)), jnp.ones((2, 2, 2)),
                jnp.ones((11,)), jnp.ones((4,))],
        "bf16": [jnp.ones((6,), jnp.bfloat16), jnp.ones((3, 3), jnp.bfloat16),
                 jnp.ones((5,), jnp.bfloat16)],
    }

    @hvd_jax.jit(in_specs=(P(),), out_specs=P())
    def reduce_tree(t):
        return hvd_jax.allreduce_pytree(t, average=True)

    hlo = reduce_tree.lower(tree).compile().as_text()
    ars = _collectives(hlo, "all-reduce")
    # One fused buffer per dtype group at most; XLA's combiner may merge
    # the groups further into a single variadic all-reduce (observed on
    # CPU: one op carrying (f32[6], f32[22])) — never one per leaf.
    n_dtypes = 2
    assert 1 <= len(ars) <= n_dtypes, (len(ars), [a[1] for a in ars])
    # Every chip participates in each (world = one group of 8).
    for groups, _ in ars:
        assert sum(_group_sizes(groups)) == 8, groups


def test_flat_vs_hierarchical_same_result(hvd):
    """The two schedules are interchangeable numerically (same devices,
    same order — topology._build_two_tier's invariant)."""
    mesh = _mesh2d(2, 4)
    x = jnp.arange(24.0, dtype=jnp.float32)
    hier = jax.jit(shard_map(
        lambda v: hierarchical_allreduce(v, "ici", "dcn"), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False))(x)
    flat_mesh = Mesh(np.array(jax.devices()), ("hvd",))
    flat = jax.jit(shard_map(
        lambda v: lax.psum(v, "hvd"), mesh=flat_mesh,
        in_specs=P(), out_specs=P(), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat))


# -- the exchange inside a traced step packs only the small leaves ----------

def _shape_elems(shape_literal: str):
    """[(dtype, elements)] of every array shape in an HLO shape literal."""
    return [(dt, int(np.prod([int(d) for d in dims.split(",") if d] or [1])))
            for dt, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]*)\]",
                                       shape_literal)]


_ITEMSIZE = {"f32": 4, "bf16": 2, "s32": 4}


def _reduce_tree(hvd, verb, average):
    """A ``hvd.jax.jit`` program that takes a replicated tree through one
    of the public spellings of the dense exchange."""
    import horovod_tpu.jax as hvd_jax

    @hvd_jax.jit(in_specs=(P(),), out_specs=P())
    def reduce_tree(t):
        if verb == "grouped_allreduce":
            leaves, treedef = jax.tree.flatten(t)
            return jax.tree.unflatten(
                treedef, hvd.grouped_allreduce(leaves, average=average))
        fn = (hvd_jax if verb.startswith("jax.") else hvd).allreduce_pytree
        return fn(t, average=average)

    return reduce_tree


def _exchange_tree():
    from horovod_tpu.ops.collectives import FUSION_THRESHOLD_ELEMS as big

    return big, {
        "f32": [jnp.ones((8, big // 4)), jnp.ones((7,)), jnp.ones((big,)),
                jnp.ones((3, 5)), jnp.ones((big - 1,))],
        # (not bf16: the CPU backend widens it before it reduces)
        "s32": [jnp.ones((3 * big,), jnp.int32), jnp.ones((6,), jnp.int32),
                jnp.ones((3, 3), jnp.int32)],
    }


@pytest.mark.parametrize("verb", ["allreduce_pytree", "jax.allreduce_pytree",
                                  "grouped_allreduce"])
def test_traced_exchange_copies_no_large_leaf(hvd, verb):
    """The compiled exchange of a tree of large and small leaves: the
    only ``concatenate`` results are the small buffers, one a dtype (no
    large leaf is among any concatenate's operands); what the collectives
    carry is the tree's bytes and nothing more; each spans the world."""
    big, tree = _exchange_tree()
    hlo = _reduce_tree(hvd, verb, True).lower(tree).compile().as_text()
    small = {dt: sum(x.size for x in leaves if x.size < big)
             for dt, leaves in tree.items()}
    concats = [_shape_elems(m.group(1)) for m in re.finditer(
        r"= (\S+) concatenate\(", hlo)]
    assert sorted(c for (c,) in concats) == sorted(small.items()), concats
    ars = _collectives(hlo, "all-reduce")
    carried = sum(_ITEMSIZE[dt] * n for _, shape in ars
                  for dt, n in _shape_elems(shape))
    assert carried == sum(x.nbytes for x in jax.tree.leaves(tree)), ars
    # The large leaves are reduced in their own shapes, never flattened
    # into a buffer: nothing the collectives carry is larger than a leaf.
    assert max(n for _, shape in ars for _, n in _shape_elems(shape)) \
        == 3 * big, ars
    for groups, _ in ars:
        assert _group_sizes(groups) == [8], groups


@pytest.mark.parametrize("compression,wire", [("none", "f32"),
                                              ("bf16", "bf16")])
def test_distributed_optimizer_exchanges_leaf_by_leaf(hvd, compression, wire):
    """``DistributedOptimizer(adamw, fused_update=True)`` over the mesh:
    the updates of plain ``lax.pmean`` + optax, and on the wire every
    large gradient in its own shape and in the compressor's dtype."""
    import optax

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops.collectives import FUSION_THRESHOLD_ELEMS as big

    params = {"w": jnp.linspace(-1.0, 1.0, 2 * big).reshape(2, big),
              "v": jnp.linspace(0.5, 1.5, big + 8), "b": jnp.ones((5,)),
              "g": jnp.full((3, 3), 0.25)}
    x = jnp.arange(8.0)[:, None] + 1.0
    inner = optax.adamw(1e-2, weight_decay=0.01)
    opt = hvd_jax.DistributedOptimizer(
        inner, fused_update=True,
        compression=hvd_jax.Compression.resolve(compression))

    def grads_of(p, xi):
        return jax.grad(lambda q: sum(
            jnp.sum(jnp.sin(leaf * xi)) for leaf in jax.tree.leaves(q)))(p)

    @hvd_jax.jit(in_specs=(P(), P(), P(hvd_jax.HVD_AXIS)), out_specs=P())
    def step(p, state, xs):
        return opt.update(grads_of(p, xs[0, 0]), state, p)[0]

    def plain(p, state, xs):
        g = grads_of(p, xs[0, 0])
        if compression == "bf16":
            g = jax.tree.map(lambda t: lax.pmean(
                t.astype(jnp.bfloat16), "hvd").astype(t.dtype), g)
        else:
            g = lax.pmean(g, "hvd")
        return inner.update(g, state, p)[0]

    want = jax.jit(shard_map(
        plain, mesh=hvd.mesh(), in_specs=(P(), P(), P("hvd")),
        out_specs=P(), check_vma=False))(params, inner.init(params), x)
    got = step(params, opt.init(params), x)
    for key in params:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]), rtol=1e-6,
                                   atol=1e-9, err_msg=key)

    # The wire, as the program asks for it (the CPU backend may widen a
    # bf16 all-reduce afterwards): one all_reduce a large leaf, in its
    # shape and the wire dtype, and one small buffer.
    text = step.lower(params, opt.init(params), x).as_text()
    operands = re.findall(
        r'"stablehlo\.all_reduce"\(.*?\(tensor<([\dx]+)x(\w+)>\)', text,
        flags=re.S)
    assert sorted(operands) == sorted(
        [(f"2x{big}", wire), (f"{big + 8}", wire), ("14", wire)]), operands


@pytest.fixture
def two_tier_world(monkeypatch):
    """The 8-device world as 2 slices of 4 with the hierarchical knob on
    (tests/test_hierarchical_wiring.py has the same world)."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TWO_TIER_SHAPE", "2,4")
    monkeypatch.setenv("HVD_HIERARCHICAL_ALLREDUCE", "1")
    hvd.shutdown()
    hvd.init()
    yield hvd
    monkeypatch.undo()
    hvd.shutdown()
    hvd.init()


@pytest.mark.parametrize("verb", ["allreduce_pytree", "jax.allreduce_pytree"])
def test_large_leaf_takes_the_hierarchical_route_by_itself(
        two_tier_world, verb):
    """Under the two-tier mesh with the knob on, a large leaf is
    reduce-scattered over ICI, all-reduced over DCN at a quarter of its
    size and all-gathered over ICI, as itself: the small leaves' buffer
    makes the same trip beside it, not with it."""
    from horovod_tpu.ops.collectives import FUSION_THRESHOLD_ELEMS as big

    n = 2 * big
    tree = {"w": jnp.arange(n, dtype=jnp.float32).reshape(2, big),
            "b": jnp.ones((8,)), "g": jnp.ones((2, 2))}
    reduce_tree = _reduce_tree(two_tier_world, verb, False)
    hlo = reduce_tree.lower(tree).compile().as_text()

    def carried(op, group_sizes):
        found = _collectives(hlo, op)
        assert found, (op, hlo[-2000:])
        for groups, _ in found:
            assert sorted(_group_sizes(groups)) == group_sizes, (op, groups)
        return sorted(k for _, shape in found for _, k in _shape_elems(shape))

    assert carried("reduce-scatter", [4, 4]) == [12 // 4, n // 4]
    assert carried("all-reduce", [2, 2, 2, 2]) == [12 // 4, n // 4]
    assert carried("all-gather", [4, 4]) == [12, n]
    assert [_shape_elems(m.group(1)) for m in re.finditer(
        r"= (\S+) concatenate\(", hlo)] == [[("f32", 12)]]
    out = reduce_tree(tree)
    for key in tree:
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(tree[key]) * 8)
