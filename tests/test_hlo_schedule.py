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
