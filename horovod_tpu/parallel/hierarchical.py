"""Two-tier hierarchical collectives: reduce-scatter(ICI) → cross-tier
op(DCN) → all-gather(ICI).

This is the TPU-native re-design of the reference's hierarchical allreduce —
NCCL ReduceScatter → host-staged cross-node MPI_Allreduce → NCCL AllGather
(reference: horovod/common/operations.cc:1194-1346) — with XLA collectives
replacing both NCCL and MPI and no host staging buffer. The reference pads
fused buffers to 64-element atomic units so the scatter divides evenly
(reference: operations.h:52-54, operations.cc:712-731); here the same
padding happens at trace time with static shapes.

These run *inside* SPMD code over a mesh that has both tiers as named axes
(see :func:`horovod_tpu.parallel.mesh.two_tier_mesh`).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from horovod_tpu.common import phases as _phases
from horovod_tpu.parallel.mesh import DCN_AXIS, ICI_AXIS


def _padded_flat(x, inner: int):
    flat = jnp.ravel(x)
    rem = flat.size % inner
    pad = inner - rem if rem else 0
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def hierarchical_allreduce(
    x,
    inner_axis: str = ICI_AXIS,
    outer_axis: str = DCN_AXIS,
    average: bool = False,
    dcn_policy=None,
):
    """Allreduce ``x`` across both tiers, moving only 1/inner_size of the
    payload over the slow outer tier per chip.

    Cost model (why this beats flat allreduce across DCN): flat ring
    allreduce sends 2·N bytes/chip over DCN; hierarchical sends 2·N/L where
    L = inner size, with the bulk 2·N·(L-1)/L riding ICI — the same
    bandwidth argument as the reference's NCCL/MPI split
    (operations.cc:1194-1346).

    ``dcn_policy`` (a quantized wire policy from
    :mod:`horovod_tpu.jax.compression`) composes the EQuARX block-scaled
    wire with the tier split: the ICI reduce-scatter stays at the resident
    dtype, and ONLY the 1/L shard crosses the outer tier quantized
    (payload + f32 scales, block-padded) — cross-tier bytes drop by both
    the tier factor AND the wire factor. Requires a float ``x``; a
    single-tier outer axis elides the quantization entirely (no wire hop
    to shrink, and the digest stays on the unquantized path).
    """
    inner = lax.psum(1, inner_axis)  # static at trace time
    with _phases.phase("hvd_pack"):
        flat, pad = _padded_flat(x, inner)
    with _phases.phase("hvd_allreduce"):
        chunk = lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                                 tiled=True)
    if dcn_policy is not None and lax.psum(1, outer_axis) > 1:
        from horovod_tpu.jax import quantize as _Q

        # The quantized wire names its own pack / exchange / unpack.
        chunk = _Q.spmd_allreduce(chunk, outer_axis, False, dcn_policy)
    else:
        with _phases.phase("hvd_allreduce"):
            chunk = lax.psum(chunk, outer_axis)
    with _phases.phase("hvd_allreduce"):
        out = lax.all_gather(chunk, inner_axis, axis=0, tiled=True)
    with _phases.phase("hvd_unpack"):
        if pad:
            out = out[:-pad]
        if average:
            world = inner * lax.psum(1, outer_axis)
            if (jnp.issubdtype(out.dtype, jnp.floating)
                    or jnp.issubdtype(out.dtype, jnp.complexfloating)):
                out = (out / world).astype(x.dtype)
            else:
                out = out // world
        return out.reshape(x.shape)


def hierarchical_allgather(x, inner_axis: str = ICI_AXIS,
                           outer_axis: str = DCN_AXIS):
    """Allgather along dim 0 across both tiers (reference: the MPI
    shared-memory-window hierarchical allgather, operations.cc:875-1010).

    Gather over the outer tier first (each chip contributes its block once
    over DCN), then share over ICI... except XLA already routes a flat
    all_gather over the fastest links; the two-phase form exists for
    explicit control. Result ordering is outer-major, matching a flat
    gather over a (outer, inner)-ordered mesh.
    """
    with _phases.phase("hvd_allreduce"):
        outer = lax.all_gather(x, outer_axis, axis=0, tiled=True)
        both = lax.all_gather(outer, inner_axis, axis=1,
                              tiled=False)  # (outer*n, inner, ...)
    # Reorder to global rank order: outer-major, inner-minor.
    o = lax.psum(1, outer_axis)
    i = lax.psum(1, inner_axis)
    n = x.shape[0]
    with _phases.phase("hvd_unpack"):
        both = both.reshape((o, n, i) + x.shape[1:])
        both = jnp.swapaxes(both, 1, 2)
        return both.reshape((o * i * n,) + x.shape[1:])
