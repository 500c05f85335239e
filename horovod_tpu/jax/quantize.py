"""Block-scaled quantized collectives — the math behind the ``int8`` /
``fp8`` wire policies (EQuARX, arxiv 2506.17615; the MLPerf TPU-pod work,
arxiv 1909.09756, shows reduced-precision communication is load-bearing
for pod-scale efficiency).

The next multi-chip scaling win must cut BYTES on the wire. A cast to bf16
halves them; block-scaled int8 quarters them: per ``block`` contiguous
elements the wire carries ``round(x * qmax / amax)`` at 1 byte/element
plus ONE f32 scale — 4/(1 + 4/block) ≈ 3.9x fewer bytes than f32 at the
default block of 512, scales included.

Why there is no "quantized psum": summing int8 payloads saturates, and
widening them for an in-network sum would put the full width right back
on the wire. The TPU-native shape (EQuARX §3) keeps every wire hop at
the quantized width and does the accumulation at f32 on-chip:

- **reduce-scatter phase** = quantize the local buffer → int8
  ``all_to_all`` (each rank receives every rank's copy of ITS chunk,
  payload + scales) → dequantize-accumulate in f32. Wire bytes per rank:
  (world-1)/world of the quantized buffer, exactly a ring
  reduce-scatter's traffic at 1/4 width.
- **all-gather phase** = requantize the (updated) shard → int8
  ``all_gather`` (payload + scales) → dequantize.

Both phases compile into the ``shard_map`` step
(:func:`horovod_tpu.jax.shard_update` composes them with the fused
sharded-update epilogue), and the engines apply the same wire format to
their 16 MB execution chunks through the shared data plane
(:class:`horovod_tpu.core.engine.JaxExecutor` — shared by the python and
C++ engines, which is what makes their reduction digests bit-identical
by construction).

Quantization is deterministic and rank-symmetric: zero blocks get scale
1.0 (payload zeros), so zero padding is reduction-neutral exactly like
the unquantized padding contract, and ties round half-to-even
(``jnp.round`` / ``np.rint`` agree).
"""

from __future__ import annotations

import numpy as np

#: Elements per f32 scale. Mirrored by compression._QuantCompressor.block;
#: per-policy overrides ride the policy object.
DEFAULT_BLOCK = 512

_WIRE_NP_DTYPES = {}


def np_wire_dtype(policy) -> np.dtype:
    """Numpy dtype of the policy's wire payload (fp8 via ml_dtypes)."""
    name = policy.wire_dtype_name
    dt = _WIRE_NP_DTYPES.get(name)
    if dt is None:
        if name == "int8":
            dt = np.dtype(np.int8)
        else:
            import ml_dtypes

            dt = np.dtype(getattr(ml_dtypes, name))
        _WIRE_NP_DTYPES[name] = dt
    return dt


def padded_len(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def wire_bytes_of(n: int, policy) -> tuple:
    """(payload_bytes, scale_bytes) the policy ships for an n-element
    float buffer (block-padded) — the analytic form of the measured
    ``engine.wire_bytes`` counters, used by the benchmark's split."""
    npad = padded_len(n, policy.block)
    return npad * np_wire_dtype(policy).itemsize, (npad // policy.block) * 4


# ---------------------------------------------------------------------------
# jnp math (compiled + eager jax paths)
# ---------------------------------------------------------------------------

def _jnp():
    import jax.numpy as jnp

    return jnp


def _phase(name: str):
    # jax stays a lazy import here, as in _jnp (the numpy twins below
    # serve the engines' host-side data plane).
    from horovod_tpu.common import phases

    return phases.phase(name)


def quantize(flat, policy):
    """1-D float array (length % block == 0) -> (payload wire-dtype,
    scales f32 of length n/block). Zero blocks get scale 1.0 so their
    payload is exactly zero (padding neutrality)."""
    jnp = _jnp()
    with _phase("hvd_pack"):
        x = flat.astype(jnp.float32).reshape(-1, policy.block)
        amax = jnp.max(jnp.abs(x), axis=1)
        scale = jnp.where(amax > 0, amax / policy.qmax,
                          1.0).astype(jnp.float32)
        y = x / scale[:, None]
        if policy.round_to_int:
            payload = jnp.clip(jnp.round(y), -policy.qmax,
                               policy.qmax).astype(jnp.int8)
        else:
            payload = y.astype(jnp.dtype(policy.wire_dtype_name))
        return payload.reshape(flat.shape[0]), scale


def dequantize(payload, scales, policy, out_dtype=None):
    """Inverse of :func:`quantize`; f32 math, optionally cast."""
    jnp = _jnp()
    with _phase("hvd_unpack"):
        x = (payload.astype(jnp.float32).reshape(-1, policy.block)
             * scales.reshape(-1)[:, None]).reshape(payload.shape[0])
        return x if out_dtype is None else x.astype(out_dtype)


def spmd_exchange_accumulate(payload, scales, ax, policy):
    """The reduce-scatter phase on PRE-quantized values: int8
    ``all_to_all`` of (payload, scales) — each rank receives every
    rank's copy of its own chunk — then dequantize-accumulate in f32.
    Split out of :func:`spmd_reduce_scatter` so the error-feedback path
    (shard_update) can quantize once, keep the transmitted value for the
    residual, and exchange here."""
    from jax import lax

    jnp = _jnp()
    world = lax.psum(1, ax)
    nb = scales.shape[0]
    with _phase("hvd_allreduce"):
        p = lax.all_to_all(payload.reshape(world, -1), ax,
                           split_axis=0, concat_axis=0)
        s = lax.all_to_all(scales.reshape(world, -1), ax,
                           split_axis=0, concat_axis=0)
    with _phase("hvd_unpack"):
        contrib = (p.astype(jnp.float32).reshape(world, nb // world,
                                                 policy.block)
                   * s[:, :, None])
        return contrib.sum(axis=0).reshape(payload.shape[0] // world)


def spmd_reduce_scatter(flat, ax, policy):
    """Quantized reduce-scatter inside SPMD code: ``flat`` is this
    rank's (n,) buffer with n divisible by world*block; returns the f32
    (n/world,) SUM shard. The wire carries int8 payload + f32 scales via
    ``all_to_all`` (module docstring — a psum_scatter would have to sum
    payloads); accumulation runs at f32 on-chip."""
    payload, scales = quantize(flat, policy)
    return spmd_exchange_accumulate(payload, scales, ax, policy)


def spmd_gather_dequantize(payload, scales, ax, policy, out_dtype=None):
    """The all-gather phase on PRE-quantized shard values: tiled int8
    ``all_gather`` of (payload, scales), dequantized on arrival. Every
    rank (the owner included) applies the DEQUANTIZED values, so the
    gathered state is identical everywhere."""
    from jax import lax

    with _phase("hvd_allreduce"):
        p = lax.all_gather(payload, ax, axis=0, tiled=True)
        s = lax.all_gather(scales, ax, axis=0, tiled=True)
    return dequantize(p, s, policy, out_dtype)


def spmd_all_gather(shard, ax, policy, out_dtype=None):
    """Quantized tiled all-gather inside SPMD code: ``shard`` (m,) with
    m divisible by block; returns the (world*m,) dequantized buffer."""
    payload, scales = quantize(shard, policy)
    return spmd_gather_dequantize(payload, scales, ax, policy, out_dtype)


def spmd_allreduce(tensor, ax, average: bool, policy):
    """Generic quantized allreduce for SPMD code: ravel → pad →
    quantized reduce-scatter → (average) → requantize → quantized
    all-gather → unpad/reshape. This is the stateless surface (no
    error-feedback residual — that needs a state carrier; see
    shard_update)."""
    from jax import lax

    jnp = _jnp()
    world = lax.psum(1, ax)
    with _phase("hvd_pack"):
        flat = tensor.reshape(-1)
        n = flat.shape[0]
        npad = padded_len(n, world * policy.block)
        if npad != n:
            flat = jnp.concatenate(
                [flat, jnp.zeros((npad - n,), flat.dtype)])
    shard = spmd_reduce_scatter(flat, ax, policy)
    if average:
        with _phase("hvd_unpack"):
            shard = shard / world
    out = spmd_all_gather(shard, ax, policy)
    with _phase("hvd_unpack"):
        return out[:n].reshape(tensor.shape).astype(tensor.dtype)


def eager_exchange_accumulate(payload, scales, policy, world):
    """Eager twin of :func:`spmd_exchange_accumulate` over the FULL
    buffer: allgather the pre-quantized (payload, scales) across the
    world — the same bytes/hop the in-step exchange ships — and
    dequantize-accumulate on this controller. Returns the f32 sum."""
    from horovod_tpu.ops import collectives as _C

    jnp = _jnp()
    npad = payload.shape[0]
    p = jnp.asarray(np.asarray(_C.allgather(payload))).reshape(world, npad)
    s = jnp.asarray(np.asarray(_C.allgather(scales))).reshape(world, -1)
    return (p.astype(jnp.float32).reshape(world, -1, policy.block)
            * s[:, :, None]).sum(axis=0).reshape(npad)


def eager_allreduce(tensor, average: bool, policy):
    """Quantized allreduce for eager host calls: quantize the local
    contribution, allgather payload + scales across the world,
    dequantize-accumulate on this controller. Matches the
    eager-collective semantics of :mod:`horovod_tpu.ops.collectives`
    (each local chip contributes this controller's value)."""
    from horovod_tpu.ops import collectives as _C

    jnp = _jnp()
    flat = jnp.asarray(tensor).reshape(-1)
    n = flat.shape[0]
    npad = padded_len(max(n, 1), policy.block)
    if npad != n:
        flat = jnp.concatenate([flat, jnp.zeros((npad - n,), flat.dtype)])
    payload, scales = quantize(flat, policy)
    world = _C._topo._require_init().size
    out = eager_exchange_accumulate(payload, scales, policy, world)
    if average:
        out = out / world
    return out[:n].reshape(jnp.shape(tensor)).astype(tensor.dtype)


# ---------------------------------------------------------------------------
# numpy twins (the engines' host-side data plane — core/engine.py stages
# the QUANTIZED buffers, so host->device traffic shrinks with the wire)
# ---------------------------------------------------------------------------

def np_quantize(flat: np.ndarray, policy):
    """Host-side :func:`quantize` twin. Pads to a block multiple itself
    (engine chunks are pow2-bucketed, but defensive padding keeps any
    block size correct); returns (payload, scales, padded_len)."""
    npad = padded_len(max(flat.shape[0], 1), policy.block)
    payload = np.empty((npad,), np_wire_dtype(policy))
    scales = np.empty((npad // policy.block,), np.float32)
    np_quantize_into(flat, policy, payload, scales,
                     np.empty((npad,), np.float32))
    return payload, scales, npad


def np_quantize_into(flat: np.ndarray, policy, payload: np.ndarray,
                     scales: np.ndarray, work: np.ndarray):
    """:func:`np_quantize` staged into caller-owned buffers — the engines
    check ``payload``/``scales``/``work`` out of their buffer pool so the
    steady-state wire staging allocates nothing (``work`` is an f32
    scratch of ``payload``'s length; all three are 1-d, length/dtype
    exact). The math is bit-identical to :func:`np_quantize` — rint
    (ties to even) then clip then int cast — which is what keeps the
    python/C++ engine reduction digests equal under a quantized policy."""
    n = flat.shape[0]
    npad = payload.shape[0]
    work[:n] = np.asarray(flat, np.float32)
    work[n:] = 0.0
    x = work.reshape(-1, policy.block)
    amax = np.max(np.abs(x), axis=1)
    np.copyto(scales, np.where(amax > 0, amax / policy.qmax, 1.0),
              casting="unsafe")
    np.divide(x, scales.reshape(-1, 1), out=x)
    if policy.round_to_int:
        np.rint(x, out=x)
        np.clip(x, -policy.qmax, policy.qmax, out=x)
    np.copyto(payload, work[:npad], casting="unsafe")


def np_dequantize_sum(payloads: np.ndarray, scales: np.ndarray,
                      policy) -> np.ndarray:
    """(world, npad) payload rows + (world, nblocks) scale rows ->
    f32 (npad,) sum of the dequantized contributions."""
    world, npad = payloads.shape
    x = (payloads.astype(np.float32).reshape(world, -1, policy.block)
         * scales.reshape(world, -1)[:, :, None])
    return x.sum(axis=0).reshape(npad)
