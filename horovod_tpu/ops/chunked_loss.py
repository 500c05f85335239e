"""Chunked softmax cross-entropy over a large vocabulary — the LM-head
loss without materializing the logits.

Motivation (measured, docs/benchmarks.md): for BERT-base at bs8/seq512 the
``[4096, 30522]`` f32 logits tensor is ~500 MB; the stock
``lm_head -> optax.softmax_cross_entropy_with_integer_labels`` path
writes it, re-reads it for logsumexp + label gather, and materializes its
gradient again in the backward — several GB of HBM traffic per step on a
bandwidth-bound chip (~22% of the whole training step). The reference has
no transformer, but the same idea is its fp16-compression playbook (C11):
spend FLOPs to move fewer bytes.

This op streams the vocabulary in chunks with an online logsumexp —
structurally the flash-attention trick (ops/flash_attention.py) applied to
the classifier head: the forward keeps only ``logsumexp`` and the label's
logit per token; the backward recomputes each chunk's logits, forms
``softmax - onehot`` on the fly, and accumulates dx / dW / db. Peak live
memory for the head drops from O(N*V) to O(N*chunk).

Measured on a v5e (docs/benchmarks.md "LM-head loss"): *throughput* is
parity-class with the stock path (XLA's own fusion of the head is
excellent; the backward's logits recompute costs the MXU what the
skipped HBM round-trips save) — slightly ahead at large batch×vocab,
slightly behind at BERT-base bs8. The wins are the O(N·chunk) memory
cap (vocab- and batch-scaling headroom the stock path lacks) and the
head staying off the remat path.

API mirrors ``optax.softmax_cross_entropy_with_integer_labels`` but takes
the head weights explicitly (they never produce logits in HBM):

    losses = chunked_softmax_cross_entropy(hidden, kernel, bias, labels)
    loss = losses.mean()

Matmuls run with bf16 operands and f32 accumulation
(``preferred_element_type``) — full MXU rate, stable f32 logsumexp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 2048


def _chunk_logits(h2d, kernel, bias, c0, width):
    """One chunk's logits in f32: (h2d @ kernel[:, c0:c0+width]) + bias.
    bf16 operands, f32 accumulation."""
    kc = jax.lax.dynamic_slice_in_dim(kernel, c0, width, axis=1)
    bc = jax.lax.dynamic_slice_in_dim(bias, c0, width, axis=0)
    logits = jax.lax.dot_general(
        h2d, kc.astype(h2d.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits + bc.astype(jnp.float32)[None, :]


def _pad_vocab(kernel, bias, chunk):
    """Pad V up to a chunk multiple. Padded bias is -inf-like so the ghost
    columns vanish from logsumexp; labels never point at them."""
    v = kernel.shape[1]
    pad = (-v) % chunk
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, (0, pad), constant_values=-1e30)
    return kernel, bias, v + pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_softmax_cross_entropy(hidden, kernel, bias, labels,
                                  chunk: int = DEFAULT_CHUNK):
    """Per-token losses ``logsumexp(h@W+b) - (h@W+b)[label]``.

    hidden: [..., H] (any leading shape; bf16 or f32)
    kernel: [H, V], bias: [V] — the head parameters
    labels: [...] int32, same leading shape as hidden.
      Precondition: ``0 <= label < V`` for every position. An
      out-of-range label (e.g. a -100 ignore-index) is NOT detected:
      its label-logit carry stays 0, the loss silently degrades to
      ``lse - 0``, and the backward emits a pure-softmax gradient.
      Mask ignored positions via the cotangent instead — clip their
      labels into range and weight the returned per-token losses with 0
      (that zero flows through ``g`` in the backward, zeroing their
      gradient); ``tests/test_chunked_loss.py::
      test_mask_ignored_labels_via_cotangent`` pins the convention.
    Returns f32 losses with the leading shape.
    """
    losses, _ = _fwd(hidden, kernel, bias, labels, chunk)
    return losses


def _fwd(hidden, kernel, bias, labels, chunk):
    lead = hidden.shape[:-1]
    h2d = hidden.reshape(-1, hidden.shape[-1])
    lab = labels.reshape(-1)
    n = h2d.shape[0]
    kernel_p, bias_p, vpad = _pad_vocab(kernel, bias, chunk)
    nchunks = vpad // chunk

    def body(carry, idx):
        m, s, lbl = carry
        c0 = idx * chunk
        logits = _chunk_logits(h2d, kernel_p, bias_p, c0, chunk)
        cmax = logits.max(axis=1)
        new_m = jnp.maximum(m, cmax)
        s = s * jnp.exp(m - new_m) + jnp.exp(
            logits - new_m[:, None]).sum(axis=1)
        local = lab - c0
        inside = (local >= 0) & (local < chunk)
        safe = jnp.clip(local, 0, chunk - 1)
        got = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
        lbl = jnp.where(inside, got, lbl)
        return (new_m, s, lbl), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, lbl), _ = jax.lax.scan(body, init, jnp.arange(nchunks))
    lse = jnp.log(s) + m
    losses = (lse - lbl).reshape(lead)
    return losses, (hidden, kernel, bias, labels, lse)


def _bwd(chunk, residuals, g):
    hidden, kernel, bias, labels, lse = residuals
    lead = hidden.shape[:-1]
    h2d = hidden.reshape(-1, hidden.shape[-1])
    lab = labels.reshape(-1)
    gflat = g.reshape(-1).astype(jnp.float32)
    kernel_p, bias_p, vpad = _pad_vocab(kernel, bias, chunk)
    nchunks = vpad // chunk
    hdim, v = kernel.shape

    def body(dx, idx):
        c0 = idx * chunk
        logits = _chunk_logits(h2d, kernel_p, bias_p, c0, chunk)
        probs = jnp.exp(logits - lse[:, None])
        local = lab - c0
        onehot = (local[:, None] ==
                  jnp.arange(chunk)[None, :]).astype(jnp.float32)
        dlog = (probs - onehot) * gflat[:, None]          # [N, chunk] f32
        kc = jax.lax.dynamic_slice_in_dim(kernel_p, c0, chunk, axis=1)
        dlog_b = dlog.astype(h2d.dtype)
        # dx accumulates across chunks (carry); dW/db stack per chunk.
        dx = dx + jax.lax.dot_general(
            dlog_b, kc.astype(h2d.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dkc = jax.lax.dot_general(
            h2d, dlog_b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, chunk]
        return dx, (dkc, dlog.sum(axis=0))

    dx0 = jnp.zeros((h2d.shape[0], hdim), jnp.float32)
    dx, (dks, dbs) = jax.lax.scan(body, dx0, jnp.arange(nchunks))
    dkernel = jnp.moveaxis(dks, 0, 1).reshape(hdim, vpad)[:, :v]
    dbias = dbs.reshape(vpad)[:v]
    return (dx.astype(hidden.dtype).reshape(hidden.shape),
            dkernel.astype(kernel.dtype),
            dbias.astype(bias.dtype),
            None)


chunked_softmax_cross_entropy.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Pallas kernel version — the XLA scan above caps live memory but still
# round-trips each [N, chunk] logits tile through HBM (the two-pass
# max/exp reduction defeats single-kernel fusion). These kernels keep the
# tile in VMEM, flash-attention style (ops/flash_attention.py is the
# structural template; vocabulary columns play the role of keys).
# ---------------------------------------------------------------------------

import jax.experimental.pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from horovod_tpu.ops.pallas_mode import resolve_interpret  # noqa: E402

_STAT = 128  # lane width for (block_n, 128) row-stat scratch tiles


def _ce_fwd_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, lbl_ref,
                   m_ref, l_ref, acc_ref, *, nv: int, block_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[...].astype(jnp.float32)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(jnp.exp(logits - m_new), axis=1,
                                     keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    local = lab_ref[...] - vi * block_v                     # (bn, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.sum(jnp.where(col == local, logits, 0.0), axis=1,
                     keepdims=True)
    acc_ref[...] += jnp.broadcast_to(picked, acc_ref.shape)

    @pl.when(vi == nv - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        lse_ref[...] = m_ref[:, :1] + jnp.log(safe)
        lbl_ref[...] = acc_ref[:, :1]


def _ce_dlog(x, w_ref, b_ref, lab_ref, lse_ref, g_ref, vi, block_v):
    """Recompute one tile's (softmax - onehot) * g from the row stats —
    shared by both backward kernels (the flash recurrence's `ds`)."""
    w = w_ref[...].astype(x.dtype)
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[...].astype(jnp.float32)
    p = jnp.exp(logits - lse_ref[...])                       # (bn, bv) f32
    local = lab_ref[...] - vi * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    return (p - (col == local).astype(jnp.float32)) * g_ref[...], w


def _ce_dx_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dx_ref,
                  acc_ref, *, nv: int, block_v: int):
    # grid (nn, nv): vocab inner — dx accumulates in VMEM scratch.
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dlog, w = _ce_dlog(x, w_ref, b_ref, lab_ref, lse_ref, g_ref, vi,
                       block_v)
    acc_ref[...] += jax.lax.dot_general(
        dlog.astype(x.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == nv - 1)
    def _finalize():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _ce_dw_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref,
                  dw_ref, db_ref, accw_ref, accb_ref,
                  *, nn: int, block_v: int):
    # grid (nv, nn): tokens inner — dW/db accumulate in VMEM scratch.
    vi = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        accw_ref[...] = jnp.zeros_like(accw_ref)
        accb_ref[...] = jnp.zeros_like(accb_ref)

    x = x_ref[...]
    dlog, _ = _ce_dlog(x, w_ref, b_ref, lab_ref, lse_ref, g_ref, vi,
                       block_v)
    accw_ref[...] += jax.lax.dot_general(
        x, dlog.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    accb_ref[...] += jnp.broadcast_to(
        jnp.sum(dlog, axis=0, keepdims=True), accb_ref.shape)

    @pl.when(ni == nn - 1)
    def _finalize():
        dw_ref[...] = accw_ref[...]
        db_ref[...] = accb_ref[:1, :]


def _pad_rows(a, mult, value=0):
    pad = (-a.shape[0]) % mult
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=value)
    return a


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_v", "interpret"))
def _ce_fwd_call(h2d, kernel, bias, lab, block_n, block_v, interpret):
    n0, hdim = h2d.shape
    kernel_p, bias_p, vpad = _pad_vocab(kernel, bias, block_v)
    # Stream W in the compute dtype: an f32 W would double every kernel's
    # dominant HBM traffic (each token-block pass re-reads all of W).
    kernel_p = kernel_p.astype(h2d.dtype)
    x = _pad_rows(h2d, block_n)
    labs = _pad_rows(lab[:, None], block_n)
    n = x.shape[0]
    nn, nv = n // block_n, vpad // block_v
    lse, lbl = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, nv=nv, block_v=block_v),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, hdim), lambda i, j: (i, 0)),
            pl.BlockSpec((hdim, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, _STAT), jnp.float32),
                        pltpu.VMEM((block_n, _STAT), jnp.float32),
                        pltpu.VMEM((block_n, _STAT), jnp.float32)],
        interpret=interpret,
        name="xent_fwd",
    )(x, kernel_p, bias_p[None, :], labs)
    return (lse[:n0, 0] - lbl[:n0, 0]), lse[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_v", "interpret"))
def _ce_bwd_call(h2d, kernel, bias, lab, lse, g, block_n, block_v,
                 interpret):
    n0, hdim = h2d.shape
    v = kernel.shape[1]
    kernel_p, bias_p, vpad = _pad_vocab(kernel, bias, block_v)
    kernel_p = kernel_p.astype(h2d.dtype)  # see _ce_fwd_call
    x = _pad_rows(h2d, block_n)
    labs = _pad_rows(lab[:, None], block_n)
    n = x.shape[0]
    # Padded rows carry g=0 => dlog rows vanish; their garbage lse is inert.
    gpad = _pad_rows(g.astype(jnp.float32)[:, None], block_n)
    lsep = _pad_rows(lse[:, None], block_n)
    nn, nv = n // block_n, vpad // block_v
    inputs = (x, kernel_p, bias_p[None, :], labs, lsep, gpad)
    # Two kernels, each with a clean VMEM accumulator over its inner grid
    # axis (the split of ops/flash_attention.py's _dq_kernel/_dkv_kernel):
    # a cross-OUTER-axis accumulator would need non-contiguous
    # output-block revisits, which pallas does not give. The flash
    # backward gets round that by keeping a whole head's dq in VMEM
    # (_fused_bwd_kernel); not tried for dx here: no cell runs this.
    n_specs = [
        pl.BlockSpec((block_n, hdim), lambda i, j: (i, 0)),
        pl.BlockSpec((hdim, block_v), lambda i, j: (0, j)),
        pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
        pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
    ]
    dx = pl.pallas_call(
        functools.partial(_ce_dx_kernel, nv=nv, block_v=block_v),
        grid=(nn, nv),
        in_specs=n_specs,
        # dx leaves in the compute dtype (the caller casts to
        # hidden.dtype anyway); the accumulator scratch stays f32.
        out_specs=pl.BlockSpec((block_n, hdim), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hdim), h2d.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, hdim), jnp.float32)],
        interpret=interpret,
        name="xent_dx",
    )(*inputs)
    # The dW kernel carries three (hdim, vocab-tile) buffers — the
    # double-buffered W input, the f32 scratch accumulator, and the
    # double-buffered f32 dW output — so it runs a smaller token tile to
    # stay inside the 16 MB scoped-VMEM stack at full vocab-tile width.
    bn_dw = 256 if n % 256 == 0 and block_n > 256 else block_n
    nn_dw = n // bn_dw
    v_specs = [
        pl.BlockSpec((bn_dw, hdim), lambda j, i: (i, 0)),
        pl.BlockSpec((hdim, block_v), lambda j, i: (0, j)),
        pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
    ]
    dw, db = pl.pallas_call(
        functools.partial(_ce_dw_kernel, nn=nn_dw, block_v=block_v),
        grid=(nv, nn_dw),
        in_specs=v_specs,
        out_specs=[
            pl.BlockSpec((hdim, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hdim, vpad), jnp.float32),
            jax.ShapeDtypeStruct((1, vpad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hdim, block_v), jnp.float32),
                        pltpu.VMEM((8, block_v), jnp.float32)],
        interpret=interpret,
        name="xent_dw",
    )(*inputs)
    return dx[:n0], dw[:, :v], db[0, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_softmax_cross_entropy(hidden, kernel, bias, labels,
                                block_n: int = 512, block_v: int = 1024,
                                interpret: bool | None = None):
    """Pallas-kernel LM-head loss: same contract as
    :func:`chunked_softmax_cross_entropy`, but the per-tile logits never
    leave VMEM in either direction. Off-TPU the kernels run in pallas
    interpret mode (tests/CPU; logged once, ops/pallas_mode.py)."""
    losses, _ = _fused_fwd_rule(hidden, kernel, bias, labels, block_n,
                                block_v, interpret)
    return losses


def _fused_fwd_rule(hidden, kernel, bias, labels, block_n, block_v,
                    interpret):
    lead = hidden.shape[:-1]
    h2d = hidden.reshape(-1, hidden.shape[-1])
    lab = labels.reshape(-1)
    losses, lse = _ce_fwd_call(
        h2d, kernel, bias, lab, block_n, block_v,
        resolve_interpret(interpret, "fused_softmax_cross_entropy"))
    return losses.reshape(lead), (hidden, kernel, bias, labels, lse)


def _fused_bwd_rule(block_n, block_v, interpret, residuals, g):
    hidden, kernel, bias, labels, lse = residuals
    h2d = hidden.reshape(-1, hidden.shape[-1])
    dx, dw, db = _ce_bwd_call(
        h2d, kernel, bias, labels.reshape(-1), lse, g.reshape(-1),
        block_n, block_v,
        resolve_interpret(interpret, "fused_softmax_cross_entropy"))
    return (dx.astype(hidden.dtype).reshape(hidden.shape),
            dw.astype(kernel.dtype), db.astype(bias.dtype), None)


fused_softmax_cross_entropy.defvjp(_fused_fwd_rule, _fused_bwd_rule)
