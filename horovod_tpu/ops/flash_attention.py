"""Flash attention as a pallas TPU kernel — forward AND backward.

The hot op of transformer training. XLA's stock attention materializes the
(s × s) logits in HBM; this kernel streams one (block_q × d) Q tile and one
(block_k × d) K/V tile through VMEM per grid step with an online softmax,
so HBM traffic is O(s·d) instead of O(s²) and VMEM residency is bounded by
the block sizes regardless of sequence length — the standard flash
formulation (Dao et al.), written for the MXU: accumulation in f32, block
sizes default to 512 (a multiple of the 128-wide systolic tile; on a v5e
at d = 64 the forward takes 0.98 µs for a 512 × 512 tile of scores and
2.3 µs for the same area in 256-blocks: PERF.md, PR 28), the forward's q
block to 1,024 rows where no window cuts its rows short.

The forward's row statistics are whole (block_q, 128) tiles that the k
loop reads and writes as they are: the running maximum equal along its
lanes, the running sum as one partial sum a lane, added up once a row.
Reading one column of such a tile and broadcasting a column back at
every k step, as this kernel did before PR 28, relaid the tile out lane
by lane and cost more than the step's two matrix products (2.2–2.4
against 1.0–1.3 µs a tile on a v5e, at head sizes 64 and 128).

Training works end-to-end: :func:`flash_attention` carries a
``jax.custom_vjp`` whose backward recomputes attention probabilities from
the saved log-sum-exp row statistics (no (s × s) residuals), per the
flash backward recurrence:

    p_ij = exp(q_i·k_j·scale − lse_i)
    dv_j = Σ_i p_ij · do_i
    ds_ij = p_ij · (do_i·v_j − Δ_i),   Δ_i = do_i·o_i
    dq_i = Σ_j ds_ij · k_j · scale
    dk_j = Σ_i ds_ij · q_i · scale

One kernel makes all three (``fused_flash_dkv_bwd_bhsd``): it walks the
K tiles, streaming Q, computes p and ds once a tile and adds the tile's
share to dv, dk and to its q block's rows of dq, five matrix products a
tile. dq's rows come round once for every K tile, so the kernel keeps a
whole head's dq, dk and dv as (s, d) f32 accumulators in VMEM and asks
Mosaic for the room (``vmem_limit_bytes``). Where they do not fit
(:func:`fused_backward_fits`: the sequence, the head size and the dtype
decide, at trace time; 24 of 32 MiB at s = 8,192, d = 128 in bf16) two
kernels run instead, one producing dQ (grid over Q tiles, streaming K/V)
and one dK/dV (grid over K tiles, streaming Q), each recomputing p and
ds: seven products a tile, with accumulators of one block. The kernels
are bound by their MXU passes, so time goes by the products: on a v5e
the fused kernel takes 0.68–0.78 of the two's time (PERF.md, PR 30) and
its results equal theirs to the bit.

``causal=True, window=w`` keeps, for query i, the keys i-w < j <= i (a
sliding window): the innermost grid axis then covers only the k (or q)
blocks the band touches, so blocks outside it are skipped, not masked.
Grouped key-value heads: ``k`` and ``v`` may carry fewer heads than ``q``
(query head h reads key-value head h // group); the fused kernel walks
a group's query heads outside the K tiles (dq resident a query head, dk
and dv a key-value head), the dK/dV kernel in its innermost axis; both
sum them in f32 in the same order. The two backward kernels trace the
program they traced before PR 28 changed the forward's
(``tests/test_flash_window.py`` pins its hash): they are that change's
control, and the fused kernel's.

Plugs in anywhere the model zoo accepts an ``attention_fn``
(:class:`horovod_tpu.models.TransformerConfig`) and composes with sequence
parallelism: inside :func:`horovod_tpu.parallel.ulysses_attention` it
kernels the per-head full-sequence attention, and ring attention's
per-block math is the same online-softmax update this kernel runs locally.

Off-TPU (tests, CPU debugging) the kernels run in pallas interpret mode —
same code path, scalar semantics; the fallback is logged once
(:mod:`horovod_tpu.ops.pallas_mode`).

Matmul precision follows the input dtype: bf16 operands take the MXU's
native single pass, f32 inputs ask Mosaic for full f32 contraction — on
the chip the default would round f32 operands to bf16 and miss the f32
reference by ~1e-2.

(Reference parity note: kuroko1t/horovod contains no attention ops — this
is TPU-native scope beyond the reference, serving its examples' model
families at scale.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_mode import resolve_interpret

NEG_INF = -1e30
_LANES = 128  # of a vector register: the width of the row-stat tiles


def _precision(dtype):
    """In-kernel matmul precision for inputs of ``dtype`` (module doc)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _mm(a, b, precision):
    """(m, k) @ (k, n) on the MXU, f32 accumulation."""
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _mask_block(sblk, qi, ki, block_q, block_k, window=None):
    """Causal (with ``window``: banded) mask for one (block_q, block_k)
    logits tile."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, sblk.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, sblk.shape, 1)
    if window is None:
        return jnp.where(q_pos >= k_pos, sblk, NEG_INF)
    return jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), sblk,
                     NEG_INF)


def _max(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _min(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


class _Band:
    """Which blocks a grid row visits: arithmetic the index maps and the
    kernels share, on python ints and on traced indices alike. Row ``i``
    of the forward and dQ grids is a q block and visits k blocks
    ``k_first(i) .. k_last(i)``; row ``i`` of the dK/dV grid is a k block
    and visits q blocks ``q_first(i) .. q_last(i)``. Without a window the
    innermost axis spans every block and the causal ones outside are
    clamped and skipped; with one it spans ``n_k`` (``n_q``) blocks from
    the first visible, the most any row needs."""

    def __init__(self, window, s, block_q, block_k):
        self.window, self.bq, self.bk = window, block_q, block_k
        self.nq, self.nk = s // block_q, s // block_k
        self.n_k, self.n_q = self.nk, self.nq
        if window is not None:
            self.n_k = max(self.k_last(i) - self.k_first(i) + 1
                           for i in range(self.nq))
            self.n_q = max(self.q_last(i) - self.q_first(i) + 1
                           for i in range(self.nk))

    def k_first(self, i):
        return _max(i * self.bq - (self.window - 1), 0) // self.bk

    def k_last(self, i):
        return (i * self.bq + self.bq - 1) // self.bk

    def q_first(self, i):
        return (i * self.bk) // self.bq

    def q_last(self, i):
        return _min((i * self.bk + self.bk + self.window - 2) // self.bq,
                    self.nq - 1)


# ---------------------------------------------------------------------------
# Forward: grid (batch*heads, nq, nk) — K/V innermost so one K/V tile is
# resident at a time; output + lse written on the last K step from VMEM
# scratch accumulators.
# ---------------------------------------------------------------------------

def _stat_lanes(block_k: int) -> int:
    """Width of the forward's row-stat tiles: one vector register's lanes
    where the k block is made of whole ones, else the k block's own."""
    return block_k if block_k % _LANES else _LANES


def _at_width(x, n: int):
    """``x`` (rows, w), equal along its lanes, at width ``n``: whole
    tiles side by side or a leading slice where that serves, since
    either keeps the lane layout; a broadcast from one column is what
    the k loop must not pay (module doc)."""
    w = x.shape[1]
    if n % w == 0:
        return jnp.tile(x, (1, n // w)) if n > w else x
    if n < w:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, scale: float, nk: int,
                block_q: int, block_k: int, band: _Band):
    qi = pl.program_id(1)
    step = pl.program_id(2)  # of the nk this row visits
    window = band.window
    ki = step if window is None else band.k_first(qi) + step
    precision = _precision(q_ref.dtype)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A banded row starts at its first visible block, so the causal test
    # is the only one left: it also ends the row's shorter visits.
    visible = (qi * block_q + block_q > ki * block_k) if causal else True

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        sblk = _mm(q, kb.T, precision)  # (bq, bk) on the MXU
        if causal:
            sblk = _mask_block(sblk, qi, ki, block_q, block_k, window)
        # A row whose keys in this block are all masked (a band's first
        # block) leaves m at NEG_INF and p at 1; the first block with a
        # visible key (the diagonal at the latest) rescales that by
        # alpha = exp(NEG_INF - m) = 0.
        # The running maximum is a whole (block_q, lanes) tile, equal
        # along its lanes, read and written as it is; the running sum
        # keeps one partial sum a lane, added up once a row in _finalize.
        lanes = m_ref.shape[1]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
        p = jnp.exp(sblk - _at_width(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[...] * alpha
        for j in range(0, block_k, lanes):
            l_new += p[:, j:j + lanes]
        l_ref[...] = l_new
        acc_ref[...] = (acc_ref[...] * _at_width(alpha, acc_ref.shape[1])
                        + _mm(p, vb, precision))
        m_ref[...] = m_new

    @pl.when(step == nk - 1)
    def _finalize():
        l = jnp.sum(l_ref[...], axis=1, keepdims=True)
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(safe)  # (bq, 1) lane


def _kv_index(causal: bool, band: _Band, group: int):
    """K/V index map for grids where the k tile is the innermost axis.
    For causal attention the index is clamped to the last visible k block
    of the current q block: pallas skips the HBM->VMEM copy when the
    block index repeats between grid steps, so fully-masked steps (whose
    compute pl.when also skips) cost no memory traffic. A banded row
    counts from its first visible block. Query head b reads key-value
    head b // group."""
    def index(b, i, j):
        if group > 1:
            b = b // group
        if band.window is not None:
            j = jnp.minimum(band.k_first(i) + j, band.k_last(i))
        elif causal:
            j = jnp.minimum(j, band.k_last(i))
        return (b, j, 0)

    return index


def _q_index(causal: bool, band: _Band, group: int):
    """Q-side index map for the dK/dV grid (q tile innermost): clamped up
    to the first visible q block of the current k block (same
    repeated-index DMA-skip trick as _kv_index). The innermost axis walks
    the ``group`` query heads of key-value head b one after the other."""
    def index(b, i, j):
        if group > 1:
            b, j = b * group + j // band.n_q, j % band.n_q
        if band.window is not None:
            j = jnp.minimum(band.q_first(i) + j, band.q_last(i))
        elif causal:
            j = jnp.maximum(j, band.q_first(i))
        return (b, j, 0)

    return index


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "window"))
def _fwd_bhsd(q, k, v, causal, block_q, block_k, interpret, window=None):
    bh, s, d = q.shape
    band = _Band(window, s, block_q, block_k)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=d ** -0.5,
                               nk=band.n_k, block_q=block_q,
                               block_k=block_k, band=band)
    kv_idx = _kv_index(causal, band, bh // k.shape[0])
    stat = pltpu.VMEM((block_q, _stat_lanes(block_k)), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(bh, band.nq, band.n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # Row stats ride a trailing unit lane dim: Mosaic requires the
            # last two block dims be (8, 128)-divisible or array-equal.
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32), stat, stat,
        ],
        interpret=interpret,
        name="flash_fwd_bhsd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward. _bwd_bhsd takes the fused kernel (p and ds once a tile, then
# dv, dk and dq: grid over K tiles, whole-head accumulators in VMEM) where
# those accumulators fit, else the two kernels below, which each recompute
# p from (q, k, lse) and which a test pins as they are.
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dq_ref,
               acc_ref, *, causal: bool, scale: float, nk: int,
               block_q: int, block_k: int, band: _Band):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    window = band.window
    ki = step if window is None else band.k_first(qi) + step
    precision = _precision(q_ref.dtype)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    visible = (qi * block_q + block_q > ki * block_k) if causal else True

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        sblk = _mm(q, kb.T, precision)
        if causal:
            sblk = _mask_block(sblk, qi, ki, block_q, block_k, window)
        p = jnp.exp(sblk - lse_ref[0])  # lse block is (bq, 1)
        dp = _mm(do, vb.T, precision)
        ds = p * (dp - delta_ref[0])
        acc_ref[...] += _mm(ds, kb, precision) * scale

    @pl.when(step == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                scale: float, nq: int, block_q: int, block_k: int,
                band: _Band, group: int):
    ki = pl.program_id(1)
    step = pl.program_id(2)  # of group x nq: the heads, then their blocks
    window = band.window
    qi = step if group == 1 else step % nq
    if window is not None:
        qi = band.q_first(ki) + qi
    precision = _precision(q_ref.dtype)

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if window is not None:  # the row starts at its first visible block
        visible = qi <= band.q_last(ki)
    else:
        visible = (qi * block_q + block_q > ki * block_k) if causal else True

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        sblk = _mm(q, kb.T, precision)
        if causal:
            sblk = _mask_block(sblk, qi, ki, block_q, block_k, window)
        p = jnp.exp(sblk - lse_ref[0])  # lse block is (bq, 1)
        dv_acc[...] += _mm(p.T, do, precision)
        dp = _mm(do, vb.T, precision)
        ds = p * (dp - delta_ref[0])
        dk_acc[...] += _mm(ds.T, q, precision)  # q already carries `scale`

    @pl.when(step == group * nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      causal: bool, scale: float, block_q: int,
                      block_k: int, band: _Band):
    """One tile of the dK/dV grid, query heads of a group outside the k
    blocks: p and ds once, then dv, dk and this q block's rows of dq. The
    three accumulators hold a whole head, (s, d) in f32: dq's rows come
    round once a k block, dk's and dv's once a head of the group."""
    g, ki, step = (pl.program_id(axis) for axis in (1, 2, 3))
    window = band.window
    qi = step if window is None else band.q_first(ki) + step
    precision = _precision(q_ref.dtype)
    first = (ki == 0) & (step == 0)
    last = (ki == pl.num_programs(2) - 1) & (step == pl.num_programs(3) - 1)

    @pl.when(first)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first & (g == 0))
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if window is not None:  # the row starts at its first visible block
        visible = qi <= band.q_last(ki)
    else:
        visible = (qi * block_q + block_q > ki * block_k) if causal else True

    @pl.when(visible)
    def _compute():
        rows_q = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        rows_k = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        sblk = _mm(q, kb.T, precision)
        if causal:
            sblk = _mask_block(sblk, qi, ki, block_q, block_k, window)
        p = jnp.exp(sblk - lse_ref[0])  # lse block is (bq, 1)
        dv_acc[rows_k, :] += _mm(p.T, do, precision)
        dp = _mm(do, vb.T, precision)
        ds = p * (dp - delta_ref[0])
        dk_acc[rows_k, :] += _mm(ds.T, q, precision)  # q carries `scale`
        dq_acc[rows_q, :] += _mm(ds, kb, precision) * scale

    @pl.when(last)
    def _finalize_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(last & (g == pl.num_programs(1) - 1))
    def _finalize_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


#: Most bytes of whole-head accumulators and output blocks that the fused
#: backward may keep in VMEM: a quarter of a v5e core's 128 MiB. The
#: decoder cell's layers (s 8,192, d 128, bf16) hold 24 MiB; twice the
#: rows, or the same rows in f32, go to the two kernels.
_FUSED_BWD_VMEM_BYTES = 32 << 20
#: Mosaic's scoped default, which the fused call's limit adds for its
#: tiles and temporaries: what the two kernels live in.
_SCOPED_VMEM_BYTES = 16 << 20


def _fused_bwd_bytes(s: int, d: int, dtype) -> int:
    """VMEM the fused backward keeps for a head: dq, dk and dv as (s, d)
    f32 accumulators and as output blocks in ``dtype``, two buffers each;
    the lanes padded to whole registers."""
    lanes = -(-d // _LANES) * _LANES
    return 3 * s * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def fused_backward_fits(s: int, d: int, dtype) -> bool:
    """Whether the backward of (s, d) heads in ``dtype`` is the fused
    kernel: a head's accumulators and output blocks fit the constant."""
    return _fused_bwd_bytes(s, d, dtype) <= _FUSED_BWD_VMEM_BYTES


def _fused_bwd(q, k, v, lse, delta, do, causal, block_q, block_k, interpret,
               band: _Band):
    """dq, dk and dv from one kernel: grid (key-value head, head of its
    group, k block, q block), five products a tile."""
    bh, s, d = q.shape
    group = bh // k.shape[0]
    q_idx = _q_index(causal, band, 1)

    def q_side(b, g, i, j):
        return q_idx(b * group + g, i, j)

    q_spec = pl.BlockSpec((1, block_q, d), q_side)
    row_spec = pl.BlockSpec((1, block_q, 1), q_side)
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, g, i, j: (b, i, 0))
    dq_spec = pl.BlockSpec((1, s, d), lambda b, g, i, j: (b * group + g, 0, 0))
    dkv_spec = pl.BlockSpec((1, s, d), lambda b, g, i, j: (b, 0, 0))
    head = pltpu.VMEM((s, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_bwd_kernel, causal=causal, scale=d ** -0.5,
                          block_q=block_q, block_k=block_k, band=band),
        grid=(k.shape[0], group, band.nk, band.n_q),
        in_specs=[q_spec, k_spec, k_spec, row_spec, row_spec, q_spec],
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[head, head, head],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(_fused_bwd_bytes(s, d, q.dtype)
                              + _SCOPED_VMEM_BYTES)),
        interpret=interpret,
        name="fused_flash_dkv_bwd_bhsd",
    )(q, k, v, lse, delta, do)


def _two_kernel_bwd(q, k, v, lse, delta, do, causal, block_q, block_k,
                    interpret, band: _Band):
    """dq from one kernel (grid over q blocks, k innermost) and dk, dv
    from another (grid over k blocks, the group's heads and their q
    blocks innermost), seven products a tile: what runs where a head's
    accumulators do not fit, and the program ``tests/test_flash_window.py``
    pins."""
    bh, s, d = q.shape
    group = bh // k.shape[0]
    q_spec_i = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec_j = pl.BlockSpec((1, block_k, d), _kv_index(causal, band, group))
    row_spec_i = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=d ** -0.5,
                          nk=band.n_k, block_q=block_q, block_k=block_k,
                          band=band),
        grid=(bh, band.nq, band.n_k),
        in_specs=[q_spec_i, k_spec_j, k_spec_j, row_spec_i, row_spec_i,
                  q_spec_i],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq_bwd_bhsd",
    )(q, k, v, lse, delta, do)

    # dK/dV: grid over K tiles of the key-value heads, Q innermost (the
    # group's query heads one after the other).
    q_idx = _q_index(causal, band, group)
    q_spec_j = pl.BlockSpec((1, block_q, d), q_idx)
    k_spec_i = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    row_spec_j = pl.BlockSpec((1, block_q, 1), q_idx)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=d ** -0.5,
                          nq=band.n_q, block_q=block_q, block_k=block_k,
                          band=band, group=group),
        grid=(k.shape[0], band.nk, group * band.n_q),
        in_specs=[q_spec_j, k_spec_i, k_spec_i, row_spec_j, row_spec_j,
                  q_spec_j],
        out_specs=[k_spec_i, k_spec_i],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_dkv_bwd_bhsd",
    )(q, k, v, lse, delta, do)
    return dq, dk, dv


def _delta(do, out):
    """Δ_i = do_i · o_i, a cheap row reduction XLA fuses on its own; keeps
    the trailing unit lane dim the row-stat BlockSpecs need."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "window"))
def _bwd_bhsd(q, k, v, lse, do, out, causal, block_q, block_k, interpret,
              window=None):
    """The fused kernel where a head's accumulators fit in VMEM, by the
    shapes and the dtype alone; else the two kernels."""
    _, s, d = q.shape
    backward = (_fused_bwd if fused_backward_fits(s, d, q.dtype)
                else _two_kernel_bwd)
    return backward(
        q, k, v, lse, _delta(do, out), do, causal, block_q, block_k,
        interpret, _Band(window, s, block_q, block_k))


# ---------------------------------------------------------------------------
# custom_vjp core on (batch*heads, seq, head_dim) arrays
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, block_q, block_k, interpret, window,
           fwd_block_q):
    out, _ = _fwd_bhsd(q, k, v, causal, fwd_block_q, block_k, interpret,
                       window)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window,
               fwd_block_q):
    out, lse = _fwd_bhsd(q, k, v, causal, fwd_block_q, block_k, interpret,
                         window)
    # Residuals are O(s·d) + O(s): inputs, output, and the softmax row
    # statistics — never the (s × s) probabilities.
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, fwd_block_q,
               res, do):
    q, k, v, out, lse = res
    return _bwd_bhsd(q, k, v, lse, do, out, causal, block_q, block_k,
                     interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _auto_block(s: int, cap: int = 512) -> int:
    """Largest block <= cap that divides s and that Mosaic accepts as the
    second-to-last block dim: a multiple of the 128-wide MXU tile when
    there is one (512 measured fastest on v5e; see docs/benchmarks.md),
    else the whole sequence, else a multiple of the 8-row sublane tile.
    A sequence with none of these has no legal tiling: raise."""
    top = min(cap, s)
    for cand in range(top - top % 128, 0, -128):
        if s % cand == 0:
            return cand
    if s <= cap:
        return s
    for cand in range(top - top % 8, 0, -8):
        if s % cand == 0:
            return cand
    raise ValueError(
        f"flash_attention: seq len {s} has no divisor <= {cap} that is a "
        "multiple of 8, so it cannot be tiled for the TPU; pad the "
        "sequence to a multiple of 8 (128 for full MXU tiles)")


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None,
                    window: int | None = None):
    """Exact attention, flash-style, differentiable. Shapes
    (batch, seq, heads, head_dim) — the model zoo's ``attention_fn``
    contract; ``k`` and ``v`` may carry a divisor of ``q``'s heads
    (grouped key-value heads: query head h reads head h // group).
    ``window`` (with ``causal``) keeps the keys i - window < j <= i of
    query i. ``bias`` is not supported by the kernel (use the stock
    attention for biased variants). Block sizes default to
    :func:`_auto_block` (the forward's q block, without a window, to
    twice its cap); explicit block sizes must divide ``seq`` and hold for
    every kernel."""
    if bias is not None:
        raise NotImplementedError(
            "flash_attention does not take a bias; use "
            "models.transformer.dot_product_attention for biased attention")
    b, s, h, d = q.shape
    if k.shape != v.shape or h % k.shape[2]:
        raise ValueError(
            f"flash_attention: {h} query heads over key/value shapes "
            f"{k.shape} / {v.shape}: want equal shapes whose head count "
            "divides the query's")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_attention: window goes with "
                             "causal=True and is at least 1")
        window = None if window >= s else int(window)
    # The forward alone takes a q block of up to 1,024 rows where none is
    # asked for and no window cuts the row short: its time a tile falls
    # with the rows a k tile serves (PERF.md, PR 28), and a band two
    # blocks wide would only visit more of what it masks.
    if block_q is None:
        block_q = _auto_block(s)
        fwd_block_q = block_q if window is not None else _auto_block(s, 1024)
    else:
        block_q = fwd_block_q = min(block_q, s)
    block_k = _auto_block(s) if block_k is None else min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq len {s} must be divisible by block sizes "
            f"({block_q}, {block_k})")
    interpret = resolve_interpret(interpret, "flash_attention")
    if not interpret and any(blk % 8 and blk != s
                             for blk in (block_q, block_k)):
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must be multiples of 8 "
            f"or the whole sequence ({s}) to compile for the TPU")

    def to_bhsd(t):
        return jnp.transpose(t, (0, 2, 1, 3)).reshape(-1, s, d)

    out = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal,
                 block_q, block_k, interpret, window, fwd_block_q)
    return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))


def flash_attention_causal(q, k, v, bias=None, **kw):
    """Causal variant matching the ``attention_fn`` signature."""
    return flash_attention(q, k, v, bias, causal=True, **kw)
