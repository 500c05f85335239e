"""A selective scan whose decay differs by channel and by state (Mamba-1),
in a chunked form with a carried state and a hand-written backward pass.

The recurrence, for ``C`` channels with ``N`` states each (``B_t`` and
``C_t`` shared by the channels):

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t      h_{-1} = 0
    y_t = h_t . C_t + D * x_t

The decay ``exp(dt_t[c] A[c, n])`` is one number a channel and a state, so
no matrix product over channels exists (``ops/ssd.py`` needs one scalar a
head): the work is ``T C N`` state updates on the vector units, and the
sequence is the only axis to split. ``T`` positions are ``T / chunk``
chunks; every pass walks the ``chunk`` positions of a chunk one after the
other, *all chunks at once* (each step is elementwise work on
(chunks, N, C) states), and a short scan carries the state from chunk to
chunk:

* forward: every chunk from a zero state (its own part of ``y`` and its
  end state); the carry, which gives the state entering each chunk; the
  part of ``y`` that the entering state adds,
  ``sum_n C_t[n] exp(A[c, n] S_t[c]) h_in[c, n]`` with ``S_t`` the sum of
  ``dt`` from the chunk's start, elementwise over positions.
* backward (``jax.custom_vjp``): the residuals are the inputs and the
  states entering the chunks (T / chunk x C x N float32), nothing by
  token. ``GROUP_TOKENS`` positions at a time, from the last to the
  first, the states are computed again from the entering ones and kept
  for that group alone; the states' cotangent is carried back from chunk
  to chunk and walked through the group's chunks in reverse.

Sums of ``dt``, decays, states and their cotangents are float32; the
inputs may be bfloat16 and are widened on the way in. Every exponent is
``A`` (negative) times a sum of ``dt`` (positive).

Plain ``jax.numpy``: no pallas kernel. Everything traced here goes under
the scope ``sel_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.phases import scope

_F32 = jnp.float32
#: Positions whose states the backward pass holds at once (N x C float32
#: each): 1,024 are 336 MB at 5,120 channels of 16 states.
GROUP_TOKENS = 1024
#: Positions a traced loop body walks (``lax.scan``'s ``unroll``).
UNROLL = 4


def _by_position(t):
    """(batch, chunks, chunk, ...) -> (chunk, batch, chunks, ...) float32:
    a pass scans the leading axis."""
    return jnp.moveaxis(t.astype(_F32), 2, 0)


def _from_positions(t):
    return jnp.moveaxis(t, 0, 2)


def _outer(states, channels):
    """(.., N) and (.., C) -> (.., N, C)."""
    return states[..., :, None] * channels[..., None, :]


def _carry(decay, ends, start, reverse=False):
    """The state entering each chunk, (batch, chunks, N, C), from the
    chunks' own end states and whole-chunk decays: ``start`` before the
    first chunk. ``reverse`` runs from the last chunk back, which is the
    same recurrence for the states' cotangents. Also the state after the
    last chunk walked."""
    def step(h, inputs):
        g, s = inputs
        return g * h + s, h

    after, entering = lax.scan(
        step, start, (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(ends, 1, 0)),
        reverse=reverse)
    return jnp.moveaxis(entering, 0, 1), after


def _chunked(x, dt, b, c, chunk):
    bsz, t, channels = x.shape
    if t % chunk:
        raise ValueError(f"selective_scan: {t} positions are no whole "
                         f"number of chunks of {chunk}")
    n = t // chunk
    return tuple(v.reshape(bsz, n, chunk, v.shape[-1])
                 for v in (x, dt, b, c))


def _run(x, dt, a, b, c, d, chunk):
    """(y in x's shape and dtype, the states entering the chunks)."""
    with scope("sel_scan"):
        xc, dtc, bc, cc = _chunked(x, dt, b, c, chunk)
        a = a.astype(_F32).T                               # (N, C)
        bsz, n_chunks = xc.shape[:2]
        zero = jnp.zeros((bsz, n_chunks, *a.shape), _F32)

        def position(carry, at):
            h, s = carry
            x_t, dt_t, b_t, c_t = at
            s = s + dt_t
            h = (jnp.exp(dt_t[..., None, :] * a) * h
                 + _outer(b_t, dt_t * x_t))
            return (h, s), ((h * c_t[..., None]).sum(-2), s)

        (ends, total), (y, sums) = lax.scan(
            position, (zero, zero[..., 0, :]),
            tuple(map(_by_position, (xc, dtc, bc, cc))), unroll=UNROLL)
        entering, _ = _carry(jnp.exp(total[..., None, :] * a), ends,
                             zero[:, 0])
        # what the entering state adds, a state at a time: elementwise
        # over (positions, C), so no (positions, N, C) array is made
        y, sums = _from_positions(y), _from_positions(sums)
        c32 = cc.astype(_F32)
        for n in range(a.shape[0]):
            y = y + (c32[..., n, None] * jnp.exp(sums * a[n])
                     * entering[:, :, None, n, :])
        y = y + d.astype(_F32) * xc.astype(_F32)
        return y.reshape(x.shape).astype(x.dtype), entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    return _run(x, dt, a, b, c, d, chunk)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk):
    y, entering = _run(x, dt, a, b, c, d, chunk)
    return y, (x, dt, a, b, c, d, entering)


def _group_size(n_chunks: int, chunk: int) -> int:
    """Chunks of one group of the backward pass: the most that divide
    ``n_chunks`` and hold at most ``GROUP_TOKENS`` positions."""
    most = max(1, GROUP_TOKENS // chunk)
    return max(g for g in range(1, min(most, n_chunks) + 1)
               if n_chunks % g == 0)


def _scan_bwd(chunk, residuals, dy):
    x_in, dt_in, a_in, b_in, c_in, d_in, entering = residuals
    with scope("sel_scan"):
        x, dt, b, c = _chunked(x_in, dt_in, b_in, c_in, chunk)
        dy = dy.reshape(x.shape)
        a = a_in.astype(_F32).T                            # (N, C)
        d = d_in.astype(_F32)
        bsz, n_chunks = x.shape[:2]
        size = _group_size(n_chunks, chunk)

        def groups(t):  # (batch, chunks, ...) -> (groups, batch, size, ...)
            return jnp.moveaxis(
                t.reshape(bsz, n_chunks // size, size, *t.shape[2:]), 1, 0)

        def group(carry, inputs):
            """One group of chunks, every chunk at once: ``lam`` is the
            cotangent of the state after the group's last chunk."""
            lam, da = carry
            x_g, dt_g, b_g, c_g, dy_g, h_in = inputs
            by_position = tuple(map(_by_position,
                                    (x_g, dt_g, b_g, c_g, dy_g)))

            # the states again, kept for this group: before each position
            def forward(carry, at):
                h, s, direct = carry
                x_t, dt_t, b_t, c_t, dy_t = at
                s = s + dt_t
                h_next = (jnp.exp(dt_t[..., None, :] * a) * h
                          + _outer(b_t, dt_t * x_t))
                dc_t = (h_next * dy_t[..., None, :]).sum(-1)
                # what dy_t gives the state entering the chunk
                direct = direct + jnp.exp(s[..., None, :] * a) * _outer(
                    c_t, dy_t)
                return (h_next, s, direct), (h, dc_t)

            zero = jnp.zeros_like(h_in)
            (_, total, direct), (before, dc) = lax.scan(
                forward, (h_in, zero[..., 0, :], zero), by_position,
                unroll=UNROLL)
            # the cotangent of the state after each chunk of the group
            after, lam = _carry(jnp.exp(total[..., None, :] * a), direct,
                                lam, reverse=True)

            def backward(carry, at):
                future, da = carry    # decay_{t+1} * lambda_{t+1}
                (x_t, dt_t, b_t, c_t, dy_t), h = at
                lam_t = _outer(c_t, dy_t) + future
                decay = jnp.exp(dt_t[..., None, :] * a)
                through_b = (lam_t * b_t[..., None]).sum(-2)
                db_t = (lam_t * (dt_t * x_t)[..., None, :]).sum(-1)
                through_decay = lam_t * h * decay
                ddt_t = x_t * through_b + (through_decay * a).sum(-2)
                da = da + through_decay * dt_t[..., None, :]
                return (decay * lam_t, da), (dt_t * through_b, ddt_t, db_t)

            (_, da_g), (dx, ddt, db) = lax.scan(
                backward, (after, zero), (by_position, before),
                reverse=True, unroll=UNROLL)
            grads = tuple(map(_from_positions, (dx, ddt, db, dc)))
            return (lam, da + da_g.sum((0, 1))), grads

        start = (jnp.zeros((bsz, *a.shape), _F32), jnp.zeros_like(a))
        (_, da), grads = lax.scan(
            group, start, tuple(map(groups, (x, dt, b, c, dy, entering))),
            reverse=True)
        dx, ddt, db, dc = (
            jnp.moveaxis(g, 0, 1).reshape(bsz, n_chunks, *g.shape[3:])
            for g in grads)
        dy32, x32 = dy.astype(_F32), x.astype(_F32)
        dx = dx + d * dy32
        dd = (dy32 * x32).sum((0, 1, 2))
        return tuple(g.reshape(v.shape).astype(v.dtype) for g, v in zip(
            (dx, ddt, da.T, db, dc, dd), residuals))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a, b, c, d, *, chunk: int):
    """The scan of the module's docstring over ``t`` positions.

    Args:
      x: (batch, t, channels), the compute dtype.
      dt: (batch, t, channels) step sizes, positive (after the softplus).
      a: (channels, states), negative: the decay rate of each state.
      b, c: (batch, t, states): the input and output maps of a position,
        shared by the channels.
      d: (channels,), the skip from x to y.
      chunk: positions a chunk; ``t`` must be a whole number of them.

    Returns y (batch, t, channels) in x's dtype. Differentiable in every
    array argument; each gradient comes in its argument's dtype.
    """
    return _scan(x, dt, a, b, c, d, chunk)
