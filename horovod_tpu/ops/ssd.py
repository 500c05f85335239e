"""A selective state-space scan in its chunked form, with a hand-written
backward pass.

The recurrence, a head at a time (state ``h`` of (state size, head size),
a scalar decay a head, ``B`` and ``C`` shared by the heads of a group):

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t        y_t = C_t . h_t + D x_t

is computed over chunks of ``chunk`` positions (the "state-space duality"
form): inside a chunk by matrix products against the lower-triangular
decay ``exp(cum_i - cum_j)``, ``cum`` the running sum of ``dt A``; then
every chunk's end state, the carry of the state from chunk to chunk (a
``lax.scan`` over the chunks, the only sequential part), and the part of
each output that the state entering its chunk gives. Decay sums, decays
and states are float32; the products are fed the inputs' dtype (bfloat16
in training) and accumulate in float32. Every exponent is of a sum over
later-minus-earlier positions and so never positive.

The backward pass is written out (``jax.custom_vjp``): autodiff of the
forward would transpose its bfloat16 products into mixed float32 x
bfloat16 ones and keep the (heads, chunk, chunk) decay and score tiles of
every chunk. Here the residuals are the inputs and the states entering
the chunks (heads x state x head size a chunk); the tiles are computed
again, chunk-parallel, and the carry runs once more in reverse.

Plain ``jax.numpy``: no pallas kernel. Everything traced here goes under
the scope ``ssm_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.phases import scope

_F32 = jnp.float32


def _dot(spec, *operands):
    """``einsum`` with float32 accumulation; float32 operands multiply at
    full precision (the TPU's default would round them to bfloat16)."""
    exact = operands[0].dtype == _F32
    return jnp.einsum(spec, *operands, preferred_element_type=_F32,
                      precision=lax.Precision.HIGHEST if exact else None)


def _chunked(x, dt, b, c, chunk):
    """The arguments by chunk and by group: x (B, c, Q, G, K, P), dt
    (B, c, Q, G, K), b and c (B, c, Q, G, N); K heads a group."""
    bsz, t, heads, p = x.shape
    groups = b.shape[2]
    if t % chunk:
        raise ValueError(f"ssd_scan: {t} positions are no whole number of "
                         f"chunks of {chunk}")
    if heads % groups:
        raise ValueError(f"ssd_scan: {heads} heads over {groups} groups")
    n = t // chunk
    return (x.reshape(bsz, n, chunk, groups, heads // groups, p),
            dt.astype(_F32).reshape(bsz, n, chunk, groups, heads // groups),
            b.reshape(bsz, n, chunk, groups, -1),
            c.reshape(bsz, n, chunk, groups, -1))


def _tiles(dt, a, b, c):
    """What both passes need of one chunk's decays: ``cum`` (B, c, Q, G,
    K), the running sum of dt A; ``kern`` (B, c, G, K, Q, Q), the scores
    C_i . B_j times the decay from j to i, zero above the diagonal; and
    ``decay``, that decay alone."""
    cum = jnp.cumsum(dt * a, axis=2)
    by_head = jnp.moveaxis(cum, 2, -1)                  # (B, c, G, K, Q)
    q = by_head.shape[-1]
    later = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        later, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = _dot("bcign,bcjgn->bcgij", c, b)
    return cum, scores[:, :, :, None] * decay, decay


def _carry(decay, states, reverse=False):
    """The state entering each chunk, (B, c, G, K, N, P) float32, from the
    chunks' own end states and their whole-chunk decays (B, c, G, K):
    zero before the first chunk. ``reverse`` runs from the last chunk
    back, which is the same recurrence for the states' cotangents."""
    def step(h, inputs):
        g, s = inputs
        return g[..., None, None] * h + s, h

    _, entering = lax.scan(step, jnp.zeros_like(states[:, 0]),
                           (jnp.moveaxis(decay, 1, 0),
                            jnp.moveaxis(states, 1, 0)), reverse=reverse)
    return jnp.moveaxis(entering, 0, 1)


def _forward(x, dt, a, b, c, d):
    """y (float32) and the states entering the chunks, all by chunk."""
    cum, kern, _ = _tiles(dt, a, b, c)
    last = cum[:, :, -1]                                # (B, c, G, K)
    by_key = jnp.moveaxis(dt, 2, -1)[..., None, :]      # dt_j along keys
    within = _dot("bcgkij,bcjgkp->bcigkp", (kern * by_key).astype(x.dtype),
                  x)
    to_end = jnp.exp(last[:, :, None] - cum) * dt       # (B, c, Q, G, K)
    states = _dot("bcjgn,bcjgkp->bcgknp", b,
                  (x * to_end[..., None]).astype(x.dtype))
    entering = _carry(jnp.exp(last), states)
    carried = _dot("bcign,bcgknp->bcigkp", c, entering.astype(x.dtype))
    y = within + jnp.exp(cum)[..., None] * carried + d[:, :, None] * x
    return y, entering


def _run(x, dt, a, b, c, d, chunk):
    """(y in x's shape and dtype, the states entering the chunks)."""
    with scope("ssm_scan"):
        xc, dtc, bc, cc = _chunked(x, dt, b, c, chunk)
        groups = b.shape[2]
        y, entering = _forward(xc, dtc, a.reshape(groups, -1), bc, cc,
                               d.reshape(groups, -1))
        return y.reshape(x.shape).astype(x.dtype), entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, a, b, c, d, chunk):
    return _run(x, dt, a, b, c, d, chunk)[0]


def _ssd_fwd(x, dt, a, b, c, d, chunk):
    y, entering = _run(x, dt, a, b, c, d, chunk)
    return y, (x, dt, a, b, c, d, entering)


def _ssd_bwd(chunk, residuals, dy):
    x_in, dt_in, a_in, b_in, c_in, d_in, entering = residuals
    with scope("ssm_scan"):
        x, dt, b, c = _chunked(x_in, dt_in, b_in, c_in, chunk)
        groups = b.shape[3]
        a, d = a_in.reshape(groups, -1), d_in.reshape(groups, -1)
        dtype = x.dtype
        dy = dy.reshape(x.shape)
        dyf, xf = dy.astype(_F32), x.astype(_F32)
        cum, kern, decay = _tiles(dt, a, b, c)
        last = cum[:, :, -1]
        by_key = jnp.moveaxis(dt, 2, -1)[..., None, :]
        # (.., Q) -> (Q, ..)
        to_key = lambda t: jnp.moveaxis(t, -1, 2)  # noqa: E731

        # D x
        dd = (dyf * xf).sum((0, 1, 2, 5))
        dx = d[:, :, None] * dyf

        # the part of y that the entering state gives
        out_decay = jnp.exp(cum)
        entering_low = entering.astype(dtype)
        dy_decayed = (dyf * out_decay[..., None]).astype(dtype)
        dc = _dot("bcigkp,bcgknp->bcign", dy_decayed, entering_low)
        d_entering = _dot("bcign,bcigkp->bcgknp", c, dy_decayed)
        carried = _dot("bcign,bcgknp->bcigkp", c, entering_low)
        dcum = (dyf * carried).sum(-1) * out_decay

        # the carry, in reverse: lam_c = direct_c + g_c lam_{c+1}; what
        # reaches chunk c's own end state is lam_{c+1}
        chunk_decay = jnp.exp(last)
        d_states = _carry(chunk_decay, d_entering, reverse=True)
        dlast = (d_states * entering).sum((-1, -2)) * chunk_decay

        # the chunks' end states: sum_j to_end_j B_j (x) x_j
        end_decay = jnp.exp(last[:, :, None] - cum)
        to_end = end_decay * dt
        d_states_low = d_states.astype(dtype)
        through_b = _dot("bcjgn,bcgknp->bcjgkp", b, d_states_low)
        dx += to_end[..., None] * through_b
        d_to_end = (through_b * xf).sum(-1)
        db = _dot("bcjgkp,bcgknp->bcjgn",
                  (xf * to_end[..., None]).astype(dtype), d_states_low)
        ddt = d_to_end * end_decay
        moved = d_to_end * to_end
        dlast += moved.sum(2)
        dcum -= moved

        # inside the chunks: m_ij = scores_ij decay_ij dt_j
        m = kern * by_key
        dm = _dot("bcigkp,bcjgkp->bcgkij", dy, x)
        dx += _dot("bcgkij,bcigkp->bcjgkp", m.astype(dtype), dy)
        through_dt = dm * kern
        ddt += to_key(through_dt.sum(-2))
        moved = through_dt * by_key
        dcum += to_key(moved.sum(-1) - moved.sum(-2))
        d_scores = (dm * decay * by_key).sum(3).astype(dtype)
        dc += _dot("bcgij,bcjgn->bcign", d_scores, b)
        db += _dot("bcgij,bcign->bcjgn", d_scores, c)

        # cum is the running sum of dt A, and last its final entry
        dcum = dcum.at[:, :, -1].add(dlast)
        da_t = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), 2), 2)
        ddt += da_t * a
        da = (da_t * dt).sum((0, 1, 2))

        return tuple(g.reshape(v.shape).astype(v.dtype) for g, v in zip(
            (dx, ddt, da, db, dc, dd), residuals))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, d, *, chunk: int):
    """The scan of the module's docstring over ``t`` positions.

    Args:
      x: (batch, t, heads, head size), the compute dtype.
      dt: (batch, t, heads) step sizes, positive (after the softplus);
        computed in float32.
      a: (heads,) float32, negative: the decay rate of each head.
      b, c: (batch, t, groups, state size): input and output maps, one a
        group; head h belongs to group h // (heads / groups).
      d: (heads,) float32, the skip from x to y.
      chunk: positions a chunk; ``t`` must be a whole number of them.

    Returns y (batch, t, heads, head size) in x's dtype. Differentiable
    in every array argument.
    """
    return _ssd(x, dt, a.astype(_F32), b, c, d.astype(_F32), chunk)
