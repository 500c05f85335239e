"""Mixture-of-experts with expert parallelism over an ``ep`` mesh axis.

Switch-Transformer-style top-1 token-choice routing with capacity: tokens
are dispatched to experts with one ``all_to_all`` (each chip owns
``n_experts / ep`` experts' FFN weights), expert FFNs run as dense batched
matmuls on the MXU, and a mirror ``all_to_all`` brings results home.
Overflow tokens beyond expert capacity pass through the residual (their
combine weight is zero) — standard Switch semantics.

No reference equivalent (data-parallel only, SURVEY.md §2.3); this
completes the ep axis of the hybrid mesh. All dispatch/combine logic is
one-hot einsum — no gather/scatter with dynamic shapes, so XLA tiles
everything statically.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.phases import scope


def _top1_dispatch(x, router_logits, n_experts: int, capacity: int):
    """Build dispatch/combine tensors for top-1 routing.

    Returns (dispatch (t,E,C) bool-ish float, combine (t,E,C) float,
    aux_loss scalar).
    """
    t = x.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # (t,)
    gate = jnp.max(probs, axis=-1)  # (t,)
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)
    # Position of each token within its chosen expert's queue.
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (t, E)
    keep = (pos < capacity) * onehot
    pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos_clamped, capacity,
                                dtype=jnp.float32)  # (t, E, C)
    dispatch = keep[..., None] * pos_onehot  # (t, E, C)
    combine = dispatch * gate[:, None, None]
    # Switch load-balancing loss: E * sum_e fraction_tokens_e * mean_prob_e.
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux, t


def moe_layer(
    x,
    router_w,
    expert_wi,
    expert_wo,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    act: Callable = jax.nn.gelu,
):
    """Apply an expert-parallel MoE FFN block inside SPMD code.

    Args:
      x: (tokens_local, hidden) this chip's tokens.
      router_w: (hidden, n_experts_global) router weights (replicated).
      expert_wi: (experts_local, hidden, ff) this chip's experts' input
        projections — experts are sharded over ``axis_name``.
      expert_wo: (experts_local, ff, hidden).
      capacity_factor: per-expert queue size multiplier.

    Returns:
      (tokens_local, hidden) output, plus the scalar load-balancing aux
      loss (already pmean'd over the ep axis).
    """
    ep = lax.psum(1, axis_name)
    e_local = expert_wi.shape[0]
    n_experts = e_local * ep
    t, hidden = x.shape
    capacity = max(1, int(t * capacity_factor / n_experts))

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    dispatch, combine, aux, _ = _top1_dispatch(x, logits, n_experts,
                                               capacity)

    # (t, E, C) x (t, h) -> (E, C, h): token payloads in expert queues.
    expert_in = jnp.einsum("tec,th->ech", dispatch, x.astype(jnp.float32))
    # Route queues to their owning chips: (E, C, h) = (ep, e_local, C, h);
    # all_to_all swaps the ep dim for a source-chip dim.
    expert_in = expert_in.reshape(ep, e_local, capacity, hidden)
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                               concat_axis=0, tiled=True)
    # Post-all_to_all layout is again (ep, e_local, C, h), but dim 0 now
    # indexes SOURCE chips: row s holds chip s's queue for this chip's
    # local experts. Merge source × capacity into one batch per expert.
    expert_in = jnp.transpose(expert_in, (1, 0, 2, 3)).reshape(
        e_local, ep * capacity, hidden)

    # Dense batched expert FFNs on the MXU.
    h1 = act(jnp.einsum("ebh,ehf->ebf", expert_in,
                        expert_wi.astype(jnp.float32)))
    out = jnp.einsum("ebf,efh->ebh", h1, expert_wo.astype(jnp.float32))

    # Reverse the routing.
    out = out.reshape(e_local, ep, capacity, hidden)
    out = jnp.transpose(out, (1, 0, 2, 3))  # (ep, e_local, C, h)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=True)
    out = out.reshape(n_experts, capacity, hidden)

    y = jnp.einsum("tec,ech->th", combine, out)
    return y.astype(x.dtype), lax.pmean(aux, axis_name)


# ---------------------------------------------------------------------------
# One chip's share of a top-k expert layer: no capacity, nothing dropped
# ---------------------------------------------------------------------------

def _hidden(xb, w_gate, w_up):
    """One block of rows through one expert's input products, f32
    accumulation: ``(h, saved)``, h the hidden activation in float32.
    With a gate matrix the expert is SwiGLU, ``silu(x Wg) * (x Wu)``;
    without one (``w_gate`` None) it is ``relu(x Wu)^2``."""
    if w_gate is None:
        r = jax.nn.relu(
            jnp.dot(xb, w_up, preferred_element_type=jnp.float32))
        return r * r, (r,)
    a = jnp.dot(xb, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(xb, w_up, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(a)
    return a * s * u, (a, s, u)


def _hidden_grads(dh, xb, w_gate, w_up, saved):
    """``(dxb, dWg, dWu)`` of one block from ``dh`` (float32); ``dWg`` is
    None for an expert without a gate."""
    dtype = xb.dtype
    if w_gate is None:
        (r,) = saved
        du = (dh * 2.0 * r).astype(dtype)
        return (jnp.dot(du, w_up.T, preferred_element_type=jnp.float32),
                None,
                jnp.dot(xb.T, du, preferred_element_type=jnp.float32))
    a, s, u = saved
    da = (dh * u * (s * (1.0 + a * (1.0 - s)))).astype(dtype)
    du = (dh * (a * s)).astype(dtype)
    dxb = (jnp.dot(da, w_gate.T, preferred_element_type=jnp.float32)
           + jnp.dot(du, w_up.T, preferred_element_type=jnp.float32))
    return (dxb, jnp.dot(xb.T, da, preferred_element_type=jnp.float32),
            jnp.dot(xb.T, du, preferred_element_type=jnp.float32))


def _block_operands(b, x, w_gate, w_up, w_down, rows, block_expert):
    """Block ``b``'s token rows, its expert and the expert's matrices."""
    with scope("moe_dispatch"):
        r = rows[b]
        xb = x[r]
    g = block_expert[b]
    return (r, xb, g, None if w_gate is None else w_gate[g], w_up[g],
            w_down[g])


@jax.custom_vjp
def _expert_blocks(x, w_gate, w_up, w_down, slot_weight, rows, block_expert,
                   n_blocks):
    """sum over this chip's kept assignments of weight * E_e(x[token]),
    (tokens, hidden) f32. The assignments lie sorted by expert in blocks
    of ``rows.shape[1]`` rows, each block one expert's (``block_expert``);
    only the first ``n_blocks`` hold any, and only those are computed: the
    work follows what the router sent, not the most it could send.
    ``w_gate`` None: the experts have no gate (``_hidden``)."""
    def body(b, y):
        r, xb, _, wg, wu, wd = _block_operands(b, x, w_gate, w_up, w_down,
                                               rows, block_expert)
        with scope("moe_experts"):
            h, _ = _hidden(xb, wg, wu)
            ob = jnp.dot(h.astype(x.dtype), wd,
                         preferred_element_type=jnp.float32)
        with scope("moe_combine"):
            return y.at[r].add(slot_weight[b][:, None] * ob)

    return lax.fori_loop(0, n_blocks, body,
                         jnp.zeros(x.shape, jnp.float32))


def _expert_blocks_fwd(x, w_gate, w_up, w_down, slot_weight, rows,
                       block_expert, n_blocks):
    y = _expert_blocks(x, w_gate, w_up, w_down, slot_weight, rows,
                       block_expert, n_blocks)
    # No residual the size of the rows: the backward pass gathers and
    # recomputes each block from x, as the forward pass did.
    return y, (x, w_gate, w_up, w_down, slot_weight, rows, block_expert,
               n_blocks)


def _expert_blocks_bwd(res, dy):
    x, w_gate, w_up, w_down, slot_weight, rows, block_expert, n_blocks = res

    def body(b, carry):
        dx, dwg, dwu, dwd, dweight = carry
        r, xb, g, wg, wu, wd = _block_operands(b, x, w_gate, w_up, w_down,
                                               rows, block_expert)
        with scope("moe_combine"):
            dyb = dy[r]
            dob = (slot_weight[b][:, None] * dyb).astype(x.dtype)
        with scope("moe_experts"):
            h, saved = _hidden(xb, wg, wu)
            # d(weight) = dy . E(x) = (dy Wd^T) . h, with no product more
            dh_unweighted = jnp.dot(dyb.astype(x.dtype), wd.T,
                                    preferred_element_type=jnp.float32)
            dweight = dweight.at[b].set((dh_unweighted * h).sum(-1))
            dxb, dwg_b, dwu_b = _hidden_grads(
                slot_weight[b][:, None] * dh_unweighted, xb, wg, wu, saved)
            dwd = dwd.at[g].add(jnp.dot(
                h.astype(x.dtype).T, dob,
                preferred_element_type=jnp.float32))
            if dwg is not None:
                dwg = dwg.at[g].add(dwg_b)
            dwu = dwu.at[g].add(dwu_b)
        with scope("moe_dispatch"):
            dx = dx.at[r].add(dxb)
        return dx, dwg, dwu, dwd, dweight

    def zeros(a):
        return None if a is None else jnp.zeros(a.shape, jnp.float32)

    dx, dwg, dwu, dwd, dweight = lax.fori_loop(
        0, n_blocks, body, (zeros(x), zeros(w_gate), zeros(w_up),
                            zeros(w_down), zeros(slot_weight)))
    return (dx.astype(x.dtype),
            None if dwg is None else dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype), dwd.astype(w_down.dtype),
            dweight.astype(slot_weight.dtype), None, None, None)


_expert_blocks.defvjp(_expert_blocks_fwd, _expert_blocks_bwd)


def _pick(top_e, values, n_experts: int, over: int):
    """The sum over axis ``over`` of ``values`` (broadcast to (tokens, k,
    n_experts)) where expert ``top_e[t, j]`` is the column's own, 0.0
    elsewhere: the compare is fused into the reduction, nothing of
    tokens x k x n_experts is stored."""
    hit = top_e[:, :, None] == lax.broadcasted_iota(
        jnp.int32, (1, 1, n_experts), 2)
    return jnp.where(hit, values, 0.0).sum(over)


@jax.custom_vjp
def _chosen(scores, top_e):
    """``scores[t, top_e[t, j]]``, (tokens, k), with no gather, and no
    scatter backward: a sum over the experts in which every term but one
    is an exact 0.0, so ``jnp.take_along_axis``'s values bit for bit; its
    transpose is the same compare summed over k (a token's experts are
    distinct, so at most one term there too)."""
    return _pick(top_e, scores[:, None, :], scores.shape[1], 2)


def _chosen_fwd(scores, top_e):
    return _chosen(scores, top_e), (top_e, scores.shape[1])


def _chosen_bwd(res, d):
    top_e, n_experts = res
    return _pick(top_e, d[:, :, None], n_experts, 1), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(scores, k: int):
    """``lax.top_k`` whose values transpose as :func:`_chosen`'s do, by a
    compare and a sum over k, where autodiff's rule scatters tokens x k
    updates into (tokens, n_experts)."""
    return tuple(lax.top_k(scores, k))


def _top_k_fwd(scores, k):
    values, experts = _top_k(scores, k)
    return (values, experts), (experts, scores.shape[1])


def _top_k_bwd(k, res, cotangents):
    experts, n_experts = res
    return (_pick(experts, cotangents[0][:, :, None], n_experts, 1),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def _route(x, router_w, top_k: int, scaling: float):
    """Each token's top-k experts of ALL the router scores, (tokens, k)
    int32, and their weights: the float32 softmax probabilities of the
    chosen, renormalised to ``scaling``. ``top_k``'s values are the
    weights as they come, so there is no gather, and no scatter backward
    (:func:`_top_k`)."""
    # On a widened bfloat16 x the v5e's compiler leaves out the passes
    # over x's zero low parts by itself (PERF.md, PR 35).
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top_p, top_e = _top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return top_e, scaling * top_p / top_p.sum(-1, keepdims=True)


def sigmoid_route(bias, eps: float = 0.0):
    """A router for :func:`expert_share_layer`'s ``route``: float32
    sigmoid scores over ALL experts; a token's top-k are chosen by score
    plus ``bias`` (n_experts,), a correction that only moves the choice
    (no gradient reaches it); the weights are the scores of the chosen,
    without the bias, times ``scaling`` over their sum plus ``eps``. They
    are picked by compares (:func:`_chosen`): no gather of tokens x k
    scores, no scatter of as many backward."""
    def route(x, router_w, top_k: int, scaling: float):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, top_e = lax.top_k(
            scores + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        top_s = _chosen(scores, top_e)
        # the product, the sum, then the quotient: with eps 0 the ops of
        # the one-line form, in its order, and no add of 0.0
        weights = scaling * top_s
        total = top_s.sum(-1, keepdims=True)
        if eps:
            total = total + eps
        return top_e, weights / total

    return route


@jax.custom_vjp
def _held_first(mine, w):
    """Each held expert's tokens first, in token order, by one sort along
    the tokens of (held, tokens) ``mine`` and weights ``w``: the rows'
    tokens (0 past the expert's own) and their weights."""
    return _held_first_fwd(mine, w)[0]


def _held_first_fwd(mine, w):
    n = mine.shape[1]
    token = lax.broadcasted_iota(jnp.int32, mine.shape, 1)
    key, sorted_w = lax.sort((jnp.where(mine, token, n + token), w),
                             dimension=1, is_stable=False, num_keys=1)
    return (jnp.where(key < n, key, 0), sorted_w), key % n


def _held_first_bwd(order, cotangents):
    # A row of ``order`` is a permutation of the tokens: sorting by it puts
    # d(w) back in token order, where the sort's transpose would scatter.
    return None, lax.sort((order, cotangents[1]), dimension=1,
                          is_stable=False, num_keys=1)[1]


_held_first.defvjp(_held_first_fwd, _held_first_bwd)


def expert_share_layer(x, router_w, w_gate, w_up, w_down, *,
                       first_expert: int, top_k: int, scaling: float = 1.0,
                       block_rows: int = 256, route=None, router_x=None):
    """One chip's share of a top-k routed expert layer under expert
    parallelism: it is told which experts it holds, routes over all of
    them, keeps every assignment to an expert it holds (no capacity, no
    drop) and returns the part of the layer's result its experts give.

    Args:
      x: (tokens, hidden) this chip's tokens, in the compute dtype.
      router_w: (hidden, n_experts) router over ALL experts (float32).
      w_gate, w_up: (held, hidden, ff); w_down: (held, ff, hidden): the
        experts ``first_expert .. first_expert + held - 1``, in the
        compute dtype: SwiGLU, or with ``w_gate`` None the two-matrix
        ``Wd relu(Wu x)^2``.
      top_k: experts a token is sent to.
      route: ``(x, router_w, top_k, scaling) -> (experts (tokens, k)
        int32, weights (tokens, k) float32)``. None is the softmax
        router: the top-k softmax probabilities renormalised to
        ``scaling``; :func:`sigmoid_route` makes the other.
      router_x: (tokens, router width) what the router scores, where that
        is not ``x``: experts that work in a latent are routed on the
        layer's full-width input.

    Returns ``(y, (kept, elsewhere))``: y (tokens, hidden) in x's dtype,
    the sum over the token's top-k experts held here of weight x expert;
    kept (held,) int32 assignments each held expert got; elsewhere int32
    assignments that went to experts on other chips. What the absent
    experts would add is another chip's to compute: no exchange is traced
    and nothing stands in for it.

    The kept assignments are laid out in a (held, tokens) table (a token's
    top-k experts are distinct, so it loses nothing): one sort along the tokens
    puts each expert's own first, in whole blocks of ``block_rows``
    (:func:`_held_first`; no scatter, nothing of tokens x top_k but compares),
    and a loop over the blocks in use multiplies each by its expert's matrices:
    its length follows the routing, so a skewed router costs time, never
    tokens. ``jax.lax.ragged_dot`` on the same rows needs a buffer for the most
    any routing can keep, tokens x min(top_k, held) rows, 25 times what a
    uniform router sends at 8 of 256 experts, and leaves the rows past the
    groups unwritten in every product of its backward pass: 3.5 times this
    loop's time on a v5e, 2.9 GB of temporaries against 0.4 (PERF.md, PR 27).
    """
    t, _ = x.shape
    held = w_up.shape[0]
    with scope("moe_route"):
        top_e, weight = (route or _route)(
            x if router_x is None else router_x, router_w, top_k, scaling)

    with scope("moe_dispatch"):
        # An expert's segment of the table is whole blocks of tokens long.
        pad = ((0, -t % block_rows), (0, 0))
        hit = (jnp.pad(top_e - first_expert, pad, constant_values=-1)
               == jnp.arange(held)[:, None, None])        # (held, t, k)
        mine = hit.any(-1)
        kept = mine.sum(-1, dtype=jnp.int32)
        elsewhere = t * top_k - kept.sum()
        rows, slot_weight = _held_first(
            mine, jnp.where(hit, jnp.pad(weight, pad), 0.0).sum(-1))
        blocks_of = (kept + block_rows - 1) // block_rows
        ends = jnp.cumsum(blocks_of)
        # Block b is block b - (the blocks before its expert) of its
        # expert's segment; the blocks past ends[-1] are never read.
        b = jnp.arange(mine.size // block_rows)
        block_expert = jnp.minimum(jnp.searchsorted(ends, b, side="right"),
                                   held - 1).astype(jnp.int32)
        at = jnp.minimum(block_expert * (b.size // held) + b
                         - (ends - blocks_of)[block_expert], b[-1])
        rows = rows.reshape(-1, block_rows)[at]
        slot_weight = slot_weight.reshape(-1, block_rows)[at]

    y = _expert_blocks(x, w_gate, w_up, w_down, slot_weight, rows,
                       block_expert, ends[-1])
    return y.astype(x.dtype), (kept, elsewhere)
