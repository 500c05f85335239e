"""Plain reference of family ``hybrid_lm``: the forward pass and the loss
in straightforward ``jax.numpy``, float32: the state-space recurrence one
position after the other, masked softmax attention, a dense loop over the
experts held; no kernel, no chunked scan, no grouped product. It reads the
parameter tree the system's model makes and imports nothing of the system.

What it computes, from the configuration's keys (in backticks). Block l
is ``x + mixer_l(rms(x))`` with RMSNorm eps `layer_norm_epsilon`; its
mixer is character l of `hybrid_override_pattern`. With H = `mamba_num_
heads` heads of P = `mamba_head_dim` over G = `n_groups` groups of state
N = `ssm_state_size` held here (head h in group h // (H/G)):

    M:  [z | xBC | dt] = u W_in           widths H P, H P + 2 G N, H
        xBC = silu(conv(xBC))             depthwise, causal, `conv_kernel`
                                          taps, with bias
        [x | B | C] = xBC                 widths H P, G N, G N
        dt = softplus(dt + dt_bias);  A = -exp(A_log)     (a scalar a head)
        h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t      h_{-1} = 0
        y_t = C_t . h_t + D x_t
        out = (rms_group(y * silu(z)) * w) W_out          mean square over
                                          each group's H P / G channels
    *:  q = u Wq [s, n, d], k = u Wk, v = u Wv [s, n_kv, d]   (n = `num_
        attention_heads`, n_kv = `num_key_value_heads`, d = `head_dim`)
        a_i,head = softmax_j<=i(q_i,head . k_j,head//(n/n_kv) / sqrt(d)) v_j
        out = a Wo                        no positions are added (`assumed`)
    E:  s = sigmoid(u Wr) over all `published.n_routed_experts`
        T = top-`num_experts_per_tok` of s + b   (b: the correction bias)
        w_e = `routed_scaling_factor` * s_e / sum_{e' in T} s_e'
        z = u W1                          to `moe_latent_size`
        out = (sum_{e in T, held here} w_e Wd_e relu(Wu_e z)^2) W2
              + Wd relu(Wu u)^2           the shared expert, full width
        held here: `first_expert` .. `first_expert` + `n_routed_experts` - 1
    logits = rms(x_L) Whead;  loss = mean CE(logits, roll(tokens, -1))

The recurrence is a ``lax.scan`` over the positions, 8,192 sequential
steps a block at the cell's size (a step is elementwise work on a
(heads, P, N) state, some tens of microseconds on the chip: seconds a
block and pass, which the check can afford). Its backward pass would keep
the state of every position (1 MB each at the cell's size), so the whole
state-space mixer goes a segment of ``SEGMENT`` positions at a time, each
under ``jax.checkpoint``, with the state and the convolution's last rows
carried between them: the arithmetic and its order are the sequential
recurrence's, the segment is no chunk of a chunked form (1.3 GB of
float32 intermediates a block otherwise, and the check did not fit). The
rest is computed in blocks so that float32 at 8,192 positions fits beside
the optimizer's state: each block of the model under ``jax.checkpoint``,
attention by block of query rows, the MLPs and logits by blocks of rows,
the experts one after the other into one sum. The blocks change the order
of nothing that is summed over keys, features or experts within a row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 1024     # rows of one block of a projection, an MLP or the logits
Q_ROWS = 512    # query rows of one block of attention
SEGMENT = 128   # positions of a state-space mixer under one checkpoint


def _blocked(fn, *rows, block):
    """``fn`` over blocks of ``block`` leading rows of ``rows``, one block
    at a time, each recomputed in the backward pass."""
    n = rows[0].shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)
    split = [r.reshape(n // block, block, *r.shape[1:]) for r in rows]
    out = jax.lax.map(lambda parts: jax.checkpoint(fn)(*parts), tuple(split))
    return jax.tree.map(lambda o: o.reshape(n, *o.shape[2:]), out)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2_mlp(h, up, down):
    return _blocked(lambda r: jnp.square(jax.nn.relu(r @ up)) @ down, h,
                    block=ROWS)


def _recurrence(h, x, dt, a, b, c, d):
    """From the state h (G, K, P, N) on, one position after the other:
    x (s, G, K, P), dt (s, G, K), a and d (G, K), b and c (s, G, N), K
    heads a group sharing its B and C. Returns the state after the last
    position and y (s, G, K, P)."""
    def position(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * a)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return h, (h * c_t[:, None, None, :]).sum(-1) + d[:, :, None] * x_t

    return jax.lax.scan(position, h, (x, dt, b, c))


def _state_space(u, p, config):
    """The whole mixer a segment of positions at a time, the state and
    the convolution's last rows carried from segment to segment."""
    heads, hp = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    inner, bc = heads * hp, groups * n
    taps = config["conv_kernel"]
    by_group = (groups, heads // groups)   # head h in group h // (H/G)
    assert p["in_proj"]["kernel"].shape[1] == 2 * inner + 2 * bc + heads
    assert p["conv_kernel"].shape == (taps, inner + 2 * bc)
    a, d = -jnp.exp(p["A_log"]).reshape(by_group), p["D"].reshape(by_group)

    @jax.checkpoint
    def segment(carry, u_rows):
        h, before = carry    # the state; the taps - 1 rows of xBC before
        s = u_rows.shape[0]
        z, xbc, dt = jnp.split(u_rows @ p["in_proj"]["kernel"],
                               [inner, 2 * inner + 2 * bc], axis=1)
        # depthwise and causal: tap k reads position t - (taps - 1 - k)
        padded = jnp.concatenate([before, xbc])
        x, b, c = jnp.split(jax.nn.silu(p["conv_bias"] + sum(
            p["conv_kernel"][k] * padded[k:k + s] for k in range(taps))),
            [inner, inner + bc], axis=1)
        h, y = _recurrence(
            h, x.reshape(s, *by_group, hp),
            jax.nn.softplus(dt + p["dt_bias"]).reshape(s, *by_group), a,
            b.reshape(s, groups, n), c.reshape(s, groups, n), d)
        gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(
            s, groups, inner // groups)
        normed = gated * jax.lax.rsqrt(
            jnp.square(gated).mean(-1, keepdims=True)
            + config["layer_norm_epsilon"])
        out = ((normed.reshape(s, inner) * p["norm_scale"])
               @ p["out_proj"]["kernel"])
        return (h, padded[s:]), out

    s = u.shape[0]
    rows = min(SEGMENT, s)
    assert s % rows == 0, (s, rows)
    start = (jnp.zeros((*by_group, hp, n), jnp.float32),
             jnp.zeros((taps - 1, inner + 2 * bc), jnp.float32))
    _, out = jax.lax.scan(segment, start, u.reshape(s // rows, rows, -1))
    return out.reshape(s, -1)


def _attention(u, p, config):
    """Causal softmax attention, a query head at a time; query head h
    reads key-value head h // (n / n_kv)."""
    s = u.shape[0]
    q = jnp.einsum("sh,hnd->nsd", u, p["query"]["kernel"])
    k = jnp.einsum("sh,hnd->nsd", u, p["key"]["kernel"])
    v = jnp.einsum("sh,hnd->nsd", u, p["value"]["kernel"])
    n, _, d = q.shape
    assert (n, d) == (config["num_attention_heads"], config["head_dim"])
    assert k.shape[0] == config["num_key_value_heads"]
    group = n // k.shape[0]
    j = jnp.arange(s)[None, :]

    def head(args):
        q_h, k_h, v_h = args

        def rows(q_rows, i):  # (Q_ROWS, d), (Q_ROWS, 1): the positions
            scores = q_rows @ k_h.T / math.sqrt(d)
            return jax.nn.softmax(
                jnp.where(j <= i, scores, -jnp.inf), -1) @ v_h

        return _blocked(rows, q_h, jnp.arange(s)[:, None], block=Q_ROWS)

    out = jax.lax.map(jax.checkpoint(head), (
        q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)))
    return jnp.einsum("nsd,ndh->sh", out, p["out"]["kernel"])


def _experts(u, p, config):
    """The shared expert at the full width plus the held experts' part of
    the routed sum, in the latent and projected back."""
    scores = jax.nn.sigmoid(u @ p["router"])
    assert scores.shape[1] == config["published"]["n_routed_experts"]
    _, top_e = jax.lax.top_k(scores + p["router_bias"],
                             config["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=1)
    weight = (config["routed_scaling_factor"] * top_s
              / top_s.sum(-1, keepdims=True))
    z = u @ p["latent_in"]["kernel"]
    held = p["experts_up"].shape[0]
    assert held == config["n_routed_experts"]

    @jax.checkpoint
    def expert(e, up, down):
        w_e = jnp.where(top_e == e, weight, 0.0).sum(-1, keepdims=True)
        return w_e * _relu2_mlp(z, up, down)

    # one expert after the other into one sum: the held experts' parts
    # are never held side by side
    routed, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None),
        jnp.zeros_like(z),
        (config["first_expert"] + jnp.arange(held), p["experts_up"],
         p["experts_down"]))
    return (_relu2_mlp(u, p["shared_up"]["kernel"], p["shared_down"]["kernel"])
            + routed @ p["latent_out"]["kernel"])


_MIXERS = {"M": _state_space, "*": _attention, "E": _experts}


def _sequence_loss(params, tokens, config):
    eps = config["layer_norm_epsilon"]
    x = params["tok_embed"]["embedding"][tokens]
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    for l, kind in enumerate(pattern):
        x = jax.checkpoint(
            lambda x, p, kind=kind: x + _MIXERS[kind](
                _rms(x, p["norm"]["scale"], eps), p["mixer"], config))(
                    x, params[f"block_{l}"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    head = params["lm_head"]["kernel"]

    def rows(x_rows, targets):
        logp = jax.nn.log_softmax(x_rows @ head, -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    return _blocked(rows, x, jnp.roll(tokens, -1), block=ROWS).mean()


def loss(params, extra, batch, config):
    """Mean next-token cross-entropy of one micro-batch, float32. Every
    sequence has the same length, so the mean over sequences of their
    means is the mean over tokens. The sequences go one after the other
    in a python loop: a ``lax.map`` over them would carry a second copy of
    every weight's gradient through its backward pass."""
    (tokens,) = batch
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return sum(_sequence_loss(params, t, config)
               for t in tokens) / tokens.shape[0]
