"""Plain reference of family ``sambay_lm``: the forward pass and the loss
in straightforward ``jax.numpy``, float32: the selective recurrence one
position after the other, differential attention as masked softmax, the
memory and the shared keys and values handed from layer to layer as plain
values; no kernel, no chunked scan. It reads the parameter tree the
system's model makes and imports nothing of the system.

What it computes, from the configuration's keys (in backticks). With n =
`published.num_hidden_layers`, h = n / 2, the layers held are
`layers_held` (published indices l); layer l is

    x <- x + mixer_l(LN(x));  x <- x + Wd (silu(Wg LN(x)) * Wu LN(x))

LN = LayerNorm with scale and bias, eps `layer_norm_eps`; the MLP is
`intermediate_size` wide without bias. Layer l is a state-space layer
where l % `mb_per_layer` == 0. With C = `mamba_expand` x `hidden_size`
channels of N = `mamba_d_state` states, a = `num_attention_heads` query
heads and a_kv = `num_key_value_heads` key-value heads of d =
`hidden_size` / a:

    M (state-space, l <= h):
        [x | z] = u W_in                  widths C, C
        x = silu(conv(x))                 depthwise, causal, `mamba_d_conv`
                                          taps, with bias
        [delta | B | C] = x W_x           widths `mamba_dt_rank`, N, N
        dt = softplus(delta W_dt + dt_bias);  A = -exp(A_log)    (C, N)
        h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t   h_{-1} = 0
        y_t = h_t . C_t + D * x_t
        out = (y * silu(z)) W_out;   layer h hands on m = y
    S (l < h, not state-space), F (l = h + 1):
        [q | k | v] = u W_qkv + b         a, a_kv, a_kv heads of d
        pair i of the query heads is heads (2i, 2i + 1) = (q1, q2); it
        reads pair i // (a / a_kv) of the key-value heads, (k1, k2) and
        (v1, v2)
        P1 = softmax_j(q1 . k1_j / sqrt(d)),  P2 = softmax_j(q2 . k2_j /
        sqrt(d))   over j <= i, and in S also i - j < `sliding_window`
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
        lambda_init = 0.8 - 0.6 exp(-0.3 l)
        o = rms((P1 - lambda P2) [v1 | v2]) w (1 - lambda_init)
                                          the RMS over the pair's 2 d
        out = o W_o + b;   layer h + 1 hands on its k, v
    G (l >= h + 2, state-space position):  out = (silu(u W_in) * m) W_out
    X (l >= h + 2, other):  q = u W_q + b;  k, v are layer h + 1's; as F
    logits = LN(x_L) E^T (E the embedding: the head is tied);
    loss = mean CE(logits, roll(tokens, -1))

``(P1 - lambda P2) [v1 | v2]`` is the published ``[attn(q1, k1, v1) |
attn(q1, k1, v2)] - lambda [attn(q2, k2, v1) | attn(q2, k2, v2)]``: two
score maps a pair, each against a value 2 d wide.

The recurrence is a ``lax.scan`` over the positions, 8,192 sequential
steps a layer at the cell's size, each elementwise work on a (C, N)
state. Its backward pass would keep the state of every position (328 KB
each at the cell's size, 2.7 GB a layer), so the whole state-space mixer
goes a segment of ``SEGMENT`` positions at a time, each under
``jax.checkpoint``, with the state and the convolution's last rows
carried between them: the arithmetic and its order are the sequential
recurrence's, the segment is no chunk of a chunked form. The rest is
computed in blocks so that float32 at 8,192 positions fits beside the
optimizer's state: each layer under ``jax.checkpoint``, attention by
pair of heads and block of query rows, the MLPs and logits by blocks of
rows. The blocks change the order of nothing that is summed over keys or
features within a row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 512      # rows of one block of an MLP, a memory unit or the logits
Q_ROWS = 256    # query rows of one block of attention
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


def _layer_norm(x, p, eps):
    centred = x - x.mean(-1, keepdims=True)
    return (centred * jax.lax.rsqrt(
        jnp.square(centred).mean(-1, keepdims=True) + eps)
        * p["scale"] + p["bias"])


def _kind(l, config):
    half = config["published"]["num_hidden_layers"] // 2
    state_space = l % config["mb_per_layer"] == 0
    if l >= half + 2:
        return "G" if state_space else "X"
    if state_space:
        return "M"
    return "F" if l == half + 1 else "S"


def _state_space(u, p, config, hand_on):
    """(out, y where ``hand_on``): the mixer a segment of positions at a
    time, the state and the convolution's last rows carried from segment
    to segment."""
    channels = config["mamba_expand"] * config["hidden_size"]
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    rank = config["mamba_dt_rank"]
    assert p["in_proj"]["kernel"].shape[1] == 2 * channels
    assert p["conv_kernel"].shape == (taps, channels)
    assert p["x_proj"]["kernel"].shape == (channels, rank + 2 * n)
    a = -jnp.exp(p["A_log"]).T      # (N, C): the channels along the lanes

    def position(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(a * dt_t) * h + b_t[:, None] * (dt_t * x_t)
        return h, (h * c_t[:, None]).sum(0) + p["D"] * x_t

    @jax.checkpoint
    def segment(carry, u_rows):
        h, before = carry    # the state; the taps - 1 rows of x before
        s = u_rows.shape[0]
        x, z = jnp.split(u_rows @ p["in_proj"]["kernel"], 2, axis=1)
        # depthwise and causal: tap k reads position t - (taps - 1 - k)
        padded = jnp.concatenate([before, x])
        x = jax.nn.silu(p["conv_bias"] + sum(
            p["conv_kernel"][k] * padded[k:k + s] for k in range(taps)))
        delta, b, c = jnp.split(x @ p["x_proj"]["kernel"],
                                [rank, rank + n], axis=1)
        dt = jax.nn.softplus(delta @ p["dt_proj"]["kernel"] + p["dt_bias"])
        h, y = jax.lax.scan(position, h, (x, dt, b, c))
        out = (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"]
        return (h, padded[s:]), (out, y if hand_on else None)

    s = u.shape[0]
    rows = min(SEGMENT, s)
    assert s % rows == 0, (s, rows)
    start = (jnp.zeros((n, channels), jnp.float32),
             jnp.zeros((taps - 1, channels), jnp.float32))
    _, (out, y) = jax.lax.scan(segment, start,
                               u.reshape(s // rows, rows, -1))
    return out.reshape(s, -1), y.reshape(s, channels) if hand_on else None


def _differential(u, p, shared_kv, l, kind, config):
    """(out, (k, v)): k and v as (s, a_kv * d), computed here or given.
    Heads (2i, 2i + 1) are neighbouring blocks of d columns, so a pair's
    [q1 | q2] and [v1 | v2] are one block of 2 d each. A pair of query
    heads is projected where it is used, from its own columns of the
    weights: no array holds every head's queries at once."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["hidden_size"] // heads
    s = u.shape[0]
    if kind == "X":
        w_q, b_q = p["query"]["kernel"], p["query"]["bias"]
        k, v = shared_kv
    else:
        w_q, w_kv = jnp.split(p["qkv"]["kernel"], [heads * d], axis=1)
        b_q, b_kv = jnp.split(p["qkv"]["bias"], [heads * d])
        k, v = jnp.split(u @ w_kv + b_kv, 2, axis=1)
    assert w_q.shape[1] == heads * d and k.shape[1] == kv_heads * d
    per_kv = heads // kv_heads     # query pairs that a key-value pair serves
    start = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    window = config["sliding_window"] if kind == "S" else s
    j = jnp.arange(s)[None, :]
    pair_of = lambda t, i: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        t, 2 * i * d, 2 * d, axis=t.ndim - 1)

    def pair(i):
        q_i = u @ pair_of(w_q, i) + pair_of(b_q, i)         # [q1 | q2]
        k_i, v_i = pair_of(k, i // per_kv), pair_of(v, i // per_kv)
        k1_h, k2_h = k_i[:, :d], k_i[:, d:]

        def rows(q_rows, at):   # (Q_ROWS, 2 d), (Q_ROWS, 1): the positions
            keep = (j <= at) & (at - j < window)

            def probabilities(q_h, k_h):
                return jax.nn.softmax(jnp.where(
                    keep, q_h @ k_h.T / math.sqrt(d), -jnp.inf), -1)

            o = (probabilities(q_rows[:, :d], k1_h)
                 - lam * probabilities(q_rows[:, d:], k2_h)) @ v_i
            return (o * jax.lax.rsqrt(
                jnp.square(o).mean(-1, keepdims=True)
                + config["layer_norm_eps"]) * p["norm_scale"]
                * (1.0 - start))

        return _blocked(rows, q_i, jnp.arange(s)[:, None], block=Q_ROWS)

    o = jax.lax.map(jax.checkpoint(pair), jnp.arange(heads // 2))
    # (pairs, s, 2 d) against the pairs' rows of the output projection
    out = jnp.einsum("psd,pdh->sh", o, p["out"]["kernel"].reshape(
        heads // 2, 2 * d, -1)) + p["out"]["bias"]
    return out, (k, v)


def _mlp(u, p):
    return _blocked(
        lambda r: (jax.nn.silu(r @ p["mlp_gate"]["kernel"])
                   * (r @ p["mlp_up"]["kernel"])) @ p["mlp_down"]["kernel"],
        u, block=ROWS)


def _layer(x, memory, shared_kv, p, l, config):
    eps = config["layer_norm_eps"]
    half = config["published"]["num_hidden_layers"] // 2
    kind = _kind(l, config)
    u = _layer_norm(x, p["mixer_norm"], eps)
    if kind == "M":
        out, y = _state_space(u, p["mixer"], config, l == half)
        if l == half:
            memory = y
    elif kind == "G":
        out = _blocked(
            lambda r, m: (jax.nn.silu(r @ p["mixer"]["in_proj"]["kernel"])
                          * m) @ p["mixer"]["out_proj"]["kernel"],
            u, memory, block=ROWS)
    else:
        out, kv = _differential(u, p["mixer"], shared_kv, l, kind, config)
        if l == half + 1:
            shared_kv = kv
    x = x + out
    return x + _mlp(_layer_norm(x, p["mlp_norm"], eps), p), memory, shared_kv


def _hidden(params, tokens, config):
    """The final LayerNorm's output for one sequence, (s, hidden)."""
    embedding = params["tok_embed"]["embedding"]
    assert embedding.shape == (config["vocab_size"], config["hidden_size"])
    x, memory, shared_kv = embedding[tokens], None, None
    for l in config["layers_held"]:
        x, memory, shared_kv = jax.checkpoint(
            lambda x, m, kv, p, l=l: _layer(x, m, kv, p, l, config))(
                x, memory, shared_kv, params[f"layer_{l}"])
    return _layer_norm(x, params["final_norm"], config["layer_norm_eps"])


def logits(params, tokens, config):
    """(s, vocabulary held) logits of one sequence: the tied head."""
    return _hidden(params, tokens, config) @ params["tok_embed"][
        "embedding"].T


def _sequence_loss(params, tokens, config):
    embedding = params["tok_embed"]["embedding"]

    def rows(x_rows, targets):
        logp = jax.nn.log_softmax(x_rows @ embedding.T, -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    return _blocked(rows, _hidden(params, tokens, config),
                    jnp.roll(tokens, -1), block=ROWS).mean()


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
