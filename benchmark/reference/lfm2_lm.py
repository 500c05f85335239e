"""Plain reference of family ``lfm2_lm``: the forward pass and the loss in
straightforward ``jax.numpy``, float32, masked softmax attention, no
kernel, no grouped product, no sort. It reads the parameter tree the
system's model makes and imports nothing of the system.

What it computes, from the configuration's keys (in backticks). Layer l
is a published index of `layers_held`; `num_attention_heads` n query heads
over `num_key_value_heads` n_kv of d = `hidden_size` / n; no bias; every
RMSNorm has eps `norm_eps`:

    h = rms(x)
    `layer_types[l]` conv:  [B | C | x~] = h W_in  (d_model -> 3 d_model)
        v = B * x~;  z_t = sum_{k < K} w_k * v_{t - K + 1 + k}, v = 0
        before the first position, K = `conv_L_cache`;  op = (C * z) W_out
    full_attention:  q = rms_q(h Wq) [s, n, d], k = rms_k(h Wk) [s, n_kv,
        d], each head over its d dims;  v = h Wv
        q, k = rope(q), rope(k)   (theta `rope_theta`, the whole head,
        halves paired as (x[:d/2], x[d/2:]))
        a_i,head = softmax_{j <= i}(q_i,head . k_j,head//(n/n_kv) / sqrt(d))
        v_j;  op = a Wo
    x = x + op;  h2 = rms(x)
    dense (l < `num_dense_layers`):  x = x + Wd(silu(Wg h2) * (Wu h2))
    sparse:  s = sigmoid(h2 Wr) over all `published.num_experts`
             T = top-`num_experts_per_tok`(s + b), b the expert bias
             w_e = `routed_scaling_factor` * s_e / (sum_{e' in T} s_e'
                   + `router_eps`)
             x = x + sum_{e in T, held here} w_e E_e(h2)
             held here: `first_expert` .. `first_expert` + `num_experts` - 1
    logits = rms(x_L) E^T (E the embedding: the head is tied)
    loss = mean CE(logits, roll(tokens, -1))

It is computed in blocks so that float32 at 8,192 positions fits beside
the optimizer's state: each layer under ``jax.checkpoint``, attention by
key-value head and block of query rows, the dense MLP and the logits by
blocks of rows, each held expert densely on every token, weighted by its
routing mask, one after the other. The blocks change the order of nothing
that is summed over keys, features or experts within a row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 1024    # rows of one block of an MLP or of the logits
Q_ROWS = 512   # query rows of one block of attention


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


def _rope(x, theta):
    """x (s, heads, d): every dim of the head rotated, halves paired."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """q (s, n, d), k and v (s, n_kv, d) -> (s, n, d), causal. One
    key-value head at a time; its group's query heads are further rows of
    the same product."""
    s, n, d = q.shape
    n_kv = k.shape[1]
    group = n // n_kv
    j = jnp.arange(s)[None, :]

    def kv_head(args):
        q_g, k_h, v_h = args  # (group * s, d), (s, d), (s, d)

        def rows(q_rows, i):  # (Q_ROWS, d), (Q_ROWS, 1): the positions
            scores = q_rows @ k_h.T / math.sqrt(d)
            return jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf),
                                  -1) @ v_h

        at = jnp.tile(jnp.arange(s), group)[:, None]
        return _blocked(rows, q_g, at, block=Q_ROWS)

    # query head h reads key-value head h // group
    q_g = q.reshape(s, n_kv, group, d).transpose(1, 2, 0, 3).reshape(
        n_kv, group * s, d)
    out = jax.lax.map(jax.checkpoint(kv_head), (
        q_g, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.reshape(n_kv, group, s, d).transpose(2, 0, 1, 3).reshape(
        s, n, d)


def _shifted(v, n):
    """v (s, c) moved n positions later, zeros in front."""
    if n == 0:
        return v
    return jnp.concatenate([jnp.zeros((n, v.shape[1]), v.dtype), v[:-n]])


def _short_conv(h, p, kernel):
    b, c, x = jnp.split(h @ p["in_proj"]["kernel"], 3, axis=-1)
    v = b * x
    z = sum(p["taps"][k] * _shifted(v, kernel - 1 - k)
            for k in range(kernel))
    return (c * z) @ p["out_proj"]["kernel"]


def _grouped_attention(h, a, config):
    n, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // n
    eps = config["norm_eps"]
    q = _rms(jnp.einsum("sh,hnd->snd", h, a["query"]["kernel"]),
             a["query_norm"], eps)
    k = _rms(jnp.einsum("sh,hnd->snd", h, a["key"]["kernel"]),
             a["key_norm"], eps)
    v = jnp.einsum("sh,hnd->snd", h, a["value"]["kernel"])
    assert q.shape[1:] == (n, d) and k.shape[1:] == (n_kv, d)
    theta = config["rope_theta"]
    out = _attention(_rope(q, theta), _rope(k, theta), v)
    return jnp.einsum("snd,ndh->sh", out, a["out"]["kernel"])


def _swiglu(h, gate, up, down):
    return _blocked(lambda r: (jax.nn.silu(r @ gate) * (r @ up)) @ down, h,
                    block=ROWS)


def _experts_share(h, p, config):
    """sum over the token's top-k experts held here of w_e E_e(h)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    _, top_e = jax.lax.top_k(scores + p["expert_bias"],
                             config["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, -1)
    weight = (config["routed_scaling_factor"] * top_s
              / (top_s.sum(-1, keepdims=True) + config["router_eps"]))
    held = p["experts_gate"].shape[0]
    assert held == config["num_experts"]
    assert scores.shape[-1] == config["published"]["num_experts"]

    def expert(args):
        e, gate, up, down = args
        w_e = jnp.where(top_e == e, weight, 0.0).sum(-1, keepdims=True)
        return w_e * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    parts = jax.lax.map(jax.checkpoint(expert), (
        config["first_expert"] + jnp.arange(held), p["experts_gate"],
        p["experts_up"], p["experts_down"]))
    return parts.sum(0)


def _layer(x, p, l, config):
    eps = config["norm_eps"]
    if config["layer_types"][l] == "conv":
        x = x + _short_conv(_rms(x, p["conv_norm"]["scale"], eps), p["conv"],
                            config["conv_L_cache"])
    else:
        x = x + _grouped_attention(
            _rms(x, p["attention_norm"]["scale"], eps), p["attention"],
            config)
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    if l < config["num_dense_layers"]:
        return x + _swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                           p["mlp_down"]["kernel"])
    return x + _experts_share(h, p["moe"], config)


def logits(params, tokens, config):
    """(s, vocabulary) float32 logits of one sequence, unblocked: for the
    tests' tiny sizes."""
    x = _trunk(params, tokens, config)
    return x @ params["tok_embed"]["embedding"].T


def _trunk(params, tokens, config):
    x = params["tok_embed"]["embedding"][tokens]
    for l in config["layers_held"]:
        x = jax.checkpoint(
            lambda x, p, l=l: _layer(x, p, l, config))(
                x, params[f"layer_{l}"])
    return _rms(x, params["final_norm"]["scale"], config["norm_eps"])


def _sequence_loss(params, tokens, config):
    x = _trunk(params, tokens, config)
    embedding = params["tok_embed"]["embedding"]

    def rows(x_rows, targets):
        logp = jax.nn.log_softmax(x_rows @ embedding.T, -1)
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
