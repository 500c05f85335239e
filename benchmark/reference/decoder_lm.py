"""Plain reference of family ``decoder_lm``: the forward pass and the loss
in straightforward ``jax.numpy``, float32, masked softmax attention, no
kernel, no grouped product. It reads the parameter tree the system's
model makes and imports nothing of the system.

What it computes, from the configuration's keys (in backticks). Layer l
has n_l query heads (`num_attention_heads_per_layer`), `num_key_value_
heads` key-value heads of `head_dim`, no bias, RMSNorm eps `rms_norm_eps`:

    h  = rms(x);  q = h Wq [s, n_l, d];  k = h Wk, v = h Wv [s, n_kv, d]
    q, k = rope_l(q), rope_l(k)
    a_i,head = softmax_j(q_i,head . k_j,head//(n_l/n_kv) / sqrt(d)) v_j
               over j <= i (`layer_types[l]` full_attention), or over
               i - `sliding_window` < j <= i (sliding_attention)
    a_i,head = sigmoid(h_i . wg_head) * a_i,head
    x  = x + a Wo;  h2 = rms(x)
    dense  (`mlp_layer_types[l]`):  x = x + Wd(silu(Wg h2) * (Wu h2))
    sparse: p = softmax(h2 Wr) over all `published.num_experts`
            T = top-`num_experts_per_tok`(p)
            w_e = `moe_routed_scaling_factor` * p_e / sum_{e' in T} p_e'
            x = x + S(h2) + sum_{e in T, held here} w_e E_e(h2)
            held here: `first_expert` .. `first_expert` + `num_experts` - 1
    logits = rms(x_L) Whead;  loss = mean CE(logits, roll(tokens, -1))

``rope_l`` follows `rope_parameters`: the leading `partial_rotary_factor`
of the head is rotated, halves paired as (x[:r/2], x[r/2:]); `default`
uses theta^(-2i/r); `yarn` blends that with it over `factor` by a linear
ramp between the dims that turn `beta_fast` and `beta_slow` times in
`original_max_position_embeddings`, and scales cos and sin by
`attention_factor`.

It is computed in blocks so that float32 at 8,192 positions fits beside
the optimizer's state: each layer under ``jax.checkpoint``, attention by
key-value head and block of query rows, the MLPs and the logits by blocks of
rows, the experts one after the other. The blocks change the order of
nothing that is summed over keys, features or experts within a row.
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


def _inv_freq(rope, rotary_dim):
    i = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    freq = rope["rope_theta"] ** (2.0 * i / rotary_dim)
    if rope["rope_type"] == "default":
        return 1.0 / freq
    assert rope["rope_type"] == "yarn", rope["rope_type"]

    def turns(r):  # the dim that turns r times in the original length
        c = (rotary_dim * math.log(
            rope["original_max_position_embeddings"] / (2 * math.pi * r))
            / (2 * math.log(rope["rope_theta"])))
        return min(max(c, 0), rotary_dim - 1)

    low = math.floor(turns(rope["beta_fast"]))
    high = math.ceil(turns(rope["beta_slow"]))
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / freq + ramp / (rope["factor"] * freq)


def _rope(x, rope):
    """x (s, heads, d): rotate the leading partial_rotary_factor of d."""
    s, _, d = x.shape
    r = int(d * rope["partial_rotary_factor"])
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * _inv_freq(rope, r)
    factor = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(q, k, v, window):
    """q (s, n, d), k and v (s, n_kv, d) -> (s, n, d); causal, and with
    ``window`` only the last ``window`` keys. One key-value head at a
    time; its group's query heads are further rows of the same product."""
    s, n, d = q.shape
    n_kv = k.shape[1]
    group = n // n_kv
    j = jnp.arange(s)[None, :]

    def kv_head(args):
        q_g, k_h, v_h = args  # (group * s, d), (s, d), (s, d)

        def rows(q_rows, i):  # (Q_ROWS, d), (Q_ROWS, 1): the positions
            scores = q_rows @ k_h.T / math.sqrt(d)
            keep = j <= i
            if window is not None:
                keep &= i - j < window
            return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1) @ v_h

        at = jnp.tile(jnp.arange(s), group)[:, None]
        return _blocked(rows, q_g, at, block=Q_ROWS)

    # query head h reads key-value head h // group
    q_g = q.reshape(s, n_kv, group, d).transpose(1, 2, 0, 3).reshape(
        n_kv, group * s, d)
    out = jax.lax.map(jax.checkpoint(kv_head), (
        q_g, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.reshape(n_kv, group, s, d).transpose(2, 0, 1, 3).reshape(
        s, n, d)


def _swiglu(h, gate, up, down):
    return _blocked(lambda r: (jax.nn.silu(r @ gate) * (r @ up)) @ down, h,
                    block=ROWS)


def _experts_share(h, p, config):
    """sum over the token's top-k experts held here of w_e E_e(h)."""
    probs = jax.nn.softmax(h @ p["router"], -1)
    top_p, top_e = jax.lax.top_k(probs, config["num_experts_per_tok"])
    weight = (config["moe_routed_scaling_factor"] * top_p
              / top_p.sum(-1, keepdims=True))
    held = p["experts_gate"].shape[0]
    assert held == config["num_experts"]
    assert probs.shape[-1] == config["published"]["num_experts"]

    def expert(args):
        e, gate, up, down = args
        w_e = jnp.where(top_e == e, weight, 0.0).sum(-1, keepdims=True)
        return w_e * _swiglu(h, gate, up, down)

    parts = jax.lax.map(jax.checkpoint(expert), (
        config["first_expert"] + jnp.arange(held), p["experts_gate"],
        p["experts_up"], p["experts_down"]))
    return parts.sum(0)


def _layer(x, p, l, config):
    eps = config["rms_norm_eps"]
    kind = config["layer_types"][l]
    a = p["attention"]
    h = _rms(x, p["attention_norm"]["scale"], eps)
    q = jnp.einsum("sh,hnd->snd", h, a["query"]["kernel"])
    k = jnp.einsum("sh,hnd->snd", h, a["key"]["kernel"])
    v = jnp.einsum("sh,hnd->snd", h, a["value"]["kernel"])
    assert q.shape[1:] == (config["num_attention_heads_per_layer"][l],
                           config["head_dim"])
    assert k.shape[1] == config["num_key_value_heads"]
    rope = config["rope_parameters"][kind]
    out = _attention(_rope(q, rope), _rope(k, rope), v, {
        "full_attention": None,
        "sliding_attention": config["sliding_window"]}[kind])
    out = out * jax.nn.sigmoid(h @ a["gate"]["kernel"])[..., None]
    x = x + jnp.einsum("snd,ndh->sh", out, a["out"]["kernel"])
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    if config["mlp_layer_types"][l] == "dense":
        return x + _swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                           p["mlp_down"]["kernel"])
    m = p["moe"]
    shared = _swiglu(h, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
                     m["shared_down"]["kernel"])
    return x + shared + _experts_share(h, m, config)


def _sequence_loss(params, tokens, config):
    x = params["tok_embed"]["embedding"][tokens]
    for l in range(config["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, p, l=l: _layer(x, p, l, config))(
                x, params[f"layer_{l}"])
    x = _rms(x, params["final_norm"]["scale"], config["rms_norm_eps"])
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
