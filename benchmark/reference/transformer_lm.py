"""Plain reference of family ``transformer_lm``: the forward pass and the
loss in straightforward ``jax.numpy``, float32, stock attention, no
remat, no kernel. It reads the parameter tree the system's model makes
and imports nothing of the system.

What it computes, per ``horovod_tpu/models/transformer.py`` (pre-norm):

    x = tok_embed[tokens] + pos_embed[0:s]
    per layer:  x += out(softmax(q k^T / sqrt(d)) v),  q,k,v = proj(LN(x))
                x += W2 gelu_tanh(W1 LN(x))
    logits = lm_head(LN(x));  loss = mean CE(logits, roll(tokens, -1))

The encoder is not causal; layer norm has eps 1e-6 (flax's default).
The layers' parameters are stacked and the layers run as one
``lax.scan`` under ``jax.checkpoint``: the arithmetic is the same, the
program is a twelfth the size (it has to stay in a compile cache that
the machine caps), and float32 scores at 2,048 positions fit one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _layer(x, p):
    h = _layer_norm(x, p["LayerNorm_0"])
    a = p["MultiHeadAttention_0"]
    proj = lambda n: (jnp.einsum("bsh,hnd->bsnd", h, a[n]["kernel"])  # noqa: E731
                      + a[n]["bias"])
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * q.shape[-1] ** -0.5
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bsnd,ndh->bsh", ctx, a["out"]["kernel"]) \
        + a["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _dense(jax.nn.gelu(_dense(h, p["Dense_0"]), approximate=True),
               p["Dense_1"])
    return x + h


def loss(params, extra, batch, config):
    """Mean next-token cross-entropy of one micro-batch, float32."""
    (tokens,) = batch
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[1]
    x = (params["tok_embed"]["embedding"][tokens]
         + params["pos_embed"]["embedding"][:s][None])
    layers = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"layer_{i}"] for i in range(config["num_hidden_layers"])])
    x, _ = jax.lax.scan(
        lambda x, layer: (jax.checkpoint(_layer)(x, layer), None), x, layers)
    logits = _dense(_layer_norm(x, params["final_norm"]), params["lm_head"])
    logp = jax.nn.log_softmax(logits, -1)
    targets = jnp.roll(tokens, -1, axis=1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()
