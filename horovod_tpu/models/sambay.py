"""A decoder-hybrid-decoder language model (SambaY with differential
attention): a self-decoder of selective-scan and window-attention mixers,
one full-attention layer, and a cross-decoder whose layers read what two
earlier layers made, the *memory* of the last selective scan and the keys
and values of the full-attention layer.

Layer ``l`` (its published index, 0 .. n - 1) is

    x <- x + mixer_l(LN(x));  x <- x + MLP(LN(x))

LN = LayerNorm with scale and bias; MLP = ``down(silu(gate(x)) * up(x))``
without bias (the published fused ``gate_up`` matrix held as its two
halves: the same parameters and products). The embedding is tied to the
head; a final LayerNorm; no positional encoding anywhere. The mixer's
kind follows from ``l`` alone, by the published rule (:func:`layer_kind`;
``h = n / 2``, a layer is a state-space layer where ``l %
mb_per_layer == 0``):

* ``M`` (state-space, l <= h): ``[x, z] = in_proj(u)``; ``x =
  silu(conv1d_causal(x))`` (depthwise, with bias); ``[delta, B, C] =
  x_proj(x)``; ``dt = softplus(dt_proj(delta) + dt_bias)``; ``A =
  -exp(A_log)`` (channels x states); ``h_t = exp(dt_t (x) A) * h_{t-1} +
  (dt_t * x_t) (x) B_t``; ``y_t = h_t . C_t + D * x_t``
  (:func:`horovod_tpu.ops.selective_scan.selective_scan`); out =
  ``out_proj(y * silu(z))``. Layer ``h`` also hands on ``m = y`` (before
  the gate): the **memory**.
* ``S`` (window differential attention, other l < h) and ``F`` (full, l =
  h + 1): ``[q, k, v] = Wqkv(u) + b``; heads paired ``(2i, 2i + 1)`` into
  ``q1, q2``, ``k1, k2``, ``v1, v2`` (a key-value pair serves ``group``
  query pairs); ``a1 = [attn(q1, k1, v1), attn(q1, k1, v2)]``, ``a2 =
  [attn(q2, k2, v1), attn(q2, k2, v2)]``, each twice a head wide, ``attn``
  causal softmax at scale ``d^-1/2`` (inside the band in ``S``);
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o = RMSNorm(a1 - lambda a2)
  (1 - lambda_init)``; out = ``out_proj(o) + b``. Layer ``h + 1`` hands
  on its ``k, v``.
* ``G`` (gated memory unit, state-space positions l >= h + 2): out =
  ``out_proj(silu(in_proj(u)) * m)``, ``m`` the memory at the same
  position.
* ``X`` (cross differential attention, other l >= h + 2): ``q = Wq(u) +
  b`` only; ``k, v`` are layer ``h + 1``'s; full causal; lambda, norm and
  ``out_proj`` of its own.

``layers`` names the published indices this chip holds, in order; a cut
keeps ``l`` (and so the kind and ``lambda_init``) of every layer it
keeps. The stack carries ``(x, memory, shared_kv)`` from layer to layer;
``remat`` wraps one layer with those three as its inputs, so the memory's
and the shared keys' gradients add over the rematerialised consumers.

bf16 compute, f32 parameters. Attention is four calls of the flash
kernels a layer (:func:`horovod_tpu.models.decoder.causal_attention`).
Modules are named ``tok_embed``, ``layer_<l>``, ``final_norm`` and
``lm_head`` (the tied head's product: no parameter of its own); the parts
of a mixer go under ``common.phases.MODEL_SCOPES`` (``ssm_conv``,
``sel_scan``, ``attn_diff``, ``gmu``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.common.phases import scope
from horovod_tpu.models.decoder import (TiedHead, _dense, _swiglu,
                                        causal_attention)
from horovod_tpu.models.hybrid import _dt_bias_init, causal_conv_silu
from horovod_tpu.ops.selective_scan import selective_scan


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int
    hidden_dim: int
    num_layers: int              # n, the published depth: the rule's
    layers: Tuple[int, ...]      # published indices held, ascending
    mlp_dim: int
    num_heads: int               # query heads, hidden_dim / num_heads wide
    num_kv_heads: int
    window: int
    dt_rank: int                 # of the step sizes' projection
    mb_per_layer: int = 2
    ssm_state: int = 16
    ssm_expand: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128        # of the scan: an implementation size
    dt_min: float = 1e-3         # the step sizes the bias starts from
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads

    @property
    def inner_dim(self) -> int:
        return self.ssm_expand * self.hidden_dim


def layer_kind(l: int, num_layers: int, mb_per_layer: int) -> str:
    """The mixer of published layer ``l`` of ``num_layers``: ``M``, ``S``,
    ``F``, ``G`` or ``X`` (the module's docstring)."""
    half = num_layers // 2
    state_space = mb_per_layer > 0 and l % mb_per_layer == 0
    if l >= half + 2:
        return "G" if state_space else "X"
    if state_space:
        return "M"
    return "F" if l == half + 1 else "S"


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def check_layers(cfg: SambaYConfig) -> None:
    """The rule's premises, and that what a held layer reads is held."""
    n, half = cfg.num_layers, cfg.num_layers // 2
    if n % 4 or layer_kind(half, n, cfg.mb_per_layer) != "M" \
            or layer_kind(half + 1, n, cfg.mb_per_layer) != "F":
        raise ValueError(
            f"{n} layers with a state-space layer every "
            f"{cfg.mb_per_layer}: want a depth divisible by 4 whose layer "
            f"{half} is a state-space layer and layer {half + 1} is not")
    held = list(cfg.layers)
    if held != sorted(set(held)) or not held or not (
            0 <= held[0] and held[-1] < n):
        raise ValueError(f"layers {held}: want ascending indices of the "
                         f"{n} published")
    kinds = {layer_kind(l, n, cfg.mb_per_layer) for l in held}
    for kind, source in (("G", half), ("X", half + 1)):
        if kind in kinds and source not in held:
            raise ValueError(f"layers {held} hold a {kind!r} layer and not "
                             f"layer {source}, which makes what it reads")
    if cfg.num_heads % 2 or cfg.num_kv_heads % 2 or (
            cfg.num_heads % cfg.num_kv_heads):
        raise ValueError(
            f"{cfg.num_heads} query heads over {cfg.num_kv_heads} key-value "
            "heads: differential attention pairs both, a whole number of "
            "query pairs a key-value pair")


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1 .. N) along the states, the same for every channel."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


class SelectiveMixer(nn.Module):
    """The ``M`` mixer; returns (out, the scan's output before the gate)."""

    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        inner, n = cfg.inner_dim, cfg.ssm_state
        rank = cfg.dt_rank
        x, z = jnp.split(_dense(2 * inner, cfg, "in_proj")(u), 2, axis=-1)
        x = causal_conv_silu(
            x,
            self.param("conv_kernel", nn.initializers.lecun_normal(),
                       (cfg.conv_kernel, inner), jnp.float32),
            self.param("conv_bias", nn.initializers.zeros, (inner,),
                       jnp.float32), cfg.dtype)
        delta, b, c = jnp.split(_dense(rank + 2 * n, cfg, "x_proj")(x),
                                [rank, rank + n], axis=-1)
        dt = nn.softplus(
            _dense(inner, cfg, "dt_proj")(delta).astype(jnp.float32)
            + self.param("dt_bias", _dt_bias_init(cfg), (inner,),
                         jnp.float32))
        a_log = self.param("A_log", _a_log_init, (inner, n), jnp.float32)
        d = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        y = selective_scan(x, dt, -jnp.exp(a_log), b, c, d,
                           chunk=cfg.chunk_size)
        return _dense(cfg.hidden_dim, cfg, "out_proj")(y * nn.silu(z)), y


def _pairs(t):
    """(batch, seq, heads, d) -> heads (2i) and heads (2i + 1)."""
    return t[:, :, 0::2], t[:, :, 1::2]


class DifferentialAttention(nn.Module):
    """The ``S``, ``F`` and ``X`` mixers of published layer ``l``; returns
    (out, the keys and values it used)."""

    cfg: SambaYConfig
    l: int
    kind: str

    @nn.compact
    def __call__(self, u, shared_kv):
        cfg, d = self.cfg, self.cfg.head_dim
        heads, kv_heads = cfg.num_heads, cfg.num_kv_heads
        if self.kind == "X":
            q = nn.Dense(heads * d, dtype=cfg.dtype, name="query")(u)
            k, v = shared_kv
        else:
            qkv = nn.Dense((heads + 2 * kv_heads) * d, dtype=cfg.dtype,
                           name="qkv")(u)
            q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d],
                                axis=-1)
            k, v = (t.reshape(*t.shape[:2], kv_heads, d) for t in (k, v))
        q = q.reshape(*q.shape[:2], heads, d)
        window = cfg.window if self.kind == "S" else None
        attend = lambda *qkv: causal_attention(*qkv, window)  # noqa: E731
        with scope("attn_diff"):
            (q1, q2), (k1, k2), (v1, v2) = _pairs(q), _pairs(k), _pairs(v)
        maps = [[attend(q1, k1, v1), attend(q1, k1, v2)],
                [attend(q2, k2, v1), attend(q2, k2, v2)]]
        lam = [self.param(name, nn.initializers.normal(0.1), (d,),
                          jnp.float32)
               for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                            "lambda_k2")]
        scale = self.param("norm_scale", nn.initializers.ones, (2 * d,),
                           jnp.float32)
        start = lambda_init(self.l)
        with scope("attn_diff"):
            a1, a2 = (jnp.concatenate(m, axis=-1).astype(jnp.float32)
                      for m in maps)
            full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
                    - jnp.exp(jnp.sum(lam[2] * lam[3])) + start)
            o = a1 - full * a2
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                                  + cfg.ln_eps) * scale * (1.0 - start)
            o = o.reshape(*o.shape[:2], heads * d).astype(cfg.dtype)
        return nn.Dense(cfg.hidden_dim, dtype=cfg.dtype, name="out")(o), (k, v)


class GatedMemoryUnit(nn.Module):
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.cfg
        with scope("gmu"):
            gate = _dense(cfg.inner_dim, cfg, "in_proj")(u)
            return _dense(cfg.hidden_dim, cfg, "out_proj")(
                nn.silu(gate) * memory)


class SambaYLayer(nn.Module):
    """Published layer ``l``: ``(x, memory, shared_kv)`` in and out; a
    layer that makes the memory or the shared keys and values puts its own
    in their place."""

    cfg: SambaYConfig
    l: int

    @nn.compact
    def __call__(self, x, memory, shared_kv):
        cfg, l = self.cfg, self.l
        half = cfg.num_layers // 2
        kind = layer_kind(l, cfg.num_layers, cfg.mb_per_layer)
        norm = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=cfg.ln_eps, dtype=cfg.dtype, name=name)
        u = norm("mixer_norm")(x)
        if kind == "M":
            out, y = SelectiveMixer(cfg, name="mixer")(u)
            if l == half:
                memory = y
        elif kind == "G":
            out = GatedMemoryUnit(cfg, name="mixer")(u, memory)
        else:
            out, kv = DifferentialAttention(cfg, l, kind, name="mixer")(
                u, shared_kv)
            if l == half + 1:
                shared_kv = kv
        x = x + out
        x = x + _swiglu(norm("mlp_norm")(x), cfg.mlp_dim, cfg, "mlp_")
        return x, memory, shared_kv


class SambaYLM(nn.Module):
    """Tokens in, float32 logits out, over the rows of the embedding held
    here."""

    cfg: SambaYConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        check_layers(cfg)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
                         name="tok_embed")
        x, memory, shared_kv = embed(tokens), None, None
        layer = nn.remat(SambaYLayer) if cfg.remat else SambaYLayer
        for l in cfg.layers:
            x, memory, shared_kv = layer(cfg, l, name=f"layer_{l}")(
                x, memory, shared_kv)
        x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype,
                         name="final_norm")(x)
        return TiedHead(name="lm_head")(x, embed.embedding)
