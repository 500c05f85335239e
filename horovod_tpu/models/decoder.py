"""A causal decoder whose layers differ: the sparse-expert, mixed-attention
block of today's large open models, built from a tuple of layer specs.

Each layer names its attention (``full`` causal or a ``window`` of the
last w keys), its own number of query heads over a fixed number of
key-value heads (grouped heads), and its MLP (``dense`` SwiGLU, or
``sparse``: a top-k softmax router over all experts, the experts this
chip holds, and a shared expert). Pre-norm with RMSNorm, no bias, rotary
positions in two forms (YaRN frequencies on part of the head for full
layers, the default on the whole head for window layers), a sigmoid gate
per head on the attention output, an untied float32 head. bf16 compute,
f32 parameters, ``remat`` per layer.

Attention goes through :func:`horovod_tpu.ops.flash_attention.
flash_attention` (``causal=True, window=...``, k and v at their own head
count); the expert layer is :func:`horovod_tpu.parallel.moe.
expert_share_layer`: under expert parallelism a chip holds ``experts_held``
of the ``num_experts`` the router scores, from ``first_expert`` on, and
computes their part of the result. On one chip no exchange is traced.

Modules are named as ``TransformerLM``'s are (``tok_embed``, ``layer_N``,
``final_norm``, ``lm_head``), which is what readers of device traces go
by; the parts of a layer go under ``common.phases.MODEL_SCOPES``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.common.phases import scope
from horovod_tpu.parallel.moe import expert_share_layer


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attention: str  # "full" | "window"
    num_heads: int  # query heads of this layer
    mlp: str        # "dense" | "sparse"


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary positions of one attention kind. ``rotary_dim`` leading dims
    of the head are rotated, halves paired as (x[:r/2], x[r/2:]).
    ``yarn_factor`` None is the default form (theta^(-2i/r))."""

    theta: float
    rotary_dim: int
    yarn_factor: Optional[float] = None
    original_max_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_dim: int
    head_dim: int
    num_kv_heads: int
    layers: Tuple[LayerSpec, ...]
    mlp_dim: int                 # the dense layers' SwiGLU width
    window: int
    rope_full: Optional[RopeSpec]    # None: no rotary positions
    rope_window: Optional[RopeSpec]
    num_experts: int = 0         # the router's outputs: all experts
    experts_held: int = 0        # of them, held on this chip
    first_expert: int = 0
    top_k: int = 1
    expert_dim: int = 0
    shared_dim: int = 0
    routed_scaling: float = 1.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False
    head_gate: bool = True       # the sigmoid gate a head on the output


def rope_inv_freq(spec: RopeSpec):
    """(rotary_dim / 2,) float32 inverse frequencies."""
    half = spec.rotary_dim // 2
    freq = spec.theta ** (jnp.arange(half, dtype=jnp.float32)
                          * 2.0 / spec.rotary_dim)
    if spec.yarn_factor is None:
        return 1.0 / freq

    def correction(rotations):  # the dim that turns `rotations` times
        c = (spec.rotary_dim * math.log(
            spec.original_max_len / (2 * math.pi * rotations))
            / (2 * math.log(spec.theta)))
        return min(max(c, 0), spec.rotary_dim - 1)

    low = math.floor(correction(spec.beta_fast))
    high = math.ceil(correction(spec.beta_slow))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / freq + ramp / (spec.yarn_factor * freq)


def apply_rope(x, spec: RopeSpec):
    """Rotate the leading ``rotary_dim`` dims of (batch, seq, heads, d)."""
    with scope("attn_rope"):
        s, r = x.shape[1], spec.rotary_dim
        angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
                 * rope_inv_freq(spec)[None, :])
        cos = (jnp.cos(angle) * spec.attention_factor)[None, :, None, :]
        sin = (jnp.sin(angle) * spec.attention_factor)[None, :, None, :]
        x1 = x[..., :r // 2].astype(jnp.float32)
        x2 = x[..., r // 2:r].astype(jnp.float32)
        turned = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate(
            [turned.astype(x.dtype), x[..., r:]], axis=-1)


def _dense(features, cfg, name, **kw):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name,
                    **kw)


def _swiglu(x, width, cfg, prefix):
    gate = _dense(width, cfg, f"{prefix}gate")(x)
    up = _dense(width, cfg, f"{prefix}up")(x)
    return _dense(cfg.hidden_dim, cfg, f"{prefix}down")(nn.silu(gate) * up)


def stack_counters(kept, elsewhere, held: int):
    """The routing counters of a call, stacked over its expert layers in
    order: ``expert_kept`` int32 (layers, held) and ``expert_elsewhere``
    int32 (layers,); empty where the model has no such layer."""
    return {"expert_kept": (jnp.stack(kept) if kept
                            else jnp.zeros((0, max(held, 1)), jnp.int32)),
            "expert_elsewhere": (jnp.stack(elsewhere) if elsewhere
                                 else jnp.zeros((0,), jnp.int32))}


def causal_attention(q, k, v, window=None):
    """Causal attention of (batch, seq, heads, head size) through the
    flash kernels, over the last ``window`` keys where one is given;
    ``k`` and ``v`` may carry a divisor of ``q``'s heads (query head h
    reads key-value head h // group)."""
    # pallas loads with the first decoder traced, not with the zoo
    from horovod_tpu.ops import flash_attention as fa

    return fa.flash_attention(q, k, v, causal=True, window=window)


class GroupedAttention(nn.Module):
    cfg: DecoderConfig
    spec: LayerSpec

    @nn.compact
    def __call__(self, h):
        cfg, spec = self.cfg, self.spec
        proj = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, cfg.head_dim), use_bias=False, dtype=cfg.dtype,
            name=name)
        q = proj(spec.num_heads, "query")(h)
        k = proj(cfg.num_kv_heads, "key")(h)
        v = proj(cfg.num_kv_heads, "value")(h)
        windowed = spec.attention == "window"
        rope = cfg.rope_window if windowed else cfg.rope_full
        if rope is not None:
            q, k = apply_rope(q, rope), apply_rope(k, rope)
        out = causal_attention(q, k, v, cfg.window if windowed else None)
        if cfg.head_gate:
            with scope("attn_gate"):
                gate = nn.sigmoid(_dense(spec.num_heads, cfg, "gate")(h))
                out = out * gate[..., None]
        return nn.DenseGeneral(cfg.hidden_dim, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, name="out")(out)


class SparseMLP(nn.Module):
    """The shared expert plus this chip's share of the routed experts."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        per_expert = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (cfg.hidden_dim, cfg.num_experts), jnp.float32)
        held = (cfg.experts_held, cfg.hidden_dim, cfg.expert_dim)
        w_gate = self.param("experts_gate", per_expert, held, jnp.float32)
        w_up = self.param("experts_up", per_expert, held, jnp.float32)
        w_down = self.param("experts_down", per_expert,
                            (held[0], held[2], held[1]), jnp.float32)
        with scope("moe_shared"):
            shared = _swiglu(h, cfg.shared_dim, cfg, "shared_")
        b, s, d = h.shape
        routed, counts = expert_share_layer(
            h.reshape(b * s, d), router, w_gate.astype(cfg.dtype),
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            first_expert=cfg.first_expert, top_k=cfg.top_k,
            scaling=cfg.routed_scaling)
        return shared + routed.reshape(b, s, d), counts


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    spec: LayerSpec

    @nn.compact
    def __call__(self, x):
        cfg, spec = self.cfg, self.spec
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=cfg.rms_eps, dtype=cfg.dtype, name=name)
        x = x + GroupedAttention(cfg, spec, name="attention")(
            norm("attention_norm")(x))
        h = norm("mlp_norm")(x)
        if spec.mlp == "sparse":
            y, counts = SparseMLP(cfg, name="moe")(h)
            return x + y, counts
        return x + _swiglu(h, cfg.mlp_dim, cfg, "mlp_"), None


class Decoder(nn.Module):
    """Tokens in, float32 logits out; with ``return_counters`` also, per
    sparse layer in order, the assignments each held expert got in this
    call (int32 (layers, held)) and those routed to other chips' experts
    (int32 (layers,))."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, tokens, return_counters: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
                     name="tok_embed")(tokens)
        layer = nn.remat(DecoderLayer) if cfg.remat else DecoderLayer
        kept, elsewhere = [], []
        for i, spec in enumerate(cfg.layers):
            x, counts = layer(cfg, spec, name=f"layer_{i}")(x)
            if counts is not None:
                kept.append(counts[0])
                elsewhere.append(counts[1])
        x = nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                       name="final_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          name="lm_head")(x)
        if not return_counters:
            return logits
        return logits, stack_counters(kept, elsewhere, cfg.experts_held)
