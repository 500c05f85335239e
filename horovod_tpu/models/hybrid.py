"""A causal language model whose blocks are one mixer each, of three kinds:
the hybrid state-space / attention / sparse-expert stack of today's large
open models, built from a pattern string.

Every block is ``x + mixer(RMSNorm(x))``; ``pattern`` names the mixer of
each block by one character:

* ``M``, a state-space mixer: one input projection to ``[z | xBC | dt]``,
  a causal depthwise convolution with bias and a SiLU on ``xBC``, the
  selective scan of :func:`horovod_tpu.ops.ssd.ssd_scan` (a scalar decay a
  head, ``B`` and ``C`` shared by the heads of a group, computed by
  chunks), ``y * silu(z)`` through an RMSNorm whose mean square is taken
  a group at a time, and an output projection. No bias but the
  convolution's.
* ``*``, full causal attention over grouped key-value heads, without
  rotary positions or a gate: :class:`horovod_tpu.models.decoder.
  GroupedAttention` through the flash kernels.
* ``E``, a latent sparse-expert block: a sigmoid router over ALL experts
  at the full width whose correction bias moves the choice only
  (:func:`horovod_tpu.parallel.moe.sigmoid_route`), a projection into a
  narrower latent, this chip's share of the routed experts there
  (two matrices and ``relu^2``, no gate;
  :func:`horovod_tpu.parallel.moe.expert_share_layer`), a projection
  back, and a shared expert of the same form at the full width.

The head counts (state-space heads and their groups, query and key-value
heads) and the experts held are what this chip holds: under tensor
parallelism by heads and expert parallelism they are a share of the
model's, and the block's result is this chip's part of it. On one chip no
exchange is traced and nothing stands in for the absent chips.

bf16 compute, f32 parameters, ``remat`` per block, an untied float32
head. Modules are named ``tok_embed``, ``block_N``, ``final_norm``,
``lm_head``; the parts of a block go under ``common.phases.MODEL_SCOPES``
(``ssm_conv``, ``ssm_scan``, ``ssm_norm``, ``moe_latent`` and the expert
layer's five).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.common.phases import scope
from horovod_tpu.models.decoder import (DecoderConfig, GroupedAttention,
                                        LayerSpec, _dense, causal_conv,
                                        stack_counters)
from horovod_tpu.ops.ssd import ssd_scan
from horovod_tpu.parallel.moe import expert_share_layer, sigmoid_route

MIXERS = "ME*"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_dim: int
    pattern: str                 # a character of MIXERS a block
    # M: heads and groups held, head and state size
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    dt_min: float = 1e-3         # the step sizes the bias starts from
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    # *: query and key-value heads held
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    # E
    num_experts: int = 0         # the router's outputs: all experts
    experts_held: int = 0        # of them, held on this chip
    first_expert: int = 0
    top_k: int = 1
    latent_dim: int = 0          # the routed experts' input and output
    expert_dim: int = 0
    shared_dim: int = 0
    routed_scaling: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False


def _relu2(x):
    r = nn.relu(x)
    return r * r


def _dt_bias_init(cfg):
    """The inverse softplus of step sizes drawn log-uniformly between
    ``dt_min`` and ``dt_max`` and floored at ``dt_floor``."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                     + math.log(cfg.dt_min))
        dt = jnp.maximum(dt, cfg.dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def causal_conv_silu(x, taps, bias, dtype):
    """A depthwise causal convolution over (batch, t, channels)
    (:func:`horovod_tpu.models.decoder.causal_conv`), plus ``bias``, and
    its SiLU, under ``ssm_conv``. Float32 inside, ``dtype`` out."""
    with scope("ssm_conv"):
        return nn.silu(bias + causal_conv(x, taps)).astype(dtype)


class SSMMixer(nn.Module):
    """The state-space mixer over the heads and groups held."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        heads, p = cfg.ssm_heads, cfg.ssm_head_dim
        groups, n = cfg.ssm_groups, cfg.ssm_state
        inner, bc = heads * p, groups * n
        zxbcdt = _dense(2 * inner + 2 * bc + heads, cfg, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)

        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (cfg.conv_kernel, inner + 2 * bc), jnp.float32)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (inner + 2 * bc,), jnp.float32)
        xbc = causal_conv_silu(xbc, conv_w, conv_b, cfg.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)

        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,),
                             jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        d = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        lead = x.shape[:2]
        y = ssd_scan(x.reshape(*lead, heads, p),
                     nn.softplus(dt.astype(jnp.float32) + dt_bias),
                     -jnp.exp(a_log), b.reshape(*lead, groups, n),
                     c.reshape(*lead, groups, n), d, chunk=cfg.chunk_size)

        scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                           jnp.float32)
        with scope("ssm_norm"):
            gated = (y.reshape(*lead, inner).astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32)))
            by_group = gated.reshape(*lead, groups, inner // groups)
            by_group = by_group * jax.lax.rsqrt(
                (by_group * by_group).mean(-1, keepdims=True) + cfg.rms_eps)
            y = (by_group.reshape(*lead, inner) * scale).astype(cfg.dtype)
        return _dense(cfg.hidden_dim, cfg, "out_proj")(y)


class LatentExperts(nn.Module):
    """The shared expert plus this chip's share of the routed experts in
    their latent; returns ``(y, (kept, elsewhere))``."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        per_expert = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (cfg.hidden_dim, cfg.num_experts), jnp.float32)
        # moves the choice of experts only: no gradient, zeros at the start
        bias = self.param("router_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        w_up = self.param(
            "experts_up", per_expert,
            (cfg.experts_held, cfg.latent_dim, cfg.expert_dim), jnp.float32)
        w_down = self.param(
            "experts_down", per_expert,
            (cfg.experts_held, cfg.expert_dim, cfg.latent_dim), jnp.float32)
        with scope("moe_shared"):
            shared = _dense(cfg.hidden_dim, cfg, "shared_down")(
                _relu2(_dense(cfg.shared_dim, cfg, "shared_up")(h)))
        b, s, d = h.shape
        with scope("moe_latent"):
            z = _dense(cfg.latent_dim, cfg, "latent_in")(h)
        routed, counts = expert_share_layer(
            z.reshape(b * s, cfg.latent_dim), router, None,
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            first_expert=cfg.first_expert, top_k=cfg.top_k,
            scaling=cfg.routed_scaling, route=sigmoid_route(bias),
            router_x=h.reshape(b * s, d))
        with scope("moe_latent"):
            routed = _dense(cfg.hidden_dim, cfg, "latent_out")(
                routed.reshape(b, s, cfg.latent_dim))
        return shared + routed, counts


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)
        if self.kind == "M":
            return x + SSMMixer(cfg, name="mixer")(h), None
        if self.kind == "*":
            attention = DecoderConfig(
                vocab_size=cfg.vocab_size, hidden_dim=cfg.hidden_dim,
                head_dim=cfg.head_dim, num_kv_heads=cfg.num_kv_heads,
                layers=(), mlp_dim=0, window=0, rope_full=None,
                rope_window=None, dtype=cfg.dtype, head_gate=False)
            return x + GroupedAttention(
                attention, LayerSpec("full", cfg.num_heads, "none"),
                name="mixer")(h), None
        y, counts = LatentExperts(cfg, name="mixer")(h)
        return x + y, counts


class HybridLM(nn.Module):
    """Tokens in, float32 logits out; with ``return_counters`` also, per
    ``E`` block in order, the assignments each held expert got in this
    call (int32 (blocks, held)) and those routed to other chips' experts
    (int32 (blocks,))."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens, return_counters: bool = False):
        cfg = self.cfg
        unknown = set(cfg.pattern) - set(MIXERS)
        if unknown:
            raise ValueError(f"pattern {cfg.pattern!r} names mixers "
                             f"{sorted(unknown)}: want characters of "
                             f"{MIXERS!r}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
                     name="tok_embed")(tokens)
        block = nn.remat(HybridBlock) if cfg.remat else HybridBlock
        kept, elsewhere = [], []
        for i, kind in enumerate(cfg.pattern):
            x, counts = block(cfg, kind, name=f"block_{i}")(x)
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
