"""Phase names inside the compiled step.

A step under :func:`horovod_tpu.jax.jit` is one XLA program; the engine's
timeline and telemetry stop at its dispatch. What the framework issues
*inside* it goes under one of a few fixed names, so that a device trace
can be read by what the program asked for and not by what XLA made of it.
``phase(name)`` is ``jax.named_scope(name)``: the name becomes part of
``metadata.op_name`` of every HLO instruction traced under it (xprof and
Perfetto show it with the op), and costs nothing at run time. There is no
switch. A new code path inside the step goes under one of these names or
adds one to the tuple (docs/observability.md, "Phase names in the
compiled step").
"""

from __future__ import annotations

import functools

import jax
from jax.experimental.xla_metadata import set_xla_metadata

#: pack: gradient tree -> the collective's operand (ravel, per-dtype
#: concatenate, pad, compress / quantize). allreduce: the collective(s).
#: unpack: slices and reshapes back to leaves, decompress, the average.
#: numerics: the in-step gradient health statistics. optimizer: the inner
#: optax update.
PHASES = ("hvd_pack", "hvd_allreduce", "hvd_unpack", "hvd_numerics",
          "hvd_optimizer")

#: Pallas kernels' ``name=``. The flash names keep the ``_fwd_bhsd`` /
#: ``_bwd_bhsd`` of the jitted functions round them, which readers of
#: device traces already match. The fused backward kernel, which makes
#: dq in the dK/dV kernel's pass, holds that kernel's name whole, so a
#: reader that knows three flash kernels counts it as the dK/dV one and
#: reads no dQ kernel where every layer took it.
KERNELS = ("flash_fwd_bhsd", "flash_dq_bwd_bhsd", "flash_dkv_bwd_bhsd",
           "fused_flash_dkv_bwd_bhsd", "xent_fwd", "xent_dx", "xent_dw")

#: What a model's own layers issue, where a device trace should tell the
#: parts of one layer apart (``scope(name)``): the expert layer of
#: ``parallel/moe.py`` (``moe_route``: router scores, softmax, top-k and
#: the weights; ``moe_dispatch``: counting the assignments, laying them
#: out by expert and gathering the rows; ``moe_experts``: the grouped
#: products over the experts held, forward and backward; ``moe_combine``:
#: weighting the rows and adding them back; ``moe_shared``: the shared
#: expert), the attention of ``models/decoder.py`` (``attn_rope``: the
#: rotary positions; ``attn_gate``: the gate on the heads' output) and the
#: blocks of ``models/hybrid.py``: its state-space mixer (``ssm_conv``:
#: the causal depthwise convolution and its activation; ``ssm_scan``:
#: everything of ``ops/ssd.py``, forward and backward; ``ssm_norm``: the
#: gated group norm) and the two latent projections round its routed
#: experts (``moe_latent``), and the mixers of ``models/sambay.py``
#: (``sel_scan``: everything of ``ops/selective_scan.py``, forward and
#: backward; ``attn_diff``: what differential attention does outside its
#: kernels and projections, the pairing of heads, lambda, the difference
#: and the norm over a pair; ``gmu``: the whole gated memory unit, its two
#: projections and the gate ``silu(.) * memory`` between them, which the
#: compiler fuses into them; its Mamba mixer's convolution goes under
#: ``ssm_conv``), and the decoder's gated short convolution and query-key
#: norm (``short_conv``: what lies between the operator's two projections,
#: the product of ``B`` and ``x``, the causal sum of the taps and the
#: product with ``C``; ``attn_qk_norm``: the per-head RMSNorm of the
#: queries and the keys). Kept apart from ``PHASES``,
#: which is the framework's own vocabulary and which readers hold a copy
#: of.
MODEL_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                "moe_shared", "attn_rope", "attn_gate", "ssm_conv",
                "ssm_scan", "ssm_norm", "moe_latent", "sel_scan",
                "attn_diff", "gmu", "short_conv", "attn_qk_norm")

#: Stamped on every op of a ``hvd.jax.jit`` step as the frontend
#: attribute ``hvd_phases``. jax's persistent compile cache keys on the
#: program without its debug info, so a program that differs from a
#: cached one only in names would be served the cached executable, old
#: names and all; the attribute is in the key. Bump it with ``PHASES``,
#: ``KERNELS``, ``MODEL_SCOPES`` or a move of where a name is emitted.
VOCABULARY_VERSION = "7"


def phase(name: str):
    """``jax.named_scope(name)`` for a name of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}: the vocabulary is "
                         f"{PHASES} (horovod_tpu/common/phases.py)")
    return jax.named_scope(name)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`MODEL_SCOPES`."""
    if name not in MODEL_SCOPES:
        raise ValueError(f"unknown scope {name!r}: the model scopes are "
                         f"{MODEL_SCOPES} (horovod_tpu/common/phases.py)")
    return jax.named_scope(name)


def stamped(fn):
    """``fn``, traced with :data:`VOCABULARY_VERSION` on every op."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with set_xla_metadata(hvd_phases=VOCABULARY_VERSION):
            return fn(*args, **kwargs)

    return traced
