"""Do the pallas kernels compile for the chip, and are they right there?

    chiprun -- python -m horovod_tpu.ops.kernel_check

Compiles every pallas kernel of this package with ``interpret=False`` at
the shapes the models use and compares it with its plain-XLA reference:
flash attention forward and both backward kernels against
``models.transformer`` attention in f32 (the reference at full f32 matmul
precision — the TPU default would round it to bf16), and the fused
LM-head cross-entropy against the chunked XLA scan. Tolerances are the
ones ``tests/test_flash_attention.py`` uses in interpret mode. Exits
nonzero on the first mismatch, or off a TPU. The CPU tier runs the same
checks at toy shapes in interpret mode (tests/test_flash_attention.py).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.transformer import (
    causal_attention,
    dot_product_attention,
)
from horovod_tpu.ops.chunked_loss import (
    chunked_softmax_cross_entropy,
    fused_softmax_cross_entropy,
)
from horovod_tpu.ops.flash_attention import flash_attention

# (batch, seq, heads, head_dim, causal): BERT-base's attention, and a
# long causal sequence.
FLASH_SHAPES = ((8, 512, 12, 64, False), (2, 2048, 12, 64, True))
# (tokens, hidden, vocab): BERT-base bs8 x seq512 into its LM head.
LOSS_SHAPE = (4096, 768, 30522)


def _value_and_grads(fn, **kw):
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), **kw))


def check_flash(b, s, h, d, causal, interpret=False, **blocks):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
               for _ in range(3))
    ref_fn = causal_attention if causal else dot_product_attention

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=interpret,
                            **blocks)
        return jnp.sum(o * o), o

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            o = ref_fn(q, k, v)
        return jnp.sum(o * o), o

    (_, out), grads = _value_and_grads(flash, has_aux=True)(q, k, v)
    (_, out_ref), grads_ref = _value_and_grads(ref, has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)
    for got, want, name in zip(grads, grads_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4, err_msg=f"d{name}")


def check_fused_loss(n, hidden, vocab, interpret=False, **blocks):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(n, hidden) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(hidden, vocab) * 0.02, jnp.float32)
    b = jnp.asarray(rng.randn(vocab) * 0.01, jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, (n,)), jnp.int32)

    def fused(x, w, b):
        return fused_softmax_cross_entropy(
            x, w, b, labels, interpret=interpret, **blocks).mean()

    def chunked(x, w, b):
        return chunked_softmax_cross_entropy(x, w, b, labels).mean()

    loss, grads = _value_and_grads(fused)(x, w, b)
    loss_ref, grads_ref = _value_and_grads(chunked)(x, w, b)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
    # Both paths feed the MXU bf16 operands and dx leaves in bf16, so
    # element-wise tolerances would be about bf16 rounding (one ulp is
    # 4e-3); bound the worst error against the gradient's own scale.
    for got, want, name in zip(grads, grads_ref, ("dx", "dw", "db")):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        worst = np.abs(got - want).max() / np.abs(want).max()
        assert worst < 1e-2, f"{name}: max error {worst:.2e} of its scale"


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_check: needs a TPU, found platform={dev.platform!r}",
              file=sys.stderr)
        return 1
    for shape in FLASH_SHAPES:
        check_flash(*shape)
        print(f"flash_attention fwd+bwd {shape}: compiled, matches f32 "
              f"reference ({dev.device_kind})", flush=True)
    check_fused_loss(*LOSS_SHAPE)
    print(f"fused_softmax_cross_entropy fwd+bwd {LOSS_SHAPE}: compiled, "
          f"matches the chunked scan ({dev.device_kind})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
