"""Do the pallas kernels compile for the chip, and are they and the
chunked state-space scan right there?

    chiprun -- python -m horovod_tpu.ops.kernel_check

Compiles every pallas kernel of this package with ``interpret=False`` at
the shapes the models use and compares it with its plain-XLA reference:
flash attention forward and backward (the fused kernel and, over its
byte rule, the two) against masked softmax in f32 (the reference at full
f32 matmul precision — the TPU default would round it to bf16), with f32
inputs and with bf16 ones, and the fused LM-head cross-entropy against
the chunked XLA scan; and the chunked state-space scan of ``ops/ssd.py``
(plain XLA, its backward pass written by hand) against the recurrence one
position after the other in f32, values and all six gradients, at the
hybrid cell's shape. The f32 tolerances are the ones
``tests/test_flash_attention.py`` uses in interpret mode; bf16 inputs are
held to a share of each result's own scale, as the fused loss is. Exits
nonzero on the first mismatch, or off a TPU. The CPU tier runs the same
checks at toy shapes in interpret mode (tests/test_flash_attention.py).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops.chunked_loss import (
    chunked_softmax_cross_entropy,
    fused_softmax_cross_entropy,
)
from horovod_tpu.ops.flash_attention import (
    flash_attention,
    fused_backward_fits,
)
from horovod_tpu.ops.ssd import ssd_scan

# (batch, seq, heads, head_dim, causal, window, key-value heads):
# BERT-base's attention, a long causal sequence, and the decoder cell's
# two kinds of layer: a sliding window under 72 query heads over 8, and
# full causal attention under 48 over 8. In bf16 every one takes the
# fused backward kernel; the decoder's in f32 are over its byte rule and
# take the two kernels (``fused_backward_fits``; each line says which).
FLASH_SHAPES = ((8, 512, 12, 64, False, None, 12),
                (2, 2048, 12, 64, True, None, 12),
                (1, 8192, 72, 128, True, 512, 8),
                (1, 8192, 48, 128, True, None, 8))
# Worst error of a bf16 run as a share of the result's largest entry:
# the output and the probabilities round to eight bits of mantissa.
BF16_SHARE = 2e-2
# (tokens, hidden, vocab): BERT-base bs8 x seq512 into its LM head.
LOSS_SHAPE = (4096, 768, 30522)
# (batch, positions, heads, head size, groups, state size, chunk): one
# chip's share of the hybrid cell's state-space mixer.
SSD_SHAPE = (1, 8192, 32, 64, 2, 128, 128)
# Worst error of the scan in f32 as a share of the result's largest
# entry: both sides are f32 throughout and differ by the order of sums
# over up to 8,192 positions and by the TPU's f32 ``exp``.
SSD_F32_SHARE = 1e-4


def _value_and_grads(fn, **kw):
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), **kw))


def plain_attention(q, k, v, causal, window=None):
    """Masked softmax attention one query head at a time, so that the
    scores held at once are (batch, s, s) and not (batch, heads, s, s);
    query head h reads key-value head h // group."""
    s, h, d = q.shape[1:]
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    keep = (j <= i) if causal else jnp.ones((s, s), bool)
    if window is not None:
        keep &= i - j < window

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh)

    by_head = [jnp.moveaxis(t, 2, 0) for t in (q, k, v)]
    by_head[1:] = [jnp.repeat(t, h // k.shape[2], axis=0)
                   for t in by_head[1:]]
    return jnp.moveaxis(jax.lax.map(head, tuple(by_head)), 0, 2)


def check_flash(b, s, h, d, causal, window=None, kv_heads=None,
                dtype=jnp.float32, interpret=False, **blocks):
    """Forward and the three gradients against :func:`plain_attention`;
    returns each result's worst error as a share of its largest entry."""
    kv_heads = kv_heads or h
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, s, heads, d), dtype)
               for heads in (h, kv_heads, kv_heads))

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            interpret=interpret,
                            **blocks).astype(jnp.float32)
        return jnp.sum(o * o), o

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            o = plain_attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                                causal, window)
        return jnp.sum(o * o), o

    (_, out), grads = _value_and_grads(flash, has_aux=True)(q, k, v)
    (_, out_ref), grads_ref = _value_and_grads(ref, has_aux=True)(q, k, v)
    # f32: element by element at the interpret-mode tests' tolerances;
    # dk and dv of grouped heads sum ``group`` heads' gradients, each
    # held to the absolute tolerance, so the sum to ``group`` times it.
    group = h // kv_heads
    tolerances = {"out": (2e-4, 2e-5), "dq": (2e-3, 2e-4),
                  "dk": (2e-3, 2e-4 * group), "dv": (2e-3, 2e-4 * group)}
    shares = {}
    for got, want, name in zip((out,) + grads, (out_ref,) + grads_ref,
                               tolerances):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        shares[name] = float(np.abs(got - want).max() / np.abs(want).max())
        if dtype == jnp.float32:
            rtol, atol = tolerances[name]
            np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                       err_msg=name)
        else:
            assert shares[name] < BF16_SHARE, (
                f"{name}: max error {shares[name]:.2e} of its scale")
    return shares


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


def sequential_scan(x, dt, a, b, c, d, segment=128):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t, y_t = C_t . h_t + D x_t
    one position after the other in f32, head h with group h // (heads /
    groups); the positions in checkpointed segments, so that the backward
    pass keeps a segment's states and not all 8,192."""
    x, dt, b, c = (t.astype(jnp.float32) for t in (x, dt, b, c))
    bsz, t, heads, p = x.shape
    groups = b.shape[2]
    x = x.reshape(bsz, t, groups, heads // groups, p)
    dt = dt.reshape(bsz, t, groups, -1)
    a, d = a.reshape(groups, -1), d.reshape(groups, -1)

    def position(h, at):  # h (batch, G, K, P, N)
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None])
        return h, (h * c_t[:, :, None, None]).sum(-1) + d[..., None] * x_t

    @jax.checkpoint
    def run(h, part):
        return jax.lax.scan(position, h, part)

    segment = min(segment, t)
    parts = jax.tree.map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape(
            t // segment, segment, bsz, *v.shape[2:]), (x, dt, b, c))
    h0 = jnp.zeros((bsz, groups, heads // groups, p, b.shape[3]))
    _, y = jax.lax.scan(run, h0, parts)
    return jnp.moveaxis(y.reshape(t, bsz, heads, p), 0, 1)


def check_ssd(bsz, t, heads, p, groups, n, chunk, dtype=jnp.float32):
    """``ssd_scan`` and its six gradients against :func:`sequential_scan`
    on the same (rounded) inputs; returns each result's worst error as a
    share of its largest entry."""
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    args = (jax.random.normal(ks[0], (bsz, t, heads, p), dtype),
            # step sizes and decay rates over the model's initial ranges
            jnp.exp(jax.random.uniform(ks[1], (bsz, t, heads),
                                       minval=np.log(1e-3),
                                       maxval=np.log(1e-1))),
            -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (bsz, t, groups, n), dtype),
            jax.random.normal(ks[4], (bsz, t, groups, n), dtype),
            jax.random.normal(ks[5], (heads,)))
    mix = jax.random.normal(ks[6], (bsz, t, heads, p))

    def total(fn):
        def loss(*a):  # the six arguments, then the mix
            y = fn(*a[:6]).astype(jnp.float32)
            return jnp.sum(y * a[6]), y
        return jax.jit(jax.value_and_grad(loss, argnums=range(6),
                                          has_aux=True))

    (_, out), grads = total(
        lambda *a: ssd_scan(*a, chunk=chunk))(*args, mix)
    (_, out_ref), grads_ref = total(sequential_scan)(*args, mix)
    limit = SSD_F32_SHARE if dtype == jnp.float32 else BF16_SHARE
    shares = {}
    for name, got, want in zip(("y", "dx", "ddt", "da", "db", "dc", "dd"),
                               (out,) + grads, (out_ref,) + grads_ref):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        shares[name] = float(np.abs(got - want).max() / np.abs(want).max())
        assert shares[name] < limit, (
            f"{name}: max error {shares[name]:.2e} of its scale, "
            f"limit {limit:.0e}")
    return shares


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_check: needs a TPU, found platform={dev.platform!r}",
              file=sys.stderr)
        return 1
    for shape in FLASH_SHAPES:
        for dtype in (jnp.float32, jnp.bfloat16):
            shares = check_flash(*shape, dtype=dtype)
            worst = ", ".join(f"{n} {e:.1e}" for n, e in shares.items())
            backward = ("fused" if fused_backward_fits(shape[1], shape[3],
                                                       dtype)
                        else "two kernels")
            print(f"flash_attention fwd+bwd {shape} {dtype.__name__}: "
                  f"compiled, matches the f32 reference (backward: "
                  f"{backward}; worst error by its scale: {worst}; "
                  f"{dev.device_kind})", flush=True)
    for dtype in (jnp.float32, jnp.bfloat16):
        shares = check_ssd(*SSD_SHAPE, dtype=dtype)
        worst = ", ".join(f"{n} {e:.1e}" for n, e in shares.items())
        print(f"ssd_scan fwd+bwd {SSD_SHAPE} {dtype.__name__}: matches the "
              f"sequential f32 recurrence (worst error by its scale: "
              f"{worst}; {dev.device_kind})", flush=True)
    check_fused_loss(*LOSS_SHAPE)
    print(f"fused_softmax_cross_entropy fwd+bwd {LOSS_SHAPE}: compiled, "
          f"matches the chunked scan ({dev.device_kind})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
