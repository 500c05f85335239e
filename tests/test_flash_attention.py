"""Flash attention kernel vs stock attention (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (
    causal_attention,
    dot_product_attention,
)
from horovod_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_causal,
)


def _qkv(b=2, s=64, h=2, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = (causal_attention if causal else dot_product_attention)(q, k, v)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_multiblock_vs_singleblock():
    q, k, v = _qkv(s=32)
    a = flash_attention(q, k, v, block_q=32, block_k=32)
    b = flash_attention(q, k, v, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    """jax.grad through the kernel (custom_vjp flash backward) vs autodiff
    through the stock attention (the oracle pattern of the reference's
    gradient tests, test_tensorflow.py:321-346 / test_torch.py:351-403)."""
    import jax

    q, k, v = _qkv(b=1, s=32, h=2, d=8)
    ref_fn = causal_attention if causal else dot_product_attention

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=16)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = ref_fn(q, k, v)
        return jnp.sum(o * o)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def test_flash_value_and_grad_trains():
    """A training step through attention_fn=flash_attention must run and
    reduce the loss (the round-1 kernel crashed under jax.grad)."""
    import jax
    import optax

    from horovod_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=32, num_layers=1, num_heads=2, hidden_dim=16,
        mlp_dim=32, max_len=16, dtype=jnp.float32, dropout_rate=0.0,
        causal=True,
        attention_fn=lambda q, k, v, bias=None: flash_attention(
            q, k, v, bias, causal=True, block_q=8, block_k=8))
    m = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 16)))
    params = m.init(jax.random.PRNGKey(0), tokens)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = m.apply(p, tokens)
            tgt = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_flash_grad_multiblock_consistency():
    """Gradients must not depend on the block decomposition."""
    import jax

    q, k, v = _qkv(b=1, s=32, h=1, d=8)

    def loss(q, k, v, bq, bk):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=bq, block_k=bk) ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 32, 32)
    g2 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 8, 16)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_rejects_bias_and_bad_blocks():
    q, k, v = _qkv(s=16)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, bias=jnp.zeros((1,)))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=10)


def test_flash_default_blocks_snap_to_seq():
    """Default block sizes must handle any seq that has a reasonable
    divisor (e.g. 96 = 3*32, not a multiple of the 128 tile)."""
    q, k, v = _qkv(s=96)
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v)  # no explicit blocks
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_as_model_attention_fn():
    """The kernel slots into the transformer via attention_fn."""
    import jax

    from horovod_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, hidden_dim=16,
        mlp_dim=32, max_len=16, dtype=jnp.float32, dropout_rate=0.0,
        causal=True, attention_fn=flash_attention_causal)
    m = TransformerLM(cfg)
    tokens = jnp.arange(16)[None] % 64
    variables = m.init(jax.random.PRNGKey(0), tokens)
    out_flash = m.apply(variables, tokens)

    cfg_ref = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, hidden_dim=16,
        mlp_dim=32, max_len=16, dtype=jnp.float32, dropout_rate=0.0,
        causal=True)
    out_ref = TransformerLM(cfg_ref).apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               rtol=2e-3, atol=2e-4)


def test_flash_in_ulysses():
    """Flash kernel inside Ulysses sequence parallelism."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from horovod_tpu import parallel

    devs = jax.devices()[:4]
    mesh = parallel.hybrid_mesh({"sp": 4}, devs)
    q, k, v = _qkv(b=1, s=32, h=4, d=8)
    ref = dot_product_attention(q, k, v)

    def body(q, k, v):
        return parallel.ulysses_attention(
            q, k, v, "sp",
            attention_fn=lambda q, k, v, bias: flash_attention(
                q, k, v, bias, block_q=8, block_k=8))

    spec = P(None, "sp", None, None)
    out = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_auto_block_is_always_a_legal_tpu_tile():
    """Mosaic takes a second-to-last block dim only if it is a multiple
    of 8 or the whole array dim: _auto_block returns nothing else, and
    raises for a sequence that has no such divisor."""
    from horovod_tpu.ops.flash_attention import _auto_block

    for s in range(1, 2100):
        try:
            blk = _auto_block(s)
        except ValueError as exc:
            assert "multiple of 8" in str(exc)
            assert s > 512 and not any(
                s % c == 0 for c in range(8, 513, 8)), s
            continue
        assert s % blk == 0 and blk <= 512, (s, blk)
        assert blk % 8 == 0 or blk == s, (s, blk)
    assert _auto_block(512) == 512 and _auto_block(2048) == 512
    assert _auto_block(96) == 96 and _auto_block(1000) == 200
    with pytest.raises(ValueError, match="pad the sequence"):
        _auto_block(514)


def test_unaligned_explicit_blocks_rejected_when_compiling():
    q, k, v = _qkv(s=24)
    with pytest.raises(ValueError, match="multiples of 8"):
        flash_attention(q, k, v, block_q=12, block_k=12, interpret=False)


def test_interpret_fallback_is_recorded_once(caplog):
    import logging

    from horovod_tpu.ops import pallas_mode

    pallas_mode.INTERPRETED.discard("probe_kernel")
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        assert pallas_mode.resolve_interpret(None, "probe_kernel") is True
        assert pallas_mode.resolve_interpret(None, "probe_kernel") is True
        # An explicit choice is the caller's and is not reported.
        assert pallas_mode.resolve_interpret(False, "other") is False
    assert "probe_kernel" in pallas_mode.INTERPRETED
    assert "other" not in pallas_mode.INTERPRETED
    assert sum("probe_kernel" in r.getMessage()
               for r in caplog.records) == 1
    pallas_mode.INTERPRETED.discard("probe_kernel")


def test_kernel_check_runs_at_toy_shapes_in_interpret_mode():
    """The on-chip kernel check (python -m horovod_tpu.ops.kernel_check)
    keeps working: same comparisons, toy shapes, interpret mode."""
    from horovod_tpu.ops import kernel_check

    kernel_check.check_flash(1, 32, 2, 8, True, interpret=True,
                             block_q=8, block_k=16)
    kernel_check.check_fused_loss(16, 8, 70, interpret=True,
                                  block_n=8, block_v=32)
    assert kernel_check.main() == 1  # the real run needs a TPU
